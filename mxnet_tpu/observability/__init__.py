"""Unified training telemetry (no reference counterpart — the reference
scatters this across the engine profiler and ad-hoc logging).

Three pillars, one import:

* :mod:`.metrics` — process-wide counters/gauges/histograms with a
  Prometheus text exposition (:func:`dump_metrics`) and a zero-overhead
  no-op mode (MXNET_TELEMETRY flag).
* :mod:`.tracing` — :func:`trace_span` nested chrome://tracing spans
  into the profiler buffer; :func:`device_scope` for labels inside
  compiled programs.
* :mod:`.instruments` — ready-made wiring: XLA compile accounting via
  jax.monitoring, HBM watermark sampling, per-step accounting.
* :mod:`.health` — active training-health layer: one fused non-finite
  reduction per step over loss/grads/params plus grad-norm and
  update-ratio gauges, with an MXNET_HEALTH policy
  (off|warn|raise|skip_step).
* :mod:`.flight_recorder` — lock-guarded last-K ring of per-step health
  records; dumps one atomic triage file on anomaly, uncaught exception,
  or demand (render with tools/health_report.py).
* :mod:`.request_trace` — request-scoped tracing: one
  :class:`~.request_trace.RequestTrace` per served request threaded
  submit→completion through the serving/generation engines, with exact
  queue/batch/compute/fetch latency attribution, a bounded tail-exemplar
  reservoir, and chrome-trace export (``tools/trace_report.py
  --requests``).
* :mod:`.perf` — roofline attribution (ISSUE 13): analytic FLOPs/HBM
  bytes per compiled program against the chip's published peaks,
  achieved-vs-roofline MFU / HBM-utilization gauges, the fit-loop
  step-time waterfall (data-wait / host / device / kvstore, summing to
  the step wall exactly), and the ``BENCH_LEDGER.jsonl`` perf-ledger
  helpers (render with ``tools/perf_report.py``).
* :mod:`.stats_schema` — the ONE stats vocabulary both serving engines'
  ``get_stats()`` snapshots conform to.
* :mod:`.exposition` — opt-in stdlib HTTP plane
  (``MXNET_OBS_HTTP_PORT``): ``/metrics`` (Prometheus text),
  ``/statusz`` (live engine/provider JSON), ``/healthz``, ``/tracez``
  (tail request-trace exemplars), ``/varz?window=`` (trailing-window
  rates/quantiles).
* :mod:`.promparse` — the scrape side of the exposition contract: the
  ONE Prometheus text-format parser (round-trip-tested against
  :func:`dump_metrics`) that the fleet aggregator, obs_smoke and the
  compliance tests share.
* :mod:`.timeseries` — the time-series plane (ISSUE 17): a background
  sampler snapshots the registry into bounded per-instrument rings
  (``MXNET_OBS_TS_*``), with windowed queries — counter ``rate()``,
  gauge avg/min/max, bucket-delta histogram quantiles ("p99 over the
  last minute", not since boot) — behind ``/varz`` and the
  ``timeseries`` flight-recorder provider.
* :mod:`.fleet` — :class:`~.fleet.FleetAggregator`: scrape N workers'
  ``/metrics``, merge into fleet-level series with per-worker labels
  (histograms bit-exactly, rates reset-safely), mark workers
  stale/dead on missed scrapes; per-rank kvstore heartbeat ages ride
  along as queryable series.
* :mod:`.slo_monitor` — SLO objectives (latency-threshold,
  availability) evaluated as multi-window burn rates with hysteresis —
  the alert layer the autoscaler (serving/control/autoscale.py) acts
  on.
* :mod:`.dist_trace` — cross-rank training observability (ISSUE 19):
  rank-stamped step waterfalls merged into one fleet timeline with a
  per-segment critical path, kvstore-server straggler attribution
  (``kvstore.rank_lateness_ms{rank=}`` + last-arriver ranking), and
  per-step divergence sentinels (``MXNET_DIST_SENTINEL=warn|raise``)
  comparing grad-norm/param-checksum fingerprints across ranks
  server-side (render with ``tools/dist_report.py``).

See docs/observability.md for the metrics catalog, the "where did my
step time go" workflow (profiler dump → tools/trace_report.py), the
"where did my REQUEST's latency go" workflow (request tracing →
``/tracez`` / ``trace_report --requests``), and docs/health.md for the
"why did my run go bad" workflow.
"""
from . import metrics
from . import instruments
from . import tracing
from . import health
from . import flight_recorder
from . import request_trace
from . import stats_schema
from . import exposition
from . import perf
from . import promparse
from . import timeseries
from . import fleet
from . import slo_monitor
from . import dist_trace
from .metrics import (counter, gauge, histogram, dump_metrics,
                      reset_metrics, set_enabled, enabled)
from .tracing import trace_span, device_scope
from .instruments import sample_memory, record_step, retrace_causes
from .health import TrainingHealthError
from .request_trace import RequestTrace

__all__ = ["metrics", "instruments", "tracing", "health", "flight_recorder",
           "request_trace", "stats_schema", "exposition", "perf",
           "promparse", "timeseries", "fleet", "slo_monitor", "dist_trace",
           "counter", "gauge", "histogram", "dump_metrics", "reset_metrics",
           "set_enabled", "enabled", "trace_span", "device_scope",
           "sample_memory", "record_step", "retrace_causes",
           "TrainingHealthError", "RequestTrace"]

# honor an env-set MXNET_TELEMETRY at import: installs the jax.monitoring
# hooks so compiles are counted from the first jit call
if metrics.enabled():
    instruments.install_jax_hooks()

# honor an env-set MXNET_OBS_HTTP_PORT at import: the exposition plane
# comes up with the process, no code change in the serving script
exposition.maybe_start_from_env()
