"""Runtime flag surface (reference: docs/faq/env_var.md — the MXNET_* env
layer; dmlc::GetEnv call sites e.g. src/executor/graph_executor.cc:282).

The reference reads ``MXNET_*`` environment variables at points of use; this
module is the equivalent single place to look flags up. Flags are read from
the environment on first access and can be overridden programmatically with
:func:`set_flag` (tests use this).

Flags currently honored:

``MXNET_CONV_SPACE_TO_DEPTH`` (default 1)
    Rewrite stride-2 channels-last stem convolutions with few input
    channels (e.g. ResNet's 7x7/2 on RGB) into a space-to-depth conv so
    the contraction feeds the MXU's 128 lanes instead of wasting them on
    a 3-channel input. Purely an implementation rewrite — weight shapes
    and numerics (up to bf16 rounding) are unchanged.

``MXNET_BACKWARD_DO_MIRROR`` (default 0)
    Recompute-instead-of-store for backward (reference:
    graph_executor.cc:282-296): wraps the forward in ``jax.checkpoint``
    so activations are rematerialized in backward, trading FLOPs for
    HBM footprint.

``MXNET_POOLING_MASK_BWD`` (default 0)
    Max-pool backward as fused strided tie-splitting masks instead of
    XLA's SelectAndScatter (ops/nn.py _maxpool_mask_bwd). Measured ~14%
    slower for ResNet-50 on v5e (PERF_NOTES.md) — kept as an experiment
    knob for other backends/window shapes.

``MXNET_EXEC_DISABLE_JIT`` (default 0)
    Debug switch: run graph programs eagerly (op-by-op) instead of one
    compiled XLA program — the analog of MXNET_ENGINE_TYPE=NaiveEngine
    for hunting numeric/tracing bugs.

``MXNET_DEBUG_NANS`` (default 0)
    Turn on jax_debug_nans: any NaN produced by a compiled program
    raises at the producing op (SURVEY §5.2's debug lever — the TPU
    analog of the reference's NaiveEngine + MXNET_ENGINE_INFO hunt for
    silent corruption). Set the env var before import, or call
    ``config.set_flag("MXNET_DEBUG_NANS", 1)`` at runtime. Combine with
    MXNET_EXEC_DISABLE_JIT=1 to localize to a single eager op.

``MXNET_RING_ATTENTION_FLASH`` (default 1)
    Per-ring-step local attention in ring_attention: 1 = use the Pallas
    flash kernel for each K/V block when running on TPU (dense XLA
    elsewhere), 0 = always the dense blockwise formula, 2 = force the
    kernel on any backend (interpret mode off-TPU; for tests).

``MXNET_TELEMETRY`` (default 0)
    Master switch for the observability/ metrics registry. 0 = no-op
    instruments (< 1 µs per call, regression-tested); 1 = counters,
    gauges and histograms record, the eager dispatcher measures its
    host-dispatch vs device-compute split (it fences per op — a
    measurement mode, not a fast path), the executor records per-program
    run latency, and jax.monitoring compile hooks are installed.

``MXNET_TELEMETRY_MEMSTATS`` (default 1)
    Under telemetry, sample ``device.memory_stats()`` into the
    ``hbm.live_bytes`` / ``hbm.peak_bytes`` gauges once per training
    step (host RSS fallback on backends without allocator stats). 0
    skips the sampling (it is one PJRT call per step).

``MXNET_TELEMETRY_RETRACE`` (default 0)
    Also flip jax's ``explain_cache_misses`` and keep the most recent
    retrace-cause explanations for ``dump_metrics()``. Off by default:
    it makes jax log a WARNING per tracing cache miss.

``MXNET_HEALTH`` (default ``off``)
    Active training-health policy (observability/health.py): one fused
    non-finite reduction per step over loss/grads/params with grad-norm
    and update-to-param-ratio gauges. ``off`` keeps every wired call
    site on its zero-cost no-op path; ``warn`` logs anomalies and dumps
    the flight recorder; ``raise`` raises TrainingHealthError on the
    faulting step; ``skip_step`` additionally withholds the parameter
    update so weights stay finite. String-valued and read straight from
    the environment (override at runtime with
    ``observability.health.set_policy``) — like MXNET_PROFILER_MODE,
    NOT routed through the integer get_flag machinery.

``MXNET_HEALTH_RING`` (default 256)
    Capacity of the flight recorder's last-K ring of per-step health
    records (observability/flight_recorder.py).

``MXNET_HEALTH_DUMP_DIR`` (default ``health_dumps/``)
    Directory flight-recorder triage dumps are written into (atomic
    temp+rename; created on demand, never the repo root). String-valued,
    env-only; ``flight_recorder.configure(dump_dir=...)`` overrides at
    runtime.

``MXNET_SERVING_MAX_WAIT_MS`` (default 5)
    Micro-batching deadline of the serving engine (serving/engine.py):
    the dispatcher coalesces queued requests into the largest batch
    bucket available within this many milliseconds of the oldest queued
    request's admission; a full bucket flushes immediately. 0 disables
    coalescing-by-waiting (every collect flushes whatever is queued).

``MXNET_SERVING_QUEUE`` (default 1024)
    Admission-queue bound, in ROWS. Beyond it the configured
    backpressure applies: ``MXNET_SERVING_BACKPRESSURE=block`` (default)
    stalls submitters, ``reject`` raises QueueFullError. The
    backpressure policy itself is a string env var (not integer
    get_flag machinery), like MXNET_HEALTH.

``MXNET_SERVING_PIPELINE`` (default 2)
    In-flight batch window of the pipelined dispatcher: batch N+1 is
    staged and dispatched while batch N executes; host fetches drain
    when the window is full. 2 = classic double buffering; 1 disables
    the overlap (debug).

``MXNET_SERVING_BUCKETS`` (default ``1,2,4,8,16,32``)
    Comma-separated batch-bucket ladder of the serving engine. Requests
    are padded up to the smallest fitting bucket, so the steady-state
    compile count is bounded by len(buckets) x replicas, never by
    traffic. String-valued, env-only (pass ``buckets=`` to
    ServingConfig to override at runtime).

``MXNET_GEN_PAGE_SIZE`` (default 16)
    KV-cache page size, in tokens, of the generation subsystem
    (serving/generation/): sequences allocate cache storage page-wise —
    on prefill for the prompt, one page at a time as decode crosses
    page boundaries. A ``generation.page_size`` tuning-cache entry
    (autotune.tune_generation) wins over this flag; an explicit
    ``GenerationConfig(page_size=...)`` wins over both.

``MXNET_GEN_DECODE_BLOCKS`` (default 128)
    Key-block bound, in tokens, of the paged decode attention step
    (``paged_decode_attention``): keys stream through the online-softmax
    recurrence in blocks of this many positions, bounding the gathered
    K/V working set. Same resolution order as MXNET_GEN_PAGE_SIZE via
    the ``generation.decode_blocks`` tunable.

``MXNET_GEN_MAX_BATCH`` (default 8)
    Decode slot count of the continuous-batching scheduler. The decode
    step is ONE compiled program over this fixed slot layout (inactive
    slots are masked), so this also bounds per-step compute.

``MXNET_GEN_MAX_SEQ`` (default 256)
    Per-sequence cache capacity in tokens: every request must satisfy
    ``prompt + max_new_tokens <= MXNET_GEN_MAX_SEQ``. Sizes the page
    table (and, with MXNET_GEN_POOL_PAGES=0, the page pool).

``MXNET_GEN_POOL_PAGES`` (default 0 = auto)
    Total device page-pool size (including the reserved trash page 0).
    0 sizes it for the worst case: ``max_batch`` sequences at
    ``max_seq`` tokens. Smaller pools oversubscribe slots — admission
    control then holds requests until evictions free pages.

``MXNET_GEN_QUEUE`` (default 64)
    Admission-queue bound of the generation scheduler, in REQUESTS.
    Beyond it ``MXNET_GEN_BACKPRESSURE`` applies: ``block`` (default)
    stalls submitters, ``reject`` raises QueueFullError. The policy
    is a string env var (not integer get_flag machinery), like
    MXNET_SERVING_BACKPRESSURE.

``MXNET_GEN_PREFILL_BUCKETS`` (default: powers of two up to
    MXNET_GEN_MAX_SEQ)
    Comma-separated prompt-length bucket ladder: prompts pad up to the
    smallest fitting bucket so prefill compiles are bounded by ladder
    size, never by traffic. String-valued, env-only (pass
    ``prefill_buckets=`` to GenerationConfig to override at runtime).

``MXNET_GEN_KV_DTYPE`` (default ``model``)
    KV-page storage dtype of the paged generation cache
    (docs/quantization.md): ``model`` keeps the checkpoint dtype,
    ``bfloat16`` halves fp32 pools, ``int8`` stores symmetric-int8
    pages with per-(position, head) fp32 scales dequantized inside the
    decode attention's streaming recurrence — roughly half the decode
    HBM traffic of bf16 pages. Resolution: explicit
    ``GenerationConfig(kv_dtype=...)`` > ``generation.kv_dtype``
    tuning-cache entry (``autotune.tune_generation_kv``) > this env.
    String-valued, env-only — like MXNET_HEALTH, NOT routed through the
    integer get_flag machinery.

``MXNET_GEN_SPEC_K`` (default 0 = off)
    Speculation depth of the generation engine (docs/generation.md):
    each scheduler iteration proposes this many draft tokens per slot
    and verifies all k+1 positions in ONE compiled batched-verify
    program, committing 1..k+1 tokens per step — token-exact vs
    non-speculative decode. 0 keeps the plain q-length-1 decode path
    bit-for-bit. Resolution: explicit ``GenerationConfig(spec_k=...)``
    > ``generation.spec_k`` tuning-cache entry
    (``autotune.tune_generation_spec``) > this flag.

``MXNET_GEN_SPEC_NGRAM`` (default 2)
    N-gram length of the model-free prompt-lookup draft proposer (used
    when no draft model is passed): drafts continue the most recent
    earlier occurrence of the sequence's final n-gram in its own
    prompt + generated history.

``MXNET_QUANT_TABLE`` (default unset)
    Calibration-table JSON path the ``quantize`` graph pass resolves
    when no table is attached explicitly (``quantize=<path>`` in
    MXNET_GRAPH_PASSES or ``InferenceServer(quantize=...)`` win;
    runtime override: ``graph_pass.set_calibration_table``).
    String-valued, env-only.

``MXNET_GRAPH_PASSES`` (default ``default``)
    Bind-time graph-optimization pipeline (graph_pass/,
    docs/graph_passes.md): ``default`` runs the numerically exact
    passes — inference loss-head simplification + dead-node pruning,
    BatchNorm→conv/FC folding, the autotuner-consulting layout rewrite,
    the ``fuse`` fusion-region pass (docs/fusion.md), and constant
    folding of frozen-parameter subgraphs; ``all`` additionally enables
    the opt-in bf16 ``amp`` rewrite (fp32 islands for
    softmax/norm/loss); ``off`` disables the layer; ``-<pass>`` drops
    one pass (``-fuse`` is the unfused A/B arm); ``layout=NHWC`` forces
    the layout target. Grammar in docs/graph_passes.md. String-valued
    and read by graph_pass straight from the environment (runtime
    override: ``graph_pass.set_passes``) — like MXNET_HEALTH, NOT
    routed through the integer get_flag machinery.

``MXNET_FUSION_BLOCK_M`` / ``MXNET_FUSION_BLOCK_N`` /
``MXNET_FUSION_BLOCK_K`` (defaults 128 / 128 / 512)
    Block-bound defaults of the fused matmul + epilogue Pallas kernels
    (parallel/fused.py): tile upper bounds for the output rows/cols and
    the contraction depth.  A tuned ``fusion.blocks`` cache entry for
    the shape bucket wins (docs/autotune.md).  What runs is a tile the
    TPU lowering accepts: the whole dimension when it fits under its
    bound, else the largest divisor that is a multiple of 8 (rows) /
    128 (columns, depth); a shape with none declines to the reference
    composition by a static rule (docs/fusion.md).

``MXNET_FUSION_KERNEL`` (default 1)
    Lower eligible fused regions through the Pallas kernel family on
    TPU. 0 = always use the reference composition (the region node
    still fuses graph-side — one program region, exterior-bytes
    accounting — but XLA owns the lowering).

``MXNET_FUSION_INTERPRET`` (default 0)
    Force the Pallas fused-kernel path in interpret mode on any
    backend — the CPU test/CI lever (tools/fuse_smoke.py exercises the
    real kernel path with it).

``MXNET_FUSION_MIN_BYTES`` (default 0)
    Minimum analytic interior-bytes saving (the ``2 x interior output
    bytes`` candidate formula) for a region to be carved; smaller
    matches are reported as rejected with ``below_min_bytes``.

``MXNET_TUNE`` (default 0)
    Autotuner mode (autotune/, docs/autotune.md): ``0`` consults the
    persistent tuning cache at the wired call sites (fused-kernel
    block bounds, serving bucket ladder, executor remat) — a hit is one
    dict probe, a miss falls back to the defaults below, and no
    measurement ever runs; ``1`` additionally runs the measured search
    on a miss at shape-local call sites (outside any jax trace);
    ``-1`` bypasses cache lookups entirely (an A/B baseline).

``MXNET_TUNE_TRIALS`` (default 12)
    Measurement budget per search: total candidates timed (median-of-k
    each) after analytic-cost pruning.

``MXNET_TUNE_CACHE`` (default ``~/.cache/mxnet_tpu/tuning.json``)
    Tuning-cache file path. String-valued, env-only (like
    MXNET_PROFILER_MODE). ``MXNET_TUNE_FINGERPRINT`` (env-only)
    overrides the device fingerprint half of every cache key — tests,
    or shipping one tuned cache to a known fleet.

``MXNET_FAULTS`` (default unset) / ``MXNET_FAULTS_SEED`` (default 0)
    Deterministic fault-injection spec for the resilience layer
    (resilience/faults.py; grammar in docs/resilience.md), e.g.
    ``kvstore.push:drop@p=0.01;serving.replica_execute:raise@call=7``.
    Unset, every declared injection point is a few-nanosecond no-op.
    String-valued,
    env-only (``resilience.faults.configure`` overrides at runtime).

``MXNET_RETRY_MAX`` (default 3)
    Attempt budget of the shared retry primitive (resilience/retry.py)
    — total tries including the first. Used by kvstore push/pull and
    the PS RPC layer (reconnect-between-attempts).

``MXNET_RETRY_BASE_MS`` / ``MXNET_RETRY_MAX_MS`` (defaults 10 / 2000)
    First backoff delay and its doubling cap, milliseconds. Each delay
    is down-jittered by up to 25% so synchronized clients desynchronize.

``MXNET_RETRY_DEADLINE_MS`` (default 30000)
    Wall-clock cap across all attempts of one retried operation; 0
    disables. Bounds scheduling only — an attempt already blocked in a
    recv is the transport timeout's job.

``MXNET_SERVING_DEADLINE_MS`` (default 0 = off)
    Per-request deadline of the serving engine: a request still queued
    this many ms after submit is failed with ``DeadlineExceeded``
    *before* dispatch — a backlogged server sheds stale work instead of
    serving answers nobody is waiting for.

``MXNET_SERVING_COOLDOWN_MS`` (default 1000)
    Circuit-breaker cooldown: a replica whose dispatch faulted is
    quarantined out of round-robin for this long, then re-admitted via
    a zero-batch probe (success re-admits, failure re-quarantines).

``MXNET_GEN_SUBMIT_TIMEOUT`` (default 0 = wait forever)
    Block-mode ``Generator.submit`` wait bound, milliseconds: a full
    admission queue that stays full this long raises QueueFullError
    instead of blocking the caller indefinitely.

``MXNET_GEN_DEADLINE_MS`` (default 0 = off)
    Per-request queue deadline of the generation engine — the
    ``MXNET_SERVING_DEADLINE_MS`` analog: a request still queued this
    many ms after submit is failed with ``DeadlineExceeded`` *before*
    prefill dispatch. An :class:`~mxnet_tpu.serving.control.SLOClass`
    with its own ``deadline_ms`` overrides this default per class.

``MXNET_GEN_PREFIX_CACHE`` (default 0 = off)
    Serving control plane's radix-tree prefix cache
    (serving/control/, docs/serving_control.md): 1 shares the KV pages
    of page-aligned common prompt prefixes across requests
    (copy-on-write, refcounted), so a repeated system prompt prefills
    once and later requests prefill only their suffix. Opt-in: a cold
    engine keeps the original prefill numeric path bit-for-bit.

``MXNET_GEN_PREFIX_PAGES`` (default 0 = pool-bounded)
    Prefix-cache capacity in KV pages; beyond it insertion evicts
    least-recently-matched leaves. 0 bounds the cache only by the pool
    itself (admission pressure reclaims cached pages LRU-first either
    way). Resolution: ``GenerationConfig(prefix_pages=...)`` >
    ``control.prefix_pages`` tuning-cache entry > this flag.

``MXNET_GEN_SLO_AGING_MS`` (default 500)
    Starvation bound of SLO-class admission: every this-many ms of
    queue wait boosts a request's effective priority by one tier, so a
    low-priority class eventually outranks fresh high-priority
    arrivals. 0 disables aging (strict priority). Resolution:
    ``GenerationConfig(slo_aging_ms=...)`` > ``control.slo_aging``
    tuning-cache entry > this flag.

``MXNET_IO_STREAMING`` (default 0)
    Backend switch of the ``ImageRecordIter`` factory (runtime/,
    docs/data_pipeline.md): 1 returns the async streaming pipeline
    (:class:`~mxnet_tpu.runtime.pipeline.StreamingIter` — parallel
    decode workers, batch assembly off the training thread,
    double-buffered device staging); 0 keeps the MXNet-1.0 synchronous
    shape (PrefetchingIter over ImageIter). Batch-for-batch identical
    output either way for same-``seed`` (or unshuffled) streams without
    random augmenters (tools/io_smoke.py guards it; random augmenters
    draw per-worker randomness on both backends and are not
    bit-reproducible across them); an explicit ``streaming=`` argument
    wins over the flag.

``MXNET_IO_DECODE_WORKERS`` (default 0 = auto)
    Decode/augment worker-pool size of the streaming input pipeline.
    0 sizes automatically (host cores, capped at 8). Resolution order
    at iterator construction: explicit ``decode_workers=`` argument >
    ``io.decode_workers`` tuning-cache entry
    (``autotune.tune_input_pipeline``) > this flag > auto.

``MXNET_IO_PREFETCH_DEPTH`` (default 2)
    Bound of the streaming pipeline's finished-batch queue, in batches
    — how far the decode stages may run ahead of the consumer (host
    memory is the price of depth). Same resolution order as
    MXNET_IO_DECODE_WORKERS via the ``io.prefetch_depth`` tunable.

``MXNET_IO_STAGE_DEPTH`` (default 2)
    Device-staging window of the streaming pipeline: how many batches
    are kept transferred (one pytree ``device_put`` each) ahead of the
    consumer. 2 = classic double buffering — batch N+1's transfer
    overlaps batch N's compute; 1 disables the overlap (debug).

``MXNET_OBS_TRACE_SAMPLE`` (default 1)
    Request-trace sampling of the serving stack
    (observability/request_trace.py): every sampled request carries a
    ``RequestTrace`` from submit to completion with exact
    queue/batch/compute/fetch (serving) or queue/prefill/decode
    (generation) latency attribution. 0 = tracing off (shared no-op
    trace), 1 = every request, N = 1-in-N.

``MXNET_OBS_RESERVOIR`` (default 32)
    Capacity of the request-trace tail reservoir: the slowest-K
    requests ever seen (p99 exemplars) plus the most-recent-K full span
    timelines, served by the exposition plane's ``/tracez``.

``MXNET_OBS_HTTP_PORT`` (default unset = off)
    Opt-in live exposition plane (observability/exposition.py): a
    stdlib HTTP daemon thread serving ``/metrics`` (Prometheus text),
    ``/statusz`` (live engine/provider JSON), ``/healthz`` and
    ``/tracez``. Set to a port (0 = ephemeral) before import, or call
    ``observability.exposition.start_http(port)`` at runtime. Binds
    127.0.0.1 unless ``MXNET_OBS_HTTP_HOST`` widens it. String-valued,
    env-only — like MXNET_PROFILER_MODE, NOT routed through the integer
    get_flag machinery (unset must mean "off", not port 0).

``MXNET_OBS_TS_INTERVAL_MS`` (default 1000)
    Sampling period of the time-series plane
    (observability/timeseries.py): a background daemon thread snapshots
    the metrics registry into per-instrument bounded rings every this
    many milliseconds, powering the ``/varz?window=`` trailing-window
    queries (counter rates, gauge avg/min/max, bucket-delta histogram
    quantiles) and the ``timeseries`` flight-recorder provider. Started
    with the exposition plane (or ``timeseries.start_sampler()``).
    0 = no sampler (and /varz explains why). Per-sample cost is one
    locked registry walk.

``MXNET_OBS_TS_RETAIN`` (default 600)
    Ring depth of the time-series sampler, in samples per instrument —
    at the default 1 s interval, 10 minutes of look-back. Bounds host
    memory: older samples are evicted, so windows wider than
    interval×retain silently see a shorter baseline.

``MXNET_OBS_FLEET_INTERVAL_MS`` (default 1000)
    Scrape period of the FleetAggregator (observability/fleet.py):
    every worker ``/metrics`` endpoint is fetched, parsed (promparse)
    and merged into fleet-level series with per-worker labels each
    interval.

``MXNET_OBS_FLEET_STALE_SCRAPES`` (default 3)
    Consecutive failed scrapes before a worker is marked ``stale``
    (still merged from history, flagged in ``fleet_status()``).

``MXNET_OBS_FLEET_DEAD_SCRAPES`` (default 10)
    Consecutive failed scrapes before a worker is marked ``dead``: its
    series stop being appended (they go stale in windowed queries
    rather than flat-lining at the last value) and the autoscaler can
    count it out of availability.

``MXNET_AUTOSCALE_MIN`` (default 1) / ``MXNET_AUTOSCALE_MAX`` (default 8)
    Clamp bounds for ``AutoscalePolicy`` decisions
    (serving/control/autoscale.py): the replica count proposed to
    ``InferenceServer.resize_replicas`` always lands in
    [MIN, MAX], whatever the burn rates say.

``MXNET_AUTOSCALE_COOLDOWN_MS`` (default 30000)
    Minimum spacing between autoscale *actions*. Scale-downs also
    require the low-load condition to hold over the whole trailing
    window (hysteresis) so flapping input cannot oscillate the fleet.

``MXNET_DIST_SENTINEL`` (default ``off``)
    Cross-rank divergence sentinel policy (``off`` / ``warn`` /
    ``raise``, observability/dist_trace.py): when a distributed kvstore
    is constructed with the policy on, every fit step ships a tiny
    fingerprint (grad-norm + param-checksum + loss, lifted from the
    health plane's verdict — requires ``MXNET_HEALTH`` active, costs
    zero extra device syncs) to kvstore shard 0, which compares it
    across ranks and flags desync: ``warn`` logs + flight-records it,
    ``raise`` raises ``DistDivergenceError`` before the next checkpoint
    can absorb the corruption. String-valued and env-only — like
    MXNET_HEALTH, NOT routed through the integer get_flag machinery.

``MXNET_DIST_SENTINEL_TOL`` (default 1e-5)
    Relative tolerance for cross-rank fingerprint agreement: fields
    disagree when ``|a-b| > tol * max(1, |a|, |b|)``. Float-valued and
    env-only. Bit-exact data-parallel replicas can run tight; loosen it
    for genuinely asynchronous training (dist_async ranks see different
    weights by design — step skew is the signal there, not norm drift).

``MXNET_DIST_SENTINEL_SKEW`` (default 2)
    Max step-index spread between ranks before the sentinel flags a
    skew desync (a wedged or restarted rank falls behind its peers even
    when every individual fingerprint looks healthy).

``MXNET_DIST_ROUNDS`` (default 128)
    History bound (rounds) of the kvstore server's straggler
    attribution ring (dist_trace.RoundTracker): completed sync rounds
    keep per-rank arrival lateness for the last N rounds; the
    cumulative ranking and the ``kvstore.rank_lateness_ms{rank=}``
    histograms are unaffected by the bound.

``MXNET_DIST_BUCKET_BYTES`` (default 4194304)
    Gradient-bucket size of the mesh kvstore (kvstore_mesh.py): pushed
    gradients pack into flat per-dtype buckets of at most this many
    bytes, and each bucket's fused all-reduce / reduce-scatter
    dispatches as soon as its keys are stashed — early buckets' exchange
    overlaps the rest of backward. Also the declared autotune knob
    ``dist.bucket_bytes`` (tuning cache beats this flag; an explicit
    ``KVStoreMesh(bucket_bytes=...)`` beats both).

``MXNET_MESH_ZERO1`` (default 1)
    ZeRO-1 optimizer-state sharding on the mesh kvstore: the gradient
    exchange becomes reduce-scatter, each rank updates (and holds
    optimizer state for) only its 1/N shard, and updated parameter
    shards all-gather back — per-chip optimizer memory drops ~1/N.
    0 = plain all-reduce with every rank running the full update.
    Bit-identical results either way for elementwise optimizers
    (docs/distributed.md).

``MXNET_MESH_PROCS`` (default 2)
    Process count of the CPU fake cluster spawned by
    ``tools/mesh_smoke.py`` (real deployments size the cluster via the
    launcher / jax.distributed, not this flag).

``MXNET_PERF`` (default 1)
    Roofline attribution layer (observability/perf.py): analytic
    FLOPs/HBM-bytes accounting per compiled program, achieved-vs-
    roofline ``perf.mfu_pct`` / ``perf.hbm_util_pct`` gauges, and the
    fit-loop step-time waterfall (data-wait / host dispatch / device
    compute / kvstore segments that sum to the step wall exactly).
    Cost walks run once per (program, shape signature); steady-state
    steps pay dict probes only. 0 = the whole layer off
    (``tests/test_perf.py::test_perf_disabled_is_inert``).

``MXNET_PERF_RING`` (default 64)
    Capacity of the per-step waterfall ring surfaced by the flight
    recorder's ``perf`` provider, ``/statusz`` and
    ``tools/perf_report.py``.

``MXNET_PROFILER_RING`` (default 200000)
    Bound of the profiler's in-memory event ring (profiler.py): beyond
    it the OLDEST events are evicted and counted
    (``profiler.dropped_events()``, the ``profiler.events_dropped``
    metric, ``droppedEventsCount`` in the dump) so a week-long serving
    process with spans on cannot grow host memory without bound.

``MXNET_PROFILER_MODE`` (default ``symbolic``)
    Initial profiler mode (``symbolic`` / ``imperative`` / ``all``) so a
    trace can be captured from an unmodified script via env alone;
    ``profiler.set_config(mode=...)`` still overrides at runtime.
    String-valued and read by profiler.py straight from the
    environment — env-only, NOT routed through the integer-coercing
    ``get_flag``/``set_flag`` machinery below.
"""
import os

__all__ = ["get_flag", "set_flag", "flag_doc"]

_overrides = {}

_DEFAULTS = {
    "MXNET_CONV_SPACE_TO_DEPTH": 1,
    "MXNET_BACKWARD_DO_MIRROR": 0,
    "MXNET_EXEC_DISABLE_JIT": 0,
    # max-pool backward as fused strided masks instead of XLA's
    # SelectAndScatter (each window's gradient splits evenly across
    # tied maxima; see ops/nn.py _maxpool_mask_bwd)
    "MXNET_POOLING_MASK_BWD": 0,
    "MXNET_DEBUG_NANS": 0,
    "MXNET_RING_ATTENTION_FLASH": 1,
    "MXNET_TELEMETRY": 0,
    "MXNET_TELEMETRY_MEMSTATS": 1,
    "MXNET_TELEMETRY_RETRACE": 0,
    "MXNET_HEALTH_RING": 256,
    "MXNET_SERVING_MAX_WAIT_MS": 5,
    "MXNET_SERVING_QUEUE": 1024,
    "MXNET_SERVING_PIPELINE": 2,
    "MXNET_TUNE": 0,
    "MXNET_TUNE_TRIALS": 12,
    "MXNET_FUSION_BLOCK_M": 128,
    "MXNET_FUSION_BLOCK_N": 128,
    "MXNET_FUSION_BLOCK_K": 512,
    "MXNET_FUSION_KERNEL": 1,
    "MXNET_FUSION_INTERPRET": 0,
    "MXNET_FUSION_MIN_BYTES": 0,
    "MXNET_GEN_PAGE_SIZE": 16,
    "MXNET_GEN_DECODE_BLOCKS": 128,
    "MXNET_GEN_MAX_BATCH": 8,
    "MXNET_GEN_MAX_SEQ": 256,
    "MXNET_GEN_POOL_PAGES": 0,
    "MXNET_GEN_QUEUE": 64,
    "MXNET_GEN_SUBMIT_TIMEOUT": 0,
    "MXNET_GEN_DEADLINE_MS": 0,
    "MXNET_GEN_PREFIX_CACHE": 0,
    "MXNET_GEN_PREFIX_PAGES": 0,
    "MXNET_GEN_SLO_AGING_MS": 500,
    "MXNET_GEN_SPEC_K": 0,
    "MXNET_GEN_SPEC_NGRAM": 2,
    "MXNET_RETRY_MAX": 3,
    "MXNET_RETRY_BASE_MS": 10,
    "MXNET_RETRY_MAX_MS": 2000,
    "MXNET_RETRY_DEADLINE_MS": 30000,
    "MXNET_SERVING_DEADLINE_MS": 0,
    "MXNET_SERVING_COOLDOWN_MS": 1000,
    "MXNET_OBS_TRACE_SAMPLE": 1,
    "MXNET_OBS_RESERVOIR": 32,
    "MXNET_OBS_TS_INTERVAL_MS": 1000,
    "MXNET_OBS_TS_RETAIN": 600,
    "MXNET_DIST_SENTINEL_SKEW": 2,
    "MXNET_DIST_ROUNDS": 128,
    "MXNET_DIST_BUCKET_BYTES": 4 << 20,
    "MXNET_MESH_ZERO1": 1,
    "MXNET_MESH_PROCS": 2,
    "MXNET_OBS_FLEET_INTERVAL_MS": 1000,
    "MXNET_OBS_FLEET_STALE_SCRAPES": 3,
    "MXNET_OBS_FLEET_DEAD_SCRAPES": 10,
    "MXNET_AUTOSCALE_MIN": 1,
    "MXNET_AUTOSCALE_MAX": 8,
    "MXNET_AUTOSCALE_COOLDOWN_MS": 30000,
    "MXNET_PERF": 1,
    "MXNET_PERF_RING": 64,
    "MXNET_PROFILER_RING": 200000,
    "MXNET_IO_STREAMING": 0,
    "MXNET_IO_DECODE_WORKERS": 0,
    "MXNET_IO_PREFETCH_DEPTH": 2,
    "MXNET_IO_STAGE_DEPTH": 2,
}


def _apply_debug_nans(value):
    import jax

    jax.config.update("jax_debug_nans", bool(value))


def _apply_telemetry(value):
    # keep the registry's cached switch in sync with the flag (and
    # install the jax.monitoring hooks on first enable)
    from .observability import metrics as _metrics

    _metrics._enabled = bool(value)
    if value:
        from .observability import instruments as _instruments

        _instruments.install_jax_hooks()


def _apply_obs_sample(value):
    # keep request_trace's cached sampling rate coherent with the flag
    from .observability import request_trace as _rtrace

    _rtrace._apply_sample_flag(value)


def _apply_perf(value):
    # keep perf's cached activity switch coherent with the flag
    from .observability import perf as _perf

    _perf._apply_perf_flag(value)


_APPLIERS = {"MXNET_DEBUG_NANS": _apply_debug_nans,
             "MXNET_TELEMETRY": _apply_telemetry,
             "MXNET_OBS_TRACE_SAMPLE": _apply_obs_sample,
             "MXNET_PERF": _apply_perf}


def get_flag(name, default=None):
    """Integer-valued flag: override > environment > default."""
    if name in _overrides:
        return _overrides[name]
    if default is None:
        default = _DEFAULTS.get(name, 0)
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def set_flag(name, value):
    """Programmatic override (set to None to clear)."""
    if value is None:
        _overrides.pop(name, None)
    else:
        _overrides[name] = int(value)
    if name in _APPLIERS:
        _APPLIERS[name](get_flag(name))


def flag_doc():
    return __doc__


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache for an entry point
    (``chip_smoke.py``, ``perfbench/run.py`` call this before their
    first jit) and return the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and
    nothing is set in code — the cache can be placed from outside. Else
    the cache lives at ``<checkout>/.jax_cache``: a FIXED path, never one
    built from a temporary name, pid or time, because a directory that
    moves between runs never hits. The library itself never calls this:
    importing ``mxnet_tpu`` leaves caching as the process found it (the
    test suite's compile-count tests depend on that)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# env-set appliers take effect at import (flag levers that configure
# the backend rather than being polled per call)
if get_flag("MXNET_DEBUG_NANS"):
    _apply_debug_nans(1)
