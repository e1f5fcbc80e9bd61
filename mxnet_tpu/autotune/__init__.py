"""Search-based autotuner (ISSUE 6; ROADMAP open item 2).

Turns the repo's hand-picked performance constants — the fused
kernel's block bounds, the serving bucket ladder, per-graph layout and
remat policy — into one tuned, persisted, observable subsystem:

* :mod:`.registry` — call sites declare their knob + search space
  (``fusion.blocks``, ``serving.buckets``, ``graph.layout``,
  ``exec.remat``),
* :mod:`.cost_model` — analytic roofline estimates prune candidates
  (the chip's published peaks, VMEM feasibility),
* :mod:`.search` — measured search decides (median-of-k, warmup
  discarded, incumbent default always in the running),
* :mod:`.cache` — winners persist per device fingerprint in
  ``MXNET_TUNE_CACHE`` (default ``~/.cache/mxnet_tpu/tuning.json``),
  written atomically; consumers pay one dict probe at trace time.

Modes (``MXNET_TUNE``): ``0`` (default) consult the cache, never
measure; ``1`` additionally search on a miss at shape-local call sites
(outside any jax trace); ``-1`` bypass lookups entirely (A/B baseline).
Quick start: docs/autotune.md.
"""
from . import cache, cost_model, registry, search
from .cache import (cache_path, device_fingerprint, lookup, lookup_entry,
                    record, reload, reset, reset_stats, scrub_stale, stats)
from .registry import declare, get as get_tunable, names as tunable_names
from .search import SearchConfig, SearchResult, median_time, tune_and_record

__all__ = ["cache", "registry", "cost_model", "search",
           "cache_path", "device_fingerprint", "lookup", "lookup_entry",
           "lookup_or_tune", "record", "reload", "reset", "reset_stats",
           "scrub_stale", "stats", "declare", "get_tunable",
           "tunable_names", "SearchConfig", "SearchResult", "median_time",
           "tune_and_record", "mode", "enabled",
           "tune_fused_matmul", "tune_serving_buckets", "tune_layout",
           "tune_remat", "tune_generation", "tune_generation_kv",
           "tune_generation_spec", "tune_quantize_layers",
           "tune_input_pipeline", "tune_control"]


# the layout knob has no single in-package call site (models take
# layout= at construction), so unlike the serving/remat tunables
# it is declared here at package import — registry.get("graph.layout")
# must work without the lazily-loaded tuners module; its generic
# measured-choice tuner is tuners.tune_layout
declare(
    "graph.layout",
    space={"layout": ("NHWC", "NCHW")},
    default=lambda ctx: {"layout": str(ctx.get("default", "NHWC"))},
    doc="Per-graph data layout: NHWC feeds the MXU lanes on TPU; "
        "NCHW can win on other backends. Measured "
        "through a caller-supplied train/infer step (tune_layout).")


def _flag_default(field, flag):
    # flags resolve at consult time, not at import, so env/config
    # ordering doesn't matter
    def default(ctx):
        from ..config import get_flag

        return {field: get_flag(flag)}
    return default


# generation-subsystem knobs (ISSUE 7): consulted by
# serving/generation/engine.py (explicit GenerationConfig arg > tuning
# cache > MXNET_GEN_* flag), measured by tuners.tune_generation. The
# consuming engine loads lazily, so — like graph.layout — the
# declarations live here where a fresh process registers them at import.
declare(
    "generation.page_size",
    space={"page_size": (8, 16, 32, 64)},
    default=_flag_default("page_size", "MXNET_GEN_PAGE_SIZE"),
    doc="KV-cache page size in tokens: allocation granularity of the "
        "paged generation cache (small pages waste less on short "
        "sequences; large pages gather in fewer, longer DMA runs).")
declare(
    "generation.decode_blocks",
    space=lambda ctx: {"decode_blocks": tuple(
        b for b in (32, 64, 128, 256, 512)
        if b <= int(ctx.get("max_seq", 512))) or (32,)},
    default=_flag_default("decode_blocks", "MXNET_GEN_DECODE_BLOCKS"),
    doc="Decode-attention key-block bound in tokens "
        "(paged_decode_attention's online-softmax streaming window).")


def _kv_dtypes():
    # the engine owns the valid dtype set (KV_DTYPES); resolving it
    # lazily keeps the three consumers (space, default validation,
    # Generator._resolve_kv_dtype) in lockstep when a dtype is added
    from ..serving.generation.engine import KV_DTYPES

    return KV_DTYPES


def _kv_dtype_default(ctx):
    # MXNET_GEN_KV_DTYPE is a string env (like MXNET_HEALTH), not an
    # integer get_flag — read it directly at consult time
    import os

    val = os.environ.get("MXNET_GEN_KV_DTYPE", "").strip().lower()
    return {"kv_dtype": val if val in _kv_dtypes() else "model"}


declare(
    "generation.kv_dtype",
    space=lambda ctx: {"kv_dtype": tuple(sorted(_kv_dtypes()))},
    default=_kv_dtype_default,
    doc="KV-page storage dtype of the paged decode cache (ISSUE 11): "
        "decode is an HBM-gather workload, so narrower pages are "
        "near-linearly faster — int8 pages carry per-(position, head) "
        "fp32 scales and dequantize inside the online-softmax "
        "recurrence. tune_generation_kv arbitrates the candidates "
        "against a measured token-agreement budget vs the model-dtype "
        "decode.")
declare(
    "generation.spec_k",
    space={"spec_k": (0, 1, 2, 4, 8)},
    default=_flag_default("spec_k", "MXNET_GEN_SPEC_K"),
    doc="Speculation depth of the generation engine (ISSUE 16): draft "
        "tokens proposed per slot per step, all verified in ONE batched "
        "program (0 = speculation off). Larger k amortizes more "
        "scheduler iterations per verify call but wastes verify width "
        "when acceptance is low — workload-dependent, so "
        "tune_generation_spec measures it through the live-generator "
        "replay measurer.")
# distributed-training knob (ISSUE 20): consulted by KVStoreMesh at
# construction (explicit arg > tuning cache keyed "dp<N>" >
# MXNET_DIST_BUCKET_BYTES). Small buckets dispatch collectives earlier
# (more backward overlap) but pay more program launches; large buckets
# amortize launches but serialize the exchange behind the last key.
# Declared here at package import — the graph.layout precedent — because
# kvstore_mesh loads lazily.
declare(
    "dist.bucket_bytes",
    space={"bucket_bytes": (1 << 20, 4 << 20, 16 << 20, 64 << 20)},
    default=_flag_default("bucket_bytes", "MXNET_DIST_BUCKET_BYTES"),
    doc="Gradient-bucket size in bytes for the mesh kvstore's fused "
        "collectives: pushed grads pack into flat per-dtype buckets and "
        "each bucket's all-reduce / reduce-scatter dispatches the moment "
        "its keys are present, overlapping the rest of backward "
        "(docs/distributed.md).")
# serving-control-plane knobs (ISSUE 14): consulted by the generation
# engine at construction (explicit GenerationConfig arg > tuning cache
# > MXNET_GEN_* flag), measured by tuners.tune_control. Declared here
# at package import — the graph.layout precedent — because the engine
# loads lazily.
declare(
    "control.prefix_pages",
    space=lambda ctx: {"prefix_pages": tuple(sorted(set(
        max(1, int(ctx.get("pool_pages", 64)) * f // 8)
        for f in (1, 2, 4, 8)))) or (8,)},
    default=_flag_default("prefix_pages", "MXNET_GEN_PREFIX_PAGES"),
    doc="Prefix-cache capacity in KV pages (serving/control/): a larger "
        "cache keeps more cold prefixes resident (higher hit rate) but "
        "competes with live sequences for pool pages — admission "
        "pressure reclaims cached pages LRU-first either way.")
declare(
    "control.slo_aging",
    space={"aging_ms": (0, 100, 250, 500, 1000, 2000)},
    default=_flag_default("aging_ms", "MXNET_GEN_SLO_AGING_MS"),
    doc="SLO-admission aging interval in ms: queue wait per one-tier "
        "effective-priority boost (starvation bound of weighted "
        "admission). 0 = strict priority, small values converge toward "
        "FIFO, large values toward strict tiers.")
declare(
    "quantize.layers",
    space={},
    default=None,
    doc="Per-layer precision of the int8 PTQ graph pass: the cached "
        "value's {'skip': [op names]} pins layers to fp32. Driven by "
        "tune_quantize_layers (greedy drop of the most damaging layer "
        "until the measured top-1 agreement budget holds), keyed by "
        "graph fingerprint; run_quantize consults it at every bind.")


# input-pipeline knobs (ISSUE 10): consulted by runtime/pipeline.py at
# StreamingIter construction (explicit arg > tuning cache under
# io_pipeline_key (host cores x batch geometry) > MXNET_IO_* flag >
# auto), measured by tuners.tune_input_pipeline. The consuming pipeline
# loads lazily, so — the graph.layout precedent — the declarations live
# here where a fresh process registers them at import.
declare(
    "io.decode_workers",
    space=lambda ctx: {"workers": tuple(sorted(set(
        w for w in (1, 2, 4, 8, 16,
                    int(ctx.get("cpus", 4)),
                    max(1, int(ctx.get("cpus", 4)) // 2))
        if w <= int(ctx.get("cpus", 4)))))},
    default=_flag_default("workers", "MXNET_IO_DECODE_WORKERS"),
    doc="Decode/augment worker-pool size of the streaming input "
        "pipeline: JPEG decode + numpy augmenters release the GIL, so "
        "throughput scales with workers until the host's cores (or its "
        "memory bandwidth) saturate.")
declare(
    "io.prefetch_depth",
    space={"depth": (2, 3, 4, 6, 8)},
    default=_flag_default("depth", "MXNET_IO_PREFETCH_DEPTH"),
    doc="Finished-batch queue bound of the streaming input pipeline, "
        "in batches: how far decode may run ahead of the consumer "
        "(absorbs decode-time jitter at the price of host batch "
        "memory).")


# fusion-region kernel blocks (ISSUE 15): consulted by
# parallel/fused.py at trace time (explicit call arg > tuning cache
# under the pow2 shape-bucket key > MXNET_FUSION_BLOCK_* flags),
# measured by tuners.tune_fused_matmul. Declared here at package import
# — the graph.layout precedent — because the consuming kernel module
# loads lazily with the graph executor.
def _fusion_default(ctx):
    from ..config import get_flag

    return {"block_m": get_flag("MXNET_FUSION_BLOCK_M"),
            "block_n": get_flag("MXNET_FUSION_BLOCK_N"),
            "block_k": get_flag("MXNET_FUSION_BLOCK_K")}


def _fusion_space(ctx):
    M = int(ctx.get("M", 1024))
    N = int(ctx.get("N", 1024))
    K = int(ctx.get("K", 1024))
    dims = lambda top: tuple(b for b in (64, 128, 256, 512, 1024)  # noqa: E731
                             if b <= max(64, top)) or (64,)
    return {"block_m": dims(M), "block_n": dims(N), "block_k": dims(K)}


declare(
    "fusion.blocks",
    space=_fusion_space,
    default=_fusion_default,
    cost=cost_model.fused_matmul_cost,
    doc="Fused matmul+epilogue kernel tile bounds (parallel/fused.py): "
        "output-row/col blocks and contraction depth, VMEM-pruned by "
        "cost_model.fused_matmul_cost, keyed per pow2 (M, N, K) shape "
        "bucket.")


def mode():
    """MXNET_TUNE: -1 bypass, 0 consult-only (default), 1 search on
    miss."""
    from ..config import get_flag

    return get_flag("MXNET_TUNE")


def enabled():
    return mode() >= 0


def lookup_or_tune(op, key, dtype=None, ctx=None):
    """The consulting call sites' trace-time entry point.

    Hit → the tuned value (one dict probe). Miss → None (caller falls
    back to its config.py default), EXCEPT when ``MXNET_TUNE=1`` and the
    call happens outside any jax trace: then the op's auto-tuner runs a
    measured search on the spot, records the winner, and returns it.
    Mid-trace misses never search — a measurement storm inside someone
    else's jit would corrupt both the trace and the timings.
    """
    if mode() < 0:
        return None
    val = cache.lookup(op, key, dtype)
    if val is not None or mode() != 1:
        return val
    import jax

    if not jax.core.trace_ctx.is_top_level():
        return None
    # the guard above proves we are OUTSIDE any jax trace here; resolve
    # the tuner through getattr so the static traced-closure analysis
    # (graftlint) doesn't drag the whole measurement stack into the
    # consulting call site's trace context
    import importlib

    _fn = getattr(importlib.import_module(__name__ + ".tuners"),
                  "auto_tune")
    try:
        return _fn(op, key, dict(ctx or {}))
    except Exception as err:  # tuning is an optimization, never a crash
        import logging

        logging.getLogger(__name__).warning(
            "autotune: search for %s failed (%r); using defaults", op, err)
        return None


def __getattr__(name):
    # concrete tuners import serving/parallel lazily; loading them on
    # first use keeps `import mxnet_tpu` free of the heavy path.
    # (importlib, not `from . import`: the latter probes this very
    # __getattr__ through hasattr and recurses)
    if name in ("tune_fused_matmul", "tune_serving_buckets",
                "tune_layout", "tune_remat", "tune_generation",
                "tune_generation_kv", "tune_generation_spec",
                "tune_quantize_layers",
                "tune_input_pipeline", "tune_control",
                "control_replay_measurer", "pipeline_replay_measurer",
                "generation_replay_measurer", "tuners"):
        import importlib

        tuners = importlib.import_module(__name__ + ".tuners")
        return tuners if name == "tuners" else getattr(tuners, name)
    raise AttributeError(name)
