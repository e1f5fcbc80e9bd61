"""Continuous-batching autoregressive generation engine.

The serving engine (engine.py one level up) batches *stateless* forward
passes; this module is the stateful analog for autoregressive decode —
the "millions of users" LLM workload (ROADMAP item 1). Three moving
parts, all riding the same compile-count discipline as serving:

* **Prefill/decode split.** A new request's prompt is padded up a
  token-length bucket ladder (the :mod:`..buckets` machinery, applied to
  sequence length instead of batch rows) and runs ONE full causal
  forward — the Pallas flash kernel on TPU — that returns the prompt's
  K/V, scattered straight into the paged cache, plus the first sampled
  token. Compile count: ``len(prefill_buckets)``.
* **Single-program decode.** The decode step is ONE compiled program
  regardless of batch composition: a fixed ``max_batch`` slot layout,
  an active-slot mask, per-slot traced sampling knobs, and
  gather/scatter against the page pool
  (:func:`~...parallel.flash_attention.paged_decode_attention`). Mixed
  prompt lengths, mid-flight joins, evictions — none of it retraces.
  Compile count: 1.
* **Iteration-level scheduling.** Between decode steps the scheduler
  evicts finished sequences (EOS / max-tokens), frees their pages, and
  admits queued requests into the vacated slots — continuous batching,
  so a long sequence never convoys short ones. Admission is bounded
  (``MXNET_GEN_QUEUE`` requests) with block/reject backpressure, and
  page-pool admission control reserves worst-case pages up front so a
  mid-flight cache extension can never deadlock. Results stream through
  per-request handles (a future for the full output + a token iterator).

Weights come straight from training: any
:class:`~...parallel.transformer.TransformerParallel` checkpoint decodes
here through the shared layer math (``decode_forward`` /
``prefill_forward``).
"""
from __future__ import annotations

import collections
import threading
import time
import weakref

import numpy as np

from ...config import get_flag
from ...observability import request_trace as _rtrace
from ...observability import stats_schema as _schema
from ...resilience import DeadlineExceeded, faults as _faults
from ..buckets import pick_bucket
from ..control import PrefixCache, SLOClass, resolve_class
from ..control.slo import ClassQueue
from ..engine import QueueFullError, ServerClosedError
from .kv_cache import PagePool
from .sampling import SamplingParams, sample_tokens, verify_tokens
from .speculative import ngram_propose

# chaos-testable injection point (resilience/faults.py): a raise here
# is contained by the scheduler — the slots in the faulted step fail,
# their pages free, and the loop keeps serving queued requests
_faults.declare("generation.decode_step",
                doc="inside one continuous-batching decode iteration, "
                    "before the compiled step dispatches")

__all__ = ["GenerationConfig", "Generator", "GenerationHandle",
           "SamplingParams", "SLOClass", "QueueFullError",
           "ServerClosedError", "DeadlineExceeded"]

# the generation.page_size / generation.decode_blocks / generation.
# kv_dtype knobs this engine consults (explicit config arg > tuning
# cache > MXNET_GEN_* flag) are declared in autotune/__init__ — like
# graph.layout, this module loads lazily, and registry.get must work in
# a process that never imported it

# valid KV-page storage dtypes ("model" = the checkpoint's dtype)
KV_DTYPES = frozenset({"model", "bfloat16", "int8"})


def _quantize_kv(arr):
    """Symmetric-int8 quantization of K/V vectors along head_dim: one
    fp32 scale per (…, head). Traced inside the prefill/decode programs
    — the cast to int8 happens before the HBM scatter, so pages (and
    the decode gather they feed) move quarter-width bytes."""
    import jax.numpy as jnp

    a32 = arr.astype(jnp.float32)
    amax = jnp.max(jnp.abs(a32), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(a32 / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def default_prefill_ladder(max_seq):
    """Power-of-two prompt-length buckets up to ``max_seq`` (always
    topped by ``max_seq`` itself so any admissible prompt fits)."""
    ladder, b = [], 16
    while b < max_seq:
        ladder.append(b)
        b <<= 1
    ladder.append(int(max_seq))
    return tuple(sorted(set(ladder)))


def generation_tune_key(model, max_batch, max_seq):
    """The ``generation.*`` tuning-cache key for one (checkpoint shape,
    slot geometry) — shared by :class:`Generator`'s consult and
    ``autotune.tune_generation``'s record so they can never drift."""
    c = model.cfg
    sig = "lm-L%d-d%d-H%d-ff%d-e%d-V%d-%s" % (
        c["n_layers"], c["d_model"], c["n_heads"], c["d_ff"],
        c["n_experts"], c["vocab"], np.dtype(model.dtype).name)
    return (sig, "B%d-T%d" % (int(max_batch), int(max_seq)))


class GenerationConfig:
    """Knobs for :class:`Generator`. Defaults come from the
    ``MXNET_GEN_*`` environment (docs/generation.md has the tuning
    table); ``page_size``/``decode_blocks`` left unset resolve through
    the autotuner cache first (docs/autotune.md)."""

    def __init__(self, page_size=None, decode_blocks=None, max_batch=None,
                 max_seq=None, pool_pages=None, prefill_buckets=None,
                 max_queue=None, backpressure=None, submit_timeout_ms=None,
                 amp=None, kv_dtype=None, prefix_cache=None,
                 prefix_pages=None, slo_aging_ms=None, deadline_ms=None,
                 spec_k=None, spec_ngram=None):
        import os

        # None = follow the graph-pass layer (amp in MXNET_GRAPH_PASSES);
        # True/False force the bf16 prefill/decode rewrite per bind
        self.amp = amp
        # KV-page storage dtype: None resolves in Generator (explicit >
        # generation.kv_dtype tuning-cache entry > MXNET_GEN_KV_DTYPE >
        # "model"). "int8" stores symmetric-int8 pages with per-
        # (position, head) fp32 scales alongside — the decode-bandwidth
        # lever (ISSUE 11); "bfloat16" halves fp32 pools without scales
        if kv_dtype is not None:
            kv_dtype = str(kv_dtype).lower()
            if kv_dtype not in KV_DTYPES:
                raise ValueError("kv_dtype must be one of %s, got %r"
                                 % (sorted(KV_DTYPES), kv_dtype))
        self.kv_dtype = kv_dtype
        # None = resolve in Generator: explicit > tuning cache > flag
        self.page_size = None if page_size is None else int(page_size)
        self.decode_blocks = (None if decode_blocks is None
                              else int(decode_blocks))
        self.max_batch = (get_flag("MXNET_GEN_MAX_BATCH")
                          if max_batch is None else int(max_batch))
        self.max_seq = (get_flag("MXNET_GEN_MAX_SEQ")
                        if max_seq is None else int(max_seq))
        self.pool_pages = (get_flag("MXNET_GEN_POOL_PAGES")
                           if pool_pages is None else int(pool_pages))
        if prefill_buckets is None:
            spec = os.environ.get("MXNET_GEN_PREFILL_BUCKETS", "").strip()
            prefill_buckets = ([int(t) for t in
                                spec.replace(",", " ").split()]
                               if spec else default_prefill_ladder(
                                   self.max_seq))
        self.prefill_buckets = tuple(sorted(set(
            int(b) for b in prefill_buckets)))
        self.max_queue = (get_flag("MXNET_GEN_QUEUE")
                          if max_queue is None else int(max_queue))
        self.backpressure = (backpressure if backpressure is not None
                             else os.environ.get("MXNET_GEN_BACKPRESSURE",
                                                 "block"))
        # 0 = block forever (legacy); >0 = a full queue that stays full
        # this many ms raises QueueFullError instead of wedging the
        # caller with no escape hatch
        self.submit_timeout_ms = (get_flag("MXNET_GEN_SUBMIT_TIMEOUT")
                                  if submit_timeout_ms is None
                                  else float(submit_timeout_ms))
        if self.submit_timeout_ms < 0:
            raise ValueError("submit_timeout_ms must be >= 0 (0 = no "
                             "timeout)")
        # ---- serving control plane (ISSUE 14) ----
        # radix-tree prefix cache: opt-in (MXNET_GEN_PREFIX_CACHE) — a
        # cold engine keeps the PR 7 prefill numeric path bit-for-bit
        self.prefix_cache = (bool(get_flag("MXNET_GEN_PREFIX_CACHE"))
                             if prefix_cache is None else bool(prefix_cache))
        # None = resolve in Generator: explicit > tuning cache > flag
        self.prefix_pages = (None if prefix_pages is None
                             else int(prefix_pages))
        self.slo_aging_ms = (None if slo_aging_ms is None
                             else float(slo_aging_ms))
        # default queue deadline for every SLO class that doesn't carry
        # its own — the MXNET_SERVING_DEADLINE_MS analog (0 = off):
        # expired-in-queue requests fail DeadlineExceeded BEFORE prefill
        self.deadline_ms = (float(get_flag("MXNET_GEN_DEADLINE_MS"))
                            if deadline_ms is None else float(deadline_ms))
        # ---- speculative decoding (ISSUE 16) ----
        # spec_k: draft tokens proposed per slot per step; 0 = off (the
        # PR 7 decode path bit-for-bit). None = resolve in Generator:
        # explicit > generation.spec_k tuning cache > MXNET_GEN_SPEC_K
        self.spec_k = None if spec_k is None else int(spec_k)
        self.spec_ngram = (int(get_flag("MXNET_GEN_SPEC_NGRAM"))
                           if spec_ngram is None else int(spec_ngram))
        if self.spec_k is not None and self.spec_k < 0:
            raise ValueError("spec_k must be >= 0 (0 = speculation off)")
        if self.spec_ngram < 1:
            raise ValueError("spec_ngram must be >= 1")
        if self.deadline_ms < 0:
            raise ValueError("deadline_ms must be >= 0 (0 = no deadline)")
        if self.prefix_pages is not None and self.prefix_pages < 0:
            raise ValueError("prefix_pages must be >= 0 (0 = pool-bounded)")
        if self.slo_aging_ms is not None and self.slo_aging_ms < 0:
            raise ValueError("slo_aging_ms must be >= 0 (0 = no aging)")
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if self.max_seq < 2:
            raise ValueError("max_seq must be >= 2")
        if self.backpressure not in ("block", "reject"):
            raise ValueError("backpressure must be 'block' or 'reject', "
                             "got %r" % (self.backpressure,))
        if not self.prefill_buckets or self.prefill_buckets[0] < 1:
            raise ValueError("prefill_buckets must be positive ints")
        if self.prefill_buckets[-1] > self.max_seq:
            raise ValueError(
                "largest prefill bucket %d exceeds max_seq %d"
                % (self.prefill_buckets[-1], self.max_seq))


class GenerationHandle:
    """One request's result surface: ``result()`` blocks for the full
    generated-token list; ``stream()`` yields tokens as the scheduler
    produces them (iteration-level granularity)."""

    def __init__(self):
        import concurrent.futures

        self.future = concurrent.futures.Future()
        self._tokens = collections.deque()
        self._cond = threading.Condition()
        self._closed = False          # guarded-by: self._cond

    # scheduler-side -----------------------------------------------------
    def _push(self, token):
        with self._cond:
            self._tokens.append(token)
            self._cond.notify_all()

    def _finish(self, tokens):
        try:
            self.future.set_result(list(tokens))
        except Exception:
            pass  # future cancelled by the caller: same terminal state
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def _fail(self, err):
        try:
            if not self.future.done():
                self.future.set_exception(err)
        except Exception:
            pass
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    # caller-side --------------------------------------------------------
    def result(self, timeout=None):
        """The full generated token list (excludes the prompt)."""
        return self.future.result(timeout)

    def done(self):
        return self.future.done()

    def stream(self, timeout=None):
        """Yield generated tokens as they arrive; raises the request's
        error (if any) once the stream drains."""
        while True:
            with self._cond:
                while not self._tokens and not self._closed:
                    if not self._cond.wait(timeout):
                        raise TimeoutError("no token within %ss" % timeout)
                if self._tokens:
                    tok = self._tokens.popleft()
                else:
                    break
            yield tok
        err = self.future.exception() if self.future.done() else None
        if err is not None:
            raise err


class _Seq:
    """Scheduler-side state of one admitted sequence (slot-resident)."""

    __slots__ = ("handle", "prompt", "prompt_len", "params", "tokens",
                 "worst", "t_submit", "t_first", "t_last", "trace", "slo")

    def __init__(self, handle, prompt, params, worst, t_submit,
                 trace=_rtrace.NOOP_TRACE, slo=None):
        self.handle = handle
        self.prompt = prompt          # token list (prefix-cache insert)
        self.prompt_len = len(prompt)
        self.params = params          # SamplingParams
        self.worst = worst            # worst-case cached tokens (pages)
        self.tokens = []              # generated so far
        self.t_submit = t_submit
        self.t_first = None
        self.t_last = None            # last token instant (ITL)
        self.trace = trace            # RequestTrace (submit -> evict)
        self.slo = slo if slo is not None else resolve_class(None)


_Pending = collections.namedtuple(
    "_Pending", ["prompt", "params", "handle", "t_submit", "trace",
                 "slo", "deadline"])

# every live generator, GC-pruned — ONE "generation" flight-recorder
# provider walks them (same discipline as serving._live_servers)
_live_generators = weakref.WeakSet()

# gauges owned by a Generator (the KV gauges belong to its PagePool,
# which dies with it): removed from the registry when the owner stops
# or is collected so /metrics never serves a dead engine's last values
_GENERATOR_GAUGES = ("generation.slo_queue_depth",
                     "generation.decode_batch_occupancy",
                     "generation.kv_pages_used",
                     "generation.kv_bytes_used")


def _generators_state():
    views = []
    for gen in list(_live_generators):
        try:
            views.append(gen.get_stats())
        except Exception as err:
            views.append({"error": repr(err)})
    if not views:
        return None
    return views[0] if len(views) == 1 else {"generators": views}


class Generator:
    """Continuous-batching autoregressive generator for one checkpoint.

    ::

        model = TransformerParallel(mesh, vocab=..., ...)
        params = model.load_checkpoint("ckpt")     # or model.init(seed)
        gen = generation.Generator(model, params)
        h = gen.submit([1, 2, 3], SamplingParams(max_new_tokens=16))
        for tok in h.stream():
            ...                                    # or h.result()
        gen.stop()                                 # drains by default

    ``model`` is a :class:`~...parallel.transformer.TransformerParallel`
    (its layer math is shared between training, prefill and decode, so
    any training checkpoint serves unchanged); ``params`` its parameter
    dict. Unset ``page_size``/``decode_blocks`` resolve through the
    autotuner (``generation.*`` tuning-cache entries recorded by
    ``autotune.tune_generation``), then the ``MXNET_GEN_*`` flags.

    **Speculative decoding** (docs/generation.md): with ``spec_k > 0``
    each scheduler iteration proposes k draft tokens per slot and
    verifies all k+1 positions in ONE compiled batched-verify program —
    token-exact vs non-speculative decode (``sampling.verify_tokens``).
    Passing ``draft_model``/``draft_params`` (a smaller
    TransformerParallel checkpoint with the SAME vocab) selects the
    draft-model proposer; otherwise the model-free n-gram/prompt-lookup
    proposer runs. ``spec_k == 0`` (the default) keeps the PR 7 decode
    path bit-for-bit.
    """

    def __init__(self, model, params, config=None, start=True,
                 draft_model=None, draft_params=None):
        import jax

        self._model = model
        self._params = params
        cfg = config if config is not None else GenerationConfig()
        self._cfg = cfg
        c = model.cfg
        self._tune_key = generation_tune_key(model, cfg.max_batch,
                                             cfg.max_seq)
        self.page_size = self._resolve("generation.page_size", "page_size",
                                       cfg.page_size, "MXNET_GEN_PAGE_SIZE")
        self.decode_blocks = self._resolve(
            "generation.decode_blocks", "decode_blocks", cfg.decode_blocks,
            "MXNET_GEN_DECODE_BLOCKS")
        # ---- speculative decoding (ISSUE 16) --------------------------
        # consult order: explicit config > generation.spec_k tuning-cache
        # entry > MXNET_GEN_SPEC_K (corrupt cache entries degrade to the
        # flag); k = 0 keeps the non-speculative decode path bit-for-bit
        self.spec_k = self._resolve("generation.spec_k", "spec_k",
                                    cfg.spec_k, "MXNET_GEN_SPEC_K",
                                    minimum=0)
        self.spec_ngram = int(cfg.spec_ngram)
        self._draft_model = draft_model
        if draft_model is not None:
            if draft_params is None:
                raise ValueError("draft_model requires draft_params")
            if int(draft_model.cfg["vocab"]) != int(c["vocab"]):
                raise ValueError(
                    "draft model vocab %d != target vocab %d — draft "
                    "proposals must be target token ids"
                    % (draft_model.cfg["vocab"], c["vocab"]))
        self.spec_mode = ("off" if self.spec_k == 0
                          else "draft" if draft_model is not None
                          else "ngram")
        self._spec_draft = self.spec_mode == "draft"
        self._draft_params = draft_params if self._spec_draft else None
        # mixed-precision policy for the prefill/decode program builds:
        # the graph-pass layer's amp rewrite, applied functionally (the
        # model is jax functions, not a symbol graph) — params cast to
        # bf16 at program entry, logits returned to fp32 before sampling
        # (the fp32 island), all inside the compiled programs. Opt-in:
        # GenerationConfig(amp=True) or amp in MXNET_GRAPH_PASSES.
        from ... import graph_pass

        if cfg.amp is None:
            self._amp = "amp" in graph_pass.PassConfig().passes
        else:
            self._amp = bool(cfg.amp)
        if self._amp:
            # cast ONCE at construction so the device holds (and every
            # decode step reads) half-width weights — an in-program cast
            # would stream fp32 from HBM each step and deliver none of
            # the bandwidth win on the HBM-bound decode path
            self._params = self._amp_params(params)
            if self._draft_params is not None:
                self._draft_params = self._amp_params(self._draft_params)
            graph_pass.note_program(
                "generation", amp=True,
                dtype=str(np.dtype(model.dtype).name),
                tune_key=list(self._tune_key))

        S = cfg.max_batch
        self._max_pages = -(-cfg.max_seq // self.page_size)
        pool_pages = cfg.pool_pages or (S * self._max_pages + 1)

        L, H = c["n_layers"], c["n_heads"]
        hd = c["d_model"] // H
        dt = np.dtype(model.dtype)
        # KV-page storage dtype (ISSUE 11): "model" keeps the checkpoint
        # dtype; "bfloat16"/"int8" store narrower pages — the decode
        # step is an HBM-gather workload, so page width IS its bandwidth
        self.kv_dtype = self._resolve_kv_dtype(cfg.kv_dtype)
        self._quant_kv = self.kv_dtype == "int8"
        if self.kv_dtype == "model":
            pool_dt = dt
        elif self.kv_dtype == "int8":
            pool_dt = np.dtype(np.int8)
        else:
            import jax.numpy as jnp

            pool_dt = np.dtype(jnp.bfloat16)
        # device bytes per cached token: K + V across layers/heads at
        # the pool dtype, plus the per-(position, head) fp32 scales an
        # int8 pool stores alongside — the PagePool byte model behind
        # the kv_bytes_used gauge
        bytes_per_token = 2 * L * H * hd * pool_dt.itemsize
        if self._quant_kv:
            bytes_per_token += 2 * L * H * 4
        self.pool = PagePool(pool_pages, self.page_size,
                             bytes_per_token=bytes_per_token,
                             kv_dtype=self.kv_dtype)

        # ---- serving control plane (ISSUE 14) -------------------------
        # prefix cache: radix tree over page-aligned token blocks sharing
        # KV pages COW across requests (serving/control/prefix_cache.py)
        self._use_prefix = bool(cfg.prefix_cache)
        if self._use_prefix:
            cap = self._resolve("control.prefix_pages", "prefix_pages",
                                cfg.prefix_pages, "MXNET_GEN_PREFIX_PAGES",
                                minimum=0)
            self.prefix_cache = PrefixCache(self.pool, capacity_pages=cap)
        else:
            self.prefix_cache = None
        # SLO admission: priority tiers with aging between decode steps
        # (serving/control/slo.py); aging_ms = 0 disables the boost
        self._aging_ms = self._resolve("control.slo_aging", "aging_ms",
                                       cfg.slo_aging_ms,
                                       "MXNET_GEN_SLO_AGING_MS", minimum=0)

        # committed to the model's device: an UNcommitted fresh pool
        # would carry a different sharding signature than the compiled
        # programs' outputs and cost one spurious recompile per bucket
        self._pool_shape = (L, pool_pages, self.page_size, H, hd)
        self._scale_shape = (L, pool_pages, self.page_size, H)
        self._pool_dtype = pool_dt
        # draft-model KV planes ride in the SAME donated pools pytree
        # ("dk"/"dv", same page geometry): COW page copies, trash-page
        # masking, donation and _recover_pools apply to the draft cache
        # for free, and target + draft K/V for a page's positions always
        # travel together (prefix sharing stays consistent). Draft pages
        # are never quantized — the draft is already the small model.
        if self._spec_draft:
            dc = draft_model.cfg
            self._draft_pool_shape = (
                dc["n_layers"], pool_pages, self.page_size,
                dc["n_heads"], dc["d_model"] // dc["n_heads"])
            self._draft_pool_dtype = np.dtype(draft_model.dtype)
            # accounted separately from bytes_per_token (the TARGET-
            # cache byte model behind kv_bytes_used); get_stats surfaces
            self.draft_bytes_per_token = (
                2 * dc["n_layers"] * dc["d_model"]
                * self._draft_pool_dtype.itemsize)
        else:
            self.draft_bytes_per_token = 0
        # fresh pools are placed exactly as a program returns them —
        # replicated over the model's mesh. An aval carries its mesh, so
        # a pool placed on a bare device would give the first warmed
        # bucket a different jit key from the one traffic then presents
        # (a program's output), and that bucket would compile twice
        from jax.sharding import NamedSharding, PartitionSpec

        self._pool_sharding = NamedSharding(model.mesh, PartitionSpec())
        self._pools = self._fresh_pools()  # guarded-by: self._pages_lock
        if self._quant_kv:
            # provenance: crash dumps must say this engine's programs
            # decode against quantized pages (the amp-note discipline)
            graph_pass.note_program(
                "generation", kv_dtype=self.kv_dtype,
                tune_key=list(self._tune_key))

        # slot state: scheduler-thread-only numpy mirrors of the decode
        # program's inputs (no lock — only _loop touches them)
        self._page_table = np.zeros((S, self._max_pages), np.int32)
        self._seq_len = np.zeros(S, np.int32)
        self._active = np.zeros(S, bool)
        self._last_token = np.zeros(S, np.int32)
        self._temp = np.zeros(S, np.float32)
        self._top_k = np.zeros(S, np.int32)
        self._keys = np.zeros((S, 2), np.uint32)
        self._slots = [None] * S      # _Seq per occupied slot

        self._cond = threading.Condition()
        # per-SLO-class FIFO queues with priority + aging selection —
        # FIFO within a class, weighted admission between classes
        self._queue = ClassQueue(aging_ms=self._aging_ms)  # guarded-by: self._cond
        self._stop = False                  # guarded-by: self._cond
        self._abort = False                 # guarded-by: self._cond
        self._n_active = 0                  # guarded-by: self._cond

        self._lock = threading.Lock()
        self._stats = collections.Counter()  # guarded-by: self._lock
        # serializes page-pool rebinds: the scheduler thread owns them in
        # steady state, but warmup() runs on the caller's thread
        self._pages_lock = threading.Lock()

        # donation lets XLA update the page pools in place; CPU has no
        # donation support, so skip it there (avoids a per-compile warn).
        # The whole pool pytree (pages + int8 scales) is ONE argument.
        donate = () if jax.default_backend() == "cpu" else (1,)
        self._donating = bool(donate)
        self._decode_jit = jax.jit(self._decode_step, donate_argnums=donate)
        self._prefill_jit = jax.jit(self._prefill_step,
                                    donate_argnums=donate)
        # speculative programs: ONE batched verify (+ ONE draft decode
        # in draft mode) — the whole compile-count delta of speculation
        self._verify_jit = (jax.jit(self._verify_step,
                                    donate_argnums=donate)
                            if self.spec_k else None)
        self._draft_jit = (jax.jit(self._draft_decode_step,
                                   donate_argnums=donate)
                           if self._spec_draft else None)

        self._thread = None
        self._life = threading.Lock()  # serializes start()/stop()
        _live_generators.add(self)
        from ...observability import flight_recorder, metrics

        flight_recorder.register_provider("generation", _generators_state)
        # a collected (not stopped) generator must not leave its gauges
        # frozen at their last values in /metrics
        metrics.unregister_on_collect(self, _GENERATOR_GAUGES)
        if start:
            self.start()

    def _amp_params(self, params):
        """The amp pass applied to this engine's functional programs:
        fp32 parameter leaves cast to bf16 ONCE at construction, so the
        device-resident copy every prefill/decode program reads is
        half-width (the bn_fold/fold analog of baking the rewrite into
        the weights). No-op when amp is off — token-exactness is the
        default contract."""
        if not self._amp:
            return params
        import jax
        import jax.numpy as jnp

        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16)
            if getattr(a, "dtype", None) == jnp.float32 else a, params)

    def _fresh_pools(self):
        """The device KV state as ONE donated pytree: K and V page
        pools, plus their fp32 scale pools in int8 mode. A dict (not
        two attributes) so the quantized layout threads through the
        compiled programs without forking their signatures."""
        import jax

        pools = {"k": np.zeros(self._pool_shape, self._pool_dtype),
                 "v": np.zeros(self._pool_shape, self._pool_dtype)}
        if self._quant_kv:
            pools["ks"] = np.zeros(self._scale_shape, np.float32)
            pools["vs"] = np.zeros(self._scale_shape, np.float32)
        if self._spec_draft:
            pools["dk"] = np.zeros(self._draft_pool_shape,
                                   self._draft_pool_dtype)
            pools["dv"] = np.zeros(self._draft_pool_shape,
                                   self._draft_pool_dtype)
        return jax.device_put(pools, self._pool_sharding)

    def _recover_pools(self, err):
        """After a FAILED donated prefill/decode call the old pool
        buffers may already be consumed — every later call would then
        die on a donated-buffer error, failing 100% of traffic while
        the generator looks alive. Re-materialize empty pools and evict
        every active sequence (their cached K/V went down with the old
        buffers). No-op when donation is off (CPU): the old pools are
        still valid there and unaffected sequences keep their cache."""
        if not self._donating:
            return
        for slot, seq in enumerate(self._slots):
            if seq is not None:
                self._evict(slot, failed=err)
        with self._pages_lock:
            self._pools = self._fresh_pools()

    def _resolve(self, op, field, explicit, flag, minimum=1):
        """Knob resolution: explicit config arg > tuning cache > flag.
        ``minimum`` bounds what a cache entry may supply (the control
        knobs accept 0 = off/unbounded; the geometry knobs don't)."""
        if explicit is not None:
            return int(explicit)
        from ... import autotune

        tuned = autotune.lookup(op, key=self._tune_key)
        if isinstance(tuned, dict):
            try:
                val = int(tuned.get(field))
                if val >= minimum:
                    return val
            except (TypeError, ValueError):
                pass  # corrupt cache entry: tuning is an optimization
        return int(get_flag(flag))

    def _resolve_kv_dtype(self, explicit):
        """KV-page dtype resolution: explicit config arg >
        ``generation.kv_dtype`` tuning-cache entry
        (autotune.tune_generation_kv arbitrates int8 vs bf16 against a
        token-agreement budget) > MXNET_GEN_KV_DTYPE env > "model"."""
        import os

        if explicit is not None:
            return explicit  # validated by GenerationConfig
        from ... import autotune

        tuned = autotune.lookup("generation.kv_dtype", key=self._tune_key)
        if isinstance(tuned, dict):
            val = str(tuned.get("kv_dtype", "")).lower()
            if val in KV_DTYPES:
                return val
        env = os.environ.get("MXNET_GEN_KV_DTYPE", "").strip().lower()
        return env if env in KV_DTYPES else "model"

    @classmethod
    def from_checkpoint(cls, path, model, **kwargs):
        """Generator over a :meth:`TransformerParallel.save_checkpoint`
        file — the training-to-serving handoff."""
        return cls(model, model.load_checkpoint(path), **kwargs)

    # -------------------------------------------------- compiled programs
    def _scatter_kv(self, pools, dest, off, k_new, v_new):
        """Write new K/V vectors into the page pools at (dest, off) —
        quantizing on the way in int8 mode (scales land in the scale
        pools at the same coordinates). ``k_new``/``v_new``:
        (L, n, H, hd) [prefill rows] or (L, S, H, hd) [decode]."""
        pools = dict(pools)
        if self._quant_kv:
            kq, ksc = _quantize_kv(k_new)
            vq, vsc = _quantize_kv(v_new)
            pools["k"] = pools["k"].at[:, dest, off].set(kq)
            pools["v"] = pools["v"].at[:, dest, off].set(vq)
            pools["ks"] = pools["ks"].at[:, dest, off].set(ksc)
            pools["vs"] = pools["vs"].at[:, dest, off].set(vsc)
        else:
            dt = pools["k"].dtype
            pools["k"] = pools["k"].at[:, dest, off].set(k_new.astype(dt))
            pools["v"] = pools["v"].at[:, dest, off].set(v_new.astype(dt))
        return pools

    def _suffix_attend(self, pools, page_row, prefix_len,
                       kname="k", vname="v", quant=None):
        """Attention hook for the control plane's suffix prefill: each
        suffix query attends the cached prefix — gathered from the paged
        pool through this slot's page row, masked to ``prefix_len`` —
        plus the causal suffix itself. Scores, softmax and the PV
        contraction accumulate in fp32 (the subsystem-wide discipline),
        and int8 pools dequantize on gather exactly like
        ``paged_decode_attention``. ``prefix_len == 0`` (a cache miss,
        or warmup) masks the whole gathered region, so ONE compiled
        program per bucket serves hit and miss traffic alike — the
        compile-count contract stays ``len(prefill_buckets) + 1``.
        The flip side: a cache-enabled engine's MISSES also pay the
        masked prefix-region gather/scores (~bucket x max_seq extra per
        layer), which is why the cache is opt-in — no-sharing
        workloads keep the lean cold program (docs/serving_control.md
        "Miss-path cost").

        ``kname``/``vname``/``quant`` select which page planes the hook
        reads: the defaults are the target cache; the speculative
        draft-model prefill passes ``"dk"``/``"dv"``, ``quant=False``
        (draft pages are never quantized)."""
        import jax.numpy as jnp

        max_ctx = self._max_pages * self.page_size
        quant = self._quant_kv if quant is None else bool(quant)

        def attend(li, q, k, v):
            T, hd = q.shape[2], q.shape[3]
            kp = pools[kname][li][page_row].reshape(max_ctx, -1, hd)
            vp = pools[vname][li][page_row].reshape(max_ctx, -1, hd)
            kp = kp.astype(jnp.float32)
            vp = vp.astype(jnp.float32)
            if quant:
                kp = kp * pools["ks"][li][page_row].reshape(
                    max_ctx, -1)[..., None]
                vp = vp * pools["vs"][li][page_row].reshape(
                    max_ctx, -1)[..., None]
                # the fresh suffix K/V attend through the SAME
                # quantize->dequantize round trip their pages will hold:
                # a later request that reads these positions from the
                # cache then sees bit-identical values, so warm-cache
                # and cold-cache generations agree token-for-token even
                # at int8 (the sharing-exactness contract)
                kq, ksc = _quantize_kv(k)
                vq, vsc = _quantize_kv(v)
                k = kq.astype(jnp.float32) * ksc[..., None]
                v = vq.astype(jnp.float32) * vsc[..., None]
            else:
                # same discipline for narrow non-quantized pools
                # (kv_dtype="bfloat16" under an fp32 model): round-trip
                # the fresh suffix K/V through the pages' storage dtype
                # so warm- and cold-cache runs see identical values.
                # A no-op when pool dtype == model dtype.
                k = k.astype(pools[kname].dtype)
                v = v.astype(pools[vname].dtype)
            scale = float(1.0 / np.sqrt(hd))
            qf = q.astype(jnp.float32) * scale
            sp = jnp.einsum("bhqd,khd->bhqk", qf, kp)
            live = jnp.arange(max_ctx, dtype=jnp.int32) < prefix_len
            sp = jnp.where(live[None, None, None, :], sp, -jnp.inf)
            ss = jnp.einsum("bhqd,bhkd->bhqk", qf, k.astype(jnp.float32))
            causal = jnp.tril(jnp.ones((T, T), bool))
            ss = jnp.where(causal[None, None], ss, -jnp.inf)
            s = jnp.concatenate([sp, ss], axis=-1)
            # every row's own (causal-diagonal) score is live -> the max
            # is finite and the softmax denominator positive
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            p = jnp.where(jnp.isneginf(s), 0.0, p)
            w = p / jnp.sum(p, axis=-1, keepdims=True)
            out = (jnp.einsum("bhqk,khd->bhqd", w[..., :max_ctx], vp)
                   + jnp.einsum("bhqk,bhkd->bhqd", w[..., max_ctx:],
                                v.astype(jnp.float32)))
            return out.astype(q.dtype)

        return attend

    def _prefill_step(self, params, pools, tokens, length, prefix_len,
                      page_row, cow_src, cow_dst, key, temp, top_k,
                      draft_params):
        """ONE compiled program per prompt bucket: causal forward over
        the (suffix) tokens, K/V scattered into the paged cache, first
        token sampled. ``tokens``: (1, bucket) int32; ``page_row``:
        (max_pages,) int32 (0-padded — unallocated positions scatter to
        the trash page).

        With the prefix cache active, ``tokens`` holds only the SUFFIX
        past the longest cached prefix: ``prefix_len`` global positions
        are served read-only from shared pages through the attention
        hook, and the ``cow_src -> cow_dst`` page copy privatizes the
        last shared page before the one write that may land in it (the
        page-aligned full-prefix-hit case; 0 -> 0 is a trash-page
        no-op). Prefix length, like batch composition, is DATA — the
        compile count stays ``len(prefill_buckets) + 1``.

        In draft-model speculation mode the draft's prefill is FUSED
        into this same program (``draft_params`` non-None): the draft
        forward scatters its K/V into the ``dk``/``dv`` planes at the
        same page coordinates, so the per-bucket compile count never
        grows. ``draft_params`` is None (an empty pytree, not a traced
        value) in every other mode."""
        import jax.numpy as jnp

        bucket = tokens.shape[1]
        if self._use_prefix:
            pools = {n: a.at[:, cow_dst].set(a[:, cow_src])
                     for n, a in pools.items()}
            attend = self._suffix_attend(pools, page_row, prefix_len)
        else:
            attend = None  # cold engines keep the PR 7 path bit-for-bit
        logits, ks, vs = self._model.prefill_forward(params, tokens,
                                                     attend=attend)
        logits = logits.astype(jnp.float32)  # fp32 sampling island
        pos = prefix_len + jnp.arange(bucket, dtype=jnp.int32)
        pidx = pos // self.page_size
        # padded suffix rows past the page table scatter to the trash
        # page (a suffix bucket may overhang max_seq when the prefix is
        # long; page_row is 0 beyond the owned pages either way)
        dest = jnp.where(pidx < self._max_pages,
                         page_row[jnp.minimum(pidx, self._max_pages - 1)],
                         0)
        off = pos % self.page_size
        pools = self._scatter_kv(pools, dest, off, ks[:, 0], vs[:, 0])
        if self._spec_draft:
            d_attend = (self._suffix_attend(pools, page_row, prefix_len,
                                            kname="dk", vname="dv",
                                            quant=False)
                        if self._use_prefix else None)
            _, dks, dvs = self._draft_model.prefill_forward(
                draft_params, tokens, attend=d_attend)
            ddt = pools["dk"].dtype
            pools = dict(pools)
            pools["dk"] = pools["dk"].at[:, dest, off].set(
                dks[:, 0].astype(ddt))
            pools["dv"] = pools["dv"].at[:, dest, off].set(
                dvs[:, 0].astype(ddt))
        last = logits[0, length - 1]
        tok, new_key = sample_tokens(last[None], key[None], temp[None],
                                     top_k[None])
        return pools, tok[0], new_key[0]

    def _decode_step(self, params, pools, page_table, seq_len,
                     active, last_token, temp, top_k, keys):
        """THE decode program: one step for every slot, active or not.
        Fixed shapes throughout — batch composition, sequence lengths
        and sampling mixes are all data, never compile keys. The pool
        dtype (int8 vs model/bf16) is part of the program's SIGNATURE —
        one compiled decode program per pool mode, never per batch."""
        import jax.numpy as jnp

        from ...parallel.flash_attention import paged_decode_attention

        S = self._cfg.max_batch
        page = self.page_size
        rows = jnp.arange(S)
        pidx = seq_len // page
        off = seq_len % page
        # inactive slots scatter to the trash page 0; active slots own
        # disjoint pages, so the writes never collide
        dest = jnp.where(active, page_table[rows, pidx], 0)
        state = dict(pools)
        quant = self._quant_kv

        def attend(li, q, k_new, v_new):
            if quant:
                kq, ksc = _quantize_kv(k_new)
                vq, vsc = _quantize_kv(v_new)
                state["k"] = state["k"].at[li, dest, off].set(kq)
                state["v"] = state["v"].at[li, dest, off].set(vq)
                state["ks"] = state["ks"].at[li, dest, off].set(ksc)
                state["vs"] = state["vs"].at[li, dest, off].set(vsc)
            else:
                dt = state["k"].dtype
                state["k"] = state["k"].at[li, dest, off].set(
                    k_new.astype(dt))
                state["v"] = state["v"].at[li, dest, off].set(
                    v_new.astype(dt))
            return paged_decode_attention(
                q, state["k"][li], state["v"][li], page_table, seq_len + 1,
                block_tokens=self.decode_blocks,
                k_scale=state["ks"][li] if quant else None,
                v_scale=state["vs"][li] if quant else None)

        logits = self._model.decode_forward(params, last_token, attend)
        logits = logits.astype(jnp.float32)  # fp32 sampling island
        toks, new_keys = sample_tokens(logits, keys, temp, top_k)
        toks = jnp.where(active, toks, -1)
        new_keys = jnp.where(active[:, None], new_keys, keys)
        return state, toks, new_keys

    def _draft_decode_step(self, draft_params, pools, page_table,
                           seq_len, active, token):
        """THE draft-decode program (draft-model speculation mode): one
        greedy step of the draft model against its ``dk``/``dv`` page
        planes — the existing paged decode path at draft scale. Called
        k times per scheduler iteration with ``seq_len + j`` (the draft
        cache advancing through the candidate positions); masked slots
        scatter to the trash page. Greedy on purpose: proposals are
        hints the verify step checks, so draft sampling noise would only
        lower acceptance, never change outputs. Compile count: 1."""
        import jax.numpy as jnp

        from ...parallel.flash_attention import paged_decode_attention

        S = self._cfg.max_batch
        page = self.page_size
        rows = jnp.arange(S)
        pidx = jnp.minimum(seq_len // page, self._max_pages - 1)
        off = seq_len % page
        dest = jnp.where(active, page_table[rows, pidx], 0)
        state = dict(pools)

        def attend(li, q, k_new, v_new):
            dt = state["dk"].dtype
            state["dk"] = state["dk"].at[li, dest, off].set(
                k_new.astype(dt))
            state["dv"] = state["dv"].at[li, dest, off].set(
                v_new.astype(dt))
            return paged_decode_attention(
                q, state["dk"][li], state["dv"][li], page_table,
                seq_len + 1, block_tokens=self.decode_blocks)

        logits = self._draft_model.decode_forward(draft_params, token,
                                                  attend)
        nxt = jnp.argmax(logits.astype(jnp.float32), axis=-1)
        return state, nxt.astype(jnp.int32)

    def _verify_step(self, params, pools, page_table, seq_len, active,
                     last_token, draft, span, temp, top_k, keys):
        """THE batched-verify program of speculative decoding: all k+1
        candidate positions of every slot in ONE fixed-shape forward —
        a short-prefill shape (q-length k+1), not k sequential decodes.

        Position 0 is the slot's last committed token (exactly what the
        decode step would feed), positions 1..k its draft candidates.
        All k+1 K/V are scattered into the pages OPTIMISTICALLY —
        positions at or past ``span`` (the per-slot emission budget:
        min(k+1, remaining max_new)) land on the trash page, so writes
        never outrun the admission-time page reservation. Rejected
        positions need no device-side rollback: every attention path
        masks by committed length, so stale tail K/V is invisible until
        overwritten — only the host-side page accounting rolls back
        (``PagePool.shrink`` in ``_spec_once``). Acceptance itself is
        ``sampling.verify_tokens`` (token-exact sample-and-match).
        Fixed shapes throughout: batch composition, spans and accept
        patterns are DATA. Compile count: 1."""
        import jax.numpy as jnp

        from ...parallel.flash_attention import paged_verify_attention

        S = self._cfg.max_batch
        Q = self.spec_k + 1
        page = self.page_size
        tokens = jnp.concatenate([last_token[:, None], draft], axis=1)
        rows = jnp.arange(S)[:, None]
        pos = seq_len[:, None] + jnp.arange(Q, dtype=jnp.int32)[None, :]
        pidx = pos // page
        ok = (active[:, None]
              & (jnp.arange(Q, dtype=jnp.int32)[None, :] < span[:, None])
              & (pidx < self._max_pages))
        dest = jnp.where(
            ok, page_table[rows, jnp.minimum(pidx, self._max_pages - 1)],
            0)
        off = pos % page
        state = dict(pools)
        quant = self._quant_kv

        def attend(li, q, k_new, v_new):
            # (S, Q) scatter coordinates; masked positions collide on
            # the trash page, active ones are disjoint by construction
            if quant:
                kq, ksc = _quantize_kv(k_new)
                vq, vsc = _quantize_kv(v_new)
                state["k"] = state["k"].at[li, dest, off].set(kq)
                state["v"] = state["v"].at[li, dest, off].set(vq)
                state["ks"] = state["ks"].at[li, dest, off].set(ksc)
                state["vs"] = state["vs"].at[li, dest, off].set(vsc)
            else:
                dt = state["k"].dtype
                state["k"] = state["k"].at[li, dest, off].set(
                    k_new.astype(dt))
                state["v"] = state["v"].at[li, dest, off].set(
                    v_new.astype(dt))
            return paged_verify_attention(
                q, state["k"][li], state["v"][li], page_table, seq_len,
                block_tokens=self.decode_blocks,
                k_scale=state["ks"][li] if quant else None,
                v_scale=state["vs"][li] if quant else None)

        logits = self._model.verify_forward(params, tokens, attend)
        logits = logits.astype(jnp.float32)  # fp32 sampling island
        out, n_emit, new_keys = verify_tokens(logits, draft, span,
                                              active, keys, temp, top_k)
        return state, out, n_emit, new_keys

    def warmup(self):
        """Compile every prefill bucket plus the decode program against
        the trash page, so the first request never pays a compile.
        Returns the number of programs warmed.

        Safe to call even while traffic flows: warmup drives the
        programs with SYNTHETIC all-inactive state (zeros — identical
        shapes and dtypes to the live mirrors, writes land only on the
        trash page) rather than reading the scheduler thread's slot
        mirrors, and the page-pool rebinds serialize on the same lock
        the scheduler holds during its calls."""
        import jax

        # PRNGKey construction is itself a (tiny) jitted program; build
        # one now so admission never pays its compile
        np.asarray(jax.random.PRNGKey(0))
        S = self._cfg.max_batch
        n = 0
        with self._pages_lock:
            for bucket in self._cfg.prefill_buckets:
                pools, tok, _ = self._prefill_jit(
                    self._params, self._pools,
                    np.zeros((1, bucket), np.int32), np.int32(1),
                    np.int32(0), np.zeros(self._max_pages, np.int32),
                    np.int32(0), np.int32(0),
                    np.zeros(2, np.uint32), np.float32(0), np.int32(0),
                    self._draft_params)
                jax.block_until_ready(tok)
                self._pools = pools
                n += 1
            pools, toks, _ = self._decode_jit(
                self._params, self._pools,
                np.zeros((S, self._max_pages), np.int32),
                np.zeros(S, np.int32), np.zeros(S, bool),
                np.zeros(S, np.int32), np.zeros(S, np.float32),
                np.zeros(S, np.int32), np.zeros((S, 2), np.uint32))
            jax.block_until_ready(toks)
            self._pools = pools
            n += 1
            if self._verify_jit is not None:
                # the speculative programs: ONE verify (+ ONE draft
                # decode in draft mode) — warmed all-inactive like the
                # decode program, writes land only on the trash page
                pools, out, _, _ = self._verify_jit(
                    self._params, self._pools,
                    np.zeros((S, self._max_pages), np.int32),
                    np.zeros(S, np.int32), np.zeros(S, bool),
                    np.zeros(S, np.int32),
                    np.zeros((S, self.spec_k), np.int32),
                    np.zeros(S, np.int32), np.zeros(S, np.float32),
                    np.zeros(S, np.int32), np.zeros((S, 2), np.uint32))
                jax.block_until_ready(out)
                self._pools = pools
                n += 1
            if self._draft_jit is not None:
                pools, nxt = self._draft_jit(
                    self._draft_params, self._pools,
                    np.zeros((S, self._max_pages), np.int32),
                    np.zeros(S, np.int32), np.zeros(S, bool),
                    np.zeros(S, np.int32))
                jax.block_until_ready(nxt)
                self._pools = pools
                n += 1
        return n

    # ----------------------------------------------------------- lifecycle
    def start(self):
        """Launch the scheduler thread (idempotent)."""
        with self._life:
            if self._thread is not None and self._thread.is_alive():
                return self
            with self._cond:
                self._stop = False
                self._abort = False
            self._thread = threading.Thread(
                target=self._loop, name="mxnet-generation-scheduler",
                daemon=True)
            self._thread.start()
        return self

    def stop(self, drain=True, timeout=None):
        """Shut down. ``drain=True`` (default) finishes every admitted
        and queued request first; ``drain=False`` fails queued AND
        in-flight requests with :class:`ServerClosedError`.

        ``timeout`` (seconds) bounds the drain: a wedged decode step
        used to hang ``stop`` forever — past the timeout every still-
        pending request fails with :class:`ServerClosedError` and
        ``stop`` returns (the daemon scheduler exits if it unwedges).

        Speculative traffic keeps the drain contract exact: a stop
        racing an in-flight batched-verify step finalizes every token
        that step accepted (``_spec_once`` commits per-slot bursts
        atomically before the loop re-reads stop state), so no caller
        ever sees a half-accepted sequence; rejected-position pages are
        returned on the same step (``PagePool.shrink``), and an abort
        (``drain=False``) frees all speculative extensions through the
        normal eviction release."""
        with self._cond:
            self._stop = True
            self._abort = not drain
            self._cond.notify_all()
        with self._life:
            thread, self._thread = self._thread, None
            if thread is not None:
                thread.join(timeout)
                if thread.is_alive():
                    self._abandon_drain(timeout)
            elif self._queue or self._n_active:
                self._loop()  # never started: honor the drain contract
            if (self.prefix_cache is not None
                    and (thread is None or not thread.is_alive())):
                # scheduler down -> nothing can match again: release the
                # cache's page references so a drained pool reports
                # zero pages (assert_no_leaks holds after stop)
                self.prefix_cache.clear()
        # stopped engine: its gauges leave /metrics instead of freezing
        # at their last values (start() re-creates them on next write)
        from ...observability import metrics

        for name in _GENERATOR_GAUGES:
            metrics.unregister(name)
        return self

    def _abandon_drain(self, timeout):
        """Drain timed out: unblock every caller. Slot state and pages
        stay with the wedged scheduler thread (it aborts if it ever
        unwedges); handles are failed best-effort — _fail is idempotent
        so a slot the thread later finishes is a no-op race."""
        err = ServerClosedError(
            "stop(drain=True) timed out after %ss; remaining requests "
            "failed" % timeout)
        with self._cond:
            self._abort = True
            stranded = self._queue.drain()
            self._class_gauges(self._queue.depths())
            self._cond.notify_all()
        for ent in stranded:
            ent.handle._fail(err)
            ent.trace.finish("error")
        for seq in list(self._slots):
            if seq is not None:
                seq.handle._fail(err)
                seq.trace.finish("error")
        with self._lock:
            self._stats["drain_timeouts"] += 1

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop(drain=True)

    @property
    def running(self):
        return self._thread is not None and self._thread.is_alive()

    # -------------------------------------------------------------- submit
    def submit(self, prompt, params=None, slo=None):
        """Enqueue one generation request; returns a
        :class:`GenerationHandle`. ``prompt``: iterable of int token
        ids; ``params``: :class:`SamplingParams` (default: greedy, 32
        new tokens); ``slo``: an :class:`~..control.SLOClass`, a builtin
        tier name (``"interactive"``/``"standard"``/``"batch"``), or
        None for the standard tier — higher tiers preempt queue order
        (never in-flight slots), the class deadline (or
        ``MXNET_GEN_DEADLINE_MS``) sheds queue-expired requests with
        :class:`DeadlineExceeded` before prefill."""
        from ...observability import metrics

        params = params if params is not None else SamplingParams()
        slo_cls = resolve_class(slo)
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        top = self._cfg.prefill_buckets[-1]
        if len(prompt) > top:
            raise ValueError(
                "prompt of %d tokens exceeds the largest prefill bucket "
                "%d (raise MXNET_GEN_PREFILL_BUCKETS / max_seq)"
                % (len(prompt), top))
        if len(prompt) + params.max_new_tokens > self._cfg.max_seq:
            raise ValueError(
                "prompt %d + max_new_tokens %d exceeds max_seq %d"
                % (len(prompt), params.max_new_tokens, self._cfg.max_seq))
        worst = len(prompt) + params.max_new_tokens - 1
        if self.pool.pages_for(worst) > self.pool.capacity:
            raise ValueError(
                "request needs %d KV pages but the pool only holds %d "
                "(raise MXNET_GEN_POOL_PAGES)"
                % (self.pool.pages_for(worst), self.pool.capacity))
        handle = GenerationHandle()
        # request-scoped trace (ISSUE 12): queue ends at admission, a
        # prefix_match phase covers the cache lookup, prefill ends at
        # the first token (TTFT), one decode phase per generated token,
        # finish at eviction/stream end
        trace = _rtrace.begin("generation")
        trace.annotate(prompt_len=len(prompt),
                       max_new_tokens=params.max_new_tokens,
                       slo=slo_cls.name)
        t_submit = time.monotonic()
        dl_ms = (slo_cls.deadline_ms if slo_cls.deadline_ms is not None
                 else self._cfg.deadline_ms)
        deadline = (t_submit + dl_ms / 1e3) if dl_ms > 0 else None
        ent = _Pending(prompt, params, handle, t_submit, trace,
                       slo_cls, deadline)
        with self._cond:
            if self._stop:
                trace.finish("rejected")
                raise ServerClosedError("submit() after stop()")
            if self._cfg.backpressure == "reject":
                if len(self._queue) >= self._cfg.max_queue:
                    with self._lock:
                        self._stats["rejected"] += 1
                    metrics.counter("generation.rejected").inc()
                    trace.finish("rejected")
                    raise QueueFullError(
                        "admission queue full (%d requests); raise "
                        "MXNET_GEN_QUEUE or use backpressure='block'"
                        % len(self._queue))
            else:
                wait_s = self._cfg.submit_timeout_ms / 1e3
                give_up = (time.monotonic() + wait_s) if wait_s > 0 else None
                while len(self._queue) >= self._cfg.max_queue:
                    remaining = (None if give_up is None
                                 else give_up - time.monotonic())
                    if remaining is not None and remaining <= 0:
                        with self._lock:
                            self._stats["submit_timeouts"] += 1
                        metrics.counter("generation.submit_timeouts").inc()
                        trace.finish("rejected")
                        raise QueueFullError(
                            "admission queue still full after %.0f ms "
                            "(MXNET_GEN_SUBMIT_TIMEOUT); %d requests "
                            "queued" % (self._cfg.submit_timeout_ms,
                                        len(self._queue)))
                    self._cond.wait(remaining)
                    if self._stop:
                        trace.finish("rejected")
                        raise ServerClosedError(
                            "server stopped while submit() was blocked")
            self._queue.push(ent)
            depths = self._queue.depths()
            self._cond.notify_all()
        with self._lock:
            self._stats["requests"] += 1
        metrics.counter("generation.requests").inc()
        metrics.counter("generation.slo_requests",
                        labels={"slo": slo_cls.name},
                        help="requests submitted per SLO class").inc()
        self._class_gauges(depths)
        return handle

    @staticmethod
    def _class_gauges(depths):
        """Refresh every per-class queue-depth gauge — called on each
        queue transition (submit/admit/shed/drain) so an emptied class
        reads 0 instead of its last nonzero depth forever."""
        from ...observability import metrics

        for name, depth in depths.items():
            metrics.gauge("generation.slo_queue_depth",
                          labels={"slo": name},
                          help="queued requests per SLO class").set(depth)

    def generate(self, prompt, params=None, timeout=None):
        """Synchronous convenience: ``submit(...).result(timeout)``."""
        return self.submit(prompt, params).result(timeout)

    # ----------------------------------------------------------- scheduler
    def _loop(self):
        while True:
            aborted = None
            with self._cond:
                while (not self._queue and not self._n_active
                       and not self._stop):
                    self._cond.wait()
                if self._stop:
                    if self._abort:
                        aborted = self._queue.drain()
                        self._class_gauges(self._queue.depths())
                        self._cond.notify_all()
                    elif not self._queue and not self._n_active:
                        return
            if aborted is not None:
                self._fail_all(aborted)
                return
            self._admit_pending()
            if self._n_active:
                try:
                    # spec_k > 0 swaps the q-length-1 decode iteration
                    # for propose + batched verify; k = 0 keeps the
                    # non-speculative path bit-for-bit
                    if self.spec_k:
                        self._spec_once()
                    else:
                        self._decode_once()
                except Exception as err:
                    # contain the fault to the slots in the faulted
                    # step: fail those requests, free their pages, keep
                    # the loop alive for queued/later traffic
                    from ...observability import metrics

                    with self._lock:
                        self._stats["decode_faults"] += 1
                    metrics.counter("generation.decode_faults").inc()
                    for slot, seq in enumerate(self._slots):
                        if seq is not None:
                            self._evict(slot, failed=err)
                    self._recover_pools(err)

    def _fail_all(self, pending):
        err = ServerClosedError("generator stopped without draining")
        for ent in pending:
            ent.handle._fail(err)
            ent.trace.finish("error")
        for slot, seq in enumerate(self._slots):
            if seq is not None:
                self._evict(slot, failed=err)

    def _free_slot(self):
        for s, seq in enumerate(self._slots):
            if seq is None:
                return s
        return None

    def _shed(self, expired):
        """Fail queue-expired requests with DeadlineExceeded BEFORE any
        prefill dispatch (the serving-engine shedding semantics): a
        backlogged generator stops burning prefill compute on answers
        nobody is waiting for."""
        from ...observability import metrics

        now = time.monotonic()
        for ent in expired:
            ent.handle._fail(DeadlineExceeded(
                "generation request expired in queue after %.0f ms "
                "(class %r deadline)" % ((now - ent.t_submit) * 1e3,
                                         ent.slo.name)))
            ent.trace.finish("deadline_expired")
            metrics.counter("generation.deadline_expired").inc()
            metrics.counter("generation.slo_expired",
                            labels={"slo": ent.slo.name},
                            help="queue-expired requests per SLO class"
                            ).inc()
        with self._lock:
            self._stats["expired"] += len(expired)

    def _pressure_admit(self, ent, worst):
        """The conservative ``can_admit(worst)`` gate failed — account
        the sharing the request would actually get before reclaiming
        anything. A PROBE match (counters untouched, refs dropped right
        back — the scheduler thread is the only evictor, so the real
        match in ``_prefill`` sees the same tree) supplies the
        shared-page discount; only the remaining shortfall of COLD
        cached prefixes is reclaimed LRU-first, so pressure never
        shreds the very prefix a pending request is about to share.
        Returns True when admission can proceed."""
        if self.prefix_cache is None:
            return False
        for attempt in range(2):
            shared, matched = self.prefix_cache.match(ent.prompt,
                                                      record=False)
            cow = matched > 0 and matched == len(ent.prompt)
            n_shared = len(shared)
            for p in shared:
                self.pool.decref(p)
            if self.pool.can_admit(worst, shared_pages=n_shared, cow=cow):
                return True
            if attempt or not self.prefix_cache.reclaim(
                    self.pool.admission_shortfall(
                        worst, shared_pages=n_shared, cow=cow)):
                return False
            # reclaim released something: re-probe (the probe's LRU
            # bump shields this request's own chain, but a tiny cache
            # may still have shrunk the match)
        return False

    def _admit_pending(self):
        """Admit queued requests into free slots — between decode steps,
        which is what makes the batching *continuous*. Admission order
        is the SLO scheduler's (serving/control/slo.py): highest
        effective priority (tier + aging boost) first, FIFO within a
        class, queue-expired requests shed first; a pool full of cached
        prefixes reclaims them under pressure instead of stalling."""
        while True:
            with self._cond:
                expired = self._queue.shed_expired(time.monotonic())
                depths = self._queue.depths() if expired else None
                if expired:
                    self._cond.notify_all()  # queue space freed
            if expired:
                self._shed(expired)
                self._class_gauges(depths)
            slot = self._free_slot()
            if slot is None:
                return
            with self._cond:
                ent = self._queue.select(time.monotonic())
                if ent is None:
                    return
                worst = len(ent.prompt) + ent.params.max_new_tokens - 1
                if not self.pool.can_admit(worst):
                    if not self._pressure_admit(ent, worst):
                        return  # decode on, eviction frees some pages
                self._queue.pop(ent)
                depths = self._queue.depths()
                self._n_active += 1
                self._cond.notify_all()  # wake blocked submitters
            self._class_gauges(depths)
            try:
                self._prefill(slot, ent, worst)
            except Exception as err:  # fail THIS request, not the thread
                self._reset_slot(slot, worst)
                with self._cond:
                    self._n_active -= 1
                    self._cond.notify_all()
                ent.handle._fail(err)
                ent.trace.finish("error")
                # under donation the failed call may have consumed the
                # pool buffers other sequences' caches live in
                self._recover_pools(err)

    def _prefill(self, slot, ent, worst):
        import jax

        from ...observability import metrics

        plen = len(ent.prompt)
        sp = ent.params
        ent.trace.event("queue")  # admission = end of queue wait
        # --- prefix-cache match (control plane): longest cached page-
        # aligned prefix attaches read-only; only the suffix prefills
        shared, matched, cow = [], 0, False
        if self.prefix_cache is not None:
            shared, matched = self.prefix_cache.match(ent.prompt)
            # a prompt that IS a cached page-aligned prefix still needs
            # its last token recomputed (the suffix forward produces the
            # first-token logits); that one write lands inside the last
            # shared page -> copy-on-write privatizes it
            cow = matched > 0 and matched == plen
            ent.trace.annotate(prefix_hit=bool(matched),
                               prefix_tokens=int(matched))
            metrics.counter("generation.prefix_hits" if matched
                            else "generation.prefix_misses").inc()
            # the phase exists only on control-plane engines: cold
            # engines keep the PR 12 queue/prefill/decode partition
            ent.trace.event("prefix_match")
        suffix_start = plen - 1 if cow else matched
        suffix = ent.prompt[suffix_start:]
        if suffix_start:
            metrics.counter("generation.prefill_tokens_skipped").inc(
                suffix_start)
            with self._lock:
                self._stats["prefix_hits"] += 1
                self._stats["prefill_tokens_skipped"] += suffix_start
        bucket = pick_bucket(len(suffix), self._cfg.prefill_buckets)
        try:
            pages = self.pool.admit(slot, plen, worst,
                                    shared_pages=shared, cow_last=cow)
        except BaseException:
            for p in shared:
                self.pool.decref(p)  # match's refs never reached a slot
            raise
        cow_src = cow_dst = 0
        if cow:
            cow_src, cow_dst = self.pool.cow(slot, len(shared) - 1)
            pages = self.pool.pages_of(slot)
        row = np.zeros(self._max_pages, np.int32)
        row[:len(pages)] = pages
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :len(suffix)] = suffix
        key = np.asarray(jax.random.PRNGKey(sp.seed), np.uint32)
        with self._pages_lock:
            pools, tok, nkey = self._prefill_jit(
                self._params, self._pools, tokens,
                np.int32(len(suffix)), np.int32(suffix_start), row,
                np.int32(cow_src), np.int32(cow_dst), key,
                np.float32(sp.temperature), np.int32(sp.top_k),
                self._draft_params)
            self._pools = pools
        # the ONE host sync of admission: the prompt's first token (this
        # is also the time-to-first-token mark)
        first = int(np.asarray(tok))  # graftlint: disable=G001 — admission-boundary fetch, not a hot-loop sync
        seq = _Seq(ent.handle, ent.prompt, sp, worst, ent.t_submit,
                   ent.trace, slo=ent.slo)
        seq.t_first = time.monotonic()
        seq.t_last = seq.t_first
        # prefill ends at the first sampled token — this instant IS the
        # time-to-first-token mark
        ent.trace.event("prefill")
        ent.trace.annotate(prefill_bucket=bucket, slot=slot)
        metrics.histogram(
            "generation.ttft_ms",
            help="time to first token (submit -> first sampled token)"
        ).observe((seq.t_first - ent.t_submit) * 1e3)
        self._slots[slot] = seq
        self._page_table[slot, :] = row
        self._seq_len[slot] = plen
        self._active[slot] = True
        self._last_token[slot] = first
        self._temp[slot] = sp.temperature
        self._top_k[slot] = sp.top_k
        self._keys[slot] = np.array(nkey, np.uint32)  # copy: jax views are read-only
        with self._lock:
            self._stats["prefills"] += 1
            self._stats["tokens"] += 1
        metrics.counter("generation.prefill_batches").inc()
        metrics.counter("generation.tokens_generated").inc()
        self._emit(slot, first)

    def _emit(self, slot, token):
        """Stream one token; evict on EOS / max-tokens."""
        seq = self._slots[slot]
        seq.tokens.append(token)
        seq.handle._push(token)
        if (token == seq.params.eos_id
                or len(seq.tokens) >= seq.params.max_new_tokens):
            self._evict(slot)

    def _reset_slot(self, slot, worst):
        self._slots[slot] = None
        self._active[slot] = False
        self._seq_len[slot] = 0
        self._last_token[slot] = 0
        self._temp[slot] = 0.0
        self._top_k[slot] = 0
        self._page_table[slot, :] = 0
        self.pool.release(slot, worst)

    def _evict(self, slot, failed=None):
        from ...observability import metrics

        seq = self._slots[slot]
        if failed is None and self.prefix_cache is not None:
            # cold prefixes enter the tree on eviction: the prompt's
            # full pages just served real traffic and hold position-
            # exact K/V (decode writes never land below the prompt's
            # last full page, so they stay pure-prompt content)
            try:
                self.prefix_cache.insert(seq.prompt,
                                         self.pool.pages_of(slot))
            except Exception:
                with self._lock:
                    self._stats["prefix_insert_errors"] += 1
        self._reset_slot(slot, seq.worst)
        with self._cond:
            self._n_active -= 1
            self._cond.notify_all()
        if failed is not None:
            seq.handle._fail(failed)
            seq.trace.finish("error")
        else:
            seq.handle._finish(seq.tokens)
            seq.trace.finish("ok")
            metrics.counter("generation.slo_completed",
                            labels={"slo": seq.slo.name},
                            help="completed requests per SLO class").inc()
        with self._lock:
            self._stats["evicted"] += 1
            if failed is None:
                self._stats["completed"] += 1
        metrics.counter("generation.sequences_evicted").inc()

    def _decode_once(self):
        """One iteration of the continuous-batching loop: extend pages
        where a sequence crosses a page boundary, run THE decode
        program, stream the sampled tokens, evict the finished."""
        from ...observability import metrics

        t0 = time.monotonic()
        _faults.inject("generation.decode_step")
        for slot, seq in enumerate(self._slots):
            if seq is None:
                continue
            need = int(self._seq_len[slot]) // self.page_size
            owned = self.pool.pages_of(slot)
            if need >= len(owned):  # extend-on-decode
                self._page_table[slot, need] = self.pool.extend(slot)
        with self._pages_lock:
            pools, toks, nkeys = self._decode_jit(
                self._params, self._pools,
                self._page_table, self._seq_len, self._active,
                self._last_token, self._temp, self._top_k, self._keys)
            self._pools = pools
        n_active = int(self._active.sum())
        # the decode loop's one bounded host fetch per step (everything
        # else above is dispatch): S int32 tokens + S keys
        sampled = np.asarray(toks)  # graftlint: disable=G001 — per-step token fetch IS the product of the decode loop
        self._keys = np.array(nkeys, np.uint32)  # copy: jax views are read-only
        t_tok = time.monotonic()
        itl_hist = metrics.histogram(
            "generation.itl_ms",
            help="inter-token latency (consecutive sampled tokens of "
                 "one request)")
        for slot, seq in enumerate(self._slots):
            if seq is None:
                continue
            self._seq_len[slot] += 1
            tok = int(sampled[slot])
            self._last_token[slot] = tok
            # one decode phase per generated token: the trace's decode
            # spans ARE the request's inter-token latencies
            seq.trace.event("decode")
            if seq.t_last is not None:
                itl_hist.observe((t_tok - seq.t_last) * 1e3)
            seq.t_last = t_tok
            self._emit(slot, tok)
        with self._lock:
            self._stats["decode_steps"] += 1
            self._stats["tokens"] += n_active
        metrics.counter("generation.tokens_generated").inc(n_active)
        metrics.gauge("generation.decode_batch_occupancy").set(
            100.0 * n_active / self._cfg.max_batch)
        metrics.histogram("generation.decode_step_ms").observe(
            (time.monotonic() - t0) * 1e3)

    def _propose(self, spans):
        """The draft phase of one speculative iteration: k candidate
        tokens per slot. n-gram mode is pure host numpy (prompt-lookup
        over each sequence's own history); draft-model mode chains k
        calls of THE draft-decode program, advancing the draft's page
        planes through the candidate positions. Returns (S, k) int32."""
        k = self.spec_k
        S = self._cfg.max_batch
        drafts = np.zeros((S, k), np.int32)
        if not self._spec_draft:
            for slot, seq in enumerate(self._slots):
                if seq is None:
                    continue
                drafts[slot] = ngram_propose(seq.prompt + seq.tokens, k,
                                             self.spec_ngram)
            return drafts
        toks = self._last_token
        with self._pages_lock:
            pools = self._pools
            for j in range(k):
                act = self._active & (j < spans)
                pools, nxt = self._draft_jit(
                    self._draft_params, pools, self._page_table,
                    self._seq_len + np.int32(j), act, toks)
                # ONE bounded fetch per draft position (k small ints
                # per slot): the proposal feeds back as the next
                # draft-step input AS NUMPY, keeping every chained call
                # on the warmed compile key (a committed device array
                # here would carry a different sharding and retrace)
                drafts[:, j] = np.asarray(nxt)  # graftlint: disable=G001 — draft-phase token fetch, bounded by spec_k
                toks = drafts[:, j]
            self._pools = pools
        return drafts

    def _spec_once(self):
        """One speculative iteration of the continuous-batching loop:
        extend pages to cover the worst-case span, propose k drafts per
        slot, run THE batched-verify program once, then commit each
        slot's 1..span accepted+sampled tokens — rolling back the page
        bookkeeping for rejected positions (``PagePool.shrink``; the
        stale device K/V is masked by committed lengths, so rollback is
        host-side accounting only).

        Emission is per-slot ATOMIC: every token the verify step
        accepted for a slot is pushed before the loop re-examines stop/
        abort state, so ``stop(drain=True)`` racing an in-flight verify
        finalizes accepted tokens and never delivers a half-accepted
        sequence (the drain contract; regression-tested next to the
        PR 8 stop-timeout tests)."""
        from ...observability import metrics

        t0 = time.monotonic()
        _faults.inject("generation.decode_step")
        k = self.spec_k
        S = self._cfg.max_batch
        # per-slot emission budget: min(k+1, remaining max_new) >= 1 —
        # caps in-program scatters at the admission page reservation and
        # emission at the request's token budget
        spans = np.zeros(S, np.int32)
        for slot, seq in enumerate(self._slots):
            if seq is None:
                continue
            span = min(k + 1, seq.worst - int(self._seq_len[slot]))
            spans[slot] = span
            need = self.pool.pages_for(int(self._seq_len[slot]) + span)
            owned = self.pool.pages_of(slot)
            while len(owned) < need:  # extend-on-decode, span-deep
                self._page_table[slot, len(owned)] = self.pool.extend(slot)
                owned = self.pool.pages_of(slot)
        t_draft = time.monotonic()
        drafts = self._propose(spans)
        t_verify = time.monotonic()
        with self._pages_lock:
            pools, out_toks, n_emit, nkeys = self._verify_jit(
                self._params, self._pools, self._page_table,
                self._seq_len, self._active, self._last_token, drafts,
                spans, self._temp, self._top_k, self._keys)
            self._pools = pools
        n_active = int(self._active.sum())
        # the speculative loop's one bounded host fetch per step:
        # S x (k+1) int32 tokens + S accept counts + S keys
        out = np.asarray(out_toks)  # graftlint: disable=G001 — per-step token fetch IS the product of the decode loop
        accepted = np.asarray(n_emit)  # graftlint: disable=G001 — rides the same per-step fetch boundary
        self._keys = np.array(nkeys, np.uint32)  # copy: jax views are read-only
        t_tok = time.monotonic()
        itl_hist = metrics.histogram(
            "generation.itl_ms",
            help="inter-token latency (consecutive sampled tokens of "
                 "one request)")
        rate_hist = metrics.histogram(
            "generation.spec_accept_rate",
            help="per-step draft acceptance rate (accepted / proposed, "
                 "slots with a nonzero proposal budget)")
        tpv_hist = metrics.histogram(
            "generation.spec_tokens_per_verify",
            help="tokens committed per slot per batched-verify call "
                 "(1 = no draft survived, k+1 = all accepted + bonus)")
        emitted_total = proposed_total = accepted_total = 0
        for slot, seq in enumerate(self._slots):
            if seq is None:
                continue
            m = max(1, int(accepted[slot]))
            toks = [int(t) for t in out[slot, :m]]
            self._seq_len[slot] += m
            self._last_token[slot] = toks[-1]
            proposed = max(0, int(spans[slot]) - 1)
            proposed_total += proposed
            accepted_total += m - 1
            tpv_hist.observe(m)
            if proposed:
                rate_hist.observe((m - 1) / proposed)
            # the m tokens left ONE program together: each is charged an
            # equal share of the step gap (normalized inter-token
            # latency, comparable with the non-speculative itl_ms)
            gap_ms = ((t_tok - seq.t_last) * 1e3 / m
                      if seq.t_last is not None else None)
            for tok in toks:
                if self._slots[slot] is None:
                    break  # EOS / max-tokens evicted the slot mid-burst
                seq.trace.event("decode")
                if gap_ms is not None:
                    itl_hist.observe(gap_ms)
                emitted_total += 1
                self._emit(slot, tok)
            if self._slots[slot] is not None:
                seq.t_last = t_tok
                if m < int(spans[slot]):
                    # rejection rollback: return the tail pages only
                    # speculated-over positions needed; device K/V there
                    # is stale-but-masked until the pages are reissued
                    if self.pool.shrink(slot, int(self._seq_len[slot])):
                        n_own = len(self.pool.pages_of(slot))
                        self._page_table[slot, n_own:] = 0
        with self._lock:
            self._stats["decode_steps"] += 1
            self._stats["spec_steps"] += 1
            self._stats["tokens"] += emitted_total
            self._stats["spec_proposed"] += proposed_total
            self._stats["spec_accepted"] += accepted_total
            self._stats["spec_draft_ms"] += (t_verify - t_draft) * 1e3
            self._stats["spec_verify_ms"] += (t_tok - t_verify) * 1e3
        metrics.counter(
            "generation.spec_proposed",
            help="draft tokens proposed to the batched-verify step"
        ).inc(proposed_total)
        metrics.counter(
            "generation.spec_accepted",
            help="draft tokens accepted by the batched-verify step"
        ).inc(accepted_total)
        metrics.counter("generation.tokens_generated").inc(emitted_total)
        metrics.histogram(
            "generation.spec_draft_ms",
            help="draft-proposal phase per speculative step").observe(
            (t_verify - t_draft) * 1e3)
        metrics.histogram(
            "generation.spec_verify_ms",
            help="batched-verify phase per speculative step").observe(
            (t_tok - t_verify) * 1e3)
        metrics.gauge("generation.decode_batch_occupancy").set(
            100.0 * n_active / self._cfg.max_batch)
        metrics.histogram("generation.decode_step_ms").observe(
            (time.monotonic() - t0) * 1e3)

    # --------------------------------------------------------------- stats
    def get_stats(self):
        """Operational snapshot conforming to the shared engine-stats
        schema (observability/stats_schema.py) — consumed by the
        flight-recorder "generation" provider and /statusz. Legacy flat
        keys (queued, active, pool, ...) are preserved on top of the
        shared core."""
        with self._cond:
            queued = len(self._queue)
            class_depths = self._queue.depths()
            n_active = self._n_active
            stopped = self._stop
        with self._lock:
            counters = dict(self._stats)
        pool = self.pool.get_stats()
        # speculation acceptance accounting (ISSUE 16) — the decode
        # waterfall (PR 13) reads draft_ms/verify_ms to attribute draft
        # vs verify time inside the decode phase
        spec_prop = counters.get("spec_proposed", 0)
        spec_acc = counters.get("spec_accepted", 0)
        speculative = {
            "mode": self.spec_mode,
            "k": self.spec_k,
            "ngram": self.spec_ngram,
            "steps": counters.get("spec_steps", 0),
            "proposed": spec_prop,
            "accepted": spec_acc,
            "accept_rate": (round(spec_acc / spec_prop, 4)
                            if spec_prop else None),
            "draft_ms": round(counters.get("spec_draft_ms", 0.0), 3),
            "verify_ms": round(counters.get("spec_verify_ms", 0.0), 3),
            "draft_bytes_per_token": self.draft_bytes_per_token,
        }
        control = {
            "slo": {"aging_ms": self._aging_ms,
                    "deadline_ms": float(self._cfg.deadline_ms),
                    "queues": class_depths,
                    "expired": counters.get("expired", 0)},
            "prefix_cache": (self.prefix_cache.get_stats()
                             if self.prefix_cache is not None else None),
            "prefill_tokens_skipped": counters.get(
                "prefill_tokens_skipped", 0),
            "pages_shared": pool["pages_shared"],
            "cow_copies": pool["cow_copies"],
        }
        return _schema.engine_stats(
            "generation", counters,
            queue_depth=queued,
            completed=counters.get("completed", 0),
            running=self.running, stopped=stopped,
            capacity={
                "max_batch": self._cfg.max_batch,
                "active_slots": n_active,
                "kv_pages_used": pool["used"],
                "kv_pages_capacity": pool["capacity"],
                "kv_bytes_used": pool["kv_bytes_used"],
                "kv_bytes_capacity": pool["kv_bytes_capacity"],
                "queue_limit_requests": self._cfg.max_queue,
            },
            config={
                "max_seq": self._cfg.max_seq,
                "page_size": self.page_size,
                "decode_blocks": self.decode_blocks,
                "kv_dtype": self.kv_dtype,
                "prefill_buckets": list(self._cfg.prefill_buckets),
                "backpressure": self._cfg.backpressure,
                "prefix_cache": self._use_prefix,
                "slo_aging_ms": self._aging_ms,
                "deadline_ms": float(self._cfg.deadline_ms),
                "spec_k": self.spec_k,
                "spec_mode": self.spec_mode,
            },
            resilience={
                "decode_faults": counters.get("decode_faults", 0),
                "drain_timeouts": counters.get("drain_timeouts", 0),
            },
            control=control,
            provenance={"amp": bool(self._amp),
                        "kv_dtype": self.kv_dtype},
            extra={
                "queued": queued, "active": n_active,
                "max_batch": self._cfg.max_batch,
                "max_seq": self._cfg.max_seq,
                "page_size": self.page_size,
                "decode_blocks": self.decode_blocks,
                "kv_dtype": self.kv_dtype,
                "prefill_buckets": list(self._cfg.prefill_buckets),
                "pool": pool,
                "speculative": speculative,
            })

    def kv_read_bytes_per_token(self, ctx_len):
        """HBM bytes ONE decode step reads from the KV pool for one slot
        at context length ``ctx_len`` — the analytic
        bytes-per-generated-token witness the ``generation_lm`` bench
        reports (decode is gather-bound, so this IS the step's traffic
        model; int8 pools roughly halve it vs bf16, quarter vs fp32)."""
        return int(ctx_len) * self.pool.bytes_per_token
