"""Executor — symbolic graph execution as single compiled XLA programs.

Reference: src/executor/graph_executor.cc (GraphExecutor) and
include/mxnet/executor.h. The reference binds a graph into per-node engine
ops (InitCachedOps, graph_executor.cc:1226) with memory planning and bulk
segments; here the entire forward (and forward+backward for training) DAG is
lowered into ONE ``jax.jit`` program — the "whole-graph-to-one-XLA-program"
design that SURVEY.md §7.3(6) names as the performance requirement. Gradient
construction (the nnvm::pass::Gradient analog, graph_executor.cc:303) is
``jax.vjp`` over the lowered function; memory planning, inplace and bulk
execution are XLA buffer assignment and fusion.

Forward in train mode computes outputs, updated aux states AND gradients in
one fused program (seeded with ones — loss-head ops ignore the seed via their
custom_vjp, reproducing MXNet's head-gradient semantics); ``backward()`` then
just applies the stashed gradients according to grad_req. An explicit
``backward(out_grads)`` recompiles with real seeds.
"""
from __future__ import annotations

from .autotune.registry import declare as _declare_tunable
from .base import MXNetError
from .context import Context


def _remat_default(ctx):
    from .config import get_flag

    return {"mirror": int(bool(get_flag("MXNET_BACKWARD_DO_MIRROR")))}


# the executor's program-build knob (ISSUE 6): store activations vs
# jax.checkpoint recompute for the fused train program — a measured
# HBM-footprint/backward-FLOPs tradeoff, keyed per graph fingerprint
# (autotune.tune_remat drives the measurement)
_declare_tunable(
    "exec.remat",
    space={"mirror": (0, 1)},
    default=_remat_default,
    doc="Fused train program remat policy: 0 = store activations, "
        "1 = rematerialize in backward (jax.checkpoint).")


def _maybe_jit(f):
    """jax.jit unless MXNET_EXEC_DISABLE_JIT is set — the debug analog of
    MXNET_ENGINE_TYPE=NaiveEngine (reference: src/engine/naive_engine.cc:36,
    the serial engine the threaded engine's own error message recommends
    for bug hunts)."""
    import jax

    from .config import get_flag

    if get_flag("MXNET_EXEC_DISABLE_JIT"):
        return f
    return jax.jit(f)


def _maybe_mirror(loss_fn, mirror=None):
    """Wrap the forward in jax.checkpoint when remat is on: activations
    are rematerialized during backward instead of stored — the
    reference's memory-mirroring pass (graph_executor.cc:282-296,
    docs/faq/env_var.md MXNET_BACKWARD_DO_MIRROR) expressed as remat.
    ``mirror=None`` reads the flag; callers with a tuned per-graph
    decision (``_GraphProgram.remat_mirror``) pass it explicitly."""
    import jax

    from .config import get_flag

    if mirror is None:
        mirror = get_flag("MXNET_BACKWARD_DO_MIRROR")
    if mirror:
        return jax.checkpoint(loss_fn)
    return loss_fn

__all__ = ["Executor", "resolve_output_indices"]


def resolve_output_indices(names, outputs):
    """Map requested output heads — indices, exact output names, or bare
    node names (``_output`` suffix optional) — onto positions in
    ``names``. Shared by Executor.select_outputs and the Module-level
    ``predict(outputs=...)`` plumbing so the resolution rules can never
    drift."""
    sel = []
    for o in outputs:
        if isinstance(o, int):
            if not 0 <= o < len(names):
                raise ValueError("outputs: index %d out of range (%d "
                                 "outputs)" % (o, len(names)))
            sel.append(o)
        elif o in names:
            sel.append(names.index(o))
        elif o + "_output" in names:
            sel.append(names.index(o + "_output"))
        else:
            raise ValueError("outputs: %r is not an output (outputs: %s)"
                             % (o, list(names)))
    return sel


class _GraphProgram:
    """Compiled evaluation plan for one Symbol."""

    _INIT_OPS = ("_zeros", "_ones", "_full")

    def __init__(self, symbol, tuning_key=None):
        # ``tuning_key`` pins the fingerprint when ``symbol`` is a
        # pass-rewritten graph: autotune entries (exec.remat,
        # serving.buckets) are keyed by the ORIGINAL graph so tuned
        # decisions keep resolving under any pass config
        self.symbol = symbol
        self.topo = [n for n in symbol.topo_nodes() if not n.is_variable]
        self.rng_nodes = [n for n in self.topo
                          if n.opdef().needs_rng]
        args, aux = symbol._classify_vars()
        self.arg_names = [n.name for n in args]
        self.aux_names = [n.name for n in aux]
        # init-op nodes with 0 (unknown) dims in their declared shape: their
        # real shape comes from graph inference at bind time — the nnvm
        # backward-shape-flow behavior RNN begin_state zeros rely on
        self._deferred_init_nodes = [
            n for n in self.topo
            if n.op in self._INIT_OPS
            and 0 in tuple(n.parsed_attrs().get("shape", ()))]
        self._init_shape_cache = {}
        self._sel_topo = {}
        self._perf_costs = {}  # (mode, shape sig) -> analytic cost dict
        self._tuning_key = tuning_key
        import threading

        self._jit_cache = {}  # guarded-by: self._jit_lock
        self._jit_lock = threading.Lock()

    def tuning_key(self):
        """Stable graph fingerprint for tuning-cache keys: node count +
        a hash of the op sequence INCLUDING each node's op params
        (num_hidden, kernel, ... — so same-topology models of different
        widths never collide on a tuned decision). Bound input shapes
        are deliberately not part of it; where they matter they ride in
        the shape-bucket part of the cache key. (Shared construction
        with graph_pass.graph_fingerprint — one fingerprint language
        across the tuner and the pass layer.)"""
        if self._tuning_key is None:
            from .graph_pass import graph_fingerprint

            self._tuning_key = graph_fingerprint(self.symbol)
        return self._tuning_key

    def topo_for(self, sel):
        """(topo subset, output entries) for a selection of output
        indices — the dead-output-pruned walk behind ``predict(
        outputs=...)``. Memoized per selection."""
        if sel is None:
            return self.topo, self.symbol._outputs
        key = tuple(sel)
        cached = self._sel_topo.get(key)
        if cached is not None:
            return cached
        entries = [self.symbol._outputs[i] for i in key]
        reachable = set()
        stack = [n for n, _ in entries]
        while stack:
            node = stack.pop()
            if id(node) in reachable:
                continue
            reachable.add(id(node))
            stack.extend(src for src, _ in node.inputs)
        topo = [n for n in self.topo if id(n) in reachable]
        self._sel_topo[key] = (topo, entries)  # graftlint: disable=G003 — host-side memo of a graph walk
        return topo, entries

    def perf_cost(self, arg_d, aux_d, train=False):
        """Analytic FLOPs + HBM-bytes accounting for this program at the
        given bound arrays (observability.perf, ISSUE 13), memoized per
        (mode, shape signature) alongside the compiled program — the
        walk runs once per shape, steady-state runs pay one dict probe.
        Returns None when shape inference cannot cover the graph."""
        key = (bool(train),
               tuple(sorted((n, tuple(v.shape)) for n, v in arg_d.items())),
               tuple(sorted((n, tuple(v.shape)) for n, v in aux_d.items())))
        if key not in self._perf_costs:
            from .observability import perf as _perf

            var_shapes = {n: tuple(v.shape) for n, v in arg_d.items()}
            var_shapes.update((n, tuple(v.shape))
                              for n, v in aux_d.items())
            # compute dtype = the widest bound tensor's (bf16 params ->
            # 2-byte traffic model; fp32 -> 4)
            db = 4
            if arg_d:
                biggest = max(arg_d.values(),
                              key=lambda v: getattr(v, "size", 0))
                db = getattr(getattr(biggest, "dtype", None), "itemsize", 4)
            names = self.symbol.list_outputs()
            graph = names[0] if names else "program"
            self._perf_costs[key] = _perf.program_cost(  # graftlint: disable=G003 — host-side memo, computed post-run
                self.symbol, self.topo, var_shapes, dtype_bytes=db,
                train=train, graph="%s/%dn" % (graph, len(self.topo)))
        return self._perf_costs[key]

    def remat_mirror(self):
        """Remat decision for this graph's fused train program: a tuned
        ``exec.remat`` cache entry (autotune.tune_remat) wins over the
        MXNET_BACKWARD_DO_MIRROR flag. Consulted once per train_fn build
        — one dict probe, cached with the compiled program."""
        from .autotune import lookup

        tuned = lookup("exec.remat", key=self.tuning_key())
        if tuned is not None:
            return bool(tuned.get("mirror", 0))
        from .config import get_flag

        return bool(get_flag("MXNET_BACKWARD_DO_MIRROR"))

    def _resolve_init_shapes(self, arg_shapes):
        """Infer concrete shapes for deferred init-op nodes given the bound
        argument shapes (memoized per shape signature)."""
        key = tuple(sorted((k, tuple(v)) for k, v in arg_shapes.items()))
        if key in self._init_shape_cache:
            return self._init_shape_cache[key]
        internals = self.symbol.get_internals()
        names = internals.list_outputs()
        entries = internals._outputs
        try:
            _, out_shapes, _ = internals.infer_shape_partial(**arg_shapes)
        except Exception:
            out_shapes = [None] * len(entries)
        by_id = {}
        for (node, idx), shape in zip(entries, out_shapes):
            if shape is not None and idx == 0:
                by_id[id(node)] = tuple(shape)
        overrides = {}
        for n in self._deferred_init_nodes:
            shape = by_id.get(id(n))
            if shape is None or 0 in shape:
                raise MXNetError(
                    "cannot infer shape for %s node %r with declared shape "
                    "%s" % (n.op, n.name, n.parsed_attrs().get("shape")))
            overrides[id(n)] = shape
        self._init_shape_cache[key] = overrides  # graftlint: disable=G003 — idempotent memo of trace-time shape inference
        return overrides

    def assign_contexts(self, group2ctx, default_ctx):
        """Map each node to a device from its ``ctx_group`` user attr —
        the AssignContext + PlaceDevice pass (graph_executor.cc:317-421);
        returns {id(node): jax device} for nodes bound off-default."""
        ctx_map = {}
        for node in self.topo:
            if node.is_variable:
                continue
            grp = node.user_attrs.get("ctx_group")
            if grp is None:
                continue
            if grp not in group2ctx:
                raise MXNetError(
                    "ctx_group %r has no mapping in group2ctx (groups: %s)"
                    % (grp, sorted(group2ctx)))
            ctx = group2ctx[grp]
            if ctx != default_ctx:
                ctx_map[id(node)] = ctx.jax_device()
        return ctx_map

    # --- raw graph evaluation (traced under jit) --------------------------
    def _eval(self, arg_d, aux_d, rngs, is_train, callback=None,
              ctx_map=None, sel=None):
        """Walk the graph once. With ``callback`` (only ever passed from
        the eager monitor path), fire ``callback(entry_name, value)`` per
        node output — the reference's per-node monitor hook
        (GraphExecutor::ExecuteMonCallback, graph_executor.cc:199).
        With ``ctx_map`` (eager model-parallel path), inputs of a mapped
        node are device_put onto its assigned device first — the
        _CrossDeviceCopy insertion of the PlaceDevice pass; eager jax
        dispatch then runs the op on that device."""
        from .observability.tracing import device_scope

        env = {}
        aux_updates = {}
        rng_i = [0]
        overrides = {}
        if self._deferred_init_nodes:
            overrides = self._resolve_init_shapes(
                {k: tuple(v.shape) for k, v in arg_d.items()})
        topo, out_entries = self.topo_for(sel)

        def get_entry(e):
            n, i = e
            if n.is_variable:
                if n.name in arg_d:
                    return arg_d[n.name]
                return aux_d[n.name]
            return env[(id(n), i)]

        for node in topo:
            opdef = node.opdef()
            attrs = node.parsed_attrs()
            if id(node) in overrides:
                from .ops.registry import OpAttrs

                attrs = OpAttrs(dict(attrs._d, shape=overrides[id(node)]))
            n_main = node.num_main_inputs()
            ins = [get_entry(e) for e in node.inputs[:n_main]]
            auxs = [get_entry(e) for e in node.inputs[n_main:]]
            if ctx_map and id(node) in ctx_map:
                import jax

                dev = ctx_map[id(node)]
                # ONE pytree transfer instead of len(ins)+len(auxs)
                # per-array dispatches — device_put batches the whole
                # cross-device copy into a single host round-trip
                ins, auxs = jax.device_put((ins, auxs), dev)
            rng = None
            if opdef.needs_rng:
                rng = rngs[rng_i[0]]
                rng_i[0] += 1
            # the reference profiler's per-operator naming
            # (src/engine/profiler.cc): the node's name rides in the
            # op_name of every HLO operation it lowers to
            with device_scope(node.name):
                outs, new_aux = opdef.apply(attrs, ins, auxs,
                                            is_train=is_train, rng=rng)
            for i, o in enumerate(outs):
                env[(id(node), i)] = o
                if callback is not None:
                    # <node>_output entry naming (symbol.py list_outputs)
                    callback(node.name + "_output" if len(outs) == 1
                             else "%s_output%d" % (node.name, i), o)
            for e, nv in zip(node.inputs[n_main:], new_aux):
                src, _ = e
                if src.is_variable:
                    aux_updates[src.name] = nv
        outputs = tuple(get_entry(e) for e in out_entries)
        return outputs, aux_updates

    # --- compiled entry points --------------------------------------------
    def infer_fn(self, sel=None):
        # locked check-then-set: concurrent callers (serving warmup vs
        # its dispatcher thread) must share ONE jit wrapper, or the same
        # bucket shape compiles twice. ``sel`` (a tuple of output
        # indices) builds a dead-output-pruned program — the compiled
        # form of ``predict(outputs=...)``; each selection caches its
        # own program.
        key = "infer" if sel is None else ("infer", tuple(sel))
        with self._jit_lock:
            if key not in self._jit_cache:
                def f(arg_d, aux_d, rngs, _sel=sel):
                    outs, _ = self._eval(arg_d, aux_d, rngs, False,
                                         sel=_sel)
                    return outs

                self._jit_cache[key] = _maybe_jit(f)
            return self._jit_cache[key]

    def train_fn(self, grad_names):
        """One fused program: outputs + aux updates + grads w.r.t. grad_names."""
        import jax

        key = ("train", tuple(grad_names))
        with self._jit_lock:
            if key not in self._jit_cache:
                mirror = self.remat_mirror()

                def f(nograd_d, grad_d, aux_d, rngs, seeds):
                    def inner(gd):
                        merged = dict(nograd_d)
                        merged.update(gd)
                        outs, aux_upd = self._eval(merged, aux_d, rngs, True)
                        return tuple(outs), aux_upd

                    inner = _maybe_mirror(inner, mirror)
                    outs, vjp, aux_upd = jax.vjp(inner, grad_d, has_aux=True)
                    grads = vjp(tuple(seeds))[0]
                    return outs, aux_upd, grads

                self._jit_cache[key] = _maybe_jit(f)
            return self._jit_cache[key]


class Executor:
    """Bound executor (reference: include/mxnet/executor.h:53, executor.py)."""

    def __init__(self, symbol, ctx, args, args_grad, grad_req, aux_states,
                 shared_exec=None, group2ctx=None, frozen_params=None):
        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else Context(ctx)
        self.arg_dict = dict(args)
        self.grad_dict = dict(args_grad or {})
        self.grad_req = dict(grad_req)
        self.aux_dict = dict(aux_states or {})
        self._output_names = symbol.list_outputs()
        self._orig_arg_names = symbol.list_arguments()
        self._orig_aux_names = symbol.list_auxiliary_states()
        self._out_sel = None
        self._param_version = 0
        self._fold_vals = {}
        self._fold_version = -1
        if shared_exec is not None and shared_exec._symbol is symbol:
            # re-bind (reshape / bucket switch): the compiled-program
            # cache AND the bind-time pass results ride across — a
            # shape seen before never re-runs the pipeline or re-folds
            self._prog = shared_exec._prog
            self._opt = shared_exec._opt
            self._train_prog = shared_exec._train_prog
            self._fold_vals = dict(shared_exec._fold_vals)
            self._fold_version = shared_exec._fold_version
            self._param_version = shared_exec._param_version
        else:
            # model-parallel graphs run eagerly node-by-node; keep them
            # off the pass layer (ctx_group placement must see the
            # user's own nodes)
            self._opt = (self._run_graph_passes(symbol, frozen_params)
                         if group2ctx is None else None)
            self._prog = (_GraphProgram(self._opt.symbol,
                                        tuning_key=self._opt.graph_key)
                          if self._opt is not None
                          else _GraphProgram(symbol))
            # inference-only rewrites (pruned loss heads, folded BN,
            # dropped Dropout) must not leak into an explicit
            # forward(is_train=True) on this executor — that path gets
            # a lazily-built program over the ORIGINAL graph
            self._train_prog = (self._prog if self._opt is None
                                or self._opt.for_training else None)
        self._fold_names = (self._opt.fold_names if self._opt is not None
                            else frozenset())
        # model parallelism: ctx_group attrs -> devices (reference:
        # group2ctx through AssignContext, graph_executor.cc:317-421)
        self._group2ctx = group2ctx
        self._ctx_map = (self._prog.assign_contexts(group2ctx, self._ctx)
                         if group2ctx else None)
        self._arg_names = [n for n in self._prog.arg_names
                           if n not in self._fold_names]
        self._aux_names = self._prog.aux_names
        # an argument may live in aux_dict: bn_fold retires a BatchNorm,
        # so its moving stats feed plain arithmetic (arg slots) while
        # the bound arrays still sit in the aux dict
        missing = [n for n in self._arg_names
                   if n not in self.arg_dict and n not in self.aux_dict]
        if missing:
            raise MXNetError("bind: missing arguments %s" % missing)
        self.outputs = []
        self._stashed_grads = None
        # a re-bind (reshape / bucket switch) keeps the monitor armed:
        # calibration (graph_pass.quantize.calibrate) feeds batches of
        # arbitrary size through Module.forward, and the spy must
        # survive the executor swap a shape change triggers
        self._monitor_callback = (shared_exec._monitor_callback
                                  if shared_exec is not None else None)
        self._monitor_use_jit = (shared_exec._monitor_use_jit
                                 if shared_exec is not None else False)
        self._monitor_jit_cache = {}
        self._health_steps = 0

    def _run_graph_passes(self, symbol, frozen_params):
        """Bind-time pass pipeline (graph_pass package): returns the
        OptimizedGraph, or None when the layer is off / nothing changed
        (the program then lowers the original symbol object, keeping
        graph fingerprints — and tuning-cache keys — stable)."""
        from . import graph_pass

        cfg = graph_pass.PassConfig()
        if not cfg.enabled:
            return None
        inference = not any(req != "null"
                            for req in self.grad_req.values())
        frozen = set(frozen_params or ())
        if inference:
            # aux states cannot be fed through forward() and are not
            # mutated by an inference program — always freezable there
            frozen.update(self.aux_dict)
        shapes = {n: tuple(v.shape) for n, v in self.arg_dict.items()}
        shapes.update((n, tuple(v.shape)) for n, v in self.aux_dict.items())
        dtypes = {n: v.dtype for n, v in self.arg_dict.items()}
        dtypes.update((n, v.dtype) for n, v in self.aux_dict.items())
        return graph_pass.optimize_for_bind(
            symbol, for_training=not inference, frozen=frozen,
            arg_shapes=shapes, arg_dtypes=dtypes, config=cfg)

    # --- properties mirroring the reference -------------------------------
    # the public array views follow the ORIGINAL symbol's argument/aux
    # lists (reference API), independent of what the pass layer pruned,
    # folded, or re-classified in the compiled program
    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._orig_arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self._orig_arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._orig_aux_names]

    @property
    def output_dict(self):
        return dict(zip(self.current_output_names, self.outputs))

    @property
    def current_output_names(self):
        """Output names as currently produced (honors select_outputs)."""
        if self._out_sel is None:
            return self._output_names
        return [self._output_names[i] for i in self._out_sel]

    # --- execution ----------------------------------------------------------
    def select_outputs(self, outputs):
        """Restrict inference forwards to a subset of the graph's heads
        (by name or index; None restores all). The compiled program is
        dead-output-pruned to the selection's ancestors — the executor
        half of ``predict(outputs=...)``; training forwards ignore it."""
        if outputs is None:
            self._out_sel = None
            return
        self._out_sel = tuple(
            resolve_output_indices(self._output_names, outputs))

    def _train_program(self):
        """The program train-mode forwards run: the bound program when no
        inference-only rewrite happened, else a lazily-built program over
        the ORIGINAL graph (a grad_req='null' executor may still be asked
        to forward(is_train=True) — reference semantics — and must see
        dropout/loss heads/BN train behavior unrewritten)."""
        if self._train_prog is None:
            self._train_prog = _GraphProgram(self._symbol)
        return self._train_prog

    def _arg_datas(self, prog=None):
        """Program argument feed: bound arrays (args may live in the aux
        dict after bn_fold) plus the fold-pass constants, re-evaluated
        only when the parameter version has bumped."""
        if prog is None:
            prog = self._prog
        folded = self._folded() if prog is self._prog else {}
        d = {}
        for n in prog.arg_names:
            if n in folded:
                continue
            arr = self.arg_dict.get(n)
            if arr is None:
                arr = self.aux_dict[n]
            d[n] = arr._data
        d.update(folded)
        return d

    def _folded(self):
        if self._opt is None or not self._opt.fold_exprs:
            return {}
        if self._fold_version != self._param_version:
            values = {}
            for n in self._opt.fold_inputs:
                arr = self.arg_dict.get(n)
                if arr is None:
                    arr = self.aux_dict[n]
                values[n] = arr._data
            self._fold_vals = self._opt.fold(values)
            self._fold_version = self._param_version
        return self._fold_vals

    def _rng_keys(self, prog=None):
        from . import random as _random

        prog = prog if prog is not None else self._prog
        return tuple(_random.next_key() for _ in prog.rng_nodes)

    def forward(self, is_train=False, **kwargs):
        """Run forward (reference: GraphExecutor::Forward, graph_executor.cc:81).

        In train mode this runs the fused forward+backward XLA program and
        stashes gradients for the subsequent :meth:`backward` call.
        """
        from .ndarray.ndarray import _from_data

        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("unknown argument %r in forward" % k)
            self.arg_dict[k]._set_data(
                v._data.astype(self.arg_dict[k]._data.dtype))
            if self._opt is not None and k in self._opt.fold_input_set:
                # a "frozen" argument just changed through the reference
                # forward-kwargs path: invalidate the folded constants so
                # the new value takes effect (reference semantics)
                self._param_version += 1

        # train-mode forwards on an inference-optimized executor use the
        # unrewritten program (see _train_program)
        prog = self._train_program() if is_train else self._prog
        arg_d = self._arg_datas(prog)
        aux_d = {n: self.aux_dict[n]._data for n in prog.aux_names}
        rngs = self._rng_keys(prog)

        if self._monitor_callback is not None:
            # per-node spy pass: fire the callback for every node output
            # entry (reference: graph_executor.cc:199 ExecuteMonCallback;
            # monitoring disables bulk exec there too — here it runs one
            # eager un-jitted forward, OR — with use_jit — one compiled
            # forward whose interior values reach the host through
            # jax.debug.callback; in train mode the compiled fwd+bwd
            # still runs below for gradients, so a monitored train step
            # pays roughly two forwards; a debug-only cost)
            if self._monitor_use_jit and not self._ctx_map:
                import jax

                outs, aux_upd = self._monitored_jit(is_train)(
                    arg_d, aux_d, rngs)
                # debug.callback delivery is asynchronous on accelerator
                # backends; the monitor reads its stats dict right after
                # forward() returns, so drain the effects queue here
                jax.effects_barrier()
            else:
                outs, aux_upd = prog._eval(
                    arg_d, aux_d, rngs, is_train, ctx_map=self._ctx_map,
                    callback=lambda name, v: self._monitor_callback(
                        name, _from_data(v)))
            if not is_train:
                for n, nv in aux_upd.items():
                    self.aux_dict[n]._set_data(nv)
                if self._out_sel is not None:
                    # the monitored spy pass runs the full graph; honor
                    # the output selection on the way out
                    outs = [outs[i] for i in self._out_sel]
                self.outputs = [_from_data(o) for o in outs]
                self._stashed_grads = None
                return self.outputs

        if self._ctx_map:
            # model-parallel graphs run eagerly so each op dispatches on
            # its assigned device (per-op execution is also what the
            # reference does — engine pushes per node)
            return self._forward_model_parallel(is_train, arg_d, aux_d,
                                                rngs)

        from . import profiler as _profiler
        from .observability import metrics as _metrics
        from .observability import perf as _perf

        profiled = _profiler.symbolic_active()
        telemetry = _metrics.enabled()
        # fenced measurement also when a fit-step waterfall scope is open
        # (observability.perf): the host/device split feeds per-program
        # MFU attribution + the step waterfall's device segment. Scope-
        # gated on purpose — async predict loops outside fit keep their
        # pipelining.
        perf_on = _perf.step_active()
        t0 = _profiler._now_us() if (profiled or telemetry or perf_on) else 0

        if not is_train:
            outs = self._prog.infer_fn(self._out_sel)(arg_d, aux_d, rngs)
            self._stashed_grads = None
        else:
            grad_names = tuple(n for n in prog.arg_names
                               if self.grad_req.get(n, "null") != "null")
            nograd_d = {n: v for n, v in arg_d.items() if n not in grad_names}
            grad_d = {n: arg_d[n] for n in grad_names}
            # seed ones: loss heads ignore it (custom_vjp); matches MXNet's
            # backward()-without-head-grads convention
            seeds = self._ones_seeds(arg_d, aux_d, rngs, prog)
            outs, aux_upd, grads = prog.train_fn(grad_names)(
                nograd_d, grad_d, aux_d, rngs, seeds)
            for n, nv in aux_upd.items():
                self.aux_dict[n]._set_data(nv)
            self._stashed_grads = grads
        if profiled or telemetry or perf_on:
            # one event per compiled-program run — the engine-op analog
            # (a whole graph is ONE engine push here, SURVEY.md §7.1).
            # t1 - t0 = host dispatch (trace/lower/enqueue), t2 - t1 =
            # the device-compute wait: the PR 2 fenced split, applied to
            # the graph path
            import jax

            t1 = _profiler._now_us()
            jax.block_until_ready(outs)
            t2 = _profiler._now_us()
            dur_us = t2 - t0
            name = "forward_backward" if is_train else "forward"
            if profiled:
                _profiler.record(name, "executor", t0, dur_us)
            if telemetry:
                _metrics.counter("dispatch.graph").inc()
                _metrics.histogram("executor.run_ms").observe(dur_us / 1e3)
            if perf_on:
                _perf.note_program_run(
                    prog.perf_cost(arg_d, aux_d, train=is_train),
                    device_s=(t2 - t1) / 1e6, host_s=(t1 - t0) / 1e6)
        self.outputs = [_from_data(o) for o in outs]
        return self.outputs

    def _forward_model_parallel(self, is_train, arg_d, aux_d, rngs,
                                seeds=None, grads_only=False):
        """group2ctx forward(+backward prep): eager multi-device walk with
        jax.vjp for gradients; cross-device copies are the device_puts the
        ctx_map inserts (reference: _CrossDeviceCopy nodes). With
        ``grads_only`` (the explicit backward(out_grads) recompute) the
        gradients are returned and NO state is touched — aux states,
        self.outputs, and stashed grads stay as the user's forward left
        them (the non-parallel path has the same discard semantics)."""
        import jax
        import jax.numpy as jnp

        from .ndarray.ndarray import _from_data

        prog = self._prog
        if not is_train:
            outs, _ = prog._eval(arg_d, aux_d, rngs, False,
                                 ctx_map=self._ctx_map)
            if self._out_sel is not None:  # eager path: slice post-hoc
                outs = [outs[i] for i in self._out_sel]
            self._stashed_grads = None
            self.outputs = [_from_data(o) for o in outs]
            return self.outputs
        grad_names = tuple(n for n in self._arg_names
                           if self.grad_req.get(n, "null") != "null")
        nograd_d = {n: v for n, v in arg_d.items() if n not in grad_names}
        grad_d = {n: arg_d[n] for n in grad_names}

        def f(gd):
            merged = dict(nograd_d)
            merged.update(gd)
            outs, aux_upd = prog._eval(merged, aux_d, rngs, True,
                                       ctx_map=self._ctx_map)
            return tuple(outs), aux_upd

        outs, vjp, aux_upd = jax.vjp(f, grad_d, has_aux=True)
        if seeds is None:
            seeds = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
        grads = vjp(tuple(seeds))[0]
        if grads_only:
            return grads
        for n, nv in aux_upd.items():
            self.aux_dict[n]._set_data(nv)
        self._stashed_grads = grads
        self.outputs = [_from_data(o) for o in outs]
        return self.outputs

    def _ones_seeds(self, arg_d, aux_d, rngs, prog=None):
        """Ones cotangents matching the outputs' abstract shapes/dtypes."""
        import jax
        import jax.numpy as jnp

        prog = prog if prog is not None else self._prog
        key = tuple((n, tuple(v.shape), str(v.dtype))
                    for n, v in sorted(arg_d.items()))
        cache = prog._jit_cache.setdefault("seed_specs", {})
        if key not in cache:
            specs = jax.eval_shape(prog.infer_fn(), arg_d, aux_d, rngs)
            cache[key] = [(s.shape, s.dtype) for s in specs]
        return tuple(jnp.ones(s, dtype=d) for s, d in cache[key])

    def backward(self, out_grads=None, is_train=True):
        """Apply gradients into grad arrays per grad_req (reference:
        GraphExecutor::Backward, graph_executor.cc:94)."""
        if out_grads is not None:
            from .ndarray.ndarray import NDArray

            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            prog = self._train_program()
            arg_d = self._arg_datas(prog)
            aux_d = {n: self.aux_dict[n]._data for n in prog.aux_names}
            seeds = tuple(g._data for g in out_grads)
            if self._ctx_map:
                grads = self._forward_model_parallel(
                    True, arg_d, aux_d, self._rng_keys(), seeds=seeds,
                    grads_only=True)
            else:
                grad_names = tuple(n for n in prog.arg_names
                                   if self.grad_req.get(n, "null") != "null")
                nograd_d = {n: v for n, v in arg_d.items()
                            if n not in grad_names}
                grad_d = {n: arg_d[n] for n in grad_names}
                _, _, grads = prog.train_fn(grad_names)(
                    nograd_d, grad_d, aux_d, self._rng_keys(prog), seeds)
        else:
            if self._stashed_grads is None:
                raise MXNetError("backward() called without a prior "
                                 "forward(is_train=True)")
            grads = self._stashed_grads
        for n, g in grads.items():
            req = self.grad_req.get(n, "null")
            garr = self.grad_dict.get(n)
            if req == "null" or garr is None:
                continue
            if req == "add":
                garr._set_data(garr._data + g.astype(garr._data.dtype))
            else:
                garr._set_data(g.astype(garr._data.dtype))
        return [self.grad_dict.get(n) for n in self._arg_names]

    # --- utilities -----------------------------------------------------------
    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        """(reference: executor.py:235)"""
        for name, array in arg_params.items():
            if name in self.arg_dict:
                array.copyto(self.arg_dict[name])
            elif not allow_extra_params:
                raise ValueError("Find name \"%s\" that is not in the arguments"
                                 % name)
        if aux_params:
            for name, array in aux_params.items():
                if name in self.aux_dict:
                    array.copyto(self.aux_dict[name])
                elif not allow_extra_params:
                    raise ValueError("Find name \"%s\" that is not in the "
                                     "auxiliary states" % name)
        # the fold-pass constants are functions of the parameters just
        # replaced: bump the version so the next forward re-folds
        self._param_version += 1

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor bound to new input shapes (reference:
        executor.py:376). jit shape-signature caching makes this cheap —
        the program object (and its compile cache) is shared."""
        from . import ndarray as nd

        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args, new_grads = {}, {}
        # iterate the ORIGINAL symbol's argument list: the bound arrays
        # cover it even when the optimized program dropped some (pruned
        # labels) or added fold constants (those ride via shared_exec)
        for name, shape in zip(self._symbol.list_arguments(), arg_shapes):
            old = self.arg_dict[name]
            if tuple(old.shape) == tuple(shape):
                new_args[name] = old
                if name in self.grad_dict:
                    new_grads[name] = self.grad_dict[name]
            else:
                new_args[name] = nd.zeros(shape, ctx=self._ctx, dtype=old.dtype)
                if name in self.grad_dict:
                    new_grads[name] = nd.zeros(shape, ctx=self._ctx,
                                               dtype=old.dtype)
        new_aux = {}
        for name, shape in zip(self._symbol.list_auxiliary_states(),
                               aux_shapes):
            old = self.aux_dict[name]
            new_aux[name] = old if tuple(old.shape) == tuple(shape) else \
                nd.zeros(shape, ctx=self._ctx, dtype=old.dtype)
        ex = Executor(self._symbol, self._ctx, new_args, new_grads,
                      self.grad_req, new_aux, shared_exec=self,
                      group2ctx=self._group2ctx)
        return ex

    def _monitored_jit(self, is_train):
        """One compiled forward whose per-node outputs reach the host
        monitor through ``jax.debug.callback`` — the in-jit analog of the
        eager spy pass (monitor.py docstring's promised path). The host
        side reads ``self._monitor_callback`` at fire time, so the cached
        program survives callback swaps."""
        key = bool(is_train)
        fn = self._monitor_jit_cache.get(key)
        if fn is None:
            import functools

            import jax

            from . import ndarray as nd

            def fire(name, host_val):
                cb = self._monitor_callback
                if cb is not None:
                    cb(name, nd.array(host_val))

            def traced_cb(name, value):
                jax.debug.callback(functools.partial(fire, name), value)

            prog = self._train_program() if is_train else self._prog

            def f(arg_d, aux_d, rngs):
                return prog._eval(arg_d, aux_d, rngs, is_train,
                                  callback=traced_cb)

            fn = _maybe_jit(f)
            self._monitor_jit_cache[key] = fn
        return fn

    def set_monitor_callback(self, callback, use_jit=False):
        """Install a per-output monitor (reference: MXExecutorSetMonitorCallback;
        executes an uncompiled node-by-node pass when used via debug
        tools). With ``use_jit`` the monitored forward runs as ONE
        compiled program and interior node values reach the callback via
        ``jax.debug.callback`` instead of an eager per-op walk (ignored
        for model-parallel group2ctx graphs, which always run eagerly)."""
        self._monitor_callback = callback
        self._monitor_use_jit = bool(use_jit)

    def perf_program_cost(self, is_train=False):
        """Analytic cost of the program a forward(is_train=...) on this
        executor runs, at its currently-bound shapes (memoized on the
        program) — the group-level perf note's input
        (executor_group.DataParallelExecutorGroup.forward)."""
        prog = self._train_program() if is_train else self._prog
        arg_d = self._arg_datas(prog)
        aux_d = {n: self.aux_dict[n]._data for n in prog.aux_names}
        return prog.perf_cost(arg_d, aux_d, train=is_train)

    def fused_regions(self):
        """Fusion-region summaries of the compiled inference program —
        ``[{name, base_op, members, lowering, reason}]`` per
        ``_FusedRegion`` node the fuse pass carved at bind
        (graph_pass/fuse.py, docs/fusion.md). ``lowering`` is "kernel"
        (the Pallas fused kernel) or "reference" (the unfused
        composition) with the ``reason`` — the same static
        ``ops.fused.kernel_decision`` the trace-time lowering asks, at
        this executor's bound shapes and dtypes. Empty when the pass is
        off or nothing matched; the program-level twin of the pass
        report, readable without a flight-recorder dump (tests,
        tools/fuse_smoke.py, chip_smoke.py)."""
        import json as _json

        import jax

        from .ops import fused as _fused

        nodes = [n for n in self._prog.topo if n.op == "_FusedRegion"]
        if not nodes:
            return []
        bound = dict(self.aux_dict)
        bound.update(self.arg_dict)
        internals = self._prog.symbol.get_internals()
        feed = [n for n in internals.list_arguments() if n in bound]
        shapes = internals.infer_shape_partial(
            **{n: tuple(bound[n].shape) for n in feed})[1]
        dtypes = internals.infer_type(
            **{n: bound[n].dtype for n in feed})[1]
        avals = {}
        for (node, idx), shape, dtype in zip(internals._outputs, shapes,
                                             dtypes):
            key = node.name if node.is_variable else (id(node), idx)
            src = bound.get(node.name) if node.is_variable else None
            avals[key] = (src if src is not None else
                          None if shape is None else
                          jax.ShapeDtypeStruct(tuple(shape), dtype))
        wants_kernel, interpret, why_off = _fused.use_kernel()
        out = []
        for node in nodes:
            attrs = node.parsed_attrs()
            try:
                members = _json.loads(
                    node.user_attrs.get("__fused_members__", "[]"))
            except ValueError:
                members = []
            ins = [avals.get(n.name if n.is_variable else (id(n), i))
                   for n, i in node.inputs]
            if not wants_kernel:
                plan, why = None, why_off
            elif None in ins:
                plan, why = None, "input shapes not inferable"
            else:
                plan, why = _fused.kernel_decision(attrs, ins, interpret)
            out.append({"name": node.name, "base_op": attrs.base_op,
                        "members": members,
                        "lowering": "reference" if plan is None
                        else "kernel",
                        "reason": why})
        return out

    def named_health_arrays(self):
        """``(kind, name, NDArray)`` triples for the health layer: every
        output and every gradient buffer this executor exposes."""
        out = [("loss", name, o)
               for name, o in zip(self.current_output_names, self.outputs)]
        out.extend(("grad", name, g)
                   for name, g in sorted(self.grad_dict.items())
                   if g is not None)
        return out

    def health_check(self, wall_s=None):
        """Fused non-finite check over this executor's outputs and grads
        (observability.health.guard_step) — the wiring point for code
        that drives executors directly rather than through Module/fit.
        Returns the Verdict, or None when MXNET_HEALTH is off."""
        from .observability import health

        if not health.active():
            return None
        named = self.named_health_arrays()
        self._health_steps += 1
        return health.guard_step(
            "executor",
            losses=[(n, a) for k, n, a in named if k == "loss"],
            grads=[(n, a) for k, n, a in named if k == "grad"],
            params=[(n, a) for n, a in sorted(self.arg_dict.items())
                    if n in self.grad_dict],
            step=self._health_steps, wall_s=wall_s, can_skip=False,
            sync=True)  # one-shot diagnostic: the caller wants THIS step
