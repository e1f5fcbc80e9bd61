"""ShardedTrainer — the whole training step as one sharded XLA program.

This is the performance-critical path SURVEY.md §7.3(6) calls out: no per-op
dispatch, no explicit KVStore push/pull — forward + backward + all-reduce +
fused optimizer update compile into a single ``jax.jit`` over a Mesh. It is
the TPU-native equivalent of:

- DataParallelExecutorGroup replica forward/backward
  (python/mxnet/module/executor_group.py:394-554),
- KVStore 'device' gradient reduce (src/kvstore/comm.h:482 CommDevice),
- the fused optimizer update ops (src/operator/optimizer_op.cc),

with XLA sharding propagation emitting the ICI collectives that CommDevice
performed as explicit P2P copies.
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError
from ..executor import _GraphProgram
from ..ops.registry import get_op

__all__ = ["ShardedTrainer"]

# optimizer name → (update op, aux state names in op order)
_FUSED_OPT = {
    "sgd": ("sgd_update", ()),
    "sgd_mom": ("sgd_mom_update", ("mom",)),
    "mp_sgd": ("mp_sgd_update", ("weight32",)),
    "mp_sgd_mom": ("mp_sgd_mom_update", ("mom", "weight32")),
    "adam": ("adam_update", ("mean", "var")),
    "rmsprop": ("rmsprop_update", ("n",)),
    "rmspropalex": ("rmspropalex_update", ("n", "g", "delta")),
    "ftrl": ("ftrl_update", ("z", "n")),
}


class ShardedTrainer:
    """Compile a Symbol's training step over a device mesh.

    Parameters
    ----------
    symbol : Symbol
        Loss-headed training symbol (e.g. ...SoftmaxOutput).
    mesh : jax.sharding.Mesh
        Mesh with a data-parallel axis (default name 'dp').
    optimizer : str
        'sgd' / 'mp_sgd' (momentum>0 selects the _mom variant), 'adam',
        'rmsprop', 'rmspropalex', or 'ftrl' — every fused update op in
        ops/optimizer_ops.py. 'mp_sgd' keeps an fp32 master copy of bf16
        weights (reference mp_sgd_update, src/operator/optimizer_op.cc).
    optimizer_params : dict
        lr/wd/momentum/... forwarded to the fused update op.
    data_names / label_names : input variable names (sharded on dp).
    dtype : computation dtype for params/activations (np.float32 or bf16).
    """

    def __init__(self, symbol, mesh, optimizer="sgd", optimizer_params=None,
                 data_names=("data",), label_names=("softmax_label",),
                 dp_axis="dp", dtype=np.float32):
        import jax

        self.symbol = symbol
        self.mesh = mesh
        self.dp_axis = dp_axis
        self.dtype = dtype
        self._prog = _GraphProgram(symbol)
        self._input_names = [n for n in (*data_names, *label_names)
                             if n in self._prog.arg_names]
        self.param_names = [n for n in self._prog.arg_names
                            if n not in self._input_names]
        self.aux_names = list(self._prog.aux_names)

        opt_params = dict(optimizer_params or {})
        self._lr = opt_params.pop("learning_rate", opt_params.pop("lr", 0.01))
        self._user_rescale = "rescale_grad" in opt_params
        momentum = opt_params.get("momentum", 0.0)
        if optimizer in ("sgd", "mp_sgd"):
            if momentum > 0:
                optimizer += "_mom"
            else:
                opt_params.pop("momentum", None)
        if optimizer not in _FUSED_OPT:
            raise MXNetError("ShardedTrainer supports %s; got %r"
                             % (sorted(_FUSED_OPT), optimizer))
        op_name, state_names = _FUSED_OPT[optimizer]
        self._opt_opdef = get_op(op_name)
        self._opt_state_names = state_names
        # parse once with a placeholder lr to validate + fill defaults; the
        # live (possibly scheduled) lr is spliced in as a traced scalar
        self._opt_defaults = dict(
            self._opt_opdef.parse_attrs(dict(opt_params, lr=0.0))._d)
        self._label_set = set(label_names)
        self._step_fn = None

        from .mesh import data_parallel_sharding, replicated_sharding
        self._dp_sharding = data_parallel_sharding(mesh, dp_axis)
        self._rep_sharding = replicated_sharding(mesh)

    # --- state initialization --------------------------------------------
    def init(self, data_shapes, initializer=None, seed=0):
        """Allocate replicated params/aux and zero optimizer state.

        ``data_shapes``: dict name→GLOBAL batch shape for data+label inputs.
        Returns the state dict used by :meth:`step`.
        """
        import jax
        import jax.numpy as jnp

        from ..initializer import Xavier, InitDesc

        initializer = initializer or Xavier(rnd_type="gaussian",
                                            factor_type="in", magnitude=2)
        if not self._user_rescale:
            # Module convention: rescale_grad = 1/global_batch_size
            # (python/mxnet/module/module.py:init_optimizer)
            batch = next(iter(data_shapes.values()))[0]
            self._opt_defaults["rescale_grad"] = 1.0 / float(batch)
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(**data_shapes)
        shapes = dict(zip(self._prog.arg_names, arg_shapes))
        aux_shape_d = dict(zip(self.aux_names, aux_shapes))

        np.random.seed(seed)
        params = {}
        for name in self.param_names:
            buf = np.zeros(shapes[name], dtype=np.float32)
            initializer(InitDesc(name), buf)
            params[name] = jax.device_put(buf.astype(self.dtype),
                                          self._rep_sharding)
        aux = {}
        for name in self.aux_names:
            fill = 1.0 if name.endswith("_var") or name.endswith("var") else 0.0
            if name.endswith("moving_var"):
                fill = 1.0
            aux[name] = jax.device_put(
                jnp.full(aux_shape_d[name], fill, dtype=np.float32),
                self._rep_sharding)
        def _init_state(state_name, param_name):
            # the mp_sgd master copy starts as the fp32 value of the
            # (possibly bf16) initialized weight, not zeros
            if state_name == "weight32":
                return jax.device_put(
                    jnp.asarray(params[param_name], dtype=np.float32),
                    self._rep_sharding)
            return jax.device_put(
                jnp.zeros(shapes[param_name], dtype=np.float32),
                self._rep_sharding)

        opt_state = {
            name: tuple(_init_state(s, name) for s in self._opt_state_names)
            for name in self.param_names}
        return {"params": params, "aux": aux, "opt": opt_state, "step": 0}

    def shard_batch(self, arrays):
        """Place host arrays onto the mesh, batch-sharded along dp."""
        import jax

        return {k: jax.device_put(np.asarray(v) if k in self._label_set
                                  else np.asarray(v, dtype=self.dtype),
                                  self._dp_sharding)
                for k, v in arrays.items()}

    # --- the compiled step -------------------------------------------------
    def _step_body(self):
        import jax
        import jax.numpy as jnp

        prog = self._prog
        opt_opdef = self._opt_opdef
        from ..observability import device_scope
        from ..ops.registry import OpAttrs

        def step(params, aux, opt_state, batch, lr, step_i):
            # lr is a traced scalar so LR schedules don't recompile
            opt_attrs = OpAttrs(dict(self._opt_defaults, lr=lr))
            rng_base = jax.random.fold_in(jax.random.PRNGKey(0), step_i)
            rngs = tuple(jax.random.fold_in(rng_base, i)
                         for i in range(len(prog.rng_nodes)))

            def loss_fn(p):
                arg_d = dict(batch)
                arg_d.update(p)
                with device_scope("forward"):
                    outs, aux_upd = prog._eval(arg_d, aux, rngs, True)
                return tuple(outs), aux_upd

            from ..executor import _maybe_mirror

            outs, vjp, aux_upd = jax.vjp(_maybe_mirror(loss_fn), params,
                                         has_aux=True)
            seeds = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
            grads = vjp(seeds)[0]

            new_params = {}
            new_opt = {}
            with device_scope("update"):
                for name in self.param_names:
                    w, g = params[name], grads[name]
                    states = opt_state[name]
                    (new_w,), new_states = opt_opdef.apply(
                        opt_attrs, (w, g.astype(w.dtype)), states)
                    # keep the carried weight dtype stable (bf16 weights
                    # with fp32 optimizer state = the mp_sgd master-copy
                    # pattern, src/operator/optimizer_op.cc mp_sgd_update)
                    new_params[name] = new_w.astype(w.dtype)
                    new_opt[name] = tuple(new_states)
            new_aux = dict(aux)
            new_aux.update(aux_upd)
            return new_params, new_aux, new_opt, outs

        return step

    def _build_step(self):
        import jax

        return jax.jit(self._step_body(), donate_argnums=(0, 1, 2))

    def _build_multi_step(self, n_steps):
        """n_steps training steps as ONE XLA program via lax.scan — the
        TPU-native training loop: no host round-trip per step (the engine
        bulk-segment idea, graph_executor.cc:1345 InitOpSegs, taken to its
        XLA conclusion). Returns (new_state_parts, last_outs)."""
        import jax

        body = self._step_body()

        def multi(params, aux, opt_state, batch, lrs, step0):
            def scan_body(carry, lr):
                params, aux, opt_state, i = carry
                params, aux, opt_state, outs = body(
                    params, aux, opt_state, batch, lr, i)
                import jax.numpy as jnp

                # carry a per-step scalar (not the full output tensor) so
                # the stacked result stays tiny but still depends on the
                # whole step's compute
                return (params, aux, opt_state, i + 1), jnp.mean(
                    outs[0].astype(jnp.float32))

            (params, aux, opt_state, _), losses = jax.lax.scan(
                scan_body, (params, aux, opt_state, step0), lrs)
            return params, aux, opt_state, losses

        return jax.jit(multi, donate_argnums=(0, 1, 2))

    def multi_step(self, state, batch, n_steps):
        """Run ``n_steps`` steps on one batch in a single dispatch; returns
        (new_state, per-step first-output-mean stack). LR schedules are
        honored per step (the schedule is evaluated on host and fed to the
        scan as a per-step vector)."""
        import numpy as np

        from ..observability import trace_span

        step0 = state["step"]
        with trace_span("sharded_trainer.multi_step", "parallel",
                        step=step0, steps=n_steps):
            key = ("multi", n_steps)
            if not hasattr(self, "_multi_fns"):
                self._multi_fns = {}
            if key not in self._multi_fns:
                self._multi_fns[key] = self._build_multi_step(n_steps)
            lrs = np.asarray(
                [self._lr(step0 + i) if callable(self._lr) else self._lr
                 for i in range(n_steps)], dtype=np.float32)
            with trace_span("sharded_trainer.enqueue", "parallel"):
                params, aux, opt, outs = self._multi_fns[key](
                    state["params"], state["aux"], state["opt"], batch,
                    lrs, np.int32(step0))
            return ({"params": params, "aux": aux, "opt": opt,
                     "step": step0 + n_steps}, outs)

    def lower_step(self, state, batch):
        """``jax.jit(...).lower(...)`` of the fused train step, for HLO
        inspection (tools/hlo_layout_audit.py counts layout-moving ops
        in the optimized module)."""
        if self._step_fn is None:
            self._step_fn = self._build_step()
        lr = self._lr(state["step"]) if callable(self._lr) else self._lr
        return self._step_fn.lower(
            state["params"], state["aux"], state["opt"], batch,
            np.float32(lr), np.int32(state["step"]))

    def step(self, state, batch):
        """Run one training step; returns (new_state, outputs).

        ``batch``: dict of sharded arrays from :meth:`shard_batch`."""
        from ..observability import trace_span

        with trace_span("sharded_trainer.step", "parallel",
                        step=state["step"]):
            if self._step_fn is None:
                self._step_fn = self._build_step()
            lr = self._lr(state["step"]) if callable(self._lr) else self._lr
            with trace_span("sharded_trainer.enqueue", "parallel"):
                params, aux, opt, outs = self._step_fn(
                    state["params"], state["aux"], state["opt"], batch,
                    np.float32(lr), np.int32(state["step"]))
            return ({"params": params, "aux": aux, "opt": opt,
                     "step": state["step"] + 1}, outs)

    # --- checkpoint / resume ------------------------------------------------
    def save_checkpoint(self, state, prefix, epoch=0):
        """Write ``prefix-symbol.json`` + ``prefix-%04d.params`` (the
        Module checkpoint pair, reference model.py:366) plus
        ``prefix-%04d.opt.npz`` holding optimizer state and step count, so
        sharded training resumes exactly. Multi-host: process 0 writes
        (replicated state is identical everywhere) to a SHARED
        filesystem, then all processes fence before anyone loads."""
        from .mesh import write_and_fence

        write_and_fence(lambda: self._write_checkpoint(state, prefix,
                                                       epoch),
                        "sharded_ckpt_%s_%d" % (prefix, epoch))

    def _write_checkpoint(self, state, prefix, epoch):
        from .. import ndarray as nd

        self.symbol.save("%s-symbol.json" % prefix)
        save_dict = {}
        for k, v in state["params"].items():
            # bf16 round-trips exactly through fp32
            save_dict["arg:%s" % k] = nd.array(
                np.asarray(v, dtype=np.float32))
        for k, v in state["aux"].items():
            save_dict["aux:%s" % k] = nd.array(np.asarray(v))
        nd.save("%s-%04d.params" % (prefix, epoch), save_dict)
        opt_np = {"step": np.int64(state["step"]),
                  "rescale_grad": np.float64(
                      self._opt_defaults.get("rescale_grad", 1.0))}
        for name, states in state["opt"].items():
            for i, s in enumerate(states):
                opt_np["%s/%d" % (name, i)] = np.asarray(s)
        np.savez("%s-%04d.opt.npz" % (prefix, epoch), **opt_np)

    def load_checkpoint(self, prefix, epoch=0):
        """Rebuild the training state dict from a checkpoint; every
        process loads and re-places onto its mesh (replicated), so the
        resumed run is bit-identical to an uninterrupted one."""
        import jax
        import jax.numpy as jnp

        from .. import ndarray as nd

        loaded = nd.load("%s-%04d.params" % (prefix, epoch))
        params, aux = {}, {}
        for k, v in loaded.items():
            tag, name = k.split(":", 1)
            if tag == "arg":
                params[name] = jax.device_put(
                    jnp.asarray(v.asnumpy(), dtype=self.dtype),  # graftlint: disable=G001 — one-time checkpoint load
                    self._rep_sharding)
            else:
                aux[name] = jax.device_put(jnp.asarray(v.asnumpy()),  # graftlint: disable=G001 — one-time checkpoint load
                                           self._rep_sharding)
        missing = set(self.param_names) - set(params)
        if missing:
            raise MXNetError("checkpoint %r is missing parameters: %s"
                             % (prefix, sorted(missing)))
        with np.load("%s-%04d.opt.npz" % (prefix, epoch)) as z:
            step = int(z["step"])
            if not self._user_rescale and "rescale_grad" in z:
                # init() derives this from the batch size; a resumed
                # trainer must apply the same scale without init(). The
                # compiled step baked the old value in at trace time, so
                # drop any compiled functions when it changes
                new_scale = float(z["rescale_grad"])
                if self._opt_defaults.get("rescale_grad") != new_scale:
                    self._opt_defaults["rescale_grad"] = new_scale
                    self._step_fn = None
                    if hasattr(self, "_multi_fns"):
                        self._multi_fns.clear()
            opt_state = {}
            for name in self.param_names:
                opt_state[name] = tuple(
                    jax.device_put(jnp.asarray(z["%s/%d" % (name, i)]),
                                   self._rep_sharding)
                    for i in range(len(self._opt_state_names)))
        return {"params": params, "aux": aux, "opt": opt_state,
                "step": step}

    # --- inference ----------------------------------------------------------
    def forward_fn(self):
        """Compiled inference forward over the mesh (batch-sharded)."""
        import jax

        prog = self._prog

        def fwd(params, aux, batch):
            arg_d = dict(batch)
            arg_d.update(params)
            outs = prog._eval(arg_d, aux, (), False)[0]
            return outs

        return jax.jit(fwd)
