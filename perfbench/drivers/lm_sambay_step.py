"""Driver: a decoder-hybrid-decoder LM (state-space layers, differential
attention, gated memory units) through the function
``TransformerParallel.step_fn`` returns — the ``lm_mla_moe_step`` contract
(its window's call and feed, the probe step that reads the first gradient,
each layer recomputed in the backward pass, one slice of the vocabulary),
with the model built from the ``phi4flash`` family's published keys and
the needed operations counted by ``flops_sambay``.

As in ``lm_gqa_moe_step`` the window does not run at the rate of the first
three steps: those, which the reference follows, run at the
configuration's ``learning_rate`` (a bfloat16 update has to survive its
rounding to be read), and from step 3 on the same compiled step (the rate
is its argument) runs at ``window_learning_rate``, under which thirty
steps on four repeating batches stay finite on every seed."""
import importlib

import numpy as np

from perfbench.drivers import lm_gqa_moe_step

PROBE_LR = lm_gqa_moe_step.PROBE_LR


class Cell(lm_gqa_moe_step.Cell):
    def __init__(self, config, sizes, seed, devices):
        import jax
        import jax.numpy as jnp

        from mxnet_tpu.parallel import make_mesh
        from mxnet_tpu.parallel.transformer import TransformerParallel
        from perfbench import flops_sambay, seeded

        self.config, self.seed = config, seed
        self.ref = importlib.import_module(
            "perfbench.reference." + config["reference"])
        self.dtype = jnp.dtype(config["compute_dtype"])
        self.lr = config["optimizer"]["learning_rate"]
        B, T = sizes["batch_per_chip"] * len(devices), sizes["seq_len"]
        self.shape = (B, T)
        mesh = make_mesh({"dp": len(devices)}, devices=devices)
        self.model = TransformerParallel.from_config(
            mesh, config, dtype=np.dtype(self.dtype),
            remat=config.get("recompute") == "per_layer")
        self.table = self.ref.param_table(config)
        shardings = self.model.param_shardings()
        mine = {n: tuple(s) for n, (s, _) in self.model.param_table().items()}
        theirs = {n: tuple(s) for n, (s, _) in self.table.items()}
        if mine != theirs:
            raise SystemExit("the program's parameters are not the "
                             "configuration's: %s"
                             % sorted(set(mine.items()) ^ set(theirs.items())))
        self._make = lambda: seeded.make_params(self.table, seed, self.dtype,
                                                shardings)
        self.params = self._make()
        self._rates = (self.lr, config["optimizer"]["window_learning_rate"])
        self.rate = self.lr
        self.model.step_fn(lr=self.lr)   # the one jitted step, made here
        self.step = lambda params, tok, tgt: self.model.step_fn(
            lr=self.rate)(params, tok, tgt)
        vocab = config["vocab_size"]     # the slice: ids are drawn from it
        self._tokens = jax.jit(lambda key: jax.random.randint(
            key, (B, T + 1), 0, vocab).astype(jnp.int32))
        self.n_pool = sizes["pool"]
        self.pool = [self.model.shard_batch(*self._feed(i))
                     for i in range(self.n_pool)]
        self.units_per_step = B * T
        self.flops_per_step = B * T * flops_sambay.train_flops_per_token(
            config, T)
        kind = jnp.finfo(self.dtype)
        self._round = lambda x: jax.lax.reduce_precision(
            x, kind.nexp, kind.nmant)
        self._distance = jax.jit(lambda leaf, start: jnp.sqrt(jnp.sum(
            jnp.square(leaf.astype(jnp.float32) - self._round(start)))))
        self._read = {}


def build(config, sizes, seed, devices):
    return Cell(config, sizes, seed, devices)
