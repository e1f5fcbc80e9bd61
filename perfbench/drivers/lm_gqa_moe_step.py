"""Driver: a grouped-query LM whose layers differ in head count, mask and
rotary positions, with routed experts, through the function
``TransformerParallel.step_fn`` returns — the ``lm_mla_moe_step`` contract
(its window's call and feed, the probe step that reads the first gradient,
each layer recomputed in the backward pass, one rank's share of the experts
and of the vocabulary), with the model built from the ``laguna`` family's
published keys and the needed operations counted by ``flops_gqa_moe``.

One thing differs: the window does not run at the rate of the first three
steps. Those, which the reference follows, run at the configuration's
``learning_rate`` (1.0: a bfloat16 update has to survive its rounding to be
read). Kept up for forty more steps on four repeating batches that rate is
past what plain SGD holds: the loss swings between 5.2 and 9.8, the router
with it, and the pairs a layer sends to the 32 held experts, 16,384 in
expectation, end a window anywhere from 602 to 32,084, another way on every
seed; the grouped matmuls run the live tiles only, so a step took 421 to
580 ms inside one window and the window read the seed's luck (my chip runs,
PR 33: PERF.md section 6; the driver's check read 0.53% and 0.75% between
runs of one program). From step 3 on the same compiled step (the rate is
its argument) runs at ``window_learning_rate``, under which the router
stays where the seed and the first three steps put it: 13,049 to 18,376
pairs a layer at a window's end, every step within 1 ms of the median."""
import importlib

import numpy as np

from perfbench.drivers import lm_mla_moe_step

PROBE_LR = lm_mla_moe_step.PROBE_LR


class Cell(lm_mla_moe_step.Cell):
    def __init__(self, config, sizes, seed, devices):
        import jax
        import jax.numpy as jnp

        from mxnet_tpu.parallel import make_mesh
        from mxnet_tpu.parallel.transformer import TransformerParallel
        from perfbench import flops_gqa_moe, seeded

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
        if mine != {n: tuple(s) for n, (s, _) in self.table.items()}:
            raise SystemExit("the program's parameters are not the "
                             "configuration's: %s"
                             % sorted(set(mine.items())
                                      ^ set((n, tuple(s)) for n, (s, _)
                                            in self.table.items())))
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
        self.flops_per_step = B * T * flops_gqa_moe.train_flops_per_token(
            config, T)
        kind = jnp.finfo(self.dtype)
        self._round = lambda x: jax.lax.reduce_precision(
            x, kind.nexp, kind.nmant)
        self._distance = jax.jit(lambda leaf, start: jnp.sqrt(jnp.sum(
            jnp.square(leaf.astype(jnp.float32) - self._round(start)))))
        self._read = {}

    def dispatch(self, i):
        self.rate = self._rates[i >= 3]   # run.py: three steps, then the window
        return super().dispatch(i)


def build(config, sizes, seed, devices):
    return Cell(config, sizes, seed, devices)
