"""Driver: a latent-attention LM with routed experts through the function
``TransformerParallel.step_fn`` returns — the ``lm_step`` contract and its
window's call and feed, with the model described by its layers from the
published configuration's keys, each layer recomputed in the backward pass,
and one rank's share of the experts and of the vocabulary held here.

What ``correct`` reads of the first gradient does not go through the
configuration's learning rate: at 1.0 bfloat16 SGD rounds most of a small
leaf's update away (the routed experts' leaves keep 46% of their first
move: PERF.md, PR 28), and a gradient read from that move cannot tell a
dead expert from a live one. Set-up therefore calls the window's compiled
step once from the seeded start at ``PROBE_LR`` — a power of two, so that
``lr * g`` is exact, and so large that nothing of the move is swallowed —
reads every leaf's move against its start, and makes the state again from
the seed. The three steps the reference follows, and the window, then run
at the configuration's rate.
"""
import importlib

import numpy as np

from perfbench.drivers import lm_step

#: the learning rate of the first gradient's probe: the smallest leaves'
#: gradients (rms 5e-6 an element at the cell's size) then move their
#: weights (0.02; norm weights 1.0) by more than the weights themselves
PROBE_LR = 2.0 ** 14


class Cell(lm_step.Cell):
    def __init__(self, config, sizes, seed, devices):
        import jax
        import jax.numpy as jnp

        from mxnet_tpu.parallel import make_mesh
        from mxnet_tpu.parallel.transformer import TransformerParallel
        from perfbench import flops_mla_moe, seeded

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
                             % sorted(set(mine) ^ set(self.table)))
        self._make = lambda: seeded.make_params(self.table, seed, self.dtype,
                                                shardings)
        self.params = self._make()
        self.step = self.model.step_fn(lr=self.lr)
        vocab = config["vocab_size"]     # the slice: ids are drawn from it
        self._tokens = jax.jit(lambda key: jax.random.randint(
            key, (B, T + 1), 0, vocab).astype(jnp.int32))
        self.n_pool = sizes["pool"]
        self.pool = [self.model.shard_batch(*self._feed(i))
                     for i in range(self.n_pool)]
        self.units_per_step = B * T
        self.flops_per_step = B * T * flops_mla_moe.train_flops_per_token(
            config, T)
        kind = jnp.finfo(self.dtype)
        self._round = lambda x: jax.lax.reduce_precision(
            x, kind.nexp, kind.nmant)
        self._distance = jax.jit(lambda leaf, start: jnp.sqrt(jnp.sum(
            jnp.square(leaf.astype(jnp.float32) - self._round(start)))))
        self._read = {}

    def _start(self, name):
        """A leaf's seeded start as float32, rounded as the training dtype
        rounds. ``make_leaf`` casts down and up again inside one program,
        and XLA may keep the excess precision of such a round trip (on the
        TPU it does: PERF.md, PR 28); ``reduce_precision`` is never
        elided. With the unrounded start a bf16 leaf's change reads its
        own rounding noise."""
        from perfbench import seeded

        return self._round(seeded.make_leaf(self.table, name, self.seed,
                                            self.dtype))

    def _moved(self):
        """Norm of each leaf's distance from its seeded start, one leaf at
        a time and waited for: one float32 copy of one start is live, and
        no step runs beside it."""
        from perfbench import seeded

        return {n: float(self._distance(self.params[n], seeded.make_leaf(
            self.table, n, self.seed, self.dtype))) for n in self.table}

    def _first_gradient(self):
        """The probe (see the module's note): one call of the compiled step
        on the first batch at ``PROBE_LR``, every leaf's move over that
        rate, and the state made again from the seed."""
        self.params, _ = self.model.step_fn(lr=PROBE_LR)(
            self.params, *self.pool[0])
        grad = {n: v / PROBE_LR for n, v in self._moved().items()}
        self.params = None      # freed before the state is made again
        self.params = self._make()
        return grad

    def dispatch(self, i):
        if i == 0:
            self._read["grad"] = self._first_gradient()
        tok, tgt = self.pool[i % self.n_pool]
        self.params, loss = self.step(self.params, tok, tgt)
        if i == 2:
            self._read["change"] = self._moved()
        return loss

    def readings(self, losses):
        return {"loss": list(losses), **self._read}

    def routing_stats(self, i=0):
        """What the router sends to the held experts on pool batch ``i``."""
        return self.model.routing_stats(self.params, self.pool[i][0])

    def reference(self, quant=False, share=1.0):
        """``share`` < 1 plants the fault "part of the batch left out, the
        mean taken over the rest": the first rows, repeated — or, of a
        one-row batch, the first part of the sequence alone."""
        rows = int(self.shape[0] * share)
        batches = []
        for i in range(3):
            tok, tgt = self._feed(i % self.n_pool)
            if rows:
                tok, tgt = (np.concatenate([x[:rows]] * int(1 / share))
                            for x in (tok, tgt))
            else:
                cut = int(self.shape[1] * share)
                tok, tgt = tok[:, :cut], tgt[:, :cut]
            batches.append((tok, tgt))
        return self.ref.three_steps(self.config, self._start, batches,
                                    quant=quant)


def build(config, sizes, seed, devices):
    return Cell(config, sizes, seed, devices)
