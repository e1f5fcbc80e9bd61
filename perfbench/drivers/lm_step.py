"""Driver: the causal LM through the function ``TransformerParallel.
step_fn`` returns (the construction of ``chip_smoke.py``'s leg
``lm_train_flash``), whole model on the cell's chips' dp mesh. The flat
parameter dict is made here on the device from ``--seed`` (``model.init``
draws every normal on the host), so that the reference can make it again."""
import importlib

import numpy as np


class Cell:
    unit = "tokens"

    def __init__(self, config, sizes, seed, devices):
        import jax
        import jax.numpy as jnp

        from mxnet_tpu.parallel import make_mesh
        from mxnet_tpu.parallel.transformer import TransformerParallel
        from perfbench import flops, seeded

        self.config, self.seed = config, seed
        self.ref = importlib.import_module(
            "perfbench.reference." + config["reference"])
        self.dtype = jnp.dtype(config["compute_dtype"])
        self.lr = config["optimizer"]["learning_rate"]
        B, T = sizes["batch_per_chip"] * len(devices), sizes["seq_len"]
        self.shape = (B, T)
        mesh = make_mesh({"dp": len(devices)}, devices=devices)
        self.model = TransformerParallel(
            mesh, vocab=config["vocab_size"], d_model=config["hidden_size"],
            n_heads=config["num_attention_heads"],
            n_layers=config["num_hidden_layers"],
            d_ff=config["intermediate_size"], n_experts=config["n_experts"],
            dtype=np.dtype(self.dtype))
        self.table = self.ref.param_table(config)
        shardings = self.model.param_shardings()
        if set(shardings) != set(self.table):
            raise SystemExit("the program's parameters are not the "
                             "configuration's: %s"
                             % sorted(set(shardings) ^ set(self.table)))
        self.params = seeded.make_params(self.table, seed, self.dtype,
                                         shardings)
        self.step = self.model.step_fn(lr=self.lr)
        vocab = config["vocab_size"]
        self._tokens = jax.jit(lambda key: jax.random.randint(
            key, (B, T + 1), 0, vocab).astype(jnp.int32))
        self.n_pool = sizes["pool"]
        self.pool = [self.model.shard_batch(*self._feed(i))
                     for i in range(self.n_pool)]
        self.units_per_step = B * T
        self.flops_per_step = B * T * flops.lm_train_flops_per_token(config, T)
        self._read = {}

    def _feed(self, i):
        from perfbench import seeded

        tok = np.asarray(self._tokens(seeded.key_for(self.seed, 1 + i)))
        return tok[:, :-1], tok[:, 1:]

    def _diff_norms(self):
        """Norm of each leaf's distance from its seeded start, enqueued
        before the next step takes the buffers."""
        import jax
        import jax.numpy as jnp

        from perfbench import seeded

        norm = jax.jit(lambda leaf, start: jnp.sqrt(jnp.sum(jnp.square(
            leaf.astype(jnp.float32) - start))))
        return {n: norm(self.params[n], seeded.make_leaf(
            self.table, n, self.seed, self.dtype)) for n in self.table}

    # --- the window's call and feed ---------------------------------------
    def dispatch(self, i):
        tok, tgt = self.pool[i % self.n_pool]
        self.params, loss = self.step(self.params, tok, tgt)
        if i == 0:  # plain SGD: the first gradient is the first move / lr
            self._read["grad"] = self._diff_norms()
        if i == 2:
            self._read["change"] = self._diff_norms()
        return loss

    def complete(self, handle):
        return float(handle)

    def readings(self, losses):
        return {"loss": list(losses),
                "grad": {n: float(v) / self.lr
                         for n, v in self._read["grad"].items()},
                "change": {n: float(v)
                           for n, v in self._read["change"].items()}}

    def step_temp_bytes(self):
        """The compiled step's temporaries on one chip (XLA's own count)."""
        return self.model._step_jit.lower(
            self.params, *self.pool[0], self.lr).compile().memory_analysis(
            ).temp_size_in_bytes

    def release(self):
        self.params = self.pool = None

    # --- the plain reference, once the window has closed ------------------
    def reference(self, quant=False, share=1.0):
        """``share`` < 1 plants the fault "part of the batch left out, the
        mean taken over the rest": the first rows, repeated."""
        from perfbench import seeded

        rows = int(self.shape[0] * share)
        batches = [tuple(np.concatenate([x[:rows]] * int(1 / share))
                         for x in self._feed(i % self.n_pool))
                   for i in range(3)]
        return self.ref.three_steps(
            self.config,
            lambda n: seeded.make_leaf(self.table, n, self.seed, self.dtype),
            batches, quant=quant)


def build(config, sizes, seed, devices):
    return Cell(config, sizes, seed, devices)
