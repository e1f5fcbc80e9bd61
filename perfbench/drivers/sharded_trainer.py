"""Driver: a symbol-graph ResNet through ``ShardedTrainer.step`` on a dp
mesh over the cell's chips (the construction of ``chip_smoke.py``'s leg
``resnet50_sharded``). The state is built here from ``--seed`` in the
trainer's own layout, so that the reference can make the same weights."""
import importlib

import numpy as np


class Cell:
    unit = "images"

    def __init__(self, config, sizes, seed, devices):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from mxnet_tpu.models.resnet import resnet
        from mxnet_tpu.parallel import ShardedTrainer, make_mesh
        from perfbench import flops, seeded

        self.config, self.seed = config, seed
        self.ref = importlib.import_module(
            "perfbench.reference." + config["reference"])
        self.batch = B = sizes["batch_per_chip"] * len(devices)
        size, ch = config["image_size"], config["image_channels"]
        self.dtype = jnp.dtype(config["compute_dtype"])
        opt = config["optimizer"]
        self.lr, self.wd = opt["learning_rate"], opt["wd"]
        mesh = make_mesh({"dp": len(devices)}, devices=devices)
        symbol = resnet(units=config["units"], num_stages=len(config["units"]),
                        filter_list=config["filters"],
                        num_classes=config["num_classes"],
                        image_shape=(ch, size, size), bottle_neck=True,
                        layout=config["layout"])
        self.trainer = ShardedTrainer(
            symbol, mesh, optimizer=opt["name"],
            optimizer_params={"learning_rate": self.lr, "wd": self.wd,
                              "momentum": opt["momentum"],
                              "rescale_grad": 1.0 / B},
            dtype=np.dtype(self.dtype))
        data_shape = (B, size, size, ch)
        self.table = self.ref.param_table(config)
        arg_shapes, _, aux_shapes = symbol.infer_shape(
            data=data_shape, softmax_label=(B,))
        have = dict(zip(symbol.list_arguments(), arg_shapes))
        want = {k: tuple(v[0]) for k, v in self.table.items()}
        if {k: tuple(have[k]) for k in self.trainer.param_names} != want:
            raise SystemExit("the program's parameters are not the "
                             "configuration's: %s" % sorted(
                                 set(self.trainer.param_names) ^ set(want)))
        rep, dp = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
        params = seeded.make_params(self.table, seed, self.dtype, rep)
        zeros = jax.jit(lambda shape: jnp.zeros(shape, jnp.float32),
                        static_argnums=0, out_shardings=rep)
        aux = {n: zeros(tuple(s)) + (1.0 if n.endswith("var") else 0.0)
               for n, s in zip(self.trainer.aux_names, aux_shapes)}
        # the float32 start of every leaf: the master copy under mp_sgd,
        # and what the first gradient and the change are read against
        self._w0 = jax.jit(lambda p: jax.tree.map(
            lambda x: x.astype(jnp.float32), p))(params)
        self.master = opt["name"] == "mp_sgd"
        self.state = {"params": params, "aux": aux, "step": 0, "opt": {
            n: (zeros(want[n]),) + ((jnp.copy(self._w0[n]),)
                                    if self.master else ())
            for n in want}}

        classes = config["num_classes"]

        def batch(key):
            kd, kl = jax.random.split(key)
            return {"data": jax.random.uniform(kd, data_shape, jnp.float32)
                    .astype(self.dtype),
                    "softmax_label": jax.random.randint(
                        kl, (B,), 0, classes).astype(jnp.float32)}

        self._batch = jax.jit(batch, out_shardings={"data": dp,
                                                    "softmax_label": dp})
        self.n_pool = sizes["pool"]
        self.pool = [self._batch(seeded.key_for(seed, 1 + i))
                     for i in range(self.n_pool)]
        self._labels = [np.asarray(b["softmax_label"]).astype(np.int64)
                        for b in self.pool]
        self.units_per_step = B
        self.flops_per_step = B * flops.resnet_train_flops_per_image(config)
        self._read = {}

    # --- the window's call and feed ---------------------------------------
    def dispatch(self, i):
        import jax
        import jax.numpy as jnp

        self.state, outs = self.trainer.step(self.state,
                                             self.pool[i % self.n_pool])
        if i == 0:  # the first gradient, from the momentum it left
            lr, wd = self.lr, self.wd
            self._read["grad"] = jax.jit(lambda opt, w0: {
                k: jnp.sqrt(jnp.sum(jnp.square(
                    -opt[k][0] / lr - wd * w0[k]))) for k in w0})(
                        self.state["opt"], self._w0)
        if i == 2:
            now = ({k: v[1] for k, v in self.state["opt"].items()}
                   if self.master else self.state["params"])
            self._read["change"] = jax.jit(lambda p, w0: {
                k: jnp.sqrt(jnp.sum(jnp.square(
                    p[k].astype(jnp.float32) - w0[k]))) for k in w0})(
                        now, self._w0)
            self._w0 = None
        return outs[0], i

    def complete(self, handle):
        probs, i = handle
        p = np.asarray(probs).astype(np.float32)
        idx = self._labels[i % self.n_pool]
        return float(-np.mean(np.log(p[np.arange(len(idx)), idx] + 1e-30)))

    def readings(self, losses):
        return {"loss": list(losses),
                **{k: {n: float(v) for n, v in tree.items()}
                   for k, tree in self._read.items()}}

    def step_temp_bytes(self):
        """The compiled step's temporaries on one chip (XLA's own count)."""
        return self.trainer.lower_step(
            self.state, self.pool[0]).compile().memory_analysis(
            ).temp_size_in_bytes

    def release(self):
        self.state = self.pool = self._w0 = None

    # --- the plain reference, once the window has closed ------------------
    def reference(self, quant=False, share=1.0):
        """``share`` < 1 plants the fault "part of the batch left out, the
        mean taken over the rest": the first rows, repeated to fill the
        batch, give just that mean and those batch statistics."""
        import jax.numpy as jnp

        from perfbench import seeded

        p0 = {n: seeded.make_leaf(self.table, n, self.seed, self.dtype)
              for n in self.table}
        batches = []
        for i in range(3):
            b = self._batch(seeded.key_for(self.seed, 1 + i % self.n_pool))
            rows = int(self.batch * share)
            batches.append(tuple(
                jnp.concatenate([x[:rows]] * int(1 / share)) for x in (
                    b["data"].astype(jnp.float32),
                    b["softmax_label"].astype(jnp.int32))))
        return self.ref.three_steps(self.config, p0, batches, quant=quant)


def build(config, sizes, seed, devices):
    return Cell(config, sizes, seed, devices)
