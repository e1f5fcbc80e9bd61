"""Plain reference: the pre-activation bottleneck ResNet (He et al.,
arXiv:1603.05027) as MXNet's ``symbols/resnet.py`` builds it, its softmax
cross-entropy, gradients and momentum SGD, in float32 ``jax.numpy`` at
``highest`` matmul precision. Imports nothing of the program.

``quant`` puts the control in its place: every value the program holds
in bfloat16 (weights, each layer's activations, their cotangents) rounded
to fp8 (e4m3, per-tensor scale), the step below what the configuration
states; batch-norm statistics and accumulations stay float32, as there.
"""
import json
import math

import jax
import jax.numpy as jnp


def param_table(cfg):
    """name -> (shape, std or the constant it starts at), in the order the
    seed's keys are folded in. Shapes are NHWC / HWIO."""
    f, c = cfg["filters"], cfg["image_channels"]
    table = {"bn_data_gamma": ((c,), 1.0), "bn_data_beta": ((c,), 0.0)}

    def conv(name, kh, cin, cout):
        table[name + "_weight"] = ((kh, kh, cin, cout),
                                   ("normal", math.sqrt(2.0 / (kh * kh * cin))))

    def bn(name, ch):
        table[name + "_gamma"] = ((ch,), 1.0)
        table[name + "_beta"] = ((ch,), 0.0)

    conv("conv0", 7, c, f[0])
    bn("bn0", f[0])
    cin = f[0]
    for s, n_units in enumerate(cfg["units"]):
        cout = f[s + 1]
        mid = int(cout * cfg["bottleneck_ratio"])
        for u in range(n_units):
            p = "stage%d_unit%d" % (s + 1, u + 1)
            bn(p + "_bn1", cin)
            conv(p + "_conv1", 1, cin, mid)
            bn(p + "_bn2", mid)
            conv(p + "_conv2", 3, mid, mid)
            bn(p + "_bn3", mid)
            conv(p + "_conv3", 1, mid, cout)
            if u == 0:
                conv(p + "_sc", 1, cin, cout)
            cin = cout
    bn("bn1", cin)
    table["fc1_weight"] = ((cfg["num_classes"], cin),
                           ("normal", math.sqrt(2.0 / cin)))
    table["fc1_bias"] = ((cfg["num_classes"],), 0.0)
    return table


def _round_e4m3(x):
    """Round to e4m3 (3 mantissa bits, normal down to 2**-6, largest 448)
    under a per-tensor scale, in float32 arithmetic."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    y = x / scale
    exponent = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -6)))
    step = jnp.exp2(exponent - 3.0)
    return jnp.round(y / step) * step * scale


@jax.custom_vjp
def fp8_store(x):
    """The control's rounding, wherever the program holds a bfloat16 value:
    the value forward and its cotangent backward are both kept in fp8."""
    return _round_e4m3(x)


fp8_store.defvjp(lambda x: (_round_e4m3(x), None),
                 lambda _, g: (_round_e4m3(g),))


def store(x, quant):
    return fp8_store(x) if quant else x


def _conv(x, w, stride, pad, quant):
    return store(jax.lax.conv_general_dilated(
        x, store(w, quant), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC")), quant)


def _bn(x, gamma, beta, eps, quant=False, relu=True):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) * jax.lax.rsqrt(var + eps) * gamma + beta
    return store(jax.nn.relu(y) if relu else y, quant)


def _unit(p, x, prefix, stride, project, eps, quant):
    g = lambda n: p[prefix + n]
    a1 = _bn(x, g("_bn1_gamma"), g("_bn1_beta"), eps, quant)
    y = _conv(a1, g("_conv1_weight"), 1, 0, quant)
    y = _bn(y, g("_bn2_gamma"), g("_bn2_beta"), eps, quant)
    y = _conv(y, g("_conv2_weight"), stride, 1, quant)
    y = _bn(y, g("_bn3_gamma"), g("_bn3_beta"), eps, quant)
    y = _conv(y, g("_conv3_weight"), 1, 0, quant)
    short = _conv(a1, g("_sc_weight"), stride, 0, quant) if project else x
    return store(y + short, quant)


def mean_loss(p, data, labels, cfg, quant=False):
    """Mean cross-entropy of the batch: its gradient is what the program's
    SoftmaxOutput (p - onehot) times rescale_grad = 1/batch hands SGD."""
    eps = cfg["bn_eps"]
    # bn_data: fix_gamma=True, gamma counts as 1 and gets no gradient
    x = _bn(store(data, quant), 1.0, p["bn_data_beta"], eps, quant,
            relu=False)
    x = _conv(x, p["conv0_weight"], 2, 3, quant)
    x = _bn(x, p["bn0_gamma"], p["bn0_beta"], eps, quant)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])
    for s, n_units in enumerate(cfg["units"]):
        for u in range(n_units):
            unit = jax.checkpoint(
                lambda p, x, prefix="stage%d_unit%d" % (s + 1, u + 1),
                stride=2 if (u == 0 and s > 0) else 1, project=(u == 0):
                _unit(p, x, prefix, stride, project, eps, quant))
            x = unit(p, x)
    x = _bn(x, p["bn1_gamma"], p["bn1_beta"], eps, quant)
    x = store(jnp.mean(x, axis=(1, 2)), quant)
    logits = store(x @ store(p["fc1_weight"], quant).T + p["fc1_bias"],
                    quant)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


_STEPS = {}


def make_step(cfg, quant=False):
    """One momentum-SGD step: (params, momentum, data, labels) ->
    (params, momentum, loss, gradient norms by leaf); one jitted function
    per configuration, so that a process compiles it once."""
    key = (json.dumps(cfg, sort_keys=True), quant)
    if key not in _STEPS:
        _STEPS[key] = _make_step(cfg, quant)
    return _STEPS[key]


def _make_step(cfg, quant):
    opt = cfg["optimizer"]
    lr, mom_c, wd = opt["learning_rate"], opt["momentum"], opt["wd"]

    def step(p, mom, data, labels):
        loss, g = jax.value_and_grad(
            lambda p: mean_loss(p, data, labels, cfg, quant))(p)
        mom = {k: mom_c * mom[k] - lr * (g[k] + wd * p[k]) for k in p}
        return ({k: p[k] + mom[k] for k in p}, mom, loss, _norms(g))

    return jax.jit(step)


def three_steps(cfg, params0, batches, quant=False):
    """Follow the program's first steps: ``params0`` name -> float32 array,
    ``batches`` a list of (data float32 NHWC, labels int32). Returns the
    numbers the comparison reads, as host floats."""
    step = make_step(cfg, quant)
    with jax.default_matmul_precision("highest"):
        p = dict(params0)
        mom = {k: jnp.zeros_like(v) for k, v in p.items()}
        losses, grad = [], None
        for data, labels in batches:
            p, mom, loss, g = step(p, mom, data, labels)
            losses.append(float(loss))
            grad = grad or {k: float(v) for k, v in g.items()}
        change = _norms({k: p[k] - params0[k] for k in p})
    return {"loss": losses, "grad": grad,
            "change": {k: float(v) for k, v in change.items()}}
