"""Elementwise unary/binary/scalar operators.

Reference: src/operator/tensor/elemwise_unary_op_basic.cc,
elemwise_binary_op_basic.cc, elemwise_binary_scalar_op_*.cc and the functor
zoo in src/operator/mshadow_op.h (~400 LoC of unary/binary functors with hand
gradients). Here each op is one jnp/lax expression; XLA fuses chains of them
into single kernels (the mshadow expression-template role) and JAX autodiff
supplies the gradients the reference wrote by hand.
"""
from __future__ import annotations


from .param import Float, Int
from .registry import register_op, alias_op


def _jnp():
    import jax.numpy as jnp

    return jnp


def _elemwise_infer(n_in, n_out=1):
    """Same-shape inference with per-dim merge backfill: known dims of any
    input fix the rest (matches ElemwiseShape in
    src/operator/elemwise_op_common.h with 0-dim wildcards)."""

    def infer(attrs, in_shapes, aux_shapes):
        merged = None
        for s in in_shapes:
            if s is None:
                continue
            if merged is None:
                merged = tuple(s)
            elif len(s) == len(merged):
                merged = tuple(a if a != 0 else b
                               for a, b in zip(merged, s))
        if merged is None:
            return None
        return ([merged] * len(in_shapes), [merged] * n_out, aux_shapes)

    return infer


def _reg_unary(name, f, aliases=(), doc=""):
    jnp = _jnp()

    def fn(attrs, x, _f=f):
        return _f(jnp, x)

    op = register_op(name, fn, num_inputs=1, infer_shape=_elemwise_infer(1), doc=doc)
    for a in aliases:
        alias_op(name, a)
    return op


def _register_unary_ops():
    jnp = _jnp()
    import jax

    table = {
        "abs": lambda jnp, x: jnp.abs(x),
        "sign": lambda jnp, x: jnp.sign(x),
        "rint": lambda jnp, x: jnp.rint(x),
        "round": lambda jnp, x: jnp.round(x),
        "ceil": lambda jnp, x: jnp.ceil(x),
        "floor": lambda jnp, x: jnp.floor(x),
        "trunc": lambda jnp, x: jnp.trunc(x),
        "fix": lambda jnp, x: jnp.trunc(x),
        "square": lambda jnp, x: jnp.square(x),
        "sqrt": lambda jnp, x: jnp.sqrt(x),
        "rsqrt": lambda jnp, x: jax.lax.rsqrt(x),
        "cbrt": lambda jnp, x: jnp.cbrt(x),
        "rcbrt": lambda jnp, x: 1.0 / jnp.cbrt(x),
        "exp": lambda jnp, x: jnp.exp(x),
        "log": lambda jnp, x: jnp.log(x),
        "log10": lambda jnp, x: jnp.log10(x),
        "log2": lambda jnp, x: jnp.log2(x),
        "log1p": lambda jnp, x: jnp.log1p(x),
        "expm1": lambda jnp, x: jnp.expm1(x),
        "gamma": lambda jnp, x: jnp.exp(jax.scipy.special.gammaln(x)),
        "gammaln": lambda jnp, x: jax.scipy.special.gammaln(x),
        "erf": lambda jnp, x: jax.scipy.special.erf(x),
        "sin": lambda jnp, x: jnp.sin(x),
        "cos": lambda jnp, x: jnp.cos(x),
        "tan": lambda jnp, x: jnp.tan(x),
        "arcsin": lambda jnp, x: jnp.arcsin(x),
        "arccos": lambda jnp, x: jnp.arccos(x),
        "arctan": lambda jnp, x: jnp.arctan(x),
        "degrees": lambda jnp, x: jnp.degrees(x),
        "radians": lambda jnp, x: jnp.radians(x),
        "sinh": lambda jnp, x: jnp.sinh(x),
        "cosh": lambda jnp, x: jnp.cosh(x),
        "tanh": lambda jnp, x: jnp.tanh(x),
        "arcsinh": lambda jnp, x: jnp.arcsinh(x),
        "arccosh": lambda jnp, x: jnp.arccosh(x),
        "arctanh": lambda jnp, x: jnp.arctanh(x),
        "reciprocal": lambda jnp, x: 1.0 / x,
        "negative": lambda jnp, x: -x,
        "relu": lambda jnp, x: jnp.maximum(x, 0),
        "sigmoid": lambda jnp, x: jax.nn.sigmoid(x),
        "softsign": lambda jnp, x: x / (1.0 + jnp.abs(x)),
        "logical_not": lambda jnp, x: (x == 0).astype(x.dtype),
    }
    for name, f in table.items():
        _reg_unary(name, f)

    # identity family
    def _copy(attrs, x):
        return x + 0 if False else x  # identity; jit makes the copy question moot

    register_op("_copy", _copy, num_inputs=1, infer_shape=_elemwise_infer(1),
                doc="Identity (reference: elemwise_unary_op_basic.cc _copy)")
    alias_op("_copy", "identity")

    def _block_grad(attrs, x):
        import jax

        return jax.lax.stop_gradient(x)

    register_op("BlockGrad", _block_grad, num_inputs=1,
                infer_shape=_elemwise_infer(1),
                doc="Stop gradient (reference: elemwise_unary_op_basic.cc BlockGrad)")
    alias_op("BlockGrad", "stop_gradient")


def _register_binary_ops():
    """Same-shape elementwise binary (reference: elemwise_binary_op_basic.cc).
    The public overloads use the broadcast_* family; these internal names back
    the symbol-level ``_plus`` etc."""
    import jax

    jnp = _jnp()
    table = {
        "elemwise_add": lambda a, b: a + b,
        "elemwise_sub": lambda a, b: a - b,
        "elemwise_mul": lambda a, b: a * b,
        "elemwise_div": lambda a, b: a / b,
        "_maximum": lambda a, b: jnp.maximum(a, b),
        "_minimum": lambda a, b: jnp.minimum(a, b),
        "_hypot": lambda a, b: jnp.hypot(a, b),
        "_power": lambda a, b: jnp.power(a, b),
        "_mod": lambda a, b: jnp.mod(a, b),
        "_equal": lambda a, b: (a == b).astype(a.dtype),
        "_not_equal": lambda a, b: (a != b).astype(a.dtype),
        "_greater": lambda a, b: (a > b).astype(a.dtype),
        "_greater_equal": lambda a, b: (a >= b).astype(a.dtype),
        "_lesser": lambda a, b: (a < b).astype(a.dtype),
        "_lesser_equal": lambda a, b: (a <= b).astype(a.dtype),
    }
    for name, f in table.items():
        def fn(attrs, a, b, _f=f):
            return _f(a, b)

        register_op(name, fn, num_inputs=2, infer_shape=_elemwise_infer(2))
    alias_op("elemwise_add", "_plus")
    alias_op("elemwise_sub", "_minus")
    alias_op("elemwise_sub", "_sub")
    alias_op("elemwise_mul", "_mul")
    alias_op("elemwise_div", "_div")

    # variadic sum (reference: elemwise_sum.cc add_n / ElementWiseSum)
    def add_n(attrs, *xs):
        out = xs[0]
        for x in xs[1:]:
            out = out + x
        return out

    register_op(
        "add_n",
        add_n,
        params={"num_args": Int(default=1)},
        num_inputs=lambda attrs: attrs.num_args,
        input_names=lambda attrs: ["arg%d" % i for i in range(attrs.num_args)],
        infer_shape=lambda attrs, i, a: _elemwise_infer(attrs.num_args)(attrs, i, a),
        doc="Element-wise sum of N arrays (reference: elemwise_sum.cc)",
    )
    alias_op("add_n", "ElementWiseSum")
    alias_op("add_n", "_sum")


def _register_scalar_ops():
    """Tensor-scalar ops (reference: elemwise_binary_scalar_op_basic.cc etc.),
    used by the NDArray/Symbol operator overloads."""
    jnp = _jnp()
    table = {
        "_plus_scalar": lambda x, s: x + s,
        "_minus_scalar": lambda x, s: x - s,
        "_rminus_scalar": lambda x, s: s - x,
        "_mul_scalar": lambda x, s: x * s,
        "_div_scalar": lambda x, s: x / s,
        "_rdiv_scalar": lambda x, s: s / x,
        "_mod_scalar": lambda x, s: jnp.mod(x, s),
        "_rmod_scalar": lambda x, s: jnp.mod(s, x),
        "_power_scalar": lambda x, s: jnp.power(x, s),
        "_rpower_scalar": lambda x, s: jnp.power(s, x),
        "_maximum_scalar": lambda x, s: jnp.maximum(x, s),
        "_minimum_scalar": lambda x, s: jnp.minimum(x, s),
        "_hypot_scalar": lambda x, s: jnp.hypot(x, s),
        "_equal_scalar": lambda x, s: (x == s).astype(x.dtype),
        "_not_equal_scalar": lambda x, s: (x != s).astype(x.dtype),
        "_greater_scalar": lambda x, s: (x > s).astype(x.dtype),
        "_greater_equal_scalar": lambda x, s: (x >= s).astype(x.dtype),
        "_lesser_scalar": lambda x, s: (x < s).astype(x.dtype),
        "_lesser_equal_scalar": lambda x, s: (x <= s).astype(x.dtype),
    }
    for name, f in table.items():
        def fn(attrs, x, _f=f):
            return _f(x, attrs.scalar)

        register_op(name, fn, params={"scalar": Float()}, num_inputs=1,
                    infer_shape=_elemwise_infer(1))

    def smooth_l1(attrs, x):
        # f(x) = 0.5 (sigma x)^2 if |x| < 1/sigma^2 else |x| - 0.5/sigma^2
        # (reference: elemwise_binary_scalar_op_extended.cc:86,
        # mshadow_op::smooth_l1_loss) — the SSD localization loss
        s2 = attrs.scalar * attrs.scalar
        ax = jnp.abs(x)
        return jnp.where(ax < 1.0 / s2, 0.5 * s2 * x * x, ax - 0.5 / s2)

    register_op("smooth_l1", smooth_l1, params={"scalar": Float(default=1.0)},
                num_inputs=1, infer_shape=_elemwise_infer(1))


_register_unary_ops()
_register_binary_ops()
_register_scalar_ops()
