"""The selective-scan kernel pair (parallel/ssm_scan.py) in the Pallas
interpreter, and its chunked ``lax.scan`` form, against the recurrence
written one time step at a time: outputs and all six gradients, at lengths
that are and are not a multiple of the chunk."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.observability import metrics as obs
from mxnet_tpu.parallel.ssm_scan import ssm_scan, ssm_scan_xla

OPERANDS = ("u", "delta", "A", "B", "C", "D")


def time_steps(u, delta, A, Bm, Cm, D):
    """h_t = exp(delta_t A) h_{t-1} + (delta_t u_t) (x) B_t; y_t = h_t C_t
    + D u_t, one step at a time."""
    def step(h, xs):
        u_t, d_t, b_t, c_t = xs
        h = (jnp.exp(d_t[..., None] * A) * h
             + (d_t * u_t)[..., None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], -1)

    Bsz, _, E = u.shape
    _, y = jax.lax.scan(step, jnp.zeros((Bsz, E, A.shape[1]), u.dtype),
                        tuple(x.transpose(1, 0, 2)
                              for x in (u, delta, Bm, Cm)))
    return y.transpose(1, 0, 2) + D * u


def operands(T, B=2, E=24, N=4, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    f32 = jnp.float32       # the package turns x64 on
    u = jax.random.normal(k[0], (B, T, E), f32)
    delta = 0.3 * jax.nn.softplus(jax.random.normal(k[1], (B, T, E), f32))
    A = -jnp.exp(0.5 * jax.random.normal(k[2], (E, N), f32))
    Bm, Cm = (jax.random.normal(k[i], (B, T, N), f32) for i in (3, 4))
    D = jax.random.normal(k[5], (E,), f32)
    weight = jax.random.normal(k[6], (B, T, E), f32)
    return (u, delta, A, Bm, Cm, D), weight


def value_and_grads(fn, ops, weight):
    return jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight),
        argnums=tuple(range(6)))(*ops)


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


FORMS = {
    "kernel": lambda *a: ssm_scan(*a, chunk=16, channels=8, interpret=True),
    "xla": lambda *a: ssm_scan_xla(*a, chunk=16),
}
_cache = {}


def both(form, T):
    if (form, T) not in _cache:
        ops, weight = operands(T)
        with jax.default_matmul_precision("highest"):
            _cache[form, T] = (value_and_grads(FORMS[form], ops, weight),
                               value_and_grads(time_steps, ops, weight))
    return _cache[form, T]


@pytest.mark.parametrize("T", [64, 50, 7])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_output_matches_the_time_step_recurrence(form, T):
    (got, _), (want, _) = both(form, T)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want)) + 1e-4
    ops, _ = operands(T)
    assert rel(FORMS[form](*ops), time_steps(*ops)) < 1e-5


@pytest.mark.parametrize("operand", range(6), ids=OPERANDS)
@pytest.mark.parametrize("T", [64, 50])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_gradient_matches_the_time_step_recurrence(form, T, operand):
    (_, got), (_, want) = both(form, T)
    assert got[operand].shape == want[operand].shape
    assert rel(got[operand], want[operand]) < 1e-5


def test_bf16_operands_keep_a_float32_state():
    """u in bfloat16 (as the layer hands it over): y comes back in
    bfloat16 and is the float32 recurrence of the rounded u to one
    rounding; delta, A and the state stay float32."""
    ops, _ = operands(64)
    u16 = ops[0].astype(jnp.bfloat16)
    got = ssm_scan(u16, *ops[1:], chunk=16, channels=8, interpret=True)
    want = time_steps(u16.astype(jnp.float32), *ops[1:])
    assert got.dtype == jnp.bfloat16
    assert rel(got.astype(jnp.float32), want) < 2 ** -7


def test_a_traced_kernel_call_is_counted_and_an_xla_call_is_not():
    ops, _ = operands(32)
    before = obs.get_value("ssm_scan.kernel", 0)
    obs.set_enabled(True)
    try:
        ssm_scan(*ops, chunk=16, channels=8, interpret=True)
        assert obs.get_value("ssm_scan.kernel", 0) == before + 1
        ssm_scan(*ops)      # off the TPU: the chunked lax.scan
        assert obs.get_value("ssm_scan.kernel", 0) == before + 1
    finally:
        obs.set_enabled(False)


def test_no_state_for_every_token_is_made():
    """Neither pass of the kernel pair holds a (B, T, E, N) array: the
    largest operand of the traced forward and backward is (B, T, E)."""
    ops, weight = operands(64)
    jaxpr = jax.make_jaxpr(lambda *a: value_and_grads(
        FORMS["kernel"], a, weight))(*ops)
    B, T, E = ops[0].shape
    N = ops[2].shape[1]
    sizes = [int(np.prod(v.aval.shape)) for eqn in jaxpr.jaxpr.eqns
             for v in eqn.outvars]
    assert max(sizes) < B * T * E * N
