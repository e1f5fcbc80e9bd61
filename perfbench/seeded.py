"""Weights and inputs from ``--seed``: made on the device, in the type they
are trained in, by one generator that set-up and the reference both call
(the reference makes its own copy again; the program hands it nothing)."""
import functools

import jax
import jax.numpy as jnp


def key_for(seed, stream=0):
    """Any whole number, also one past 2**31, to a key."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


def _leaf(key, index, shape, init, dtype):
    if isinstance(init, tuple):  # ("normal", std)
        x = jnp.float32(init[1]) * jax.random.normal(
            jax.random.fold_in(key, index), shape, dtype=jnp.float32)
    else:
        x = jnp.full(shape, init, jnp.float32)
    return x.astype(dtype)


def make_params(table, seed, dtype, shardings):
    """Every leaf of ``table`` (name -> (shape, init)) in one jitted call,
    placed by ``shardings`` (one for all, or a dict by name)."""
    names = list(table)
    if not isinstance(shardings, dict):
        shardings = {n: shardings for n in names}
    make = jax.jit(
        lambda key: {n: _leaf(key, i, *table[n], dtype)
                     for i, n in enumerate(names)},
        out_shardings={n: shardings[n] for n in names})
    return make(key_for(seed))


@functools.lru_cache(maxsize=None)
def _leaf_fn(shape, init, dtype):
    # the index is traced, so leaves of one shape share one program
    return jax.jit(lambda key, index: _leaf(key, index, shape, init, dtype)
                   .astype(jnp.float32))


def make_leaf(table, name, seed, dtype):
    """One leaf, bit for bit what :func:`make_params` gave it, as float32."""
    shape, init = table[name]
    return _leaf_fn(tuple(shape), init, jnp.dtype(dtype))(
        key_for(seed), list(table).index(name))
