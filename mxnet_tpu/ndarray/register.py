"""Imperative op invocation + ``mx.nd.*`` codegen.

Reference: python/mxnet/ndarray/register.py:168 generates a Python function
per registered C op at import; src/imperative/imperative.cc:86 (Invoke)
dispatches it. Here `populate_namespaces` generates the same surface from the
Python op registry, and :func:`invoke` is the Invoke analog: parse attrs,
split tensor/param kwargs, run the op's compiled JAX kernel, and — when the
autograd tape is recording — capture the ``jax.vjp`` closure as a TapeNode
(RecordOp analog, imperative.cc:182).
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError
from ..ops.registry import OP_REGISTRY, eager_call
from .ndarray import NDArray, _from_data

__all__ = ["invoke", "record_apply", "populate_namespaces"]


def _cot_dtype(dtype):
    """Cotangent dtype for an output: float0 for non-inexact outputs."""
    import jax

    if np.issubdtype(np.dtype(dtype) if dtype != "bfloat16" else np.float32,
                     np.inexact) or str(dtype) == "bfloat16":
        return dtype
    return jax.dtypes.float0


def _record(f, input_arrays, name, datas=None):
    """Run ``f`` over raw inputs with vjp capture; returns (outs, new_aux).

    ``f``: (raw jax arrays...) -> ((outputs...), (new_aux...))
    ``datas``: pre-normalized raw arrays (device-gathered); defaults to the
    arrays' own data.
    """
    import jax

    from .. import autograd

    if datas is None:
        datas = tuple(a._data for a in input_arrays)
    outs, vjp_fn, new_aux = jax.vjp(lambda *xs: f(*xs), *datas, has_aux=True)
    node = autograd.TapeNode(
        vjp_fn,
        list(input_arrays),
        len(outs),
        [tuple(o.shape) for o in outs],
        [_cot_dtype(o.dtype) for o in outs],
        name=name,
        prim_fn=f,
    )
    return outs, new_aux, node


def record_apply(f, inputs, name="fn"):
    """Differentiable application of a pure jax function to NDArrays.

    Used for python-level sugar (indexing, reshape, transpose) so those stay
    on the autograd tape like any registered op.
    """
    from .. import autograd

    if autograd.is_recording():
        def wrapped(*xs):
            out = f(*xs)
            out = out if isinstance(out, tuple) else (out,)
            return out, ()

        outs, _, node = _record(wrapped, inputs, name)
        res = []
        for i, o in enumerate(outs):
            arr = _from_data(o)
            arr._autograd_node = node
            arr._autograd_index = i
            res.append(arr)
        return res
    out = f(*(a._data for a in inputs))
    out = out if isinstance(out, tuple) else (out,)
    return [_from_data(o) for o in out]


def invoke(opdef, args, kwargs):
    """Invoke one registered op imperatively (Imperative::Invoke analog)."""
    from .. import profiler as _profiler
    from ..observability import metrics as _metrics

    profiled = _profiler.imperative_active()
    telemetry = _metrics.enabled()
    if not (profiled or telemetry):
        return _invoke_impl(opdef, args, kwargs)

    # measured path: run synchronously so durations mean compute, not
    # dispatch (the reference measures inside the engine worker,
    # src/engine/profiler.cc SetOprStart/SetOprEnd). The host-side
    # dispatch cost (t1 - t0: attr parsing, tracing, enqueue RTT) vs the
    # device-compute remainder (t2 - t1: block_until_ready delta) is THE
    # eager-gap decomposition the round-5 review asked for — see
    # PERF_NOTES.md.
    import jax

    t0 = _profiler._now_us()
    res = _invoke_impl(opdef, args, kwargs)
    t1 = _profiler._now_us()
    jax.block_until_ready(
        [r._data for r in
         (res if isinstance(res, (list, tuple)) else [res])])
    t2 = _profiler._now_us()
    if profiled:
        _profiler.record(opdef.name, "operator", t0, t2 - t0)
    if telemetry:
        _metrics.counter("dispatch.eager").inc()
        _metrics.histogram("dispatch.host_us").observe(t1 - t0)
        _metrics.histogram("dispatch.device_us").observe(t2 - t1)
    return res


def _invoke_impl(opdef, args, kwargs):
    from .. import autograd
    from .. import random as _random

    out = kwargs.pop("out", None)
    kwargs.pop("name", None)  # accepted for symbol-compat, unused eagerly

    tensor_kwargs = {}
    attr_kwargs = {}
    for k, v in kwargs.items():
        if isinstance(v, NDArray):
            tensor_kwargs[k] = v
        else:
            attr_kwargs[k] = v

    # reference signatures allow trailing positional params: nd.clip(x,0,1)
    args = opdef.bind_positional_params(args, attr_kwargs, NDArray)

    # variadic ops: auto-fill num_args from positional inputs (Concat, add_n...)
    if "num_args" in opdef.params and "num_args" not in attr_kwargs:
        attr_kwargs["num_args"] = len(args) + len(tensor_kwargs)

    attrs = opdef.parse_attrs(attr_kwargs)
    n_in = opdef.get_num_inputs(attrs)
    aux_names = opdef.get_aux_names(attrs)

    inputs = list(args)
    if tensor_kwargs:
        all_names = opdef.get_input_names(attrs) + aux_names
        slots = {n: i for i, n in enumerate(all_names)}
        full = [None] * len(all_names)
        for i, a in enumerate(inputs):
            full[i] = a
        for k, v in tensor_kwargs.items():
            if k not in slots:
                raise MXNetError("%s: unknown input %r (inputs: %s)"
                                 % (opdef.name, k, all_names))
            full[slots[k]] = v
        inputs = [x for x in full if x is not None]

    main, aux = inputs[:n_in], inputs[n_in:]
    if aux_names and len(aux) != len(aux_names):
        raise MXNetError("%s: expected %d aux states %s, got %d inputs beyond "
                         "the %d main inputs" % (opdef.name, len(aux_names),
                                                 aux_names, len(aux), n_in))

    is_train = autograd.is_training()
    rng = _random.next_key() if opdef.needs_rng else None
    from ..ops.registry import normalize_device_placement

    normalized = normalize_device_placement(
        tuple(a._data for a in main) + tuple(a._data for a in aux))
    main_datas, aux_datas = normalized[:len(main)], normalized[len(main):]

    if autograd.is_recording():
        def f(*xs):
            return opdef.apply(attrs, xs, aux_datas, is_train=is_train, rng=rng)

        outs, new_aux, node = _record(f, main, opdef.name, datas=main_datas)
        results = []
        for i, o in enumerate(outs):
            arr = _from_data(o)
            arr._autograd_node = node
            arr._autograd_index = i
            results.append(arr)
    else:
        outs, new_aux = eager_call(opdef, attrs, main_datas, aux_datas,
                                   is_train=is_train, rng=rng)
        results = [_from_data(o) for o in outs]

    # mutate aux states in place (BatchNorm moving stats, optimizer-op state —
    # FStatefulCompute aux semantics, include/mxnet/op_attr_types.h); ops that
    # should not update in eval mode return their aux unchanged there
    if aux:
        for a, nv in zip(aux, new_aux):
            a._set_data(nv)

    if out is not None:
        outs_nd = out if isinstance(out, (list, tuple)) else [out]
        for dst, src in zip(outs_nd, results):
            dst._set_data(src._data.astype(dst._data.dtype))
        return out

    if len(results) == 1:
        return results[0]
    return results


def _make_op_func(opdef):
    def op_fn(*args, **kwargs):
        return invoke(opdef, args, kwargs)

    op_fn.__name__ = opdef.name
    op_fn.__qualname__ = opdef.name
    op_fn.__doc__ = opdef.doc or ("%s (TPU-native)" % opdef.name)
    return op_fn


def populate_namespaces(op_module, internal_module, contrib_module=None):
    """Generate ``mx.nd.*`` / ``mx.nd._internal._*`` functions (codegen-at-import,
    reference python/mxnet/ndarray/register.py:168)."""
    for name, opdef in OP_REGISTRY.items():
        fn = _make_op_func(opdef)
        if name.startswith("_contrib_") and contrib_module is not None:
            setattr(internal_module, name, fn)
            pub = _make_op_func(opdef)
            pub.__name__ = pub.__qualname__ = name[len("_contrib_"):]
            setattr(contrib_module, name[len("_contrib_"):], pub)
        elif name.startswith("_"):
            setattr(internal_module, name, fn)
        else:
            setattr(op_module, name, fn)
