"""The ``_FusedRegion`` operator — execution side of the fusion-region
pass (graph_pass/fuse.py, ISSUE 15).

One node stands in for a carved matmul/conv + epilogue chain.  Its
attrs carry the whole region: the base op name + its original string
attrs (re-parsed through the base opdef, so param semantics can never
drift), and the epilogue as a JSON step list (act / scalar / cast /
vec / res — the grammar in docs/fusion.md).  Extra epilogue operands
(residual tensors, per-channel rescale vectors, the int8 island's fp32
bias) ride as additional node inputs after the base op's own.

Lowering, decided by :func:`kernel_decision` — a static function of the
node's attrs and its inputs' shapes and dtypes, asked at trace time and
again by the executor's region report, which names the reason:

* **Pallas fused kernel** (parallel/fused.py) when the base is a
  float matmul-shaped op on TPU (or under ``MXNET_FUSION_INTERPRET``):
  FullyConnected, 2-d ``dot``, ``batch_dot`` and 1x1 stride-1 NHWC
  Convolution — fp32 VMEM accumulation, epilogue before the HBM
  writeback.  The backward is ``jax.custom_vjp`` over the reference
  composition (recompute — the flash-attention escape-hatch shape).
* **Reference composition** otherwise (general convolutions, int8
  islands whose exact int32 accumulation XLA owns, shapes with no
  tiling the TPU lowering accepts, non-TPU backends): the SAME registry
  ops the unfused
  graph would run, applied in the same order inside this one node —
  numerically identical to the unfused subgraph by construction, and
  the mid-trace-safe fallback the pass contract requires.
"""
from __future__ import annotations

import json

import numpy as np

from ..base import MXNetError
from .param import Int, Str
from .registry import get_op, register_op

__all__ = ["EPILOGUE_ACTS", "fused_region_parts", "kernel_decision",
           "use_kernel"]

# activation kinds the fuse pass may carve (kernel + reference agree;
# parallel/fused.py _ACTS is the kernel-side twin, asserted in tests)
EPILOGUE_ACTS = ("relu", "sigmoid", "tanh", "softrelu", "softsign")

_FLOATS = ("float32", "bfloat16", "float16")


def fused_region_parts(attrs):
    """(base opdef, parsed base attrs, epilogue step list, n_base) from a
    ``_FusedRegion`` node's parsed attrs — shared by execution, shape
    and dtype inference, and the perf accounting walk."""
    base = get_op(attrs.base_op)
    battrs = base.parse_attrs(json.loads(attrs.base_attrs))
    steps = json.loads(attrs.epilogue)
    return base, battrs, steps, int(attrs.n_base)


def _extra_steps(steps):
    return [s for s in steps if s["kind"] in ("vec", "res")]


def _apply_reference(base, battrs, steps, base_inputs, extras):
    """The unfused subgraph, replayed through the SAME registry ops in
    the same order — the parity contract of the pass."""
    out = base.apply(battrs, base_inputs)[0][0]
    ei = 0
    for step in steps:
        kind = step["kind"]
        if kind == "act":
            op = get_op(step["op"])
            kw = {"act_type": step["act"]} if step["op"] == "Activation" \
                else {}
            out = op.apply(op.parse_attrs(kw), [out])[0][0]
        elif kind == "scalar":
            op = get_op(step["op"])
            out = op.apply(op.parse_attrs({"scalar": step["scalar"]}),
                           [out])[0][0]
        elif kind == "cast":
            op = get_op("Cast")
            out = op.apply(op.parse_attrs({"dtype": step["dtype"]}),
                           [out])[0][0]
        elif kind in ("vec", "res"):
            op = get_op(step["op"])
            other = extras[ei]
            ei += 1
            ins = [out, other] if step.get("slot", 0) == 0 else [other, out]
            out = op.apply(op.parse_attrs({}), ins)[0][0]
        else:
            raise MXNetError("fused region: unknown epilogue step %r"
                             % (step,))
    return out


def _kernel_epilogue(steps):
    """Translate graph steps into the kernel's static epilogue tuples:
    ``(tuples, None)``, or ``(None, reason)`` when a step has no kernel
    form."""
    from ..parallel import fused as F

    out = []
    for step in steps:
        kind = step["kind"]
        why = None
        if kind == "act":
            if F.supported_act(step["act"]):
                out.append(("act", step["act"]))
            else:
                why = "activation %s" % step["act"]
        elif kind == "scalar":
            out.append(("scalar", step["op"], float(step["scalar"])))
        elif kind == "cast":
            if step["dtype"] in _FLOATS:
                out.append(("cast", step["dtype"]))
            else:
                why = "cast to %s" % step["dtype"]
        elif kind == "res":
            if step["op"] in ("elemwise_add", "elemwise_mul"):
                out.append(("res", step["op"]))
            else:
                why = "residual op %s" % step["op"]
        elif kind == "vec":
            form = {("full", "broadcast_add"): ("res", "elemwise_add"),
                    ("full", "broadcast_mul"): ("res", "elemwise_mul"),
                    ("lastdim", "broadcast_add"): ("vadd",),
                    ("lastdim", "broadcast_mul"): ("vmul",),
                    }.get((step.get("bshape"), step["op"]))
            if form is not None:
                out.append(form)
            else:
                # a channel vector on a non-last axis (NCHW conv) has no
                # kernel form — the reference composition handles it
                why = "%s over a non-last axis" % step["op"]
        else:
            why = "step kind %s" % kind
        if why is not None:
            return None, "epilogue has no kernel form: " + why
    return tuple(out), None


def _matmul_dims(base, battrs, shapes):
    """``((batch, M, K, N, wt, has_bias), None)`` — the contraction this
    base op is, read off its input SHAPES — or ``(None, reason)`` when it
    has no matmul form. ``batch`` is None for the dense 2-d kernel."""
    name = base.name
    data, weight = tuple(shapes[0]), tuple(shapes[1])
    if name == "FullyConnected":
        rows = data[0] if battrs.flatten else int(np.prod(data[:-1]))
        return (None, int(rows), int(np.prod(data)) // int(rows),
                weight[0], True, not battrs.no_bias), None
    if name in ("dot", "batch_dot"):
        nd = 2 if name == "dot" else 3
        if battrs.get("transpose_a") or battrs.get("transpose_b"):
            return None, "%s with a transposed operand" % name
        if len(data) != nd or len(weight) != nd:
            return None, "%s operands are not %d-d" % (name, nd)
        return ((data[0] if nd == 3 else None), data[-2], data[-1],
                weight[-1], False, False), None
    if name == "Convolution":
        layout = battrs.layout or ""
        if len(data) != 4 or not layout.endswith("C"):
            return None, ("Convolution layout %s is not channels-last"
                          % (layout or "NCHW"))
        if (tuple(battrs.kernel) != (1, 1)
                or tuple(battrs.stride or (1, 1)) != (1, 1)
                or tuple(battrs.pad or (0, 0)) != (0, 0)
                or int(battrs.num_group or 1) != 1
                or bool(battrs.get("dilate") and
                        tuple(battrs.dilate) != (1, 1))):
            return None, "Convolution is not a dense 1x1 stride-1 matmul"
        return (None, int(np.prod(data[:3])), data[3],
                int(battrs.num_filter), False, not battrs.no_bias), None
    return None, "base op %s" % name


def kernel_decision(attrs, in_avals, interpret=False):
    """THE static kernel-vs-reference decision for one ``_FusedRegion``
    node: ``(plan, None)`` when the Pallas kernel applies, ``(None,
    reason)`` when the region lowers its reference composition. A
    function of the node's attrs and its inputs' shapes and dtypes only
    (``in_avals``: anything with ``.shape``/``.dtype``) — it builds and
    traces nothing, so the executor's region report
    (``Executor.fused_regions``) and the trace-time lowering below ask
    the same question and get the same answer."""
    from ..parallel import fused as F

    base, battrs, steps, n_base = fused_region_parts(attrs)
    for t in in_avals:
        if str(t.dtype) not in _FLOATS:
            return None, "%s operand (int8 islands stay with XLA)" % t.dtype
    kern_steps, why = _kernel_epilogue(steps)
    if kern_steps is None:
        return None, why
    dims, why = _matmul_dims(base, battrs, [t.shape for t in
                                            in_avals[:n_base]])
    if dims is None:
        return None, why
    batch, M, K, N, wt, has_bias = dims
    extra_shapes = [t.shape for t in in_avals[n_base:]]
    if has_bias:
        kern_steps = (("bias",),) + kern_steps
        extra_shapes = [in_avals[2].shape] + extra_shapes
    tiles, why = F.kernel_plan(M, N, K, in_avals[0].dtype, kern_steps,
                               extra_shapes, batch=batch,
                               interpret=interpret)
    if tiles is None:
        return None, "no TPU tiling: " + why
    return {"batch": batch, "M": M, "K": K, "N": N, "wt": wt,
            "has_bias": has_bias, "epilogue": kern_steps,
            "tiles": tiles}, None


def _run_kernel(plan, base_inputs, extras, out_aval, interpret):
    """The Pallas lowering of a region :func:`kernel_decision` accepted."""
    from ..parallel import fused as F

    x, w = base_inputs[0], base_inputs[1]
    if plan["has_bias"]:
        extras = [base_inputs[2]] + list(extras)
    bm, bn, bk = plan["tiles"]
    kw = dict(extras=extras, epilogue=plan["epilogue"],
              block_m=bm, block_n=bn, block_k=bk,
              out_dtype=out_aval.dtype, interpret=interpret)
    if plan["batch"] is not None:
        out = F.fused_batch_matmul(x, w, **kw)
    else:
        out = F.fused_matmul(x.reshape(plan["M"], plan["K"]),
                             w.reshape((plan["N"], plan["K"]) if plan["wt"]
                                       else (plan["K"], plan["N"])),
                             wt=plan["wt"], **kw)
    return out.reshape(out_aval.shape)


def use_kernel():
    """``(lower regions to the Pallas kernel?, interpreted?, why not)`` —
    the TPU backend compiles it, ``MXNET_FUSION_INTERPRET`` interprets it
    anywhere, everything else composes the reference."""
    import jax

    from ..config import get_flag

    if get_flag("MXNET_FUSION_INTERPRET"):
        return True, True, None
    if not get_flag("MXNET_FUSION_KERNEL"):
        return False, False, "MXNET_FUSION_KERNEL=0"
    if jax.default_backend() != "tpu":
        return False, False, ("backend %s composes the reference"
                              % jax.default_backend())
    return True, False, None


def _fused_region(attrs, *inputs):
    import jax

    base, battrs, steps, n_base = fused_region_parts(attrs)
    wants_kernel, interpret, _ = use_kernel()

    def reference(*ins):
        return _apply_reference(base, battrs, steps, list(ins[:n_base]),
                                list(ins[n_base:]))

    plan = None
    if wants_kernel:
        plan, _ = kernel_decision(attrs, inputs, interpret)
    if plan is None:
        # not on a kernel backend, or no kernel form at this shape/dtype
        # (a static decision): lower the unfused composition — flash
        # attention's prime-T rule applied to fusion regions
        return reference(*inputs)
    out_aval = jax.eval_shape(reference, *inputs)

    # Pallas forward, reference-recompute backward: the custom_vjp keeps
    # training binds differentiable without a hand-written backward per
    # epilogue combination (the residuals are just the region inputs)
    def kernel(*ins):
        return _run_kernel(plan, list(ins[:n_base]), list(ins[n_base:]),
                           out_aval, interpret)

    f = jax.custom_vjp(kernel)

    def fwd(*ins):
        return kernel(*ins), ins

    def bwd(res, g):
        _, vjp = jax.vjp(reference, *res)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f(*inputs)


def _fused_num_inputs(attrs):
    steps = json.loads(attrs.epilogue)
    return int(attrs.n_base) + len(_extra_steps(steps))


def _fused_input_names(attrs):
    base = get_op(attrs.base_op)
    battrs = base.parse_attrs(json.loads(attrs.base_attrs))
    names = base.get_input_names(battrs)
    steps = json.loads(attrs.epilogue)
    return names + ["fused_extra%d" % i
                    for i in range(len(_extra_steps(steps)))]


def _fused_infer_shape(attrs, in_shapes, aux_shapes):
    base, battrs, steps, n_base = fused_region_parts(attrs)
    res = base.run_infer_shape(battrs, in_shapes[:n_base], [])
    if res is None:
        return None
    base_in, outs = list(res[0]), list(res[1])
    out = outs[0]
    extras = []
    for i, step in enumerate(_extra_steps(steps)):
        given = in_shapes[n_base + i] if n_base + i < len(in_shapes) \
            else None
        same_shape = step["kind"] == "res" or step.get("bshape") == "full"
        if given is None:
            extras.append(tuple(out) if out is not None and same_shape
                          else None)
        elif same_shape and out is not None and len(given) == len(out):
            # the _bcast_infer partial-dim discipline: an unknown (0)
            # extra dim backfills from the region output — the backward
            # shape flow RNN begin-state zeros ride through residual/
            # h2h-add chains
            extras.append(tuple(o if g == 0 else g
                                for g, o in zip(given, out)))
        else:
            extras.append(tuple(given))
    return (base_in + extras, [out], aux_shapes)


def _fused_infer_backward(attrs, out_shapes, in_shapes):
    """Backward shape flow through the region: epilogue steps preserve
    shape, so the region output IS the base output — delegate to the
    base op's backward rule (FullyConnected assigns batch from the
    output; RNN begin-state zeros depend on this flow reaching through
    fused FC+activation chains) and backfill same-shape extras."""
    base, battrs, steps, n_base = fused_region_parts(attrs)
    out = list(in_shapes)
    if base.infer_backward is not None:
        back = base.infer_backward(battrs, list(out_shapes),
                                   list(in_shapes[:n_base]))
        if back is not None:
            out[:n_base] = list(back)[:n_base]
    o = out_shapes[0] if out_shapes else None
    for i, step in enumerate(_extra_steps(steps)):
        j = n_base + i
        if j < len(out) and out[j] is None and o is not None and (
                step["kind"] == "res" or step.get("bshape") == "full"):
            out[j] = tuple(o)
    if out == list(in_shapes):
        return None
    return out


def _fused_infer_dtype(attrs, in_dtypes, aux_dtypes):
    base, battrs, steps, n_base = fused_region_parts(attrs)
    res = base.run_infer_dtype(battrs, in_dtypes[:n_base], [])
    d = res[1][0] if res is not None else (in_dtypes[0] or "float32")
    for step in steps:
        if step["kind"] == "cast":
            d = step["dtype"]
    return (list(in_dtypes), [d], list(aux_dtypes))


register_op(
    "_FusedRegion", _fused_region,
    params={"base_op": Str(), "base_attrs": Str(default="{}"),
            "epilogue": Str(default="[]"), "n_base": Int(default=2)},
    num_inputs=_fused_num_inputs,
    input_names=_fused_input_names,
    infer_shape=_fused_infer_shape,
    infer_backward=_fused_infer_backward,
    infer_dtype=_fused_infer_dtype,
    visible=False,
    doc="Fusion-region node (graph_pass/fuse.py): base matmul/conv + "
        "epilogue chain lowered to a Pallas fused kernel "
        "(parallel/fused.py) with an unfused reference-composition "
        "fallback.  Never user-constructed; docs/fusion.md.")
