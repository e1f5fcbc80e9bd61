"""Runtime kernel compilation — the TPU-native ``mx.rtc``.

Reference: python/mxnet/rtc.py CudaModule/CudaKernel over NVRTC
(src/common/rtc.cc:188): users hand the framework raw kernel source at
runtime and launch it on NDArrays. The TPU counterpart of NVRTC is
Pallas/Mosaic — kernels are Python functions over ``Ref``s compiled for
the TPU's VMEM/MXU — so :class:`PallasModule` keeps the reference's
module/get_kernel/launch surface while the kernel language is Pallas:

    source = '''
    def axpy(x_ref, y_ref, out_ref, *, alpha):
        out_ref[...] = y_ref[...] + alpha * x_ref[...]
    '''
    module = mx.rtc.PallasModule(source)
    func = module.get_kernel(
        "axpy", "const float32 *x, const float32 *y, float32 *out, "
                "float32 alpha")
    func.launch([x, y, out, 3.0], mx.gpu(0), (1, 1, 1))

Signature grammar matches the reference's: pointer parameters are
tensors (``const`` = input, mutable = output), value parameters are
scalars forwarded as keyword arguments. The kernel function receives
input Refs (declaration order), then output Refs, then scalars — the
``pallas_call`` calling convention. ``grid_dims`` becomes the pallas
grid; ``block_dims``/``shared_mem`` have no TPU meaning (blocking is
expressed with BlockSpecs inside the kernel source via the exported
``pl`` namespace) and must be left at their defaults.

``CudaModule`` exists for API parity and raises: there is no CUDA
toolchain on a TPU host.
"""
from __future__ import annotations

import numpy as np

from .base import MXNetError
from .ndarray import NDArray

__all__ = ["PallasModule", "PallasKernel", "CudaModule"]

from .base import np_dtype as _np_dtype

# C-style alias -> canonical dtype name; resolution goes through
# base.np_dtype so 'bfloat16' gets a real (ml_dtypes) dtype like
# everywhere else in the package
_DTYPES = {name: _np_dtype(canon) for name, canon in {
    "float": "float32", "float32": "float32",
    "double": "float64", "float64": "float64",
    "half": "float16", "float16": "float16",
    "bfloat16": "bfloat16",
    "int": "int32", "int32": "int32",
    "int8": "int8", "uint8": "uint8",
    "int64": "int64", "long": "int64",
    "bool": "bool",
}.items()}


def _parse_signature(signature):
    """Parse the reference's signature grammar into parameter specs.

    Returns a list of (name, dtype, is_tensor, is_input).
    """
    params = []
    for raw in signature.split(","):
        tokens = raw.replace("*", " * ").split()
        if not tokens:
            continue
        is_const = tokens[0] == "const"
        if is_const:
            tokens = tokens[1:]
        if not tokens:
            raise MXNetError("malformed signature fragment %r" % raw)
        type_word = tokens[0]
        rest = tokens[1:]
        is_tensor = "*" in rest
        rest = [t for t in rest if t != "*"]
        name = rest[-1] if rest else None
        if type_word not in _DTYPES:
            raise MXNetError(
                "unsupported type %r in signature (supported: %s)"
                % (type_word, ", ".join(sorted(_DTYPES))))
        if not name:
            raise MXNetError("parameter in %r has no name" % raw)
        params.append((name, _DTYPES[type_word], is_tensor,
                       is_const or not is_tensor))
    return params


class PallasModule(object):
    """Compile Pallas kernel source at runtime (CudaModule analog).

    Parameters
    ----------
    source : str
        Python source defining one or more kernel functions over Refs.
        The namespace provides ``jnp`` (jax.numpy), ``jax``, ``pl``
        (jax.experimental.pallas) and ``np``.
    options : tuple of str
        Accepted for API parity; must be empty (no compiler flags here —
        XLA/Mosaic owns codegen).
    exports : tuple of str
        Optional allow-list of kernel names; empty exports every
        function defined by ``source``.
    """

    def __init__(self, source, options=(), exports=()):
        if isinstance(options, str):
            options = (options,)
        if isinstance(exports, str):
            exports = (exports,)
        if options:
            raise MXNetError("PallasModule takes no compiler options "
                             "(XLA/Mosaic owns code generation); got %r"
                             % (options,))
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        namespace = {"jnp": jnp, "jax": jax, "pl": pl, "np": np}
        try:
            exec(compile(source, "<rtc>", "exec"), namespace)
        except SyntaxError as e:
            raise MXNetError("rtc source failed to parse: %s" % e)
        injected = {"jnp", "jax", "pl", "np"}
        self._fns = {k: v for k, v in namespace.items()
                     if callable(v) and not k.startswith("_")
                     and k not in injected}
        # optional per-kernel launch specs: a module-level dict named
        # `<kernel>_spec` may carry in_specs/out_specs (pl.BlockSpec
        # blocking — the TPU-native replacement for CUDA block_dims)
        self._specs = {k[:-len("_spec")]: v for k, v in namespace.items()
                       if k.endswith("_spec") and isinstance(v, dict)}
        if exports:
            missing = [e for e in exports if e not in self._fns]
            if missing:
                raise MXNetError("exports not defined by source: %s"
                                 % missing)
            self._fns = {k: self._fns[k] for k in exports}
        if not self._fns:
            raise MXNetError("rtc source defines no kernel functions")

    def get_kernel(self, name, signature):
        """Bind a kernel function to a launch signature
        (reference: rtc.py:get_kernel)."""
        if name not in self._fns:
            raise MXNetError("kernel %r not found (module defines: %s)"
                             % (name, sorted(self._fns)))
        return PallasKernel(self._fns[name], name,
                            _parse_signature(signature),
                            spec=self._specs.get(name))


class PallasKernel(object):
    """A launchable kernel (CudaKernel analog)."""

    def __init__(self, fn, name, params, spec=None):
        self._fn = fn
        self.name = name
        self._params = params
        self._spec = spec or {}
        self._calls = {}   # (grid, shapes, dtypes, scalars, interp) -> call

    def launch(self, args, ctx, grid_dims=(1, 1, 1), block_dims=None,
               shared_mem=0):
        """Run the kernel on ``args`` (reference: rtc.py:launch:185).

        Tensor outputs (non-const pointer parameters) are written back
        into the passed NDArrays, preserving the reference's in-place
        launch semantics on a functional backend.

        ``grid_dims`` maps to the pallas grid (trailing 1s dropped);
        ``block_dims``/``shared_mem`` are CUDA-isms with no TPU meaning
        and must stay None/0.
        """
        import jax
        from jax.experimental import pallas as pl

        if block_dims not in (None, (1, 1, 1)) or shared_mem:
            raise MXNetError(
                "block_dims/shared_mem are CUDA launch parameters; on "
                "TPU express blocking with BlockSpecs in the kernel "
                "source")
        if len(args) != len(self._params):
            raise MXNetError("kernel %s takes %d arguments, got %d"
                             % (self.name, len(self._params), len(args)))
        from .context import Context

        device = Context(ctx).jax_device() if ctx is not None else None
        in_arrays, out_nds, scalars = [], [], {}
        out_shapes = []
        for arg, (pname, dtype, is_tensor, is_input) in zip(args,
                                                            self._params):
            if is_tensor:
                if not isinstance(arg, NDArray):
                    raise MXNetError("argument %r must be an NDArray"
                                     % pname)
                if is_input:
                    a = arg._data.astype(dtype)
                    if device is not None:
                        a = jax.device_put(a, device)
                    in_arrays.append(a)
                else:
                    out_nds.append(arg)
                    out_shapes.append(
                        jax.ShapeDtypeStruct(arg.shape, dtype))
            else:
                # cast scalars to the declared C type (int truncates)
                scalars[pname] = np.asarray(arg, dtype=dtype).item()  # graftlint: disable=G001 — host scalar cast; no device buffer involved
        grid = tuple(int(g) for g in grid_dims)
        while len(grid) > 1 and grid[-1] == 1:
            grid = grid[:-1]

        # the reference launches IN PLACE: the kernel may read an output
        # buffer's current contents (accumulate patterns). Feed each
        # output's current value as a hidden seed input; a wrapper copies
        # it into the out Ref before the user kernel runs, so out Refs
        # are initialized, and the user arity stays (inputs..., outputs...)
        n_in, n_out = len(in_arrays), len(out_nds)
        seed_arrays = []
        for nd_out, oshape in zip(out_nds, out_shapes):
            a = nd_out._data.astype(oshape.dtype)
            if device is not None:
                a = jax.device_put(a, device)
            seed_arrays.append(a)

        # Mosaic-compile when the launch context is a real TPU; interpret
        # everywhere else (CPU harness, virtual meshes)
        platform = (device.platform if device is not None
                    else jax.default_backend())
        interpret = platform != "tpu"
        key = (grid, interpret,
               tuple((a.shape, str(a.dtype)) for a in in_arrays),
               tuple((s.shape, str(s.dtype)) for s in out_shapes),
               tuple(sorted(scalars.items())))
        call = self._calls.get(key)
        if call is None:
            call = self._build_call(grid, in_arrays, out_shapes, scalars,
                                    interpret, n_in, n_out)
            self._calls[key] = call
        outs = call(*in_arrays, *seed_arrays)
        if len(out_shapes) == 1:
            outs = (outs,)
        for nd_out, val in zip(out_nds, outs):
            nd_out._set_data(val.astype(nd_out._data.dtype))
        return [o for o in out_nds]

    def _build_call(self, grid, in_arrays, out_shapes, scalars, interpret,
                    n_in, n_out):
        import functools

        from jax.experimental import pallas as pl

        from .parallel.pallas_common import pallas_call

        user_fn = (functools.partial(self._fn, **scalars) if scalars
                   else self._fn)

        def kernel(*refs):
            # seed refs (n_in:n_in+n_out) are aliased INTO the outputs
            # via input_output_aliases, so each out buffer already holds
            # the passed NDArray's contents — no copy, and grid programs
            # never clobber one another's writes
            ins = refs[:n_in]
            outs = refs[n_in + n_out:]
            user_fn(*ins, *outs)

        extra = {}
        out_specs = self._spec.get("out_specs")
        if "in_specs" in self._spec or out_specs is not None:
            in_specs = list(self._spec.get(
                "in_specs",
                [pl.BlockSpec(s.shape, lambda *i, _n=len(s.shape):
                              (0,) * _n)
                 for s in in_arrays]))
            # the seed inputs block exactly like their outputs
            seed_specs = (list(out_specs)
                          if isinstance(out_specs, (list, tuple))
                          else [out_specs] * n_out)
            extra["in_specs"] = in_specs + seed_specs
            if out_specs is not None:
                extra["out_specs"] = (out_specs
                                      if len(out_shapes) != 1
                                      or not isinstance(out_specs,
                                                        (list, tuple))
                                      else out_specs[0])
        # pallas_common.pallas_call: the kernel traces with x64 scoped
        # off (kernel dtypes come from the signature and are unaffected)
        return pallas_call(
            kernel,
            out_shape=(out_shapes if len(out_shapes) != 1
                       else out_shapes[0]),
            grid=grid if grid != (1,) else (),
            input_output_aliases={n_in + j: j for j in range(n_out)},
            interpret=interpret, **extra)


class CudaModule(object):
    """API-parity stub: CUDA runtime compilation does not exist on a TPU
    host (reference: rtc.py:CudaModule over NVRTC, src/common/rtc.cc).
    Use :class:`PallasModule` — the same module/get_kernel/launch flow
    with Pallas as the kernel language."""

    def __init__(self, source, options=(), exports=()):
        raise MXNetError(
            "CudaModule requires NVRTC/CUDA; this is a TPU build — use "
            "mx.rtc.PallasModule (same API, Pallas kernel source)")
