"""The one door every Pallas TPU kernel in the package goes through.

Two things every kernel (flash attention, the fused matmul family,
``mx.rtc`` user kernels) needs from the TPU lowering, kept here so no call
site can forget either:

* **No x64 in a kernel trace.** The package turns ``jax_enable_x64`` on
  globally (``mxnet_tpu/__init__.py`` — fp64 operator parity), and under
  it every Python scalar in a kernel body or index map becomes an
  i64/f64 constant that Mosaic refuses ("Unsupported cast: float64 ->
  float32"). :func:`pallas_call` traces the kernel, its index maps and
  its grid with x64 scoped off; operand dtypes come from the arrays and
  are unaffected.
* **Tiles the lowering accepts.** A block's last dimension must be a
  multiple of 128 lanes and its second-to-last a multiple of 8 sublanes,
  or equal the array's full dimension (Mosaic for the v5e takes 8-row
  multiples for bf16 blocks too). :func:`aligned_block` is the only tile
  chooser: it returns a legal, usefully large tile or None, and None is
  a *static decline* — the caller lowers its dense / reference
  composition instead.
"""
from __future__ import annotations

import functools

__all__ = ["LANES", "SUBLANES", "aligned_block", "pallas_call"]

#: a TPU vector register is 8 sublanes x 128 lanes — the tile of a
#: block's last two dimensions
LANES = 128
SUBLANES = 8


def aligned_block(n, bound, align):
    """Largest tile of a length-``n`` axis at or below ``bound`` that the
    TPU lowering accepts: the whole axis when it fits under the bound,
    else the largest divisor of ``n`` that is a multiple of ``align``
    (``align=1``: the Pallas interpreter, which has no tiling rule).
    None — the static decline — when there is no such divisor, or only
    ones more than 8x short of the bound (prime-ish ``n``): tiny tiles
    waste the MXU and explode the grid."""
    n, bound, align = int(n), int(bound), int(align)
    if n <= bound:
        return n
    for b in range(bound - bound % align, 0, -align):
        if n % b == 0:
            return b if b * 8 >= bound else None
    return None


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call`` whose kernel body, index maps and grid are traced
    with jax x64 off (see the module docstring). Same keyword arguments;
    returns the same callable."""
    import jax
    from jax.experimental import pallas as pl

    call = pl.pallas_call(kernel, **kwargs)

    @functools.wraps(call)
    def traced_32bit(*args):
        with jax.enable_x64(False):
            return call(*args)

    return traced_32bit
