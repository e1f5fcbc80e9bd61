#!/usr/bin/env python3
"""The gated short-convolution kernels alone, on the chip:

    python tools/short_conv_bench.py [--check] [--sweep]      (on a TPU)

``--check``: ``y``, ``d bcx`` and ``dw`` of the kernel pair (bf16 operands)
against the formula in float32 (``short_conv_xla``), at ``--check-tokens``
tokens a sequence of the cell ``lfm2_train_t8192_b2``'s layer (2 sequences,
2048 channels, 3 taps). ``--sweep``: forward, and forward + backward, time
at ``--tokens`` over (rows x channels) tile bounds, beside the least time
the bytes allow (``perfbench/flops_lfm2.py``'s count at 819 GB/s) and the
formula in XLA; the winners are constants of ``parallel/short_conv.py``.
One JSON line a reading. ``--tokens 64 --check-tokens 64 --channels-total
256 --tiles 16x128`` rehearses on the CPU in the interpreter (never a
reading).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: v5e HBM bytes a second (perfbench/peaks.json)
HBM = 819e9


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--check-tokens", type=int, default=8192)
    ap.add_argument("--channels-total", type=int, default=2048)
    ap.add_argument("--tiles", nargs="*", default=["512x1024"],
                    help="rows x channels, e.g. 256x1024 512x2048")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import mxnet_tpu  # noqa: F401  (x64 on, as every kernel meets it)
    from mxnet_tpu.parallel import short_conv as sc

    on_chip = jax.default_backend() == "tpu"
    interpret = None if on_chip else True
    B, d, K = 2, args.channels_total, 3     # the cell's sequences and taps
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind,
                      "platform": device.platform}), flush=True)

    def operands(T, seed=0):
        k = jax.random.split(jax.random.PRNGKey(seed), 3)
        bcx = jax.random.normal(k[0], (B, T, 3 * d), jnp.float32)
        w = 0.5 * jax.random.normal(k[1], (K, d), jnp.float32)
        dy = jax.random.normal(k[2], (B, T, d), jnp.float32)
        return (bcx.astype(jnp.bfloat16), w.astype(jnp.bfloat16)), dy

    def weighed(fn):     # the cotangent rides as an operand, not a constant
        return lambda dy, bcx, w: jnp.sum(fn(bcx, w).astype(jnp.float32)
                                          * dy)

    def both(fn, ops, dy):
        return jax.jit(jax.value_and_grad(weighed(fn), argnums=(1, 2)))(
            dy, *ops)

    if args.check:
        ops, dy = operands(args.check_tokens)
        f32 = lambda a: a.astype(jnp.float32)
        want = both(lambda bcx, w: sc.short_conv_xla(f32(bcx), f32(w)),
                    ops, dy)
        got = both(lambda bcx, w: sc.short_conv(bcx, w, interpret=interpret),
                   ops, dy)
        y_want = jax.jit(lambda a, b: sc.short_conv_xla(f32(a), f32(b)))(*ops)
        y_got = jax.jit(lambda a, b: sc.short_conv(
            a, b, interpret=interpret))(*ops)
        gaps = {"y": float(jnp.linalg.norm(f32(y_got) - y_want)
                           / jnp.linalg.norm(y_want))}
        for name, g, r in zip(("dbcx", "dw"), got[1], want[1]):
            gaps[name] = float(jnp.linalg.norm(f32(g) - r)
                               / jnp.linalg.norm(r))
        print(json.dumps({"check": gaps, "tokens": args.check_tokens}),
              flush=True)

    if args.sweep:
        ops, dy = operands(args.tokens)
        rows_io = B * args.tokens * 2     # bf16 rows of one channel
        least = {"fwd_ms": 1e3 * rows_io * 4 * d / HBM,
                 "fwd_bwd_ms": 1e3 * rows_io * 11 * d / HBM}
        forms = [("xla", lambda bcx, w: sc.short_conv_xla(bcx, w))]
        for tile in args.tiles:
            rows, channels = (int(x) for x in tile.split("x"))
            forms.append((tile, lambda bcx, w, r=rows, c=channels:
                          sc.short_conv(bcx, w, rows=r, channels=c,
                                        interpret=interpret)))
        for name, conv in forms:
            fwd = jax.jit(lambda dy, bcx, w, conv=conv: conv(bcx, w))  # graftlint: disable=G002 — one compile a tile is what the sweep times
            fb = jax.jit(jax.grad(weighed(conv), argnums=(1, 2)))  # graftlint: disable=G002 — one compile a tile is what the sweep times
            row = {"tiles": name, "tokens": args.tokens, "batch": B,
                   "channels": d}
            try:
                for key, fn in (("fwd_ms", fwd), ("fwd_bwd_ms", fb)):
                    jax.block_until_ready(fn(dy, *ops))
                    times = []
                    for _ in range(10):
                        t0 = time.perf_counter()
                        jax.block_until_ready(fn(dy, *ops))
                        times.append(1e3 * (time.perf_counter() - t0))
                    row[key] = sorted(times)[len(times) // 2]
                    if on_chip:
                        row[key.replace("_ms", "_roofline_pct")] = (
                            100.0 * least[key] / row[key])
            except Exception as e:  # a tile past VMEM: say so, go on
                row["error"] = repr(e)[:300]
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
