#!/usr/bin/env python
"""Chip calibration microbench: sustained matmul TF/s and HBM GB/s.

Round-3's roofline defense rested on a calibration measuring 65% of spec
matmul and 54% of spec HBM (PERF_NOTES.md). This is the better-tuned
version the round-3 verdict asked for:

- every measurement chains N dependent iterations inside ONE compiled XLA
  program (lax.scan with a carried data dependence), so host dispatch is
  amortized to nothing — the fenced wall time is device time;
- matmul sweeps shapes (square and MXU-tiled rectangles) and dtypes;
- HBM sweeps copy / scale / triad kernels over working sets far beyond
  the caches, counting exact touched bytes.

Prints one JSON line with the best sustained numbers; these are THE
capability ceilings later rooflines must cite.  The tree's recorded
copy of the last calibration lives in ``autotune.cost_model.CEILINGS``
(the single table every MFU/roofline consumer imports — ISSUE 13); the
output includes the measured-vs-recorded deltas so a recalibration run
says immediately whether the table needs updating.
"""
import json
import os
import sys
import time


sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))


def _timed_scan(step, init_carry, n_iters, n_repeats=3):
    """Best wall time of scan(step, carry, length=n_iters) — one program,
    fenced with ``block_until_ready`` on its scalar checksum (which
    depends on every iteration)."""
    import jax
    import jax.numpy as jnp

    def body(carry, _):
        return step(carry), None

    @jax.jit
    def run(carry):
        out, _ = jax.lax.scan(body, carry, None, length=n_iters)
        leaves = jax.tree_util.tree_leaves(out)
        acc = jnp.float32(0)
        for leaf in leaves:
            acc = acc + jnp.sum(leaf.astype(jnp.float32))
        return acc

    jax.block_until_ready(run(init_carry))  # compile + warm
    best = float("inf")
    for _ in range(n_repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(run(init_carry))
        best = min(best, time.perf_counter() - t0)
    return best


def bench_matmul():
    import jax.numpy as jnp

    results = []
    for dtype in ("bfloat16", "float32"):
        for m, k, n in ((4096, 4096, 4096), (8192, 8192, 8192),
                        (16384, 8192, 8192), (8192, 16384, 8192),
                        (12288, 12288, 12288), (16384, 16384, 16384)):
            try:
                import jax

                a = jnp.ones((m, k), dtype)
                # B must NOT be a constant splat: XLA's algebraic simplifier
                # rewrites dot(x, splat(c)) into a broadcast reduction and
                # the "matmul" disappears. Random values are irreducible.
                b = jax.random.normal(
                    jax.random.PRNGKey(0), (k, n)).astype(dtype)
                # ≥20 TFLOP per run: one dispatch is noise against it
                iters = max(4, int(2e13 / (2 * m * k * n)))

                def step(carry):
                    # dependent chain: each matmul consumes the previous.
                    # B rides in the carry so it stays a runtime buffer —
                    # as a closure constant it would be baked into the HLO
                    # (a huge compile payload) and, if splat, XLA's
                    # algebraic simplifier would delete the dot entirely.
                    # Normalize per iteration so the chain neither explodes
                    # nor underflows; couple through a full reduction when
                    # the output shape differs from the carry shape so XLA
                    # cannot dead-code any part of the product.
                    x, b = carry
                    y = x @ b
                    scale = jax.lax.rsqrt(
                        jnp.mean(jnp.square(y.astype(jnp.float32)))
                        + 1e-30).astype(x.dtype)
                    if y.shape == x.shape:
                        return y * scale, b
                    return x * (1.0 + 1e-30
                                * (jnp.sum(y) * scale).astype(x.dtype)), b

                dt = _timed_scan(step, (a, b), iters)
                tf_s = 2.0 * m * k * n * iters / dt / 1e12
                results.append({"shape": [m, k, n], "dtype": dtype,
                                "tflops": round(tf_s, 1)})
                print("[matmul] %s %s: %.1f TF/s"
                      % ((m, k, n), dtype, tf_s), file=sys.stderr)
            except Exception as err:  # OOM on big shapes: skip
                print("[matmul] %s %s failed: %r"
                      % ((m, k, n), dtype, err), file=sys.stderr)
    return results


def bench_hbm():
    import jax.numpy as jnp

    results = []
    n_elem = 1 << 28  # 256M elements ≥ 512MB in bf16 — far beyond caches
    for dtype, bytes_per in (("bfloat16", 2), ("float32", 4)):
        x = jnp.ones((n_elem,), dtype)

        # NOTE: the scale constant must be exactly representable in bf16
        # (1 + 2^-7 — bf16 has 7 mantissa bits); a constant that rounds to
        # 1.0 lets XLA fold the whole kernel to identity and report
        # impossible bandwidth.
        c = 1.0078125
        kernels = {
            # name: (step fn, bytes touched per iteration)
            "scale": (lambda v: v * c, 2 * n_elem * bytes_per),
            "triad": (lambda v: v * c + 0.5, 2 * n_elem * bytes_per),
        }
        for name, (step, nbytes) in kernels.items():
            iters = max(8, int(1e12 / nbytes))
            dt = _timed_scan(step, x, iters)
            gb_s = nbytes * iters / dt / 1e9
            results.append({"kernel": name, "dtype": dtype,
                            "gb_s": round(gb_s, 1)})
            print("[hbm] %s %s: %.1f GB/s" % (name, dtype, gb_s),
                  file=sys.stderr)
    return results


def main():
    import mxnet_tpu as mx

    dev = mx.tpu(0).jax_device()  # raises without a chip
    matmul = bench_matmul()
    hbm = bench_hbm()
    out = {
        "device": dev.device_kind,
        "matmul": matmul,
        "hbm": hbm,
        "best_tflops": max((r["tflops"] for r in matmul), default=None),
        "best_gb_s": max((r["gb_s"] for r in hbm), default=None),
    }
    # measured vs the tree's recorded table (the basis every MFU number
    # cites): large deltas mean cost_model.CEILINGS needs updating
    from mxnet_tpu.autotune.cost_model import CEILINGS

    recorded = {"matmul_tf_s": CEILINGS["matmul_tf_s"],
                "hbm_gb_s": CEILINGS["hbm_gb_s"]}
    out["recorded_ceilings"] = recorded
    if out["best_tflops"]:
        out["vs_recorded_matmul_pct"] = round(
            100.0 * out["best_tflops"] / recorded["matmul_tf_s"], 1)
    if out["best_gb_s"]:
        out["vs_recorded_hbm_pct"] = round(
            100.0 * out["best_gb_s"] / recorded["hbm_gb_s"], 1)
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
