#!/usr/bin/env python3
"""The grouped-query and windowed flash kernels alone, on the chip:

    chiprun -- python tools/flash_window_bench.py [--check] [--sweep]

``--check``: out, dq, dk, dv of the kernels (bf16) against the dense
formula (float32 at ``highest``), one k/v head's group at a time, at the
shapes of the cell ``laguna_train_t8192_b2``: (H, Hkv) = (48, 8) causal
and (64, 8) under a window of 512, T = 8,192, head width 128.
``--sweep``: forward and backward device time of both shapes over tile
bounds, and the backward fused against two passes (the fused pass of a
group holds ``group * T`` rows of dq in VMEM, under the grouped calls' own
budget and scoped limit). One JSON line a reading; the winners are
constants of ``parallel/flash_attention.py``. ``--tokens 256 --window 64``
rehearses on the CPU in the interpreter (never a reading).
"""
import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--window", type=int, default=512)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu  # noqa: F401  (x64 on, as every kernel meets it)

    fa = importlib.import_module("mxnet_tpu.parallel.flash_attention")
    on_chip = jax.default_backend() == "tpu"
    T, D, Hkv = args.tokens, 128, 8
    shapes = [("full", 48, None), ("window", 64, args.window)]
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind,
                      "platform": device.platform}), flush=True)

    def operands(H, B, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        q, do = (jax.random.normal(k, (B, H, T, D), jnp.float32)
                 .astype(jnp.bfloat16) for k in (ks[0], ks[3]))
        k, v = (jax.random.normal(k, (B, Hkv, T, D), jnp.float32)
                .astype(jnp.bfloat16) for k in ks[1:3])
        return q, k, v, do

    def flash(window, **tiles):
        return lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=window, interpret=not on_chip,
            **tiles)

    # one jitted program a configuration (each is compiled once and timed)
    def forward_of(window, **tiles):
        return jax.jit(flash(window, **tiles))

    def gradients_of(window, do, **tiles):
        return jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            flash(window, **tiles)(q, k, v).astype(jnp.float32) * do),
            (0, 1, 2)))

    def dense_of(window):
        @jax.jit
        def dense(q, k, v, do):
            with jax.default_matmul_precision("highest"):
                o, vjp = jax.vjp(lambda q, k, v: fa._dense_with_lse(
                    q, k, v, causal=True, window=window)[0], q, k, v)
                return (o,) + vjp(do)
        return dense

    def rel(a, b):
        a, b = (np.asarray(x, np.float64) for x in (a, b))
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    if args.check:
        for name, H, window in shapes:
            q, k, v, do = operands(H, 1)
            G = H // Hkv
            got = {"out": [], "dq": [], "dk": [], "dv": []}
            want = {n: [] for n in got}
            out = forward_of(window)(q, k, v)
            grads = gradients_of(window, do)(q, k, v)
            dense = dense_of(window)

            for h in range(Hkv):        # one k/v head's group at a time
                part = (slice(None), slice(h * G, (h + 1) * G))
                kv = (slice(None), slice(h, h + 1))
                o, dq, dk, dv = dense(*(a.astype(jnp.float32) for a in (
                    q[part], k[kv], v[kv], do[part])))
                for n, g, w in (("out", out[part], o),
                                ("dq", grads[0][part], dq),
                                ("dk", grads[1][kv], dk),
                                ("dv", grads[2][kv], dv)):
                    got[n].append(np.asarray(g, np.float32))
                    want[n].append(np.asarray(w))
            print(json.dumps({"check": name, "heads": [H, Hkv], "T": T,
                              "window": window, **{
                                  n: rel(np.concatenate(got[n], 1),
                                         np.concatenate(want[n], 1))
                                  for n in got}}), flush=True)

    def seconds(fn, *ops):
        jax.block_until_ready(fn(*ops))      # compiles
        best = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*ops))
            best.append(time.perf_counter() - t0)
        return sorted(best)[len(best) // 2]

    if args.sweep:
        budget = fa._GROUPED_FUSED_BWD_VMEM_BUDGET
        for name, H, window in shapes:
            q, k, v, do = operands(H, args.batch)
            bounds = ((256, 512, 1024, 2048) if window is None
                      else (256, 512, 1024, 2048))
            for b in bounds:
                for bk in {b, max(b // 2, 128)} if window else {b}:
                    fn = forward_of(window, block_q=b, block_k=bk)
                    try:
                        ms = 1e3 * seconds(fn, q, k, v)
                    except Exception as err:  # Mosaic's refusal, VMEM
                        ms = str(err)[:120]
                    print(json.dumps({"sweep": name, "pass": "forward",
                                      "block_q": b, "block_k": bk,
                                      "ms": ms}), flush=True)
            fwd_ms = 1e3 * seconds(forward_of(window), q, k, v)
            for fused in (False, True):
                fa._GROUPED_FUSED_BWD_VMEM_BUDGET = budget if fused else 0
                for b in bounds:
                    if b < 256:
                        continue
                    fa._forward_call.cache_clear()
                    fa._backward_call.cache_clear()
                    step = gradients_of(window, do, block_q_bwd=b,
                                        block_k_bwd=b)
                    try:
                        ms = 1e3 * seconds(step, q, k, v) - fwd_ms
                    except Exception as err:
                        ms = str(err)[:120]
                    print(json.dumps({"sweep": name, "pass": "backward",
                                      "fused": fused, "block": b, "ms": ms,
                                      "forward_ms": fwd_ms}), flush=True)
            fa._GROUPED_FUSED_BWD_VMEM_BUDGET = budget
    return 0


if __name__ == "__main__":
    sys.exit(main())
