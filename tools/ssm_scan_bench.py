#!/usr/bin/env python3
"""The selective-scan kernels alone, on the chip:

    chiprun -- python tools/ssm_scan_bench.py [--check] [--sweep]

``--check``: y and the six gradients of the kernel pair (u in bf16)
against the chunked ``lax.scan`` form in float32, at ``--check-tokens``
steps of the cell ``phi4flash_train_t8192_b1``'s layer (5120 channels, 16
states). ``--sweep``: forward and forward + backward time at ``--tokens``
steps over (chunk, channels) tile bounds; the winners are constants of
``parallel/ssm_scan.py``. One JSON line a reading. ``--tokens 64
--channels-total 256`` rehearses on the CPU in the interpreter (never a
reading).
"""
import argparse
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
    ap.add_argument("--check-tokens", type=int, default=1024)
    ap.add_argument("--channels-total", type=int, default=5120)
    ap.add_argument("--states", type=int, default=16)
    ap.add_argument("--tiles", nargs="*", default=["256x512"],
                    help="chunk x channels, e.g. 128x512 256x1024")
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import mxnet_tpu  # noqa: F401  (x64 on, as every kernel meets it)
    from mxnet_tpu.parallel import ssm_scan as ss

    on_chip = jax.default_backend() == "tpu"
    interpret = None if on_chip else True
    E, N = args.channels_total, args.states
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind,
                      "platform": device.platform}), flush=True)

    def operands(T, seed=0):
        k = jax.random.split(jax.random.PRNGKey(seed), 7)
        u = jax.random.normal(k[0], (1, T, E), jnp.float32)
        delta = 0.05 * jax.nn.softplus(jax.random.normal(k[1], (1, T, E)))
        A = -jnp.exp(0.3 * jax.random.normal(k[2], (E, N))) * jnp.arange(
            1, N + 1, dtype=jnp.float32)
        Bm, Cm = (jax.random.normal(k[i], (1, T, N)) for i in (3, 4))
        D = jnp.ones((E,), jnp.float32)
        w = jax.random.normal(k[6], (1, T, E), jnp.float32)
        return (u.astype(jnp.bfloat16), delta, A, Bm, Cm, D), w

    def weighed(fn):     # the weights ride as an operand, not a constant
        return lambda w, *a: jnp.sum(fn(*a).astype(jnp.float32) * w)

    def both(fn, ops, w):
        return jax.jit(jax.value_and_grad(
            weighed(fn), argnums=tuple(range(1, 7))))(w, *ops)

    if args.check:
        ops, w = operands(args.check_tokens)
        want = both(lambda *a: ss.ssm_scan_xla(
            a[0].astype(jnp.float32), *a[1:]), ops, w)
        got = both(lambda *a: ss.ssm_scan(*a, interpret=interpret), ops, w)
        gaps = {"y_sum": abs(float(got[0]) - float(want[0]))
                / abs(float(want[0]))}
        for name, g, r in zip(("u", "delta", "A", "B", "C", "D"),
                              got[1], want[1]):
            g, r = g.astype(jnp.float32), r.astype(jnp.float32)
            gaps["d" + name] = float(jnp.linalg.norm(g - r)
                                     / jnp.linalg.norm(r))
        print(json.dumps({"check": gaps, "tokens": args.check_tokens}),
              flush=True)

    if args.sweep:
        ops, w = operands(args.tokens)
        for tile in args.tiles:
            chunk, channels = (int(x) for x in tile.split("x"))
            scan = lambda *a: ss.ssm_scan(*a, chunk=chunk, channels=channels,
                                          interpret=interpret)
            fwd = jax.jit(lambda w, *a: scan(*a))  # graftlint: disable=G002 — one compile a tile is what the sweep times
            fb = jax.jit(jax.grad(weighed(scan), argnums=tuple(range(1, 7))))  # graftlint: disable=G002 — one compile a tile is what the sweep times
            row = {"chunk": chunk, "channels": channels,
                   "tokens": args.tokens}
            for name, fn in (("fwd_ms", fwd), ("fwd_bwd_ms", fb)):
                jax.block_until_ready(fn(w, *ops))
                times = []
                for _ in range(args.repeats):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(w, *ops))
                    times.append(1e3 * (time.perf_counter() - t0))
                row[name] = sorted(times)[len(times) // 2]
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
