#!/usr/bin/env python3
"""Faults planted in the program of a cell, each of which the comparison
that decides ``correct`` has to catch:

    chiprun -- python tools/plant_faults.py --fault <name> --seed <n>

runs the fault's cell as ``perfbench/run.py`` does (a 2 s window) with one
mechanism broken underneath and prints the result line; ``correct`` must
read false. ``--rehearse`` walks the tiny sizes on the CPU. The same
faults are tier-1 tests at small sizes (tests/test_sambay_layers.py,
tests/test_lfm2_layers.py).

Of ``phi4flash_train_t8192_b1``:

- ``causal_mask_in_the_windowed_layers``: the windowed differential layers
  see the whole prefix;
- ``lambda_zero``: the second softmax map of every differential layer
  counts for nothing;
- ``gmu_reads_its_own_input``: a gated memory unit gates its own layer's
  input (repeated to the memory's width) in place of the memory;
- ``cross_layer_makes_its_own_kv``: a cross layer attends k and v cut from
  its own input in place of the k and v it was handed;
- ``scan_state_in_bf16``: the selective scan's state is rounded to
  bfloat16 after every step.

Of ``lfm2_train_t8192_b2``:

- ``conv_taps_reversed``: the short convolution looks ahead where it
  should look back (the sequence run through it backwards);
- ``conv_gates_b_and_c_swapped``: the in-projection's first two thirds
  trade places, ``y = B * conv(C * x~)``;
- ``no_qk_norm``: q and k reach the attention unnormed;
- ``expert_bias_ignored``: the router selects by its scores alone.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FAULTS = ("causal_mask_in_the_windowed_layers", "lambda_zero",
          "gmu_reads_its_own_input", "cross_layer_makes_its_own_kv",
          "scan_state_in_bf16")
LFM2_FAULTS = ("conv_taps_reversed", "conv_gates_b_and_c_swapped",
               "no_qk_norm", "expert_bias_ignored")
#: the cell each fault is planted in by default
CELL_OF = dict([(f, "phi4flash_train_t8192_b1") for f in FAULTS]
               + [(f, "lfm2_train_t8192_b2") for f in LFM2_FAULTS])


def scan_with_a_bf16_state(u, delta, A, Bm, Cm, D, chunk=256):
    """The recurrence of ``ssm_scan`` with the state held in bfloat16."""
    import jax
    import jax.numpy as jnp

    Bsz, T, E = u.shape
    chunk = min(chunk, T)
    f32 = jnp.float32

    def by_chunk(x):
        return x.astype(f32).reshape(Bsz, T // chunk, chunk, -1).transpose(
            1, 2, 0, 3)

    def step(h, xs):
        u_t, dt, b_t, c_t = xs
        h = (jnp.exp(dt[..., None] * A) * h.astype(f32)
             + (dt * u_t)[..., None] * b_t[:, None, :]).astype(jnp.bfloat16)
        return h, jnp.sum(h.astype(f32) * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(
        jax.checkpoint(lambda h, xs: jax.lax.scan(step, h, xs)),
        jnp.zeros((Bsz, E, A.shape[1]), jnp.bfloat16),
        tuple(by_chunk(x) for x in (u, delta, Bm, Cm)))
    y = y.reshape(T, Bsz, E).transpose(1, 0, 2)
    return (y + D.astype(f32) * u.astype(f32)).astype(u.dtype)


def plant(model, fault):
    """Break one mechanism of ``model`` (a ``TransformerParallel`` of the
    ``phi4flash`` or the ``lfm2_moe`` family); returns the function that
    mends it again."""
    import jax.numpy as jnp

    from mxnet_tpu.parallel import lm_layers, moe, short_conv, ssm_scan

    undo = []

    def patch(owner, name, value):
        old = getattr(owner, name)
        setattr(owner, name, value)
        undo.append(lambda: setattr(owner, name, old))

    if fault == "causal_mask_in_the_windowed_layers":
        for layer in model.arch["diff"]["layers"].values():
            old = layer["window"]
            layer["window"] = None
            undo.append(lambda layer=layer, old=old: layer.update(window=old))
    elif fault == "lambda_zero":
        attend, calls = model._attend, []

        def first_map_only(q, k, v, scale, window=None):
            calls.append(None)
            out = attend(q, k, v, scale, window)
            return out if len(calls) % 2 else jnp.zeros_like(out)

        patch(model, "_attend", first_map_only)
    elif fault == "gmu_reads_its_own_input":
        sound = lm_layers.gated_memory
        patch(lm_layers, "gated_memory",
              lambda params, li, x, memory, arch: sound(
                  params, li, x, jnp.tile(
                      x, memory.shape[-1] // x.shape[-1]), arch))
    elif fault == "cross_layer_makes_its_own_kv":
        sound = lm_layers.diff_attention

        def own_kv(params, li, x, arch, attend, kv=None):
            if kv is not None:
                (k0, _), v = kv
                B, Hkv, T, hd = k0.shape
                cut = x.reshape(B, T, Hkv, -1).transpose(0, 2, 1, 3)
                kv = ((cut[..., :hd], cut[..., hd:2 * hd]),
                      cut[..., 2 * hd:2 * hd + v.shape[-1]])
            return sound(params, li, x, arch, attend, kv)

        patch(lm_layers, "diff_attention", own_kv)
    elif fault == "scan_state_in_bf16":
        patch(ssm_scan, "ssm_scan", scan_with_a_bf16_state)
    elif fault == "conv_taps_reversed":
        sound = short_conv.short_conv
        patch(short_conv, "short_conv",
              lambda bcx, w: sound(bcx[:, ::-1], w)[:, ::-1])
    elif fault == "conv_gates_b_and_c_swapped":
        sound = short_conv.short_conv

        def swapped(bcx, w):
            d = bcx.shape[-1] // 3
            return sound(jnp.concatenate(
                [bcx[..., d:2 * d], bcx[..., :d], bcx[..., 2 * d:]], -1), w)

        patch(short_conv, "short_conv", swapped)
    elif fault == "no_qk_norm":
        patch(lm_layers, "qk_norm", lambda params, li, q, k, arch: (q, k))
    elif fault == "expert_bias_ignored":
        sound = moe.select
        patch(moe, "select",
              lambda logits, bias, top_k: sound(logits, None, top_k))
    else:
        raise SystemExit("no fault %r (%s)" % (fault, ", ".join(CELL_OF)))
    return lambda: [mend() for mend in reversed(undo)]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", help="the fault's own cell by default")
    ap.add_argument("--fault", required=True, choices=sorted(CELL_OF))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace = 0
    args.workload = args.workload or CELL_OF[args.fault]

    from perfbench import run

    result = run.run_cell(args, rehearse=args.rehearse,
                          sabotage=lambda cell: plant(cell.model, args.fault))
    result["fault"] = args.fault
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
