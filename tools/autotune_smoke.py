#!/usr/bin/env python
"""CPU-fast autotune smoke (tier-1 CI guard, docs/autotune.md).

End-to-end in seconds, no accelerator and no real kernel timings: a
stubbed measurer with deterministic synthetic costs drives the real
search driver over the real declared search space, then the persistent
cache is verified the way production uses it:

1. the search finds the stub's optimum and the winner lands in the cache
   file (atomic write, correct key),
2. a SECOND PROCESS with the warm cache resolves the entry through
   ``autotune.lookup`` with ZERO search measurements (the acceptance bar:
   nobody pays the search twice),
3. ``graftlint`` is clean against the committed baseline — the autotune
   subsystem sits on trace-time hot paths and must stay free of
   host-sync/retrace hazards.

Prints a one-line JSON summary (optionally written to argv[1]); any
violation raises, failing the CI step.
"""
import json
import os
import subprocess
import sys
import tempfile

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
sys.path.insert(0, _REPO)

_SHAPE = {"M": 256, "N": 256, "K": 512}
_OPT = {"block_m": 256, "block_n": 128, "block_k": 256}

_CHILD = """
import json, sys
sys.path.insert(0, %r)
from mxnet_tpu import autotune
from mxnet_tpu.parallel.fused import fused_shape_key

val = autotune.lookup("fusion.blocks", fused_shape_key(**%r),
                      dtype="bfloat16")
stats = autotune.stats()
assert val == %r, "warm-cache lookup returned %%r" %% (val,)
assert stats["hits"] == 1, stats
assert stats["measurements"] == 0 and stats["searches"] == 0, (
    "a warm cache must never measure: %%s" %% stats)
print(json.dumps(stats))
""" % (_REPO, _SHAPE, _OPT)


def main(out_path=None):
    tmp = tempfile.mkdtemp(prefix="autotune_smoke_")
    cache_file = os.path.join(tmp, "tuning.json")
    os.environ["MXNET_TUNE_CACHE"] = cache_file
    os.environ["MXNET_TUNE_FINGERPRINT"] = "smoke-device"

    from mxnet_tpu import autotune
    from mxnet_tpu.autotune import SearchConfig, registry, search
    from mxnet_tpu.parallel.fused import fused_shape_key

    # stubbed measurer: a deterministic cost surface with its optimum at
    # _OPT — exercises pruning/refinement/counters without a device
    calls = []

    def measure(c):
        calls.append(dict(c))
        return 1e-3 + sum(abs(c[k] - _OPT[k]) for k in _OPT) * 1e-6

    tunable = registry.get("fusion.blocks")
    res = search.search(tunable, measure, ctx=dict(_SHAPE, dtype_bytes=2),
                        cfg=SearchConfig(trials=12))
    assert res.best == _OPT, "search missed the stub optimum: %r" % res.best
    assert res.measured == len(calls) > 0, (res.measured, len(calls))
    assert autotune.stats()["measurements"] == len(calls), autotune.stats()

    autotune.record("fusion.blocks", fused_shape_key(**_SHAPE), res.best,
                    dtype="bfloat16", ms=res.best_s * 1e3,
                    trials=res.measured)
    assert os.path.exists(cache_file), "cache file was not written"
    with open(cache_file) as f:
        payload = json.load(f)
    keys = list(payload["entries"])
    assert keys == ["smoke-device|fusion.blocks|M256,N256,K512"
                    "|bfloat16"], keys

    # second process, warm cache: hit, zero measurements
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    child = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                           capture_output=True, text=True, timeout=300)
    assert child.returncode == 0, (
        "warm-cache child failed:\n%s%s" % (child.stdout, child.stderr))
    child_stats = json.loads(child.stdout.strip().splitlines()[-1])

    # graftlint: the committed tree must be clean against the baseline
    rc = subprocess.call(
        [sys.executable, "-m", "tools.graftlint", "mxnet_tpu", "tools",
         "--disable", "G003:tools/",
         "--baseline", os.path.join("tools", "graftlint",
                                    "baseline.json")],
        cwd=_REPO)
    assert rc == 0, "graftlint found NEW violations (rc %d)" % rc

    summary = {
        "search_measurements": len(calls),
        "search_best": res.best,
        "cache_file": cache_file,
        "second_process_stats": child_stats,
        "graftlint": "clean",
    }
    print(json.dumps(summary))
    if out_path:
        with open(out_path, "w") as sink:
            json.dump(summary, sink, indent=1)
    print("[autotune_smoke] OK — search converged in %d measurements, "
          "warm second process measured 0" % len(calls), file=sys.stderr)
    return summary


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else None)
