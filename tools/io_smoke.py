#!/usr/bin/env python
"""Fast CPU smoke of the streaming input pipeline (tier-1 CI guard).

End-to-end in seconds, no accelerator:

1. **Exactness** — the async streaming pipeline (parallel decode,
   off-thread assembly, double-buffered device staging) must produce
   batch-for-batch IDENTICAL output (data, labels, pad) to the
   synchronous ``ImageIter`` path over the same record file, across
   epochs including the trailing short batch — unshuffled AND with a
   seeded per-epoch shuffle.
2. **Fit-loop exactness** — a small ``Module.fit`` fed by each backend
   lands on identical parameters with an identical XLA compile count
   (the streaming iterator must introduce zero extra programs).
3. **Clean shutdown** — after ``close()`` the process has zero leaked
   pipeline threads (feeder + decode pool + prefetchers all join).

Prints a one-line JSON summary (optionally written to argv[1]); any
violation raises, failing the CI step.
"""
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_rec(path, n=36, size=16, fmt=".jpg"):
    """Synthetic labeled record file — THE tools/ builder (also used by
    bench_input_pipeline.py). Labels
    are the distinct record ids, which the exactness assertions key on."""
    from mxnet_tpu import recordio

    rng = np.random.RandomState(0)
    rec, idx = path + ".rec", path + ".idx"
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    for i in range(n):
        img = rng.randint(0, 255, (size, size, 3)).astype(np.uint8)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i), i, 0), img, img_fmt=fmt,
            quality=90))
    w.close()
    return rec, idx


def collect(it, epochs=2):
    out = []
    for e in range(epochs):
        if e:
            it.reset()
        for b in it:
            out.append((b.data[0].asnumpy().copy(),  # graftlint: disable=G001 — smoke verifies batch CONTENTS on host
                        b.label[0].asnumpy().copy(), int(b.pad or 0)))  # graftlint: disable=G001 — same: host-side verification
    return out


def assert_same(ref, got, tag):
    assert len(ref) == len(got), \
        "%s: %d vs %d batches" % (tag, len(ref), len(got))
    for i, ((rd, rl, rp), (gd, gl, gp)) in enumerate(zip(ref, got)):
        assert rp == gp, "%s: batch %d pad %d vs %d" % (tag, i, rp, gp)
        np.testing.assert_array_equal(rd, gd,
                                      err_msg="%s: batch %d data" % (tag, i))
        np.testing.assert_array_equal(rl, gl,
                                      err_msg="%s: batch %d label" % (tag, i))


def small_fit(make_iter):
    import mxnet_tpu as mx
    from mxnet_tpu.observability import metrics as M

    np.random.seed(4)
    mx.random.seed(4)
    x = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Flatten(x), num_hidden=4, name="fc"),
        name="softmax")
    it = make_iter()
    mod = mx.mod.Module(net, context=mx.cpu())
    c0 = M.get_value("jit.compile_count", 0)
    try:
        mod.fit(it, num_epoch=2, optimizer="sgd",
                optimizer_params=(("learning_rate", 0.05),),
                initializer=mx.init.Uniform(0.2))
    finally:
        closer = getattr(it, "close", None)
        if closer:
            closer()
    compiles = M.get_value("jit.compile_count", 0) - c0
    return ({k: v.asnumpy().copy() for k, v in mod.get_params()[0].items()},
            compiles)


def main(out_path=None):
    import mxnet_tpu as mx
    from mxnet_tpu import observability as obs
    from mxnet_tpu.image import ImageIter
    from mxnet_tpu.runtime import StreamingIter

    obs.set_enabled(True)
    obs.reset_metrics()

    tmp = tempfile.mkdtemp(prefix="io_smoke_")
    rec, idx = build_rec(os.path.join(tmp, "data"))
    shape, bs = (3, 16, 16), 8
    baseline_threads = set(threading.enumerate())

    # 1a. unshuffled exactness (trailing partial batch included: 36 % 8)
    sync = ImageIter(batch_size=bs, data_shape=shape, path_imgrec=rec,
                     path_imgidx=idx)
    ref = collect(sync)
    sync.close()
    stream = StreamingIter(path_imgrec=rec, path_imgidx=idx,
                           data_shape=shape, batch_size=bs)
    got = collect(stream)
    stats = stream.get_stats()
    stream.close()
    assert_same(ref, got, "unshuffled")
    assert any(p for _, _, p in got), "expected a padded trailing batch"

    # 1b. seeded-shuffle exactness (same RNG stream drives both orders)
    sync = ImageIter(batch_size=bs, data_shape=shape, path_imgrec=rec,
                     path_imgidx=idx, shuffle=True, seed=3)
    ref_s = collect(sync)
    sync.close()
    stream = StreamingIter(path_imgrec=rec, path_imgidx=idx,
                           data_shape=shape, batch_size=bs, shuffle=True,
                           seed=3)
    got_s = collect(stream)
    stream.close()
    assert_same(ref_s, got_s, "shuffled")
    assert ref_s[0][1].tolist() != ref[0][1].tolist(), \
        "shuffle produced the unshuffled order"

    # 2. fit-loop exactness + flat compile count across backends: the
    # FIRST fit pays the model's compiles whatever feeds it, so warm
    # once, then compare the steady-state per-fit compile delta —
    # streaming must add ZERO programs over the synchronous baseline
    small_fit(lambda: mx.io.ImageRecordIter(rec, shape, bs,
                                            path_imgidx=idx,
                                            streaming=False))
    params_sync, compiles_sync = small_fit(
        lambda: mx.io.ImageRecordIter(rec, shape, bs, path_imgidx=idx,
                                      streaming=False))
    params_stream, compiles_stream = small_fit(
        lambda: mx.io.ImageRecordIter(rec, shape, bs, path_imgidx=idx,
                                      streaming=True))
    for k in params_sync:
        np.testing.assert_array_equal(
            params_sync[k], params_stream[k],
            err_msg="fit diverged on %s" % k)
    assert compiles_stream == compiles_sync, \
        "streaming fit changed the compile count: %d vs %d" % (
            compiles_stream, compiles_sync)

    # 3. clean shutdown: zero leaked threads once iterators close
    time.sleep(0.5)
    leaked = [t.name for t in threading.enumerate()
              if t not in baseline_threads and t.is_alive()]
    assert not leaked, "leaked threads after close(): %s" % leaked

    summary = {
        "batches": len(got),
        "padded_batches": sum(1 for _, _, p in got if p),
        "fit_compiles": compiles_stream,
        "pipeline_verdict": stats["verdict"],
        "host_stall_pct": stats["host_stall_pct"],
        "decode_workers": stats["decode_workers"],
        "leaked_threads": leaked,
        "ok": True,
    }
    if out_path:
        with open(out_path, "w") as sink:
            json.dump(summary, sink, indent=1)
    print("[io_smoke] OK — %d batches exact (sync == streaming, "
          "shuffled + unshuffled), fit params identical at %d compiles, "
          "0 leaked threads" % (len(got), compiles_stream),
          file=sys.stderr)
    print(json.dumps(summary))
    return summary


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else None)
