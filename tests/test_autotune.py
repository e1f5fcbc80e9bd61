"""Autotuner tests (ISSUE 6): persistent tuning cache semantics, search
driver behavior, and the three consulting call sites (fused-kernel
blocks, executor remat, serving bucket ladder).

The acceptance-critical properties regression-tested here:

* round-trip persistence + atomic merge-on-write under concurrent tuners,
* stale-entry invalidation when the device fingerprint changes,
* the cache-HIT path never triggers a measurement (in-process and in a
  second process with a warm cache — the measurement counter is the
  witness),
* consulting call sites fall back to config defaults on a miss and stay
  numerically correct with tuned entries.
"""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autotune
from mxnet_tpu import config as mxconfig
from mxnet_tpu.autotune import SearchConfig, cache, cost_model, registry
from mxnet_tpu.autotune import search as tsearch

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tune_env(tmp_path, monkeypatch):
    """Hermetic cache file + pinned fingerprint; clean counters."""
    monkeypatch.setenv("MXNET_TUNE_CACHE", str(tmp_path / "tuning.json"))
    monkeypatch.setenv("MXNET_TUNE_FINGERPRINT", "fp-A")
    cache.reset()
    cache.reset_stats()
    yield tmp_path
    cache.reset()
    cache.reset_stats()


# --------------------------------------------------------------- cache
def test_round_trip_persistence(tune_env):
    key = ("M512", "N64", "K64")
    autotune.record("fusion.blocks", key,
                    {"block_m": 256, "block_n": 512},
                    dtype="bfloat16", ms=1.25, trials=5)
    # fresh-process simulation: drop every in-memory structure
    cache.reset()
    assert autotune.lookup("fusion.blocks", key,
                           dtype="bfloat16") == {"block_m": 256,
                                                 "block_n": 512}
    entry = autotune.lookup_entry("fusion.blocks", key,
                                  dtype="bfloat16")
    assert entry["fingerprint"] == "fp-A"
    assert entry["ms"] == 1.25 and entry["trials"] == 5
    with open(os.environ["MXNET_TUNE_CACHE"]) as f:
        payload = json.load(f)
    assert payload["version"] == 1
    assert list(payload["entries"]) == [
        "fp-A|fusion.blocks|M512,N64,K64|bfloat16"]


def test_dtype_and_key_separate_entries(tune_env):
    autotune.record("op", "k", {"v": 1}, dtype="bfloat16")
    autotune.record("op", "k", {"v": 2}, dtype="float32")
    autotune.record("op", "k2", {"v": 3}, dtype="bfloat16")
    assert autotune.lookup("op", "k", dtype="bfloat16") == {"v": 1}
    assert autotune.lookup("op", "k", dtype="float32") == {"v": 2}
    assert autotune.lookup("op", "k2", dtype="bfloat16") == {"v": 3}


def test_concurrent_tuners_atomic_merge(tune_env):
    """N threads record+persist concurrently; every entry lands and the
    file is never torn (parses as JSON at the end)."""
    n = 12
    errs = []

    def tuner(i):
        try:
            autotune.record("op%d" % i, ("k", i), {"winner": i})
        except Exception as err:  # pragma: no cover
            errs.append(err)

    threads = [threading.Thread(target=tuner, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errs
    with open(os.environ["MXNET_TUNE_CACHE"]) as f:
        payload = json.load(f)
    assert len(payload["entries"]) == n
    cache.reset()
    for i in range(n):
        assert autotune.lookup("op%d" % i, ("k", i)) == {"winner": i}


def test_cross_process_merge_on_write(tune_env):
    """A second tuner process writing the same file does not lose this
    process's entries (merge-on-write), and vice versa."""
    autotune.record("op.mine", "k", {"v": "mine"})
    child = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r)\n"
         "from mxnet_tpu import autotune\n"
         "autotune.record('op.theirs', 'k', {'v': 'theirs'})\n" % _REPO],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert child.returncode == 0, child.stderr
    # our in-memory copy predates the child's write; a re-record must
    # merge, not clobber
    autotune.record("op.mine2", "k", {"v": "mine2"})
    cache.reset()
    for op, v in (("op.mine", "mine"), ("op.theirs", "theirs"),
                  ("op.mine2", "mine2")):
        assert autotune.lookup(op, "k") == {"v": v}, op


def test_stale_fingerprint_invalidation(tune_env, monkeypatch):
    key = ("M512", "N64", "K64")
    autotune.record("fusion.blocks", key, {"block_m": 256},
                    dtype="bfloat16")
    # same cache file, different chip: the entry must never match
    monkeypatch.setenv("MXNET_TUNE_FINGERPRINT", "fp-B")
    cache.reset()
    assert autotune.lookup("fusion.blocks", key,
                           dtype="bfloat16") is None
    assert autotune.scrub_stale() == 1
    with open(os.environ["MXNET_TUNE_CACHE"]) as f:
        assert json.load(f)["entries"] == {}
    # back on fp-A: entry is gone from disk too
    monkeypatch.setenv("MXNET_TUNE_FINGERPRINT", "fp-A")
    cache.reset()
    assert autotune.lookup("fusion.blocks", key,
                           dtype="bfloat16") is None


def test_bypass_mode_skips_lookup(tune_env):
    autotune.record("op", "k", {"v": 1})
    mxconfig.set_flag("MXNET_TUNE", -1)
    try:
        assert autotune.lookup("op", "k") is None
        assert autotune.lookup_or_tune("op", "k") is None
    finally:
        mxconfig.set_flag("MXNET_TUNE", None)
    assert autotune.lookup("op", "k") == {"v": 1}


# -------------------------------------------------------------- search
def test_search_measures_default_first_and_finds_optimum(tune_env):
    t = registry.declare(
        "test.knob", space={"a": (1, 2, 3, 4), "b": (10, 20)},
        default=lambda ctx: {"a": 4, "b": 20})
    log = []

    def measure(c):
        log.append(dict(c))
        return 1e-3 + abs(c["a"] - 2) * 1e-4 + abs(c["b"] - 10) * 1e-5

    res = tsearch.search(t, measure, cfg=SearchConfig(trials=16))
    assert log[0] == {"a": 4, "b": 20}, "incumbent default measured first"
    assert res.best == {"a": 2, "b": 10}
    assert res.measured == len(log) <= 16
    assert cache.stats()["measurements"] == len(log)
    assert cache.stats()["searches"] == 1


def test_search_budget_and_dedup(tune_env):
    t = registry.declare("test.knob2", space={"a": tuple(range(32))})
    calls = []
    res = tsearch.search(t, lambda c: calls.append(dict(c)) or 1.0,
                         cfg=SearchConfig(trials=5))
    assert res.measured == 5 and len(calls) == 5
    assert len({tuple(sorted(c.items())) for c in calls}) == 5


def test_cache_hit_never_triggers_measurement(tune_env):
    """The acceptance bar: once an entry exists, neither lookup nor
    lookup_or_tune (even with MXNET_TUNE=1) may run a measurement."""
    t = registry.declare("test.knob3", space={"a": (1, 2)})
    res = tsearch.search(t, lambda c: 1.0, cfg=SearchConfig(trials=2))
    autotune.record("test.knob3", "shape", res.best)
    assert cache.stats()["measurements"] > 0
    cache.reset_stats()
    mxconfig.set_flag("MXNET_TUNE", 1)
    try:
        for _ in range(3):
            assert autotune.lookup("test.knob3", "shape") == res.best
            assert autotune.lookup_or_tune("test.knob3",
                                           "shape") == res.best
    finally:
        mxconfig.set_flag("MXNET_TUNE", None)
    stats = cache.stats()
    assert stats["measurements"] == 0 and stats["searches"] == 0
    assert stats["hits"] == 6


def test_second_process_zero_measurements(tune_env):
    """A fresh process with a warm cache resolves the fused kernel's
    blocks through the real call site (``resolve_blocks``) with ZERO
    measurements, even under MXNET_TUNE=1 (the compile/measure-counter
    regression)."""
    from mxnet_tpu.parallel.fused import fused_shape_key

    tuned = {"block_m": 64, "block_n": 64, "block_k": 128}
    autotune.record("fusion.blocks", fused_shape_key(128, 128, 256), tuned,
                    dtype="float32")
    child_src = (
        "import sys; sys.path.insert(0, %r)\n"
        "from mxnet_tpu import autotune\n"
        "from mxnet_tpu.parallel.fused import resolve_blocks\n"
        "assert resolve_blocks(128, 128, 256) == (64, 64, 128)\n"
        "s = autotune.stats()\n"
        "assert s['measurements'] == 0 and s['searches'] == 0, s\n"
        "assert s['hits'] == 1, s\n"
        "print('OK', s)\n" % _REPO)
    child = subprocess.run(
        [sys.executable, "-c", child_src],
        env=dict(os.environ, JAX_PLATFORMS="cpu", MXNET_TUNE="1"),
        capture_output=True, text=True, timeout=600)
    assert child.returncode == 0, child.stdout + child.stderr
    assert "OK" in child.stdout


def test_lookup_or_tune_never_searches_inside_trace(tune_env):
    """A miss during someone else's jit trace must not measure, even
    with MXNET_TUNE=1."""
    import jax

    mxconfig.set_flag("MXNET_TUNE", 1)
    try:
        registry.declare("test.traced", space={"a": (1,)})
        seen = []

        def f(x):
            seen.append(autotune.lookup_or_tune("test.traced", "k"))
            return x * 2

        jax.jit(f)(np.float32(1.0))
        assert seen == [None]
        assert cache.stats()["measurements"] == 0
        assert cache.stats()["searches"] == 0
    finally:
        mxconfig.set_flag("MXNET_TUNE", None)


# ---------------------------------------------------------- cost model
def test_expected_padding_math():
    # ladder (1,2,4): sizes 1->1, 2->2, 3->4, 4->4 : alloc 11 / real 10
    assert cost_model.expected_padding((1, 2, 4), [1, 2, 3, 4]) == \
        pytest.approx(0.1)
    # oversize chunks at the top bucket first: 10 -> 4+4+2
    assert cost_model.expected_padding((1, 2, 4), [10]) == 0.0
    assert cost_model.expected_padding((4,), [1]) == 3.0


# ------------------------------------------------- consulting call sites
def test_graph_tuning_key_stable_and_shape_free():
    from mxnet_tpu.executor import _GraphProgram

    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=8, name="fc"),
        name="softmax")
    net2 = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                              name="fc"), name="softmax")
    other = mx.sym.SoftmaxOutput(
        mx.sym.Activation(mx.sym.FullyConnected(
            mx.sym.Variable("data"), num_hidden=8, name="fc"),
            act_type="relu"), name="softmax")
    # same topology, different width: must NOT collide (a remat/ladder
    # decision measured on the small model would mis-steer the big one)
    wider = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=1024,
                              name="fc"), name="softmax")
    assert _GraphProgram(net).tuning_key() == \
        _GraphProgram(net2).tuning_key()
    assert _GraphProgram(net).tuning_key() != \
        _GraphProgram(other).tuning_key()
    assert _GraphProgram(net).tuning_key() != \
        _GraphProgram(wider).tuning_key()


def test_executor_consults_tuned_remat(tune_env):
    from mxnet_tpu.executor import _GraphProgram

    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=8, name="fc"),
        name="softmax")
    prog = _GraphProgram(net)
    assert prog.remat_mirror() is False  # config default
    autotune.record("exec.remat", prog.tuning_key(), {"mirror": 1})
    assert prog.remat_mirror() is True
    # the tuned remat program still trains: one fused fwd+bwd step
    ex = net.simple_bind(mx.cpu(), data=(4, 6), grad_req="write")
    rng = np.random.RandomState(0)
    ex.arg_dict["data"][:] = rng.rand(4, 6).astype(np.float32)
    ex.arg_dict["fc_weight"][:] = rng.rand(8, 6).astype(np.float32) * 0.1
    ex.forward(is_train=True)
    ex.backward()
    g = ex.grad_dict["fc_weight"].asnumpy()
    assert np.isfinite(g).all() and np.abs(g).sum() > 0


def test_serving_consults_tuned_ladder(tune_env):
    from mxnet_tpu.autotune.tuners import model_key
    from mxnet_tpu.serving import InferenceServer, ServingConfig

    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=8, name="fc"),
        name="softmax")
    rng = np.random.RandomState(0)
    arg_params = {"fc_weight": mx.nd.array(
        rng.randn(8, 4).astype(np.float32)),
        "fc_bias": mx.nd.zeros((8,))}
    mkey = model_key(net)
    autotune.record("serving.buckets", (mkey, "default"),
                    {"buckets": [1, 4, 16]})
    autotune.record("serving.buckets", (mkey, "batchy"),
                    {"buckets": [8, 64]})
    srv = InferenceServer(net, arg_params,
                          data_shapes=[("data", (1, 4))], start=False)
    assert srv._cfg.buckets == (1, 4, 16)
    srv2 = InferenceServer(net, arg_params,
                           data_shapes=[("data", (1, 4))], start=False,
                           traffic_key="batchy")
    assert srv2._cfg.buckets == (8, 64)
    # explicit config always wins over the cache
    srv3 = InferenceServer(net, arg_params,
                           data_shapes=[("data", (1, 4))], start=False,
                           config=ServingConfig(buckets=(1, 2)))
    assert srv3._cfg.buckets == (1, 2)
    # and a tuned server still answers correctly
    srv.start()
    try:
        x = rng.rand(3, 4).astype(np.float32)
        out = srv.predict(x, timeout=120)
        w = arg_params["fc_weight"].asnumpy()
        logits = x @ w.T
        e = np.exp(logits - logits.max(axis=1, keepdims=True))
        np.testing.assert_allclose(out, e / e.sum(axis=1, keepdims=True),
                                   atol=1e-4)
    finally:
        srv.stop()


def test_tune_serving_buckets_stub_measurer(tune_env):
    from mxnet_tpu.autotune.tuners import model_key, tune_serving_buckets
    from mxnet_tpu.serving import InferenceServer
    from mxnet_tpu.serving.buckets import traffic_signature

    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=8, name="fc"),
        name="softmax")
    arg_params = {"fc_weight": mx.nd.zeros((8, 4)),
                  "fc_bias": mx.nd.zeros((8,))}
    sizes = [1, 1, 2, 3, 8]

    def measure(c):  # favor short ladders topping out at 8
        ladder = c["buckets"]
        return 1e-3 * len(ladder) + (0.1 if max(ladder) != 8 else 0.0)

    ladder = tune_serving_buckets(net, arg_params,
                                  [("data", (1, 4))], sizes,
                                  measure=measure, trials=8)
    assert max(ladder) == 8
    mkey = model_key(net)
    assert autotune.lookup("serving.buckets", (mkey, "default")) == \
        {"buckets": ladder}
    assert autotune.lookup(
        "serving.buckets", (mkey, traffic_signature(sizes))) == \
        {"buckets": ladder}
    srv = InferenceServer(net, arg_params,
                          data_shapes=[("data", (1, 4))], start=False)
    assert list(srv._cfg.buckets) == ladder


def test_ladder_candidates_and_signature():
    from mxnet_tpu.serving.buckets import (ladder_candidates,
                                           traffic_signature)

    cands = ladder_candidates(sizes=[1, 1, 2, 3, 8, 20])
    assert all(max(c) == 32 for c in cands)
    assert (1, 2, 4, 8, 16, 32) in cands
    assert (32,) in cands
    assert traffic_signature([1, 1, 2, 3, 8, 20]) == "p50x2-p95x8-maxx32"
    assert traffic_signature([]) == "empty"


def test_corrupt_cache_entries_degrade_to_defaults(tune_env):
    """A hand-edited/corrupt cache entry must degrade to the config
    defaults at every consulting call site, never crash."""
    from mxnet_tpu.autotune.tuners import model_key
    from mxnet_tpu.parallel.fused import fused_shape_key, resolve_blocks
    from mxnet_tpu.serving import InferenceServer
    from mxnet_tpu.serving.buckets import DEFAULT_BUCKETS

    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=8, name="fc"),
        name="softmax")
    autotune.record("serving.buckets", (model_key(net), "default"),
                    {"buckets": []})
    srv = InferenceServer(net, {"fc_weight": mx.nd.zeros((8, 4)),
                                "fc_bias": mx.nd.zeros((8,))},
                          data_shapes=[("data", (1, 4))], start=False)
    assert srv._cfg.buckets == DEFAULT_BUCKETS

    key = fused_shape_key(128, 128, 256)
    flags = tuple(mxconfig.get_flag("MXNET_FUSION_BLOCK_" + d)
                  for d in "MNK")
    autotune.record("fusion.blocks", key,
                    {"block_m": "garbage", "block_n": -5, "block_k": None},
                    dtype="float32")
    assert resolve_blocks(128, 128, 256) == flags
    # a sound field beside corrupt ones is still honoured
    autotune.record("fusion.blocks", key,
                    {"block_m": 64, "block_n": "x"}, dtype="float32")
    assert resolve_blocks(128, 128, 256) == (64,) + flags[1:]

    # NON-DICT values (a hand-edited "value": [...]) must degrade too
    for value in ([128, 256], "64"):
        autotune.record("fusion.blocks", key, value, dtype="float32")
        assert resolve_blocks(128, 128, 256) == flags
    autotune.record("serving.buckets", (model_key(net), "default"),
                    [1, 2, 4])
    srv2 = InferenceServer(net, {"fc_weight": mx.nd.zeros((8, 4)),
                                 "fc_bias": mx.nd.zeros((8,))},
                           data_shapes=[("data", (1, 4))], start=False)
    assert srv2._cfg.buckets == DEFAULT_BUCKETS


def test_non_dict_entry_body_reads_as_miss(tune_env):
    """A hand-edited entry BODY (not just the value field) must read as
    a miss at load time — lookup/scrub/save never crash on it."""
    autotune.record("op.good", "k", {"v": 1})
    path = os.environ["MXNET_TUNE_CACHE"]
    with open(path) as f:
        payload = json.load(f)
    payload["entries"]["fp-A|op.bad|k|-"] = "oops"
    with open(path, "w") as f:
        json.dump(payload, f)
    cache.reset()
    assert autotune.lookup("op.bad", "k") is None
    assert autotune.lookup("op.good", "k") == {"v": 1}
    assert autotune.scrub_stale() == 0  # must not crash on the string
    cache.save()
    cache.reset()
    assert "fp-A|op.bad|k|-" not in cache.entries()


def test_scrub_preserves_other_process_entries(tune_env, monkeypatch):
    """scrub_stale's write merges the on-disk state first: entries a
    second process saved since we loaded survive the scrub."""
    autotune.record("op.mine", "k", {"v": 1})  # loads + persists
    # another process lands fresh fp-A work plus a stale fp-B entry
    path = os.environ["MXNET_TUNE_CACHE"]
    with open(path) as f:
        payload = json.load(f)
    payload["entries"]["fp-A|op.theirs|k|-"] = {
        "value": {"v": 2}, "fingerprint": "fp-A"}
    payload["entries"]["fp-B|op.old|k|-"] = {
        "value": {"v": 3}, "fingerprint": "fp-B"}
    with open(path, "w") as f:
        json.dump(payload, f)
    # our in-memory view predates that write; scrub must still keep it
    assert autotune.scrub_stale() == 1
    cache.reset()
    assert autotune.lookup("op.mine", "k") == {"v": 1}
    assert autotune.lookup("op.theirs", "k") == {"v": 2}
    with open(path) as f:
        assert "fp-B|op.old|k|-" not in json.load(f)["entries"]


def test_all_tunables_registered_at_package_import(tune_env):
    """Every declared knob — including graph.layout, which has no
    in-package call site — must be visible in a FRESH process without
    touching the lazily-loaded tuners module."""
    child = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r)\n"
         "from mxnet_tpu.autotune import registry, tunable_names\n"
         "names = tunable_names()\n"
         "for n in ('exec.remat', 'serving.buckets', 'graph.layout'):\n"
         "    assert n in names, (n, names)\n"
         "    registry.get(n)\n"
         "print('OK')\n" % _REPO],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert child.returncode == 0, child.stdout + child.stderr


def test_tune_layout_generic(tune_env):
    from mxnet_tpu.autotune.tuners import tune_layout

    times = {"NHWC": 2e-3, "NCHW": 1e-3}
    winner = tune_layout(lambda c: times[c["layout"]],
                         key=("toy", "b4"), default="NHWC")
    assert winner == "NCHW"
    assert autotune.lookup("graph.layout", ("toy", "b4")) == \
        {"layout": "NCHW"}


def test_tune_remat_generic(tune_env):
    from mxnet_tpu.autotune.tuners import tune_remat

    winner = tune_remat(lambda c: 1e-3 if c["mirror"] else 2e-3, "g-key")
    assert winner == 1
    assert autotune.lookup("exec.remat", "g-key") == {"mirror": 1}
