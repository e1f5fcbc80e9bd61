"""What happens before the first timed step: the ``jit.trace`` /
``jit.lower`` / ``jit.compile`` ring spans JAX's own events give, what the
persistent cache did inside a compile, the ``python.gc`` spans, the
package's ``mxnet_tpu.import`` span, and the start-up readers of
``perfbench/layer_metrics/`` over a hand-filled ring and over a rehearsal
of a cell."""
import gc
import glob
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

import mxnet_tpu as mx
from mxnet_tpu import observability as obs
from mxnet_tpu.observability import instruments

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tools")):
    if path not in sys.path:
        sys.path.insert(0, path)
import trace_report  # noqa: E402
from perfbench import run as harness  # noqa: E402


@pytest.fixture
def telemetry():
    obs.set_enabled(True)
    obs.reset_metrics()
    yield
    obs.reset_metrics()
    obs.set_enabled(False)


def ring_since(mark, *names):
    return [ev for ev in mx.profiler.events_tail(4096, compiles=True)
            if ev["ts"] >= mark and ev["name"] in names]


def by_fun(events, fun):
    return [ev for ev in events if ev["args"]["fun"] == fun]


# ------------------------------------------------- the compile spans
def test_fresh_jit_leaves_trace_lower_and_compile_spans(telemetry):
    def startup_probe(x):
        return jnp.sin(x) * 2.0

    x = jnp.ones((8,))
    mark = mx.profiler._now_us()
    with obs.trace_span("probe.step", "parallel", step=7):
        with obs.trace_span("probe.enqueue", "parallel"):
            jax.jit(startup_probe)(x).block_until_ready()
    events = ring_since(mark, "jit.trace", "jit.lower", "jit.compile",
                        "probe.enqueue")
    (enqueue,) = [ev for ev in events if ev["name"] == "probe.enqueue"]
    lo, hi = enqueue["ts"], enqueue["ts"] + enqueue["dur"]
    for name, fun in (("jit.trace", "startup_probe"),
                      ("jit.lower", "jit(startup_probe)"),
                      ("jit.compile", "jit(startup_probe)")):
        (ev,) = by_fun([e for e in events if e["name"] == name], fun)
        assert ev["cat"] == "jit"
        assert ev["args"]["parent"] == "probe.enqueue"
        assert ev["args"]["step"] == 7
        assert lo <= ev["ts"] and ev["ts"] + ev["dur"] <= hi + 1.0
        assert ev["dur"] > 0
    (compile_,) = by_fun([e for e in events if e["name"] == "jit.compile"],
                         "jit(startup_probe)")
    assert compile_["args"]["cache"] == "off"  # the tests place no cache


@pytest.fixture
def cache_dir(tmp_path):
    """A persistent compile cache of this test's own."""
    before = jax.config.jax_compilation_cache_dir
    threshold = jax.config.jax_persistent_cache_min_compile_time_secs
    compilation_cache.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", threshold)
    compilation_cache.reset_cache()


def test_compile_span_says_written_then_hit(telemetry, cache_dir):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    def cached_probe(x):
        return x * 3.0 + 1.0

    x = jnp.arange(16.0)
    mark = mx.profiler._now_us()
    jax.jit(cached_probe)(x).block_until_ready()
    jax.clear_caches()
    jax.jit(cached_probe)(x).block_until_ready()
    spans = by_fun(ring_since(mark, "jit.compile"), "jit(cached_probe)")
    assert [ev["args"]["cache"] for ev in spans] == ["written", "hit"]
    assert obs.metrics.get_value("jit.persistent_cache_misses") >= 1
    assert obs.metrics.get_value("jit.persistent_cache_hits") >= 1


def test_compile_under_the_threshold_says_compiled(telemetry, cache_dir):
    def small_probe(x):
        return x - 5.0

    mark = mx.profiler._now_us()
    jax.jit(small_probe)(jnp.arange(4.0)).block_until_ready()
    (span,) = by_fun(ring_since(mark, "jit.compile"), "jit(small_probe)")
    assert span["args"]["cache"] == "compiled"
    assert not os.listdir(cache_dir)  # looked up, compiled, not stored


def test_nothing_is_recorded_with_telemetry_off():
    assert not obs.enabled() and not mx.profiler.spans_active()

    def quiet_probe(x):
        return x + 9.0

    mark = mx.profiler._now_us()
    jax.jit(quiet_probe)(jnp.arange(4.0)).block_until_ready()
    gc.collect()
    assert ring_since(mark, "jit.trace", "jit.lower", "jit.compile",
                      "python.gc") == []


# --------------------------------------------- garbage collection
def test_full_collection_leaves_a_python_gc_span(telemetry):
    mark = mx.profiler._now_us()
    gc.collect()
    spans = ring_since(mark, "python.gc")
    assert [ev["args"]["generation"] for ev in spans] == [2]
    assert spans[0]["cat"] == "host" and "collected" in spans[0]["args"]
    assert obs.metrics.get_value("gc.pause.ms",
                                 labels={"generation": 2}) == 1


def test_python_gc_lies_in_the_xplane_host_plane(telemetry, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    (found,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)
    (span,) = [ev for ev in trace_report.load_xplane(found)["host"]
               if ev["name"] == "python.gc"]
    assert span["args"]["cat"] == "host"
    assert span["args"]["generation"] == 2


CHILD = """
import gc, json
import mxnet_tpu as mx
from mxnet_tpu import observability as obs
obs.set_enabled(True)
names = [ev["name"] for ev in mx.profiler.events_tail(64)]
with mx.profiler._lock:       # a collection where the ring's lock is held
    gc.collect()
with obs.metrics._lock:       # ... and the registry's
    gc.collect()
gc.collect()                  # both free: all three are recorded now
full = [ev for ev in mx.profiler.events_tail(4096)
        if ev["name"] == "python.gc" and ev["args"]["generation"] == 2]
print(json.dumps({"first": names[:1], "full": len(full),
                  "ordered": all(a["ts"] < b["ts"]
                                 for a, b in zip(full, full[1:]))}))
"""


@pytest.fixture(scope="module")
def fresh_process():
    """A process that imports the package with telemetry off, turns it
    on, and collects while each lock is held (a hook that waited for the
    lock would hang it: the time limit fails the test instead)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_TELEMETRY="0")
    out = subprocess.run([sys.executable, "-c", CHILD], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_import_span_is_in_the_ring_after_set_enabled(fresh_process):
    assert fresh_process["first"] == ["mxnet_tpu.import"]


def test_collection_under_a_held_lock_is_recorded_later(fresh_process):
    assert fresh_process["full"] == 3 and fresh_process["ordered"]


def test_gc_hook_returns_at_once_with_telemetry_off():
    assert not obs.enabled()
    instruments.install_jax_hooks()  # idempotent; telemetry stays off
    assert instruments._on_gc in gc.callbacks
    instruments._on_gc("start", {"generation": 2})
    assert instruments._gc_open is None


# ------------------------------------------- the readers, on a hand ring
@pytest.fixture
def clean_ring(tmp_path):
    mx.profiler.set_config(filename=str(tmp_path / "ring.json"))
    mx.profiler.dump_profile()  # empties the ring
    yield
    mx.profiler.dump_profile()
    mx.profiler.set_config(filename="profile.json")


def test_events_tail_passes_over_compile_spans(clean_ring):
    for i in range(3):
        mx.profiler.record("own.step", "parallel", 10.0 * i, 1.0)
        for j in range(100):
            mx.profiler.record("jit.trace", "jit", 10.0 * i + 2, 1.0)
    assert [ev["name"] for ev in mx.profiler.events_tail(2)] == [
        "own.step", "own.step"]
    assert len(mx.profiler.events_tail(500, compiles=True)) == 303


MS = 1e3  # ring times are microseconds


def put(name, cat, ts_ms, dur_ms, **args):
    mx.profiler.record(name, cat, ts_ms * MS, dur_ms * MS, args=args or None)


def fill_startup_ring(probe=True, compiles=True):
    """Import, set-up's traces and compiles, a collection; a routed
    driver's probe step; three first steps; a window of three steps with
    two collections in it; two traced steps; then the reference's own
    compile and collection, which lie after the window."""
    put("mxnet_tpu.import", "host", -500.0, 450.0)
    # a trace holding two nested ones (counted once), then its lowering
    put("jit.trace", "jit", 120.0, 10.0, fun="inner")
    put("jit.trace", "jit", 135.0, 5.0, fun="inner2")
    put("jit.trace", "jit", 100.0, 100.0, fun="step")
    put("jit.lower", "jit", 200.0, 50.0, fun="jit(step)")
    if compiles:
        put("jit.compile", "jit", 250.0, 2000.0, fun="jit(step)",
            cache="written")
        put("jit.compile", "jit", 2300.0, 500.0, fun="jit(make)",
            cache="compiled")
        put("jit.compile", "jit", 2850.0, 300.0, fun="jit(probe)",
            cache="hit")
    put("python.gc", "host", 3200.0, 40.0, generation=2, collected=5)
    put("sharded_trainer.multi_step", "parallel", 3300.0, 1.0)  # no .step
    t = 3400.0
    for i in range(int(probe) + 3 + 3 + 2):
        put("transformer.step", "parallel", t, 10.0, step=i)
        t += 100.0
    window = 3400.0 + 100.0 * (int(probe) + 3)
    put("python.gc", "host", window + 50.0, 7.0, generation=0, collected=1)
    put("python.gc", "host", window + 150.0, 3.0, generation=2, collected=0)
    if compiles:
        put("jit.compile", "jit", t + 100.0, 900.0, fun="jit(reference)",
            cache="written")
    put("jit.trace", "jit", t + 50.0, 40.0, fun="reference")
    put("python.gc", "host", t + 1200.0, 90.0, generation=2, collected=9)


READ_ON_A_HAND_RING = {
    "setup_import_s": 0.45,
    "setup_trace_s": 0.15,          # 100 + 50 ms, the nested two inside
    "setup_compile_s": 2.5,         # written + compiled, not the hit
    "setup_cache_read_s": 0.3,
    "setup_programs_compiled": 2,
    "setup_gc_s": 0.04,
    "gc_ms_max.tokens": 7.0,        # the window's, not the reference's
    "gc_ms_max.images": 7.0,
}


@pytest.mark.parametrize("probe", [True, False], ids=["probe", "no_probe"])
@pytest.mark.parametrize("metric", list(READ_ON_A_HAND_RING))
def test_startup_reader_on_a_hand_filled_ring(clean_ring, capsys, metric,
                                              probe):
    fill_startup_ring(probe=probe)
    read = harness.layer_reader(metric)
    value = read({"steps": 3}, {"steps": 2}, {"driver": "any"}, None)
    assert value == pytest.approx(READ_ON_A_HAND_RING[metric])
    assert capsys.readouterr().err == ""


def test_gc_ms_max_reads_zero_where_the_window_holds_no_collection(
        clean_ring):
    fill_startup_ring()
    read = harness.layer_reader("gc_ms_max.tokens")
    # a window of the two traced steps' predecessors only: no collection
    assert read({"steps": 1}, {"steps": 2}, {}, None) == 0.0


@pytest.mark.parametrize("metric", list(READ_ON_A_HAND_RING))
def test_startup_reader_without_step_spans_gives_none(clean_ring, capsys,
                                                      metric):
    put("jit.compile", "jit", 0.0, 10.0, fun="jit(x)", cache="off")
    read = harness.layer_reader(metric)
    assert read({"steps": 3}, {"steps": 2}, {}, None) is None
    err = capsys.readouterr().err
    assert err.startswith("perfbench: 0 step spans in the ring")
    assert err.rstrip().endswith("%s left out" % metric.split(".")[0])


@pytest.mark.parametrize("metric", list(READ_ON_A_HAND_RING))
def test_startup_reader_on_a_parent_ring_gives_none(clean_ring, capsys,
                                                    metric):
    fill_startup_ring(compiles=False)  # the parent records no jit.compile
    read = harness.layer_reader(metric)
    assert read({"steps": 3}, {"steps": 2}, {}, None) is None
    assert "no jit.compile span in the ring" in capsys.readouterr().err


# --------------------------------------- a rehearsal of a cell, traced
def test_rehearsal_reports_every_startup_metric(tmp_path):
    cell = "lm_train_t2048_b2"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", cell, "--seed",
         "2147483650", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])["metrics"]
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = {m["name"] for m in bench["per_layer"]
              if m["name"] in READ_ON_A_HAND_RING and cell in m["workloads"]}
    assert listed == set(READ_ON_A_HAND_RING) - {"gc_ms_max.images"}
    for name in listed:
        assert got[name]["value"] >= 0, name
    assert got["setup_programs_compiled"]["value"] >= 1
    assert 0 < got["setup_trace_s"]["value"] < 300
    assert "gc_ms_max.images" not in got
    # the reference's compiles after the window leave the accepted span
    # readers' tail to the program's own spans
    assert {"step_call_ms.tokens", "enqueue_ms.tokens",
            "step_call_ms_max.tokens"} <= set(got)
