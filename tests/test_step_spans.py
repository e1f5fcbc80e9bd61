"""The program's own spans and scopes at the two entry points the benchmark
measures (ISSUE 26): ``ShardedTrainer.step`` / ``multi_step`` and the
function ``TransformerParallel.step_fn`` returns, at the cells' rehearsal
sizes; the per-layer readers over the span ring; and the operator's
device-trace report on a small hand-written ``.xplane.pb``."""
import contextlib
import glob
import importlib
import os
import re
import sys
import time

import jax
import pytest

import mxnet_tpu as mx
from mxnet_tpu import observability as obs
from mxnet_tpu.observability import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tools")):
    if path not in sys.path:
        sys.path.insert(0, path)
import trace_report  # noqa: E402
from perfbench import run as harness  # noqa: E402

CELLS = {"sharded_trainer": "resnet50_train_b256",
         "transformer": "lm_train_t2048_b2"}


def build_cell(prefix):
    _, _, workload, config = harness.load_cell(CELLS[prefix], True)
    driver = importlib.import_module("perfbench.drivers." + config["driver"])
    return driver.build(config, workload["sizes"], 0, jax.devices()[:1])


@pytest.fixture(scope="module")
def cells():
    """Both cells at their rehearsal sizes, each with one step behind it
    (so the step is compiled before any test times or traces it)."""
    built = {}
    for prefix in CELLS:
        built[prefix] = cell = build_cell(prefix)
        cell.complete(cell.dispatch(0))
    return built


@pytest.fixture
def telemetry():
    obs.set_enabled(True)
    obs.reset_metrics()
    yield
    obs.reset_metrics()
    obs.set_enabled(False)


def spans_since(mark, prefix):
    return [ev for ev in mx.profiler.events_tail(64)
            if ev["ts"] >= mark and ev["name"].startswith(prefix + ".")]


def lowered_step(cell):
    if hasattr(cell, "trainer"):
        return cell.trainer.lower_step(cell.state, cell.pool[0])
    return cell.model._step_jit.lower(cell.params, *cell.pool[0], cell.lr)


# ------------------------------------------------------------- host spans
@pytest.mark.parametrize("prefix", list(CELLS))
def test_step_leaves_a_span_with_its_enqueue_child(cells, telemetry, prefix):
    assert not mx.profiler.spans_active()  # telemetry alone fills the ring
    mark = mx.profiler._now_us()
    cell = cells[prefix]
    cell.complete(cell.dispatch(1))
    cell.complete(cell.dispatch(2))
    spans = spans_since(mark, prefix)
    steps = [ev for ev in spans if ev["name"] == prefix + ".step"]
    enqueues = [ev for ev in spans if ev["name"] == prefix + ".enqueue"]
    assert len(steps) == len(enqueues) == 2
    assert steps[1]["args"]["step"] == steps[0]["args"]["step"] + 1
    for step, enqueue in zip(steps, enqueues):
        assert step["cat"] == "parallel" and "parent" not in step["args"]
        assert enqueue["args"] == {"parent": prefix + ".step",
                                   "step": step["args"]["step"]}
        assert step["ts"] <= enqueue["ts"]
        assert (enqueue["ts"] + enqueue["dur"]
                <= step["ts"] + step["dur"] + 1e-3)
    # one observation a span: the histogram's count is the boundary's
    assert obs.metrics.get_value("span.%s.step.ms" % prefix) == 2
    assert obs.metrics.get_value("span.%s.enqueue.ms" % prefix) == 2


@pytest.mark.parametrize("prefix", list(CELLS))
def test_step_leaves_nothing_with_telemetry_off(cells, prefix):
    assert not obs.enabled() and not mx.profiler.spans_active()
    mark = mx.profiler._now_us()
    cell = cells[prefix]
    cell.complete(cell.dispatch(1))
    assert spans_since(mark, prefix) == []
    assert not getattr(tracing._open, "stack", None)


def test_first_step_builds_and_multi_step_numbers_its_steps(telemetry):
    cell = build_cell("sharded_trainer")
    mark = mx.profiler._now_us()
    cell.complete(cell.dispatch(0))
    state, _ = cell.trainer.multi_step(cell.state, cell.pool[0], 2)
    assert state["step"] == 3
    spans = {ev["name"]: ev for ev in spans_since(mark, "sharded_trainer")}
    # the first call traces, lowers and compiles the step inside its
    # enqueue: the compile is that span's child, at step 0
    assert any(ev["name"] == "jit.compile" and ev["ts"] >= mark
               and ev["args"].get("parent") == "sharded_trainer.enqueue"
               and ev["args"].get("step") == 0
               for ev in mx.profiler.events_tail(4096, compiles=True))
    multi = spans["sharded_trainer.multi_step"]
    assert multi["args"] == {"step": 1, "steps": 2}
    assert spans["sharded_trainer.enqueue"]["args"] == {
        "parent": "sharded_trainer.multi_step", "step": 1}


@pytest.mark.parametrize("prefix", list(CELLS))
def test_spans_lie_in_the_xplane_host_plane(cells, telemetry, tmp_path,
                                            prefix):
    cell = cells[prefix]
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("perfbench.dispatch"):
            handle = cell.dispatch(1)
        cell.complete(handle)
    finally:
        jax.profiler.stop_trace()
    (found,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)
    host = {ev["name"]: ev for ev in trace_report.load_xplane(found)["host"]}
    outer, step, enqueue = (host["perfbench.dispatch"],
                            host[prefix + ".step"],
                            host[prefix + ".enqueue"])
    assert "cat" not in outer["args"]  # not a program span
    assert step["args"]["cat"] == "parallel"
    assert isinstance(step["args"]["step_num"], int)
    assert enqueue["args"]["parent"] == prefix + ".step"
    assert enqueue["args"]["step"] == step["args"]["step_num"]
    for inner, around in ((enqueue, step), (step, outer)):
        assert around["ts"] <= inner["ts"]
        assert (inner["ts"] + inner["dur"]
                <= around["ts"] + around["dur"] + 1e-3)


def test_span_costs_under_ten_microseconds_with_telemetry_on(telemetry):
    """ISSUE 26's budget is 10 us a span with telemetry on and no JAX
    trace (4.9 us measured on the sandbox); held at three times that, on
    the best of several rounds, for a loaded CI host."""
    best = float("inf")
    for _ in range(7):
        t0 = time.perf_counter()
        for i in range(2000):
            with obs.trace_span("cost.step", "parallel", step=i):
                with obs.trace_span("cost.enqueue", "parallel"):
                    pass
        best = min(best, (time.perf_counter() - t0) / 4000)
    assert best < 30e-6, "%.1f us a span" % (best * 1e6)


# ----------------------------------------- scopes in the compiled programs
SCOPES = {"sharded_trainer": ("jvp(forward)", "transpose(jvp(forward))",
                              "/update/", "/bn0/", "/stage1_unit1_conv1/"),
          "transformer": ("jvp(embed)", "jvp(l0/attn)", "jvp(l1/ffn)",
                          "transpose(jvp(l0/attn))", "jvp(head_loss)",
                          "/update/")}


@pytest.mark.parametrize("prefix", list(CELLS))
def test_lowered_step_names_phases_nodes_and_layers(cells, prefix):
    text = lowered_step(cells[prefix]).as_text(debug_info=True)
    for scope in SCOPES[prefix]:
        assert scope in text, scope


def strip_debug_info(hlo_text):
    """An HLO module's text without ``metadata={...}`` and without the
    stack-frame tables those index."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo_text)
    return re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(?:\d+ .*\n)+", "\n", text)


@pytest.mark.parametrize("prefix", list(CELLS))
def test_optimized_step_is_the_same_without_the_scopes(cells, monkeypatch,
                                                       prefix):
    scoped = lowered_step(cells[prefix]).compile().as_text()
    assert "jvp(" in scoped and "jvp(" not in strip_debug_info(scoped)
    for module in (obs, tracing, sys.modules[
            "mxnet_tpu.parallel.transformer"]):
        monkeypatch.setattr(module, "device_scope",
                            lambda name: contextlib.nullcontext())
    bare = lowered_step(build_cell(prefix)).compile().as_text()
    assert SCOPES[prefix][0] in scoped and SCOPES[prefix][0] not in bare
    assert strip_debug_info(scoped) == strip_debug_info(bare)


# ------------------------------------------------- the per-layer readers
def fill_ring(prefix, durations_ms):
    """A ring of ``<prefix>.step`` spans, each with an ``.enqueue`` child
    of half its length, as the harness leaves it: first steps, window,
    traced steps, in order."""
    for i, ms in enumerate(durations_ms):
        ts = 1e9 + 1e4 * i
        mx.profiler.record(prefix + ".enqueue", "parallel", ts + 1,
                           ms * 500.0, args={"parent": prefix + ".step",
                                             "step": i})
        mx.profiler.record(prefix + ".step", "parallel", ts, ms * 1e3,
                           args={"step": i})


@pytest.fixture
def clean_ring(tmp_path):
    mx.profiler.set_config(filename=str(tmp_path / "ring.json"))
    mx.profiler.dump_profile()  # empties the ring
    yield
    mx.profiler.dump_profile()
    mx.profiler.set_config(filename="profile.json")


READERS = {"step_call_ms.images": 3.0, "enqueue_ms.tokens": 1.5,
           "step_call_ms_max.images": 5.0}


@pytest.mark.parametrize("metric", list(READERS))
def test_reader_takes_the_windows_spans(clean_ring, metric, capsys):
    driver, prefix = (("lm_step", "transformer") if metric.endswith("tokens")
                      else ("sharded_trainer", "sharded_trainer"))
    # 3 first steps of 90 ms, a window of 4 (1, 5, 2, 4: mean 3, max 5),
    # 2 traced steps of 70 ms
    fill_ring(prefix, [90.0] * 3 + [1.0, 5.0, 2.0, 4.0] + [70.0] * 2)
    read = harness.layer_reader(metric)
    value = read({"steps": 4}, {"steps": 2}, {"driver": driver}, None)
    assert value == pytest.approx(READERS[metric])
    assert capsys.readouterr().err == ""
    # a ring that cannot hold window + traced steps: no guess
    assert read({"steps": 8}, {"steps": 2}, {"driver": driver}, None) is None
    err = capsys.readouterr().err
    assert err.startswith("perfbench: 9 %s." % prefix) and "need 10" in err


def test_reader_returns_none_for_a_program_without_the_spans(clean_ring,
                                                             capsys):
    read = harness.layer_reader("step_call_ms.tokens")
    assert read({"steps": 4}, {"steps": 2}, {"driver": "lm_step"},
                None) is None
    assert capsys.readouterr().err.startswith(
        "perfbench: 0 transformer.step spans")
    assert read({"steps": 4}, {"steps": 2}, {"driver": "other"},
                None) is None


# -------------------------------------- the operator's device-trace report
@pytest.mark.parametrize("op_name,expected", [
    ("jit(step)/jvp(forward)/bn0/dot_general", ("forward", "bn0")),
    ("jit(step)/transpose(jvp(forward))/stage1_unit1_conv2/conv_general",
     ("backward", "stage1_unit1_conv2")),
    ("jit(step)/transpose(jvp(checkpoint(forward)))/bn0/mul",
     ("backward", "bn0")),
    ("jit(step)/update/mul", ("update", "update")),
    ("jit(step)/transpose(jvp(l1/attn))/transpose;jit(step)/x",
     ("backward", "l1/attn")),
    ("jit(step)/jvp(head_loss)/jit(log_softmax)/reduce_max",
     ("forward", "head_loss/log_softmax")),
    ("jit(step)/jvp()/cond/branch_1_fun/l1/moe/combine/take",
     ("forward", "l1/moe/combine")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/cond/branch_0_fun/"
     "transpose(jvp(l2/moe/dispatch))/segment_sum/scatter-add",
     ("backward", "l2/moe/dispatch/segment_sum")),
    ("jit(step)/sub", ("unscoped", "")),
    ("", ("unscoped", "")),
])
def test_split_op_name(op_name, expected):
    assert trace_report.split_op_name(op_name) == expected
    assert trace_report.program_scope("l17/attn/flash_fwd") == "l*/attn"
    assert trace_report.program_scope("head_loss/log_softmax") == "head_loss"
    assert (trace_report.program_scope("l2/moe/dispatch/segment_sum")
            == "l*/moe/dispatch")
    assert (trace_report.program_scope("l6/conv/mix/short_conv_bwd")
            == "l*/conv/mix")


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = bf16[8,8]{1,0} fusion(%p), kind=kOutput"
    stats { metadata_id: 1
            str_value: "jit(step)/jvp(forward)/bn0/dot_general:" }
    stats { metadata_id: 2 int64_value: 394000000 }
    stats { metadata_id: 3 int64_value: 64 } } }
  event_metadata { key: 2 value { id: 2
    name: "%fusion.2 = bf16[8,8]{1,0} fusion(%p), kind=kLoop"
    stats { metadata_id: 1
            str_value: "jit(step)/transpose(jvp(forward))/fc1/mul:" } } }
  event_metadata { key: 3 value { id: 3
    name: "%fusion.3 = f32[8]{0} fusion(%p), kind=kLoop"
    stats { metadata_id: 1 str_value: "jit(step)/update/sub:" } } }
  event_metadata { key: 4 value { id: 4
    name: "%copy.7 = bf16[8,8]{0,1} copy(%x)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "flops" } }
  stat_metadata { key: 3 value { id: 3 name: "bytes_accessed" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "python3" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 0 duration_ps: 11000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 3000000
             stats { metadata_id: 1 str_value: "parallel" } }
    events { metadata_id: 3 offset_ps: 6500000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "sharded_trainer.step" } }
  event_metadata { key: 2 value { id: 2 name: "perfbench.dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "$trainer.py:285 step" } }
  stat_metadata { key: 1 value { id: 1 name: "cat" } }
}
"""


def test_device_report_reads_an_xplane(tmp_path):
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(XSPACE))
    trace = trace_report.load_xplane(str(tmp_path))
    assert [ev["name"] for ev in trace["host"]] == [
        "perfbench.dispatch", "sharded_trainer.step"]  # no $frame
    rep = trace_report.device_report(trace, trace_report.load_peaks())
    assert rep["busy_us"] == pytest.approx(9.0)
    assert rep["window_us"] == pytest.approx(10.0)
    assert rep["phases_us"] == pytest.approx(
        {"forward": 4.0, "backward": 2.0, "update": 2.0, "unscoped": 1.0})
    assert sum(rep["phases_us"].values()) == pytest.approx(rep["busy_us"])
    assert dict(rep["scopes_us"])[("backward", "fc1")] == pytest.approx(2.0)
    assert rep["unscoped_us"] == [("copy", pytest.approx(1.0))]
    # the copy carries no scope: it is owned by the operation it feeds
    assert rep["copies_us"] == [
        (("backward", "fc1", "next scoped"), pytest.approx(1.0))]
    ops = dict(rep["ops"])
    first = ops["fusion.1 bf16[8,8] kOutput"]
    # 394 MFLOP in 4 us against 197 TFLOP/s: half the roofline, FLOP-bound
    assert first["roofline_pct"] == pytest.approx(50.0)
    assert first["binds"] == "flops"
    assert ops["fusion.2 bf16[8,8] kLoop"]["roofline_pct"] is None
    (gap,) = rep["gaps"]
    assert gap["us"] == pytest.approx(1.0)
    assert gap["span"] == "sharded_trainer.step"
    text = trace_report.format_device_report(rep, str(path))
    assert "not in trace" in text and "sharded_trainer.step" in text
