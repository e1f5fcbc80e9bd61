"""Roofline-attribution layer tests (ISSUE 13): hand-counted FLOPs/bytes
vs the walker (EXACT equality, no tolerance), step-waterfall partition
exactness, ledger append/diff/verdict round-trip, and the perf sections
of /statusz, get_stats() and /metrics."""
import json
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.autotune import cost_model
from mxnet_tpu.observability import exposition, metrics as M, perf
from mxnet_tpu.observability import stats_schema


@pytest.fixture(autouse=True)
def _perf_reset():
    perf.reset()
    yield
    perf.reset()


@pytest.fixture
def telemetry():
    from mxnet_tpu import observability as obs

    obs.set_enabled(True)
    obs.reset_metrics()
    yield
    obs.reset_metrics()
    obs.set_enabled(False)


def _walk(sym, var_shapes, dtype_bytes=4, train=False):
    topo = [n for n in sym.topo_nodes() if not n.is_variable]
    return perf.program_cost(sym, topo, var_shapes,
                             dtype_bytes=dtype_bytes, train=train,
                             graph="test")


def _row(cost, name):
    return next(r for r in cost["ops"] if r["name"] == name)


# ------------------------------------------------- hand-counted rules

def test_conv_flops_bytes_hand_counted():
    # NCHW conv: data (2, 3, 8, 8), 16 filters 3x3 pad 1 -> out (2, 16,
    # 8, 8). K = 3*3*3 = 27; out elems = 2*16*8*8 = 2048.
    data = mx.sym.var("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=16,
                             pad=(1, 1), name="conv")
    cost = _walk(net, {"data": (2, 3, 8, 8),
                       "conv_weight": (16, 3, 3, 3),
                       "conv_bias": (16,)})
    row = _row(cost, "conv")
    out_elems = 2 * 16 * 8 * 8
    assert row["flops"] == 2 * 27 * out_elems + out_elems  # MACs + bias
    in_elems = 2 * 3 * 8 * 8 + 16 * 3 * 3 * 3 + 16
    assert row["bytes"] == (in_elems + out_elems) * 4
    assert cost["flops"] == row["flops"]  # single-node graph


def test_conv_nhwc_no_bias_hand_counted():
    # channels-last, no bias: data (1, 8, 8, 4), 8 filters 2x2 ->
    # out (1, 7, 7, 8); K = 2*2*4 = 16
    data = mx.sym.var("data")
    net = mx.sym.Convolution(data, kernel=(2, 2), num_filter=8,
                             no_bias=True, layout="NHWC", name="conv")
    cost = _walk(net, {"data": (1, 8, 8, 4),
                       "conv_weight": (2, 2, 4, 8)})
    row = _row(cost, "conv")
    out_elems = 1 * 7 * 7 * 8
    assert row["flops"] == 2 * 16 * out_elems  # no bias term


def test_fc_flops_bytes_hand_counted():
    # flatten FC: data (4, 2, 5) -> in_dim 10, 6 hidden -> out (4, 6)
    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=6, name="fc")
    cost = _walk(net, {"data": (4, 2, 5), "fc_weight": (6, 10),
                       "fc_bias": (6,)})
    row = _row(cost, "fc")
    assert row["flops"] == 2 * 10 * 24 + 24
    assert row["bytes"] == (4 * 2 * 5 + 6 * 10 + 6 + 24) * 4


def test_fc_no_flatten_no_bias_hand_counted():
    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=7, flatten=False,
                                no_bias=True, name="fc")
    cost = _walk(net, {"data": (3, 5, 4), "fc_weight": (7, 4)})
    row = _row(cost, "fc")
    assert row["flops"] == 2 * 4 * (3 * 5 * 7)


def test_batch_dot_hand_counted():
    a = mx.sym.var("a")
    b = mx.sym.var("b")
    net = mx.sym.batch_dot(a, b)
    cost = _walk(net, {"a": (2, 3, 4), "b": (2, 4, 5)})
    row = cost["ops"][0]
    assert row["flops"] == 2 * 4 * (2 * 3 * 5)  # 2*K*out_elems
    assert row["bytes"] == (2 * 3 * 4 + 2 * 4 * 5 + 2 * 3 * 5) * 4


def test_flash_attention_cost_hand_counted():
    B, H, T, D = 2, 8, 1024, 64
    flops, nbytes = perf.flash_attention_cost(B, H, T, D, causal=False,
                                              dtype_bytes=2)
    assert flops == 4 * B * H * T * T * D
    assert nbytes == 4 * B * H * T * D * 2
    cf, cb = perf.flash_attention_cost(B, H, T, D, causal=True,
                                       dtype_bytes=2)
    assert cf == flops // 2  # causal dead-block skip halves the grid
    bf, bb = perf.flash_attention_cost(B, H, T, D, causal=False,
                                       dtype_bytes=2, backward=True)
    assert bf == int(flops * 2.5) and bb == nbytes * 2


def test_movement_ops_are_zero_flops():
    data = mx.sym.var("data")
    net = mx.sym.Flatten(mx.sym.Reshape(data, shape=(2, -1)),
                         name="flat")
    cost = _walk(net, {"data": (2, 3, 4)})
    assert all(r["flops"] == 0 for r in cost["ops"])
    assert all(r["bound"] == "bandwidth" for r in cost["ops"])


def test_resnet_toy_zoo_graph_exact():
    """The walker vs an independent hand computation over the resnet-toy
    zoo graph — every node, exact integers."""
    from mxnet_tpu.models import get_resnet

    sym = get_resnet(num_classes=10, num_layers=8,
                     image_shape=(3, 16, 16))
    dshape = (2, 3, 16, 16)
    arg_shapes, _, aux_shapes = sym.infer_shape(data=dshape,
                                                softmax_label=(2,))
    var_shapes = dict(zip(sym.list_arguments(), map(tuple, arg_shapes)))
    var_shapes.update(zip(sym.list_auxiliary_states(),
                          map(tuple, aux_shapes)))
    cost = _walk(sym, var_shapes)

    # independent per-node computation from inferred entry shapes
    internals = sym.get_internals()
    entries = internals._outputs
    _, out_shapes, _ = internals.infer_shape_partial(**var_shapes)
    shape_of = {}
    for (node, idx), shp in zip(entries, out_shapes):
        if shp is not None and not node.is_variable:
            shape_of[(id(node), idx)] = tuple(shp)

    def eshape(e):
        n, i = e
        return (var_shapes.get(n.name) if n.is_variable
                else shape_of.get((id(n), i)))

    def prod(s):
        out = 1
        for v in s:
            out *= int(v)
        return out

    expect_flops = expect_bytes = 0
    for node in sym.topo_nodes():
        if node.is_variable:
            continue
        n_main = node.num_main_inputs()
        ins = [eshape(e) for e in node.inputs[:n_main] if eshape(e)]
        nout = node.opdef().get_num_outputs(node.parsed_attrs())
        outs = [shape_of[(id(node), i)] for i in range(nout)
                if (id(node), i) in shape_of]
        in_el = sum(prod(s) for s in ins)
        out_el = sum(prod(s) for s in outs)
        attrs = node.parsed_attrs()
        if node.op == "Convolution":
            k = (ins[0][1] // int(attrs.get("num_group", 1) or 1)) \
                * prod(attrs.get("kernel"))
            f = 2 * k * prod(outs[0])
            if not attrs.get("no_bias"):
                f += prod(outs[0])
        elif node.op == "FullyConnected":
            in_dim = prod(ins[0][1:]) if attrs.get("flatten", True) \
                else ins[0][-1]
            f = 2 * in_dim * prod(outs[0])
            if not attrs.get("no_bias"):
                f += prod(outs[0])
        elif node.op == "Pooling":
            f = in_el
        elif node.op == "BatchNorm":
            f = 4 * out_el
        elif node.op == "SoftmaxOutput":
            f = 5 * out_el
        elif node.op == "Activation":
            f = 1 * out_el
        elif node.op == "Flatten":
            f = 0
        elif node.op == "broadcast_add":
            f = 1 * out_el
        else:
            raise AssertionError("unhandled op %s — extend the hand "
                                 "count" % node.op)
        expect_flops += f
        expect_bytes += (in_el + out_el) * 4
    assert cost["flops"] == expect_flops       # exact, no tolerance
    assert cost["hbm_bytes"] == expect_bytes
    # train program totals are the documented integer multiples
    train = _walk(sym, var_shapes, train=True)
    assert train["flops"] == perf.TRAIN_FLOPS_MULT * expect_flops
    assert train["hbm_bytes"] == perf.TRAIN_BYTES_MULT * expect_bytes


def test_roofline_seconds_basis_is_cost_model():
    cost = _walk(mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=4,
                                       no_bias=True, name="fc"),
                 {"data": (2, 8), "fc_weight": (4, 8)})
    assert cost["roofline_s"] == cost_model.roofline_seconds(
        cost["flops"], cost["hbm_bytes"])
    assert cost["ridge_intensity"] == cost_model.ridge_intensity()
    # one peak table in the program: the ceilings ARE the published
    # peaks, what ``mfu_pct`` in PERF_LEDGER.jsonl is a share of
    from mxnet_tpu.context import DEVICE_PEAKS

    peaks = DEVICE_PEAKS["TPU v5 lite"]
    assert cost_model.PEAK_FLOPS_PER_S == peaks["bf16_flops_per_s"] == 197e12
    assert cost_model.PEAK_HBM_BYTES_PER_S == peaks["hbm_bytes_per_s"]
    assert cost_model.CEILINGS["matmul_tf_s"] == 197.0
    assert cost_model.CEILINGS["hbm_gb_s"] == 819.0
    assert "DEVICE_PEAKS" in cost_model.CEILINGS["source"]
    assert cost_model.roofline_seconds(197e12, 0) == pytest.approx(1.0)
    assert cost_model.roofline_seconds(0, 819e9) == pytest.approx(1.0)


def test_fusion_candidates_ranked_by_saved_bytes():
    rows = [
        {"name": "a", "op": "Activation", "flops": 10, "bytes": 100,
         "out_bytes": 40, "bound": "bandwidth"},
        {"name": "b", "op": "Activation", "flops": 10, "bytes": 100,
         "out_bytes": 30, "bound": "bandwidth"},
        {"name": "mm", "op": "dot", "flops": 10**9, "bytes": 10,
         "out_bytes": 10, "bound": "compute"},
        {"name": "c", "op": "softmax", "flops": 10, "bytes": 100,
         "out_bytes": 25, "bound": "bandwidth"},
        {"name": "d", "op": "Activation", "flops": 10, "bytes": 100,
         "out_bytes": 20, "bound": "bandwidth"},
    ]
    cands = perf.fusion_candidates(rows)
    assert [c["ops"] for c in cands] == [["a", "b"], ["c", "d"]]
    assert cands[0]["saved_bytes"] == 2 * 40  # interior outputs only
    assert cands[1]["saved_bytes"] == 2 * 25


# ----------------------------------------------- fit-loop integration

def _toy_fit(steps=3, bs=8):
    rng = np.random.RandomState(0)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    x = rng.rand(bs * steps, 10).astype(np.float32)
    y = rng.randint(0, 4, bs * steps).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=bs,
                           label_name="softmax_label")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params=(("learning_rate", 0.1),))
    return mod


def test_waterfall_partition_exact():
    _toy_fit(steps=3)
    falls = perf.waterfalls()
    assert len(falls) == 3
    for rec in falls:
        parts = (rec["data_wait_s"] + rec["device_s"] + rec["kvstore_s"]
                 + rec["host_s"])
        # exact by construction: host is computed as the residual
        assert rec["host_s"] == rec["wall_s"] - (rec["data_wait_s"]
                                                 + rec["device_s"]
                                                 + rec["kvstore_s"])
        assert abs(parts - rec["wall_s"]) < 1e-9
        assert rec["data_wait_s"] > 0      # the lookahead timed next()
        assert rec["device_s"] > 0         # the fenced split fired
        assert rec["wall_s"] > rec["device_s"]


def test_fit_populates_program_attribution():
    _toy_fit(steps=4)
    progs = perf.program_table()
    assert len(progs) == 1
    p = progs[0]
    assert p["mode"] == "train"
    assert p["flops"] > 0 and p["hbm_bytes"] > 0
    assert p["runs"] >= 3 and p["warmup_runs"] == 1
    assert p["mfu_pct"] is not None and p["mfu_pct"] > 0
    assert p["residual"] is not None and p["residual"] > 0
    assert p["ops_top"] and p["fusion_candidates"] is not None
    # no dangling step scope after fit (would fence later forwards)
    assert not perf.step_active()


def test_multi_replica_group_fences_once():
    """Data-parallel groups dispatch ALL replicas before the perf fence
    (a per-executor fence would serialize them): one group-level note
    per step, per-replica cost, replicas annotated."""
    rng = np.random.RandomState(0)
    steps, bs = 3, 8
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        data, num_hidden=4, name="fc"), name="softmax")
    x = rng.rand(bs * steps, 6).astype(np.float32)
    y = rng.randint(0, 4, bs * steps).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=bs,
                           label_name="softmax_label")
    mod = mx.mod.Module(net, context=[mx.cpu(), mx.cpu()])
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params=(("learning_rate", 0.1),))
    assert len(mod._exec_group.execs) == 2
    progs = perf.program_table()
    assert len(progs) == 1
    p = progs[0]
    assert p["replicas"] == 2
    # one note per step (group-level), not one per replica
    assert p["runs"] + p["warmup_runs"] == steps
    falls = perf.waterfalls()
    assert len(falls) == steps
    for rec in falls:
        assert rec["device_s"] > 0
        assert rec["host_s"] == rec["wall_s"] - (rec["data_wait_s"]
                                                 + rec["device_s"]
                                                 + rec["kvstore_s"])


def test_scope_suspended_hides_and_restores():
    perf.step_begin()
    assert perf.step_active()
    with perf.scope_suspended():
        assert not perf.step_active()
        perf.note_kv(1.0)  # swallowed: no scope visible
    assert perf.step_active()
    rec = perf.step_end(step=1)
    assert rec["kvstore_s"] == 0.0


def test_warmup_run_does_not_publish_program_gauge(telemetry):
    cost = {"graph": "g", "mode": "train", "flops": 10 ** 9,
            "hbm_bytes": 10 ** 6, "roofline_s": 1e-4,
            "ridge_intensity": 240.5, "basis": "forward walk",
            "ops": [], "fusion_candidates": []}
    # the instrument may already exist (earlier tests in a full run);
    # the property under test is that the WARMUP note does not touch it
    before = M.get_value("perf.mfu_pct", None, labels={"scope": "program"})
    perf.note_program_run(cost, device_s=1e-3, host_s=1e-3)
    # first (warmup) run: registry excluded AND gauge unpublished
    assert M.get_value("perf.mfu_pct", None,
                       labels={"scope": "program"}) == before
    perf.note_program_run(cost, device_s=1e-3, host_s=1e-3)
    assert M.get_value("perf.mfu_pct", 0,
                       labels={"scope": "program"}) > 0


def test_perf_disabled_is_inert():
    from mxnet_tpu.config import set_flag

    set_flag("MXNET_PERF", 0)
    try:
        _toy_fit(steps=2)
        assert perf.waterfalls() == []
        assert perf.program_table() == []
    finally:
        set_flag("MXNET_PERF", None)


def test_kvstore_segment_accounted():
    perf.step_begin()
    perf.note_kv(0.25)
    perf.note_kv(0.25)
    perf.note_data_wait(0.125)
    rec = perf.step_end(step=1)
    assert rec["kvstore_s"] == 0.5
    assert rec["data_wait_s"] == 0.125
    assert rec["host_s"] == rec["wall_s"] - (0.5 + 0.125)


# ------------------------------------------------------------- ledger

def test_ledger_round_trip_and_verdict(tmp_path):
    path = str(tmp_path / "BENCH_LEDGER.jsonl")
    row = {"ts": "t1", "quick": True, "fingerprint": {"device": "cpu"},
           "benches": {"a": {"value": 100.0, "unit": "x",
                             "mfu_pct": 27.9},
                       "b": {"value": 5.0, "unit": "x"}},
           "programs": [{"graph": "g", "mode": "train", "flops": 123,
                         "hbm_bytes": 456, "roofline_ms": 0.1,
                         "residual": 2.0}]}
    perf.append_ledger(row, path)
    perf.append_ledger(dict(row, ts="t2"), path)
    rows = perf.read_ledger(path)
    assert [r["ts"] for r in rows] == ["t1", "t2"]
    assert perf.ledger_verdict(rows)["verdict"] == "ok"

    # bench newly failing -> hard regression
    bad = dict(row, ts="t3",
               benches={"a": {"error": "RuntimeError"},
                        "b": {"value": 5.0, "unit": "x"}})
    perf.append_ledger(bad, path)
    v = perf.ledger_verdict(perf.read_ledger(path))
    assert v["verdict"] == "regression"
    assert any("newly failing" in r for r in v["regressions"])


def test_ledger_flags_analytic_drift_and_throughput_warning(tmp_path):
    path = str(tmp_path / "l.jsonl")
    base = {"ts": "t1", "quick": True, "fingerprint": {"device": "cpu"},
            "benches": {"a": {"value": 100.0, "unit": "x"}},
            "programs": [{"graph": "g", "mode": "train", "flops": 100,
                          "hbm_bytes": 200}]}
    perf.append_ledger(base, path)
    drift = dict(base, ts="t2",
                 benches={"a": {"value": 50.0, "unit": "x"}},
                 programs=[{"graph": "g", "mode": "train", "flops": 101,
                            "hbm_bytes": 200}])
    perf.append_ledger(drift, path)
    v = perf.ledger_verdict(perf.read_ledger(path))
    assert v["verdict"] == "regression"          # flops drift is hard
    assert any("analytic flops drift" in r for r in v["regressions"])
    assert any("throughput" in w for w in v["warnings"])  # drop = warn


def test_ledger_incomparable_rows_skip_gating(tmp_path):
    path = str(tmp_path / "l.jsonl")
    perf.append_ledger({"ts": "t1", "quick": False,
                        "fingerprint": {"device": "TPU v5"},
                        "benches": {"a": {"value": 1.0, "unit": "x"}}},
                       path)
    perf.append_ledger({"ts": "t2", "quick": True,
                        "fingerprint": {"device": "cpu"},
                        "benches": {"a": {"error": "boom"}}}, path)
    v = perf.ledger_verdict(perf.read_ledger(path))
    assert v["verdict"] == "ok" and "note" in v


def test_ledger_skips_corrupt_lines(tmp_path):
    path = str(tmp_path / "l.jsonl")
    perf.append_ledger({"ts": "t1"}, path)
    with open(path, "a") as f:
        f.write("{truncated\n")
    perf.append_ledger({"ts": "t2"}, path)
    assert [r["ts"] for r in perf.read_ledger(path)] == ["t1", "t2"]


# ----------------------------------------- exposition + stats schema

def test_statusz_and_metrics_carry_perf(telemetry):
    _toy_fit(steps=2)
    port = exposition.start_http(0)
    try:
        def get(path):
            r = urllib.request.urlopen(
                "http://127.0.0.1:%d%s" % (port, path), timeout=10)
            return r.read().decode()

        statusz = json.loads(get("/statusz"))
        pz = statusz["perf"]
        assert pz["mfu_pct"] is not None
        assert pz["waterfall"] is not None
        assert statusz["providers"]["perf"]["programs"]
        prom = get("/metrics")
        for family in ("mxnet_perf_mfu_pct", "mxnet_perf_hbm_util_pct"):
            assert "# TYPE %s gauge" % family in prom
            assert "# HELP %s" % family in prom
            assert '%s{scope="step"}' % family in prom
            assert '%s{scope="program"}' % family in prom
    finally:
        exposition.stop_http()


def test_engine_stats_carry_perf_section():
    stats = stats_schema.engine_stats(
        "serving", {"requests": 1}, queue_depth=0, completed=1,
        running=True, stopped=False, capacity={}, config={},
        resilience={})
    stats_schema.validate(stats)
    assert "perf" in stats and isinstance(stats["perf"], dict)
    assert set(stats["perf"]) >= {"mfu_pct", "hbm_util_pct", "programs",
                                  "waterfall"}


def test_perf_report_compare_and_renders(tmp_path):
    _toy_fit(steps=2)
    from mxnet_tpu.observability import flight_recorder

    dump_a = flight_recorder.dump(path=str(tmp_path / "a.json"))
    _toy_fit(steps=2)
    dump_b = flight_recorder.dump(path=str(tmp_path / "b.json"))
    from tools import perf_report

    cmp = perf_report.compare_perf(dump_a, dump_b)
    segs = {r["segment"] for r in cmp["waterfall"]}
    assert segs == {"wall_s", "data_wait_s", "host_s", "device_s",
                    "kvstore_s"}
    assert cmp["mfu_pct"]["delta"] is not None
    assert cmp["programs"] and cmp["programs"][0]["delta_flops"] == 0
    text = perf_report.format_compare_perf(cmp)
    assert "delta_ms" in text and "mfu_pct" in text
    section = perf_report.load_perf_section(dump_b)
    assert "roofline attribution" in perf_report.format_roofline(
        section, dump_b)
    assert "step-time waterfall" in perf_report.format_waterfall(
        section, dump_b)
