"""The benchmark's own tests: ``python -m pytest perfbench/tests -q``.
CPU only, tiny sizes; nothing here is a measurement."""
import argparse
import json
import os
import re
import subprocess
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from perfbench import check, flops, run, trace_reduce  # noqa: E402

BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def config(name):
    return json.load(open(os.path.join(BENCH, "configs", name + ".json")))


# --- the trace reduction, on the recorded fixture ---------------------------
def test_trace_reduction_on_the_recorded_fixture():
    fx = json.load(open(os.path.join(HERE, "fixture_trace.json")))
    devices = [[tuple(e) for e in fx["device_ops"]]]
    host = [tuple(e) for e in fx["host_spans"]]
    out = trace_reduce.reduce_events(devices, host, fx["steps"])
    want = fx["expect"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-12)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-12)
    assert out["idle_share"] == pytest.approx(want["idle_share"], rel=1e-9)
    for name, seconds in want["by_name_s"].items():
        assert out["by_name_s"][name] == pytest.approx(seconds, rel=1e-12)
    assert out["breakdown"]["device_ops"][0][0] == want["top_op"]
    assert len(out["breakdown"]["idle_gaps"]) == 3


def test_busy_union_counts_overlap_once_and_names_the_gaps():
    events = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("a", 32, 1)]
    busy, gaps = trace_reduce.busy_union(events, 0, 40)
    assert busy == 20 and gaps == [(15, 30), (35, 40)]
    assert trace_reduce.by_name(events, 0, 40) == {"a": 11, "b": 10, "c": 5}
    out = trace_reduce.reduce_events(
        [events], [("perfbench.wait", 14, 20)], steps=1)
    assert out["idle_share"] == pytest.approx(1 - 20 / 35)
    assert out["breakdown"]["idle_gaps"][0] == ["perfbench.wait", 15e-9]


# --- operations from shapes, against hand counts ----------------------------
def test_resnet50_flops_match_the_hand_count():
    cfg = config("resnet50_preact")
    assert flops.resnet_forward_macs(cfg) == 4_089_184_256
    assert flops.resnet_train_flops_per_image(cfg) == 24_535_105_536


def test_lm_flops_per_token_from_its_shapes():
    cfg = config("lm_pythia_1.4b")
    d, f, v, layers = 2048, 8192, 50304, 24
    matmul = layers * (4 * d * d + 2 * d * f + d) + d * v
    assert flops.lm_matmul_params(cfg) == matmul == 1_311_031_296
    # causal attention: 2 + 4 matmuls of 2*T*T*hd over half the square
    att = layers * 6 * 2048 * d
    assert flops.lm_train_flops_per_token(cfg, 2048) == 6 * matmul + att


def test_flash_needed_counts_two_matmuls_forward_four_backward_causal():
    assert flops.attention_flops(1, 1, 128, 64, False) == 2 * 128 * 128 * 64
    assert flops.attention_flops(1, 1, 128, 64, True) == 4 * 128 * 128 * 64
    cfg = config("lm_pythia_1.4b")
    need, byts = flops.flash_needed(cfg, 2, 2048)
    assert need == 24 * 6 * 2 * 16 * 2048 * 2048 * 128
    tensor, stats = 2 * 2048 * 2048 * 2, 2 * 16 * 2048 * 4
    assert byts == 24 * (12 * tensor + 3 * stats)


# --- BENCHMARK.json resolves to files, and keeps to the allowed names -------
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_resolves_and_names_hold():
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    configs = {c["name"]: c for c in BENCHMARK["configs"]}
    for c in configs.values():
        assert NAME.match(c["name"]) and os.path.exists(
            os.path.join(ROOT, c["file"]))
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        for key in ("driver", "reference"):
            assert os.path.exists(os.path.join(
                BENCH, key + "s" if key == "driver" else key,
                cfg[key] + ".py"))
    for w in BENCHMARK["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert os.path.exists(os.path.join(BENCH, "workloads",
                                           w["name"] + ".json"))
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e
        assert callable(run.layer_reader(m["name"]))
    assert "setup_s" in e2e


# --- the harness end to end at the rehearsal's sizes ------------------------
def test_rehearse_prints_correct_false_on_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "3000000001", "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["device"]["platform"] == "cpu"
    assert list(last)[-1] == "compared"
    assert "loss_gap" in out.stderr


def _run(cell, sabotage=None, seed=5):
    args = argparse.Namespace(workload=cell, seed=seed, seconds=0.5, trace=0)
    return run.run_cell(args, rehearse=True, sabotage=sabotage)


def _stale_state(cell):
    """A step that returns its state unchanged."""
    import jax
    import jax.numpy as jnp

    copy = lambda tree: jax.tree.map(
        lambda x: jnp.copy(x) if hasattr(x, "dtype") else x, tree)
    if hasattr(cell, "trainer"):
        real = cell.trainer.step

        def step(state, batch):
            _, outs = real(copy(state), batch)
            return state, outs
        cell.trainer.step = step
    else:
        real = cell.step

        def step(params, tok, tgt):
            _, loss = real(copy(params), tok, tgt)
            return params, loss
        cell.step = step


def _part_of_batch(share):
    """Only the first ``share`` of the rows reach the step, repeated to
    fill the batch: the rest left out, the mean taken over what is left
    (share 1/2), or each chip left with its own rows (share 1/chips)."""
    def sabotage(cell):
        import jax
        import jax.numpy as jnp

        def cut(x):
            rows = int(x.shape[0] * share)
            tiled = jnp.concatenate([x[:rows]] * int(1 / share))
            return jax.device_put(tiled, x.sharding)
        cell.pool = jax.tree.map(cut, cell.pool)
    return sabotage


@pytest.mark.parametrize("cell", CELLS)
def test_sound_rehearsal_is_correct(cell):
    result = _run(cell)
    assert result["correct"], result["compared"]


FAULTS = [(c, "stale_state") for c in CELLS] + [
    (c, "half_batch") for c in CELLS] + [
    (w["name"], "no_exchange") for w in BENCHMARK["workloads"]
    if w["chips"] > 1]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_a_broken_timed_path_comes_out_not_correct(cell, fault):
    sabotage = {"stale_state": _stale_state,
                "half_batch": _part_of_batch(0.5),
                "no_exchange": _part_of_batch(0.25)}[fault]
    result = _run(cell, sabotage)
    assert not result["correct"], result["compared"]
    held = {k: v for k, v in result["compared"].items()
            if v["limit"] is not None and not v["value"] <= v["limit"]}
    assert held, result["compared"]


# --- the control: the reference in fp8 in the program's place ---------------
@pytest.mark.parametrize("cell", [c for c in CELLS
                                  if not c.endswith("dp4")])
def test_the_fp8_control_is_not_correct(cell):
    import importlib

    import jax

    _, entry, workload, cfg = run.load_cell(cell, True)
    driver = importlib.import_module("perfbench.drivers." + cfg["driver"])
    built = driver.build(cfg, workload["sizes"], 9,
                         jax.devices()[:entry["chips"]])
    built.release()
    want = built.reference()
    correct, compared = check.compare(built.reference(quant=True), want,
                                      workload["limits"])
    assert not correct, compared
    assert check.compare(want, want, workload["limits"])[0]
