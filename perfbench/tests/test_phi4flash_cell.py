"""The cell PR 36 added, ``phi4flash_train_t8192_b1``, on the CPU at its
rehearsal sizes: ``python -m pytest perfbench/tests -q``. Nothing here is a
measurement."""
import argparse
import importlib.util
import json
import os
import subprocess
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from perfbench import flops_sambay, run, trace_reduce  # noqa: E402
from perfbench.flops_gqa_moe import visible_pairs  # noqa: E402

CELL = "phi4flash_train_t8192_b1"
READERS = ["ssm_scan_time_pct", "ssm_scan_roofline", "diff_flash_time_pct",
           "diff_flash_roofline", "diff_window_flash_roofline"]
PEAKS = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]
BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))

_spec = importlib.util.spec_from_file_location(
    "plant_faults", os.path.join(ROOT, "tools", "plant_faults.py"))
plant_faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(plant_faults)


def config(name="phi4_mini_flash_l10"):
    return json.load(open(os.path.join(BENCH, "configs", name + ".json")))


def _run(sabotage=None, seed=5):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.5, trace=0)
    return run.run_cell(args, rehearse=True, sabotage=sabotage)


def _held_and_failed(result):
    return {k: v for k, v in result["compared"].items()
            if v["limit"] is not None and not v["value"] <= v["limit"]}


# --- the walk, end to end, in a process of its own ---------------------------
def test_rehearse_walks_the_cell():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000001", "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    compared = last["compared"]
    assert compared["window_compiles"]["value"] == 0
    assert compared["nonfinite_steps"]["value"] == 0
    assert not [k for k, v in compared.items() if v["limit"] is not None
                and not v["value"] <= v["limit"]], compared
    assert {"dispatch_ms.tokens", "step_ms_max.tokens",
            "device_idle_pct.tokens"} <= set(last["metrics"])


def test_the_window_runs_the_checked_step_at_its_own_rate():
    seen = []

    def sabotage(cell):
        dispatch = cell.dispatch

        def watched(i):
            out = dispatch(i)
            seen.append((i, cell.rate))
            return out
        cell.dispatch = watched

    result = _run(sabotage)
    opt = config()["optimizer"]
    assert opt["window_learning_rate"] < opt["learning_rate"] == 1.0
    assert [r for i, r in seen if i < 3] == [1.0] * 3
    assert len(seen) > 3 and {r for i, r in seen if i >= 3} == {
        opt["window_learning_rate"]}
    assert result["compared"]["window_compiles"]["value"] == 0
    assert result["correct"], result["compared"]


# --- planted faults come out not correct -------------------------------------
@pytest.mark.parametrize("fault", plant_faults.FAULTS[:4])
def test_a_fault_in_the_new_mechanisms_is_not_correct(fault):
    result = _run(lambda cell: plant_faults.plant(cell.model, fault))
    assert not result["correct"] and _held_and_failed(result), result[
        "compared"]


# --- the new readers, on a fixture and on traces without their kernels --------
def _fixture():
    fx = json.load(open(os.path.join(HERE, "fixture_trace_phi4flash.json")))
    return fx, trace_reduce.reduce_events(
        [[tuple(e) for e in fx["device_ops"]]], [], fx["steps"])


@pytest.mark.parametrize("name", READERS)
def test_a_new_reader_reads_its_kernels_and_nothing_without_them(name):
    fx, trace = _fixture()
    cfg = config()
    window = {"sizes": {"batch_per_chip": 1, "seq_len": 8192}}
    read = run.layer_reader(name)
    value = read(window, trace, cfg, PEAKS)
    expect = fx["expect"]
    assert trace["busy_s"] == pytest.approx(expect["busy_s"])
    if name == "ssm_scan_time_pct":
        want = 100 * expect["scan_s"] / expect["busy_s"]
    elif name == "diff_flash_time_pct":
        want = 100 * (expect["full_s"] + expect["window_s"]) / expect[
            "busy_s"]
    else:
        if name == "ssm_scan_roofline":
            need, spent = flops_sambay.scan_needed(cfg, 1, 8192), expect[
                "scan_s"]
        else:
            windowed = "window" in name
            need = flops_sambay.flash_needed(cfg, 1, 8192, windowed)
            spent = expect["window_s" if windowed else "full_s"]
        least = max(need[0] / PEAKS["bf16_flops_per_s"],
                    need[1] / PEAKS["hbm_bytes_per_s"])
        want = 100 * least / spent
    assert isinstance(value, float) and value == pytest.approx(want, rel=1e-9)
    # the other cells' steps hold none of it, or are not its model
    for other in ("fixture_trace.json", "fixture_trace_new_cells.json",
                  "fixture_trace_laguna.json"):
        old = json.load(open(os.path.join(HERE, other)))
        plain = trace_reduce.reduce_events(
            [[tuple(e) for e in old["device_ops"]]], [], old["steps"])
        if name.startswith("ssm"):
            assert read(window, plain, cfg, PEAKS) is None
        assert read(window, plain, config("laguna_xs2_ep8"), PEAKS) is None
    assert read(window, None, cfg, PEAKS) is None
    assert read(window, trace, config("lm_pythia_1.4b"), PEAKS) is None


# --- operations from shapes, against hand counts -----------------------------
def test_the_cell_needs_what_the_issue_reckoned():
    cfg = config()
    parts = flops_sambay.forward_flops_per_token(cfg, 8192)
    assert flops_sambay.mixer_matmul_params(cfg, "mamba") == 41_123_840
    assert flops_sambay.mixer_matmul_params(cfg, "full") == 19_660_800
    assert flops_sambay.mixer_matmul_params(cfg, "cross") == 13_107_200
    assert flops_sambay.mixer_matmul_params(cfg, "gmu") == 26_214_400
    assert flops_sambay.pair_flops(cfg) == 15_360
    assert parts["ffn"] == 10 * 2 * 78_643_200
    assert parts["head"] == 2 * 2560 * 25008
    assert parts["full_scores"] == 3 * 15_360 * visible_pairs(8192) / 8192
    assert parts["window_scores"] == 2 * 15_360 * visible_pairs(
        8192, 512) / 8192
    assert parts["scan"] == 3 * 7 * 5120 * 16
    per_token = flops_sambay.train_flops_per_token(cfg, 8192)
    assert round(per_token / 1e9, 1) == 7.3
    assert round(8192 * per_token / 1e12, 1) == 59.7
    flops, byts = flops_sambay.scan_needed(cfg, 1, 8192)
    assert flops == 3 * 3 * 8192 * 7 * 5120 * 16
    assert byts == 3 * 8192 * 2 * (5 * 5120 + 3 * 192)
    flops, byts = flops_sambay.flash_needed(cfg, 1, 8192, windowed=True)
    assert flops == 2 * 3 * 15_360 * visible_pairs(8192, 512)
    rows = 8192 * 2
    q, k, v, o, stats = (rows * 20 * 64, rows * 10 * 64, rows * 10 * 128,
                         rows * 20 * 128, 20 * 8192 * 4)
    assert byts == 2 * 2 * 3 * (q + k + v + o + stats)


def test_config_holds_the_published_keys_and_its_parameters():
    import importlib

    cfg = config()
    ref = importlib.import_module("perfbench.reference." + cfg["reference"])
    count = 0
    for shape, _ in ref.param_table(cfg).values():
        n = 1
        for s in shape:
            n *= s
        count += n
    assert count == cfg["parameters"] == 1_111_945_600
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == cfg["name"])
    assert len(entry["why"]) <= 200
    assert cfg["reduced"] == entry["reduced"] == ["num_hidden_layers",
                                                  "vocab_size"]
    assert cfg["published"]["num_hidden_layers"] == 32
    assert cfg["published"]["vocab_size"] == 200064 == 8 * cfg["vocab_size"]
    assert cfg["deployment"]["first_layer"] == 12
    assert [k for k, _ in ref.layer_kinds(cfg)] == [
        "mamba", "window", "mamba", "window", "mamba", "full", "gmu",
        "cross", "gmu", "cross"]
    assert {k: cfg["assumed"][k] for k in (
        "mamba_d_state", "mamba_d_conv", "mamba_expand",
        "mamba_dt_rank")} == {"mamba_d_state": 16, "mamba_d_conv": 4,
                              "mamba_expand": 2, "mamba_dt_rank": 160}
    assert len(cfg["departures"]) == 4
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Phi-4-mini-flash-reasoning")
    assert cfg["source"] == row["source_url"] == entry["source"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key


def test_the_cell_is_on_the_lists_the_issue_names():
    lists = {m["name"]: m.get("workloads", [])
             for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]}
    on = sorted(n for n, cells in lists.items() if CELL in cells)
    assert on == sorted([
        "tokens_per_s", "dispatch_ms.tokens", "step_ms_max.tokens",
        "mfu_pct.tokens", "device_idle_pct.tokens"] + READERS)
    for name in READERS:
        assert lists[name] == [CELL]
