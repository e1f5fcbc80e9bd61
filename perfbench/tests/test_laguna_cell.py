"""The cell PR 33 added, ``laguna_train_t8192_b2``, on the CPU at its
rehearsal sizes: ``python -m pytest perfbench/tests -q``. Nothing here is a
measurement. (``test_harness.py`` takes the cell in by itself: the sound
rehearsal, a stale state, half the batch and the fp8 control.)"""
import argparse
import json
import os
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

from perfbench import flops_gqa_moe, run, trace_reduce  # noqa: E402

CELL = "laguna_train_t8192_b2"
READERS = ["gqa_flash_time_pct", "gqa_flash_roofline",
           "window_flash_roofline"]
PEAKS = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]
BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def config(name="laguna_xs2_ep8"):
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


# --- planted faults come out not correct -------------------------------------
@pytest.mark.parametrize("fault", ["full_mask_in_the_sliding_layers",
                                   "no_gate"])
def test_a_fault_in_the_new_mechanisms_is_not_correct(fault):
    """The program's sliding layers given the full causal mask, or its
    gate left out, against the sound reference."""
    def sabotage(cell):
        gqa = cell.model.arch["gqa"]
        if fault == "no_gate":
            gqa["gate"] = False     # the leaf stays, unused: gradient 0
        else:
            for layer in gqa["layers"]:
                layer["window"] = None

    result = _run(sabotage)
    assert not result["correct"] and _held_and_failed(result), result[
        "compared"]


def test_the_window_runs_the_checked_step_at_its_own_rate():
    """Steps 0 to 2, which the reference follows, at the configuration's
    learning rate; the window at ``window_learning_rate``; one compiled
    step for both (the rate is its argument), and one ``cell.step`` that
    a test can break for both."""
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


# --- the new readers, on a fixture and on traces without their kernels --------
def _fixture():
    fx = json.load(open(os.path.join(HERE, "fixture_trace_laguna.json")))
    return fx, trace_reduce.reduce_events(
        [[tuple(e) for e in fx["device_ops"]]], [], fx["steps"])


@pytest.mark.parametrize("name", READERS)
def test_a_new_reader_reads_its_kernels_and_nothing_without_them(name):
    fx, trace = _fixture()
    cfg = config()
    window = {"sizes": {"batch_per_chip": 2, "seq_len": 8192}}
    read = run.layer_reader(name)
    value = read(window, trace, cfg, PEAKS)
    assert trace["busy_s"] == pytest.approx(fx["expect"]["busy_s"])
    full, band = fx["expect"]["full_s"], fx["expect"]["window_s"]
    if name == "gqa_flash_time_pct":
        want = 100 * (full + band) / fx["expect"]["busy_s"]
    else:
        windowed = name.startswith("window")
        flops, byts = flops_gqa_moe.flash_needed(cfg, 2, 8192, windowed)
        least = max(flops / PEAKS["bf16_flops_per_s"],
                    byts / PEAKS["hbm_bytes_per_s"])
        want = 100 * least / (band if windowed else full)
    assert isinstance(value, float) and value == pytest.approx(want, rel=1e-9)
    # a ResNet step and a sarvam step hold none of it, or are not its model
    for other in ("fixture_trace.json", "fixture_trace_new_cells.json"):
        old = json.load(open(os.path.join(HERE, other)))
        plain = trace_reduce.reduce_events(
            [[tuple(e) for e in old["device_ops"]]], [], old["steps"])
        if "new_cells" not in other:
            assert read(window, plain, cfg, PEAKS) is None
        assert read(window, plain, config("sarvam_105b_ep8"), PEAKS) is None
    assert read(window, None, cfg, PEAKS) is None
    assert read(window, trace, config("lm_pythia_1.4b"), PEAKS) is None


def test_the_accepted_gmm_readers_count_this_cell():
    """``moe_gmm_roofline`` reads ``flops_mla_moe.gmm_needed``, which takes
    the routed layers as ``num_hidden_layers - first_k_dense_replace``: the
    derived key in the configuration's file."""
    _, trace = _fixture()
    cfg = config()
    window = {"sizes": {"batch_per_chip": 2, "seq_len": 8192}}
    share = run.layer_reader("moe_gmm_roofline")(window, trace, cfg, PEAKS)
    expert = 3 * 2048 * 512
    flops = 4 * 3 * 2 * 16384 * expert
    byts = 4 * 3 * (32 * expert * 2 + 2 * 16384 * 2048 * 2)
    least = max(flops / PEAKS["bf16_flops_per_s"],
                byts / PEAKS["hbm_bytes_per_s"])
    assert share == pytest.approx(100 * least / 0.003, rel=1e-9)
    assert run.layer_reader("moe_gmm_time_pct")(
        window, trace, cfg, PEAKS) == pytest.approx(100 * 0.003 / 0.4)


# --- operations from shapes, against hand counts -----------------------------
def test_flops_by_hand_at_one_small_shape():
    cfg = dict(hidden_size=32, head_dim=8, num_key_value_heads=2, gating=True,
               num_hidden_layers=2, layer_types=["full_attention",
                                                 "sliding_attention"],
               mlp_layer_types=["dense", "sparse"],
               num_attention_heads_per_layer=[4, 6], sliding_window=3,
               intermediate_size=64, moe_intermediate_size=24,
               shared_expert_intermediate_size=40, num_experts=4,
               num_experts_per_tok=2, published={"num_experts": 8},
               vocab_size=50)
    assert flops_gqa_moe.visible_pairs(8) == 36
    assert flops_gqa_moe.visible_pairs(8, 3) == 1 + 2 + 6 * 3 == 21
    assert flops_gqa_moe.visible_pairs(8, 8) == 36
    parts = flops_gqa_moe.forward_flops_per_token(cfg, 8)
    full = 2 * 32 * 4 * 8 + 2 * 32 * 2 * 8 + 32 * 4
    sliding = 2 * 32 * 6 * 8 + 2 * 32 * 2 * 8 + 32 * 6
    assert parts["attn_proj"] == 2 * (full + sliding)
    assert parts["full_scores"] == 4 * 4 * 8 * 36 / 8
    assert parts["window_scores"] == 6 * 4 * 8 * 21 / 8
    assert parts["dense_ffn"] == 2 * 3 * 32 * 64
    assert parts["router"] == 2 * 32 * 8
    assert parts["shared"] == 2 * 3 * 32 * 40
    assert parts["routed"] == 2 * (2 * 4 / 8) * 3 * 32 * 24
    assert parts["head"] == 2 * 32 * 50
    flops, byts = flops_gqa_moe.flash_needed(cfg, 2, 8, windowed=True)
    assert flops == 3 * 2 * 6 * 4 * 8 * 21
    q, kv, stats = 2 * 8 * 8 * 2 * 6, 2 * 8 * 8 * 2 * 2, 2 * 6 * 8 * 4
    assert byts == 6 * q + 6 * kv + 3 * stats
    flops, byts = flops_gqa_moe.flash_needed(cfg, 2, 8, windowed=False)
    assert flops == 3 * 2 * 4 * 4 * 8 * 36


def test_the_cell_needs_what_the_issue_reckoned():
    cfg = config()
    parts = flops_gqa_moe.forward_flops_per_token(cfg, 8192)
    total = sum(parts.values())
    matmuls = total - parts["full_scores"] - parts["window_scores"]
    assert matmuls == 2 * 275_841_024, matmuls
    assert round(3 * matmuls / 1e9, 2) == 1.66
    assert round(3 * parts["full_scores"] / 1e9, 2) == 0.60
    assert round(3 * parts["window_scores"] / 1e9, 2) == 0.15
    assert flops_gqa_moe.expected_pairs_per_token(cfg) == 1.0
    assert round(16384 * flops_gqa_moe.train_flops_per_token(cfg, 8192)
                 / 1e12, 1) == 39.4


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
    assert count == cfg["parameters"] == 691_623_936
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == cfg["name"])
    assert len(entry["why"]) <= 200 and len(entry["reduced"]) <= 16
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types",
        "mlp_layer_types", "num_attention_heads_per_layer"]
    assert {k: cfg["published"][k] for k in (
        "num_hidden_layers", "num_experts", "vocab_size")} == {
        "num_hidden_layers": 40, "num_experts": 256, "vocab_size": 100352}
    assert cfg["deployment"]["chips_per_layer"] == 8
    assert cfg["deployment"]["experts_held"] == [0, 32]
    assert cfg["first_k_dense_replace"] == cfg["mlp_layer_types"].index(
        "sparse") == 1
    assert len(cfg["assumed"]) >= 6 and len(cfg["departures"]) == 4
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Laguna-XS.2")
    assert cfg["source"] == row["source_url"] == entry["source"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):    # cut with the depth
        assert cfg[key] == row["config"][key][:cfg["num_hidden_layers"]]


def test_the_cell_is_on_the_lists_the_issue_names():
    lists = {m["name"]: m.get("workloads", [])
             for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]}
    on = sorted(n for n, cells in lists.items() if CELL in cells)
    assert on == sorted([
        "tokens_per_s", "dispatch_ms.tokens", "step_ms_max.tokens",
        "mfu_pct.tokens", "device_idle_pct.tokens", "moe_gmm_roofline",
        "moe_gmm_time_pct"] + READERS)
    for name in READERS:
        assert lists[name] == [CELL]
