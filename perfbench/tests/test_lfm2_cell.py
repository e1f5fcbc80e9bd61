"""The cell ``lfm2_train_t8192_b2`` on the CPU at its
rehearsal sizes: ``python -m pytest perfbench/tests -q``. Nothing here is a
measurement."""
import argparse
import importlib
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

from perfbench import flops_lfm2, flops_mla_moe, run, trace_reduce  # noqa: E402
from tools import plant_faults  # noqa: E402

CELL = "lfm2_train_t8192_b2"
READERS = ["short_conv_time_pct", "short_conv_roofline", "lfm2_flash_time_pct",
           "lfm2_flash_roofline"]
PEAKS = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]
BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def config(name="lfm2_24b_a2b_ep8"):
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
    assert not _held_and_failed(last), compared
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
    assert [r for i, r in seen if i < 3] == [opt["learning_rate"]] * 3
    assert len(seen) > 3 and {r for i, r in seen if i >= 3} == {
        opt["window_learning_rate"]}
    assert result["compared"]["window_compiles"]["value"] == 0
    assert result["correct"], result["compared"]


# --- planted faults come out not correct -------------------------------------
@pytest.mark.parametrize("fault", plant_faults.LFM2_FAULTS)
def test_a_fault_in_the_new_mechanisms_is_not_correct(fault):
    assert plant_faults.CELL_OF[fault] == CELL
    result = _run(lambda cell: plant_faults.plant(cell.model, fault))
    assert not result["correct"] and _held_and_failed(result), result[
        "compared"]


# --- the new readers, on a fixture and on traces without their kernels --------
def _fixture():
    fx = json.load(open(os.path.join(HERE, "fixture_trace_lfm2.json")))
    return fx, trace_reduce.reduce_events(
        [[tuple(e) for e in fx["device_ops"]]], [], fx["steps"])


@pytest.mark.parametrize("name", READERS + ["moe_gmm_roofline"])
def test_a_reader_reads_its_kernels_and_nothing_without_them(name):
    fx, trace = _fixture()
    cfg = config()
    window = {"sizes": {"batch_per_chip": 2, "seq_len": 8192}}
    read = run.layer_reader(name)
    value = read(window, trace, cfg, PEAKS)
    expect = fx["expect"]
    assert trace["busy_s"] == pytest.approx(expect["busy_s"])
    spent = expect["flash_s" if "flash" in name else "conv_s"]
    if name.endswith("time_pct"):
        want = 100 * spent / expect["busy_s"]
    else:
        need = (flops_lfm2.flash_needed(cfg, 2, 8192) if "flash" in name
                else flops_lfm2.short_conv_needed(cfg, 2, 8192))
        if name == "moe_gmm_roofline":
            need, spent = flops_mla_moe.gmm_needed(cfg, 2 * 8192), expect[
                "gmm_s"]
        least = max(need[0] / PEAKS["bf16_flops_per_s"],
                    need[1] / PEAKS["hbm_bytes_per_s"])
        want = 100 * least / spent
    assert isinstance(value, float) and value == pytest.approx(want, rel=1e-9)
    if name == "moe_gmm_roofline":
        return
    # the other cells' steps hold none of it (the flash kernels they do
    # hold), or are not its model
    for other in ("fixture_trace.json", "fixture_trace_new_cells.json",
                  "fixture_trace_laguna.json", "fixture_trace_phi4flash.json"):
        if "flash" in name:
            break
        old = json.load(open(os.path.join(HERE, other)))
        plain = trace_reduce.reduce_events(
            [[tuple(e) for e in old["device_ops"]]], [], old["steps"])
        assert read(window, plain, cfg, PEAKS) is None
    for model in ("laguna_xs2_ep8", "phi4_mini_flash_l10", "sarvam_105b_ep8",
                  "lm_pythia_1.4b"):
        assert read(window, trace, config(model), PEAKS) is None
    assert read(window, None, cfg, PEAKS) is None


# --- operations from shapes, against hand counts -----------------------------
def test_the_needed_counts_of_the_cell():
    cfg = config()
    parts = flops_lfm2.forward_flops_per_token(cfg, 8192)
    d = 2048
    assert parts["conv_proj"] == 7 * 2 * 4 * d * d
    assert parts["conv_mix"] == 7 * 7 * d
    assert parts["attn_proj"] == 2 * 2 * (2 * d * d + 2 * d * 512)
    assert parts["scores"] == 2 * 32 * 4 * 64 * 8193 / 2
    assert parts["dense_ffn"] == 2 * 3 * d * 11776
    assert parts["router"] == 8 * 2 * d * 64
    assert parts["routed"] == 8 * 2 * 0.5 * 3 * d * 1536
    assert parts["head"] == 2 * d * 8192
    total = sum(parts.values())
    assert round(total / 1e6) == 600
    assert round(parts["conv_proj"] / total, 2) == 0.39
    per_token = flops_lfm2.train_flops_per_token(cfg, 8192)
    assert round(per_token / 1e9, 2) == 1.80
    assert round(16384 * per_token / 1e12, 1) == 29.5
    flops, byts = flops_lfm2.short_conv_needed(cfg, 2, 8192)
    assert flops == 7 * 3 * 16384 * 7 * d
    assert byts == 7 * (16384 * 2 * 11 * d + 3 * d * 8)
    assert round(byts / PEAKS["hbm_bytes_per_s"] * 1e3, 1) == 6.3
    # the two attention layers: 32 query heads over 8 k/v heads of 64
    flops, byts = flops_lfm2.flash_needed(cfg, 2, 8192)
    assert flops == 2 * 3 * 2 * 32 * 4 * 64 * (8192 * 8193 // 2)
    rows = 2 * 8192 * 64 * 2
    assert byts == 2 * (6 * 32 * rows + 6 * 8 * rows + 3 * 2 * 32 * 8192 * 4)
    assert round(parts["scores"] * 3 * 16384 / flops, 3) == 1.0
    # the accepted moe_gmm readers count this configuration as laguna's
    assert flops_mla_moe.expected_pairs_per_token(cfg) == 0.5
    assert (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
            == sum(f == "expert" for _, f in flops_lfm2.layers_of(cfg)) == 8)


def test_config_holds_the_published_keys_and_its_parameters():
    cfg = config()
    ref = importlib.import_module("perfbench.reference." + cfg["reference"])
    count = 0
    for shape, _ in ref.param_table(cfg).values():
        n = 1
        for s in shape:
            n *= s
        count += n
    assert count == cfg["parameters"] == 832_652_032
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == cfg["name"])
    assert len(entry["why"]) <= 200
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types",
        "num_dense_layers"]
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"], pub["vocab_size"],
            pub["num_dense_layers"]) == (40, 64, 65536, 2)
    assert pub["vocab_size"] == 8 * cfg["vocab_size"]
    assert pub["num_experts"] == 8 * cfg["num_experts"]
    assert cfg["layer_types"] == pub["layer_types"][1:10]
    assert cfg["deployment"]["experts_held"] == [0, 8]
    assert [k for k, _ in ref.layer_kinds(cfg)] == [
        "conv", "attention", "conv", "conv", "conv", "attention", "conv",
        "conv", "conv"]
    # the published config.json's keys that no cut may change
    published = dict(
        conv_L_cache=3, conv_bias=False, hidden_size=2048,
        intermediate_size=11776, max_position_embeddings=128000,
        model_type="lfm2_moe", moe_intermediate_size=1536, norm_eps=1e-5,
        norm_topk_prob=True, num_attention_heads=32, num_experts_per_tok=4,
        num_key_value_heads=8, routed_scaling_factor=1,
        use_expert_bias=True,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"})
    assert {k: cfg[k] for k in published} == published
    assert cfg["source"] == entry["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")


def test_the_cell_is_on_the_lists_of_its_metrics():
    lists = {m["name"]: m.get("workloads", [])
             for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]}
    on = {n for n, cells in lists.items() if CELL in cells}
    assert {"tokens_per_s", "setup_s", "dispatch_ms.tokens",
            "step_ms_max.tokens", "mfu_pct.tokens", "device_idle_pct.tokens",
            "moe_gmm_roofline", "moe_gmm_time_pct", "gc_ms_max.tokens",
            *READERS} <= on | {"setup_s"}
    assert {n for n in lists if n.startswith("setup_") and lists[n]} <= on
    for name in READERS:
        assert lists[name] == [CELL]
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
