"""The cells PR 28 added, on the CPU at their rehearsal sizes:
``python -m pytest perfbench/tests -q``. Nothing here is a measurement."""
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

from perfbench import flops_mla_moe, run, trace_reduce  # noqa: E402

NEW_CELLS = ["sarvam_train_t8192_b1", "resnet50_train_dp4"]
NEW_READERS = ["mla_flash_roofline", "mla_flash_time_pct", "moe_gmm_roofline",
               "moe_gmm_time_pct", "allreduce_ms.images"]
PEAKS = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]


def config(name):
    return json.load(open(os.path.join(BENCH, "configs", name + ".json")))


def _run(cell, sabotage=None, seed=5):
    args = argparse.Namespace(workload=cell, seed=seed, seconds=0.5, trace=0)
    return run.run_cell(args, rehearse=True, sabotage=sabotage)


def _held_and_failed(result):
    return {k: v for k, v in result["compared"].items()
            if v["limit"] is not None and not v["value"] <= v["limit"]}


# --- the walk, end to end, in a process of its own ---------------------------
@pytest.mark.parametrize("cell", NEW_CELLS)
def test_rehearse_walks_a_new_cell(cell):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "3000000001", "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] >= (4 if cell.endswith("dp4") else 1)
    compared = last["compared"]
    assert compared["window_compiles"]["value"] == 0
    assert compared["nonfinite_steps"]["value"] == 0
    assert not [k for k, v in compared.items() if v["limit"] is not None
                and not v["value"] <= v["limit"]], compared


# --- planted faults come out not correct -------------------------------------
def test_dropping_the_routed_part_is_not_correct():
    """The experts held here add nothing: the shared expert and attention
    alone go on to the next layer. At the configuration's own
    ``routed_scaling_factor``; the first gradient of the experts' leaves is
    what reads it."""
    from mxnet_tpu.parallel import moe

    real = moe.combine

    def sabotage(cell):
        import jax.numpy as jnp

        assert cell.config["routed_scaling_factor"] == 2.5
        moe.combine = lambda rows, weight, plan: jnp.zeros(
            (plan["row_of_pair"].shape[0], rows.shape[-1]), rows.dtype)

    try:
        result = _run("sarvam_train_t8192_b1", sabotage)
    finally:
        moe.combine = real
    failed = _held_and_failed(result)
    assert not result["correct"] and "grad_gap" in failed, result["compared"]
    assert failed["grad_gap"]["at"].split("_", 1)[1] in (
        "moe_wg", "moe_wu", "moe_wd")
    assert _run("sarvam_train_t8192_b1")["correct"]


def test_skipping_the_dp4_exchange_is_not_correct():
    """The gradients are not exchanged: every chip takes the step on its
    own rows (its own batch statistics, its own gradient, no psum) and
    keeps what it got, so the four copies of the weights drift apart."""
    def sabotage(cell):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        trainer = cell.trainer
        body = trainer._step_body()
        alone = shard_map(
            body, mesh=trainer.mesh,
            in_specs=(P(), P(), P(), P("dp"), P(), P()),
            out_specs=(P(), P(), P(), P("dp")), check_vma=False)
        trainer._step_body = lambda: alone

    result = _run("resnet50_train_dp4", sabotage)
    assert not result["correct"] and _held_and_failed(result), result[
        "compared"]
    assert _run("resnet50_train_dp4")["correct"]


# --- the new readers, on a recorded cut and on a trace without their kernels --
def _fixture():
    fx = json.load(open(os.path.join(HERE, "fixture_trace_new_cells.json")))
    return fx, trace_reduce.reduce_events(
        [[tuple(e) for e in fx["device_ops"]]], [], fx["steps"])


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_reads_its_kernels_and_nothing_without_them(name):
    fx, trace = _fixture()
    cfg = config("sarvam_105b_ep8")
    window = {"sizes": {"batch_per_chip": 1, "seq_len": 8192}}
    read = run.layer_reader(name)
    value = read(window, trace, cfg, PEAKS)
    assert isinstance(value, float) and value > 0
    assert value == pytest.approx(fx["expect"][name], rel=1e-9)
    # the first benchmark's fixture: a ResNet step, none of these kernels
    old = json.load(open(os.path.join(HERE, "fixture_trace.json")))
    plain = trace_reduce.reduce_events(
        [[tuple(e) for e in old["device_ops"]]], [], old["steps"])
    assert read(window, plain, cfg, PEAKS) is None
    assert read(window, None, cfg, PEAKS) is None
    # the flash kernels of the first LM are not latent attention's
    if name.startswith("mla_"):
        assert read(window, trace, config("lm_pythia_1.4b"), PEAKS) is None


# --- operations from shapes, against hand counts -----------------------------
def test_sarvam_flops_per_token_by_part():
    cfg = config("sarvam_105b_ep8")
    parts = flops_mla_moe.forward_flops_per_token(cfg, 8192)
    mla = 4096 * 64 * 192 + 4096 * 576 + 512 * 64 * 256 + 64 * 128 * 4096
    assert flops_mla_moe.mla_matmul_params(cfg) == mla == 94_633_984
    assert parts["mla_proj"] == 5 * 2 * mla
    assert parts["mla_scores"] == 5 * 64 * 8192 * (192 + 128)
    assert parts["dense_ffn"] == 2 * 3 * 4096 * 16384
    assert parts["router"] == 4 * 2 * 4096 * 128
    expert = 3 * 4096 * 2048
    assert flops_mla_moe.expected_pairs_per_token(cfg) == 1.0
    assert parts["shared"] == parts["routed"] == 4 * 2 * expert
    assert parts["head"] == 2 * 4096 * 32768
    total = sum(parts.values())
    assert total == 2_863_136_768 and round(total / 1e9, 2) == 2.86
    assert flops_mla_moe.train_flops_per_token(cfg, 8192) == 3 * total
    assert round(3 * total * 8192 / 1e12, 1) == 70.4
    share = lambda *names: sum(parts[n] for n in names) / total
    assert round(100 * share("mla_proj", "mla_scores")) == 62
    assert round(100 * share("mla_scores")) == 29
    assert round(100 * share("router", "shared", "routed")) == 14
    assert round(100 * share("dense_ffn")) == 14
    assert round(100 * share("head")) == 9


def test_sarvam_kernels_needed_flops_and_bytes():
    cfg = config("sarvam_105b_ep8")
    flops, byts = flops_mla_moe.flash_needed(cfg, 1, 8192)
    assert flops == 5 * 3 * 64 * 8192 * 8192 * 320
    q, k, v = 8192 * 64 * 192 * 2, 8192 * (64 * 128 + 64) * 2, 8192 * 64 * 256
    stats = 64 * 8192 * 4
    assert byts == 5 * (3 * (q + k + v) + 3 * v + 3 * stats)
    flops, byts = flops_mla_moe.gmm_needed(cfg, 8192)
    expert = 3 * 4096 * 2048
    assert flops == 4 * 3 * 2 * 8192 * expert
    assert byts == 4 * 3 * (16 * expert * 2 + 2 * 8192 * 4096 * 2)


def test_sarvam_config_holds_the_published_keys_and_its_parameters():
    import importlib

    cfg = config("sarvam_105b_ep8")
    ref = importlib.import_module("perfbench.reference." + cfg["reference"])
    table = ref.param_table(cfg)
    count = 0
    for shape, _ in table.values():
        n = 1
        for s in shape:
            n *= s
        count += n
    assert count == cfg["parameters"] == 2_656_353_280
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 32, "num_experts": 128,
                                "vocab_size": 262144}
    assert cfg["deployment"]["chips_per_layer"] == 8
    assert cfg["deployment"]["experts_held"] == [0, 16]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "sarvam-105b")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
