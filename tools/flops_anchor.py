"""Per-config FLOP anchors from XLA cost analysis (VERDICT r4 weak #5:
"SSD-300 165.7 img/s has no comparison point ... LSTM-PTB 565.6
unanchored").

Compiles the SAME graphs bench_all times and reads XLA's
``cost_analysis()['flops']``, then converts the recorded BENCH_ALL
rates into achieved TF/s and percent of the chip's measured matmul
ceiling — imported from ``autotune.cost_model.CEILINGS``, the ONE
calibrated table (ISSUE 13: three independently-stated ceilings made
MFU numbers lie) — so every headline number is relatable to the
hardware, not free-floating.

Run anywhere (CPU fine: FLOP counts are graph properties; fusion noise
is a few percent):  python tools/flops_anchor.py
"""
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

from mxnet_tpu.autotune.cost_model import MEASURED_MATMUL_TF  # noqa: E402


def _graph_forward_flops(symbol, shapes):
    """FLOPs of one compiled forward of ``symbol`` (inference mode)."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.executor import _GraphProgram

    prog = _GraphProgram(symbol)
    arg_shapes, _, aux_shapes = symbol.infer_shape(**shapes)
    rng = np.random.RandomState(0)
    args = {name: rng.normal(0, 0.05, s).astype(np.float32)
            for name, s in zip(prog.arg_names, arg_shapes)}
    aux = {name: np.full(s, 1.0 if name.endswith("var") else 0.0,
                         np.float32)
           for name, s in zip(prog.aux_names, aux_shapes)}

    from mxnet_tpu import random as _mxrandom

    rngs = tuple(_mxrandom.next_key() for _ in prog.rng_nodes)

    def fn(arg_d, aux_d, rng_keys):
        outs, _ = prog._eval(arg_d, aux_d, rng_keys, False)
        return outs

    compiled = jax.jit(fn).lower(args, aux, rngs).compile()
    return float(compiled.cost_analysis()["flops"])


def resnet50_train_flops(batch=32):
    import jax

    from mxnet_tpu.models import get_resnet
    from mxnet_tpu.parallel import ShardedTrainer, make_mesh

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    sym = get_resnet(num_classes=1000, num_layers=50, layout="NHWC")
    trainer = ShardedTrainer(sym, mesh, optimizer="sgd",
                             optimizer_params={"learning_rate": 0.1,
                                               "momentum": 0.9},
                             dtype=np.dtype("bfloat16"))
    shapes = {"data": (batch, 224, 224, 3), "softmax_label": (batch,)}
    state = trainer.init(shapes)
    rng = np.random.RandomState(0)
    b = trainer.shard_batch({
        "data": rng.uniform(0, 1, shapes["data"]).astype(np.float32),
        "softmax_label": rng.randint(0, 1000, batch).astype(np.float32)})
    compiled = trainer.lower_step(state, b).compile()
    return float(compiled.cost_analysis()["flops"]) / batch


def ssd300_forward_flops(batch=8, size=300):
    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu.models.ssd import get_ssd

    net = get_ssd(num_classes=20, mode="train")
    return _graph_forward_flops(
        net, {"data": (batch, 3, size, size),
              "label": (batch, 3, 5)}) / batch


def lstm_ptb_forward_flops(bs=32, seq_len=35, hidden=200, layers=2,
                           vocab=10000):
    import mxnet_tpu as mx

    stack = mx.rnn.FusedRNNCell(hidden, num_layers=layers, mode="lstm")
    data = mx.sym.Variable("data")
    embed = mx.sym.Embedding(data, input_dim=vocab, output_dim=hidden,
                             name="embed")
    outputs, _ = stack.unroll(seq_len, inputs=embed, merge_outputs=True)
    pred = mx.sym.Reshape(outputs, shape=(-1, hidden))
    pred = mx.sym.FullyConnected(pred, num_hidden=vocab, name="pred")
    net = mx.sym.softmax(pred)
    return _graph_forward_flops(net, {"data": (bs, seq_len)}) / bs


def main():
    anchors = {}
    anchors["resnet50_train"] = {
        "gflops_per_img_train_step": round(
            resnet50_train_flops() / 1e9, 2)}
    anchors["ssd300"] = {
        "gflops_per_img_fwd": round(ssd300_forward_flops() / 1e9, 2)}
    anchors["lstm_ptb"] = {
        "gflops_per_sample_fwd": round(
            lstm_ptb_forward_flops() / 1e9, 3)}

    # relate the rates of the last bench_all.py run to the measured
    # ceiling. BENCH_ALL.json is that run's output, not a committed
    # record: a fresh checkout has none, and the anchors then stand alone
    bench_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_ALL.json")
    recorded = {}
    if os.path.exists(bench_path):
        with open(bench_path) as f:
            recorded = json.load(f).get("configs", {})

    def relate(key, cfg_key, g_per_item, train_mult):
        rate = recorded.get(cfg_key, {}).get("value")
        if rate:
            tf = rate * g_per_item * train_mult / 1e3
            anchors[key]["recorded_rate"] = rate
            anchors[key]["achieved_tf_s"] = round(tf, 2)
            anchors[key]["pct_measured_matmul_ceiling"] = round(
                100 * tf / MEASURED_MATMUL_TF, 1)

    relate("resnet50_train", "resnet50_train_bs32",
           anchors["resnet50_train"]["gflops_per_img_train_step"], 1.0)
    relate("ssd300", "ssd300_train",
           anchors["ssd300"]["gflops_per_img_fwd"], 3.0)
    relate("lstm_ptb", "lstm_ptb_train",
           anchors["lstm_ptb"]["gflops_per_sample_fwd"], 3.0)
    print(json.dumps(anchors))


if __name__ == "__main__":
    main()
