"""Every Pallas kernel lowers — and compiles — for TPU from the CPU sandbox.

``jax.export`` with ``platforms=["tpu"]`` runs the Pallas TPU lowering —
the block-shape rule (last two block dimensions divisible by 8 and 128, or
the full dimension) lives there — without a chip. The shapes are
chip_smoke.py's real ones plus the misaligned ones the old tile rule got
wrong, so an (8,128) regression fails tier-1 on the CPU.

Mosaic itself only runs when XLA compiles for a TPU, and some refusals are
Mosaic's alone: under the package's global x64 a literal in an index map
is an i64 and ``func.return (i32, i32, i64)`` "fails to legalize", which no
lowering sees. libtpu is installed beside jax, so the last test compiles
the kernels ahead of time for an abstract v5e
(``jax.experimental.topologies``) — XLA:TPU and Mosaic, no chip. It is ONE
test because libtpu admits one process at a time."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.parallel.flash_attention import flash_attention
from mxnet_tpu.parallel.fused import (fused_batch_matmul, fused_matmul,
                                      kernel_plan)
from mxnet_tpu.parallel.pallas_common import aligned_block


def _tpu_kernels(fn, *avals):
    """``kernel_name`` of each Mosaic custom call in ``fn`` lowered for
    TPU, in program order."""
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*avals)
    return re.findall(r'kernel_name = "([^"]*)"', exported.mlir_module())


def _tpu_calls(fn, *avals):
    """Number of Mosaic custom calls in ``fn`` lowered for TPU."""
    return len(_tpu_kernels(fn, *avals))


def _aval(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


@pytest.mark.parametrize("shape,dtype", [
    ((2, 16, 2048, 128), "bfloat16"),  # the cell lm_train_t2048_b2
    ((1, 16, 8192, 128), "bfloat16"),  # the longest local length queued
    ((8, 8, 2048, 64), "bfloat16"),   # the LM step (chip_smoke)
    ((1, 8, 1024, 64), "bfloat16"),   # chip_smoke's parity shape
    ((1, 8, 128, 64), "bfloat16"),    # the smallest prefill bucket
    ((2, 4, 100, 64), "float32"),     # misaligned, fits one block
    ((1, 2, 1000, 64), "bfloat16"),   # the same under the default bounds
    ((1, 2, 1920, 64), "float32"),    # 15 x 128: no power-of-two block
])
def test_flash_kernels_lower_for_tpu(shape, dtype):
    q = _aval(shape, dtype)
    assert jax.config.jax_enable_x64  # the package default the kernels face
    assert _tpu_calls(lambda q, k, v: flash_attention(q, k, v, causal=True),
                      q, q, q) == 1

    def loss(q, k, v):
        out, lse = flash_attention(q, k, v, causal=True, return_lse=True)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)

    # forward + the one fused backward — and not the dense formula. The
    # backward's name holds "flash_attention_bwd_dq": the benchmark's
    # flash metrics sum device time over that substring
    names = _tpu_kernels(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert names == ["flash_attention_fwd", "flash_attention_bwd_dqkv"]
    assert "flash_attention_bwd_dq" in names[1]


def test_flash_two_pass_backward_lowers_for_tpu(monkeypatch):
    # the path beyond the fused backward's VMEM budget stays lowerable
    import importlib

    monkeypatch.setattr(
        importlib.import_module("mxnet_tpu.parallel.flash_attention"),
        "_FUSED_BWD_VMEM_BUDGET", 0)
    q = _aval((2, 16, 2048, 128), "bfloat16")

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    assert _tpu_kernels(jax.grad(loss, argnums=(0, 1, 2)), q, q, q) == [
        "flash_attention_fwd", "flash_attention_bwd_dkv",
        "flash_attention_bwd_dq"]


@pytest.mark.parametrize("shape", [
    (1, 64, 8192, 192, 128),   # the cell sarvam_train_t8192_b1
    (2, 4, 1024, 192, 128),
])
def test_flash_with_a_narrower_v_lowers_for_tpu(shape):
    B, H, T, D, Dv = shape
    q, v = _aval((B, H, T, D), "bfloat16"), _aval((B, H, T, Dv), "bfloat16")

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, scale=0.135)
                       .astype(jnp.float32))

    names = _tpu_kernels(jax.grad(loss, argnums=(0, 1, 2)), q, q, v)
    assert names == ["flash_attention_fwd", "flash_attention_bwd_dqkv"]


def _grouped_loss(window):
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=window)
                       .astype(jnp.float32))
    return loss


@pytest.mark.parametrize("heads,window,names", [
    # the cell laguna_train_t8192_b2: a full layer and a sliding one
    (48, None, ["flash_attention_fwd", "flash_attention_bwd_dqkv"]),
    (64, 512, ["flash_attention_window_fwd",
               "flash_attention_window_bwd_dqkv"]),
    (8, 512, ["flash_attention_window_fwd",           # a window, no group
              "flash_attention_window_bwd_dqkv"]),
    (128, None, ["flash_attention_fwd",    # 16 heads a group: two passes
                 "flash_attention_bwd_dkv", "flash_attention_bwd_dq"]),
])
def test_grouped_and_windowed_flash_lowers_for_tpu(heads, window, names):
    q = _aval((2, heads, 8192, 128), "bfloat16")
    kv = _aval((2, 8, 8192, 128), "bfloat16")
    assert _tpu_kernels(jax.grad(_grouped_loss(window), argnums=(0, 1, 2)),
                        q, kv, kv) == names


def _rotary_loss(window, rot, start):
    """The cells' rotated calls: tables of ``rot`` channels from
    ``start`` at T = 8192, q and k unrotated."""
    from mxnet_tpu.parallel.lm_layers import rope_tables

    cos, sin = rope_tables(8192, rot, {"theta": 10000})

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=window,
                                       rope=(cos, sin, start))
                       .astype(jnp.float32))
    return loss


#: (heads, k/v heads, q/k width, v width, window, rotated channels, from)
ROTARY_CALLS = {
    "laguna_full": (48, 8, 128, 128, None, 64, 0),
    "laguna_sliding": (64, 8, 128, 128, 512, 128, 0),
    "sarvam": (8, 8, 192, 128, None, 64, 128),      # 8 of its 64 heads
    "lfm2": (32, 8, 64, 64, None, 64, 0),          # every channel turns
}


@pytest.mark.parametrize("call", sorted(ROTARY_CALLS))
def test_rotary_flash_keeps_its_kernels_and_their_names(call):
    """The rotation inside the kernels adds no kernel and renames none:
    the readers of the trace count them by name."""
    H, Hkv, D, Dv, window, rot, start = ROTARY_CALLS[call]
    names = _tpu_kernels(
        jax.grad(_rotary_loss(window, rot, start), argnums=(0, 1, 2)),
        _aval((2, H, 8192, D), "bfloat16"),
        _aval((2, Hkv, 8192, D), "bfloat16"),
        _aval((2, Hkv, 8192, Dv), "bfloat16"))
    part = "window_" if window else ""
    assert names == ["flash_attention_%sfwd" % part,
                     "flash_attention_%sbwd_dqkv" % part]


def _gmm_step(tokens, top_k, held, d, f, dtype="bfloat16", n_rows=None):
    from mxnet_tpu.parallel import moe

    def loss(x, w, idx, weight):
        plan = moe.plan_dispatch(idx, (0, held), moe.GMM_BLOCK_ROWS, n_rows)
        rows = moe.gmm(moe.dispatch(x, plan), w, plan, interpret=False)
        return jnp.sum(moe.combine(rows, weight, plan).astype(jnp.float32))

    return (jax.grad(loss, argnums=(0, 1, 3)),
            (_aval((tokens, d), dtype), _aval((held, d, f), dtype),
             _aval((tokens, top_k), "int32"),
             _aval((tokens, top_k), "float32")))


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """``dispatch`` / ``combine`` choose the compiled kernel where the
    backend is a TPU (``gmm`` is told so by its argument)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("n_rows,moved_by", [
    (None, []),                        # room for every pair: XLA sums
    # dispatch's backward (the gradient does not read combine's forward)
    (20480, ["moe_sum_to_tokens"])])
def test_grouped_matmul_kernels_lower_for_tpu(n_rows, moved_by,
                                              as_on_the_chip):
    # the cell's expert layer: 8,192 tokens, top 8, 16 experts held,
    # 4096 x 2048. Forward, the input's gradient (the same kernel, the
    # weight read transposed) and the weight's gradient; the readers
    # match the ``moe_gmm`` prefix, which the rows -> tokens kernel of the
    # compact layout stays out of
    fn, avals = _gmm_step(8192, 8, 16, 4096, 2048, n_rows=n_rows)
    names = _tpu_kernels(fn, *avals)
    assert sorted(names) == ["moe_gmm_dw", "moe_gmm_fwd",
                             "moe_gmm_fwd"] + moved_by


@pytest.mark.parametrize("N,held,E,d,f,rows", [
    (8192, 16, 128, 4096, 2048, (20480, 69632)),     # sarvam_train_t8192_b1
    (16384, 32, 256, 2048, 512, (40960, 139264))])   # laguna_train_t8192_b2
def test_the_routed_block_lowers_for_tpu_in_both_layouts(N, held, E, d, f,
                                                         rows,
                                                         as_on_the_chip):
    # a cell's routed block (top 8), forward and backward: a conditional
    # each, both layouts in it; under the ``moe_gmm`` prefix (which the
    # benchmark's readers sum over) the two grouped-matmul kernels and no
    # other, and beside them the compact layout's rows -> tokens kernel
    from mxnet_tpu.parallel import moe

    k, tile = 8, moe.GMM_BLOCK_ROWS
    budgets = (moe.compact_row_budget(N, k, held, E, tile),
               moe.row_budget(N, k, held, tile))
    assert budgets == rows

    def block(plan, x, weight, wg, wu, wd):
        rows = moe.dispatch(x, plan)
        act = (jax.nn.silu(moe.gmm(rows, wg, plan, interpret=False))
               * moe.gmm(rows, wu, plan, interpret=False))
        return moe.combine(moe.gmm(act, wd, plan, interpret=False), weight,
                           plan)

    def loss(layouts, x, weight, wg, wu, wd, idx):
        plans = [moe.plan_dispatch(idx, (0, held), tile, R) for R in layouts]
        return jnp.sum(moe.in_the_layout_that_fits(
            block, *plans, x, weight, wg, wu, wd).astype(jnp.float32))

    avals = (_aval((N, d), "bfloat16"), _aval((N, k), "float32"),
             _aval((held, d, f), "bfloat16"), _aval((held, d, f), "bfloat16"),
             _aval((held, f, d), "bfloat16"), _aval((N, k), "int32"))

    def lowered(layouts):
        fn = jax.value_and_grad(lambda *a: loss(layouts, *a),
                                argnums=(0, 1, 2, 3, 4))
        return jax.export.export(jax.jit(fn), platforms=["tpu"])(
            *avals).mlir_module()

    both = lowered(budgets)
    names = re.findall(r'kernel_name = "([^"]*)"', both)
    assert set(names) == {"moe_gmm_fwd", "moe_gmm_dw", "moe_sum_to_tokens"}
    assert {n for n in names if n.startswith("moe_gmm")} == {
        "moe_gmm_fwd", "moe_gmm_dw"}
    assert both.count("stablehlo.case") == 2
    assert re.search(r"tensor<%dx%dxbf16>" % (budgets[1], d), both)
    # the compact layout alone: combine's forward and dispatch's backward
    # through the kernel, and no float array as long as the worst-case
    # layout or the (token, slot) pairs
    compact = lowered((budgets[0], budgets[0]))
    assert "stablehlo.case" not in compact
    assert compact.count('kernel_name = "moe_sum_to_tokens"') == 2
    wide = re.findall(r"tensor<(?:%d|%d)(?:x\d+)*x(?:bf16|f32)>"
                      % (N * k, budgets[1]), compact)
    assert not wide, sorted(set(wide))
    assert re.search(r"tensor<%dx%dxbf16>" % (budgets[0], d), compact)


def _cell_step_lowered(cell):
    """The whole train step of a ``from_config`` cell of BENCHMARK.json,
    lowered for the TPU from shapes alone at the cell's own sizes."""
    import json
    import os

    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.transformer import TransformerParallel

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    config = json.load(open(os.path.join(root, next(
        c["file"] for c in bench["configs"] if c["name"] == entry["config"]))))
    sizes = json.load(open(os.path.join(
        root, "perfbench", "workloads", cell + ".json")))["sizes"]
    dtype = jnp.dtype(config["compute_dtype"])
    model = TransformerParallel.from_config(
        make_mesh({"dp": 1}, devices=jax.devices()[:1]), config,
        dtype=np.dtype(dtype), remat=config.get("recompute") == "per_layer")
    model.step_fn(lr=1.0)
    params = {n: jax.ShapeDtypeStruct(shape, dtype)
              for n, (shape, _) in model.param_table().items()}
    tokens = jax.ShapeDtypeStruct(
        (sizes["batch_per_chip"], sizes["seq_len"]), jnp.int32)
    return model._step_jit.trace(params, tokens, tokens, 1.0).lower(
        lowering_platforms=("tpu",)).as_text()


def test_the_state_space_cells_step_lowers_for_tpu(as_on_the_chip):
    """``phi4flash_train_t8192_b1`` at 8,192 tokens and the published
    widths: the scan's kernels in both passes (three state-space layers),
    the differential layers' flash calls forward and backward, causal and
    windowed, and no array of a state a token (8192 x 5120 x 16) anywhere
    in the step."""
    text = _cell_step_lowered("phi4flash_train_t8192_b1")
    names = re.findall(r'kernel_name = "([^"]*)"', text)
    # once a state-space layer forward, and not again in its recomputation
    # (the layer keeps the scan's output and its chunk states by name)
    assert names.count("ssm_scan_fwd") == 3
    assert names.count("ssm_scan_bwd") == 3
    # the flash calls are jitted functions, written once a shape
    assert {"flash_attention_fwd", "flash_attention_window_fwd",
            "flash_attention_bwd_dqkv",
            "flash_attention_window_bwd_dqkv"} <= set(names)
    assert not re.search(r"8192x5120x16x|5120x16x8192x|8192x16x5120x", text)


@pytest.mark.parametrize("tokens", [8192, 1000])
def test_scan_kernels_lower_for_tpu(tokens):
    """The cell's layer (5120 channels, 16 states) at its length and at
    one that is no multiple of the chunk (padded with delta = 0)."""
    from mxnet_tpu.parallel.ssm_scan import ssm_scan

    def loss(u, delta, A, Bm, Cm, D):
        return jnp.sum(ssm_scan(u, delta, A, Bm, Cm, D, interpret=False)
                       .astype(jnp.float32))

    E, N = 5120, 16
    avals = (_aval((1, tokens, E), "bfloat16"),
             _aval((1, tokens, E), "float32"), _aval((E, N), "float32"),
             _aval((1, tokens, N), "bfloat16"),
             _aval((1, tokens, N), "bfloat16"), _aval((E,), "bfloat16"))
    assert _tpu_kernels(jax.grad(loss, argnums=tuple(range(6))),
                        *avals) == ["ssm_scan_fwd", "ssm_scan_bwd"]


@pytest.mark.parametrize("shape", [(2, 8192, 6144), (1, 1000, 768)],
                         ids=["lfm2_train_t8192_b2", "a_length_of_no_tile"])
def test_short_conv_kernels_lower_for_tpu(shape):
    """The cell's conv layer (2 x 8,192 tokens, 2048 channels, 3 taps),
    forward and backward, under the names the benchmark's readers match;
    a length no tile of 16 divides takes the formula, and no kernel."""
    from mxnet_tpu.parallel.short_conv import short_conv

    def loss(bcx, w):
        return jnp.sum(short_conv(bcx, w, interpret=False)
                       .astype(jnp.float32) ** 2)

    names = _tpu_kernels(jax.grad(loss, argnums=(0, 1)),
                         _aval(shape, "bfloat16"),
                         _aval((3, shape[2] // 3), "bfloat16"))
    assert names == (["short_conv_fwd", "short_conv_bwd"]
                     if shape[1] % 16 == 0 else [])


def test_flash_declines_a_length_with_no_legal_block():
    # 1100 > the backward bound 1024 and no multiple of 128 divides it: a
    # static decline to the dense formula, not a lowering error
    q = _aval((1, 2, 1100, 64), "bfloat16")
    assert _tpu_calls(lambda q, k, v: flash_attention(q, k, v, causal=True),
                      q, q, q) == 0


# (M, K, N): ResNet-50 NHWC 1x1 convs at bs32/bs8 (chip_smoke's list,
# thinned), M=392 the old rule tiled 98 x 512, and odd full-dim shapes
@pytest.mark.parametrize("M,K,N", [
    (100352, 64, 256), (100352, 256, 64), (25088, 512, 128),
    (6272, 1024, 512), (1568, 2048, 512), (392, 512, 2048),
    (392, 2048, 512), (97, 101, 89)])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_fused_matmul_lowers_for_tpu(M, K, N, dtype):
    epilogue = (("res", "elemwise_add"), ("act", "relu"))
    tiles, why = kernel_plan(M, N, K, dtype, epilogue, [(M, N)])
    assert tiles is not None, why
    assert _tpu_calls(
        lambda x, w, r: fused_matmul(x, w, extras=[r], epilogue=epilogue,
                                     wt=False),
        _aval((M, K), dtype), _aval((K, N), dtype),
        _aval((M, N), dtype)) == 1


@pytest.mark.parametrize("M", [32, 8])
def test_classifier_is_declined_by_a_named_static_rule(M):
    # N=1000 under the default 128 bound: the old rule tiled it 512 x 125
    # and the lowering refused; now the plan declines before anything is
    # built, fused_matmul returns None and the caller composes the reference
    epilogue = (("bias",), ("act", "relu"))
    tiles, why = kernel_plan(M, 1000, 2048, "bfloat16", epilogue, [(1000,)])
    assert tiles is None and "N=1000" in why and "128" in why
    x, w, b = (np.zeros(s, np.float32)
               for s in ((M, 2048), (1000, 2048), (1000,)))
    assert fused_matmul(x, w, extras=[b], epilogue=epilogue) is None
    # under a bound that holds the whole dimension it is one legal block
    assert _tpu_calls(
        lambda x, w, b: fused_matmul(x, w, extras=[b], epilogue=epilogue,
                                     block_n=1024),
        _aval((M, 2048), "bfloat16"), _aval((1000, 2048), "bfloat16"),
        _aval((1000,), "bfloat16")) == 1


def test_fused_batch_matmul_lowers_for_tpu():
    epilogue = (("scalar", "_mul_scalar", 0.125), ("res", "elemwise_add"))
    B, M, K, N = 64, 512, 64, 512  # chip_smoke's attention-shaped batch
    assert _tpu_calls(
        lambda x, w, r: fused_batch_matmul(x, w, extras=[r],
                                           epilogue=epilogue),
        _aval((B, M, K), "bfloat16"), _aval((B, K, N), "bfloat16"),
        _aval((B, M, N), "bfloat16")) == 1


def test_aligned_block_rule():
    assert aligned_block(392, 512, 8) == 392       # fits: the whole axis
    assert aligned_block(392, 128, 8) == 56        # largest 8-multiple divisor
    assert aligned_block(1000, 512, 128) is None   # no 128-multiple divides
    assert aligned_block(2048, 1024, 128) == 1024
    assert aligned_block(1920, 1024, 128) == 640
    assert aligned_block(96, 32, 1) == 32          # the interpreter: any divisor
    assert aligned_block(1009, 128, 1) is None     # prime: only tiny tiles


def _abstract_v5e(monkeypatch):
    """An abstract v5e device to compile for, or skip: libtpu describes
    the topology without any chip attached."""
    from jax.experimental import topologies

    for key, value in (("TPU_SKIP_MDS_QUERY", "1"),
                       ("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                       ("TPU_WORKER_HOSTNAMES", "localhost")):
        monkeypatch.setenv(key, value)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as err:  # no libtpu here, or another process holds it
        pytest.skip("no abstract TPU topology: %s" % str(err)[:200])
    return topo.devices[0]


def test_kernels_compile_for_v5e_ahead_of_time(monkeypatch):
    from jax.sharding import SingleDeviceSharding

    on_chip = SingleDeviceSharding(_abstract_v5e(monkeypatch))

    def compiled_calls(fn, *avals):
        avals = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip)
                 for a in avals]
        lowered = jax.jit(fn).trace(*avals).lower(
            lowering_platforms=("tpu",))
        return lowered.compile().as_text().count("tpu_custom_call")

    assert jax.config.jax_enable_x64

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    # chip_smoke's parity shape, the cell's, the longest local length
    # queued: forward + the fused backward (its (T, D) dq scratch under
    # the raised VMEM limit is Mosaic's to refuse, no lowering's)
    for shape in ((1, 8, 1024, 64), (2, 16, 2048, 128), (1, 16, 8192, 128)):
        q = _aval(shape, "bfloat16")
        assert compiled_calls(jax.grad(loss, argnums=(0, 1, 2)),
                              q, q, q) == 2, shape
    # latent attention's 192/128 heads at the cell's length (8 of its 64
    # heads), and the grouped matmul over 16 held experts at its widths
    q, v = (_aval((1, 8, 8192, w), "bfloat16") for w in (192, 128))
    assert compiled_calls(jax.grad(loss, argnums=(0, 1, 2)), q, q, v) == 2
    fn, avals = _gmm_step(8192, 8, 16, 4096, 2048)
    assert compiled_calls(fn, *avals) == 3
    # grouped-query heads at the cell laguna_train_t8192_b2's shapes: 48
    # over 8 causal and 64 over 8 under a window of 512 — the fused
    # backward's (group * T, D) dq scratch under the grouped calls' limit
    # is Mosaic's to refuse — and the grouped matmul over 32 held experts
    # of 512
    kv = _aval((1, 8, 8192, 128), "bfloat16")
    for heads, window in ((48, None), (64, 512)):
        q = _aval((1, heads, 8192, 128), "bfloat16")
        assert compiled_calls(jax.grad(_grouped_loss(window),
                                       argnums=(0, 1, 2)), q, kv, kv) == 2
    fn, avals = _gmm_step(16384, 8, 32, 2048, 512)
    assert compiled_calls(fn, *avals) == 3
    # the same three shapes with the rotary tables: q and k rotated in
    # VMEM, dq and dk turned back — Mosaic's lane rolls and concatenations
    # at 128 and 192 lanes, and the tables' VMEM, are its to refuse
    for H, Hkv, D, Dv, window, rot, start in ROTARY_CALLS.values():
        assert compiled_calls(
            jax.grad(_rotary_loss(window, rot, start), argnums=(0, 1, 2)),
            _aval((1, H, 8192, D), "bfloat16"),
            _aval((1, Hkv, 8192, D), "bfloat16"),
            _aval((1, Hkv, 8192, Dv), "bfloat16")) == 2
    # the compact layout's rows -> tokens kernel at both routed cells'
    # shapes, weighted (combine's forward) and not (dispatch's backward)
    from mxnet_tpu.parallel import moe

    for N, R, d in ((8192, 20480, 4096), (16384, 40960, 2048)):
        call = moe._sum_call(N, *moe._SUM_BLOCK, False)
        operands = (_aval((R, d), "bfloat16"), _aval((R,), "int32"),
                    _aval((1,), "int32"), _aval((R,), "float32"))
        assert compiled_calls(call, *operands) == 1
        assert compiled_calls(call, *operands[:3]) == 1
    # the selective scan at the cell phi4flash_train_t8192_b1's layer
    # (5120 channels, 16 states, 8,192 steps): the backward's chunk of
    # states in VMEM under the kernels' own limit is Mosaic's to refuse —
    # and its differential flash calls: q/k 64 wide, V 128 wide, 20 query
    # head pairs over 10, causal and under the window of 512
    from mxnet_tpu.parallel.ssm_scan import ssm_scan

    def scan_loss(u, delta, A, Bm, Cm, D):
        return jnp.sum(ssm_scan(u, delta, A, Bm, Cm, D, interpret=False)
                       .astype(jnp.float32))

    small = _aval((1, 8192, 16), "bfloat16")
    assert compiled_calls(
        jax.grad(scan_loss, argnums=tuple(range(6))),
        _aval((1, 8192, 5120), "bfloat16"), _aval((1, 8192, 5120), "float32"),
        _aval((5120, 16), "float32"), small, small,
        _aval((5120,), "bfloat16")) == 2
    q, k, v = (_aval((1, heads, 8192, width), "bfloat16")
               for heads, width in ((20, 64), (10, 64), (10, 128)))
    for window in (None, 512):
        assert compiled_calls(jax.grad(_grouped_loss(window),
                                       argnums=(0, 1, 2)), q, k, v) == 2
    # the gated short convolution at the cell lfm2_train_t8192_b2's layer
    # (2 x 8,192 tokens, 3 x 2048 gates): its tiles' VMEM under the
    # kernels' own limit, and the sublane rolls, are Mosaic's to refuse
    from mxnet_tpu.parallel.short_conv import short_conv

    assert compiled_calls(
        jax.grad(lambda bcx, w: jnp.sum(short_conv(
            bcx, w, interpret=False).astype(jnp.float32) ** 2),
            argnums=(0, 1)),
        _aval((2, 8192, 6144), "bfloat16"), _aval((3, 2048), "bfloat16")) == 2
    epilogue = (("bias",), ("act", "relu"))
    assert compiled_calls(
        lambda x, w, b: fused_matmul(x, w, extras=[b], epilogue=epilogue),
        _aval((392, 2048), "bfloat16"), _aval((512, 2048), "bfloat16"),
        _aval((512,), "bfloat16")) == 1
    res = (("scalar", "_mul_scalar", 0.125), ("res", "elemwise_add"))
    assert compiled_calls(
        lambda x, w, r: fused_batch_matmul(x, w, extras=[r], epilogue=res),
        _aval((8, 512, 64), "bfloat16"), _aval((8, 64, 512), "bfloat16"),
        _aval((8, 512, 512), "bfloat16")) == 1
