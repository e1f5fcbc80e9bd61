"""chip_smoke.py's contract off the chip, and the pieces it leans on:
the rehearsal passes on the CPU and says it is one; without the flag it
refuses to run on the CPU; ``mx.tpu()`` never resolves to a CPU device;
the compile-cache helper can be placed from outside."""
import json
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _run(args, cache_dir):
    env = dict(os.environ)
    # one CPU device, like the sandbox the driver runs it in; and a
    # placed cache, so the run leaves nothing in the checkout
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    return subprocess.run([sys.executable, _SMOKE] + args, env=env,
                          capture_output=True, text=True, timeout=600)


def test_dryrun_passes_on_cpu_and_says_dryrun(tmp_path):
    in_checkout = os.path.join(_REPO, ".jax_cache")
    was_there = os.path.exists(in_checkout)
    proc = _run(["--dryrun"], tmp_path)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    rows = [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]
    header, legs, last = rows[0], rows[1:-2], rows[-1]
    assert header["dryrun"] is True and header["device"]["platform"] == "cpu"
    assert header["compile_cache_dir"] == str(tmp_path)
    assert [r["leg"] for r in legs] == [
        "resnet50_sharded", "resnet50_module_fit", "lm_train_flash",
        "fused_kernels", "generate", "device_trace"]
    assert all(r["ok"] and r["dryrun"] for r in legs), legs
    # the rehearsal can never print the chip pass line
    assert last == {"ok": True, "dryrun": True, "device": header["device"]}
    # with the cache placed from outside, none appears in the checkout
    assert os.path.exists(in_checkout) == was_there


def test_without_the_flag_a_cpu_is_refused(tmp_path, monkeypatch, capsys):
    # in-process: the refusal comes before anything is switched on
    monkeypatch.syspath_prepend(_REPO)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    import chip_smoke

    with pytest.raises(SystemExit) as refused:
        chip_smoke.main([])
    assert refused.value.code not in (0, None)  # a sentence: exit status 1
    assert "platform 'cpu'" in refused.value.code
    assert "not a TPU" in refused.value.code
    assert capsys.readouterr().out == ""  # no header, no legs, no result


def test_tpu_context_never_resolves_to_a_cpu_device():
    with pytest.raises(mx.MXNetError, match="no TPU device"):
        mx.tpu(0).jax_device()
    # the documented harness alias still does
    assert mx.gpu(0).jax_device().platform == "cpu"
    with pytest.raises(mx.MXNetError, match="no published peaks"):
        mx.context.device_peaks("cpu")


def test_compile_cache_helper_can_be_placed_from_outside(monkeypatch):
    import jax

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert mx.config.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before  # nothing set
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert mx.config.enable_compile_cache() == os.path.join(
            _REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            _REPO, ".jax_cache")
    finally:
        # tests run with the cache off (tests/conftest.py)
        jax.config.update("jax_compilation_cache_dir", before)
