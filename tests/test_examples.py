"""Every example stays runnable (the reference keeps example/ working via
tests/python/train; here each script's --smoke mode runs in CI)."""
import os
import subprocess
import sys

import pytest

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_EXAMPLES = [
    "examples/image_classification/train_mnist.py",
    "examples/image_classification/train_imagenet.py",
    "examples/image_classification/benchmark_score.py",
    "examples/rnn/lstm_bucketing.py",
    "examples/ssd/train_ssd_toy.py",
    "examples/ssd/train_ssd.py",
    "examples/ssd/evaluate.py",
    "examples/model_parallel_lstm/model_parallel_lstm.py",
    "examples/sparse/linear_classification.py",
    "examples/gluon/mnist_gluon.py",
    "examples/transformer/train_lm.py",
    "examples/gan/dcgan.py",
    "examples/recommenders/matrix_factorization.py",
    "examples/rnn/char_rnn.py",
    "examples/autoencoder/autoencoder.py",
    "examples/numpy_ops/custom_softmax.py",
    "examples/profiler/profile_training.py",
    "examples/reinforcement_learning/dqn_gridworld.py",
    "examples/bi_lstm_sort/lstm_sort.py",
    "examples/adversary/fgsm.py",
    "examples/segmentation/fcn_xs.py",
]


@pytest.mark.parametrize("script", _EXAMPLES,
                         ids=[os.path.basename(s) for s in _EXAMPLES])
def test_example_smoke(script):
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    from launch import clean_env

    # clean_env strips the TPU_* vars that would override
    # JAX_PLATFORMS and land half the arrays on a real TPU
    env = clean_env()
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("MXTPU_PS_ADDR", None)
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags +
                            " --xla_force_host_platform_device_count=8"
                            ).strip()
    res = subprocess.run(
        [sys.executable, os.path.join(_REPO, script), "--smoke"],
        env=env, cwd=_REPO, capture_output=True, timeout=900)
    assert res.returncode == 0, "%s failed:\n%s\n%s" % (
        script, res.stdout.decode()[-3000:], res.stderr.decode()[-3000:])


def test_example_dist_train():
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    from launch import launch_local

    script = os.path.join(_REPO, "examples/distributed/dist_train.py")
    for kvstore, num_servers in [("dist_sync", 0), ("dist_async", 1)]:
        procs = launch_local(
            2, [sys.executable, script, "--kvstore", kvstore,
                "--num-epochs", "1"], num_servers=num_servers)
        try:
            for i, p in enumerate(procs):
                out, _ = p.communicate(timeout=300)
                assert p.returncode == 0, "%s worker %d:\n%s" % (
                    kvstore, i, out.decode()[-3000:])
                assert b"DIST_TRAIN_OK" in out
        finally:
            for p in procs.ps_procs:
                p.kill()


def test_synth_cifar_reproduction_pipeline(tmp_path):
    """The published reproduction recipe (examples/image_classification/
    README.md) end-to-end at CI scale: deterministic dataset generation,
    .rec train/val, ResNet-8 via the real CLI, accuracy sanity bar."""
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    from launch import clean_env

    env = clean_env()
    env["JAX_PLATFORMS"] = "cpu"
    gen = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools/make_synth_cifar.py"),
         "--out", str(tmp_path), "--train", "600", "--val", "200"],
        env=env, cwd=_REPO, capture_output=True, timeout=300)
    assert gen.returncode == 0, gen.stderr.decode()[-2000:]

    res = subprocess.run(
        [sys.executable,
         os.path.join(_REPO, "examples/image_classification/"
                             "train_imagenet.py"),
         "--data-train", str(tmp_path / "train.rec"),
         "--data-val", str(tmp_path / "val.rec"),
         "--image-shape", "3,28,28", "--num-classes", "10",
         "--network", "resnet-8", "--batch-size", "64",
         "--lr", "0.1", "--lr-step-epochs", "2", "--num-epochs", "3"],
        env=env, cwd=_REPO, capture_output=True, timeout=580)
    assert res.returncode == 0, res.stderr.decode()[-3000:]
    import re

    accs = re.findall(rb"Validation-accuracy=([0-9.]+)", res.stderr
                      + res.stdout)
    assert accs, (res.stdout[-1000:], res.stderr[-1000:])
    # at CI scale (600 imgs, 3 epochs) the tail epoch can oscillate;
    # the bar is that training LEARNED, so gate on the best epoch
    assert max(float(a) for a in accs) > 0.5, accs
