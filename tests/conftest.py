"""Test harness config: force JAX onto CPU with 8 virtual devices so
multi-device (mesh/sharding) paths are exercised without TPU hardware —
the analog of the reference running multi-device tests by mapping ctx
groups onto cpu(0)/cpu(1) (tests/python/unittest/test_multi_device_exec.py).

Overrides any ambient JAX_PLATFORMS (e.g. ``tpu`` on a machine with a
chip): unit tests must be hermetic and fast; the real chip is exercised by
chip_smoke.py. The persistent compilation cache stays OFF here — nothing in
the library turns it on (``config.enable_compile_cache`` is for entry
points), and an ambient ``JAX_COMPILATION_CACHE_DIR`` is dropped — so the
compile-count tests keep their meaning.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# pytest plugins (jaxtyping) may import jax before this conftest runs, baking
# in the ambient JAX_PLATFORMS; override through the config as well — safe as
# long as no backend has been initialized yet.
import jax

jax.config.update("jax_platforms", "cpu")
assert len(jax.devices()) >= 8, (
    "test harness expected 8 virtual CPU devices, got %s" % jax.devices())

# ---- crash flight recorder: armed for the whole tier-1 run ----------------
# A failing test dumps the recorder (ring + metrics snapshot + span tail +
# env fingerprint) into MXNET_HEALTH_DUMP_DIR; CI uploads the directory as
# a workflow artifact (.github/workflows/ci.yml, if: always()).
os.environ.setdefault("MXNET_HEALTH_DUMP_DIR", "health_dumps")

# ---- autotuner: hermetic tuning cache -------------------------------------
# The persistent tuning cache defaults to ~/.cache/mxnet_tpu/tuning.json;
# a developer's tuned entries must never steer (or be clobbered by) unit
# tests, so the whole run gets a throwaway cache file. Tests that exercise
# the cache override this again per-test (tests/test_autotune.py).
import tempfile

os.environ["MXNET_TUNE_CACHE"] = os.path.join(
    tempfile.mkdtemp(prefix="mxnet_tune_test_"), "tuning.json")

import pytest  # noqa: E402

_FAILURE_DUMPS = {"n": 0, "max": 5}  # bound artifact size on mass failures


def pytest_configure(config):
    from mxnet_tpu.observability import flight_recorder

    flight_recorder.install()


def pytest_collection_modifyitems(items):
    """Run the TPU-lowering guards first. The tier-1 command (ROADMAP.md)
    is time-limited and gets through about the first quarter of the suite
    in collection order; tests/test_tpu_lowering.py takes seconds and is
    what makes a tiling or x64 regression of a Pallas kernel fail tier-1
    on the CPU, so it must not sit behind the limit under "t"."""
    first = [i for i in items if i.path.name == "test_tpu_lowering.py"]
    if first:
        items[:] = first + [i for i in items
                            if i.path.name != "test_tpu_lowering.py"]


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    if rep.when == "call" and rep.failed \
            and _FAILURE_DUMPS["n"] < _FAILURE_DUMPS["max"]:
        _FAILURE_DUMPS["n"] += 1
        try:
            from mxnet_tpu.observability import flight_recorder

            # explicit path: this hook fires BEFORE fixture teardown, so
            # a failing health test's tmp_path dump_dir override is still
            # in effect — the CI artifact uploads health_dumps/ only
            out_dir = os.environ.get("MXNET_HEALTH_DUMP_DIR",
                                     "health_dumps")
            os.makedirs(out_dir, exist_ok=True)
            flight_recorder.dump(
                "test-failure:%s" % item.nodeid,
                path=os.path.join(out_dir, "health_dump_failure_%02d.json"
                                  % _FAILURE_DUMPS["n"]))
        except Exception:
            pass  # triage must never turn one failure into two
