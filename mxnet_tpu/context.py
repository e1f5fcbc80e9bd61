"""Device context — the user-facing placement handle.

Mirrors the reference's ``python/mxnet/context.py`` (Context, cpu(), gpu(),
current_context) but resolves onto JAX devices: ``cpu(i)`` maps to host CPU
devices; ``gpu(i)`` maps to the i-th accelerator chip reported by
``jax.local_devices()`` and ``tpu(i)`` to the i-th TPU chip. On a CPU-only
test environment (JAX_PLATFORMS=cpu with
``--xla_force_host_platform_device_count=N``) ``gpu(i)`` resolves onto the
virtual CPU devices, which is exactly how the reference's multi-device
tests map ctx groups onto cpu(0)/cpu(1) (tests/python/unittest/test_multi_device_exec.py).
``tpu(i)`` never does: it names the hardware, and raises where there is none,
so a measurement that asks for the chip cannot run on the host unnoticed.
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
           "num_gpus", "DEVICE_PEAKS", "device_peaks"]

#: Published per-chip peaks, keyed by jax ``device_kind`` — the ONE table
#: utilization figures divide by (chip_smoke.py, autotune/cost_model.py,
#: observability/perf.py). A device that is not here is an error, never a
#: default.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e"'},
}

_thread_state = threading.local()


class Context:
    """Device context (reference: python/mxnet/context.py:23).

    Works as a ``with`` scope setting the default context for array creation.
    """

    # mirror the reference's devtype codes; 'tpu' gets a new code
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    @property
    def _key(self):
        return (self.device_typeid, self.device_id)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, Context) and self._key == other._key

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        _ctx_stack().append(self)
        return self

    def __exit__(self, *exc):
        _ctx_stack().pop()

    # --- JAX resolution -------------------------------------------------
    def jax_device(self):
        """The jax.Device this context resolves to.

        Contexts address LOCAL devices: in a multi-process (jax.distributed)
        job each worker's mx.cpu(0)/mx.gpu(0) is its own process-local
        device, matching the reference where each PS worker owns its own
        GPUs (kvstore_dist.h) — global devices are only touched by
        collectives."""
        import jax

        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = [d for d in jax.local_devices(backend="cpu")]
        elif self.device_type == "tpu":
            devs = [d for d in jax.local_devices() if d.platform == "tpu"]
            if not devs:
                raise MXNetError(
                    "%s: no TPU device — jax found platform %r (%s). "
                    "mx.tpu() never falls back to the host; use mx.gpu() "
                    "for the CPU test harness." % (
                        self, jax.default_backend(),
                        ", ".join(sorted({d.device_kind
                                          for d in jax.local_devices()}))))
        else:
            devs = _accelerator_devices()
        if self.device_id >= len(devs):
            raise ValueError(
                "%s out of range: only %d %s device(s) visible"
                % (self, len(devs), self.device_type)
            )
        return devs[self.device_id]


def _accelerator_devices():
    """Non-CPU jax devices, falling back to (possibly virtualized) CPU devices.

    The fallback makes gpu() contexts usable in the CPU test harness where
    --xla_force_host_platform_device_count provides N virtual devices.
    """
    import jax

    devs = [d for d in jax.local_devices() if d.platform != "cpu"]
    return devs if devs else [d for d in jax.local_devices(backend="cpu")]


def cpu(device_id=0):
    """Return a CPU context (reference: python/mxnet/context.py:131)."""
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Return an accelerator context. On this build 'gpu' is an alias for the
    TPU chip so that reference scripts written against ``mx.gpu(i)`` run
    unmodified (north-star requirement)."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """Return a TPU context. Resolves to TPU-platform devices only and
    raises where jax found none — benchmarks and ``chip_smoke.py`` use it
    so that a missing chip is an error, not a slow run on the host."""
    return Context("tpu", device_id)


def device_peaks(device_kind):
    """The :data:`DEVICE_PEAKS` row for ``device_kind``; unknown kinds
    raise (a utilization against a guessed peak is worse than none)."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise MXNetError(
            "no published peaks for device_kind %r (known: %s) — add its "
            "row, with a source, to mxnet_tpu.context.DEVICE_PEAKS"
            % (device_kind, sorted(DEVICE_PEAKS))) from None


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def num_gpus():
    """Number of accelerator chips visible (reference exposes mx.context.num_gpus
    in later versions; used by tests/examples to skip)."""
    import jax

    return len([d for d in jax.devices() if d.platform != "cpu"]) or len(
        jax.devices("cpu")
    )


def _ctx_stack():
    if not hasattr(_thread_state, "ctx_stack"):
        _thread_state.ctx_stack = []  # graftlint: disable=G003 — host ctx bookkeeping, idempotent at trace time
    return _thread_state.ctx_stack


def current_context():
    """The innermost ``with Context`` scope, else cpu(0)."""
    stack = _ctx_stack()
    return stack[-1] if stack else Context("cpu", 0)
