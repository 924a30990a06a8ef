"""Device context.

Reference: ``include/mxnet/base.h:117-228`` (Context {kCPU,kGPU,kCPUPinned} +
dev_id) and ``python/mxnet/context.py``.  TPU-native version: a Context names a
JAX device — ``cpu(i)`` maps to a host-platform device, ``tpu(i)`` to a TPU
chip.  ``gpu(i)`` is kept as an alias for the accelerator context so reference
training scripts run unchanged (on this stack "the accelerator" is the TPU).
"""
from __future__ import annotations

import os
import threading

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "tpu", "gpu", "cpu_pinned", "current_context",
           "num_devices", "require_tpu"]


class Context:
    """A device context (device type + device id).

    Contexts are cheap value objects usable as ``with`` blocks to set the
    default device, mirroring ``mx.Context`` semantics.
    """

    # dev_type codes kept numerically compatible with the reference
    # (include/mxnet/base.h: kCPU=1, kGPU=2, kCPUPinned=3); TPU gets 4.
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}
    _default_ctx = threading.local()

    __slots__ = ("device_typeid", "device_id", "_old_ctx")

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in Context.devstr2type:
                raise MXNetError("unknown device type %r" % (device_type,))
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # -- JAX mapping -------------------------------------------------------
    def jax_device(self):
        """Resolve this context to a concrete jax.Device.

        'gpu' and 'tpu' both resolve to the accelerator platform when one is
        present (the reference's device layer is swappable — base.h keeps the
        'gpu' name for whatever the accelerator is; here it is the TPU).
        An accelerator ``device_id`` past the last device raises: asking
        for more chips than exist must not train four times on chip 0.
        ``cpu(i)`` keeps wrapping — reference scripts and the tests use
        ``cpu(1)``, ``cpu(2)`` as distinct labels for host memory on a
        one-device host.
        """
        if self.device_type in ("cpu", "cpu_pinned"):
            devs = _platform_devices("cpu")
            if not devs:
                raise MXNetError("no devices available for context %s"
                                 % self)
            return devs[self.device_id % len(devs)]
        devs = _accelerator_devices()
        if not 0 <= self.device_id < len(devs):
            raise MXNetError(
                "context %s: device_id out of range, %d %s device(s) "
                "available" % (self, len(devs),
                               devs[0].platform if devs else "accelerator"))
        return devs[self.device_id]


def _platform_devices(platform):
    # local_devices: a context must never resolve to another process's
    # device (multi-process jax.distributed — arrays created through the
    # NDArray layer are per-process; only the mesh spans processes).
    # backend=platform keeps the CPU backend reachable on accelerator
    # hosts, where the default backend's local_devices has no cpu rows.
    try:
        return list(jax.local_devices(backend=platform))
    except RuntimeError:
        return []


_ACCEL_CACHE = None


def _accelerator_devices():
    """All non-host devices, falling back to host devices (so `tpu` contexts
    keep working in CPU-only test environments, the way the reference's test
    suite substitutes cpu contexts for gpus — tests/python/unittest)."""
    global _ACCEL_CACHE
    if _ACCEL_CACHE is None:
        devs = [d for d in jax.local_devices() if d.platform != "cpu"]
        _ACCEL_CACHE = devs if devs else list(jax.local_devices())
    return _ACCEL_CACHE


def require_tpu(what):
    """The first JAX device, which must be a TPU for ``what`` to go on.

    ``tpu(i)`` resolves to host devices where there is no accelerator
    (above), which suits the tests and must never pass for a
    measurement: whatever reports a rate calls this first.  The CPU is
    accepted only when asked for by name — ``JAX_PLATFORMS=cpu``, a
    plumbing run."""
    dev = jax.devices()[0]
    if dev.platform != "tpu" and \
            "cpu" not in os.environ.get("JAX_PLATFORMS", ""):
        raise MXNetError(
            "%s measures on a TPU and JAX reports %r; set "
            "JAX_PLATFORMS=cpu to ask for a CPU plumbing run"
            % (what, dev.platform))
    return dev


def cpu(device_id=0):
    """Return a CPU context."""
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    """Pinned-host-memory context (reference kCPUPinned). On TPU hosts this is
    simply host memory — PJRT stages transfers internally."""
    return Context("cpu_pinned", device_id)


def tpu(device_id=0):
    """Return a TPU context."""
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Accelerator context alias: reference scripts that say ``mx.gpu(i)`` get
    TPU chip ``i`` here (the reference itself reuses 'gpu' naming for HIP)."""
    return Context("gpu", device_id)


def num_devices(device_type="tpu"):
    if device_type in ("cpu", "cpu_pinned"):
        return len(_platform_devices("cpu"))
    return len(_accelerator_devices())


def current_context():
    """The thread-local default context (default: tpu(0) if an accelerator is
    present else cpu(0))."""
    ctx = getattr(Context._default_ctx, "value", None)
    if ctx is not None:
        return ctx
    if any(d.platform != "cpu" for d in jax.devices()):
        return tpu(0)
    return cpu(0)
