"""Device context model mapped onto JAX/PJRT devices.

Reference surface: ``python/mxnet/context.py`` (``Context``, ``cpu()``,
``gpu()``, ``current_context``).  TPU-native redesign:

- ``mx.tpu(i)`` is first-class; ``mx.gpu(i)`` is an *alias* for the i-th
  accelerator so reference-era scripts written against ``mx.gpu`` run
  unchanged on TPU.
- A ``Context`` resolves to a concrete ``jax.Device``; array placement uses
  ``jax.device_put`` and sharding machinery rather than the reference's
  per-device CUDA streams.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
           "num_gpus", "num_tpus", "gpu_memory_info", "default_jax_device"]


class Context:
    """Device context (reference: python/mxnet/context.py -> class Context)."""

    # devtype ids kept compatible with the reference enum where it exists
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}
    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise MXNetError(f"unknown device type {device_type!r}")
            self.device_typeid = self.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx: Optional[Context] = None

    # -- identity ----------------------------------------------------------
    @property
    def device_type(self) -> str:
        return self.devtype2str[self.device_typeid]

    @property
    def _canonical_typeid(self):
        # gpu is an alias for the i-th accelerator == tpu (module docstring)
        return 6 if self.device_typeid == 2 else self.device_typeid

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self._canonical_typeid == other._canonical_typeid
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self._canonical_typeid, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- JAX resolution ----------------------------------------------------
    def jax_device(self) -> "jax.Device":
        """Resolve to a concrete jax.Device.

        cpu -> a host-platform device; tpu/gpu -> the i-th accelerator
        (any non-cpu platform).
        """
        devs = _devices_for(self.device_type)
        if self.device_id >= len(devs):
            raise MXNetError(
                f"context {self} out of range: only {len(devs)} "
                f"{self.device_type} device(s) visible to JAX")
        return devs[self.device_id]

    def empty_cache(self):
        """Release cached device memory (reference: Context.empty_cache).

        PJRT owns pooling; this is a best-effort hint."""
        try:
            self.jax_device().memory_stats()
        except Exception:
            pass

    # -- scoping -----------------------------------------------------------
    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, *exc):
        Context._default_ctx.value = self._old_ctx
        return False


def default_jax_device() -> "jax.Device":
    """The device an unsharded jitted program compiles for and runs on:
    the ``jax.default_device`` in force (a pinned serving replica builds
    under one), else the default backend's first device."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.devices()[0]
    return jax.devices(dev)[0] if isinstance(dev, str) else dev


def _accel_devices():
    # local (addressable) accelerators only: device counts must agree
    # with what Context can actually address in a multi-process job
    return [d for d in jax.local_devices() if d.platform != "cpu"]


def _devices_for(device_type: str):
    # Contexts address THIS process's devices: under jax.distributed each
    # process may only touch its local (addressable) devices — global
    # jax.devices() entries from other hosts cannot back an NDArray.
    if device_type in ("cpu", "cpu_pinned", "cpu_shared"):
        try:
            return jax.local_devices(backend="cpu")
        except RuntimeError:
            # cpu platform not initialised alongside an accelerator; fall
            # back to whatever the default platform is.
            return jax.local_devices()
    accel = _accel_devices()
    if accel:
        return accel
    # No accelerator present: cpu devices stand in (e.g. the 8-device
    # virtual CPU mesh used by the test suite).
    return jax.local_devices()


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias for the i-th accelerator; on TPU machines this IS a TPU chip."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def num_gpus() -> int:
    return len(_accel_devices())


def num_tpus() -> int:
    return len(_accel_devices())


def gpu_memory_info(device_id: int = 0):
    """(free, total) bytes for the i-th accelerator, when the platform
    reports it (reference: mx.context.gpu_memory_info)."""
    dev = Context("gpu", device_id).jax_device()
    stats = dev.memory_stats() or {}
    total = stats.get("bytes_limit", 0)
    used = stats.get("bytes_in_use", 0)
    return (total - used, total)


def current_context() -> Context:
    ctx = getattr(Context._default_ctx, "value", None)
    if ctx is None:
        ctx = default_context()
    return ctx


def default_context() -> Context:
    """Accelerator if present else cpu (the bench path wants the chip)."""
    if _accel_devices():
        return Context("tpu", 0)
    return Context("cpu", 0)
