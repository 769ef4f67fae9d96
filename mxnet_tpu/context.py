"""Device context (reference: python/mxnet/context.py, include/mxnet/base.h:116-227).

The reference's ``Context`` names a (device_type, device_id) pair and every NDArray /
Executor is pinned to one. On TPU the natural device set is ``jax.devices()``; we map

* ``mx.cpu(i)``  -> host platform device i (or a virtual CPU device when running
  under ``--xla_force_host_platform_device_count``, which is how multi-device tests
  emulate a pod slice — the analog of the reference's CPU-fake-device trick in
  tests/python/unittest/test_multi_device_exec.py:20-33),
* ``mx.tpu(i)``  -> TPU chip i,
* ``mx.gpu(i)``  -> alias for ``mx.tpu(i)`` so reference example scripts that say
  ``ctx=[mx.gpu(k) for k in range(n)]`` run unmodified on a TPU host.
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context"]


class Context:
    """A device context. With-scope semantics match the reference
    (python/mxnet/context.py:24-93): ``with mx.Context('tpu', 1): ...``.
    """

    _default_ctx = threading.local()

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}

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

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __repr__(self):
        return self.__str__()

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # --- jax integration -------------------------------------------------
    @property
    def jax_device(self):
        """Resolve this context to a concrete jax device."""
        import jax

        if self.device_type in ("cpu", "cpu_pinned"):
            devs = [d for d in jax.devices() if d.platform == "cpu"]
            if not devs:
                devs = jax.devices("cpu")
            return devs[self.device_id % len(devs)]
        # tpu / gpu alias: a chip, or an error. Host devices stand in only
        # where the caller pinned jax to the CPU platform (the test rig's
        # JAX_PLATFORMS=cpu with virtual devices) — never because a chip
        # failed to show up.
        devs = [d for d in jax.devices() if d.platform != "cpu"]
        if not devs and _pinned_to_cpu():
            devs = jax.devices()
            return devs[self.device_id % len(devs)]
        if not 0 <= self.device_id < len(devs):
            raise MXNetError(
                "%s: jax sees %d accelerator device(s) (%s)" % (
                    self, len(devs),
                    ", ".join(str(d) for d in jax.devices())))
        return devs[self.device_id]


def _pinned_to_cpu():
    """True when the caller restricted jax to the CPU platform
    (``JAX_PLATFORMS=cpu`` or ``jax.config.update("jax_platforms", "cpu")``)."""
    import jax

    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def _default_value():
    v = getattr(Context._default_ctx, "value", None)
    if v is None:
        v = Context("cpu", 0)
        Context._default_ctx.value = v
    return v


def cpu(device_id=0):
    """Return a CPU context (reference: python/mxnet/context.py:95)."""
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Alias of :func:`tpu` — keeps reference scripts using mx.gpu() runnable."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """Return a TPU context for chip ``device_id``."""
    return Context("tpu", device_id)


def current_context():
    """Return the current context in the with-scope stack (default cpu(0))."""
    return _default_value()


def num_tpus():
    """Number of attached accelerator chips (0 on CPU-only hosts) — the
    analog of the reference's mx.context counting via cudaGetDeviceCount.
    A backend that fails to initialise raises: a chip that is expected and
    absent must not read as "none attached"."""
    import jax

    return len([d for d in jax.devices() if d.platform != "cpu"])


def auto(device_id=0):
    """Best available context: ``tpu(device_id)`` when a chip is visible,
    else ``cpu(device_id)``. Not in the reference (its scripts take --gpus);
    the examples use this to pick the accelerator automatically."""
    return (Context("tpu", device_id) if num_tpus()
            else Context("cpu", device_id))


def num_gpus():
    """Reference-script compatibility alias for :func:`num_tpus`."""
    return num_tpus()
