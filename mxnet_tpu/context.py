"""Device context.

Analog of the reference `Context` (include/mxnet/base.h:116-207) with a
first-class `tpu` device type beside cpu/gpu/cpu_pinned. A Context maps to
a concrete `jax.Device` of the platform it names: `tpu(n)` is TPU n of
this process or an MXNetError, never a CPU. The one exception is a
process explicitly pinned to the CPU (`JAX_PLATFORMS=cpu`, the test
tier's virtual mesh): there an accelerator context degrades to the CPU
devices so the same user code runs under test.
"""
from __future__ import annotations

import threading

import jax

from .base import MXNetError


class Context:
    """Device handle: cpu/gpu/tpu/cpu_pinned + id, backed by a jax.Device.

    The reference Context (include/mxnet/base.h:116-207) with tpu
    first-class."""
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in Context.devstr2type:
                raise MXNetError(f"unknown device type {device_type!r}")
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id

    @property
    def device_type(self) -> str:
        return Context.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    # -- jax device resolution ------------------------------------------
    def jax_device(self):
        """Resolve to the concrete jax.Device (see `resolve_device`)."""
        pinned = _pinned_to_cpu()
        platform = "cpu" if pinned else _PLATFORM_OF[self.device_type]
        return resolve_device(self.device_type, self.device_id,
                              _devices_for_platform(platform), pinned)

    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *_):
        Context._default_ctx.stack.pop()


_PLATFORM_OF = {"cpu": "cpu", "cpu_pinned": "cpu", "gpu": "gpu",
                "tpu": "tpu"}

# set once this module has asked jax for devices: from then on the XLA
# client exists and its start-up options (set_memory_fraction) are fixed
_backend_touched = False


def _pinned_to_cpu():
    """True in a process explicitly held to the CPU backend
    (JAX_PLATFORMS=cpu or the jax_platforms config): the test tier."""
    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def resolve_device(device_type, device_id, devices, pinned_to_cpu):
    """The rule that places a context, over an explicit device list:
    `devices` are the process-local devices of the context's platform
    (or, in a CPU-pinned process without that platform, the CPU
    devices).

    Host contexts (cpu/cpu_pinned) are nominal in the reference — any
    id names host memory — so their ids wrap. An accelerator context
    names a physical chip: absent platform or id beyond the local
    count raises, unless the process is pinned to the CPU, where ids
    wrap over the virtual CPU mesh."""
    platform = _PLATFORM_OF[device_type]
    have = [d for d in devices if d.platform == platform]
    if platform == "cpu" or pinned_to_cpu:
        pool = have or list(devices)
        if not pool:
            raise MXNetError(f"{device_type}({device_id}): no device")
        return pool[device_id % len(pool)]
    if not have:
        raise MXNetError(
            f"{device_type}({device_id}): this process has no "
            f"{platform} device. Set JAX_PLATFORMS=cpu to run on the "
            "host CPU deliberately.")
    if not 0 <= device_id < len(have):
        raise MXNetError(
            f"{device_type}({device_id}): this process has "
            f"{len(have)} {platform} device(s)")
    return have[device_id]


def _devices_for_platform(platform: str):
    """Process-LOCAL devices of one platform ([] when jax has no such
    backend): under jax.distributed each process may only place data
    on its own devices."""
    global _backend_touched
    _backend_touched = True
    try:
        return list(jax.local_devices(backend=platform))
    except RuntimeError:
        return []


def cpu(device_id: int = 0) -> Context:
    """CPU context."""
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """GPU context: a jax GPU device (never an alias for a TPU)."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    """TPU context."""
    return Context("tpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    """Pinned-host context (maps to cpu under jax)."""
    return Context("cpu_pinned", device_id)


def current_context() -> Context:
    """Innermost `with Context(...)` scope, else the default."""
    stack = getattr(Context._default_ctx, "stack", None)
    if stack:
        return stack[-1]
    return default_context()


def default_context() -> Context:
    """Default = tpu when jax's default backend is a TPU, else cpu."""
    global _backend_touched
    _backend_touched = True
    return Context("tpu" if jax.default_backend() == "tpu" else "cpu", 0)


def num_devices(device_type: str = "tpu") -> int:
    """Process-local device count for a device type."""
    devs = _devices_for_platform(device_type)
    return len(devs)


def set_memory_fraction(fraction, preallocate=None):
    """HBM pool sizing knob (counterpart of the reference's
    MXNET_GPU_MEM_POOL_RESERVE, src/storage/pooled_storage_manager.h:
    28-47). The XLA runtime owns the device allocator, so this maps to
    its client options — it must run BEFORE the process first resolves
    a device (any Context/NDArray use); afterwards it raises.

    Also reachable via env: MXNET_TPU_MEM_FRACTION (read at import).
    """
    import os

    if _backend_touched:
        raise MXNetError(
            "set_memory_fraction must be called before the first "
            "device use (the XLA client reads it at initialization)")
    os.environ["XLA_PYTHON_CLIENT_MEM_FRACTION"] = str(float(fraction))
    if preallocate is not None:
        os.environ["XLA_PYTHON_CLIENT_PREALLOCATE"] = (
            "true" if preallocate else "false")


def memory_stats(ctx=None):
    """Device-memory introspection (counterpart of the reference's
    pooled storage manager stats, src/storage/pooled_storage_manager.h:
    28-47 — there the pool is hand-managed; here allocation belongs to
    the XLA runtime, and this surfaces its per-device counters).

    Returns a dict (bytes_in_use, peak_bytes_in_use, bytes_limit, ...
    as provided by the PJRT backend) or {} on backends without memory
    accounting (CPU).
    """
    c = ctx if ctx is not None else current_context()
    dev = c.jax_device() if isinstance(c, Context) else c
    try:
        stats = dev.memory_stats()
    except Exception:
        return {}
    return dict(stats or {})


# MXNET_TPU_MEM_FRACTION: declarative form of set_memory_fraction
# (import-time here is before any device use in normal programs).
def _apply_mem_fraction_env():
    import os

    frac = os.environ.get("MXNET_TPU_MEM_FRACTION")
    if frac:
        os.environ.setdefault("XLA_PYTHON_CLIENT_MEM_FRACTION", frac)


_apply_mem_fraction_env()
