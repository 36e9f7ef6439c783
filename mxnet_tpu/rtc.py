"""Runtime-compiled custom kernels — the Pallas escape hatch.

Capability parity with the reference RTC (src/common/mxrtc.cc:24-133 +
python/mxnet/rtc.py: user-supplied CUDA source JIT-compiled with NVRTC
and launched on NDArrays). The TPU analog accepts a user-supplied
**Pallas kernel function** (written against jax.experimental.pallas,
the TPU kernel language) instead of CUDA source text, and launches it
on NDArrays. Same role: hand-written device code for ops the stock
library doesn't cover, without rebuilding the framework.

    import jax.numpy as jnp
    def my_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    k = mx.rtc.PallasKernel("double", my_kernel)
    y = k.push([x], out_shapes=[x.shape])     # NDArray in/out

CUDA source via `MXRtc` raises a clear error pointing here.
"""
from __future__ import annotations

import jax

from . import utils as _utils
from .base import MXNetError
from .context import current_context
from .ndarray import NDArray


class PallasKernel(object):
    """Wrap a user Pallas kernel for NDArray launch.

    kernel_fn: function taking (in_ref..., out_ref...) pallas Refs.
    Extra pallas_call options (grid, in_specs, out_specs,
    compiler_params) pass through.
    """

    def __init__(self, name, kernel_fn, **pallas_kwargs):
        self.name = name
        self.kernel_fn = kernel_fn
        self.pallas_kwargs = pallas_kwargs
        self._compiled = {}

    def compiled(self, out_shapes, out_dtypes=None):
        """The jitted pallas_call for these output shapes — compiled
        on a TPU, interpreted elsewhere (utils.pallas_interpret)."""
        from jax.experimental import pallas as pl
        import numpy as np

        interpret = _utils.pallas_interpret()
        if out_dtypes is None:
            out_dtypes = [np.float32] * len(out_shapes)
        key = (
            tuple(tuple(s) for s in out_shapes),
            tuple(str(d) for d in out_dtypes),
            bool(interpret),
        )
        fn = self._compiled.get(key)
        if fn is None:
            out_shape = [
                jax.ShapeDtypeStruct(tuple(s), d)
                for s, d in zip(out_shapes, out_dtypes)
            ]
            if len(out_shape) == 1:
                out_shape = out_shape[0]
            call = pl.pallas_call(
                self.kernel_fn,
                out_shape=out_shape,
                interpret=interpret,
                **self.pallas_kwargs,
            )
            fn = jax.jit(call)
            self._compiled[key] = fn
        return fn

    def push(self, ins, out_shapes, out_dtypes=None):
        """Launch on a list of NDArrays; returns list of NDArrays."""
        args = [a._data if isinstance(a, NDArray) else a for a in ins]
        out = self.compiled(out_shapes, out_dtypes)(*args)
        if not isinstance(out, (tuple, list)):
            out = (out,)
        ctx = current_context()
        return [NDArray(o, ctx=ctx) for o in out]


class MXRtc(object):
    """Reference-API shim: CUDA source cannot run on TPU; point users
    at PallasKernel (python/mxnet/rtc.py had __init__(name, inputs,
    outputs, kernel) + push(ins, outs, grid_dims, block_dims))."""

    def __init__(self, name, inputs, outputs, kernel):
        raise MXNetError(
            "MXRtc compiles CUDA with NVRTC and cannot target TPUs. "
            "Write the kernel with jax.experimental.pallas and wrap it "
            "in mxnet_tpu.rtc.PallasKernel instead."
        )
