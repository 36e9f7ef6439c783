"""Imperative NDArray.

Analog of the reference NDArray (include/mxnet/ndarray.h:58,
src/ndarray/ndarray.cc) + the Python frontend (python/mxnet/ndarray.py).

TPU-native mapping of the reference's async mutable-array semantics onto
immutable jax.Arrays:

- The reference `Chunk` (Storage handle + engine var) becomes a tiny
  `Chunk` holding the current jax.Array *version* of the buffer; mutation
  rebinds `chunk.data`. jax's async dispatch replaces the dependency
  engine for ordering: every op on a jax.Array is queued on the device
  stream, and `wait_to_read`/`asnumpy` are `block_until_ready`/device_get
  — the same user-visible laziness as engine `WaitToRead`
  (include/mxnet/ndarray.h:153-161).
- Views (`x[i]`, `x[a:b]` — reference At/Slice aliasing,
  ndarray.h:286-340) carry (base, index); reads recompute from base,
  writes scatter into base, so write-through aliasing is preserved
  without raw pointers.
- The op namespace (mx.nd.dot, mx.nd.FullyConnected, ...) is generated
  from the single op registry at import, the analog of the ctypes
  codegen from MXListAllOpNames (python/mxnet/_ctypes/ndarray.py).
"""
from __future__ import annotations

import struct
import sys

# Generated op functions below shadow some builtins at module level
# (slice, sum, max, min, abs, round are all op names); keep handles to the
# builtins for internal use.
_py_slice = slice

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd as _autograd
from . import profiler as _profiler
from . import random as _random
from .base import MXNetError, _auto_name
from .context import Context, cpu, current_context, default_context, gpu, tpu
from .ops import registry as _registry

_DTYPE_TO_ID = {
    np.dtype(np.float32): 0,
    np.dtype(np.float64): 1,
    np.dtype(np.float16): 2,
    np.dtype(np.uint8): 3,
    np.dtype(np.int32): 4,
    np.dtype(np.int8): 5,
    np.dtype(np.int64): 6,
    np.dtype(jnp.bfloat16): 7,
}
_ID_TO_DTYPE = {v: k for k, v in _DTYPE_TO_ID.items()}


class Chunk:
    """Holds the live jax.Array for an NDArray; rebound on mutation.

    Identity of a Chunk is the analog of the reference's engine variable
    (NDArray::var(), include/mxnet/ndarray.h:171) — the autograd tape and
    executors key buffers by chunk id."""

    __slots__ = ("data",)

    def __init__(self, data):
        self.data = data


class NDArray:
    """Mutable n-d array with imperative semantics over jax buffers.

    A version-tracked Chunk indirection gives the reference's
    imperative model (in-place ops, write-through views, engine-var
    identity, lazy asnumpy sync) on immutable XLA arrays — see
    include/mxnet/ndarray.h:58."""

    __slots__ = ("_chunk", "_base", "_index", "_ctx", "writable")

    def __init__(self, data, ctx=None, base=None, index=None, writable=True):
        self._ctx = ctx if ctx is not None else default_context()
        self._base = base
        self._index = index
        self._chunk = Chunk(data)
        self.writable = writable

    # ----------------------------------------------------------- buffer
    @property
    def _data(self):
        if self._base is not None:
            return self._base._data[self._index]
        return self.chunk_data()

    def chunk_data(self):
        return self._chunk.data

    def _set_data(self, val):
        if self._base is not None:
            base_val = self._base._data.at[self._index].set(val)
            self._base._set_data(base_val)
        else:
            self._chunk.data = val

    # ------------------------------------------------------- properties
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def size(self):
        s = 1
        for d in self.shape:
            s *= d
        return s

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def dtype(self):
        return np.dtype(self._data.dtype)

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def T(self):
        return transpose(self)

    def __len__(self):
        return self.shape[0]

    def __repr__(self):
        return f"<NDArray {'x'.join(map(str, self.shape))} @{self._ctx}>\n{self.asnumpy()}"

    # ----------------------------------------------------------- sync
    def wait_to_read(self):
        _profiler.count_host_sync("blocking_waits")
        jax.block_until_ready(self._data)

    def wait_to_write(self):
        _profiler.count_host_sync("blocking_waits")
        jax.block_until_ready(self._data)

    def asnumpy(self):
        # fresh writable copy, matching the reference's D2H copy semantics
        # (device_get can return a read-only view of the device buffer)
        _profiler.count_host_sync("blocking_fetches")
        return np.array(jax.device_get(self._data))

    def asscalar(self):
        a = self.asnumpy()
        if a.size != 1:
            raise MXNetError("The current array is not a scalar")
        return a.reshape(())[()]

    # ----------------------------------------------------------- moves
    def copyto(self, other):
        if isinstance(other, NDArray):
            if other is self:
                return other
            other._set_data(
                jax.device_put(self._data, other._ctx.jax_device()).astype(
                    other.dtype
                )
            )
            return other
        if isinstance(other, Context):
            return NDArray(
                jax.device_put(self._data, other.jax_device()), ctx=other
            )
        raise MXNetError(f"cannot copy to {other!r}")

    def copy(self):
        return NDArray(self._data + 0, ctx=self._ctx)

    def as_in_context(self, context):
        if context == self._ctx:
            return self
        return self.copyto(context)

    def astype(self, dtype):
        return NDArray(self._data.astype(np.dtype(dtype)), ctx=self._ctx)

    # ----------------------------------------------------------- views
    def __getitem__(self, key):
        if isinstance(key, int):
            return NDArray(None, ctx=self._ctx, base=self, index=key)
        if isinstance(key, _py_slice):
            if key.step not in (None, 1):
                # stepped slices are copies, not views; mark read-only so a
                # write can't silently miss the base (reference raised on
                # stepped slices, ndarray.py Slice step check)
                return NDArray(self._data[key], ctx=self._ctx,
                               writable=False)
            return NDArray(None, ctx=self._ctx, base=self, index=key)
        if isinstance(key, tuple):
            return NDArray(None, ctx=self._ctx, base=self, index=key)
        if isinstance(key, NDArray):
            return NDArray(
                self._data[key._data.astype(jnp.int32)], ctx=self._ctx
            )
        raise MXNetError(f"unsupported index {key!r}")

    def __setitem__(self, key, value):
        if not self.writable:
            raise MXNetError("array is not writable")
        if isinstance(value, NDArray):
            val = value._data
            if value._ctx != self._ctx:
                # keep the write on this array's device (reference
                # CopyFromTo handles the cross-device hop)
                val = jax.device_put(val, self._ctx.jax_device())
        elif np.isscalar(value):
            val = value
        else:
            val = jnp.asarray(np.asarray(value, dtype=self.dtype))
        full = isinstance(key, _py_slice) and key == _py_slice(None)
        if full:
            if np.isscalar(val):
                new = jnp.full(self.shape, val, self.dtype)
            else:
                new = jnp.broadcast_to(val, self.shape).astype(self.dtype)
        else:
            new = self._data.at[key].set(val)
        if _autograd.is_recording():
            _record_mutation(
                self, key,
                value if isinstance(value, NDArray) else None, val, full
            )
        self._set_data(new)

    def _at(self, idx):
        return self[idx]

    def _slice(self, start, stop):
        return self[start:stop]

    def reshape(self, shape, **kwargs):
        if isinstance(shape, int):
            shape = (shape,)
        return NDArray(jnp.reshape(self._data, shape), ctx=self._ctx)

    def broadcast_to(self, shape):
        return NDArray(jnp.broadcast_to(self._data, shape), ctx=self._ctx)

    # ------------------------------------------------------- arithmetic
    # In-place variants route through `out=self` so the mutation is a
    # recorded tape entry (sequential env update in replay), not a silent
    # buffer swap — see code-review finding on dropped `+=` gradients.
    def __add__(self, other):
        return add(self, other)

    def __radd__(self, other):
        return add(self, other)

    def __iadd__(self, other):
        return add(self, other, out=self)

    def __sub__(self, other):
        return subtract(self, other)

    def __rsub__(self, other):
        return invoke_scalar_op("_rminus_scalar", self, other)

    def __isub__(self, other):
        return subtract(self, other, out=self)

    def __mul__(self, other):
        return multiply(self, other)

    def __rmul__(self, other):
        return multiply(self, other)

    def __imul__(self, other):
        return multiply(self, other, out=self)

    def __div__(self, other):
        return divide(self, other)

    def __truediv__(self, other):
        return divide(self, other)

    def __rdiv__(self, other):
        return invoke_scalar_op("_rdiv_scalar", self, other)

    def __rtruediv__(self, other):
        return invoke_scalar_op("_rdiv_scalar", self, other)

    def __idiv__(self, other):
        return divide(self, other, out=self)

    __itruediv__ = __idiv__

    def __mod__(self, other):
        return modulo(self, other)

    def __rmod__(self, other):
        return invoke_scalar_op("_rmod_scalar", self, other)

    def __pow__(self, other):
        return power(self, other)

    def __rpow__(self, other):
        return invoke_scalar_op("_rpower_scalar", self, other)

    def __neg__(self):
        return _invoke_by_name("negative", [self], {})

    def __abs__(self):
        return _invoke_by_name("abs", [self], {})

    def __eq__(self, other):
        return _cmp(self, other, "_equal", "_equal_scalar")

    def __ne__(self, other):
        return _cmp(self, other, "_not_equal", "_not_equal_scalar")

    def __gt__(self, other):
        return _cmp(self, other, "_greater", "_greater_scalar")

    def __ge__(self, other):
        return _cmp(self, other, "_greater_equal", "_greater_equal_scalar")

    def __lt__(self, other):
        return _cmp(self, other, "_lesser", "_lesser_scalar")

    def __le__(self, other):
        return _cmp(self, other, "_lesser_equal", "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    def __bool__(self):
        return bool(self.asnumpy().all())

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __getstate__(self):
        return {"data": self.asnumpy(), "ctx_type": self._ctx.device_type,
                "ctx_id": self._ctx.device_id}

    def __setstate__(self, state):
        ctx = Context(state["ctx_type"], state["ctx_id"])
        self._ctx = ctx
        self._base = None
        self._index = None
        self._chunk = Chunk(jnp.asarray(state["data"]))
        self.writable = True


# ---------------------------------------------------------------- invoke


def invoke(opdef, inputs, params, out=None):
    """Imperative dispatch of a registered op (analog of
    MXImperativeInvoke, src/c_api/c_api_ndarray.cc:322)."""
    params = opdef.normalize_params(params)
    kwargs = {}
    rng = None
    if opdef.needs_rng:
        rng = _random.next_key()
        kwargs["rng"] = rng
    if opdef.needs_mode:
        kwargs["is_train"] = _autograd.is_training()
    in_vals = [x._data for x in inputs]
    res = opdef.fn(*in_vals, **params, **kwargs)
    if not isinstance(res, tuple):
        res = (res,)
    ctx = inputs[0]._ctx if inputs else _params_ctx(params)
    n_out = opdef.resolved_num_outputs(params)
    n_aux = len(opdef.aux_names)

    # Write functional aux updates back into the trailing aux inputs —
    # restores the reference's mutable aux_states semantics imperatively.
    if n_aux and kwargs.get("is_train") and len(res) > n_out:
        aux_inputs = inputs[-n_aux:]
        for aux_nd, new_val in zip(aux_inputs, res[n_out:]):
            aux_nd._set_data(new_val)
    res = res[:n_out]

    outputs = []
    if out is not None:
        outs = out if isinstance(out, (list, tuple)) else [out]
        for o, val in zip(outs, res):
            o._set_data(val.astype(o.dtype) if o.dtype != val.dtype else val)
            outputs.append(o)
    else:
        outputs = [NDArray(val, ctx=ctx) for val in res]

    if _autograd.is_recording():
        _autograd.record_op(
            opdef, params, inputs, outputs, rng=rng, input_values=in_vals
        )

    if len(outputs) == 1:
        return outputs[0]
    return outputs


def _record_mutation(target, key, value_nd, raw_val, full):
    """Record an NDArray.__setitem__ as a synthetic tape op so gradients
    flow through imperative mutation (analog of the reference engine
    tracking write-vars)."""
    from .ops.registry import OpDef

    if value_nd is not None:
        if full:
            fn = lambda base, v: jnp.broadcast_to(v, base.shape).astype(
                base.dtype
            )
        else:
            fn = lambda base, v, _k=key: base.at[_k].set(v)
        inputs = [target, value_nd]
    else:
        if full:
            fn = lambda base, _v=raw_val: jnp.full(base.shape, _v, base.dtype)
        else:
            fn = lambda base, _k=key, _v=raw_val: base.at[_k].set(_v)
        inputs = [target]
    opdef = OpDef(name="_setitem", fn=fn)
    _autograd.record_op(
        opdef, {}, inputs, [target],
        input_values=[x._data for x in inputs],
    )


def _params_ctx(params):
    ctx = params.get("ctx")
    if isinstance(ctx, Context):
        return ctx
    if isinstance(ctx, str):
        # 'cpu(0)' / 'tpu(0)' string form from symbol attrs
        name, _, rest = ctx.partition("(")
        return Context(name, int(rest.rstrip(")") or 0))
    return current_context()


def _invoke_by_name(name, inputs, params, out=None):
    return invoke(_registry.get(name), inputs, params, out)


def invoke_scalar_op(name, data, scalar, out=None):
    return _invoke_by_name(name, [data], {"scalar": float(scalar)}, out)


def _binary_dispatch(lhs, rhs, elem_op, scalar_op, rscalar_op=None,
                     out=None):
    if isinstance(lhs, NDArray) and isinstance(rhs, NDArray):
        return _invoke_by_name(elem_op, [lhs, rhs], {}, out)
    if isinstance(lhs, NDArray):
        return invoke_scalar_op(scalar_op, lhs, rhs, out)
    if isinstance(rhs, NDArray):
        if rscalar_op is None:
            return invoke_scalar_op(scalar_op, rhs, lhs, out)
        return invoke_scalar_op(rscalar_op, rhs, lhs, out)
    raise MXNetError("expected at least one NDArray operand")


def add(lhs, rhs, out=None):
    return _binary_dispatch(lhs, rhs, "elemwise_add", "_plus_scalar",
                            out=out)


def subtract(lhs, rhs, out=None):
    return _binary_dispatch(
        lhs, rhs, "elemwise_sub", "_minus_scalar", "_rminus_scalar", out=out
    )


def multiply(lhs, rhs, out=None):
    return _binary_dispatch(lhs, rhs, "elemwise_mul", "_mul_scalar",
                            out=out)


def divide(lhs, rhs, out=None):
    return _binary_dispatch(
        lhs, rhs, "elemwise_div", "_div_scalar", "_rdiv_scalar", out=out
    )


def modulo(lhs, rhs, out=None):
    return _binary_dispatch(lhs, rhs, "_mod", "_mod_scalar", "_rmod_scalar",
                            out=out)


def power(base, exp, out=None):
    return _binary_dispatch(
        base, exp, "_power", "_power_scalar", "_rpower_scalar", out=out
    )


def maximum(lhs, rhs, out=None):
    return _binary_dispatch(lhs, rhs, "_maximum", "_maximum_scalar",
                            out=out)


def minimum(lhs, rhs, out=None):
    return _binary_dispatch(lhs, rhs, "_minimum", "_minimum_scalar",
                            out=out)


def _cmp(lhs, rhs, elem_op, scalar_op):
    if isinstance(rhs, NDArray):
        return _invoke_by_name(elem_op, [lhs, rhs], {})
    return invoke_scalar_op(scalar_op, lhs, rhs)


# -------------------------------------------------------------- creation


def array(source_array, ctx=None, dtype=None):
    if isinstance(source_array, NDArray):
        src = source_array.asnumpy()
    else:
        src = np.asarray(source_array)
    if dtype is None:
        dtype = src.dtype if src.dtype != np.float64 else np.float32
    ctx = ctx or current_context()
    data = jax.device_put(src.astype(np.dtype(dtype)), ctx.jax_device())
    return NDArray(data, ctx=ctx)


def empty(shape, ctx=None, dtype=np.float32):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=np.float32):
    ctx = ctx or current_context()
    with jax.default_device(ctx.jax_device()):
        return NDArray(jnp.zeros(shape, np.dtype(dtype)), ctx=ctx)


def ones(shape, ctx=None, dtype=np.float32):
    ctx = ctx or current_context()
    with jax.default_device(ctx.jax_device()):
        return NDArray(jnp.ones(shape, np.dtype(dtype)), ctx=ctx)


def full(shape, val, ctx=None, dtype=np.float32):
    ctx = ctx or current_context()
    with jax.default_device(ctx.jax_device()):
        return NDArray(jnp.full(shape, val, np.dtype(dtype)), ctx=ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None,
           dtype=np.float32):
    ctx = ctx or current_context()
    with jax.default_device(ctx.jax_device()):
        out = jnp.arange(start, stop, step, np.dtype(dtype))
        if repeat > 1:
            out = jnp.repeat(out, repeat)
        return NDArray(out, ctx=ctx)


def zeros_like(other):
    return zeros(other.shape, ctx=other._ctx, dtype=other.dtype)


def ones_like_nd(other):
    return ones(other.shape, ctx=other._ctx, dtype=other.dtype)


def moveaxis(tensor, source, destination):
    return NDArray(
        jnp.moveaxis(tensor._data, source, destination), ctx=tensor._ctx
    )


def transpose(data, axes=None):
    return _invoke_by_name("transpose", [data], {"axes": axes or ()})


def concatenate(arrays, axis=0, always_copy=True):
    if len(arrays) == 1 and not always_copy:
        return arrays[0]
    return _invoke_by_name("Concat", list(arrays), {"dim": axis})


def onehot_encode(indices, out):
    depth = out.shape[1]
    return _invoke_by_name("one_hot", [indices], {"depth": depth}, out=out)


def waitall():
    # jax dispatch is per-array; effectful waits happen on access. This
    # mirrors Engine::WaitForAll for API parity.
    jax.effects_barrier()


# ----------------------------------------------------------- save / load

_FILE_MAGIC = 0x112  # kMXAPINDArrayListMagic (src/c_api/c_api.cc)
_ND_MAGIC = 0xF993FAC9  # NDArray binary chunk magic


def save(fname, data):
    """Save NDArrays in a reference-style binary container
    (src/ndarray/ndarray.cc:605 Save/Load): magic + reserved + arrays +
    names. Types/shapes round-trip; usable for prefix-%04d.params files."""
    if isinstance(data, NDArray):
        data, keys = [data], []
    elif isinstance(data, dict):
        keys = list(data.keys())
        data = list(data.values())
    else:
        keys = []
        data = list(data)
    with open(fname, "wb") as f:
        f.write(struct.pack("<QQ", _FILE_MAGIC, 0))
        f.write(struct.pack("<Q", len(data)))
        for nd in data:
            arr = nd.asnumpy()
            dtid = _DTYPE_TO_ID[np.dtype(arr.dtype)]
            f.write(struct.pack("<I", _ND_MAGIC))
            f.write(struct.pack("<I", arr.ndim))
            f.write(struct.pack(f"<{arr.ndim}q", *arr.shape))
            f.write(struct.pack("<ii", nd.context.device_typeid, nd.context.device_id))
            f.write(struct.pack("<i", dtid))
            raw = arr.tobytes()
            f.write(struct.pack("<Q", len(raw)))
            f.write(raw)
        f.write(struct.pack("<Q", len(keys)))
        for k in keys:
            kb = k.encode("utf-8")
            f.write(struct.pack("<Q", len(kb)))
            f.write(kb)


def load_frombuffer(buf):
    """Load NDArrays from an in-memory container (reference
    MXNDArrayLoadFromBuffer, src/c_api/c_api.cc)."""
    import tempfile

    with tempfile.NamedTemporaryFile(suffix=".params") as tf:
        tf.write(buf)
        tf.flush()
        return load(tf.name)


def load(fname):
    with open(fname, "rb") as f:
        magic, _ = struct.unpack("<QQ", f.read(16))
        if magic != _FILE_MAGIC:
            raise MXNetError(f"invalid NDArray file {fname!r}")
        (n,) = struct.unpack("<Q", f.read(8))
        arrays = []
        for _ in range(n):
            (nd_magic,) = struct.unpack("<I", f.read(4))
            if nd_magic != _ND_MAGIC:
                raise MXNetError("corrupt NDArray chunk")
            (ndim,) = struct.unpack("<I", f.read(4))
            shape = struct.unpack(f"<{ndim}q", f.read(8 * ndim))
            devtype, devid = struct.unpack("<ii", f.read(8))
            (dtid,) = struct.unpack("<i", f.read(4))
            (nbytes,) = struct.unpack("<Q", f.read(8))
            arr = np.frombuffer(f.read(nbytes), dtype=_ID_TO_DTYPE[dtid])
            arrays.append(array(arr.reshape(shape), dtype=arr.dtype))
        (nk,) = struct.unpack("<Q", f.read(8))
        keys = []
        for _ in range(nk):
            (klen,) = struct.unpack("<Q", f.read(8))
            keys.append(f.read(klen).decode("utf-8"))
    if keys:
        return dict(zip(keys, arrays))
    return arrays


# ---------------------------------------------- generated op namespace


def _op_param_order(opdef):
    """Ordered non-input parameter names from the registered fn's
    signature, so positional params (e.g. nd.uniform(0, 1), nd.clip(x,
    -1, 1)) map correctly instead of being dropped."""
    import inspect

    input_names = set(opdef.arg_names or ()) | set(opdef.aux_names)
    skip = input_names | {"rng", "is_train"}
    order = []
    try:
        sig = inspect.signature(opdef.fn)
    except (TypeError, ValueError):
        return order
    for p in sig.parameters.values():
        if p.kind in (p.VAR_POSITIONAL, p.VAR_KEYWORD):
            continue
        if p.name in skip:
            continue
        order.append(p.name)
    return order


def _op_doc(opdef, func_name, flavor):
    """Docstring for a generated op function: the registered fn's doc
    when present, else a synthesized signature summary."""
    doc = opdef.fn.__doc__
    ins = ", ".join(opdef.arg_names or ("*inputs",))
    params = sorted(set(opdef.coerce) | set(opdef.defaults))
    lines = [doc.strip()] if doc else [f"{opdef.name} operator."]
    lines.append("")
    lines.append(f"{flavor} form. Inputs: {ins}.")
    if params:
        lines.append(f"Params: {', '.join(params)}.")
    if opdef.aux_names:
        lines.append(f"Aux states: {', '.join(opdef.aux_names)}.")
    alias = [a for a in (opdef.aliases or ()) if a != func_name]
    if alias:
        lines.append(f"Also available as: {', '.join(alias)}.")
    return "\n".join(lines)


def _make_op_function(opdef, func_name):
    input_names = tuple(opdef.arg_names or ()) + tuple(opdef.aux_names)
    param_order = _op_param_order(opdef)

    def op_func(*args, **kwargs):
        out = kwargs.pop("out", None)
        kwargs.pop("name", None)
        inputs = []
        params = {}
        free_params = [p for p in param_order if p not in kwargs]
        fp = iter(free_params)
        for a in args:
            if isinstance(a, NDArray):
                inputs.append(a)
            else:
                pname = next(fp, None)
                if pname is None:
                    raise MXNetError(
                        f"{func_name}: too many positional arguments"
                    )
                params[pname] = a
        by_name = {}
        for k, v in kwargs.items():
            if isinstance(v, NDArray):
                if k in input_names:
                    by_name[k] = v
                else:
                    raise MXNetError(
                        f"{func_name}: unexpected NDArray kwarg {k!r}"
                    )
            else:
                params[k] = v
        if by_name:
            merged = []
            pos = iter(inputs)
            for an in input_names:
                if an in by_name:
                    merged.append(by_name[an])
                else:
                    nxt = next(pos, None)
                    if nxt is not None:
                        merged.append(nxt)
            inputs = merged
        return invoke(opdef, inputs, params, out=out)

    op_func.__name__ = func_name
    op_func.__doc__ = _op_doc(opdef, func_name, "Imperative")
    return op_func


_this = sys.modules[__name__]
for _name in _registry.list_ops():
    _opdef = _registry.get(_name)
    if not hasattr(_this, _name):
        setattr(_this, _name, _make_op_function(_opdef, _name))

# convenience aliases matching python/mxnet/ndarray.py public names
ones_like = getattr(_this, "ones_like")
true_divide = divide
negative = lambda arr: -arr


def imdecode(str_img, clip_rect=(0, 0, 0, 0), out=None, index=0,
             channels=3, mean=None):
    """Decode an image bytestring (reference src/io/image_io.cc imdecode
    NDArray op). Uses PIL/cv2 on host; TPU gets the decoded tensor."""
    from .image import imdecode as _imdecode

    return _imdecode(str_img, to_rgb=True)
