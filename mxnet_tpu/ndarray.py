"""Imperative NDArray API (``mx.nd``).

Reference: ``include/mxnet/ndarray.h`` + ``src/ndarray/ndarray.cc`` +
``python/mxnet/ndarray.py`` (SURVEY §2.1/§2.6).

TPU-native design: an NDArray owns a ``jax.Array`` (a PJRT buffer on the
context's device).  The reference's async engine semantics map 1:1 onto
JAX/PJRT async dispatch — every op returns immediately with a future-backed
buffer, and ``asnumpy()``/``wait_to_read()`` are the sync points (reference
``NDArray::WaitToRead`` ``ndarray.h:126``; here ``block_until_ready``).
Dependency ordering needs no engine: data dependencies ARE the XLA/PJRT
dataflow.  Mutation (``a[:] = x``, ``+=``) rebinds the underlying buffer,
which matches the reference's write-var semantics for every reader that goes
through the NDArray object.

The ``mx.nd.<op>`` functions are generated from the op registry at import —
the analog of ``_init_ndarray_module`` (``python/mxnet/_ctypes/ndarray.py:155``)
generating functions from the C op registry.  Each call dispatches through a
jit-cached XLA computation (``ops/registry.py:jitted_apply``).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from . import profiler as _profiler
from . import random as _random
from . import tracing as _tracing
from .base import MXNetError
from .context import Context, current_context
from .ops import registry as _reg
from .ops.matrix import _infer_reshape

__all__ = ["NDArray", "array", "zeros", "ones", "empty", "full", "arange",
           "concatenate", "load", "save", "imdecode", "onehot_encode", "waitall"]

# generated op functions shadow some builtins at module level (nd.slice,
# nd.sum, ...) — keep safe references for use inside this module
_py_slice = slice


def _np_dtype(dtype):
    if dtype is None:
        return np.float32
    if str(dtype) == "bfloat16":
        return jnp.bfloat16
    return np.dtype(dtype)


class NDArray:
    """A tensor on a device context, with async-dispatch semantics."""

    __slots__ = ["_jx", "_ctx"]
    # numpy should defer to our reflected ops
    __array_priority__ = 100.0

    def __init__(self, data, ctx=None, dtype=None):
        if isinstance(data, NDArray):
            self._jx = data._jx
            self._ctx = ctx or data._ctx
            return
        ctx = ctx or current_context()
        arr = np.asarray(data, dtype=_np_dtype(dtype) if dtype else None)
        if arr.dtype == np.float64:
            arr = arr.astype(np.float32)
        self._jx = jax.device_put(arr, ctx.jax_device())
        self._ctx = ctx

    def _transfer_src(self):
        """What the executor should hand to ``jax.device_put`` when this
        array feeds a bound input — overridden by host-backed arrays to
        expose the raw numpy buffer (one host→device copy, no staging)."""
        return self._jx

    @staticmethod
    def _from_jax(jx, ctx=None):
        out = NDArray.__new__(NDArray)
        out._jx = jx
        if ctx is None:
            plat = jx.devices().pop().platform if hasattr(jx, "devices") else "cpu"
            ctx = Context("cpu" if plat == "cpu" else "tpu", 0)
        out._ctx = ctx
        return out

    # -- properties -------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._jx.shape)

    @property
    def dtype(self):
        dt = self._jx.dtype
        return dt.type if hasattr(dt, "type") and dt.names is None else dt

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self):
        return self._jx.ndim

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def T(self):
        return NDArray._from_jax(self._jx.T, self._ctx)

    # -- sync points ------------------------------------------------------
    def asnumpy(self):
        """Blocking copy to host (reference ``ndarray.py`` asnumpy; the sync
        point, like WaitToRead + CopyDeviceToCPU)."""
        with _tracing.host_read("asnumpy"):
            return np.asarray(self._jx)

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def wait_to_read(self):
        with _tracing.host_read("wait_to_read"):
            self._jx.block_until_ready()

    wait_to_write = wait_to_read

    # -- conversions / movement ------------------------------------------
    def astype(self, dtype):
        return NDArray._from_jax(self._jx.astype(_np_dtype(dtype)), self._ctx)

    def copy(self):
        return NDArray._from_jax(self._jx + 0, self._ctx)

    def copyto(self, other):
        """reference ``ndarray.py`` copyto(Context|NDArray)"""
        if isinstance(other, Context):
            return NDArray._from_jax(
                jax.device_put(self._jx, other.jax_device()), other)
        if isinstance(other, NDArray):
            if other.shape != self.shape:
                raise MXNetError("copyto: shape mismatch %s vs %s"
                                 % (self.shape, other.shape))
            # preserve the destination's (possibly mesh-) sharding so copies
            # into globally-placed arrays stay global
            other._jx = jax.device_put(self._jx.astype(other._jx.dtype),
                                       other._jx.sharding)
            return other
        raise TypeError("copyto does not support type " + str(type(other)))

    def as_in_context(self, context):
        if context == self._ctx:
            return self
        return self.copyto(context)

    def detach(self):
        return NDArray._from_jax(jax.lax.stop_gradient(self._jx), self._ctx)

    # -- shape ops --------------------------------------------------------
    def reshape(self, shape, **kwargs):
        if isinstance(shape, int):
            shape = (shape,)
        return NDArray._from_jax(
            self._jx.reshape(_infer_reshape(tuple(shape), self.shape)), self._ctx)

    def broadcast_to(self, shape):
        return NDArray._from_jax(jnp.broadcast_to(self._jx, shape), self._ctx)

    def expand_dims(self, axis):
        return NDArray._from_jax(jnp.expand_dims(self._jx, axis), self._ctx)

    def flatten(self):
        return NDArray._from_jax(self._jx.reshape(self.shape[0], -1), self._ctx)

    def transpose(self, axes=None):
        return NDArray._from_jax(jnp.transpose(self._jx, axes), self._ctx)

    def slice_axis(self, axis, begin, end):
        idx = [_py_slice(None)] * self.ndim
        idx[axis] = _py_slice(begin, end)
        return NDArray._from_jax(self._jx[tuple(idx)], self._ctx)

    # -- indexing ---------------------------------------------------------
    def _idx(self, key):
        if isinstance(key, NDArray):
            return key._jx
        if isinstance(key, tuple):
            return tuple(k._jx if isinstance(k, NDArray) else k for k in key)
        return key

    def __getitem__(self, key):
        return NDArray._from_jax(self._jx[self._idx(key)], self._ctx)

    def __setitem__(self, key, value):
        v = value._jx if isinstance(value, NDArray) else value
        if isinstance(key, _py_slice) and key == _py_slice(None):
            if np.isscalar(v):
                self._jx = jnp.full_like(self._jx, v)
            else:
                self._jx = jnp.broadcast_to(
                    jnp.asarray(v, self._jx.dtype), self.shape)
                self._jx = jax.device_put(self._jx, self._ctx.jax_device())
        else:
            self._jx = self._jx.at[self._idx(key)].set(v)

    def __len__(self):
        return self.shape[0]

    def __iter__(self):
        for i in range(self.shape[0]):
            yield self[i]

    # -- arithmetic -------------------------------------------------------
    def _binop(self, other, fn):
        o = other._jx if isinstance(other, NDArray) else other
        return NDArray._from_jax(fn(self._jx, o), self._ctx)

    def __add__(self, o):
        return self._binop(o, jnp.add)

    __radd__ = __add__

    def __sub__(self, o):
        return self._binop(o, jnp.subtract)

    def __rsub__(self, o):
        return self._binop(o, lambda a, b: jnp.subtract(b, a))

    def __mul__(self, o):
        return self._binop(o, jnp.multiply)

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binop(o, jnp.divide)

    __div__ = __truediv__

    def __rtruediv__(self, o):
        return self._binop(o, lambda a, b: jnp.divide(b, a))

    __rdiv__ = __rtruediv__

    def __pow__(self, o):
        return self._binop(o, jnp.power)

    def __mod__(self, o):
        return self._binop(o, jnp.mod)

    def __neg__(self):
        return NDArray._from_jax(-self._jx, self._ctx)

    def __abs__(self):
        return NDArray._from_jax(jnp.abs(self._jx), self._ctx)

    def __iadd__(self, o):
        self._jx = self._binop(o, jnp.add)._jx
        return self

    def __isub__(self, o):
        self._jx = self._binop(o, jnp.subtract)._jx
        return self

    def __imul__(self, o):
        self._jx = self._binop(o, jnp.multiply)._jx
        return self

    def __itruediv__(self, o):
        self._jx = self._binop(o, jnp.divide)._jx
        return self

    def _cmp(self, o, fn):
        return self._binop(o, lambda a, b: fn(a, b).astype(a.dtype))

    def __eq__(self, o):
        if o is None:
            return False
        return self._cmp(o, jnp.equal)

    def __ne__(self, o):
        if o is None:
            return True
        return self._cmp(o, jnp.not_equal)

    def __gt__(self, o):
        return self._cmp(o, jnp.greater)

    def __ge__(self, o):
        return self._cmp(o, jnp.greater_equal)

    def __lt__(self, o):
        return self._cmp(o, jnp.less)

    def __le__(self, o):
        return self._cmp(o, jnp.less_equal)

    __hash__ = object.__hash__

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("ambiguous truth value of multi-element NDArray")

    def __repr__(self):
        return "<NDArray %s @%s>\n%s" % (
            "x".join(str(s) for s in self.shape), self._ctx, self.asnumpy())

    # -- persistence hooks ------------------------------------------------
    def __getstate__(self):
        return {"data": self.asnumpy(), "ctx_type": self._ctx.device_typeid,
                "ctx_id": self._ctx.device_id}

    def __setstate__(self, st):
        ctx = Context(st["ctx_type"], st["ctx_id"])
        try:
            dev = ctx.jax_device()
        except Exception:
            ctx = Context("cpu", 0)
            dev = ctx.jax_device()
        self._jx = jax.device_put(st["data"], dev)
        self._ctx = ctx


# ---------------------------------------------------------------------------
# creation functions (reference python/mxnet/ndarray.py factory fns)
# ---------------------------------------------------------------------------
def array(source_array, ctx=None, dtype=None):
    return NDArray(source_array, ctx=ctx, dtype=dtype)


class _HostNDArray(NDArray):
    """Iterator fast-path NDArray: numpy-backed until first real use.

    ``_jx`` materializes (``device_put`` onto ``_ctx``) the moment any
    NDArray semantics are exercised — arithmetic, slicing, ``copyto``,
    ``wait_to_read`` — so the full NDArray contract holds.  The one
    consumer that must NOT trigger materialization is the executor's
    input transfer (``_transfer_src``), which moves the raw buffer
    host→device in a single copy.  ``asnumpy`` on the un-materialized
    buffer returns a COPY, preserving the "asnumpy is never aliased"
    contract while the executor may still read the original buffer.
    """

    __slots__ = []

    @property
    def _jx(self):
        v = NDArray._jx.__get__(self)
        if isinstance(v, np.ndarray):
            v = jax.device_put(v, self._ctx.jax_device())
            NDArray._jx.__set__(self, v)
        return v

    @_jx.setter
    def _jx(self, v):
        NDArray._jx.__set__(self, v)

    def _transfer_src(self):
        return NDArray._jx.__get__(self)  # raw buffer; no materialization

    # shape/dtype inspection must not force materialization (Module
    # checks provide_data shapes on every batch)
    @property
    def shape(self):
        return tuple(NDArray._jx.__get__(self).shape)

    @property
    def dtype(self):
        dt = NDArray._jx.__get__(self).dtype
        return dt.type if hasattr(dt, "type") and dt.names is None else dt

    @property
    def ndim(self):
        return NDArray._jx.__get__(self).ndim

    def asnumpy(self):
        v = NDArray._jx.__get__(self)
        if isinstance(v, np.ndarray):
            return v.copy()
        with _tracing.host_read("asnumpy"):
            return np.asarray(v)

    def wait_to_read(self):
        v = NDArray._jx.__get__(self)
        if not isinstance(v, np.ndarray):
            with _tracing.host_read("wait_to_read"):
                v.block_until_ready()

    wait_to_write = wait_to_read


def from_host(source_array, ctx=None):
    """Wrap a freshly-allocated host numpy array WITHOUT the staging copy.

    The returned NDArray carries the numpy buffer as-is until first use;
    the executor's input ``device_put`` moves it host→device directly
    (one copy total, instead of numpy→CPU-jax→device).  This is the
    data-iterator fast path — a 128×3×224×224 f32 batch is 77 MB, and
    ``jax.device_put`` to the CPU backend costs ~0.3 ms/img of pure
    memcpy the training device never needed.

    Contract: the caller must NOT mutate ``source_array`` after wrapping
    (iterators allocate a fresh batch buffer per ``next()``).
    """
    arr = np.asarray(source_array)
    if arr.dtype == np.float64:
        arr = arr.astype(np.float32)
    out = _HostNDArray.__new__(_HostNDArray)
    out._jx = arr
    out._ctx = ctx or Context("cpu", 0)
    return out


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None):
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray._from_jax(
        jax.device_put(jnp.zeros(shape, _np_dtype(dtype)), ctx.jax_device()), ctx)


def ones(shape, ctx=None, dtype=None):
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray._from_jax(
        jax.device_put(jnp.ones(shape, _np_dtype(dtype)), ctx.jax_device()), ctx)


def full(shape, val, ctx=None, dtype=None):
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray._from_jax(
        jax.device_put(jnp.full(shape, val, _np_dtype(dtype)), ctx.jax_device()),
        ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype="float32"):
    ctx = ctx or current_context()
    a = jnp.arange(start, stop, step, dtype=_np_dtype(dtype))
    if repeat > 1:
        a = jnp.repeat(a, repeat)
    return NDArray._from_jax(jax.device_put(a, ctx.jax_device()), ctx)


def concatenate(arrays, axis=0, always_copy=True):
    return NDArray._from_jax(
        jnp.concatenate([a._jx for a in arrays], axis=axis), arrays[0]._ctx)


def onehot_encode(indices, out):
    """legacy ``_onehot_encode`` (``ndarray.cc:748-867``)"""
    depth = out.shape[1]
    out._jx = jax.nn.one_hot(indices._jx.astype(jnp.int32), depth,
                             dtype=out._jx.dtype)
    return out


def imdecode(str_img, clip_rect=(0, 0, 0, 0), out=None, index=0, channels=3,
             mean=None):
    """Decode an image buffer (reference ``_imdecode``), via PIL when
    present, else OpenCV (always available in this framework)."""
    import io as _io

    buf = str_img if isinstance(str_img, bytes) else str_img.encode()
    try:
        from PIL import Image

        img = Image.open(_io.BytesIO(buf))
        arr = np.asarray(img.convert("RGB" if channels == 3 else "L"),
                         dtype=np.float32)
    except ImportError:
        from .image import imdecode as _cv_imdecode

        arr = _cv_imdecode(buf, flag=1 if channels == 3 else 0)
        arr = np.asarray(arr, np.float32)
    if arr.ndim == 2:
        arr = arr[:, :, None]
    arr = arr.transpose(2, 0, 1)[None]
    x0, y0, x1, y1 = clip_rect
    if (x0, y0, x1, y1) != (0, 0, 0, 0):
        height, width = arr.shape[2], arr.shape[3]
        if not (0 <= x0 < x1 <= width and 0 <= y0 < y1 <= height):
            raise MXNetError(
                "imdecode: clip_rect %r out of bounds for %dx%d image"
                % (clip_rect, width, height))
        arr = arr[:, :, y0:y1, x0:x1]
    if mean is not None:
        arr = arr - mean.asnumpy()
    res = array(arr)
    if out is not None:
        if not 0 <= index < out.shape[0]:
            raise MXNetError("imdecode: index %d out of range for out with "
                             "batch %d" % (index, out.shape[0]))
        if res.shape[1:] != out.shape[1:]:
            raise MXNetError("imdecode: decoded shape %r does not match out "
                             "slot shape %r" % (res.shape[1:], out.shape[1:]))
        out[index:index + 1] = res
        return out
    return res


def _imdecode(mean, index=0, x0=0, y0=0, x1=0, y1=0, n_channels=3,
              size=0, str_img=None, out=None):
    """Raw legacy ``_imdecode`` NDArray function (``ndarray.cc:832-867``),
    same argument order as the reference registration (mean, index, crop
    window, n_channels, size, image bytes): decode + crop + optional mean
    subtract, CHW float32 output.  ``mean=None`` or an empty array is the
    reference's dummy no-mean handle."""
    if str_img is None:
        raise MXNetError("_imdecode: str_img (image bytes) is required")
    return imdecode(str_img, clip_rect=(x0, y0, x1, y1), out=out, index=index,
                    channels=n_channels, mean=mean if (mean is not None and
                                                       mean.size > 0) else None)


def waitall():
    """reference MXNDArrayWaitAll — barrier on all async work."""
    (jax.device_put(0.0) + 0).block_until_ready()


# ---------------------------------------------------------------------------
# save / load — same API as reference ``nd.save/load`` (``ndarray.py:1740``)
# AND the same on-disk bytes: the dmlc magic-header stream
# (``src/ndarray/ndarray.cc:650-678``: uint64 magic 0x112 + reserved,
# vector<NDArray> [TShape(u32 ndim + u32 dims) + Context(i32 type,id) +
# i32 dtype flag + raw bytes], vector<string> names) — params files are
# byte-compatible with reference tooling in both directions.  Loading also
# auto-detects this framework's earlier .npz container.
# ---------------------------------------------------------------------------
import struct as _struct

_DMLC_MAGIC = 0x112
# reference mshadow type flags (0.9.x); 5 is unused there — claimed here as
# a bfloat16 extension so TPU-dtype arrays round-trip exactly
_FLAG_TO_DTYPE = {0: "float32", 1: "float64", 2: "float16", 3: "uint8",
                  4: "int32", 5: "bfloat16"}
_DTYPE_TO_FLAG = {v: k for k, v in _FLAG_TO_DTYPE.items()}


def _write_array_segment(f, a):
    """One array's dmlc segment (ndim, shape, context, dtype flag,
    data) — the unit _save_dmlc repeats and the unit the reference's
    MXNDArraySaveRawBytes serializes alone."""
    arr = a.asnumpy() if isinstance(a, NDArray) else np.asarray(a)
    dname = str(a._jx.dtype) if isinstance(a, NDArray) else str(arr.dtype)
    if dname not in _DTYPE_TO_FLAG:
        raise MXNetError("save: dtype %r has no dmlc type flag" % dname)
    if dname == "bfloat16":
        arr = np.asarray(a._jx).view(np.uint16) \
            if isinstance(a, NDArray) else arr.view(np.uint16)
    f.write(_struct.pack("<I", arr.ndim))
    f.write(_struct.pack("<%dI" % arr.ndim, *arr.shape))
    f.write(_struct.pack("<ii", 1, 0))           # Context: cpu(0)
    f.write(_struct.pack("<i", _DTYPE_TO_FLAG[dname]))
    f.write(np.ascontiguousarray(arr).tobytes())


def _save_dmlc(f, names, arrays):
    f.write(_struct.pack("<QQ", _DMLC_MAGIC, 0))
    f.write(_struct.pack("<Q", len(arrays)))
    for a in arrays:
        _write_array_segment(f, a)
    f.write(_struct.pack("<Q", len(names)))
    for n in names:
        b = n.encode()
        f.write(_struct.pack("<Q", len(b)) + b)


def _read_array_segment(rd, rdbytes):
    """Inverse of _write_array_segment (shared by _load_dmlc and
    load_from_raw_bytes)."""
    (ndim,) = rd("<I")
    shape = rd("<%dI" % ndim) if ndim else ()
    _dev_type, _dev_id = rd("<ii")
    (flag,) = rd("<i")
    dname = _FLAG_TO_DTYPE.get(flag)
    if dname is None:
        raise MXNetError("unknown dtype flag %d" % flag)
    if dname == "bfloat16":
        import jax.numpy as jnp_

        n = int(np.prod(shape)) if shape else 1
        raw = np.frombuffer(rdbytes(2 * n), np.uint16).reshape(shape)
        return array(raw.view(jnp_.bfloat16))
    dt = np.dtype(dname)
    n = int(np.prod(shape)) if shape else 1
    raw = np.frombuffer(rdbytes(dt.itemsize * n), dt).reshape(shape)
    return array(raw)


def _load_dmlc(f):
    def rdbytes(size):
        buf = f.read(size)
        if len(buf) != size:
            raise MXNetError("truncated params file")
        return buf

    def rd(fmt):
        return _struct.unpack(fmt, rdbytes(_struct.calcsize(fmt)))

    magic, _reserved = rd("<QQ")
    if magic != _DMLC_MAGIC:
        raise MXNetError("bad params magic 0x%x" % magic)
    (count,) = rd("<Q")
    arrays = []
    for _ in range(count):
        arrays.append(_read_array_segment(rd, rdbytes))
    (n_names,) = rd("<Q")
    if n_names and n_names != len(arrays):
        raise MXNetError("malformed params file: %d names for %d arrays"
                         % (n_names, len(arrays)))
    names = []
    for _ in range(n_names):
        (ln,) = rd("<Q")
        names.append(rdbytes(ln).decode())
    return names, arrays


def save(fname, data):
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        names, arrays = list(data.keys()), list(data.values())
    elif isinstance(data, (list, tuple)):
        names, arrays = [], list(data)
    else:
        raise MXNetError("save: need NDArray, list, or dict")
    with open(str(fname), "wb") as f:
        _save_dmlc(f, names, arrays)


def _load_path(fname):
    import os

    # np.savez appends .npz; accept either spelling on load
    for cand in (fname, str(fname) + ".npz"):
        if os.path.exists(cand):
            return cand
    raise IOError("no such file: %r" % fname)


def load(fname):
    path = _load_path(fname)
    with open(path, "rb") as f:
        head = f.read(8)
    if len(head) == 8 and _struct.unpack("<Q", head)[0] == _DMLC_MAGIC:
        with open(path, "rb") as f:
            names, arrays = _load_dmlc(f)
        if not names:
            # 0 names: a nameless list save — except 0 arrays, which is an
            # empty dict save (dict-expecting callers dominate)
            return arrays if arrays else {}
        return dict(zip(names, arrays))
    # back-compat: this framework's earlier .npz container
    with np.load(path) as f:
        keys = sorted(f.files)
        if not keys:
            return {}
        if keys[0].startswith("l:"):
            return [array(f[k]) for k in keys]
        return {k[2:]: array(f[k]) for k in keys}


def save_raw_bytes(arr):
    """Serialize ONE NDArray to bytes (reference MXNDArraySaveRawBytes /
    ``NDArray::Save`` to a string stream): the single dmlc array segment
    without the multi-array file header."""
    import io as _io

    f = _io.BytesIO()
    _write_array_segment(f, arr)
    return f.getvalue()


def load_from_raw_bytes(buf):
    """Inverse of :func:`save_raw_bytes` (reference
    MXNDArrayLoadFromRawBytes)."""
    import io as _io

    f = _io.BytesIO(bytes(buf))

    def rdbytes(size):
        b = f.read(size)
        if len(b) != size:
            raise MXNetError("truncated raw NDArray bytes")
        return b

    def rd(fmt):
        return _struct.unpack(fmt, rdbytes(_struct.calcsize(fmt)))

    return _read_array_segment(rd, rdbytes)


# ---------------------------------------------------------------------------
# op-function generation (the _init_ndarray_module analog)
# ---------------------------------------------------------------------------
def _invoke(op, args, kwargs):
    out = kwargs.pop("out", None)
    kwargs.pop("name", None)
    ctx = kwargs.pop("ctx", None)
    # split tensor kwargs (named inputs) from attr kwargs; bare numpy
    # arrays count as tensors too (the reference's CustomOp callbacks run
    # mx.nd ops on the host views they are handed)
    def _is_tensor(v):
        # 0-d numpy arrays keep filling scalar params positionally
        return isinstance(v, NDArray) or \
            (isinstance(v, np.ndarray) and v.ndim > 0)

    # tensors stay raw (numpy uncoerced) until the declared-order input
    # list is assembled, so the op's context comes from the first NDArray
    # in *declared argument order* — not call-site arg/kwarg ordering —
    # and numpy operands are then coerced onto that context
    named_inputs = {k: v for k, v in kwargs.items() if _is_tensor(v)}
    attr_kwargs = {k: v for k, v in kwargs.items() if not _is_tensor(v)}
    pos_inputs = [a for a in args if _is_tensor(a)]
    attr_args = [a for a in args if not _is_tensor(a)]
    if attr_args:
        # positional scalars fill the op's params in declaration order
        # (reference generated fns: e.g. nd.uniform(0, 1, shape=...));
        # the auto-counted variable-arity param is never positional
        ordered = [k for k in op.params if k != op.key_var_num_args]
        if len(attr_args) > len(ordered):
            raise MXNetError("%s: too many positional params (%d given, "
                             "%d exist: %s)" % (op.name, len(attr_args),
                                                len(ordered), ordered))
        for k, v in zip(ordered, attr_args):
            if k in attr_kwargs:
                raise MXNetError("%s: got multiple values for param %r"
                                 % (op.name, k))
            attr_kwargs[k] = v
    if op.key_var_num_args and op.key_var_num_args not in attr_kwargs:
        attr_kwargs[op.key_var_num_args] = len(pos_inputs) + len(named_inputs)
    attrs = op.canonicalize_attrs(attr_kwargs)
    arg_names = op.list_arguments(attrs)
    aux_names = op.list_aux_states(attrs)

    inputs = []
    aux_arrays = []
    pi = iter(pos_inputs)
    consumed_pos = 0
    for nm in arg_names:
        if nm in named_inputs:
            inputs.append(named_inputs.pop(nm))
        else:
            try:
                inputs.append(next(pi))
                consumed_pos += 1
            except StopIteration:
                raise MXNetError("%s: missing input %r" % (op.name, nm))
    for nm in aux_names:
        if nm in named_inputs:
            aux_arrays.append(named_inputs.pop(nm))
        else:
            try:
                aux_arrays.append(next(pi))
            except StopIteration:
                raise MXNetError("%s: missing aux state %r" % (op.name, nm))
    if named_inputs:
        raise MXNetError("%s: unknown input kwargs %s"
                         % (op.name, sorted(named_inputs)))
    # NB: builtins like ``sum`` are shadowed by generated op fns here
    leftover = len(list(pi))
    if leftover:
        raise MXNetError("%s: %d surplus positional NDArray input(s) "
                         "(op takes %d inputs + %d aux)"
                         % (op.name, leftover, len(arg_names),
                            len(aux_names)))
    op_ctx = next((a._ctx for a in inputs + aux_arrays
                   if isinstance(a, NDArray)), None)

    def _as_nd(v):
        return v if isinstance(v, NDArray) else array(np.asarray(v),
                                                      ctx=op_ctx)

    inputs = [_as_nd(v) for v in inputs]
    aux_arrays = [_as_nd(v) for v in aux_arrays]

    rng = _random.next_key() if op.needs_rng else None
    with _profiler.span(op.name, "imperative") as sp:
        if inputs:
            octx = op_ctx or inputs[0]._ctx  # op_ctx None => all-numpy inputs
        else:
            octx = ctx or current_context()
        # trace-time device hint: lowering decisions (Pallas vs XLA)
        # follow the op's device, not the process default backend — set
        # BEFORE the cache lookup (the jit cache keys on the device)
        tok = _reg.trace_device.set(octx.platform)
        try:
            fn = _reg.jitted_apply(op.name, _reg.attrs_key(attrs), True)
            if inputs:
                outs, aux_up = fn([x._jx for x in inputs],
                                  [x._jx for x in aux_arrays], rng)
            else:
                with jax.default_device(octx.jax_device()):
                    outs, aux_up = fn([], [], rng)
        finally:
            _reg.trace_device.reset(tok)
        sp.sync(outs)
    # write aux updates back (reference mutates aux NDArrays in the op)
    for arr, new in zip(aux_arrays, aux_up or []):
        arr._jx = new
    results = [NDArray._from_jax(o, octx) for o in outs]
    if out is not None:
        outs_list = [out] if isinstance(out, NDArray) else list(out)
        for dst, src in zip(outs_list, results):
            dst._jx = src._jx
        return out
    return results[0] if len(results) == 1 else results


def _make_op_func(op_name):
    op = _reg.get(op_name)

    def fn(*args, **kwargs):
        return _invoke(op, args, kwargs)

    fn.__name__ = op_name
    fn.__doc__ = op.doc or ("TPU-native op %r (see mxnet_tpu.ops)" % op_name)
    return fn


def _init_ndarray_module():
    mod = sys.modules[__name__]
    for name in _reg.list_ops():
        if not hasattr(mod, name):
            setattr(mod, name, _make_op_func(name))


_init_ndarray_module()


def __getattr__(name):
    # ops registered AFTER import (registry.register in user code)
    # resolve lazily, so late registration behaves like the built-ins
    try:
        _reg.get(name)
    except MXNetError:
        raise AttributeError("module %r has no attribute %r"
                             % (__name__, name)) from None
    fn = _make_op_func(name)
    setattr(sys.modules[__name__], name, fn)
    return fn
