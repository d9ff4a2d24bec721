"""Marshalling layer for the frontend C ABI (src/frontend_capi.cc).

The embedded interpreter inside ``libmxnet_tpu_frontend.so`` imports this
module once and drives the whole framework through these thin functions —
plain ints/strings/lists cross the C boundary, every object stays a
``PyObject*`` handle on the C side.  Keeping the marshalling here (rather
than in CPython C-API calls) keeps the C++ layer small and the behavior
identical to what a Python user gets.

Reference analog: ``src/c_api/c_api*.cc`` (2452 LoC of C++ glue over the
C++ runtime); here the runtime is the Python package itself, so the glue
is Python (SURVEY §2.7 row: C ABI is "the real public surface").
"""

from __future__ import annotations

import ctypes

import numpy as np

from . import io as mxio
from . import ndarray as nd
from . import optimizer as opt
from . import symbol as sym
from .context import Context
from .kvstore import create as kv_create
from .ndarray import NDArray

_DTYPES = {0: np.float32, 1: np.float64, 2: np.float16, 3: np.uint8,
           4: np.int32, 6: "bfloat16"}
_DTYPE_CODES = {"float32": 0, "float64": 1, "float16": 2, "uint8": 3,
                "int32": 4, "bfloat16": 6}


def _ctx(dev_type, dev_id):
    # 1/3 = cpu (pinned alias), 2 = accelerator alias, 4 = tpu
    return Context("cpu" if dev_type in (1, 3) else "tpu", dev_id)


def _np_dtype(code):
    if code not in _DTYPES:
        raise ValueError("unknown dtype code %d" % code)
    d = _DTYPES[code]
    if d == "bfloat16":
        import jax.numpy as jnp

        return jnp.bfloat16
    return d


def _host_view(addr, size, np_dtype):
    buf = (ctypes.c_char * (size * np.dtype(np_dtype).itemsize)) \
        .from_address(addr)
    return np.frombuffer(buf, dtype=np_dtype, count=size)


# ---- NDArray --------------------------------------------------------------

def nd_create(shape, dev_type, dev_id, dtype):
    return nd.zeros(tuple(shape), ctx=_ctx(dev_type, dev_id),
                    dtype=_np_dtype(dtype))


def nd_copy_from(a, addr, size):
    # host buffer is in the array's dtype unless bf16 (no numpy dtype on
    # the C side): bf16 arrays take f32 host data
    host_dt = np.float32 if str(a.dtype) == "bfloat16" else a.dtype
    # a copy the array owns: on the CPU backend ``jnp.asarray`` may alias a
    # numpy buffer instead of copying it, and this one is the caller's, who
    # may free or reuse it as soon as the call returns
    a[:] = _host_view(addr, size, host_dt).reshape(a.shape).copy()


def nd_copy_to(a, addr, size):
    host_dt = np.float32 if str(a.dtype) == "bfloat16" else a.dtype
    out = _host_view(addr, size, host_dt)
    out[:] = np.asarray(a.asnumpy(), dtype=host_dt).reshape(-1)


def nd_shape(a):
    return tuple(int(d) for d in a.shape)


def nd_dtype(a):
    return _DTYPE_CODES.get(str(np.dtype(a.dtype).name)
                            if str(a.dtype) != "bfloat16" else "bfloat16",
                            0)


def nd_save(fname, arrays, keys):
    if keys is None:
        nd.save(fname, list(arrays))
    else:
        nd.save(fname, dict(zip(keys, arrays)))


def nd_load(fname):
    data = nd.load(fname)
    if isinstance(data, dict):
        keys = list(data.keys())
        return keys, [data[k] for k in keys]
    return None, list(data)


def nd_save_raw(arr):
    return nd.save_raw_bytes(arr)


def nd_load_raw(addr, size):
    return nd.load_from_raw_bytes(
        ctypes.string_at(ctypes.c_void_p(addr), size))


def rtc_create(name, input_names, output_names, kernel):
    from . import rtc

    return rtc.Rtc(name, list(input_names), list(output_names), kernel)


def rtc_push(r, ins, outs):
    r.push(list(ins), list(outs))


def invoke(op_name, inputs, keys, vals):
    fn = getattr(nd, op_name)
    out = fn(*inputs, **dict(zip(keys, vals)))
    if isinstance(out, (list, tuple)):
        return list(out)
    return [out]


def wait_all():
    nd.waitall()


def list_ops():
    from .ops.registry import list_ops as _lo

    return list(_lo())


def get_version():
    from . import __version__ as v

    parts = (v.split(".") + ["0", "0"])[:3]
    nums = [int("".join(ch for ch in p if ch.isdigit()) or 0)
            for p in parts]
    return nums[0] * 10000 + nums[1] * 100 + nums[2]


def get_device_count(dev_type):
    if dev_type in (1, 3):
        import os

        return os.cpu_count() or 1
    from .context import num_tpus

    return num_tpus()


def list_data_iters():
    return [n for n in ("NDArrayIter", "CSVIter", "ImageRecordIter",
                        "ImageIter", "MNISTIter", "LibSVMIter",
                        "PrefetchingIter", "ResizeIter")
            if hasattr(mxio, n)]


# ---- profiler -------------------------------------------------------------

def profiler_set_config(mode, filename):
    from . import profiler

    profiler.profiler_set_config(mode="all" if mode else "symbolic",
                                 filename=filename)


def profiler_set_state(state):
    from . import profiler

    profiler.profiler_set_state("run" if state else "stop")


def profiler_dump():
    from . import profiler

    profiler.dump_profile()


def random_seed(seed):
    from . import random as _random

    _random.seed(seed)


def nd_slice(a, begin, end):
    return a[begin:end]


def nd_at(a, idx):
    return a[idx]


def nd_reshape(a, dims):
    return a.reshape(tuple(dims))


def nd_context(a):
    ctx = a.context
    return (1 if ctx.device_type == "cpu" else 4), int(ctx.device_id)


# ---- Symbol ---------------------------------------------------------------

def sym_var(name):
    return sym.Variable(name)


def sym_copy(s):
    """Deep graph clone (reference MXSymbolCopy): fresh nodes, shared
    OpDefs — so composing/attr-editing the copy cannot mutate graphs the
    original (or an executor bound to it) still references."""
    from .symbol import Symbol, _Node

    memo = {}
    for node in s._nodes():  # post-order: inputs are cloned before users
        memo[id(node)] = _Node(
            node.op, node.name, dict(node.attrs),
            [(memo[id(c)], ci) for c, ci in node.inputs],
            dict(node.misc_attr))
    return Symbol([(memo[id(n)], i) for n, i in s._outputs])


def sym_print(s):
    return s.debug_str() if hasattr(s, "debug_str") else repr(s)


def sym_get_attr(s, key):
    v = s.attr(key)
    return ("", 0) if v is None else (str(v), 1)


def sym_set_attr(s, key, value):
    s._set_attr(**{key: value})


def sym_list_attr(s, recursive):
    d = s.attr_dict() if recursive else (s.list_attr() or {})
    pairs = []
    if recursive:
        for node, attrs in sorted(d.items()):
            for k, v in sorted(attrs.items()):
                pairs += ["%s$%s" % (node, k), str(v)]
    else:
        for k, v in sorted(d.items()):
            pairs += [str(k), str(v)]
    return pairs


def sym_get_internals(s):
    return s.get_internals()


def sym_get_output(s, index):
    return s[int(index)]


def sym_compose(s, name, keys, args):
    """In-place compose (reference MXSymbolCompose): rewire variable
    inputs of every node in ``s`` to the given symbols' heads."""
    if keys is None:
        keys = s.list_arguments()[:len(args)]
    mapping = {}
    for k, a in zip(keys, args):
        mapping[k] = a._entry()
    # validate BEFORE mutating: a failing call must leave the graph
    # untouched (renaming needs a single-output head)
    head = s._entry()[0] if name else None
    for node in s._nodes():
        node.inputs = [
            mapping[child.name] if child.is_variable
            and child.name in mapping else (child, ci)
            for child, ci in node.inputs]
    if head is not None:
        head.name = name
    return None


def sym_infer_shape_partial(s, names, shapes):
    args, outs, auxs = s.infer_shape_partial(**dict(zip(names, shapes)))
    fix = lambda ls: [tuple(int(d) for d in t) if t is not None else ()
                      for t in (ls or [])]
    return fix(args), fix(outs), fix(auxs)


def sym_op(op_name, name, pkeys, pvals, ikeys, inputs):
    kwargs = dict(zip(pkeys, pvals))
    if name:
        kwargs["name"] = name
    fn = getattr(sym, op_name)
    if ikeys is None:
        return fn(*inputs, **kwargs)
    kwargs.update(dict(zip(ikeys, inputs)))
    return fn(**kwargs)


def sym_group(syms):
    return sym.Group(list(syms))


def sym_list(s, which):
    if which == 0:
        return s.list_arguments()
    if which == 1:
        return s.list_auxiliary_states()
    return s.list_outputs()


def sym_json(s):
    return s.tojson()


def sym_from_json(js):
    return sym.load_json(js)


def sym_infer_shape(s, names, shapes):
    args, outs, auxs = s.infer_shape(**dict(zip(names, shapes)))
    fix = lambda ls: [tuple(int(d) for d in t) for t in (ls or [])]
    return fix(args), fix(outs), fix(auxs)


# ---- Executor -------------------------------------------------------------

def exec_simple_bind(s, dev_type, dev_id, names, shapes, grad_req):
    return s.simple_bind(_ctx(dev_type, dev_id), grad_req=grad_req,
                         **dict(zip(names, shapes)))


def exec_forward(ex, is_train):
    ex.forward(is_train=bool(is_train))


def exec_backward(ex, head_grads):
    ex.backward(head_grads if head_grads else None)


def exec_outputs(ex):
    return list(ex.outputs)


def exec_get(ex, which, name):
    d = (ex.arg_dict, ex.grad_dict, ex.aux_dict)[which]
    return d.get(name)


def exec_print(ex):
    lines = ["Executor (ctx=%s)" % (ex._ctx,)]
    for title, d in (("args", ex.arg_dict), ("aux", ex.aux_dict)):
        for n, a in d.items():
            lines.append("  %s %s: %s %s" % (title, n,
                                             tuple(a.shape), a.dtype))
    for i, o in enumerate(ex.outputs or []):
        lines.append("  output[%d]: %s %s" % (i, tuple(o.shape), o.dtype))
    return "\n".join(lines)


def exec_set_monitor(ex, cb_addr, data_addr):
    """Install a C monitor callback (MXFrontExecutorSetMonitorCallback):
    trampoline the (name, NDArrayHandle, user_data) C signature through
    ctypes.  ``id(arr)`` IS the PyObject* the C side treats as a handle;
    an owned reference is taken before the call, so the handle follows
    the same contract as every other NDArrayHandle in the ABI — the
    callback releases it with MXFrontNDArrayFree (and may keep it alive
    past the callback's return until then)."""
    if not cb_addr:
        ex.set_monitor_callback(None)
        return
    cfn = ctypes.CFUNCTYPE(None, ctypes.c_char_p, ctypes.c_void_p,
                           ctypes.c_void_p)(cb_addr)
    user = ctypes.c_void_p(data_addr)

    def monitor(name, arr):
        ctypes.pythonapi.Py_IncRef(ctypes.py_object(arr))
        cfn(str(name).encode(), ctypes.c_void_p(id(arr)), user)

    ex.set_monitor_callback(monitor)


# ---- custom ops from C ----------------------------------------------------

_custom_keepalive = []  # registered trampolines live for the process


def custom_op_register(op_type, num_inputs, infer_addr, fwd_addr,
                       bwd_addr, user_addr):
    """Register a C-authored operator (MXFrontCustomOpRegister).

    The reference's ``MXCustomOpRegister`` hands C function pointers to
    its engine (``src/operator/custom/custom.cc:183``); here the
    pointers are wrapped with ctypes and staged into the traced graph
    with ``jax.pure_callback`` exactly like Python ``CustomOp``s
    (``ops/custom.py``) — so a C custom op works from imperative
    invoke, symbols, executors, and under jit.
    """
    import jax
    import jax.numpy as jnp

    from .ops.registry import register as _register

    u32p = ctypes.POINTER(ctypes.c_uint32)
    f32p = ctypes.POINTER(ctypes.c_float)
    INFER = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_uint32, u32p,
                             ctypes.POINTER(u32p), u32p, u32p,
                             ctypes.c_void_p)
    FWD = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_uint32,
                           ctypes.POINTER(f32p), ctypes.POINTER(ctypes.c_uint64),
                           f32p, ctypes.c_uint64, ctypes.c_void_p)
    BWD = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_uint32,
                           ctypes.POINTER(f32p), f32p,
                           ctypes.POINTER(f32p),
                           ctypes.POINTER(ctypes.c_uint64),
                           ctypes.c_uint64, ctypes.c_void_p)
    infer = INFER(infer_addr)
    fwd = FWD(fwd_addr)
    bwd = BWD(bwd_addr) if bwd_addr else None
    user = ctypes.c_void_p(user_addr)
    _custom_keepalive.append((infer, fwd, bwd))
    n = int(num_inputs)

    def _out_shape(in_shapes):
        nds = (ctypes.c_uint32 * n)(*[len(s) for s in in_shapes])
        bufs = [(ctypes.c_uint32 * max(len(s), 1))(*s) for s in in_shapes]
        ptrs = (u32p * n)(*[ctypes.cast(b, u32p) for b in bufs])
        cap = 16
        out = (ctypes.c_uint32 * cap)()
        ndim = ctypes.c_uint32(cap)
        if infer(n, nds, ptrs, ctypes.byref(ndim), out, user) != 0:
            raise RuntimeError("%s: infer_shape callback failed" % op_type)
        return tuple(int(out[i]) for i in range(ndim.value))

    def _in_ptrs(arrs):
        ptrs = (f32p * n)(*[a.ctypes.data_as(f32p) for a in arrs])
        sizes = (ctypes.c_uint64 * n)(*[a.size for a in arrs])
        return ptrs, sizes

    def _fwd_host(oshape, *arrs):
        # oshape was fixed at trace time (_call_fwd); re-running the C
        # infer_shape callback here would add a per-step ctypes round
        # trip and could disagree with the traced result type
        arrs = [np.ascontiguousarray(np.asarray(a, np.float32))
                for a in arrs]
        outb = np.zeros(oshape, np.float32)
        ptrs, sizes = _in_ptrs(arrs)
        if fwd(n, ptrs, sizes, outb.ctypes.data_as(f32p), outb.size,
               user) != 0:
            raise RuntimeError("%s: forward callback failed" % op_type)
        return outb

    def _bwd_host(og, *arrs):
        arrs = [np.ascontiguousarray(np.asarray(a, np.float32))
                for a in arrs]
        og = np.ascontiguousarray(np.asarray(og, np.float32))
        grads = [np.zeros(a.shape, np.float32) for a in arrs]
        ptrs, sizes = _in_ptrs(arrs)
        gptrs = (f32p * n)(*[g.ctypes.data_as(f32p) for g in grads])
        if bwd(n, ptrs, og.ctypes.data_as(f32p), gptrs, sizes, og.size,
               user) != 0:
            raise RuntimeError("%s: backward callback failed" % op_type)
        return tuple(grads)

    def _call_fwd(xs):
        import functools

        oshape = _out_shape([tuple(map(int, x.shape)) for x in xs])
        res = jax.ShapeDtypeStruct(oshape, np.float32)
        return jax.pure_callback(functools.partial(_fwd_host, oshape),
                                 res, *[x.astype(jnp.float32) for x in xs])

    @jax.custom_vjp
    def op_fn(*xs):
        return _call_fwd(xs)

    def op_fwd(*xs):
        return _call_fwd(xs), xs

    if bwd is not None:
        def op_bwd(xs, og):
            res = tuple(jax.ShapeDtypeStruct(tuple(map(int, x.shape)),
                                             np.float32) for x in xs)
            gs = jax.pure_callback(
                _bwd_host, res, og.astype(jnp.float32),
                *[x.astype(jnp.float32) for x in xs])
            return tuple(g.astype(x.dtype) for g, x in zip(gs, xs))
    else:
        def op_bwd(xs, og):
            # header contract (c_frontend_api.h): gradient through a
            # backward-less C op is a TRACE-TIME error, not silent zeros
            raise RuntimeError(
                "%s: registered without a backward callback; gradient "
                "through it is undefined (MXFrontCustomOpRegister)"
                % op_type)

    op_fn.defvjp(op_fwd, op_bwd)

    def apply_fn(attrs, inputs, aux, is_train, rng):
        return [op_fn(*inputs)], None

    _register(op_type, apply_fn,
              arguments=tuple("data%d" % i for i in range(n)),
              hint=op_type.lower())


# ---- RecordIO -------------------------------------------------------------

def recio_open(uri, flag):
    from .recordio import MXRecordIO

    return MXRecordIO(uri, flag)


def recio_close(r):
    r.close()


def recio_write(r, addr, size):
    buf = ctypes.string_at(ctypes.c_void_p(addr), size)
    r.write(buf)


def recio_tell(r):
    return int(r.tell())


def recio_read(r):
    data = r.read()
    return data  # bytes or None at EOF


def recio_seek(r, pos):
    r.record.seek(int(pos))


# ---- Optimizer ------------------------------------------------------------

def opt_create(name, keys, vals):
    optimizer = opt.create(name, **dict(zip(keys, vals)))
    return opt.get_updater(optimizer)


def opt_update(updater, index, weight, grad):
    updater(index, grad, weight)


# ---- KVStore --------------------------------------------------------------

def kvstore_create(type_):
    return kv_create(type_)


def kv_init(kv, key, value):
    kv.init(key, value)


def kv_push(kv, key, value, priority):
    kv.push(key, value, priority=priority)


def kv_pull(kv, key, out, priority):
    kv.pull(key, out=out, priority=priority)


def kv_set_optimizer(kv, name, keys, vals):
    kv.set_optimizer(opt.create(name, **dict(zip(keys, vals))))


def kv_rank(kv):
    return int(kv.rank)


def kv_size(kv):
    return int(kv.num_workers)


def kv_barrier(kv):
    kv._barrier() if hasattr(kv, "_barrier") else None


def kv_close(kv):
    close = getattr(kv, "close", None)
    if close is not None:
        close()


# ---- DataIter -------------------------------------------------------------

class _IterState:
    """Iterator + its current batch (MXDataIterNext/GetData contract)."""

    def __init__(self, it):
        self.it = it
        self.batch = None

    def next(self):
        try:
            self.batch = next(self.it)
            return True
        except StopIteration:
            self.batch = None
            return False

    def before_first(self):
        self.it.reset()
        self.batch = None


def iter_create(name, keys, vals):
    fn = getattr(mxio, name)
    return _IterState(fn(**dict(zip(keys, vals))))


def iter_create_nd(data, label, batch_size, shuffle, last_batch_handle):
    return _IterState(mxio.NDArrayIter(
        data, label, batch_size=batch_size, shuffle=bool(shuffle),
        last_batch_handle=last_batch_handle))


def iter_next(st):
    return st.next()


def iter_before_first(st):
    st.before_first()


def iter_data(st):
    return st.batch.data[0]


def iter_label(st):
    return st.batch.label[0]


def iter_pad(st):
    return int(st.batch.pad or 0)
