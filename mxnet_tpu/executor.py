"""Executor — bind a Symbol and run forward/backward.

Reference: ``src/executor/graph_executor.cc`` + ``python/mxnet/executor.py``
(SURVEY §3.1).  The reference builds a full fwd+bwd nnvm graph, plans memory,
and pushes one engine op per node.  TPU-native collapse: the WHOLE symbol
traces into ONE jitted XLA computation —

* Gradient pass (``graph_executor.cc:219``)      -> ``jax.vjp``
* InferShape/InferType (``:413``)                -> ``jax.eval_shape`` tracing
* PlanMemory / InitDataEntryMemory (``:425``)    -> XLA buffer assignment
* InitCachedOps / bulk segments (``:544,678``)   -> the jit cache itself
* engine var-dependency scheduling               -> XLA dataflow + PJRT async

``forward(is_train=True)`` runs ONE fused fwd+bwd XLA computation (with
default all-ones head gradients — loss ops ignore them by design, matching
``backward()`` with no out_grads) and stashes the gradients;
``backward()`` then just applies them honoring grad_req.  This mirrors the
reference executor's single cached fwd+bwd graph (``InitCachedOps``) and is
the TPU-optimal shape: one compiled step, no residual round-trips.  An
explicit ``backward(out_grads)`` re-runs the fused computation with those
cotangents (rare, non-loss graphs).

grad_req semantics ('write'/'add'/'null') follow ``include/mxnet/op_attr_types.h``
kWriteTo/kAddTo/kNullOp; 'add' accumulates into the bound grad arrays.
"""

from __future__ import annotations

import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import compile_cache as _compile_cache
from . import perfdebug as _perfdebug
from . import profiler as _profiler
from . import random as _random
from . import telemetry as _telemetry
from .base import MXNetError
from .context import Context
from .ndarray import NDArray, zeros as nd_zeros
from .ops import registry as _ops_registry


class _DeviceHintFn:
    """Wraps an executor's jitted step so tracing (first call, or .lower)
    runs with ``ops.registry.trace_device`` set to the executor's device —
    device-dependent lowering (Pallas vs XLA) must follow the
    computation's device, not the process-wide default backend.

    ``compile_note`` (a kind string, set only when telemetry is enabled at
    build time) times the FIRST call — which pays jax tracing + XLA
    compilation synchronously — into the ``xla.compile.*`` metrics;
    ``attrib`` (``(exec_name, kind_name)``, set when
    :mod:`mxnet_tpu.perfdebug` attribution OR
    :mod:`mxnet_tpu.compile_cache` manifest recording is enabled at
    build time) additionally captures the first call's
    compiled-executable cost / memory / HLO fingerprint and/or records
    the build's replayable identity (kind + abstract signature) into the
    compile-once warm-up registry.  After the first call the wrapper is
    a single attribute check per dispatch."""

    def __init__(self, fn, dev_type, compile_note=None, attrib=None,
                 kind=None):
        self._fn = fn
        self._dev = dev_type
        self._note = compile_note
        self._attrib = attrib
        self._kind = kind

    def __call__(self, *args, **kwargs):
        if self._note is not None or self._attrib is not None:
            return self._first_call(args, kwargs)
        tok = _ops_registry.trace_device.set(self._dev)
        try:
            return self._fn(*args, **kwargs)
        finally:
            _ops_registry.trace_device.reset(tok)

    def _first_call(self, args, kwargs):
        note, self._note = self._note, None
        attrib, self._attrib = self._attrib, None
        tok = _ops_registry.trace_device.set(self._dev)
        t0 = time.perf_counter()
        try:
            return self._fn(*args, **kwargs)
        finally:
            _ops_registry.trace_device.reset(tok)
            dt = time.perf_counter() - t0
            if note is not None:
                _telemetry.inc("xla.compile.seconds", dt, kind=note)
                _telemetry.observe("xla.compile.first_call_seconds", dt,
                                   kind=note)
            if attrib is not None:
                # shapes/dtypes only (aval metadata survives donation);
                # neither hook ever raises into the step
                if _perfdebug.enabled():
                    _perfdebug.capture(attrib[0], attrib[1], self.lower,
                                       args, kwargs)
                if _compile_cache.recording():
                    _compile_cache.note_build(
                        attrib[0], self._kind if self._kind is not None
                        else attrib[1], self.lower, args, kwargs, dt)

    def lower(self, *args, **kwargs):
        tok = _ops_registry.trace_device.set(self._dev)
        try:
            return self._fn.lower(*args, **kwargs)
        finally:
            _ops_registry.trace_device.reset(tok)

__all__ = ["Executor"]


# ops whose outputs are NOT worth recomputing under mirror mode — the
# FLOP-heavy set the reference's mirror predicate also skips
# (graph_executor.cc:205-219: MXNET_BACKWARD_DO_MIRROR recomputes cheap
# activations in backward instead of storing them)
_MIRROR_SKIP = frozenset({
    "Convolution", "Deconvolution", "FullyConnected", "dot", "batch_dot",
    "RNN", "MultiHeadAttention", "FlashAttention", "Correlation",
    "Embedding", "Custom", "_Native", "_NDArray",
})


def _mirror_mode():
    """0 = off; 1 = segment remat between FLOP anchors; 2 = whole-graph
    remat saving only matmul/conv outputs (max memory savings, ~1/3 more
    FLOPs — the deep end of the reference's mirror trade)."""
    import os

    # lint: ok[tracer-purity] read at trace time BY DESIGN — the executor keys its fn cache (_get_fn, _seg_fn) on this function's value, so a changed value retraces
    v = os.environ.get("MXNET_BACKWARD_DO_MIRROR", "")
    if v in ("", "0"):
        return 0
    if v in ("2", "dots", "full"):
        return 2
    return 1


def _mirror_enabled():
    return _mirror_mode() != 0


def _dots_and_convs_saveable(prim, *_args, **_params):
    return prim.name in ("dot_general", "conv_general_dilated")


def _graph_forward(symbol, arg_vals, aux_vals, is_train, rng):
    """Trace the symbol DAG; returns (outputs list, new_aux dict).

    Under ``MXNET_BACKWARD_DO_MIRROR`` (read at trace time) training
    forwards are traced with segment-level rematerialization: maximal runs
    of cheap ops between FLOP-heavy anchors execute inside one
    ``jax.checkpoint``, so only segment *inputs* stay live across
    fwd/bwd — the activations inside a run (BN/activation/pad/... chains)
    are recomputed during backward, exactly the reference's mirror trade
    (``graph_executor.cc:205-219``).
    """
    nodes = symbol._nodes()
    mode = _mirror_mode() if is_train else 0
    if mode == 1:
        return _graph_forward_mirror(symbol, nodes, arg_vals, aux_vals, rng)
    if mode == 2:
        def whole(av, xv):
            return _graph_forward_plain(symbol, nodes, av, xv, True, rng)

        return jax.checkpoint(whole, policy=_dots_and_convs_saveable)(
            arg_vals, aux_vals)
    return _graph_forward_plain(symbol, nodes, arg_vals, aux_vals,
                                is_train, rng)


def _bn_relu_peephole(symbol, nodes):
    """BatchNorm nodes whose SOLE consumer is a relu ``Activation`` are
    applied in the Activation's slot, the relu written ``jnp.maximum(out,
    0)`` inside the BatchNorm.  Kept for what it measures, not for a
    kernel (the fused one it fed is gone): ResNet-50's ``train`` program
    at batch 256 holds 10.284 GB of temporaries with it and 11.106 GB
    with ``jax.nn.relu`` as an op of its own, one stem-sized activation
    more (compiled for v5e; 12.01 against 12.83 GB at the peak on the
    chip, equal speed: PERF.md, PR 29).  Returns ({id(bn)}, {id(act):
    bn_node})."""
    count = {}
    for node in nodes:
        if node.is_variable:
            continue
        for c, ci in node.inputs:
            k = (id(c), ci)
            count[k] = count.get(k, 0) + 1
    for n, i in symbol._outputs:
        k = (id(n), i)
        count[k] = count.get(k, 0) + 1  # graph outputs must materialize
    bn_defer, act_fuse = set(), {}
    for node in nodes:
        if node.is_variable or node.op is None \
                or node.op.name != "Activation" \
                or node.attrs.get("act_type") != "relu":
            continue
        child, ci = node.inputs[0]
        if ci != 0 or child.is_variable or child.op is None \
                or child.op.name != "BatchNorm":
            continue
        a = child.attrs
        if a.get("use_global_stats") or a.get("output_mean_var"):
            continue
        if count.get((id(child), 0), 0) != 1:
            continue
        bn_defer.add(id(child))
        act_fuse[id(node)] = child
    return bn_defer, act_fuse


def _graph_forward_plain(symbol, nodes, arg_vals, aux_vals, is_train, rng):
    from .ops.nn import _batch_norm as _bn_apply

    entry_val = {}
    new_aux = {}
    bn_defer, act_fuse = _bn_relu_peephole(symbol, nodes) \
        if is_train else (set(), {})
    bn_stash = {}
    for ni, node in enumerate(nodes):
        if node.is_variable:
            if node.name in arg_vals:
                entry_val[(id(node), 0)] = arg_vals[node.name]
            elif node.name in aux_vals:
                entry_val[(id(node), 0)] = aux_vals[node.name]
            else:
                raise MXNetError("unbound variable %r" % node.name)
            continue
        op = node.op
        na = node.num_args()
        if id(node) in bn_defer:
            # computed inside the consuming relu Activation's slot
            bn_stash[id(node)] = (
                [entry_val[(id(c), ci)] for c, ci in node.inputs[:na]],
                [entry_val[(id(c), ci)] for c, ci in node.inputs[na:]])
            continue
        if id(node) in act_fuse:
            bn_node = act_fuse[id(node)]
            bn_ins, bn_auxs = bn_stash[id(bn_node)]
            outs, aux_up = _bn_apply(bn_node.attrs, bn_ins, bn_auxs,
                                     True, None, act_type="relu")
            entry_val[(id(node), 0)] = outs[0]
            if aux_up is not None:
                na_bn = bn_node.num_args()
                for (child, _ci), new in zip(bn_node.inputs[na_bn:],
                                             aux_up):
                    new_aux[child.name] = new
            continue
        ins = [entry_val[(id(c), ci)] for c, ci in node.inputs[:na]]
        auxs = [entry_val[(id(c), ci)] for c, ci in node.inputs[na:]]
        key = jax.random.fold_in(rng, ni) if op.needs_rng else None
        outs, aux_up = op.apply(node.attrs, ins, auxs, is_train, key)
        for i, o in enumerate(outs):
            entry_val[(id(node), i)] = o
        if aux_up is not None:
            for (child, _ci), new in zip(node.inputs[na:], aux_up):
                new_aux[child.name] = new
    outputs = [entry_val[(id(n), i)] for n, i in symbol._outputs]
    return outputs, new_aux


def _graph_forward_mirror(symbol, nodes, arg_vals, aux_vals, rng,
                          max_seg=32):
    """Mirror-mode trace: greedy segments of non-anchor ops under one
    ``jax.checkpoint`` each."""
    entry_val = {}
    new_aux = {}

    def run_nodes(node_list, local):
        """Execute (node, ni) list against the ``local`` entry map; returns
        (per-node outs, per-node aux_up)."""
        outs_all, aux_all = [], []
        for node, ni in node_list:
            op = node.op
            na = node.num_args()
            ins = [local[(id(c), ci)] for c, ci in node.inputs[:na]]
            auxs = [local[(id(c), ci)] for c, ci in node.inputs[na:]]
            key = jax.random.fold_in(rng, ni) if op.needs_rng else None
            outs, aux_up = op.apply(node.attrs, ins, auxs, True, key)
            for i, o in enumerate(outs):
                local[(id(node), i)] = o
            outs_all.append(list(outs))
            aux_all.append(list(aux_up) if aux_up is not None else None)
        return outs_all, aux_all

    def record(node_list, outs_all, aux_all):
        for (node, _ni), outs, aux_up in zip(node_list, outs_all, aux_all):
            for i, o in enumerate(outs):
                entry_val[(id(node), i)] = o
            if aux_up is not None:
                na = node.num_args()
                for (child, _ci), new in zip(node.inputs[na:], aux_up):
                    new_aux[child.name] = new

    def flush(segment):
        if not segment:
            return
        in_seg = {id(n) for n, _ in segment}
        ext = []
        seen = set()
        for node, _ni in segment:
            for c, ci in node.inputs:
                k = (id(c), ci)
                if id(c) not in in_seg and k not in seen:
                    seen.add(k)
                    ext.append(k)
        ext_vals = [entry_val[k] for k in ext]

        def seg_fn(vals):
            return run_nodes(segment, dict(zip(ext, vals)))

        outs_all, aux_all = jax.checkpoint(seg_fn)(ext_vals)
        record(segment, outs_all, aux_all)

    segment = []
    for ni, node in enumerate(nodes):
        if node.is_variable:
            flush(segment)
            segment = []
            if node.name in arg_vals:
                entry_val[(id(node), 0)] = arg_vals[node.name]
            elif node.name in aux_vals:
                entry_val[(id(node), 0)] = aux_vals[node.name]
            else:
                raise MXNetError("unbound variable %r" % node.name)
        elif node.op.name in _MIRROR_SKIP:
            flush(segment)
            segment = []
            outs_all, aux_all = run_nodes([(node, ni)], entry_val)
            record([(node, ni)], outs_all, aux_all)
        else:
            segment.append((node, ni))
            if len(segment) >= max_seg:
                flush(segment)
                segment = []
    flush(segment)
    outputs = [entry_val[(id(n), i)] for n, i in symbol._outputs]
    return outputs, new_aux


def _nonfinite_expr(values):
    """Trace-time helper: ONE fused logical-or over every floating leaf —
    ``True`` iff any value contains NaN/Inf.  This is the in-graph NaN
    guard reduction the train kinds fold into the step (docs/resilience.md):
    the host reads a single scalar instead of pulling every output and
    gradient."""
    flags = [jnp.logical_not(jnp.all(jnp.isfinite(v))) for v in values
             if jnp.issubdtype(v.dtype, jnp.floating)]
    if not flags:
        return jnp.zeros((), jnp.bool_)
    out = flags[0]
    for f in flags[1:]:
        out = jnp.logical_or(out, f)
    return out


_ANY_NONFINITE_JIT = None


def any_nonfinite(values):
    """One jitted logical-or reduction over ``values`` (device arrays) →
    python bool.  The sync is a single scalar transfer; the per-array
    reductions run on device.  Used by the NaN-guard fallback for
    executors without an accumulated in-graph flag (e.g. after a fault
    injection poisoned gradients out-of-graph)."""
    vals = [v for v in values if jnp.issubdtype(v.dtype, jnp.floating)]
    if not vals:
        return False
    global _ANY_NONFINITE_JIT
    if _ANY_NONFINITE_JIT is None:
        _ANY_NONFINITE_JIT = jax.jit(_nonfinite_expr)
    return bool(_ANY_NONFINITE_JIT(vals))


def _global_norm_expr(values):
    """Trace-time helper: one fused sum-of-squares over every floating
    leaf → the global L2 norm as an f32 scalar.  Math in f32 so bf16
    gradients don't overflow the square."""
    total = jnp.zeros((), jnp.float32)
    for v in values:
        total = total + jnp.sum(jnp.square(v.astype(jnp.float32)))
    return jnp.sqrt(total)


_GLOBAL_NORM_JIT = None


def global_norm(values):
    """One jitted global-L2-norm reduction over ``values`` (device
    arrays) → python float; single scalar transfer like
    :func:`any_nonfinite`.  The statistic the training sentinel's
    ``anomaly_policy`` z-scores (docs/resilience.md "Statistical
    anomaly rollback")."""
    vals = [v for v in values if jnp.issubdtype(v.dtype, jnp.floating)]
    if not vals:
        return 0.0
    global _GLOBAL_NORM_JIT
    if _GLOBAL_NORM_JIT is None:
        _GLOBAL_NORM_JIT = jax.jit(_global_norm_expr)
    return float(_GLOBAL_NORM_JIT(vals))


def _kind_name(kind):
    """Human name of an executor program kind: the kind string itself,
    or a tuple kind's head (``("train_sgd", ...)`` -> ``"train_sgd"``,
    placement segments -> ``"seg"``)."""
    if isinstance(kind, str):
        return kind
    if kind[0] == "seg":
        return "seg"
    return str(kind[0])


def sgd_step_math(p, g, mom, lr, wd, momentum, rescale, clip):
    """One SGD(-momentum) parameter step, math in f32, result cast back to
    the stored dtype (bf16 params stay bf16).  Shared by the two-dispatch
    fused update (Module._try_fused_update) and the single-dispatch
    ``train_sgd`` executor kind so their numerics can never diverge.
    Returns (new_p, new_mom_or_None)."""
    g = g.astype(jnp.float32) * rescale
    if clip > 0:
        g = jnp.clip(g, -clip, clip)
    g = g + wd * p.astype(jnp.float32)
    if momentum != 0.0:
        m = momentum * mom.astype(jnp.float32) - lr * g
        return (p.astype(jnp.float32) + m).astype(p.dtype), \
            m.astype(mom.dtype)
    return (p.astype(jnp.float32) - lr * g).astype(p.dtype), None


class Executor:
    """reference ``python/mxnet/executor.py:25``"""

    def __init__(self, symbol, ctx, arg_dict, grad_dict, grad_req, aux_dict,
                 group2ctx=None):
        self._symbol = symbol
        self._ctx = ctx
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.output_names = symbol.list_outputs()
        self.arg_dict = arg_dict
        self.grad_dict = grad_dict
        self.aux_dict = aux_dict
        if isinstance(grad_req, str):
            grad_req = {n: grad_req for n in self.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            grad_req = dict(zip(self.arg_names, grad_req))
        self.grad_req = {n: grad_req.get(n, "null") for n in self.arg_names}
        self._group2ctx = group2ctx or {}
        self.outputs = []
        self._monitor_callback = None
        self._pending_grads = None
        self._last_state = None
        self._rng_step = 0
        self._fns = {}
        self._build_counts = {}  # program identity -> build count
        self._needs_rng = None
        self._rng_cache = None
        self._seg_chain = None
        self._global_mesh = None  # set by Module in multi-process mode
        self._spmd_mesh = None    # set by Module for single-process meshes
        # in-graph NaN guard (Module._install_nan_guard): train kinds fold
        # a logical-or reduction over outputs+grads into the step and
        # accumulate it here as a device scalar — read via
        # consume_nan_flag() at the caller's cadence, no per-batch pulls
        self._nan_guard = False
        self._nan_acc = None    # accumulated device flag (or None)
        self._nan_batch = None  # THIS batch's flag (gates metric stats)
        self._nan_stale = False  # out-of-graph grad mutation invalidated it
        self._nan_false = None  # cached device False scalar
        self._init_placement()

    arg_arrays = property(lambda s: [s.arg_dict[n] for n in s.arg_names])
    grad_arrays = property(lambda s: [s.grad_dict.get(n) for n in s.arg_names])
    aux_arrays = property(lambda s: [s.aux_dict[n] for n in s.aux_names])

    # -- jitted graph functions ------------------------------------------
    def _symbol_name(self):
        outs = self.output_names
        return outs[0].rsplit("_output", 1)[0] if outs else "exec"

    def _diff_names(self):
        return [n for n in self.arg_names if self.grad_req[n] != "null"]

    def _note_build(self, kind):
        """Record one jitted-program build (``xla.compile.count``) and run
        the recompilation detector.

        Builds are counted per program *identity* — the executor kind
        (``predict``/``train``/``train_sgd``/...; placement segments key
        on ``(seg, index, is_train)``) with tuple-kind parameters and the
        env fingerprint stripped — so each program's legitimate first
        build counts once and only REbuilds of the same identity
        accumulate: hyperparameters baked into a fused-step cache key,
        env-fingerprint flips.  An identity built more than
        ``MXNET_RECOMPILE_WARN_THRESHOLD`` times (default 8, 0 disables)
        warns with the executor's name and bumps
        ``xla.recompile_warnings``; a many-segment executor compiling
        everything exactly once never trips it.  Returns the telemetry
        compile-note for :class:`_DeviceHintFn` first-call timing (None
        when disabled)."""
        kind_name = _kind_name(kind)
        if isinstance(kind, str):
            ident = kind
        elif kind[0] == "seg":  # ("seg", si, is_train, fingerprint)
            ident = kind[:3]
        else:
            ident = kind_name
        builds = self._build_counts[ident] = \
            self._build_counts.get(ident, 0) + 1
        limit = int(os.environ.get("MXNET_RECOMPILE_WARN_THRESHOLD", "8"))
        if 0 < limit < builds:
            logging.warning(
                "executor %r compiled its %r program %d times (threshold "
                "%d): recompilation churn — per-step hyperparameter "
                "changes or env-fingerprint flips retrace/recompile every "
                "time (MXNET_RECOMPILE_WARN_THRESHOLD tunes this).%s",
                self._symbol_name(), kind_name, builds, limit,
                " Rebuilds are served from the persistent compile cache "
                "(cheap loads, but the retrace cost remains)."
                if _compile_cache.enabled() else
                " The persistent compile cache is off "
                "(JAX_ENABLE_COMPILATION_CACHE): every rebuild is a "
                "full compile.")
            _telemetry.inc("xla.recompile_warnings")
        if not _telemetry.enabled():
            return None
        _telemetry.inc("xla.compile.count", kind=kind_name)
        return kind_name

    def _get_fn(self, kind):
        # keyed on the one knob a trace depends on: a mirror toggle must
        # retrace, not silently reuse a stale jit
        cache_key = (kind, _mirror_mode())
        if cache_key in self._fns:
            # IN-PROCESS jit function reuse — split from the on-disk
            # xla.compile.persistent_cache_hits (compile_cache.py)
            _telemetry.inc("xla.compile.fn_cache_hits")
            return self._fns[cache_key]
        symbol = self._symbol
        arg_names = list(self.arg_names)
        aux_names = list(self.aux_names)
        diff_names = self._diff_names()

        def _vjp_parts(args, aux, rng):
            amap = dict(zip(arg_names, args))
            axmap = dict(zip(aux_names, aux))
            nondiff = {n: v for n, v in amap.items() if n not in diff_names}

            def g(diff_args):
                vals = dict(nondiff)
                vals.update(diff_args)
                outs, new_aux = _graph_forward(symbol, vals, axmap, True, rng)
                return tuple(outs), new_aux

            outs, vjp_fn, new_aux = jax.vjp(
                g, {n: amap[n] for n in diff_names}, has_aux=True)
            new_aux_list = [new_aux.get(n, axmap[n]) for n in aux_names]
            return outs, new_aux_list, vjp_fn

        if kind == "predict":
            def f(args, aux, rng):
                outs, _ = _graph_forward(
                    symbol, dict(zip(arg_names, args)),
                    dict(zip(aux_names, aux)), False, rng)
                return outs

            fn = jax.jit(f)
        elif kind == "train":
            # fused fwd+bwd with default (ones) head grads — one XLA step
            def f(args, aux, rng):
                outs, new_aux_list, vjp_fn = _vjp_parts(args, aux, rng)
                (grads,) = vjp_fn(tuple(jnp.ones_like(o) for o in outs))
                return list(outs), new_aux_list, grads

            fn = jax.jit(f)
        elif kind == "train_guard":
            # fused fwd+bwd + in-graph NaN guard: one extra scalar output
            # or-accumulating non-finiteness of outputs+grads into the
            # carried flag (replaces the per-gradient asnumpy() loop)
            def f(args, aux, rng, nan_acc):
                outs, new_aux_list, vjp_fn = _vjp_parts(args, aux, rng)
                (grads,) = vjp_fn(tuple(jnp.ones_like(o) for o in outs))
                flag = _nonfinite_expr(
                    list(outs) + [grads[n] for n in diff_names])
                return (list(outs), new_aux_list, grads,
                        jnp.logical_or(nan_acc, flag), flag)

            fn = jax.jit(f)
        elif kind == "train_fwd":
            # forward-only in train mode (aux updates, no grads) — used when
            # the caller never calls backward (e.g. Monitor probing)
            def f(args, aux, rng):
                outs, new_aux = _graph_forward(
                    symbol, dict(zip(arg_names, args)),
                    dict(zip(aux_names, aux)), True, rng)
                new_aux_list = [new_aux.get(n, ax)
                                for n, ax in zip(aux_names, aux)]
                return outs, new_aux_list

            fn = jax.jit(f)
        elif kind == "train_with_grads":
            # explicit head cotangents (non-loss graphs)
            def f(args, aux, rng, out_grads):
                outs, new_aux_list, vjp_fn = _vjp_parts(args, aux, rng)
                (grads,) = vjp_fn(tuple(out_grads))
                return list(outs), new_aux_list, grads

            fn = jax.jit(f)
        elif isinstance(kind, tuple) and kind[0] == "train_sgd":
            # ONE dispatch for fwd+bwd+SGD(-momentum) update with donated
            # param/momentum buffers — the whole training step is a single
            # XLA computation (the reference's bulk-segment idea taken to
            # its TPU conclusion).  Hyperparameters are baked into the
            # compiled step; Module caches per hyper-tuple.  With
            # ``guard`` the step also folds the NaN-guard reduction in: a
            # non-finite batch's param/momentum update is withheld
            # in-graph (jnp.where on the batch flag — the fused step
            # never applies a poisoned update) and the flag or-accumulates
            # into the carried scalar for the host's lazy read.
            _, upd_names_t, momentum, rescale, clip, guard = kind
            upd_names = list(upd_names_t)
            other_names = [n for n in arg_names if n not in upd_names_t]

            def _step_core(upd_vals, other_vals, aux, rng, moms, lrs, wds):
                amap = dict(zip(upd_names, upd_vals))
                amap.update(zip(other_names, other_vals))
                args = [amap[n] for n in arg_names]
                outs, new_aux_list, vjp_fn = _vjp_parts(args, aux, rng)
                (grads,) = vjp_fn(tuple(jnp.ones_like(o) for o in outs))
                new_p, new_m = [], []
                for i, n in enumerate(upd_names):
                    p, m = sgd_step_math(
                        amap[n], grads[n], moms[i] if momentum != 0.0
                        else None, lrs[i], wds[i], momentum, rescale, clip)
                    new_p.append(p)
                    if m is not None:
                        new_m.append(m)
                grad_list = [grads[n] for n in upd_names]
                return list(outs), new_aux_list, new_p, new_m, grad_list

            if guard:
                def f(upd_vals, other_vals, aux, rng, moms, lrs, wds,
                      nan_acc):
                    outs, new_aux_list, new_p, new_m, grad_list = \
                        _step_core(upd_vals, other_vals, aux, rng, moms,
                                   lrs, wds)
                    flag = _nonfinite_expr(outs + grad_list)
                    new_p = [jnp.where(flag, p0, p1)
                             for p0, p1 in zip(upd_vals, new_p)]
                    new_m = [jnp.where(flag, m0, m1)
                             for m0, m1 in zip(moms, new_m)]
                    return (outs, new_aux_list, new_p, new_m, grad_list,
                            jnp.logical_or(nan_acc, flag), flag)
            else:
                f = _step_core

            fn = jax.jit(f, donate_argnums=(0, 4))
        elif isinstance(kind, tuple) and kind[0] == "train_sgd_mesh":
            # the ZeRO variant of train_sgd (kvstore='mesh', PAPERS.md
            # "Automatic Cross-Replica Sharding of Weight Update"):
            # eligible params' updates shard over the mesh batch axis —
            # the batch-summed gradient is consumed row-sharded (GSPMD
            # lowers the would-be all-reduce to a reduce-scatter), each
            # device updates only its momentum/param rows, and the new
            # rows all-gather back into the replicated parameter.  Full
            # gradients are never materialized, so this kind returns no
            # grad_list (grad_dict goes stale, like the scan kind).
            (_, upd_names_t, zero_names_t, momentum, rescale, clip,
             guard, axis) = kind
            from .kvstore_mesh import mesh_param_step

            mesh = self._spmd_mesh
            if mesh is None:
                raise MXNetError(
                    "train_sgd_mesh requires a mesh-bound executor")
            upd_names = list(upd_names_t)
            zero_set = frozenset(zero_names_t)
            other_names = [n for n in arg_names if n not in upd_names_t]
            # the per-param dispatch + layout pinning is the SHARED
            # helper, so this kind and Module's two-dispatch fused
            # update can never diverge numerically
            mstep = mesh_param_step(mesh, momentum, rescale, clip,
                                    zero_names_t, guard=guard,
                                    axis_name=axis)

            def _mesh_core(upd_vals, other_vals, aux, rng, moms, lrs,
                           wds):
                amap = dict(zip(upd_names, upd_vals))
                amap.update(zip(other_names, other_vals))
                args = [amap[n] for n in arg_names]
                outs, new_aux_list, vjp_fn = _vjp_parts(args, aux, rng)
                (grads,) = vjp_fn(tuple(jnp.ones_like(o) for o in outs))
                new_p, new_m, zflags, plain_grads = [], [], [], []
                for i, n in enumerate(upd_names):
                    m_in = moms[i] if momentum != 0.0 else None
                    p, m, zf = mstep(n, amap[n], grads[n], m_in, lrs[i],
                                     wds[i])
                    if zf is not None:
                        zflags.append(zf)
                    elif n not in zero_set:
                        plain_grads.append(grads[n])
                    new_p.append(p)
                    if m is not None:
                        new_m.append(m)
                return list(outs), new_aux_list, new_p, new_m, zflags, \
                    plain_grads

            if guard:
                def f(upd_vals, other_vals, aux, rng, moms, lrs, wds,
                      nan_acc):
                    (outs, new_aux_list, new_p, new_m, zflags,
                     plain_grads) = _mesh_core(upd_vals, other_vals, aux,
                                               rng, moms, lrs, wds)
                    # unsharded residue checks its full grads; the ZeRO
                    # params' flags were psum'd from the scattered rows
                    flag = _nonfinite_expr(outs + plain_grads)
                    for zf in zflags:
                        flag = jnp.logical_or(flag, zf)
                    new_p = [jnp.where(flag, p0, p1)
                             for p0, p1 in zip(upd_vals, new_p)]
                    new_m = [jnp.where(flag, m0, m1)
                             for m0, m1 in zip(moms, new_m)]
                    return (outs, new_aux_list, new_p, new_m,
                            jnp.logical_or(nan_acc, flag), flag)
            else:
                def f(upd_vals, other_vals, aux, rng, moms, lrs, wds):
                    outs, new_aux_list, new_p, new_m, _zf, _pg = \
                        _mesh_core(upd_vals, other_vals, aux, rng, moms,
                                   lrs, wds)
                    return outs, new_aux_list, new_p, new_m

            fn = jax.jit(f, donate_argnums=(0, 4))
        elif isinstance(kind, tuple) and kind[0] == "train_sgd_scan":
            # K full train steps inside ONE dispatch: lax.scan over stacked
            # input batches with params/momenta/aux as carry.  The
            # reference bulks engine ops into segments to cut dispatch
            # overhead (``graph_executor.cc:678`` InitOpSegs /
            # MXNET_EXEC_BULK_EXEC_TRAIN); bulking across steps is the
            # same trade one level up, against the per-step dispatch.
            (_, upd_names_t, scan_names_t, momentum, rescale, clip,
             collect) = kind
            upd_names = list(upd_names_t)
            scan_names = list(scan_names_t)
            static_names = [n for n in arg_names
                            if n not in upd_names_t and n not in scan_names_t]

            def f(upd_vals, static_vals, aux, rng, moms, lrs, wds, stacks):
                def body(carry, xs):
                    cur_p, cur_m, cur_aux, cur_rng = carry
                    amap = dict(zip(upd_names, cur_p))
                    amap.update(zip(static_names, static_vals))
                    amap.update(zip(scan_names, xs))
                    args = [amap[n] for n in arg_names]
                    outs, new_aux_list, vjp_fn = _vjp_parts(
                        args, cur_aux, cur_rng)
                    (grads,) = vjp_fn(tuple(jnp.ones_like(o) for o in outs))
                    new_p, new_m = [], []
                    for i, n in enumerate(upd_names):
                        p, m = sgd_step_math(
                            amap[n], grads[n], cur_m[i] if momentum != 0.0
                            else None, lrs[i], wds[i], momentum, rescale,
                            clip)
                        new_p.append(p)
                        if m is not None:
                            new_m.append(m)
                    nxt_rng = jax.random.fold_in(cur_rng, 1)
                    # collect=False skips the K-step output stack — at
                    # PTB shapes the stacked softmax (K, N*T, vocab) is
                    # GBs of HBM nobody reads (b256/bulk-80 OOM'd 27 GB)
                    return ((new_p, new_m, new_aux_list, nxt_rng),
                            list(outs) if collect else None)

                (new_p, new_m, new_aux_list, _), outs_stack = jax.lax.scan(
                    body, (list(upd_vals), list(moms), list(aux), rng),
                    list(stacks))
                return outs_stack, new_aux_list, new_p, new_m

            fn = jax.jit(f, donate_argnums=(0, 4))
        elif isinstance(kind, tuple) and kind[0] == "predict_scan":
            # K inference forwards in ONE dispatch (lax.scan over stacked
            # inputs) — the serving-throughput analog of train_sgd_scan
            _, scan_names_t = kind
            scan_names = list(scan_names_t)
            static_names = [n for n in arg_names if n not in scan_names_t]

            def f(static_vals, aux, rng, stacks):
                axmap = dict(zip(aux_names, aux))

                def body(carry, xs):
                    amap = dict(zip(static_names, static_vals))
                    amap.update(zip(scan_names, xs))
                    outs, _ = _graph_forward(symbol, amap, axmap, False,
                                             rng)
                    return carry, list(outs)

                _, outs_stack = jax.lax.scan(body, 0, list(stacks))
                return outs_stack

            fn = jax.jit(f)
        else:
            raise ValueError(kind)
        attrib = (self._symbol_name(), _kind_name(kind)) \
            if _perfdebug.enabled() or _compile_cache.recording() else None
        fn = _DeviceHintFn(fn, self._ctx.platform,
                           self._note_build(kind), attrib, kind=kind)
        self._fns[cache_key] = fn
        return fn

    # -- group2ctx placement (model parallelism) --------------------------
    def _init_placement(self):
        """The ``PlaceDevice`` pass analog (reference
        ``graph_executor.cc:231-305`` + ``src/operator/cross_device_copy.cc``).

        Nodes annotated with a ``ctx_group`` attr (``mx.AttrScope``) are
        assigned the mapped context; variables adopt their first consumer's
        context (reference ``AssignContext``), and parameter / gradient /
        aux NDArrays are MOVED onto those devices at bind time.  Execution
        then runs as per-device jitted *segments* — maximal topo runs on
        one device — with ``jax.device_put`` at segment boundaries playing
        the ``_CrossDeviceCopy`` role.  When every group maps to the bind
        context the plan collapses and the whole-graph single-jit fast
        path is used."""
        self._segments = None
        if not self._group2ctx:
            return
        nodes = self._symbol._nodes()
        base = self._ctx
        dev_of = {}
        distinct = False
        for node in nodes:
            if node.is_variable:
                continue
            g = node.misc_attr.get("ctx_group")
            ctx = self._group2ctx.get(g, base) if g is not None else base
            dev_of[id(node)] = ctx
            if ctx.jax_device() != base.jax_device():
                distinct = True
        if not distinct:
            return
        # variables adopt the context of their first consumer
        for node in nodes:
            if node.is_variable:
                continue
            for child, _ci in node.inputs:
                if child.is_variable and id(child) not in dev_of:
                    dev_of[id(child)] = dev_of[id(node)]
        for node in nodes:
            if node.is_variable and id(node) not in dev_of:
                dev_of[id(node)] = base
        name2ctx = {n.name: dev_of[id(n)] for n in nodes if n.is_variable}
        for group in (self.arg_dict, self.aux_dict, self.grad_dict):
            for n, arr in group.items():
                ctx = name2ctx.get(n)
                if ctx is not None and \
                        arr._ctx.jax_device() != ctx.jax_device():
                    arr._jx = jax.device_put(arr._jx, ctx.jax_device())
                    arr._ctx = ctx
        # maximal same-device topo runs of compute nodes
        ni_of = {id(n): i for i, n in enumerate(nodes)}
        segs = []
        for node in nodes:
            if node.is_variable:
                continue
            d = dev_of[id(node)]
            if segs and segs[-1][0].jax_device() == d.jax_device():
                segs[-1][1].append(node)
            else:
                segs.append((d, [node]))
        # per-segment IO: external entries consumed / entries needed later
        produced_by = {}
        for si, (_d, seg_nodes) in enumerate(segs):
            for n in seg_nodes:
                produced_by[id(n)] = si
        seg_io = []
        out_entries = {(id(n), i) for n, i in self._symbol._outputs}
        for si, (_d, seg_nodes) in enumerate(segs):
            in_keys, seen = [], set()
            for n in seg_nodes:
                for c, ci in n.inputs:
                    k = (id(c), ci)
                    if produced_by.get(id(c)) == si:
                        continue
                    if k not in seen:
                        seen.add(k)
                        in_keys.append(k)
            seg_io.append([in_keys, None])
        consumers = {}
        for si, (_d, seg_nodes) in enumerate(segs):
            for k in seg_io[si][0]:
                consumers.setdefault(k, []).append(si)
        for si, (_d, seg_nodes) in enumerate(segs):
            outs = []
            for n in seg_nodes:
                nouts = len(n.op.list_outputs(n.attrs))
                for i in range(nouts):
                    k = (id(n), i)
                    if k in consumers or k in out_entries:
                        outs.append(k)
            seg_io[si][1] = outs
        self._segments = segs
        self._seg_io = seg_io
        self._seg_ni = ni_of
        self._seg_dev_of = dev_of

    def _seg_fn(self, si, is_train):
        key = ("seg", si, is_train, _mirror_mode())
        if key in self._fns:
            _telemetry.inc("xla.compile.fn_cache_hits")
            return self._fns[key]
        _dev, seg_nodes = self._segments[si]
        in_keys, out_keys = self._seg_io[si]
        ni_of = self._seg_ni
        # entry keys are ids — rebuild the local maps inside the closure
        def f(in_vals, rng):
            entry = dict(zip(in_keys, in_vals))
            aux_updates = []
            for node in seg_nodes:
                op = node.op
                na = node.num_args()
                ins = [entry[(id(c), ci)] for c, ci in node.inputs[:na]]
                auxs = [entry[(id(c), ci)] for c, ci in node.inputs[na:]]
                k = jax.random.fold_in(rng, ni_of[id(node)]) \
                    if op.needs_rng else None
                outs, aux_up = op.apply(node.attrs, ins, auxs, is_train, k)
                for i, o in enumerate(outs):
                    entry[(id(node), i)] = o
                if aux_up is not None and is_train:
                    for (child, _ci), new in zip(node.inputs[na:], aux_up):
                        aux_updates.append((child.name, new))
            return [entry[k2] for k2 in out_keys], dict(aux_updates)

        attrib = (self._symbol_name(), "seg%d" % si) \
            if _perfdebug.enabled() or _compile_cache.recording() else None
        fn = _DeviceHintFn(jax.jit(f), _dev.platform,
                           self._note_build(key), attrib,
                           kind=("seg", si, is_train))
        self._fns[key] = fn
        return fn

    def _forward_segmented(self, is_train):
        """Forward across placement segments; training stores a vjp chain
        for ``backward``."""
        entry = {}
        arg_map = {n: a for n, a in self.arg_dict.items()}
        for node in self._symbol._nodes():
            if not node.is_variable:
                continue
            arr = arg_map.get(node.name)
            if arr is None:
                arr = self.aux_dict.get(node.name)
            if arr is None:
                raise MXNetError("unbound variable %r" % node.name)
            entry[(id(node), 0)] = arr._jx
        rng = self.next_rng()
        diff = set(self._diff_names())
        chain = []
        new_aux_all = {}
        train_grads = is_train and bool(diff)
        for si, (dev, _seg_nodes) in enumerate(self._segments):
            in_keys, out_keys = self._seg_io[si]
            jdev = dev.jax_device()
            ins = [jax.device_put(entry[k], jdev) for k in in_keys]
            srng = jax.device_put(rng, jdev)
            fn = self._seg_fn(si, is_train)
            if train_grads:
                outs, vjp_fn, aux_d = jax.vjp(
                    lambda vals: fn(vals, srng), ins, has_aux=True)
            else:
                outs, aux_d = fn(ins, rng=srng)
                vjp_fn = None
            for k, v in zip(out_keys, outs):
                entry[k] = v
            new_aux_all.update(aux_d)
            chain.append((vjp_fn, in_keys, out_keys,
                          [(o.shape, o.dtype) for o in outs], dev))
        if is_train:
            for name, v in new_aux_all.items():
                arr = self.aux_dict.get(name)
                if arr is not None:
                    arr._jx = v
        outs = [entry[(id(n), i)] for n, i in self._symbol._outputs]
        self._seg_chain = chain if train_grads else None
        self._pending_grads = "segmented" if train_grads else None
        self._last_state = None
        out_ctx = self._segments[-1][0]
        self.outputs = [NDArray._from_jax(o, out_ctx) for o in outs]
        if self._monitor_callback is not None:
            for name, arr in zip(self.output_names, self.outputs):
                self._monitor_callback(name, arr)
        return self.outputs

    def _backward_segmented(self, out_grads):
        """Chain segment vjps in reverse; cross-segment cotangents hop
        devices exactly where ``_CrossDeviceCopy`` nodes would sit."""
        cot = {}
        out_entries = [(id(n), i) for n, i in self._symbol._outputs]
        if out_grads is None:
            for k, o in zip(out_entries, self.outputs):
                cot[k] = jnp.ones(o.shape, o.dtype)
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            for k, g in zip(out_entries, out_grads):
                cot[k] = g._jx if isinstance(g, NDArray) else jnp.asarray(g)
        var_name = {id(n): n.name for n in self._symbol._nodes()
                    if n.is_variable}
        diff = set(self._diff_names())
        grads = {}
        for vjp_fn, in_keys, out_keys, out_avals, dev in \
                reversed(self._seg_chain):
            jdev = dev.jax_device()
            out_cots = tuple(
                jax.device_put(cot[k], jdev) if k in cot
                else jnp.zeros(shape, dtype)
                for k, (shape, dtype) in zip(out_keys, out_avals))
            (in_cots,) = vjp_fn(list(out_cots))
            for k, c in zip(in_keys, in_cots):
                nm = var_name.get(k[0])
                if nm is not None:
                    if nm in diff:
                        grads[nm] = grads[nm] + c if nm in grads else c
                else:
                    cot[k] = cot[k] + c if k in cot else c
        return grads

    def _small_target(self):
        """Placement for executor-owned smalls (rng key, guard scalar):
        the executor's device — or, when the arrays are global over a
        single-process mesh, replicated over that mesh (a device-0
        committed scalar cannot enter a jit whose other arguments span
        the mesh)."""
        if self._spmd_mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            return NamedSharding(self._spmd_mesh, PartitionSpec())
        return self._ctx.jax_device()

    # -- in-graph NaN guard ----------------------------------------------
    def _nan_acc_in(self):
        """The accumulator value to feed the next guarded dispatch."""
        if self._nan_acc is not None:
            return self._nan_acc
        if self._nan_false is None:
            self._nan_false = jax.device_put(np.zeros((), np.bool_),
                                             self._small_target())
        return self._nan_false

    def consume_nan_flag(self):
        """Read-and-reset the accumulated in-graph guard flag: ONE scalar
        device→host transfer (blocks until the steps that produced it
        complete — the caller picks the cadence via
        ``MXNET_NAN_CHECK_PERIOD``)."""
        if self._nan_acc is None:
            return False
        flag = bool(np.asarray(self._nan_acc))  # host-sync: ok — one scalar at the guard cadence
        self._nan_acc = None
        self._nan_stale = False
        return flag

    def next_rng(self):
        """Per-dispatch rng key on the executor's device.

        Graphs with no rng-consuming ops (the common CNN case) reuse ONE
        cached device key — XLA dead-code-eliminates the argument, and the
        per-step ``jax.random.split`` dispatch + ``device_put`` round trip
        disappear from the hot loop.
        Graphs that do consume rng draw a fresh key every dispatch."""
        if self._needs_rng is None:
            self._needs_rng = any(
                (not n.is_variable) and n.op.needs_rng
                for n in self._symbol._nodes())
        if self._global_mesh is not None:
            # multi-process SPMD: the key must be a global replicated
            # array (and identical on every process — fold a counter on a
            # fixed base rather than splitting process-local state).  The
            # counter advances HERE so every caller (forward, fused step,
            # bulk) gets a fresh key.
            from . import dist as _dist

            if self._needs_rng:
                self._rng_step += 1
                key = np.asarray(jax.random.fold_in(  # host-sync: ok — tiny key, dist replication needs host numpy
                    jax.random.PRNGKey(_random.get_seed()), self._rng_step))
                return _dist.replicate(self._global_mesh, key)
            if self._rng_cache is None:
                self._rng_cache = _dist.replicate(
                    self._global_mesh,
                    np.asarray(jax.random.PRNGKey(0)))  # host-sync: ok — one-time key replication
            return self._rng_cache
        if self._needs_rng:
            return jax.device_put(_random.next_key(),
                                  self._small_target())
        if self._rng_cache is None:
            self._rng_cache = jax.device_put(_random.next_key(),
                                             self._small_target())
        return self._rng_cache

    # -- compile-once warm-up (docs/how_to/perf.md "Compile once") --------
    def precompile(self, entries, logger=logging):
        """AOT-build the programs a warm-up manifest recorded: for each
        entry, rebuild the jitted function for its kind, ``lower`` it
        against the recorded abstract signature and ``compile`` — with
        the persistent compile cache populated this is a disk load, not
        an XLA compile, so a restart performs zero cold compiles before
        its first real dispatch.  Nothing is EXECUTED: no parameter,
        optimizer or rng state is touched, which is what makes this safe
        immediately before an exact ``resume="auto"`` restart.

        A program whose lowered HLO no longer matches the manifest's
        fingerprint is the invalidation signal (counted + logged — the
        fresh build simply wins); entries that cannot be reconstructed
        (placement segments, foreign kinds, shape mismatches) are
        skipped or counted as errors, never raised.  Returns a summary
        dict."""
        out = {"replayed": 0, "skipped": 0, "errors": 0,
               "fingerprint_changes": 0}
        for e in entries:
            try:
                kind = _compile_cache.kind_from_json(e.get("kind"))
            except MXNetError:
                out["skipped"] += 1
                continue
            head = kind if isinstance(kind, str) \
                else (kind[0] if kind else None)
            sig = e.get("sig")
            if head not in _compile_cache.REPLAYABLE_KINDS or sig is None:
                out["skipped"] += 1
                continue
            try:
                args, kwargs = _compile_cache.signature_from_json(
                    sig, device=self._ctx.jax_device())
                fn = self._get_fn(kind)
                lowered = fn.lower(*args, **kwargs)
                if e.get("fingerprint"):
                    fp = _perfdebug.fingerprint_text(lowered.as_text())
                    if fp != e["fingerprint"]:
                        out["fingerprint_changes"] += 1
                        _telemetry.inc(
                            "compile_cache.manifest.fingerprint_changes")
                        _telemetry.event(
                            "compile_cache.fingerprint_change",
                            exec=e.get("exec"), kind=e.get("kind_name"),
                            shapes=e.get("shapes"),
                            old=e["fingerprint"], new=fp)
                        logger.warning(
                            "compile_cache: %s/%s@%s lowers to different "
                            "HLO than the warm-up manifest recorded "
                            "(%s -> %s): code or trace-env changed since "
                            "the manifest was written; compiling fresh",
                            e.get("exec"), e.get("kind_name"),
                            e.get("shapes"), e["fingerprint"], fp)
                lowered.compile()
                out["replayed"] += 1
            except Exception as exn:  # noqa: broad-except — replay is
                # an optimization; a stale manifest entry must degrade
                # to lazy compilation, never break bind/fit/serving
                out["errors"] += 1
                _telemetry.inc("compile_cache.manifest.replay_errors")
                logger.warning(
                    "compile_cache: manifest replay of %s/%s@%s failed "
                    "(%s: %s); it will compile lazily instead",
                    e.get("exec"), e.get("kind_name"), e.get("shapes"),
                    type(exn).__name__, exn)
        return out

    # -- API --------------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        """reference ``executor.py:86``"""
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("forward: unknown input %r" % k)
            dst = self.arg_dict[k]
            if isinstance(v, NDArray):
                src = v._transfer_src()
                val = src.astype(dst._jx.dtype) \
                    if src.dtype != dst._jx.dtype else src
                # inputs may live on another device (reference CopyFromTo
                # semantics): move to the executor's device; same-device
                # put is free
                dst._jx = jax.device_put(val, self._ctx.jax_device())
            else:
                dst[:] = v
        # per-dispatch batch flag: only a guarded TRAIN dispatch sets it —
        # an eval forward (score during a guarded fit) must never inherit
        # the last training batch's flag as a metric gate
        self._nan_batch = None
        if self._segments is not None:
            self._rng_step += 1
            return self._forward_segmented(is_train)
        args = [a._jx for a in self.arg_arrays]
        aux = [a._jx for a in self.aux_arrays]
        # rng must live on the executor's device: jit rejects mixed-device
        # args (e.g. cpu-bound module on a machine whose default is TPU)
        rng = self.next_rng()
        self._rng_step += 1
        fused_bwd = is_train and bool(self._diff_names())
        name = ("%s_forward%s" % (self._symbol_name(),
                                  "_backward" if fused_bwd else "")) \
            if _profiler.running() else ""
        with _profiler.span(name, "symbolic") as sp:
            if is_train:
                if self._diff_names():
                    if self._nan_guard:
                        outs, new_aux, grads, acc, batch_flag = \
                            self._get_fn("train_guard")(
                                args, aux, rng, self._nan_acc_in())
                        self._nan_acc = acc
                        self._nan_batch = batch_flag
                        self._nan_stale = False
                    else:
                        outs, new_aux, grads = self._get_fn("train")(
                            args, aux, rng)
                    self._pending_grads = grads
                    self._last_state = (args, aux, rng)
                    sp.sync(grads)
                else:
                    outs, new_aux = self._get_fn("train_fwd")(args, aux, rng)
                    self._pending_grads = None
                    self._last_state = None
                for arr, new in zip(self.aux_arrays, new_aux):
                    arr._jx = new
            else:
                outs = self._get_fn("predict")(args, aux, rng)
                self._pending_grads = None
                self._last_state = None
            sp.sync(outs)
        self.outputs = [NDArray._from_jax(o, self._ctx) for o in outs]
        if self._monitor_callback is not None:
            for name, arr in zip(self.output_names, self.outputs):
                self._monitor_callback(name, arr)
        return self.outputs

    def backward(self, out_grads=None):
        """reference ``executor.py:134`` — applies grads into grad arrays
        honoring grad_req (they were computed fused with forward)."""
        if not self._diff_names():
            return
        if self._pending_grads is None:
            raise MXNetError("backward called before forward(is_train=True)")
        if self._pending_grads == "segmented":
            grads = self._backward_segmented(out_grads)
        elif out_grads is None:
            grads = self._pending_grads
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            dev = self._ctx.jax_device()
            out_grads = [jax.device_put(
                g._jx if isinstance(g, NDArray) else jnp.asarray(g), dev)
                for g in out_grads]
            args, aux, rng = self._last_state
            bname = ("%s_backward" % self._symbol_name()) \
                if _profiler.running() else ""
            with _profiler.span(bname, "symbolic") as sp:
                _outs, _new_aux, grads = self._get_fn("train_with_grads")(
                    args, aux, rng, out_grads)
                sp.sync(grads)
        for name in self._diff_names():
            g = grads.get(name)
            dst = self.grad_dict.get(name)
            if dst is None:
                continue
            if g is None:
                # segmented (group2ctx) backward only produces cotangents
                # for variables reached by the chain; a bound-but-unused
                # differentiable param gets a zero gradient (write) or is
                # left untouched (add)
                if self.grad_req[name] != "add":
                    dst._jx = jnp.zeros_like(dst._jx)
                continue
            if self.grad_req[name] == "add":
                dst._jx = dst._jx + g
            else:
                dst._jx = g

    def set_monitor_callback(self, callback):
        """reference MXExecutorSetMonitorCallback (outputs-level monitor)."""
        self._monitor_callback = callback

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        """reference ``executor.py`` copy_params_from"""
        for k, v in arg_params.items():
            if k in self.arg_dict:
                v.copyto(self.arg_dict[k])
            elif not allow_extra_params:
                raise MXNetError("unknown arg %r" % k)
        if aux_params:
            for k, v in aux_params.items():
                if k in self.aux_dict:
                    v.copyto(self.aux_dict[k])
                elif not allow_extra_params:
                    raise MXNetError("unknown aux %r" % k)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Rebind with new shapes; params with unchanged shapes are shared
        (reference executor.py reshape → shared-pool rebind; here the jit
        cache keys on shape so each shape compiles once)."""
        new_shapes = dict(kwargs)
        var_shape, var_dtype, _ = self._symbol._infer_shapes_full(new_shapes)
        arg_dict, grad_dict = {}, {}
        for n in self.arg_names:
            s = var_shape[n]
            if s == self.arg_dict[n].shape:
                arg_dict[n] = self.arg_dict[n]
                if self.grad_dict.get(n) is not None:
                    grad_dict[n] = self.grad_dict[n]
            else:
                if not (partial_shaping or n in kwargs or allow_up_sizing):
                    raise MXNetError(
                        "reshape: arg %r changes shape %s->%s without "
                        "partial_shaping" % (n, self.arg_dict[n].shape, s))
                arg_dict[n] = nd_zeros(s, ctx=self._ctx,
                                       dtype=self.arg_dict[n].dtype)
                if self.grad_req[n] != "null":
                    grad_dict[n] = nd_zeros(s, ctx=self._ctx,
                                            dtype=self.arg_dict[n].dtype)
        aux_dict = {}
        for n in self.aux_names:
            s = var_shape[n]
            aux_dict[n] = self.aux_dict[n] if s == self.aux_dict[n].shape \
                else nd_zeros(s, ctx=self._ctx, dtype=self.aux_dict[n].dtype)
        return Executor(self._symbol, self._ctx, arg_dict, grad_dict,
                        dict(self.grad_req), aux_dict, self._group2ctx)

    def debug_str(self):
        lines = ["Symbol outputs: %s" % self.output_names]
        for node in self._symbol._nodes():
            lines.append("%s %s" % (node.op.name if node.op else "var",
                                    node.name))
        return "\n".join(lines)

    # -- binding constructors --------------------------------------------
    @staticmethod
    def _bind(symbol, ctx, args, args_grad=None, grad_req="write",
              aux_states=None, group2ctx=None, shared_exec=None):
        """reference ``Executor::Bind`` ``graph_executor.cc:917``"""
        if isinstance(ctx, (list, tuple)):
            if len(ctx) != 1:
                raise MXNetError("Executor binds one context; use Module "
                                 "for multi-device data parallelism")
            ctx = ctx[0]
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        if isinstance(args, (list, tuple)):
            arg_dict = dict(zip(arg_names, args))
        else:
            arg_dict = dict(args)
        if aux_states is None:
            aux_dict = {}
        elif isinstance(aux_states, (list, tuple)):
            aux_dict = dict(zip(aux_names, aux_states))
        else:
            aux_dict = dict(aux_states)
        missing_aux = [n for n in aux_names if n not in aux_dict]
        if missing_aux:
            # allocate zero-init aux (shapes inferred from bound args)
            shapes = {n: a.shape for n, a in arg_dict.items()}
            var_shape, _vd, _ = symbol._infer_shapes_full(shapes)
            for n in missing_aux:
                aux_dict[n] = nd_zeros(var_shape[n], ctx=ctx)
        if args_grad is None:
            grad_dict = {}
        elif isinstance(args_grad, (list, tuple)):
            grad_dict = {n: g for n, g in zip(arg_names, args_grad)
                         if g is not None}
        else:
            grad_dict = dict(args_grad)
        return Executor(symbol, ctx, arg_dict, grad_dict, grad_req, aux_dict,
                        group2ctx)

    @staticmethod
    def _simple_bind(symbol, ctx, grad_req="write", type_dict=None,
                     shared_exec=None, group2ctx=None, **kwargs):
        """reference ``symbol.py:837`` simple_bind — infer + allocate."""
        if isinstance(ctx, (list, tuple)):
            ctx = ctx[0]
        type_dict = dict(type_dict or {})
        # __shape__ attrs are consumed inside _infer_shapes_full
        for node in symbol._nodes():
            if node.is_variable and "__dtype__" in node.misc_attr \
                    and node.name not in type_dict:
                type_dict[node.name] = node.misc_attr["__dtype__"]
        var_shape, var_dtype, _ = symbol._infer_shapes_full(kwargs, type_dict)
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        unknown = [n for n in arg_names + aux_names
                   if var_shape.get(n) is None]
        if unknown:
            raise MXNetError("simple_bind: cannot infer shapes for %s — "
                             "provide them as kwargs" % unknown)
        arg_dict = {}
        grad_dict = {}
        if isinstance(grad_req, str):
            req = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            req = dict(zip(arg_names, grad_req))
        else:
            req = {n: grad_req.get(n, "null") for n in arg_names}
        for n in arg_names:
            dt = type_dict.get(n) or var_dtype.get(n) or np.float32
            arg_dict[n] = nd_zeros(var_shape[n], ctx=ctx, dtype=dt)
            if req.get(n, "null") != "null":
                grad_dict[n] = nd_zeros(var_shape[n], ctx=ctx, dtype=dt)
        aux_dict = {n: nd_zeros(var_shape[n], ctx=ctx,
                                dtype=var_dtype.get(n) or np.float32)
                    for n in aux_names}
        # shared_exec (bucketing): share parameter arrays with the shared
        # executor (reference shared data_pool_, graph_executor.cc:336-340)
        if shared_exec is not None:
            for n in arg_names:
                src = shared_exec.arg_dict.get(n)
                if src is not None and src.shape == arg_dict[n].shape:
                    arg_dict[n] = src
                    if n in shared_exec.grad_dict and n in grad_dict:
                        grad_dict[n] = shared_exec.grad_dict[n]
            for n in aux_names:
                src = shared_exec.aux_dict.get(n)
                if src is not None and src.shape == aux_dict[n].shape:
                    aux_dict[n] = src
        return Executor(symbol, ctx, arg_dict, grad_dict, req, aux_dict,
                        group2ctx)
