"""The single operator registry.

The reference has THREE coexisting op registration systems (SURVEY §2.3:
legacy ``OperatorProperty`` ``include/mxnet/operator.h:166``, NNVM ``FCompute``
``include/mxnet/op_attr_types.h``, and the dead SimpleOp
``include/mxnet/operator_util.h:243``). This framework has exactly one.

An :class:`OpDef` bundles everything the reference spreads across attr maps
(FInferShape/FInferType/FGradient/FResourceRequest/DeclareBackwardDependency):

* ``arguments``/``aux_states``/``outputs`` — named I/O (may depend on attrs,
  e.g. Concat's ``num_args``, Convolution's ``no_bias``).
* ``params`` — typed attr spec (the ``DMLC_DECLARE_PARAMETER`` analog); values
  are parsed from python values *or* strings so graph JSON round-trips.
* ``apply`` — a pure JAX function ``(attrs, inputs, aux, is_train, rng) ->
  (outputs, aux_updates)``.  Shape/dtype inference is DERIVED from it via
  ``jax.eval_shape`` (no hand-written InferShape pass), and gradients come
  from JAX autodiff through it (ops with bespoke backward semantics — e.g.
  SoftmaxOutput — embed a ``jax.custom_vjp`` inside ``apply``).

Both the imperative ``mx.nd.*`` namespace and the symbolic ``mx.sym.*``
namespace are generated from this registry at import, mirroring how the
reference generates python functions from the C op registry at import
(``python/mxnet/_ctypes/ndarray.py:155``).
"""

from __future__ import annotations

import ast
from functools import lru_cache

import jax
import numpy as np

from ..base import MXNetError

__all__ = [
    "OpDef", "register", "get", "list_ops", "REQUIRED",
    "pbool", "pint", "pfloat", "pstr", "ptuple", "ptuple_or_int", "pdtype",
    "attrs_key", "jitted_apply",
]

_REGISTRY: dict[str, "OpDef"] = {}
_ALIASES: dict[str, str] = {}

# op-name -> count of OpDef.apply calls this process (trace-time compute
# invocations — NOT word-grep mentions).  tests/conftest.py dumps this
# at session end when MXNET_OP_COVERAGE_OUT is set; tools/gen_op_census
# reads the dump so the census "coverage" column counts real executions.
INVOCATIONS: dict[str, int] = {}

REQUIRED = object()


# ---------------------------------------------------------------------------
# attr parsers (strings from graph JSON / user kwargs -> canonical python)
# ---------------------------------------------------------------------------

def pbool(v):
    if isinstance(v, str):
        return v.strip().lower() in ("true", "1", "yes")
    return bool(v)


def pint(v):
    return int(v)


def pfloat(v):
    return float(v)


def pstr(v):
    return str(v)


def ptuple(v):
    """Parse '(2, 2)' / '[2,2]' / (2,2) / 2 -> tuple of ints."""
    if isinstance(v, str):
        v = ast.literal_eval(v.strip())
    if isinstance(v, (int, float)):
        return (int(v),)
    return tuple(int(x) for x in v)


def ptuple_or_int(v):
    t = ptuple(v)
    return t


_DTYPE_NAMES = {
    "float32": np.float32, "float64": np.float64, "float16": np.float16,
    "bfloat16": "bfloat16", "uint8": np.uint8, "int32": np.int32,
    "int8": np.int8, "int64": np.int64, "bool": np.bool_,
}


def pdtype(v):
    """dtype attr -> canonical string name."""
    if v is None:
        return None
    if isinstance(v, str):
        if v in _DTYPE_NAMES:
            return v
        raise MXNetError("unknown dtype %r" % v)
    return np.dtype(v).name if not str(v) == "bfloat16" else "bfloat16"


def np_dtype(name):
    import jax.numpy as jnp

    if name == "bfloat16":
        return jnp.bfloat16
    return np.dtype(name)


# ---------------------------------------------------------------------------
# OpDef
# ---------------------------------------------------------------------------

def _as_fn(x):
    return x if callable(x) else (lambda attrs, _x=x: list(_x))


class OpDef:
    def __init__(self, name, apply_fn, *, arguments=("data",), aux_states=(),
                 outputs=("output",), params=None, needs_rng=False,
                 hint=None, key_var_num_args=None, doc="", open_params=False):
        self.name = name
        self._apply = apply_fn
        self._arguments = _as_fn(arguments)
        self._aux_states = _as_fn(aux_states)
        self._outputs = _as_fn(outputs)
        self.params = params or {}
        self.needs_rng = needs_rng
        # accept arbitrary extra string kwargs (the Custom op's string-kwarg
        # protocol, reference ``src/operator/custom/custom.cc:183``)
        self.open_params = open_params
        # attr naming the variable-arity input count (reference nnvm
        # `key_var_num_args`, e.g. Concat's num_args)
        self.key_var_num_args = key_var_num_args
        self.hint = hint or name.lower().lstrip("_")
        self.doc = doc
        # optional backward shape-inference rule, attached by ops/infer.py:
        # (attrs, in_shapes, in_dtypes, aux_shapes) -> (in_shapes, aux_shapes)
        self.infer_inputs = None

    # -- I/O names --------------------------------------------------------
    def list_arguments(self, attrs):
        return list(self._arguments(attrs))

    def list_aux_states(self, attrs):
        return list(self._aux_states(attrs))

    def list_outputs(self, attrs):
        return list(self._outputs(attrs))

    # -- attrs ------------------------------------------------------------
    def canonicalize_attrs(self, kwargs):
        """kwargs -> plain dict with parsed values; rejects unknown keys."""
        out = {}
        for k, (parser, default) in self.params.items():
            if k in kwargs and kwargs[k] is not None:
                out[k] = parser(kwargs[k])
            elif default is REQUIRED:
                raise MXNetError("op %s: required param %r missing" % (self.name, k))
            else:
                out[k] = default
        unknown = set(kwargs) - set(self.params)
        if unknown:
            if self.open_params:
                for k in unknown:
                    out[k] = str(kwargs[k])
            else:
                raise MXNetError(
                    "op %s: unknown params %s" % (self.name, sorted(unknown)))
        return out

    # -- compute ----------------------------------------------------------
    def apply(self, attrs, inputs, aux, is_train, rng):
        """Returns (outputs_list, aux_updates_list_or_None)."""
        INVOCATIONS[self.name] = INVOCATIONS.get(self.name, 0) + 1
        res = self._apply(attrs, list(inputs), list(aux), is_train, rng)
        if isinstance(res, tuple) and len(res) == 2 and isinstance(res[0], list):
            outs, aux_up = res
        elif isinstance(res, list):
            outs, aux_up = res, None
        else:
            outs, aux_up = [res], None
        n = len(self.list_outputs(attrs))
        if len(outs) != n:
            raise MXNetError(
                "op %s: apply returned %d outputs, declared %d" % (self.name, len(outs), n)
            )
        return outs, aux_up

    def infer(self, attrs, in_avals, aux_avals, is_train=True):
        """Output/aux-update avals via jax.eval_shape — the InferShape/InferType
        analog (reference runs nnvm passes at ``graph_executor.cc:413-414``)."""
        key = jax.random.PRNGKey(0) if self.needs_rng else None

        def f(inputs, aux):
            return self.apply(attrs, inputs, aux, is_train, key)

        return jax.eval_shape(f, list(in_avals), list(aux_avals))


# ---------------------------------------------------------------------------
# registration / lookup
# ---------------------------------------------------------------------------

def register(name, apply_fn=None, *, aliases=(), **kw):
    """Register an op; usable as decorator: ``@register('dot', ...)``."""

    def _do(fn):
        op = OpDef(name, fn, **kw)
        if name in _REGISTRY:
            raise MXNetError("op %s registered twice" % name)
        _REGISTRY[name] = op
        for a in aliases:
            _ALIASES[a] = name
        return fn

    if apply_fn is not None:
        return _do(apply_fn)
    return _do


def get(name) -> OpDef:
    name = _ALIASES.get(name, name)
    if name not in _REGISTRY:
        raise MXNetError("unknown op %r" % name)
    return _REGISTRY[name]


def list_ops():
    return sorted(_REGISTRY) + sorted(_ALIASES)


# ---------------------------------------------------------------------------
# jitted eager dispatch (imperative path)
# ---------------------------------------------------------------------------
# The reference pushes every imperative op through the engine with var deps
# (``src/c_api/c_api_ndarray.cc:323`` MXImperativeInvoke → PushFCompute); here
# each (op, attrs, is_train) gets one jitted callable and XLA/PJRT async
# dispatch provides the same fire-and-forget semantics.

# device the current executor trace targets ("tpu"/"cpu"/None) — set by
# the executor/imperative dispatch around tracing so device-dependent
# lowering decisions (Pallas vs XLA) follow the computation's actual
# device, not the process-wide jax.default_backend()
import contextvars as _contextvars

trace_device = _contextvars.ContextVar("mxnet_tpu_trace_device",
                                       default=None)


def on_tpu():
    """Whether the computation being traced targets a TPU: the
    executor/imperative dispatch sets :data:`trace_device`; outside any
    such trace the process default backend decides."""
    dev = trace_device.get()
    if dev is not None:
        return dev == "tpu"
    return jax.default_backend() == "tpu"


def count_kernel_path(op, path, reason):
    """Trace-time record of which lowering a Pallas-capable op took:
    ``ops.kernel_path{op, path, reason}`` with ``path`` one of
    ``pallas`` / ``interpret`` (reason ``ok``) or ``xla`` — an op whose
    kernel was asked for and refused says why (``not_tpu``, ``tile``,
    ``vmem``, ``dtype``), so a run can print the path each op really
    took instead of trusting the request."""
    from .. import telemetry

    telemetry.inc("ops.kernel_path", op=op, path=path, reason=reason)


def jitted_apply(op_name, attrs_tuple, is_train):
    # keyed on the trace device too: the traced jaxpr bakes in
    # device-dependent lowering decisions (Pallas vs XLA), so a CPU call
    # must not reuse a TPU-traced function or vice versa
    return _jitted_apply(op_name, attrs_tuple, is_train, trace_device.get())


@lru_cache(maxsize=None)
def _jitted_apply(op_name, attrs_tuple, is_train, _dev_key):
    op = get(op_name)
    attrs = dict(attrs_tuple)

    def f(inputs, aux, rng):
        outs, aux_up = op.apply(attrs, inputs, aux, is_train, rng)
        return outs, (aux_up if aux_up is not None else [])

    return jax.jit(f)


def attrs_key(attrs):
    """Canonical hashable form of a parsed-attr dict."""
    return tuple(sorted(attrs.items()))
