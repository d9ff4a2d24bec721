"""The selective state-space recurrence over a run of positions.

``s_t = exp(dt_t a) * s_{t-1} + (dt_t u_t) b_t^T``, ``y_t = s_t c_t``, with
``dt``/``u (T, d_inner)``, ``b``/``c (T, d_state)``, ``a``/``s (d_state,
d_inner)``, all float32 (``d_inner`` last: whole lanes).  A position with
``dt = 0`` leaves the state as it was, which is how a caller pads.

On a TPU trace :func:`ssm_scan` is one Pallas kernel, ``ssm_scan``: the
state of a block of ``d_inner`` stays in VMEM while the positions go
through it one after another, so what touches HBM is ``dt``, ``u`` and
``y`` once.  Elsewhere, and at shapes the kernel refuses
(:func:`ssm_scan_plan`), the positions go through in chunks with a prefix
scan inside a chunk, whose ``(chunk, d_state, d_inner)`` products are
written and read some ten times over: on the v5e that was over half of a
prefill of ``models/sambay.py`` (PERF.md section 6, PR 31).  The choice is
counted under ``ops.kernel_path``.
"""

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["ssm_scan", "ssm_scan_plan"]

#: positions the plain path takes at once, and positions a grid step of
#: the kernel holds (``b`` and ``c`` of a grid step stand in VMEM one
#: ``(d_state, 1)`` column a position, a tile each)
_CHUNK = 128
#: lanes of ``d_inner`` a grid step of the kernel carries the state of
_BLOCK = 512


def _scan_xla(dt, u, b, c, a, state):
    t = dt.shape[0]
    chunk = math.gcd(t, _CHUNK)

    def combine(first, then):
        return first[0] * then[0], then[0] * first[1] + then[1]

    def through(s, xs):
        dt_c, u_c, b_c, c_c = xs
        decay = jnp.exp(dt_c[:, None, :] * a[None])
        push = (dt_c * u_c)[:, None, :] * b_c[:, :, None]
        decay, push = jax.lax.associative_scan(combine, (decay, push))
        states = decay * s[None] + push
        return states[-1], (states * c_c[:, :, None]).sum(1)

    state, y = jax.lax.scan(through, state, tuple(
        x.reshape((t // chunk, chunk) + x.shape[1:])
        for x in (dt, u, b, c)))
    return state, y.reshape(t, -1)


def _scan_kernel(dt_ref, u_ref, b_ref, c_ref, a_ref, s0_ref, y_ref, s_ref,
                 state_scr, *, chunk):
    """Grid ``(blocks of d_inner, chunks of positions)``, positions
    innermost: the block's state stays in VMEM across its chunks."""
    from jax.experimental import pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _start():
        state_scr[...] = s0_ref[...]

    a = a_ref[...]                                  # (d_state, block)

    def position(t, s):
        dt_t = dt_ref[pl.ds(t, 1), :]               # (1, block)
        s = jnp.exp(dt_t * a) * s \
            + (dt_t * u_ref[pl.ds(t, 1), :]) * b_ref[t]   # b_t (d_state, 1)
        y_ref[pl.ds(t, 1), :] = jnp.sum(s * c_ref[t], axis=0, keepdims=True)
        return s

    state_scr[...] = jax.lax.fori_loop(0, chunk, position, state_scr[...])

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        s_ref[...] = state_scr[...]


def _scan_pallas(dt, u, b, c, a, state, block, chunk, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, d = dt.shape
    n = a.shape[0]
    rows = pl.BlockSpec((chunk, block), lambda i, j: (j, i))
    cols = pl.BlockSpec((chunk, n, 1), lambda i, j: (j, 0, 0))
    held = pl.BlockSpec((n, block), lambda i, j: (0, i))
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid=(d // block, t // chunk),
        in_specs=[rows, rows, cols, cols, held, held],
        out_specs=[rows, held],
        out_shape=[jax.ShapeDtypeStruct((t, d), jnp.float32),
                   jax.ShapeDtypeStruct((n, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, block), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="ssm_scan",
        interpret=interpret,
    )(dt, u, b[:, :, None], c[:, :, None], a, state)
    return state, y


def ssm_scan_plan(dt, a):
    """``((block, chunk), reason)``: the lanes of ``d_inner`` and the
    positions a grid step of the kernel takes at these shapes with
    ``reason`` None, or ``(None, reason)`` with why the call takes the
    plain path (``ops.kernel_path`` reasons).  The rules are the v5e
    compiler's (``tests/test_tpu_aot_compile.py``)."""
    from .registry import on_tpu

    t, d = dt.shape
    if not on_tpu():
        return None, "not_tpu"
    if dt.dtype != jnp.float32 or a.dtype != jnp.float32:
        return None, "dtype"
    if d % 128:
        return None, "lanes"
    chunk = min(t, _CHUNK)
    if t % chunk or chunk % 8:
        return None, "tile"
    block = _BLOCK if d % _BLOCK == 0 else 128
    return (block, chunk), None


def ssm_scan(dt, u, b, c, a, state):
    """The recurrence from ``state`` over the positions of ``dt``: ``(the
    last state (d_state, d_inner), y (T, d_inner))``."""
    from .registry import count_kernel_path

    sizes, reason = ssm_scan_plan(dt, a)
    if reason is None:
        count_kernel_path("ssm_scan", "pallas", "ok")
        return _scan_pallas(dt, u, b, c, a, state, *sizes)
    count_kernel_path("ssm_scan", "xla", reason)
    return _scan_xla(dt, u, b, c, a, state)
