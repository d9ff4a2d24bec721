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

:func:`ssd_scan` is the second recurrence, a state-space layer whose decay
is ONE value a head (Mamba-2): ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
B_t^T``, ``y_t = S_t C_t + D x_t`` for every head's ``S (head_dim,
d_state)``, ``B`` and ``C`` the same for all heads.  A scalar decay lets a
chunk of positions go through as matrix products (the SSD form,
arXiv:2405.21060), which is what its docstring sets out.
"""

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["ssm_scan", "ssm_scan_plan", "ssd_scan", "ssd_scan_plan"]

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


# ---------------------------------------------------------------------------
# the chunked matrix form of a recurrence whose decay is one value a head
# ---------------------------------------------------------------------------

#: positions a chunk of :func:`ssd_scan` takes where the caller names none
_SSD_CHUNK = 256
#: heads a grid step of the kernel carries the state of
_SSD_HEADS = 8
_NEVER = -1e30
#: the two products that read and make the carried state are float32
#: through on both paths (six bfloat16 passes on the chip, where the
#: default is one)
_EXACT = jax.lax.Precision.HIGHEST


def _ssd_logs(dt, a, q):
    """``L (T, heads)``: the running sum of ``dt_t a`` inside each chunk of
    ``q`` positions, every value at or below 0."""
    t, h = dt.shape
    return jnp.cumsum((dt * a).reshape(t // q, q, h), axis=1).reshape(t, h)


def _ssd_xla(x, dt, a, b, c, d, state, q):
    """The chunked form in ``jnp``, a chunk after another; the carried
    state's two products float32 through, as the kernel has them."""
    t, h = dt.shape
    n = b.shape[1]
    p = x.shape[1] // h
    logs = _ssd_logs(dt, a, q)
    below = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]

    def through(s, xs):
        x_c, dt_c, l_c, b_c, c_c = xs
        x_c = x_c.reshape(q, h, p)
        decay = jnp.exp(jnp.where(below[:, :, None],
                                  l_c[:, None, :] - l_c[None, :, :], _NEVER))
        xw = dt_c[:, :, None] * x_c
        y = jnp.einsum("tsh,shp->thp", decay * (c_c @ b_c.T)[:, :, None],
                       xw) + jnp.exp(l_c)[:, :, None] * jnp.einsum(
            "tn,nhp->thp", c_c, s.reshape(n, h, p), precision=_EXACT)
        last = l_c[-1]
        s = jnp.repeat(jnp.exp(last), p)[None, :] * s + jnp.einsum(
            "sn,shp->nhp", b_c, jnp.exp(last[None] - l_c)[:, :, None] * xw,
            precision=_EXACT).reshape(n, h * p)
        return s, (y + d[None, :, None] * x_c).reshape(q, h * p)

    state, y = jax.lax.scan(through, state, tuple(
        v.reshape((t // q, q) + v.shape[1:]) for v in (x, dt, logs, b, c)))
    return state, y.reshape(t, -1)


def _ssd_kernel(x_ref, dtc_ref, lc_ref, lr_ref, bt_ref, c_ref, d_ref, s0_ref,
                y_ref, s_ref, state_scr, *, heads, width):
    """Grid ``(blocks of heads, chunks of positions)``, chunks innermost:
    the block's state ``(d_state, heads x width)`` stays in VMEM across its
    chunks.  The heads go through 128 lanes at a time (two of 64), each
    beside zeros where its neighbour lies, so every slice is of whole
    tiles."""
    from jax.experimental import pallas as pl

    j = pl.program_id(1)
    f32, bf16 = jnp.float32, jnp.bfloat16
    q = x_ref.shape[0]
    per = 128 // width                    # heads in 128 lanes

    @pl.when(j == 0)
    def _start():
        state_scr[...] = s0_ref[...]

    c_q, b_t = c_ref[...], bt_ref[...]        # (q, d_state), (d_state, q)
    cb = jnp.dot(c_q.astype(bf16), b_t.astype(bf16),
                 preferred_element_type=f32)                       # (q, q)
    below = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)

    def by_head(values):
        """A value a head, laid over the lanes of its head."""
        out = values[0]
        for i in range(1, per):
            out = jnp.where(lane >= i * width, values[i], out)
        return out

    for g in range(heads // per):
        lanes = pl.ds(128 * g, 128)
        xs = x_ref[:, lanes]                              # (q, 128)
        mine = range(per * g, per * g + per)
        l_col = [lc_ref[0, :, h:h + 1] for h in mine]     # (q, 1)
        dt_col = [dtc_ref[0, :, h:h + 1] for h in mine]
        l_end = [lr_ref[h:h + 1, q - 1:q] for h in mine]  # (1, 1)
        acc = None
        for i, h in enumerate(mine):
            own = (lane >= i * width) & (lane < (i + 1) * width)
            decay = jnp.exp(jnp.where(
                below, l_col[i] - lr_ref[h:h + 1, :], _NEVER))
            part = jnp.dot(
                (decay * cb).astype(bf16),
                jnp.where(own, xs * dt_col[i], 0.0).astype(bf16),
                preferred_element_type=f32)
            acc = part if acc is None else acc + part
        held = state_scr[:, lanes]                        # (d_state, 128)
        # the product that reads the carried state is float32 through ...
        acc = acc + by_head([jnp.exp(v) for v in l_col]) * jnp.dot(
            c_q, held, preferred_element_type=f32, precision=_EXACT)
        y_ref[:, lanes] = acc + d_ref[:, lanes] * xs
        push = by_head([dt_col[i] * jnp.exp(l_end[i] - l_col[i])
                        for i in range(per)])
        # ... and so is the one that makes it: what a chunk rounds here a
        # session carries for as long as its slowest head remembers
        state_scr[:, lanes] = by_head([jnp.exp(v) for v in l_end]) * held \
            + jnp.dot(b_t, xs * push, preferred_element_type=f32,
                      precision=_EXACT)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        s_ref[...] = state_scr[...]


def _ssd_pallas(x, dt, a, b, c, d, state, heads, q, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, h = dt.shape
    n = b.shape[1]
    width = x.shape[1] // h
    wide = heads * width
    logs = _ssd_logs(dt, a, q)

    def columns(v):
        """``(T, heads) -> (blocks, T, heads a block)``: a head's values
        down a column of its block."""
        return v.reshape(t, h // heads, heads).transpose(1, 0, 2)

    rows = pl.BlockSpec((q, wide), lambda i, j: (j, i))
    cols = pl.BlockSpec((1, q, heads), lambda i, j: (i, j, 0))
    held = pl.BlockSpec((n, wide), lambda i, j: (0, i))
    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, heads=heads, width=width),
        grid=(h // heads, t // q),
        in_specs=[rows, cols, cols,
                  pl.BlockSpec((heads, q), lambda i, j: (i, j)),
                  pl.BlockSpec((n, q), lambda i, j: (0, j)),
                  pl.BlockSpec((q, n), lambda i, j: (j, 0)),
                  pl.BlockSpec((1, wide), lambda i, j: (0, i)), held],
        out_specs=[rows, held],
        out_shape=[jax.ShapeDtypeStruct((t, h * width), jnp.float32),
                   jax.ShapeDtypeStruct((n, h * width), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, wide), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="ssd_scan",
        interpret=interpret,
    )(x, columns(dt), columns(logs), logs.T, b.T, c,
      jnp.repeat(d, width)[None, :], state)
    return state, y


def ssd_scan_plan(x, dt, b, chunk=_SSD_CHUNK):
    """``((heads, chunk), reason)``: the heads whose state a grid step of
    the kernel carries and the positions of a chunk at these shapes with
    ``reason`` None, or ``((None, chunk), reason)`` with why the call takes
    the plain path (``ops.kernel_path`` reasons), which goes through the
    same chunks.  A chunk is ``chunk`` positions, or all of them where
    there are fewer.  The rules are the v5e compiler's
    (``tests/test_tpu_aot_compile.py``)."""
    from .registry import on_tpu

    t, h = dt.shape
    q = min(t, chunk)
    if t % q:
        raise ValueError("%d positions are no whole chunks of %d" % (t, q))
    width = x.shape[1] // h
    if not on_tpu():
        return (None, q), "not_tpu"
    if any(v.dtype != jnp.float32 for v in (x, dt, b)):
        return (None, q), "dtype"
    if width > 128 or 128 % width or (h * width) % 128:
        # heads go through 128 lanes at a time, whole heads in them
        return (None, q), "lanes"
    if q % 128 or b.shape[1] % 8:
        return (None, q), "tile"
    heads = _SSD_HEADS if h % _SSD_HEADS == 0 \
        and (_SSD_HEADS * width) % 128 == 0 else h
    return (heads, q), None


def ssd_scan(x, dt, a, b, c, d, state, chunk=_SSD_CHUNK):
    """The recurrence of ``heads`` heads from ``state`` over the positions
    of ``dt``, in chunks of ``chunk`` positions (``Q``): ``(the last state
    (d_state, heads x head_dim), y (T, heads x head_dim))``.

    ``x (T, heads x head_dim)``, ``dt (T, heads)`` (after its softplus),
    ``a``/``d (heads,)``, ``b``/``c (T, d_state)``, all float32; a head's
    ``head_dim`` values lie side by side in ``x``, ``y`` and the state's
    rows.  With ``L_t`` the running sum of ``dt_t a`` inside a chunk and
    ``S_prev`` the state before it,

    ``y_t = sum_{s<=t} exp(L_t - L_s) (C_t . B_s) dt_s x_s + exp(L_t)
    S_prev C_t + D x_t``, ``S = exp(L_Q) S_prev + sum_s exp(L_Q - L_s)
    dt_s x_s B_s^T``:

    three matrix products a head and one for all heads (``C B^T``), every
    exponent at or below 0.  A position with ``dt = 0`` and ``x = 0`` leaves
    the state as it was, which is how a caller pads.

    On a TPU trace this is one Pallas kernel, ``ssd_scan``: grid over
    blocks of heads and, innermost, chunks, the block's state in VMEM
    across its chunks, the masked decay ``(Q, Q)`` of a head made and used
    in VMEM, so what touches HBM is ``x``, ``dt``, ``B``, ``C`` and ``y``
    once.  Its products take bfloat16 operands and accumulate in float32
    (as the published kernels do), but the two that read and make the
    carried state (``S_prev C_t`` and ``x B^T``), which are float32
    through: what a chunk rounded into the state a session would carry for
    as long as its slowest head remembers.  Elsewhere, and at shapes
    the kernel refuses (:func:`ssd_scan_plan`), the same chunks go through
    ``jnp`` einsums with the same two products float32 through, so the
    state a prompt leaves is the same on both paths (off the recurrence by
    4e-06 of its size on the chip; 2e-03 were those two at the default).
    On the chip the compiler fuses a chunk's decays into the product that
    reads them, so the einsums alone read within a sixth of the kernel;
    served in the prefill they cost the granite-4.0-h-micro cell 0.9-2.2%
    of its tokens and 8 s of set-up on the same seeds, which is why the
    kernel is here (PERF.md section 6, PR 46).  The choice is counted
    under ``ops.kernel_path``."""
    from .registry import count_kernel_path

    (heads, q), reason = ssd_scan_plan(x, dt, b, chunk)
    if reason is None:
        count_kernel_path("ssd_scan", "pallas", "ok")
        return _ssd_pallas(x, dt, a, b, c, d, state, heads, q)
    count_kernel_path("ssd_scan", "xla", reason)
    return _ssd_xla(x, dt, a, b, c, d, state, q)
