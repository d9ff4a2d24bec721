"""Attention ops: flash attention (Pallas TPU kernel + blockwise-scan core).

The 2017 reference has NO attention operator (SURVEY §5.7: long-sequence
support there is bucketing + cuDNN fused RNN only).  This module is the
new-capability half of the long-context story; the other half is ring
attention / context parallelism in ``mxnet_tpu.parallel.ring`` which reuses
the same blockwise online-softmax core over an ICI ring.

Design:

* ``_attn_reference`` — O(L^2)-memory softmax(QK^T)V, the numerics oracle.
* ``_flash_scan`` — blockwise online softmax as a ``lax.scan`` over K/V
  blocks: O(L) memory, pure JAX, runs on any backend, fully differentiable.
* ``_flash_pallas`` — the TPU kernel: grid (batch*heads, q_blocks, k_blocks),
  K innermost ("arbitrary" dimension semantics) with VMEM scratch carrying
  (m, l, acc) across K steps — the canonical TPU flash-attention schedule
  (MXU for the two dots, VPU for the online-softmax rescale).
* ``_flash_blocks`` — the same online softmax with the queries in blocks
  too, so that a *window* (a query at ``p`` reads ``p-window+1..p``) skips
  the K/V blocks wholly outside it, and with K/V of fewer heads than Q read
  by their group of query heads without a repeated copy.
* ``flash_attention`` — ``jax.custom_vjp``: forward picks the Pallas kernel
  on a TPU trace (``_kernel_refusal`` says when not, and the choice is
  counted under ``ops.kernel_path``) else the scan; backward recomputes blockwise
  from the saved (o, lse) residuals — the standard FA2 backward, written as
  plain JAX matmuls per K block so XLA schedules them on the MXU.
  ``block=B`` with ``causal``: causal by blocks of ``B`` positions (a row
  sees its whole block), on every path.

* ``decode_attention`` — one query a slot over a dense K/V cache whose
  slots hold different numbers of rows (the decode step's full-attention
  layer): on a TPU trace a Pallas kernel that walks only the rows up to
  each slot's length, to 128 (``_decode_pallas``), else the two einsums
  and the softmax over every row (``_decode_xla``); not differentiated.

* ``latent_attention`` — the absorbed step of latent (MLA) attention: all
  heads of a slot over the slot's latent rows, which serve as keys and as
  values; on a TPU trace ``_latent_pallas`` (the decode kernel's walk, one
  copy of a chunk for both products, multiplied in sub-blocks), else
  ``_latent_xla``.

* ``write_slot_rows`` — the decode step's one new K (or V) row a slot (a
  run of ``B`` rows for a model that generates by blocks),
  put into a heads-major cache: on a TPU trace one Pallas kernel an array
  with every slot's tile of rows in flight at once (``_slot_write_pallas``),
  else one update-slice a slot (``_slot_write_xla``).

Shapes follow (batch, heads, seq, head_dim) throughout.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .registry import REQUIRED, pbool, pfloat, pint, register

NEG_INF = -1e30


def _attn_reference(q, k, v, causal=False, scale=None, kv_offset=0,
                    window=None, block=None):
    """Quadratic-memory reference attention (numerics oracle for tests).
    ``block`` with ``causal``: causal by blocks of ``block`` positions, a
    row sees its whole block (:func:`flash_attention`)."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    qi = jnp.arange(q.shape[2])[:, None]
    ki = jnp.arange(k.shape[2])[None, :] + kv_offset
    if causal:
        s = jnp.where(_sees(qi, ki, block), s, NEG_INF)
    if window is not None:
        s = jnp.where(ki > qi - window, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _sees(qi, ki, block):
    """The causal mask: query ``qi`` reads key ``ki`` at or below it, or,
    with ``block``, anywhere in a block of ``block`` positions at or below
    its own (``ki // block <= qi // block``)."""
    if block is None:
        return qi >= ki
    return qi // block >= ki // block


# ---------------------------------------------------------------------------
# blockwise scan core (shared by CPU path, backward pass, and ring attention)
# ---------------------------------------------------------------------------

def _flash_scan(q, k, v, causal, scale, block_k=512):
    """Blockwise attention as lax.scan over K blocks. Returns (out, lse).

    O(Lq·D + block_k·D) live memory per (batch, head); the scan is the
    XLA-native analog of the flash-attention loop.
    """
    orig_dtype = q.dtype
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    lk = k.shape[2]
    block_k = min(block_k, lk)
    nb = (lk + block_k - 1) // block_k
    pad = nb * block_k - lk
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, 0), (0, pad), (0, 0)))
    # lint: ok[recompile-hazard] block_k is a blocking-tuning knob with one default — per-value specialization is the intent
    kb = kf.reshape(kf.shape[0], kf.shape[1], nb, block_k, kf.shape[3])
    vb = vf.reshape(*kb.shape[:4], vf.shape[3])
    kb = jnp.moveaxis(kb, 2, 0)  # (nb, B, H, block_k, D)
    vb = jnp.moveaxis(vb, 2, 0)

    b, h, lq, _ = q.shape
    o0 = jnp.zeros((b, h, lq, v.shape[3]), jnp.float32)
    m0 = jnp.full((b, h, lq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, lq), jnp.float32)

    def step_masked(carry, kv):
        i, k_blk, v_blk = kv
        o, m, l = carry
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk) * scale
        kpos = i * block_k + jnp.arange(block_k)
        valid = kpos < lk
        if causal:
            qi = jnp.arange(lq)[:, None]
            valid = valid[None, :] & (qi >= kpos[None, :])
        else:
            valid = jnp.broadcast_to(valid[None, :], (lq, block_k))
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v_blk)
        return (o_new, m_new, l_new), None

    (o, m, l), _ = jax.lax.scan(
        step_masked, (o0, m0, l0),
        (jnp.arange(nb), kb, vb))
    l = jnp.maximum(l, 1e-30)
    out = (o / l[..., None]).astype(orig_dtype)
    lse = m + jnp.log(l)
    return out, lse


def _k_block_range(qb, block_q, block_k, num_kb, causal, window):
    """``(first, last)`` K blocks that hold a position some query of Q
    block ``qb`` reads: up to the diagonal when causal, from the window's
    far edge when there is one.  Works on Python and traced integers."""
    last = num_kb - 1
    if causal:
        last = jnp.minimum(last, (qb * block_q + block_q - 1) // block_k)
    first = 0
    if window is not None:
        first = jnp.maximum(0, qb * block_q - window + 1) // block_k
    return first, last


def _flash_blocks(q, k, v, causal, scale, block_q, block_k, window,
                  block=None):
    """Blockwise attention with the queries in blocks too: ``lax.map`` over
    Q blocks, and inside it a loop over the K/V blocks of
    :func:`_k_block_range` alone, so a block wholly outside the window (or
    above the diagonal) costs nothing.  ``k``/``v (B, kv_heads, Lk, D)`` may
    have fewer heads than ``q (B, H, Lq, D)``: query head ``i`` reads K/V
    head ``i // (H // kv_heads)``, by a grouped product and no copy.
    Products take operands in the inputs' dtype and accumulate in float32;
    the weights are rounded to ``v``'s dtype before their product.
    ``block``: causal by blocks (:func:`_sees`); it divides ``block_q`` and
    ``block_k``, so the K/V blocks visited are those of ``causal``.
    Returns (out, lse); forward only (the loop's trip count is traced)."""
    b, h, lq, d = q.shape
    n, lk, dv = k.shape[1], k.shape[2], v.shape[3]
    g = h // n
    block_q, block_k = min(block_q, lq), min(block_k, lk)
    if lq % block_q or lk % block_k:
        raise ValueError("blocks (%d, %d) do not divide lengths (%d, %d)"
                         % (block_q, block_k, lq, lk))
    num_qb, num_kb = lq // block_q, lk // block_k
    # lint: ok[recompile-hazard] block_q/block_k are blocking-tuning knobs with one value a caller — per-value specialization is the intent
    qr = jnp.moveaxis(q.reshape(b, n, g, num_qb, block_q, d), 3, 0)
    # lint: ok[recompile-hazard] as above
    kr = k.reshape(b, n, num_kb, block_k, d)
    # lint: ok[recompile-hazard] as above
    vr = v.reshape(b, n, num_kb, block_k, dv)

    def one_q_block(args):
        qb, q_blk = args                         # (b, n, g, block_q, d)
        qi = qb * block_q + jnp.arange(block_q)[:, None]

        def one_k_block(kb, carry):
            o, m, l = carry
            k_blk = jax.lax.dynamic_index_in_dim(kr, kb, 2, keepdims=False)
            v_blk = jax.lax.dynamic_index_in_dim(vr, kb, 2, keepdims=False)
            s = jnp.einsum("bngqd,bnkd->bngqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            ki = kb * block_k + jnp.arange(block_k)[None, :]
            valid = jnp.ones((block_q, block_k), bool)
            if causal:
                valid = valid & _sees(qi, ki, block)
            if window is not None:
                valid = valid & (ki > qi - window)
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
            l_new = l * alpha + p.sum(axis=-1)
            o_new = o * alpha[..., None] + jnp.einsum(
                "bngqk,bnkd->bngqd", p.astype(v.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return o_new, m_new, l_new

        first, last = _k_block_range(qb, block_q, block_k, num_kb, causal,
                                     window)
        o, m, l = jax.lax.fori_loop(
            first, last + 1, one_k_block,
            (jnp.zeros((b, n, g, block_q, dv), jnp.float32),
             jnp.full((b, n, g, block_q), NEG_INF, jnp.float32),
             jnp.zeros((b, n, g, block_q), jnp.float32)))
        l = jnp.maximum(l, 1e-30)
        return (o / l[..., None]).astype(q.dtype), m + jnp.log(l)

    out, lse = jax.lax.map(one_q_block, (jnp.arange(num_qb), qr))
    return (jnp.moveaxis(out, 0, 3).reshape(b, h, lq, dv),
            jnp.moveaxis(lse, 0, 3).reshape(b, h, lq))


# ---------------------------------------------------------------------------
# Pallas TPU forward kernel
# ---------------------------------------------------------------------------

def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
               scale, causal, window, block_q, block_k, num_kb, block=None):
    """Online-softmax flash attention body; grid = (BH, num_qb, num_kb),
    K innermost with scratch (m, l, acc) carried across K steps.  Both
    products take their operands as they come (bfloat16 stays bfloat16 on
    the MXU) and accumulate in float32; the weights are rounded to ``v``'s
    dtype before theirs.  ``block`` (a power of two that divides both block
    sizes): causal by blocks, which changes the mask of the diagonal block
    alone; the last position of ``qi``'s block is ``qi | (block - 1)``."""
    from jax.experimental import pallas as pl

    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _body():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]    # (block_q | block_k, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = None
        if causal or window is not None:
            qi = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            ki = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            if causal:
                valid = qi >= ki if block is None else qi | (block - 1) >= ki
            if window is not None:
                near = ki > qi - window
                valid = near if valid is None else valid & near
            s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[:, 0]                       # (block_q,)
        l_prev = l_scr[:, 0]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        if window is not None:
            # a query whose window starts past this block has read nothing
            # yet: its maximum is still NEG_INF and exp(0) must not count
            p = jnp.where(valid, p, 0.0)
        l_cur = l_prev * alpha + p.sum(axis=-1)
        m_scr[:, 0] = m_cur
        l_scr[:, 0] = l_cur
        acc_scr[:] = acc_scr[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal or window is not None:
        # skip the K blocks no query of this Q block reads: above the
        # diagonal, and wholly beyond the window's far edge
        first, last = _k_block_range(qb, block_q, block_k, num_kb, causal,
                                     window)

        @pl.when((kb >= first) & (kb <= last))
        def _():
            _body()
    else:
        _body()

    @pl.when(kb == num_kb - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0] = (acc_scr[:] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, :, 0] = (m_scr[:, 0] + jnp.log(l))


def _flash_pallas(q, k, v, causal, scale, block_q=256, block_k=512,
                  interpret=False, window=None, block=None):
    """``k``/``v`` may have fewer heads than ``q``: a query head's grid
    steps are handed its K/V head's blocks by the index map, and no copy of
    K or V is made.  ``v``'s rows may be of another width than ``q``'s and
    ``k``'s, and the context is of ``v``'s.  A skipped step (see :func:`_fa_kernel`) asks for the
    nearest block that is read, so it fetches nothing new."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lq, d = q.shape
    n, lk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = h // n
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    num_qb = lq // block_q
    num_kb = lk // block_k
    bh = b * h
    qr = q.reshape(bh, lq, d)
    kr = k.reshape(b * n, lk, d)
    vr = v.reshape(b * n, lk, dv)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_kb=num_kb, block=block)

    def kv_block(b_, q_, k_):
        first, last = _k_block_range(q_, block_q, block_k, num_kb, causal,
                                     window)
        return (b_ // group, jnp.clip(k_, first, last), 0)

    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b_, q_, k_: (b_, q_, 0)),
            pl.BlockSpec((1, block_k, d), kv_block),
            pl.BlockSpec((1, block_k, dv), kv_block),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b_, q_, k_: (b_, q_, 0)),
            # lse rides as (bh, lq, 1) so the block's minor-two dims are
            # (block_q, 1) — sublane divisible by 8, lane equal to the
            # array dim.  A (1, block_q) block puts 1 in the sublane
            # slot and fails Mosaic's tile rule
            pl.BlockSpec((1, block_q, 1), lambda b_, q_, k_: (b_, q_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # m
            pltpu.VMEM((block_q, 128), jnp.float32),   # l
            pltpu.VMEM((block_q, dv), jnp.float32),    # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention",
        interpret=interpret,
    )(qr, kr, vr)
    # the reshape drops the trailing singleton the lse BlockSpec needed
    return out.reshape(b, h, lq, dv), lse.reshape(b, h, lq)


def _kernel_refusal(q, k, block_q, block_k, block=None):
    """Why the Pallas kernel cannot take this call (``ops.kernel_path``
    reason), or None when it can.

    The rule is what the v5e compiler accepts, asked of it shape by shape
    (``tests/test_tpu_aot_compile.py`` holds the kernel to it): the grid
    needs each block to divide its sequence, and Mosaic needs a block's
    second-minor extent divisible by 8 unless the block spans the whole
    dimension.  The head dim is always a whole dimension of its block,
    so every head width lowers — 64 included; an earlier ``d % 128``
    gate kept such heads off the kernel for no reason the compiler
    gives."""
    from .registry import on_tpu

    if not on_tpu():
        return "not_tpu"
    lq, lk = q.shape[2], k.shape[2]
    bq, bk = min(block_q, lq), min(block_k, lk)
    if lq % bq or lk % bk:
        return "tile"
    if (bq != lq and bq % 8) or (bk != lk and bk % 8):
        return "tile"
    if block is not None and block & (block - 1):
        # the kernel's mask of a block is a bit-or (:func:`_fa_kernel`)
        return "block"
    return None


# ---------------------------------------------------------------------------
# custom-vjp flash attention (public functional API)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, block_q, block_k, window=None,
           block=None):
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, window,
                        block)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, window=None,
               block=None):
    from .registry import count_kernel_path

    if q.shape[1] % k.shape[1]:
        raise ValueError("%d query heads over %d K/V heads"
                         % (q.shape[1], k.shape[1]))
    op = "FlashAttention" if window is None else "FlashAttention.window"
    if block is not None:
        bq, bk = min(block_q, q.shape[2]), min(block_k, k.shape[2])
        if not causal or bq % block or bk % block:
            raise ValueError(
                "block=%d needs causal=True and to divide the blocks "
                "(%d, %d)" % (block, bq, bk))
        op = "FlashAttention.block"
    reason = _kernel_refusal(q, k, block_q, block_k, block)
    if reason is None:
        count_kernel_path(op, "pallas", "ok")
        out, lse = _flash_pallas(q, k, v, causal, scale, block_q, block_k,
                                 window=window, block=block)
    else:
        count_kernel_path(op, "xla", reason)
        if window is None and block is None and q.shape[1] == k.shape[1]:
            out, lse = _flash_scan(q, k, v, causal, scale, block_k)
        else:
            out, lse = _flash_blocks(q, k, v, causal, scale, block_q,
                                     block_k, window, block)
    return out, (q, k, v, out, lse)


def _flash_bwd_core(causal, scale, block_q, block_k, res, do, dlse=None,
                    window=None, block=None):
    """FA2 backward: blockwise over K, plain-JAX matmuls (MXU via XLA).

    ``dlse`` (optional, (B,H,Lq) f32) is the cotangent of the logsumexp
    output: d lse_i / d s_ij = p_ij, so it enters as ``ds += p * dlse``
    — the one extra term that makes the (out, lse) PAIR differentiable
    (ring attention merges blocks through lse, so lse carries real
    gradients there).  K/V of fewer heads than Q are repeated here, and
    their gradients summed over each group."""
    q, k, v, out, lse = res
    group = q.shape[1] // k.shape[1]
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if group > 1:
        kf, vf = (jnp.repeat(a, group, axis=1) for a in (kf, vf))
    dof = do.astype(jnp.float32)
    of = out.astype(jnp.float32)
    delta = (dof * of).sum(axis=-1)                  # (B,H,Lq)

    lk = k.shape[2]
    bk = min(block_k, lk)
    nb = (lk + bk - 1) // bk
    pad = nb * bk - lk
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = jnp.moveaxis(kf.reshape(kf.shape[0], kf.shape[1], nb, bk, kf.shape[3]), 2, 0)
    vb = jnp.moveaxis(vf.reshape(vf.shape[0], vf.shape[1], nb, bk, vf.shape[3]), 2, 0)

    lq = q.shape[2]

    def step(dq, kv):
        i, k_blk, v_blk = kv
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk) * scale
        kpos = i * bk + jnp.arange(bk)
        valid = jnp.broadcast_to((kpos < lk)[None, :], (lq, bk))
        qi = jnp.arange(lq)[:, None]
        if causal:
            valid = valid & _sees(qi, kpos[None, :], block)
        if window is not None:
            valid = valid & (kpos[None, :] > qi - window)
        p = jnp.where(valid, jnp.exp(s - lse[..., None]), 0.0)
        dv_blk = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof, v_blk)
        dsum = dp - delta[..., None]
        if dlse is not None:
            dsum = dsum + dlse.astype(jnp.float32)[..., None]
        ds = p * dsum * scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, k_blk)
        dk_blk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        return dq, (dk_blk, dv_blk)

    dq0 = jnp.zeros_like(qf)
    dq, (dk_b, dv_b) = jax.lax.scan(step, dq0, (jnp.arange(nb), kb, vb))

    def of_kv_heads(d_b):
        d = jnp.moveaxis(d_b, 0, 2).reshape(
            kf.shape[:3] + d_b.shape[-1:])[:, :, :lk]
        if group == 1:
            return d
        return d.reshape(k.shape[0], k.shape[1], group, lk, -1).sum(2)

    return (dq.astype(q.dtype), of_kv_heads(dk_b).astype(k.dtype),
            of_kv_heads(dv_b).astype(v.dtype))


def _flash_bwd(causal, scale, block_q, block_k, window, block, res, do):
    return _flash_bwd_core(causal, scale, block_q, block_k, res, do,
                           window=window, block=block)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# (out, lse) pair — the differentiable unit ring attention merges
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_pair(q, k, v, causal, scale, block_q, block_k):
    out, res = _flash_fwd(q, k, v, causal, scale, block_q, block_k)
    return out, res[4]


def _flash_pair_fwd(q, k, v, causal, scale, block_q, block_k):
    out, res = _flash_fwd(q, k, v, causal, scale, block_q, block_k)
    return (out, res[4]), res


def _flash_pair_bwd(causal, scale, block_q, block_k, res, cts):
    do, dlse = cts
    return _flash_bwd_core(causal, scale, block_q, block_k, res, do,
                           dlse=dlse)


_flash_pair.defvjp(_flash_pair_fwd, _flash_pair_bwd)


def flash_attention_with_lse(q, k, v, causal=False, softmax_scale=None,
                             block_q=256, block_k=512):
    """Like :func:`flash_attention` but also returns the per-query
    logsumexp (B, H, Lq) — differentiable in BOTH outputs, which is what
    lets ``parallel.ring`` merge per-shard kernel calls with gradients
    flowing through the merge weights."""
    if softmax_scale is None:
        softmax_scale = float(1.0 / np.sqrt(q.shape[-1]))
    return _flash_pair(q, k, v, bool(causal), float(softmax_scale),
                       int(block_q), int(block_k))


def flash_attention(q, k, v, causal=False, softmax_scale=None,
                    block_q=256, block_k=512, window=None, block=None):
    """Memory-efficient attention.  ``q (batch, heads, seq, head_dim)``;
    ``k``/``v (batch, kv_heads, seq, head_dim)`` with ``kv_heads`` a
    divisor of ``heads`` (query head ``i`` reads K/V head ``i // (heads //
    kv_heads)``, with no repeated copy of K and V).  ``v`` may have rows of
    a width of its own (latent attention's expanded heads score over 192
    values and carry 128), which is then the context's.  ``window``: a query at
    position ``p`` reads ``p - window + 1 .. p`` (with ``causal``), and the
    K/V blocks wholly outside are skipped.  ``block`` (with ``causal``):
    causal by blocks of ``block`` positions, row ``i`` reads column ``j``
    iff ``j // block <= i // block`` — causal between blocks, both ways
    inside one (generation by diffusion over blocks); it divides
    ``block_q`` and ``block_k``, the K/V blocks visited are those of
    ``causal`` and only the mask of the diagonal block changes.  The
    backward pass honours it."""
    if softmax_scale is None:
        softmax_scale = float(1.0 / np.sqrt(q.shape[-1]))
    return _flash(q, k, v, bool(causal), float(softmax_scale),
                  int(block_q), int(block_k),
                  None if window is None else int(window),
                  None if block is None else int(block))


# ---------------------------------------------------------------------------
# decode attention: one query a slot, over the rows the slot holds
# ---------------------------------------------------------------------------

#: bytes of K (and as many of V) one copy of the decode kernel moves: a
#: loop turn costs its waits and the issue of the next copies whatever they
#: move, so it should move a megabyte; two buffers of K and two of V are
#: then 4 MiB of the 16 MiB a kernel may use on a v5e
_DECODE_CHUNK_BYTES = 1 << 20
#: rows to which a slot's edge is read: the lanes of a tile of scores
_DECODE_PIECE = 128


def _decode_xla(q, cache_k, cache_v, lengths, scale):
    """Every row of the cache, masked: the scores over all ``rows``, their
    softmax in float32, the weights rounded to the cache's dtype, the
    weighted sum accumulated in float32."""
    scores = jnp.einsum("skgd,skmd->skgm", q, cache_k,
                        preferred_element_type=jnp.float32) * scale
    if lengths.ndim == 2:
        # a horizon a half of the group
        mask = jnp.arange(cache_k.shape[2])[None, None, :] \
            <= jnp.repeat(lengths, q.shape[2] // 2, axis=1)[:, :, None]
        mask = mask[:, None]
    else:
        mask = jnp.arange(cache_k.shape[2])[None, :] <= lengths[:, None]
        mask = mask[:, None, None, :]
    att = jax.nn.softmax(jnp.where(mask, scores, NEG_INF), axis=-1)
    return jnp.einsum("skgm,skmd->skgd", att.astype(cache_v.dtype), cache_v,
                      preferred_element_type=jnp.float32)


def _walk_slot(len_ref, pairs, sems, turns, softmax, chunk, piece,
               accumulate, low_ref=None):
    """The walk a decode kernel makes of its slot's rows: one grid step a
    slot, the caches in HBM.  A slot of length ``n`` is walked in ``n //
    chunk + 1`` turns of a loop: ``n // chunk`` whole chunks of ``chunk``
    rows, one copy each of every cache array, then the edge, of which only
    the pieces of ``piece`` rows at or below ``n`` are copied and
    multiplied.  The turns of all slots alternate between two buffers
    (``turns`` counts them across grid steps): a turn first starts the
    copies of the next one, which after a slot's last turn is the next
    slot's first, then waits for its own.

    ``pairs``: of each cache array ``(S, n, rows, d)`` in HBM, it and its
    two buffers ``(2, n, chunk, d)`` in VMEM; ``sems (len(pairs), 2)``.
    ``softmax``: the slot's running maximum, sum and accumulator in VMEM,
    set to nothing seen before the first turn.  ``accumulate(rows, first,
    n)`` is handed each array's rows ``(n, rows, d)`` from row ``first`` of
    the slot, and in the edge the slot's length ``n`` above which rows take
    no part (they hold whatever an earlier session, or an earlier turn,
    left); None in a whole chunk.  ``low_ref``: a second, lower horizon a
    slot that some of the caller's queries have (``low_ref[i] <=
    len_ref[i]``): a whole chunk that reaches above it is handed the slot's
    length too, for the caller to mask by the horizon each query has."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i, slots = pl.program_id(0), pl.num_programs(0)
    per = chunk // piece

    def copies(slot, first, rows, buf, at):
        """Every array's copy of ``rows`` rows from row ``first`` of
        ``slot`` into row ``at`` of buffer ``buf``."""
        return [pltpu.make_async_copy(
            hbm.at[slot, :, pl.ds(first, rows), :],
            vmem.at[buf, :, pl.ds(at, rows), :], sems.at[which, buf])
            for which, (hbm, vmem) in enumerate(pairs)]

    def whole(slot, c, buf):
        return copies(slot, pl.multiple_of(c * chunk, chunk), chunk, buf, 0)

    def edge(slot, c, p, buf):
        return copies(slot, pl.multiple_of(c * chunk + p * piece, piece),
                      piece, buf, p * piece)

    def start(slot, c, buf):
        n = len_ref[slot]

        @pl.when(c < n // chunk)
        def _whole():
            for copy in whole(slot, c, buf):
                copy.start()

        @pl.when(c == n // chunk)
        def _edge():
            for p in range(per):
                @pl.when(p <= n % chunk // piece)
                def _piece():
                    for copy in edge(slot, c, p, buf):
                        copy.start()

    @pl.when(i == 0)
    def _cold():
        turns[0] = 0
        start(0, 0, 0)

    length = len_ref[i]
    last = length // chunk
    turn0 = turns[0]
    m_scr, l_scr, acc_scr = softmax
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def turn(c, carry):
        buf = (turn0 + c) % 2
        more = c < last

        @pl.when(more | (i + 1 < slots))
        def _ahead():
            start(jnp.where(more, i, jnp.minimum(i + 1, slots - 1)),
                  jnp.where(more, c + 1, 0), 1 - buf)

        @pl.when(more)
        def _whole():
            for copy in whole(i, c, buf):
                copy.wait()
            if low_ref is None:
                accumulate([vmem[buf] for _, vmem in pairs], c * chunk, None)
                return
            below = (c + 1) * chunk <= low_ref[i] + 1

            @pl.when(below)
            def _below():
                accumulate([vmem[buf] for _, vmem in pairs], c * chunk, None)

            @pl.when(~below)
            def _across():
                accumulate([vmem[buf] for _, vmem in pairs], c * chunk,
                           length)

        @pl.when(c == last)
        def _edge():
            def piece_of(p, carry):
                for copy in edge(i, c, p, buf):
                    copy.wait()
                rows = pl.ds(pl.multiple_of(p * piece, piece), piece)
                accumulate([vmem[buf, :, rows, :] for _, vmem in pairs],
                           c * chunk + p * piece, length)
                return carry
            jax.lax.fori_loop(0, length % chunk // piece + 1, piece_of, 0)
        return carry

    jax.lax.fori_loop(0, last + 1, turn, 0)
    turns[0] = turn0 + last + 1


def _decode_kernel(len_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems,
                   turns, m_scr, l_scr, acc_scr, *, scale, chunk, piece,
                   low_ref=None):
    """:func:`_walk_slot` over K and V: the running maximum, sum and
    accumulator of the slot's ``(kv_heads, group)`` queries stay in VMEM
    across its turns.  With ``low_ref`` the first half of the group sees
    the rows up to ``low_ref[i]`` and the second those up to
    ``len_ref[i]``, which the walk reaches."""
    from jax.experimental import pallas as pl

    low = None if low_ref is None else low_ref[pl.program_id(0)]

    def accumulate(held, first, n):
        """``k``, ``v (kv, rows, d)`` join the running softmax."""
        k, v = held
        s = jax.lax.dot_general(
            q_ref[0], k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale    # (kv, g, rows)
        if n is not None:
            rows = k.shape[1]
            at = first + jax.lax.broadcasted_iota(jnp.int32, (1, 1, rows), 2)
            if low_ref is None:
                s = jnp.where(at <= n, s, NEG_INF)
            else:
                g = s.shape[1]
                half = jax.lax.broadcasted_iota(jnp.int32, (1, g, 1), 1)
                s = jnp.where(at <= jnp.where(half < g // 2, low, n), s,
                              NEG_INF)
            at = first + jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1)
            v = jnp.where(at <= n, v, jnp.zeros_like(v))
        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_cur
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # (kv, g, d)

    _walk_slot(len_ref, ((k_hbm, k_buf), (v_hbm, v_buf)), sems, turns,
               (m_scr, l_scr, acc_scr), chunk, piece, accumulate, low_ref)
    o_ref[0] = acc_scr[...] / l_scr[...]


def _decode_kernel_halves(len_ref, low_ref, *refs, **sizes):
    """:func:`_decode_kernel` with a horizon a half of the group: the lower
    one is a second scalar-prefetch operand."""
    _decode_kernel(len_ref, *refs, low_ref=low_ref, **sizes)


@functools.partial(jax.jit, static_argnames=("scale", "chunk", "piece",
                                             "interpret"))
def _decode_pallas(q, cache_k, cache_v, lengths, scale, chunk, piece,
                   interpret=False):
    """Jitted on its own, as :func:`_write_slot_rows` is: a step that calls
    it for eight layers traces and lowers the kernel once a shape, not once
    a layer, each of the times an engine's set-up lowers its step."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, kv, g, d = q.shape
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # a horizon a half of the group: the walk's is the higher one
    halves = lengths.ndim == 2
    horizons = (lengths[:, 1], lengths[:, 0]) if halves else (lengths,)

    def whole(i, *lens):
        return (i, 0, 0, 0)

    return pl.pallas_call(
        functools.partial(_decode_kernel_halves if halves
                          else _decode_kernel, scale=scale, chunk=chunk,
                          piece=piece),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(horizons),
            grid=(s,),
            in_specs=[pl.BlockSpec((1, kv, g, d), whole), hbm, hbm],
            out_specs=pl.BlockSpec((1, kv, g, d), whole),
            scratch_shapes=[pltpu.VMEM((2, kv, chunk, d), cache_k.dtype),
                            pltpu.VMEM((2, kv, chunk, d), cache_v.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32),            # turns
                            pltpu.VMEM((kv, g, 1), jnp.float32),    # m
                            pltpu.VMEM((kv, g, 1), jnp.float32),    # l
                            pltpu.VMEM((kv, g, d), jnp.float32)]),  # acc
        out_shape=jax.ShapeDtypeStruct((s, kv, g, d), jnp.float32),
        # the buffers, their semaphores and the count of turns carry over
        # from a slot to the next: the slots run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="decode_attention",
        interpret=interpret,
    )(*horizons, q, cache_k, cache_v)


def _decode_chunk(cache_k):
    """Rows of a whole chunk at these shapes: all K/V heads of as many rows
    as make :data:`_DECODE_CHUNK_BYTES`, a power of two of pieces, no more
    than the cache has."""
    kv, rows, d = cache_k.shape[1:]
    chunk = _DECODE_PIECE
    while chunk * 2 * kv * d * cache_k.dtype.itemsize <= _DECODE_CHUNK_BYTES \
            and chunk * 2 <= rows:
        chunk *= 2
    return chunk


def decode_attention_plan(q, cache_k):
    """``(granule, reason)``: the rows to whose multiple the Pallas kernel
    reads a slot at these shapes (a slot of length ``n`` costs ``n //
    granule + 1`` granules of K and of V) with ``reason`` None, or ``(rows,
    reason)`` with why the call takes the plain path, which reads all
    ``rows`` of every slot (``ops.kernel_path`` reasons).

    Rows of whole 128-lane width only (``lanes``: a model of narrower
    heads caches them in pairs, as :mod:`~mxnet_tpu.models.sambay` does),
    in a cache of whole granules (``tile``), a granule of all K/V heads
    within what one buffer may hold (``vmem``).  The kernel moves whole
    chunks (:func:`_decode_chunk`) below a slot's edge and granules at it.
    The rules are the v5e compiler's, asked shape by shape
    (``tests/test_tpu_aot_compile.py``)."""
    from .registry import on_tpu

    kv, rows, d = cache_k.shape[1:]
    if not on_tpu():
        return rows, "not_tpu"
    if cache_k.dtype not in (jnp.bfloat16, jnp.float32) \
            or q.dtype != cache_k.dtype:
        return rows, "dtype"
    if d % 128:
        # a cache of rows narrower than the 128 lanes lives rows-minor on
        # the chip, and the kernel's copies move whole tiles of 128 lanes
        return rows, "lanes"
    if rows % _DECODE_PIECE:
        return rows, "tile"
    if _DECODE_PIECE * kv * d * cache_k.dtype.itemsize \
            > 2 * _DECODE_CHUNK_BYTES:
        return rows, "vmem"
    return _DECODE_PIECE, None


def decode_attention(q, cache_k, cache_v, lengths, scale):
    """Attention of one query a slot over a dense cache whose slots hold
    different numbers of rows.

    ``q (S, kv_heads, group, d)``: the queries grouped by the K/V head they
    read; ``cache_k``/``cache_v (S, kv_heads, rows, d)``; ``lengths (S,)``
    int32 within ``0 .. rows - 1``, the inclusive horizon: slot ``i``
    attends rows ``0 .. lengths[i]``, so every slot reads one row at the
    least.  ``lengths (S, 2)`` is a horizon a HALF of the group, ``[i, 0]
    <= [i, 1]``: the first ``group // 2`` queries of every K/V head see the
    rows ``0 .. lengths[i, 0]`` and the others ``0 .. lengths[i, 1]`` (a
    pass of a model that generates by blocks over two blocks of a slot at
    once, :mod:`~mxnet_tpu.models.sdar`): one walk of the slot's rows as
    far as the higher horizon, the lower one a mask on the rows above it.
    Returns the context ``(S, kv_heads, group, d)`` float32.
    Scores accumulate in float32 from operands in the cache's dtype, the
    weights are rounded to the cache's dtype before the product with V,
    which accumulates in float32.

    On a TPU trace the Pallas kernel walks, of each slot, only the rows at
    or below its length, to a multiple of 128, and keeps the softmax in
    VMEM across them: neither rows nobody holds nor the ``(S, kv_heads,
    group, rows)`` scores touch HBM.  Elsewhere, and at shapes the kernel
    refuses (:func:`decode_attention_plan`), every row is read and masked.
    The choice is counted under ``ops.kernel_path``."""
    from .registry import count_kernel_path

    if lengths.ndim == 2 and (lengths.shape[1] != 2 or q.shape[2] % 2):
        raise ValueError("horizons %s for a group of %d: one a slot, or "
                         "two and a group of two halves"
                         % (lengths.shape, q.shape[2]))
    _, reason = decode_attention_plan(q, cache_k)
    if reason is None:
        count_kernel_path("decode_attention", "pallas", "ok")
        return _decode_pallas(q, cache_k, cache_v, lengths, float(scale),
                              _decode_chunk(cache_k), _DECODE_PIECE)
    count_kernel_path("decode_attention", "xla", reason)
    return _decode_xla(q, cache_k, cache_v, lengths, scale)


# ---------------------------------------------------------------------------
# latent attention: one query a head and slot, over the latent rows it holds
# ---------------------------------------------------------------------------

#: bytes of latent rows one copy of the latent kernel moves (the rotated
#: keys' copy beside it moves a quarter as much): 1024 rows at the
#: DeepSeek-V2 shapes.  Read again at PR 40 with the products in sub-blocks
#: (tools/perf/mla_variants.py, PERF.md section 6): 2048 rows a copy halve
#: the turns and read the same (0.5% slower, 0.1% faster in two grids),
#: because ``_walk_slot`` issues a possible copy for every piece of a
#: chunk at every turn, twice as many; 512 rows read 9% slower.  The other
#: layout, one array of 640 values a row and one copy a chunk, read 2%
#: faster at PR 39 and was not taken
_LATENT_CHUNK_BYTES = 1 << 20
#: bytes of float32 scores one sub-block of the latent kernel makes: the
#: vector registers of a v5e core, 64 of 4 KiB (512 rows for 128 heads;
#: 256 rows read the same to 0.7% slower, the whole chunk 2-4% slower)
_LATENT_SCORE_BYTES = 1 << 18


def _latent_xla(q_lat, q_rope, cache_lat, cache_rope, lengths):
    """Every row of the cache, masked: both products of the scores over all
    ``rows``, their softmax in float32, the weights rounded to the cache's
    dtype, the weighted sum of the same latent rows in float32."""
    lat, rope = cache_lat[:, 0], cache_rope[:, 0]
    scores = jnp.einsum("shc,smc->shm", q_lat, lat,
                        preferred_element_type=jnp.float32) \
        + jnp.einsum("shr,smr->shm", q_rope, rope,
                     preferred_element_type=jnp.float32)
    mask = jnp.arange(lat.shape[1])[None, :] <= lengths[:, None]
    att = jax.nn.softmax(jnp.where(mask[:, None, :], scores, NEG_INF), -1)
    return jnp.einsum("shm,smc->shc", att.astype(lat.dtype), lat,
                      preferred_element_type=jnp.float32)


def _latent_kernel(len_ref, ql_ref, qr_ref, lat_hbm, rope_hbm, o_ref,
                   lat_buf, rope_buf, sems, turns, m_scr, l_scr, acc_scr, *,
                   chunk, piece, sub, walk=_walk_slot):
    """:func:`_walk_slot` over a cache whose row is one latent vector: ONE
    copy of a chunk of latent rows is the right-hand side of the scores and
    of the weighted sum, all ``heads`` queries of the slot against it at
    once; the rotated keys' narrower rows ride in a copy of their own and
    add their product to the scores.

    The copy's granule is the walk's (``chunk`` rows, at the edge ``piece``)
    and the products' is ``sub`` rows, one step of the running softmax
    each.  A whole chunk is multiplied in sub-blocks unrolled into one
    basic block, the scores of the next written before the softmax of this
    one: the chip's scheduler keeps program order, and so has products to
    put under the exponentials.  The edge's pieces are waited for in the
    walk and multiplied after it, from the buffer they lie in, in the same
    sub-blocks, masked above the slot's length; a sub-block wholly above it
    is skipped.  The running maximum ``(heads, lanes)`` is kept the same in
    every lane and the running sum as one partial sum a lane, added up once
    a slot, pair by pair: a step reduces across lanes once, for the
    maximum.  (``walk``: tools/perf/mla_variants.py times the products
    over a walk that copies nothing.)"""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes = m_scr.shape[1]

    def scores(lat, rope):
        contract = (((1,), (1,)), ((), ()))
        return jax.lax.dot_general(ql_ref[0], lat, contract,
                                   preferred_element_type=jnp.float32) \
            + jax.lax.dot_general(qr_ref[0], rope, contract,
                                  preferred_element_type=jnp.float32)

    def across(x, n):
        """``x (heads, lanes)``, the same in every lane, over ``n``."""
        return x if n == lanes else jnp.concatenate([x] * (n // lanes), 1)

    def join(s, lat):
        """Scores ``s (heads, rows)`` and their rows ``lat (rows, c)`` as
        one step of the running softmax."""
        rows = s.shape[1]
        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - across(m_cur, rows))
        l_cur = l_scr[...] * alpha
        for at in range(0, rows, lanes):
            l_cur = l_cur + p[:, at:at + lanes]
        l_scr[...] = l_cur
        m_scr[...] = m_cur
        acc_scr[...] = acc_scr[...] * across(alpha, lat.shape[1]) \
            + jax.lax.dot_general(
                p.astype(lat.dtype), lat, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # (heads, c)

    def accumulate(held, first, n):
        """A whole chunk ``lat (1, chunk, c)``, ``rope (1, chunk, r)`` joins
        the running softmax.  A piece of the edge (``n`` given) is NOT
        multiplied here, 128 rows a step of the softmax: the walk has
        waited for it, and ``the_edge`` takes all of them at once."""
        if n is not None:
            return
        lat, rope = held[0][0], held[1][0]
        s = scores(lat[:sub], rope[:sub])
        for at in range(0, chunk, sub):
            ahead = at + sub
            s_next = scores(lat[ahead:ahead + sub], rope[ahead:ahead + sub]) \
                if ahead < chunk else None
            join(s, lat[at:ahead])
            s = s_next

    def the_edge():
        """The slot's last, partial chunk, from where the walk left it.
        What is read here of the walk beyond ``accumulate``'s arguments,
        and has to change with it
        (``test_the_latent_kernel_reads_the_edge_where_the_walk_left_it``
        fails on each):

        - turn ``t`` of all slots uses buffer ``t % 2``, and ``turns[0]``
          counts the turns made, this slot's last one included: the edge
          is in buffer ``(turns[0] - 1) % 2``;
        - piece ``p`` of the edge, the slot's rows from ``first + p *
          piece``, lies at row ``p * piece`` of that buffer, and every
          piece at or below the length has been waited for when the walk
          returns;
        - no copy into that buffer is started before the next grid step
          (the last turn started the next slot's first into the other).

        Rows above the length hold whatever an earlier turn left."""
        n = len_ref[pl.program_id(0)]
        first = n // chunk * chunk
        buf = (turns[0] - 1) % 2
        for at in range(0, chunk, sub):
            @pl.when(first + at <= n)
            def _sub_block():
                lat = lat_buf[buf, 0, at:at + sub, :]
                s = scores(lat, rope_buf[buf, 0, at:at + sub, :])
                row = first + at + jax.lax.broadcasted_iota(
                    jnp.int32, (1, sub), 1)
                s = jnp.where(row <= n, s, NEG_INF)
                row = first + at + jax.lax.broadcasted_iota(
                    jnp.int32, (sub, 1), 0)
                join(s, jnp.where(row <= n, lat, jnp.zeros_like(lat)))

    walk(len_ref, ((lat_hbm, lat_buf), (rope_hbm, rope_buf)), sems, turns,
         (m_scr, l_scr, acc_scr), chunk, piece, accumulate)
    the_edge()
    # the lanes' partial sums pair by pair, lane i with lane i + w: the
    # order of least rounding, the same here and in the interpreter, and
    # the sum comes out in every lane
    l = l_scr[...]
    w = lanes
    while w > 1:
        w //= 2
        l = l + pltpu.roll(l, w, 1)
    o_ref[0] = acc_scr[...] / across(l, acc_scr.shape[1])


@functools.partial(jax.jit, static_argnames=("chunk", "piece", "sub",
                                             "interpret"))
def _latent_pallas(q_lat, q_rope, cache_lat, cache_rope, lengths, chunk,
                   piece, sub, interpret=False):
    """Jitted on its own, as :func:`_decode_pallas` is.  ``sub``: the rows
    multiplied at a time, a divisor of ``chunk``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, h, c = q_lat.shape
    r = q_rope.shape[2]
    # the softmax's state a lane of a vector register wide, where the
    # shapes are whole registers (on the chip they are: the plan's rules)
    lanes = math.gcd(128, sub, c)
    hbm = pl.BlockSpec(memory_space=pl.ANY)

    def whole(i, lens):
        return (i, 0, 0)

    return pl.pallas_call(
        functools.partial(_latent_kernel, chunk=chunk, piece=piece, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s,),
            in_specs=[pl.BlockSpec((1, h, c), whole),
                      pl.BlockSpec((1, h, r), whole), hbm, hbm],
            out_specs=pl.BlockSpec((1, h, c), whole),
            scratch_shapes=[pltpu.VMEM((2, 1, chunk, c), cache_lat.dtype),
                            pltpu.VMEM((2, 1, chunk, r), cache_rope.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32),         # turns
                            pltpu.VMEM((h, lanes), jnp.float32),  # m
                            pltpu.VMEM((h, lanes), jnp.float32),  # l
                            pltpu.VMEM((h, c), jnp.float32)]),    # acc
        out_shape=jax.ShapeDtypeStruct((s, h, c), jnp.float32),
        # buffers, semaphores and the count of turns carry over from a slot
        # to the next: the slots run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="latent_attention",
        interpret=interpret,
    )(lengths, q_lat, q_rope, cache_lat, cache_rope)


def _latent_chunk(cache_lat):
    """Rows of a whole chunk: as many latent rows as make
    :data:`_LATENT_CHUNK_BYTES`, a power of two of pieces, no more than the
    cache has."""
    rows, c = cache_lat.shape[2:]
    chunk = _DECODE_PIECE
    while chunk * 2 * c * cache_lat.dtype.itemsize <= _LATENT_CHUNK_BYTES \
            and chunk * 2 <= rows:
        chunk *= 2
    return chunk


def _latent_sub(heads, chunk):
    """Rows multiplied at a time: as many as make :data:`_LATENT_SCORE_BYTES`
    of scores for all heads, a power of two of pieces, no more than a
    chunk."""
    sub = _DECODE_PIECE
    while heads * sub * 2 * 4 <= _LATENT_SCORE_BYTES and sub * 2 <= chunk:
        sub *= 2
    return sub


def latent_attention_plan(q_lat, cache_lat, cache_rope):
    """``(granule, reason)`` as :func:`decode_attention_plan` gives them:
    the rows to whose multiple the Pallas kernel reads a slot, or all
    ``rows`` with why the call takes the plain path.  Latent and rotated
    rows both of whole 128-lane width (``lanes``: a model pads its 64
    rotated values to 128, and counts the padding in what its cache
    holds), one dtype, bfloat16 or float32, for queries and both caches
    (``dtype``), a cache of whole granules (``tile``)."""
    from .registry import on_tpu

    rows = cache_lat.shape[2]
    if not on_tpu():
        return rows, "not_tpu"
    if cache_lat.dtype not in (jnp.bfloat16, jnp.float32) \
            or not q_lat.dtype == cache_lat.dtype == cache_rope.dtype:
        return rows, "dtype"
    if cache_lat.shape[3] % 128 or cache_rope.shape[3] % 128:
        return rows, "lanes"
    if rows % _DECODE_PIECE:
        return rows, "tile"
    return _DECODE_PIECE, None


def latent_attention(q_lat, q_rope, cache_lat, cache_rope, lengths):
    """Absorbed latent attention of one decode step: all heads of a slot
    read the slot's latent rows, which are their keys and their values.

    ``q_lat (S, heads, c)``: each head's query carried into the latent
    space; ``q_rope (S, heads, r)``: its rotated part, both with the
    softmax's scale already on them; ``cache_lat (S, 1, rows, c)`` the
    latent rows and ``cache_rope (S, 1, rows, r)`` the one rotated key a row
    that every head reads; ``lengths (S,)`` int32 within ``0 .. rows - 1``,
    the inclusive horizon.  ``score[s, h, m] = q_lat[s, h] . lat[s, m] +
    q_rope[s, h] . rope[s, m]``,
    softmax over ``m <= lengths[s]`` in float32, and the context ``(S,
    heads, c)`` float32 is the weights (rounded to the cache's dtype) over
    the same latent rows.  Neither a key nor a value of any head is built.

    On a TPU trace the Pallas kernel walks, of each slot, only the rows at
    or below its length, to a multiple of 128; one copy of a chunk
    (:func:`_latent_chunk` rows) serves both products, which take it
    :func:`_latent_sub` rows at a time, and the softmax stays in VMEM.
    Both sizes are decided here from the shapes.  Elsewhere, and where the
    kernel refuses (:func:`latent_attention_plan`), every row is read and
    masked.  The choice is counted under ``ops.kernel_path``."""
    from .registry import count_kernel_path

    _, reason = latent_attention_plan(q_lat, cache_lat, cache_rope)
    if reason is None:
        count_kernel_path("latent_attention", "pallas", "ok")
        chunk = _latent_chunk(cache_lat)
        return _latent_pallas(q_lat, q_rope, cache_lat, cache_rope, lengths,
                              chunk, _DECODE_PIECE,
                              _latent_sub(q_lat.shape[1], chunk))
    count_kernel_path("latent_attention", "xla", reason)
    return _latent_xla(q_lat, q_rope, cache_lat, cache_rope, lengths)


# ---------------------------------------------------------------------------
# slot rows: one new row a slot into a heads-major cache
# ---------------------------------------------------------------------------

#: bytes of VMEM the row writer's tiles may take: every slot's tile at once
#: where they fit, else two buffers of half as much, one group of slots
#: fetched while the other is changed and written back; with the rows
#: beside them the kernel stays inside the 16 MiB one may use on a v5e
_SLOT_WRITE_TILE_BYTES = 12 << 20
#: ... and of the rows themselves, which the kernel holds whole
_SLOT_WRITE_ROWS_BYTES = 2 << 20


def _slot_write_xla(cache, rows, at):
    """One update-slice a slot, in place in whatever layout the cache
    lives in (:func:`transformer_lm.write_rows` has why); a pass over all
    ``R`` rows would move the whole cache."""
    pieces = jnp.split(rows if rows.ndim == 4 else rows[:, :, None],
                       cache.shape[0])
    for i, piece in enumerate(pieces):
        cache = jax.lax.dynamic_update_slice(
            cache, piece, (i, 0, at[i], 0), allow_negative_indices=False)
    return cache


def _slot_write_tile(dtype):
    """Rows of the least tile of ``dtype`` that can be written alone: the
    sublanes of a vector register times the values packed in one."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _slot_write_kernel(at_ref, rows_hbm, cache_hbm, out_hbm, tiles, rows,
                       fetched, sems, *, run=1):
    """One invocation an array.  ``cache_hbm`` and ``out_hbm`` are the
    same buffer and stay in HBM; what moves is, of each slot, the aligned
    ``(n, tile, d)`` tile that holds row ``at[i]`` (a bfloat16 row is half
    a packed sublane, so the tile and not the row is the least that can be
    written).  A slot's ``run`` rows ``at[i] .. at[i] + run - 1`` lie in
    that one tile (``run`` divides the tile's rows and ``at[i]``), and
    ``rows`` holds them as ``(S, n * run, d)``, head-major.  A group's
    fetches are all started before one is waited for; then a slot at a time the fetch is waited for, the row replaced in
    VMEM and the write-back started, so nothing waits in turn but the
    scalar core that issues them.  A fetch has a semaphore of its own
    (``fetched``): copies of one size on one semaphore cannot be told
    apart, and a tile must not be changed before its own copy has landed.
    The write-backs of a buffer share one, since they are only ever waited
    for all together.  With more than one group (``tiles`` is then two
    buffers of a group) the next group's fetches are in flight meanwhile,
    into the other buffer."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, n, _, d = cache_hbm.shape
    group, tile = tiles.shape[1], tiles.shape[3]
    groups = -(-s // group)
    written, rows_in = sems.at[0], sems.at[1, 0]

    def tile_of(ref, i):
        first = pl.multiple_of((at_ref[i] // tile) * tile, tile)
        return ref.at[i, :, pl.ds(first, tile), :]

    def fetch(g, j):
        return pltpu.make_async_copy(tile_of(cache_hbm, g * group + j),
                                     tiles.at[g % 2, j], fetched.at[g % 2, j])

    def write_back(g, j):
        return pltpu.make_async_copy(tiles.at[g % 2, j],
                                     tile_of(out_hbm, g * group + j),
                                     written.at[g % 2])

    def each_slot_of(g, body):
        def step(j, carry):
            body(g, j)
            return carry
        jax.lax.fori_loop(0, min(group, s - g * group), step, 0)

    def through_vmem(g, j):
        i = g * group + j
        fetch(g, j).wait()
        sublane = jax.lax.broadcasted_iota(jnp.int32, (tile, d), 0)
        first = at_ref[i] % tile
        hits = [sublane == (first + r if r else first) for r in range(run)]
        for h in range(n):
            for r, hit in enumerate(hits):
                row = jnp.broadcast_to(rows[i, pl.ds(h * run + r, 1), :],
                                       (tile, d))
                tiles[g % 2, j, h] = jnp.where(hit, row, tiles[g % 2, j, h])
        write_back(g, j).start()

    all_rows = pltpu.make_async_copy(rows_hbm, rows, rows_in)
    all_rows.start()
    each_slot_of(0, lambda g, j: fetch(g, j).start())
    all_rows.wait()
    for g in range(groups):
        if g + 1 < groups:
            if g:
                # the buffer the next group lands in is the last group's
                each_slot_of(g - 1, lambda g, j: write_back(g, j).wait())
            each_slot_of(g + 1, lambda g, j: fetch(g, j).start())
        each_slot_of(g, through_vmem)
    for g in range(max(0, groups - 2), groups):
        each_slot_of(g, lambda g, j: write_back(g, j).wait())


def _slot_write_pallas(cache, rows, at, group, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, n, _, d = cache.shape
    run = rows.shape[2] if rows.ndim == 4 else 1
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    buffers = 1 if group >= s else 2
    return pl.pallas_call(
        functools.partial(_slot_write_kernel, run=run),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(),
            in_specs=[hbm, hbm],
            out_specs=hbm,
            scratch_shapes=[
                pltpu.VMEM((buffers, group, n,
                            _slot_write_tile(cache.dtype), d), cache.dtype),
                pltpu.VMEM((s, n * run, d), cache.dtype),
                pltpu.SemaphoreType.DMA((buffers, group)),
                pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        # operands: at, rows, cache; the cache is written where it lies
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=16 << 20),
        name="slot_write",
        interpret=interpret,
    )(at, rows.reshape(s, n * run, d), cache)


def write_slot_rows_plan(cache, rows):
    """``(group, reason)``: the slots whose tiles the Pallas kernel holds
    in VMEM at once with ``reason`` None (all ``S`` where they fit, else a
    group that is fetched while another is written back), or
    ``(0, reason)`` with why the call takes the plain path
    (``ops.kernel_path`` reasons).  Decided from what can be seen here:
    bfloat16 and float32 caches only (``dtype``), rows of whole 128-lane
    width (``lanes``: a narrower cache lives rows-minor on the chip, as
    :func:`decode_attention_plan` has it), ``R`` a multiple of the tile's
    rows (``tile``), and the rows and one tile a buffer inside the
    kernel's VMEM (``vmem``); a run of rows a slot has to divide the
    tile's rows, so that it lies in one tile (``run``)."""
    from .registry import on_tpu

    s, n, r, d = cache.shape
    run = rows.shape[2] if rows.ndim == 4 else 1
    if rows.shape not in ((s, n, d), (s, n, run, d)):
        raise ValueError("rows %s for a cache %s: one (n, d) or one run "
                         "(n, B, d) a slot" % (rows.shape, cache.shape))
    if not on_tpu():
        return 0, "not_tpu"
    if cache.dtype not in (jnp.bfloat16, jnp.float32):
        return 0, "dtype"
    if d % 128:
        return 0, "lanes"
    tile = _slot_write_tile(cache.dtype)
    if r % tile:
        return 0, "tile"
    if tile % run:
        return 0, "run"
    itemsize = cache.dtype.itemsize
    tile_bytes = n * tile * d * itemsize
    # in VMEM a slot's ``(n, d)`` rows fill whole tiles of ``tile`` sublanes
    rows_bytes = s * -(-n * run // tile) * tile * d * itemsize
    if rows_bytes > _SLOT_WRITE_ROWS_BYTES \
            or 2 * tile_bytes > _SLOT_WRITE_TILE_BYTES:
        return 0, "vmem"
    if s * tile_bytes <= _SLOT_WRITE_TILE_BYTES:
        return s, None
    return _SLOT_WRITE_TILE_BYTES // (2 * tile_bytes), None


def write_slot_rows(cache, rows, at):
    """``cache (S, n, R, d)`` with ``rows[i] (n, d)``, cast to the cache's
    dtype, at ``[i, :, at[i]]``; ``at (S,)`` int32 within ``0 .. R - 1``
    (a ring's caller passes ``pos % R``).  Every other element is bit for
    bit what it was, and under donation the cache is written in place.
    ``rows (S, n, B, d)`` is a run of ``B`` rows a slot, at ``[i, :, at[i]
    .. at[i] + B - 1]`` with ``at[i]`` a multiple of ``B`` (a pass over a
    block of a model that generates by blocks): a run that ``B`` divides
    never crosses a tile.

    On a TPU trace one Pallas kernel an array moves each slot's tile of
    rows through VMEM, all slots' tiles in flight at once
    (:func:`write_slot_rows_plan` has the sizes and the refusals);
    elsewhere one update-slice a slot.  The choice is counted under
    ``ops.kernel_path``."""
    from .registry import count_kernel_path

    group, reason = write_slot_rows_plan(cache, rows)
    if reason is None:
        count_kernel_path("slot_write", "pallas", "ok")
    else:
        count_kernel_path("slot_write", "xla", reason)
    return _write_slot_rows(cache, rows, at, group)


@functools.partial(jax.jit, static_argnums=3)
def _write_slot_rows(cache, rows, at, group):
    """Either path as the plan chose (``group`` 0: the update-slices).
    Jitted on its own so that a step which writes many arrays traces the
    writer once a shape, not once a layer and array: an engine traces its
    step twice at every start, the server's set-up."""
    rows = rows.astype(cache.dtype)
    if group:
        return _slot_write_pallas(cache, rows, at, group)
    return _slot_write_xla(cache, rows, at)


# ---------------------------------------------------------------------------
# registered ops
# ---------------------------------------------------------------------------

def _flash_attention_op(attrs, inputs, aux, is_train, rng):
    q, k, v = inputs
    return [flash_attention(q, k, v, causal=attrs["causal"],
                            softmax_scale=attrs["softmax_scale"] or None,
                            block_q=attrs["block_q"], block_k=attrs["block_k"])]


register("_contrib_FlashAttention", _flash_attention_op,
         arguments=("query", "key", "value"),
         params={"causal": (pbool, False),
                 "softmax_scale": (pfloat, 0.0),
                 "block_q": (pint, 256), "block_k": (pint, 512)},
         aliases=("FlashAttention",), hint="flashattention")


def _mha_op(attrs, inputs, aux, is_train, rng):
    """MultiHeadAttention: (B, L, E) inputs, fused qkv projection weights."""
    x_q, x_kv, w_qkv, w_out = inputs[:4]
    b_qkv = inputs[4] if len(inputs) > 4 else None
    b_out = inputs[5] if len(inputs) > 5 else None
    num_heads = attrs["num_heads"]
    e = x_q.shape[-1]
    hd = e // num_heads
    wq, wk, wv = jnp.split(w_qkv, 3, axis=0)  # each (E, E)
    q = jnp.einsum("ble,fe->blf", x_q, wq)
    kk = jnp.einsum("ble,fe->blf", x_kv, wk)
    vv = jnp.einsum("ble,fe->blf", x_kv, wv)
    if b_qkv is not None:
        bq, bk_, bv = jnp.split(b_qkv, 3)
        q, kk, vv = q + bq, kk + bk_, vv + bv

    def heads(t):
        return t.reshape(t.shape[0], t.shape[1], num_heads, hd).transpose(0, 2, 1, 3)

    o = flash_attention(heads(q), heads(kk), heads(vv), causal=attrs["causal"])
    o = o.transpose(0, 2, 1, 3).reshape(x_q.shape[0], x_q.shape[1], e)
    out = jnp.einsum("ble,fe->blf", o, w_out)
    if b_out is not None:
        out = out + b_out
    return [out]


register("_contrib_MultiHeadAttention", _mha_op,
         arguments=lambda a: (["query", "key_value", "qkv_weight", "out_weight"]
                              + ([] if a["no_bias"] else ["qkv_bias", "out_bias"])),
         params={"num_heads": (pint, REQUIRED), "causal": (pbool, False),
                 "no_bias": (pbool, False)},
         aliases=("MultiHeadAttention",), hint="multiheadattention")
