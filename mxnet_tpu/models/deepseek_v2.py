"""DeepSeek-V2-style decoder for the decode tier: latent (MLA) attention
whose cache holds one compressed row a token, and one group of experts
under a group-limited router.

Layer ``l`` over the stream ``x`` (pre-norm, ``RMS(x, g) = x g /
sqrt(mean(x^2) + eps)``, no biases):

* **queries**: ``h = RMS(x, g1)``; ``c_q = RMS(h W_DQ, g_q)`` (``q_rank``);
  ``q = c_q W_UQ``: ``heads`` of ``nope_dim + rope_dim``, the last
  ``rope_dim`` rotated;
* **what the cache holds**: ``[c_kv', k_r'] = h W_DKV`` (``kv_rank +
  rope_dim``); ``c_kv = RMS(c_kv', g_kv)``; ``k_rope = RoPE(k_r')``, one a
  token, read by every head;
* **expanded** (a prompt): head ``i`` has ``k_nope_i = c_kv W_UK_i``, ``v_i
  = c_kv W_UV_i``; ``score_i(t, s) = (q_nope_i(t) . k_nope_i(s) + q_rope_i(t)
  . k_rope(s)) scale``, causal softmax in float32, ``o_i = sum_s p v_i(s)``;
* **absorbed** (a step): ``q_lat_i = W_UK_i q_nope_i`` (``kv_rank``);
  ``score_i(s) = (q_lat_i . c_kv(s) + q_rope_i . k_rope(s)) scale``;
  ``o_lat_i = sum_s p c_kv(s)``; ``o_i = o_lat_i W_UV_i``: the same
  numbers, and neither a key nor a value of a head is built for a held row;
* ``x += concat_i(o_i) W_O``;
* ``scale = (nope_dim + rope_dim)^-0.5 m^2``, ``m = 0.1 mscale_all_dim
  ln(factor) + 1``; the rotation is over the pairs ``(x[2i], x[2i+1])`` by
  YaRN's frequencies (:func:`yarn_inv_freq`), its cosines and sines scaled
  by ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``;
  the rotated values come out half-split (all the pairs' first values, then
  their second), for queries and keys alike, which no score can tell;
* layers below ``first_dense``: ``x += SwiGLU_dense(RMS(x, g2))``; the
  others ``x += 16 sum_chosen s_e E_e(h) + SwiGLU_shared(h)`` with the
  router ``group_limited`` of :func:`~mxnet_tpu.models.exaone_moe.route`:
  softmax over all experts, the best ``topk_group`` of ``n_group`` groups
  by their best expert, the best ``top_k`` experts inside them, weights not
  renormed.  This chip adds the terms of the ``experts_held`` experts from
  ``first_expert`` and the shared term (:func:`~mxnet_tpu.models.exaone_moe
  .sparse_mlp`);
* head: ``RMS(x, gf) Wh``, untied, over the slice of the vocabulary held.

**The cache** (:meth:`DeepSeekV2.cache_spec`): a ``latent`` entry a layer
(:class:`~mxnet_tpu.models.transformer_lm.LatentLayer`), ``(slots, 1,
max_len, kv_rank)`` latent rows and ``(slots, 1, max_len, 128)`` rotated
keys: the ``rope_dim`` values in the first lanes and zeros after them, since
the chip moves whole 128-lane rows (``kv_rank + 128`` values a token lie in
memory where ``kv_rank + rope_dim`` are needed; the engine's
``serving.cache.bytes{kind="latent"}`` counts what lies there).  A step's
new row goes into each through :func:`ops.attention.write_slot_rows`, and
the step reads them through :func:`ops.attention.latent_attention`: on the
TPU only the rows a slot holds, one copy of a chunk for the scores and the
weighted sum.  The prompt's expanded heads go through
:func:`ops.attention.flash_attention`, which scores over ``nope_dim +
rope_dim`` and carries ``v_dim``.

**Precision** is :mod:`~mxnet_tpu.models.exaone_moe`'s: products of
operands in the weights' dtype accumulated in float32; stream, norms,
rotation, the router and the softmaxes in float32; ``c_kv`` and ``k_rope``
rounded to the cache's dtype before either path attends over them, so what
the cache holds is what the prompt attended over.

:func:`forward_logits` is the in-repo plain reference: float32, ``highest``
precision, the expanded form, no cache, one sequence, each expert in a
plain loop.  Prefill and decode step share :func:`_block`, which takes its
cache access as an argument.
"""

from __future__ import annotations

import math
from collections import namedtuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import (flash_attention, latent_attention,
                             write_slot_rows)
from .exaone_moe import (_mm, _rms, _swiglu, count_picks, route,
                         routing_gauges, sparse_mlp)
from .transformer_lm import LatentLayer

__all__ = ["DeepSeekV2Config", "DeepSeekV2", "init_params", "forward_logits",
           "yarn_inv_freq", "yarn_mscale", "softmax_scale"]

#: ``layers`` is how many are held, the first ``first_dense`` of them with
#: a dense MLP; ``shared_ffn`` the width of the shared experts taken
#: together; ``first_expert`` / ``experts_held`` this chip's share of
#: ``num_experts``; ``rope_*`` and ``mscale*`` YaRN's parameters; ``router``
#: / ``activation`` name the expert layer's choices
DeepSeekV2Config = namedtuple("DeepSeekV2Config", [
    "vocab", "embed", "heads", "q_rank", "kv_rank", "nope_dim", "rope_dim",
    "v_dim", "layers", "first_dense", "dense_ffn", "expert_ffn",
    "shared_ffn", "num_experts", "top_k", "n_group", "topk_group",
    "first_expert", "experts_held", "routed_scale", "rope_theta",
    "rope_factor", "rope_original", "beta_fast", "beta_slow", "mscale",
    "mscale_all_dim", "eps", "max_len", "eos_id", "router", "activation"],
    defaults=("group_limited", "silu"))

#: the prompt's attention: Q rows and K/V rows of a block
_BLOCK_Q, _BLOCK_K = 512, 512
#: lanes a cached row is a whole number of
_LANES = 128


# -- YaRN ----------------------------------------------------------------------
def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg):
    """``(rope_dim / 2,)`` float64: pair ``i`` turns by ``theta ** (-2i /
    rope_dim)`` where it makes more than ``beta_fast`` turns over the
    original positions, by that over ``factor`` where it makes fewer than
    ``beta_slow``, and by the linear blend between the two pairs (the
    correction dimensions, rounded outwards) where those happen."""
    d = cfg.rope_dim
    extra = cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def correction_dim(turns):
        return d * math.log(cfg.rope_original / (turns * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(correction_dim(cfg.beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / ((high if high != low else high + 0.001) - low), 0, 1)
    return extra / cfg.rope_factor * ramp + extra * (1 - ramp)


def softmax_scale(cfg):
    return (cfg.nope_dim + cfg.rope_dim) ** -0.5 \
        * yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim) ** 2


def _rope(cfg, x, pos):
    """Rotate ``x (T, n, rope_dim)`` at positions ``pos (T,)``: the pairs
    ``(x[2i], x[2i+1])``, and the result half-split."""
    inv = jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    m = yarn_mscale(cfg.rope_factor, cfg.mscale) \
        / yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim)
    cos, sin = jnp.cos(ang)[:, None] * m, jnp.sin(ang)[:, None] * m
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- parameters ----------------------------------------------------------------
def init_params(cfg, seed=0, dtype=jnp.bfloat16):
    """Seeded parameters (host arrays; the engine commits them to its
    device): normal(0, 0.02), the projections into the stream scaled by
    ``1/sqrt(2 layers)``, gains 1, the router's matrix float32.  The two
    halves of the published ``kv_b_proj`` are held apart and heads first,
    ``w_uk (heads, nope_dim, kv_rank)`` and ``w_uv (heads, kv_rank,
    v_dim)``: the absorbed step multiplies by each on its own."""
    rs = np.random.RandomState(seed)
    e, h = cfg.embed, cfg.heads
    resid = 0.02 / math.sqrt(2.0 * cfg.layers)

    def nrm(*shape, s=0.02, dt=dtype):
        return jnp.asarray(rs.normal(0, s, shape).astype(np.float32), dt)

    def swiglu(width, *lead):
        return {"gate": nrm(*lead, e, width), "up": nrm(*lead, e, width),
                "down": nrm(*lead, width, e, s=resid)}

    layers = []
    for l in range(cfg.layers):
        p = {"ln1": jnp.ones((e,), jnp.float32),
             "ln2": jnp.ones((e,), jnp.float32),
             "q_norm": jnp.ones((cfg.q_rank,), jnp.float32),
             "kv_norm": jnp.ones((cfg.kv_rank,), jnp.float32),
             "wq_a": nrm(e, cfg.q_rank),
             "wq_b": nrm(cfg.q_rank, h * (cfg.nope_dim + cfg.rope_dim)),
             "wkv_a": nrm(e, cfg.kv_rank + cfg.rope_dim),
             "w_uk": nrm(h, cfg.nope_dim, cfg.kv_rank),
             "w_uv": nrm(h, cfg.kv_rank, cfg.v_dim),
             "wo": nrm(h * cfg.v_dim, e, s=resid)}
        if l < cfg.first_dense:
            p["mlp"] = swiglu(cfg.dense_ffn)
        else:
            p["moe"] = dict(swiglu(cfg.expert_ffn, cfg.experts_held),
                            router=nrm(e, cfg.num_experts, dt=jnp.float32),
                            shared=swiglu(cfg.shared_ffn))
        layers.append(p)
    return {"embed": nrm(cfg.vocab, e), "head": nrm(e, cfg.vocab),
            "ln_f": jnp.ones((e,), jnp.float32), "layers": layers}


# -- the plain reference -------------------------------------------------------
def forward_logits(cfg, params, tokens, with_choices=False):
    """``tokens (T,) int32 -> (T, vocab)`` float32 logits of one sequence:
    the equations of the module docstring in float32 at ``highest``
    precision, the expanded form, no cache, each held expert in a plain
    loop.  ``with_choices`` also returns the router's choices, one ``(T,
    top_k)`` array an expert layer."""
    (t,) = tokens.shape
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]
    choices = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for p in params["layers"]:
            h = _rms(x, p["ln1"], cfg.eps)
            q = (_rms(h @ p["wq_a"], p["q_norm"], cfg.eps) @ p["wq_b"]) \
                .reshape(t, cfg.heads, cfg.nope_dim + cfg.rope_dim)
            q_nope, q_rope = q[..., :cfg.nope_dim], \
                _rope(cfg, q[..., cfg.nope_dim:], pos)
            kv = h @ p["wkv_a"]
            c_kv = _rms(kv[:, :cfg.kv_rank], p["kv_norm"], cfg.eps)
            k_rope = _rope(cfg, kv[:, None, cfg.kv_rank:], pos)[:, 0]
            k_nope = jnp.einsum("tc,hdc->thd", c_kv, p["w_uk"])
            v = jnp.einsum("tc,hcd->thd", c_kv, p["w_uv"])
            scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
                      + jnp.einsum("qhr,kr->hqk", q_rope, k_rope)) \
                * softmax_scale(cfg)
            att = jax.nn.softmax(jnp.where(causal[None], scores, -1e30), -1)
            ctx = jnp.einsum("hqk,khd->qhd", att, v)
            x = x + ctx.reshape(t, -1) @ p["wo"]
            h = _rms(x, p["ln2"], cfg.eps)
            if "mlp" in p:
                x = x + _swiglu(h, p["mlp"])
                continue
            moe = p["moe"]
            chosen, w = route(cfg, h, moe)
            choices.append(chosen)
            y = _swiglu(h, moe["shared"])
            for e in range(cfg.experts_held):
                mine = chosen == cfg.first_expert + e
                w_e = jnp.where(mine, w, 0.0).sum(-1, keepdims=True)
                y = y + w_e * _swiglu(
                    h, {n: moe[n][e] for n in ("gate", "up", "down")})
            x = x + y
        logits = _rms(x, params["ln_f"], cfg.eps) @ params["head"]
    return (logits, choices) if with_choices else logits


# -- the block, shared by prefill and decode step ------------------------------
def _block(cfg, l, p, x, pos, scope, attend, counts=None):
    """One layer over rows ``x (T, embed)`` float32 at absolute positions
    ``pos (T,)``.  ``attend(l, p, q_nope, q_rope, c_kv, k_rope)`` is the
    caller's cache access: it is handed ``q_nope (T, heads, nope_dim)`` and
    ``q_rope (T, heads, rope_dim)`` (rotated) in float32, ``c_kv (T,
    kv_rank)`` (normed) and ``k_rope (T, rope_dim)`` (rotated) in the
    weights' dtype, and returns the context ``(T, heads, v_dim)``.
    ``scope`` names the attention's span; ``counts(l, chosen)`` is told an
    expert layer's choices."""
    t = x.shape[0]
    dt = p["wq_a"].dtype
    with jax.named_scope(scope):
        h = _rms(x, p["ln1"], cfg.eps)
        q = _mm(_rms(_mm(h, p["wq_a"]), p["q_norm"], cfg.eps), p["wq_b"]) \
            .reshape(t, cfg.heads, cfg.nope_dim + cfg.rope_dim)
        kv = _mm(h, p["wkv_a"])
        c_kv = _rms(kv[:, :cfg.kv_rank], p["kv_norm"], cfg.eps)
        k_rope = _rope(cfg, kv[:, None, cfg.kv_rank:], pos)[:, 0]
        ctx = attend(l, p, q[..., :cfg.nope_dim],
                     _rope(cfg, q[..., cfg.nope_dim:], pos),
                     c_kv.astype(dt), k_rope.astype(dt))
        x = x + _mm(ctx.reshape(t, -1), p["wo"])
    h = _rms(x, p["ln2"], cfg.eps)
    if "mlp" in p:
        with jax.named_scope("mlp.dense"):
            return x + _swiglu(h, p["mlp"])
    y, chosen = sparse_mlp(cfg, h, p["moe"])
    if counts is not None:
        counts(l, chosen)
    return x + y


def _head(cfg, params, x):
    with jax.named_scope("head"):
        return _mm(_rms(x, params["ln_f"], cfg.eps), params["head"])


def _in_lanes(rows):
    """``rows (..., rope_dim)`` as whole 128-lane rows, zeros after."""
    pad = -rows.shape[-1] % _LANES
    return jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)])


class DeepSeekV2:
    """The model object the decode engine is given (its model protocol,
    :mod:`mxnet_tpu.serving.decode`): cache specification, prefill, decode
    step, and the routing and row counters as extra device state."""

    def __init__(self, cfg, cache_dtype=jnp.bfloat16):
        if cfg.num_experts % cfg.n_group:
            raise ValueError("%d experts in %d groups"
                             % (cfg.num_experts, cfg.n_group))
        if not 0 <= cfg.first_expert <= cfg.first_expert \
                + cfg.experts_held <= cfg.num_experts:
            raise ValueError("experts %d..%d are not within 0..%d"
                             % (cfg.first_expert, cfg.first_expert
                                + cfg.experts_held, cfg.num_experts))
        self.cfg = cfg
        #: what the cache holds its rows in (the tests' float32 runs pass
        #: float32; rows are rounded to it before they are attended)
        self.cache_dtype = cache_dtype
        self.sparse = max(0, cfg.layers - cfg.first_dense)

    # -- the protocol ------------------------------------------------------
    def cache_spec(self):
        cfg = self.cfg
        widths = (cfg.kv_rank, cfg.rope_dim + -cfg.rope_dim % _LANES)
        return tuple(LatentLayer("latent", cfg.max_len, widths,
                                 self.cache_dtype)
                     for _ in range(cfg.layers))

    def extra_state(self):
        """The device counters (uint32, wrapping), counted in decode steps
        over active rows: picks routed to each held expert of each expert
        layer, picks made in all, rows stepped, steps that stepped any,
        ``rows_latent`` the latent rows ONE layer reads for them
        (:meth:`counters` gives them over all layers; the fastest of the
        counters: 128 slots of 5,000 rows at 55 steps a second wrap it in
        two minutes, and a count is exact while reads are less than a wrap
        apart), and ``rows_reached`` the (row, expert layer) pairs with at
        least one pick here: what an exchange would send this chip."""
        zero = jnp.zeros((), jnp.uint32)
        return {"moe_picks": jnp.zeros((self.sparse, self.cfg.experts_held),
                                       jnp.uint32),
                "moe_picks_total": zero, "rows": zero, "steps": zero,
                "rows_latent": zero, "rows_reached": zero}

    def counters(self, extra):
        """The extra state read back (whole numbers), with the gauges the
        engine publishes under ``gauges``: picks a held expert sees a step,
        the busiest held expert's picks over the mean's, the held experts'
        share of all picks, and the share of (row, expert layer) pairs that
        reach this chip."""
        picks = np.asarray(extra["moe_picks"], np.int64)
        out = {name: int(extra[name]) for name in (
            "moe_picks_total", "rows", "steps", "rows_latent",
            "rows_reached")}
        out["rows_latent"] *= self.cfg.layers
        out["moe_picks"] = picks.tolist()
        gauges = routing_gauges(picks, out["steps"])
        if gauges:
            gauges["serving.moe.local_share"] = \
                float(picks.sum()) / out["moe_picks_total"]
            gauges["serving.moe.rows_reached_share"] = \
                out["rows_reached"] / (out["rows"] * self.sparse)
            out["gauges"] = gauges
        return out

    def prefill(self, params, tokens, length):
        """One bucket-padded prompt ``tokens (P,)`` of ``length`` real
        tokens -> ``(last_logits (vocab,), lats, ropes)``: of each layer
        the latent rows ``(1, P, kv_rank)`` and the rotated keys ``(1, P,
        128)`` of positions ``0..P-1``, to write into a slot from row 0.
        The heads are expanded from the rounded latent rows and attended
        in blocks."""
        cfg = self.cfg
        (p_len,) = tokens.shape
        pos = jnp.arange(p_len)
        scale = softmax_scale(cfg)
        lats, ropes = [], []

        def attend(l, p, q_nope, q_rope, c_kv, k_rope):
            # what the cache will hold is what the prompt attends over
            dt = c_kv.dtype
            held_lat = c_kv.astype(self.cache_dtype)
            held_rope = k_rope.astype(self.cache_dtype)
            lats.append(held_lat[None])
            ropes.append(_in_lanes(held_rope)[None])
            c_kv, k_rope = held_lat.astype(dt), held_rope.astype(dt)
            q = jnp.concatenate([q_nope, q_rope], -1).astype(dt)
            k = jnp.concatenate([
                jnp.einsum("tc,hdc->htd", c_kv, p["w_uk"],
                           preferred_element_type=jnp.float32).astype(dt),
                jnp.broadcast_to(k_rope[None], (cfg.heads,) + k_rope.shape)],
                -1)
            v = jnp.einsum("tc,hcd->htd", c_kv, p["w_uv"],
                           preferred_element_type=jnp.float32).astype(dt)
            ctx = flash_attention(
                jnp.swapaxes(q, 0, 1)[None], k[None], v[None], causal=True,
                softmax_scale=scale, block_q=_BLOCK_Q, block_k=_BLOCK_K)[0]
            return jnp.swapaxes(ctx, 0, 1)

        x = params["embed"][tokens].astype(jnp.float32)
        for l, p in enumerate(params["layers"]):
            x = _block(cfg, l, p, x, pos, "attn.latent.expand", attend)
        last = jnp.take(x, jnp.clip(length - 1, 0, p_len - 1), axis=0)
        return _head(cfg, params, last), tuple(lats), tuple(ropes)

    def decode_step(self, params, cache_lat, cache_rope, last_tok, lengths,
                    active, extra):
        """One token for all ``S`` slots: the incoming token's latent row
        and rotated key go to position ``lengths`` of each slot, and the
        absorbed attention reads everything the slot holds.  Returns
        ``(logits (S, vocab), cache_lat, cache_rope, extra)``."""
        cfg = self.cfg
        pos = jnp.clip(lengths, 0, cfg.max_len - 1)
        scale = softmax_scale(cfg)
        new_lat, new_rope = list(cache_lat), list(cache_rope)
        live = active.astype(jnp.uint32)
        picks, reached = [], []

        def attend(l, p, q_nope, q_rope, c_kv, k_rope):
            dt = c_kv.dtype
            new_lat[l] = write_slot_rows(cache_lat[l], c_kv[:, None], pos)
            new_rope[l] = write_slot_rows(
                cache_rope[l], _in_lanes(k_rope)[:, None], pos)
            # the scale rides on the queries: it costs the kernel nothing
            q_lat = jnp.einsum("shd,hdc->shc", q_nope.astype(dt), p["w_uk"],
                               preferred_element_type=jnp.float32) * scale
            o_lat = latent_attention(
                q_lat.astype(self.cache_dtype),
                _in_lanes(q_rope * scale).astype(self.cache_dtype),
                new_lat[l], new_rope[l], pos)
            return jnp.einsum("shc,hcd->shd", o_lat.astype(dt), p["w_uv"],
                              preferred_element_type=jnp.float32)

        def counts(l, chosen):
            mine = count_picks(cfg, chosen, live)
            picks.append(mine)
            local = chosen - cfg.first_expert
            hit = ((local >= 0) & (local < cfg.experts_held)).any(-1)
            reached.append((live * hit.astype(jnp.uint32)).sum())

        x = params["embed"][last_tok].astype(jnp.float32)
        for l, p in enumerate(params["layers"]):
            x = _block(cfg, l, p, x, pos, "attn.latent", attend, counts)
        logits = _head(cfg, params, x)
        rows = live.sum()
        extra = {
            "moe_picks": extra["moe_picks"] + (
                jnp.stack(picks) if picks else 0),
            "moe_picks_total": extra["moe_picks_total"]
            + rows * np.uint32(cfg.top_k * self.sparse),
            "rows": extra["rows"] + rows,
            "steps": extra["steps"] + (rows > 0).astype(jnp.uint32),
            "rows_latent": extra["rows_latent"]
            + (live * (pos + 1).astype(jnp.uint32)).sum(),
            "rows_reached": extra["rows_reached"] + sum(reached)}
        return logits, tuple(new_lat), tuple(new_rope), extra
