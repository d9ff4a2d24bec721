"""SmallThinker-style decoder for the decode tier: a router that reads the
layer's input before attention, softmax-routed ReGLU experts that are all
held here, and rings of thousands of rows beside full layers.

Layer ``l`` over its input ``x_in`` (``RMS(x, g) = x g / sqrt(mean(x^2) +
eps)``):

* **router, before attention**: ``r = x_in Wr`` (of ``x_in`` itself, not
  of its norm; float32 at ``highest``), ``chosen = top_k(r)``, ``w =
  softmax(r[chosen])`` over the chosen;
* **attention**: ``h = RMS(x_in, g1)``; ``q = h Wq`` (``heads`` of
  ``head_dim``), ``k = h Wk``, ``v = h Wv`` (``kv_heads``), no biases, no
  QK-norm; where ``rope_layout[l]`` is 1, ``q`` and ``k`` are rotated (RoPE
  over the whole head, half-split pairs); where ``window_layout[l]`` is 1
  a query at ``p`` reads ``p-window+1..p``, else ``0..p``; query head ``i``
  reads K/V head ``i // (heads // kv_heads)``; ``x = x_in + ctx Wo``.  The
  two layouts are two published lists and each is read for what it says;
* **experts**: ``h2 = RMS(x, g2)``; ``E_e(h) = (relu(h Wg_e) * (h Wu_e))
  Wd_e``; ``x = x + sum over the chosen e of w_e E_e(h2)``.  No shared
  expert, no dense layer;
* **head**: ``logits = RMS(x, gf) Wh``, untied.

The expert layer is :mod:`~mxnet_tpu.models.exaone_moe`'s (``route``,
``routed_experts``: told ``(first_expert, experts_held, num_experts)``, the
router ``softmax`` and the activation ``relu`` by name), and so are the
rotation, the norm, the products' precision (that module's docstring has
the list; ``eps`` is this configuration's) and the ring's arithmetic.

**The prompt's attention goes through blocks**
(:func:`ops.attention.flash_attention` with ``window`` and grouped K/V
heads): no ``(heads, P, P)`` array exists at any bucket.  **The experts of
a prompt's thousands of rows** take the grouped product, a step's few rows
the every-expert one (:func:`~mxnet_tpu.models.exaone_moe.expert_product`).

**The cache** (:meth:`SmallThinker.cache_spec`): a full layer ``(slots,
kv_heads, max_len, head_dim)``, a window layer a ring ``(slots, kv_heads,
window, head_dim)`` written at ``pos % window``, K stored already rotated.
Ring row ``j`` holds position ``j`` until the ring wraps and every row is
live after, so the rows a step reads are ``0..min(pos, window-1)``: the
decode step reads a ring through :func:`ops.attention.decode_attention` as
it reads a full layer, only the blocks of rows a slot holds, and no
``(slots, kv_heads, group, window)`` scores touch HBM.

:func:`forward_logits` is the in-repo plain reference: float32, ``highest``
precision, no cache, one sequence, each expert in a plain loop, written out
on its own.  Prefill and decode step share :func:`_block`, which takes its
cache access as an argument.
"""

from __future__ import annotations

import math
from collections import namedtuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import (decode_attention, flash_attention,
                             write_slot_rows)
from .exaone_moe import (_grouped, _mm, _rms, _rope, count_picks, ring_src,
                         route, routed_experts, routing_gauges)
from .transformer_lm import CacheLayer

__all__ = ["SmallThinkerConfig", "SmallThinker", "init_params",
           "forward_logits"]

#: ``layers`` is how many are held; ``rope_layout`` / ``window_layout`` say
#: of each whether it rotates and whether it reads a window (1) or
#: everything (0).  ``first_expert`` / ``experts_held`` are this chip's
#: share of ``num_experts``; ``router`` / ``activation`` name the expert
#: layer's choices.
SmallThinkerConfig = namedtuple("SmallThinkerConfig", [
    "vocab", "embed", "heads", "kv_heads", "head_dim", "layers",
    "rope_layout", "window_layout", "expert_ffn", "num_experts", "top_k",
    "first_expert", "experts_held", "window", "rope_theta", "eps",
    "max_len", "eos_id", "router", "activation"],
    defaults=("softmax", "relu"))

#: the prompt's attention: Q rows and K/V rows of a block
_BLOCK_Q, _BLOCK_K = 512, 512


def init_params(cfg, seed=0, dtype=jnp.bfloat16):
    """Seeded parameters (host arrays; the engine commits them to its
    device): normal(0, 0.02), the projections into the stream scaled by
    ``1/sqrt(2 layers)``, gains 1, the router's matrix float32."""
    rs = np.random.RandomState(seed)
    e, hd, f = cfg.embed, cfg.head_dim, cfg.expert_ffn
    resid = 0.02 / math.sqrt(2.0 * cfg.layers)

    def nrm(*shape, s=0.02, dt=dtype):
        return jnp.asarray(rs.normal(0, s, shape).astype(np.float32), dt)

    layers = [{
        "ln1": jnp.ones((e,), jnp.float32),
        "ln2": jnp.ones((e,), jnp.float32),
        "wq": nrm(e, cfg.heads * hd), "wk": nrm(e, cfg.kv_heads * hd),
        "wv": nrm(e, cfg.kv_heads * hd),
        "wo": nrm(cfg.heads * hd, e, s=resid),
        "moe": {"router": nrm(e, cfg.num_experts, dt=jnp.float32),
                "gate": nrm(cfg.experts_held, e, f),
                "up": nrm(cfg.experts_held, e, f),
                "down": nrm(cfg.experts_held, f, e, s=resid)}}
        for _ in range(cfg.layers)]
    return {"embed": nrm(cfg.vocab, e), "head": nrm(e, cfg.vocab),
            "ln_f": jnp.ones((e,), jnp.float32), "layers": layers}


# -- the plain reference -------------------------------------------------------
def forward_logits(cfg, params, tokens, with_choices=False):
    """``tokens (T,) int32 -> (T, vocab)`` float32 logits of one sequence:
    the equations of the module docstring in float32 at ``highest``
    precision, no cache, each held expert in a plain loop.
    ``with_choices`` also returns the router's choices, one ``(T, top_k)``
    array a layer."""
    (t,) = tokens.shape
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    group = cfg.heads // cfg.kv_heads
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]
    choices = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for l, p in enumerate(params["layers"]):
            moe = p["moe"]
            picked, chosen = jax.lax.top_k(x @ moe["router"], cfg.top_k)
            w = jax.nn.softmax(picked, axis=-1)
            choices.append(chosen)
            h = _rms(x, p["ln1"], cfg.eps)
            q = (h @ p["wq"]).reshape(t, cfg.heads, cfg.head_dim)
            k = (h @ p["wk"]).reshape(t, cfg.kv_heads, cfg.head_dim)
            v = (h @ p["wv"]).reshape(t, cfg.kv_heads, cfg.head_dim)
            if cfg.rope_layout[l]:
                q, k = _rope(cfg, q, pos), _rope(cfg, k, pos)
            mask = causal
            if cfg.window_layout[l]:
                mask = mask & (pos[None, :] > pos[:, None] - cfg.window)
            k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
            scores = jnp.einsum("qhd,khd->hqk", q, k) \
                / math.sqrt(cfg.head_dim)
            att = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), -1)
            ctx = jnp.einsum("hqk,khd->qhd", att, v)
            x = x + ctx.reshape(t, -1) @ p["wo"]
            h = _rms(x, p["ln2"], cfg.eps)
            y = jnp.zeros_like(x)
            for e in range(cfg.experts_held):
                mine = chosen == cfg.first_expert + e
                w_e = jnp.where(mine, w, 0.0).sum(-1, keepdims=True)
                inner = jax.nn.relu(h @ moe["gate"][e]) * (h @ moe["up"][e])
                y = y + w_e * (inner @ moe["down"][e])
            x = x + y
        logits = _rms(x, params["ln_f"], cfg.eps) @ params["head"]
    return (logits, choices) if with_choices else logits


# -- the block, shared by prefill and decode step ------------------------------
def _block(cfg, l, p, x, pos, attend, counts=None):
    """One layer over rows ``x (T, embed)`` float32 at absolute positions
    ``pos (T,)``.  ``attend(l, q, k, v)`` is the caller's cache access: it
    is handed ``q (T, heads, d)``, ``k``/``v (T, kv_heads, d)`` (rotated
    where the layer rotates, in the weights' dtype) and returns the context
    ``(T, heads, d)``.  ``counts(l, chosen)`` is told the layer's choices."""
    t = x.shape[0]
    dt = p["wq"].dtype
    with jax.named_scope("moe.route"):
        chosen, w = route(cfg, x, p["moe"])
    with jax.named_scope("attn.window" if cfg.window_layout[l]
                         else "attn.full"):
        h = _rms(x, p["ln1"], cfg.eps)
        q = _mm(h, p["wq"]).reshape(t, cfg.heads, cfg.head_dim)
        k = _mm(h, p["wk"]).reshape(t, cfg.kv_heads, cfg.head_dim)
        v = _mm(h, p["wv"]).reshape(t, cfg.kv_heads, cfg.head_dim)
        if cfg.rope_layout[l]:
            q, k = _rope(cfg, q, pos), _rope(cfg, k, pos)
        ctx = attend(l, q.astype(dt), k.astype(dt), v.astype(dt))
        x = x + _mm(ctx.reshape(t, -1), p["wo"])
    with jax.named_scope("moe.experts"):
        y = routed_experts(cfg, _rms(x, p["ln2"], cfg.eps), chosen, w,
                           p["moe"])
    if counts is not None:
        counts(l, chosen)
    return x + y


def _head(cfg, params, x):
    with jax.named_scope("head"):
        return _mm(_rms(x, params["ln_f"], cfg.eps), params["head"])


class SmallThinker:
    """The model object the decode engine is given (its model protocol,
    :mod:`mxnet_tpu.serving.decode`): cache specification, prefill, decode
    step, and the routing and row counters as extra device state."""

    def __init__(self, cfg, cache_dtype=jnp.bfloat16):
        if cfg.heads % cfg.kv_heads:
            raise ValueError("heads=%d not a multiple of kv_heads=%d"
                             % (cfg.heads, cfg.kv_heads))
        if min(len(cfg.rope_layout), len(cfg.window_layout)) < cfg.layers:
            raise ValueError("rope_layout/window_layout name fewer than "
                             "layers=%d layers" % cfg.layers)
        if not 0 <= cfg.first_expert <= cfg.first_expert \
                + cfg.experts_held <= cfg.num_experts:
            raise ValueError("experts %d..%d are not within 0..%d"
                             % (cfg.first_expert, cfg.first_expert
                                + cfg.experts_held, cfg.num_experts))
        self.cfg = cfg
        #: what the cache holds K and V in (the tests' float32 runs pass
        #: float32; K and V are rounded to it before they are attended)
        self.cache_dtype = cache_dtype
        self.rings = sum(1 for l in range(cfg.layers)
                         if cfg.window_layout[l])

    # -- the protocol ------------------------------------------------------
    def cache_spec(self):
        cfg, dtype = self.cfg, self.cache_dtype
        return tuple(
            CacheLayer("ring", cfg.window, cfg.kv_heads, cfg.head_dim,
                       dtype, True)
            if cfg.window_layout[l] else
            CacheLayer("full", cfg.max_len, cfg.kv_heads, cfg.head_dim,
                       dtype, True) for l in range(cfg.layers))

    def extra_state(self):
        """The device counters (uint32, wrapping), counted in decode
        steps over active rows: picks routed to each held expert of each
        layer, picks made in all, rows stepped, steps that stepped any;
        ``rows_full`` the rows a full layer holds for them and
        ``rows_ring`` the rows a ring holds (at most the window each)."""
        zero = jnp.zeros((), jnp.uint32)
        return {"moe_picks": jnp.zeros((self.cfg.layers,
                                        self.cfg.experts_held), jnp.uint32),
                "moe_picks_total": zero, "rows": zero, "steps": zero,
                "rows_full": zero, "rows_ring": zero}

    def counters(self, extra):
        """The extra state read back (whole numbers), with the gauges the
        engine publishes under ``gauges``: picks a held expert sees a step,
        the busiest held expert's picks over the mean's, and the share of
        the cache rows the slots' layers hold (``max_len`` a full layer,
        ``window`` a ring, over active slots) that the attention read."""
        cfg = self.cfg
        picks = np.asarray(extra["moe_picks"], np.int64)
        out = {name: int(extra[name]) for name in (
            "moe_picks_total", "rows", "steps", "rows_full", "rows_ring")}
        out["moe_picks"] = picks.tolist()
        gauges = routing_gauges(picks, out["steps"])
        if out["rows"]:
            full = cfg.layers - self.rings
            gauges["serving.attn.rows_read_share"] = (
                full * out["rows_full"] + self.rings * out["rows_ring"]) \
                / (out["rows"] * (full * cfg.max_len
                                  + self.rings * cfg.window))
        if gauges:
            out["gauges"] = gauges
        return out

    def prefill(self, params, tokens, length):
        """One bucket-padded prompt ``tokens (P,)`` of ``length`` real
        tokens -> ``(last_logits (vocab,), ks, vs)``: what to write into a
        slot of each layer's cache from row 0, K/V heads first (a full
        layer's positions ``0..P-1``; a ring whole, holding the last
        ``window`` positions below ``length`` where they belong)."""
        cfg = self.cfg
        (p_len,) = tokens.shape
        pos = jnp.arange(p_len)
        src = ring_src(length, cfg.window, p_len)
        scale = 1.0 / math.sqrt(cfg.head_dim)
        ks, vs = [], []

        def attend(l, q, k, v):
            window = cfg.window_layout[l]
            q, k, v = (jnp.swapaxes(a, 0, 1) for a in (q, k, v))
            ctx = flash_attention(
                q[None], k[None], v[None], causal=True, softmax_scale=scale,
                block_q=_BLOCK_Q, block_k=_BLOCK_K,
                window=cfg.window if window else None)[0]
            for rows, held in ((k, ks), (v, vs)):
                held.append((rows[:, src] if window else rows).astype(
                    self.cache_dtype))
            return jnp.swapaxes(ctx, 0, 1)

        x = params["embed"][tokens].astype(jnp.float32)
        for l, p in enumerate(params["layers"]):
            x = _block(cfg, l, p, x, pos, attend)
        last = jnp.take(x, jnp.clip(length - 1, 0, p_len - 1), axis=0)
        return _head(cfg, params, last), tuple(ks), tuple(vs)

    def decode_step(self, params, cache_k, cache_v, last_tok, lengths,
                    active, extra):
        """One token for all ``S`` slots: the incoming token's K/V goes to
        position ``lengths`` of each slot's cache (row ``lengths % window``
        of a ring) and is attended over with everything the slot holds.
        Returns ``(logits (S, vocab), cache_k, cache_v, extra)``."""
        cfg = self.cfg
        pos = jnp.clip(lengths, 0, cfg.max_len - 1)
        scale = 1.0 / math.sqrt(cfg.head_dim)
        new_k, new_v = list(cache_k), list(cache_v)
        live = active.astype(jnp.uint32)
        picks = []

        def attend(l, q, k, v):
            # either kind is written at ``at`` and read up to the horizon:
            # a ring row holds its own position until the ring wraps, and
            # every row of it is live after
            ring = cfg.window_layout[l]
            at = pos % cfg.window if ring else pos
            horizon = jnp.minimum(pos, cfg.window - 1) if ring else pos
            new_k[l] = write_slot_rows(cache_k[l], k, at)
            new_v[l] = write_slot_rows(cache_v[l], v, at)
            return decode_attention(_grouped(cfg, q), new_k[l], new_v[l],
                                    horizon, scale)

        def counts(l, chosen):
            picks.append(count_picks(cfg, chosen, live))

        x = params["embed"][last_tok].astype(jnp.float32)
        for l, p in enumerate(params["layers"]):
            x = _block(cfg, l, p, x, pos, attend, counts)
        logits = _head(cfg, params, x)
        rows = live.sum()
        held = (pos + 1).astype(jnp.uint32)
        extra = {
            "moe_picks": extra["moe_picks"] + jnp.stack(picks),
            "moe_picks_total": extra["moe_picks_total"]
            + rows * np.uint32(cfg.top_k * cfg.layers),
            "rows": extra["rows"] + rows,
            "steps": extra["steps"] + (rows > 0).astype(jnp.uint32),
            "rows_full": extra["rows_full"] + (live * held).sum(),
            "rows_ring": extra["rows_ring"] + (live * jnp.minimum(
                held, np.uint32(cfg.window))).sum()}
        return logits, tuple(new_k), tuple(new_v), extra
