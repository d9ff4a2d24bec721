"""K-EXAONE-style decoder for the decode tier: window and full attention
in one cache, and one chip's share of sigmoid-routed experts.

The block is pre-norm: ``x += Attn(RMS(x))``, ``x += MLP(RMS(x))``, eps
1e-5.  Attention has ``heads`` query heads over ``kv_heads`` K/V heads of
``head_dim`` (query head ``i`` reads K/V head ``i // (heads // kv_heads)``),
no biases, RMS-normed ``q`` and ``k`` per head.  A *window* layer
(``layer_types[l] == "sliding_attention"``) rotates ``q`` and ``k`` (RoPE,
half-split pairs) and a query at position ``p`` reads ``p-window+1..p``; a
*full* layer has no positions at all and reads everything before it.  The
MLP of a *dense* layer is one SwiGLU; a *sparse* layer routes:
``s = sigmoid(h Wr)``, ``chosen = top_k(s + bias)``, ``w = s / (sum over
chosen of s + 1e-20) * routed_scale``, ``y = sum over chosen of w_e E_e(h)
+ E_shared(h)``.

**This chip's share.**  The layer is told ``(first_expert, experts_held,
num_experts)``.  The router, the choice and the weights are over all
``num_experts``; the sum runs over the chosen experts that are held here.
What the absent experts would add is left out (they live on other chips,
whose exchange is not this module's), the shared expert is computed whole.
Nothing is dropped at any imbalance: a row may pick ``top_k`` held experts
and gets every one of them.  :func:`sparse_mlp` over each share, the shared
expert counted once, adds up to the uncut layer
(``tests/test_exaone_moe.py``).

**The routed product** is :func:`routed_experts`, which chooses one of two
from the shapes it is given (:func:`expert_product`; no option).  *Every*:
every held expert's gated unit over all rows, combined with weights that
are zero where a row did not choose the expert; fixed shapes, no sort.  At
a decode step of few rows an expert the held experts' weights are what the
product reads and their bytes bind it; PERF.md section 6 (PR 27) has what
was measured against it.  *Grouped*, where every expert over every row
would be bound by operations the routing never asked for (a pass of 384
rows over 128 held experts of which a row picks 8; a prompt's hundreds
and thousands of rows): the picks laid out by expert, each expert's rows
multiplied through its own weights alone, and summed back a row
(:mod:`~mxnet_tpu.ops.grouped_product`: on the TPU one Pallas kernel a
layer that streams each held expert's weights once and multiplies its
rows in tiles, gate, up and down in one visit; elsewhere, and at shapes
the kernel's plan refuses, the picks sorted by expert and one
``lax.ragged_dot`` a projection), which does the operations the
routing asks of this share and not ``num_experts / top_k`` times as many.
Neither has a capacity and neither drops a pick at any imbalance.

**Three routers, two gates** (``cfg.router``, ``cfg.activation``):
``sigmoid`` with selection bias and scale as above; ``softmax`` over the
chosen (``chosen = top_k(h Wr)``, ``w = softmax`` of the chosen scores); or
``group_limited`` (``s = softmax(h Wr)`` over all; of the ``cfg.n_group``
equal groups the best ``cfg.topk_group`` by their best expert stay; the
best ``top_k`` experts among theirs; ``w = s routed_scale``, not renormed:
:mod:`~mxnet_tpu.models.deepseek_v2`); the gated unit's activation is
``silu`` or ``relu``.  :func:`route` takes the rows
the router reads, which need not be the rows the experts are fed
(:mod:`~mxnet_tpu.models.smallthinker` routes a layer's input before its
attention).

**Precision.**  Weights and cache bfloat16 (whatever dtype ``params`` come
in is used as it is: the tests run float32).  Every matrix product takes
operands in the weights' dtype and accumulates in float32.  Kept in
float32: the residual stream and its additions, the norms, the rotation,
the router's product (``highest`` precision: its 128 scores decide a
discrete choice), sigmoid, top-k and weights, the scores' softmax, the
logits.  Rounded to the weights' dtype: the normed activations entering a
product, ``q``/``k``/``v`` (so what the cache holds is what the prefill
attended over), the attention weights entering the weighted sum, and the
SwiGLU's inner activations.

**The cache** (:meth:`ExaoneMoE.cache_spec`): a full layer holds
``(slots, kv_heads, max_len, head_dim)``; a window layer a ring
``(slots, kv_heads, window, head_dim)`` written at ``pos % window``, K
stored already rotated, masked by the absolute position each ring row
holds.  A prompt longer than the window leaves its last ``window``
positions in the ring.  K/V heads come before positions because that is
the order both attention products read: compiled for the chip with
positions first, every step copied every cache array into this order and
back (PERF.md section 6, PR 27).  The decode step reads a full layer
through :func:`ops.attention.decode_attention`: on the TPU only the rows at
or below each slot's length, to 128 (PERF.md section 6, PR 28, 35); this
model's ring of 128 rows is read whole, every row of it live once a session
is past the window (a ring of thousands of rows goes through the same
kernel as a full layer: :mod:`~mxnet_tpu.models.smallthinker`, and PERF.md
section 6, PR 33, has what the chip read for rings of 128 and 512 rows).
A step's new row goes into either kind through
:func:`ops.attention.write_slot_rows` (PERF.md section 6, PR 32).

:func:`forward_logits` is the in-repo plain reference: float32, ``highest``
precision, no cache, one sequence, written out on its own.  Prefill and
decode step share :func:`_block`, which takes its cache access as an
argument.
"""

from __future__ import annotations

import math
from collections import namedtuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import (decode_attention, decode_attention_plan,
                             write_slot_rows)
from ..ops.grouped_product import (grouped_product, grouped_product_plan,
                                   product_seconds, rows_into_groups,
                                   sum_out_of_groups)
from .transformer_lm import CacheLayer

__all__ = ["ExaoneConfig", "ExaoneMoE", "init_params",
           "forward_logits", "sparse_mlp", "route", "routed_experts",
           "expert_product"]

#: ``layers`` is how many are held; ``layer_types`` / ``mlp_types`` name
#: each ("sliding_attention" | "full_attention", "dense" | "sparse").
#: ``first_expert`` / ``experts_held`` are this chip's share of
#: ``num_experts``; ``vocab`` is the slice of the vocabulary held here;
#: ``router`` ("sigmoid" | "softmax") and ``activation`` ("silu" | "relu")
#: name the expert layer's two choices.
ExaoneConfig = namedtuple("ExaoneConfig", [
    "vocab", "embed", "heads", "kv_heads", "head_dim", "layers",
    "layer_types", "mlp_types", "dense_ffn", "expert_ffn", "num_experts",
    "top_k", "first_expert", "experts_held", "window", "rope_theta",
    "routed_scale", "max_len", "eos_id", "router", "activation"],
    defaults=("sigmoid", "silu"))

EPS = 1e-5
_NEG = jnp.float32(-1e30)


def init_params(cfg, seed=0, dtype=jnp.bfloat16):
    """Seeded parameters (host arrays; the engine commits them to its
    device).  ``layers`` is a list, since dense and sparse layers differ;
    the router's matrix and selection bias stay float32."""
    rs = np.random.RandomState(seed)
    e, hd = cfg.embed, cfg.head_dim
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
             "q_norm": jnp.ones((hd,), jnp.float32),
             "k_norm": jnp.ones((hd,), jnp.float32),
             "wq": nrm(e, cfg.heads * hd), "wk": nrm(e, cfg.kv_heads * hd),
             "wv": nrm(e, cfg.kv_heads * hd),
             "wo": nrm(cfg.heads * hd, e, s=resid)}
        if cfg.mlp_types[l] == "sparse":
            p["moe"] = dict(
                swiglu(cfg.expert_ffn, cfg.experts_held),
                router=nrm(e, cfg.num_experts, dt=jnp.float32),
                bias=nrm(cfg.num_experts, s=0.01, dt=jnp.float32),
                shared=swiglu(cfg.expert_ffn))
        else:
            p["mlp"] = swiglu(cfg.dense_ffn)
        layers.append(p)
    return {"embed": nrm(cfg.vocab, e), "head": nrm(e, cfg.vocab),
            "ln_f": jnp.ones((e,), jnp.float32), "layers": layers}


# -- pieces both the program and the reference are written from ----------------
def _rms(x, g, eps=EPS):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _mm(a, w):
    """``a @ w``: operands in the weights' dtype, float32 accumulation."""
    return jnp.dot(a.astype(w.dtype), w,
                   preferred_element_type=jnp.float32)


_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _swiglu(h, w, act=jax.nn.silu):
    a = act(_mm(h, w["gate"])) * _mm(h, w["up"])
    return _mm(a, w["down"])


def _rope(cfg, x, pos):
    """Rotate ``x (T, n, head_dim)`` at positions ``pos (T,)``: pairs are
    ``(x[i], x[i + head_dim/2])``, frequency ``theta ** (-2i/head_dim)``."""
    half = cfg.head_dim // 2
    inv = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def route(cfg, h, moe):
    """``h (T, embed)`` float32, the rows the router reads -> ``(chosen (T,
    top_k) int32 over all num_experts, weights (T, top_k) float32)``, by
    the router ``cfg.router`` names."""
    with jax.default_matmul_precision("highest"):
        s = jnp.dot(h.astype(jnp.float32), moe["router"])
    if cfg.router == "softmax":
        # a softmax over all the scores, renormed over the chosen, is the
        # softmax over the chosen
        picked, chosen = jax.lax.top_k(s, cfg.top_k)
        return chosen.astype(jnp.int32), jax.nn.softmax(picked, axis=-1)
    if cfg.router == "group_limited":
        # softmax over all; a group's score is its best expert's; the best
        # ``topk_group`` groups stay and the rest score 0; the best
        # ``top_k`` of what stays, weights not renormed
        s = jax.nn.softmax(s, axis=-1)
        groups = s.reshape(s.shape[0], cfg.n_group, -1)
        _, best = jax.lax.top_k(groups.max(-1), cfg.topk_group)
        stays = jax.nn.one_hot(best, cfg.n_group, dtype=bool).any(1)
        picked, chosen = jax.lax.top_k(
            jnp.where(stays[:, :, None], groups, 0.0).reshape(s.shape),
            cfg.top_k)
        return chosen.astype(jnp.int32), picked * cfg.routed_scale
    if cfg.router != "sigmoid":
        raise ValueError("no router %r (sigmoid | softmax | group_limited)"
                         % (cfg.router,))
    s = jax.nn.sigmoid(s)
    _, chosen = jax.lax.top_k(s + moe["bias"], cfg.top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scale
    return chosen.astype(jnp.int32), w


def _combine(cfg, chosen, w):
    """``(T, experts_held)`` float32: each row's weight for each held
    expert, 0 where the row did not choose it."""
    local = chosen - cfg.first_expert
    hit = jax.nn.one_hot(local, cfg.experts_held, dtype=jnp.float32)
    return (hit * w[..., None]).sum(1)


def expert_product(cfg, rows, live=None):
    """"every" or "grouped": which product :func:`routed_experts` runs over
    ``rows`` rows, of which ``live`` are expected to take part (all of
    them where none is given: every expert multiplies every row it is
    handed, the grouped product's tiles the picks of the live ones, and its
    layout has a place for every pick handed), from the shapes alone
    (``rows``, ``experts_held``,
    ``num_experts``, ``top_k``, ``embed``, ``expert_ffn``): whichever
    :func:`~mxnet_tpu.ops.grouped_product.product_seconds` reckons cheaper
    on the chip (the readings it was fitted to, one layer alone at the four
    routing cells' shapes, stand above it), a tie to the product that sorts
    nothing.  The every-expert product does ``experts_held`` experts a row
    where the routing asks this share for ``top_k x experts_held /
    num_experts``, and reads every held weight once; that is free while the
    weights' bytes bind (a step's few rows an expert: the layout's cost
    keeps it on every) and is what a pass of hundreds of rows, or a
    prompt's thousands, pays for: grouped from some 290 rows at SDAR's
    shape, 340 at K-EXAONE's."""
    every, grouped = product_seconds(
        cfg.embed, cfg.expert_ffn, cfg.experts_held, rows,
        rows * cfg.top_k,
        (rows if live is None else live) * cfg.top_k / cfg.num_experts)
    return "grouped" if grouped < every else "every"


def _every_expert(act, h, comb, moe):
    """``sum_x comb[t, x] * E_x(h[t])``: every held expert over every row,
    the combine weight applied before the down projection so that experts
    and inner width contract in one product."""
    dt = moe["gate"].dtype
    hb = h.astype(dt)
    g = jnp.einsum("te,xef->txf", hb, moe["gate"],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("te,xef->txf", hb, moe["up"],
                   preferred_element_type=jnp.float32)
    a = (act(g) * u * comb[:, :, None]).astype(dt)
    return jnp.einsum("txf,xfe->te", a, moe["down"],
                      preferred_element_type=jnp.float32)


def _grouped_experts(cfg, act, h, chosen, w, moe, live=None):
    """The same sum over the picks grouped by expert: a pick of an expert
    held elsewhere adds exactly nothing; no group has a capacity.  By the
    kernel of :mod:`~mxnet_tpu.ops.grouped_product` where its plan takes
    the shapes (counted under ``ops.kernel_path``; its row tile follows the
    picks of the ``live`` rows expected to take part, all of them where
    none is given), else
    :func:`_sorted_experts`.  The layout, the gather into it and the sum
    out of it are this product's and are timed with it."""
    from ..ops.registry import count_kernel_path

    held = cfg.experts_held
    tiles, reason = grouped_product_plan(
        moe["gate"], (chosen.size if live is None else live * cfg.top_k)
        / cfg.num_experts)
    if reason is not None:
        count_kernel_path("grouped_product", "xla", reason)
        return _sorted_experts(cfg, act, h, chosen, w, moe)
    count_kernel_path("grouped_product", "pallas", "ok")
    local = chosen - cfg.first_expert
    key = jnp.where((local >= 0) & (local < held), local, held)
    xs, ws, owned, dest = rows_into_groups(
        h.astype(moe["gate"].dtype), w, key, held, tiles[0])
    # a call that says which rows are live also has the kernel claim the
    # fast memory its tiles need and no more (grouped_product has why)
    y = grouped_product(xs, ws, owned, moe["gate"], moe["up"], moe["down"],
                        act, *tiles, snug=live is not None)
    return sum_out_of_groups(y, dest, key, held)


def _sorted_experts(cfg, act, h, chosen, w, moe):
    """The grouped product off the TPU and at shapes the kernel's plan
    refuses: the picks sorted by expert and one ``lax.ragged_dot`` a
    projection.  A pick of an expert held elsewhere sorts behind every
    group and adds nothing."""
    dt = moe["gate"].dtype
    t, k = chosen.shape
    held = cfg.experts_held
    local = chosen - cfg.first_expert
    mine = (local >= 0) & (local < held)
    key = jnp.where(mine, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    hs = h.astype(dt)[order // k]                       # (T top_k, embed)
    ws = jnp.where(mine, w, 0.0).reshape(-1)[order]

    def product(a, name):
        return jax.lax.ragged_dot(a, moe[name], sizes,
                                  preferred_element_type=jnp.float32)

    a = (act(product(hs, "gate")) * product(hs, "up")
         * ws[:, None]).astype(dt)
    y = jnp.where((jnp.arange(t * k) < sizes.sum())[:, None],
                  product(a, "down"), 0.0)
    back = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    return y[back].reshape(t, k, -1).sum(1)


def routed_experts(cfg, h, chosen, w, moe, live=None):
    """The held experts' part of ``h (T, embed)``: ``sum over the chosen
    and held x of w[t, x] * E_x(h[t])``, by the product
    :func:`expert_product` chooses for ``T`` rows (counted under
    ``ops.kernel_path``).  ``live = (mask (T,) bool, rows)``: the rows that
    take part, and how many of them the caller expects (a whole number, for
    the choice of the product and of its row tile); a row outside the mask
    takes no pick, of any expert, and comes back zero."""
    from ..ops.registry import count_kernel_path

    act = _ACTIVATIONS[cfg.activation]
    expected = ()
    if live is not None:
        mask, rows = live
        # a pick of no expert anywhere: no place in a layout, weight 0
        chosen = jnp.where(mask[:, None], chosen, -1)
        expected = (rows,)
    path = expert_product(cfg, h.shape[0], *expected)
    count_kernel_path("routed_experts", path, "rows")
    if path == "grouped":
        with jax.named_scope("moe.group"):
            return _grouped_experts(cfg, act, h, chosen, w, moe, *expected)
    return _every_expert(act, h, _combine(cfg, chosen, w), moe)


def sparse_mlp(cfg, h, moe, shared=True, router_input=None, live=None):
    """The sparse MLP of this share: ``(y (T, embed), chosen (T, top_k))``.
    The router reads ``router_input`` (``h`` where none is given).
    ``shared=False`` leaves the shared expert out (a share other than the
    one that counts it, when shares are added up; a model that has none).
    ``live``: the rows that take part in the routed experts and how many
    are expected (:func:`routed_experts`); ``chosen`` is every row's."""
    with jax.named_scope("moe.route"):
        chosen, w = route(cfg, h if router_input is None else router_input,
                          moe)
    with jax.named_scope("moe.experts"):
        y = routed_experts(cfg, h, chosen, w, moe, live)
    if shared:
        with jax.named_scope("moe.shared"):
            y = y + _swiglu(h, moe["shared"])
    return y, chosen


def ring_src(length, window, p_len):
    """Where a prefill's ring takes its rows from: ring row ``j`` holds the
    last position below ``length`` that is ``j`` modulo the window; rows no
    position has reached yet hold what the decode step never reads."""
    return jnp.clip(
        (length - 1) - ((length - 1 - jnp.arange(window)) % window),
        0, p_len - 1)


def count_picks(cfg, chosen, live):
    """``(experts_held,)`` uint32: the picks the live rows gave each held
    expert."""
    hit = jax.nn.one_hot(chosen - cfg.first_expert, cfg.experts_held,
                         dtype=jnp.uint32)
    return (hit * live[:, None, None]).sum((0, 1))


def routing_gauges(picks, steps):
    """The gauges of ``picks (layers, experts_held)`` counted over ``steps``
    steps: picks a held expert sees a step, and the busiest held expert's
    picks over the mean's; none while nothing was counted."""
    if not (steps and picks.sum()):
        return {}
    return {"serving.moe.tokens_per_expert":
            float(picks.sum()) / (picks.size * steps),
            "serving.moe.imbalance": float(picks.max() / picks.mean())}


# -- the plain reference -------------------------------------------------------
def forward_logits(cfg, params, tokens, with_choices=False):
    """``tokens (T,) int32 -> (T, vocab)`` float32 logits of one sequence:
    the equations of the module docstring in float32 at ``highest``
    precision, no cache, each held expert in a plain loop.
    ``with_choices`` also returns the router's choices, one ``(T, top_k)``
    array a sparse layer."""
    (t,) = tokens.shape
    f32 = jnp.float32
    params = jax.tree_util.tree_map(lambda a: a.astype(f32), params)
    group = cfg.heads // cfg.kv_heads
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]
    choices = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for l, p in enumerate(params["layers"]):
            h = _rms(x, p["ln1"])
            q = (h @ p["wq"]).reshape(t, cfg.heads, cfg.head_dim)
            k = (h @ p["wk"]).reshape(t, cfg.kv_heads, cfg.head_dim)
            v = (h @ p["wv"]).reshape(t, cfg.kv_heads, cfg.head_dim)
            q, k = _rms(q, p["q_norm"]), _rms(k, p["k_norm"])
            mask = causal
            if cfg.layer_types[l] == "sliding_attention":
                q, k = _rope(cfg, q, pos), _rope(cfg, k, pos)
                mask = mask & (pos[None, :] > pos[:, None] - cfg.window)
            k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
            scores = jnp.einsum("qhd,khd->hqk", q, k) \
                / math.sqrt(cfg.head_dim)
            att = jax.nn.softmax(jnp.where(mask[None], scores, _NEG), -1)
            ctx = jnp.einsum("hqk,khd->qhd", att, v)
            x = x + ctx.reshape(t, -1) @ p["wo"]
            h = _rms(x, p["ln2"])
            if "mlp" in p:
                x = x + _swiglu(h, p["mlp"])
                continue
            moe = p["moe"]
            chosen, w = route(cfg, h, moe)
            choices.append(chosen)
            y = _swiglu(h, moe["shared"])
            for e in range(cfg.experts_held):
                mine = (chosen == cfg.first_expert + e)
                w_e = jnp.where(mine, w, 0.0).sum(-1, keepdims=True)
                y = y + w_e * _swiglu(
                    h, {n: moe[n][e] for n in ("gate", "up", "down")})
            x = x + y
        logits = _rms(x, params["ln_f"]) @ params["head"]
    return (logits, choices) if with_choices else logits


# -- the block, shared by prefill and decode step ------------------------------
def _block(cfg, l, p, x, pos, attend, counts=None):
    """One layer over rows ``x (T, embed)`` float32 at absolute positions
    ``pos (T,)``.  ``attend(l, q, k, v)`` is the caller's cache access: it
    is handed ``q (T, heads, d)``, ``k``/``v (T, kv_heads, d)`` (normed,
    rotated, in the weights' dtype) and returns the context ``(T, heads,
    d)`` float32.  ``counts(l, chosen)`` is told a sparse layer's choices."""
    window = cfg.layer_types[l] == "sliding_attention"
    t = x.shape[0]
    dt = p["wq"].dtype
    with jax.named_scope("attn.window" if window else "attn.full"):
        h = _rms(x, p["ln1"])
        q = _mm(h, p["wq"]).reshape(t, cfg.heads, cfg.head_dim)
        k = _mm(h, p["wk"]).reshape(t, cfg.kv_heads, cfg.head_dim)
        v = _mm(h, p["wv"]).reshape(t, cfg.kv_heads, cfg.head_dim)
        q, k = _rms(q, p["q_norm"]), _rms(k, p["k_norm"])
        if window:
            q, k = _rope(cfg, q, pos), _rope(cfg, k, pos)
        ctx = attend(l, q.astype(dt), k.astype(dt), v.astype(dt))
        x = x + _mm(ctx.reshape(t, -1), p["wo"])
    h = _rms(x, p["ln2"])
    if "mlp" in p:
        with jax.named_scope("mlp.dense"):
            return x + _swiglu(h, p["mlp"])
    y, chosen = sparse_mlp(cfg, h, p["moe"])
    if counts is not None:
        counts(l, chosen)
    return x + y


def _grouped(cfg, q):
    """``(T, heads, d) -> (T, kv_heads, group, d)``."""
    return q.reshape(q.shape[0], cfg.kv_heads, cfg.heads // cfg.kv_heads,
                     cfg.head_dim)


def _softmax_ctx(scores, mask, values, spec_ctx):
    att = jax.nn.softmax(jnp.where(mask, scores, _NEG), axis=-1)
    return jnp.einsum(spec_ctx, att.astype(values.dtype), values,
                      preferred_element_type=jnp.float32)


class ExaoneMoE:
    """The model object the decode engine is given (its model protocol,
    :mod:`mxnet_tpu.serving.decode`): cache specification, prefill, decode
    step, and the routing counters as extra device state."""

    def __init__(self, cfg, cache_dtype=jnp.bfloat16):
        if cfg.heads % cfg.kv_heads:
            raise ValueError("heads=%d not a multiple of kv_heads=%d"
                             % (cfg.heads, cfg.kv_heads))
        if len(cfg.layer_types) < cfg.layers \
                or len(cfg.mlp_types) < cfg.layers:
            raise ValueError("layer_types/mlp_types name fewer than "
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
        self.sparse = [l for l in range(cfg.layers)
                       if cfg.mlp_types[l] == "sparse"]

    # -- the protocol ------------------------------------------------------
    def cache_spec(self):
        cfg, dtype = self.cfg, self.cache_dtype
        return tuple(
            CacheLayer("ring", cfg.window, cfg.kv_heads, cfg.head_dim,
                       dtype, True)
            if cfg.layer_types[l] == "sliding_attention" else
            CacheLayer("full", cfg.max_len, cfg.kv_heads, cfg.head_dim,
                       dtype, True) for l in range(cfg.layers))

    def extra_state(self):
        """The device counters (uint32, wrapping), counted in decode
        steps over active rows: picks routed to each held expert of each
        sparse layer, picks made in all, rows stepped; and, over the full
        layers, the blocks of cache rows the attention read and the blocks
        the rows' slots hold (:func:`ops.attention.decode_attention`: where
        it reads every row, a slot is one block)."""
        return {"attn_blocks_read": jnp.zeros((), jnp.uint32),
                "attn_blocks_held": jnp.zeros((), jnp.uint32),
                "moe_picks": jnp.zeros((len(self.sparse),
                                        self.cfg.experts_held), jnp.uint32),
                "moe_picks_total": jnp.zeros((), jnp.uint32),
                "rows": jnp.zeros((), jnp.uint32),
                "steps": jnp.zeros((), jnp.uint32)}

    def counters(self, extra):
        """The extra state read back (whole numbers), with the gauges the
        engine publishes under ``gauges``: picks a held expert sees a step,
        the held experts' share of all picks, the busiest held expert's
        picks over the mean's, and the share of the full layers' cache rows
        (``slots x max_len`` a step, over active slots) that the attention
        read."""
        picks = np.asarray(extra["moe_picks"], np.int64)
        total, steps = int(extra["moe_picks_total"]), int(extra["steps"])
        read, held = (int(extra["attn_blocks_" + k]) for k in ("read", "held"))
        out = {"moe_picks": picks.tolist(), "moe_picks_total": total,
               "rows": int(extra["rows"]), "steps": steps,
               "attn_blocks_read": read, "attn_blocks_held": held}
        gauges = routing_gauges(picks, steps)
        if gauges:
            gauges["serving.moe.local_share"] = float(picks.sum()) / total
        if held:
            gauges["serving.attn.rows_read_share"] = read / held
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
        causal = pos[None, :] <= pos[:, None]
        near = pos[None, :] > pos[:, None] - cfg.window
        src = ring_src(length, cfg.window, p_len)
        ks, vs = [], []

        def attend(l, q, k, v):
            window = cfg.layer_types[l] == "sliding_attention"
            scores = jnp.einsum("qkgd,mkd->kgqm", _grouped(cfg, q), k,
                                preferred_element_type=jnp.float32) \
                / math.sqrt(cfg.head_dim)
            ctx = _softmax_ctx(scores, (causal & near) if window
                               else causal, v, "kgqm,mkd->qkgd")
            for rows, held in ((k, ks), (v, vs)):
                held.append(jnp.swapaxes(
                    rows[src] if window else rows, 0, 1).astype(
                        self.cache_dtype))
            return ctx

        x = params["embed"][tokens].astype(jnp.float32)
        for l, p in enumerate(params["layers"]):
            x = _block(cfg, l, p, x, pos, attend)
        last = jnp.take(x, jnp.clip(length - 1, 0, p_len - 1), axis=0)
        with jax.named_scope("head"):
            logits = _mm(_rms(last, params["ln_f"]), params["head"])
        return logits, tuple(ks), tuple(vs)

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
        ring = jnp.arange(cfg.window)
        # the absolute position ring row j holds once ``pos`` is written
        ring_holds = pos[:, None] - ((pos[:, None] - ring[None]) % cfg.window)
        ring_mask = (ring_holds >= 0)[:, None, None, :]
        live = active.astype(jnp.uint32)
        picks, blocks = [], []

        def attend(l, q, k, v):
            q = _grouped(cfg, q)
            if cfg.layer_types[l] == "sliding_attention":
                at = pos % cfg.window
                ck = write_slot_rows(cache_k[l], k, at)
                cv = write_slot_rows(cache_v[l], v, at)
                new_k[l], new_v[l] = ck, cv
                scores = jnp.einsum("skgd,skmd->skgm", q, ck,
                                    preferred_element_type=jnp.float32) \
                    * scale
                return _softmax_ctx(scores, ring_mask, cv, "skgm,skmd->skgd")
            # a full layer reads the rows each slot holds, in blocks
            ck = write_slot_rows(cache_k[l], k, pos)
            cv = write_slot_rows(cache_v[l], v, pos)
            new_k[l], new_v[l] = ck, cv
            blocks.append(decode_attention_plan(q, ck)[0])
            return decode_attention(q, ck, cv, pos, scale)

        def counts(l, chosen):
            picks.append(count_picks(cfg, chosen, live))

        x = params["embed"][last_tok].astype(jnp.float32)
        for l, p in enumerate(params["layers"]):
            x = _block(cfg, l, p, x, pos, attend, counts)
        with jax.named_scope("head"):
            logits = _mm(_rms(x, params["ln_f"]), params["head"])
        rows = live.sum()
        # a full layer read ``pos // b + 1`` of a slot's ``max_len // b``
        # blocks, ``b`` the rows to whose multiple its trace's path reads
        read = sum((live * (pos // b + 1).astype(jnp.uint32)).sum()
                   for b in blocks)
        held = rows * np.uint32(sum(cfg.max_len // b for b in blocks))
        extra = {"attn_blocks_read": extra["attn_blocks_read"] + read,
                 "attn_blocks_held": extra["attn_blocks_held"] + held,
                 "moe_picks": extra["moe_picks"] + jnp.stack(picks).reshape(
                     extra["moe_picks"].shape),
                 "moe_picks_total": extra["moe_picks_total"]
                 + rows * np.uint32(cfg.top_k * len(self.sparse)),
                 "rows": extra["rows"] + rows,
                 "steps": extra["steps"] + (rows > 0).astype(jnp.uint32)}
        return logits, tuple(new_k), tuple(new_v), extra
