"""Granite-4.0-H-style hybrid decoder for the decode tier (``model_type``
``granitemoehybrid`` with no experts: the shape of granite-4.0-h-micro):
Mamba-2 layers beside a few layers of grouped-query attention without
positions, and four fixed multipliers.

``x`` is the residual stream, ``RMS`` an RMSNorm with gain (``eps`` 1e-5),
``E`` the embedding; no matrix has a bias.  ``x_0 = embedding_multiplier
E[tok]``; every layer ``l``: ``x += residual_multiplier Mix_l(RMS1_l(x))``;
``x += residual_multiplier MLP_l(RMS2_l(x))``; ``logits = RMS_f(x) E^T /
logits_scaling`` (tied).  ``MLP(h) = W_out (silu(g) * u)``, ``[g, u] = W_in
h``.  ``cfg.layer_types[l]`` says which mixer a layer has:

* **mamba** (``H`` heads of ``P``, a state of ``N`` values a channel, a
  convolution of ``K`` taps, ``d_inner = H P``): ``[z, xBC, dt] = W_in h``
  (``d_inner``, ``d_inner + 2 N``, ``H``); ``xBC_t = silu(b_c + sum_j w_c[:,
  j] xBC_{t-K+1+j})`` (causal, depthwise, zeros before the sequence); ``[x,
  B, C] = xBC`` (``d_inner``, ``N``, ``N``); ``dt_t = softplus(dt_t +
  dt_bias)`` and ``A = -exp(A_log)``, a value a head each; a head's state
  ``S (P, N)``: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t
  C_t + D x_t``, ``B`` and ``C`` the same for all heads (one group); ``y =
  RMS(y * silu(z))`` over all of ``d_inner`` (the gate BEFORE the norm);
  ``Mix = W_out y``;
* **attention**: ``[q, k, v] = W_qkv h``, ``heads`` query heads over
  ``kv_heads`` K/V heads of ``head_dim``, query head ``i`` reading K/V head
  ``i // (heads / kv_heads)``; scores ``q . k * attention_multiplier`` (the
  configuration's number, not ``1 / sqrt(head_dim)``); causal; nothing
  rotates and nothing is added for a position; ``Mix = W_o ctx``.

**How the program holds a state.**  ``(N, d_inner)`` float32, a head's ``P``
channels side by side in the rows (``d_inner`` last: whole lanes), so a
decode step's sum over ``N`` runs down the sublanes and the chunked scan's
products (:func:`~mxnet_tpu.ops.ssm.ssd_scan`) write 128 lanes at a time.
The decay of a head is laid over its ``P`` lanes by a repeat.

**How the program holds K and V.**  Heads of 64 are cached in pairs, as
:mod:`~mxnet_tpu.models.sambay` caches them and for its reason (rows
narrower than 128 lanes live rows-minor on the v5e): K as ``[k_2g, k_2g+1]``
and V as ``[v_2g, v_2g+1]``, both ``(kv_heads / 2, rows, 2 head_dim)``.  A
query is laid beside zeros where the other head of its pair lies, so its
product with the pair is its product with its own K head exactly, and of
the context ``softmax . [v_2g, v_2g+1]`` it takes its own half.  A prefill
attends with heads of ``head_dim`` laid singly and only lays what it
leaves in the slot in pairs.  :func:`forward_logits` knows no pairs.

**Precision**: weights, K/V and the convolution's tail in their own dtype
(bfloat16 as served), products of operands in the weights' dtype
accumulated in float32; float32: the residual stream, the norms, ``dt``,
every ``exp``, the recurrent state and the scan's sums, softmax, logits.
``xBC`` is rounded to the cache's dtype where it enters the convolution, so
the tail a slot holds is what the prefill convolved.

**Slot state** (:meth:`GraniteHybrid.cache_spec`, an entry a layer, in
layer order): a mamba layer a :class:`~mxnet_tpu.models.transformer_lm.
StateLayer` of the state ``(N, d_inner)`` float32 and the convolution's
tail ``(K - 1, d_inner + 2 N)``; an attention layer ``max_len`` rows of
K and V pairs.

**Prefill** runs every layer over the whole bucket (every layer leaves
state), the scan with ``dt = 0`` and ``x = 0`` at padded positions, so the
state it returns is the state after ``length`` tokens and the tail is the
convolution's last ``K - 1`` real inputs; the head reads the last real
position alone.

:func:`forward_logits` is the in-repo plain reference: float32, ``highest``
precision, no cache, one sequence, the recurrence a plain loop over
positions.  Departures from the published implementation
(``modeling_granitemoehybrid.py``): none in the mathematics; the published
code clamps ``dt`` to ``time_step_limit`` whose default is ``(0, inf)``: no
clamp; with ``num_local_experts`` 0 its ``shared_mlp`` is the whole MLP.
"""

from __future__ import annotations

import math
from collections import namedtuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import decode_attention, write_slot_rows
from ..ops.ssm import ssd_scan
from .transformer_lm import CacheLayer, StateLayer

__all__ = ["GraniteHybridConfig", "GraniteHybrid", "init_params",
           "forward_logits"]

#: ``layer_types`` a tuple of "mamba" | "attention"; ``m_heads`` heads of
#: ``m_head_dim`` make ``d_inner``; ``chunk`` the positions the prefill's
#: scan takes at once; ``ffn`` the SwiGLU's inner width
GraniteHybridConfig = namedtuple("GraniteHybridConfig", [
    "vocab", "embed", "heads", "kv_heads", "head_dim", "layer_types", "ffn",
    "m_heads", "m_head_dim", "d_state", "d_conv", "chunk",
    "embedding_multiplier", "attention_multiplier", "residual_multiplier",
    "logits_scaling", "max_len", "eos_id"])

EPS = 1e-5
_NEG = jnp.float32(-1e30)


def d_inner(cfg):
    return cfg.m_heads * cfg.m_head_dim


def conv_dim(cfg):
    """Channels the convolution runs over: ``x``, ``B`` and ``C``."""
    return d_inner(cfg) + 2 * cfg.d_state


def init_params(cfg, seed=0, dtype=jnp.bfloat16, std=0.02):
    """Seeded parameters (host arrays): normal(0, ``std``), the embedding
    normal(0, ``std / embedding_multiplier``) so that the stream starts at
    ``std`` (the embedding is the head too: at ``std`` a token's own logit,
    ``embedding_multiplier`` times its embedding's square, stands above
    all others whatever the layers compute, and greedy decoding repeats
    the prompt's last token); ``A`` uniform 1 .. 16 a head, ``D = 1``,
    ``dt_bias`` the inverse softplus of a log-uniform 0.001 .. 0.1, the
    convolution uniform within ``1 / sqrt(d_conv)`` and its bias normal(0,
    ``std``), gains 1.  The projections into the residual stream are not
    scaled down by the depth: ``residual_multiplier`` is that scale.
    Matrices in ``dtype``; gains, the convolution, ``dt_bias``, ``A_log``
    and ``D`` float32."""
    rs = np.random.RandomState(seed)
    e, d, h = cfg.embed, d_inner(cfg), cfg.m_heads
    f32 = jnp.float32

    def nrm(*shape, s=std, dt=dtype):
        return jnp.asarray(rs.normal(0, s, shape).astype(np.float32), dt)

    layers = []
    for kind in cfg.layer_types:
        p = {"norm1": jnp.ones((e,), f32), "norm2": jnp.ones((e,), f32),
             "mlp_in": nrm(e, 2 * cfg.ffn),
             "mlp_out": nrm(cfg.ffn, e)}
        if kind == "mamba":
            step = np.exp(rs.uniform(math.log(1e-3), math.log(1e-1), (h,)))
            p.update(
                w_in=nrm(e, d + conv_dim(cfg) + h),
                conv_w=jnp.asarray(rs.uniform(
                    -1.0, 1.0, (conv_dim(cfg), cfg.d_conv))
                    / math.sqrt(cfg.d_conv), f32),
                conv_b=nrm(conv_dim(cfg), dt=f32),
                dt_bias=jnp.asarray(np.log(np.expm1(step)), f32),
                A_log=jnp.asarray(np.log(rs.uniform(1.0, 16.0, (h,))), f32),
                D=jnp.ones((h,), f32), norm=jnp.ones((d,), f32),
                w_out=nrm(d, e))
        else:
            wide = cfg.heads * cfg.head_dim
            p.update(w_qkv=nrm(e, wide + 2 * cfg.kv_heads * cfg.head_dim),
                     w_o=nrm(wide, e))
        layers.append(p)
    return {"embed": nrm(cfg.vocab, e, s=std / cfg.embedding_multiplier),
            "norm_f": jnp.ones((e,), f32),
            "layers": layers}


# -- pieces both the program and the reference are written from ----------------
def _rms(x, g):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * g


def _mm(a, w):
    """``a @ w``: operands in the weights' dtype, float32 accumulation."""
    return jnp.dot(a.astype(w.dtype), w,
                   preferred_element_type=jnp.float32)


def _gated_norm(y, z, g):
    """The mixer's norm over all of ``d_inner``, the gate before it."""
    return _rms(y * jax.nn.silu(z), g)


# -- the plain reference -------------------------------------------------------
def forward_logits(cfg, params, tokens):
    """``tokens (T,) int32 -> (T, vocab)`` float32 logits of one sequence:
    the equations of the module docstring in float32 at ``highest``
    precision, no cache, no chunk, the recurrence a plain loop over
    positions, heads of ``head_dim`` laid singly."""
    return _forward(cfg, params, tokens)[0]


def forward_states(cfg, params, tokens):
    """The recurrent state every mamba layer holds after the last of
    ``tokens``, by the same plain pass, each as the program lays it:
    ``(d_state, d_inner)`` float32, in layer order."""
    return _forward(cfg, params, tokens)[1]


def _forward(cfg, params, tokens):
    (t,) = tokens.shape
    f32 = jnp.float32
    params = jax.tree_util.tree_map(lambda a: a.astype(f32), params)
    d, n, h, p_ = d_inner(cfg), cfg.d_state, cfg.m_heads, cfg.m_head_dim
    hd, reads = cfg.head_dim, cfg.heads // cfg.kv_heads
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]

    states = []
    with jax.default_matmul_precision("highest"):
        x = cfg.embedding_multiplier * params["embed"][tokens]
        for kind, p in zip(cfg.layer_types, params["layers"]):
            hid = _rms(x, p["norm1"])
            if kind == "mamba":
                z, xbc, dt = jnp.split(hid @ p["w_in"],
                                       [d, d + conv_dim(cfg)], axis=-1)
                before = jnp.concatenate(
                    [jnp.zeros((cfg.d_conv - 1, conv_dim(cfg)), f32), xbc])
                xbc = jax.nn.silu(p["conv_b"] + sum(
                    p["conv_w"][:, j] * before[j:j + t]
                    for j in range(cfg.d_conv)))
                xs, b, c = jnp.split(xbc, [d, d + n], axis=-1)
                xs = xs.reshape(t, h, p_)
                dt = jax.nn.softplus(dt + p["dt_bias"])
                a = -jnp.exp(p["A_log"])

                def token(s, at):
                    dt_t, x_t, b_t, c_t = at
                    s = jnp.exp(dt_t * a)[:, None, None] * s \
                        + (dt_t[:, None] * x_t)[:, :, None] * b_t
                    return s, s @ c_t

                left, y = jax.lax.scan(token, jnp.zeros((h, p_, n), f32),
                                       (dt, xs, b, c))
                states.append(left.transpose(2, 0, 1).reshape(n, d))
                y = (y + p["D"][:, None] * xs).reshape(t, d)
                mix = _gated_norm(y, z, p["norm"]) @ p["w_out"]
            else:
                wide = cfg.heads * hd
                q, k, v = jnp.split(
                    hid @ p["w_qkv"], [wide, wide + cfg.kv_heads * hd],
                    axis=-1)
                q = q.reshape(t, cfg.kv_heads, reads, hd)
                k, v = (m.reshape(t, cfg.kv_heads, hd) for m in (k, v))
                scores = jnp.einsum("qgjd,kgd->gjqk", q, k) \
                    * cfg.attention_multiplier
                att = jax.nn.softmax(
                    jnp.where(causal[None, None], scores, _NEG), -1)
                mix = jnp.einsum("gjqk,kgd->qgjd", att, v) \
                    .reshape(t, wide) @ p["w_o"]
            x = x + cfg.residual_multiplier * mix
            gate, up = jnp.split(_rms(x, p["norm2"]) @ p["mlp_in"], 2,
                                 axis=-1)
            x = x + cfg.residual_multiplier * (
                (jax.nn.silu(gate) * up) @ p["mlp_out"])
        return _rms(x, params["norm_f"]) @ params["embed"].T \
            / cfg.logits_scaling, states


# -- one block a layer kind, shared by prefill and decode step -----------------
def _pair_queries(cfg, q):
    """``q (T, heads, d)`` by the K/V pair it reads, each beside zeros
    where the other head of the pair lies: ``(T, kv_heads / 2, 2 heads /
    kv_heads, 2 d)``, the first half of a group reading the pair's first
    head."""
    t = q.shape[0]
    reads = cfg.heads // cfg.kv_heads
    q = q.reshape(t, cfg.kv_heads // 2, 2 * reads, cfg.head_dim)
    zero = jnp.zeros_like(q)
    first = (jnp.arange(2 * reads) < reads)[:, None]
    return jnp.where(first, jnp.concatenate([q, zero], -1),
                     jnp.concatenate([zero, q], -1))


def _own_half(cfg, ctx):
    """Of the context over a pair ``(T, kv_heads / 2, 2 heads / kv_heads,
    2 d)`` each query's own half: ``(T, heads x d)`` in the heads' order."""
    t = ctx.shape[0]
    reads, hd = cfg.heads // cfg.kv_heads, cfg.head_dim
    ctx = ctx.reshape(t, cfg.kv_heads // 2, 2, reads, 2, hd)
    return jnp.stack([ctx[:, :, 0, :, 0], ctx[:, :, 1, :, 1]], 2) \
        .reshape(t, cfg.heads * hd)


def _mamba(cfg, l, p, x, access):
    """``x + residual_multiplier Mix`` of a mamba layer over rows ``x``.
    ``access.window(l, xbc)`` is handed the convolution's input ``(T,
    conv_dim)`` in the cache's dtype and returns every row's last ``K``
    inputs ``(T, K, conv_dim)``; ``access.recur(l, xs, dt, a, b, c, d)``
    is the recurrence from the state the caller holds, ``y (T,
    d_inner)``."""
    d, n = d_inner(cfg), cfg.d_state
    with jax.named_scope(access.ssd_scope):
        z, xbc, dt = jnp.split(_mm(_rms(x, p["norm1"]), p["w_in"]),
                               [d, d + conv_dim(cfg)], axis=-1)
        with jax.named_scope("ssd.conv"):
            last = access.window(l, xbc.astype(access.model.cache_dtype))
            xbc = jax.nn.silu(p["conv_b"] + jnp.einsum(
                "tjd,dj->td", last.astype(jnp.float32), p["conv_w"]))
        xs, b, c = jnp.split(xbc, [d, d + n], axis=-1)
        y = access.recur(l, xs, jax.nn.softplus(dt + p["dt_bias"]),
                         -jnp.exp(p["A_log"]), b, c, p["D"])
        return x + cfg.residual_multiplier * _mm(
            _gated_norm(y, z, p["norm"]), p["w_out"])


def _attention(cfg, l, p, x, access):
    """``x + residual_multiplier Mix`` of an attention layer.
    ``access.attend(l, q, k, v)`` is handed ``q (T, heads, d)`` and ``k``,
    ``v (T, kv_heads, d)`` in the weights' dtype and returns the context
    ``(T, heads x d)`` float32."""
    t = x.shape[0]
    wide, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    with jax.named_scope("attn.full"):
        dt = p["w_qkv"].dtype
        q, k, v = jnp.split(_mm(_rms(x, p["norm1"]), p["w_qkv"])
                            .astype(dt), [wide, wide + kv], axis=-1)
        ctx = access.attend(l, q.reshape(t, cfg.heads, cfg.head_dim),
                            k.reshape(t, cfg.kv_heads, cfg.head_dim),
                            v.reshape(t, cfg.kv_heads, cfg.head_dim))
        return x + cfg.residual_multiplier * _mm(ctx, p["w_o"])


def _mlp(cfg, p, x):
    with jax.named_scope("mlp.dense"):
        gate, up = jnp.split(_mm(_rms(x, p["norm2"]), p["mlp_in"]), 2,
                             axis=-1)
        return x + cfg.residual_multiplier * _mm(
            jax.nn.silu(gate) * up, p["mlp_out"])


def _layers(cfg, params, x, access):
    for l, (kind, p) in enumerate(zip(cfg.layer_types, params["layers"])):
        x = (_mamba if kind == "mamba" else _attention)(cfg, l, p, x,
                                                        access)
        x = _mlp(cfg, p, x)
    return x


def _embed(cfg, params, tokens):
    return cfg.embedding_multiplier \
        * params["embed"][tokens].astype(jnp.float32)


def _head(cfg, params, x):
    with jax.named_scope("head"):
        e = params["embed"]
        return jnp.einsum(
            "te,ve->tv", _rms(x, params["norm_f"]).astype(e.dtype), e,
            preferred_element_type=jnp.float32) / cfg.logits_scaling


def _pairs(cfg, rows):
    """``(T, kv_heads, d) -> (kv_heads / 2, T, 2 d)``: what a slot holds."""
    t = rows.shape[0]
    return jnp.swapaxes(rows.reshape(t, cfg.kv_heads // 2,
                                     2 * cfg.head_dim), 0, 1)


class _Prefill:
    """State access of one bucket-padded prompt: nothing held before it."""

    ssd_scope = "ssd.scan"

    def __init__(self, model, p_len, length):
        cfg = self.cfg = model.cfg
        self.model = model
        pos = jnp.arange(p_len)
        self.real = (pos < length)[:, None]
        self.causal = pos[None, :] <= pos[:, None]
        # the convolution's last inputs: positions length-K+1 .. length-1
        self.tail_src = length - (cfg.d_conv - 1) \
            + jnp.arange(cfg.d_conv - 1)
        #: layer -> what it leaves in a slot
        self.firsts, self.seconds = {}, {}

    def window(self, l, xbc):
        cfg = self.cfg
        t = xbc.shape[0]
        xbc = jnp.where(self.real, xbc, jnp.zeros_like(xbc))
        before = jnp.concatenate(
            [jnp.zeros((cfg.d_conv - 1, xbc.shape[1]), xbc.dtype), xbc])
        self.seconds[l] = jnp.where(
            (self.tail_src >= 0)[:, None],
            xbc[jnp.clip(self.tail_src, 0, t - 1)], 0)
        return jnp.stack([before[j:j + t] for j in range(cfg.d_conv)], 1)

    def recur(self, l, xs, dt, a, b, c, d):
        cfg = self.cfg
        self.firsts[l], y = ssd_scan(
            jnp.where(self.real, xs, 0.0), jnp.where(self.real, dt, 0.0),
            a, b, c, d, jnp.zeros((cfg.d_state, d_inner(cfg)), jnp.float32),
            cfg.chunk)
        return y

    def attend(self, l, q, k, v):
        cfg = self.cfg
        t = q.shape[0]
        self.firsts[l], self.seconds[l] = _pairs(cfg, k), _pairs(cfg, v)
        scores = jnp.einsum(
            "qgjd,kgd->gjqk", q.reshape(t, cfg.kv_heads, -1, cfg.head_dim),
            k, preferred_element_type=jnp.float32) \
            * cfg.attention_multiplier
        att = jax.nn.softmax(
            jnp.where(self.causal[None, None], scores, _NEG), axis=-1)
        return jnp.einsum("gjqk,kgd->qgjd", att.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).reshape(t, -1)


class _Step:
    """State access of one token for every slot: what the slots hold."""

    ssd_scope = "ssd.step"

    def __init__(self, model, firsts, seconds, pos):
        self.cfg, self.model, self.pos = model.cfg, model, pos
        self.firsts, self.seconds = list(firsts), list(seconds)

    def window(self, l, xbc):
        last = jnp.concatenate([self.seconds[l], xbc[:, None]], 1)
        self.seconds[l] = last[:, 1:]
        return last

    def recur(self, l, xs, dt, a, b, c, d):
        width = self.cfg.m_head_dim

        def lanes(v):
            """A value a head ``(S, heads)`` over the lanes of its head."""
            return jnp.repeat(v, width, axis=-1)

        state = lanes(jnp.exp(dt * a))[:, None, :] * self.firsts[l] \
            + (lanes(dt) * xs)[:, None, :] * b[:, :, None]
        self.firsts[l] = state
        return (state * c[:, :, None]).sum(1) + lanes(d[None]) * xs

    def attend(self, l, q, k, v):
        cfg, pos = self.cfg, self.pos
        s = q.shape[0]
        k, v = (rows.reshape(s, cfg.kv_heads // 2, 2 * cfg.head_dim)
                for rows in (k, v))
        ck = self.firsts[l] = write_slot_rows(self.firsts[l], k, pos)
        cv = self.seconds[l] = write_slot_rows(self.seconds[l], v, pos)
        return _own_half(cfg, decode_attention(
            _pair_queries(cfg, q), ck, cv, pos, cfg.attention_multiplier))


class GraniteHybrid:
    """The model object the decode engine is given (its model protocol,
    :mod:`mxnet_tpu.serving.decode`): slot-state specification, prefill,
    decode step, and the row counters as extra device state."""

    def __init__(self, cfg, cache_dtype=jnp.bfloat16):
        if cfg.heads % cfg.kv_heads or cfg.kv_heads % 2:
            raise ValueError("heads=%d, kv_heads=%d: K/V heads are cached "
                             "in pairs, each read by whole groups of "
                             "queries" % (cfg.heads, cfg.kv_heads))
        unknown = set(cfg.layer_types) - {"mamba", "attention"}
        if unknown:
            raise ValueError("layer_types %s: a layer is \"mamba\" or "
                             "\"attention\"" % sorted(unknown))
        self.cfg = cfg
        #: what K, V and the convolution's tail are held in (the recurrent
        #: state is float32 whatever this is)
        self.cache_dtype = cache_dtype

    # -- the protocol ------------------------------------------------------
    def cache_spec(self):
        cfg, dtype = self.cfg, self.cache_dtype
        made = {
            "mamba": StateLayer(
                "state", ((cfg.d_state, d_inner(cfg)),
                          (cfg.d_conv - 1, conv_dim(cfg))),
                (jnp.float32, dtype)),
            "attention": CacheLayer("full", cfg.max_len, cfg.kv_heads // 2,
                                    2 * cfg.head_dim, dtype, True)}
        return tuple(made[kind] for kind in cfg.layer_types)

    def extra_state(self):
        """The device counters (uint32, wrapping), counted in decode steps
        over active slots: ``rows`` the slots stepped (each reads and
        writes every state whole), ``rows_full`` the rows an attention
        layer holds for them (every one of them holds the same),
        ``steps`` the steps that stepped any."""
        return {name: jnp.zeros((), jnp.uint32)
                for name in ("rows", "rows_full", "steps")}

    def counters(self, extra):
        """The extra state read back, whole numbers by name."""
        return {name: int(value) for name, value in extra.items()}

    def prefill(self, params, tokens, length):
        """One bucket-padded prompt ``tokens (P,)`` of ``length`` real
        tokens -> ``(last_logits (vocab,), firsts, seconds)``: for every
        layer the two values of one slot (a state and its tail whole; an
        attention layer's positions ``0 .. P-1``)."""
        cfg = self.cfg
        (p_len,) = tokens.shape
        access = _Prefill(self, p_len, length)
        x = _layers(cfg, params, _embed(cfg, params, tokens), access)
        at = jnp.clip(length - 1, 0, p_len - 1)
        layers = range(len(cfg.layer_types))
        return (_head(cfg, params, x[at][None])[0],
                tuple(access.firsts[l] for l in layers),
                tuple(access.seconds[l] for l in layers))

    def decode_step(self, params, firsts, seconds, last_tok, lengths,
                    active, extra):
        """One token for all ``S`` slots: every state and tail advances by
        one token, the incoming K/V goes to position ``lengths`` of each
        attention layer and is attended over with everything the slot
        holds.  Returns ``(logits (S, vocab), firsts, seconds, extra)``."""
        cfg = self.cfg
        pos = jnp.clip(lengths, 0, cfg.max_len - 1)
        access = _Step(self, firsts, seconds, pos)
        x = _layers(cfg, params, _embed(cfg, params, last_tok), access)
        logits = _head(cfg, params, x)
        live = active.astype(jnp.uint32)
        rows = live.sum()
        extra = {
            "rows": extra["rows"] + rows,
            "rows_full": extra["rows_full"]
            + (live * (pos + 1).astype(jnp.uint32)).sum(),
            "steps": extra["steps"] + (rows > 0).astype(jnp.uint32)}
        return logits, tuple(access.firsts), tuple(access.seconds), extra
