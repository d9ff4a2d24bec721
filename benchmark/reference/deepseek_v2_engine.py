"""Plain reference of the served DeepSeek-V2-shaped decoder, one chip's
share (family ``deepseek_v2_engine``).

Written from the equations (ISSUE 39, Tentpole), not from the program.
``x`` is the residual stream, pre-norm, ``RMS(x, g) = x g / sqrt(mean(x^2)
+ eps)``, no biases; layer ``l``:

* ``h = RMS(x, g1)``; ``c_q = RMS(h W_DQ, g_q)``; ``q = c_q W_UQ``, ``heads``
  of ``qk_nope_head_dim + qk_rope_head_dim``; the last ``qk_rope_head_dim``
  of each are rotated;
* ``[c_kv', k_r'] = h W_DKV`` (``kv_lora_rank + qk_rope_head_dim``); ``c_kv
  = RMS(c_kv', g_kv)``; ``k_rope = RoPE(k_r')``, one a token, read by every
  head;
* the expanded form and no other: ``k_nope_i = c_kv W_UK_i``, ``v_i = c_kv
  W_UV_i`` for each head; ``score_i(t, s) = (q_nope_i(t) . k_nope_i(s) +
  q_rope_i(t) . k_rope(s)) scale``; causal softmax in float32; ``o_i = sum_s
  p v_i(s)``; ``x += concat_i(o_i) W_O``;
* ``scale = (nope + rope)^-0.5 m^2``, ``m = 0.1 mscale_all_dim ln(factor) +
  1``;
* RoPE over the pairs ``(x[2j], x[2j+1])``, base ``rope_theta``, YaRN's
  inverse frequencies (:func:`inv_freq`), cosines and sines scaled by
  ``(0.1 mscale ln(factor) + 1) / (0.1 mscale_all_dim ln(factor) + 1)``;
* layers below ``first_k_dense_replace``: ``x += SwiGLU(RMS(x, g2))`` of
  ``intermediate_size``; the others: ``s = softmax(RMS(x, g2) W_r)`` over
  all ``n_routed_experts`` in float32; a group's score is its best
  expert's; the best ``topk_group`` of ``n_group`` groups stay; the best
  ``num_experts_per_tok`` experts among theirs are chosen; ``x +=
  routed_scaling_factor sum over chosen AND held e of s_e E_e(h) +
  SwiGLU_shared(h)`` (no renorm; ``E_e`` a SwiGLU of
  ``moe_intermediate_size``, the shared one of ``n_shared_experts`` times
  that).  The sum runs over the ``experts_held`` experts from
  ``first_expert``; what the absent experts would add is left out;
* head: ``RMS(x, gf) Wh`` over the slice of the vocabulary, untied.

Departures from the published model, each under ``assumed`` in the
configuration's file: the weights are random (the two halves of
``kv_b_proj`` drawn as two arrays, heads first); the rotated values are
laid out half-split after the rotation, queries and keys alike, which
changes no score; no token ends a session.

No cache, no kernels: whole sequences, every layer in float32 at ``highest``
precision.  The weights are made on the device from the seed in bfloat16
and widened a layer (and, of the experts, an expert) at a time; queries go
through attention a block at a time (:data:`QUERY_BLOCK`) and the logits
are read in blocks of rows, so the pass fits beside the weights.  A call
takes one row of tokens in which whole sequences lie end to end
(:func:`pack`), as the benchmark's other references do.

It imports nothing of ``mxnet_tpu`` and takes nothing the program made.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# what is no model's: drawing a matrix, rounding through fp8, the norm,
# laying sequences end to end in rows, gaps under the best logit
from benchmark.reference.exaone_moe_engine import (  # noqa: F401 — pack is the adapter's
    _frozen, _normal, _rms, _to_fp8, pack)
from benchmark.reference.sambay_engine import _gaps

INIT_STD = 0.02
#: queries attended at once, and rows whose logits are read at once
QUERY_BLOCK = 128
LOGIT_BLOCK = 2048


def sizes(config):
    """The shapes of a config file as a dict of numbers."""
    if config["topk_method"] != "group_limited_greedy" \
            or config["scoring_func"] != "softmax" \
            or config["norm_topk_prob"] \
            or config["rope_scaling"]["type"] != "yarn" \
            or int(config["moe_layer_freq"]) != 1:
        raise ValueError("the reference has the softmax group-limited "
                         "greedy router without renorm, YaRN, and an "
                         "expert layer after every dense one alone")
    yarn = config["rope_scaling"]
    return {
        "vocab": int(config["vocab_size"]),
        "embed": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope_dim": int(config["qk_nope_head_dim"]),
        "rope_dim": int(config["qk_rope_head_dim"]),
        "v_dim": int(config["v_head_dim"]),
        "layers": int(config["num_hidden_layers"]),
        "first_dense": int(config["first_k_dense_replace"]),
        "dense_ffn": int(config["intermediate_size"]),
        "expert_ffn": int(config["moe_intermediate_size"]),
        "shared_ffn": int(config["n_shared_experts"])
        * int(config["moe_intermediate_size"]),
        "num_experts": int(config["n_routed_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "n_group": int(config["n_group"]),
        "topk_group": int(config["topk_group"]),
        "first_expert": int(config["first_expert"]),
        "experts_held": int(config["experts_held"]),
        "routed_scale": float(config["routed_scaling_factor"]),
        "rope_theta": float(config["rope_theta"]),
        "rope_factor": float(yarn["factor"]),
        "rope_original": int(yarn["original_max_position_embeddings"]),
        "beta_fast": float(yarn["beta_fast"]),
        "beta_slow": float(yarn["beta_slow"]),
        "mscale": float(yarn["mscale"]),
        "mscale_all_dim": float(yarn["mscale_all_dim"]),
        "eps": float(config["rms_norm_eps"]),
        "max_len": int(config["engine"]["max_len"]),
    }


def init_weights(config, seed, device):
    """The weights, drawn on ``device`` from ``seed`` (any whole number):
    normal(0, 0.02) (a CPU-sized configuration states an ``init_std`` of
    its own, at which its few narrow layers move the stream as the real
    widths' do), the projections into the residual stream scaled by
    1/sqrt(2 layers), gains 1; matrices in the configuration's weight
    dtype, the router's matrix in float32.  ``w_uk (heads, nope, kv_rank)``
    and ``w_uv (heads, kv_rank, v)`` are the two halves of ``kv_b_proj``."""
    z = sizes(config)
    dtype = jnp.dtype(config["precision"]["weights"])
    e, h, f32 = z["embed"], z["heads"], jnp.float32
    init_std = float(config.get("init_std", INIT_STD))
    resid = init_std / math.sqrt(2.0 * z["layers"])
    with jax.default_device(device):
        root = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                                  seed // (2 ** 31))
        count = [0]

        def nrm(*shape, std=init_std, dt=dtype):
            count[0] += 1
            return _normal(jax.random.fold_in(root, count[0]), shape, std,
                           dt)

        def swiglu(width, *lead):
            return {"gate": nrm(*lead, e, width), "up": nrm(*lead, e, width),
                    "down": nrm(*lead, width, e, std=resid)}

        layers = []
        for l in range(z["layers"]):
            p = {"ln1": jnp.ones((e,), f32), "ln2": jnp.ones((e,), f32),
                 "q_norm": jnp.ones((z["q_rank"],), f32),
                 "kv_norm": jnp.ones((z["kv_rank"],), f32),
                 "wq_a": nrm(e, z["q_rank"]),
                 "wq_b": nrm(z["q_rank"],
                             h * (z["nope_dim"] + z["rope_dim"])),
                 "wkv_a": nrm(e, z["kv_rank"] + z["rope_dim"]),
                 "w_uk": nrm(h, z["nope_dim"], z["kv_rank"]),
                 "w_uv": nrm(h, z["kv_rank"], z["v_dim"]),
                 "wo": nrm(h * z["v_dim"], e, std=resid)}
            if l < z["first_dense"]:
                p["mlp"] = swiglu(z["dense_ffn"])
            else:
                p["moe"] = dict(
                    swiglu(z["expert_ffn"], z["experts_held"]),
                    router=nrm(e, z["num_experts"], dt=f32),
                    shared=swiglu(z["shared_ffn"]))
            layers.append(p)
        return {"embed": nrm(z["vocab"], e), "head": nrm(e, z["vocab"]),
                "ln_f": jnp.ones((e,), f32), "layers": layers}


# -- the forward pass ----------------------------------------------------------
def _through_fp8(a):
    """A latent row as an fp8 cache would hold it: e4m3 and back, no
    scale (a normed row's values lie well inside e4m3's range)."""
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


class Variant:
    """How a forward pass computes: the dtype the weights are read in, the
    dtype activations are held in, the dtype products accumulate in, and
    what the latent rows and rotated keys go through before they are
    attended over (what a cache of another precision would hold)."""

    def __init__(self, name, weights=None, act=jnp.float32,
                 acc=jnp.float32, held=None):
        self.name, self.weights, self.act, self.acc, self.held = \
            name, weights, act, acc, held

    def w(self, a):
        return a if self.weights is None else self.weights(a)

    def mm(self, a, w):
        w = self.w(w).astype(self.act)
        return jnp.dot(a.astype(self.act), w,
                       preferred_element_type=self.acc).astype(self.act)

    def einsum(self, spec, a, w):
        w = self.w(w).astype(self.act)
        return jnp.einsum(spec, a.astype(self.act), w,
                          preferred_element_type=self.acc).astype(self.act)


#: the reference itself; a reading in the configuration's own precision
#: (bfloat16 weights and activations, float32 accumulation); and the two
#: controls, each the nearest precision below what the configuration
#: states for one thing: the weights through fp8 with bfloat16
#: accumulation, and the latent cache kept in fp8 with all else as stated
REFERENCE = Variant("float32")
STATED = Variant("bfloat16", act=jnp.bfloat16)
CONTROL_FP8 = Variant("fp8", weights=_to_fp8, act=jnp.bfloat16,
                      acc=jnp.bfloat16)
CONTROL_LATENT_FP8 = Variant("latent-fp8", act=jnp.bfloat16,
                             held=_through_fp8)
VARIANTS = {v.name: v for v in (REFERENCE, STATED, CONTROL_FP8,
                                CONTROL_LATENT_FP8)}
CONTROLS = (CONTROL_FP8, CONTROL_LATENT_FP8)


def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(z):
    """YaRN's inverse frequency of each rotated pair, as the public
    modeling code computes it (recalled; ``assumed`` in the configuration):
    ``theta^(-2j/d)`` blended with that over ``factor`` by a linear ramp
    between the correction dimensions of ``beta_fast`` and ``beta_slow``
    turns over the original positions (the first rounded down, the second
    up)."""
    d = z["rope_dim"]
    plain = z["rope_theta"] ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def correction(turns):
        return d * math.log(z["rope_original"] / (turns * 2 * math.pi)) \
            / (2 * math.log(z["rope_theta"]))

    low = max(math.floor(correction(z["beta_fast"])), 0)
    high = min(math.ceil(correction(z["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    return plain / z["rope_factor"] * (1 - keep) + plain * keep


def softmax_scale(z):
    return (z["nope_dim"] + z["rope_dim"]) ** -0.5 \
        * yarn_mscale(z["rope_factor"], z["mscale_all_dim"]) ** 2


def _rope(z, x, pos):
    """``x (T, n, rope_dim)`` rotated at ``pos``, pairs ``(x[2j],
    x[2j+1])``, laid out half-split."""
    ang = pos.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq(z), jnp.float32)[None]
    m = yarn_mscale(z["rope_factor"], z["mscale"]) \
        / yarn_mscale(z["rope_factor"], z["mscale_all_dim"])
    cos, sin = jnp.cos(ang)[:, None] * m, jnp.sin(ang)[:, None] * m
    xf = x.astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin, odd * cos + even * sin],
                           -1).astype(x.dtype)


def _attention(z, va, block, q_nope, q_rope, k_nope, k_rope, v, seg, pos):
    """Causal softmax attention of every token within its own sequence,
    ``block`` queries at a time."""
    t = q_nope.shape[0]
    block = min(block, t)
    scale = softmax_scale(z)

    def attend(args):
        qn, qr, qseg, qpos = args
        mask = (seg[None, :] == qseg[:, None]) & (pos[None, :]
                                                  <= qpos[:, None])
        scores = (jnp.einsum("qhd,khd->hqk", qn, k_nope,
                             preferred_element_type=va.acc)
                  .astype(jnp.float32)
                  + jnp.einsum("qhr,kr->hqk", qr, k_rope,
                               preferred_element_type=va.acc)
                  .astype(jnp.float32)) * scale
        att = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), -1)
        return jnp.einsum("hqk,khd->qhd", att.astype(va.act), v,
                          preferred_element_type=va.acc).astype(va.act)

    # the same block, one after another (``t`` is a multiple of ``block``)
    out = jax.lax.map(attend, (
        q_nope.reshape(t // block, block, *q_nope.shape[1:]),
        q_rope.reshape(t // block, block, *q_rope.shape[1:]),
        seg.reshape(t // block, block), pos.reshape(t // block, block)))
    return out.reshape(t, *v.shape[1:])


def route(z, h, moe):
    """(chosen (T, top_k) over all experts, their weights), in float32: the
    plain reading of group-limited greedy choice."""
    s = jax.nn.softmax(jnp.dot(h.astype(jnp.float32), moe["router"]), -1)
    per = z["num_experts"] // z["n_group"]
    group_best = s.reshape(-1, z["n_group"], per).max(-1)
    _, kept = jax.lax.top_k(group_best, z["topk_group"])
    group_of = jnp.arange(z["num_experts"]) // per
    stays = (group_of[None, :, None] == kept[:, None, :]).any(-1)
    weight, chosen = jax.lax.top_k(jnp.where(stays, s, 0.0), z["top_k"])
    return chosen, weight * z["routed_scale"]


def _swiglu(va, h, w):
    return va.mm(jax.nn.silu(va.mm(h, w["gate"])) * va.mm(h, w["up"]),
                 w["down"])


def layer(z, va, block, w, x, seg, pos):
    """One block over a row of sequences ``x (T, embed)``, its attention
    ``block`` queries at a time; also the router's choices in an expert
    layer (else None)."""
    t = x.shape[0]
    nope = z["nope_dim"]
    h = _rms(x, w["ln1"], z["eps"])
    c_q = _rms(va.mm(h, w["wq_a"]), w["q_norm"], z["eps"])
    q = va.mm(c_q, w["wq_b"]).reshape(t, z["heads"], nope + z["rope_dim"])
    q_rope = _rope(z, q[..., nope:], pos)
    kv = va.mm(h, w["wkv_a"])
    c_kv = _rms(kv[:, :z["kv_rank"]], w["kv_norm"], z["eps"])
    k_rope = _rope(z, kv[:, None, z["kv_rank"]:], pos)[:, 0]
    if va.held is not None:
        c_kv, k_rope = va.held(c_kv), va.held(k_rope)
    k_nope = va.einsum("tc,hdc->thd", c_kv, w["w_uk"])
    v = va.einsum("tc,hcd->thd", c_kv, w["w_uv"])
    ctx = _attention(z, va, block, q[..., :nope], q_rope, k_nope, k_rope, v,
                     seg, pos)
    x = x + va.mm(ctx.reshape(t, -1), w["wo"])
    h = _rms(x, w["ln2"], z["eps"])
    if "mlp" in w:
        return x + _swiglu(va, h, w["mlp"]), None
    moe = w["moe"]
    chosen, weight = route(z, h, moe)

    def add_expert(y, held):
        # one held expert after another, each a plain SwiGLU over all
        # rows, weighted by what the rows that chose it gave it
        e, expert = held
        mine = chosen == z["first_expert"] + e
        w_e = jnp.where(mine, weight, 0.0).sum(-1, keepdims=True)
        return y + w_e.astype(y.dtype) * _swiglu(va, h, expert), None

    y, _ = jax.lax.scan(
        add_expert, _swiglu(va, h, moe["shared"]),
        (jnp.arange(z["experts_held"]),
         {n: moe[n] for n in ("gate", "up", "down")}))
    return x + y, chosen


def _highest(va, fn):
    if va is REFERENCE:
        with jax.default_matmul_precision("highest"):
            return fn()
    return fn()


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer_jit(zf, va_name, block, w, x, seg, pos):
    va = VARIANTS[va_name]
    return _highest(va, lambda: layer(dict(zf), va, block, w, x, seg, pos))


def forward_hidden(z, params, tokens, seg=None, pos=None, variant=REFERENCE,
                   with_choices=False):
    """``tokens (T,) int32 -> (T, embed)``: the residual stream after the
    last layer of a row of sequences (one sequence from position 0 where
    ``seg``/``pos`` are not given), a jitted call a layer so that one
    layer's float32 copy lives at a time.  ``with_choices`` also returns
    each expert layer's choices."""
    zf = _frozen(z)
    if seg is None:
        seg = jnp.zeros(tokens.shape, jnp.int32)
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"][tokens].astype(variant.act)
    choices = []
    for w in params["layers"]:
        x, chosen = _layer_jit(zf, variant.name, QUERY_BLOCK, w, x, seg, pos)
        if chosen is not None:
            choices.append(chosen)
    return (x, choices) if with_choices else x


@functools.partial(jax.jit, static_argnums=(0, 1))
def _logits_jit(va_name, eps, head, x):
    va = VARIANTS[va_name]
    return _highest(va, lambda: va.mm(_rms(x, head["ln_f"], eps),
                                      head["head"]).astype(jnp.float32))


def _head(params):
    return {k: params[k] for k in ("ln_f", "head")}


def forward_logits(z, params, tokens, seg=None, pos=None,
                   variant=REFERENCE):
    """``(T, vocab)`` float32 logits, whole: for the CPU-sized tests."""
    x = forward_hidden(z, params, tokens, seg, pos, variant)
    return _logits_jit(variant.name, z["eps"], _head(params), x)


def _blocks(t):
    """``(start, stop)`` of the blocks of rows whose logits are read at
    once."""
    return [(i, min(i + LOGIT_BLOCK, t)) for i in range(0, t, LOGIT_BLOCK)]


def best_tokens(z, params, x, variant):
    """The token each row's logits put first, ``(T,) int32``, the logits
    read a block of rows at a time."""
    head = _head(params)
    return jnp.concatenate([
        jnp.argmax(_logits_jit(variant.name, z["eps"], head, x[i:j]), -1)
        for i, j in _blocks(x.shape[0])]).astype(jnp.int32)


def gaps_below_best(z, params, x, chosen):
    """By how much the reference's logit of ``chosen[k, i]`` lies below the
    largest logit of row ``i``, ``(K, T)``: 0 where the chosen token is the
    reference's own.  The logits are read a block of rows at a time."""
    head = _head(params)
    return jnp.concatenate([
        _gaps(_logits_jit(REFERENCE.name, z["eps"], head, x[i:j]),
              chosen[:, i:j])
        for i, j in _blocks(x.shape[0])], axis=-1)
