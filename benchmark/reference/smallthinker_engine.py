"""Plain reference of the served SmallThinker-shaped decoder (family
``smallthinker_engine``).

Written from the equations (ISSUE 33, section 1), not from the program.
``x`` is the residual stream, ``RMS(x, g) = x g / sqrt(mean(x^2) + eps)``;
layer ``l`` over its input ``x_in``:

* router, before attention: ``r = x_in Wr`` (of ``x_in`` itself, not of its
  norm; float32), ``chosen = top_k(r)``, ``w = softmax(r[chosen])`` over
  the chosen (a softmax over all the scores, renormed over the chosen, is
  this);
* attention: ``h = RMS(x_in, g1)``; ``q = h Wq`` (``heads`` of
  ``head_dim``), ``k = h Wk``, ``v = h Wv`` (``kv_heads``), no biases, no
  QK-norm; where ``rope_layout[l]`` is 1, ``q`` and ``k`` are rotated (RoPE
  over the whole head, half-split pairs); where ``sliding_window_layout[l]``
  is 1 a query at ``p`` reads ``p - window + 1 .. p``, else ``0 .. p``;
  query head ``i`` reads K/V head ``i // (heads // kv_heads)``; scores
  ``q.k / sqrt(head_dim)``, softmax; ``x = x_in + ctx Wo``;
* experts: ``h2 = RMS(x, g2)``; ``E_e(h) = (relu(h Wg_e) * (h Wu_e)) Wd_e``;
  ``x = x + sum over chosen AND held e of w_e E_e(h2)``.  No shared expert,
  no dense layer.  The router, the choice and the weights are over all the
  experts; the sum over the ``experts_held`` from ``first_expert`` (all of
  them in the benchmark's configuration);
* head: ``RMS(x, gf) Wh``, untied.

No cache, no kernels: whole sequences, every layer in float32 at ``highest``
precision.  The weights are made on the device from the seed in bfloat16
(what the configuration states) and widened a layer at a time, so the
float32 pass fits beside them; queries go through attention in blocks and
the logits are read in blocks of rows for the same reason (``8192 x
151,936`` float32 never stands whole).  A call takes one row of tokens in
which whole sequences lie end to end (:func:`pack`): ``seg`` names each
token's sequence and ``pos`` its position in it, and a token attends within
its own sequence only, which is the mathematics of one sequence a call at
one compiled shape for all of them.

It imports nothing of ``mxnet_tpu`` and takes nothing the program made; what
is no model's own (drawing a matrix from a key, fp8 rounding, the norm, the
rotation, :func:`pack`, the gaps under the best logit) it shares with the
benchmark's other references.
"""

import functools
import math

import jax
import jax.numpy as jnp

# what is no model's: drawing a matrix, rounding through fp8, the norm, the
# rotation, laying sequences end to end in rows, gaps under the best logit
from benchmark.reference.exaone_moe_engine import (  # noqa: F401 — pack is the adapter's
    _frozen, _normal, _rms, _rope, _to_fp8, pack)
from benchmark.reference.sambay_engine import _gaps

INIT_STD = 0.02
#: queries attended at once, and rows whose logits are read at once
QUERY_BLOCK = 256
LOGIT_BLOCK = 512


def sizes(config):
    """The shapes of a config file, as a dict of whole numbers and the two
    per-layer lists cut to the layers held."""
    if not (config["moe_primary_router_apply_softmax"]
            and config["norm_topk_prob"]):
        raise ValueError("the reference has the softmax router with "
                         "renormed weights alone")
    n = int(config["num_hidden_layers"])
    return {
        "vocab": int(config["vocab_size"]),
        "embed": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "layers": n,
        "rope_layout": tuple(int(b) for b in config["rope_layout"][:n]),
        "window_layout": tuple(
            int(b) for b in config["sliding_window_layout"][:n]),
        "expert_ffn": int(config["moe_ffn_hidden_size"]),
        "num_experts": int(config["moe_num_primary_experts"]),
        "top_k": int(config["moe_num_active_primary_experts"]),
        "first_expert": int(config["first_expert"]),
        "experts_held": int(config["experts_held"]),
        "window": int(config["sliding_window_size"]),
        "rope_theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "max_len": int(config["engine"]["max_len"]),
    }


def init_weights(config, seed, device):
    """The weights, drawn on ``device`` from ``seed`` (any whole number):
    normal(0, 0.02), the projections into the residual stream scaled by
    1/sqrt(2 layers), gains 1; matrices in the configuration's weight
    dtype, the router's matrix in float32."""
    z = sizes(config)
    dtype = jnp.dtype(config["precision"]["weights"])
    e, hd, f, f32 = z["embed"], z["head_dim"], z["expert_ffn"], jnp.float32
    resid = INIT_STD / math.sqrt(2.0 * z["layers"])
    with jax.default_device(device):
        root = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                                  seed // (2 ** 31))
        count = [0]

        def nrm(*shape, std=INIT_STD, dt=dtype):
            count[0] += 1
            return _normal(jax.random.fold_in(root, count[0]), shape, std,
                           dt)

        layers = [{
            "ln1": jnp.ones((e,), f32), "ln2": jnp.ones((e,), f32),
            "wq": nrm(e, z["heads"] * hd), "wk": nrm(e, z["kv_heads"] * hd),
            "wv": nrm(e, z["kv_heads"] * hd),
            "wo": nrm(z["heads"] * hd, e, std=resid),
            "moe": {"router": nrm(e, z["num_experts"], dt=f32),
                    "gate": nrm(z["experts_held"], e, f),
                    "up": nrm(z["experts_held"], e, f),
                    "down": nrm(z["experts_held"], f, e, std=resid)}}
            for _ in range(z["layers"])]
        return {"embed": nrm(z["vocab"], e), "head": nrm(e, z["vocab"]),
                "ln_f": jnp.ones((e,), f32), "layers": layers}


# -- the forward pass ----------------------------------------------------------
class Variant:
    """How a forward pass computes: the dtype the weights are read in, the
    dtype activations are held in, the dtype products accumulate in, and
    whether a window layer keeps to its window."""

    def __init__(self, name, weights=None, act=jnp.float32,
                 acc=jnp.float32, windowed=True):
        self.name, self.weights, self.act, self.acc, self.windowed = \
            name, weights, act, acc, windowed

    def w(self, a):
        return a if self.weights is None else self.weights(a)

    def mm(self, a, w):
        w = self.w(w).astype(self.act)
        return jnp.dot(a.astype(self.act), w,
                       preferred_element_type=self.acc).astype(self.act)


#: the reference itself; a reading in the configuration's own precision
#: (bfloat16 weights and activations, float32 accumulation: what a plain
#: forward pass gives at the program's precision); and the two controls:
#: the nearest precision below it (weights through fp8, activations in
#: bfloat16, products accumulated in bfloat16), and the reference with the
#: window left out (its window layers reading ``0 .. p``)
REFERENCE = Variant("float32")
STATED = Variant("bfloat16", act=jnp.bfloat16)
CONTROL_FP8 = Variant("fp8", weights=_to_fp8, act=jnp.bfloat16,
                      acc=jnp.bfloat16)
CONTROL_NO_WINDOW = Variant("no-window", windowed=False)
VARIANTS = {v.name: v for v in (REFERENCE, STATED, CONTROL_FP8,
                                CONTROL_NO_WINDOW)}
CONTROLS = (CONTROL_FP8, CONTROL_NO_WINDOW)


def _attention(z, va, window, q, k, v, seg, pos):
    """Causal (and, in a window layer, windowed) softmax attention of every
    token within its own sequence, a block of queries at a time."""
    t = q.shape[0]
    group = z["heads"] // z["kv_heads"]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    block = min(QUERY_BLOCK, t)

    def attend(args):
        qb, qseg, qpos = args
        mask = (seg[None, :] == qseg[:, None]) & (pos[None, :]
                                                  <= qpos[:, None])
        if window:
            mask = mask & (pos[None, :] > qpos[:, None] - z["window"])
        scores = jnp.einsum("qhd,khd->hqk", qb, k,
                            preferred_element_type=va.acc) \
            .astype(jnp.float32) / math.sqrt(z["head_dim"])
        att = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), -1)
        return jnp.einsum("hqk,khd->qhd", att.astype(va.act), v,
                          preferred_element_type=va.acc).astype(va.act)

    # the same block, one after another (``t`` is a multiple of ``block``)
    out = jax.lax.map(attend, (q.reshape(t // block, block, *q.shape[1:]),
                               seg.reshape(t // block, block),
                               pos.reshape(t // block, block)))
    return out.reshape(q.shape)


def route(z, x_in, moe):
    """(chosen (T, top_k) over all experts, their weights), in float32, of
    the layer's own input."""
    picked, chosen = jax.lax.top_k(
        jnp.dot(x_in.astype(jnp.float32), moe["router"]), z["top_k"])
    return chosen, jax.nn.softmax(picked, axis=-1)


def layer(z, va, rotate, window, w, x, seg, pos):
    """One block over a row of sequences ``x (T, embed)``; ``rotate`` and
    ``window`` are the layer's two entries of the published layouts.
    Returns the stream and the router's choices."""
    t = x.shape[0]
    moe = w["moe"]
    chosen, weight = route(z, x, moe)
    h = _rms(x, w["ln1"], z["eps"])
    q = va.mm(h, w["wq"]).reshape(t, z["heads"], z["head_dim"])
    k = va.mm(h, w["wk"]).reshape(t, z["kv_heads"], z["head_dim"])
    v = va.mm(h, w["wv"]).reshape(t, z["kv_heads"], z["head_dim"])
    if rotate:
        q, k = _rope(q, pos, z["rope_theta"]), _rope(k, pos, z["rope_theta"])
    ctx = _attention(z, va, window and va.windowed, q, k, v, seg, pos)
    x = x + va.mm(ctx.reshape(t, -1), w["wo"])
    h = _rms(x, w["ln2"], z["eps"])

    def add_expert(y, held):
        # one held expert after another, each a plain ReGLU over all rows,
        # weighted by what the rows that chose it gave it
        e, expert = held
        mine = chosen == z["first_expert"] + e
        w_e = jnp.where(mine, weight, 0.0).sum(-1, keepdims=True)
        inner = jax.nn.relu(va.mm(h, expert["gate"])) \
            * va.mm(h, expert["up"])
        return y + w_e.astype(y.dtype) * va.mm(inner, expert["down"]), None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (jnp.arange(z["experts_held"]),
         {n: moe[n] for n in ("gate", "up", "down")}))
    return x + y, chosen


def _highest(va, fn, *args):
    if va is REFERENCE or va is CONTROL_NO_WINDOW:
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return fn(*args)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _layer_jit(zf, va_name, rotate, window, w, x, seg, pos):
    va = VARIANTS[va_name]
    return _highest(va, lambda: layer(dict(zf), va, rotate, window, w, x,
                                      seg, pos))


def forward_hidden(z, params, tokens, seg=None, pos=None, variant=REFERENCE,
                   with_choices=False):
    """``tokens (T,) int32 -> (T, embed)``: the residual stream after the
    last layer of a row of sequences (one sequence from position 0 where
    ``seg``/``pos`` are not given), a jitted call a layer so that one
    layer's float32 copy lives at a time.  ``with_choices`` also returns
    each layer's choices."""
    zf = _frozen(z)
    if seg is None:
        seg = jnp.zeros(tokens.shape, jnp.int32)
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"][tokens].astype(variant.act)
    choices = []
    for l, w in enumerate(params["layers"]):
        x, chosen = _layer_jit(zf, variant.name, bool(z["rope_layout"][l]),
                               bool(z["window_layout"][l]), w, x, seg, pos)
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
