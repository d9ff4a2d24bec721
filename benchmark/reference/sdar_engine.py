"""Plain reference of the served SDAR-shaped decoder (family
``sdar_engine``): a Qwen3-MoE layer under a mask that is causal by blocks,
and the replay of generation by diffusion over blocks.

Written from the equations (ISSUE 42, Tentpole part 1), not from the
program.  ``x`` is the residual stream, ``RMS(x, g) = x g / sqrt(mean(x^2)
+ eps)``, ``B`` the block length; layer over a row at position ``p``:

* attention: ``h = RMS(x, g1)``; ``q = h Wq`` (``heads`` of ``head_dim``),
  ``k = h Wk``, ``v = h Wv`` (``kv_heads``), no biases; ``q`` and ``k`` each
  head through ``RMS(., gq | gk)`` over its ``head_dim`` values (one weight
  for all heads), then rotated at ``p`` (RoPE over the whole head,
  half-split pairs); scores ``q.k / sqrt(head_dim)`` under the mask ``M``:
  row ``i`` sees column ``j`` iff ``j // B <= i // B`` (causal between
  blocks, both ways inside one), softmax; query head ``i`` reads K/V head
  ``i // (heads // kv_heads)``; ``x = x + ctx Wo``;
* experts: ``h2 = RMS(x, g2)``; ``s = h2 Wr`` (float32); the best ``top_k``;
  weights the softmax over those; ``x = x + sum over chosen AND held e of
  w_e Wd_e (silu(h2 Wg_e) * (h2 Wu_e))``.  No shared expert, every layer
  sparse; the sum over the ``experts_held`` from ``first_expert`` (all of
  them in the benchmark's configuration);
* head: ``RMS(x, gf) Wh``, untied; the logits at a position are of that
  position's OWN token.

**The replay** (:func:`replay`).  Generation fixes the positions of a block
over several passes; pass ``t`` runs the block as it stands (the positions
fixed before ``t`` hold their tokens, the others the mask token) against
the FINAL K and V of every earlier block and against itself.  So the
reference runs the final transcript once under ``M`` and keeps every
layer's K and V, and then, for each pass ``t``, ALL blocks' states before
pass ``t`` in one more forward whose rows read the final K and V of
earlier blocks and their own block's state.  Of a pass's logits it reads
(:func:`read_rows`), a row, the best logit, the log of the softmax's sum
and the logit of a given token: a served token's gap below the best at the
pass that fixed it, and the log-confidence ``best - logsumexp`` that
decides which position a pass fixes.

No cache, no kernels: whole sequences, every layer in float32 at ``highest``
precision.  The weights are made on the device from the seed in bfloat16
(what the configuration states) and widened a layer at a time, so the
float32 pass fits beside them; queries go through attention in blocks and
the logits are read in blocks of rows for the same reason.  A call takes
one row of tokens in which whole sequences lie end to end
(``exaone_moe_engine.pack``; every sequence is whole blocks, so each starts
at a block's first position): ``seg`` names each token's sequence and
``pos`` its position in it, and a token attends within its own sequence.

It imports nothing of ``mxnet_tpu`` and takes nothing the program made; what
is no model's own (drawing a matrix from a key, fp8 rounding, the norm, the
rotation, :func:`pack`) it shares with the benchmark's other references.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.exaone_moe_engine import (  # noqa: F401 — pack is the adapter's
    _frozen, _normal, _rms, _rope, _to_fp8, pack)

INIT_STD = 0.02
#: queries attended at once, and rows whose logits are read at once
QUERY_BLOCK = 256
LOGIT_BLOCK = 512


def sizes(config):
    """The shapes of a config file and the sampler's settings, as a dict."""
    a = config["assumed"]
    if not config["norm_topk_prob"] or config["decoder_sparse_step"] != 1 \
            or config["mlp_only_layers"]:
        raise ValueError("the reference has renormed top-k weights and "
                         "every layer sparse alone")
    return {
        "vocab": int(config["vocab_size"]),
        "embed": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "layers": int(config["num_hidden_layers"]),
        "expert_ffn": int(config["moe_intermediate_size"]),
        "num_experts": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "first_expert": int(config["first_expert"]),
        "experts_held": int(config["experts_held"]),
        "rope_theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "max_len": int(config["engine"]["max_len"]),
        "block": int(a["block_length"]["value"]),
        "denoise_steps": int(a["denoising_steps"]["value"]),
        "remasking": str(a["remasking"]["value"]),
        "threshold": float(a["confidence_threshold"]["value"]),
        "mask_id": int(a["mask_token_id"]["value"]),
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
            "q_norm": jnp.ones((hd,), f32), "k_norm": jnp.ones((hd,), f32),
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
def _through_fp8(a):
    """A K or V row as an fp8 cache would hold it: e4m3 and back, no scale
    (a normed, rotated row's values lie well inside e4m3's range)."""
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


class Variant:
    """How a forward pass computes: the dtype the weights are read in, the
    dtype activations are held in, the dtype products accumulate in, and
    what K and V rows go through before they are attended over (what a
    cache of another precision would hold)."""

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

    def keeps(self, a):
        return a if self.held is None else self.held(a)


#: the reference itself; a reading in the configuration's own precision
#: (bfloat16 weights and activations, float32 accumulation); and the two
#: controls, each the nearest precision below what the configuration
#: states for one thing: the weights through fp8 with bfloat16
#: accumulation, and the K/V cache kept in fp8 with all else as stated
REFERENCE = Variant("float32")
STATED = Variant("bfloat16", act=jnp.bfloat16)
CONTROL_FP8 = Variant("fp8", weights=_to_fp8, act=jnp.bfloat16,
                      acc=jnp.bfloat16)
CONTROL_CACHE_FP8 = Variant("cache-fp8", act=jnp.bfloat16,
                            held=_through_fp8)
VARIANTS = {v.name: v for v in (REFERENCE, STATED, CONTROL_FP8,
                                CONTROL_CACHE_FP8)}
CONTROLS = (CONTROL_FP8, CONTROL_CACHE_FP8)


def _attention(z, va, q, k, v, seg, pos, final=None):
    """Softmax attention under ``M`` of every token within its own
    sequence, a block of queries at a time.  With ``final`` (the K and V of
    the final transcript, row for row): a row reads the FINAL rows of the
    blocks before its own and its own block's rows of ``k``/``v``: a pass
    over every block's state at once."""
    t = q.shape[0]
    group = z["heads"] // z["kv_heads"]
    b = z["block"]
    if final is not None:
        k, v = (jnp.concatenate([f, own]) for f, own in zip(final, (k, v)))
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    rows = min(QUERY_BLOCK, t)

    def attend(args):
        qb, qseg, qpos = args
        same = seg[None, :] == qseg[:, None]
        before = pos[None, :] // b < qpos[:, None] // b
        inside = pos[None, :] // b == qpos[:, None] // b
        mask = same & (before | inside) if final is None else \
            jnp.concatenate([same & before, same & inside], axis=1)
        scores = jnp.einsum("qhd,khd->hqk", qb, k,
                            preferred_element_type=va.acc) \
            .astype(jnp.float32) / math.sqrt(z["head_dim"])
        att = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), -1)
        return jnp.einsum("hqk,khd->qhd", att.astype(va.act), v,
                          preferred_element_type=va.acc).astype(va.act)

    out = jax.lax.map(attend, (q.reshape(t // rows, rows, *q.shape[1:]),
                               seg.reshape(t // rows, rows),
                               pos.reshape(t // rows, rows)))
    return out.reshape(q.shape)


def route(z, h, moe):
    """(chosen (T, top_k) over all experts, their weights), in float32."""
    picked, chosen = jax.lax.top_k(
        jnp.dot(h.astype(jnp.float32), moe["router"]), z["top_k"])
    return chosen, jax.nn.softmax(picked, axis=-1)


def layer(z, va, w, x, seg, pos, final=None):
    """One block over a row of sequences ``x (T, embed)``.  Returns the
    stream, the layer's K and V as a cache would hold them, and the
    router's choices."""
    t = x.shape[0]
    h = _rms(x, w["ln1"], z["eps"])
    q = va.mm(h, w["wq"]).reshape(t, z["heads"], z["head_dim"])
    k = va.mm(h, w["wk"]).reshape(t, z["kv_heads"], z["head_dim"])
    v = va.mm(h, w["wv"]).reshape(t, z["kv_heads"], z["head_dim"])
    q = _rope(_rms(q, w["q_norm"], z["eps"]), pos, z["rope_theta"])
    k = _rope(_rms(k, w["k_norm"], z["eps"]), pos, z["rope_theta"])
    k, v = va.keeps(k), va.keeps(v)
    ctx = _attention(z, va, q, k, v, seg, pos, final)
    x = x + va.mm(ctx.reshape(t, -1), w["wo"])
    h = _rms(x, w["ln2"], z["eps"])
    moe = w["moe"]
    chosen, weight = route(z, h, moe)

    def add_expert(y, held):
        # one held expert after another, each a plain SwiGLU over all rows,
        # weighted by what the rows that chose it gave it
        e, expert = held
        mine = chosen == z["first_expert"] + e
        w_e = jnp.where(mine, weight, 0.0).sum(-1, keepdims=True)
        inner = jax.nn.silu(va.mm(h, expert["gate"])) \
            * va.mm(h, expert["up"])
        return y + w_e.astype(y.dtype) * va.mm(inner, expert["down"]), None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (jnp.arange(z["experts_held"]),
         {n: moe[n] for n in ("gate", "up", "down")}))
    return x + y, (k, v), chosen


def _highest(va, fn):
    if va is REFERENCE:
        with jax.default_matmul_precision("highest"):
            return fn()
    return fn()


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_jit(zf, va_name, w, x, seg, pos, final):
    va = VARIANTS[va_name]
    return _highest(va, lambda: layer(dict(zf), va, w, x, seg, pos, final))


def forward_hidden(z, params, tokens, seg=None, pos=None, variant=REFERENCE,
                   finals=None, with_kv=False):
    """``tokens (T,) int32 -> (T, embed)``: the residual stream after the
    last layer of a row of sequences under ``M`` (one sequence from
    position 0 where ``seg``/``pos`` are not given), a jitted call a layer
    so that one layer's float32 copy lives at a time.  ``finals``: each
    layer's K and V of the final transcript, which the rows then read for
    the blocks before their own (a pass; :func:`_attention`).  ``with_kv``
    also returns each layer's K and V."""
    zf = _frozen(z)
    if seg is None:
        seg = jnp.zeros(tokens.shape, jnp.int32)
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"][tokens].astype(variant.act)
    kept = []
    for l, w in enumerate(params["layers"]):
        x, kv, _chosen = _layer_jit(zf, variant.name, w, x, seg, pos,
                                    None if finals is None else finals[l])
        kept.append(kv)
    return (x, kept) if with_kv else x


@functools.partial(jax.jit, static_argnums=(0, 1))
def _logits_jit(va_name, eps, head, x):
    va = VARIANTS[va_name]
    return _highest(va, lambda: va.mm(_rms(x, head["ln_f"], eps),
                                      head["head"]).astype(jnp.float32))


def _head(params):
    return {k: params[k] for k in ("ln_f", "head")}


def forward_logits(z, params, tokens, seg=None, pos=None,
                   variant=REFERENCE, finals=None):
    """``(T, vocab)`` float32 logits, whole: for the CPU-sized tests."""
    x = forward_hidden(z, params, tokens, seg, pos, variant, finals)
    return _logits_jit(variant.name, z["eps"], _head(params), x)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _read_jit(va_name, eps, head, x, tokens):
    logits = _logits_jit.__wrapped__(va_name, eps, head, x)
    took = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return (jnp.max(logits, -1), jnp.argmax(logits, -1).astype(jnp.int32),
            jax.nn.logsumexp(logits, -1), took)


def read_rows(z, params, x, tokens, variant=REFERENCE):
    """Of each row's logits ``(best, its token, logsumexp, the logit of
    tokens[i])``, four ``(T,)`` arrays, the logits read a block of rows at
    a time."""
    head = _head(params)
    parts = [_read_jit(variant.name, z["eps"], head, x[i:j], tokens[i:j])
             for i in range(0, x.shape[0], LOGIT_BLOCK)
             for j in (min(i + LOGIT_BLOCK, x.shape[0]),)]
    return tuple(jnp.concatenate(p) for p in zip(*parts))


def replay(z, params, tokens, fixed_at, seg, pos, variant=REFERENCE):
    """What every pass computed, for a row of whole-block sequences:
    ``tokens (T,)`` the final transcript, ``fixed_at (T,)`` the pass that
    fixed each position (-1: the prompt's; padding -1 too).  Returns the
    residual stream after the last layer of each pass, ``denoise_steps``
    arrays ``(T, embed)``: pass ``t`` runs every block's state before it
    (positions with ``fixed_at < t`` hold their tokens, the rest the mask
    token) against the final K and V of the blocks before
    (:func:`read_rows` reads a pass's logits)."""
    _x, finals = forward_hidden(z, params, tokens, seg, pos, variant,
                                with_kv=True)
    return [forward_hidden(
        z, params, jnp.where(fixed_at < t, tokens, z["mask_id"]), seg, pos,
        variant, finals) for t in range(z["denoise_steps"])]
