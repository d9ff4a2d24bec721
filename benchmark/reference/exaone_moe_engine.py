"""Plain reference of the served K-EXAONE-shaped decoder, one chip's share
(family ``exaone_moe_engine``).

Written from the equations (ISSUE 27, section 1), not from the program.
``x`` is the residual stream, ``RMS(x, g) = x g / sqrt(mean(x^2) + eps)``:

* block: ``x += Attn_l(RMS(x, g1))``, ``x += MLP_l(RMS(x, g2))``;
* attention: ``q = h Wq`` (``heads`` of ``head_dim``), ``k = h Wk``,
  ``v = h Wv`` (``kv_heads``), no biases; ``q``, ``k`` RMS-normed per head;
  a ``sliding_attention`` layer rotates them (RoPE, half-split pairs) and a
  query at ``p`` reads ``p - window + 1 .. p``; a ``full_attention`` layer
  rotates nothing and reads ``0 .. p``.  Query head ``i`` reads K/V head
  ``i // (heads // kv_heads)``; scores ``q.k / sqrt(head_dim)``, softmax;
* dense MLP: ``(silu(h Wg) * (h Wu)) Wd``;
* sparse MLP: ``s = sigmoid(h Wr)``; ``chosen = top_k(s + b)``;
  ``w_e = s_e / (sum over chosen of s + 1e-20) * routed_scaling_factor``;
  ``y = sum over chosen AND held e of w_e E_e(h) + E_shared(h)``.  The
  router, the choice and the weights are over all ``num_experts``; the sum
  over the ``experts_held`` experts from ``first_expert`` that this chip
  holds.  What the absent experts would add is left out;
* head: ``RMS(x, gf) Wh`` over the vocabulary slice.

No cache, no kernels: whole sequences, every layer in float32 at ``highest``
precision.  The weights are made on the device from the seed in bfloat16
(what the configuration states) and upcast a layer at a time, so the
float32 pass fits beside them; queries go through attention in blocks for
the same reason.  A call takes one row of tokens in which whole sequences
lie end to end (:func:`pack`): ``seg`` names each token's sequence and
``pos`` its position in it, and a token attends within its own sequence
only, which is the mathematics of one sequence a call at one compiled
shape for all of them (a shape a length cost more time in compiles than
the check has).

It imports nothing of ``mxnet_tpu`` and takes nothing the program made.
"""

import functools
import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02
#: the selection bias is drawn small, so that it decides near-ties only
BIAS_STD = 0.01
#: queries attended at once in the reference
QUERY_BLOCK = 512


def sizes(config):
    """The shapes of a config file, as a dict of whole numbers and the two
    per-layer lists cut to the layers held."""
    n = int(config["num_hidden_layers"])
    return {
        "vocab": int(config["vocab_size"]),
        "embed": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "layers": n,
        "layer_types": tuple(config["layer_types"][:n]),
        "mlp_types": tuple(config["mlp_layer_types"][:n]),
        "dense_ffn": int(config["intermediate_size"]),
        "expert_ffn": int(config["moe_intermediate_size"]),
        "num_experts": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "first_expert": int(config["first_expert"]),
        "experts_held": int(config["experts_held"]),
        "window": int(config["sliding_window"]),
        "rope_theta": float(config["rope_parameters"]["rope_theta"]),
        "routed_scale": float(config["routed_scaling_factor"]),
        "eps": float(config["rms_norm_eps"]),
        "max_len": int(config["engine"]["max_len"]),
    }


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_weights(config, seed, device):
    """The weights, drawn on ``device`` from ``seed`` (any whole number):
    normal(0, 0.02), the projections into the residual stream scaled by
    1/sqrt(2 layers), gains 1; matrices in the configuration's weight
    dtype, the router's matrix and selection bias in float32."""
    z = sizes(config)
    dtype = jnp.dtype(config["precision"]["weights"])
    e, hd, f32 = z["embed"], z["head_dim"], jnp.float32
    resid = INIT_STD / math.sqrt(2.0 * z["layers"])
    with jax.default_device(device):
        root = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                                  seed // (2 ** 31))
        count = [0]

        def nrm(*shape, std=INIT_STD, dt=dtype):
            count[0] += 1
            return _normal(jax.random.fold_in(root, count[0]), shape, std,
                           dt)

        def swiglu(width, *lead):
            return {"gate": nrm(*lead, e, width), "up": nrm(*lead, e, width),
                    "down": nrm(*lead, width, e, std=resid)}

        layers = []
        for l in range(z["layers"]):
            p = {"ln1": jnp.ones((e,), f32), "ln2": jnp.ones((e,), f32),
                 "q_norm": jnp.ones((hd,), f32),
                 "k_norm": jnp.ones((hd,), f32),
                 "wq": nrm(e, z["heads"] * hd),
                 "wk": nrm(e, z["kv_heads"] * hd),
                 "wv": nrm(e, z["kv_heads"] * hd),
                 "wo": nrm(z["heads"] * hd, e, std=resid)}
            if z["mlp_types"][l] == "sparse":
                p["moe"] = dict(
                    swiglu(z["expert_ffn"], z["experts_held"]),
                    router=nrm(e, z["num_experts"], dt=f32),
                    bias=nrm(z["num_experts"], std=BIAS_STD, dt=f32),
                    shared=swiglu(z["expert_ffn"]))
            else:
                p["mlp"] = swiglu(z["dense_ffn"])
            layers.append(p)
        return {"embed": nrm(z["vocab"], e), "head": nrm(e, z["vocab"]),
                "ln_f": jnp.ones((e,), f32), "layers": layers}


# -- the forward pass ----------------------------------------------------------
class Precision:
    """How a forward pass computes: the dtype the weights are read in, the
    dtype activations are held in, and the dtype products accumulate in."""

    def __init__(self, name, weights=None, act=jnp.float32,
                 acc=jnp.float32):
        self.name, self.weights, self.act, self.acc = name, weights, act, \
            acc

    def w(self, a):
        return a if self.weights is None else self.weights(a)

    def mm(self, a, w):
        w = self.w(w).astype(self.act)
        return jnp.dot(a.astype(self.act), w,
                       preferred_element_type=self.acc).astype(self.act)


def _to_fp8(w):
    """Round a matrix (or a stack of them) to float8 e4m3 and back, each
    matrix with a scale that puts its largest entry at e4m3's largest."""
    axes = tuple(range(w.ndim - 2, w.ndim)) if w.ndim >= 2 else None
    scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axes,
                    keepdims=True) / 448.0
    return ((w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            * scale)


#: the reference itself; a reading in the configuration's own precision
#: (bfloat16 weights and activations, float32 accumulation: what a plain
#: forward pass gives at the program's precision); and the control, the
#: nearest precision below it (weights through fp8, activations in
#: bfloat16, products accumulated in bfloat16)
REFERENCE = Precision("float32")
STATED = Precision("bfloat16", act=jnp.bfloat16)
CONTROL = Precision("fp8", weights=_to_fp8, act=jnp.bfloat16,
                    acc=jnp.bfloat16)
PRECISIONS = {p.name: p for p in (REFERENCE, STATED, CONTROL)}


def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
            * g).astype(x.dtype)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half].astype(jnp.float32), \
        x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _swiglu(pr, h, w):
    return pr.mm(jax.nn.silu(pr.mm(h, w["gate"])) * pr.mm(h, w["up"]),
                 w["down"])


def _attention(z, pr, window, q, k, v, seg, pos):
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
                            preferred_element_type=pr.acc) \
            .astype(jnp.float32) / math.sqrt(z["head_dim"])
        att = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), -1)
        return jnp.einsum("hqk,khd->qhd", att.astype(pr.act), v,
                          preferred_element_type=pr.acc).astype(pr.act)

    # the same block, one after another (``t`` is a multiple of ``block``)
    out = jax.lax.map(attend, (q.reshape(t // block, block, *q.shape[1:]),
                               seg.reshape(t // block, block),
                               pos.reshape(t // block, block)))
    return out.reshape(q.shape)


def route(z, h, moe):
    """(chosen (T, top_k) over all experts, their weights), in float32."""
    s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32), moe["router"]))
    _, chosen = jax.lax.top_k(s + moe["bias"], z["top_k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * z["routed_scale"]


def layer(z, pr, window, w, x, seg, pos):
    """One block over a row of sequences ``x (T, embed)``, ``window``
    saying which kind of attention it has; also the router's choices in a
    sparse layer (else None)."""
    t = x.shape[0]
    h = _rms(x, w["ln1"], z["eps"])
    q = pr.mm(h, w["wq"]).reshape(t, z["heads"], z["head_dim"])
    k = pr.mm(h, w["wk"]).reshape(t, z["kv_heads"], z["head_dim"])
    v = pr.mm(h, w["wv"]).reshape(t, z["kv_heads"], z["head_dim"])
    q, k = _rms(q, w["q_norm"], z["eps"]), _rms(k, w["k_norm"], z["eps"])
    if window:
        q, k = _rope(q, pos, z["rope_theta"]), _rope(k, pos, z["rope_theta"])
    ctx = _attention(z, pr, window, q, k, v, seg, pos)
    x = x + pr.mm(ctx.reshape(t, -1), w["wo"])
    h = _rms(x, w["ln2"], z["eps"])
    if "mlp" in w:
        return x + _swiglu(pr, h, w["mlp"]), None
    moe = w["moe"]
    chosen, weight = route(z, h, moe)

    def add_expert(y, held):
        # one held expert after another, each a plain SwiGLU over all
        # rows, weighted by what the rows that chose it gave it
        e, expert = held
        mine = chosen == z["first_expert"] + e
        w_e = jnp.where(mine, weight, 0.0).sum(-1, keepdims=True)
        return y + w_e.astype(y.dtype) * _swiglu(pr, h, expert), None

    y, _ = jax.lax.scan(
        add_expert, _swiglu(pr, h, moe["shared"]),
        (jnp.arange(z["experts_held"]),
         {n: moe[n] for n in ("gate", "up", "down")}))
    return x + y, chosen


def _frozen(z):
    return tuple(sorted(z.items()))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer_jit(zf, pr_name, window, w, x, seg, pos):
    pr = PRECISIONS[pr_name]
    z = dict(zf)
    if pr is REFERENCE:
        with jax.default_matmul_precision("highest"):
            return layer(z, pr, window, w, x, seg, pos)
    return layer(z, pr, window, w, x, seg, pos)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head_jit(zf, pr_name, params, x):
    pr = PRECISIONS[pr_name]
    z = dict(zf)
    h = _rms(x, params["ln_f"], z["eps"])
    if pr is REFERENCE:
        with jax.default_matmul_precision("highest"):
            return pr.mm(h, params["head"]).astype(jnp.float32)
    return pr.mm(h, params["head"]).astype(jnp.float32)


def pack(lengths, row):
    """Lay sequences of these lengths end to end in rows of ``row`` tokens,
    the longest first, each into the first row that has room: a list of
    rows, each a list of (index of the sequence, where it starts)."""
    rows, room = [], []
    for i in sorted(range(len(lengths)), key=lambda i: -lengths[i]):
        if lengths[i] > row:
            raise ValueError("a sequence of %d tokens in rows of %d"
                             % (lengths[i], row))
        for r, free in enumerate(room):
            if lengths[i] <= free:
                break
        else:
            rows.append([])
            room.append(row)
            r = len(rows) - 1
        rows[r].append((i, row - room[r]))
        room[r] -= lengths[i]
    return rows


def forward_logits(z, params, tokens, seg=None, pos=None,
                   precision=REFERENCE, with_choices=False):
    """``tokens (T,) int32 -> (T, vocab)`` float32 logits of a row of
    sequences (one sequence from position 0 where ``seg``/``pos`` are not
    given), a jitted call a layer so that one layer's float32 copy lives
    at a time.  ``with_choices`` also returns each sparse layer's
    choices."""
    zf = _frozen(z)
    if seg is None:
        seg = jnp.zeros(tokens.shape, jnp.int32)
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"][tokens].astype(precision.act)
    choices = []
    for l, w in enumerate(params["layers"]):
        x, chosen = _layer_jit(
            zf, precision.name,
            z["layer_types"][l] == "sliding_attention", w, x, seg, pos)
        if chosen is not None:
            choices.append(chosen)
    logits = _head_jit(zf, precision.name,
                       {"ln_f": params["ln_f"], "head": params["head"]}, x)
    return (logits, choices) if with_choices else logits


@jax.jit
def gaps_below_best(logits, chosen):
    """By how much the logit of ``chosen[i]`` lies below the largest logit
    of row ``i``: 0 where the chosen token is the reference's own."""
    took = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
    return jnp.max(logits, axis=-1) - took
