"""Plain reference of the served Granite-4.0-H-shaped decoder (family
``granite_hybrid_engine``; ``model_type`` ``granitemoehybrid`` with no
experts, the shape of granite-4.0-h-micro).

Written from the equations (ISSUE 46), not from the program.  ``x`` is the
residual stream, ``RMS`` an RMSNorm with gain, ``E`` the embedding; no
matrix has a bias.  ``x_0 = embedding_multiplier E[tok]``; every layer
``l``: ``x += residual_multiplier Mix_l(RMS1_l(x))``; ``x +=
residual_multiplier MLP_l(RMS2_l(x))``; ``logits = RMS_f(x) E^T /
logits_scaling``.

* ``MLP(h) = W_out (silu(g) * u)``, ``[g, u] = W_in h``
  (``shared_intermediate_size``: with no experts the shared MLP is the
  whole MLP);
* mamba (``layer_types[l] == "mamba"``; ``H`` heads of ``P``, ``N`` state
  values a channel, ``K`` taps): ``[z, xBC, dt] = W_in h``; ``xBC_t =
  silu(b_c + sum_j w_c[:, j] xBC_{t-K+1+j})``, zeros before the sequence;
  ``[x, B, C] = xBC``; ``dt_t = softplus(dt_t + dt_bias)``, ``A =
  -exp(A_log)``, a value a head; a head's state ``S (P, N)``: ``S_t =
  exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, zero before the sequence; ``y_t =
  S_t C_t + D x_t``, ``B`` and ``C`` the same for all heads; ``y = RMS(y *
  silu(z))`` over all ``H P`` channels, the gate before the norm; ``Mix =
  W_out y``;
* attention: ``[q, k, v] = W_qkv h``; query head ``i`` reads K/V head ``i
  // (heads / kv_heads)``; scores ``q . k * attention_multiplier``; causal;
  no positions anywhere; ``Mix = W_o ctx``.

Departures from the published implementation
(``modeling_granitemoehybrid.py``): none in the mathematics.  It clamps
``dt`` to ``time_step_limit``, whose default is ``(0, inf)``: no clamp; its
chunked scan is the same recurrence in another order of sums.

No cache, no kernels, no chunk: whole sequences, every layer at every
position in float32 at ``highest`` precision, the recurrence one position
after another.  The weights are made on the device from the seed in
bfloat16 and upcast a layer at a time.  A call takes one row of tokens in
which whole sequences lie end to end (:func:`pack`): ``seg`` names each
token's sequence and ``pos`` its position in it; attention stays within a
sequence, and the recurrent state and the convolution's inputs start from
zero at every sequence's first token.  The logits are read in blocks of
rows (:func:`gaps_below_best`), so ``row x vocabulary`` float32 never
stands whole.

It imports nothing of ``mxnet_tpu`` and takes nothing the program made.
The ways a forward pass may compute (:class:`Precision`: the reference, the
stated precision, and the two controls one step below it: weights through
fp8, the recurrent state kept in bfloat16) and :func:`pack` are the Phi-4
reference's, which names them for any model with a recurrent state; two
readings in the program's own precision are added below.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import sambay_engine as _phi4
from benchmark.reference.sambay_engine import (  # noqa: F401 — the families
    # lay sequences in rows, draw weights, step down in precision and read
    # gaps in one way
    REFERENCE, STATED, Precision, _gaps, _normal, _uniform, pack)

INIT_STD = 0.02

#: beside the Phi-4 reference's two controls (weights through fp8; the
#: recurrent state kept in bfloat16 inside a bfloat16 pass), two READINGS
#: in the precision the program itself computes in, which that pass cannot
#: tell apart from its own roundings: float32 activations and stream with
#: the backend's default matrix products (on the chip one bfloat16 pass of
#: the operands, float32 sums: what the program's casts do), and the same
#: with the recurrent state kept in bfloat16 between positions, which is
#: what a program that halved this cell's bytes by rounding the state would
#: serve.  No limit is set from them; PERF.md section 7 has what they read
SERVED_LIKE = Precision("float32-stream")
SERVED_LIKE_STATE = Precision("float32-stream-state-bfloat16",
                              state=jnp.bfloat16)
CONTROLS = _phi4.CONTROLS + (SERVED_LIKE, SERVED_LIKE_STATE)
PRECISIONS = dict(_phi4.PRECISIONS, **{p.name: p for p in (
    SERVED_LIKE, SERVED_LIKE_STATE)})
#: queries attended at once, and rows whose logits are read at once
QUERY_BLOCK = 512
LOGIT_BLOCK = 512


def sizes(config):
    """The shapes of a config file, from its published keys."""
    e = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    z = {
        "vocab": int(config["vocab_size"]), "embed": e, "heads": heads,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": e // heads,
        "layer_types": tuple(config["layer_types"]),
        "ffn": int(config["shared_intermediate_size"]),
        "m_heads": int(config["mamba_n_heads"]),
        "m_head_dim": int(config["mamba_d_head"]),
        "d_state": int(config["mamba_d_state"]),
        "d_conv": int(config["mamba_d_conv"]),
        "chunk": int(config["mamba_chunk_size"]),
        "embedding_multiplier": float(config["embedding_multiplier"]),
        "attention_multiplier": float(config["attention_multiplier"]),
        "residual_multiplier": float(config["residual_multiplier"]),
        "logits_scaling": float(config["logits_scaling"]),
        "eps": float(config["rms_norm_eps"]),
        "max_len": int(config["engine"]["max_len"]),
        # the CPU-sized stand-ins of the tests set it: at their widths
        # 0.02 leaves the mixers a thousandth of the stream
        "init_std": float(config.get("init_std", INIT_STD)),
    }
    if len(z["layer_types"]) != int(config["num_hidden_layers"]) \
            or z["m_heads"] * z["m_head_dim"] \
            != int(config["mamba_expand"]) * e \
            or int(config["mamba_n_groups"]) != 1 \
            or int(config["num_local_experts"]) != 0:
        raise ValueError("the equations above are of one group of B and C, "
                         "no experts, and mamba_expand x hidden_size = "
                         "mamba_n_heads x mamba_d_head channels")
    return z


def init_weights(config, seed, device):
    """The weights, drawn on ``device`` from ``seed`` (any whole number):
    normal(0, 0.02); the embedding normal(0, 0.02 / embedding_multiplier),
    so that the stream starts at 0.02 (the configuration's ``assumed`` says
    why); the convolution uniform within 1/sqrt(d_conv) and its bias
    normal(0, 0.02), so that one left out shows; ``A`` uniform 1 .. 16 a
    head, ``D = 1``, ``dt_bias`` the inverse softplus of a log-uniform
    0.001 .. 0.1; gains 1.  The projections into the residual stream are
    not scaled down by the depth: ``residual_multiplier`` is that scale.
    Matrices in the configuration's weight dtype, the rest float32."""
    z = sizes(config)
    dtype = jnp.dtype(config["precision"]["weights"])
    e, h, n = z["embed"], z["m_heads"], z["d_state"]
    d = h * z["m_head_dim"]
    conv = d + 2 * n
    wide, kv = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
    f32 = jnp.float32
    std = z["init_std"]
    with jax.default_device(device):
        root = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                                  seed // (2 ** 31))
        count = [0]

        def key():
            count[0] += 1
            return jax.random.fold_in(root, count[0])

        def nrm(*shape, std=std, dt=dtype):
            return _normal(key(), shape, std, dt)

        layers = []
        for kind in z["layer_types"]:
            p = {"norm1": jnp.ones((e,), f32), "norm2": jnp.ones((e,), f32),
                 "mlp_in": nrm(e, 2 * z["ffn"]),
                 "mlp_out": nrm(z["ffn"], e)}
            if kind == "mamba":
                bound = 1.0 / math.sqrt(z["d_conv"])
                step = jnp.exp(_uniform(key(), (h,), math.log(1e-3),
                                        math.log(1e-1)))
                p.update(
                    w_in=nrm(e, d + conv + h),
                    conv_w=_uniform(key(), (conv, z["d_conv"]), -bound,
                                    bound),
                    conv_b=nrm(conv, dt=f32),
                    dt_bias=jnp.log(jnp.expm1(step)),
                    A_log=jnp.log(_uniform(key(), (h,), 1.0, 16.0)),
                    D=jnp.ones((h,), f32), norm=jnp.ones((d,), f32),
                    w_out=nrm(d, e))
            else:
                p.update(w_qkv=nrm(e, wide + 2 * kv),
                         w_o=nrm(wide, e))
            layers.append(p)
        return {"embed": nrm(z["vocab"], e,
                             std=std / z["embedding_multiplier"]),
                "norm_f": jnp.ones((e,), f32),
                "layers": layers}


# -- the forward pass ----------------------------------------------------------
def _rms(z, x, g):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                               + z["eps"]) * g).astype(x.dtype)


def _mamba(z, pr, w, h, pos):
    """``Mix`` of a mamba layer over a row of sequences."""
    f32 = jnp.float32
    t = h.shape[0]
    heads, width, n = z["m_heads"], z["m_head_dim"], z["d_state"]
    d = heads * width
    gate, xbc, dt = jnp.split(pr.mm(h, w["w_in"]), [d, 2 * d + 2 * n],
                              axis=-1)
    # the convolution reaches back within its own sequence only
    conv = w["conv_b"] + sum(
        w["conv_w"][:, z["d_conv"] - 1 - back]
        * jnp.where((pos >= back)[:, None],
                    jnp.roll(xbc, back, axis=0), 0).astype(f32)
        for back in range(z["d_conv"]))
    xbc = jax.nn.silu(conv).astype(pr.act)
    xs, b, c = jnp.split(xbc.astype(f32), [d, d + n], axis=-1)
    xs = xs.reshape(t, heads, width)
    dt = jax.nn.softplus(dt.astype(f32) + w["dt_bias"])        # (T, heads)
    a = -jnp.exp(w["A_log"])                                   # (heads,)

    def token(s, at):
        dt_t, x_t, b_t, c_t, first = at
        s = jnp.where(first, 0.0, s.astype(f32))
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        s = s.astype(pr.state)
        return s, s.astype(f32) @ c_t

    _, y = jax.lax.scan(token, jnp.zeros((heads, width, n), pr.state),
                        (dt, xs, b, c, pos == 0))
    y = (y + w["D"][:, None] * xs).reshape(t, d)
    y = _rms(z, y * jax.nn.silu(gate.astype(f32)), w["norm"])
    return pr.mm(y.astype(pr.act), w["w_out"])


def _attention(z, pr, w, h, seg, pos):
    """``Mix`` of an attention layer: every token within its own sequence,
    a block of queries at a time, heads of ``head_dim`` laid singly."""
    f32 = jnp.float32
    t, hd = h.shape[0], z["head_dim"]
    wide, kv = z["heads"] * hd, z["kv_heads"] * hd
    q, k, v = jnp.split(pr.mm(h, w["w_qkv"]), [wide, wide + kv], axis=-1)
    q = q.reshape(t, z["kv_heads"], -1, hd)       # (T, K/V head, reader, d)
    k, v = (m.reshape(t, z["kv_heads"], hd) for m in (k, v))
    block = min(QUERY_BLOCK, t)

    def attend(args):
        qb, qseg, qpos = args
        mask = (seg[None, :] == qseg[:, None]) & (pos[None, :]
                                                  <= qpos[:, None])
        scores = jnp.einsum("qgjd,kgd->gjqk", qb, k,
                            preferred_element_type=pr.acc) \
            .astype(f32) * z["attention_multiplier"]
        att = jax.nn.softmax(jnp.where(mask[None, None], scores, -1e30), -1)
        return jnp.einsum("gjqk,kgd->qgjd", att.astype(pr.act), v,
                          preferred_element_type=pr.acc).astype(f32)

    ctx = jax.lax.map(attend, (q.reshape(t // block, block, *q.shape[1:]),
                               seg.reshape(t // block, block),
                               pos.reshape(t // block, block)))
    return pr.mm(ctx.reshape(t, wide).astype(pr.act), w["w_o"])


def layer(z, pr, kind, w, x, seg, pos):
    """One layer over a row of sequences ``x (T, embed)``."""
    h = _rms(z, x, w["norm1"])
    mix = _mamba(z, pr, w, h, pos) if kind == "mamba" \
        else _attention(z, pr, w, h, seg, pos)
    x = x + (z["residual_multiplier"] * mix).astype(x.dtype)
    gate, up = jnp.split(pr.mm(_rms(z, x, w["norm2"]), w["mlp_in"]), 2,
                         axis=-1)
    return x + (z["residual_multiplier"]
                * pr.mm(jax.nn.silu(gate) * up, w["mlp_out"])).astype(x.dtype)


def _frozen(z):
    return tuple(sorted(z.items()))


def _highest(pr, fn, *args):
    if pr is REFERENCE:
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return fn(*args)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer_jit(zf, pr_name, kind, w, x, seg, pos):
    pr = PRECISIONS[pr_name]
    return _highest(pr, layer, dict(zf), pr, kind, w, x, seg, pos)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _final_jit(zf, pr_name, g, x):
    z = dict(zf)
    return (_rms(z, x, g).astype(jnp.float32)
            / z["logits_scaling"]).astype(PRECISIONS[pr_name].act)


def forward_hidden(z, params, tokens, seg=None, pos=None,
                   precision=REFERENCE):
    """``tokens (T,) int32 -> (T, embed)``: what the head multiplies with
    ``E^T``, ``RMS_f(x) / logits_scaling`` of the residual stream after the
    last layer, of a row of sequences (one sequence from position 0 where
    ``seg``/``pos`` are not given); a jitted call a layer so that one
    layer's float32 copy lives at a time."""
    zf = _frozen(z)
    tokens = jnp.asarray(tokens)
    if seg is None:
        seg = jnp.zeros(tokens.shape, jnp.int32)
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)
    x = (z["embedding_multiplier"]
         * params["embed"][tokens].astype(jnp.float32)).astype(precision.act)
    for kind, w in zip(z["layer_types"], params["layers"]):
        x = _layer_jit(zf, precision.name, kind, w, x, seg, pos)
    return _final_jit(zf, precision.name, params["norm_f"], x)


@functools.partial(jax.jit, static_argnums=(0,))
def _logits_jit(pr_name, embed, h):
    pr = PRECISIONS[pr_name]
    return _highest(
        pr, lambda h: pr.mm(h, embed.T).astype(jnp.float32), h)


def forward_logits(z, params, tokens, seg=None, pos=None,
                   precision=REFERENCE):
    """``(T, vocab)`` float32 logits, whole: for the CPU-sized tests."""
    return _logits_jit(precision.name, params["embed"], forward_hidden(
        z, params, tokens, seg, pos, precision))


def _blocks(t):
    """``(start, stop)`` of the blocks of rows whose logits are read at
    once."""
    return [(i, min(i + LOGIT_BLOCK, t)) for i in range(0, t, LOGIT_BLOCK)]


def best_tokens(params, h, precision):
    """The token each row's logits put first, ``(T,) int32``, the logits
    read a block of rows at a time."""
    return jnp.concatenate([
        jnp.argmax(_logits_jit(precision.name, params["embed"], h[i:j]), -1)
        for i, j in _blocks(h.shape[0])]).astype(jnp.int32)


def gaps_below_best(params, h, chosen, precision=REFERENCE):
    """By how much the logit of ``chosen[k, i]`` lies below the largest
    logit of row ``i``, ``(K, T)``: 0 where the chosen token is the
    reference's own.  The logits are read a block of rows at a time."""
    return jnp.concatenate([
        _gaps(_logits_jit(precision.name, params["embed"], h[i:j]),
              chosen[:, i:j])
        for i, j in _blocks(h.shape[0])], axis=-1)
