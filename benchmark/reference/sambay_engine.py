"""Plain reference of the served SambaY-shaped decoder (family
``sambay_engine``; the shape of Phi-4-mini-flash-reasoning,
arXiv:2507.06607).

Written from the equations (ISSUE 31, section 1), not from the program.
``x`` is the residual stream, ``LN`` a LayerNorm with gain and bias.  Every
layer ``l`` of ``n``: ``x += Mix_l(LN1_l(x)); x += MLP_l(LN2_l(x))``; then
``logits = LN_f(x) E^T``, ``E`` the embedding.  No positions are embedded.

* ``MLP(h) = W2 (up * silu(gate))``, ``[gate, up] = W1 h``;
* mamba (even ``l <= n/2``): ``[u, z] = W_in h``; ``u_t = silu(b_c + sum_j
  w_c[:, j] u_{t-3+j})``, zeros before the sequence; ``[d, B_t, C_t] = W_x
  u_t``; ``dt_t = softplus(W_dt d + b_dt)``; ``A = -exp(A_log)``; ``s_t =
  exp(dt_t A) s_{t-1} + (dt_t u_t) B_t^T``, zero before the sequence; ``y_t
  = s_t C_t + D u_t``; ``Mix = W_out (y_t silu(z_t))``.  Layer ``n/2``'s
  ``y_t`` is the memory ``m_t``;
* gated memory unit (even ``l > n/2``): ``Mix = W_out (m_t silu(W_in h))``;
* differential attention (odd ``l``): ``[q, k, v] = W_qkv h + b``, heads of
  ``head_dim``; query pair ``p`` is heads ``(2p, 2p+1) = (q1, q2)``, K/V
  pair ``g`` is ``(k1, k2) = (k_2g, k_2g+1)`` and ``V_g = [v_2g, v_2g+1]``;
  pair ``p`` reads K/V pair ``p // (heads / kv_heads)``.  ``a_i =
  softmax(q_i k_i^T / sqrt(head_dim)) V``; ``lam = exp(lq1.lk1) -
  exp(lq2.lk2) + lam0_l``, ``lam0_l = 0.8 - 0.6 exp(-0.3 l)``; ``ctx_p = (1
  - lam0_l) RMSNorm(a_1 - lam a_2)``; ``Mix = W_o [ctx_0 ..] + b_o``.  Odd
  ``l < n/2 + 1`` read ``p - window + 1 .. p``; ``l = n/2 + 1`` reads ``0
  .. p``; odd ``l`` after it have ``W_q``/``W_o`` only and read layer ``n/2
  + 1``'s K and V.

No cache, no kernels: whole sequences, every layer at every position in
float32 at ``highest`` precision, the recurrence a plain loop over
positions.  The weights are made on the device from the seed in bfloat16
and upcast a layer at a time.  A call takes one row of tokens in which
whole sequences lie end to end (:func:`pack`): ``seg`` names each token's
sequence and ``pos`` its position in it; attention stays within a sequence,
and the recurrent state and the convolution's inputs start from zero at
every sequence's first token.  The logits are read in blocks of rows
(:func:`gaps_below_best`), so ``row x vocabulary`` float32 never stands whole.

It imports nothing of ``mxnet_tpu`` and takes nothing the program made
(:func:`pack` is the K-EXAONE reference's).
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.exaone_moe_engine import pack  # noqa: F401 — the
# families lay sequences end to end in rows in one way

INIT_STD = 0.02
LAMBDA_STD = 0.1
#: queries attended at once, and rows whose logits are read at once
QUERY_BLOCK = 512
LOGIT_BLOCK = 512
EPS = 1e-5


def sizes(config):
    """The shapes of a config file: published keys, then the assumed."""
    a = config["assumed"]
    e = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    return {
        "vocab": int(config["vocab_size"]), "embed": e, "heads": heads,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": e // heads,
        "layers": int(config["num_hidden_layers"]),
        "ffn": int(config["intermediate_size"]),
        "mb_per_layer": int(config["mb_per_layer"]),
        "window": int(config["sliding_window"]),
        "d_inner": int(a["expand"]) * e, "d_state": int(a["d_state"]),
        "d_conv": int(a["d_conv"]), "dt_rank": int(a["dt_rank"]),
        "max_len": int(config["engine"]["max_len"]),
        # the CPU-sized stand-ins of the tests set it: at their widths
        # 0.02 leaves the recurrence a millionth of the stream
        "init_std": float(config.get("init_std", INIT_STD)),
    }


def layer_kinds(z):
    """"mamba" | "window" | "full" | "gmu" | "cross" for every layer."""
    shared = z["layers"] // 2 + 1
    kinds = []
    for l in range(z["layers"]):
        recurrent = l % z["mb_per_layer"] == 0
        if l == shared:
            kinds.append("full")
        elif l < shared:
            kinds.append("mamba" if recurrent else "window")
        else:
            kinds.append("gmu" if recurrent else "cross")
    return kinds


def lam0(l):
    return 0.8 - 0.6 * math.exp(-0.3 * l)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi)


def init_weights(config, seed, device):
    """The weights, drawn on ``device`` from ``seed`` (any whole number):
    normal(0, 0.02), the projections into the residual stream scaled by
    1/sqrt(2 layers); the convolution uniform within 1/sqrt(d_conv);
    ``A_log = log(1 .. d_state)``, ``D = 1``, ``b_dt`` the inverse softplus
    of a log-uniform 0.001 .. 0.1; ``lq*``/``lk*`` normal(0, 0.1); gains 1;
    every other bias normal(0, 0.02), so that one left out shows.  Matrices
    in the configuration's weight dtype, the rest float32."""
    z = sizes(config)
    dtype = jnp.dtype(config["precision"]["weights"])
    e, hd, d, n = z["embed"], z["head_dim"], z["d_inner"], z["d_state"]
    f32 = jnp.float32
    std = z["init_std"]
    resid = std / math.sqrt(2.0 * z["layers"])
    with jax.default_device(device):
        root = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                                  seed // (2 ** 31))
        count = [0]

        def key():
            count[0] += 1
            return jax.random.fold_in(root, count[0])

        def nrm(*shape, std=std, dt=dtype):
            return _normal(key(), shape, std, dt)

        def bias(*shape):
            return nrm(*shape, dt=f32)

        layers = []
        for kind in layer_kinds(z):
            p = {"ln1_g": jnp.ones((e,), f32), "ln1_b": bias(e),
                 "ln2_g": jnp.ones((e,), f32), "ln2_b": bias(e),
                 "w1": nrm(e, 2 * z["ffn"]),
                 "w2": nrm(z["ffn"], e, std=resid)}
            if kind == "mamba":
                bound = 1.0 / math.sqrt(z["d_conv"])
                step = jnp.exp(_uniform(key(), (d,), math.log(1e-3),
                                        math.log(1e-1)))
                p.update(
                    w_in=nrm(e, 2 * d),
                    conv_w=_uniform(key(), (d, z["d_conv"]), -bound, bound),
                    conv_b=bias(d),
                    w_x=nrm(d, z["dt_rank"] + 2 * n),
                    w_dt=nrm(z["dt_rank"], d),
                    b_dt=jnp.log(jnp.expm1(step)),
                    A_log=jnp.log(jnp.broadcast_to(
                        jnp.arange(1, n + 1, dtype=f32), (d, n))),
                    D=jnp.ones((d,), f32), w_out=nrm(d, e, std=resid))
            elif kind == "gmu":
                p.update(w_in=nrm(e, d), w_out=nrm(d, e, std=resid))
            else:
                wide = z["heads"] * hd
                if kind == "cross":
                    p.update(w_q=nrm(e, wide), b_q=bias(wide))
                else:
                    both = wide + 2 * z["kv_heads"] * hd
                    p.update(w_qkv=nrm(e, both), b_qkv=bias(both))
                p.update(w_o=nrm(wide, e, std=resid), b_o=bias(e),
                         subln=jnp.ones((2 * hd,), f32),
                         **{name: nrm(hd, std=LAMBDA_STD, dt=f32)
                            for name in ("lq1", "lk1", "lq2", "lk2")})
            layers.append(p)
        return {"embed": nrm(z["vocab"], e), "ln_f_g": jnp.ones((e,), f32),
                "ln_f_b": bias(e), "layers": layers}


# -- the forward pass ----------------------------------------------------------
class Precision:
    """How a forward pass computes: the dtype the weights are read in, the
    dtype activations are held in, the dtype products accumulate in, and
    the dtype the recurrent state is kept in between positions."""

    def __init__(self, name, weights=None, act=jnp.float32,
                 acc=jnp.float32, state=jnp.float32):
        self.name, self.weights, self.act, self.acc, self.state = \
            name, weights, act, acc, state

    def mm(self, a, w):
        if self.weights is not None:
            w = self.weights(w)
        return jnp.dot(a.astype(self.act), w.astype(self.act),
                       preferred_element_type=self.acc).astype(self.act)


def _to_fp8(w):
    """Round a matrix to float8 e4m3 and back, with a scale that puts its
    largest entry at e4m3's largest."""
    scale = jnp.max(jnp.abs(w.astype(jnp.float32))) / 448.0
    return ((w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            * scale)


#: the reference itself; a reading in the configuration's own precision
#: (bfloat16 weights and activations, float32 accumulation and state); and
#: the two controls, each one step below it: the weights through fp8 with
#: products accumulated in bfloat16, and the recurrent state kept in
#: bfloat16 between positions
REFERENCE = Precision("float32")
STATED = Precision("bfloat16", act=jnp.bfloat16)
CONTROL_FP8 = Precision("fp8", weights=_to_fp8, act=jnp.bfloat16,
                        acc=jnp.bfloat16)
CONTROL_STATE = Precision("state-bfloat16", act=jnp.bfloat16,
                          state=jnp.bfloat16)
PRECISIONS = {p.name: p for p in (REFERENCE, STATED, CONTROL_FP8,
                                  CONTROL_STATE)}
CONTROLS = (CONTROL_FP8, CONTROL_STATE)


def _ln(x, g, b):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, -1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + EPS) * g + b).astype(x.dtype)


def _mamba(z, pr, w, h, pos):
    """``(Mix, y)`` of a state-space layer over a row of sequences."""
    f32 = jnp.float32
    n, r = z["d_state"], z["dt_rank"]
    u, gate = jnp.split(pr.mm(h, w["w_in"]), 2, axis=-1)
    # the convolution reaches back within its own sequence only
    conv = w["conv_b"] + sum(
        w["conv_w"][:, z["d_conv"] - 1 - back]
        * jnp.where((pos >= back)[:, None],
                    jnp.roll(u, back, axis=0), 0).astype(f32)
        for back in range(z["d_conv"]))
    u = jax.nn.silu(conv).astype(pr.act)
    d, b, c = jnp.split(pr.mm(u, w["w_x"]), [r, r + n], axis=-1)
    dt = jax.nn.softplus(pr.mm(d, w["w_dt"]).astype(f32) + w["b_dt"])
    a = -jnp.exp(w["A_log"]).T            # (d_state, d_inner): whole lanes

    def token(s, xs):
        dt_t, u_t, b_t, c_t, first = xs
        s = jnp.where(first, 0.0, s.astype(f32))
        s = jnp.exp(dt_t[None, :] * a) * s \
            + (dt_t * u_t)[None, :] * b_t[:, None]
        s = s.astype(pr.state)
        return s, (s.astype(f32) * c_t[:, None]).sum(0)

    _, y = jax.lax.scan(
        token, jnp.zeros((n, z["d_inner"]), pr.state),
        (dt, u.astype(f32), b.astype(f32), c.astype(f32), pos == 0))
    y = (y + w["D"] * u.astype(f32)).astype(pr.act)
    return pr.mm(y * jax.nn.silu(gate), w["w_out"]), y


def _attention(z, pr, kind, lam0_l, w, h, seg, pos, shared):
    """``(Mix, (k, v))`` of a window, full or cross layer: every token
    within its own sequence, a block of queries at a time, heads of
    ``head_dim`` in explicit pairs."""
    f32 = jnp.float32
    t, hd = h.shape[0], z["head_dim"]
    reads = z["heads"] // z["kv_heads"]
    if kind == "cross":
        q = pr.mm(h, w["w_q"]) + w["b_q"].astype(pr.act)
        k, v = shared
    else:
        wide = z["heads"] * hd
        q, k, v = jnp.split(
            pr.mm(h, w["w_qkv"]) + w["b_qkv"].astype(pr.act),
            [wide, wide + z["kv_heads"] * hd], axis=-1)
    q = q.reshape(t, -1, 2, hd)                       # (T, pairs, half, d)
    kp = jnp.repeat(k.reshape(t, -1, 2, hd), reads, axis=1)
    vp = jnp.repeat(v.reshape(t, -1, 2 * hd), reads, axis=1)
    lam = jnp.exp(jnp.dot(w["lq1"], w["lk1"])) \
        - jnp.exp(jnp.dot(w["lq2"], w["lk2"])) + lam0_l
    block = min(QUERY_BLOCK, t)

    def attend(args):
        qb, qseg, qpos = args
        mask = (seg[None, :] == qseg[:, None]) & (pos[None, :]
                                                  <= qpos[:, None])
        if kind == "window":
            mask = mask & (pos[None, :] > qpos[:, None] - z["window"])
        scores = jnp.einsum("qpid,kpid->piqk", qb, kp,
                            preferred_element_type=pr.acc) \
            .astype(f32) / math.sqrt(hd)
        att = jax.nn.softmax(jnp.where(mask[None, None], scores, -1e30), -1)
        return jnp.einsum("piqk,kpe->qpie", att.astype(pr.act), vp,
                          preferred_element_type=pr.acc).astype(f32)

    a12 = jax.lax.map(attend, (q.reshape(t // block, block, *q.shape[1:]),
                               seg.reshape(t // block, block),
                               pos.reshape(t // block, block)))
    a12 = a12.reshape(t, -1, 2, 2 * hd)
    diff = a12[:, :, 0] - lam * a12[:, :, 1]
    ctx = (1.0 - lam0_l) * diff * jax.lax.rsqrt(
        jnp.mean(diff * diff, -1, keepdims=True) + EPS) * w["subln"]
    mix = pr.mm(ctx.reshape(t, -1).astype(pr.act), w["w_o"]) \
        + w["b_o"].astype(pr.act)
    return mix, (k, v)


def layer(z, pr, kind, lam0_l, w, x, seg, pos, mem, shared):
    """One layer over a row of sequences ``x (T, embed)``: ``(x, y of a
    mamba layer or None, (k, v) of an attention layer or None)``."""
    h = _ln(x, w["ln1_g"], w["ln1_b"])
    y = kv = None
    if kind == "mamba":
        mix, y = _mamba(z, pr, w, h, pos)
    elif kind == "gmu":
        mix = pr.mm(mem * jax.nn.silu(pr.mm(h, w["w_in"])), w["w_out"])
    else:
        mix, kv = _attention(z, pr, kind, lam0_l, w, h, seg, pos, shared)
    x = x + mix
    gate, up = jnp.split(pr.mm(_ln(x, w["ln2_g"], w["ln2_b"]), w["w1"]), 2,
                         axis=-1)
    return x + pr.mm(up * jax.nn.silu(gate), w["w2"]), y, kv


def _frozen(z):
    return tuple(sorted(z.items()))


def _highest(pr, fn, *args):
    if pr is REFERENCE:
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return fn(*args)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer_jit(zf, pr_name, kind, lam0_l, w, x, seg, pos, mem, shared):
    pr = PRECISIONS[pr_name]
    return _highest(pr, layer, dict(zf), pr, kind, lam0_l, w, x, seg, pos,
                    mem, shared)


def forward_hidden(z, params, tokens, seg=None, pos=None,
                   precision=REFERENCE):
    """``tokens (T,) int32 -> (T, embed)``: the residual stream after the
    last layer of a row of sequences (one sequence from position 0 where
    ``seg``/``pos`` are not given), a jitted call a layer so that one
    layer's float32 copy lives at a time."""
    zf = _frozen(z)
    if seg is None:
        seg = jnp.zeros(tokens.shape, jnp.int32)
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"][tokens].astype(precision.act)
    mem = shared = None
    for l, (kind, w) in enumerate(zip(layer_kinds(z), params["layers"])):
        x, y, kv = _layer_jit(zf, precision.name, kind, jnp.float32(lam0(l)),
                              w, x, seg, pos, mem, shared)
        if l == z["layers"] // 2:
            mem = y
        if kind == "full":
            shared = kv
    return x


@functools.partial(jax.jit, static_argnums=(0,))
def _logits_jit(pr_name, params, x):
    pr = PRECISIONS[pr_name]

    def logits(x):
        h = _ln(x, params["ln_f_g"], params["ln_f_b"])
        return pr.mm(h, params["embed"].T).astype(jnp.float32)

    return _highest(pr, logits, x)


def _head(params):
    return {k: params[k] for k in ("embed", "ln_f_g", "ln_f_b")}


def forward_logits(z, params, tokens, seg=None, pos=None,
                   precision=REFERENCE):
    """``(T, vocab)`` float32 logits, whole: for the CPU-sized tests."""
    x = forward_hidden(z, params, tokens, seg, pos, precision)
    return _logits_jit(precision.name, _head(params), x)


def _blocks(t):
    """``(start, stop)`` of the blocks of rows whose logits are read at
    once."""
    return [(i, min(i + LOGIT_BLOCK, t)) for i in range(0, t, LOGIT_BLOCK)]


def best_tokens(params, x, precision):
    """The token each row's logits put first, ``(T,) int32``, the logits
    read a block of rows at a time."""
    head = _head(params)
    return jnp.concatenate([
        jnp.argmax(_logits_jit(precision.name, head, x[i:j]), -1)
        for i, j in _blocks(x.shape[0])]).astype(jnp.int32)


@jax.jit
def _gaps(logits, chosen):
    took = jnp.take_along_axis(logits, chosen.T, axis=-1)      # (B, K)
    return (jnp.max(logits, axis=-1, keepdims=True) - took).T  # (K, B)


def gaps_below_best(params, x, chosen, precision=REFERENCE):
    """By how much the logit of ``chosen[k, i]`` lies below the largest
    logit of row ``i``, ``(K, T)``: 0 where the chosen token is the
    reference's own.  The logits are read a block of rows at a time."""
    head = _head(params)
    return jnp.concatenate([
        _gaps(_logits_jit(precision.name, head, x[i:j]), chosen[:, i:j])
        for i, j in _blocks(x.shape[0])], axis=-1)
