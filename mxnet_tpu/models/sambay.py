"""SambaY-style decoder-hybrid-decoder for the decode tier (the shape of
Phi-4-mini-flash-reasoning, arXiv:2507.06607): state-space layers and
gated memory units beside window and full attention, one K/V layer read by
every cross-attention layer after it.

``x`` is the residual stream, ``LN`` a LayerNorm with gain and bias.  Every
layer ``l`` is ``x += Mix_l(LN1_l(x)); x += MLP_l(LN2_l(x))``, then ``logits
= LN_f(x) E^T`` with ``E`` the embedding (tied, no bias).  No positional
embedding anywhere.  ``MLP(h) = W2 (up * silu(gate))``, ``[gate, up] = W1
h``.  With ``n`` layers and ``mb_per_layer`` 2 (:func:`layer_kinds`):

* **mamba** (even ``l <= n/2``): ``[u, z] = W_in h``; ``u_t = silu(b_c +
  sum_j w_c[:, j] u_{t-3+j})`` (causal depthwise convolution of width
  ``d_conv``, zeros before the sequence); ``[d, B_t, C_t] = W_x u_t``;
  ``dt_t = softplus(W_dt d + b_dt)``; ``A = -exp(A_log)``; ``s_t = exp(dt_t
  A) * s_{t-1} + (dt_t * u_t) B_t^T`` (zero before the sequence); ``y_t =
  s_t C_t + D * u_t``; ``Mix = W_out (y_t * silu(z_t))``.  Layer ``n/2``'s
  ``y_t``, before the gate, is the memory ``m_t``;
* **gmu**, the gated memory unit (even ``l > n/2``): ``Mix = W_out (m_t *
  silu(W_in h))``, ``m_t`` the memory of the same token; no state of its
  own;
* **differential attention** (odd ``l``): ``[q, k, v] = W_qkv h + b``, heads
  of ``head_dim``.  Heads pair by neighbours: query pair ``p`` is heads
  ``(2p, 2p+1) = (q1, q2)``, K/V pair ``g`` is ``(k1, k2) = (k_2g,
  k_2g+1)`` with ``V_g = [v_2g, v_2g+1]`` (twice as wide), and query pair
  ``p`` reads K/V pair ``p // (heads // kv_heads)``.  ``a1 = softmax(q1
  k1^T / sqrt(head_dim)) V``, ``a2`` likewise from ``(q2, k2)``; ``lam =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lam0_l``, ``lam0_l = 0.8 - 0.6 exp(-0.3
  l)``; ``ctx_p = (1 - lam0_l) RMSNorm(a1 - lam a2)`` (gain, over the
  pair's ``2 head_dim``); ``Mix = W_o [ctx_0 ..] + b_o``.

  - **window** (odd ``l < n/2 + 1``): position ``p`` reads ``p - window +
    1 .. p``;
  - **full** (``l = n/2 + 1``): every position up to ``p``; its K and V
    are the shared cache;
  - **cross** (odd ``l > n/2 + 1``): ``W_q`` and ``W_o`` only; reads the
    full layer's K and V with its own ``lq*``, ``lk*``, ``lam0_l`` and
    RMSNorm.

**How the program holds a pair.**  K is cached as the pair ``[k1, k2]`` and
V as ``V_g``, both ``(kv_heads / 2, rows, 2 head_dim)``: one shape, whole
128-lane rows at a ``head_dim`` of 64.  (A cache of ``(kv_heads, rows, 64)``
lives rows-minor on the v5e, and a kernel that wants it row-major copied the
whole of it four times a step: PERF.md section 6, PR 31.)  A query half is
laid beside zeros, ``q1 -> [q1, 0]`` and ``q2 -> [0, q2]``, so that its
product with the pair is its product with its own half, exactly: the
zeros add ``0.0`` to a float32 sum.  :func:`forward_logits` does not do
this; it is written from the equations with heads of ``head_dim``.

**Precision** as :mod:`~mxnet_tpu.models.exaone_moe` lists it (weights and
K/V in their own dtype, products accumulated in float32, the residual
stream, norms, softmax and logits in float32), and besides, in float32:
the recurrent state, ``dt``, ``exp(dt A)`` and the scan, the convolution's
sum, ``lam`` and the pair's RMSNorm.  ``u`` is rounded to the cache's dtype
where it enters the convolution, so the tail a slot holds is what the
prefill convolved.

**Slot state** (:meth:`SambaY.cache_spec`, one entry a layer that keeps
any, in layer order): a mamba layer a :class:`~mxnet_tpu.models.
transformer_lm.StateLayer` of the state ``(d_state, d_inner)`` float32
(``d_inner`` last: whole lanes) and the convolution's tail ``(d_conv - 1,
d_inner)``; a window layer a ring of ``window`` rows written at ``pos %
window``; the full layer ``max_len`` rows; gmu and cross layers nothing.

**Prefill** runs the layers up to the full one over the whole bucket, the
scan with ``dt = 0`` and ``u = 0`` at padded positions, so the state it
returns is the state after ``length`` tokens and the tail is the
convolution's last ``d_conv - 1`` real inputs.  The layers after the full
one write no state and only the last position's logits are served, so they
run for that position alone: the architecture's own prefill.

:func:`forward_logits` is the in-repo plain reference: float32, ``highest``
precision, no cache, one sequence, every layer at every position.
"""

from __future__ import annotations

import math
from collections import namedtuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import decode_attention, write_slot_rows
from ..ops.ssm import ssm_scan
from .transformer_lm import CacheLayer, StateLayer

__all__ = ["SambaYConfig", "SambaY", "layer_kinds", "init_params",
           "forward_logits", "lam0"]

#: ``heads``/``kv_heads`` count heads of ``head_dim`` (pairs are two of
#: them); ``ffn`` is the SwiGLU's inner width; ``window`` counts the
#: query's own position
SambaYConfig = namedtuple("SambaYConfig", [
    "vocab", "embed", "heads", "kv_heads", "head_dim", "layers", "ffn",
    "mb_per_layer", "window", "d_inner", "d_state", "d_conv", "dt_rank",
    "max_len", "eos_id"])

EPS = 1e-5
_NEG = jnp.float32(-1e30)


def layer_kinds(cfg):
    """The kind of every layer: "mamba" | "window" | "full" | "gmu" |
    "cross".  The full layer is ``layers / 2 + 1``; before it state-space
    layers (every ``mb_per_layer``-th, from 0) alternate with window
    attention, after it gated memory units with cross attention."""
    shared = cfg.layers // 2 + 1
    if shared % cfg.mb_per_layer == 0 or shared >= cfg.layers:
        raise ValueError("layers=%d, mb_per_layer=%d: layer %d has to be "
                         "an attention layer" % (cfg.layers,
                                                 cfg.mb_per_layer, shared))
    kinds = []
    for l in range(cfg.layers):
        recurrent = l % cfg.mb_per_layer == 0
        if l < shared:
            kinds.append("mamba" if recurrent else "window")
        elif l == shared:
            kinds.append("full")
        else:
            kinds.append("gmu" if recurrent else "cross")
    return tuple(kinds)


def lam0(l):
    """``lam0_l`` of layer ``l`` (counted from 0)."""
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def init_params(cfg, seed=0, dtype=jnp.bfloat16, std=0.02):
    """Seeded parameters (host arrays): normal(0, ``std``), the projections
    into the residual stream scaled by ``1 / sqrt(2 layers)``, ``A_log =
    log(1 .. d_state)``, ``D = 1``, ``b_dt`` the inverse softplus of a
    log-uniform 0.001 .. 0.1, ``lq*``/``lk*`` normal(0, 0.1), the
    convolution uniform within ``1 / sqrt(d_conv)`` (four taps of deviation
    0.02 would pass a fiftieth of their input).  Matrices in
    ``dtype``; gains, biases, the convolution, ``A_log`` and ``D``
    float32."""
    rs = np.random.RandomState(seed)
    e, hd, d, n = cfg.embed, cfg.head_dim, cfg.d_inner, cfg.d_state
    resid = std / math.sqrt(2.0 * cfg.layers)
    f32 = jnp.float32

    def nrm(*shape, s=std, dt=dtype):
        return jnp.asarray(rs.normal(0, s, shape).astype(np.float32), dt)

    layers = []
    for kind in layer_kinds(cfg):
        p = {"ln1_g": jnp.ones((e,), f32), "ln1_b": jnp.zeros((e,), f32),
             "ln2_g": jnp.ones((e,), f32), "ln2_b": jnp.zeros((e,), f32),
             "w1": nrm(e, 2 * cfg.ffn), "w2": nrm(cfg.ffn, e, s=resid)}
        if kind == "mamba":
            step = np.exp(rs.uniform(math.log(1e-3), math.log(1e-1), (d,)))
            p.update(
                w_in=nrm(e, 2 * d),
                conv_w=jnp.asarray(rs.uniform(
                    -1.0, 1.0, (d, cfg.d_conv)) / math.sqrt(cfg.d_conv), f32),
                conv_b=jnp.zeros((d,), f32),
                w_x=nrm(d, cfg.dt_rank + 2 * n),
                w_dt=nrm(cfg.dt_rank, d),
                b_dt=jnp.asarray(np.log(np.expm1(step)), f32),
                A_log=jnp.asarray(np.log(np.broadcast_to(
                    np.arange(1, n + 1, dtype=np.float32), (d, n)))),
                D=jnp.ones((d,), f32), w_out=nrm(d, e, s=resid))
        elif kind == "gmu":
            p.update(w_in=nrm(e, d), w_out=nrm(d, e, s=resid))
        else:
            wide = cfg.heads * hd
            if kind == "cross":
                p.update(w_q=nrm(e, wide), b_q=jnp.zeros((wide,), f32))
            else:
                both = wide + 2 * cfg.kv_heads * hd
                p.update(w_qkv=nrm(e, both), b_qkv=jnp.zeros((both,), f32))
            p.update(w_o=nrm(wide, e, s=resid), b_o=jnp.zeros((e,), f32),
                     subln=jnp.ones((2 * hd,), f32),
                     **{name: nrm(hd, s=0.1, dt=f32)
                        for name in ("lq1", "lk1", "lq2", "lk2")})
        layers.append(p)
    return {"embed": nrm(cfg.vocab, e), "ln_f_g": jnp.ones((e,), f32),
            "ln_f_b": jnp.zeros((e,), f32), "layers": layers}


# -- pieces both the program and the reference are written from ----------------
def _ln(x, g, b):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * g + b


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * g


def _mm(a, w):
    """``a @ w``: operands in the weights' dtype, float32 accumulation."""
    return jnp.dot(a.astype(w.dtype), w,
                   preferred_element_type=jnp.float32)


def _lam(p, l):
    return jnp.exp(jnp.dot(p["lq1"], p["lk1"])) \
        - jnp.exp(jnp.dot(p["lq2"], p["lk2"])) + lam0(l)


def _mlp(p, x):
    with jax.named_scope("mlp.dense"):
        gate, up = jnp.split(_mm(_ln(x, p["ln2_g"], p["ln2_b"]), p["w1"]),
                             2, axis=-1)
        return x + _mm(up * jax.nn.silu(gate), p["w2"])


# -- the plain reference -------------------------------------------------------
def forward_logits(cfg, params, tokens):
    """``tokens (T,) int32 -> (T, vocab)`` float32 logits of one sequence:
    the equations of the module docstring in float32 at ``highest``
    precision, no cache, the recurrence a plain loop over positions, heads
    of ``head_dim`` in explicit pairs."""
    (t,) = tokens.shape
    f32 = jnp.float32
    params = jax.tree_util.tree_map(lambda a: a.astype(f32), params)
    hd, n, r = cfg.head_dim, cfg.d_state, cfg.dt_rank
    reads = cfg.heads // cfg.kv_heads     # query pairs a K/V pair
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]
    near = pos[None, :] > pos[:, None] - cfg.window
    mem = shared = None

    def pairs(a):
        """``(T, heads, d) -> (T, heads/2, 2, d)``: neighbours."""
        return a.reshape(t, -1, 2, hd)

    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for l, (kind, p) in enumerate(zip(layer_kinds(cfg),
                                          params["layers"])):
            h = _ln(x, p["ln1_g"], p["ln1_b"])
            if kind == "mamba":
                u, z = jnp.split(h @ p["w_in"], 2, axis=-1)
                before = jnp.concatenate(
                    [jnp.zeros((cfg.d_conv - 1, cfg.d_inner), f32), u])
                u = jax.nn.silu(p["conv_b"] + sum(
                    p["conv_w"][:, j] * before[j:j + t]
                    for j in range(cfg.d_conv)))
                d, b, c = jnp.split(u @ p["w_x"], [r, r + n], axis=-1)
                dt = jax.nn.softplus(d @ p["w_dt"] + p["b_dt"])
                a = -jnp.exp(p["A_log"])                  # (d_inner, n)

                def token(s, xs):
                    dt_t, u_t, b_t, c_t = xs
                    s = jnp.exp(dt_t[:, None] * a) * s \
                        + (dt_t * u_t)[:, None] * b_t[None, :]
                    return s, s @ c_t

                _, y = jax.lax.scan(
                    token, jnp.zeros((cfg.d_inner, n), f32), (dt, u, b, c))
                y = y + p["D"] * u
                if l == cfg.layers // 2:
                    mem = y
                x = x + (y * jax.nn.silu(z)) @ p["w_out"]
            elif kind == "gmu":
                x = x + (mem * jax.nn.silu(h @ p["w_in"])) @ p["w_out"]
            else:
                if kind == "cross":
                    q = (h @ p["w_q"] + p["b_q"]).reshape(t, -1, hd)
                    k, v = shared
                else:
                    q, k, v = (a.reshape(t, -1, hd) for a in jnp.split(
                        h @ p["w_qkv"] + p["b_qkv"],
                        [cfg.heads * hd, (cfg.heads + cfg.kv_heads) * hd],
                        axis=-1))
                    if kind == "full":
                        shared = (k, v)
                mask = (causal & near) if kind == "window" else causal
                q, kp = pairs(q), pairs(k)       # (T, pairs, 2, hd)
                vp = pairs(v).reshape(t, -1, 2 * hd)
                # query pair p reads K/V pair p // reads
                kp, vp = (jnp.repeat(a, reads, axis=1) for a in (kp, vp))
                scores = jnp.einsum("qpid,kpid->piqk", q, kp) \
                    / math.sqrt(hd)
                att = jax.nn.softmax(
                    jnp.where(mask[None, None], scores, _NEG), -1)
                a12 = jnp.einsum("piqk,kpe->qpie", att, vp)
                diff = a12[:, :, 0] - _lam(p, l) * a12[:, :, 1]
                ctx = (1.0 - lam0(l)) * _rms(diff, p["subln"])
                x = x + ctx.reshape(t, -1) @ p["w_o"] + p["b_o"]
            h = _ln(x, p["ln2_g"], p["ln2_b"])
            gate, up = jnp.split(h @ p["w1"], 2, axis=-1)
            x = x + (up * jax.nn.silu(gate)) @ p["w2"]
        return _ln(x, params["ln_f_g"], params["ln_f_b"]) \
            @ params["embed"].T


# -- one block a layer kind, shared by prefill and decode step -----------------
def _pair_queries(cfg, q):
    """``q (T, heads, d)`` by the K/V pair it reads, each half beside
    zeros where the other half of the pair lies: ``(T, kv_heads / 2, 2
    heads / kv_heads, 2 d)``, query ``j`` of a group the half ``j % 2``."""
    t = q.shape[0]
    q = q.reshape(t, cfg.kv_heads // 2, -1, cfg.head_dim)
    zero = jnp.zeros_like(q)
    first = (jnp.arange(q.shape[2]) % 2 == 0)[:, None]
    return jnp.where(first, jnp.concatenate([q, zero], -1),
                     jnp.concatenate([zero, q], -1))


def _mamba(cfg, l, p, x, access):
    """``(x + Mix, y)``.  ``access.window(l, u)`` is handed the
    convolution's input ``u (T, d_inner)`` and returns every row's last
    ``d_conv`` inputs ``(T, d_conv, d_inner)``; ``access.recur(l, dt, u, b,
    c, a)`` is the recurrence from the state the caller holds, returning
    ``y (T, d_inner)``."""
    n, r = cfg.d_state, cfg.dt_rank
    with jax.named_scope(access.ssm_scope):
        h = _ln(x, p["ln1_g"], p["ln1_b"])
        u, z = jnp.split(_mm(h, p["w_in"]), 2, axis=-1)
        last = access.window(l, u.astype(p["w_in"].dtype))
        u = jax.nn.silu(p["conv_b"] + jnp.einsum(
            "tjd,dj->td", last.astype(jnp.float32), p["conv_w"]))
        d, b, c = jnp.split(_mm(u, p["w_x"]), [r, r + n], axis=-1)
        dt = jax.nn.softplus(_mm(d, p["w_dt"]) + p["b_dt"])
        y = access.recur(l, dt, u, b, c, -jnp.exp(p["A_log"]).T) \
            + p["D"] * u
        return x + _mm(y * jax.nn.silu(z), p["w_out"]), y


def _gmu(p, x, mem):
    with jax.named_scope("gmu"):
        h = _ln(x, p["ln1_g"], p["ln1_b"])
        return x + _mm(mem * jax.nn.silu(_mm(h, p["w_in"])), p["w_out"])


def _attention(cfg, l, kind, p, x, access):
    """``x + Mix`` of a window, full or cross layer.  ``access.attend(l,
    kind, q, k, v)`` is handed ``q (T, kv_heads / 2, 2 heads / kv_heads, 2
    d)`` (:func:`_pair_queries`) and, but in a cross layer, the K and V
    pairs ``(T, kv_heads / 2, 2 d)`` in the weights' dtype, and returns the
    context ``(T,) + q.shape[1:]`` float32."""
    t = x.shape[0]
    wide = cfg.heads * cfg.head_dim
    with jax.named_scope("attn." + kind):
        h = _ln(x, p["ln1_g"], p["ln1_b"])
        if kind == "cross":
            q, k, v = _mm(h, p["w_q"]) + p["b_q"], None, None
            dt = p["w_q"].dtype
        else:
            q, k, v = jnp.split(_mm(h, p["w_qkv"]) + p["b_qkv"],
                                [wide, wide + cfg.kv_heads * cfg.head_dim],
                                axis=-1)
            dt = p["w_qkv"].dtype
            k, v = (a.astype(dt).reshape(t, cfg.kv_heads // 2,
                                         2 * cfg.head_dim) for a in (k, v))
        q = _pair_queries(cfg, q.astype(dt).reshape(t, cfg.heads,
                                                    cfg.head_dim))
        ctx = access.attend(l, kind, q, k, v)
        # queries (2i, 2i + 1) of a group are pair i's two halves
        ctx = ctx.reshape(t, cfg.heads // 2, 2, 2 * cfg.head_dim)
        diff = ctx[:, :, 0] - _lam(p, l) * ctx[:, :, 1]
        ctx = (1.0 - lam0(l)) * _rms(diff, p["subln"])
        return x + _mm(ctx.reshape(t, -1), p["w_o"]) + p["b_o"]


def _layers(cfg, params, x, mem, access, which):
    """Layers ``which`` in turn over rows ``x``: ``(x, mem)``."""
    kinds = layer_kinds(cfg)
    for l in which:
        p = params["layers"][l]
        if kinds[l] == "mamba":
            x, y = _mamba(cfg, l, p, x, access)
            if l == cfg.layers // 2:
                mem = y
        elif kinds[l] == "gmu":
            x = _gmu(p, x, mem)
        else:
            x = _attention(cfg, l, kinds[l], p, x, access)
        x = _mlp(p, x)
    return x, mem


def _head(params, x):
    with jax.named_scope("head"):
        h = _ln(x, params["ln_f_g"], params["ln_f_b"])
        e = params["embed"]
        return jnp.einsum("te,ve->tv", h.astype(e.dtype), e,
                          preferred_element_type=jnp.float32)


def _softmax_ctx(scores, mask, values, spec):
    att = jax.nn.softmax(jnp.where(mask, scores, _NEG), axis=-1)
    return jnp.einsum(spec, att.astype(values.dtype), values,
                      preferred_element_type=jnp.float32)


class _Prefill:
    """State access of one bucket-padded prompt: nothing held before it."""

    ssm_scope = "ssm.scan"

    def __init__(self, model, p_len, length):
        cfg = self.cfg = model.cfg
        self.model, self.length = model, length
        pos = jnp.arange(p_len)
        self.real = pos < length
        self.causal = pos[None, :] <= pos[:, None]
        self.near = pos[None, :] > pos[:, None] - cfg.window
        # ring row j holds the last position below ``length`` that is j
        # modulo the window; rows no position has reached yet hold what
        # the decode step's mask never reads
        self.ring_src = jnp.clip(
            (length - 1) - ((length - 1 - jnp.arange(cfg.window))
                            % cfg.window), 0, p_len - 1)
        # the convolution's last inputs: positions length-3 .. length-1
        self.tail_src = length - (cfg.d_conv - 1) \
            + jnp.arange(cfg.d_conv - 1)
        #: layer -> what it leaves in a slot; the full layer's K and V as
        #: they were made, for the layers after it
        self.firsts, self.seconds, self.shared = {}, {}, None

    def window(self, l, u):
        cfg = self.cfg
        t = u.shape[0]
        u = jnp.where(self.real[:, None], u, jnp.zeros_like(u))
        before = jnp.concatenate(
            [jnp.zeros((cfg.d_conv - 1, cfg.d_inner), u.dtype), u])
        tail = jnp.where((self.tail_src >= 0)[:, None],
                         u[jnp.clip(self.tail_src, 0, t - 1)], 0)
        self.seconds[l] = tail.astype(self.model.cache_dtype)
        return jnp.stack([before[j:j + t] for j in range(cfg.d_conv)], 1)

    def recur(self, l, dt, u, b, c, a):
        dt = jnp.where(self.real[:, None], dt, 0.0)
        self.firsts[l], y = ssm_scan(
            dt, u, b, c, a, jnp.zeros(a.shape, jnp.float32))
        return y

    def attend(self, l, kind, q, k, v):
        scores = jnp.einsum("qgjd,mgd->gjqm", q, k,
                            preferred_element_type=jnp.float32) \
            / math.sqrt(self.cfg.head_dim)
        for rows, held in ((k, self.firsts), (v, self.seconds)):
            held[l] = jnp.swapaxes(
                rows[self.ring_src] if kind == "window" else rows, 0,
                1).astype(self.model.cache_dtype)
        if kind == "full":
            self.shared = (k, v)
        return _softmax_ctx(
            scores, (self.causal & self.near) if kind == "window"
            else self.causal, v, "gjqm,mgd->qgjd")


class _LastRow:
    """State access of the layers after the full one in a prefill: the last
    real position's row alone, reading the K and V the full layer made."""

    def __init__(self, cfg, k, v, length):
        self.cfg, self.k, self.v = cfg, k, v
        self.seen = (jnp.arange(k.shape[0]) < length)[None, None, None, :]

    def attend(self, l, kind, q, k, v):
        scores = jnp.einsum("qgjd,mgd->gjqm", q, self.k,
                            preferred_element_type=jnp.float32) \
            / math.sqrt(self.cfg.head_dim)
        return _softmax_ctx(scores, self.seen, self.v, "gjqm,mgd->qgjd")


class _Step:
    """State access of one token for every slot: what the slots hold."""

    ssm_scope = "ssm.step"

    def __init__(self, model, firsts, seconds, pos):
        cfg = self.cfg = model.cfg
        self.model, self.pos = model, pos
        self.firsts, self.seconds = list(firsts), list(seconds)
        self.scale = 1.0 / math.sqrt(cfg.head_dim)
        ring = jnp.arange(cfg.window)
        # the absolute position ring row j holds once ``pos`` is written
        holds = pos[:, None] - ((pos[:, None] - ring[None]) % cfg.window)
        self.ring_mask = (holds >= 0)[:, None, None, :]

    def window(self, l, u):
        i = self.model.entry[l]
        last = jnp.concatenate(
            [self.seconds[i], u[:, None].astype(self.seconds[i].dtype)], 1)
        self.seconds[i] = last[:, 1:]
        return last

    def recur(self, l, dt, u, b, c, a):
        i = self.model.entry[l]
        state = jnp.exp(dt[:, None, :] * a[None]) * self.firsts[i] \
            + (dt * u)[:, None, :] * b[:, :, None]
        self.firsts[i] = state
        return (state * c[:, :, None]).sum(1)

    def attend(self, l, kind, q, k, v):
        model, pos = self.model, self.pos
        if kind == "window":
            i = model.entry[l]
            at = pos % self.cfg.window
            # the slot's one row, not a select over the whole ring: at
            # 128 slots of 512 rows that pass took 0.51 ms an array on
            # the chip, the row writer 0.02 (PERF.md section 6, PR 32)
            ck = self.firsts[i] = write_slot_rows(self.firsts[i], k, at)
            cv = self.seconds[i] = write_slot_rows(self.seconds[i], v, at)
            scores = jnp.einsum("sgjd,sgmd->sgjm", q, ck,
                                preferred_element_type=jnp.float32) \
                * self.scale
            return _softmax_ctx(scores, self.ring_mask, cv,
                                "sgjm,sgmd->sgjd")
        i = model.entry[model.shared]
        if kind == "full":
            self.firsts[i] = write_slot_rows(self.firsts[i], k, pos)
            self.seconds[i] = write_slot_rows(self.seconds[i], v, pos)
        # the full layer and every cross layer read the rows each slot
        # holds of the same two arrays
        return decode_attention(q, self.firsts[i], self.seconds[i], pos,
                                self.scale)


class SambaY:
    """The model object the decode engine is given (its model protocol,
    :mod:`mxnet_tpu.serving.decode`): slot-state specification, prefill,
    decode step, and the row counters as extra device state."""

    def __init__(self, cfg, cache_dtype=jnp.bfloat16):
        if cfg.heads % cfg.kv_heads or cfg.kv_heads % 2:
            raise ValueError("heads=%d, kv_heads=%d: K/V heads pair, and "
                             "each pair is read by whole query pairs"
                             % (cfg.heads, cfg.kv_heads))
        self.cfg = cfg
        #: what K, V and the convolution's tail are held in (the recurrent
        #: state is float32 whatever this is)
        self.cache_dtype = cache_dtype
        self.kinds = layer_kinds(cfg)
        #: the full layer, whose K and V every cross layer reads
        self.shared = cfg.layers // 2 + 1
        keeps = [l for l, kind in enumerate(self.kinds)
                 if kind in ("mamba", "window", "full")]
        #: layer -> its entry of :meth:`cache_spec`
        self.entry = {l: i for i, l in enumerate(keeps)}

    # -- the protocol ------------------------------------------------------
    def cache_spec(self):
        cfg, dtype = self.cfg, self.cache_dtype
        pairs, wide = cfg.kv_heads // 2, 2 * cfg.head_dim
        made = {
            "mamba": StateLayer(
                "state", ((cfg.d_state, cfg.d_inner),
                          (cfg.d_conv - 1, cfg.d_inner)),
                (jnp.float32, dtype)),
            "window": CacheLayer("ring", cfg.window, pairs, wide, dtype,
                                 True),
            "full": CacheLayer("full", cfg.max_len, pairs, wide, dtype,
                               True)}
        return tuple(made[self.kinds[l]] for l in self.entry)

    def extra_state(self):
        """The device counters (uint32, wrapping), counted in decode steps
        over active slots: ``rows_full`` the rows the shared layer holds
        for them (every one of its readers reads these), ``rows_ring`` the
        rows a ring holds for them (at most the window each), ``rows`` the
        slots stepped, ``steps`` the steps that stepped any."""
        return {name: jnp.zeros((), jnp.uint32)
                for name in ("rows_full", "rows_ring", "rows", "steps")}

    def counters(self, extra):
        """The extra state read back, whole numbers by name."""
        return {name: int(value) for name, value in extra.items()}

    def prefill(self, params, tokens, length):
        """One bucket-padded prompt ``tokens (P,)`` of ``length`` real
        tokens -> ``(last_logits (vocab,), firsts, seconds)``: for every
        entry of :meth:`cache_spec` the two values of one slot (a state
        and its tail whole; a ring whole, holding the last ``window``
        positions below ``length`` where they belong; the full layer's
        positions ``0 .. P-1``)."""
        cfg = self.cfg
        (p_len,) = tokens.shape
        access = _Prefill(self, p_len, length)
        x = params["embed"][tokens].astype(jnp.float32)
        x, mem = _layers(cfg, params, x, None, access,
                         range(self.shared + 1))
        at = jnp.clip(length - 1, 0, p_len - 1)
        last = _LastRow(cfg, *access.shared, length)
        x, _ = _layers(cfg, params, x[at][None], mem[at][None], last,
                       range(self.shared + 1, cfg.layers))
        return (_head(params, x)[0],
                tuple(access.firsts[l] for l in self.entry),
                tuple(access.seconds[l] for l in self.entry))

    def decode_step(self, params, firsts, seconds, last_tok, lengths,
                    active, extra):
        """One token for all ``S`` slots: the states and tails advance by
        one token, the incoming K/V goes to position ``lengths`` of the
        full layer (row ``lengths % window`` of a ring) and is attended
        over with everything the slot holds.  Returns ``(logits (S,
        vocab), firsts, seconds, extra)``."""
        cfg = self.cfg
        pos = jnp.clip(lengths, 0, cfg.max_len - 1)
        access = _Step(self, firsts, seconds, pos)
        x = params["embed"][last_tok].astype(jnp.float32)
        x, _ = _layers(cfg, params, x, None, access, range(cfg.layers))
        logits = _head(params, x)
        live = active.astype(jnp.uint32)
        held = (pos + 1).astype(jnp.uint32)
        rows = live.sum()
        extra = {
            "rows_full": extra["rows_full"] + (live * held).sum(),
            "rows_ring": extra["rows_ring"] + (live * jnp.minimum(
                held, np.uint32(cfg.window))).sum(),
            "rows": extra["rows"] + rows,
            "steps": extra["steps"] + (rows > 0).astype(jnp.uint32)}
        return logits, tuple(access.firsts), tuple(access.seconds), extra
