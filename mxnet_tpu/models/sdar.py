"""SDAR-style decoder for the decode tier: a Qwen3-MoE layer under a mask
that is causal by blocks, and generation by diffusion over blocks of ``B``
positions, where a step is a denoising pass and yields none or ``B`` tokens
a slot.

Layer ``l`` over a row ``x`` (float32 residual) at position ``p`` (``RMS(x,
g) = x g / sqrt(mean(x^2) + eps)``):

* **attention**: ``h = RMS(x, g1)``; ``q = h Wq`` (``heads`` of
  ``head_dim``), ``k = h Wk``, ``v = h Wv`` (``kv_heads``), no biases;
  ``q`` and ``k`` each head through ``RMS(., gq | gk)`` over its
  ``head_dim`` values (one weight for all heads), then rotated at ``p``
  (RoPE over the whole head, half-split pairs); ``a = softmax(q k^T /
  sqrt(head_dim) + M) v``, query head ``i`` reading K/V head ``i // (heads
  // kv_heads)``, where **``M`` lets row ``i`` see column ``j`` iff ``j //
  B <= i // B``**: causal between blocks, both ways inside one; ``x = x + a
  Wo``;
* **experts**: ``h2 = RMS(x, g2)``; ``s = h2 Wr`` (float32, ``highest``),
  the best ``top_k``, weights the softmax over those; ``x = x + sum over
  the chosen e of w_e Wd_e (silu(h2 Wg_e) * (h2 Wu_e))``.  No shared
  expert, every layer sparse;
* **head**: ``logits = RMS(x, gf) Wh``, untied.  The logits at a masked
  position give that position's OWN token (no shift by one).

**Generation** (the family's published sampler, ``generate.py`` beside the
source config; :func:`generate_plain` writes it out).  Blocks are absolute:
block ``k`` is positions ``kB .. kB + B - 1``.  A prompt of ``n`` tokens
fills positions ``0 .. n - 1``; the whole blocks below ``floor(n / B) B``
are prefilled under ``M`` for their K and V alone, and the block that holds
position ``floor(n / B) B`` starts with the prompt's tail fixed and the
rest masked.  **A pass** runs the block's ``B`` rows against the cache and
themselves, takes at every position not yet fixed the token ``x0`` (the
best, or at a temperature a draw) and its probability ``c``, and fixes
some: with ``denoise_steps = T`` the quota of pass ``t`` is ``B // T``, one
more in the first ``B % T`` passes (:func:`quota`); ``low_confidence_static``
fixes the quota's best ``c``; ``low_confidence_dynamic`` every position with
``c > threshold`` if those are at least the quota, else as static;
``sequential`` the first of the unfixed.  A pass that leaves every position
fixed **delivers** the block: its tokens beyond the prompt and below ``n +
max_new`` are the session's next tokens, the length grows by ``B`` and the
next block opens.  What the cache has to keep of the block are the K and V
of its FINAL tokens, which no pass has computed yet (a pass writes the K
and V of the block as it stood at entry): the published loop spends a pass
of its own on them, the **commit**, which reads every weight and fixes
nothing.  Here the finished block stays the slot's *pending* block and its
``B`` rows ride the slot's NEXT pass, the one that opens the next block,
for their K and V alone (:meth:`SDAR.decode_step`): a block costs its
denoising passes and no other, and a session that ends with its block
writes no commit at all.  The rules run in the engine's tail
(:mod:`mxnet_tpu.serving.decode`), from this ``cfg``.

Departures from ``generate.py``, each for a stated reason: which positions
are fixed is kept as flags and not read off ``token == mask_id``, so a
prompt may hold any id; a fixed position is never rewritten (the published
top-k over a block with fewer unfixed positions than the quota would pick
fixed ones); at a temperature the draw and its probability are of
``softmax(logits / temperature)`` and no top-k or top-p cut is made.

The expert layer is :mod:`~mxnet_tpu.models.exaone_moe`'s (``sparse_mlp``
with ``shared=False``: told ``(first_expert, experts_held, num_experts)``,
the router ``softmax`` and the activation ``silu`` by name), and so are the
rotation, the norm and the products' precision (that module's docstring has
the list; ``eps`` is this configuration's).

**The cache** (:meth:`SDAR.cache_spec`): every layer a full layer
``(slots, kv_heads, max_len, head_dim)``, K stored normed and rotated.
**Every pass writes** the open block's K and V at rows ``length .. length +
B - 1`` and, before them, the pending block's at ``length - B .. length -
1`` (:func:`ops.attention.write_slot_rows`, two runs of ``B`` rows; rows
above a slot's length are hidden, so a pass that fixes nothing leaves
nothing behind) and attends with :func:`ops.attention.decode_attention`:
every row of a block sees the same rows (all earlier ones and its own
block), so a pass is that kernel at ``group = (heads // kv_heads) x 2B``
with a horizon a half, ``length - 1`` for the pending block's queries and
``length + B - 1`` for the open one's, and the cache is read once for
``2B`` positions.  The last layer leaves the pending rows at their K and V
and the head runs over the open block's rows alone.  A prompt's attention
goes through :func:`ops.attention.flash_attention` with ``block=B``.

:func:`forward_logits` is the in-repo plain reference: float32, ``highest``
precision, no cache, one sequence, under ``M``, each expert in a plain
loop.  Prefill and decode step share :func:`_block`, which takes its cache
access as an argument.
"""

from __future__ import annotations

import math
from collections import namedtuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import (decode_attention, flash_attention,
                             write_slot_rows)
from .exaone_moe import (_mm, _rms, _rope, count_picks, routing_gauges,
                         sparse_mlp)
from .transformer_lm import CacheLayer

__all__ = ["SDARConfig", "SDAR", "init_params", "forward_logits",
           "generate_plain", "quota", "REMASKING"]

#: the sampler's three rules, by the published names
REMASKING = ("low_confidence_dynamic", "low_confidence_static", "sequential")

#: ``layers`` is how many are held; ``first_expert`` / ``experts_held`` are
#: this chip's share of ``num_experts``; ``mask_id`` is the token a position
#: not yet fixed reads (its embedding is all the program needs of it);
#: ``block`` / ``denoise_steps`` / ``remasking`` / ``threshold`` are the
#: sampler's (the engine's tail reads them here and nowhere else);
#: ``router`` / ``activation`` name the expert layer's choices.
SDARConfig = namedtuple("SDARConfig", [
    "vocab", "embed", "heads", "kv_heads", "head_dim", "layers",
    "expert_ffn", "num_experts", "top_k", "first_expert", "experts_held",
    "rope_theta", "eps", "max_len", "eos_id", "mask_id", "block",
    "denoise_steps", "remasking", "threshold", "router", "activation"],
    defaults=(4, 4, "low_confidence_dynamic", 0.9, "softmax", "silu"))

#: the prompt's attention: Q rows and K/V rows of a block
_BLOCK_Q, _BLOCK_K = 512, 512

#: the extra state's scalar counters (:meth:`SDAR.extra_state` has what each
#: counts and who counts it)
_COUNTERS = ("moe_picks_total", "rows", "commit_rows", "steps", "passes",
             "rows_read", "commits", "tokens_committed",
             "fixed_by_threshold", "fixed_by_quota")


def quota(cfg, t):
    """Positions pass ``t`` has to fix: ``B // T``, one more in the first
    ``B % T`` passes.  Works on Python and traced integers."""
    return cfg.block // cfg.denoise_steps \
        + (t < cfg.block % cfg.denoise_steps)


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
        "q_norm": jnp.ones((hd,), jnp.float32),
        "k_norm": jnp.ones((hd,), jnp.float32),
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
    """``tokens (T,) int32 -> (T, vocab)`` float32 logits of one sequence
    from position 0: the equations of the module docstring in float32 at
    ``highest`` precision under the mask ``M``, no cache, each held expert
    in a plain loop.  Row ``i`` holds the logits of position ``i``'s own
    token.  ``with_choices`` also returns the router's choices, one ``(T,
    top_k)`` array a layer."""
    (t,) = tokens.shape
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    group = cfg.heads // cfg.kv_heads
    pos = jnp.arange(t)
    sees = pos[None, :] // cfg.block <= pos[:, None] // cfg.block
    choices = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for p in params["layers"]:
            h = _rms(x, p["ln1"], cfg.eps)
            q = (h @ p["wq"]).reshape(t, cfg.heads, cfg.head_dim)
            k = (h @ p["wk"]).reshape(t, cfg.kv_heads, cfg.head_dim)
            v = (h @ p["wv"]).reshape(t, cfg.kv_heads, cfg.head_dim)
            q = _rope(cfg, _rms(q, p["q_norm"], cfg.eps), pos)
            k = _rope(cfg, _rms(k, p["k_norm"], cfg.eps), pos)
            k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
            scores = jnp.einsum("qhd,khd->hqk", q, k) \
                / math.sqrt(cfg.head_dim)
            att = jax.nn.softmax(jnp.where(sees[None], scores, -1e30), -1)
            ctx = jnp.einsum("hqk,khd->qhd", att, v)
            x = x + ctx.reshape(t, -1) @ p["wo"]
            h = _rms(x, p["ln2"], cfg.eps)
            moe = p["moe"]
            picked, chosen = jax.lax.top_k(h @ moe["router"], cfg.top_k)
            w = jax.nn.softmax(picked, axis=-1)
            choices.append(chosen)
            y = jnp.zeros_like(x)
            for e in range(cfg.experts_held):
                mine = chosen == cfg.first_expert + e
                w_e = jnp.where(mine, w, 0.0).sum(-1, keepdims=True)
                inner = jax.nn.silu(h @ moe["gate"][e]) * (h @ moe["up"][e])
                y = y + w_e * (inner @ moe["down"][e])
            x = x + y
        logits = _rms(x, params["ln_f"], cfg.eps) @ params["head"]
    return (logits, choices) if with_choices else logits


def generate_plain(cfg, params, prompt, max_new, temperature=0.0, seed=0,
                   forward=None):
    """The published loop written out plainly, one sequence, no cache:
    ``(tokens, fixed_at, passes)`` — the ``max_new`` tokens after
    ``prompt`` (fewer where ``eos_id`` or ``max_len`` ends them), of each
    the pass of its block at which it was fixed, and the denoising passes
    run in all (the published loop's commit, a pass that fixes nothing, is
    not counted: the engine runs none).  Every pass is one
    :func:`forward_logits` over
    the transcript so far and the block as it stands, of which the block's
    rows are read; ``forward(tokens) -> (T, vocab)`` stands in for it where
    given.  Keys are the engine's: ``fold_in(fold_in(PRNGKey(seed),
    position), pass)``."""
    if forward is None:
        def forward(tokens):
            return forward_logits(cfg, params, tokens)
    b = cfg.block
    seq = [int(t) for t in prompt]
    n = len(seq)
    end = min(n + int(max_new), cfg.max_len)
    start = n // b * b
    block, seq = seq[start:], seq[:start]
    fixed_at = [-1] * len(block)
    out, out_at, passes = [], [], 0
    while True:
        block = block + [cfg.mask_id] * (b - len(block))
        fixed_at = fixed_at + [None] * (b - len(fixed_at))
        t = 0
        while None in fixed_at:
            logits = np.asarray(forward(jnp.asarray(
                seq + block, jnp.int32)))[start:start + b]
            if temperature > 0:
                logits = logits / np.float32(max(temperature, 1e-6))
                x0 = [int(jax.random.categorical(
                    jax.random.fold_in(jax.random.fold_in(
                        jax.random.PRNGKey(seed), start + i), t),
                    jnp.asarray(logits[i]))) for i in range(b)]
            else:
                x0 = [int(row.argmax()) for row in logits]
            prob = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
            conf = {i: float(prob[i, x0[i]]) for i in range(b)
                    if fixed_at[i] is None}
            want = int(quota(cfg, t))
            by_conf = sorted(conf, key=lambda i: (-conf[i], i))
            if cfg.remasking == "sequential":
                take = sorted(conf)[:want]
            elif cfg.remasking == "low_confidence_static":
                take = by_conf[:want]
            elif cfg.remasking == "low_confidence_dynamic":
                take = [i for i in conf if conf[i] > cfg.threshold]
                if len(take) < want:
                    take = by_conf[:want]
            else:
                raise ValueError("no remasking %r (%s)"
                                 % (cfg.remasking, " | ".join(REMASKING)))
            for i in take:
                block[i], fixed_at[i] = x0[i], t
            t += 1
            passes += 1
        ended = False
        for i in range(b):
            if fixed_at[i] >= 0 and start + i < end and not ended:
                out.append(block[i])
                out_at.append(fixed_at[i])
                ended = block[i] == cfg.eos_id
        seq, start = seq + block, start + b
        if ended or start >= end:
            return out, out_at, passes
        block, fixed_at = [], []


# -- the block, shared by prefill and decode step ------------------------------
def _block(cfg, l, p, x, pos, attend, counts=None, live=None, lead=0):
    """One layer over rows ``x (T, embed)`` float32 at absolute positions
    ``pos (T,)``.  ``attend(l, q, k, v)`` is the caller's cache access: it
    is handed ``q (T, heads, d)``, ``k``/``v (T, kv_heads, d)`` (normed,
    rotated, in the weights' dtype) and returns the context ``(T, heads,
    d)``.  ``counts(l, chosen)`` is told the layer's choices.  ``live``:
    the rows that take part in the experts and how many are expected
    (:func:`exaone_moe.routed_experts`).  ``lead``: the first rows of ``x``
    that are in this layer for their K and V alone (the pending blocks' in
    a step's LAST layer, whose output nothing reads): ``attend`` is handed
    their ``k`` and ``v`` with the others' and the queries of the rows
    after them, and those ``T - lead`` rows are what comes back."""
    t = x.shape[0]
    dt = p["wq"].dtype
    with jax.named_scope("attn.block"):
        h = _rms(x, p["ln1"], cfg.eps)
        k = _mm(h, p["wk"]).reshape(t, cfg.kv_heads, cfg.head_dim)
        v = _mm(h, p["wv"]).reshape(t, cfg.kv_heads, cfg.head_dim)
        k = _rope(cfg, _rms(k, p["k_norm"], cfg.eps), pos)
        if lead:
            x, h, pos, t = x[lead:], h[lead:], pos[lead:], t - lead
        q = _mm(h, p["wq"]).reshape(t, cfg.heads, cfg.head_dim)
        q = _rope(cfg, _rms(q, p["q_norm"], cfg.eps), pos)
        ctx = attend(l, q.astype(dt), k.astype(dt), v.astype(dt))
        x = x + _mm(ctx.reshape(t, -1), p["wo"])
    y, chosen = sparse_mlp(cfg, _rms(x, p["ln2"], cfg.eps), p["moe"],
                           shared=False, live=live)
    if counts is not None:
        counts(l, chosen)
    return x + y


class SDAR:
    """The model object the decode engine is given (its model protocol,
    :mod:`mxnet_tpu.serving.decode`): a model that declares a block length
    (``cfg.block``), so a step is a pass over ``(slots, B)`` rows."""

    def __init__(self, cfg, cache_dtype=jnp.bfloat16):
        if cfg.heads % cfg.kv_heads:
            raise ValueError("heads=%d not a multiple of kv_heads=%d"
                             % (cfg.heads, cfg.kv_heads))
        if not 0 <= cfg.first_expert <= cfg.first_expert \
                + cfg.experts_held <= cfg.num_experts:
            raise ValueError("experts %d..%d are not within 0..%d"
                             % (cfg.first_expert, cfg.first_expert
                                + cfg.experts_held, cfg.num_experts))
        if cfg.remasking not in REMASKING:
            raise ValueError("no remasking %r (%s)"
                             % (cfg.remasking, " | ".join(REMASKING)))
        if not 1 <= cfg.denoise_steps <= cfg.block \
                or cfg.max_len % cfg.block:
            raise ValueError(
                "denoise_steps=%d not within 1..block=%d, or block does "
                "not divide max_len=%d"
                % (cfg.denoise_steps, cfg.block, cfg.max_len))
        if (cfg.denoise_steps + 1) ** cfg.block >= 2 ** 31:
            # the engine's packed read carries a block's passes as ONE
            # int32 a slot, a digit of base ``denoise_steps + 1`` a position
            raise ValueError("block=%d at denoise_steps=%d: the passes of "
                             "a block do not fit one int32"
                             % (cfg.block, cfg.denoise_steps))
        self.cfg = cfg
        #: what the cache holds K and V in (the tests' float32 runs pass
        #: float32; K and V are rounded to it before they are attended)
        self.cache_dtype = cache_dtype

    # -- the protocol ------------------------------------------------------
    def cache_spec(self):
        cfg = self.cfg
        return tuple(CacheLayer("full", cfg.max_len, cfg.kv_heads,
                                cfg.head_dim, self.cache_dtype, True)
                     for _ in range(cfg.layers))

    def extra_state(self):
        """The device counters (uint32, wrapping).  Counted here, in passes
        over live slots: picks routed to each held expert of each layer
        (every row of a live slot's open block, and of its pending one),
        picks made in all, ``rows`` stepped (live ones: open and pending),
        ``commit_rows`` (the pending ones among them), steps that stepped
        any, ``passes`` (live slot-passes) and ``rows_read`` (the cache
        rows a layer's attention read for them).  Counted by the engine's
        tail, which knows what a pass decided: ``commits`` (blocks whose
        final K and V a pass wrote), ``tokens_committed`` (tokens
        delivered), ``fixed_by_threshold`` and ``fixed_by_quota``
        (positions fixed, by which branch of the rule)."""
        return dict({name: jnp.zeros((), jnp.uint32) for name in _COUNTERS},
                    moe_picks=jnp.zeros((self.cfg.layers,
                                         self.cfg.experts_held), jnp.uint32))

    def counters(self, extra):
        """The extra state read back (whole numbers), with the gauges the
        engine publishes under ``gauges``: picks a held expert sees a step,
        the busiest held expert's picks over the mean's, tokens delivered
        a live slot-pass (``B / T`` with one position a pass) and the share
        of live slot-passes that carried a pending block's rows (``1 / T``
        then)."""
        picks = np.asarray(extra["moe_picks"], np.int64)
        out = {name: int(extra[name]) for name in _COUNTERS}
        out["moe_picks"] = picks.tolist()
        gauges = routing_gauges(picks, out["steps"])
        if out["passes"]:
            gauges["serving.decode.tokens_per_pass"] = \
                out["tokens_committed"] / out["passes"]
            gauges["serving.decode.commit_rows_share"] = \
                out["commit_rows"] / (self.cfg.block * out["passes"])
        if gauges:
            out["gauges"] = gauges
        return out

    def prefill(self, params, tokens, length):
        """One bucket-padded prompt ``tokens (P,)`` of ``length`` real
        tokens -> ``(first_block (B,) int32, ks, vs)``: K and V of every
        position of the bucket under ``M``, to write into a slot from row
        0 (the slot's length becomes ``length // B * B``: the rows of the
        whole blocks below it are what is kept), and the block that holds
        position ``length // B * B`` as generation finds it, the prompt's
        ``length % B`` last tokens and the mask token after them.  No
        logits: the published sampler uses a prompt for K and V alone."""
        cfg = self.cfg
        (p_len,) = tokens.shape
        if p_len % cfg.block:
            raise ValueError("a prefill bucket of %d tokens is not whole "
                             "blocks of %d" % (p_len, cfg.block))
        pos = jnp.arange(p_len)
        scale = 1.0 / math.sqrt(cfg.head_dim)
        ks, vs = [], []

        def attend(l, q, k, v):
            q, k, v = (jnp.swapaxes(a, 0, 1) for a in (q, k, v))
            ctx = flash_attention(
                q[None], k[None], v[None], causal=True, softmax_scale=scale,
                block_q=_BLOCK_Q, block_k=_BLOCK_K, block=cfg.block)[0]
            ks.append(k.astype(self.cache_dtype))
            vs.append(v.astype(self.cache_dtype))
            return jnp.swapaxes(ctx, 0, 1)

        x = params["embed"][tokens].astype(jnp.float32)
        # nothing reads the last layer's output, so what follows its K and
        # V is dead code to the compiler: the prefill of a pipeline's LAST
        # stage.  (A stage that hands its output on would return ``x`` and
        # run that layer whole; no caller here takes it.)
        for l, p in enumerate(params["layers"]):
            x = _block(cfg, l, p, x, pos, attend)
        masks = jnp.full((cfg.block,), cfg.mask_id, jnp.int32)
        tail = jax.lax.dynamic_slice(
            jnp.concatenate([tokens.astype(jnp.int32), masks]),
            (length // cfg.block * cfg.block,), (cfg.block,))
        first = jnp.where(jnp.arange(cfg.block) < length % cfg.block,
                          tail, masks)
        return first, tuple(ks), tuple(vs)

    def decode_step(self, params, cache_k, cache_v, block, lengths, active,
                    extra):
        """One pass for all ``S`` slots: ``block (S, B)`` int32 are the
        tokens of each slot's OPEN block (the mask token where a position
        is not fixed), at positions ``lengths .. lengths + B - 1``.  Their
        K/V go to those rows of each slot's cache and the ``B`` rows attend
        over everything up to ``lengths + B - 1``.  Returns ``(logits (S,
        B, vocab), cache_k, cache_v, extra)``: row ``[i, j]`` the logits of
        position ``lengths[i] + j``'s own token.

        The pass runs ``(S, 2B)`` rows.  ``extra["pending"] (S,)`` bool and
        ``extra["pending_block"] (S, B)`` int32 are the engine's: a slot's
        block that its last pass left whole and delivered, final tokens
        whose K and V the cache does not hold yet.  Its ``B`` rows ride
        this pass at positions ``lengths - B .. lengths - 1`` for their K
        and V alone, under the horizon ``lengths - 1`` (what a pass of
        their own would have seen); no logits are made of them.  The
        pending rows of a slot with nothing pending are DEAD: they are
        written where the open block's rows then overwrite them, take no
        pick in the expert layer and count in no counter.  In the last
        layer the pending rows stop at their K and V (the experts' picks
        counted there are the open rows')."""
        cfg = self.cfg
        s, b = block.shape
        group = cfg.heads // cfg.kv_heads
        at = jnp.clip(lengths, 0, cfg.max_len - b)
        pending = extra["pending"] & active
        below = jnp.where(pending, at - b, at)
        # the rows in the order (pending | open, slot, position)
        pos = (jnp.stack([below, at])[:, :, None]
               + jnp.arange(b)[None, None, :]).reshape(-1)
        horizon = jnp.stack([below, at], axis=1) + (b - 1)
        scale = 1.0 / math.sqrt(cfg.head_dim)
        new_k, new_v = list(cache_k), list(cache_v)
        live = active.astype(jnp.uint32)
        live_rows = jnp.repeat(jnp.concatenate([pending, active]), b)
        # a quarter of the slots carry a pending block (one pass in four
        # of a block of four): what the expert layer sizes its tiles by
        expected = s * b + s * b // cfg.denoise_steps
        last = cfg.layers - 1
        picks = []

        def by_slot(rows):
            """``(2 S B, n, d) -> two of (S, n, B, d)``: a slot's run of
            pending rows, and of open ones."""
            return jnp.swapaxes(rows.reshape(2, s, b, *rows.shape[1:]), 2, 3)

        def attend(l, q, k, v):
            # two runs of B rows a slot: a run of 2B from a row that only B
            # divides could cross a tile.  The open block's goes last, over
            # a dead run
            for new, cache, rows in ((new_k, cache_k[l], by_slot(k)),
                                     (new_v, cache_v[l], by_slot(v))):
                new[l] = write_slot_rows(
                    write_slot_rows(cache, rows[0], below), rows[1], at)
            # every row of a block sees the same rows: a slot's queries
            # ride as one group of ``2B x group`` over each K/V head, its
            # first half (the pending block's) under the lower horizon, and
            # the cache is read once for both.  (The last layer's are the
            # open block's alone.)
            n = q.shape[0] // (s * b)
            q = q.reshape(n, s, b, cfg.kv_heads, group, cfg.head_dim)
            ctx = decode_attention(
                jnp.transpose(q, (1, 3, 0, 2, 4, 5)).reshape(
                    s, cfg.kv_heads, n * b * group, cfg.head_dim),
                new_k[l], new_v[l], horizon if n == 2 else horizon[:, 1],
                scale)
            ctx = ctx.reshape(s, cfg.kv_heads, n, b, group, cfg.head_dim)
            return jnp.transpose(ctx, (2, 0, 3, 1, 4, 5)).reshape(
                n * s * b, cfg.heads, cfg.head_dim)

        def counts(l, chosen):
            picks.append(count_picks(
                cfg, chosen, (live_rows[s * b:] if l == last
                              else live_rows).astype(jnp.uint32)))

        x = params["embed"][jnp.concatenate(
            [extra["pending_block"], block]).reshape(-1)].astype(jnp.float32)
        for l, p in enumerate(params["layers"]):
            if l < last:
                x = _block(cfg, l, p, x, pos, attend, counts,
                           (live_rows, expected))
            else:
                # nothing reads the last layer's output of a pending row:
                # past its K and V the layer runs the open rows alone
                x = _block(cfg, l, p, x, pos, attend, counts,
                           (live_rows[s * b:], s * b), lead=s * b)
        with jax.named_scope("head"):
            logits = _mm(_rms(x, params["ln_f"], cfg.eps), params["head"])
        slots = live.sum()
        commit_rows = pending.sum().astype(jnp.uint32) * np.uint32(b)
        rows = slots * np.uint32(b) + commit_rows
        extra = dict(
            extra,
            moe_picks=extra["moe_picks"] + jnp.stack(picks),
            moe_picks_total=extra["moe_picks_total"] + np.uint32(cfg.top_k)
            * (rows * np.uint32(cfg.layers) - commit_rows),
            rows=extra["rows"] + rows,
            commit_rows=extra["commit_rows"] + commit_rows,
            steps=extra["steps"] + (slots > 0).astype(jnp.uint32),
            passes=extra["passes"] + slots,
            rows_read=extra["rows_read"]
            + (live * (at + b).astype(jnp.uint32)).sum())
        return logits.reshape(s, b, -1), tuple(new_k), tuple(new_v), extra
