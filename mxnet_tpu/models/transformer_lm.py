"""Decode-capable transformer LM — the served autoregressive workload.

``parallel/lm.py`` is the *training* flagship (dp x tp x pp x sp x ep in
one SPMD step); this module is its serving-side counterpart: a compact
decoder-only transformer whose forward math is split exactly along the
line a continuous-batching server needs (docs/serving.md "Continuous
batching & replica pool"):

* :func:`prefill_kv` — run the full prompt once, return the last-token
  logits plus the per-layer K/V rows to seed a slot of the engine's
  device-resident cache;
* :func:`decode_step_math` — ONE token for ALL ``S`` cache slots at
  once: write the incoming token's K/V into each slot's cache row
  (:func:`write_rows`), attend over ``positions <= length`` and produce ``(S, vocab)``
  logits.  Fixed shapes in, fixed shapes out — the function compiles
  once per ``(S, max_len)`` and never again
  (:mod:`mxnet_tpu.serving.decode` wraps it with sampling and slot
  state into the single jitted step);
* :func:`forward_logits` — plain batched teacher-forcing forward, the
  ground truth the decode path is pinned bit-compatible against
  (``tests/test_decode.py``: greedy decode == argmax of the full
  forward).

The math is deliberately single-device per replica — multi-replica
throughput comes from :class:`~mxnet_tpu.serving.pool.ReplicaPool`
spreading engines over ``jax.devices()``, not from sharding one model.
"""

from __future__ import annotations

import io
import json
from collections import namedtuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["LMConfig", "CacheLayer", "slot_shape", "DecodeModel",
           "init_params", "forward_logits", "prefill_kv",
           "write_rows", "decode_step_math", "prefill_kv_paged",
           "decode_step_paged", "params_to_blob", "params_from_blob"]

#: model hyperparameters; ``max_len`` bounds the KV cache (and therefore
#: prompt + generated length), ``eos_id`` is the token that retires a
#: sequence early
LMConfig = namedtuple("LMConfig", ["vocab", "embed", "heads", "layers",
                                   "ffn", "max_len", "eos_id"])


#: one layer of a model's cache, as the decode engine builds it (K and V
#: each, ``(slots,) + slot_shape(layer)`` of ``dtype``): ``kind`` "full"
#: (``rows`` = max_len, row ``p`` holds position ``p``) or "ring" (``rows``
#: = a window, row ``p % rows`` holds position ``p``); ``heads_major`` says
#: a slot is ``(kv_heads, rows, head_dim)`` and not ``(rows, kv_heads,
#: head_dim)``: the order in which the model's own attention reads it
CacheLayer = namedtuple("CacheLayer", ["kind", "rows", "kv_heads",
                                       "head_dim", "dtype", "heads_major"],
                        defaults=(False,))


def slot_shape(layer):
    """The shape of one slot of a :class:`CacheLayer`."""
    if layer.heads_major:
        return (layer.kv_heads, layer.rows, layer.head_dim)
    return (layer.rows, layer.kv_heads, layer.head_dim)


def init_params(cfg, seed=0, dtype=jnp.float32):
    """Parameter pytree (host -> the caller ``device_put``s it where the
    replica lives).  Per-layer weights are stacked on axis 0 so the
    pytree stays flat and a layer loop indexes rows."""
    if cfg.embed % cfg.heads:
        raise ValueError("embed=%d not divisible by heads=%d"
                         % (cfg.embed, cfg.heads))
    rs = np.random.RandomState(seed)

    def nrm(*shape, s=0.05):
        return jnp.asarray(rs.normal(0, s, shape).astype(np.float32),
                           dtype=dtype)

    L, E, F = cfg.layers, cfg.embed, cfg.ffn
    return {
        "embed": nrm(cfg.vocab, E),
        "pos": nrm(cfg.max_len, E),
        "head": nrm(E, cfg.vocab),
        "ln_f": jnp.ones((E,), dtype),
        "blocks": {
            "ln1": jnp.ones((L, E), dtype),
            "qkv_w": nrm(L, E, 3 * E),
            "out_w": nrm(L, E, E),
            "ln2": jnp.ones((L, E), dtype),
            "up_w": nrm(L, E, F),
            "down_w": nrm(L, F, E),
        },
    }


def _rmsnorm(x, g):
    return x * g * jax.lax.rsqrt(
        (x.astype(jnp.float32) ** 2).mean(-1, keepdims=True)
        + 1e-6).astype(x.dtype)


def _layer(blocks, l):
    return {k: v[l] for k, v in blocks.items()}


def forward_logits(cfg, params, tokens):
    """Teacher-forcing forward: ``tokens (B, T) int32 -> (B, T, vocab)``
    float32 logits — training/eval and the decode-parity ground truth."""
    b, t = tokens.shape
    pos = jnp.arange(t)
    x = params["embed"][tokens] + params["pos"][pos][None]
    causal = pos[None, :] <= pos[:, None]            # (q, k)
    hd = cfg.embed // cfg.heads
    scale = 1.0 / np.sqrt(hd)
    for l in range(cfg.layers):
        p = _layer(params["blocks"], l)
        h = _rmsnorm(x, p["ln1"])
        qkv = jnp.einsum("bte,ef->btf", h, p["qkv_w"])
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(a):
            return a.reshape(b, t, cfg.heads, hd)

        scores = jnp.einsum("bqhd,bkhd->bhqk", heads(q), heads(k)) * scale
        att = jax.nn.softmax(
            jnp.where(causal[None, None], scores, jnp.float32(-1e30)),
            axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", att, heads(v))
        x = x + jnp.einsum("bte,ef->btf",
                           ctx.reshape(b, t, cfg.embed), p["out_w"])
        h = _rmsnorm(x, p["ln2"])
        x = x + jnp.einsum("btf,fe->bte",
                           jax.nn.gelu(jnp.einsum("bte,ef->btf", h,
                                                  p["up_w"])), p["down_w"])
    x = _rmsnorm(x, params["ln_f"])
    return jnp.einsum("bte,ev->btv", x, params["head"]).astype(jnp.float32)


def prefill_kv(cfg, params, tokens, length):
    """One prompt through the model: ``tokens (P,) int32`` (bucket-padded,
    ``length`` real tokens) -> ``(last_logits (vocab,), ks, vs)`` where
    ``ks``/``vs`` are per-layer tuples of ``(P, heads, head_dim)`` cache
    rows for positions ``0..P-1``.  Rows past ``length`` hold pad-token
    K/V — the decode attention mask (``position <= slot length``) never
    reads them before the decode step itself overwrites them in place.
    """
    (p,) = tokens.shape
    pos = jnp.arange(p)
    x = params["embed"][tokens] + params["pos"][pos]
    causal = pos[None, :] <= pos[:, None]
    hd = cfg.embed // cfg.heads
    scale = 1.0 / np.sqrt(hd)
    ks, vs = [], []
    for l in range(cfg.layers):
        pl = _layer(params["blocks"], l)
        h = _rmsnorm(x, pl["ln1"])
        qkv = jnp.einsum("te,ef->tf", h, pl["qkv_w"])
        q, k, v = (a.reshape(p, cfg.heads, hd)
                   for a in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
        att = jax.nn.softmax(
            jnp.where(causal[None], scores, jnp.float32(-1e30)), axis=-1)
        ctx = jnp.einsum("hqk,khd->qhd", att, v)
        x = x + jnp.einsum("te,ef->tf",
                           ctx.reshape(p, cfg.embed), pl["out_w"])
        h = _rmsnorm(x, pl["ln2"])
        x = x + jnp.einsum("tf,fe->te",
                           jax.nn.gelu(jnp.einsum("te,ef->tf", h,
                                                  pl["up_w"])),
                           pl["down_w"])
        ks.append(k)
        vs.append(v)
    x = _rmsnorm(x, params["ln_f"])
    logits = jnp.einsum("te,ev->tv", x, params["head"]).astype(jnp.float32)
    last = jnp.take(logits, jnp.clip(length - 1, 0, p - 1), axis=0)
    return last, tuple(ks), tuple(vs)


@jax.jit
def write_rows(cache, rows, pos):
    """``cache (S, max_len, heads, head_dim)`` with ``rows[i]`` (of
    ``(S, heads, head_dim)``) written at ``[i, pos[i]]``; ``pos (S,)``
    lies within ``0..max_len-1``.  Equal, as an array, to
    ``cache.at[arange(S), pos].set(rows)``.

    One ``dynamic_update_slice`` a slot, and not that scatter: an
    update-slice is done in place in whatever layout the compiler keeps
    the cache in, while a scatter wants the cache row-major.  On the TPU
    the dense cache lives position-minor (what both attention
    contractions read), and the scatter cost two transposes of the whole
    array a layer and a step, most of the step's time (PERF.md, PR 25).
    Nothing here asks which layout or which backend it is: where the
    cache is kept row-major the update-slices are in place just the same.

    Jitted on its own so that a step traces the ``S`` update-slices once
    and not once a layer and array: an engine traces its step twice at
    every start, and that time is the server's set-up.
    """
    pieces = jnp.split(rows[:, None], cache.shape[0])
    for i, piece in enumerate(pieces):
        cache = jax.lax.dynamic_update_slice(
            cache, piece, (i, pos[i], 0, 0), allow_negative_indices=False)
    return cache


def decode_step_math(cfg, params, cache_k, cache_v, last_tok, lengths):
    """One decode token for all ``S`` slots.

    ``cache_k``/``cache_v``: per-layer tuples of ``(S, max_len, heads,
    head_dim)``; ``last_tok (S,) int32`` is each slot's most recent
    token (prompt tail after prefill, previous sample afterwards);
    ``lengths (S,) int32`` is each slot's cache fill — the position the
    incoming token's K/V is written to, and the inclusive attention
    horizon.  Returns ``(logits (S, vocab), new_cache_k, new_cache_v)``.

    Inactive slots ride along (fixed shape => no recompile): their
    row lands where the mask makes it unreachable until a real write
    replaces it, and their logits are discarded host-side.

    The rows go in through :func:`write_rows`, which assumes nothing
    about the layout the cache lives in on the device and is not a
    scatter, so the compiled step holds no copy of a cache-sized array.
    """
    (s, m) = cache_k[0].shape[:2]
    hd = cfg.embed // cfg.heads
    scale = 1.0 / np.sqrt(hd)
    kpos = jnp.arange(m)
    pos = jnp.clip(lengths, 0, cfg.max_len - 1)
    x = params["embed"][last_tok] + params["pos"][pos]
    new_k, new_v = [], []
    for l in range(cfg.layers):
        pl = _layer(params["blocks"], l)
        h = _rmsnorm(x, pl["ln1"])
        qkv = jnp.einsum("se,ef->sf", h, pl["qkv_w"])
        q, k, v = (a.reshape(s, cfg.heads, hd)
                   for a in jnp.split(qkv, 3, axis=-1))
        ck = write_rows(cache_k[l], k, pos)
        cv = write_rows(cache_v[l], v, pos)
        scores = jnp.einsum("shd,smhd->shm", q, ck) * scale
        mask = kpos[None, None, :] <= pos[:, None, None]
        att = jax.nn.softmax(
            jnp.where(mask, scores, jnp.float32(-1e30)), axis=-1)
        ctx = jnp.einsum("shm,smhd->shd", att, cv)
        x = x + jnp.einsum("se,ef->sf",
                           ctx.reshape(s, cfg.embed), pl["out_w"])
        h = _rmsnorm(x, pl["ln2"])
        x = x + jnp.einsum("sf,fe->se",
                           jax.nn.gelu(jnp.einsum("se,ef->sf", h,
                                                  pl["up_w"])),
                           pl["down_w"])
        new_k.append(ck)
        new_v.append(cv)
    x = _rmsnorm(x, params["ln_f"])
    logits = jnp.einsum("se,ev->sv", x, params["head"]).astype(jnp.float32)
    return logits, tuple(new_k), tuple(new_v)


def prefill_kv_paged(cfg, params, pool_k, pool_v, table, tokens, start,
                     length):
    """Suffix prefill through a block table — the paged twin of
    :func:`prefill_kv` (``mxnet_tpu.serving.kvblocks`` owns the block
    bookkeeping; this is pure math).

    ``pool_k``/``pool_v``: per-layer tuples of ``(num_blocks,
    block_size, heads, head_dim)`` pool rows; ``table (max_blocks,)
    int32`` maps the slot's logical block index to a pool row (0 = the
    reserved scratch block, where unallocated entries point).
    ``tokens (P,) int32`` is the bucket-padded transcript SUFFIX
    occupying absolute positions ``start .. start+P-1``: ``start = 0``
    is a cold prefill, ``start > 0`` is a prefix-cache hit that runs
    ZERO compute for the shared positions — their K/V is already
    resident in the table's blocks and is only gathered for attention.
    ``length`` is the absolute transcript length.  Returns
    ``(last_logits (vocab,), new_pool_k, new_pool_v)``.

    Bit-identity with the dense path is by construction: K/V rows are
    scattered into the pool, gathered back through the table and
    statically sliced to ``max_len``, so scores, mask and softmax see
    EXACTLY the shapes :func:`decode_step_math`'s attention sees; lanes
    past a row's horizon are exact zeros under the ``-1e30`` mask, and
    unallocated lanes read scratch garbage that the mask also zeroes.
    Bucket-pad rows scatter to the scratch block or to not-yet-read
    rows past ``length`` — the same never-read discipline as the dense
    prefill's pad rows.
    """
    (p,) = tokens.shape
    (mb,) = table.shape
    bs = pool_k[0].shape[1]
    m = cfg.max_len
    hd = cfg.embed // cfg.heads
    scale = 1.0 / np.sqrt(hd)
    pos = start + jnp.arange(p)            # absolute positions
    posc = jnp.clip(pos, 0, m - 1)         # only pad rows ever clamp
    blk = table[posc // bs]
    off = posc % bs
    x = params["embed"][tokens] + params["pos"][posc]
    kpos = jnp.arange(m)
    mask = kpos[None, :] <= pos[:, None]
    new_k, new_v = [], []
    for l in range(cfg.layers):
        pl = _layer(params["blocks"], l)
        h = _rmsnorm(x, pl["ln1"])
        qkv = jnp.einsum("te,ef->tf", h, pl["qkv_w"])
        q, k, v = (a.reshape(p, cfg.heads, hd)
                   for a in jnp.split(qkv, 3, axis=-1))
        pk = pool_k[l].at[blk, off].set(k)
        pv = pool_v[l].at[blk, off].set(v)
        ck = pk[table].reshape(mb * bs, cfg.heads, hd)[:m]
        cv = pv[table].reshape(mb * bs, cfg.heads, hd)[:m]
        scores = jnp.einsum("qhd,khd->hqk", q, ck) * scale
        att = jax.nn.softmax(
            jnp.where(mask[None], scores, jnp.float32(-1e30)), axis=-1)
        ctx = jnp.einsum("hqk,khd->qhd", att, cv)
        x = x + jnp.einsum("te,ef->tf",
                           ctx.reshape(p, cfg.embed), pl["out_w"])
        h = _rmsnorm(x, pl["ln2"])
        x = x + jnp.einsum("tf,fe->te",
                           jax.nn.gelu(jnp.einsum("te,ef->tf", h,
                                                  pl["up_w"])),
                           pl["down_w"])
        new_k.append(pk)
        new_v.append(pv)
    x = _rmsnorm(x, params["ln_f"])
    logits = jnp.einsum("te,ev->tv", x, params["head"]).astype(jnp.float32)
    last = jnp.take(logits, jnp.clip(length - 1 - start, 0, p - 1),
                    axis=0)
    return last, tuple(new_k), tuple(new_v)


def decode_step_paged(cfg, params, pool_k, pool_v, tables, last_tok,
                      lengths):
    """One decode token for all ``S`` slots through per-slot block
    tables — the paged twin of :func:`decode_step_math`.

    ``tables (S, max_blocks) int32`` names each slot's pool rows; the
    incoming token's K/V scatters into the block covering position
    ``lengths`` (the engine allocates that block before dispatch), the
    slot's whole table is gathered and statically sliced to
    ``(S, max_len)``, and attention proceeds exactly as the dense
    step's — same shapes, same mask, same floats.  Inactive slots hold
    all-zero tables: their scatter lands in the scratch block and their
    gathered lanes are mask-dead, the paged rendition of the dense
    step's unreachable-row idiom.  Fixed shapes throughout — ONE
    compile per ``(S, max_len, num_blocks, block_size)``, ever.
    """
    s, mb = tables.shape
    bs = pool_k[0].shape[1]
    m = cfg.max_len
    hd = cfg.embed // cfg.heads
    scale = 1.0 / np.sqrt(hd)
    rows = jnp.arange(s)
    kpos = jnp.arange(m)
    pos = jnp.clip(lengths, 0, m - 1)
    wblk = tables[rows, pos // bs]
    woff = pos % bs
    x = params["embed"][last_tok] + params["pos"][pos]
    new_k, new_v = [], []
    for l in range(cfg.layers):
        pl = _layer(params["blocks"], l)
        h = _rmsnorm(x, pl["ln1"])
        qkv = jnp.einsum("se,ef->sf", h, pl["qkv_w"])
        q, k, v = (a.reshape(s, cfg.heads, hd)
                   for a in jnp.split(qkv, 3, axis=-1))
        pk = pool_k[l].at[wblk, woff].set(k)
        pv = pool_v[l].at[wblk, woff].set(v)
        ck = pk[tables].reshape(s, mb * bs, cfg.heads, hd)[:, :m]
        cv = pv[tables].reshape(s, mb * bs, cfg.heads, hd)[:, :m]
        scores = jnp.einsum("shd,smhd->shm", q, ck) * scale
        mask = kpos[None, None, :] <= pos[:, None, None]
        att = jax.nn.softmax(
            jnp.where(mask, scores, jnp.float32(-1e30)), axis=-1)
        ctx = jnp.einsum("shm,smhd->shd", att, cv)
        x = x + jnp.einsum("se,ef->sf",
                           ctx.reshape(s, cfg.embed), pl["out_w"])
        h = _rmsnorm(x, pl["ln2"])
        x = x + jnp.einsum("sf,fe->se",
                           jax.nn.gelu(jnp.einsum("se,ef->sf", h,
                                                  pl["up_w"])),
                           pl["down_w"])
        new_k.append(pk)
        new_v.append(pv)
    x = _rmsnorm(x, params["ln_f"])
    logits = jnp.einsum("se,ev->sv", x, params["head"]).astype(jnp.float32)
    return logits, tuple(new_k), tuple(new_v)


class DecodeModel:
    """This LM as the decode engine's model protocol
    (:mod:`mxnet_tpu.serving.decode`): a float32 cache of ``layers`` full
    layers, the functions above behind the protocol's names, no extra
    device state, and the paged twins."""

    def __init__(self, cfg):
        self.cfg = cfg

    def cache_spec(self):
        cfg = self.cfg
        return tuple(CacheLayer("full", cfg.max_len, cfg.heads,
                                cfg.embed // cfg.heads, jnp.float32)
                     for _ in range(cfg.layers))

    def extra_state(self):
        return None

    def prefill(self, params, tokens, length):
        return prefill_kv(self.cfg, params, tokens, length)

    def decode_step(self, params, cache_k, cache_v, last_tok, lengths,
                    active, extra):
        del active
        logits, cache_k, cache_v = decode_step_math(
            self.cfg, params, cache_k, cache_v, last_tok, lengths)
        return logits, cache_k, cache_v, extra

    def prefill_paged(self, params, pool_k, pool_v, table, tokens, start,
                      length):
        return prefill_kv_paged(self.cfg, params, pool_k, pool_v, table,
                                tokens, start, length)

    def decode_step_paged(self, params, pool_k, pool_v, tables, last_tok,
                          lengths):
        return decode_step_paged(self.cfg, params, pool_k, pool_v, tables,
                                 last_tok, lengths)


def params_to_blob(cfg, params):
    """Serialize ``(cfg, params)`` to one npz blob (the serving publish
    payload format, :func:`mxnet_tpu.serving.save_model` convention)."""
    flat = {"__config__": np.frombuffer(
        json.dumps(cfg._asdict()).encode(), np.uint8)}
    for k, v in params.items():
        if isinstance(v, dict):
            for k2, v2 in v.items():
                flat["%s.%s" % (k, k2)] = np.asarray(v2)
        else:
            flat[k] = np.asarray(v)
    buf = io.BytesIO()
    np.savez(buf, **flat)
    return buf.getvalue()


def params_from_blob(blob):
    """Inverse of :func:`params_to_blob`: ``(cfg, params)``."""
    with np.load(io.BytesIO(blob)) as z:
        cfg = LMConfig(**json.loads(bytes(z["__config__"]).decode()))
        params = {"blocks": {}}
        for k in z.files:
            if k == "__config__":
                continue
            if k.startswith("blocks."):
                params["blocks"][k.split(".", 1)[1]] = jnp.asarray(z[k])
            else:
                params[k] = jnp.asarray(z[k])
    return cfg, params
