"""Decode-capable transformer LM — the served autoregressive workload.

``parallel/lm.py`` is the *training* flagship (one SPMD step over dp x tp
x pp x sp x ep); this is its serving-side counterpart, one device a replica
(:class:`~mxnet_tpu.serving.pool.ReplicaPool` spreads engines over devices).

The layer is written once, in :func:`_block`, which takes its cache access
as an argument.  The five entry points are what a continuous-batching
server needs (docs/serving.md) and differ in that access alone: teacher
forcing (:func:`forward_logits`, which ``tests/test_decode.py`` pins greedy
decode against), a prompt into a slot and one token for every slot
(:func:`prefill_kv`, :func:`decode_step_math`), and the same two through a
block pool (:func:`prefill_kv_paged`, :func:`decode_step_paged`).
"""

import functools
import io
import json
from collections import namedtuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["LMConfig", "CacheLayer", "StateLayer", "LatentLayer",
           "slot_shape",
           "slot_arrays", "DecodeModel",
           "init_params", "forward_logits", "prefill_kv",
           "write_rows", "ladder", "attended_rows", "decode_step_math",
           "prefill_kv_paged",
           "decode_step_paged", "params_to_blob", "params_from_blob"]

#: model hyperparameters; ``max_len`` bounds the KV cache (so prompt +
#: generated length), ``eos_id`` is the token that retires a sequence early
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


#: one layer's recurrent slot state, as the decode engine builds it: two
#: per-slot arrays of fixed shape, ``(slots,) + shapes[i]`` of ``dtypes[i]``
#: (a state-space layer's state and the tail of its convolution).  ``kind``
#: is "state": no length masks it, so an admission overwrites both whole
StateLayer = namedtuple("StateLayer", ["kind", "shapes", "dtypes"])


#: one layer's latent slot state, as the decode engine builds it: ``kind``
#: "latent"; of each of ``rows`` positions one compressed row that stands
#: for the keys and the values of every head, and beside it the one rotated
#: key all heads read: two arrays ``(1, rows, widths[i])`` of ``dtype`` a
#: slot (the 1 where a K or a V has its heads: the row writer's layout).
#: Row ``p`` holds position ``p``; a session's length hides what the slot's
#: last session left, and an admission writes from row 0, as in a "full"
#: layer.  ``widths`` are what lies in memory, padding to whole lanes
#: included where a model pads
LatentLayer = namedtuple("LatentLayer", ["kind", "rows", "widths", "dtype"])


def slot_shape(layer):
    """The shape of one slot of a :class:`CacheLayer`."""
    if layer.heads_major:
        return (layer.kv_heads, layer.rows, layer.head_dim)
    return (layer.rows, layer.kv_heads, layer.head_dim)


def slot_arrays(entry):
    """``((shape, dtype), (shape, dtype))`` of the two per-slot arrays an
    entry of a cache specification describes: a :class:`CacheLayer`'s K
    and V, a :class:`StateLayer`'s own two, a :class:`LatentLayer`'s
    latent rows and rotated key rows."""
    if entry.kind == "state":
        return tuple(zip(entry.shapes, entry.dtypes))
    if entry.kind == "latent":
        return tuple(((1, entry.rows, w), entry.dtype)
                     for w in entry.widths)
    return ((slot_shape(entry), entry.dtype),) * 2


def init_params(cfg, seed=0, dtype=jnp.float32):
    """Parameter pytree on the host (the caller ``device_put``s it where
    the replica lives); per-layer weights are stacked on axis 0."""
    if cfg.embed % cfg.heads:
        raise ValueError("embed=%d not divisible by heads=%d"
                         % (cfg.embed, cfg.heads))
    rs = np.random.RandomState(seed)

    def nrm(*shape, s=0.05):
        return jnp.asarray(rs.normal(0, s, shape).astype(np.float32),
                           dtype=dtype)

    L, E, F = cfg.layers, cfg.embed, cfg.ffn
    return {
        "embed": nrm(cfg.vocab, E), "pos": nrm(cfg.max_len, E),
        "head": nrm(E, cfg.vocab), "ln_f": jnp.ones((E,), dtype),
        "blocks": {
            "ln1": jnp.ones((L, E), dtype), "qkv_w": nrm(L, E, 3 * E),
            "out_w": nrm(L, E, E), "ln2": jnp.ones((L, E), dtype),
            "up_w": nrm(L, E, F), "down_w": nrm(L, F, E)}}


def _rmsnorm(x, g):
    return x * g * jax.lax.rsqrt(
        (x.astype(jnp.float32) ** 2).mean(-1, keepdims=True)
        + 1e-6).astype(x.dtype)


def _block(cfg, pl, x, attend):
    """One layer over rows ``x (..., embed)``, whatever the leading axes.
    ``attend(q, k, v)`` is the caller's cache access: handed this layer's
    ``q``/``k``/``v (..., heads, head_dim)``, it returns ``q``'s context."""
    h = _rmsnorm(x, pl["ln1"])
    qkv = jnp.einsum("...e,ef->...f", h, pl["qkv_w"])
    q, k, v = (a.reshape(x.shape[:-1] + (cfg.heads, cfg.embed // cfg.heads))
               for a in jnp.split(qkv, 3, axis=-1))
    ctx = attend(q, k, v)
    x = x + jnp.einsum("...e,ef->...f", ctx.reshape(x.shape), pl["out_w"])
    h = _rmsnorm(x, pl["ln2"])
    up = jax.nn.gelu(jnp.einsum("...e,ef->...f", h, pl["up_w"]))
    return x + jnp.einsum("...f,fe->...e", up, pl["down_w"])


def _blocks(cfg, params, x, attend):
    """Every layer in turn; ``attend(l, q, k, v)`` is told which."""
    for l in range(cfg.layers):
        pl = {name: w[l] for name, w in params["blocks"].items()}
        x = _block(cfg, pl, x, functools.partial(attend, l))
    return x


def _embed(params, tokens, pos):
    return params["embed"][tokens] + params["pos"][pos]


def _logits(params, x):
    x = _rmsnorm(x, params["ln_f"])
    return jnp.einsum("...e,ev->...v", x, params["head"]).astype(jnp.float32)


def _attend_rows(q, k, v, mask):
    """Query rows ``q (..., Q, heads, d)`` over key rows ``k``/``v (..., K,
    heads, d)`` where ``mask (Q, K)`` says so."""
    scores = jnp.einsum("...qhd,...khd->...hqk", q, k) \
        * (1.0 / np.sqrt(q.shape[-1]))
    att = jax.nn.softmax(
        jnp.where(mask[None], scores, jnp.float32(-1e30)), axis=-1)
    return jnp.einsum("...hqk,...khd->...qhd", att, v)


#: positions in one lane tile of the dense cache as the chip keeps it
#: (positions-minor): a prefix of whole tiles is read without the rest
_TILE = 128
#: the most rungs a ladder has: one branch each, in every layer's attention
_RUNGS = 8


def ladder(max_len):
    """``(width, rungs)``: the prefixes of a slot's ``max_len`` positions
    the decode step's attention may read instead of all of them.  A rung
    every ``width`` positions, the last ``max_len`` itself; ``width`` is
    the fewest whole lane tiles that give at most ``_RUNGS`` rungs.  A
    function of ``max_len`` alone: 1024 gives 128, 256, ..., 1024; 128 or
    less gives one rung, and a step with one rung has no branch."""
    width = -(-max_len // (_TILE * _RUNGS)) * _TILE
    return width, tuple(range(width, max_len, width)) + (max_len,)


def attended_rows(max_len, longest):
    """The rung a step reads whose longest active slot holds ``longest``
    rows: the host's reading of what :func:`_rung` chooses on the device."""
    width, rungs = ladder(max_len)
    return rungs[min(max(longest, 0), max_len - 1) // width]


def _rung(max_len, pos, active):
    """Which rung of :func:`ladder` holds every active slot's inclusive
    horizon ``pos (S,)``, or None where the ladder has one rung.  A slot
    that is not active (``active`` None: all are) holds nobody's session:
    its stale length does not hold the ladder up, and its row of the
    step's output is the host's to drop."""
    from ..ops.registry import count_kernel_path

    width, rungs = ladder(max_len)
    if len(rungs) == 1:
        count_kernel_path("attend_slots", "whole", "one_rung")
        return None
    count_kernel_path("attend_slots", "ladder", "ok")
    live = pos if active is None else jnp.where(active, pos, 0)
    return live.max() // width


def _attend_slots(q, kt, vt, pos, rung=None):
    """One query a slot, ``q (S, heads, d)``, over that slot's rows
    ``kt``/``vt (S, heads, d, M)``, positions last, at positions ``<= pos
    (S,)``.  ``rung`` (:func:`_rung`) says how many of the ``M`` rows any
    slot's horizon reaches: the branch taken reads that prefix alone (None:
    ``M`` is one rung, and there is no branch).  A row above a slot's
    horizon has weight exactly 0, so every rung that holds the horizons
    gives the context all ``M`` rows give, up to the order of a sum of
    zeros.

    Positions last is the order the chip keeps the dense cache in: there
    the step's transpose moves nothing, a prefix of whole lane tiles is a
    slice the reading fusion takes in, and no branch holds a copy (handed
    ``(S, M, heads, d)`` every branch copied both arrays whole: the v5e
    compiler's layouts inside a conditional).  Both products are a float32
    multiply and sum, what the compiler makes of an einsum of one query a
    slot outside a branch: inside one it made the scores a matrix product
    over K and q rounded to bfloat16."""
    scale = 1.0 / np.sqrt(q.shape[-1])

    def over(rows):
        def attend(q, kt, vt):
            scores = (q[..., None] * kt[..., :rows]).sum(2) * scale
            mask = jnp.arange(rows) <= pos[:, None, None]
            att = jax.nn.softmax(
                jnp.where(mask, scores, jnp.float32(-1e30)), axis=-1)
            return (att[:, :, None] * vt[..., :rows]).sum(3)
        return attend

    if rung is None:
        return over(kt.shape[-1])(q, kt, vt)
    return jax.lax.switch(
        rung, [over(rows) for rows in ladder(kt.shape[-1])[1]], q, kt, vt)


def forward_logits(cfg, params, tokens):
    """Teacher-forcing forward: ``tokens (B, T) int32 -> (B, T, vocab)``
    float32 logits — training/eval and the decode-parity ground truth."""
    pos = jnp.arange(tokens.shape[1])
    x = _embed(params, tokens, pos)
    causal = pos[None, :] <= pos[:, None]            # (q, k)
    return _logits(params, _blocks(
        cfg, params, x, lambda l, q, k, v: _attend_rows(q, k, v, causal)))


def prefill_kv(cfg, params, tokens, length):
    """One prompt through the model: ``tokens (P,) int32`` (bucket-padded,
    ``length`` real tokens) -> ``(last_logits (vocab,), ks, vs)``, ``ks``/
    ``vs`` per-layer tuples of ``(P, heads, head_dim)`` cache rows for
    positions ``0..P-1``.  Rows past ``length`` hold pad-token K/V, which
    the decode step's mask (``position <= slot length``) never reads
    before the step itself overwrites them."""
    (p,) = tokens.shape
    pos = jnp.arange(p)
    x = _embed(params, tokens, pos)
    causal = pos[None, :] <= pos[:, None]
    ks, vs = [], []

    def attend(l, q, k, v):
        ks.append(k)
        vs.append(v)
        return _attend_rows(q, k, v, causal)

    logits = _logits(params, _blocks(cfg, params, x, attend))
    last = jnp.take(logits, jnp.clip(length - 1, 0, p - 1), axis=0)
    return last, tuple(ks), tuple(vs)


def _through_cache(cfg, params, x, cache_k, cache_v, write, attend):
    """Rows ``x`` through layers that each ``write(array, rows)`` their K/V
    into their two cache arrays, then ``attend(q, array_k, array_v)``:
    ``(logits, new_k, new_v)``.  The layouts differ in the two callbacks."""
    new_k, new_v = list(cache_k), list(cache_v)

    def through(l, q, k, v):
        new_k[l], new_v[l] = write(cache_k[l], k), write(cache_v[l], v)
        return attend(q, new_k[l], new_v[l])

    logits = _logits(params, _blocks(cfg, params, x, through))
    return logits, tuple(new_k), tuple(new_v)


@jax.jit
def write_rows(cache, rows, pos):
    """``cache (S, max_len, heads, head_dim)`` with ``rows[i]`` (of ``(S,
    heads, head_dim)``) at ``[i, pos[i]]``, ``pos (S,)`` within ``0..
    max_len-1``: as an array, ``cache.at[arange(S), pos].set(rows)``.

    One ``dynamic_update_slice`` a slot, not that scatter: it is in place
    in whatever layout the compiler keeps the cache in, while a scatter
    wants it row-major; on the TPU, where the dense cache lives
    position-minor, that cost two transposes of the whole array a layer
    and a step (PERF.md, PR 25).  Jitted on its own so that a step traces
    the ``S`` update-slices once, not once a layer and array: an engine
    traces its step twice at every start, the server's set-up."""
    pieces = jnp.split(rows[:, None], cache.shape[0])
    for i, piece in enumerate(pieces):
        cache = jax.lax.dynamic_update_slice(
            cache, piece, (i, pos[i], 0, 0), allow_negative_indices=False)
    return cache


def decode_step_math(cfg, params, cache_k, cache_v, last_tok, lengths,
                     active=None):
    """One decode token for all ``S`` slots.  ``cache_k``/``cache_v``:
    per-layer tuples of ``(S, max_len, heads, head_dim)``; ``last_tok (S,)
    int32`` is each slot's most recent token; ``lengths (S,) int32`` its
    cache fill: where the incoming K/V goes (:func:`write_rows`: no copy
    of a cache-sized array) and the inclusive attention horizon.  Returns
    ``(logits (S, vocab), new_cache_k, new_cache_v)``.  Inactive slots ride
    along: their row lands where the mask hides it until a real write
    replaces it, and the host drops their logits.  Attention reads the
    rows up to the longest horizon among the slots ``active (S,) bool``
    names (None: all), rounded up to a rung of :func:`ladder`, chosen on
    the device once a step."""
    pos = jnp.clip(lengths, 0, cfg.max_len - 1)
    rung = _rung(cache_k[0].shape[1], pos, active)
    return _through_cache(
        cfg, params, _embed(params, last_tok, pos), cache_k, cache_v,
        lambda cache, rows: write_rows(cache, rows, pos),
        lambda q, ck, cv: _attend_slots(q, ck.transpose(0, 2, 3, 1),
                                        cv.transpose(0, 2, 3, 1), pos, rung))


def _held(pool, table, m):
    """The first ``m`` positions ``table (..., max_blocks)`` names in
    ``pool (num_blocks, block_size, heads, d)``: ``(..., m, heads, d)``."""
    shape = table.shape[:-1] + (-1,) + pool.shape[2:]
    return pool[table].reshape(shape)[..., :m, :, :]


def prefill_kv_paged(cfg, params, pool_k, pool_v, table, tokens, start,
                     length):
    """The paged twin of :func:`prefill_kv`, for a transcript's suffix
    (``serving/kvblocks.py`` owns the bookkeeping).  ``pool_k``/``pool_v``:
    per-layer tuples of ``(num_blocks, block_size, heads, head_dim)``;
    ``table (max_blocks,) int32`` maps the slot's logical block to a pool
    row (0 = the scratch block, where unallocated entries point); ``tokens
    (P,) int32``, bucket-padded, sit at absolute positions ``start ..
    start+P-1`` (``start > 0``: a prefix-cache hit, whose shared positions
    are only gathered); ``length`` is the whole transcript's.  Returns
    ``(last_logits (vocab,), new_pool_k, new_pool_v)``.  Rows are scattered
    into the pool, gathered back through the table and sliced to
    ``max_len``, so the floats are the dense path's: lanes past a row's
    horizon and unallocated lanes are exact zeros under the mask; pad rows
    scatter to the scratch block or past ``length``, read by nobody."""
    (p,) = tokens.shape
    bs = pool_k[0].shape[1]
    m = cfg.max_len
    pos = start + jnp.arange(p)            # absolute positions
    posc = jnp.clip(pos, 0, m - 1)         # only pad rows ever clamp
    blk = table[posc // bs]
    off = posc % bs
    x = _embed(params, tokens, posc)
    mask = jnp.arange(m)[None, :] <= pos[:, None]
    logits, pool_k, pool_v = _through_cache(
        cfg, params, x, pool_k, pool_v,
        lambda pool, rows: pool.at[blk, off].set(rows),
        lambda q, pk, pv: _attend_rows(q, _held(pk, table, m),
                                       _held(pv, table, m), mask))
    last = jnp.take(logits, jnp.clip(length - 1 - start, 0, p - 1), axis=0)
    return last, pool_k, pool_v


def decode_step_paged(cfg, params, pool_k, pool_v, tables, last_tok,
                      lengths):
    """The paged twin of :func:`decode_step_math`.  ``tables (S,
    max_blocks) int32`` names each slot's pool rows; the incoming K/V
    scatters into the block covering position ``lengths`` (the engine
    allocates it before dispatch), the slot's table is gathered and sliced
    to ``(S, max_len)``, and the attention is the dense step's over every
    row (the lines of its last rung, with no branch): the gather has moved all
    ``max_len`` rows of every slot before attention reads one, and a
    gathered array is not kept positions last, so a branch a rung would
    copy it to read less of it.  Inactive slots hold all-zero tables:
    their scatter lands in the scratch block, their lanes are mask-dead."""
    from ..ops.registry import count_kernel_path

    bs = pool_k[0].shape[1]
    m = cfg.max_len
    rows = jnp.arange(tables.shape[0])
    pos = jnp.clip(lengths, 0, m - 1)
    wblk = tables[rows, pos // bs]
    woff = pos % bs
    count_kernel_path("attend_slots", "whole", "gathered")
    return _through_cache(
        cfg, params, _embed(params, last_tok, pos), pool_k, pool_v,
        lambda pool, rows: pool.at[wblk, woff].set(rows),
        lambda q, pk, pv: _attend_slots(
            q, _held(pk, tables, m).transpose(0, 2, 3, 1),
            _held(pv, tables, m).transpose(0, 2, 3, 1), pos))


class DecodeModel:
    """This LM as the decode engine's model protocol (:mod:`mxnet_tpu.
    serving.decode`): a float32 cache of ``layers`` full layers, the
    functions above under the protocol's names, no extra device state.
    ``attended_rows(longest)`` is the protocol's optional reading of how
    many of a slot's rows a step's attention reads."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.prefill = functools.partial(prefill_kv, cfg)
        self.prefill_paged = functools.partial(prefill_kv_paged, cfg)
        self.decode_step_paged = functools.partial(decode_step_paged, cfg)
        self.attended_rows = functools.partial(attended_rows, cfg.max_len)

    def cache_spec(self):
        cfg = self.cfg
        return tuple(CacheLayer("full", cfg.max_len, cfg.heads,
                                cfg.embed // cfg.heads, jnp.float32)
                     for _ in range(cfg.layers))

    def extra_state(self):
        return None

    def decode_step(self, params, cache_k, cache_v, last_tok, lengths,
                    active, extra):
        return decode_step_math(self.cfg, params, cache_k, cache_v,
                                last_tok, lengths, active) + (extra,)


def params_to_blob(cfg, params):
    """Serialize ``(cfg, params)`` to one npz blob (the serving publish
    payload format, :func:`mxnet_tpu.serving.save_model` convention)."""
    flat = {"__config__": np.frombuffer(
        json.dumps(cfg._asdict()).encode(), np.uint8)}
    flat.update((k, v) for k, v in params.items() if k != "blocks")
    flat.update(("blocks." + k, v) for k, v in params["blocks"].items())
    buf = io.BytesIO()
    np.savez(buf, **flat)
    return buf.getvalue()


def params_from_blob(blob):
    """Inverse of :func:`params_to_blob`: ``(cfg, params)``."""
    with np.load(io.BytesIO(blob)) as z:
        cfg = LMConfig(**json.loads(bytes(z["__config__"]).decode()))
        flat = {k: jnp.asarray(z[k]) for k in z.files if k != "__config__"}
    params = {k: v for k, v in flat.items() if "." not in k}
    params["blocks"] = {k.split(".", 1)[1]: v for k, v in flat.items()
                        if k.startswith("blocks.")}
    return cfg, params
