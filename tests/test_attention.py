"""Flash attention, decode attention + ring attention tests.

Numerics oracle is the quadratic reference attention; the blockwise scan,
the Pallas kernel (interpret mode on CPU), and the ring-parallel version
must all agree with it, forward and backward — the TPU analog of the
reference's cross-backend ``check_consistency`` harness
(``python/mxnet/test_utils.py:677``).
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops.attention import (
    _attn_reference, _decode_pallas, _decode_xla, _flash_pallas,
    _flash_scan, _slot_write_pallas, _slot_write_xla, decode_attention,
    decode_attention_plan, flash_attention, write_slot_rows,
    write_slot_rows_plan)


def _rand_qkv(b=2, h=3, lq=64, lk=64, d=16, dtype=np.float32, seed=0):
    rs = np.random.RandomState(seed)
    q = rs.normal(0, 1, (b, h, lq, d)).astype(dtype)
    k = rs.normal(0, 1, (b, h, lk, d)).astype(dtype)
    v = rs.normal(0, 1, (b, h, lk, d)).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("lk,block_k", [(64, 16), (70, 32), (128, 128)])
def test_flash_scan_matches_reference(causal, lk, block_k):
    q, k, v = _rand_qkv(lk=lk)
    out, lse = _flash_scan(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           causal, 1.0 / np.sqrt(16), block_k=block_k)
    ref = _attn_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # lse sanity: logsumexp of masked scores
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(16)
    if causal:
        mask = np.arange(64)[:, None] >= np.arange(lk)[None, :]
        s = np.where(mask, s, -1e30)
    ref_lse = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) + s.max(-1)
    np.testing.assert_allclose(np.asarray(lse), ref_lse, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads_match_reference(causal):
    q, k, v = _rand_qkv(b=1, h=2, lq=48, lk=48, d=8)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_k=16) ** 2).sum()

    def loss_ref(q, k, v):
        return (_attn_reference(q, k, v, causal=causal) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_kernel_interpret(causal):
    """Pallas kernel correctness via interpreter (no TPU in CI)."""
    q, k, v = _rand_qkv(b=1, h=2, lq=32, lk=64, d=16, seed=3)
    out, lse = _flash_pallas(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             causal, 0.25, block_q=16, block_k=16,
                             interpret=True)
    ref = _attn_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal, scale=0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_op_registered():
    import mxnet_tpu as mx

    q, k, v = _rand_qkv(b=1, h=2, lq=16, lk=16, d=8)
    out = mx.nd.FlashAttention(mx.nd.array(q), mx.nd.array(k), mx.nd.array(v))
    ref = _attn_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    from mxnet_tpu.parallel import make_mesh, ring_self_attention

    mesh = make_mesh(8, axis_names=("data",))
    q, k, v = _rand_qkv(b=2, h=2, lq=64, lk=64, d=8, seed=7)
    out = ring_self_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              mesh, seq_axis="data", causal=causal)
    ref = _attn_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_grad():
    """Training path: gradients flow through ppermute ring."""
    from mxnet_tpu.parallel import make_mesh, ring_self_attention

    mesh = make_mesh(8, axis_names=("data",))
    q, k, v = _rand_qkv(b=1, h=1, lq=32, lk=32, d=8, seed=9)

    def loss_ring(q, k, v):
        return (ring_self_attention(q, k, v, mesh, "data", causal=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (_attn_reference(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_multihead_attention_op():
    import mxnet_tpu as mx

    b, l, e, h = 2, 12, 16, 4
    rs = np.random.RandomState(0)
    x = rs.normal(0, 1, (b, l, e)).astype(np.float32)
    w_qkv = rs.normal(0, 0.1, (3 * e, e)).astype(np.float32)
    w_out = rs.normal(0, 0.1, (e, e)).astype(np.float32)
    b_qkv = rs.normal(0, 0.1, (3 * e,)).astype(np.float32)
    b_out = rs.normal(0, 0.1, (e,)).astype(np.float32)
    out = mx.nd.MultiHeadAttention(
        mx.nd.array(x), mx.nd.array(x), mx.nd.array(w_qkv),
        mx.nd.array(w_out), mx.nd.array(b_qkv), mx.nd.array(b_out),
        num_heads=h)
    assert out.shape == (b, l, e)
    # numpy reference
    wq, wk, wv = np.split(w_qkv, 3, axis=0)
    bq, bk, bv = np.split(b_qkv, 3)
    qq = x @ wq.T + bq
    kk = x @ wk.T + bk
    vv = x @ wv.T + bv

    def heads(t):
        return t.reshape(b, l, h, e // h).transpose(0, 2, 1, 3)

    ref = _attn_reference(jnp.asarray(heads(qq)), jnp.asarray(heads(kk)),
                          jnp.asarray(heads(vv)))
    ref = np.asarray(ref).transpose(0, 2, 1, 3).reshape(b, l, e) @ w_out.T + b_out
    np.testing.assert_allclose(out.asnumpy(), ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(causal):
    """All-to-all sequence parallelism (SURVEY §5.7 alternative to ring):
    exact softmax, so it must match dense attention to tight tolerance."""
    from mxnet_tpu.parallel import make_mesh, ulysses_self_attention

    mesh = make_mesh(8, axis_names=("data",))
    q, k, v = _rand_qkv(b=2, h=8, lq=64, lk=64, d=8, seed=7)
    out = ulysses_self_attention(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), mesh, seq_axis="data",
                                 causal=causal)
    ref = _attn_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_attention_grad():
    from mxnet_tpu.parallel import make_mesh, ulysses_self_attention

    mesh = make_mesh(8, axis_names=("data",))
    q, k, v = _rand_qkv(b=1, h=8, lq=32, lk=32, d=8, seed=9)

    def loss_u(q, k, v):
        return (ulysses_self_attention(q, k, v, mesh, "data",
                                       causal=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (_attn_reference(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(loss_u, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_ulysses_head_count_guard():
    from mxnet_tpu.parallel import make_mesh, ulysses_self_attention

    mesh = make_mesh(8, axis_names=("data",))
    q, k, v = _rand_qkv(b=1, h=2, lq=32, lk=32, d=8, seed=3)  # 2 % 8 != 0
    with pytest.raises(ValueError, match="n_heads"):
        ulysses_self_attention(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), mesh, seq_axis="data")


@contextlib.contextmanager
def _counting(label):
    """Yields a function that gives how often ``ops.kernel_path{label}``
    was counted since the block was entered."""
    def total():
        return telemetry.snapshot()["counters"].get(
            "ops.kernel_path", {}).get(label, 0)

    was = telemetry.enabled()
    telemetry.enable()
    before = total()
    try:
        yield lambda: total() - before
    finally:
        if not was:
            telemetry.disable()


# -- decode attention ----------------------------------------------------------
_ROWS, _CHUNK, _PIECE = 64, 32, 16


def _decode_case(lengths, group, d, dtype, seed=0, rows=_ROWS):
    """``(q, clean K, clean V, K and V with NaN in every row above each
    slot's length, lengths)``: 2 K/V heads, ``rows`` rows a slot."""
    rs = np.random.RandomState(seed)
    s = len(lengths)
    q, k, v = (jnp.asarray(rs.normal(0, 1, shape), dtype) for shape in
               [(s, 2, group, d)] + [(s, 2, rows, d)] * 2)
    lengths = jnp.asarray(lengths, jnp.int32)
    dead = (jnp.arange(rows)[None, :] > lengths[:, None])[:, None, :, None]
    return (q, k, v, jnp.where(dead, jnp.nan, k), jnp.where(dead, jnp.nan, v),
            lengths)


def _check_decode_kernel(lengths, group, d, dtype, tol, rows, chunk, piece):
    """The kernel (interpreter) against the two einsums and the softmax
    over every row.  The cache the kernel is given holds NaN in every row
    above each slot's length: a chunk or a piece it should not copy, or a
    row of the length's own piece that it should mask, would show in the
    result."""
    q, k, v, k_nan, v_nan, lengths = _decode_case(lengths, group, d, dtype,
                                                  rows=rows)
    want = _decode_xla(q, k, v, lengths, 0.25)
    got = _decode_pallas(q, k_nan, v_nan, lengths, 0.25, chunk, piece,
                         interpret=True)
    assert got.dtype == jnp.float32 and got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("group,d", [(1, 64), (8, 64), (1, 128), (8, 128)])
@pytest.mark.parametrize("lengths", [
    [0, 1, _PIECE - 1, _PIECE, _CHUNK - 1, _CHUNK, _ROWS - 1],  # the edges
    [0, 0, 0], [_ROWS - 1] * 3, [37] * 4,               # all equal
    [5, 50, 21, 33, 62, 16, 47],                        # all different
], ids=["edges", "empty", "full", "equal", "different"])
def test_decode_kernel_interpret_reads_no_row_above_a_length(
        lengths, group, d, dtype, tol):
    _check_decode_kernel(lengths, group, d, dtype, tol, _ROWS, _CHUNK, _PIECE)


def _walk_lengths(kind, rows, chunk):
    """Lengths about a chunk's and a 128-row piece's edges, and the orders
    of slots in which a copy started for "the next slot" goes wrong: the
    last slot the longest or the shortest, a full slot before an empty one
    and after it."""
    if kind == "edges":
        return [min(n, rows - 1)
                for n in (0, chunk - 1, chunk, chunk + 1, 127, 128, rows - 1)]
    if kind == "last-longest":
        return [0, chunk - 1, 0, min(127, rows - 2), 5, 0, rows - 1]
    if kind == "last-shortest":
        return [rows - 1, min(chunk + 1, rows - 1), rows - 1, 3, rows - 1,
                rows - 1, 0]
    assert kind == "ring"
    # a ring's horizon ``min(pos, window - 1)``: under, at and past the wrap
    return [min(pos, rows - 1) for pos in
            (0, 7, rows - 2, rows - 1, rows, 2 * rows, 3 * rows + 5)]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("group", [4, 7, 8])
@pytest.mark.parametrize("kind", ["edges", "last-longest", "last-shortest",
                                  "ring"])
@pytest.mark.parametrize("rows,chunk,piece", [
    (64, 16, 16),       # a chunk is a piece: every turn one copy
    (64, 64, 8),        # one chunk a slot: every turn an edge of pieces
    (512, 128, 128), (512, 256, 128),   # the chip's pieces, whole lanes
], ids=["16x16", "64x8", "128x128", "256x128"])
def test_decode_kernel_walks_whole_chunks_then_the_edge_in_pieces(
        rows, chunk, piece, kind, group, dtype, tol):
    _check_decode_kernel(_walk_lengths(kind, rows, chunk), group, 128, dtype,
                         tol, rows, chunk, piece)


def _halves_einsum(q, k, v, horizons, scale):
    """Float32 einsums, a query at a time of its own horizon: the first
    half of a slot's group sees ``0 .. horizons[i, 0]``, the second ``0 ..
    horizons[i, 1]``."""
    q, k, v = (np.asarray(a, np.float32) for a in (q, k, v))
    out = np.zeros(q.shape, np.float32)
    g = q.shape[2]
    for i, pair in enumerate(np.asarray(horizons)):
        for j in range(g):
            n = int(pair[1 if j >= g // 2 else 0]) + 1
            s = np.einsum("kd,kmd->km", q[i, :, j], k[i, :, :n]) * scale
            p = np.exp(s - s.max(-1, keepdims=True))
            out[i, :, j] = np.einsum("km,kmd->kd",
                                     p / p.sum(-1, keepdims=True),
                                     v[i, :, :n])
    return out


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("case,group,rows,chunk,piece,horizons", [
    # SDAR's shape: two blocks of four positions, eight heads a K/V head;
    # the lower horizon one block below the upper, about a piece's and a
    # chunk's edges (the lower one the last row of the chunk before), and
    # a slot with nothing pending (both the same)
    ("sdar", 64, 512, 256, 128,
     [(3, 7), (123, 127), (127, 131), (251, 255), (255, 259), (259, 263),
      (507, 511), (7, 7), (300, 300)]),
    # any two horizons: a whole chunk that reaches above the lower one is
    # masked for the first half (and one far below the walk's edge)
    ("far", 8, 512, 128, 128,
     [(0, 511), (5, 300), (127, 128), (128, 400), (200, 255), (383, 384)]),
    # a ring's horizons ``min(pos, rows - 1)``
    ("ring", 16, 64, 32, 16, [(0, 3), (59, 63), (63, 63), (30, 34)]),
], ids=lambda c: c if isinstance(c, str) else None)
def test_decode_attention_with_a_horizon_a_half_of_the_group(
        case, group, rows, chunk, piece, horizons, dtype, tol):
    """The kernel (interpreter) and the plain path against float32 einsums
    of each query's own rows; the kernel's cache holds NaN in every row
    above the slot's UPPER horizon, and the rows between the two horizons
    hold values a first-half query must not see."""
    horizons = jnp.asarray(horizons, jnp.int32)
    q, k, v, k_nan, v_nan, _ = _decode_case(
        np.asarray(horizons[:, 1]), group, 128, dtype, seed=3, rows=rows)
    want = _halves_einsum(q, k, v, horizons, 0.25)
    plain = _decode_xla(q, k, v, horizons, 0.25)
    np.testing.assert_allclose(np.asarray(plain), want, rtol=tol, atol=tol)
    got = _decode_pallas(q, k_nan, v_nan, horizons, 0.25, chunk, piece,
                         interpret=True)
    assert got.dtype == jnp.float32 and got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), want, rtol=tol, atol=tol)
    # what the lower horizon hides matters: the one-horizon form differs
    if bool((horizons[:, 0] < horizons[:, 1]).any()):
        assert float(jnp.abs(plain - _decode_xla(
            q, k, v, horizons[:, 1], 0.25)).max()) > 1e-2


def test_one_horizon_a_slot_gives_what_it_gave():
    """``lengths (S,)`` and the same horizon in both columns are the same
    bytes, on the plain path and in the kernel; the public entry refuses
    horizons it cannot read."""
    q, k, v, k_nan, v_nan, lengths = _decode_case(
        [0, 17, 63, 32], 8, 128, jnp.float32, seed=5)
    both = jnp.stack([lengths, lengths], axis=1)
    np.testing.assert_array_equal(
        np.asarray(_decode_xla(q, k, v, lengths, 0.25)),
        np.asarray(_decode_xla(q, k, v, both, 0.25)))
    np.testing.assert_array_equal(
        np.asarray(_decode_pallas(q, k_nan, v_nan, lengths, 0.25, _CHUNK,
                                  _PIECE, interpret=True)),
        np.asarray(_decode_pallas(q, k_nan, v_nan, both, 0.25, _CHUNK,
                                  _PIECE, interpret=True)))
    np.testing.assert_array_equal(
        np.asarray(decode_attention(q, k, v, both, 0.25)),
        np.asarray(decode_attention(q, k, v, lengths, 0.25)))
    with pytest.raises(ValueError, match="two halves"):
        decode_attention(q, k, v, jnp.stack([lengths] * 3, axis=1), 0.25)
    with pytest.raises(ValueError, match="two halves"):
        decode_attention(q[:, :, :7], k, v, both, 0.25)


def test_decode_xla_is_the_softmax_over_the_rows_a_slot_holds():
    q, k, v, _, _, lengths = _decode_case([0, 9, 63], 4, 16, jnp.float32)
    got = np.asarray(_decode_xla(q, k, v, lengths, 0.25))
    for i, n in enumerate(np.asarray(lengths) + 1):
        s = np.einsum("kgd,kmd->kgm", q[i], k[i, :, :n]) * 0.25
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("kgm,kmd->kgd", p / p.sum(-1, keepdims=True),
                         v[i, :, :n])
        np.testing.assert_allclose(got[i], want, rtol=2e-5, atol=2e-5)


def test_decode_attention_off_the_tpu_reads_every_row_and_says_so():
    q, k, v, _, _, lengths = _decode_case([3, 40], 8, 16, jnp.float32)
    assert decode_attention_plan(q, k) == (_ROWS, "not_tpu")

    with _counting("op=decode_attention,path=xla,reason=not_tpu") as counted:
        got = decode_attention(q, k, v, lengths, 0.25)
    assert counted() == 1
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(_decode_xla(q, k, v, lengths, 0.25)))


@pytest.mark.parametrize("shape,dtype,want", [
    # the K-EXAONE cell: whatever a chunk holds (512 rows of all 8 K/V
    # heads are 1 MiB), a slot is read to a multiple of 128 rows
    ((8, 8, 128, 4096), jnp.bfloat16, (128, None)),
    ((1, 8, 128, 32768), jnp.bfloat16, (128, None)),
    # heads of 64 on their own live rows-minor on the chip: the kernel
    # would have the cache copied; cached in pairs they are whole lanes
    ((20, 1, 64, 1024), jnp.float32, (1024, "lanes")),
    ((10, 4, 128, 4096), jnp.bfloat16, (128, None)),
    ((4, 4, 128, 384), jnp.bfloat16, (128, None)),
    ((4, 4, 128, 200), jnp.bfloat16, (200, "tile")),
    ((32, 4, 256, 1024), jnp.float32, (1024, "vmem")),
    ((8, 8, 128, 4096), jnp.float16, (4096, "dtype")),
])
def test_decode_attention_plan_on_a_tpu_trace(shape, dtype, want):
    from mxnet_tpu.ops import registry

    kv, g, d, rows = shape
    q = jax.ShapeDtypeStruct((2, kv, g, d), dtype)
    cache = jax.ShapeDtypeStruct((2, kv, rows, d), dtype)
    token = registry.trace_device.set("tpu")
    try:
        assert decode_attention_plan(q, cache) == want
    finally:
        registry.trace_device.reset(token)


# -- slot rows: one new row a slot into a heads-major cache --------------------
def _bits(x):
    """The array's bytes as integers: equality that a NaN cannot pass or
    fail by being a NaN, and that tells -0.0 from 0.0."""
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def _slot_write_case(at, rows_a_slot, dtype, n=3, d=128, seed=0):
    """``(cache, rows, at, want)``: a cache of seeded noise with a NaN, an
    infinity and a negative zero a slot among what must not change, and
    ``cache.at[arange(S), :, at].set(rows)`` computed on the host."""
    rs = np.random.RandomState(seed)
    s = len(at)
    cache = rs.normal(0, 1, (s, n, rows_a_slot, d)).astype(np.float32)
    cache[:, 0, 0, 0], cache[:, 1, -1, 1], cache[:, 2, 1, 2] = \
        np.nan, np.inf, -0.0
    cache = jnp.asarray(cache, dtype)
    rows = jnp.asarray(rs.normal(0, 1, (s, n, d)), dtype)
    want = np.array(cache)
    want[np.arange(s), :, np.asarray(at)] = np.asarray(rows)
    return cache, rows, jnp.asarray(at, jnp.int32), want


_SLOT_WRITE_CASES = {
    # rows of a ring and of a full layer; at 0, the two sides of a
    # bfloat16 tile's edge (15, 16), of a float32 tile's (7, 8), R - 1
    "ring-edges": ([0, 15, 16, 31, 7, 8], 32, 6),
    "ring-wrapped": ([p % 32 for p in (0, 31, 32, 47, 48, 1000, 4095)],
                     32, 7),
    "full-edges": ([0, 15, 16, 255, 128, 17], 256, 6),
    "full-one-tile-for-all": ([40] * 5, 256, 5),
    # slot counts the group does not divide: 7 in groups of 3, of 2 (the
    # last group one slot), and a group a slot (both buffers by turns)
    "groups-of-3": ([5, 50, 21, 33, 62, 16, 47], 64, 3),
    "groups-of-2": ([5, 50, 21, 33, 62, 16, 47], 64, 2),
    "groups-of-1": ([63, 0, 15, 16, 1], 64, 1),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("case", sorted(_SLOT_WRITE_CASES))
def test_slot_write_kernel_interpret_writes_one_row_a_slot(case, dtype):
    """The kernel (interpreter) against the scatter, bit for bit: the row
    where it belongs, every other element what it was."""
    at, rows_a_slot, group = _SLOT_WRITE_CASES[case]
    cache, rows, at, want = _slot_write_case(at, rows_a_slot, dtype)
    got = _slot_write_pallas(cache, rows, at, group, interpret=True)
    assert got.dtype == cache.dtype and got.shape == cache.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))
    # the plain path is held to the same
    np.testing.assert_array_equal(
        _bits(_slot_write_xla(cache, rows, at)), _bits(want))


@pytest.mark.parametrize("rows_dtype", [jnp.float32, jnp.bfloat16])
def test_write_slot_rows_off_the_tpu_is_the_update_slices_and_says_so(
        rows_dtype):
    """``exaone_moe.write_full``'s semantics where it lives now: rows are
    cast to the cache's dtype, the call is counted as the plain path."""
    cache, rows, at, _ = _slot_write_case([3, 40, 0, 63], 64, jnp.bfloat16)
    rows = rows.astype(rows_dtype) * 1.001
    assert write_slot_rows_plan(cache, rows) == (0, "not_tpu")
    with _counting("op=slot_write,path=xla,reason=not_tpu") as counted:
        got = write_slot_rows(cache, rows, at)
    assert counted() == 1
    want = cache.at[jnp.arange(4), :, at].set(rows.astype(jnp.bfloat16))
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("shape,dtype,want,counted_as", [
    # the Phi-4-mini-flash cell's rings and shared layer, K-EXAONE's full
    # layer and rings: every slot's tile at once
    ((128, 10, 512, 128), jnp.bfloat16, (128, None), "pallas,reason=ok"),
    ((128, 10, 4096, 128), jnp.bfloat16, (128, None), "pallas,reason=ok"),
    ((256, 8, 4096, 128), jnp.bfloat16, (256, None), "pallas,reason=ok"),
    ((256, 8, 128, 128), jnp.bfloat16, (256, None), "pallas,reason=ok"),
    ((128, 10, 512, 128), jnp.float32, (128, None), "pallas,reason=ok"),
    # 512 slots' tiles are 16.8 MB: two buffers of 6 MiB, 192 slots each
    ((512, 8, 4096, 128), jnp.bfloat16, (192, None), "pallas,reason=ok"),
    ((12, 20, 1024, 64), jnp.float32, (0, "lanes"), "xla,reason=lanes"),
    ((8, 8, 4096, 128), jnp.float16, (0, "dtype"), "xla,reason=dtype"),
    ((8, 8, 200, 128), jnp.bfloat16, (0, "tile"), "xla,reason=tile"),
    ((8, 8, 200, 128), jnp.float32, (8, None), "pallas,reason=ok"),
    ((4096, 8, 128, 128), jnp.bfloat16, (0, "vmem"), "xla,reason=vmem"),
])
def test_write_slot_rows_plan_on_a_tpu_trace(shape, dtype, want,
                                             counted_as):
    """What the plan decides from shapes and dtype, and the count each
    decision leaves (the trace is abstract: nothing runs)."""
    from mxnet_tpu.ops import registry

    s, n, _, d = shape
    cache = jax.ShapeDtypeStruct(shape, dtype)
    rows = jax.ShapeDtypeStruct((s, n, d), dtype)
    at = jax.ShapeDtypeStruct((s,), jnp.int32)
    token = registry.trace_device.set("tpu")
    try:
        assert write_slot_rows_plan(cache, rows) == want
        with _counting("op=slot_write,path=" + counted_as) as counted:
            out = jax.eval_shape(write_slot_rows, cache, rows, at)
        assert counted() == 1
        assert (out.shape, out.dtype) == (shape, dtype)
    finally:
        registry.trace_device.reset(token)
