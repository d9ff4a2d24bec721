"""models/smallthinker.py at a CPU size: prefill and decode step through
rings and full layers against the plain reference (logits, not tokens),
faults planted in the block, the two expert products, the shares of the
expert layer under the softmax router, the windowed flash attention and a
ring read through the decode-attention kernel, and the model through the
decode engine's model protocol with its counters."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import serving
from mxnet_tpu.models import exaone_moe as xm
from mxnet_tpu.models import smallthinker as st
from mxnet_tpu.models import transformer_lm as tlm
from mxnet_tpu.ops import attention, grouped_product, registry
from mxnet_tpu.serving.decode import UnsupportedKVLayout
from test_attention import _counting

WINDOW = 8


def _cfg(first_expert=0, experts_held=16, max_len=48, **more):
    return st.SmallThinkerConfig(
        vocab=96, embed=64, heads=6, kv_heads=2, head_dim=16, layers=4,
        rope_layout=(0, 1, 1, 1), window_layout=(0, 1, 1, 1), expert_ffn=32,
        num_experts=16, top_k=3, first_expert=first_expert,
        experts_held=experts_held, window=WINDOW, rope_theta=1.5e6,
        eps=1e-6, max_len=max_len, eos_id=96, **more)


_SESSIONS = [
    (3, 4, 12),       # shorter than the window, decoding across its wrap
    (8, 8, 6),        # the window exactly, a bucket of its own length
    (8, 16, 6),       # the window exactly, in a padded bucket
    (13, 16, 14),     # longer than the window: the ring holds the last 8
    (20, 32, 20),     # two wraps in the prompt, two more while decoding
]


#: the plain reference under one jit: a new length compiles one program,
#: where the bare call compiles each of its operations anew
_forward = jax.jit(st.forward_logits, static_argnums=0)


def _programs(cfg):
    """The model at ``cfg`` with a jit of its prefill and of its step, as
    they are traced now (a planted fault is in the trace)."""
    model = st.SmallThinker(cfg, jnp.float32)
    return model, jax.jit(model.prefill), jax.jit(model.decode_step)


@pytest.fixture(scope="module")
def sound():
    """ONE :func:`_programs` of the sound model for every session below:
    a session compiles only the bucket no session before it had."""
    return _programs(_cfg())


def _prefill_then_decode(cfg, prompt, bucket, new, programs):
    """The worst difference of the served logits from the reference's (at
    ``cfg``) over a session in slot 1 of 3, and the model's counters."""
    model, prefill, step = programs
    params = st.init_params(cfg, seed=prompt, dtype=jnp.float32)
    tokens = np.random.RandomState(prompt).randint(0, cfg.vocab,
                                                   prompt + new)
    want = np.asarray(_forward(cfg, params, jnp.asarray(tokens)))
    slots, slot = 3, 1
    cache = [[jnp.zeros((slots,) + tlm.slot_shape(c), c.dtype)
              for c in model.cache_spec()] for _ in range(2)]
    padded = np.zeros((bucket,), np.int32)
    padded[:prompt] = tokens[:prompt]
    last, ks, vs = prefill(params, jnp.asarray(padded), jnp.int32(prompt))
    worst = float(np.abs(np.asarray(last) - want[prompt - 1]).max())
    for side, rows in zip(cache, (ks, vs)):
        for l, r in enumerate(rows):
            side[l] = jax.lax.dynamic_update_slice(side[l], r[None],
                                                   (slot, 0, 0, 0))
    extra = model.extra_state()
    active = jnp.arange(slots) == slot
    ck, cv = tuple(cache[0]), tuple(cache[1])
    for p in range(prompt, prompt + new):
        last_tok = jnp.zeros((slots,), jnp.int32).at[slot].set(tokens[p])
        lengths = jnp.zeros((slots,), jnp.int32).at[slot].set(p)
        logits, ck, cv, extra = step(params, ck, cv, last_tok, lengths,
                                     active, extra)
        worst = max(worst, float(np.abs(np.asarray(logits[slot])
                                        - want[p]).max()))
    return worst, model.counters(jax.device_get(extra))


@pytest.mark.parametrize("prompt,bucket,new", _SESSIONS)
def test_prefill_then_decode_equal_the_reference_logits(prompt, bucket, new,
                                                        sound):
    cfg = _cfg()
    worst, counted = _prefill_then_decode(cfg, prompt, bucket, new, sound)
    assert worst < 1e-4
    assert counted["rows"] == counted["steps"] == new
    assert counted["moe_picks_total"] == new * cfg.top_k * cfg.layers \
        == np.sum(counted["moe_picks"])
    held = [p + 1 for p in range(prompt, prompt + new)]
    assert counted["rows_full"] == sum(held)
    assert counted["rows_ring"] == sum(min(h, WINDOW) for h in held)
    assert counted["gauges"]["serving.attn.rows_read_share"] == \
        pytest.approx((sum(held) + 3 * counted["rows_ring"])
                      / (new * (cfg.max_len + 3.0 * WINDOW)))


def test_the_decode_kernel_reads_rings_and_full_layers(monkeypatch):
    """The same session with both kinds of layer through the Pallas kernel
    (interpreter) in chunks of 8 rows: a ring is one chunk, read up to the
    rows it holds."""
    monkeypatch.setattr(
        st, "decode_attention",
        lambda q, ck, cv, lengths, scale: attention._decode_pallas(
            q, ck, cv, lengths, scale, 8, 8, interpret=True))
    worst, _counted = _prefill_then_decode(_cfg(), 5, 8, 9,
                                           _programs(_cfg()))
    assert worst < 1e-4


def _norm_routed(cfg, h, moe):
    return xm.route(cfg, xm._rms(h, 1.0, cfg.eps), moe)


def _softmax_over_all(cfg, h, moe):
    w, chosen = jax.lax.top_k(jax.nn.softmax(jnp.dot(h, moe["router"]), -1),
                              cfg.top_k)
    return chosen.astype(jnp.int32), w


def _past_live_rows(q, ck, cv, lengths, scale):
    if ck.shape[2] == WINDOW:
        lengths = jnp.full_like(lengths, WINDOW - 1)
    return attention.decode_attention(q, ck, cv, lengths, scale)


@pytest.mark.parametrize("fault", [
    "router_reads_the_norm", "softmax_over_all_without_the_renorm",
    "silu_for_relu", "rotation_on_a_full_layer", "window_one_short",
    "ring_read_past_its_live_rows"])
def test_a_planted_fault_moves_the_logits(monkeypatch, fault):
    """Each departure from the equations is seen by the comparison that
    passes the sound program at 1e-4 (a session shorter than the window
    that decodes past its wrap)."""
    cfg = model_cfg = _cfg()
    if fault == "router_reads_the_norm":
        monkeypatch.setattr(st, "route", _norm_routed)
    elif fault == "softmax_over_all_without_the_renorm":
        monkeypatch.setattr(st, "route", _softmax_over_all)
    elif fault == "silu_for_relu":
        model_cfg = cfg._replace(activation="silu")
    elif fault == "rotation_on_a_full_layer":
        model_cfg = cfg._replace(rope_layout=(1, 1, 1, 1))
    elif fault == "window_one_short":
        model_cfg = cfg._replace(window=WINDOW - 1)
    else:
        monkeypatch.setattr(st, "decode_attention", _past_live_rows)
    worst, _counted = _prefill_then_decode(cfg, 3, 4, 12,
                                           _programs(model_cfg))
    assert worst > 1e-3, fault


# -- the expert layer ----------------------------------------------------------
def _moe_and_rows(cfg, seed, rows=24):
    params = st.init_params(cfg, seed=seed, dtype=jnp.float32)
    rs = np.random.RandomState(seed)
    return params["layers"][1]["moe"], jnp.asarray(
        rs.normal(0, 1, (rows, cfg.embed)), jnp.float32)


def _through_the_kernel(monkeypatch, tile=8, block=16):
    """``_grouped_experts`` through the Pallas kernel (interpreter), at a
    row tile of 8 and the experts' 32 columns in two blocks."""
    monkeypatch.setattr(xm, "grouped_product_plan",
                        lambda gate, expect: ((tile, block), None))
    monkeypatch.setattr(
        xm, "grouped_product",
        lambda *a, snug=False: grouped_product._grouped_pallas(
            *a, interpret=True))


def _picks(imbalance, rs, top_k):
    """``(40, top_k)`` choices of 16 experts at an imbalance."""
    if imbalance == "one_expert_all_rows":
        chosen = np.tile(np.array([5, 2, 9]), (40, 1))
        chosen[:, 1:] = rs.randint(0, 16, (40, 2))
        chosen[:, 0] = 5
    elif imbalance == "two_experts":
        chosen = np.tile(np.array([1, 14, 1]), (40, 1))
    elif imbalance == "no_multiple_of_the_tile":
        # expert 3 has 11 rows (a tile of 8 and 3 of the next), expert 7
        # has 8 (a whole tile), expert 12 has 1; the rest go to 9 and 10
        chosen = np.tile(np.array([9, 10, 9]), (40, 1))
        chosen[:11, 0], chosen[11:19, 0], chosen[19, 0] = 3, 7, 12
    elif imbalance == "nobody_picked_at_start_middle_end":
        some = np.array([1, 2, 3, 4, 5, 6, 9, 10, 11, 12, 13, 14])
        chosen = np.stack([rs.permutation(some)[:top_k] for _ in range(40)])
    else:
        chosen = np.stack([rs.permutation(16)[:top_k] for _ in range(40)])
    return jnp.asarray(chosen, jnp.int32)


@pytest.mark.parametrize("imbalance", [
    "as_routed", "one_expert_all_rows", "two_experts", "absent_experts",
    "no_multiple_of_the_tile", "nobody_picked_at_start_middle_end"])
@pytest.mark.parametrize("router,activation", [("softmax", "relu"),
                                               ("sigmoid", "silu")])
@pytest.mark.parametrize("product", ["ragged_dot", "kernel",
                                     "kernel_bfloat16"])
def test_the_grouped_and_the_every_expert_products_are_equal(
        monkeypatch, imbalance, router, activation, product):
    """On the same rows and choices, at every imbalance, by the ragged
    product and by the kernel: nothing has a capacity and nothing is
    dropped.  In bfloat16 both round the gated unit to the weights' dtype
    and differ by the order of their float32 sums."""
    moe, h = _moe_and_rows(_cfg(), 3, rows=40)
    cfg = (_cfg(first_expert=4, experts_held=8)
           if imbalance == "absent_experts" else _cfg())._replace(
        router=router, activation=activation)
    rs = np.random.RandomState(0)
    w = jnp.asarray(rs.uniform(0.1, 1.0, (40, cfg.top_k)), jnp.float32)
    chosen = _picks(imbalance, rs, cfg.top_k)
    moe = {n: (moe[n][cfg.first_expert:cfg.first_expert + cfg.experts_held]
               if n != "router" else moe[n]) for n in moe}
    if product == "kernel_bfloat16":
        moe = {n: moe[n].astype(jnp.bfloat16) for n in moe}
    if product != "ragged_dot":
        _through_the_kernel(monkeypatch)
    act = xm._ACTIVATIONS[activation]
    every = xm._every_expert(act, h, xm._combine(cfg, chosen, w), moe)
    grouped = xm._grouped_experts(cfg, act, h, chosen, w, moe)
    top = float(jnp.abs(every).max())
    assert top > 1e-4
    np.testing.assert_allclose(
        grouped, every,
        atol=2 ** -8 * top if product == "kernel_bfloat16" else 2e-6)


@pytest.mark.parametrize("sizes", [(11, 8, 1), (0, 5, 0, 0, 9, 0), (0, 0, 0),
                                   (40,)])
def test_the_kernel_reads_and_writes_no_row_above_the_last_group(sizes):
    """The kernel alone over a layout whose padding rows hold a row at
    weight 0 (a group nobody picked owns one tile of them) and whose dead
    tiles hold NaN in rows and weights: every row of a group is its
    expert's gated unit, a padding row is zero, and no NaN reaches a live
    tile (a dead tile is neither copied nor multiplied; what its rows come
    back as is not defined, and ``_grouped_experts`` reads none of them)."""
    tile, e, f = 8, 64, 32
    rs = np.random.RandomState(len(sizes))
    gate, up, down = (jnp.asarray(rs.normal(0, 0.1, shape), jnp.float32)
                      for shape in [(len(sizes), e, f)] * 2
                      + [(len(sizes), f, e)])
    rows = grouped_product.padded_rows(40, len(sizes), tile)
    xs = np.full((rows, e), np.nan, np.float32)
    ws = np.full((rows,), np.nan, np.float32)
    want = np.zeros((rows, e), np.float32)
    tiles = [max(-(-n // tile), 1) for n in sizes]
    at = 0
    for g, n in enumerate(sizes):
        live = tiles[g] * tile
        xs[at:at + live], ws[at:at + live] = rs.normal(0, 1, (e,)), 0.0
        xs[at:at + n] = rs.normal(0, 1, (n, e))
        ws[at:at + n] = rs.uniform(0.1, 1, (n,))
        a = jax.nn.silu(xs[at:at + n] @ gate[g]) * (xs[at:at + n] @ up[g])
        want[at:at + n] = (a * ws[at:at + n, None]) @ down[g]
        at += live
    assert at < rows                    # some tiles are dead
    got = np.asarray(grouped_product._grouped_pallas(
        jnp.asarray(xs), jnp.asarray(ws), jnp.asarray(tiles, jnp.int32),
        gate, up, down, jax.nn.silu, tile, 16, interpret=True))
    np.testing.assert_allclose(got[:at], want[:at], rtol=1e-5, atol=2e-6)


def test_a_pick_of_an_expert_held_elsewhere_adds_exactly_nothing(
        monkeypatch):
    """Rows whose every pick is of an expert this share does not hold come
    back as zeros, not as what the layout's last row holds."""
    cfg = _cfg(first_expert=4, experts_held=8)
    moe, h = _moe_and_rows(_cfg(), 1, rows=40)
    moe = {n: moe[n][4:12] for n in ("gate", "up", "down")}
    chosen = np.tile(np.array([5, 6, 7]), (40, 1))
    chosen[::2] = [0, 13, 15]
    w = jnp.ones((40, 3), jnp.float32)
    act = xm._ACTIVATIONS[cfg.activation]
    for kernel in (False, True):
        if kernel:
            _through_the_kernel(monkeypatch)
        y = np.asarray(xm._grouped_experts(cfg, act, h, jnp.asarray(chosen),
                                           w, moe))
        assert not y[::2].any() and np.abs(y[1::2]).min(1).max() > 0


@pytest.mark.parametrize("reason", ["not_tpu", "dtype", "lanes", "tile",
                                    "vmem", "under_a_pick_a_row", None])
def test_a_refusal_of_the_plan_takes_the_ragged_product_and_says_why(
        monkeypatch, reason):
    """``grouped_product_plan`` under a trace bound for the chip: each
    refusal runs the sorted picks through ``lax.ragged_dot``, equals the
    every-expert product and is counted with its reason; shapes it takes
    get a row tile from the rows a group expects and a block that divides
    the experts' width, a share that a row gives less than one pick (16 of
    64 held, 3 picked) like any other."""
    e, f, dtype, n = 128, 256, jnp.float32, 16
    if reason == "dtype":
        dtype = jnp.float16
    elif reason == "lanes":
        e = 64
    elif reason == "tile":
        f = 64
    elif reason == "vmem":
        monkeypatch.setattr(grouped_product, "_BLOCK_BYTES", 1 << 10)
    elif reason == "under_a_pick_a_row":
        n = 64              # 16 of 64 held, 3 picked: 0.75 picks a row
    cfg = xm.ExaoneConfig(*([None] * 19))._replace(
        num_experts=n, top_k=3, first_expert=0, experts_held=16,
        activation="silu")
    rs = np.random.RandomState(0)
    moe = {n: jnp.asarray(rs.normal(0, 0.1, shape), dtype) for n, shape in
           (("gate", (16, e, f)), ("up", (16, e, f)), ("down", (16, f, e)))}
    if reason != "not_tpu":
        token = registry.trace_device.set("tpu")
    try:
        plan = grouped_product.grouped_product_plan(moe["gate"],
                                                    40 * 3 / n)
        if reason == "under_a_pick_a_row":
            assert plan == ((16, 256), None)
            with _counting("op=grouped_product,path=pallas,reason=ok") \
                    as counted:
                jax.eval_shape(functools.partial(
                    xm._grouped_experts, cfg, jax.nn.silu),
                    jnp.zeros((40, e)), jnp.zeros((40, 3), jnp.int32),
                    jnp.zeros((40, 3)), moe)
                assert counted() == 1
            return
        if reason is None:
            # the whole width a block: the rows a group expects, to 128
            assert plan == ((16, 256), None)
            assert [grouped_product.grouped_product_plan(
                moe["gate"], expect)[0][0] for expect in (24, 40, 700)] \
                == [32, 64, 128]
            # several blocks: four times the rows, to 512
            monkeypatch.setattr(grouped_product, "_BLOCK_BYTES",
                                128 * 128 * 4)
            assert [grouped_product.grouped_product_plan(
                moe["gate"], expect) for expect in (4.8, 24, 700)] \
                == [((32, 128), None), ((128, 128), None),
                    ((512, 128), None)]
            return
        assert plan == (None, reason)
        h = jnp.asarray(rs.normal(0, 1, (40, e)), jnp.float32)
        chosen = _picks("as_routed", rs, 3)
        w = jnp.asarray(rs.uniform(0.1, 1.0, (40, 3)), jnp.float32)
        with _counting("op=grouped_product,path=xla,reason=" + reason) \
                as counted:
            grouped = xm._grouped_experts(cfg, jax.nn.silu, h, chosen, w, moe)
            assert counted() == 1
    finally:
        if reason != "not_tpu":
            registry.trace_device.reset(token)
    every = xm._every_expert(jax.nn.silu, h, xm._combine(cfg, chosen, w), moe)
    np.testing.assert_allclose(grouped, every,
                               atol=2e-3 if reason == "dtype" else 2e-6)


def test_routed_experts_chooses_its_product_from_the_shapes(monkeypatch):
    """At this model's real widths a step's few rows take the every-expert
    product, a prompt's many rows over many more experts than a row picks
    the grouped one; and ``routed_experts`` runs the product
    ``expert_product`` names for the rows it is given."""
    cfg = _cfg()._replace(top_k=6, experts_held=64, num_experts=64,
                          embed=2560, expert_ffn=768)
    assert xm.expert_product(cfg, 48) == "every"
    assert xm.expert_product(cfg, 256) == "every"
    assert xm.expert_product(cfg, 3072) == "grouped"
    assert xm.expert_product(cfg, 8192) == "grouped"
    taken = []
    monkeypatch.setattr(xm, "_grouped_experts",
                        lambda *a: taken.append("grouped") or 0.0)
    monkeypatch.setattr(xm, "_every_expert",
                        lambda *a: taken.append("every") or 0.0)
    monkeypatch.setattr(xm, "expert_product",
                        lambda cfg, rows: "grouped" if rows >= 6 else "every")
    two = _cfg()._replace(top_k=2)
    for rows in (4, 6):
        moe, h = _moe_and_rows(two, 0, rows=rows)
        chosen, w = xm.route(two, h, moe)
        xm.routed_experts(two, h, chosen, w, moe)
    assert taken == ["every", "grouped"]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_shares_add_up_to_the_uncut_layer(seed):
    """Four chips with 4 of the 16 experts each, under the softmax router
    that reads other rows than the experts are fed: their parts of the
    layer's output sum to the uncut layer's."""
    whole = _cfg()
    moe, h = _moe_and_rows(whole, seed)
    x_in = h[::-1] * 0.1
    want, chosen = xm.sparse_mlp(whole, h, moe, shared=False,
                                 router_input=x_in)
    total = jnp.zeros_like(want)
    for share in range(4):
        cfg = _cfg(first_expert=4 * share, experts_held=4)
        part = dict(moe, **{n: moe[n][4 * share:4 * share + 4]
                            for n in ("gate", "up", "down")})
        y, chosen_here = xm.sparse_mlp(cfg, h, part, shared=False,
                                       router_input=x_in)
        np.testing.assert_array_equal(chosen_here, chosen)
        total = total + y
    np.testing.assert_allclose(total, want, atol=2e-6)
    # the router read x_in, not the rows the experts were fed
    _y, other = xm.sparse_mlp(whole, h, moe, shared=False)
    assert not np.array_equal(other, chosen)


def test_the_softmax_router_is_the_softmax_over_the_chosen():
    cfg = _cfg()
    moe, h = _moe_and_rows(cfg, 5)
    chosen, w = xm.route(cfg, h, moe)
    scores = np.asarray(jax.nn.softmax(
        jnp.dot(h, moe["router"], precision="highest"), -1))
    picked = np.take_along_axis(scores, np.asarray(chosen), -1)
    np.testing.assert_array_equal(
        np.sort(chosen, -1), np.sort(np.argsort(-scores, -1)[:, :3], -1))
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    with pytest.raises(ValueError):
        xm.route(cfg._replace(router="argmax"), h, moe)


# -- the kernels' arithmetic ---------------------------------------------------
@pytest.mark.parametrize("length,window", [(32, 64), (64, 64), (128, 48),
                                           (192, 64)])
@pytest.mark.parametrize("path", ["blocks", "pallas"])
def test_flash_attention_with_a_window_and_grouped_heads(length, window,
                                                         path):
    """Forward, at lengths under, at and over the window, K/V of 2 heads
    read by 6 query heads, against the quadratic reference with a window
    mask and the K/V heads repeated."""
    rs = np.random.RandomState(length + window)
    q = jnp.asarray(rs.normal(0, 1, (1, 6, length, 16)), jnp.float32)
    k, v = (jnp.asarray(rs.normal(0, 1, (1, 2, length, 16)), jnp.float32)
            for _ in range(2))
    want = attention._attn_reference(
        q, jnp.repeat(k, 3, axis=1), jnp.repeat(v, 3, axis=1), causal=True,
        window=window)
    scale = 0.25
    if path == "pallas":
        got, _lse = attention._flash_pallas(q, k, v, True, scale, 32, 32,
                                            interpret=True, window=window)
    else:
        got, _lse = attention._flash_blocks(q, k, v, True, scale, 32, 32,
                                            window)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(
        attention.flash_attention(q, k, v, causal=True, block_q=32,
                                  block_k=32, window=window), want,
        atol=2e-5)


def test_flash_attention_window_gradients_equal_the_reference():
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.normal(0, 1, (1, 4, 64, 8)), jnp.float32)
    k, v = (jnp.asarray(rs.normal(0, 1, (1, 2, 64, 8)), jnp.float32)
            for _ in range(2))

    def flash(q, k, v):
        return attention.flash_attention(q, k, v, causal=True, block_q=16,
                                         block_k=16, window=24).sum()

    def plain(q, k, v):
        return attention._attn_reference(
            q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1),
            causal=True, window=24).sum()

    for got, want in zip(jax.grad(flash, (0, 1, 2))(q, k, v),
                         jax.grad(plain, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_a_ring_read_through_decode_attention_is_the_masked_ring(kernel):
    """``lengths = min(pos, window - 1)`` reads what the ring's masked
    einsum reads: rows ``0..pos`` before the wrap, every row after."""
    window, slots = 16, 5
    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.normal(0, 1, (slots, 2, 3, 16)), jnp.float32)
    ck, cv = (jnp.asarray(rs.normal(0, 1, (slots, 2, window, 16)),
                          jnp.float32) for _ in range(2))
    pos = jnp.asarray([0, 7, 15, 16, 40], jnp.int32)
    ring = jnp.arange(window)
    holds = pos[:, None] - ((pos[:, None] - ring[None]) % window)
    scores = jnp.einsum("skgd,skmd->skgm", q, ck) * 0.25
    want = xm._softmax_ctx(scores, (holds >= 0)[:, None, None, :], cv,
                           "skgm,skmd->skgd")
    horizon = jnp.minimum(pos, window - 1)
    if kernel == "pallas":
        got = attention._decode_pallas(q, ck, cv, horizon, 0.25, 8, 4,
                                       interpret=True)
    else:
        got = attention.decode_attention(q, ck, cv, horizon, 0.25)
    np.testing.assert_allclose(got, want, atol=2e-6)


# -- through the engine --------------------------------------------------------
def test_the_engine_serves_the_model_by_the_protocol_alone():
    cfg = _cfg(max_len=64)
    params = st.init_params(cfg, seed=7, dtype=jnp.float32)
    model = st.SmallThinker(cfg, jnp.float32)
    spec = model.cache_spec()
    assert [c.kind for c in spec] == ["full", "ring", "ring", "ring"]
    assert [tlm.slot_shape(c) for c in spec] == \
        [(2, 64, 16)] + [(2, WINDOW, 16)] * 3
    with pytest.raises(UnsupportedKVLayout):
        serving.DecodeEngine(model, params, slots=2, prefill_buckets=(8,),
                             kv_layout="paged", autostart=False)
    engine = serving.DecodeEngine(model, params, slots=3,
                                  prefill_buckets=(8, 32), name="st")
    try:
        rs = np.random.RandomState(3)
        prompts = [rs.randint(0, cfg.vocab, n).astype(np.int32)
                   for n in (5, 8, 21, 3)]
        sessions = [engine.submit(p, max_new_tokens=18) for p in prompts]
        outs = [s.result(120) for s in sessions]
        for prompt, out in zip(prompts, outs):
            seq = np.concatenate([prompt, out])
            logits = np.asarray(_forward(cfg, params, jnp.asarray(seq)))
            # greedy: each served token is the reference's best one
            np.testing.assert_array_equal(
                out, logits[len(prompt) - 1:-1].argmax(-1))
        counted = engine.model_counters()
        assert counted["rows"] >= 4 * 17
        assert counted["moe_picks_total"] == counted["rows"] * 3 * 4
        assert counted["rows_ring"] <= counted["rows_full"]
        assert "serving.moe.tokens_per_expert" in counted["gauges"]
    finally:
        engine.close()
    with pytest.raises(ValueError):
        st.SmallThinker(cfg._replace(heads=5))
    with pytest.raises(ValueError):
        st.SmallThinker(cfg._replace(experts_held=17))
    with pytest.raises(ValueError):
        st.SmallThinker(cfg._replace(rope_layout=(0, 1)))
