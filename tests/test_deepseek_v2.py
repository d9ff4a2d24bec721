"""models/deepseek_v2.py at a CPU size: prefill (expanded heads) and decode
step (absorbed, over the latent cache) against the plain reference (logits,
not tokens), faults planted in the block, the latent kernel in the step,
the eight shares of the expert layer under the group-limited router, the
router against a plain reading, YaRN's numbers written out by hand, the
three cells' choices of expert product, and the model through the decode
engine's model protocol with its counters."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import serving
from mxnet_tpu.models import deepseek_v2 as dm
from mxnet_tpu.models import exaone_moe as xm
from mxnet_tpu.models import transformer_lm as tlm
from mxnet_tpu.ops import attention
from mxnet_tpu.serving.decode import UnsupportedKVLayout


def _cfg(first_expert=4, experts_held=4, max_len=48, **more):
    """Width 64, four heads of 8 + 8 scored and 8 carried, a latent row of
    16; one dense layer and three expert layers of 16 experts in 4 groups,
    2 groups and 3 experts a token, group 1 held."""
    return dm.DeepSeekV2Config(
        vocab=96, embed=64, heads=4, q_rank=24, kv_rank=16, nope_dim=8,
        rope_dim=8, v_dim=8, layers=4, first_dense=1, dense_ffn=96,
        expert_ffn=32, shared_ffn=64, num_experts=16, top_k=3, n_group=4,
        topk_group=2, first_expert=first_expert, experts_held=experts_held,
        routed_scale=16.0, rope_theta=10000.0, rope_factor=40.0,
        rope_original=16, beta_fast=32, beta_slow=1, mscale=0.707,
        mscale_all_dim=0.707, eps=1e-6, max_len=max_len, eos_id=96, **more)


#: weights at which four narrow layers move the stream as the real widths'
#: do (at 0.02 the logits of a width-64 model are its embedding's)
_STD = 0.2


def _params(cfg, seed):
    params = dm.init_params(cfg, seed=seed, dtype=jnp.float32)
    return jax.tree_util.tree_map(
        lambda a: a if a.ndim == 1 else a * (_STD / 0.02), params)


_SESSIONS = [
    (3, 4, 12),       # a short prompt in a padded bucket
    (8, 8, 6),        # a bucket of the prompt's own length
    (13, 16, 14),     # across a bucket's padding
    (20, 32, 20),     # past the original positions YaRN stretches from
]

#: the plain reference under one jit: a new length compiles one program
_forward = jax.jit(dm.forward_logits, static_argnums=(0, 3))


def _programs(cfg):
    """The model at ``cfg`` with a jit of its prefill and of its step, as
    they are traced now (a planted fault is in the trace)."""
    model = dm.DeepSeekV2(cfg, jnp.float32)
    return model, jax.jit(model.prefill), jax.jit(model.decode_step)


@pytest.fixture(scope="module")
def sound():
    return _programs(_cfg())


def _prefill_then_decode(cfg, prompt, bucket, new, programs):
    """The worst difference of the served logits from the reference's (at
    ``cfg``) over a session in slot 1 of 3, and the model's counters."""
    model, prefill, step = programs
    params = _params(cfg, prompt)
    tokens = np.random.RandomState(prompt).randint(0, cfg.vocab,
                                                   prompt + new)
    want = np.asarray(_forward(cfg, params, jnp.asarray(tokens), False))
    slots, slot = 3, 1
    cache = [[jnp.zeros((slots,) + shape, dtype) for shape, dtype in (
        tlm.slot_arrays(c)[i] for c in model.cache_spec())]
        for i in range(2)]
    padded = np.zeros((bucket,), np.int32)
    padded[:prompt] = tokens[:prompt]
    last, lats, ropes = prefill(params, jnp.asarray(padded),
                                jnp.int32(prompt))
    worst = float(np.abs(np.asarray(last) - want[prompt - 1]).max())
    for side, rows in zip(cache, (lats, ropes)):
        for l, r in enumerate(rows):
            side[l] = jax.lax.dynamic_update_slice(side[l], r[None],
                                                   (slot, 0, 0, 0))
    extra = model.extra_state()
    active = jnp.arange(slots) == slot
    cl, cr = tuple(cache[0]), tuple(cache[1])
    for p in range(prompt, prompt + new):
        last_tok = jnp.zeros((slots,), jnp.int32).at[slot].set(tokens[p])
        lengths = jnp.zeros((slots,), jnp.int32).at[slot].set(p)
        logits, cl, cr, extra = step(params, cl, cr, last_tok, lengths,
                                     active, extra)
        worst = max(worst, float(np.abs(np.asarray(logits[slot])
                                        - want[p]).max()))
    return worst, model.counters(jax.device_get(extra))


@pytest.mark.parametrize("prompt,bucket,new", _SESSIONS)
def test_prefill_then_decode_equal_the_reference_logits(prompt, bucket, new,
                                                        sound):
    """The absorbed step over the latent rows a prefill's expanded heads
    left gives the expanded reference's logits."""
    cfg = _cfg()
    worst, counted = _prefill_then_decode(cfg, prompt, bucket, new, sound)
    assert worst < 2e-4
    assert counted["rows"] == counted["steps"] == new
    assert counted["moe_picks_total"] == new * cfg.top_k * 3
    assert np.sum(counted["moe_picks"]) <= counted["moe_picks_total"]
    assert counted["rows_latent"] == cfg.layers * sum(
        p + 1 for p in range(prompt, prompt + new))
    assert 0 < counted["rows_reached"] <= 3 * new
    assert counted["gauges"]["serving.moe.rows_reached_share"] == \
        pytest.approx(counted["rows_reached"] / (3.0 * new))


def test_the_latent_kernel_in_the_step(monkeypatch):
    """The same session with every layer's rows through the Pallas kernel
    (interpreter) in chunks of 16 rows, multiplied 8 at a time, and pieces
    of 8."""
    monkeypatch.setattr(
        dm, "latent_attention",
        lambda ql, qr, cl, cr, lengths: attention._latent_pallas(
            ql, qr, cl, cr, lengths, 16, 8, 8, interpret=True))
    worst, _counted = _prefill_then_decode(_cfg(), 13, 16, 22,
                                           _programs(_cfg()))
    assert worst < 2e-4


def _without_the_rotated_term(ql, qr, cl, cr, lengths):
    return attention.latent_attention(ql, jnp.zeros_like(qr), cl, cr,
                                      lengths)


def _one_position_off(cache, rows, at):
    return attention.write_slot_rows(cache, rows, jnp.maximum(at - 1, 0))


def _no_group_limit(cfg, h, moe):
    s = jax.nn.softmax(jnp.dot(h, moe["router"], precision="highest"), -1)
    w, chosen = jax.lax.top_k(s, cfg.top_k)
    return chosen.astype(jnp.int32), w * cfg.routed_scale


@pytest.mark.parametrize("fault", [
    "rotated_term_left_out", "row_written_one_position_off",
    "group_limit_ignored", "factor_16_left_out", "plain_rope_frequencies",
    "scale_without_mscale"])
def test_a_planted_fault_moves_the_logits(monkeypatch, fault):
    """Each departure from the equations is seen by the comparison that
    passes the sound program at 2e-4."""
    cfg = model_cfg = _cfg()
    if fault == "rotated_term_left_out":
        monkeypatch.setattr(dm, "latent_attention",
                            _without_the_rotated_term)
    elif fault == "row_written_one_position_off":
        monkeypatch.setattr(dm, "write_slot_rows", _one_position_off)
    elif fault == "group_limit_ignored":
        monkeypatch.setattr(xm, "route", _no_group_limit)
    elif fault == "factor_16_left_out":
        model_cfg = cfg._replace(routed_scale=1.0)
    elif fault == "plain_rope_frequencies":
        model_cfg = cfg._replace(rope_factor=1.0)
    else:
        model_cfg = cfg._replace(mscale_all_dim=0.0)
    worst, _counted = _prefill_then_decode(cfg, 13, 16, 14,
                                           _programs(model_cfg))
    assert worst > 1e-2, fault


# -- the expert layer ----------------------------------------------------------
def _moe_and_rows(cfg, seed, rows=40):
    moe = _params(cfg, seed)["layers"][1]["moe"]
    rs = np.random.RandomState(seed)
    return moe, jnp.asarray(rs.normal(0, 1, (rows, cfg.embed)), jnp.float32)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_shares_add_up_to_the_uncut_layer(seed):
    """Four chips, a routing group of 4 of the 16 experts each: their parts
    of the layer's output, the shared experts counted once, sum to the
    uncut layer's."""
    whole = _cfg(first_expert=0, experts_held=16)
    moe, h = _moe_and_rows(whole, seed)
    want, chosen = xm.sparse_mlp(whole, h, moe)
    total = jnp.zeros_like(want)
    for share in range(4):
        cfg = _cfg(first_expert=4 * share, experts_held=4)
        part = dict(moe, **{n: moe[n][4 * share:4 * share + 4]
                            for n in ("gate", "up", "down")})
        y, chosen_here = xm.sparse_mlp(cfg, h, part, shared=share == 0)
        np.testing.assert_array_equal(chosen_here, chosen)
        total = total + y
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert float(jnp.abs(want).max()) > 1e-2


def test_the_group_limited_router_against_a_plain_reading():
    cfg = _cfg()
    moe, h = _moe_and_rows(cfg, 5, rows=200)
    chosen, w = (np.asarray(a) for a in xm.route(cfg, h, moe))
    scores = np.asarray(jax.nn.softmax(
        jnp.dot(h, moe["router"], precision="highest"), -1))
    per = cfg.num_experts // cfg.n_group
    without_limit = 0
    for t in range(len(scores)):
        by_group = scores[t].reshape(cfg.n_group, per)
        kept = np.argsort(-by_group.max(-1))[:cfg.topk_group]
        allowed = [g * per + i for g in kept for i in range(per)]
        want = sorted(allowed, key=lambda e: -scores[t, e])[:cfg.top_k]
        assert sorted(chosen[t]) == sorted(want)
        # never more than topk_group groups, weights not renormed, x 16
        assert len({e // per for e in chosen[t]}) <= cfg.topk_group
        np.testing.assert_allclose(
            w[t], cfg.routed_scale * scores[t, chosen[t]], rtol=1e-5)
        without_limit += sorted(np.argsort(-scores[t])[:cfg.top_k]) \
            != sorted(want)
    # the limit binds: the best three overall often span three groups
    assert without_limit > 20
    assert not np.allclose(w.sum(-1), cfg.routed_scale)
    with pytest.raises(ValueError):
        xm.route(cfg._replace(router="argmax"), h, moe)


def test_expert_product_at_the_three_cells_shapes():
    """K-EXAONE's share (16 of 128 held, 8 picked) keeps the every-expert
    product up to its largest bucket; SmallThinker (64 of 64, 6 picked)
    takes the grouped one from 1024 rows; one of DeepSeek-V2's eight groups
    (20 of 160, 6 picked: 0.75 picks a row) takes it at its buckets and the
    every-expert one at a step's 128 rows."""
    cfg = _cfg()
    exaone = cfg._replace(top_k=8, experts_held=16, num_experts=128)
    for rows in (128, 256, 512, 1024):
        assert xm.expert_product(exaone, rows) == "every"
    small = cfg._replace(top_k=6, experts_held=64, num_experts=64)
    assert [xm.expert_product(small, rows)
            for rows in (48, 512, 1023, 1024, 3072, 8192)] \
        == ["every"] * 3 + ["grouped"] * 3
    group = cfg._replace(top_k=6, experts_held=20, num_experts=160)
    assert xm.expert_product(group, 128) == "every"
    for rows in (2048, 2560, 3072, 3584, 4096):
        assert xm.expert_product(group, rows) == "grouped"
    # ... and the whole of that model on one chip, as its uncut reference
    assert xm.expert_product(group._replace(experts_held=160), 4096) \
        == "grouped"


# -- YaRN ----------------------------------------------------------------------
def test_yarn_frequencies_and_scale_by_hand():
    """The published numbers: 64 rotated values, base 10,000, factor 40
    over 4096 original positions, beta 32 and 1, mscale 0.707 twice."""
    cfg = _cfg()._replace(nope_dim=128, rope_dim=64, rope_original=4096)
    inv = dm.yarn_inv_freq(cfg)
    assert inv.shape == (32,)
    # 64 ln(4096 / (32 x 2 pi)) / (2 ln 10000) = 10.47 -> 10;
    # 64 ln(4096 / (2 pi)) / (2 ln 10000) = 22.51 -> 23
    plain = [10000.0 ** (-2.0 * j / 64) for j in range(32)]
    for j in range(11):              # pairs that turn often: as published
        assert inv[j] == pytest.approx(plain[j], rel=1e-12)
    for j in range(23, 32):          # pairs that turn rarely: 40 times slower
        assert inv[j] == pytest.approx(plain[j] / 40, rel=1e-12)
    # pair 16 is 6/13 of the way: plain x (7/13 + 6/13 / 40)
    assert inv[16] == pytest.approx(0.01 * (7 / 13 + 6 / 13 / 40), rel=1e-9)
    assert inv[16] == pytest.approx(0.0055000, rel=1e-4)
    assert np.all(np.diff(inv) < 0)
    # m = 0.1 x 0.707 x ln 40 + 1 = 1.26081; scale = 192^-0.5 m^2
    assert dm.yarn_mscale(40, 0.707) == pytest.approx(1.260804, rel=1e-6)
    assert dm.softmax_scale(cfg) == pytest.approx(0.114721, rel=1e-5)
    assert dm.softmax_scale(cfg) == pytest.approx(
        1.260804 ** 2 / math.sqrt(192), rel=1e-5)
    # position 3, pair 0: the pair (x0, x1) turned by 3 radians, laid out
    # half-split; cos and sin scaled by mscale / mscale_all_dim = 1
    x = jnp.zeros((1, 1, 64)).at[0, 0, 0].set(1.0)
    y = np.asarray(dm._rope(cfg, x, jnp.asarray([3])))[0, 0]
    assert y[0] == pytest.approx(math.cos(3.0), abs=1e-6)
    assert y[32] == pytest.approx(math.sin(3.0), abs=1e-6)
    assert np.abs(np.delete(y, [0, 32])).max() == 0.0


# -- the kernels' arithmetic ---------------------------------------------------
def _latent_operands(rows, dtype=jnp.float32, s=3):
    h, c, r = 8, 256, 128
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    return ((jax.random.normal(keys[0], (s, h, c)) * 0.1).astype(dtype),
            (jax.random.normal(keys[1], (s, h, r)) * 0.1).astype(dtype),
            jax.random.normal(keys[2], (s, 1, rows, c)).astype(dtype),
            jax.random.normal(keys[3], (s, 1, rows, r)).astype(dtype))


def _latent_float64(ql, qr, lat, rope, n):
    """The masked einsum of the same operands in numpy's float64."""
    ql, qr, lat, rope = (np.asarray(x.astype(jnp.float32), np.float64)
                         for x in (ql, qr, lat[:, 0], rope[:, 0]))
    s = np.einsum("shc,smc->shm", ql, lat) \
        + np.einsum("shr,smr->shm", qr, rope)
    held = np.arange(lat.shape[1])[None, :] <= np.asarray(n)[:, None]
    s = np.where(held[:, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("shm,smc->shc", p / p.sum(-1, keepdims=True), lat)


@pytest.mark.parametrize("lengths", [(0, 130, 511), (127, 128, 300),
                                     (255, 256, 257)])
@pytest.mark.parametrize("chunk,sub", [(128, 128), (256, 256), (256, 128)])
def test_the_latent_kernel_is_the_masked_einsum(lengths, chunk, sub):
    rows = 512
    ql, qr, lat, rope = _latent_operands(rows)
    n = jnp.asarray(lengths, jnp.int32)
    want = attention._latent_xla(ql, qr, lat, rope, n)
    got = attention._latent_pallas(ql, qr, lat, rope, n, chunk, 128, sub,
                                   interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # and no further from the same einsum in float64 than the float32
    # einsum is
    exact = _latent_float64(ql, qr, lat, rope, n)
    assert np.abs(got - exact).max() <= np.abs(want - exact).max()
    # off the TPU the public call is the masked einsum, and says so
    assert attention.latent_attention_plan(ql, lat, rope) \
        == (rows, "not_tpu")
    np.testing.assert_array_equal(
        attention.latent_attention(ql, qr, lat, rope, n), want)


@pytest.mark.parametrize("above", [False, True])
@pytest.mark.parametrize("chunk,sub", [(256, 128), (512, 128), (512, 256),
                                       (512, 512)])
def test_the_latent_kernel_in_sub_blocks(chunk, sub, above):
    """A whole chunk joins the softmax ``sub`` rows at a time; lengths on
    either side of a sub-block's and of a chunk's boundary."""
    rows = 1152
    ql, qr, lat, rope = _latent_operands(rows)
    n = jnp.asarray((chunk, chunk + sub + 1, rows - 1) if above
                    else (sub - 1, sub, chunk - 1), jnp.int32)
    got = attention._latent_pallas(ql, qr, lat, rope, n, chunk, 128, sub,
                                   interpret=True)
    np.testing.assert_allclose(
        got, attention._latent_xla(ql, qr, lat, rope, n), atol=2e-6)


@pytest.mark.parametrize("lengths", [(255, 641, 1151), (127, 512, 700)])
@pytest.mark.parametrize("sub", [128, 256])
def test_the_latent_kernel_in_sub_blocks_in_bfloat16(sub, lengths):
    """The float32 cases' operands in bfloat16, against the same einsum in
    float64: the kernel within 2e-3 (``_latent_xla`` itself, which rounds
    the weights after dividing them, stands at 2.5e-3 to 5.6e-3), no
    further than ``_latent_xla``, and the sub-blocks no further than the
    whole chunk at once is, by more than a twentieth of the limit."""
    ql, qr, lat, rope = _latent_operands(1152, jnp.bfloat16)
    n = jnp.asarray(lengths, jnp.int32)
    exact = _latent_float64(ql, qr, lat, rope, n)

    def off(got):
        return np.abs(np.asarray(got) - exact).max()

    got, whole = (off(attention._latent_pallas(
        ql, qr, lat, rope, n, 512, 128, rows, interpret=True))
        for rows in (sub, 512))
    assert got < 2e-3
    assert got <= off(attention._latent_xla(ql, qr, lat, rope, n))
    assert got <= whole + 1e-4


@pytest.mark.parametrize("sub", [128, 256])
def test_the_latent_kernel_reads_the_edge_where_the_walk_left_it(sub):
    """The kernel multiplies a slot's edge after ``_walk_slot`` returns,
    from the walk's buffer (``_latent_kernel.the_edge``).  Slots whose
    turns end in either buffer, the edge of one, two and no piece beyond
    the first, each slot's rows of a size of their own and NaN above its
    length: the other buffer holds another slot's rows or another chunk's,
    the edge's buffer above its pieces what an earlier turn left, and the
    cache above a length what no product may touch.  (Tried: the other
    buffer, and the pieces read one place on: each fails both cases.)"""
    chunk, rows = 256, 1152
    lengths = (1100, 40, 300, 700, 5, 255, 256)   # turns 5, 1, 2, 3, 1, 1, 2
    ql, qr, lat, rope = _latent_operands(rows, s=len(lengths))
    size = (1.0 + np.arange(len(lengths), dtype=np.float32))[:, None, None,
                                                              None]
    held = (np.arange(rows)[None, :]
            <= np.asarray(lengths)[:, None])[:, None, :, None]
    lat, rope = (np.where(held, np.asarray(x) * size, 0.0)
                 for x in (lat, rope))
    n = jnp.asarray(lengths, jnp.int32)
    want = attention._latent_xla(ql, qr, jnp.asarray(lat),
                                 jnp.asarray(rope), n)
    got = attention._latent_pallas(
        ql, qr, jnp.asarray(np.where(held, lat, np.nan)),
        jnp.asarray(np.where(held, rope, np.nan)), n, chunk, 128, sub,
        interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("path", ["scan", "blocks", "pallas"])
def test_flash_attention_with_values_of_their_own_width(path):
    """Scores over 24 values a head, 16 carried: the expanded heads'
    shape."""
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (1, 4, 64, 24))
    k = jax.random.normal(keys[1], (1, 4, 64, 24))
    v = jax.random.normal(keys[2], (1, 4, 64, 16))
    want = attention._attn_reference(q, k, v, causal=True)
    scale = 24 ** -0.5
    if path == "scan":
        got = attention.flash_attention(q, k, v, causal=True, block_q=16,
                                        block_k=16)
    elif path == "blocks":
        got, _ = attention._flash_blocks(q, k, v, True, scale, 16, 16, None)
    else:
        got, _ = attention._flash_pallas(q, k, v, True, scale, 16, 16,
                                         interpret=True)
    assert got.shape == (1, 4, 64, 16)
    np.testing.assert_allclose(got, want, atol=2e-6)


# -- through the engine --------------------------------------------------------
def test_the_pool_serves_the_model_and_counts_the_references_choices():
    cfg = _cfg(max_len=64)
    params = _params(cfg, 7)
    model = dm.DeepSeekV2(cfg, jnp.float32)
    spec = model.cache_spec()
    assert [c.kind for c in spec] == ["latent"] * 4
    assert [[shape for shape, _ in tlm.slot_arrays(c)] for c in spec] \
        == [[(1, 64, 16), (1, 64, 128)]] * 4
    with pytest.raises(UnsupportedKVLayout):
        serving.DecodeEngine(model, params, slots=2, prefill_buckets=(8,),
                             kv_layout="paged", autostart=False)
    pool = serving.lm_pool(model, params, n_replicas=1, name="dsv2",
                           engine_opts={"slots": 3,
                                        "prefill_buckets": (8, 32)})
    engine = pool.replicas[0].engine
    try:
        rs = np.random.RandomState(3)
        asked = [(rs.randint(0, cfg.vocab, n).astype(np.int32), 18)
                 for n in (5, 8, 21, 3)]
        sessions = [pool.generate(p, max_new_tokens=new, temperature=0.0,
                                  seed=0) for p, new in asked]
        served = [s.result(120) for s in sessions]
        counted = engine.model_counters()
        want_picks = np.zeros((3, 4), np.int64)
        rows = latent = reached = 0
        for (prompt, new), out in zip(asked, served):
            seq = jnp.asarray(np.concatenate([prompt, out]))
            logits, choices = _forward(cfg, params, seq, True)
            n = len(prompt)
            # greedy: each served token is the reference's best one
            np.testing.assert_array_equal(
                out, np.asarray(logits)[n - 1:-1].argmax(-1))
            # decode steps fed positions n .. n + new - 2
            rows += new - 1
            latent += cfg.layers * sum(p + 1 for p in range(n, n + new - 1))
            for l, chosen in enumerate(choices):
                local = np.asarray(chosen)[n:n + new - 1] - cfg.first_expert
                for x in range(4):
                    want_picks[l, x] += int((local == x).sum())
                reached += int(((local >= 0) & (local < 4)).any(-1).sum())
        assert counted["rows"] == rows
        assert counted["moe_picks_total"] == rows * cfg.top_k * 3
        np.testing.assert_array_equal(counted["moe_picks"], want_picks)
        assert counted["rows_latent"] == latent
        assert counted["rows_reached"] == reached
        assert 0 < counted["gauges"]["serving.moe.local_share"] < 1
        assert engine._cache_bytes() == {
            "latent": 3 * 4 * 64 * (16 + 128) * 4}
        # the counters count on across a rewarm
        engine.stop()
        engine.rewarm()
        engine.start()
        engine.generate(np.arange(4), max_new_tokens=4)
        assert engine.model_counters()["rows"] == rows + 3
    finally:
        pool.close(drain=False)
    with pytest.raises(ValueError):
        dm.DeepSeekV2(cfg._replace(n_group=3))
    with pytest.raises(ValueError):
        dm.DeepSeekV2(cfg._replace(experts_held=17))
