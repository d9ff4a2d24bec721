"""models/sdar.py at a CPU size: prefill and passes through the cache
against the plain reference (logits of every pass), the engine's whole
generation against the published loop written out plainly (tokens and the
pass that fixed each) under all three rules, the mask that is causal by
blocks on every path of ``flash_attention``, a run of rows through
``write_slot_rows``, ``decode_attention`` at a block's group, resume,
cancel, deadline, EOS inside a block, and the expert layer's shares."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import deepseek_v2 as dm
from mxnet_tpu.models import exaone_moe as xm
from mxnet_tpu.models import sdar
from mxnet_tpu.models import smallthinker as st
from mxnet_tpu.models import transformer_lm as tlm
from mxnet_tpu.ops import attention
from mxnet_tpu.serving.batcher import DeadlineExceeded
from mxnet_tpu.serving.decode import (DecodeEngine, GenerateSession,
                                      UnsupportedKVLayout)

B = 4


def _cfg(first_expert=0, experts_held=8, **more):
    base = dict(vocab=96, embed=32, heads=4, kv_heads=2, head_dim=16,
                layers=2, expert_ffn=16, num_experts=8, top_k=2,
                first_expert=first_expert, experts_held=experts_held,
                rope_theta=1e6, eps=1e-6, max_len=64, eos_id=96, mask_id=95)
    base.update(more)
    return sdar.SDARConfig(**base)


def _params(cfg, seed=3):
    """Seeded float32 weights with the layers' outputs and the head scaled
    up: with the init's small weights every masked position would read the
    mask token's embedding and little else, and a block would be one token
    four times at one confidence.  So the best token's probability ranges
    from a few hundredths to nearly one."""
    params = sdar.init_params(cfg, seed=seed, dtype=jnp.float32)
    layers = [dict(p, wo=p["wo"] * 100.0, wv=p["wv"] * 100.0,
                   moe=dict(p["moe"], down=p["moe"]["down"] * 100.0,
                            router=p["moe"]["router"] * 10.0))
              for p in params["layers"]]
    return dict(params, head=params["head"] * 40.0, layers=layers)


#: the plain reference under one jit: a new length compiles one program
_forward = jax.jit(sdar.forward_logits, static_argnums=0)


def _plain(cfg, params, prompt, max_new, **kw):
    return sdar.generate_plain(
        cfg, params, prompt, max_new,
        forward=lambda tokens: _forward(cfg, params, tokens), **kw)


# -- prefill and passes against the plain reference, as logits -----------------
@pytest.fixture(scope="module")
def programs():
    model = sdar.SDAR(_cfg(), jnp.float32)
    return model, jax.jit(model.prefill), jax.jit(model.decode_step)


@pytest.mark.parametrize("prompt,bucket,blocks", [
    (8, 8, 2),        # whole blocks, a bucket of its own length
    (5, 8, 2),        # a tail of one token starts the first block
    (3, 8, 3),        # shorter than a block: nothing is prefilled
    (14, 16, 2),      # a tail of two in a padded bucket
])
def test_prefill_then_passes_agree_with_the_plain_reference(
        prompt, bucket, blocks, programs):
    """Every pass's logits, of every state a block goes through (one more
    position fixed a pass, then the commit), are the plain forward's over
    the transcript and the block as it stands."""
    model, prefill, step = programs
    cfg = model.cfg
    params = _params(cfg, seed=prompt)
    start = prompt // B * B
    final = np.random.RandomState(prompt).randint(
        0, 90, start + blocks * B).astype(np.int32)
    slots, slot = 3, 1
    cache = [[jnp.zeros((slots,) + tlm.slot_shape(c), c.dtype)
              for c in model.cache_spec()] for _ in range(2)]
    padded = np.zeros((bucket,), np.int32)
    padded[:prompt] = final[:prompt]
    first, ks, vs = prefill(params, jnp.asarray(padded), jnp.int32(prompt))
    tail = prompt % B
    assert np.asarray(first).tolist() \
        == final[start:prompt].tolist() + [cfg.mask_id] * (B - tail)
    for side, rows in zip(cache, (ks, vs)):
        for l, r in enumerate(rows):
            side[l] = jax.lax.dynamic_update_slice(side[l], r[None],
                                                   (slot, 0, 0, 0))
    extra = model.extra_state()
    active = jnp.arange(slots) == slot
    worst, passes = 0.0, 0
    for k in range(blocks):
        at = start + k * B
        for fixed in range(tail if k == 0 else 0, B + 1):
            state = final[at:at + B].copy()
            state[fixed:] = cfg.mask_id
            block = np.full((slots, B), 7, np.int32)
            block[slot] = state
            lengths = np.full((slots,), 5, np.int32)
            lengths[slot] = at
            logits, ck, cv, extra = step(
                params, tuple(cache[0]), tuple(cache[1]), jnp.asarray(block),
                jnp.asarray(lengths), active, extra)
            cache = [list(ck), list(cv)]
            want = np.asarray(_forward(cfg, params, jnp.asarray(
                np.concatenate([final[:at], state]))))[at:at + B]
            worst = max(worst, float(
                np.abs(np.asarray(logits)[slot] - want).max()))
            passes += 1
    assert worst < 2e-3, worst
    got = model.counters(jax.tree_util.tree_map(np.asarray, extra))
    assert got["passes"] == passes and got["rows"] == passes * B
    assert got["moe_picks_total"] == passes * B * cfg.top_k * cfg.layers
    assert np.sum(got["moe_picks"]) == got["moe_picks_total"]


# -- the engine's generation against the published loop ------------------------
RULES = {
    "static-4": dict(remasking="low_confidence_static"),
    "static-2": dict(remasking="low_confidence_static", denoise_steps=2),
    "static-1": dict(remasking="low_confidence_static", denoise_steps=1),
    "sequential": dict(remasking="sequential"),
    "dynamic": dict(remasking="low_confidence_dynamic", threshold=0.5),
}

#: (prompt tokens, tokens to make): B divides neither, one, both
SESSIONS = [(5, 9), (8, 8), (3, 6), (14, 7), (12, 10)]


def _engine(cfg, params, slots=3, **kw):
    return DecodeEngine(sdar.SDAR(cfg, jnp.float32), params, slots=slots,
                        prefill_buckets=(8, 16, 32), name="sdar", **kw)


def _prompt(n):
    return np.random.RandomState(100 + n).randint(0, 90, n).astype(np.int32)


@pytest.mark.parametrize("rule", sorted(RULES))
def test_generation_is_the_published_loops(rule):
    """Tokens, and the pass at which each position was fixed, of sessions
    that share the engine's slots: in float32 the engine's are
    ``generate_plain``'s."""
    cfg = _cfg(**RULES[rule])
    params = _params(cfg)
    eng = _engine(cfg, params)
    try:
        sessions = [eng.submit(_prompt(n), max_new_tokens=m)
                    for n, m in SESSIONS]
        passes, made = 0, set()
        for sess, (n, m) in zip(sessions, SESSIONS):
            want, want_at, ran = _plain(cfg, params, _prompt(n), m)
            assert sess.result(120) == want
            assert sess.fixed_at == want_at
            assert len(want) == m and sess.ttft() is not None
            passes += ran
            made.update(want)
        assert len(made) > 3
        counted = eng.model_counters()
        assert counted["passes"] == passes
        assert counted["tokens_committed"] == sum(m for _, m in SESSIONS)
        fixed = counted["fixed_by_threshold"] + counted["fixed_by_quota"]
        # every position of every block but the prompts' tails
        assert fixed == sum((n + m + B - 1) // B * B - n
                            for n, m in SESSIONS)
        if rule == "dynamic":
            # the threshold fixes some positions and not others
            assert counted["fixed_by_threshold"] > 0
            assert counted["fixed_by_quota"] > 0
        else:
            assert counted["fixed_by_threshold"] == 0
        assert counted["gauges"]["serving.decode.tokens_per_pass"] \
            == pytest.approx(counted["tokens_committed"] / passes)
        card = eng.describe()
        assert card["tail"] == "block" and card["block"] == B
    finally:
        eng.close(drain=False)


def test_a_slot_is_reused_after_a_longer_session_and_eos_ends_a_block():
    """One slot: a long session, then a short one over the rows it left;
    then an end-of-sequence id that falls inside a block, which ends the
    session after that block's commit and delivers up to it."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _engine(cfg, params, slots=1)
    try:
        for n, m in [(14, 30), (5, 6)]:
            assert eng.generate(_prompt(n), max_new_tokens=m, timeout=120) \
                == _plain(cfg, params, _prompt(n), m)[0]
    finally:
        eng.close(drain=False)
    free, _at, _ran = _plain(cfg, params, _prompt(6), 18)
    # a token whose first appearance lies inside a block, not at its end
    at = next(i for i, t in enumerate(free)
              if free.index(t) == i and (6 + i) % B not in (B - 1,) and i > 2)
    ending = _cfg(eos_id=free[at])
    want = _plain(ending, params, _prompt(6), 18)[0]
    assert want == free[:at + 1]
    eng = _engine(ending, params, slots=2)
    try:
        assert eng.generate(_prompt(6), max_new_tokens=18, timeout=120) \
            == want
    finally:
        eng.close(drain=False)


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_resume_of_a_transcript_cut_inside_a_block(temperature):
    """A session that lost its replica while a block was being denoised
    holds prompt and committed blocks; re-prefilled elsewhere it redoes the
    block from its first pass under the same keys and gives the stream an
    uninterrupted run gives."""
    cfg = _cfg(**RULES["dynamic"])
    params = _params(cfg)
    prompt, new, seed = _prompt(6), 17, 1234
    eng = _engine(cfg, params)
    other = None
    try:
        whole = eng.submit(prompt, max_new_tokens=new,
                           temperature=temperature, seed=seed)
        want, want_at = whole.result(120), list(whole.fixed_at)
        assert len(want) == new
        if temperature:
            again = eng.generate(prompt, max_new_tokens=new, timeout=120,
                                 temperature=temperature, seed=seed + 1)
            assert again != want
        # cut by a stop while blocks are in flight
        handed, mid = [], threading.Event()
        seen = []

        def on_token(tok):
            seen.append(tok)
            if len(seen) >= 6:
                mid.set()

        sess = eng.submit(prompt, max_new_tokens=new,
                          temperature=temperature, seed=seed,
                          on_token=on_token)
        assert mid.wait(60)
        assert eng.stop(drain=False, hand_off=handed.extend) is True
        other = _engine(cfg, params)
        if handed:
            assert handed == [sess] and len(sess.tokens) < new
            assert (len(prompt) + len(sess.tokens)) % B == 0
            other.resume(sess)
        assert sess.result(120) == want and seen == want
        assert sess.fixed_at == want_at
        # and a transcript made by hand, cut at every block's end
        for kept in range(2, new, B):
            cut = GenerateSession(prompt, new, temperature, None, None,
                                  seed=seed)
            cut.tokens = list(want[:kept])
            cut.fixed_at = list(want_at[:kept])
            other.resume(cut)
            assert cut.result(120) == want and cut.fixed_at == want_at
    finally:
        eng.close(drain=False)
        if other is not None:
            other.close(drain=False)


def test_sequential_resumes_from_inside_a_block_too():
    """Under ``sequential`` the fixed positions are a block's first, so a
    greedy transcript cut at ANY token continues as it would have."""
    cfg = _cfg(**RULES["sequential"])
    params = _params(cfg)
    prompt, new = _prompt(5), 11
    eng = _engine(cfg, params)
    try:
        want = eng.generate(prompt, max_new_tokens=new, timeout=120)
        for kept in (1, 2, 4, 5, 6):
            cut = GenerateSession(prompt, new, 0.0, None, None, seed=0)
            cut.tokens = list(want[:kept])
            eng.resume(cut)
            assert cut.result(120) == want
    finally:
        eng.close(drain=False)


@pytest.mark.parametrize("model", ["block", "token"])
def test_the_engine_says_its_states_shapes(model):
    """``DecodeEngine.state_shapes`` is what ``_fresh_state`` is made from:
    a tool that lowers the programs asks the engine and mirrors nothing.
    A fresh block has no position fixed at any pass."""
    if model == "block":
        cfg = _cfg()
        eng = _engine(cfg, _params(cfg), autostart=False)
    else:
        cfg = tlm.LMConfig(vocab=50, embed=16, heads=2, layers=1, ffn=32,
                           max_len=32, eos_id=49)
        eng = DecodeEngine(cfg, tlm.init_params(cfg, seed=1), slots=3,
                           prefill_buckets=(8,), name="lm",
                           autostart=False)
    try:
        state = eng._fresh_state()
        shapes = eng.state_shapes(jax.ShapeDtypeStruct)
        assert jax.tree_util.tree_structure(state) \
            == jax.tree_util.tree_structure(shapes)
        assert [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(
            state)] == [(a.shape, a.dtype)
                        for a in jax.tree_util.tree_leaves(shapes)]
        assert len(state) == (11 if model == "block" else 8)
        if model == "block":
            assert state[2].shape == (3, cfg.block)
            assert (np.asarray(state[-2]) == -1).all()
            assert not np.asarray(state[-3]).any()
    finally:
        eng.close(drain=False)


@pytest.fixture
def counted():
    telemetry.reset()
    telemetry.enable()
    yield
    telemetry.disable()
    telemetry.reset()


def test_cancel_deadline_and_the_paged_layout(counted):
    cfg = _cfg()
    params = _params(cfg)
    with pytest.raises(UnsupportedKVLayout):
        _engine(cfg, params, kv_layout="paged")
    eng = _engine(cfg, params, slots=2)
    try:
        got = []
        sess = eng.submit(_prompt(6), max_new_tokens=50,
                          on_token=lambda t: got.append(t) or (
                              len(got) == 6 and sess.cancel()))
        with pytest.raises(MXNetError):
            sess.result(120)
        # what was delivered is whole blocks of the uninterrupted stream
        want = _plain(cfg, params, _prompt(6), 50)[0]
        assert sess.tokens == want[:len(sess.tokens)] == got
        assert 6 <= len(sess.tokens) < 50
        late = eng.submit(_prompt(6), max_new_tokens=50, deadline_ms=1.0)
        with pytest.raises(DeadlineExceeded):
            late.result(120)
        # the slots are free again and serve on
        assert eng.generate(_prompt(8), max_new_tokens=5, timeout=120) \
            == _plain(cfg, params, _prompt(8), 5)[0]
        assert eng.pending_rows() == 0
        assert telemetry.counter_total("serving.decode.passes.count") \
            > telemetry.counter_total("serving.decode.commits.count") > 0
        assert telemetry.hist_state("serving.decode.ttft_seconds",
                                    model="sdar")["count"] == 2
    finally:
        eng.close(drain=False)


# -- the kernels' new shapes ---------------------------------------------------
def _masked_einsum(q, k, v, block, scale):
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    pos = jnp.arange(q.shape[2])
    sees = pos[None, :] // block <= pos[:, None] // block
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    p = jax.nn.softmax(jnp.where(sees, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("path", ["reference", "blocks", "pallas", "public",
                                  "backward"])
def test_flash_attention_causal_by_blocks(path):
    rs = np.random.RandomState(0)
    heads, kv_heads = (2, 2) if path == "reference" else (4, 2)
    q = jnp.asarray(rs.normal(size=(1, heads, 32, 16)), jnp.float32)
    k, v = (jnp.asarray(rs.normal(size=(1, kv_heads, 32, 16)), jnp.float32)
            for _ in range(2))
    scale = 0.25
    want = _masked_einsum(q, k, v, B, scale)
    if path == "reference":
        got = attention._attn_reference(q, k, v, causal=True, scale=scale,
                                        block=B)
    elif path == "blocks":
        got, _lse = attention._flash_blocks(q, k, v, True, scale, 8, 16,
                                            None, B)
    elif path == "pallas":
        got, _lse = attention._flash_pallas(q, k, v, True, scale, 8, 16,
                                            interpret=True, block=B)
    elif path == "public":
        got = attention.flash_attention(q, k, v, causal=True,
                                        softmax_scale=scale, block_q=8,
                                        block_k=8, block=B)
        with pytest.raises(ValueError, match="block=3"):
            attention.flash_attention(q, k, v, causal=True, block=3)
        with pytest.raises(ValueError, match="causal"):
            attention.flash_attention(q, k, v, block=B)
    else:
        def loss(fn):
            return jax.grad(lambda q, k, v: (fn(q, k, v) ** 2).sum(),
                            argnums=(0, 1, 2))(q, k, v)
        got = loss(lambda q, k, v: attention.flash_attention(
            q, k, v, causal=True, softmax_scale=scale, block_q=8, block_k=8,
            block=B))
        want = loss(lambda q, k, v: _masked_einsum(q, k, v, B, scale))
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=2e-4)
        return
    np.testing.assert_allclose(got, want, atol=2e-5)
    # a row sees its whole block: the mask is not the causal one
    assert float(jnp.abs(got - attention._attn_reference(
        q, jnp.repeat(k, heads // kv_heads, 1),
        jnp.repeat(v, heads // kv_heads, 1), causal=True,
        scale=scale)).max()) > 1e-2


@pytest.mark.parametrize("dtype,path", [
    (jnp.float32, "xla"), (jnp.bfloat16, "xla"),
    (jnp.float32, "pallas"), (jnp.bfloat16, "pallas")])
def test_a_run_of_rows_through_write_slot_rows(dtype, path):
    rs = np.random.RandomState(1)
    s, n, r, d = 5, 2, 32, 128
    cache = jnp.asarray(rs.normal(size=(s, n, r, d)), dtype)
    rows = jnp.asarray(rs.normal(size=(s, n, B, d)), dtype)
    at = jnp.asarray([0, 4, 12, 28, 16], jnp.int32)
    want = np.array(cache.astype(jnp.float32))
    for i in range(s):
        want[i, :, int(at[i]):int(at[i]) + B] = rows[i].astype(jnp.float32)
    if path == "xla":
        got = attention.write_slot_rows(cache, rows, at)
    else:
        got = attention._slot_write_pallas(cache, rows, at, 2,
                                           interpret=True)
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)), want)
    # one row a slot as before
    one = attention.write_slot_rows(cache, rows[:, :, 0], at)
    want = np.array(cache.astype(jnp.float32))
    for i in range(s):
        want[i, :, int(at[i])] = rows[i, :, 0].astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(one.astype(jnp.float32)), want)
    with pytest.raises(ValueError, match="one run"):
        attention.write_slot_rows(cache, rows[:, :1], at)


def test_decode_attention_at_a_blocks_group():
    """``group = (heads // kv_heads) x B = 32``: the kernel in the
    interpreter against the plain path."""
    rs = np.random.RandomState(2)
    s, kv, g, d, rows = 3, 2, 32, 128, 256
    q = jnp.asarray(rs.normal(size=(s, kv, g, d)), jnp.float32)
    ck, cv = (jnp.asarray(rs.normal(size=(s, kv, rows, d)), jnp.float32)
              for _ in range(2))
    horizon = jnp.asarray([3, 130, 255], jnp.int32)
    want = attention._decode_xla(q, ck, cv, horizon, 0.1)
    got = attention._decode_pallas(q, ck, cv, horizon, 0.1, 128, 128,
                                   interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- the expert layer's shares -------------------------------------------------
def test_two_halves_of_the_experts_add_up_to_the_whole_layer():
    whole = _cfg()
    params = _params(whole)
    moe = params["layers"][0]["moe"]
    h = jnp.asarray(np.random.RandomState(4).normal(size=(12, 32)),
                    jnp.float32)
    want, chosen = xm.sparse_mlp(whole, h, moe, shared=False)
    total = 0.0
    for first in (0, 4):
        share = _cfg(first_expert=first, experts_held=4)
        held = dict(moe, **{name: moe[name][first:first + 4]
                            for name in ("gate", "up", "down")})
        y, again = xm.sparse_mlp(share, h, held, shared=False)
        assert (again == chosen).all()
        total = total + y
    np.testing.assert_allclose(total, want, atol=1e-5)


def _share(name):
    if name == "k-exaone":
        return xm.ExaoneConfig(
            vocab=8, embed=8, heads=2, kv_heads=1, head_dim=4, layers=1,
            layer_types=("full_attention",), mlp_types=("sparse",),
            dense_ffn=8, expert_ffn=8, num_experts=128, top_k=8,
            first_expert=0, experts_held=16, window=4, rope_theta=1e4,
            routed_scale=2.5, max_len=8, eos_id=8)
    if name == "smallthinker":
        return st.SmallThinkerConfig(
            vocab=8, embed=8, heads=2, kv_heads=1, head_dim=4, layers=1,
            rope_layout=(1,), window_layout=(0,), expert_ffn=8,
            num_experts=64, top_k=6, first_expert=0, experts_held=64,
            window=4, rope_theta=1e4, eps=1e-6, max_len=8, eos_id=8)
    if name == "deepseek-v2":
        return dm.DeepSeekV2Config._make(
            {**{f: 8 for f in dm.DeepSeekV2Config._fields},
             **dict(num_experts=160, top_k=6, experts_held=20,
                    first_expert=0)}[f]
            for f in dm.DeepSeekV2Config._fields)
    return _cfg(num_experts=128, top_k=8, experts_held=128)


@pytest.mark.parametrize("name,rows,want", [
    ("k-exaone", 256, "every"), ("k-exaone", 1024, "every"),
    ("smallthinker", 48, "every"), ("smallthinker", 8192, "grouped"),
    ("deepseek-v2", 128, "every"), ("deepseek-v2", 4096, "grouped"),
    ("sdar", 384, "every"), ("sdar", 1024, "grouped"),
])
def test_expert_product_decides_for_each_share_as_it_did(name, rows, want):
    assert xm.expert_product(_share(name), rows) == want
