"""models/exaone_moe.py at a CPU size: prefill and decode step through both
kinds of cache against the plain reference (logits, not tokens), the
chip's share of the experts, droplessness, and the model through the decode
engine's model protocol with its routing counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import serving
from mxnet_tpu.models import exaone_moe as xm
from mxnet_tpu.models import transformer_lm as tlm
from mxnet_tpu.ops import attention
from mxnet_tpu.serving.decode import UnsupportedKVLayout

WINDOW = 8
#: rows to whose multiple a test's decode-attention kernel reads a slot
#: (three of them in the tests' ``max_len``): its chunk and its piece
BLOCK = 16


#: the plain reference under one jit: a new length compiles one program,
#: where the bare call compiles each of its operations anew
_forward = jax.jit(xm.forward_logits, static_argnums=0,
                   static_argnames="with_choices")


@pytest.fixture
def decode_kernel(monkeypatch):
    """The full layer's attention through the Pallas kernel, run by the
    interpreter in chunks of ``BLOCK`` rows: the choice of path is patched
    where the model reads it (off the TPU it reads every row)."""
    monkeypatch.setattr(xm, "decode_attention_plan",
                        lambda q, cache_k: (BLOCK, None))
    monkeypatch.setattr(
        xm, "decode_attention",
        lambda q, ck, cv, lengths, scale: attention._decode_pallas(
            q, ck, cv, lengths, scale, BLOCK, BLOCK, interpret=True))


def _cfg(first_expert=0, experts_held=16, max_len=48):
    return xm.ExaoneConfig(
        vocab=96, embed=64, heads=8, kv_heads=2, head_dim=16, layers=5,
        layer_types=("sliding_attention",) * 3
        + ("full_attention", "sliding_attention"),
        mlp_types=("dense",) + ("sparse",) * 4, dense_ffn=96, expert_ffn=32,
        num_experts=16, top_k=4, first_expert=first_expert,
        experts_held=experts_held, window=WINDOW, rope_theta=1e6,
        routed_scale=2.5, max_len=max_len, eos_id=96)


def _share(params, first, held):
    """The parameters of the share that holds ``held`` experts from
    ``first``, cut out of an uncut model's."""
    def cut(p):
        if "moe" not in p:
            return p
        moe = dict(p["moe"], **{n: p["moe"][n][first:first + held]
                                for n in ("gate", "up", "down")})
        return dict(p, moe=moe)
    return dict(params, layers=[cut(p) for p in params["layers"]])


_SESSIONS = [
    (3, 4, 12),       # shorter than the window, decoding across its wrap
    (8, 16, 6),       # the window exactly
    (13, 16, 14),     # longer than the window: the ring holds the last 8
    (20, 32, 20),     # two wraps in the prompt, two more while decoding
]


@pytest.fixture(scope="module")
def programs():
    """``programs(path)``: the model of :func:`_prefill_then_decode` with
    ONE jit of its prefill and of its step a path (``plain``, or
    ``kernel``: traced under :func:`decode_kernel`), so that a session
    compiles only the bucket no session before it had."""
    built = {}

    def get(path):
        if path not in built:
            model = xm.ExaoneMoE(_cfg(first_expert=4, experts_held=8),
                                 jnp.float32)
            built[path] = (model, jax.jit(model.prefill),
                           jax.jit(model.decode_step))
        return built[path]

    return get


@pytest.mark.parametrize("prompt,bucket,new", _SESSIONS)
def test_prefill_then_decode_equal_the_reference_logits(prompt, bucket, new,
                                                        programs):
    """Off the TPU the full layer reads every row: a slot is one block."""
    counted = _prefill_then_decode(programs("plain"), prompt, bucket, new)
    assert counted["attn_blocks_read"] == counted["attn_blocks_held"] == new
    assert counted["gauges"]["serving.attn.rows_read_share"] == 1.0


@pytest.mark.parametrize("prompt,bucket,new", _SESSIONS)
def test_prefill_then_decode_through_the_decode_kernel(
        prompt, bucket, new, decode_kernel, programs):
    """The same sessions with the full layer's attention in the Pallas
    kernel (interpreter), beside two idle slots of length 0 that ride
    along; the counters say which blocks of the slot's 3 it read."""
    counted = _prefill_then_decode(programs("kernel"), prompt, bucket, new)
    read = sum(p // BLOCK + 1 for p in range(prompt, prompt + new))
    assert counted["attn_blocks_read"] == read
    assert counted["attn_blocks_held"] == 3 * new
    assert counted["attn_blocks_read"] <= counted["attn_blocks_held"]
    assert counted["gauges"]["serving.attn.rows_read_share"] \
        == pytest.approx(read / (3.0 * new))


def _prefill_then_decode(programs, prompt, bucket, new):
    model, prefill, step = programs
    cfg = model.cfg
    params = xm.init_params(cfg, seed=prompt, dtype=jnp.float32)
    tokens = np.random.RandomState(prompt).randint(0, cfg.vocab,
                                                   prompt + new)
    want = np.asarray(_forward(cfg, params, jnp.asarray(tokens)))
    slots, slot = 3, 1
    cache = [[jnp.zeros((slots,) + tlm.slot_shape(c), c.dtype)
              for c in model.cache_spec()] for _ in range(2)]
    padded = np.zeros((bucket,), np.int32)
    padded[:prompt] = tokens[:prompt]
    last, ks, vs = prefill(params, jnp.asarray(padded), jnp.int32(prompt))
    np.testing.assert_allclose(last, want[prompt - 1], atol=1e-4)
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
        np.testing.assert_allclose(logits[slot], want[p], atol=1e-4,
                                   err_msg="position %d" % p)
    counted = model.counters(jax.device_get(extra))
    assert counted["rows"] == counted["steps"] == new
    assert counted["moe_picks_total"] == new * cfg.top_k * 4
    return counted


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_shares_add_up_to_the_uncut_layer(seed):
    """Four chips with 4 of the 16 experts each: their parts of a sparse
    layer's output, the shared expert counted once, sum to the uncut
    layer's."""
    whole = _cfg()
    params = xm.init_params(whole, seed=seed, dtype=jnp.float32)
    moe = params["layers"][1]["moe"]
    h = jnp.asarray(np.random.RandomState(seed).normal(0, 1, (24, 64)),
                    jnp.float32)
    want, chosen = xm.sparse_mlp(whole, h, moe)
    total = 0.0
    for share in range(4):
        cfg = _cfg(first_expert=4 * share, experts_held=4)
        part = _share(params, 4 * share, 4)["layers"][1]["moe"]
        y, chosen_here = xm.sparse_mlp(cfg, h, part, shared=share == 0)
        np.testing.assert_array_equal(chosen_here, chosen)
        total = total + y
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert float(jnp.abs(want).max()) > 1e-3


@pytest.mark.parametrize("rows", [1, 7, 64])
def test_every_row_on_the_same_held_experts_is_dropped_nowhere(rows):
    """The worst imbalance: the selection bias sends every row to the same
    four experts, all held here; each row gets all four."""
    cfg = _cfg(first_expert=4, experts_held=4)
    params = xm.init_params(_cfg(), seed=3, dtype=jnp.float32)
    moe = dict(_share(params, 4, 4)["layers"][2]["moe"])
    moe["bias"] = jnp.zeros((16,)).at[4:8].set(10.0)
    h = jnp.asarray(np.random.RandomState(rows).normal(0, 1, (rows, 64)),
                    jnp.float32)
    y, chosen = xm.sparse_mlp(cfg, h, moe, shared=False)
    assert sorted(np.asarray(chosen[0]).tolist()) == [4, 5, 6, 7]
    assert (np.sort(np.asarray(chosen), -1) == [4, 5, 6, 7]).all()
    _chosen, w = xm.route(cfg, h, moe)
    want = np.zeros((rows, 64), np.float32)
    for r in range(rows):
        for e, w_e in zip(np.asarray(chosen[r]), np.asarray(w[r])):
            x = {n: moe[n][e - 4] for n in ("gate", "up", "down")}
            a = jax.nn.silu(h[r] @ x["gate"]) * (h[r] @ x["up"])
            want[r] += w_e * np.asarray(a @ x["down"])
    np.testing.assert_allclose(y, want, atol=1e-5)


@pytest.mark.parametrize("seed", [1, 2])
def test_through_the_pool_tokens_and_routing_counters(seed):
    _through_the_pool(seed, BLOCK * 3)


def test_through_the_pool_with_the_decode_kernel(decode_kernel):
    _through_the_pool(3, BLOCK)


def _through_the_pool(seed, block):
    """``lm_pool`` -> ``ReplicaPool`` -> ``DecodeEngine`` by the model
    protocol, more sessions than slots (continuous admission): greedy
    tokens equal the reference's argmax, the device's routing counters
    equal a count made from the reference's choices, and the full layer
    read the blocks of ``block`` rows that the sessions' lengths imply."""
    cfg = _cfg(first_expert=8, experts_held=4)
    params = xm.init_params(cfg, seed=seed, dtype=jnp.float32)
    pool = serving.lm_pool(xm.ExaoneMoE(cfg, jnp.float32), params,
                           n_replicas=1, name="moe-test",
                           engine_opts={"slots": 3,
                                        "prefill_buckets": (4, 16, 32)})
    try:
        rs = np.random.RandomState(seed)
        asked = [(rs.randint(0, cfg.vocab, n), new) for n, new in
                 [(3, 9), (13, 5), (20, 12), (7, 16), (2, 3), (30, 10)]]
        handles = [pool.generate(p, max_new_tokens=new, temperature=0.0,
                                 seed=0) for p, new in asked]
        served = [h.result(120) for h in handles]
        engine = pool.replicas[0].engine
        counted = engine.model_counters()
        assert engine.describe()["model_counters"]["rows"] \
            == counted["rows"]
        want_picks = np.zeros((4, 4), np.int64)
        rows = blocks_read = 0
        for (prompt, new), out in zip(asked, served):
            seq = jnp.asarray(np.concatenate([prompt, out]))
            logits, choices = _forward(cfg, params, seq, with_choices=True)
            n = len(prompt)
            assert np.asarray(jnp.argmax(logits, -1))[n - 1:-1].tolist() \
                == list(out)
            # decode steps fed positions n .. n + new - 2
            rows += new - 1
            blocks_read += sum(p // block + 1 for p in range(n, n + new - 1))
            for l, chosen in enumerate(choices):
                local = np.asarray(chosen)[n:n + new - 1] - cfg.first_expert
                for x in range(4):
                    want_picks[l, x] += int((local == x).sum())
        assert counted["rows"] == rows
        assert counted["moe_picks_total"] == rows * cfg.top_k * 4
        np.testing.assert_array_equal(counted["moe_picks"], want_picks)
        assert 0 < counted["gauges"]["serving.moe.local_share"] < 1
        assert counted["attn_blocks_read"] == blocks_read
        assert counted["attn_blocks_held"] == rows * (cfg.max_len // block)
        assert counted["gauges"]["serving.attn.rows_read_share"] \
            == pytest.approx(blocks_read / (rows * (cfg.max_len / block)))
    finally:
        pool.close(drain=False)


def test_the_paged_layout_refuses_a_cache_it_cannot_hold():
    cfg = _cfg()
    with pytest.raises(UnsupportedKVLayout):
        serving.DecodeEngine(xm.ExaoneMoE(cfg, jnp.float32), {},
                             kv_layout="paged", autostart=False)


def test_the_counters_count_on_across_a_rewarm():
    cfg = _cfg(first_expert=0, experts_held=4)
    params = xm.init_params(cfg, seed=1, dtype=jnp.float32)
    engine = serving.DecodeEngine(xm.ExaoneMoE(cfg, jnp.float32), params,
                                  slots=2, prefill_buckets=(8,))
    try:
        engine.generate(np.arange(5), max_new_tokens=6)
        before = engine.model_counters()
        assert before["rows"] == 5
        engine.stop()
        engine.rewarm()
        engine.start()
        engine.generate(np.arange(4), max_new_tokens=4)
        after = engine.model_counters()
        assert after["rows"] == 5 + 3
        assert after["moe_picks_total"] == 8 * cfg.top_k * 4
    finally:
        engine.close(drain=False)
