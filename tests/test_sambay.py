"""``models/sambay.py`` at CPU size: the decode tier's prefill and decode
step (through ``DecodeEngine`` and on their own) against the plain
reference ``forward_logits`` on seeded weights, in float32, so that any
term left out of the mathematics shows as a difference in the logits."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.models import sambay as sb
from mxnet_tpu.models import transformer_lm as tlm
from mxnet_tpu.ops import ssm
from mxnet_tpu.serving import DecodeEngine
from mxnet_tpu.serving.decode import UnsupportedKVLayout

WINDOW, MAX_LEN = 8, 64
#: the smallest depth with every kind of layer: three mamba (the last the
#: memory), two window, the full layer, one gmu, one cross
CFG = sb.SambaYConfig(vocab=96, embed=32, heads=8, kv_heads=4, head_dim=8,
                      layers=8, ffn=48, mb_per_layer=2, window=WINDOW,
                      d_inner=64, d_state=4, d_conv=4, dt_rank=2,
                      max_len=MAX_LEN, eos_id=96)
BUCKETS = (8, 32)


def _params(seed=0):
    """Seeded weights with every gain, bias and vector moved off its
    neutral start, so that one left out of the program changes the
    logits."""
    # at these widths a deviation of 0.02 leaves the recurrence a millionth
    # of the residual stream; 0.2 makes every path carry its share
    params = sb.init_params(CFG, seed, jnp.float32, std=0.2)
    rs = np.random.RandomState(seed + 1)

    def moved(a):
        if a.ndim == 1:
            return a + jnp.asarray(rs.normal(0, 0.1, a.shape), a.dtype)
        return a

    return jax.tree_util.tree_map(moved, params)


PARAMS = _params()


#: the plain reference under one jit: a new length compiles one program,
#: where the bare call compiles each of its operations anew (and lies 6e-6
#: further from the programs, which are jitted too)
_forward = jax.jit(sb.forward_logits, static_argnums=0)


def _reference(tokens, params=PARAMS):
    return np.asarray(_forward(CFG, params, jnp.asarray(tokens)))


def test_the_layer_kinds_of_the_published_depth():
    """32 layers: state-space layers 0, 2 .. 16, window attention 1 .. 15,
    the full layer 17, gated memory units 18 .. 30, cross 19 .. 31."""
    kinds = sb.layer_kinds(CFG._replace(layers=32))
    assert [l for l, k in enumerate(kinds) if k == "mamba"] \
        == list(range(0, 17, 2))
    assert [l for l, k in enumerate(kinds) if k == "window"] \
        == list(range(1, 16, 2))
    assert kinds[17] == "full"
    assert [l for l, k in enumerate(kinds) if k == "gmu"] \
        == list(range(18, 31, 2))
    assert [l for l, k in enumerate(kinds) if k == "cross"] \
        == list(range(19, 32, 2))
    assert sb.lam0(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    with pytest.raises(ValueError):
        sb.layer_kinds(CFG._replace(layers=6))    # layer 4 would be full


def test_the_slot_state_specification():
    """One entry a layer that keeps state, in layer order: a state and its
    tail (float32 whatever the cache's dtype), rings, the one full layer;
    gmu and cross layers keep nothing."""
    spec = sb.SambaY(CFG, jnp.bfloat16).cache_spec()
    assert [c.kind for c in spec] \
        == ["state", "ring", "state", "ring", "state", "full"]
    assert tlm.slot_arrays(spec[0]) == (((4, 64), jnp.float32),
                                        ((3, 64), jnp.bfloat16))
    assert tlm.slot_arrays(spec[1]) == (((2, WINDOW, 16), jnp.bfloat16),) * 2
    assert tlm.slot_arrays(spec[5]) == (((2, MAX_LEN, 16), jnp.bfloat16),) * 2


# -- the scan ------------------------------------------------------------------
@pytest.mark.parametrize("path", ["plain", "kernel"])
@pytest.mark.parametrize("t,real", [(8, 8), (32, 5), (256, 200)])
def test_the_scan_is_the_recurrence_and_padding_leaves_the_state(t, real,
                                                                 path):
    """``ops.ssm``'s scan, by the chunked prefix scan and by the kernel
    (interpreted), over one or several chunks equals the token-by-token
    recurrence, from a state that is not zero; positions with ``dt = 0``
    (a bucket's padding) leave the state as the last real position did."""
    rs = np.random.RandomState(t)
    d, n = 256, 4
    dt = np.abs(rs.normal(0, 0.5, (t, d))).astype(np.float32)
    dt[real:] = 0.0
    u, b, c = (rs.normal(0, 1, s).astype(np.float32)
               for s in ((t, d), (t, n), (t, n)))
    a = -np.exp(rs.normal(0, 1, (n, d))).astype(np.float32)
    s = s0 = rs.normal(0, 1, (n, d)).astype(np.float32)
    ys = []
    for i in range(real):
        s = np.exp(dt[i][None] * a) * s + (dt[i] * u[i])[None] * b[i][:, None]
        ys.append((s * c[i][:, None]).sum(0))
    args = tuple(jnp.asarray(x) for x in (dt, u, b, c, a, s0))
    if path == "plain":
        last, y = ssm._scan_xla(*args)
    else:
        last, y = ssm._scan_pallas(*args, 128, min(t, 128), interpret=True)
    np.testing.assert_allclose(last, s, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(y[:real], np.stack(ys), rtol=2e-5, atol=2e-5)


def test_the_scans_plan_follows_the_shapes():
    """Off the chip the plain path; on a trace bound for it the kernel at
    whole lanes, float32 and whole tiles of positions, with the reason
    where not."""
    from mxnet_tpu.ops import registry

    def plan(t, d, dtype=jnp.float32):
        return ssm.ssm_scan_plan(jax.ShapeDtypeStruct((t, d), dtype),
                                 jax.ShapeDtypeStruct((16, d), jnp.float32))

    assert plan(128, 5120) == (None, "not_tpu")
    token = registry.trace_device.set("tpu")
    try:
        assert plan(128, 5120) == ((512, 128), None)
        assert plan(1024, 5120) == ((512, 128), None)
        assert plan(32, 384) == ((128, 32), None)
        assert plan(128, 64) == (None, "lanes")
        assert plan(12, 512) == (None, "tile")
        assert plan(200, 512) == (None, "tile")
        assert plan(128, 512, jnp.bfloat16) == (None, "dtype")
    finally:
        registry.trace_device.reset(token)


# -- prefill and decode step on their own --------------------------------------
@pytest.fixture(scope="module")
def programs():
    """The model with ONE jit of its prefill and of its step for every
    case below: a case compiles only the shapes no case before it had."""
    model = sb.SambaY(CFG, jnp.float32)
    return model, jax.jit(model.prefill), jax.jit(model.decode_step)


def _slot_state(model, slots, fill):
    return [[jnp.full((slots,) + shape, fill, dtype)
             for shape, dtype in (tlm.slot_arrays(c)[i]
                                  for c in model.cache_spec())]
            for i in range(2)]


@pytest.mark.parametrize("prompt,bucket,new", [
    (1, 8, 4),       # shorter than the convolution's tail
    (2, 8, 12),      # ... and decoding past the window
    (5, 8, 6),       # shorter than the window
    (8, 8, 3),       # a bucket filled to its last row
    (12, 32, 10),    # longer than the window, in a padded bucket
    (30, 32, 20)])   # the ring wraps several times
def test_prefill_then_decode_steps_give_the_references_logits(prompt, bucket,
                                                              new, programs):
    """A padded prompt through ``prefill`` (the layers after the full one
    for the last position alone) gives the reference's last logits, and
    the state it leaves in a slot that held another session's carries the
    decode steps to the reference's logits at every later position."""
    model, prefill, step = programs
    tokens = np.random.RandomState(prompt).randint(0, CFG.vocab,
                                                   prompt + new)
    want = _reference(tokens)
    padded = np.full((bucket,), 7, np.int32)       # padding is not token 0
    padded[:prompt] = tokens[:prompt]
    last, firsts, seconds = prefill(PARAMS, jnp.asarray(padded),
                                    jnp.int32(prompt))
    np.testing.assert_allclose(last, want[prompt - 1], atol=2e-5)
    slots, slot = 3, 1
    held = _slot_state(model, slots, 0.5)          # what a session left
    for side, values in zip(held, (firsts, seconds)):
        for i, v in enumerate(values):
            side[i] = jax.lax.dynamic_update_slice(
                side[i], v[None], (slot,) + (0,) * v.ndim)
    extra = model.extra_state()
    firsts, seconds = tuple(held[0]), tuple(held[1])
    active = jnp.arange(slots) == slot
    for p in range(prompt, prompt + new):
        last_tok = jnp.zeros((slots,), jnp.int32).at[slot].set(tokens[p])
        lengths = jnp.zeros((slots,), jnp.int32).at[slot].set(p)
        logits, firsts, seconds, extra = step(
            PARAMS, firsts, seconds, last_tok, lengths, active, extra)
        np.testing.assert_allclose(logits[slot], want[p], atol=2e-5,
                                   err_msg="position %d" % p)
    counted = model.counters(jax.device_get(extra))
    assert counted["rows"] == counted["steps"] == new
    assert counted["rows_full"] == sum(range(prompt + 1, prompt + new + 1))
    assert counted["rows_ring"] == sum(
        min(p + 1, WINDOW) for p in range(prompt, prompt + new))


@pytest.mark.parametrize("fault", ["a state not reset", "memory of layer 2",
                                   "lam0 of layer 3 in layer 5",
                                   "window of 7", "no convolution bias"])
def test_the_reference_tells_a_planted_fault(fault):
    """The comparison above is not blind: each fault planted in the
    program's mathematics moves the logits by far more than its
    tolerance."""
    model = sb.SambaY(CFG, jnp.float32)
    tokens = np.random.RandomState(3).randint(0, CFG.vocab, 24)
    want = _reference(tokens)
    params, cfg_used = PARAMS, CFG
    if fault == "window of 7":
        model = sb.SambaY(CFG._replace(window=7), jnp.float32)
    elif fault == "no convolution bias":
        params = dict(PARAMS, layers=[
            dict(p, conv_b=jnp.zeros_like(p["conv_b"])) if "conv_b" in p
            else p for p in PARAMS["layers"]])
    del cfg_used
    patched = {}
    if fault == "memory of layer 2":
        mamba, kept = sb._mamba, {}

        def altered(cfg, l, p, x, access):
            x, y = mamba(cfg, l, p, x, access)
            if l == 2:
                kept["y"] = y
            return x, (kept["y"] if l == cfg.layers // 2 else y)

        patched["_mamba"] = altered
    elif fault == "lam0 of layer 3 in layer 5":
        real = sb.lam0
        patched["lam0"] = lambda l: real(3 if l == 5 else l)
    saved = {k: getattr(sb, k) for k in patched}
    for k, v in patched.items():
        setattr(sb, k, v)
    try:
        padded = np.zeros((32,), np.int32)
        padded[:20] = tokens[:20]
        last, firsts, seconds = jax.jit(model.prefill)(
            params, jnp.asarray(padded), jnp.int32(20))
        if fault == "a state not reset":
            # the slot keeps the states its last session left
            _, left, _ = jax.jit(model.prefill)(
                params, jnp.asarray(padded[::-1].copy()), jnp.int32(32))
            firsts = tuple(
                old if c.kind == "state" else mine for c, old, mine
                in zip(model.cache_spec(), left, firsts))
        lengths = jnp.full((1,), 20, jnp.int32)
        logits, *_ = jax.jit(model.decode_step)(
            params, tuple(a[None] for a in firsts),
            tuple(a[None] for a in seconds),
            jnp.asarray(tokens[20:21]), lengths, jnp.ones((1,), bool),
            model.extra_state())
    finally:
        for k, v in saved.items():
            setattr(sb, k, v)
    off = max(np.abs(np.asarray(last) - want[19]).max(),
              np.abs(np.asarray(logits[0]) - want[20]).max())
    assert off > 10 * 2e-5, off


# -- through the engine --------------------------------------------------------
class Recording(sb.SambaY):
    """The model with every prefill's and step's logits handed to the
    host as they are computed: what the engine's own programs gave."""

    def __init__(self, *args):
        super().__init__(*args)
        self.prefills, self.steps = [], []

    def prefill(self, params, tokens, length):
        out = super().prefill(params, tokens, length)
        jax.debug.callback(
            lambda n, lg: self.prefills.append((int(n), np.asarray(lg))),
            length, out[0])
        return out

    def decode_step(self, params, firsts, seconds, last_tok, lengths,
                    active, extra):
        out = super().decode_step(params, firsts, seconds, last_tok,
                                  lengths, active, extra)
        jax.debug.callback(
            lambda n, on, lg: self.steps.append(
                (np.asarray(n), np.asarray(on), np.asarray(lg))),
            lengths, active, out[0])
        return out


def _engine(model=None, **kw):
    opts = dict(slots=2, prefill_buckets=BUCKETS, name="sambay")
    opts.update(kw)
    return DecodeEngine(model or sb.SambaY(CFG, jnp.float32), PARAMS, **opts)


@pytest.fixture(scope="module")
def recording():
    """``(model, engine)``: ONE running engine over a :class:`Recording`
    model for the tests that send it sessions one after another (tier-1
    compiles every engine's step and buckets anew).  A test that stops
    it starts it again."""
    model = Recording(CFG, jnp.float32)
    eng = _engine(model)
    yield model, eng
    eng.close(drain=False)


@pytest.fixture(scope="module")
def plain():
    """ONE running engine over the model as it is."""
    eng = _engine()
    yield eng
    eng.close(drain=False)


def _forget(model):
    """Empties a shared :class:`Recording` of what earlier sessions left."""
    jax.effects_barrier()
    model.prefills.clear()
    model.steps.clear()


def _served_logits(model, slot, first, count):
    """The logits the engine computed for the session in ``slot`` at
    positions ``first .. first + count - 1`` (a step's logits at ``lengths
    = p`` choose the token at ``p + 1``)."""
    jax.effects_barrier()
    got = {}
    for lengths, active, logits in model.steps:
        if active[slot]:
            got[int(lengths[slot])] = logits[slot]
    return np.stack([got[p] for p in range(first, first + count)])


@pytest.mark.parametrize("prompt", [2, 6, 8, 20])
def test_the_engine_serves_the_references_logits(prompt, recording):
    """Prefill and decoding through ``DecodeEngine``, greedy: the logits
    its programs computed are the reference's over prompt and served
    tokens, position for position."""
    model, eng = recording
    _forget(model)
    tokens = np.random.RandomState(prompt).randint(0, CFG.vocab, prompt)
    new = 14
    sess = eng.submit(tokens, max_new_tokens=new)
    out = sess.result(60)
    assert len(out) == new
    want = _reference(np.concatenate([tokens, out]))
    jax.effects_barrier()
    mine = [lg for n, lg in model.prefills if n == prompt]
    np.testing.assert_allclose(mine[-1], want[prompt - 1], atol=2e-5)
    np.testing.assert_allclose(
        _served_logits(model, sess.slot, prompt, new - 1),
        want[prompt:prompt + new - 1], atol=2e-5)
    assert out == [int(t) for t in want[prompt - 1:-1].argmax(-1)]


def test_a_slots_second_session_does_not_see_the_firsts_state(recording):
    """Two sessions in turn in ONE slot: the second's logits are those of
    an engine whose state is new, so the admission overwrote the
    recurrent state and the convolution's tail the first left (no length
    masks them), and the engine counted both overwrites."""
    first = np.random.RandomState(1).randint(0, CFG.vocab, 9)
    second = np.random.RandomState(2).randint(0, CFG.vocab, 3)
    model, eng = recording

    def resets():
        return sum(telemetry.snapshot()["counters"].get(
            "serving.ssm.state_resets", {}).values())

    telemetry.enable()
    try:
        before = resets()
        one = eng.submit(first, max_new_tokens=20)
        one.result(60)
        _forget(model)
        two = eng.submit(second, max_new_tokens=10)
        out = two.result(60)
        assert one.slot == two.slot
        assert resets() - before == 2
    finally:
        telemetry.disable()
    used = _served_logits(model, two.slot, 3, 9)
    # a stop and a start make the slot state anew, from zeros
    eng.stop(drain=False)
    eng.start()
    _forget(model)
    fresh = eng.submit(second, max_new_tokens=10)
    assert fresh.result(60) == out
    np.testing.assert_array_equal(
        used, _served_logits(model, fresh.slot, 3, 9))
    np.testing.assert_allclose(
        used, _reference(np.concatenate([second, out]))[3:12], atol=2e-5)


@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_resume_of_a_migrated_transcript_restores_the_state(temperature,
                                                            recording,
                                                            plain):
    """A session stopped mid-generation and resumed on another engine by
    re-prefilling its transcript ends with the stream the first engine
    would have given: the re-prefill rebuilt the recurrent state."""
    prompt = np.random.RandomState(5).randint(0, CFG.vocab, 6)
    (_, eng), other = recording, plain
    want = eng.generate(prompt, max_new_tokens=18, temperature=temperature,
                        seed=11)
    mid, go_on = threading.Event(), threading.Event()
    seen, handed = [], []

    def on_token(t):
        seen.append(t)
        if len(seen) == 7:
            mid.set()
            go_on.wait(60)

    try:
        sess = eng.submit(prompt, max_new_tokens=18, temperature=temperature,
                          seed=11, on_token=on_token)
        assert mid.wait(60)
        stopper = threading.Thread(target=lambda: eng.stop(
            drain=False, hand_off=handed.extend))
        stopper.start()
        go_on.set()
        stopper.join(60)
        assert handed == [sess] and 7 <= len(sess.tokens) < 18
        other.resume(sess)
        assert sess.result(60) == want
        assert seen == want
    finally:
        go_on.set()
        eng.stop(drain=False)
        eng.start()


def test_the_paged_layout_refuses_the_model():
    with pytest.raises(UnsupportedKVLayout):
        _engine(kv_layout="paged", autostart=False)


def test_the_engine_reports_its_slot_state_by_kind():
    """``describe()`` and the ``serving.cache.bytes`` gauges count every
    array of the slot state: states and tails beside rings and the full
    layer."""
    telemetry.enable()
    try:
        eng = _engine(autostart=False)
        try:
            by_kind = {
                "state": 2 * 3 * (4 * 64 * 4 + 3 * 64 * 4),
                "ring": 2 * 2 * 2 * (2 * WINDOW * 16 * 4),
                "full": 2 * 2 * (2 * MAX_LEN * 16 * 4)}
            assert eng.describe()["kv"]["hbm_bytes"] == sum(by_kind.values())
            gauges = telemetry.snapshot()["gauges"]["serving.cache.bytes"]
            for kind, held in by_kind.items():
                (value,) = [v for k, v in gauges.items()
                            if "kind=%s" % kind in k and "sambay" in k]
                assert value == held
        finally:
            eng.close(drain=False)
    finally:
        telemetry.disable()
