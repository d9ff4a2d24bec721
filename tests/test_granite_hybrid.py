"""``models/granite_hybrid.py`` at CPU size: the decode tier's prefill and
decode step (through ``DecodeEngine`` and on their own) against the plain
reference ``forward_logits`` on seeded weights, in float32, so that any
term left out of the mathematics shows as a difference in the logits; and
``ops.ssm.ssd_scan``, the chunked matrix form of the recurrence, against
the recurrence one position after another."""

import functools
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.models import granite_hybrid as gh
from mxnet_tpu.models import transformer_lm as tlm
from mxnet_tpu.ops import attention, registry, ssm
from mxnet_tpu.serving import DecodeEngine
from mxnet_tpu.serving.decode import UnsupportedKVLayout

MAX_LEN = 64
#: one period with both kinds of layer, an attention layer between state
#: layers; no multiplier is the value a model without it would compute with
#: (the attention's is not ``1 / sqrt(head_dim)`` = 0.354)
CFG = gh.GraniteHybridConfig(
    vocab=96, embed=32, heads=8, kv_heads=4, head_dim=8,
    layer_types=("mamba", "mamba", "attention", "mamba"), ffn=48,
    m_heads=4, m_head_dim=16, d_state=8, d_conv=4, chunk=8,
    embedding_multiplier=3.0, attention_multiplier=0.2,
    residual_multiplier=0.5, logits_scaling=2.0, max_len=MAX_LEN, eos_id=96)
BUCKETS = (8, 32)
#: float32 programs against a float32 reference: what is left is the order
#: of the sums (a chunk's matrix products against a loop over positions)
TOL = 3e-5


def _params(seed=0):
    """Seeded weights with every gain and vector moved off its neutral
    start, so that one left out of the program changes the logits."""
    # at these widths a deviation of 0.02 leaves the mixers a thousandth
    # of the residual stream; 0.2 makes every path carry its share
    params = gh.init_params(CFG, seed, jnp.float32, std=0.2)
    rs = np.random.RandomState(seed + 1)

    def moved(a):
        if a.ndim == 1:
            return a + jnp.asarray(rs.normal(0, 0.1, a.shape), a.dtype)
        return a

    return jax.tree_util.tree_map(moved, params)


PARAMS = _params()

#: the plain reference under one jit: a new length compiles one program
_forward = jax.jit(gh.forward_logits, static_argnums=0)


def _reference(tokens, params=PARAMS):
    return np.asarray(_forward(CFG, params, jnp.asarray(tokens)))


def test_the_slot_state_specification():
    """An entry a layer, in layer order: a state (float32 whatever the
    cache's dtype) and the tail of the convolution over x, B and C; K and V
    of an attention layer in pairs of heads."""
    spec = gh.GraniteHybrid(CFG, jnp.bfloat16).cache_spec()
    assert [c.kind for c in spec] == ["state", "state", "full", "state"]
    assert tlm.slot_arrays(spec[0]) == (((8, 64), jnp.float32),
                                        ((3, 80), jnp.bfloat16))
    assert tlm.slot_arrays(spec[2]) == (((2, MAX_LEN, 16), jnp.bfloat16),) * 2
    with pytest.raises(ValueError):
        gh.GraniteHybrid(CFG._replace(kv_heads=1, heads=8))
    with pytest.raises(ValueError):
        gh.GraniteHybrid(CFG._replace(layer_types=("mamba", "window")))


# -- the chunked scan ----------------------------------------------------------
def _recurrence(x, dt, a, b, c, d, s0, real):
    """One position after another, in float64: ``(state (N, heads x P), y
    (real, heads x P))``."""
    h = dt.shape[1]
    n, p = b.shape[1], x.shape[1] // h
    s = np.asarray(s0, np.float64).reshape(n, h, p)
    ys = []
    for i in range(real):
        xi = x[i].astype(np.float64).reshape(h, p)
        s = np.exp(dt[i] * a)[None, :, None] * s \
            + b[i][:, None, None] * (dt[i][:, None] * xi)[None]
        ys.append((np.einsum("nhp,n->hp", s, c[i]) + d[:, None] * xi)
                  .reshape(-1))
    return s.reshape(n, h * p), np.stack(ys)


#: a chunk of 16 positions: one short of it, the whole of it, one more,
#: several chunks in a padded bucket
@pytest.mark.parametrize("path", ["plain", "kernel"])
@pytest.mark.parametrize("t,real,carried", [
    (16, 15, False), (16, 16, False), (32, 17, False), (64, 41, False),
    (48, 48, True), (32, 20, True)])
def test_the_chunked_scan_is_the_recurrence(t, real, carried, path):
    """``ops.ssm.ssd_scan``'s two forms, the ``jnp`` einsums and the kernel
    (interpreted), over one or several chunks equal the recurrence one
    position after another, from a zero state or from one carried in;
    positions with ``dt = 0`` and ``x = 0`` (a bucket's padding) leave the
    state as the last real position did."""
    rs = np.random.RandomState(t + real)
    h, p, n, q = 4, 64, 16, 16
    x = rs.normal(0, 1, (t, h * p)).astype(np.float32)
    dt = np.exp(rs.uniform(np.log(1e-3), np.log(0.5), (t, h))) \
        .astype(np.float32)
    x[real:], dt[real:] = 0.0, 0.0
    a = -rs.uniform(1, 16, (h,)).astype(np.float32)
    b, c = (rs.normal(0, 1, (t, n)).astype(np.float32) for _ in range(2))
    d = rs.normal(0, 1, (h,)).astype(np.float32)
    s0 = rs.normal(0, 1, (n, h * p)).astype(np.float32) if carried \
        else np.zeros((n, h * p), np.float32)
    want_s, want_y = _recurrence(x, dt, a, b, c, d, s0, real)
    args = tuple(jnp.asarray(v) for v in (x, dt, a, b, c, d, s0))
    # float32 sums in another order
    tol = dict(rtol=2e-5, atol=2e-5)
    if path == "plain":
        last, y = ssm._ssd_xla(*args, q)
        np.testing.assert_allclose(y[:real], want_y, **tol)
    else:
        last, y = ssm._ssd_pallas(*args, 4, q, interpret=True)
        # a chunk's own part of y takes bfloat16 operands (8 bits of
        # mantissa: 0.4% a term) and adds in float32; sums of some tens of
        # terms of size 1 land within a few hundredths.  The state is
        # float32 through, made and read
        np.testing.assert_allclose(y[:real], want_y, rtol=2e-2, atol=6e-2)
    np.testing.assert_allclose(last, want_s, **tol)


def test_the_kernel_reads_the_carried_state_in_float32():
    """With nothing pushed (``x = 0``) the kernel's ``y`` is the carried
    state's alone, and that product is float32 through: no bfloat16
    rounding of the state shows."""
    rs = np.random.RandomState(0)
    t, h, p, n, q = 32, 4, 64, 16, 16
    x = np.zeros((t, h * p), np.float32)
    dt = np.full((t, h), 0.01, np.float32)
    a = -np.ones((h,), np.float32)
    b, c = (rs.normal(0, 1, (t, n)).astype(np.float32) for _ in range(2))
    d = np.ones((h,), np.float32)
    s0 = rs.normal(0, 1, (n, h * p)).astype(np.float32)
    want_s, want_y = _recurrence(x, dt, a, b, c, d, s0, t)
    last, y = ssm._ssd_pallas(*(jnp.asarray(v) for v in (
        x, dt, a, b, c, d, s0)), 4, q, interpret=True)
    np.testing.assert_allclose(last, want_s, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)


def test_the_plain_paths_state_products_are_float32_through_too():
    """Of a chunk's four matrix products on the plain path, the two that
    read and make the carried state (``C S_prev`` and ``x B^T``) ask for
    float32 through, as the kernel computes them, so a prompt leaves the
    same state on both paths; ``C B^T`` and a chunk's own part of ``y``
    take the backend's default (one bfloat16 pass on the chip).  Off the
    chip every product is float32 anyway, so this reads what is asked
    for."""
    f32 = jnp.float32
    shapes = tuple(jax.ShapeDtypeStruct(shape, f32) for shape in (
        (32, 256), (32, 4), (4,), (32, 16), (32, 16), (4,), (16, 256)))
    text = str(jax.make_jaxpr(functools.partial(ssm._ssd_xla, q=16))(
        *shapes))
    asked = re.findall(r"(?s)dot_general\[(.*?)\n\s*\]", text)
    assert len(asked) == 4, text
    assert sorted(a.count("HIGHEST") for a in asked) == [0, 0, 2, 2], asked


def test_the_chunked_scans_plan_follows_the_shapes():
    """Off the chip the plain path; on a trace bound for it the kernel at
    whole heads in 128 lanes, float32 and whole tiles of positions, with
    the reason where not; positions that are no whole chunks are an
    error."""
    def plan(t, heads, width, n=128, dtype=jnp.float32, chunk=256):
        return ssm.ssd_scan_plan(
            jax.ShapeDtypeStruct((t, heads * width), dtype),
            jax.ShapeDtypeStruct((t, heads), jnp.float32),
            jax.ShapeDtypeStruct((t, n), jnp.float32), chunk)

    assert plan(128, 64, 64) == ((None, 128), "not_tpu")
    token = registry.trace_device.set("tpu")
    try:
        assert plan(1024, 64, 64) == ((8, 256), None)
        assert plan(512, 64, 64) == ((8, 256), None)
        assert plan(128, 64, 64) == ((8, 128), None)
        assert plan(256, 4, 128) == ((4, 256), None)
        assert plan(256, 8, 48) == ((None, 256), "lanes")
        assert plan(256, 3, 64) == ((None, 256), "lanes")
        assert plan(64, 64, 64) == ((None, 64), "tile")
        assert plan(256, 64, 64, n=12) == ((None, 256), "tile")
        assert plan(256, 64, 64, dtype=jnp.bfloat16) \
            == ((None, 256), "dtype")
        with pytest.raises(ValueError):
            plan(384, 64, 64)
    finally:
        registry.trace_device.reset(token)


def test_the_scan_counts_the_path_it_took():
    telemetry.enable()
    try:
        f32 = jnp.float32
        ssm.ssd_scan(jnp.zeros((8, 32), f32), jnp.zeros((8, 2), f32),
                     -jnp.ones((2,), f32), jnp.zeros((8, 4), f32),
                     jnp.zeros((8, 4), f32), jnp.ones((2,), f32),
                     jnp.zeros((4, 32), f32))
        paths = telemetry.snapshot()["counters"]["ops.kernel_path"]
        assert any("op=ssd_scan" in k and "path=xla" in k
                   and "reason=not_tpu" in k for k in paths)
    finally:
        telemetry.disable()


# -- heads in pairs ------------------------------------------------------------
def test_heads_cached_in_pairs_attend_as_heads_laid_singly():
    """A query beside zeros over ``[k_2g, k_2g+1]``, and its own half of
    the context over ``[v_2g, v_2g+1]``, is grouped-query attention with
    heads of ``head_dim`` laid singly: query head ``i`` over K/V head ``i //
    (heads / kv_heads)``."""
    rs = np.random.RandomState(4)
    s, rows, hd = 3, 16, CFG.head_dim
    reads = CFG.heads // CFG.kv_heads
    q = rs.normal(0, 1, (s, CFG.heads, hd)).astype(np.float32)
    k, v = (rs.normal(0, 1, (s, rows, CFG.kv_heads, hd)).astype(np.float32)
            for _ in range(2))
    pos = np.array([0, 9, 15], np.int32)
    want = np.zeros((s, CFG.heads, hd), np.float32)
    for i in range(s):
        for head in range(CFG.heads):
            g = head // reads
            sc = k[i, :pos[i] + 1, g] @ q[i, head] * CFG.attention_multiplier
            w = np.exp(sc - sc.max())
            want[i, head] = (w / w.sum()) @ v[i, :pos[i] + 1, g]
    # (S, rows, kv, d) -> (S, kv / 2, rows, 2 d)
    paired = [jnp.asarray(m.reshape(s, rows, CFG.kv_heads // 2, 2 * hd)
                          .transpose(0, 2, 1, 3)) for m in (k, v)]
    ctx = attention.decode_attention(
        gh._pair_queries(CFG, jnp.asarray(q)), *paired, jnp.asarray(pos),
        CFG.attention_multiplier)
    np.testing.assert_allclose(gh._own_half(CFG, ctx),
                               want.reshape(s, -1), rtol=1e-5, atol=1e-5)


# -- prefill and decode step on their own --------------------------------------
@pytest.fixture(scope="module")
def programs():
    """The model with ONE jit of its prefill and of its step for every
    case below: a case compiles only the shapes no case before it had."""
    model = gh.GraniteHybrid(CFG, jnp.float32)
    return model, jax.jit(model.prefill), jax.jit(model.decode_step)


def _slot_state(model, slots, fill):
    return [[jnp.full((slots,) + shape, fill, dtype)
             for shape, dtype in (tlm.slot_arrays(c)[i]
                                  for c in model.cache_spec())]
            for i in range(2)]


def _one_session(model, prefill, step, params, tokens, prompt, bucket,
                 new):
    """``(last logits of the prefill, logits of every step, the extra
    state, the slot's own first arrays at the end)`` of one session in slot
    1 of 3, whose slot held another session's state."""
    padded = np.full((bucket,), 7, np.int32)       # padding is not token 0
    padded[:prompt] = tokens[:prompt]
    last, firsts, seconds = prefill(params, jnp.asarray(padded),
                                    jnp.int32(prompt))
    slots, slot = 3, 1
    held = _slot_state(model, slots, 0.5)          # what a session left
    for side, values in zip(held, (firsts, seconds)):
        for i, v in enumerate(values):
            side[i] = jax.lax.dynamic_update_slice(
                side[i], v[None], (slot,) + (0,) * v.ndim)
    extra = model.extra_state()
    firsts, seconds = tuple(held[0]), tuple(held[1])
    active = jnp.arange(slots) == slot
    served = []
    for p in range(prompt, prompt + new):
        last_tok = jnp.zeros((slots,), jnp.int32).at[slot].set(tokens[p])
        lengths = jnp.zeros((slots,), jnp.int32).at[slot].set(p)
        logits, firsts, seconds, extra = step(
            params, firsts, seconds, last_tok, lengths, active, extra)
        served.append(np.asarray(logits[slot]))
    return (np.asarray(last), np.stack(served), extra,
            [np.asarray(a[slot]) for a in firsts])


@pytest.mark.parametrize("prompt,bucket,new", [
    (1, 8, 4),       # shorter than the convolution's tail
    (2, 8, 12),
    (7, 8, 6),       # one short of a chunk
    (8, 8, 3),       # a chunk and a bucket filled to the last row
    (9, 32, 10),     # one past a chunk, in a padded bucket
    (30, 32, 20)])   # several chunks, the state carried between them
def test_prefill_then_decode_steps_give_the_references_logits(prompt, bucket,
                                                              new, programs):
    """A padded prompt through ``prefill`` gives the reference's last
    logits, and the state it leaves in a slot that held another session's
    carries the decode steps to the reference's logits at every later
    position."""
    model, prefill, step = programs
    tokens = np.random.RandomState(prompt).randint(0, CFG.vocab,
                                                   prompt + new)
    want = _reference(tokens)
    last, served, extra, _ = _one_session(model, prefill, step, PARAMS,
                                          tokens, prompt, bucket, new)
    np.testing.assert_allclose(last, want[prompt - 1], atol=TOL)
    np.testing.assert_allclose(served, want[prompt:], atol=TOL)
    counted = model.counters(jax.device_get(extra))
    assert counted["rows"] == counted["steps"] == new
    assert counted["rows_full"] == sum(range(prompt + 1, prompt + new + 1))


def _off(got, want):
    """The distance as a share of ``want``'s size."""
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("prompt,bucket,new", [(30, 32, 20), (8, 8, 40)])
def test_the_state_a_session_leaves_is_the_recurrences(prompt, bucket, new,
                                                       programs):
    """The recurrent states themselves, not the logits they led to: after
    a prefill (several chunks, the state carried between them) and decode
    steps, every mamba layer's state in the slot is the plain reference's
    within 1e-5 of its size, where the same state rounded to bfloat16
    (which would halve a step's state bytes) lies a thousandth away.  The
    served tokens' gaps cannot tell the two apart (PERF.md section 7, PR
    46), so a change that narrows the state brings this comparison at its
    own size."""
    model, prefill, step = programs
    tokens = np.random.RandomState(prompt).randint(0, CFG.vocab,
                                                   prompt + new)
    *_, firsts = _one_session(model, prefill, step, PARAMS, tokens, prompt,
                              bucket, new)
    want = jax.jit(gh.forward_states, static_argnums=0)(
        CFG, PARAMS, jnp.asarray(tokens))
    got = [a for c, a in zip(model.cache_spec(), firsts)
           if c.kind == "state"]
    assert len(got) == len(want) == 3
    for mine, ref in zip(got, want):
        ref = np.asarray(ref)
        assert _off(mine, ref) < 1e-5
        assert _off(np.asarray(jnp.asarray(mine).astype(jnp.bfloat16)
                               .astype(jnp.float32)), ref) > 1e-3


_NEUTRAL = {"embedding_multiplier": 1.0,
            "attention_multiplier": CFG.head_dim ** -0.5,
            "residual_multiplier": 1.0, "logits_scaling": 1.0}


@pytest.mark.parametrize("fault", sorted(_NEUTRAL) + [
    "the gate after the norm", "no convolution bias", "no D",
    "a state not reset", "a tail not reset"])
def test_the_reference_tells_a_planted_fault(fault, monkeypatch):
    """The comparison above is not blind: each multiplier left out (set to
    what a model without it computes with), the gate applied after the
    norm, the convolution's bias or ``D`` left out, and a slot that keeps
    what its last session left, moves the logits by far more than the
    tolerance."""
    params, cfg = PARAMS, CFG
    if fault in _NEUTRAL:
        cfg = CFG._replace(**{fault: _NEUTRAL[fault]})
    elif fault == "the gate after the norm":
        monkeypatch.setattr(
            gh, "_gated_norm",
            lambda y, z, g: gh._rms(y, g) * jax.nn.silu(z))
    elif fault in ("no convolution bias", "no D"):
        name = "conv_b" if fault == "no convolution bias" else "D"
        params = dict(PARAMS, layers=[
            dict(p, **{name: jnp.zeros_like(p[name])}) if name in p else p
            for p in PARAMS["layers"]])
    model = gh.GraniteHybrid(cfg, jnp.float32)
    tokens = np.random.RandomState(3).randint(0, CFG.vocab, 24)
    want = _reference(tokens)
    padded = np.zeros((32,), np.int32)
    padded[:20] = tokens[:20]
    last, firsts, seconds = jax.jit(model.prefill)(
        params, jnp.asarray(padded), jnp.int32(20))
    if fault.endswith("not reset"):
        # the slot keeps what its last session left in the state layers
        _, left1, left2 = jax.jit(model.prefill)(
            params, jnp.asarray(padded[::-1].copy()), jnp.int32(32))
        kept = [c.kind == "state" for c in model.cache_spec()]
        if fault == "a state not reset":
            firsts = tuple(old if k else mine for k, old, mine
                           in zip(kept, left1, firsts))
        else:
            seconds = tuple(old if k else mine for k, old, mine
                            in zip(kept, left2, seconds))
    logits, *_ = jax.jit(model.decode_step)(
        params, tuple(a[None] for a in firsts),
        tuple(a[None] for a in seconds), jnp.asarray(tokens[20:21]),
        jnp.full((1,), 20, jnp.int32), jnp.ones((1,), bool),
        model.extra_state())
    off = max(np.abs(np.asarray(last) - want[19]).max(),
              np.abs(np.asarray(logits[0]) - want[20]).max())
    assert off > 10 * TOL, off


# -- through the engine --------------------------------------------------------
class Recording(gh.GraniteHybrid):
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
    opts = dict(slots=2, prefill_buckets=BUCKETS, name="granite")
    opts.update(kw)
    return DecodeEngine(model or gh.GraniteHybrid(CFG, jnp.float32), PARAMS,
                        **opts)


@pytest.fixture(scope="module")
def recording():
    """``(model, engine)``: ONE running engine over a :class:`Recording`
    model for the tests that send it sessions one after another.  A test
    that stops it starts it again."""
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


@pytest.mark.parametrize("prompt", [2, 7, 8, 9, 20])
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
    np.testing.assert_allclose(mine[-1], want[prompt - 1], atol=TOL)
    np.testing.assert_allclose(
        _served_logits(model, sess.slot, prompt, new - 1),
        want[prompt:prompt + new - 1], atol=TOL)
    assert out == [int(t) for t in want[prompt - 1:-1].argmax(-1)]


def test_a_slots_second_session_does_not_see_the_firsts_state(recording):
    """Two sessions in turn in ONE slot: the second's logits are those of
    an engine whose state is new, so the admission overwrote the recurrent
    states and the convolutions' tails the first left (no length masks
    them), and the engine counted both overwrites."""
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
        used, _reference(np.concatenate([second, out]))[3:12], atol=TOL)


@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_resume_of_a_migrated_transcript_restores_the_state(temperature,
                                                            recording,
                                                            plain):
    """A session stopped mid-generation and resumed on another engine by
    re-prefilling its transcript ends with the stream the first engine
    would have given: the re-prefill rebuilt every recurrent state."""
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
    """``describe()`` and the ``serving.cache.bytes`` gauges, read once at
    set-up, count every array of the slot state: states and tails beside
    the attention layer's K and V.  At the served size (64 slots of 36
    float32 states of 128 x 4096) the states alone are 4.83 GB."""
    telemetry.enable()
    try:
        eng = _engine(autostart=False)
        try:
            by_kind = {"state": 2 * 3 * (8 * 64 * 4 + 3 * 80 * 4),
                       "full": 2 * 2 * (2 * MAX_LEN * 16 * 4)}
            assert eng.describe()["kv"]["hbm_bytes"] == sum(by_kind.values())
            gauges = telemetry.snapshot()["gauges"]["serving.cache.bytes"]
            for kind, held in by_kind.items():
                (value,) = [v for k, v in gauges.items()
                            if "kind=%s" % kind in k and "granite" in k]
                assert value == held
        finally:
            eng.close(drain=False)
    finally:
        telemetry.disable()
    assert 64 * 36 * 128 * 4096 * 4 == 4_831_838_208
