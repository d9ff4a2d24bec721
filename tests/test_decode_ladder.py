"""The dense decode step's ladder (``transformer_lm.ladder``, ISSUE 48): the
step's attention reads the rows up to its longest ACTIVE slot's horizon,
rounded up to a rung of whole lane tiles and chosen on the device, and not
all ``max_len``.  Every rung that holds the horizons gives the context the
whole cache gives; a session that crosses a rung is served the tokens the
step without a ladder serves; the paged twin, which reads every row it
gathered, serves them too; a cache of one rung runs the same lines with no
branch; the engine says how many rows its dense steps read."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.models import transformer_lm as tlm
from mxnet_tpu.serving import DecodeEngine

VOCAB, EMBED, HEADS, LAYERS, FFN = 32, 16, 2, 2, 32


def _cfg(max_len):
    return tlm.LMConfig(VOCAB, EMBED, HEADS, LAYERS, FFN, max_len,
                        eos_id=VOCAB)


@pytest.fixture(autouse=True)
def _clean():
    telemetry.reset()
    telemetry.enable()
    yield
    telemetry.disable()
    telemetry.reset()


def _whole(q, kt, vt, pos, rung=None):
    """``_attend_slots`` as it stood before the ladder, two einsums over
    every row, handed the cache as the ladder is: the reference."""
    del rung
    scores = jnp.einsum("shd,shdm->shm", q, kt) \
        * (1.0 / np.sqrt(q.shape[-1]))
    mask = jnp.arange(kt.shape[-1]) <= pos[:, None, None]
    att = jax.nn.softmax(
        jnp.where(mask, scores, jnp.float32(-1e30)), axis=-1)
    return jnp.einsum("shm,shdm->shd", att, vt)


@pytest.mark.parametrize("max_len, width, rungs", [
    (32, 128, (32,)),
    (128, 128, (128,)),
    (129, 128, (128, 129)),
    (512, 128, (128, 256, 384, 512)),
    (1024, 128, tuple(range(128, 1025, 128))),
    (1100, 256, (256, 512, 768, 1024, 1100)),
    (4096, 512, tuple(range(512, 4097, 512))),
    (16384, 2048, tuple(range(2048, 16385, 2048))),
])
def test_ladder_is_whole_lane_tiles_from_max_len_alone(max_len, width,
                                                       rungs):
    """A rung every ``width`` positions, a multiple of 128, the last one
    ``max_len`` itself, never more than eight; the host's reading names the
    rung that holds a length as an inclusive horizon."""
    assert tlm.ladder(max_len) == (width, rungs)
    assert len(rungs) <= 8 and width % 128 == 0
    for longest in (-1, 0, 1, width - 1, width, max_len - 1, max_len,
                    max_len + 7):
        rows = tlm.attended_rows(max_len, longest)
        horizon = min(max(longest, 0), max_len - 1)
        assert rows in rungs and horizon < rows
        assert rows == min(r for r in rungs if r > horizon)


def _lengths(max_len):
    return {"0": [0, 0, 0, 0], "127": [127, 3, 127, 0],
            "128": [128, 128, 0, 5], "129": [129, 0, 64, 129],
            "max_len-1": [max_len - 1, 0, 7, 200],
            "mixed": [5, 300, 131, max_len // 2]}


@pytest.mark.parametrize("case", ["0", "127", "128", "129", "max_len-1",
                                  "mixed"])
@pytest.mark.parametrize("max_len", [512, 1024])
def test_each_rung_that_holds_the_horizons_gives_the_whole_context(
        max_len, case):
    """The rung chosen from the lengths, and every rung above it, returns
    what all ``max_len`` rows return to 1e-6: the rows it leaves out have
    weight exactly 0."""
    lengths = _lengths(max_len)[case]
    s, hd = len(lengths), EMBED // HEADS
    rs = np.random.RandomState(max_len + len(case))
    q = jnp.asarray(rs.normal(size=(s, HEADS, hd)), jnp.float32)
    kt, vt = (jnp.asarray(rs.normal(size=(s, HEADS, hd, max_len)),
                          jnp.float32) for _ in range(2))
    pos = jnp.clip(jnp.asarray(lengths, jnp.int32), 0, max_len - 1)
    width, rungs = tlm.ladder(max_len)
    chosen = int(tlm._rung(max_len, pos, None))
    assert chosen == max(lengths) // width
    assert rungs[chosen] == tlm.attended_rows(max_len, max(lengths))
    want = _whole(q, kt, vt, pos)
    attend = jax.jit(lambda rung: tlm._attend_slots(q, kt, vt, pos, rung))
    for rung in range(chosen, len(rungs)):
        got = attend(jnp.int32(rung))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6,
                                   err_msg="rung %d" % rung)


@pytest.mark.parametrize("max_len", [512, 1024])
def test_the_rung_follows_the_active_slots(max_len, monkeypatch):
    """A finished long session leaves its length in the state: beside
    short active slots the step takes the first rung, and their logits are
    the ones the step without a ladder returns."""
    cfg = _cfg(max_len)
    params = tlm.init_params(cfg, seed=5)
    lengths = jnp.asarray([3, max_len - 2, 100, max_len], jnp.int32)
    active = jnp.asarray([True, False, True, False])
    width, _ = tlm.ladder(max_len)
    pos = jnp.clip(lengths, 0, max_len - 1)
    assert int(tlm._rung(max_len, pos, active)) == 0
    assert int(tlm._rung(max_len, pos, None)) == (max_len - 1) // width
    assert int(tlm._rung(max_len, pos, ~active)) == (max_len - 1) // width

    s, hd = len(lengths), EMBED // HEADS
    rs = np.random.RandomState(7)
    cache = tuple(jnp.asarray(rs.normal(size=(s, max_len, HEADS, hd)),
                              jnp.float32) for _ in range(LAYERS))
    last = jnp.asarray(rs.randint(0, VOCAB, size=s), jnp.int32)

    def step():
        return jax.jit(lambda *a: tlm.decode_step_math(cfg, *a))(
            params, cache, cache, last, lengths, active)

    got = step()
    monkeypatch.setattr(tlm, "_attend_slots", _whole)
    want = step()
    live = np.asarray(active)
    np.testing.assert_allclose(got[0][live], want[0][live], rtol=1e-5,
                               atol=1e-6)
    # the rows written are the rows written whatever attention reads
    # (the first layer's exactly: the later ones' inputs pass through it)
    np.testing.assert_array_equal(got[1][0], want[1][0])
    for g, w in zip(got[1][1:] + got[2], want[1][1:] + want[2]):
        np.testing.assert_allclose(g[live], w[live], rtol=1e-5, atol=1e-6)


def _served(cfg, params, prompt, new, monkeypatch=None, **opts):
    """What an engine serves one session, and the share of rows its steps
    read; ``monkeypatch`` builds it over the step without a ladder."""
    if monkeypatch is not None:
        monkeypatch.setattr(tlm, "_attend_slots", _whole)
    eng = DecodeEngine(cfg, params, name="lm", slots=2,
                       prefill_buckets=(8, 128), max_queue=8, **opts)
    try:
        # the warm-up's steps are not this session's
        eng.model_counters()
        out = eng.generate(prompt, max_new_tokens=new, timeout=300)
        return out, eng.model_counters()
    finally:
        eng.close(drain=False)
        if monkeypatch is not None:
            monkeypatch.undo()


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_a_session_that_crosses_a_rung_is_served_the_same_tokens(
        layout, monkeypatch):
    """120 prompt tokens and 24 new ones: the horizon passes 127 on the
    way.  The dense engine serves what the step without a ladder serves,
    and the paged twin what the dense engine serves."""
    cfg = _cfg(512)
    params = tlm.init_params(cfg, seed=3)
    prompt = list(np.random.RandomState(1).randint(0, VOCAB, size=120))
    want, _ = _served(cfg, params, prompt, 24, monkeypatch)
    opts = {"kv_layout": "paged", "kv_block_size": 16} \
        if layout == "paged" else {}
    got, counted = _served(cfg, params, prompt, 24, **opts)
    assert got == want and len(got) == 24
    if layout == "paged":
        # the paged step reads every row it gathered, and says so
        assert counted == {}
        assert telemetry.snapshot()["counters"]["ops.kernel_path"][
            "op=attend_slots,path=whole,reason=gathered"] == 1
        return
    # steps at lengths 120..143: some read 128 rows of 512, some 256
    share = counted["gauges"]["serving.attn.rows_read_share"]
    assert 0.25 < share < 0.5


def test_the_engine_says_how_many_rows_its_steps_read():
    """``model_counters()`` publishes ``serving.attn.rows_read_share`` of a
    model that offers ``attended_rows``: the mean over the steps since the
    last read, from the host's mirror of the lengths; a read with no step
    since leaves the gauge as it stands."""
    cfg = _cfg(512)
    params = tlm.init_params(cfg, seed=3)
    eng = DecodeEngine(cfg, params, name="lm", slots=2,
                       prefill_buckets=(8,), max_queue=8)
    try:
        eng.model_counters()
        eng.generate([5, 7, 9, 2], max_new_tokens=6, timeout=300)
        counted = eng.model_counters()
        assert counted == {
            "gauges": {"serving.attn.rows_read_share": 128 / 512}}
        assert telemetry.gauge_value(
            "serving.attn.rows_read_share", model="lm",
            replica=eng.replica) == 0.25
        assert eng.model_counters() == {}
        assert "model_counters" not in eng.describe()
        assert telemetry.gauge_value(
            "serving.attn.rows_read_share", model="lm",
            replica=eng.replica) == 0.25
    finally:
        eng.close(drain=False)


def _step_text(cfg, slots=3):
    sds = jax.ShapeDtypeStruct
    hd = cfg.embed // cfg.heads
    params = jax.eval_shape(lambda: tlm.init_params(cfg, seed=0))
    cache = tuple(sds((slots, cfg.max_len, cfg.heads, hd), jnp.float32)
                  for _ in range(cfg.layers))
    vec = sds((slots,), jnp.int32)
    # a new function object each time: JAX keeps the trace of the last
    return jax.jit(lambda *a: tlm.decode_step_math(cfg, *a)).lower(
        params, cache, cache, vec, vec, sds((slots,), jnp.bool_)).as_text()


@pytest.mark.parametrize("max_len, path", [(32, "whole"), (128, "whole"),
                                           (512, "ladder")])
def test_a_cache_of_one_rung_has_no_branch(max_len, path):
    """128 positions or fewer are one rung: the step lowers no branch (the
    tiny configurations of the tests run the ladder's lines over every
    row).  Above that the step holds one conditional a layer.  Either way
    the trace counts its path once, as the kernels do."""
    cfg = _cfg(max_len)
    text = _step_text(cfg)
    counted = telemetry.snapshot()["counters"]["ops.kernel_path"]
    reason = "ok" if path == "ladder" else "one_rung"
    assert counted == {
        "op=attend_slots,path=%s,reason=%s" % (path, reason): 1}
    assert text.count("stablehlo.case") == \
        (cfg.layers if path == "ladder" else 0)
