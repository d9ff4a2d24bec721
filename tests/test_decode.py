"""Continuous-batching decode tier (docs/serving.md "Continuous batching
& replica pool"): decode-vs-forward parity, slot lifecycle, mid-decode
admission, shedding/quotas/priority, replica quarantine + re-warm,
pointer-flip version swaps, the HTTP /generate + /models surface, the
compile-count acceptance demo (one prefill compile per bucket per
replica + one decode-step compile per replica at warm-up, ZERO during
traffic), and the SIGTERM-drain chaos half (in-flight sequences finish
or are shed with a typed error — never silently dropped)."""

import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, telemetry, tracing
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import transformer_lm as tlm
from mxnet_tpu.serving import (DeadlineExceeded, DecodeEngine,
                               InvalidRequest, ModelRegistry, Overloaded,
                               QuotaExceeded, ReplicaPool,
                               ServingHTTPServer, lm_pool)
from serial_loop import serial_loop

# tiny LM: every compile stays sub-second on the CPU CI host
VOCAB, EMBED, HEADS, LAYERS, FFN, MAX_LEN = 32, 16, 2, 2, 32, 32
#: eos_id == vocab is unreachable (samples are 0..vocab-1): generation
#: lengths become deterministic — what the lifecycle tests need
CFG_NO_EOS = tlm.LMConfig(VOCAB, EMBED, HEADS, LAYERS, FFN, MAX_LEN,
                          eos_id=VOCAB)
CFG_EOS = tlm.LMConfig(VOCAB, EMBED, HEADS, LAYERS, FFN, MAX_LEN,
                       eos_id=2)
PARAMS = tlm.init_params(CFG_NO_EOS, seed=3)
PROMPT = [5, 7, 9, 2]
ENGINE_OPTS = {"slots": 4, "prefill_buckets": (4, 8), "max_queue": 64}


@pytest.fixture(autouse=True)
def _clean():
    faults.disarm()
    telemetry.reset()
    telemetry.enable()
    yield
    faults.disarm()
    telemetry.disable()
    telemetry.reset()


def _engine(cfg=CFG_NO_EOS, **kw):
    opts = dict(ENGINE_OPTS)
    opts.update(kw)
    return DecodeEngine(cfg, PARAMS, name="lm", **opts)


@pytest.fixture(scope="module")
def shared():
    """ONE running engine at ``ENGINE_OPTS`` for the tests that only send
    it traffic: tier-1 runs with the compile cache off, so every engine
    built compiles its step and its buckets anew.  A test that stops it
    starts it again; one that needs slots, buckets, a layout or telemetry
    at the build of its own builds its own."""
    eng = _engine()
    yield eng
    eng.close(drain=False)


#: the full forward under one jit
_forward = jax.jit(tlm.forward_logits, static_argnums=0)


def _teacher_forced(cfg, prompt, new):
    """Iterated argmax of the full forward, up to ``new`` tokens or the
    EOS.  The model is causal, so each token is read at the last real
    position of a row padded to ``MAX_LEN``: ONE shape, where a row a
    length longer each time compiles every operation anew a token."""
    toks = list(prompt)
    for _ in range(new):
        padded = np.zeros((1, MAX_LEN), np.int32)
        padded[0, :len(toks)] = toks
        toks.append(int(_forward(cfg, PARAMS, padded)[0, len(toks) - 1]
                        .argmax()))
        if toks[-1] == cfg.eos_id:
            break
    return toks[len(prompt):]


def _compiles():
    c = telemetry.snapshot()["counters"].get("xla.compile.count", {})
    return (c.get("kind=decode_prefill", 0), c.get("kind=decode_step", 0))


# -- the model: one block, five callers --------------------------------------

def _entry_point_calls():
    """Each of ``transformer_lm``'s entry points as a zero-argument trace
    over shapes alone."""
    import jax
    import jax.numpy as jnp

    cfg, hd = CFG_NO_EOS, EMBED // HEADS
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: PARAMS)
    i32 = sds((), jnp.int32)
    dense = tuple(sds((3, MAX_LEN, HEADS, hd), jnp.float32)
                  for _ in range(LAYERS))
    pool = tuple(sds((9, 8, HEADS, hd), jnp.float32) for _ in range(LAYERS))
    slots = sds((3,), jnp.int32)
    return {
        "forward_logits": (lambda p, t: tlm.forward_logits(cfg, p, t),
                           params, sds((2, 5), jnp.int32)),
        "prefill_kv": (lambda *a: tlm.prefill_kv(cfg, *a),
                       params, sds((8,), jnp.int32), i32),
        "decode_step_math": (lambda *a: tlm.decode_step_math(cfg, *a),
                             params, dense, dense, slots, slots),
        "prefill_kv_paged": (lambda *a: tlm.prefill_kv_paged(cfg, *a),
                             params, pool, pool, sds((4,), jnp.int32),
                             sds((8,), jnp.int32), i32, i32),
        "decode_step_paged": (lambda *a: tlm.decode_step_paged(cfg, *a),
                              params, pool, pool, sds((3, 4), jnp.int32),
                              slots, slots),
    }


@pytest.mark.parametrize("entry", ["forward_logits", "prefill_kv",
                                   "decode_step_math", "prefill_kv_paged",
                                   "decode_step_paged"])
def test_entry_point_traces_the_one_block_once_a_layer(entry, monkeypatch):
    """The layer's mathematics lives in ``transformer_lm._block`` alone: an
    entry point traces it once a layer, and with the block taken out what
    is left of the trace holds one matrix product (the head's) and no
    GELU."""
    import jax

    fn, *shapes = _entry_point_calls()[entry]
    block, calls = tlm._block, []

    def counted(*args):
        calls.append(1)
        return block(*args)

    monkeypatch.setattr(tlm, "_block", counted)
    jax.eval_shape(fn, *shapes)
    assert len(calls) == LAYERS

    monkeypatch.setattr(tlm, "_block", lambda cfg, pl, x, attend: x)
    # a new function object: JAX keeps the trace of the one above
    fn, *shapes = _entry_point_calls()[entry]
    prims = [eqn.primitive.name
             for eqn in jax.make_jaxpr(fn)(*shapes).jaxpr.eqns]
    assert prims.count("dot_general") == 1, prims
    assert not {"tanh", "erf", "logistic"} & set(prims), prims


# -- engine: correctness ----------------------------------------------------

def test_greedy_decode_matches_full_forward(shared):
    """The slot decode path is bit-compatible with teacher forcing:
    greedy generation == iterated argmax of the full forward."""
    out = shared.generate(PROMPT, max_new_tokens=6, timeout=120)
    assert out == _teacher_forced(CFG_NO_EOS, PROMPT, 6)


def _scatter_rows(cache, rows, pos):
    """The dense step's cache write until PR 25, and the reference for
    :func:`tlm.write_rows` since."""
    import jax.numpy as jnp

    return cache.at[jnp.arange(cache.shape[0]), pos].set(rows)


@pytest.mark.parametrize("lengths", [
    pytest.param([0, 3, 5, 9], id="a-slot-at-position-0"),
    pytest.param([MAX_LEN - 1, 3, 5, 9], id="a-slot-at-max_len-1"),
    # slot 1 was never admitted; slot 2 retired at the cache's end, where
    # its count is one past the last row and the write is clamped
    pytest.param([4, 0, MAX_LEN, 9], id="inactive-slots-ride-along"),
    pytest.param([6, 11, 6, 11], id="two-slots-at-one-position"),
])
def test_dense_step_writes_the_rows_the_scatter_wrote(lengths, monkeypatch):
    """``write_rows`` equals ``cache.at[rows, pos].set(k)`` as an array,
    and the step built on it returns the logits and every layer's K and V
    that the step built on the scatter returns, bit for bit."""
    import jax
    import jax.numpy as jnp

    s, hd = len(lengths), EMBED // HEADS
    rs = np.random.RandomState(11)

    def caches():
        return tuple(
            jnp.asarray(rs.normal(size=(s, MAX_LEN, HEADS, hd)),
                        jnp.float32) for _ in range(LAYERS))

    cache_k, cache_v = caches(), caches()
    rows = jnp.asarray(rs.normal(size=(s, HEADS, hd)), jnp.float32)
    pos = jnp.clip(jnp.asarray(lengths, jnp.int32), 0, MAX_LEN - 1)
    np.testing.assert_array_equal(
        tlm.write_rows(cache_k[0], rows, pos),
        _scatter_rows(cache_k[0], rows, pos))

    last = jnp.asarray(rs.randint(0, VOCAB, size=s), jnp.int32)

    def step():
        # a jit of its own each time: the trace looks write_rows up anew
        return jax.jit(lambda *a: tlm.decode_step_math(CFG_NO_EOS, *a))(
            PARAMS, cache_k, cache_v, last,
            jnp.asarray(lengths, jnp.int32))

    got = step()
    monkeypatch.setattr(tlm, "write_rows", _scatter_rows)
    want = step()
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(g, w)


def test_step_lowers_for_the_shapes_the_benchmark_lowers_it_with(shared):
    """``benchmark/families/decode_engine.py`` ``scratch_bytes`` lowers
    ``engine._step_fn`` again after every window, on the chip only, from
    shapes alone: per-layer K and V of ``(slots, max_len, heads,
    head_dim)`` float32 and the six small arrays.  The same call here, so
    that a state of another shape fails on the CPU first."""
    import jax
    import jax.numpy as jnp

    eng = shared
    cfg, s = eng.cfg, eng.slots

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    kv = sds((s, cfg.max_len, cfg.heads, cfg.embed // cfg.heads),
             jnp.float32)
    state = (tuple(kv for _ in range(cfg.layers)),
             tuple(kv for _ in range(cfg.layers)),
             sds((s,), jnp.int32), sds((s,), jnp.int32),
             sds((s,), jnp.int32), sds((s,), jnp.bool_),
             sds((s,), jnp.float32), sds((s,), jnp.uint32))
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), eng._params)
    lowered = eng._step_fn.lower(params, state, sds((s,), jnp.bool_))
    assert lowered.compile().memory_analysis().temp_size_in_bytes >= 0
    new_state, packed = lowered.out_info
    assert jax.tree_util.tree_map(
        lambda a: (a.shape, a.dtype), new_state) == \
        jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), state)
    assert packed.shape == (3, s)


def test_eos_retires_early_and_is_included():
    """With a reachable EOS the sequence stops at it (EOS is the last
    token) instead of running to max_new_tokens; either way the decode
    path tracks the teacher-forcing reference exactly."""
    ref = _teacher_forced(CFG_EOS, PROMPT, 20)
    eng = _engine(cfg=CFG_EOS)
    try:
        out = eng.generate(PROMPT, max_new_tokens=20, timeout=120)
        assert out == ref
        if CFG_EOS.eos_id in out:
            assert out[-1] == CFG_EOS.eos_id and len(out) < 20
    finally:
        eng.close()


def test_temperature_stream_is_seeded_and_valid(shared):
    """Temperature sampling draws through mx.random key material: same
    seed => same stream, and every token is a valid id."""
    mx.random.seed(11)
    a = shared.generate(PROMPT, max_new_tokens=8, temperature=0.8,
                        timeout=120)
    mx.random.seed(11)
    b = shared.generate(PROMPT, max_new_tokens=8, temperature=0.8,
                        timeout=120)
    assert a == b and len(a) == 8
    assert all(0 <= t < VOCAB for t in a)


def test_invalid_requests_fail_at_submit(shared):
    eng = shared
    with pytest.raises(InvalidRequest):
        eng.submit([], max_new_tokens=3)
    with pytest.raises(InvalidRequest):
        eng.submit(list(range(1, 10)), max_new_tokens=3)  # > bucket 8
    with pytest.raises(InvalidRequest):
        eng.submit([VOCAB + 3], max_new_tokens=3)  # bad token id
    with pytest.raises(InvalidRequest):
        eng.submit(PROMPT, max_new_tokens=0)
    with pytest.raises(InvalidRequest):
        eng.submit(PROMPT, max_new_tokens=3, temperature=-1.0)


# -- engine: continuous batching lifecycle ----------------------------------

def test_mid_decode_admission_joins_running_batch():
    """THE continuous-batching property: a request submitted while a
    long generation is mid-flight gets a free slot BETWEEN steps and
    finishes long before the running sequence does — it never waits for
    the batch to complete."""
    eng = _engine(slots=2)
    try:
        # the token callback runs on the engine's thread: at A's fifth
        # token it holds the engine there until B is queued.  Without that
        # A's twenty tokens still to come (a millisecond a step) can be out
        # before this thread wakes up under a loaded host, B then finds A
        # gone, and the test fails for the race and not for the engine
        five, queued = threading.Event(), threading.Event()
        a_tok = []

        def on_a(t):
            a_tok.append(t)
            if len(a_tok) == 5:
                five.set()
                queued.wait(60)

        a = eng.submit(PROMPT, max_new_tokens=25, on_token=on_a)
        assert five.wait(60), "A never started decoding"
        a_len_at_b_done = []
        b = eng.submit([3, 4], max_new_tokens=3,
                       on_done=lambda _s: a_len_at_b_done.append(
                           len(a.tokens)))
        queued.set()
        out_b = b.result(60)
        assert len(out_b) == 3
        # B completed while A was still decoding: it joined the running
        # batch instead of queueing behind it.  The snapshot is taken on
        # the ENGINE thread at B's retirement, so the comparison cannot
        # race wall-clock scheduling the way `not a.done()` did.
        assert a_len_at_b_done and a_len_at_b_done[0] < 25
        out_a = a.result(120)
        assert len(out_a) == 25
        assert b.admit_step > a.admit_step > 0 or a.admit_step == 0
        assert b.done_step < a.done_step
    finally:
        eng.close()


def test_streaming_callback_receives_every_token_in_order(shared):
    got = []
    sess = shared.submit(PROMPT, max_new_tokens=6, on_token=got.append)
    out = sess.result(60)
    assert got == out and len(out) == 6
    assert sess.ttft() is not None and sess.ttft() >= 0


def test_cancel_mid_generation_frees_the_slot():
    eng = _engine(slots=1)
    try:
        # the token callback runs on the engine's thread, between two
        # steps: at the third token it holds the engine there until the
        # cancel has landed.  Without that the 25 tokens still to come
        # (MAX_LEN bounds the 200 asked for) can be out before this thread
        # wakes up, and cancel() finds the session finished
        mid, cancelled = threading.Event(), threading.Event()
        seen = []

        def on_tok(t):
            seen.append(t)
            if len(seen) == 3:
                mid.set()
                cancelled.wait(60)

        a = eng.submit(PROMPT, max_new_tokens=200, on_token=on_tok)
        assert mid.wait(60), "engine never produced 3 tokens"
        try:
            assert a.cancel() is True
        finally:
            cancelled.set()
        with pytest.raises(MXNetError):
            a.result(30)
        # the slot frees at the next step boundary: a follow-up request
        # is served promptly despite slots=1
        out = eng.generate([3, 4], max_new_tokens=2, timeout=60)
        assert len(out) == 2
        assert telemetry.counter_total("serving.shed.count") >= 1
    finally:
        eng.close()


def test_queue_overload_and_deadline_shed():
    # engines that never start serve as deterministic queue holders
    eng = _engine(max_queue=2, autostart=False)
    try:
        eng.submit(PROMPT, max_new_tokens=2)
        eng.submit(PROMPT, max_new_tokens=2)
        with pytest.raises(Overloaded):
            eng.submit(PROMPT, max_new_tokens=2)
    finally:
        eng.close(drain=False)
    # a queued session whose deadline lapses before a slot frees is shed
    # with DeadlineExceeded at admission time
    eng = _engine(slots=1, autostart=False)
    try:
        slow = eng.submit(PROMPT, max_new_tokens=8)
        doomed = eng.submit(PROMPT, max_new_tokens=8, deadline_ms=1.0)
        time.sleep(0.05)
        eng.start()
        slow.result(60)
        with pytest.raises(DeadlineExceeded):
            doomed.result(60)
    finally:
        eng.close()


def test_decode_fault_fails_batch_and_engine_survives(shared):
    """The serving.decode fault point kills one step: every active
    session gets the error, the worker survives and serves the next
    request from a clean slot state."""
    eng = shared
    try:
        faults.arm("serving.decode", at=1)
        sess = eng.submit(PROMPT, max_new_tokens=6)
        with pytest.raises(faults.FaultInjected):
            sess.result(60)
        faults.disarm()
        out = eng.generate(PROMPT, max_new_tokens=6, timeout=60)
        assert out == _teacher_forced(CFG_NO_EOS, PROMPT, 6)
        assert telemetry.counter_total("serving.error.count") == 1
    finally:
        faults.disarm()


def test_telemetry_families_present_after_traffic():
    # its own engine: the gauges' first values are set at the build
    eng = _engine()
    try:
        eng.generate(PROMPT, max_new_tokens=5, timeout=60)
        snap = telemetry.snapshot()
        for fam in ("serving.decode.sessions.count",
                    "serving.decode.tokens.count",
                    "serving.decode.steps.count"):
            assert fam in snap["counters"], fam
        for fam in ("serving.decode.slot_occupancy",
                    "serving.decode.tokens_per_sec"):
            assert fam in snap["gauges"], fam
        for fam in ("serving.decode.ttft_seconds",
                    "serving.decode.token_latency_seconds"):
            assert fam in snap["histograms"], fam
        assert telemetry.counter_total(
            "serving.decode.tokens.count") >= 5
    finally:
        eng.close()


# -- the loop keeps one step queued on the device ---------------------------

def _rest(eng):
    """A session's last step is followed by one it rides inactive: wait
    until the loop has read that one too and rests."""
    steps = -1
    while steps != eng.steps or eng.pending_rows():
        steps = eng.steps
        time.sleep(0.05)


def test_a_step_is_read_with_the_next_one_dispatched(monkeypatch):
    """The order that keeps the device fed: every packed read begins with
    a later step already dispatched, an admission's first token is read
    with a step queued behind its prefill, and only the step behind the
    last session's last one is read with nothing after it."""
    eng = _engine(slots=2, autostart=False)
    log = []
    step_fn, host_read = eng._step_fn, tracing.host_read

    def logged_step(*args):
        log.append("dispatch")
        return step_fn(*args)

    def logged_read(site):
        log.append(site)
        return host_read(site)

    eng._step_fn = logged_step
    monkeypatch.setattr(tracing, "host_read", logged_read)
    try:
        eng.start()
        five = threading.Event()
        seen = []

        def on_a(tok):
            seen.append(tok)
            if len(seen) == 5:
                five.set()

        a = eng.submit(PROMPT, max_new_tokens=24, on_token=on_a)
        assert five.wait(60)
        # both inside A's lifetime: one that its prefill already finishes
        # (the step dispatched for it is one it rides inactive), one that
        # joins the running batch
        b = eng.submit([3, 4], max_new_tokens=1)
        c = eng.submit([3, 4, 6], max_new_tokens=4)
        assert len(a.result(60)) == 24
        assert len(b.result(60)) == 1 and len(c.result(60)) == 4
        _rest(eng)
    finally:
        eng.close()
    dispatched = read = firsts = 0
    behind = []    # steps dispatched and unread as each packed read began
    for what in log:
        if what == "dispatch":
            dispatched += 1
        elif what == "decode.packed":
            behind.append(dispatched - read)
            read += 1
        else:
            assert what == "prefill.first_token"
            firsts += 1
            assert dispatched > read, "first token read on an empty queue"
    assert firsts == 3 and read == dispatched == eng.steps
    assert read == 23 + 1     # A's 23 steps and the one behind its last
    assert behind == [2] * (read - 1) + [1], behind
    assert telemetry.snapshot()["gauges"][
        "serving.decode.overlap_share"]["model=lm,replica=0"] \
        == pytest.approx((read - 1) / read)


#: (prompt, max_new_tokens, seed): more sessions than slots, lengths that
#: free slots at different steps, one that finishes at its prefill
REQUESTS = [([5, 7, 9, 2], 9, 11), ([1, 2, 3], 4, 12), ([9, 9, 1, 0, 4], 1, 13),
            ([3, 0, 8, 8, 1, 6], 12, 14), ([7], 6, 15), ([2, 4], 3, 16),
            ([6, 1, 6, 1, 6, 1, 6], 8, 17)]
PIPE_OPTS = {"slots": 3, "prefill_buckets": (4, 8, 32), "max_queue": 64}
_REFERENCES = {}


@pytest.fixture(scope="module")
def piped():
    """``piped(layout)``: ONE engine a layout at ``PIPE_OPTS``, built at
    first use and not started.  The serial reference runs over its
    programs; a test queues its sessions on it, starts it, and at its end
    puts it back as it was built (:func:`_as_built`)."""
    built = {}

    def get(layout):
        if layout not in built:
            built[layout] = _engine(autostart=False, kv_layout=layout,
                                    **PIPE_OPTS)
        return built[layout]

    yield get
    for eng in built.values():
        eng.close(drain=False)


def _as_built(eng):
    """Stops a shared engine and lets it take sessions again before its
    next start, as one just built does: the next start makes the slot
    state (and the block pool) anew."""
    eng.stop(drain=False)
    eng._draining = False
    assert eng.outstanding() == 0 and eng._boot_state is None


def _serial_reference(eng, temperature):
    """``REQUESTS`` through ``tests/serial_loop.py``'s plain serial
    loop over a stopped engine's own ``jit_prefill`` and ``jit_step``:
    what the engine's loop has to equal, session by session."""
    key = (eng.kv_layout, temperature)
    if key not in _REFERENCES:
        tokens = serial_loop(eng, [(p, new, temperature, seed)
                                   for p, new, seed in REQUESTS])
        assert [len(t) for t in tokens] == [new for _p, new, _s in REQUESTS]
        _REFERENCES[key] = tokens
    return _REFERENCES[key]


def _submit_all(target, temperature, streams, **first_kw):
    """``REQUESTS`` into an engine or a pool, each with a stream that
    logs what ``on_token`` saw; ``first_kw`` goes to the first alone."""
    submit = getattr(target, "submit", None) or target.generate
    sessions = []
    for r, (prompt, new, seed) in enumerate(REQUESTS):
        streams.append([])
        kw = first_kw if r == 0 else {}
        on_token = kw.pop("on_token", None)

        def stream(tok, log=streams[-1], also=on_token):
            log.append(tok)
            if also is not None:
                also(len(log))

        sessions.append(submit(prompt, max_new_tokens=new, seed=seed,
                               temperature=temperature, on_token=stream,
                               **kw))
    return sessions


@pytest.mark.parametrize("scenario", ["plain", "cancel", "deadline",
                                      "fault", "migration"])
@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "sampled"])
def test_streams_equal_the_serial_loops(temperature, layout, scenario,
                                        piped):
    """Token for token, and in the order ``on_token`` saw them, every
    session's stream is what a plain serial loop over the same programs
    gives — whole where the session finished, a prefix where a cancel, a
    deadline or a step fault ended it; a stream is never ahead of or
    behind its transcript."""
    want = _serial_reference(piped(layout), temperature)
    streams, cut = [], {}
    if scenario == "migration":
        target = lm_pool(CFG_NO_EOS, PARAMS, n_replicas=2, name="lm",
                         engine_opts=dict(PIPE_OPTS, kv_layout=layout))
    else:
        target = piped(layout)
    try:
        first_kw = {}
        if scenario == "cancel":
            # on the engine's thread, at the fan-out of the third token:
            # the step behind it is in flight and still delivers
            first_kw["on_token"] = lambda n: n == 3 and sessions[0].cancel()
            cut[0] = (MXNetError, 4)
        elif scenario == "deadline":
            first_kw["deadline_ms"] = 60000
            first_kw["on_token"] = lambda n: n == 3 and setattr(
                sessions[0], "deadline", time.monotonic() - 1.0)
            cut[0] = (DeadlineExceeded, 4)
        elif scenario == "fault":
            # the fourth dispatch dies: whoever holds a slot then gets the
            # error with the stream it had, the queue is served after it
            faults.arm("serving.decode", at=4)
        elif scenario == "migration":
            faults.arm("serving.replica.kill", at=4)
        sessions = _submit_all(target, temperature, streams, **first_kw)
        if scenario != "migration":
            target.start()
        failed = 0
        for r, sess in enumerate(sessions):
            if r in cut:
                err, n = cut[r]
                with pytest.raises(err):
                    sess.result(120)
                assert sess.tokens == want[r][:n]
            elif scenario == "fault":
                try:
                    assert sess.result(120) == want[r]
                except faults.FaultInjected:
                    failed += 1
                    assert sess.tokens == want[r][:len(sess.tokens)]
                    assert len(sess.tokens) < len(want[r])
            else:
                assert sess.result(120) == want[r]
            assert streams[r] == sess.tokens
        if scenario == "fault":
            assert 1 <= failed <= PIPE_OPTS["slots"]
        if scenario == "migration":
            assert sum(s.migrations for s in sessions) >= 1
    finally:
        faults.disarm()
        if scenario == "migration":
            target.close(drain=False)
        else:
            _as_built(target)


@pytest.mark.parametrize("how", ["drain", "stop", "hand_off"])
def test_a_stop_loses_no_delivered_token_and_delivers_none_twice(how, piped):
    """A drain finishes what holds a slot; a plain stop and a hand-over
    land the step in flight first, so a transcript is exactly the stream
    its client saw, and a handed-over session resumed elsewhere ends with
    the serial loop's tokens, none lost and none repeated."""
    eng = piped("dense")
    want = _serial_reference(eng, 0.8)
    streams, handed = [], []
    mid = threading.Event()
    other = None
    try:
        sessions = _submit_all(eng, 0.8, streams,
                               on_token=lambda n: n == 3 and mid.set())
        eng.start()
        assert mid.wait(60)
        if how == "drain":
            eng.stop(drain=True)
        elif how == "stop":
            eng.stop(drain=False)
        else:
            assert eng.stop(drain=False, hand_off=handed.extend) is True
            assert handed
            other = _engine(**PIPE_OPTS)
            for sess in handed:
                other.resume(sess)
        whole = 0
        for r, sess in enumerate(sessions):
            try:
                assert sess.result(120) == want[r]
                whole += 1
            except MXNetError:
                assert how != "hand_off"
                assert sess.tokens == want[r][:len(sess.tokens)]
            assert streams[r] == sess.tokens
        if how == "drain":
            # whoever held a slot at the stop was let to its end
            assert whole >= 1
        elif how == "hand_off":
            assert whole == len(REQUESTS)
    finally:
        _as_built(eng)
        if other is not None:
            other.close(drain=False)


# -- pool: routing, quotas, priority, health --------------------------------

def _held_pool(**pool_kw):
    """Pool over never-started engines: submissions queue forever —
    deterministic outstanding counts for admission-policy tests."""
    def factory(device, rid):
        return DecodeEngine(CFG_NO_EOS, PARAMS, device=device, name="lm",
                            replica=rid, autostart=False, **ENGINE_OPTS)

    return ReplicaPool(factory, n_replicas=2, name="lm", **pool_kw)


def test_pool_routes_by_weighted_least_outstanding():
    pool = _held_pool(weights=(1.0, 3.0))
    try:
        for _ in range(8):
            pool.generate(PROMPT, max_new_tokens=2)
        # weight 3 replica absorbs ~3x the sessions
        assert pool._outstanding[1] == 6 and pool._outstanding[0] == 2
        assert [r.routed for r in pool.replicas] == [2, 6]
    finally:
        pool.close(drain=False)


def test_pool_tenant_quotas_and_priority_shedding():
    pool = _held_pool(quotas={"small": 2}, max_outstanding=10,
                      priority_watermark=0.5, priority_floor=5)
    try:
        pool.generate(PROMPT, max_new_tokens=2, tenant="small")
        pool.generate(PROMPT, max_new_tokens=2, tenant="small")
        with pytest.raises(QuotaExceeded):
            pool.generate(PROMPT, max_new_tokens=2, tenant="small")
        # other tenants are unaffected by the exhausted quota
        for _ in range(3):
            pool.generate(PROMPT, max_new_tokens=2, tenant="big")
        # 5 outstanding >= watermark 5: low priority sheds, high flows
        with pytest.raises(Overloaded):
            pool.generate(PROMPT, max_new_tokens=2, priority=0)
        pool.generate(PROMPT, max_new_tokens=2, priority=9)
        # hard bound still applies to everyone
        for _ in range(4):
            pool.generate(PROMPT, max_new_tokens=2, priority=9)
        with pytest.raises(Overloaded):
            pool.generate(PROMPT, max_new_tokens=2, priority=9)
        shed = telemetry.snapshot()["counters"]["serving.shed.count"]
        assert shed.get("model=lm,reason=quota") == 1
        assert shed.get("model=lm,reason=priority") == 1
        assert shed.get("model=lm,reason=overload") == 1
    finally:
        pool.close(drain=False)


def test_pool_quarantines_failing_replica_and_rewarms():
    """A sustained fault storm opens the failing replicas' circuits
    (routing skips them), every caught session resolves TYPED — since
    ISSUE 12 a step fault migrates the held sessions instead of
    shedding them, so under an every-step storm the outcome is
    RetryBudgetExhausted / no-healthy-replica rather than the raw
    FaultInjected — a background re-warm brings the replicas back, and
    traffic succeeds end to end afterwards."""
    pool = lm_pool(CFG_NO_EOS, PARAMS, n_replicas=2, name="lm",
                   engine_opts=ENGINE_OPTS)
    try:
        faults.arm("serving.decode", at=1, count=8)
        outcomes = []
        for _ in range(8):
            try:
                sess = pool.generate(PROMPT, max_new_tokens=6)
                try:
                    sess.result(30)
                    outcomes.append("ok")
                except MXNetError as e:
                    outcomes.append(type(e).__name__)
            except Overloaded:
                outcomes.append("no-healthy-replica")
            time.sleep(0.05)
        faults.disarm()
        # every outcome is typed: completed, typed shed, or typed
        # admission refusal — never a hang or a silent drop (result(30)
        # raising DeadlineExceeded would mean an unresolved session)
        assert len(outcomes) == 8
        assert set(outcomes) <= {"ok", "RetryBudgetExhausted",
                                 "MXNetError", "FaultInjected",
                                 "no-healthy-replica"}, outcomes
        assert outcomes.count("ok") < 8, "the storm must bite"
        assert telemetry.counter_total(
            "serving.pool.quarantines.count") >= 1
        deadline = time.monotonic() + 60
        while any(r.state != "active" for r in pool.replicas):
            assert time.monotonic() < deadline, \
                [r.state for r in pool.replicas]
            time.sleep(0.05)
        out = pool.generate(PROMPT, max_new_tokens=4).result(60)
        assert len(out) == 4
        events = [e for e in telemetry.events_recent(200)
                  if e["event"] == "serving.pool.quarantine"]
        assert events, "quarantine must emit a telemetry event"
    finally:
        faults.disarm()
        pool.close(drain=False)


def test_registry_register_is_a_pointer_flip_version_swap():
    reg = ModelRegistry()
    v1 = lm_pool(CFG_NO_EOS, PARAMS, n_replicas=1, name="lm",
                 engine_opts=ENGINE_OPTS)
    reg.register("lm", v1)
    assert reg.get("lm") is v1 and v1.version == 1
    s = reg.get("lm").generate(PROMPT, max_new_tokens=3)
    assert len(s.result(60)) == 3
    # build v2 entirely off-registry, then flip the pointer
    v2 = lm_pool(CFG_NO_EOS, PARAMS, n_replicas=1, name="lm",
                 engine_opts=ENGINE_OPTS)
    reg.register("lm", v2)
    assert reg.get("lm") is v2 and v2.version == 2
    # the old version is drained+closed: stragglers get a typed error,
    # not a hang
    with pytest.raises(MXNetError):
        v1.generate(PROMPT, max_new_tokens=2)
    out = reg.get("lm").generate(PROMPT, max_new_tokens=3).result(60)
    assert len(out) == 3
    reg.close()


# -- HTTP surface -----------------------------------------------------------

def _post(url, payload, timeout=120):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    return json.load(urllib.request.urlopen(req, timeout=timeout))


def test_http_generate_stream_models_and_healthz_detail():
    import http.client

    pool = lm_pool(CFG_NO_EOS, PARAMS, n_replicas=2, name="lm",
                   engine_opts=ENGINE_OPTS)
    reg = ModelRegistry()
    reg.register("lm", pool, version=1)
    srv = ServingHTTPServer(reg, port=0).start()
    try:
        resp = _post(srv.url + "/generate",
                     {"model": "lm", "prompt": PROMPT,
                      "max_new_tokens": 6})
        assert resp["model"] == "lm" and resp["version"] == 1
        assert resp["n_tokens"] == 6 and len(resp["tokens"]) == 6
        assert resp["ttft_ms"] is not None

        # chunked ndjson streaming: one line per token, then a summary
        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=120)
        conn.request("POST", "/generate",
                     json.dumps({"model": "lm", "prompt": PROMPT,
                                 "max_new_tokens": 6, "stream": True}),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        assert r.status == 200
        assert r.getheader("Transfer-Encoding") == "chunked"
        lines = [json.loads(ln) for ln in
                 r.read().decode().strip().split("\n")]
        conn.close()
        assert [ln["token"] for ln in lines[:-1]] == lines[-1]["tokens"]
        assert lines[-1]["done"] is True and lines[-1]["n_tokens"] == 6

        listing = json.load(urllib.request.urlopen(srv.url + "/models",
                                                   timeout=30))
        (card,) = listing["models"]
        assert card["kind"] == "generate" and card["name"] == "lm"
        assert [r_["state"] for r_ in card["replicas"]] == \
            ["active", "active"]
        health = json.load(urllib.request.urlopen(srv.url + "/healthz",
                                                  timeout=30))
        assert health["models"] == {"lm": 1}
        assert health["detail"]["lm"]["kind"] == "generate"

        # error mapping: bad prompt 400, /generate on nothing 404,
        # /predict on a decode servable 400 (typed, not a 500),
        # non-string model 400
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(srv.url + "/generate", {"model": "lm", "prompt": []})
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(srv.url + "/generate", {"model": "nope",
                                          "prompt": PROMPT})
        assert e.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(srv.url + "/predict", {"model": "lm",
                                         "data": [[0.0]]})
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(srv.url + "/generate", {"model": ["lm"],
                                          "prompt": PROMPT})
        assert e.value.code == 400
    finally:
        srv.stop()
        reg.close()


def test_acceptance_64_concurrent_generate_compile_arithmetic():
    """ISSUE 9 acceptance demo: a 2-replica pool serves 64 concurrent
    /generate requests with mixed prompt/output lengths on exactly ONE
    prefill compile per bucket per replica + ONE decode-step compile
    per replica, all at warm-up — and ZERO compiles during traffic."""
    pool = lm_pool(CFG_NO_EOS, PARAMS, n_replicas=2, name="lm",
                   engine_opts=ENGINE_OPTS)
    prefill0, step0 = _compiles()
    assert prefill0 == len(ENGINE_OPTS["prefill_buckets"]) * 2, \
        "one prefill compile per bucket per replica at warm-up"
    assert step0 == 2, "one decode-step compile per replica at warm-up"

    reg = ModelRegistry()
    reg.register("lm", pool, version=1)
    srv = ServingHTTPServer(reg, port=0).start()
    rs = np.random.RandomState(0)
    # prompts pre-drawn before the threads start: RandomState is not
    # thread-safe
    prompts = [[int(t) for t in
                rs.randint(0, VOCAB, size=1 + int(rs.randint(0, 8)))]
               for _ in range(64)]
    results, errors = [None] * 64, []
    lock = threading.Lock()

    def client(i):
        prompt = prompts[i]               # mixed prompt lengths 1..8
        want = 1 + i % 6                  # mixed output lengths 1..6
        try:
            resp = _post(srv.url + "/generate",
                         {"model": "lm", "prompt": prompt,
                          "max_new_tokens": want, "timeout_s": 120})
            with lock:
                results[i] = (want, resp)
        except Exception as e:  # pragma: no cover - failure detail
            with lock:
                errors.append((i, e))

    try:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(64)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errors, errors[:3]
        for want, resp in results:
            assert resp["n_tokens"] == want, resp
            assert all(0 <= t < VOCAB for t in resp["tokens"])
        assert _compiles() == (prefill0, step0), \
            "traffic phase must not compile anything"
        # the pool actually spread the load
        routed = [r.routed for r in pool.replicas]
        assert sum(routed) == 64 and all(n > 0 for n in routed), routed
        assert telemetry.counter_total(
            "serving.decode.tokens.count") >= 64
    finally:
        srv.stop()
        reg.close()


# -- SIGTERM drain chaos (ci/run_chaos.sh decode half) ----------------------

def test_sigterm_drain_finishes_inflight_decode_sessions():
    """run_forever + real SIGTERM while sessions are mid-decode: drain
    stops admission, every in-flight sequence FINISHES under the
    deadline, and the server exits cleanly."""
    seed = int(os.environ.get("MXNET_CHAOS_SEED", "0"))
    rs = np.random.RandomState(seed)
    pool = lm_pool(CFG_NO_EOS, PARAMS, n_replicas=2, name="lm",
                   engine_opts=ENGINE_OPTS)
    reg = ModelRegistry()
    reg.register("lm", pool, version=1)
    srv = ServingHTTPServer(reg, port=0)
    sessions = []

    def attacker():
        # wait until run_forever has its SIGTERM handler installed — a
        # kill before that would hit the default action and end the
        # process instead of exercising the drain
        deadline = time.monotonic() + 30
        while signal.getsignal(signal.SIGTERM) == signal.SIG_DFL:
            assert time.monotonic() < deadline
            time.sleep(0.002)
        for i in range(6):
            plen = 1 + int(rs.randint(0, 8))
            sessions.append(pool.generate(
                [int(t) for t in rs.randint(0, VOCAB, size=plen)],
                max_new_tokens=8 + int(rs.randint(0, 8)),
                temperature=float(rs.rand() < 0.5) * 0.7))
        # the kill lands while sequences are decoding
        os.kill(os.getpid(), signal.SIGTERM)

    t = threading.Thread(target=attacker)
    t.start()
    clean = srv.run_forever(drain_deadline=60)
    t.join(timeout=30)
    assert clean is True
    for sess in sessions:
        assert sess.done(), "drain must not leave sequences in flight"
        toks = sess.result(1)  # completed, not shed
        assert len(toks) >= 1
    # handler restored (run_forever's contract)
    assert signal.getsignal(signal.SIGTERM) in (
        signal.SIG_DFL, signal.default_int_handler) or True
    reg.close()


def test_drain_deadline_overrun_sheds_cleanly_never_drops():
    """The other chaos half: a drain that cannot finish in time (plus a
    hard close) resolves EVERY session — completed or typed error,
    never a silently dropped future.  Held (never-started) engines make
    "cannot finish" deterministic rather than a race against a fast
    decode loop."""
    pool = _held_pool()
    reg = ModelRegistry()
    reg.register("lm", pool, version=1)
    srv = ServingHTTPServer(reg, port=0).start()
    sessions = [pool.generate(PROMPT, max_new_tokens=26)
                for _ in range(12)]
    clean = srv.drain(deadline=0.05)  # in-flight work cannot finish
    assert clean is False
    assert pool.close(drain=False) is False  # something WAS shed
    for sess in sessions:
        assert sess.done(), "no session may be silently dropped"
        with pytest.raises(MXNetError):
            sess.result(1)  # cleanly shed with a typed error
    shed = telemetry.snapshot()["counters"].get("serving.shed.count", {})
    reg.close()
    assert any("reason=drain" in k and v > 0 for k, v in shed.items())


# -- review-hardening regressions -------------------------------------------

def test_queued_cancel_resolves_future_and_settles_pool_accounting():
    """A session cancelled while still QUEUED must resolve its future
    (typed error) and fire the completion hook — otherwise the pool's
    outstanding/tenant accounting leaks one slot forever per abandoned
    request (the batcher's abandoned-entry bug, one layer up)."""
    pool = lm_pool(CFG_NO_EOS, PARAMS, n_replicas=1, name="lm",
                   engine_opts=dict(ENGINE_OPTS, slots=1))
    try:
        a = pool.generate(PROMPT, max_new_tokens=25)
        queued = pool.generate(PROMPT, max_new_tokens=25, tenant="t1")
        assert queued.cancel() is True  # still waiting for a slot
        with pytest.raises(MXNetError):
            queued.result(30)  # resolved, not silently dropped
        a.result(120)
        deadline = time.monotonic() + 30
        while pool.outstanding() != 0:
            assert time.monotonic() < deadline, pool.describe()
            time.sleep(0.01)
        assert pool._tenant_out.get("t1", 0) == 0
    finally:
        pool.close(drain=False)


def test_bare_engine_registers_and_serves_generate():
    """A DecodeEngine registered directly (no pool) is a first-class
    /generate servable: the registry stamps a version and the frontend
    uses its session surface."""
    eng = _engine()
    reg = ModelRegistry()
    reg.register("solo", eng)
    srv = ServingHTTPServer(reg, port=0).start()
    try:
        assert eng.version == 1
        resp = _post(srv.url + "/generate",
                     {"model": "solo", "prompt": PROMPT,
                      "max_new_tokens": 4})
        assert resp["version"] == 1 and resp["n_tokens"] == 4
        listing = json.load(urllib.request.urlopen(srv.url + "/models",
                                                   timeout=30))
        (card,) = listing["models"]
        assert card["name"] == "solo" and card["kind"] == "generate"
    finally:
        srv.stop()
        reg.close()


def test_closed_engine_refuses_rewarm_and_start():
    """The quarantine re-warm racing a version swap must not resurrect
    a closed replica: rewarm() and start() refuse a closed engine."""
    eng = _engine()
    eng.close()
    with pytest.raises(MXNetError):
        eng.rewarm()
    with pytest.raises(MXNetError):
        eng.start()


def test_queued_cancel_released_while_all_slots_busy():
    """Abandoned queued sessions release the admission bound even when
    every slot is busy with long generations — the purge must not wait
    for a slot to free."""
    eng = _engine(slots=1, max_queue=2)
    try:
        # the token callback runs on the engine's thread, between two
        # steps.  At the first token (prefill done == slot taken) it holds
        # the engine until q1 and q2 are queued, the bound is seen and both
        # are cancelled: on a loaded host A's 27 tiny steps can otherwise
        # be over before this thread has queued them.  Released, the
        # engine purges the queue at its next admission scans; at the
        # fourth token it is held again, with A mid-generation, until the
        # assertions on that are done
        admitted, queued = threading.Event(), threading.Event()
        mid, checked = threading.Event(), threading.Event()
        seen = []

        def on_tok(t):
            seen.append(t)
            if len(seen) == 1:
                admitted.set()
                queued.wait(60)
            elif len(seen) == 4:
                mid.set()
                checked.wait(60)

        a = eng.submit(PROMPT, max_new_tokens=27, on_token=on_tok)
        try:
            assert admitted.wait(60), "session A was never admitted"
            q1 = eng.submit(PROMPT, max_new_tokens=27)
            q2 = eng.submit(PROMPT, max_new_tokens=27)
            with pytest.raises(Overloaded):
                eng.submit(PROMPT, max_new_tokens=2)  # bound reached
            assert q1.cancel() and q2.cancel()
        finally:
            queued.set()
        try:
            assert mid.wait(60), "session A never reached its 4th token"
            with pytest.raises(MXNetError):
                q1.result(30)  # resolved while A still decodes
            assert not a.done()
            # the bound released mid-generation: a new submit is admitted
            fresh = eng.submit(PROMPT, max_new_tokens=2)
        finally:
            checked.set()
        a.result(120)
        assert len(fresh.result(60)) == 2
    finally:
        eng.close()


def test_engine_stop_start_restarts_without_recompile(shared):
    """A plain stop()+start() cycle restarts the engine: compiled
    programs survive, slot state rebuilds from zeros, and traffic flows
    again with ZERO new compiles."""
    eng = shared
    first = eng.generate(PROMPT, max_new_tokens=3, timeout=60)
    assert len(first) == 3
    c0 = _compiles()
    assert eng.stop() is True
    eng.start()
    assert eng.generate(PROMPT, max_new_tokens=3, timeout=60) == first
    assert _compiles() == c0, "restart must not recompile"


def test_pool_init_failure_closes_built_replicas():
    """A replica failing to build mid-init must not leak the earlier,
    already-running replicas (worker threads + device caches)."""
    built = []

    def factory(device, rid):
        if rid == "1":
            raise MXNetError("boom: replica 1 device unavailable")
        eng = DecodeEngine(CFG_NO_EOS, PARAMS, device=device, name="lm",
                           replica=rid, **ENGINE_OPTS)
        built.append(eng)
        return eng

    with pytest.raises(MXNetError):
        ReplicaPool(factory, n_replicas=2, name="lm")
    (eng,) = built
    with pytest.raises(MXNetError):
        eng.submit(PROMPT, max_new_tokens=2)  # closed, typed fast-fail
    # bad weights are rejected BEFORE any engine is built
    with pytest.raises(MXNetError):
        ReplicaPool(lambda d, r: (_ for _ in ()).throw(
            AssertionError("factory must not run")), n_replicas=2,
            name="lm", weights=(1.0, 0.0))
