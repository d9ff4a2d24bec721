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
    position fixed a pass), are the plain forward's over the transcript and
    the block as it stands; a block's final tokens ride the next block's
    first pass as the slot's pending block, and the K and V the cache then
    holds of them are what a commit pass of their own (the block whole,
    nothing pending) writes."""
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
    active = jnp.arange(slots) == slot

    def one_pass(cache, extra, at, state, pending=None):
        """A pass of ``slot`` alone over ``state`` at row ``at``; the other
        slots hold other tokens, lengths and a pending block nobody owns."""
        block = np.full((slots, B), 7, np.int32)
        block[slot] = state
        lengths = np.full((slots,), 5, np.int32)
        lengths[slot] = at
        riding = np.full((slots, B), 9, np.int32)
        has = np.ones((slots,), bool)
        has[slot] = pending is not None
        if pending is not None:
            riding[slot] = pending
        logits, ck, cv, extra = step(
            params, tuple(cache[0]), tuple(cache[1]), jnp.asarray(block),
            jnp.asarray(lengths), active,
            dict(extra, pending=jnp.asarray(has),
                 pending_block=jnp.asarray(riding)))
        return np.asarray(logits)[slot], [list(ck), list(cv)], extra

    def masked(at, fixed):
        state = final[at:at + B].copy()
        state[fixed:] = cfg.mask_id
        return state

    extra = plain_extra = model.extra_state()
    plain = cache       # the published order: a commit pass a block
    worst, passes, commits = 0.0, 0, 0
    for k in range(blocks + 1):
        at = start + k * B
        # the last turn is the pass that opens the block after the last
        for fixed in range(tail if k == 0 else 0, B if k < blocks else 1):
            riding = final[at - B:at] if k and not fixed else None
            state = masked(at, fixed) if k < blocks \
                else np.full((B,), cfg.mask_id, np.int32)
            logits, cache, extra = one_pass(cache, extra, at, state, riding)
            passes += 1
            commits += riding is not None
            if k == blocks:
                break
            want = np.asarray(_forward(cfg, params, jnp.asarray(
                np.concatenate([final[:at], state]))))[at:at + B]
            worst = max(worst, float(np.abs(logits - want).max()))
            got, plain, plain_extra = one_pass(plain, plain_extra, at, state)
            worst = max(worst, float(np.abs(got - want).max()))
        if k < blocks:
            _, plain, plain_extra = one_pass(plain, plain_extra, at,
                                             final[at:at + B])
    assert worst < 2e-3, worst
    for mine, theirs in zip(cache[0] + cache[1], plain[0] + plain[1]):
        np.testing.assert_allclose(
            np.asarray(mine)[slot, :, :start + blocks * B],
            np.asarray(theirs)[slot, :, :start + blocks * B], atol=1e-5)
        # a slot that is not live holds no pending block whatever the
        # flag says: the rows below its length are as they were
        assert not np.asarray(mine)[[0, 2], :, :5].any()
    got = model.counters(jax.tree_util.tree_map(np.asarray, extra))
    assert commits == blocks
    assert got["passes"] == passes
    assert got["commit_rows"] == commits * B
    assert got["rows"] == (passes + commits) * B
    # a pending row picks in every layer but the last, which it leaves
    # after its K and V
    assert got["moe_picks_total"] == cfg.top_k * (
        got["rows"] * cfg.layers - got["commit_rows"])
    assert np.sum(got["moe_picks"]) == got["moe_picks_total"]
    assert got["gauges"]["serving.decode.commit_rows_share"] \
        == pytest.approx(commits / passes)


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


# -- a block's commit rides the next block's first pass ------------------------
class _ByHand:
    """The engine's own programs, dispatched by hand as its loop dispatches
    them (a prefill into a slot, a step for all slots) with no thread
    between: a test reads the slot state after any step."""

    def __init__(self, cfg, params, slots=2):
        self.eng = _engine(cfg, params, slots=slots, autostart=False)
        self.cfg = cfg
        self.state = self.eng._fresh_state()
        self.tokens, self.fixed_at, self.deliveries = {}, {}, {}
        self.done = set()

    def close(self):
        self.eng.close(drain=False)

    def admit(self, slot, prompt, new, temperature=0.0, seed=0):
        n = len(prompt)
        bucket = next(b for b in self.eng.prefill_buckets if n <= b)
        padded = np.zeros((bucket,), np.int32)
        padded[:n] = prompt
        self.state, out = self.eng._prefill_fns[bucket](
            self.eng._params, self.state, padded, np.int32(n),
            np.int32(slot), np.int32(min(n + new, self.cfg.max_len)),
            np.float32(temperature), np.uint32(seed), np.bool_(True))
        assert np.asarray(out).tolist() == [-1, 0]
        self.tokens[slot], self.fixed_at[slot] = [], []
        self.deliveries[slot] = []
        self.done.discard(slot)

    def step(self, keep=None):
        """One pass; ``deliveries[slot]`` notes the steps that delivered."""
        keep = np.ones((self.eng.slots,), bool) if keep is None else keep
        self.state, packed = self.eng._dispatch_step(self.state, keep)
        packed = np.asarray(packed)
        base = self.cfg.denoise_steps + 1
        for slot in self.tokens:
            got = [j for j in range(B) if packed[j, slot] >= 0]
            for j in got:
                self.tokens[slot].append(int(packed[j, slot]))
                self.fixed_at[slot].append(
                    int(packed[B, slot]) // base ** j % base - 1)
            self.deliveries[slot].append(bool(got))
            if packed[B + 1, slot]:
                self.done.add(slot)
        return packed

    def run(self, slot, most=200):
        for _ in range(most):
            if slot in self.done:
                return self.tokens[slot]
            self.step()
        raise AssertionError("slot %d did not finish" % slot)

    def pending(self, slot):
        return bool(np.asarray(self.state[11])[slot])

    def rows(self, slot, upto):
        """K then V of every layer, rows ``0 .. upto - 1`` of ``slot``."""
        return [np.asarray(a)[slot, :, :upto]
                for side in self.state[:2] for a in side]

    def counters(self):
        return self.eng.model_counters()


def _published_order(cfg, params, prompt, new, **kw):
    """The parent's order written out plainly, one slot through the model's
    cache: a block is denoised by passes over its ``B`` rows alone, nothing
    pending, and then COMMITTED by a pass of its own over the block whole,
    which fixes nothing (five passes a block of four at four steps).  The
    sampling is ``generate_plain``'s; only where its logits come from is
    this function's.  ``(tokens, fixed_at, K and V rows, passes run, commits
    among them)``; the session's last block is left uncommitted, since
    nothing would read its rows."""
    model = sdar.SDAR(cfg, jnp.float32)
    prefill, step = jax.jit(model.prefill), jax.jit(model.decode_step)
    n = len(prompt)
    bucket = next(b for b in (8, 16, 32) if n <= b)
    padded = np.zeros((bucket,), np.int32)
    padded[:n] = prompt
    _first, ks, vs = prefill(params, jnp.asarray(padded), jnp.int32(n))
    held = {"cache": [[jax.lax.dynamic_update_slice(
        jnp.zeros((1,) + tlm.slot_shape(c), c.dtype), r[None], (0, 0, 0, 0))
        for c, r in zip(model.cache_spec(), rows)] for rows in (ks, vs)],
        "extra": dict(model.extra_state(),
                      pending=jnp.zeros((1,), bool),
                      pending_block=jnp.zeros((1, B), jnp.int32)),
        "at": n // B * B, "passes": 0, "commits": 0}

    def one_pass(block, at):
        logits, ck, cv, held["extra"] = step(
            params, tuple(held["cache"][0]), tuple(held["cache"][1]),
            jnp.asarray(block, jnp.int32)[None], jnp.full((1,), at, jnp.int32),
            jnp.ones((1,), bool), held["extra"])
        held["cache"] = [list(ck), list(cv)]
        held["passes"] += 1
        return np.asarray(logits)[0]

    def commit(tokens, at):
        one_pass(tokens[at:at + B], at)
        held["commits"] += 1

    def forward(tokens):
        tokens = np.asarray(tokens)
        at = len(tokens) - B
        if at > held["at"]:
            commit(tokens, held["at"])
            held["at"] = at
        out = np.zeros((len(tokens), cfg.vocab), np.float32)
        out[at:] = one_pass(tokens[at:], at)
        return out

    tokens, fixed_at, _ = sdar.generate_plain(cfg, params, prompt, new,
                                              forward=forward, **kw)
    return (tokens, fixed_at, [np.asarray(a)[0] for side in held["cache"]
                               for a in side], held["passes"],
            held["commits"])


@pytest.mark.parametrize("prompt", [6, 8], ids=["inside", "edge"])
@pytest.mark.parametrize("rule", ["static", "dynamic"])
@pytest.mark.parametrize("temperature", [0.0, 0.7], ids=["greedy", "hot"])
def test_the_merged_pass_is_the_published_order(temperature, rule, prompt):
    """Tokens, the passes that fixed them and the K and V rows of every
    committed block are those of the published order, which spends a pass
    of its own on every commit; the merged engine runs none of them."""
    cfg = _cfg(**(RULES["static-4"] if rule == "static" else dict(
        remasking="low_confidence_dynamic", threshold=0.5)))
    params = _params(cfg)
    new, seed = 22, 77
    want, want_at, want_rows, ran, commits = _published_order(
        cfg, params, _prompt(prompt), new, temperature=temperature,
        seed=seed)
    assert len(want) == new
    hand = _ByHand(cfg, params)
    try:
        # a neighbour in the other slot, admitted a pass later
        hand.admit(1, _prompt(prompt), new, temperature, seed)
        hand.step()
        hand.admit(0, _prompt(5), 9)
        assert hand.run(1) == want
        assert hand.fixed_at[1] == want_at
        # the session's last block is delivered and never committed
        end = prompt + new
        kept = (end - 1) // B * B
        blocks = kept // B - prompt // B
        for mine, theirs in zip(hand.rows(1, kept), want_rows):
            np.testing.assert_allclose(mine, theirs[:, :kept], atol=1e-5)
        per_block = np.diff([0] + [i + 1 for i, gave in enumerate(
            hand.deliveries[1]) if gave]).tolist()
        assert len(per_block) == blocks + 1
        if rule == "static":
            assert per_block[1:] == [cfg.denoise_steps] * blocks
        else:
            # the threshold fixes several positions a pass: blocks of one
            # to four passes, not of ``T`` alone
            assert min(per_block) < cfg.denoise_steps and \
                len(set(per_block)) > 1, per_block
        assert not hand.pending(1)
        hand.run(0)
        got = hand.counters()
        # the neighbour's three blocks: two commits
        assert got["commits"] == blocks + 2
        assert got["commit_rows"] == got["commits"] * B
        # the passes the published order ran, less its commits
        assert sum(hand.deliveries[1]) == commits + 1 == blocks + 1
        assert sum(per_block) == ran - commits
    finally:
        hand.close()


def _until_pending(hand, slot, most=50):
    """Steps until ``slot`` holds a pending block: delivered, its final K
    and V not written."""
    for _ in range(most):
        hand.step()
        if hand.pending(slot):
            return
    raise AssertionError("slot %d never held a pending block" % slot)


@pytest.mark.parametrize("case", ["ends-with-its-block", "cancel", "resume",
                                  "admission"])
def test_what_clears_a_pending_block(case):
    """A session that ends with its block runs no commit; a cancel drops
    the pending block with the slot; ``resume()`` re-prefills a transcript
    whose last block's K and V were never written; an admission into a
    slot whose flag still stands (no loop leaves one so: the belt) starts
    with nothing pending, and its prompt's rows are not written over."""
    cfg = _cfg(**RULES["static-4"])
    params = _params(cfg)
    hand = _ByHand(cfg, params)
    other = None
    try:
        if case == "ends-with-its-block":
            # one block and no more: T passes, nothing pending after them
            hand.admit(0, _prompt(8), 4)
            assert hand.run(0) == _plain(cfg, params, _prompt(8), 4)[0]
            assert len(hand.deliveries[0]) == cfg.denoise_steps
            got = hand.counters()
            assert got["commits"] == 0 and got["commit_rows"] == 0
            assert got["passes"] == cfg.denoise_steps
            assert got["tokens_committed"] == 4
            assert not hand.pending(0)
            # and two blocks: one commit, in the second block's first pass
            hand.admit(0, _prompt(8), 8)
            hand.run(0)
            got = hand.counters()
            assert got["commits"] == 1 and got["commit_rows"] == B
            assert got["passes"] == 3 * cfg.denoise_steps
            return
        want = _plain(cfg, params, _prompt(6), 30)[0]
        hand.admit(0, _prompt(6), 30)
        _until_pending(hand, 0)
        held = list(hand.tokens[0])
        assert held == want[:len(held)] and (6 + len(held)) % B == 0
        before = hand.counters()["commits"]
        if case == "cancel":
            keep = np.ones((2,), bool)
            keep[0] = False
            packed = hand.step(keep)
            assert packed[:B, 0].tolist() == [-1] * B and not packed[B + 2, 0]
            assert not hand.pending(0)
            # the pass wrote the block's rows before it learnt of the
            # cancel; the next one writes none
            assert hand.counters()["commits"] == before + 1
            hand.step()
            assert hand.counters()["commits"] == before + 1
        elif case == "resume":
            cut = GenerateSession(_prompt(6), 30, 0.0, None, None, seed=0)
            cut.tokens = held
            cut.fixed_at = list(hand.fixed_at[0])
            other = _engine(cfg, params)
            other.resume(cut)
            assert cut.result(120) == want
            return
        # the slot serves the next session as a fresh one would
        prompt = _prompt(12)
        hand.admit(0, prompt, 9)
        if case == "admission":
            assert not hand.pending(0)
        assert hand.run(0) == _plain(cfg, params, prompt, 9)[0]
        fresh = _ByHand(cfg, params)
        try:
            fresh.admit(0, prompt, 9)
            fresh.run(0)
            for mine, theirs in zip(hand.rows(0, 16), fresh.rows(0, 16)):
                np.testing.assert_array_equal(mine, theirs)
        finally:
            fresh.close()
    finally:
        hand.close()
        if other is not None:
            other.close(drain=False)


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
        assert len(state) == (13 if model == "block" else 8)
        if model == "block":
            assert state[2].shape == (3, cfg.block)
            assert (np.asarray(state[9]) == -1).all()
            assert not np.asarray(state[8]).any()
            # and no slot holds a pending block
            assert state[11].shape == (3,) and not np.asarray(state[11]).any()
            assert state[12].shape == (3, cfg.block)
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


@pytest.mark.parametrize("product", ["every", "ragged_dot", "kernel"])
def test_a_row_outside_the_mask_takes_no_pick(monkeypatch, product):
    """``sparse_mlp(live=(mask, expected))``: the rows inside the mask come
    out as they do with no mask; a row outside it takes no pick of any
    expert (zero out of every product, no place in the kernel's layout),
    whatever it holds, NaN included; the choices returned are every
    row's.  The product and its row tile are chosen for the rows expected
    live, not for the rows handed."""
    from mxnet_tpu.ops import grouped_product as gp

    cfg = _cfg()
    moe = _params(cfg)["layers"][0]["moe"]
    rs = np.random.RandomState(6)
    h = jnp.asarray(rs.normal(size=(24, 32)), jnp.float32)
    mask = jnp.asarray(rs.rand(24) < 0.6)
    asked = []
    monkeypatch.setattr(
        xm, "expert_product", lambda cfg, rows, live=None: asked.append(
            (rows, live)) or ("every" if product == "every" else "grouped"))
    if product == "kernel":
        monkeypatch.setattr(
            xm, "grouped_product_plan", lambda gate, expect: asked.append(
                expect) or ((8, 16), None))
        monkeypatch.setattr(
            xm, "grouped_product", lambda *a, snug=False: asked.append(
                "snug" if snug else "roomy") or gp._grouped_pallas(
                    *a, interpret=True))
    want, chosen = xm.sparse_mlp(cfg, h, moe, shared=False)
    dirty = jnp.where(mask[:, None], h, jnp.nan)
    got, again = xm.sparse_mlp(cfg, dirty, moe, shared=False,
                               live=(mask, 15))
    assert (np.asarray(again)[np.asarray(mask)]
            == np.asarray(chosen)[np.asarray(mask)]).all()
    np.testing.assert_allclose(np.asarray(got)[np.asarray(mask)],
                               np.asarray(want)[np.asarray(mask)], atol=2e-6)
    if product != "every":
        # (every expert over a NaN row is NaN times a weight of zero)
        assert not np.asarray(got)[~np.asarray(mask)].any()
    assert asked[0] == (24, None) and (24, 15) in asked
    if product == "kernel":
        # and the kernel of a call that names its live rows claims the
        # fast memory it needs, the others' what they claimed
        assert asked[1:3] == [24 * cfg.top_k / cfg.num_experts, "roomy"]
        assert asked[-2:] == [15 * cfg.top_k / cfg.num_experts, "snug"]


#: embed, expert_ffn, num_experts, top_k, experts_held of the four cells
#: that route
_SHARES = {"k-exaone": (6144, 2048, 128, 8, 16),
           "smallthinker": (2560, 768, 64, 6, 64),
           "deepseek-v2": (5120, 1536, 160, 6, 20),
           "sdar": (2048, 768, 128, 8, 128)}


def _share(name):
    """The model's own configuration with its cell's real widths and
    counts of experts (no array is made of it)."""
    e, f, n, k, held = _SHARES[name]
    if name == "k-exaone":
        cfg = xm.ExaoneConfig(
            vocab=8, embed=8, heads=2, kv_heads=1, head_dim=4, layers=1,
            layer_types=("full_attention",), mlp_types=("sparse",),
            dense_ffn=8, expert_ffn=8, num_experts=128, top_k=8,
            first_expert=0, experts_held=16, window=4, rope_theta=1e4,
            routed_scale=2.5, max_len=8, eos_id=8)
    elif name == "smallthinker":
        cfg = st.SmallThinkerConfig(
            vocab=8, embed=8, heads=2, kv_heads=1, head_dim=4, layers=1,
            rope_layout=(1,), window_layout=(0,), expert_ffn=8,
            num_experts=64, top_k=6, first_expert=0, experts_held=64,
            window=4, rope_theta=1e4, eps=1e-6, max_len=8, eos_id=8)
    elif name == "deepseek-v2":
        cfg = dm.DeepSeekV2Config._make(
            8 for _ in dm.DeepSeekV2Config._fields)
    else:
        cfg = _cfg()
    return cfg._replace(embed=e, expert_ffn=f, num_experts=n, top_k=k,
                        first_expert=0, experts_held=held)


@pytest.mark.parametrize("name,rows,want", [
    # the steps: the weights' bytes bind, the layout would be all cost
    ("k-exaone", 256, "every"), ("smallthinker", 48, "every"),
    ("deepseek-v2", 128, "every"),
    # SDAR's pass of 384 rows: the operations of 128 experts a row bind
    ("sdar", 384, "grouped"),
    # the prefill buckets
    ("k-exaone", 128, "every"), ("k-exaone", 512, "grouped"),
    ("k-exaone", 1024, "grouped"),
    ("smallthinker", 3072, "grouped"), ("smallthinker", 8192, "grouped"),
    ("deepseek-v2", 2560, "grouped"), ("deepseek-v2", 4096, "grouped"),
    ("sdar", 128, "every"), ("sdar", 512, "grouped"),
    ("sdar", 1024, "grouped"),
])
def test_expert_product_decides_for_each_share_as_it_did(name, rows, want):
    assert xm.expert_product(_share(name), rows) == want


def test_the_merged_pass_s_experts_are_sized_by_the_rows_expected_live(
        monkeypatch):
    """768 rows a pass of which 480 are expected live (96 slots x 4, and a
    quarter more for the pending blocks): the grouped product, at the row
    tile 30 rows an expert ask (32), where the 768 handed would ask 64;
    and its kernel claims 32 MiB of VMEM for the 20 MB it holds, where a
    call that names no live rows claims the 64 MiB it claimed."""
    from mxnet_tpu.ops import grouped_product as gp

    cfg = _share("sdar")
    assert xm.expert_product(cfg, 768, 480) == "grouped"
    e, f = cfg.embed, cfg.expert_ffn
    assert gp.group_tiles(e, f, 2, 480 * 8 / 128)[0] == 32
    assert gp.group_tiles(e, f, 2, 768 * 8 / 128)[0] == 64
    claimed = []
    monkeypatch.setattr(gp, "_grouped_pallas",
                        lambda *a, vmem_limit: claimed.append(vmem_limit))
    gate = jax.ShapeDtypeStruct((128, e, f), jnp.bfloat16)
    for snug in (True, False):
        gp.grouped_product(None, None, None, gate, None, None, None, 32, f,
                           snug=snug)
    assert claimed == [32 << 20, 64 << 20]
