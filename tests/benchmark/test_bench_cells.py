"""The harness end to end at CPU size, past its look for a chip: each kind
of cell through ``harness.run_cell`` with the tiny configurations beside
this file (which is also both references against the program), the timed
path broken underneath it, and the lower-precision controls."""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
MANIFEST = harness.load_manifest()
#: more than 32 signed bits hold, as the driver's seeds are
BIG_SEED = 2 ** 31 + 12345


def _cell_like(traffic_kind):
    """A cell of the manifest whose traffic is of this kind: the tiny run
    reports the end-to-end metrics that cell reports."""
    for cell in MANIFEST["workloads"]:
        _c, _config, traffic = harness.resolve_cell(MANIFEST, cell["name"])
        if traffic["kind"] == traffic_kind:
            return cell
    raise LookupError(traffic_kind)


def _run(config, traffic, seed=BIG_SEED, seconds=2.0, with_control=False):
    import jax

    config = harness.load_json(os.path.join(TINY, config))
    traffic = harness.load_json(os.path.join(TINY, traffic))
    cell = _cell_like(traffic["kind"])
    return harness.run_cell(
        MANIFEST, cell["name"], seed, seconds, 0, jax.devices()[:1],
        time.monotonic(), with_control=with_control,
        cell_files=(cell, config, traffic))


@pytest.mark.parametrize("config,traffic,reports", [
    ("resnet_tiny.json", "fit_tiny.json", ["train_samples_per_s"]),
    ("lm_tiny.json", "saturate_tiny.json", ["decode_tokens_per_s",
                                            "itl_p95_ms"]),
])
def test_cell_runs_and_agrees_with_its_reference(config, traffic, reports):
    result, compared, _control = _run(config, traffic)
    assert result["correct"], compared
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["metrics"]["setup_s"]["value"] > 0
    for name in reports:
        assert result["metrics"][name]["value"] > 0
    assert result["device"]["count"] >= 1
    by_name = {c["name"]: c for c in compared}
    if "data_shard_rows" in by_name:
        rows = by_name["data_shard_rows"]
        assert rows["value"] == rows["limit"]
        assert by_name["window_param_change_norm"]["value"] > 0
    else:
        # every request the window finished was read, not a sample: all
        # but the six clients' last ones, which the close cut short
        assert by_name["served_token_mean_gap"]["requests"] >= \
            result["attempted"] - 6 > 6


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    import mxnet_tpu as mx

    monkeypatch.setattr(mx.mod.Module, "update", lambda self: None)
    result, compared, _ = _run("resnet_tiny.json", "fit_tiny.json",
                               seconds=0.5)
    assert not result["correct"]
    failed = {c["name"] for c in compared if not c["ok"]}
    assert "param_change_norm_worst_leaf" in failed


def _other_optimizer(monkeypatch, **changed):
    """``Module.fit`` with optimizer parameters other than the
    configuration's, which the reference keeps to."""
    import mxnet_tpu as mx

    fit = mx.mod.Module.fit

    def altered(self, *args, **kw):
        kw["optimizer_params"] = dict(kw["optimizer_params"], **changed)
        return fit(self, *args, **kw)

    monkeypatch.setattr(mx.mod.Module, "fit", altered)


def _half_the_batch(monkeypatch):
    """The program trains on the first half of the rows, twice over; the
    reference, which asks for the batch second, gets all of them."""
    import jax.numpy as jnp

    ref = harness.find("families", "module_fit").ref
    make_batch, calls = ref.make_batch, []

    def altered(config, batch, seed, device):
        data, labels = make_batch(config, batch, seed, device)
        calls.append(1)
        if len(calls) == 1:
            half = batch // 2
            data = jnp.concatenate([data[:half], data[:half]])
            labels = jnp.concatenate([labels[:half], labels[:half]])
        return data, labels

    monkeypatch.setattr(ref, "make_batch", altered)


@pytest.mark.parametrize("fault,fails", [
    ("momentum", "param_change_norm_all_leaves"),
    ("learning_rate", "first_grad_norm_all_leaves"),
    ("half_the_batch", "first_grad_norm_all_leaves"),
])
def test_a_fault_in_every_leaf_is_not_correct(monkeypatch, fault, fails):
    """The faults the all-leaves limits are held against, planted under
    the timed path: another momentum (0.5 for 0.9), another learning rate
    (twice), a gradient from half of the batch.  (A weight decay left out
    is NOT seen: over three steps it moves no number by more than 0.1%.)"""
    if fault == "momentum":
        _other_optimizer(monkeypatch, momentum=0.5)
    elif fault == "learning_rate":
        _other_optimizer(monkeypatch, learning_rate=0.02)
    else:
        _half_the_batch(monkeypatch)
    result, compared, _ = _run("resnet_tiny.json", "fit_tiny.json",
                               seed=3, seconds=0.3)
    assert not result["correct"]
    assert fails in {c["name"] for c in compared if not c["ok"]}, compared


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from mxnet_tpu.serving.decode import DecodeEngine

    emit = DecodeEngine._emit

    def altered(self, sess, tok):
        if len(sess.tokens) == 3:       # one token of every session
            tok = (tok + 1) % 250
            sess.tokens[-1] = tok
        emit(self, sess, tok)

    monkeypatch.setattr(DecodeEngine, "_emit", altered)
    result, compared, _ = _run("lm_tiny.json", "saturate_tiny.json",
                               seconds=1.0)
    assert not result["correct"]
    assert [c["name"] for c in compared if not c["ok"]] == \
        ["served_token_gap", "served_token_mean_gap"]


def test_a_compile_inside_the_window_is_not_correct(monkeypatch):
    counts = iter([(0, {"hits": 0, "misses": 0}),
                   (1, {"hits": 0, "misses": 1})])
    monkeypatch.setattr(harness, "_xla_compiles", lambda: next(counts))
    result, compared, _ = _run("lm_tiny.json", "saturate_tiny.json",
                               seconds=0.5)
    assert not result["correct"]
    assert [c["name"] for c in compared if not c["ok"]] == \
        ["window_compiles"]


@pytest.mark.parametrize("seed", [1, 3, BIG_SEED])
def test_lower_precision_training_fails_the_comparison(seed):
    """The control of the training cells at a size a test run holds: the
    reference in bfloat16 in the program's place fails a limit.  (Seed 2
    is left out: at this size, 8 rows and 2x2 maps, it draws a channel of
    all but no variance, and the float32 reference's own first gradient
    is then 2% off its float64 value while the program's is not.)"""
    _result, compared, control = _run("resnet_tiny.json", "fit_tiny.json",
                                      seed=seed, seconds=0.3,
                                      with_control=True)
    assert all(c["ok"] for c in compared), compared
    assert any(not c["ok"] for c in control), control


@pytest.mark.parametrize("seed", [4, BIG_SEED])
def test_the_bfloat16_control_fails_what_the_window_served(seed):
    """The decode cells' control through the check itself: at every
    position of every request the window finished, the token the bfloat16
    forward puts first lies further below the reference's best, in the
    mean, than the limit allows; the served tokens do not."""
    _result, compared, control = _run("lm_tiny.json", "saturate_tiny.json",
                                      seed=seed, seconds=1.0,
                                      with_control=True)
    assert all(c["ok"] for c in compared), compared
    failed = {(c["control"], c["name"]) for c in control if not c["ok"]}
    assert ("bfloat16", "served_token_mean_gap") in failed, control


@pytest.mark.parametrize("seed", [1, 2, BIG_SEED])
def test_lower_precision_decoding_fails_the_comparison(seed):
    """The control of the decode cells at a size a test run holds: over a
    few hundred positions the bfloat16 forward puts first a token whose
    reference logit lies further below the best than the limit allows,
    while the reference's own first tokens lie at 0."""
    import jax
    import numpy as np

    ref = harness.find("reference", "decode_engine")
    config = {"n_embd": 128, "n_layer": 4, "n_head": 4, "n_inner": 512,
              "n_positions": 256, "vocab_size": 1000,
              "assumed": {"vocab_padded": 1024}}
    limit = harness.load_json(os.path.join(TINY, "lm_tiny.json"))[
        "limits"]["served_token_gap"]
    dev = jax.devices()[0]
    params = ref.init_weights(config, seed, dev)
    tokens = jax.numpy.asarray(np.random.default_rng(seed).integers(
        0, 1000, size=256, dtype=np.int32))
    logits = ref.reference_logits(4, params, tokens)
    own = jax.numpy.argmax(logits, axis=-1)
    assert float(ref.gaps_below_best(logits, own).max()) == 0.0
    for step in ("fp8", "bfloat16"):
        low = ref.lower_precision_argmax(4, step, params, tokens)
        assert float(ref.gaps_below_best(logits, low).max()) > limit
