"""The cell ``k-exaone-236b-a23b.reason-saturate`` end to end at CPU size,
past the harness's look for a chip: the family ``exaone_moe_engine`` (which
is also the benchmark's own reference against the program), faults planted
under the timed path, and the lower-precision control."""

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
CELL = "k-exaone-236b-a23b.reason-saturate"
BIG_SEED = 2 ** 31 + 4321


def _run(seed=BIG_SEED, seconds=1.5, with_control=False, **limits):
    import jax

    cell = {c["name"]: c for c in MANIFEST["workloads"]}[CELL]
    config = harness.load_json(os.path.join(TINY, "exaone_tiny.json"))
    config["limits"].update(limits)
    return harness.run_cell(
        MANIFEST, CELL, seed, seconds, 0, jax.devices()[:1],
        time.monotonic(), with_control=with_control,
        cell_files=(cell, config, harness.load_json(
            os.path.join(TINY, "reason_tiny.json"))))


def test_the_manifest_names_the_cell_and_its_files_resolve():
    cell, config, traffic = harness.resolve_cell(MANIFEST, CELL)
    assert cell["chips"] == 1 and traffic["kind"] == "closed_loop"
    assert harness.metrics_of(MANIFEST, "end_to_end", CELL) == \
        ["setup_s", "decode_tokens_per_s"]
    entry = {c["name"]: c for c in MANIFEST["configs"]}[cell["config"]]
    assert entry["reduced"] == config["reduced"]
    # every published width stands; what is cut says so
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts"], config["num_experts_per_tok"],
            config["sliding_window"]) == (6144, 64, 8, 128, 18432, 2048,
                                          128, 8, 128)
    assert len(config["layer_types"]) == 48
    for name in harness.metrics_of(MANIFEST, "per_layer", CELL):
        harness.find("layer_metrics", name)


@pytest.mark.parametrize("seed", [5, BIG_SEED])
def test_cell_runs_and_agrees_with_its_reference(seed):
    result, compared, _control = _run(seed)
    assert result["correct"], compared
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "decode_tokens_per_s"}
    by_name = {c["name"]: c for c in compared}
    assert by_name["served_token_mean_gap"]["requests"] == 32
    assert by_name["served_token_mean_gap"]["tokens"] > 200


class _Req:
    def __init__(self, prompt, times):
        self.prompt, self.token_times = [0] * prompt, times


def _traced_run(picks, steps):
    """What a traced run hands a reader, made by hand: 10 steps of 12.5 ms
    in one traced second, two prefills, two sessions."""
    import numpy as np

    _cell, config, traffic = harness.resolve_cell(MANIFEST, CELL)
    times = [100.0 + 0.1 * i for i in range(11)]
    modules = [("jit_step", 0.1 * i, 0.0125) for i in range(10)] \
        + [("jit_prefill", 0.05, 0.02), ("jit_prefill", 0.55, 0.03)]
    return {"config": config, "traffic": traffic, "slots": 256,
            "peaks": harness.peaks_of("TPU v5 lite"),
            "window": {"t0": 90.0, "t_end": 101.0,
                       "requests": [_Req(1000, times), _Req(50, times)]},
            "trace": {"window_s": 1.0, "busy_s": 0.5,
                      "devices": [{"busy_s": 0.5, "modules": modules}],
                      "counted": {"moe_picks": np.asarray(picks, np.int64),
                                  "moe_steps": steps,
                                  "moe_rows": 256 * steps,
                                  "moe_picks_total": 256 * 8 * 4 * steps}}}


def test_the_readers_on_a_run_made_by_hand():
    from benchmark.opcount import exaone_moe_engine as opcount

    def read(name, run):
        return harness.find("layer_metrics", name).read(run)

    config = harness.resolve_cell(MANIFEST, CELL)[1]
    p = opcount.parameters(config)
    # ISSUE 27's sizing table
    assert (p["attention"], p["expert"]) == (113246208, 37748736)
    assert p["attention"] + p["dense_mlp"] == 452984832
    assert p["embed"] + p["head"] == 235929600
    assert 3711959040 < opcount.held_parameters(config) < 3711959040 + 2e5
    assert opcount.cache_bytes(config, 256) == 256 * 4096 * (4096 + 4 * 128)
    # every held expert hit in every step, 16 picks each
    run = _traced_run([[160] * 16] * 4, 10)
    assert read("moe.tokens_per_expert", run) == 16.0
    assert read("moe.imbalance", run) == 1.0
    assert read("serve.prefill_share_pct", run) == pytest.approx(10.0)
    assert read("decode.step_device_ms.throughput", run) == \
        pytest.approx(12.5)
    full = read("moe.step_roofline", run)
    # weights 7.19 GB and the little K and V two sessions hold: 8.8 ms
    assert 69.0 < full < 72.0
    # half of the held experts never picked: their weights are not read
    half = _traced_run([[320] * 8 + [0] * 8] * 4, 10)
    assert read("moe.imbalance", half) == 2.0
    assert full - read("moe.step_roofline", half) == pytest.approx(
        100 * (32 * p["expert"] * 2 / 819e9) / 0.0125, rel=0.01)
    # a window layer's K and V are capped at the window a session
    rl = harness.find("layer_metrics", "moe.step_roofline")
    assert rl.live_tokens(run, 128) == pytest.approx(128 + 55, abs=1)
    assert rl.live_tokens(run, 1 << 30) == pytest.approx(1005.5 + 55.5,
                                                         abs=1)
    # a program without the counters (the parent): nothing to read
    bare = _traced_run([[0] * 16] * 4, 0)
    del bare["trace"]["counted"]["moe_steps"]
    for name in ("moe.step_roofline", "moe.tokens_per_expert",
                 "moe.imbalance"):
        assert read(name, bare) is None
    assert read("moe.step_roofline", dict(run, trace=None)) is None


def _window_one_short(monkeypatch, xm):
    init = xm.ExaoneMoE.__init__

    def altered(self, cfg, *args):
        init(self, cfg._replace(window=cfg.window - 1), *args)

    monkeypatch.setattr(xm.ExaoneMoE, "__init__", altered)


def _weights_not_normalised(monkeypatch, xm):
    import jax
    import jax.numpy as jnp

    def altered(cfg, h, moe):
        s = jax.nn.sigmoid(jnp.dot(h, moe["router"]))
        _, chosen = jax.lax.top_k(s + moe["bias"], cfg.top_k)
        return chosen.astype(jnp.int32), jnp.take_along_axis(
            s, chosen, axis=-1) * cfg.routed_scale

    monkeypatch.setattr(xm, "route", altered)


def _an_absent_expert_computed(monkeypatch, xm):
    import jax

    def altered(cfg, chosen, w):
        # an expert this chip does not hold is computed by a held one
        local = (chosen - cfg.first_expert) % cfg.experts_held
        return (jax.nn.one_hot(local, cfg.experts_held) * w[..., None]).sum(1)

    monkeypatch.setattr(xm, "_combine", altered)


@pytest.mark.parametrize("fault", [_window_one_short,
                                   _weights_not_normalised,
                                   _an_absent_expert_computed])
def test_a_fault_under_the_timed_path_is_not_correct(monkeypatch, fault):
    from mxnet_tpu.models import exaone_moe as xm

    fault(monkeypatch, xm)
    result, compared, _control = _run(seed=9)
    assert not result["correct"]
    assert "served_token_mean_gap" in \
        {c["name"] for c in compared if not c["ok"]}, compared


def test_too_few_finished_sessions_is_not_correct():
    result, compared, _control = _run(seconds=0.3, check_sessions=4000)
    assert not result["correct"]
    assert "the check reads 4000" in compared[0]["why"]


@pytest.mark.parametrize("seed", [4, BIG_SEED])
def test_the_lower_precision_control_fails_what_the_window_served(seed):
    _result, compared, control = _run(seed, with_control=True)
    assert all(c["ok"] for c in compared), compared
    failed = {(c["control"], c["name"]) for c in control if not c["ok"]}
    assert ("fp8", "served_token_mean_gap") in failed, control


def test_the_reference_reads_a_row_of_sequences_as_each_alone():
    """Sequences laid end to end in one row, each token attending within
    its own, give the logits each sequence gives alone."""
    import jax
    import numpy as np

    from benchmark.reference import exaone_moe_engine as ref

    assert ref.pack([5, 3000, 1000, 90, 4096], 4096) == \
        [[(4, 0)], [(1, 0), (2, 3000), (3, 4000), (0, 4090)]]
    with pytest.raises(ValueError):
        ref.pack([4097], 4096)
    config = harness.load_json(os.path.join(TINY, "exaone_tiny.json"))
    z = ref.sizes(config)
    params = ref.init_weights(config, 3, jax.devices()[0])
    rs = np.random.RandomState(0)
    seqs = [rs.randint(0, z["vocab"], n).astype(np.int32)
            for n in (37, 9, 18)]
    row = np.concatenate(seqs)
    seg = np.concatenate([np.full(len(q), i, np.int32)
                          for i, q in enumerate(seqs)])
    pos = np.concatenate([np.arange(len(q), dtype=np.int32) for q in seqs])
    together = np.asarray(ref.forward_logits(z, params, row, seg, pos))
    start = 0
    for q in seqs:
        alone = np.asarray(ref.forward_logits(z, params, q))
        np.testing.assert_allclose(together[start:start + len(q)], alone,
                                   atol=2e-5)
        start += len(q)
