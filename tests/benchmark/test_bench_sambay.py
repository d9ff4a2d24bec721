"""The cell ``phi-4-mini-flash-reasoning.reason-saturate`` end to end at CPU
size, past the harness's look for a chip: the family ``sambay_engine``
(which is also the benchmark's own reference against the program), faults
planted under the timed path, and the two lower-precision controls."""

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
CELL = "phi-4-mini-flash-reasoning.reason-saturate"
BIG_SEED = 2 ** 31 + 4321


def _run(seed=BIG_SEED, seconds=1.5, with_control=False, **limits):
    import jax

    cell = {c["name"]: c for c in MANIFEST["workloads"]}[CELL]
    config = harness.load_json(os.path.join(TINY, "sambay_tiny.json"))
    config["limits"].update(limits)
    return harness.run_cell(
        MANIFEST, CELL, seed, seconds, 0, jax.devices()[:1],
        time.monotonic(), with_control=with_control,
        cell_files=(cell, config, harness.load_json(
            os.path.join(TINY, "reason_tiny.json"))))


def test_the_manifest_names_the_cell_and_its_files_resolve():
    cell, config, traffic = harness.resolve_cell(MANIFEST, CELL)
    assert cell["chips"] == 1 and traffic["kind"] == "closed_loop"
    assert cell["traffic"] == "reason-saturate-closed"
    assert harness.metrics_of(MANIFEST, "end_to_end", CELL) == \
        ["setup_s", "decode_tokens_per_s"]
    entry = {c["name"]: c for c in MANIFEST["configs"]}[cell["config"]]
    assert entry["reduced"] == config["reduced"] == []
    # every published width and count stands: nothing is cut
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["intermediate_size"],
            config["num_hidden_layers"], config["sliding_window"],
            config["vocab_size"], config["mb_per_layer"]) \
        == (2560, 40, 20, 10240, 32, 512, 200064, 2)
    assumed = config["assumed"]
    assert (assumed["d_state"], assumed["d_conv"], assumed["expand"],
            assumed["dt_rank"]) == (16, 4, 2, 160)
    names = harness.metrics_of(MANIFEST, "per_layer", CELL)
    for name in names:
        harness.find("layer_metrics", name)
    assert {"sambay.step_roofline", "sambay.prefill_roofline",
            "sambay.rows_per_slot", "sambay.state_bytes_share_pct",
            "xdec.attention_roofline", "ssm_scan_roofline",
            "serve.prefill_share_pct",
            "device.idle_pct.serve.throughput",
            "decode.step_device_ms.throughput",
            "decode.host_ms_per_step.throughput",
            "decode.admit_host_ms.throughput"} <= set(names)


def test_the_program_and_the_reference_read_one_configuration():
    """The family hands the program the reference's sizes, and the
    reference's seeded weights have the program's own shapes."""
    import jax
    import jax.numpy as jnp

    from benchmark.families import sambay_engine as family
    from benchmark.reference import sambay_engine as ref
    from mxnet_tpu.models import sambay as sb

    config = harness.resolve_cell(MANIFEST, CELL)[1]
    model = family.model_of(config)
    assert model.cfg == sb.SambaYConfig(
        vocab=200064, embed=2560, heads=40, kv_heads=20, head_dim=64,
        layers=32, ffn=10240, mb_per_layer=2, window=512, d_inner=5120,
        d_state=16, d_conv=4, dt_rank=160, max_len=4096, eos_id=200064)
    assert list(model.kinds) == ref.layer_kinds(ref.sizes(config))
    tiny = harness.load_json(os.path.join(TINY, "sambay_tiny.json"))
    mine = jax.eval_shape(lambda: sb.init_params(
        family.model_of(tiny).cfg, 0, jnp.float32))
    theirs = jax.eval_shape(
        lambda: ref.init_weights(tiny, 0, jax.devices()[0]))
    assert jax.tree_util.tree_structure(mine) \
        == jax.tree_util.tree_structure(theirs)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(theirs)))


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


def _traced_run(steps=10, kernel=True):
    """What a traced run hands a reader, made by hand: 10 steps of 25 ms in
    one traced second, two prefills, 128 live slots of 870 rows each."""
    _cell, config, traffic = harness.resolve_cell(MANIFEST, CELL)
    times = [100.0 + 0.1 * i for i in range(11)]
    modules = [("jit_step", 0.1 * i, 0.025) for i in range(10)] \
        + [("jit_prefill", 0.05, 0.02), ("jit_prefill", 0.55, 0.03)]
    ops = {"fusion f32[128,2560]": 0.2}
    if kernel:
        ops["decode_attention f32[128,10,4,128]"] = 0.08
        ops["ssm_scan f32[128,5120]"] = 0.0006
        ops["ssm_scan f32[1024,5120]"] = 0.0034
    return {"config": config, "traffic": traffic, "slots": 128,
            "peaks": harness.peaks_of("TPU v5 lite"),
            "window": {"t0": 90.0, "t_end": 101.0,
                       "requests": [_Req(100, times), _Req(600, times)]},
            "trace": {"window_s": 1.0, "busy_s": 0.3,
                      "devices": [{"busy_s": 0.3, "modules": modules,
                                   "op_seconds": ops}],
                      "counted": {"ssm_steps": steps, "rows": 128 * steps,
                                  "rows_full": 128 * 870 * steps,
                                  "rows_ring": 128 * 450 * steps}}}


def test_the_readers_on_a_run_made_by_hand():
    from benchmark.opcount import sambay_engine as opcount

    def read(name, run):
        return harness.find("layer_metrics", name).read(run)

    config = harness.resolve_cell(MANIFEST, CELL)[1]
    p = opcount.parameters(config)
    # ISSUE 31's count, by part
    assert (p["embed"], p["mlp"], p["gmu"], p["cross"]) \
        == (512163840, 78643200, 26214400, 13107200)
    assert p["mamba"] + p["vectors_mamba"] == 41241600
    assert p["attention"] == 19660800
    assert 3.852e9 < opcount.held_parameters(config) < 3.853e9
    assert 7.70e9 < opcount.weight_bytes(config) < 7.71e9
    held = opcount.cache_bytes(config, 128)
    assert held["full"] == held["ring"] == 128 * 4096 * 5120
    assert held["state"] == 9 * 128 * 5120 * (16 * 4 + 3 * 2)
    assert opcount.readers(config) == 8
    run = _traced_run()
    assert read("sambay.rows_per_slot", run) == 870.0
    by = opcount.step_state_bytes(config, 128, 128 * 870, 128 * 450)
    assert by["shared"] == 8 * 128 * 870 * 5120
    assert by["ring"] == 8 * 128 * 450 * 5120
    assert by["state"] == 2 * held["state"]
    total = opcount.step_bytes(config, 128, 128 * 870, 128 * 450)
    assert read("sambay.state_bytes_share_pct", run) == pytest.approx(
        100.0 * sum(by.values()) / total)
    # ISSUE 31: half of the step's bytes are the new mechanisms'
    assert 49.0 < read("sambay.state_bytes_share_pct", run) < 51.0
    # 15.45 GB over 819 GB/s is 18.9 ms of a 25 ms step
    assert read("sambay.step_roofline", run) == pytest.approx(
        100 * (total / 819e9) / 0.025)
    assert 74.0 < read("sambay.step_roofline", run) < 77.0
    # the kernel took 8 ms a step for 4.56 GB
    assert read("xdec.attention_roofline", run) == pytest.approx(
        100 * (by["shared"] / 819e9) / 0.008)
    assert read("xdec.attention_roofline", _traced_run(kernel=False)) is None
    # nine scans a prefill, over 128 and over 1024 positions: 8.9 and 63.5
    # MB at 819 GB/s are 0.68 ms of the kernel's 4 ms
    moved = [opcount.scan_bytes(config, b) for b in (128, 1024)]
    assert moved[0] == 4 * (3 * 128 * 5120 + 2 * 128 * 16 + 3 * 16 * 5120)
    assert opcount.scan_flops(config, 128) / 197e12 < moved[0] / 819e9
    assert read("ssm_scan_roofline", run) == pytest.approx(
        100 * (9 * sum(moved) / 819e9) / 0.004)
    assert read("ssm_scan_roofline", _traced_run(kernel=False)) is None
    # prompts of 100 and 600 tokens: buckets 128 (bytes bind: 9.44 ms) and
    # 1024 (operations bind: 20.7 ms); the median of both over 25 ms
    least = [max(opcount.prefill_flops(config, b) / 197e12,
                 opcount.prefill_bytes(config, b) / 819e9)
             for b in (128, 1024)]
    assert 9.4e-3 < least[0] < 9.5e-3 and 20.6e-3 < least[1] < 20.8e-3
    assert read("sambay.prefill_roofline", run) == pytest.approx(
        100 * (sum(least) / 2) / 0.025)
    assert read("serve.prefill_share_pct", run) == pytest.approx(
        100 * 0.05 / 0.3)
    assert read("decode.step_device_ms.throughput", run) == \
        pytest.approx(25.0)
    # a program without the counters (the parent): nothing to read
    bare = _traced_run()
    bare["trace"]["counted"] = {"decode_steps": 10}
    for name in ("sambay.step_roofline", "sambay.rows_per_slot",
                 "sambay.state_bytes_share_pct", "xdec.attention_roofline"):
        assert read(name, bare) is None
        assert read(name, dict(run, trace=None)) is None
    assert read("ssm_scan_roofline", dict(run, trace=None)) is None
    assert read("sambay.prefill_roofline", dict(run, trace=None)) is None


# -- faults planted under the timed path ---------------------------------------
def _a_state_not_reset(monkeypatch, sb):
    """The prefill's scan starts from what a slot's last session left."""
    import jax.numpy as jnp

    scan = sb.ssm_scan
    monkeypatch.setattr(sb, "ssm_scan", lambda dt, u, b, c, a, state: scan(
        dt, u, b, c, a, state + jnp.float32(0.3)))


def _memory_of_the_wrong_layer(monkeypatch, sb):
    mamba = sb._mamba
    kept = {}

    def altered(cfg, l, p, x, access):
        x, y = mamba(cfg, l, p, x, access)
        if l == 2:
            kept["y"] = y
        return x, (kept["y"] if l == cfg.layers // 2 else y)

    monkeypatch.setattr(sb, "_mamba", altered)


def _lam0_of_the_wrong_depth(monkeypatch, sb):
    lam0 = sb.lam0
    monkeypatch.setattr(sb, "lam0", lambda l: lam0(l + 2))


def _window_one_short(monkeypatch, sb):
    init = sb.SambaY.__init__

    def altered(self, cfg, *args):
        init(self, cfg._replace(window=cfg.window - 1), *args)

    monkeypatch.setattr(sb.SambaY, "__init__", altered)


def _a_cross_layer_reads_stale_rows(monkeypatch, sb):
    """The last cross layer does not see the row the full layer has just
    written."""
    attend = sb._Step.attend

    def altered(self, l, kind, q, k, v):
        if l != self.cfg.layers - 1:
            return attend(self, l, kind, q, k, v)
        i = self.model.entry[self.model.shared]
        return sb.decode_attention(q, self.firsts[i], self.seconds[i],
                                   sb.jnp.maximum(self.pos - 1, 0),
                                   self.scale)

    monkeypatch.setattr(sb._Step, "attend", altered)


@pytest.mark.parametrize("fault", [
    _a_state_not_reset, _memory_of_the_wrong_layer,
    _lam0_of_the_wrong_depth, _window_one_short,
    _a_cross_layer_reads_stale_rows])
def test_a_fault_under_the_timed_path_is_not_correct(monkeypatch, fault):
    from mxnet_tpu.models import sambay as sb

    fault(monkeypatch, sb)
    result, compared, _control = _run(seed=9)
    assert not result["correct"]
    assert "served_token_mean_gap" in \
        {c["name"] for c in compared if not c["ok"]}, compared


def test_too_few_finished_sessions_is_not_correct():
    result, compared, _control = _run(seconds=0.3, check_sessions=4000)
    assert not result["correct"]
    assert "the check reads 4000" in compared[0]["why"]


@pytest.mark.parametrize("seed", [4, BIG_SEED])
def test_both_controls_fail_what_the_window_served_passes(seed):
    """The weights through fp8, and the recurrent state kept in bfloat16:
    each fails the mean gap that the served tokens pass."""
    _result, compared, control = _run(seed, with_control=True)
    assert all(c["ok"] for c in compared), compared
    failed = {(c["control"], c["name"]) for c in control if not c["ok"]}
    assert ("fp8", "served_token_mean_gap") in failed, control
    assert ("state-bfloat16", "served_token_mean_gap") in failed, control


def test_the_reference_reads_a_row_of_sequences_as_each_alone():
    """Sequences laid end to end in one row, each token attending within
    its own and the recurrent state and the convolution starting afresh at
    each, give the logits each sequence gives alone; read in blocks, the
    gaps are those of the whole logits."""
    import jax
    import numpy as np

    from benchmark.reference import sambay_engine as ref

    config = harness.load_json(os.path.join(TINY, "sambay_tiny.json"))
    z = ref.sizes(config)
    params = ref.init_weights(config, 3, jax.devices()[0])
    rs = np.random.RandomState(0)
    seqs = [rs.randint(0, z["vocab"], n).astype(np.int32)
            for n in (37, 2, 9, 18)]
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
    monkey_block = ref.LOGIT_BLOCK
    ref.LOGIT_BLOCK = 16
    try:
        x = ref.forward_hidden(z, params, row, seg, pos)
        chosen = np.stack([np.roll(row, -1), together.argmax(-1)])
        gaps = np.asarray(ref.gaps_below_best(params, x, chosen))
        best = np.asarray(ref.best_tokens(params, x, ref.REFERENCE))
    finally:
        ref.LOGIT_BLOCK = monkey_block
    np.testing.assert_array_equal(best, together.argmax(-1))
    np.testing.assert_allclose(gaps[1], 0.0, atol=1e-6)
    np.testing.assert_allclose(
        gaps[0], together.max(-1) - together[np.arange(len(row)),
                                             np.roll(row, -1)], atol=1e-5)


def test_the_reference_is_the_programs_plain_reference():
    """Two plain references written apart, the benchmark's and
    ``models/sambay.py``'s, agree on the benchmark's seeded weights."""
    import jax
    import numpy as np

    from benchmark.families import sambay_engine as family
    from benchmark.reference import sambay_engine as ref
    from mxnet_tpu.models import sambay as sb

    config = harness.load_json(os.path.join(TINY, "sambay_tiny.json"))
    params = ref.init_weights(config, 11, jax.devices()[0])
    tokens = np.random.RandomState(1).randint(0, 96, 40).astype(np.int32)
    np.testing.assert_allclose(
        ref.forward_logits(ref.sizes(config), params, tokens),
        sb.forward_logits(family.model_of(config).cfg, params, tokens),
        atol=2e-5)
