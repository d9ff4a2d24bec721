"""The cell ``granite-4.0-h-micro.reason-saturate`` end to end at CPU size,
past the harness's look for a chip: the family ``granite_hybrid_engine``
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
CELL = "granite-4.0-h-micro.reason-saturate"
BIG_SEED = 2 ** 31 + 4646
NEW_METRICS = ["granite.step_roofline", "granite.state_bytes_share_pct",
               "granite.rows_per_slot", "granite.prefill_roofline",
               "ssd_scan_roofline", "granite.attention_roofline"]


def _run(seed=BIG_SEED, seconds=1.5, with_control=False, **limits):
    import jax

    cell = {c["name"]: c for c in MANIFEST["workloads"]}[CELL]
    config = harness.load_json(os.path.join(TINY,
                                            "granite_hybrid_tiny.json"))
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
    assert entry["source"] == config["source"]
    # every published width and count stands: nothing is cut
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["shared_intermediate_size"],
            config["num_hidden_layers"], config["vocab_size"],
            config["mamba_n_heads"], config["mamba_d_head"],
            config["mamba_d_state"], config["mamba_d_conv"],
            config["mamba_chunk_size"], config["mamba_expand"]) \
        == (2048, 32, 8, 8192, 40, 100352, 64, 64, 128, 4, 256, 2)
    assert (config["embedding_multiplier"], config["attention_multiplier"],
            config["residual_multiplier"], config["logits_scaling"]) \
        == (12, 0.015625, 0.22, 8)
    kinds = config["layer_types"]
    assert len(kinds) == 40 and [l for l, k in enumerate(kinds)
                                 if k == "attention"] == [5, 15, 25, 35]
    assert config["engine"]["slots"] == 64
    names = harness.metrics_of(MANIFEST, "per_layer", CELL)
    for name in names:
        harness.find("layer_metrics", name)
    assert set(NEW_METRICS) | {
        "serve.prefill_share_pct", "device.idle_pct.serve.throughput",
        "decode.step_device_ms.throughput",
        "decode.host_ms_per_step.throughput",
        "decode.admit_host_ms.throughput", "setup.before_program_s",
        "setup.trace_s", "setup.lower_s", "setup.relower_s",
        "setup.lowerings_per_program", "setup.unattributed_s",
        "exec.window_compiles", "exec.setup_load_s"} == set(names)
    # the new readers list the new cell alone
    for m in MANIFEST["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "decode_tokens_per_s"


def test_the_program_and_the_reference_read_one_configuration():
    """The family hands the program the reference's sizes, and the
    reference's seeded weights have the program's own shapes."""
    import jax
    import jax.numpy as jnp

    from benchmark.families import granite_hybrid_engine as family
    from benchmark.reference import granite_hybrid_engine as ref
    from mxnet_tpu.models import granite_hybrid as gh

    config = harness.resolve_cell(MANIFEST, CELL)[1]
    model = family.model_of(config)
    assert model.cfg == gh.GraniteHybridConfig(
        vocab=100352, embed=2048, heads=32, kv_heads=8, head_dim=64,
        layer_types=tuple(config["layer_types"]), ffn=8192, m_heads=64,
        m_head_dim=64, d_state=128, d_conv=4, chunk=256,
        embedding_multiplier=12.0, attention_multiplier=0.015625,
        residual_multiplier=0.22, logits_scaling=8.0, max_len=4096,
        eos_id=100352)
    kinds = [c.kind for c in model.cache_spec()]
    assert kinds.count("state") == 36 and kinds.count("full") == 4
    tiny = harness.load_json(os.path.join(TINY, "granite_hybrid_tiny.json"))
    mine = jax.eval_shape(lambda: gh.init_params(
        family.model_of(tiny).cfg, 0, jnp.float32))
    theirs = jax.eval_shape(
        lambda: ref.init_weights(tiny, 0, jax.devices()[0]))
    assert jax.tree_util.tree_structure(mine) \
        == jax.tree_util.tree_structure(theirs)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(theirs)))
    for broken in ({"mamba_n_groups": 2}, {"num_local_experts": 4},
                   {"mamba_expand": 4}, {"num_hidden_layers": 4}):
        with pytest.raises(ValueError):
            ref.sizes(dict(tiny, **broken))


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
    """What a traced run hands a reader, made by hand: 10 steps of 27 ms in
    one traced second, two prefills, 64 live slots of 1000 rows each."""
    _cell, config, traffic = harness.resolve_cell(MANIFEST, CELL)
    times = [100.0 + 0.1 * i for i in range(11)]
    modules = [("jit_step", 0.1 * i, 0.027) for i in range(10)] \
        + [("jit_prefill", 0.05, 0.02), ("jit_prefill", 0.55, 0.06)]
    ops = {"fusion f32[64,2048]": 0.2}
    if kernel:
        ops["decode_attention f32[64,4,8,128]"] = 0.01
        ops["ssd_scan f32[128,4096]"] = 0.0009
        ops["ssd_scan f32[1024,4096]"] = 0.0071
    return {"config": config, "traffic": traffic, "slots": 64,
            "peaks": harness.peaks_of("TPU v5 lite"),
            "window": {"t0": 90.0, "t_end": 101.0,
                       "requests": [_Req(100, times), _Req(600, times)]},
            "trace": {"window_s": 1.0, "busy_s": 0.35,
                      "devices": [{"busy_s": 0.35, "modules": modules,
                                   "op_seconds": ops}],
                      "counted": {"ssd_steps": steps, "rows": 64 * steps,
                                  "rows_full": 64 * 1000 * steps,
                                  "decode_steps": steps}}}


def test_the_readers_on_a_run_made_by_hand():
    from benchmark.opcount import granite_hybrid_engine as opcount

    def read(name, run):
        return harness.find("layer_metrics", name).read(run)

    config = harness.resolve_cell(MANIFEST, CELL)[1]
    p = opcount.parameters(config)
    # ISSUE 46's count, by part
    assert p["embed"] == 100352 * 2048 == 205520896
    assert p["mlp"] == 50331648
    assert p["mamba"] == 2048 * 8512 + 4096 * 2048 == 25821184
    assert p["attention"] == 10485760
    assert opcount.kinds(config) == {"mamba": 36, "attention": 4}
    assert 3.19e9 < opcount.held_parameters(config) < 3.195e9
    assert 6.38e9 < opcount.weight_bytes(config) < 6.39e9
    held = opcount.cache_bytes(config, 64)
    # 36 states of 128 x 4096 float32 a slot and their tails; 8 KB a token
    assert opcount.state_bytes(config) == 128 * 4096 * 4 + 3 * 4352 * 2
    assert held["state"] == 64 * 36 * (2097152 + 26112)
    assert 4.83e9 < 64 * 36 * 2097152 < 4.84e9
    assert opcount.row_bytes(config) * 4 == 8192
    assert held["full"] == 64 * 4096 * 8192
    run = _traced_run()
    assert read("granite.rows_per_slot", run) == 1000.0
    by = opcount.step_state_bytes(config, 64, 64 * 1000)
    assert by["state"] == 2 * held["state"]
    assert by["full"] == 64 * 1000 * 8192
    total = opcount.step_bytes(config, 64, 64 * 1000)
    # ISSUE 46: 9.66 GB of states beside 6.38 of weights and 0.5 of K and V
    assert 16.5e9 < total < 16.8e9
    assert read("granite.state_bytes_share_pct", run) == pytest.approx(
        100.0 * by["state"] / total)
    assert 57.0 < read("granite.state_bytes_share_pct", run) < 60.0
    # 16.7 GB over 819 GB/s is 20.4 ms of a 27 ms step: the bytes bind
    assert opcount.step_flops(config, 64, 64000) / 197e12 < total / 819e9
    assert read("granite.step_roofline", run) == pytest.approx(
        100 * (total / 819e9) / 0.027)
    assert 74.0 < read("granite.step_roofline", run) < 77.0
    # the kernel took 1 ms a step for 0.52 GB
    assert read("granite.attention_roofline", run) == pytest.approx(
        100 * (by["full"] / 819e9) / 0.001)
    assert read("granite.attention_roofline", _traced_run(kernel=False)) \
        is None
    # 36 scans a prefill, over 128 and over 1024 positions
    moved = [opcount.scan_bytes(config, b) for b in (128, 1024)]
    assert moved[1] == 4 * (1024 * (2 * 4096 + 64 + 256) + 2 * 128 * 4096)
    # ISSUE 46: 1.09 GFLOP a chunk of 256 a layer, and bytes that bind
    assert opcount.scan_flops(config, 1024) == 4 * (
        2 * 256 * 256 * 128 + 2 * 256 * 256 * 4096 + 4 * 256 * 128 * 4096)
    assert 1.08e9 < opcount.scan_flops(config, 256) < 1.10e9
    assert opcount.scan_flops(config, 1024) / 197e12 < moved[1] / 819e9
    assert read("ssd_scan_roofline", run) == pytest.approx(
        100 * (36 * sum(moved) / 819e9) / 0.008)
    assert read("ssd_scan_roofline", _traced_run(kernel=False)) is None
    # prompts of 100 and 600 tokens: buckets 128 (bytes bind: 7.9 ms) and
    # 1024 (operations bind: 31 ms); the median of both over 40 ms
    least = [max(opcount.prefill_flops(config, b) / 197e12,
                 opcount.prefill_bytes(config, b) / 819e9)
             for b in (128, 1024)]
    assert 7.8e-3 < least[0] < 8.0e-3 and 31e-3 < least[1] < 33e-3
    assert read("granite.prefill_roofline", run) == pytest.approx(
        100 * (sum(least) / 2) / 0.04)
    assert read("serve.prefill_share_pct", run) == pytest.approx(
        100 * 0.08 / 0.35)
    assert read("decode.step_device_ms.throughput", run) == \
        pytest.approx(27.0)
    # a program without the counters, an untraced run, another family's
    # cell: nothing to read, and nothing raised
    bare = _traced_run()
    bare["trace"]["counted"] = {"decode_steps": 10}
    other = dict(run, config=dict(config, family="sambay_engine"))
    for name in NEW_METRICS:
        assert read(name, dict(run, trace=None)) is None
        assert read(name, other) is None
    for name in ("granite.step_roofline", "granite.rows_per_slot",
                 "granite.state_bytes_share_pct",
                 "granite.attention_roofline"):
        assert read(name, bare) is None


# -- faults planted under the timed path ---------------------------------------
def _a_state_returned_unchanged(monkeypatch, gh):
    """The decode step hands back every recurrent state as it was given
    it: the tokens served are those of a model that forgets each token."""
    step = gh.GraniteHybrid.decode_step

    def altered(self, params, firsts, seconds, *rest):
        logits, new_firsts, new_seconds, extra = step(
            self, params, firsts, seconds, *rest)
        kept = tuple(old if c.kind == "state" else new for c, old, new
                     in zip(self.cache_spec(), firsts, new_firsts))
        return logits, kept, new_seconds, extra

    monkeypatch.setattr(gh.GraniteHybrid, "decode_step", altered)


def _a_stale_state_after_an_admission(monkeypatch, gh):
    """The prefill's scan starts from what a slot's last session left."""
    scan = gh.ssd_scan
    monkeypatch.setattr(
        gh, "ssd_scan", lambda x, dt, a, b, c, d, state, chunk: scan(
            x, dt, a, b, c, d, state + gh.jnp.float32(0.3), chunk))


def _a_stale_tail_after_an_admission(monkeypatch, gh):
    """The slot keeps a convolution tail that is not the prompt's."""
    window = gh._Prefill.window

    def altered(self, l, xbc):
        out = window(self, l, xbc)
        self.seconds[l] = self.seconds[l] + gh.jnp.asarray(
            0.3, self.seconds[l].dtype)
        return out

    monkeypatch.setattr(gh._Prefill, "window", altered)


def _the_gate_after_the_norm(monkeypatch, gh):
    monkeypatch.setattr(gh, "_gated_norm", lambda y, z, g:
                        gh._rms(y, g) * gh.jax.nn.silu(z))


def _the_score_scale_of_another_model(monkeypatch, gh):
    init = gh.GraniteHybrid.__init__

    def altered(self, cfg, *args):
        init(self, cfg._replace(
            attention_multiplier=cfg.head_dim ** -0.5), *args)

    monkeypatch.setattr(gh.GraniteHybrid, "__init__", altered)


def _an_attention_layer_reads_stale_rows(monkeypatch, gh):
    """The attention layers do not see the row they have just written."""
    attend = gh.decode_attention
    monkeypatch.setattr(
        gh, "decode_attention", lambda q, k, v, pos, scale: attend(
            q, k, v, gh.jnp.maximum(pos - 1, 0), scale))


@pytest.mark.parametrize("fault", [
    _a_state_returned_unchanged, _a_stale_state_after_an_admission,
    _a_stale_tail_after_an_admission, _the_gate_after_the_norm,
    _the_score_scale_of_another_model,
    _an_attention_layer_reads_stale_rows])
def test_a_fault_under_the_timed_path_is_not_correct(monkeypatch, fault):
    from mxnet_tpu.models import granite_hybrid as gh

    fault(monkeypatch, gh)
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

    from benchmark.reference import granite_hybrid_engine as ref

    config = harness.load_json(os.path.join(TINY,
                                            "granite_hybrid_tiny.json"))
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
    ``models/granite_hybrid.py``'s, agree on the benchmark's seeded
    weights."""
    import jax
    import numpy as np

    from benchmark.families import granite_hybrid_engine as family
    from benchmark.reference import granite_hybrid_engine as ref
    from mxnet_tpu.models import granite_hybrid as gh

    config = harness.load_json(os.path.join(TINY,
                                            "granite_hybrid_tiny.json"))
    params = ref.init_weights(config, 11, jax.devices()[0])
    tokens = np.random.RandomState(1).randint(0, 96, 40).astype(np.int32)
    np.testing.assert_allclose(
        ref.forward_logits(ref.sizes(config), params, tokens),
        gh.forward_logits(family.model_of(config).cfg, params, tokens),
        atol=2e-5)
