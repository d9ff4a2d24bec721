"""The cell ``sdar-30b-a3b-chat.reason-saturate`` end to end at CPU size,
past the harness's look for a chip: the family ``sdar_engine`` (which is
also the benchmark's own reference against the program: every served token
judged at the pass that fixed it), faults planted under the timed path,
both fp8 controls, the replay against whole forwards, and the readers on a
run made by hand."""

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
CELL = "sdar-30b-a3b-chat.reason-saturate"
BIG_SEED = 2 ** 31 + 4242
NEW_METRICS = ["sdar.step_roofline", "sdar.attention_roofline",
               "sdar.prefill_roofline", "sdar.flash_attention_roofline",
               "sdar.tokens_per_pass", "sdar.commit_pass_share_pct",
               "sdar.threshold_fixed_share_pct", "sdar.rows_per_slot"]


def _tiny():
    return harness.load_json(os.path.join(TINY, "sdar_tiny.json"))


def _run(seed=BIG_SEED, seconds=1.5, with_control=False, **limits):
    import jax

    cell = {c["name"]: c for c in MANIFEST["workloads"]}[CELL]
    config = _tiny()
    config["limits"].update(limits)
    return harness.run_cell(
        MANIFEST, CELL, seed, seconds, 0, jax.devices()[:1],
        time.monotonic(), with_control=with_control,
        cell_files=(cell, config, harness.load_json(
            os.path.join(TINY, "reason_tiny.json"))))


def test_the_manifest_names_the_cell_and_its_files_resolve():
    import json

    cell, config, traffic = harness.resolve_cell(MANIFEST, CELL)
    assert cell["chips"] == 1 and traffic["kind"] == "closed_loop"
    assert len(cell["why"]) <= 200
    assert harness.metrics_of(MANIFEST, "end_to_end", CELL) == \
        ["setup_s", "decode_tokens_per_s"]
    assert len(MANIFEST["workloads"]) == 7 \
        and all(c["chips"] == 1 for c in MANIFEST["workloads"])
    entry = {c["name"]: c for c in MANIFEST["configs"]}[cell["config"]]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    # every published key of the catalog's row stands but the depth
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    assert entry["source"] == config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key != "num_hidden_layers":
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["experts_held"],
            config["first_expert"]) == (6, 128, 0)
    assert config["published"]["num_hidden_layers"] == 48
    for key in ("reduced_why", "assumed", "deployment", "precision",
                "limits_why"):
        assert config[key], key
    assumed = config["assumed"]
    assert [assumed[k]["value"] for k in (
        "block_length", "denoising_steps", "remasking",
        "confidence_threshold", "mask_token_id")] \
        == [4, 4, "low_confidence_dynamic", 0.9, 151669]
    engine = config["engine"]
    assert engine["slots_why"] and engine["kv_layout"] == "dense"
    assert traffic["clients_per_slot"] * engine["slots"] \
        == engine["max_queue"]
    pt, ot = traffic["prompt_tokens"], traffic["output_tokens"]
    assert pt["max"] <= max(engine["prefill_buckets"])
    assert all(b % 4 == 0 for b in engine["prefill_buckets"])
    assert pt["max"] + ot["max"] + 3 <= engine["max_len"] == 4096
    names = harness.metrics_of(MANIFEST, "per_layer", CELL)
    for name in names:
        harness.find("layer_metrics", name)
    assert set(NEW_METRICS) <= set(names)
    # each new metric names the new cell alone; no roofline of another
    # family's reaches it
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "decode_tokens_per_s"
    assert not [n for n in names if "roofline" in n
                and not n.startswith("sdar.")]


@pytest.mark.parametrize("seed", [5, BIG_SEED])
def test_cell_runs_and_agrees_with_its_reference(seed):
    result, compared, _control = _run(seed)
    assert result["correct"], compared
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "decode_tokens_per_s"}
    by_name = {c["name"]: c for c in compared}
    assert set(by_name) == {
        "served_token_gap", "served_token_mean_gap",
        "fixed_position_mean_gap", "window_compiles"}
    # the widest position gap is a reading on the mean's line, no limit's
    assert by_name["fixed_position_mean_gap"]["widest"] \
        >= by_name["fixed_position_mean_gap"]["value"]
    mean = by_name["served_token_mean_gap"]
    assert mean["requests"] == 16 and mean["tokens"] > 100
    assert mean["left_out"] < mean["tokens"] / 4


def _block_read_causally(monkeypatch, sd):
    plain = sd.decode_attention
    monkeypatch.setattr(
        sd, "decode_attention",
        lambda q, ck, cv, horizon, scale: plain(q, ck, cv, horizon - 2,
                                                scale))


def _run_written_one_block_off(monkeypatch, sd):
    # (a run written in another ORDER is no fault: every row that reads a
    # block reads all of it, and K and V move together)
    import jax.numpy as jnp

    plain = sd.write_slot_rows
    monkeypatch.setattr(
        sd, "write_slot_rows",
        lambda cache, rows, at: plain(cache, rows, jnp.maximum(at - 4, 0)))


def _prompt_prefilled_causally(monkeypatch, sd):
    plain = sd.flash_attention
    monkeypatch.setattr(
        sd, "flash_attention",
        lambda q, k, v, **kw: plain(q, k, v, **dict(kw, block=None)))


def _logits_shifted_by_one(monkeypatch, sd):
    import jax.numpy as jnp

    plain = sd.SDAR.decode_step

    def shifted(self, *args):
        logits, *rest = plain(self, *args)
        return (jnp.roll(logits, 1, axis=1), *rest)

    monkeypatch.setattr(sd.SDAR, "decode_step", shifted)


@pytest.mark.parametrize("fault", [
    _block_read_causally, _run_written_one_block_off,
    _prompt_prefilled_causally, _logits_shifted_by_one],
    ids=lambda f: f.__name__)
def test_a_fault_under_the_timed_path_is_not_correct(monkeypatch, fault):
    from mxnet_tpu.models import sdar as sd

    fault(monkeypatch, sd)
    result, compared, _control = _run(seed=9)
    assert not result["correct"]
    assert {c["name"] for c in compared if not c["ok"]} \
        & {"served_token_mean_gap", "fixed_position_mean_gap"}, compared


def test_a_position_fixed_out_of_turn_is_not_correct(monkeypatch):
    """The tokens are the reference's best, the position is not: a sampler
    that fixes the LEAST confident open position."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import sdar as sd

    plain = sd.SDAR.decode_step

    def flat(self, *args):
        logits, *rest = plain(self, *args)
        # flatten the most confident row of every block about its best
        # logit: its best token stays, and the engine, which ranks by the
        # softmax's best probability, now fixes another position first
        best = logits.max(-1, keepdims=True)
        conf = best[..., 0] - jax.nn.logsumexp(logits, axis=-1)
        first = conf == conf.max(-1, keepdims=True)
        return (jnp.where(first[..., None], best + 0.25 * (logits - best),
                          logits), *rest)

    monkeypatch.setattr(sd.SDAR, "decode_step", flat)
    result, compared, _control = _run(seed=9)
    by_name = {c["name"]: c for c in compared}
    assert by_name["served_token_gap"]["ok"]
    assert not by_name["fixed_position_mean_gap"]["ok"], compared
    assert not result["correct"]


@pytest.mark.parametrize("fault, every, fails", [
    ("crossed", 8, "served_token_gap"),
    ("out_of_turn", 4, "fixed_position_mean_gap")])
def test_the_chip_tool_s_fault_fails_the_limit_it_is_read_for(
        monkeypatch, fault, every, fails):
    """``benchmark/tools/fault_sdar.py`` plants these under the cell's own
    load on the chip, where the limit on the widest token gap got its
    upper reading and the widest position gap got none (the
    configuration's ``limits_why``); here the same faults at the tiny
    size."""
    from benchmark.tools import fault_sdar
    from mxnet_tpu.models import sdar as sd

    # so that the step the tool replaces is put back after the test
    monkeypatch.setattr(sd.SDAR, "decode_step", sd.SDAR.decode_step)
    fault_sdar.plant(fault, every)
    result, compared, _control = _run(seed=9)
    by_name = {c["name"]: c for c in compared}
    assert not by_name[fails]["ok"], compared
    assert not result["correct"]


def test_too_few_served_sessions_is_not_correct():
    result, compared, _control = _run(seconds=0.3, check_sessions=4000)
    assert not result["correct"]
    assert "the check reads 4000" in compared[0]["why"]


@pytest.mark.parametrize("seed", [4, BIG_SEED])
def test_both_fp8_controls_fail_what_the_window_served(seed):
    _result, compared, control = _run(seed, with_control=True)
    assert all(c["ok"] for c in compared), compared
    failed = {c["control"] for c in control if not c["ok"]}
    assert {"fp8", "cache-fp8"} <= failed, control
    assert "bfloat16" in {c["control"] for c in control}


def test_the_replay_is_whole_forwards_over_each_state():
    """Sequences laid end to end in one row; pass ``t`` of the replay, all
    blocks' states at once against the final K and V, gives at every block
    the logits of one plain forward over the final transcript up to the
    block and the block's state; and the program's own plain reference
    gives the same logits."""
    import jax
    import numpy as np

    from benchmark.families import sdar_engine as family
    from benchmark.reference import sdar_engine as ref
    from mxnet_tpu.models import sdar

    config = _tiny()
    z = ref.sizes(config)
    params = ref.init_weights(config, 3, jax.devices()[0])
    rs = np.random.RandomState(0)
    lengths = [12, 8, 16]
    width = 40
    seq, pos = (np.zeros((width,), np.int32) for _ in range(2))
    seg = np.full((width,), -1, np.int32)
    at = np.full((width,), -1, np.int32)
    for i, start in ref.pack(lengths, width)[0]:
        n = lengths[i]
        seq[start:start + n] = rs.randint(0, 90, n)
        seg[start:start + n], pos[start:start + n] = i, np.arange(n)
        at[start + 5:start + n] = rs.randint(0, 4, n - 5)
    args = [jax.numpy.asarray(a) for a in (seq, at, seg, pos)]
    passes = ref.replay(z, params, *args)
    assert len(passes) == 4
    cfg = family.model_of(config).cfg
    whole = jax.jit(lambda tokens: ref.forward_logits(z, params, tokens))
    for i, start in ref.pack(lengths, width)[0]:
        n = lengths[i]
        for t, x in enumerate(passes):
            got = np.asarray(ref._logits_jit(
                "float32", z["eps"], ref._head(params), x))[start:start + n]
            for first in range(0, n, 4):
                state = np.where(at[start + first:start + first + 4] < t,
                                 seq[start + first:start + first + 4],
                                 z["mask_id"])
                tokens = jax.numpy.asarray(np.concatenate(
                    [seq[start:start + first], state]))
                want = np.asarray(whole(tokens))
                np.testing.assert_allclose(got[first:first + 4],
                                           want[first:], atol=2e-5)
        # the program's plain reference, once a sequence: the last state
        mine = np.asarray(sdar.forward_logits(cfg, params, tokens))
        np.testing.assert_allclose(mine, want, atol=2e-5)


class _Req:
    def __init__(self, prompt, sent, times):
        self.prompt, self.sent, self.token_times = [0] * prompt, sent, times
        self.tokens, self.error = [0] * len(times), None


def _traced_run():
    """A run made by hand at the cell's real configuration: 96 live slots
    at 1000 rows each, one position a pass, 20 ms a step."""
    import numpy as np

    _cell, config, traffic = harness.resolve_cell(MANIFEST, CELL)
    modules = [("jit_step", 0.1 * i, 0.02) for i in range(10)] \
        + [("jit_prefill", 0.55, 0.01)]
    requests = [_Req(100, 70.0 + 0.125 * i, [71.0 + 0.125 * i, 100.5])
                for i in range(95)] + [_Req(100, 100.0, [100.6])]
    return {"config": config, "traffic": traffic, "slots": 96,
            "peaks": harness.peaks_of("TPU v5 lite"),
            "window": {"t0": 90.0, "t_end": 101.0, "requests": requests},
            "trace": {"window_s": 1.0, "busy_s": 0.5,
                      "devices": [{
                          "busy_s": 0.5, "modules": modules,
                          "op_seconds": {
                              "decode_attention f32[96,4,32,128]": 0.03,
                              "flash_attention bf16[32,128,128]": 0.0005,
                              "fusion f32[384,2048]": 0.1}}],
                      "counted": {
                          "moe_picks": np.full((6, 128), 240, np.int64),
                          "moe_steps": 10, "moe_rows": 3840,
                          "moe_picks_total": 3840 * 8 * 6,
                          "sdar_passes": 960, "sdar_commits": 192,
                          "sdar_tokens_committed": 768,
                          "sdar_rows_read": 960 * 1004,
                          "sdar_fixed_by_threshold": 0,
                          "sdar_fixed_by_quota": 768}}}


def test_the_readers_on_a_run_made_by_hand():
    from benchmark.opcount import sdar_engine as opcount

    def read(name, run):
        return harness.find("layer_metrics", name).read(run)

    config = harness.resolve_cell(MANIFEST, CELL)[1]
    p = opcount.parameters(config)
    # ISSUE 42's count
    assert (p["attention"], p["router"], p["expert"]) \
        == (18874368, 262144, 4718592)
    assert p["embed"] == p["head"] == 151936 * 2048
    assert 4.361e9 < opcount.held_parameters(config) < 4.362e9
    assert opcount.row_bytes(config) == 2048
    assert opcount.cache_bytes(config, 96) == 96 * 4096 * 6 * 2048
    assert opcount._pairs(8, 4) == 4 * 4 + 4 * 8
    run = _traced_run()
    assert read("moe.tokens_per_expert", run) == 24.0
    assert read("moe.imbalance", run) == 1.0
    assert read("sdar.tokens_per_pass", run) == pytest.approx(0.8)
    assert read("sdar.commit_pass_share_pct", run) == 20.0
    assert read("sdar.threshold_fixed_share_pct", run) == 0.0
    assert read("sdar.rows_per_slot", run) == 1004.0
    # every weight once 8.1 GB and 1.2 GB of K and V: 11.3 ms of 20; the
    # operations of 384 rows come to 3 ms
    assert 54.0 < read("sdar.step_roofline", run) < 60.0
    # 96 x 1004 rows x 6 layers x 2 KiB at 819 GB/s in 3 ms a step
    assert read("sdar.attention_roofline", run) == pytest.approx(
        100 * opcount.attention_bytes(config, 96 * 1004) / 819e9 / 0.003)
    assert 45.0 < read("sdar.attention_roofline", run) < 50.0
    assert read("serve.prefill_share_pct", run) == pytest.approx(2.0)
    # one admission of the 128 bucket: its weights' bytes bind
    assert opcount.prefill_bytes(config, 128) / 819e9 \
        > opcount.prefill_flops(config, 128) / 197e12
    assert 70.0 < read("sdar.prefill_roofline", run) < 95.0
    assert read("sdar.flash_attention_roofline", run) == pytest.approx(
        100 * 5 * opcount.flash_bytes(config, 128) / 819e9 / 0.0005)
    assert read("sdar.flash_attention_roofline", run) < 100.0
    # a program without the counters (the parent): nothing to read
    bare = _traced_run()
    for key in ("moe_steps", "sdar_passes"):
        del bare["trace"]["counted"][key]
    for name in NEW_METRICS[:2] + NEW_METRICS[4:]:
        assert read(name, bare) is None
    # no admission in the traced seconds, no kernel time: left out
    idle = _traced_run()
    idle["window"]["requests"].pop()
    idle["trace"]["devices"][0]["op_seconds"].pop(
        "decode_attention f32[96,4,32,128]")
    assert read("sdar.prefill_roofline", idle) is None
    assert read("sdar.flash_attention_roofline", idle) is None
    assert read("sdar.attention_roofline", idle) is None
    # an untraced run, and another family's
    for name in NEW_METRICS:
        assert read(name, dict(run, trace=None)) is None
    other = _traced_run()
    other["config"] = dict(other["config"], family="smallthinker_engine")
    assert read("sdar.prefill_roofline", other) is None
    assert read("sdar.flash_attention_roofline", other) is None
