"""The cell ``deepseek-v2.doc-saturate`` end to end at CPU size, past the
harness's look for a chip: the family ``deepseek_v2_engine`` (which is also
the benchmark's own reference against the program), faults planted under
the timed path, both fp8 controls, the block-wise reference against the
whole one, and the readers on a run made by hand."""

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
CELL = "deepseek-v2.doc-saturate"
BIG_SEED = 2 ** 31 + 4321


def _tiny():
    return harness.load_json(os.path.join(TINY, "deepseek_v2_tiny.json"))


def _run(seed=BIG_SEED, seconds=1.5, with_control=False, **limits):
    import jax

    cell = {c["name"]: c for c in MANIFEST["workloads"]}[CELL]
    config = _tiny()
    config["limits"].update(limits)
    return harness.run_cell(
        MANIFEST, CELL, seed, seconds, 0, jax.devices()[:1],
        time.monotonic(), with_control=with_control,
        cell_files=(cell, config, harness.load_json(
            os.path.join(TINY, "doc_saturate_tiny.json"))))


def test_the_manifest_names_the_cell_and_its_files_resolve():
    import json

    from benchmark import loadgen

    cell, config, traffic = harness.resolve_cell(MANIFEST, CELL)
    assert cell["chips"] == 1 and traffic["kind"] == "closed_loop"
    assert harness.metrics_of(MANIFEST, "end_to_end", CELL) == \
        ["setup_s", "decode_tokens_per_s"]
    entry = {c["name"]: c for c in MANIFEST["configs"]}[cell["config"]]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "experts_held", "vocab_size"]
    # every published key of the catalog's row stands but the three cut
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "DeepSeek-V2")
    assert entry["source"] == config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in ("num_hidden_layers", "vocab_size"):
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["experts_held"],
            config["first_expert"], config["vocab_size"]) \
        == (5, 20, 0, 12800)
    assert config["published"]["num_hidden_layers"] == 60 \
        and config["published"]["vocab_size"] == 102400
    # one request a slot: every seed serves the same set in another order
    engine = config["engine"]
    assert traffic["request_set"] == engine["slots"] == 128 \
        and traffic["clients_per_slot"] == 1
    pt, ot = traffic["prompt_tokens"], traffic["output_tokens"]
    assert (pt["median"], pt["sigma"], pt["min"], pt["max"]) \
        == (3072, 0.25, 2048, 4096)
    assert (ot["median"], ot["sigma"], ot["min"], ot["max"]) \
        == (3072, 0.3, 1536, 4096)
    assert traffic["ramp_seconds"] == 30
    prompts = loadgen.lognormal_quantiles(
        traffic["request_set"], pt["median"], pt["sigma"], pt["min"],
        pt["max"])
    buckets = sorted(engine["prefill_buckets"])
    assert buckets[-1] == 4096 and all(b % 512 == 0 for b in buckets)
    padded = sum(next(b for b in buckets if b >= p) for p in prompts)
    assert padded < 1.08 * sum(prompts)
    assert pt["max"] + ot["max"] <= engine["max_len"] == 8192
    for name in harness.metrics_of(MANIFEST, "per_layer", CELL):
        harness.find("layer_metrics", name)


@pytest.mark.parametrize("seed", [5, BIG_SEED])
def test_cell_runs_and_agrees_with_its_reference(seed):
    result, compared, _control = _run(seed)
    assert result["correct"], compared
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "decode_tokens_per_s"}
    by_name = {c["name"]: c for c in compared}
    mean = by_name["served_token_mean_gap"]
    assert mean["requests"] == 32 and mean["tokens"] > 400
    # past the original positions YaRN stretches from
    assert mean["longest"] > 16


def _rotated_term_left_out(monkeypatch, dm):
    import jax.numpy as jnp

    plain = dm.latent_attention
    monkeypatch.setattr(
        dm, "latent_attention",
        lambda ql, qr, cl, cr, n: plain(ql, jnp.zeros_like(qr), cl, cr, n))


def _row_one_position_off(monkeypatch, dm):
    import jax.numpy as jnp

    plain = dm.write_slot_rows
    monkeypatch.setattr(
        dm, "write_slot_rows",
        lambda cache, rows, at: plain(cache, rows, jnp.maximum(at - 1, 0)))


def _group_limit_ignored(monkeypatch, dm):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import exaone_moe as xm

    def altered(cfg, h, moe):
        s = jax.nn.softmax(jnp.dot(h, moe["router"]), axis=-1)
        w, chosen = jax.lax.top_k(s, cfg.top_k)
        return chosen.astype(jnp.int32), w * cfg.routed_scale

    monkeypatch.setattr(xm, "route", altered)


def _factor_16_left_out(monkeypatch, dm):
    init = dm.DeepSeekV2.__init__
    monkeypatch.setattr(
        dm.DeepSeekV2, "__init__",
        lambda self, cfg, *args: init(
            self, cfg._replace(routed_scale=1.0), *args))


@pytest.mark.parametrize("fault", [
    _rotated_term_left_out, _row_one_position_off, _group_limit_ignored,
    _factor_16_left_out], ids=lambda f: f.__name__)
def test_a_fault_under_the_timed_path_is_not_correct(monkeypatch, fault):
    from mxnet_tpu.models import deepseek_v2 as dm

    fault(monkeypatch, dm)
    result, compared, _control = _run(seed=9)
    assert not result["correct"]
    assert "served_token_mean_gap" in \
        {c["name"] for c in compared if not c["ok"]}, compared


def test_the_chip_tools_fault_moves_few_tokens_far(monkeypatch):
    """``benchmark/tools/fault_deepseek_v2.py``: one token in 16 of each
    session is another session's best; the widest gap reads it."""
    from benchmark.tools import fault_deepseek_v2 as tool
    from mxnet_tpu.models import deepseek_v2 as dm

    monkeypatch.setattr(dm.DeepSeekV2, "decode_step",
                        dm.DeepSeekV2.decode_step)     # put back after
    tool.plant_crossed(16)
    result, compared, _control = _run(seed=9)
    by_name = {c["name"]: c for c in compared}
    assert not result["correct"]
    assert by_name["served_token_gap"]["value"] > 1.0
    # few: most served tokens are still the reference's best
    mean = by_name["served_token_mean_gap"]
    assert 0 < mean["not_the_best"] < mean["tokens"] / 8


def test_too_few_served_sessions_is_not_correct():
    result, compared, _control = _run(seconds=0.3, check_sessions=4000)
    assert not result["correct"]
    assert "the check reads 4000" in compared[0]["why"]


@pytest.mark.parametrize("seed", [4, BIG_SEED])
def test_both_fp8_controls_fail_what_the_window_served(seed):
    _result, compared, control = _run(seed, with_control=True)
    assert all(c["ok"] for c in compared), compared
    failed = {c["control"] for c in control if not c["ok"]}
    assert {"fp8", "latent-fp8"} <= failed, control
    assert "bfloat16" in {c["control"] for c in control}


def test_the_reference_in_blocks_is_the_reference_whole(monkeypatch):
    """Sequences laid end to end in one row, each token attending within
    its own, give the logits each sequence gives alone; queries attended a
    block at a time and logits read in blocks are those read whole."""
    import jax
    import numpy as np

    from benchmark.reference import deepseek_v2_engine as ref

    config = _tiny()
    z = ref.sizes(config)
    params = ref.init_weights(config, 3, jax.devices()[0])
    rs = np.random.RandomState(0)
    seqs = [rs.randint(0, z["vocab"], n).astype(np.int32)
            for n in (37, 9, 18)]
    row = np.concatenate(seqs)
    seg = np.concatenate([np.full(len(q), i, np.int32)
                          for i, q in enumerate(seqs)])
    pos = np.concatenate([np.arange(len(q), dtype=np.int32) for q in seqs])
    whole = np.asarray(ref.forward_logits(z, params, row, seg, pos))
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    monkeypatch.setattr(ref, "LOGIT_BLOCK", 24)
    together = np.asarray(ref.forward_logits(z, params, row, seg, pos))
    np.testing.assert_allclose(together, whole, atol=2e-4)
    assert np.abs(whole).max() > 1.0
    start = 0
    for q in seqs:
        alone = np.asarray(ref.forward_logits(z, params, q[:len(q) // 8 * 8
                                                           or len(q)]))
        np.testing.assert_allclose(
            together[start:start + len(alone)], alone, atol=2e-4)
        start += len(q)
    hidden = ref.forward_hidden(z, params, row, seg, pos)
    best = np.asarray(ref.best_tokens(z, params, hidden, ref.REFERENCE))
    np.testing.assert_array_equal(best, together.argmax(-1))
    gaps = np.asarray(ref.gaps_below_best(
        z, params, hidden, jax.numpy.asarray(row)[None]))
    np.testing.assert_allclose(
        gaps[0], together.max(-1) - together[np.arange(len(row)), row],
        atol=1e-5)


def test_the_reference_is_the_programs_plain_forward_pass():
    """Two references written apart (the benchmark's and the model file's)
    agree on the same weights, choices included."""
    import jax
    import numpy as np

    from benchmark.families import deepseek_v2_engine as family
    from benchmark.reference import deepseek_v2_engine as ref
    from mxnet_tpu.models import deepseek_v2 as dm

    config = _tiny()
    z = ref.sizes(config)
    params = ref.init_weights(config, 11, jax.devices()[0])
    tokens = np.random.RandomState(1).randint(0, z["vocab"], 32) \
        .astype(np.int32)
    cfg = family.model_of(config).cfg
    want, choices = dm.forward_logits(cfg, params, jax.numpy.asarray(tokens),
                                      with_choices=True)
    np.testing.assert_allclose(ref.forward_logits(z, params, tokens), want,
                               atol=5e-4)
    _x, mine = ref.forward_hidden(z, params, tokens, with_choices=True)
    for a, b in zip(mine, choices):
        np.testing.assert_array_equal(np.sort(a, -1), np.sort(b, -1))
    np.testing.assert_allclose(ref.inv_freq(z), dm.yarn_inv_freq(cfg))
    assert ref.softmax_scale(z) == dm.softmax_scale(cfg)


class _Req:
    def __init__(self, prompt, sent, times):
        self.prompt, self.sent, self.token_times = [0] * prompt, sent, times


def _traced_run():
    """What a traced run hands a reader, made by hand: 10 steps of 20 ms in
    one traced second over 128 slots of 5000 rows, one prefill of a 3072
    bucket of 100 ms, the 128 prompts filled in 16 s of the ramp."""
    import numpy as np

    _cell, config, traffic = harness.resolve_cell(MANIFEST, CELL)
    modules = [("jit_step", 0.1 * i, 0.02) for i in range(10)] \
        + [("jit_prefill", 0.55, 0.1)]
    requests = [_Req(3000, 70.0 + 0.125 * i, [71.0 + 0.125 * i, 100.5])
                for i in range(127)] + [_Req(3000, 100.0, [100.6])]
    return {"config": config, "traffic": traffic, "slots": 128,
            "peaks": harness.peaks_of("TPU v5 lite"),
            "window": {"t0": 90.0, "t_end": 101.0, "requests": requests},
            "trace": {"window_s": 1.0, "busy_s": 0.5,
                      "devices": [{
                          "busy_s": 0.5, "modules": modules,
                          "op_seconds": {
                              "latent_attention f32[128,128,512]": 0.09,
                              "flash_attention bf16[128,3072,128]": 0.025,
                              "fusion f32[128,5120]": 0.1}}],
                      "counted": {
                          "moe_picks": np.full((4, 20), 48, np.int64),
                          "moe_steps": 10, "moe_rows": 1280,
                          "moe_picks_total": 1280 * 6 * 4,
                          "rows_latent": 1280 * 5000 * 5,
                          "rows_reached": 1280 * 2}}}


def test_the_readers_on_a_run_made_by_hand():
    from benchmark.opcount import deepseek_v2_engine as opcount

    def read(name, run):
        return harness.find("layer_metrics", name).read(run)

    config = harness.resolve_cell(MANIFEST, CELL)[1]
    p = opcount.parameters(config)
    # ISSUE 39's sizing table
    assert (p["attention"], p["dense"], p["shared"], p["expert"]) \
        == (149225472, 188743680, 47185920, 23592960)
    assert p["embed"] + p["head"] == 2 * 12800 * 5120
    assert 3.145e9 < opcount.held_parameters(config) < 3.146e9
    assert opcount.row_bytes(config) == 1152
    # as it lies: the rotated key in 128 lanes, 1280 B a token a layer
    assert opcount.cache_bytes(config, 128) == 128 * 8192 * 5 * 1280
    # a row's bytes and operations meet on this chip
    assert opcount.latent_bytes(config, 1000) / 819e9 == pytest.approx(
        opcount.latent_flops(config, 1000) / 197e12, rel=0.02)
    run = _traced_run()
    assert read("moe.tokens_per_expert", run) == 4.8
    assert read("moe.imbalance", run) == 1.0
    assert read("mla.rows_per_slot", run) == 5000.0
    # every weight once 6.2 GB and 3.7 GB of latent rows: 12.0 ms of 20
    assert 58.0 < read("mla.step_roofline", run) < 62.0
    # 3.2 M rows at 1.414 ns (operations) in 9 ms a step
    assert read("mla.attention_roofline", run) == pytest.approx(
        100 * opcount.latent_flops(config, 128 * 5000 * 5) / 197e12 / 0.009)
    assert 45.0 < read("mla.attention_roofline", run) < 55.0
    # latent rows 3.69 GB and attention's projections 1.49 of 9.85 GB
    assert 51.0 < read("mla.latent_bytes_share_pct", run) < 54.0
    # one admission of the 3072 bucket: 46.9 ms at the peak, of 100 ms
    assert 44.0 < read("mla.prefill_roofline", run) < 50.0
    # its five calls of the prompt's attention: 1.96 ms each at the peak
    # (the heads' 0.5 GB would take 0.6 ms), of 25 ms in the kernel
    assert opcount.flash_bytes(config, 3072) == 2 * 128 * 3072 * 640
    assert read("mla.flash_attention_roofline", run) == pytest.approx(
        100 * 5 * opcount.flash_flops(config, 3072) / 197e12 / 0.025)
    assert 38.0 < read("mla.flash_attention_roofline", run) < 41.0
    assert read("serve.prefill_share_pct", run) == pytest.approx(20.0)
    assert read("serve.fill_prompt_tokens_per_s", run) == pytest.approx(
        127 * 3000 / 16.75)
    # expanded heads: scores over 192 and sums over 128, causal pairs; the
    # share's 0.75 picks a row are 1.2 T of the 4096 bucket's 13.2 T
    assert opcount.flash_flops(config, 4096) \
        == 2 * 128 * 320 * (4096 * 4097 // 2)
    assert opcount.prefill_flops(config, 4096) == pytest.approx(
        13.25e12, rel=0.01)
    # a program without the counters (the parent): nothing to read
    bare = _traced_run()
    for key in ("moe_steps", "rows_latent"):
        del bare["trace"]["counted"][key]
    for name in ("mla.step_roofline", "mla.rows_per_slot",
                 "mla.attention_roofline", "mla.latent_bytes_share_pct"):
        assert read(name, bare) is None
    # no admission in the traced seconds, no kernel time: left out
    idle = _traced_run()
    idle["window"]["requests"].pop()
    idle["trace"]["devices"][0]["op_seconds"].pop(
        "latent_attention f32[128,128,512]")
    assert read("mla.prefill_roofline", idle) is None
    assert read("mla.flash_attention_roofline", idle) is None
    assert read("mla.attention_roofline", idle) is None
    assert read("mla.step_roofline", dict(run, trace=None)) is None
    # another family's run: the prefill's reader is this family's alone
    other = _traced_run()
    other["config"] = dict(other["config"], family="smallthinker_engine")
    assert read("mla.prefill_roofline", other) is None
