"""The cell ``smallthinker-21ba3b-instruct.long-saturate`` end to end at CPU
size, past the harness's look for a chip: the family
``smallthinker_engine`` (which is also the benchmark's own reference
against the program), faults planted under the timed path, both controls,
and the readers on a run made by hand."""

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
CELL = "smallthinker-21ba3b-instruct.long-saturate"
BIG_SEED = 2 ** 31 + 4321


def _run(seed=BIG_SEED, seconds=1.5, with_control=False, **limits):
    import jax

    cell = {c["name"]: c for c in MANIFEST["workloads"]}[CELL]
    config = harness.load_json(os.path.join(TINY, "smallthinker_tiny.json"))
    config["limits"].update(limits)
    return harness.run_cell(
        MANIFEST, CELL, seed, seconds, 0, jax.devices()[:1],
        time.monotonic(), with_control=with_control,
        cell_files=(cell, config, harness.load_json(
            os.path.join(TINY, "long_saturate_tiny.json"))))


def test_the_manifest_names_the_cell_and_its_files_resolve():
    from benchmark import loadgen

    cell, config, traffic = harness.resolve_cell(MANIFEST, CELL)
    assert cell["chips"] == 1 and traffic["kind"] == "closed_loop"
    assert harness.metrics_of(MANIFEST, "end_to_end", CELL) == \
        ["setup_s", "decode_tokens_per_s"]
    entry = {c["name"]: c for c in MANIFEST["configs"]}[cell["config"]]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    # every published width and count stands; the depth is cut
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_ffn_hidden_size"],
            config["moe_num_primary_experts"],
            config["moe_num_active_primary_experts"],
            config["sliding_window_size"], config["vocab_size"],
            config["experts_held"]) == (2560, 28, 4, 128, 768, 64, 6, 4096,
                                        151936, 64)
    assert len(config["rope_layout"]) == 52 \
        and len(config["sliding_window_layout"]) == 52
    assert config["num_hidden_layers"] == 8
    # one request a slot: every seed serves the same set in another order
    engine = config["engine"]
    assert traffic["request_set"] == engine["slots"] \
        and traffic["clients_per_slot"] == 1
    pt = traffic["prompt_tokens"]
    prompts = loadgen.lognormal_quantiles(
        traffic["request_set"], pt["median"], pt["sigma"], pt["min"],
        pt["max"])
    buckets = sorted(engine["prefill_buckets"])
    assert len(buckets) <= 5 and buckets[-1] == 8192 \
        and all(b % 1024 == 0 for b in buckets)
    padded = sum(next(b for b in buckets if b >= p) for p in prompts)
    assert padded < 1.15 * sum(prompts)
    assert traffic["prompt_tokens"]["max"] \
        + traffic["output_tokens"]["max"] <= engine["max_len"] == 16384
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
    # sessions cut at the window's close are read beside finished ones,
    # past the ring's wrap
    assert mean["longest"] > 8


def _softmax_over_all_not_renormed(monkeypatch, st):
    import jax
    import jax.numpy as jnp

    def altered(cfg, h, moe):
        s = jax.nn.softmax(jnp.dot(h, moe["router"]), axis=-1)
        w, chosen = jax.lax.top_k(s, cfg.top_k)
        return chosen.astype(jnp.int32), w

    monkeypatch.setattr(st, "route", altered)


def _altered_config(**changes):
    def fault(monkeypatch, st):
        init = st.SmallThinker.__init__

        def altered(self, cfg, *args):
            init(self, cfg._replace(**{
                k: v(cfg) for k, v in changes.items()}), *args)

        monkeypatch.setattr(st.SmallThinker, "__init__", altered)
    fault.__name__ = "_altered_" + "_".join(changes)
    return fault


def _ring_read_past_its_live_rows(monkeypatch, st):
    import jax.numpy as jnp

    plain = st.decode_attention

    def altered(q, ck, cv, lengths, scale):
        if ck.shape[2] == 8:        # a ring: every row, live or not
            lengths = jnp.full_like(lengths, 7)
        return plain(q, ck, cv, lengths, scale)

    monkeypatch.setattr(st, "decode_attention", altered)


@pytest.mark.parametrize("fault", [
    _softmax_over_all_not_renormed,
    _altered_config(activation=lambda cfg: "silu"),
    _altered_config(window=lambda cfg: cfg.window - 1),
    _ring_read_past_its_live_rows], ids=lambda f: f.__name__)
def test_a_fault_under_the_timed_path_is_not_correct(monkeypatch, fault):
    """The faults that turn a served token at this size (the stream of a
    model of width 64 is mostly its embedding; ``tests/test_smallthinker.py``
    holds every one of the six against the logits)."""
    from mxnet_tpu.models import smallthinker as st

    fault(monkeypatch, st)
    result, compared, _control = _run(seed=9)
    assert not result["correct"]
    assert "served_token_mean_gap" in \
        {c["name"] for c in compared if not c["ok"]}, compared


def test_too_few_served_sessions_is_not_correct():
    result, compared, _control = _run(seconds=0.3, check_sessions=4000)
    assert not result["correct"]
    assert "the check reads 4000" in compared[0]["why"]


@pytest.mark.parametrize("seed", [4, BIG_SEED])
def test_both_controls_fail_what_the_window_served(seed):
    _result, compared, control = _run(seed, with_control=True)
    assert all(c["ok"] for c in compared), compared
    failed = {c["control"] for c in control if not c["ok"]}
    assert {"fp8", "no-window"} <= failed, control
    assert "bfloat16" in {c["control"] for c in control}


def test_the_reference_reads_a_row_of_sequences_as_each_alone():
    """Sequences laid end to end in one row, each token attending within
    its own, give the logits each sequence gives alone; and the logits
    read in blocks are the logits read whole."""
    import jax
    import numpy as np

    from benchmark.reference import smallthinker_engine as ref

    config = harness.load_json(os.path.join(TINY, "smallthinker_tiny.json"))
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
    hidden = ref.forward_hidden(z, params, row, seg, pos)
    best = np.asarray(ref.best_tokens(z, params, hidden, ref.REFERENCE))
    np.testing.assert_array_equal(best, together.argmax(-1))
    gaps = np.asarray(ref.gaps_below_best(
        z, params, hidden, jax.numpy.asarray(row)[None]))
    np.testing.assert_allclose(
        gaps[0], together.max(-1) - together[np.arange(len(row)), row],
        atol=1e-6)


def test_the_reference_is_the_programs_plain_forward_pass():
    """Two references written apart (the benchmark's and the model file's)
    agree on the same weights."""
    import jax
    import numpy as np

    from benchmark.families import smallthinker_engine as family
    from benchmark.reference import smallthinker_engine as ref
    from mxnet_tpu.models import smallthinker as st

    config = harness.load_json(os.path.join(TINY, "smallthinker_tiny.json"))
    z = ref.sizes(config)
    params = ref.init_weights(config, 11, jax.devices()[0])
    tokens = np.random.RandomState(1).randint(0, z["vocab"], 29) \
        .astype(np.int32)
    cfg = family.model_of(config).cfg
    np.testing.assert_allclose(
        ref.forward_logits(z, params, tokens),
        st.forward_logits(cfg, params, jax.numpy.asarray(tokens)),
        atol=2e-5)


class _Req:
    def __init__(self, prompt, sent, times):
        self.prompt, self.sent, self.token_times = [0] * prompt, sent, times


def _traced_run():
    """What a traced run hands a reader, made by hand: 10 steps of 20 ms in
    one traced second over 48 slots of 6000 rows, one prefill of a 4096
    bucket, the 48 prompts filled in 6 s of the ramp."""
    import numpy as np

    _cell, config, traffic = harness.resolve_cell(MANIFEST, CELL)
    modules = [("jit_step", 0.1 * i, 0.02) for i in range(10)] \
        + [("jit_prefill", 0.55, 0.03)]
    requests = [_Req(4000, 70.0 + 0.125 * i, [71.0 + 0.125 * i, 100.5])
                for i in range(47)] + [_Req(4000, 100.0, [100.6])]
    return {"config": config, "traffic": traffic, "slots": 48,
            "peaks": harness.peaks_of("TPU v5 lite"),
            "window": {"t0": 90.0, "t_end": 101.0, "requests": requests},
            "trace": {"window_s": 1.0, "busy_s": 0.5,
                      "devices": [{
                          "busy_s": 0.5, "modules": modules,
                          "op_seconds": {
                              "decode_attention f32[48,4,7,128]": 0.05,
                              "flash_attention bf16[28,4096,128]": 0.008,
                              "fusion f32[48,2560]": 0.1}}],
                      "counted": {
                          "moe_picks": np.full((8, 64), 45, np.int64),
                          "moe_steps": 10, "moe_rows": 480,
                          "moe_picks_total": 480 * 6 * 8,
                          "rows_full": 480 * 6000, "rows_ring": 480 * 4096}}}


def test_the_readers_on_a_run_made_by_hand():
    from benchmark.opcount import smallthinker_engine as opcount

    def read(name, run):
        return harness.find("layer_metrics", name).read(run)

    config = harness.resolve_cell(MANIFEST, CELL)[1]
    p = opcount.parameters(config)
    # ISSUE 33's sizing table
    assert (p["attention"], p["expert"]) == (20971520, 5898240)
    assert p["embed"] + p["head"] == 777912320
    assert 3.966e9 < opcount.held_parameters(config) < 3.968e9
    assert opcount.cache_bytes(config, 48) == 48 * 2048 * (
        2 * 16384 + 6 * 4096)
    assert opcount.kinds(config) == (2, 6)
    run = _traced_run()
    assert read("moe.tokens_per_expert", run) == 4.5
    assert read("moe.imbalance", run) == 1.0
    assert read("smallthinker.rows_per_slot", run) == 6000.0
    assert read("smallthinker.window_rows_share_pct", run) == \
        pytest.approx(100 * 4096 / 6000.0)
    assert read("serve.prefill_share_pct", run) == pytest.approx(6.0)
    # every weight once 7.2 GB, the rows 3.6 GB: 13.2 ms of a 20 ms step
    assert 62.0 < read("smallthinker.step_roofline", run) < 70.0
    # K and V of 48 x (2 x 6000 + 6 x 4096) rows in 5 ms a step
    assert read("smallthinker.attention_roofline", run) == pytest.approx(
        100 * 48 * 2048 * (2 * 6000 + 6 * 4096) / 819e9 / 0.005)
    # one admission of the 4096 bucket: 4.7 T operations (2.3 T of them
    # the experts') are 23.7 ms at the peak, of a prefill of 30 ms
    assert 75.0 < read("smallthinker.prefill_roofline", run) < 82.0
    assert 30.0 < read("flash_attention_roofline", run) < 100.0
    # 47 prompts of 4000 tokens, first submit 70.0, last first token 76.75
    assert read("serve.fill_prompt_tokens_per_s", run) == pytest.approx(
        47 * 4000 / 6.75)
    # a window layer's attention is counted with its window
    assert opcount.flash_flops(config, 8192, 4096) < \
        0.76 * opcount.flash_flops(config, 8192, None)
    assert opcount.flash_flops(config, 4096, 4096) == \
        opcount.flash_flops(config, 4096, None)
    # a program without the counters (the parent): nothing to read
    bare = _traced_run()
    for key in ("moe_steps", "rows_full", "rows_ring"):
        del bare["trace"]["counted"][key]
    for name in ("smallthinker.step_roofline", "smallthinker.rows_per_slot",
                 "smallthinker.attention_roofline",
                 "smallthinker.window_rows_share_pct"):
        assert read(name, bare) is None
    # no admission in the traced seconds, no kernel time: left out
    idle = _traced_run()
    idle["window"]["requests"].pop()
    idle["trace"]["devices"][0]["op_seconds"].pop(
        "flash_attention bf16[28,4096,128]")
    assert read("smallthinker.prefill_roofline", idle) is None
    assert read("flash_attention_roofline", idle) is None
    assert read("smallthinker.step_roofline", dict(run, trace=None)) is None
