"""The benchmark's own arithmetic, checked without a chip: percentiles and
their sample-count rule, the traffic plans as pure functions of the seed,
operation counts against hand-worked numbers, the trace reduction on the
recorded trace, and the manifest against the contract's rules of form."""

import json
import math
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, loadgen, stats  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
MANIFEST = harness.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- stats ------------------------------------------------------------------
def test_percentile_interpolates_like_numpy():
    import numpy as np

    values = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.3]
    for q in (5, 50, 95):
        assert stats.percentile(values, q) == pytest.approx(
            float(np.percentile(values, q)))


@pytest.mark.parametrize("q,n_needed", [(95.0, 200), (90.0, 100),
                                        (99.0, 1000)])
def test_tail_needs_ten_samples_beyond_it(q, n_needed):
    with pytest.raises(stats.TooFewSamples):
        stats.tail(list(range(n_needed - 1)), q)
    assert stats.tail(list(range(n_needed)), q) == pytest.approx(
        (n_needed - 1) * q / 100.0)


def test_spread_is_the_quartile_distance_over_the_median():
    import statistics

    values = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx(
        (q3 - q1) / statistics.median(values))


# -- traffic ----------------------------------------------------------------
SATURATE = harness.load_json(os.path.join(BENCH, "traffic",
                                          "saturate-closed.json"))
CONFIG = {"vocab_size": 50257}


def _closed_plan(seed):
    return harness.find("generators", "closed_loop").plan(
        SATURATE, seed, 30, CONFIG)


def test_closed_loop_plan_is_a_pure_function_of_the_seed():
    a, b, c = _closed_plan(7), _closed_plan(7), _closed_plan(8)
    same = [(r.max_new, r.prompt.tolist()) for r in a]
    assert same == [(r.max_new, r.prompt.tolist()) for r in b]
    assert same != [(r.max_new, r.prompt.tolist()) for r in c]


def test_every_seed_offers_the_same_work_in_another_order():
    a, b = _closed_plan(2 ** 31 + 5), _closed_plan(11)
    assert len(a) == len(b) == SATURATE["request_set"]
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.max_new for r in a] != [r.max_new for r in b]


def test_requests_keep_to_the_traffic_files_limits():
    p, o = SATURATE["prompt_tokens"], SATURATE["output_tokens"]
    reqs = _closed_plan(3)
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(o["min"] <= r.max_new <= o["max"] for r in reqs)
    assert all(len(r.prompt) + r.max_new <= 1024 for r in reqs)
    assert all(0 <= int(r.prompt.max()) < 50257 for r in reqs)


def test_token_gaps_are_those_that_closed_inside_the_window():
    itl = harness.find("end_to_end", "itl_p95_ms")
    r = loadgen.Request(0, [1, 2], 4)
    r.token_times = [9.5, 10.25, 10.5, 20.5]
    # 9.5 -> 10.25 closes inside [10, 20]; 10.5 -> 20.5 closes after it
    assert itl.gaps_ms({"t0": 10.0, "t_end": 20.0, "requests": [r]}) == \
        pytest.approx([750.0, 250.0])


def test_lognormal_quantiles_have_the_stated_median():
    q = loadgen.lognormal_quantiles(1001, 128, 0.9, 16, 768)
    assert q[500] == 128 and q[0] == 16 and q[-1] == 768


# -- opcount ----------------------------------------------------------------
def test_resnet50_forward_is_about_4_1_g_multiply_adds():
    op = harness.find("opcount", "module_fit")
    config = harness.load_json(os.path.join(BENCH, "configs",
                                            "resnet50.json"))
    macs = op.forward_macs_per_image(config)
    assert macs["conv0"] == 112 * 112 * 64 * 3 * 49
    assert macs["fc1"] == 2048 * 1000
    assert macs["stage1_unit1_conv2"] == 56 * 56 * 64 * 64 * 9
    total = sum(macs.values())
    assert 4.0e9 < total < 4.2e9
    assert op.train_flops_per_image(config) == \
        2 * (3 * total - macs["conv0"])


def test_gpt2_large_is_838_m_parameters_and_377_mb_a_slot():
    op = harness.find("opcount", "decode_engine")
    config = harness.load_json(os.path.join(BENCH, "configs",
                                            "gpt2-large.json"))
    p = op.parameters(config)
    assert p["blocks"] == 36 * (1280 * 3840 + 1280 * 1280
                                + 2 * 1280 * 5120 + 2 * 1280)
    assert p["embed"] == p["head"] == 50304 * 1280
    assert round(sum(p.values()) / 1e6) == 838
    assert op.cache_bytes_per_slot(config) == 2 * 36 * 1024 * 1280 * 4
    assert op.cache_bytes_per_slot(config) == \
        config["slots_analysis"]["one_slot_cache_bytes"]
    # a step reads the blocks and the head once, and only live K and V
    assert op.step_bytes(config, 0) == 4 * (p["blocks"] + p["head"] + 1280)
    assert op.step_bytes(config, 100) - op.step_bytes(config, 0) == \
        2 * 36 * 100 * 1280 * 4


# -- trace reduction --------------------------------------------------------
def test_trace_reduce_on_the_recorded_trace():
    from benchmark import trace_reduce

    r = trace_reduce.reduce(os.path.join(
        BENCH, "trace_sample", "decode_two_layer.xplane.pb"))
    expected = harness.load_json(os.path.join(
        BENCH, "trace_sample", "decode_two_layer.expected.json"))
    (d,) = r["devices"]
    assert r["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert d["span_s"] == pytest.approx(expected["span_s"], rel=1e-9)
    launches = {}
    for name, _start, _dur in d["modules"]:
        launches[name] = launches.get(name, 0) + 1
    assert launches == expected["launches"]
    assert [n for n, _ in r["breakdown"]["device_ops"][:4]] == \
        expected["top_ops"]
    assert sum(d["op_seconds"].values()) == pytest.approx(d["busy_s"],
                                                          rel=1e-6)
    assert {n for n, _s, _d in r["harness_spans"]} == \
        {"bench.sending", "bench.waiting"}
    named = dict(r["breakdown"]["idle_gaps"])
    assert all(name.startswith("bench.") or name == trace_reduce.UNNAMED
               for name in named)
    # the ten entries kept hold most of the idle time, and no more than all
    idle = d["span_s"] - d["busy_s"]
    assert 0.9 * idle < sum(named.values()) <= idle * (1 + 1e-9)


def test_union_self_time_and_names():
    from benchmark import trace_reduce as tr

    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    # a while op of 10 ns holding two body ops of 3 ns keeps 4 ns itself
    t = tr.self_times([("%while.1 = w", 0, 10), ("%fusion.2 = f", 1, 3),
                       ("%fusion.7 = f", 5, 3)])
    assert t == {"while": pytest.approx(4e-9), "fusion": pytest.approx(6e-9)}
    hlo = "%copy-start.17 = (f32[2,128]{1,0:T(2,128)S(1)}, u32[]) copy-start(x)"
    assert tr.op_kind(hlo) == "copy-start"
    assert tr.op_group(hlo) == "copy-start f32[2,128]"
    assert tr.op_group("%fusion.3 = bf16[8]{0} fusion(y)") == \
        "fusion bf16[8]"
    assert tr.module_name("jit_step(11937236725742203718)") == "jit_step"


# -- the manifest -----------------------------------------------------------
def test_names_and_units_use_only_the_permitted_characters():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            assert NAME.match(entry["name"]), entry["name"]
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry
                assert entry["better"] in ("lower", "higher")
    for cell in MANIFEST["workloads"]:
        assert NAME.match(cell["traffic"]) and len(cell["why"]) <= 200
        assert "\n" not in cell["why"] and cell["chips"] in (1, 4)
    assert len(json.dumps(MANIFEST)) < 64 * 1024


def test_every_name_resolves_to_a_file_of_its_own():
    for cell in MANIFEST["workloads"]:
        _cell, config, traffic = harness.resolve_cell(MANIFEST,
                                                      cell["name"])
        harness.find("families", config["family"])
        harness.find("generators", traffic["kind"])
        for sub in ("reference", "opcount"):
            harness.find(sub, config["family"])
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    cells = {c["name"] for c in MANIFEST["workloads"]}
    assert "setup_s" in e2e
    for m in MANIFEST["end_to_end"]:
        assert callable(harness.find("end_to_end", m["name"]).compute)
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
        assert set(m.get("workloads", ())) <= cells
    for m in MANIFEST["per_layer"]:
        assert callable(harness.find("layer_metrics", m["name"]).read)
        assert m["moves"] in e2e and "bound" not in m
        movers = {x["name"]: x for x in MANIFEST["end_to_end"]}[m["moves"]]
        for w in m.get("workloads", cells):
            assert w in movers.get("workloads", cells), (m["name"], w)
    four = [c for c in MANIFEST["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for cell in cells:
        assert len(harness.metrics_of(MANIFEST, "end_to_end", cell)) >= 2
        assert harness.metrics_of(MANIFEST, "per_layer", cell)


def test_the_harness_holds_no_cell_and_no_configuration_by_name():
    names = {c["name"] for c in MANIFEST["workloads"]} \
        | {c["name"] for c in MANIFEST["configs"]} \
        | {c["traffic"] for c in MANIFEST["workloads"]}
    for source in ("run.py", "harness.py"):
        with open(os.path.join(BENCH, source)) as f:
            text = f.read()
        for name in names:
            assert name not in text, (source, name)


def test_an_unknown_device_kind_is_an_error_not_a_default():
    assert harness.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("TPU v9", "_source", "cpu"):
        with pytest.raises(KeyError):
            harness.peaks_of(kind)


def test_config_limits_are_finite_numbers():
    for entry in MANIFEST["configs"]:
        config = harness.load_json(os.path.join(ROOT, entry["file"]))
        for key, value in config["limits"].items():
            assert math.isfinite(value) and value >= 0, key
