"""The decode tier through the executable store (``compile_cache
.stored_program``, wired in ``DecodeEngine._instrument``): a second engine in
a fresh process loads its step and every bucket and traces none of them,
serves the tokens the first served from the same seed, and reports the counts
a trace takes (``ops.kernel_path``), ``xla.compile.count`` and the warm-up
manifest's entries as the first did."""

import numpy as np

from test_executable_store import _child, _result

_ENGINE = r"""
import json, sys
import jax.numpy as jnp
import numpy as np
import mxnet_tpu
from mxnet_tpu import compile_cache as cc, serving, telemetry
from mxnet_tpu.models import smallthinker as st

telemetry.enable()
cfg = st.SmallThinkerConfig(
    vocab=96, embed=64, heads=6, kv_heads=2, head_dim=16, layers=4,
    rope_layout=(0, 1, 1, 1), window_layout=(0, 1, 1, 1), expert_ffn=32,
    num_experts=16, top_k=3, first_expert=0, experts_held=16, window=8,
    rope_theta=1.5e6, eps=1e-6, max_len=64, eos_id=96)
params = st.init_params(cfg, seed=int(sys.argv[1]), dtype=jnp.float32)
engine = serving.DecodeEngine(st.SmallThinker(cfg, jnp.float32), params,
                              slots=3, prefill_buckets=(8, 32), name="st")
rs = np.random.RandomState(3)
prompts = [rs.randint(0, cfg.vocab, n).astype(np.int32)
           for n in (5, 8, 21, 3)]
sessions = [engine.submit(p, max_new_tokens=12, temperature=t, seed=11)
            for p, t in zip(prompts, (0.0, 0.0, 0.7, 0.0))]
tokens = [[int(t) for t in s.result(120)] for s in sessions]
counted = engine.model_counters()
warm = engine.warmup_entries
engine.close()
stats = cc.stats()
counters = telemetry.snapshot()["counters"]
print("ENGINE " + json.dumps({
    "tokens": tokens, "rows": int(counted["rows"]),
    "kernel_path": counters.get("ops.kernel_path"),
    "compile_count": counters.get("xla.compile.count"),
    "builds_recorded": counters.get("compile_cache.builds_recorded"),
    "manifest": [e for e in cc.records() if e["exec"] == "serving:st"],
    "warm": warm,
    "phases": [p[0] for p in cc.phases()
               if (p[1] or "") in ("step", "prefill", "jit(step)",
                                   "jit(prefill)")],
    "stats": {k: stats[k] for k in ("store_hits", "store_misses",
                                    "store_refused")}}))
"""


def _engine(cache, seed=7):
    return _result(_child(_ENGINE, cache, str(seed), wait=False),
                   marker="ENGINE ")


def _without_seconds(entries):
    return [{k: v for k, v in e.items() if k != "compile_seconds"}
            for e in entries]


def test_a_second_engine_loads_its_step_and_every_bucket(tmp_path):
    miss = _engine(tmp_path)
    assert miss["stats"] == {"store_hits": 0, "store_misses": 3,
                             "store_refused": {}}
    # the step and two buckets: each traced, lowered once and compiled (a
    # hit in the trace cache leaves a record too: the manifest's and the
    # store's own ``lower`` of the same call)
    assert miss["phases"].count("compile") == 3
    assert miss["phases"].count("lower") == 3
    assert miss["phases"].count("trace") >= 3
    assert miss["kernel_path"] and len(miss["manifest"]) == 3
    assert all(e["fingerprint"] for e in miss["manifest"])

    hit = _engine(tmp_path)
    assert hit["stats"] == {"store_hits": 3, "store_misses": 0,
                            "store_refused": {}}
    assert hit["phases"] == ["load"] * 3       # nothing traced or lowered
    assert hit["tokens"] == miss["tokens"] and hit["rows"] == miss["rows"]
    assert all(len(t) == 12 for t in hit["tokens"])
    # what a trace counts and what the hook records, as the miss run had it
    assert hit["kernel_path"] == miss["kernel_path"]
    assert hit["compile_count"] == miss["compile_count"] == {
        "kind=decode_prefill": 2, "kind=decode_step": 1}
    assert hit["builds_recorded"] == miss["builds_recorded"]
    assert hit["manifest"] == miss["manifest"]
    assert _without_seconds(hit["warm"]) == _without_seconds(miss["warm"])

    # other weights are other arguments of the same programs
    other = _engine(tmp_path, seed=8)
    assert other["stats"]["store_hits"] == 3
    assert other["tokens"] != hit["tokens"]
    assert np.asarray(other["tokens"]).shape == (4, 12)


_POOL = r"""
import json
import mxnet_tpu
from mxnet_tpu import compile_cache as cc
from mxnet_tpu.models import transformer_lm as tlm
from mxnet_tpu.serving import lm_pool

cfg = tlm.LMConfig(32, 16, 2, 2, 32, 32, eos_id=32)
pool = lm_pool(cfg, tlm.init_params(cfg, seed=3), n_replicas=2, name="lm",
               engine_opts={"slots": 4, "prefill_buckets": (4, 8)})
sessions = [pool.generate([5, 7, 9, 2], max_new_tokens=6) for _ in range(4)]
tokens = [[int(t) for t in s.result(60)] for s in sessions]
pool.close()
stats = cc.stats()
print("POOL " + json.dumps({"tokens": tokens, "stats": {
    k: stats[k] for k in ("store_hits", "store_misses", "store_refused")}}))
"""


def test_two_replicas_keep_an_entry_a_device(tmp_path):
    """The key holds the device: each replica of a pool stores and loads
    the programs compiled for its own."""
    first = _result(_child(_POOL, tmp_path, wait=False), marker="POOL ")
    assert first["stats"] == {"store_hits": 0, "store_misses": 6,
                              "store_refused": {}}
    second = _result(_child(_POOL, tmp_path, wait=False), marker="POOL ")
    assert second["stats"] == {"store_hits": 6, "store_misses": 0,
                               "store_refused": {}}
    assert second["tokens"] == first["tokens"]
