"""Compile-once infrastructure (docs/how_to/perf.md "Compile once"):
persistent-cache tier (hit/miss split, GC bound, corrupt-entry
fallback via the ``compile_cache.read`` fault point) and the AOT
warm-up manifest tier (record → save → replay with zero cold compiles
for serving reloads and fit resume)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_cache, faults, telemetry
from mxnet_tpu import serving


@pytest.fixture(autouse=True)
def _clean(tmp_path):
    """Enable telemetry + a fresh compile cache per test; disable both
    afterwards so nothing leaks into the rest of the suite."""
    telemetry.reset()
    telemetry.enable()
    faults.disarm()
    compile_cache.reset_records()
    yield
    faults.disarm()
    if compile_cache.enabled():
        compile_cache.disable()
    compile_cache.reset_records()
    telemetry.disable()
    telemetry.reset()


def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    return mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(net, num_hidden=4, name="fc2"),
        name="softmax")


def _fresh_module(net, batch=4, in_dim=6):
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (batch, in_dim))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params()
    mod.init_optimizer()
    return mod


def _batch(batch=4, in_dim=6):
    rs = np.random.RandomState(0)
    return mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(batch, in_dim).astype(np.float32))],
        label=[mx.nd.array(np.zeros(batch, np.float32))])


# -- tier 1: the persistent cache -------------------------------------------

def test_persistent_cache_hit_miss_split(tmp_path):
    """First build misses (and writes), a FRESH module's identical
    program loads from disk — and the split counters tell the two
    caches apart: fn_cache_hits is in-process reuse, persistent_* is
    the on-disk cache."""
    compile_cache.enable(str(tmp_path / "cc"))
    net = _mlp()
    b = _batch()
    m1 = _fresh_module(net)
    m1.forward_backward(b)
    m1.update()
    s = compile_cache.stats()
    assert s["misses"] > 0 and s["hits"] == 0
    assert s["entries"] > 0 and s["bytes"] > 0
    # same executor, second dispatch: in-process fn cache, not the disk
    fn_hits0 = telemetry.counter_total("xla.compile.fn_cache_hits")
    m1.forward_backward(b)
    m1.update()
    assert telemetry.counter_total("xla.compile.fn_cache_hits") > fn_hits0
    s1 = compile_cache.stats()
    assert s1["misses"] == s["misses"]  # no new compiles
    # a fresh module re-traces but must LOAD every executable from disk
    m2 = _fresh_module(net)
    m2.forward_backward(b)
    m2.update()
    s2 = compile_cache.stats()
    assert s2["hits"] > 0
    assert s2["misses"] == s1["misses"]
    assert telemetry.counter_total(
        "xla.compile.persistent_cache_hits") == s2["hits"]
    assert telemetry.counter_total(
        "xla.compile.persistent_cache_misses") == s2["misses"]


def test_corrupt_entry_falls_back_to_clean_recompile(tmp_path):
    """The ``compile_cache.read`` fault point truncates a real on-disk
    entry mid-read: the read must degrade to a recompile (a miss), the
    result must stay correct, and the rewritten entry must serve the
    next load (self-healing)."""
    compile_cache.enable(str(tmp_path / "cc"))
    net = _mlp()
    b = _batch()
    _fresh_module(net).forward_backward(b)  # populate
    s0 = compile_cache.stats()
    assert s0["misses"] > 0
    faults.arm("compile_cache.read", at=1)
    m2 = _fresh_module(net)
    m2.forward_backward(b)  # first read hits the truncated entry
    faults.disarm()
    outs = m2.get_outputs()[0].asnumpy()
    assert np.isfinite(outs).all()
    s1 = compile_cache.stats()
    assert s1["misses"] > s0["misses"]  # the torn entry recompiled
    # self-healed: a third fresh module loads everything from disk
    m3 = _fresh_module(net)
    m3.forward_backward(b)
    s2 = compile_cache.stats()
    assert s2["misses"] == s1["misses"]
    assert s2["hits"] > s1["hits"]


def test_gc_respects_size_bound(tmp_path):
    """Distinct shapes build distinct entries; gc() with a tiny bound
    evicts oldest-read entries until under it and counts evictions."""
    compile_cache.enable(str(tmp_path / "cc"))
    net = _mlp()
    for batch in (2, 3, 4, 5):
        m = mx.mod.Module(net, context=mx.cpu())
        m.bind(data_shapes=[("data", (batch, 6))],
               label_shapes=[("softmax_label", (batch,))],
               for_training=False)
        m.init_params()
        m.forward(_batch(batch), is_train=False)
    total = compile_cache.cache_size_bytes()
    n = compile_cache.cache_entries()
    assert n >= 4
    bound = total // 2
    evicted = compile_cache.gc(max_bytes=bound)
    assert evicted > 0
    assert compile_cache.cache_size_bytes() <= bound
    assert compile_cache.cache_entries() == n - evicted
    assert compile_cache.stats()["evictions"] == evicted
    assert telemetry.counter_total(
        "xla.compile.persistent_cache_evictions") == evicted


def test_verify_sweeps_truncated_entries(tmp_path):
    compile_cache.enable(str(tmp_path / "cc"))
    _fresh_module(_mlp()).forward_backward(_batch())
    entries = [f for f in os.listdir(compile_cache.cache_dir())
               if f.endswith("-cache")]
    assert entries
    victim = os.path.join(compile_cache.cache_dir(), entries[0])
    with open(victim, "r+b") as f:
        f.truncate(0)
    dropped = compile_cache.verify(deep=True)
    assert dropped >= 1
    assert not os.path.exists(victim)
    assert compile_cache.stats()["corrupt_dropped"] >= 1


# -- tier 2: warm-up manifests ----------------------------------------------

def test_manifest_roundtrip_and_corrupt_manifest(tmp_path):
    compile_cache.enable(str(tmp_path / "cc"))
    _fresh_module(_mlp()).forward_backward(_batch())
    recs = compile_cache.records()
    assert any(r["kind_name"] == "train" for r in recs)
    for r in recs:
        assert r["fingerprint"] and r["sig"]["args"]
    path = str(tmp_path / "warmup.json")
    compile_cache.save_manifest(path, model="t")
    man = compile_cache.load_manifest(path)
    assert man["version"] == compile_cache.MANIFEST_VERSION
    assert len(man["entries"]) == len(recs)
    # a torn manifest degrades to None (lazy compilation), never raises
    with open(path, "w") as f:
        f.write(json.dumps({"version": 99})[:-4])
    assert compile_cache.load_manifest(path) is None
    assert telemetry.counter_total("compile_cache.manifest.corrupt") == 1


def test_fit_resume_replays_manifest_with_zero_cold_compiles(tmp_path):
    """The acceptance pin: a ``fit(resume='auto')`` restart replays the
    warm-up manifest (AOT pre-builds BEFORE the loop) and the whole
    restarted fit — replay included — performs 0 cold XLA compiles."""
    compile_cache.enable(str(tmp_path / "cc"))
    net = _mlp()
    rs = np.random.RandomState(0)
    x = rs.rand(16, 6).astype(np.float32)
    y = rs.randint(0, 4, 16).astype(np.float32)
    prefix = str(tmp_path / "ckpt" / "run")
    os.makedirs(os.path.dirname(prefix), exist_ok=True)

    def one_fit():
        train = mx.io.NDArrayIter(x, y, batch_size=4,
                                  last_batch_handle="discard")
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.fit(train, optimizer="sgd",
                optimizer_params={"learning_rate": 0.05},
                num_epoch=1, checkpoint_prefix=prefix, resume="auto")

    one_fit()  # cold: compiles + writes cache + manifest
    assert os.path.exists(compile_cache.manifest_path(prefix))
    s0 = compile_cache.stats()
    assert s0["misses"] > 0
    one_fit()  # restart: manifest replay + all persistent-cache loads
    s1 = compile_cache.stats()
    assert s1["misses"] == s0["misses"], \
        "resume='auto' restart performed cold XLA compiles"
    assert s1["hits"] > s0["hits"]
    assert telemetry.counter_total("compile_cache.manifest.replays") == 1
    assert telemetry.counter_total(
        "compile_cache.manifest.replay_errors") == 0


def _publish(tmp_path, net):
    rs = np.random.RandomState(0)
    params = {"fc1_weight": (rs.randn(8, 6) * 0.1).astype(np.float32),
              "fc1_bias": np.zeros(8, np.float32),
              "fc2_weight": (rs.randn(4, 8) * 0.1).astype(np.float32),
              "fc2_bias": np.zeros(4, np.float32)}
    import io as _io

    buf = _io.BytesIO()
    np.savez(buf, **params)
    model_dir = str(tmp_path / "model")
    serving.save_model(model_dir, net, buf.getvalue(), (6,),
                       buckets=(1, 4))
    return model_dir


def test_registry_reload_zero_cold_compiles(tmp_path):
    """Serving acceptance pin: loading a previously-published model a
    second time warms every bucket purely from the persistent cache —
    the per-model cold-compile gauge reads 0 — and the registry
    persists a warm-up manifest next to the publish."""
    compile_cache.enable(str(tmp_path / "cc"))
    model_dir = _publish(tmp_path, _mlp())
    reg = serving.ModelRegistry()
    reg.load_dir(model_dir)
    reg.close()
    wu = os.path.join(model_dir, serving.registry.WARMUP_MANIFEST)
    assert os.path.exists(wu)
    man = compile_cache.load_manifest(wu)
    assert len(man["entries"]) == 2  # one predict program per bucket
    s0 = compile_cache.stats()
    assert s0["misses"] > 0
    reg2 = serving.ModelRegistry()
    model = reg2.load_dir(model_dir)
    s1 = compile_cache.stats()
    assert s1["misses"] == s0["misses"], \
        "registry reload performed cold XLA compiles"
    assert s1["hits"] >= s0["hits"] + 2
    assert telemetry.gauge_value("serving.warmup.cold_compiles",
                                 model=model.name) == 0
    assert model.predict(np.zeros(6, np.float32)).shape == (4,)
    reg2.close()


def test_reload_fingerprint_change_is_flagged(tmp_path):
    """A reload whose program lowers to different HLO than the warm-up
    manifest recorded raises the invalidation event instead of silently
    re-warming."""
    compile_cache.enable(str(tmp_path / "cc"))
    model_dir = _publish(tmp_path, _mlp())
    reg = serving.ModelRegistry()
    reg.load_dir(model_dir)
    reg.close()
    wu = os.path.join(model_dir, serving.registry.WARMUP_MANIFEST)
    man = compile_cache.load_manifest(wu)
    for e in man["entries"]:
        e["fingerprint"] = "0" * 16
    compile_cache.save_manifest(wu, entries=man["entries"], model="m")
    reg2 = serving.ModelRegistry()
    reg2.load_dir(model_dir)
    reg2.close()
    assert telemetry.counter_total(
        "compile_cache.manifest.fingerprint_changes") >= 2


def test_disabled_is_inert(tmp_path):
    """With the cache off: no recording, no counters, instrument() is
    the identity."""
    assert not compile_cache.enabled()
    m = _fresh_module(_mlp())
    m.forward_backward(_batch())
    m.update()
    assert compile_cache.records() == []
    assert compile_cache.stats()["hits"] == 0
    fn = object()
    assert compile_cache.instrument(fn, "x", "y") is fn


# -- where the cache lives ----------------------------------------------------

_DIR_PROBE = r"""
import json, sys
import jax
import mxnet_tpu as mx
from mxnet_tpu import compile_cache as cc
from mxnet_tpu.base import MXNetError

out = {"enabled": cc.enabled(), "dir": cc.cache_dir(),
       "jax_dir": jax.config.jax_compilation_cache_dir}
if sys.argv[2] == "compile":
    jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(7.0)).block_until_ready()
    out["entries"] = cc.cache_entries()
    try:
        cc.enable(sys.argv[1])
        out["other_dir"] = "accepted"
    except MXNetError:
        out["other_dir"] = "refused"
    out["jax_dir_after"] = jax.config.jax_compilation_cache_dir
print("PROBE " + json.dumps(out))
"""

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dir_probe(tmp_path, compile_one=False, **env):
    """Import the package in a fresh process (the session's own cache
    setting stays untouched) and report where its cache landed."""
    full = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR",
                         "JAX_ENABLE_COMPILATION_CACHE")}
    full.update(env, PYTHONPATH=_ROOT)
    proc = subprocess.run(
        [sys.executable, "-c", _DIR_PROBE, str(tmp_path / "other"),
         "compile" if compile_one else "import"],
        env=full, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    (line,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("PROBE ")]
    return json.loads(line[len("PROBE "):])


def test_jax_variable_places_the_cache_and_is_never_overwritten(tmp_path):
    placed = str(tmp_path / "placed")
    out = _dir_probe(tmp_path, compile_one=True,
                     JAX_COMPILATION_CACHE_DIR=placed)
    assert out["enabled"] and out["dir"] == placed
    assert out["jax_dir"] == out["jax_dir_after"] == placed
    assert out["entries"] > 0 and os.listdir(placed)   # entries there...
    assert not (tmp_path / "other").exists()           # ...and nowhere else
    assert out["other_dir"] == "refused"


def test_cache_is_on_by_default_at_one_fixed_path_in_the_checkout(tmp_path):
    """Nothing set: on, at ``<checkout>/.jax_cache`` — a path with no
    temporary, pid or clock part, the same in every process."""
    out = _dir_probe(tmp_path)
    assert out["enabled"]
    assert out["dir"] == out["jax_dir"] == os.path.join(_ROOT, ".jax_cache")
    assert compile_cache.DEFAULT_DIR == out["dir"]


def test_jax_switch_keeps_the_default_cache_off(tmp_path):
    out = _dir_probe(tmp_path, JAX_ENABLE_COMPILATION_CACHE="false")
    assert not out["enabled"] and out["dir"] is None
