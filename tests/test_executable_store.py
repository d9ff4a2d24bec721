"""The executable store (``compile_cache.stored_program``, docs/how_to/perf.md
"Compile once"): a program's compiled executable kept beside the persistent
cache's entries under a key made without tracing it, so a start that finds
the entry neither traces nor lowers the program.  A fresh process loads what
another stored, to the bit and with the donation; every part of the key moves
it; a torn entry is a miss that is written again; two writers leave one whole
entry; the hook's ``lower`` still lowers after a hit; the size bound evicts
the store's entries with the cache's; what the key cannot see into is
refused by name."""

import functools
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from mxnet_tpu import compile_cache, perfdebug, telemetry

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    telemetry.reset()
    telemetry.enable()
    compile_cache.reset_records()
    # a store hit is a ``load`` with no ``lower``: leave the process's account
    # of its programs as it was (tests/benchmark/test_bench_setup_account.py
    # reads all of it, in whichever worker runs both files)
    phases = compile_cache.phases()
    yield
    with compile_cache._lock:
        compile_cache._phases.restore(phases)
    if compile_cache.enabled():
        compile_cache.disable()
    compile_cache.reset_records()
    telemetry.disable()
    telemetry.reset()


# -- one process stores, a fresh one loads --------------------------------------
_CHILD = r"""
import json, sys
import numpy as np
import jax
import mxnet_tpu
from mxnet_tpu import compile_cache as cc, perfdebug


def stored_fn(w, state, k):
    a, b = state
    return (a * k + w["w"].sum(), b + 1), (a * a).sum()


dev = jax.devices()[0]
store = cc.stored_program("test", ("stored_fn", 8), {"donate": (1,)}, dev)


def hook(f, args, kwargs, dt):
    store.save(f, args, kwargs)


fn = perfdebug.first_call_hook(jax.jit(stored_fn, donate_argnums=(1,)), hook,
                               store=store)
w = {"w": np.arange(16, dtype=np.float32).reshape(4, 4) / 7}
state = jax.device_put((np.linspace(0, 1, 8, dtype=np.float32),
                        np.arange(8, dtype=np.int32)), dev)
if sys.argv[1] == "sleep":          # two writers: start together
    import time
    time.sleep(max(0.0, float(sys.argv[2]) - time.time()))
state1, s1 = fn(w, state, np.float32(1.5))
donated = state[0].is_deleted()
state2, s2 = fn(w, state1, np.float32(0.5))
try:
    fn(w, (np.zeros(9, np.float32), np.zeros(9, np.int32)), np.float32(1))
    other_shape = "accepted"
except TypeError:
    other_shape = "TypeError"
stats = cc.stats()
print("CHILD " + json.dumps({
    "hit": store.hit, "donated": donated, "other_shape": other_shape,
    "out": [np.asarray(x).tobytes().hex() for x in (*state2, s1, s2)],
    "phases": [p[0] for p in cc.phases() if "stored_fn" in (p[1] or "")],
    "stats": {k: stats[k] for k in ("store_hits", "store_misses",
                                    "store_bytes", "store_refused",
                                    "hits", "misses")}}))
"""


def _child(script, cache, *argv, wait=True):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_ENABLE_COMPILATION_CACHE"}
    env.update(PYTHONPATH=_ROOT, JAX_COMPILATION_CACHE_DIR=str(cache))
    proc = subprocess.Popen([sys.executable, "-c", script, *argv], env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)
    return _result(proc) if wait else proc


def _result(proc, marker="CHILD "):
    out, err = proc.communicate(timeout=240)
    assert proc.returncode == 0, err[-3000:]
    (line,) = [ln for ln in out.splitlines() if ln.startswith(marker)]
    return json.loads(line[len(marker):])


def test_a_fresh_process_loads_what_another_stored(tmp_path):
    first = _child(_CHILD, tmp_path, "run")
    assert not first["hit"] and first["donated"]
    assert first["stats"]["store_misses"] == 1
    assert first["stats"]["store_bytes"] > 0
    # traced, lowered and compiled: what a start does without the store
    assert {"trace", "lower", "compile"} <= set(first["phases"])
    assert first["other_shape"] == "accepted"       # the jit retraces

    second = _child(_CHILD, tmp_path, "run")
    assert second["hit"] and second["donated"]
    # the program's one record is a load: neither traced nor lowered
    assert second["phases"] == ["load"]
    assert second["stats"]["store_hits"] == 1
    assert second["stats"]["store_misses"] == 0
    assert second["stats"]["store_refused"] == {}
    assert second["out"] == first["out"]            # to the bit
    # a loaded executable never retraces for another shape
    assert second["other_shape"] == "TypeError"


def test_two_writers_of_one_key_leave_one_whole_entry(tmp_path):
    import time

    start = str(time.time() + 8)
    writers = [_child(_CHILD, tmp_path, "sleep", start, wait=False)
               for _ in range(2)]
    results = [_result(w) for w in writers]
    assert all(not r["hit"] and r["stats"]["store_refused"] == {}
               for r in results)
    entries = [f for f in os.listdir(tmp_path) if f.endswith("-exec")]
    assert len(entries) == 1
    assert not [f for f in os.listdir(tmp_path) if ".tmp-" in f]
    with open(tmp_path / entries[0], "rb") as f:
        assert compile_cache._store_decode(f.read()) is not None
    third = _child(_CHILD, tmp_path, "run")
    assert third["hit"] and third["out"] == results[0]["out"]


# -- in one process --------------------------------------------------------------
def _program(scale=2.0, name="test", statics=None):
    """A fresh jit of a fresh function behind the hook, as the engine wires
    it: the store makes the first call, the hook writes after a miss."""
    def doubled(w, state):
        return state * scale + w["w"].sum(), state.sum()

    store = compile_cache.stored_program(
        name, ("doubled", 4), {"scale": scale} if statics is None
        else statics, jax.devices()[0])

    def hook(f, args, kwargs, dt):
        if store is not None:
            store.save(f, args, kwargs)

    fn = perfdebug.first_call_hook(jax.jit(doubled, donate_argnums=(1,)),
                                   hook, store=store)
    return fn, store


def _args():
    return ({"w": np.ones((2, 2), np.float32)},
            jax.device_put(np.arange(4, dtype=np.float32), jax.devices()[0]))


def _records(name="doubled"):
    return [p[0] for p in compile_cache.phases() if name in (p[1] or "")]


def _entries(directory):
    return sorted(f for f in os.listdir(directory) if f.endswith("-exec"))


def test_a_torn_entry_is_a_miss_that_is_written_again(tmp_path):
    cache = tmp_path / "cc"
    compile_cache.enable(str(cache))
    fn, store = _program()
    want = [np.asarray(x) for x in fn(*_args())]
    assert not store.hit
    (entry,) = _entries(cache)
    whole = (cache / entry).read_bytes()

    for torn in (whole[:len(whole) // 2], whole[:20], b"",
                 whole[:-1] + bytes([whole[-1] ^ 1])):
        (cache / entry).write_bytes(torn)
        fn, store = _program()
        got = [np.asarray(x) for x in fn(*_args())]      # raises nothing
        assert not store.hit
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        assert (cache / entry).read_bytes() == whole or \
            compile_cache._store_decode((cache / entry).read_bytes())
    s = compile_cache.stats()
    assert s["store_misses"] == 5 and s["store_hits"] == 0
    assert s["store_refused"] == {}

    before = len(_records())
    fn, store = _program()
    got = [np.asarray(x) for x in fn(*_args())]
    assert store.hit and _records()[before:] == ["load"]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert compile_cache.stats()["store_hits"] == 1
    # the deep sweep reads the store's entries by their own digest
    assert compile_cache.verify(deep=True) == 0
    (cache / entry).write_bytes(whole[:100])
    assert compile_cache.verify(deep=True) == 1 and not _entries(cache)


def test_an_entry_whose_executable_the_cache_lost_is_a_miss(tmp_path):
    """The entry names the persistent cache's key for the executable and
    holds no copy of it: evicted there, the program compiles again."""
    cache = tmp_path / "cc"
    compile_cache.enable(str(cache))
    fn, _store = _program()
    want = [np.asarray(x) for x in fn(*_args())]
    for name in os.listdir(cache):
        if name.endswith("-cache"):
            os.unlink(cache / name)
    before = len(_records())
    fn, store = _program()
    got = [np.asarray(x) for x in fn(*_args())]
    assert not store.hit and "compile" in _records()[before:]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    fn, store = _program()
    fn(*_args())
    assert store.hit and compile_cache.stats()["store_refused"] == {}


def test_lower_on_the_hook_still_lowers_after_a_hit(tmp_path):
    """``scratch_bytes()`` of the benchmark's adapters lowers an engine's
    step through the hook after the window, hit or not."""
    compile_cache.enable(str(tmp_path / "cc"))
    fn, _store = _program()
    fn(*_args())
    fn, store = _program()
    fn(*_args())
    assert store.hit
    before = len(_records())
    sds = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), _args())
    compiled = fn.lower(*sds).compile()
    assert "lower" in _records()[before:]
    assert compiled.memory_analysis() is not None
    assert fn.trace(*sds).jaxpr is not None
    # the pinned line: a kernel's lowered text carries its call stack
    assert perfdebug._FirstCallHook.lower.__code__.co_firstlineno == 337


def test_the_store_is_off_with_the_cache_and_without_statics(tmp_path):
    assert compile_cache.stored_program("t", "k", {}, jax.devices()[0]) \
        is None                                    # the cache is off
    fn, store = _program()
    assert store is None
    out, total = fn(*_args())
    np.testing.assert_array_equal(np.asarray(out), np.arange(4) * 2.0 + 4)
    # a caller that hands no store (Module's fused update) runs the jit
    compile_cache.enable(str(tmp_path / "cc"))
    fn = compile_cache.instrument(jax.jit(lambda x: x + 1), "m", "fused")
    assert np.asarray(fn(np.float32(1))) == 2.0
    assert not _entries(tmp_path / "cc")
    s = compile_cache.stats()
    assert s["store_hits"] == s["store_misses"] == s["store_bytes"] == 0


def test_what_the_key_cannot_see_is_refused_by_name(tmp_path):
    compile_cache.enable(str(tmp_path / "cc"))
    for statics in ({"fn": lambda x: x}, {"array": np.ones(3)},
                    {"method": [].append}):
        fn, store = _program(name="closed", statics=statics)
        out, _total = fn(*_args())
        np.testing.assert_array_equal(np.asarray(out),
                                      np.arange(4) * 2.0 + 4)
        assert not store.hit
    refused = compile_cache.stats()["store_refused"]
    assert list(refused) == ["closed/('doubled', 4)"]
    assert not _entries(tmp_path / "cc")
    # an argument laid out over several devices is no single-device program
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("x",))
    spread = jax.device_put(np.arange(4, dtype=np.float32),
                            jax.sharding.NamedSharding(
                                mesh, jax.sharding.PartitionSpec("x")))
    fn, store = _program(name="spread")
    fn({"w": np.ones((2, 2), np.float32)}, spread)
    assert "spread/('doubled', 4)" in compile_cache.stats()["store_refused"]


def test_the_size_bound_evicts_the_stores_entries_too(tmp_path):
    cache = tmp_path / "cc"
    compile_cache.enable(str(cache))
    for scale in (2.0, 3.0):
        fn, _store = _program(scale=scale)
        fn(*_args())
    assert len(_entries(cache)) == 2
    s = compile_cache.stats()
    assert s["store_bytes"] == sum(
        os.path.getsize(cache / e) for e in _entries(cache))
    assert s["bytes"] > s["store_bytes"]       # the cache's own entries
    assert compile_cache.gc(max_bytes=1) >= 2
    assert not _entries(cache)
    assert compile_cache.stats()["store_bytes"] == 0


def test_what_a_trace_counted_and_recorded_comes_back_with_a_hit(tmp_path):
    compile_cache.enable(str(tmp_path / "cc"))

    def once(name):
        def counted(x):
            telemetry.inc("ops.kernel_path", op="probe", path="xla",
                          reason="not_tpu")
            return x * 3

        store = compile_cache.stored_program(name, "counted", {},
                                             jax.devices()[0])

        def hook(f, args, kwargs, dt):
            if not store.hit:
                compile_cache.note_build(name, "counted", f.lower, args,
                                         kwargs, dt)
                store.save(f, args, kwargs)

        fn = perfdebug.first_call_hook(jax.jit(counted), hook, store=store)
        fn(np.arange(3, dtype=np.float32))
        return store.hit

    assert not once("prog")
    miss = (telemetry.snapshot()["counters"]["ops.kernel_path"],
            compile_cache.records())
    assert len(miss[1]) == 1 and miss[1][0]["fingerprint"]
    telemetry.reset()
    compile_cache.reset_records()
    with compile_cache.recording_scope() as scope:
        assert once("prog")
    assert telemetry.snapshot()["counters"]["ops.kernel_path"] == miss[0]
    assert compile_cache.records() == miss[1] == scope.entries
    assert telemetry.counter_total("compile_cache.builds_recorded") == 1


# -- the key ----------------------------------------------------------------------
def _key(tmp_path, **changed):
    """The key of one call, one thing about it changed."""
    source = tmp_path / "keyed_model.py"
    if not source.exists():
        source.write_text("class Model:\n    def __init__(self, width):\n"
                          "        self.width = width\n")
    sys.modules.pop("keyed_model", None)     # this test's file, not the last's
    sys.path.insert(0, str(tmp_path))
    try:
        import keyed_model
    finally:
        sys.path.remove(str(tmp_path))
    statics = {"model": keyed_model.Model(8), "slots": 4,
               "buckets": (8, 32), "dtype": np.dtype("float32"),
               "prefill": functools.partial(json.dumps, indent=1)}
    statics.update(changed.get("statics", {}))
    dev = changed.get("device", jax.devices()[0])
    args = changed.get("args", (
        {"w": np.ones((2, 2), np.float32)},
        jax.device_put(np.zeros(4, np.float32), jax.devices()[0]),
        np.int32(1)))
    return compile_cache._store_key(
        changed.get("name", "serving:lm"), changed.get("kind", ("step", 4)),
        statics, dev, args, changed.get("kwargs", {}))


_MOVES = {
    "the name": dict(name="serving:other"),
    "the build kind": dict(kind=("step", 5)),
    "a bucket": dict(kind=("prefill", 8, 4)),
    "a static's value": dict(statics={"slots": 5}),
    "a static tuple": dict(statics={"buckets": (8, 64)}),
    "a static dtype": dict(statics={"dtype": jax.numpy.bfloat16}),
    "a bound argument of a partial": dict(
        statics={"prefill": functools.partial(json.dumps, indent=2)}),
    "one more static": dict(statics={"block": 4}),
    "an argument's shape": dict(args=(
        {"w": np.ones((2, 3), np.float32)},
        np.zeros(4, np.float32), np.int32(1))),
    "an argument's dtype": dict(args=(
        {"w": np.ones((2, 2), np.float16)},
        np.zeros(4, np.float32), np.int32(1))),
    "the arguments' tree": dict(args=(
        {"v": np.ones((2, 2), np.float32)},
        np.zeros(4, np.float32), np.int32(1))),
    "a weak type": dict(args=(
        {"w": np.ones((2, 2), np.float32)}, np.zeros(4, np.float32), 1)),
    "where an argument lies": dict(args=(
        {"w": np.ones((2, 2), np.float32)},
        np.zeros(4, np.float32), np.int32(1))),
    "a keyword": dict(kwargs={"flag": np.bool_(True)}),
    "the device": dict(device=jax.devices()[1], args=(
        {"w": np.ones((2, 2), np.float32)},
        jax.device_put(np.zeros(4, np.float32), jax.devices()[1]),
        np.int32(1))),
}


@pytest.mark.parametrize("what", sorted(_MOVES))
def test_the_key_moves_with(what, tmp_path):
    compile_cache.enable(str(tmp_path / "cc"))
    assert _key(tmp_path) == _key(tmp_path)
    assert _key(tmp_path, **_MOVES[what]) != _key(tmp_path)


@pytest.mark.parametrize("what", [
    "a source file's byte", "a byte of the package's sources",
    "a flag in the environment", "an XLA flag", "a jax flag",
    "the version of jax", "the version of jaxlib", "the backend's version",
    "whether telemetry counts"])
def test_the_key_moves_with_what_lies_outside_the_call(what, tmp_path,
                                                       monkeypatch):
    compile_cache.enable(str(tmp_path / "cc"))
    before = _key(tmp_path)
    if what == "a source file's byte":
        with open(tmp_path / "keyed_model.py", "a") as f:
            f.write("#")
    elif what == "a byte of the package's sources":
        monkeypatch.setattr(compile_cache, "_package_memo", "0" * 64)
    elif what == "a flag in the environment":
        monkeypatch.setenv("MXNET_DECODE_SLOTS", "9")
    elif what == "an XLA flag":
        monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", "")
                           + " --xla_cpu_enable_fast_math=false")
    elif what == "a jax flag":
        with jax.default_matmul_precision("highest"):
            assert _key(tmp_path) != before
        return
    elif what == "the version of jax":
        monkeypatch.setattr(jax, "__version__", "0.0.1")
    elif what == "the version of jaxlib":
        import jaxlib

        monkeypatch.setattr(jaxlib, "__version__", "0.0.1")
    elif what == "the backend's version":
        class Client:
            platform_version = "another libtpu"

        class Device:
            client = Client()
            platform, device_kind, id = "cpu", "cpu", 0

        def key(*a, **kw):
            return compile_cache._store_key(
                "serving:lm", ("step", 4), {}, a[0],
                ({"w": np.ones(2, np.float32)},), {})

        assert key(Device()) != key(jax.devices()[0])
        return
    else:
        telemetry.disable()
    assert _key(tmp_path) != before


def test_an_option_jax_defines_later_moves_no_key(tmp_path):
    """A kernel's first trace imports Pallas, which defines options of its
    own; a start that loads its programs never imports it.  The key reads
    the options jax had when the cache came on, so both starts make one
    key (on the chip the second bucket of every warm start missed)."""
    from jax._src import config as jax_config

    compile_cache.enable(str(tmp_path / "cc"))
    before = _key(tmp_path)
    name = "jax_mxnet_test_option_%d" % os.getpid()
    if name not in jax.config.values:
        jax_config.bool_state(name, False, "defined after the cache came on")
    assert name in jax.config.values
    assert _key(tmp_path) == before
    with jax.default_matmul_precision("highest"):   # the others still count
        assert _key(tmp_path) != before


def test_the_weights_values_are_no_part_of_the_key(tmp_path):
    compile_cache.enable(str(tmp_path / "cc"))
    dev = jax.devices()[0]

    def args(seed):
        rs = np.random.RandomState(seed)
        return ({"w": rs.rand(2, 2).astype(np.float32)},
                jax.device_put(rs.rand(4).astype(np.float32), dev),
                np.int32(seed))

    assert _key(tmp_path, args=args(1)) == _key(tmp_path, args=args(2))


def test_the_package_digest_reads_every_source_once():
    compile_cache._package_memo = None
    digest = compile_cache._package_digest()
    assert len(digest) == 64 and compile_cache._package_memo == digest
    assert compile_cache._package_digest() is digest
