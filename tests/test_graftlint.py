"""graftlint framework tests: per-pass fixtures (true positives,
near-miss negatives, suppressions), baseline add/expire + the waiver
guard, the ``--changed`` diff-scoped lane, and the seeded-mutation
checks that pin the framework-code defect classes — removing a lock,
adding ``.item()`` to the fit loop, reusing a donated buffer, swapping
a collective's axis, feeding ``time.time()`` to a psum, overlong
PartitionSpecs, dropping a state_dict key — as *caught*."""

import io
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from ci.graftlint import RunContext, by_id, run_pass  # noqa: E402
from ci.graftlint import baseline as glbaseline  # noqa: E402
from ci.graftlint import runner as glrunner  # noqa: E402


def run_on(pass_id, code, tmp_path, name="snippet.py", env_doc=None):
    """Run one pass over a snippet; returns the PassResult."""
    p = tmp_path / name
    p.write_text(textwrap.dedent(code))
    kwargs = {}
    if env_doc is not None:
        doc = tmp_path / "env_var.md"
        doc.write_text(env_doc)
        kwargs["env_doc_path"] = doc
    ctx = RunContext(roots=[p], **kwargs)
    return run_pass(by_id(pass_id)(), ctx)


def active(result):
    return result.active


def codes(result):
    return [f.code for f in result.active]


# -- migrated passes: exit-identical behavior --------------------------------

def test_bare_except_tp_and_negative(tmp_path):
    res = run_on("bare-except", """
        def f():
            try:
                pass
            except:
                raise
            try:
                pass
            except Exception:
                pass
            try:
                pass
            except ValueError:
                pass
        """, tmp_path)
    assert sorted(codes(res)) == ["bare-except", "swallow"]


def test_bare_except_suppressions(tmp_path):
    res = run_on("bare-except", """
        try:
            pass
        except Exception:  # noqa - interpreter shutdown
            pass
        try:
            pass
        except BaseException:  # lint: ok[bare-except] shutdown path
            pass
        """, tmp_path)
    assert not active(res)
    assert len(res.suppressed) == 2


def test_print_tp_negative_and_noqa(tmp_path):
    res = run_on("print", """
        s = "print(not a call)"
        print("leak")
        obj.print("method, not builtin")
        print("cli")  # noqa: CLI path
        """, tmp_path)
    assert len(active(res)) == 1
    assert active(res)[0].line == 3


def test_env_docs_tp_and_documented(tmp_path):
    res = run_on("env-docs", """
        import os
        a = os.environ.get("MXNET_GRAFTLINT_DOCUMENTED")
        b = os.environ.get("MXNET_GRAFTLINT_MISSING")
        """, tmp_path, env_doc="## MXNET_GRAFTLINT_DOCUMENTED\nyes\n")
    assert [f.detail for f in active(res)] == ["MXNET_GRAFTLINT_MISSING"]


def test_host_sync_tp_tag_and_item(tmp_path):
    res = run_on("host-sync", """
        import numpy as np
        def f(a):
            v = a.asnumpy()
            w = np.asarray(a)
            x = a.item()
            y = a.tolist()
            ok = np.asarray([1.0])  # host-sync: ok - host literal
            ok2 = a.item()  # lint: ok[host-sync] the read IS the sync point
            return v, w, x, y, ok, ok2
        """, tmp_path)
    assert sorted(f.detail for f in active(res)) == \
        [".asnumpy()", ".item()", ".tolist()", "np.asarray(...)"]
    assert len(res.suppressed) == 2


def test_signal_restore_tp_and_balanced(tmp_path):
    res = run_on("signal-restore", """
        import signal
        def bad():
            signal.signal(signal.SIGTERM, None)
        def good():
            old = signal.signal(signal.SIGTERM, None)
            try:
                pass
            finally:
                signal.signal(signal.SIGTERM, old)
        """, tmp_path)
    assert codes(res) == ["unrestored-install"]
    assert active(res)[0].line == 4


def test_signal_restore_above_line_suppression_balances(tmp_path):
    """A comment-line-above suppression must subtract its install from
    the install/restore balance — not just hide its own report — or the
    function's OTHER, legitimately-restored install gets flagged."""
    res = run_on("signal-restore", """
        import signal
        def f():
            # lint: ok[signal-restore] process-lifetime handler by contract
            signal.signal(signal.SIGUSR1, None)
            old = signal.signal(signal.SIGTERM, None)
            try:
                pass
            finally:
                signal.signal(signal.SIGTERM, old)
        """, tmp_path)
    assert not active(res), [f.message for f in active(res)]


def test_signal_restore_module_level(tmp_path):
    res = run_on("signal-restore", """
        import signal
        signal.signal(signal.SIGTERM, None)
        """, tmp_path)
    assert codes(res) == ["module-level-install"]


# -- tracer-purity -----------------------------------------------------------

def test_tracer_purity_host_coercions(tmp_path):
    res = run_on("tracer-purity", """
        import jax
        import jax.numpy as jnp
        def f(x):
            a = float(x)
            b = x.item()
            c = jnp.sum(x)
            d = int(c)
            return a + b + d
        g = jax.jit(f)
        """, tmp_path)
    got = codes(res)
    assert got.count("host-coercion") == 3


def test_tracer_purity_traced_branch(tmp_path):
    res = run_on("tracer-purity", """
        import jax
        def f(x):
            if x > 0:
                return x
            return -x
        g = jax.jit(f)
        """, tmp_path)
    assert codes(res) == ["traced-branch"]


def test_tracer_purity_side_effects(tmp_path):
    res = run_on("tracer-purity", """
        import jax
        import logging
        import time
        def f(state, x):
            logging.info("step %s", 1)
            t = time.time()
            state.counter = 1
            print("hi")
            return x + t
        g = jax.jit(f)
        """, tmp_path)
    got = codes(res)
    assert got.count("traced-side-effect") == 3  # logging, attr, print
    assert got.count("traced-impure-read") == 1  # time.time


def test_tracer_purity_closure_reached_helper(tmp_path):
    """Helpers called from traced code are traced too — the executor's
    sgd_step_math pattern."""
    res = run_on("tracer-purity", """
        import jax
        import jax.numpy as jnp
        def helper(p):
            q = p.astype(jnp.float32)
            return float(q) + 1.0
        def step(x):
            return helper(x)
        g = jax.jit(step)
        """, tmp_path)
    assert codes(res) == ["host-coercion"]


def test_tracer_purity_near_misses_stay_silent(tmp_path):
    """The precision contract: hyperparameter branches in helpers,
    is-None tests, shape-derived conditions, jax.debug, and untraced
    functions never fire."""
    res = run_on("tracer-purity", """
        import jax
        import jax.numpy as jnp
        def sgdish(p, g, momentum, clip):
            g = g.astype(jnp.float32)
            if clip > 0:
                g = jnp.clip(g, -clip, clip)
            if momentum != 0.0:
                m = momentum * g
                return p - m, m
            return p - g, None
        def step(p, g):
            new_p, m = sgdish(p, g, 0.9, -1.0)
            if m is not None:
                new_p = new_p + 0
            if p.shape[0] > 1:
                new_p = new_p * 1
            jax.debug.print("p {}", new_p)
            return new_p
        fn = jax.jit(step)
        def not_traced(x):
            return float(x)
        """, tmp_path)
    assert not active(res), [f.message for f in active(res)]


def test_tracer_purity_suppression(tmp_path):
    res = run_on("tracer-purity", """
        import jax
        def f(x):
            return float(x)  # lint: ok[tracer-purity] trace-time constant by contract
        g = jax.jit(f)
        """, tmp_path)
    assert not active(res) and len(res.suppressed) == 1


# -- recompile-hazard --------------------------------------------------------

def test_recompile_jit_in_loop(tmp_path):
    res = run_on("recompile-hazard", """
        import jax
        def build(fns):
            out = []
            for f in fns:
                out.append(jax.jit(f))
            return out
        """, tmp_path)
    assert codes(res) == ["jit-in-loop"]


def test_recompile_mutable_closure_global_and_attr(tmp_path):
    res = run_on("recompile-hazard", """
        import jax
        SCALE = 1.0
        SCALE = 2.0
        class M:
            def build(self):
                def f(x):
                    return x * SCALE * self.gain
                return jax.jit(f)
        """, tmp_path)
    got = sorted(f.detail for f in active(res))
    assert got == ["SCALE", "self.gain"]
    assert all(f.code == "mutable-closure" for f in active(res))


def test_recompile_constant_global_is_fine(tmp_path):
    res = run_on("recompile-hazard", """
        import jax
        EPS = 1e-6
        def f(x):
            return x + EPS
        g = jax.jit(f)
        """, tmp_path)
    assert not active(res)


def test_recompile_param_shape(tmp_path):
    res = run_on("recompile-hazard", """
        import jax
        import jax.numpy as jnp
        def f(x, n):
            return x + jnp.zeros((n, 4))
        g = jax.jit(f)
        def ok(x):
            return x + jnp.zeros(x.shape)
        h = jax.jit(ok)
        """, tmp_path)
    assert codes(res) == ["param-shape"]
    assert active(res)[0].detail == "n"


def test_recompile_static_argnums_param_shape_is_intended(tmp_path):
    res = run_on("recompile-hazard", """
        import jax
        import jax.numpy as jnp
        def f(x, n):
            return x + jnp.zeros((n, 4))
        g = jax.jit(f, static_argnums=(1,))
        """, tmp_path)
    assert not active(res)


def test_recompile_computed_and_unhashable_statics(tmp_path):
    res = run_on("recompile-hazard", """
        import jax
        IDXS = (1,)
        def f(x, k):
            return x
        g = jax.jit(f, static_argnums=IDXS)
        h = jax.jit(f, static_argnums=(1,))
        y = h(1, [2, 3])
        """, tmp_path)
    assert sorted(codes(res)) == ["computed-statics", "unhashable-static"]


# -- donation ----------------------------------------------------------------

def test_donation_use_after_donate(tmp_path):
    res = run_on("donation", """
        import jax
        def f(a, b):
            return a + b
        g = jax.jit(f, donate_argnums=(0,))
        def caller(x, y):
            out = g(x, y)
            return out + x
        """, tmp_path)
    assert codes(res) == ["use-after-donate"]
    assert active(res)[0].detail == "x"


def test_donation_rebind_is_safe(tmp_path):
    res = run_on("donation", """
        import jax
        def f(a, b):
            return a + b
        g = jax.jit(f, donate_argnums=(0,))
        def caller(x, y):
            x = g(x, y)
            return x + y
        """, tmp_path)
    assert not active(res)


def test_donation_attr_chain_and_wrappers(tmp_path):
    """The module.py fused-update shape: jit wrapped in instrument()
    calls, bound to self._step, donated self attr re-read after."""
    res = run_on("donation", """
        import jax
        def instrument(fn, tag):
            return fn
        class M:
            def build(self, f):
                self._step = instrument(
                    jax.jit(f, donate_argnums=(0,)), "fused")
            def run(self):
                out = self._step(self._buf, 1)
                return out + self._buf
            def run_ok(self):
                self._buf = self._step(self._buf, 1)
                return self._buf
        """, tmp_path)
    assert codes(res) == ["use-after-donate"]
    assert active(res)[0].detail == "self._buf"


def test_donation_suppression(tmp_path):
    res = run_on("donation", """
        import jax
        def f(a):
            return a
        g = jax.jit(f, donate_argnums=(0,))
        def caller(x):
            out = g(x)
            return out, x  # lint: ok[donation] x is host-backed here, the donation is a no-op
        """, tmp_path)
    assert not active(res) and len(res.suppressed) == 1


# -- lock-discipline ---------------------------------------------------------

LOCKED_CLASS = """
    import threading
    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []
            self._count = 0
        def add(self, x):
            with self._lock:
                self._items.append(x)
                self._count += 1
        def drain(self):
            with self._lock:
                out, self._items = self._items, []
                self._count = 0
            return out
"""


def test_lock_discipline_clean_class(tmp_path):
    res = run_on("lock-discipline", LOCKED_CLASS, tmp_path)
    assert not active(res)


def test_lock_discipline_unlocked_write(tmp_path):
    res = run_on("lock-discipline", """
        import threading
        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._count = 0
            def add(self):
                with self._lock:
                    self._count += 1
            def reset_racy(self):
                self._count = 0
        """, tmp_path)
    assert codes(res) == ["unlocked-write"]
    assert active(res)[0].detail == "Box._count"


def test_lock_discipline_thread_unlocked_read(tmp_path):
    res = run_on("lock-discipline", """
        import threading
        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._running = False
                self._t = threading.Thread(target=self._run)
            def start(self):
                with self._lock:
                    self._running = True
            def _run(self):
                while self._running:
                    pass
        """, tmp_path)
    assert codes(res) == ["thread-unlocked-read"]


def test_lock_discipline_thread_shared_unguarded(tmp_path):
    """The AsyncSnapshotWriter._error defect shape: written on the
    worker thread, read from a consumer method, no lock anywhere."""
    res = run_on("lock-discipline", """
        import threading
        class W:
            def __init__(self):
                self._cv = threading.Condition()
                self._error = None
                self._slot = None
                self._t = threading.Thread(target=self._run)
            def submit(self, x):
                with self._cv:
                    self._slot = x
            def _run(self):
                try:
                    pass
                except Exception as e:
                    self._error = e
            def drain(self):
                return self._error
        """, tmp_path)
    assert codes(res) == ["thread-shared-unguarded"]
    assert active(res)[0].detail == "W._error"


def test_lock_discipline_helper_called_under_lock(tmp_path):
    """The faults._sync_env pattern: a helper whose every call site
    holds the lock needs no suppression."""
    res = run_on("lock-discipline", """
        import threading
        class R:
            def __init__(self):
                self._lock = threading.Lock()
                self._state = {}
            def _sync(self):
                self._state["k"] = 1
            def arm(self):
                with self._lock:
                    self._sync()
            def check(self):
                with self._lock:
                    self._sync()
                    return dict(self._state)
        """, tmp_path)
    assert not active(res)


def test_lock_discipline_module_level(tmp_path):
    res = run_on("lock-discipline", """
        import threading
        _lock = threading.Lock()
        _registry = {}
        def record(k, v):
            with _lock:
                _registry[k] = v
        def wipe_racy():
            _registry["gone"] = True
        def _apply():
            _registry["x"] = 1
        def locked_entry():
            with _lock:
                _apply()
        """, tmp_path)
    assert codes(res) == ["module-unlocked-write"]
    assert active(res)[0].detail == "_registry"


def test_lock_discipline_suppression(tmp_path):
    res = run_on("lock-discipline", """
        import threading
        class B:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0
            def bump(self):
                with self._lock:
                    self._n += 1
            def reset(self):
                self._n = 0  # lint: ok[lock-discipline] single-threaded teardown
        """, tmp_path)
    assert not active(res) and len(res.suppressed) == 1


# -- baselines ---------------------------------------------------------------

def test_baseline_add_then_expire(tmp_path):
    snippet = tmp_path / "mod.py"
    snippet.write_text("def f():\n    try:\n        pass\n"
                       "    except:\n        raise\n")
    bl = tmp_path / "baseline.json"
    ctx = RunContext(roots=[snippet])
    passes = [by_id("bare-except")()]

    out = io.StringIO()
    rc = glrunner.run(passes, ctx=ctx, baseline_path=bl, out=out)
    assert rc == 1

    out = io.StringIO()
    rc = glrunner.run(passes, ctx=RunContext(roots=[snippet]),
                      baseline_path=bl, update_baseline=True, out=out)
    assert rc == 0 and bl.exists()

    out = io.StringIO()
    rc = glrunner.run(passes, ctx=RunContext(roots=[snippet]),
                      baseline_path=bl, out=out)
    assert rc == 0
    assert "1 baselined" in out.getvalue()

    # the finding is fixed -> the baseline entry is STALE and reported
    snippet.write_text("def f():\n    pass\n")
    out = io.StringIO()
    rc = glrunner.run(passes, ctx=RunContext(roots=[snippet]),
                      baseline_path=bl, prune_baseline=True, out=out)
    assert rc == 0
    assert "STALE" in out.getvalue()
    assert glbaseline.load(bl) == {}


def test_baseline_does_not_mask_new_findings(tmp_path):
    snippet = tmp_path / "mod.py"
    snippet.write_text("try:\n    pass\nexcept:\n    raise\n")
    bl = tmp_path / "baseline.json"
    glbaseline.save({("bare-except", "other.py", "bare-except", ""): 1}, bl)
    out = io.StringIO()
    rc = glrunner.run([by_id("bare-except")()],
                      ctx=RunContext(roots=[snippet]),
                      baseline_path=bl, out=out)
    assert rc == 1


def test_json_artifact(tmp_path):
    snippet = tmp_path / "mod.py"
    snippet.write_text("print('x')\n")
    report = tmp_path / "report.json"
    out = io.StringIO()
    rc = glrunner.run([by_id("print")()], ctx=RunContext(roots=[snippet]),
                      baseline_path=tmp_path / "none.json",
                      json_path=str(report), out=out)
    assert rc == 1
    payload = json.loads(report.read_text())
    assert payload["total_active"] == 1
    assert payload["passes"]["print"]["active"] == 1
    assert payload["passes"]["print"]["findings"][0]["line"] == 1


# -- the repo itself ---------------------------------------------------------

def test_repo_head_is_clean_and_fast():
    """Acceptance pin: all analysis passes over mxnet_tpu/ finish clean
    (zero unsuppressed, unbaselined findings) well inside the 30s
    budget; the subprocess IS the documented entry point."""
    proc = subprocess.run(
        [sys.executable, "-m", "ci.graftlint"], cwd=str(ROOT),
        capture_output=True, text=True, timeout=30)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "graftlint: OK" in proc.stdout


def test_fixed_threaded_modules_stay_clean():
    """Regression pin for the two genuine defects the lock pass caught:
    AsyncSnapshotWriter._error hand-off and DynamicBatcher._serve_loop's
    bare stop-flag read are now lock-guarded."""
    ctx = RunContext(roots=[ROOT / "mxnet_tpu" / "checkpoint.py",
                            ROOT / "mxnet_tpu" / "serving" / "batcher.py"])
    res = run_pass(by_id("lock-discipline")(), ctx)
    assert not active(res), [f.message for f in active(res)]


def test_migrated_passes_clean_and_shims_gone():
    """The five legacy shims were deleted after their deprecation cycle
    (graftlint v2); the migrated passes stay clean on the tree and the
    old entry points are really gone."""
    for pass_id in ("bare-except", "print", "env-docs", "host-sync",
                    "signal-restore"):
        res = run_pass(by_id(pass_id)(), RunContext())
        assert not active(res), [f.message for f in active(res)]
    for shim in ("check_bare_except.py", "check_print.py",
                 "check_env_docs.py", "check_host_sync.py",
                 "check_signal_restore.py"):
        assert not (ROOT / "ci" / shim).exists(), shim


# -- seeded mutations: the pass catches the real defect classes --------------

def _mutated_copy(tmp_path, rel, old, new, name):
    src = (ROOT / rel).read_text()
    assert old in src, "mutation anchor vanished from %s" % rel
    p = tmp_path / name
    p.write_text(src.replace(old, new, 1))
    return p


def test_mutation_removing_a_lock_is_caught(tmp_path):
    """Strip the admission lock from DynamicBatcher.submit: the queue
    and depth writes race the worker -> lock-discipline must fire."""
    pristine = tmp_path / "batcher_ok.py"
    pristine.write_text(
        (ROOT / "mxnet_tpu" / "serving" / "batcher.py").read_text())
    res0 = run_pass(by_id("lock-discipline")(),
                    RunContext(roots=[pristine]))
    assert not active(res0)

    mutated = _mutated_copy(
        tmp_path, "mxnet_tpu/serving/batcher.py",
        "        with self._cond:\n"
        "            if self._closed:",
        "        if True:\n"
        "            if self._closed:",
        "batcher_mut.py")
    res1 = run_pass(by_id("lock-discipline")(),
                    RunContext(roots=[mutated]))
    assert any(f.code == "unlocked-write" for f in active(res1)), \
        [f.message for f in res1.findings]


def test_mutation_item_in_fit_loop_is_caught(tmp_path):
    """Insert a per-batch .item() next to forward_backward in the fit
    loop: host-sync must fire on the mutated copy (pristine is clean)."""
    anchor = "                        self.forward_backward(data_batch)\n"
    pristine = tmp_path / "base_module_ok.py"
    pristine.write_text(
        (ROOT / "mxnet_tpu" / "module" / "base_module.py").read_text())
    res0 = run_pass(by_id("host-sync")(), RunContext(roots=[pristine]))
    assert not active(res0)

    mutated = _mutated_copy(
        tmp_path, "mxnet_tpu/module/base_module.py", anchor,
        anchor + "                        _probe = "
                 "self.get_outputs()[0].item()\n",
        "base_module_mut.py")
    res1 = run_pass(by_id("host-sync")(), RunContext(roots=[mutated]))
    assert [f.detail for f in active(res1)] == [".item()"]


def test_mutation_reusing_donated_buffer_is_caught(tmp_path):
    """Read the donated params list after the fused update dispatch:
    donation must fire on the mutated copy (pristine is clean)."""
    anchor = ("        new_p, new_m = self._fused_step("
              "params, grads, moms, lrs, wds)\n")
    pristine = tmp_path / "module_ok.py"
    pristine.write_text(
        (ROOT / "mxnet_tpu" / "module" / "module.py").read_text())
    res0 = run_pass(by_id("donation")(), RunContext(roots=[pristine]))
    assert not active(res0)

    mutated = _mutated_copy(
        tmp_path, "mxnet_tpu/module/module.py", anchor,
        anchor + "        _leak = params[0] + 1\n",
        "module_mut.py")
    res1 = run_pass(by_id("donation")(), RunContext(roots=[mutated]))
    assert any(f.code == "use-after-donate" and f.detail == "params"
               for f in active(res1)), \
        [f.message for f in res1.findings]


def test_mutation_host_coercion_in_traced_metric_is_caught(tmp_path):
    """Coerce the device metric's traced accumulator to float inside
    the jitted step: tracer-purity must fire on the mutated copy."""
    anchor = "                stats = jnp.stack(rows)\n"
    pristine = tmp_path / "metric_ok.py"
    pristine.write_text((ROOT / "mxnet_tpu" / "metric.py").read_text())
    res0 = run_pass(by_id("tracer-purity")(), RunContext(roots=[pristine]))
    assert not active(res0)

    mutated = _mutated_copy(
        tmp_path, "mxnet_tpu/metric.py", anchor,
        anchor + "                _chk = float(stats)\n",
        "metric_mut.py")
    res1 = run_pass(by_id("tracer-purity")(), RunContext(roots=[mutated]))
    assert any(f.code == "host-coercion" and "stats" in f.detail
               for f in active(res1)), \
        [f.message for f in res1.findings]


def test_mutation_mutable_global_in_traced_guard_is_caught(tmp_path):
    """Read the rebindable _ANY_NONFINITE_JIT global inside the traced
    NaN-guard reduction: recompile-hazard must fire on the mutated
    copy."""
    anchor = ("    flags = [jnp.logical_not(jnp.all(jnp.isfinite(v))) "
              "for v in values\n")
    pristine = tmp_path / "executor_ok.py"
    pristine.write_text((ROOT / "mxnet_tpu" / "executor.py").read_text())
    res0 = run_pass(by_id("recompile-hazard")(),
                    RunContext(roots=[pristine]))
    assert not active(res0)

    mutated = _mutated_copy(
        tmp_path, "mxnet_tpu/executor.py", anchor,
        "    _hazard = _ANY_NONFINITE_JIT\n" + anchor,
        "executor_mut.py")
    res1 = run_pass(by_id("recompile-hazard")(),
                    RunContext(roots=[mutated]))
    assert any(f.code == "mutable-closure"
               and f.detail == "_ANY_NONFINITE_JIT"
               for f in active(res1)), \
        [f.message for f in res1.findings]


# -- regression: the fixed hand-offs behave ---------------------------------

def test_async_writer_error_surfaces_once_under_lock(tmp_path,
                                                     monkeypatch):
    """The _error hand-off fix keeps semantics: a writer failure raises
    on the next drain exactly once, then the writer keeps working."""
    from mxnet_tpu.checkpoint import AsyncSnapshotWriter, Snapshot

    calls = {"n": 0}

    def boom(self, snap):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("disk gone")

    monkeypatch.setattr(AsyncSnapshotWriter, "_write", boom)
    w = AsyncSnapshotWriter(str(tmp_path / "ck"))
    snap = Snapshot(epoch=0, nbatch=1, arg_params={}, aux_params={})
    assert w.submit(snap)
    with pytest.raises(RuntimeError):
        w.drain()
    w.drain()  # error consumed: second drain is clean
    assert w.submit(snap)
    w.drain()
    w.close()
    assert calls["n"] == 2


def test_batcher_stop_flag_read_under_lock_still_stops():
    """The _serve_loop fix keeps semantics: start -> serve -> stop
    terminates the worker and pending work drains."""
    from mxnet_tpu.serving.batcher import DynamicBatcher

    b = DynamicBatcher(lambda rows: rows * 2, buckets=(1, 4),
                       batch_timeout_us=500, name="lint-regress")
    b.start()
    import numpy as np

    fut = b.submit(np.ones((2, 3), np.float32))
    out = fut.result(timeout=10)
    assert out.shape == (2, 3)
    b.stop()
    assert b._thread is None


def test_mutation_removing_pool_routing_lock_is_caught(tmp_path):
    """Strip the routing lock from ReplicaPool.generate: the outstanding
    counters race the settle/health paths -> lock-discipline must fire
    (ISSUE 9 satellite: the new pool threads stay lint-clean with zero
    baseline entries, and the pass provably catches the stripped lock)."""
    pristine = tmp_path / "pool_ok.py"
    pristine.write_text(
        (ROOT / "mxnet_tpu" / "serving" / "pool.py").read_text())
    res0 = run_pass(by_id("lock-discipline")(),
                    RunContext(roots=[pristine]))
    assert not active(res0), [f.message for f in active(res0)]

    mutated = _mutated_copy(
        tmp_path, "mxnet_tpu/serving/pool.py",
        "        with self._lock:\n"
        "            if self._closed:\n"
        "                raise MXNetError(\"replica pool %r is closed\""
        " % self.name)\n"
        "            if self._total_outstanding",
        "        if True:\n"
        "            if self._closed:\n"
        "                raise MXNetError(\"replica pool %r is closed\""
        " % self.name)\n"
        "            if self._total_outstanding",
        "pool_mut.py")
    res1 = run_pass(by_id("lock-discipline")(),
                    RunContext(roots=[mutated]))
    assert any(f.code == "unlocked-write"
               and "_total_outstanding" in f.message
               for f in active(res1)), \
        [f.message for f in res1.findings]


def test_mutation_removing_controller_tick_lock_is_caught(tmp_path):
    """Strip the controller lock from FleetController.tick: the tick
    counter and the managed-model map race the describe()/decisions()
    readers -> lock-discipline must fire (ISSUE 16 satellite: the
    controller ships with a zero-findings baseline, and the pass
    provably catches the stripped lock)."""
    pristine = tmp_path / "controller_ok.py"
    pristine.write_text(
        (ROOT / "mxnet_tpu" / "serving" / "controller.py").read_text())
    res0 = run_pass(by_id("lock-discipline")(),
                    RunContext(roots=[pristine]))
    assert not active(res0), [f.message for f in active(res0)]

    mutated = _mutated_copy(
        tmp_path, "mxnet_tpu/serving/controller.py",
        "        with self._lock:\n"
        "            if self._closed:\n"
        "                return\n"
        "            self._ticks += 1",
        "        if True:\n"
        "            if self._closed:\n"
        "                return\n"
        "            self._ticks += 1",
        "controller_mut.py")
    res1 = run_pass(by_id("lock-discipline")(),
                    RunContext(roots=[mutated]))
    assert any(f.code == "unlocked-write"
               and ("_ticks" in f.message or "_models" in f.message)
               for f in active(res1)), \
        [f.message for f in res1.findings]


def test_mutation_removing_circuit_breaker_lock_is_caught(tmp_path):
    """Strip the pool lock from ReplicaPool._note_step_error: the
    circuit-breaker state writes (circuit transition, opened_at stamp)
    race the recovery thread and routing -> lock-discipline must fire
    (ISSUE 12 satellite: the failover circuit/transcript state stays
    lint-clean with zero baseline entries, and the pass provably
    catches the stripped lock)."""
    pristine = tmp_path / "pool_circuit_ok.py"
    pristine.write_text(
        (ROOT / "mxnet_tpu" / "serving" / "pool.py").read_text())
    res0 = run_pass(by_id("lock-discipline")(),
                    RunContext(roots=[pristine]))
    assert not active(res0), [f.message for f in active(res0)]

    mutated = _mutated_copy(
        tmp_path, "mxnet_tpu/serving/pool.py",
        "        with self._lock:\n"
        "            r.failures += 1",
        "        if True:\n"
        "            r.failures += 1",
        "pool_circuit_mut.py")
    res1 = run_pass(by_id("lock-discipline")(),
                    RunContext(roots=[mutated]))
    assert any(f.code == "unlocked-write" and "_circuit" in f.message
               for f in active(res1)), \
        [f.message for f in res1.findings]


def test_mutation_removing_session_transcript_lock_is_caught(tmp_path):
    """Strip the session lock from GenerateSession._resolve: the
    exactly-once completion flag — what keeps a migrated session from
    double-firing the pool's accounting hook when two engines race to
    retire it — loses its guard -> lock-discipline must fire."""
    pristine = tmp_path / "decode_ok.py"
    pristine.write_text(
        (ROOT / "mxnet_tpu" / "serving" / "decode.py").read_text())
    res0 = run_pass(by_id("lock-discipline")(),
                    RunContext(roots=[pristine]))
    assert not active(res0), [f.message for f in active(res0)]

    mutated = _mutated_copy(
        tmp_path, "mxnet_tpu/serving/decode.py",
        "        with self._lock:\n"
        "            if self._finished:\n"
        "                return False",
        "        if True:\n"
        "            if self._finished:\n"
        "                return False",
        "decode_mut.py")
    res1 = run_pass(by_id("lock-discipline")(),
                    RunContext(roots=[mutated]))
    assert any(f.code == "unlocked-write" and "_finished" in f.message
               for f in active(res1)), \
        [f.message for f in res1.findings]


def test_mutation_removing_kv_allocator_lock_is_caught(tmp_path):
    """Strip the free-list lock from BlockAllocator.alloc (ISSUE 18):
    the engine thread's pop races describe/healthz occupancy reads and
    a concurrent prefix-cache eviction's decref — the free list and
    refcount map lose their only guard -> lock-discipline must fire."""
    pristine = tmp_path / "kvblocks_ok.py"
    pristine.write_text(
        (ROOT / "mxnet_tpu" / "serving" / "kvblocks.py").read_text())
    res0 = run_pass(by_id("lock-discipline")(),
                    RunContext(roots=[pristine]))
    assert not active(res0), [f.message for f in active(res0)]

    mutated = _mutated_copy(
        tmp_path, "mxnet_tpu/serving/kvblocks.py",
        "        with self._lock:\n"
        "            if n > len(self._free):",
        "        if True:\n"
        "            if n > len(self._free):",
        "kvblocks_mut.py")
    res1 = run_pass(by_id("lock-discipline")(),
                    RunContext(roots=[mutated]))
    assert any(f.code == "unlocked-write" for f in active(res1)), \
        [f.message for f in res1.findings]


# -- collective-consistency ---------------------------------------------------

def test_collective_unknown_axis(tmp_path):
    res = run_on("collective-consistency", """
        import jax
        from jax.sharding import PartitionSpec as P
        def f(x):
            return jax.lax.psum(x, "j")
        out = jax.shard_map(f, mesh=None, in_specs=(P("i"),),
                            out_specs=P("i"))
        """, tmp_path)
    assert codes(res) == ["unknown-axis"]
    assert active(res)[0].detail == "j"


def test_collective_outside_spmd(tmp_path):
    res = run_on("collective-consistency", """
        import jax
        from jax.sharding import PartitionSpec as P
        spec = P("i")
        def lonely(x):
            return jax.lax.psum(x, "i")
        """, tmp_path)
    assert codes(res) == ["collective-outside-spmd"]


def test_collective_divergent_branch(tmp_path):
    res = run_on("collective-consistency", """
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        def f(x):
            s = jnp.sum(x)
            if s > 0:
                x = jax.lax.psum(x, "i")
            return x
        g = jax.shard_map(f, mesh=None, in_specs=(P("i"),),
                          out_specs=P("i"))
        """, tmp_path)
    assert "divergent-collective" in codes(res)


def test_collective_in_cond_branch(tmp_path):
    res = run_on("collective-consistency", """
        import jax
        from jax.sharding import PartitionSpec as P
        def br(x):
            return jax.lax.psum(x, "i")
        def keep(x):
            return x
        def f(p, x):
            return jax.lax.cond(p, br, keep, x)
        g = jax.shard_map(f, mesh=None, in_specs=(P("i"), P("i")),
                          out_specs=P("i"))
        """, tmp_path)
    assert codes(res) == ["divergent-collective"]
    assert "br" in active(res)[0].message


def test_collective_partial_plumbing_is_clean(tmp_path):
    """The ring/ulysses idiom: axis chosen by a wrapper default, bound
    through functools.partial — the interprocedural resolution must
    follow it and stay silent."""
    res = run_on("collective-consistency", """
        import functools
        import jax
        from jax.sharding import PartitionSpec as P
        def inner(x, axis_name):
            n = jax.lax.psum(1, axis_name)
            return x * n
        def wrap(x, seq_axis="i"):
            fn = functools.partial(inner, axis_name=seq_axis)
            return jax.shard_map(fn, mesh=None, in_specs=(P(seq_axis),),
                                 out_specs=P(seq_axis))(x)
        """, tmp_path)
    assert not active(res), [f.message for f in active(res)]


def test_collective_static_branch_is_clean(tmp_path):
    """Branching on a plain Python flag (trace-time specialization) or
    shape-derived statics around a collective stays silent."""
    res = run_on("collective-consistency", """
        import jax
        from jax.sharding import PartitionSpec as P
        def f(x, causal=False):
            if causal:
                x = x + 1
            if x.shape[0] > 1:
                x = x * 2
            return jax.lax.psum(x, "i")
        g = jax.shard_map(f, mesh=None, in_specs=(P("i"),),
                          out_specs=P("i"))
        """, tmp_path)
    assert not active(res), [f.message for f in active(res)]


def test_collective_method_dispatch(tmp_path):
    """Bound-method plumbing: the axis constant passed at a
    self.method call site binds PAST the implicit receiver, and a
    method reached through an unresolvable instance call
    (``r.step(x)``) counts as spmd-reachable (CHA-lite dispatch) — no
    collective-outside-spmd noise, just the real bad axis."""
    res = run_on("collective-consistency", """
        import jax
        from jax.sharding import PartitionSpec as P
        class Ring:
            def reduce(self, axis_name, v):
                return jax.lax.psum(v, axis_name)
            def step(self, x):
                return self.reduce("bogus_axis", x)
        def entry(x):
            r = Ring()
            return r.step(x)
        g = jax.shard_map(entry, mesh=None, in_specs=(P("i"),),
                          out_specs=P("i"))
        """, tmp_path)
    assert codes(res) == ["unknown-axis"], \
        [f.message for f in active(res)]
    assert active(res)[0].detail == "bogus_axis"


def test_collective_suppression(tmp_path):
    res = run_on("collective-consistency", """
        import jax
        def helper(x):
            return jax.lax.psum(x, "i")  # lint: ok[collective-consistency] wrapped by callers outside this tree
        spec_i = ("i",)
        """, tmp_path)
    assert not active(res) and len(res.suppressed) >= 1


# -- replica-divergence -------------------------------------------------------

def test_replica_divergence_time_into_collective(tmp_path):
    res = run_on("replica-divergence", """
        import time
        import jax
        def f(x):
            t = time.time()
            return jax.lax.psum(x * t, "i")
        """, tmp_path)
    assert codes(res) == ["nondet-collective"]
    assert active(res)[0].detail == "time.time()"


def test_replica_divergence_interprocedural_push(tmp_path):
    """A helper RETURNING a nondet value taints its callers across the
    call graph — the summaries layer."""
    res = run_on("replica-divergence", """
        import time
        def stamp():
            return time.time()
        def sync(kv, k, v):
            kv.push(k, v * stamp())
        """, tmp_path)
    assert codes(res) == ["nondet-kvstore"]
    assert "stamp" in active(res)[0].detail


def test_replica_divergence_set_order(tmp_path):
    res = run_on("replica-divergence", """
        def drain(kv, keys):
            pending = set(keys)
            for k in pending:
                kv.push(k, 1)
        def drain_ok(kv, keys):
            pending = set(keys)
            for k in sorted(pending):
                kv.push(k, 1)
        """, tmp_path)
    assert codes(res) == ["nondet-order"]


def test_replica_divergence_unstable_hash(tmp_path):
    res = run_on("replica-divergence", """
        def route(key, n):
            return hash(str(key)) % n
        class C:
            def __hash__(self):
                return hash(self.name)
        """, tmp_path)
    assert codes(res) == ["unstable-hash"]
    assert active(res)[0].detail == "route"


def test_replica_divergence_telemetry_timing_is_clean(tmp_path):
    """The Speedometer/push-latency idiom: time.* feeding logging or
    telemetry (not a sync sink) stays silent, as does a deterministic
    value pushed after unrelated timing."""
    res = run_on("replica-divergence", """
        import time
        def timed_push(kv, k, v, telemetry):
            t0 = time.perf_counter()
            kv.push(k, v)
            telemetry.observe("push.seconds", time.perf_counter() - t0)
        """, tmp_path)
    assert not active(res), [f.message for f in active(res)]


def test_replica_divergence_suppression(tmp_path):
    res = run_on("replica-divergence", """
        import time
        def f(kv, k):
            kv.push(k, time.time())  # lint: ok[replica-divergence] wall-clock IS the payload here
        """, tmp_path)
    assert not active(res) and len(res.suppressed) == 1


# -- spec-shape ---------------------------------------------------------------

def test_spec_shape_arity(tmp_path):
    res = run_on("spec-shape", """
        import jax
        from jax.sharding import PartitionSpec as P
        def f(a, b):
            return a + b
        def run(x):
            return jax.shard_map(f, mesh=None,
                                 in_specs=(P("i"), P("i")),
                                 out_specs=P("i"))(x)
        """, tmp_path)
    assert codes(res) == ["spec-arity"]


def test_spec_shape_rank_overflow(tmp_path):
    res = run_on("spec-shape", """
        import jax
        from jax.sharding import PartitionSpec as P
        def f(x):
            a, b = x.shape
            return x * a * b
        def run(x):
            return jax.shard_map(f, mesh=None,
                                 in_specs=(P("i", None, None),),
                                 out_specs=P("i"))(x)
        """, tmp_path)
    assert codes(res) == ["spec-rank"]


def test_spec_shape_prefix_spec_is_legal(tmp_path):
    res = run_on("spec-shape", """
        import jax
        from jax.sharding import PartitionSpec as P
        def f(x):
            a, b, c, d = x.shape
            return x * a
        def run(x):
            return jax.shard_map(f, mesh=None, in_specs=(P("i"),),
                                 out_specs=P("i"))(x)
        """, tmp_path)
    assert not active(res), [f.message for f in active(res)]


def test_spec_shape_unknown_mesh_axis(tmp_path):
    res = run_on("spec-shape", """
        import jax
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        def run(x, devs):
            mesh = Mesh(np.array(devs), ("x", "y"))
            def f(a):
                return a
            return jax.shard_map(f, mesh=mesh, in_specs=(P("z"),),
                                 out_specs=P("x"))(x)
        """, tmp_path)
    assert codes(res) == ["unknown-mesh-axis"]
    assert active(res)[0].detail == "z"


def test_spec_shape_donation_checks(tmp_path):
    res = run_on("spec-shape", """
        import jax
        def f(a, b):
            return a + b
        g = jax.jit(f, donate_argnums=(0,), static_argnums=(0,))
        h = jax.jit(f, donate_argnums=(3,))
        ok = jax.jit(f, donate_argnums=(0,), static_argnums=(1,))
        """, tmp_path)
    assert sorted(codes(res)) == ["donate-range", "donated-static"]


def test_spec_shape_conditional_def_is_silent(tmp_path):
    """The executor kind-dispatch idiom: several conditional ``def f``
    bindings make the donate target ambiguous — no finding."""
    res = run_on("spec-shape", """
        import jax
        def build(guard):
            if guard:
                def f(a, b, c, d, e):
                    return a
            else:
                def f(a, b, c, d):
                    return a
            return jax.jit(f, donate_argnums=(4,))
        """, tmp_path)
    assert not active(res), [f.message for f in active(res)]


def test_spec_shape_suppression(tmp_path):
    res = run_on("spec-shape", """
        import jax
        def f(a):
            return a
        g = jax.jit(f, donate_argnums=(1,))  # lint: ok[spec-shape] wrapper adds a second arg at runtime
        """, tmp_path)
    assert not active(res) and len(res.suppressed) == 1


# -- state-protocol -----------------------------------------------------------

def test_state_protocol_missing_and_unconsumed(tmp_path):
    res = run_on("state-protocol", """
        class It:
            def state_dict(self):
                return {"type": "It", "cursor": self.cursor,
                        "extra": self.extra}
            def load_state_dict(self, state):
                self.cursor = int(state["cursor"])
                self.epoch = int(state["epoch"])
        """, tmp_path)
    got = sorted((f.code, f.detail) for f in active(res))
    assert got == [("missing-key", "epoch"), ("unconsumed-key", "extra")]


def test_state_protocol_half(tmp_path):
    res = run_on("state-protocol", """
        class Half:
            def state_dict(self):
                return {"cursor": self.cursor}
        """, tmp_path)
    assert codes(res) == ["half-protocol"]


def test_state_protocol_tolerant_shapes_are_clean(tmp_path):
    """.get() optional keys, the exempt 'type' tag, conditional
    emission, raising halves, and whole-state delegation all stay
    silent."""
    res = run_on("state-protocol", """
        class Good:
            def state_dict(self):
                state = {"type": "Good", "cursor": self.cursor}
                if self.seq is not None:
                    state["seq"] = list(self.seq)
                return state
            def load_state_dict(self, state):
                self.cursor = int(state["cursor"])
                if state.get("seq") is not None:
                    self.seq = list(state["seq"])
        class NotImpl:
            def state_dict(self):
                raise NotImplementedError("no protocol")
            def load_state_dict(self, state):
                raise NotImplementedError("no protocol")
        class Delegating:
            def state_dict(self):
                return {"type": "Delegating", "inner": self.it.state_dict()}
            def load_state_dict(self, state):
                self.it.load_state_dict(state["inner"])
        """, tmp_path)
    assert not active(res), [f.message for f in active(res)]


def test_state_protocol_suppression(tmp_path):
    res = run_on("state-protocol", """
        class S:
            # lint: ok[state-protocol] audit field, never restored by design
            def state_dict(self):
                return {"cursor": self.cursor, "audit": self.audit}
            def load_state_dict(self, state):
                self.cursor = int(state["cursor"])
        """, tmp_path)
    assert not active(res) and len(res.suppressed) == 1


# -- seeded mutations: the v2 passes catch the distributed defects -----------

def test_mutation_swapped_psum_axis_is_caught(tmp_path):
    """Swap the axis of parallel/ring.py's psum to an undeclared name:
    collective-consistency must fire on the mutated copy."""
    pristine = tmp_path / "ring_ok.py"
    pristine.write_text(
        (ROOT / "mxnet_tpu" / "parallel" / "ring.py").read_text())
    res0 = run_pass(by_id("collective-consistency")(),
                    RunContext(roots=[pristine]))
    assert not active(res0), [f.message for f in active(res0)]

    mutated = _mutated_copy(
        tmp_path, "mxnet_tpu/parallel/ring.py",
        "    n = jax.lax.psum(1, axis_name)",
        "    n = jax.lax.psum(1, \"rings\")",
        "ring_mut.py")
    res1 = run_pass(by_id("collective-consistency")(),
                    RunContext(roots=[mutated]))
    assert any(f.code == "unknown-axis" and f.detail == "rings"
               for f in active(res1)), \
        [f.message for f in res1.findings]


def test_mutation_swapped_mesh_update_psum_axis_is_caught(tmp_path):
    """Swap the psum axis in kvstore_mesh's fused ZeRO update to an
    undeclared name: collective-consistency must fire on the mutated
    copy (ISSUE 14 satellite — the mesh plane lands lint-provable)."""
    pristine = tmp_path / "kvstore_mesh_ok.py"
    pristine.write_text(
        (ROOT / "mxnet_tpu" / "kvstore_mesh.py").read_text())
    res0 = run_pass(by_id("collective-consistency")(),
                    RunContext(roots=[pristine]))
    assert not active(res0), [f.message for f in active(res0)]
    res0s = run_pass(by_id("spec-shape")(),
                     RunContext(roots=[pristine]))
    assert not active(res0s), [f.message for f in active(res0s)]

    mutated = _mutated_copy(
        tmp_path, "mxnet_tpu/kvstore_mesh.py",
        "flag = jax.lax.psum(bad.astype(jnp.int32), axis_name) > 0",
        "flag = jax.lax.psum(bad.astype(jnp.int32), \"dataa\") > 0",
        "kvstore_mesh_mut.py")
    res1 = run_pass(by_id("collective-consistency")(),
                    RunContext(roots=[mutated]))
    assert any(f.code == "unknown-axis" and f.detail == "dataa"
               for f in active(res1)), \
        [f.message for f in res1.findings]


def test_mutation_time_into_trainer_collective_is_caught(tmp_path):
    """Insert time.time() into the lm train step's aux pmean:
    replica-divergence must fire on the mutated copy."""
    pristine = tmp_path / "lm_ok.py"
    pristine.write_text(
        (ROOT / "mxnet_tpu" / "parallel" / "lm.py").read_text())
    res0 = run_pass(by_id("replica-divergence")(),
                    RunContext(roots=[pristine]))
    assert not active(res0), [f.message for f in active(res0)]

    mutated = _mutated_copy(
        tmp_path, "mxnet_tpu/parallel/lm.py",
        "        return out, jax.lax.pmean(aux, \"data\")",
        "        import time\n"
        "        return out, jax.lax.pmean(aux * time.time(), \"data\")",
        "lm_mut.py")
    res1 = run_pass(by_id("replica-divergence")(),
                    RunContext(roots=[mutated]))
    assert any(f.code == "nondet-collective"
               and f.detail == "time.time()" for f in active(res1)), \
        [f.message for f in res1.findings]


def test_mutation_overlong_spec_is_caught(tmp_path):
    """Grow ring_self_attention's P spec past the q/k/v rank:
    spec-shape must fire on the mutated copy."""
    pristine = tmp_path / "ring_spec_ok.py"
    pristine.write_text(
        (ROOT / "mxnet_tpu" / "parallel" / "ring.py").read_text())
    res0 = run_pass(by_id("spec-shape")(), RunContext(roots=[pristine]))
    assert not active(res0), [f.message for f in active(res0)]

    mutated = _mutated_copy(
        tmp_path, "mxnet_tpu/parallel/ring.py",
        "    spec = P(None, None, seq_axis, None)",
        "    spec = P(None, None, None, seq_axis, None)",
        "ring_spec_mut.py")
    res1 = run_pass(by_id("spec-shape")(), RunContext(roots=[mutated]))
    assert any(f.code == "spec-rank" for f in active(res1)), \
        [f.message for f in res1.findings]


def test_mutation_dropped_state_key_is_caught(tmp_path):
    """Drop the pos restore from ElasticShardIter.load_state_dict:
    state-protocol must fire on the mutated copy."""
    pristine = tmp_path / "io_ok.py"
    pristine.write_text((ROOT / "mxnet_tpu" / "io.py").read_text())
    res0 = run_pass(by_id("state-protocol")(),
                    RunContext(roots=[pristine]))
    assert not active(res0), [f.message for f in active(res0)]

    mutated = _mutated_copy(
        tmp_path, "mxnet_tpu/io.py",
        "            self._pos = int(state[\"pos\"])",
        "            pass",
        "io_mut.py")
    res1 = run_pass(by_id("state-protocol")(),
                    RunContext(roots=[mutated]))
    assert any(f.code == "unconsumed-key" and f.detail == "pos"
               for f in active(res1)), \
        [f.message for f in res1.findings]


# -- the --changed diff-scoped lane ------------------------------------------

def test_changed_lane_scopes_reporting(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("print('leak')\n")
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")

    out = io.StringIO()
    rc = glrunner.run([by_id("print")()],
                      ctx=RunContext(roots=[tmp_path],
                                     changed={str(clean)}),
                      baseline_path=tmp_path / "none.json", out=out)
    assert rc == 0, out.getvalue()

    out = io.StringIO()
    rc = glrunner.run([by_id("print")()],
                      ctx=RunContext(roots=[tmp_path],
                                     changed={str(bad)}),
                      baseline_path=tmp_path / "none.json", out=out)
    assert rc == 1


def test_changed_lane_interprocedural_keeps_context(tmp_path):
    """An interprocedural pass in a --changed run still sees the whole
    tree: the axis declared in an UNCHANGED file keeps the changed
    file's collective clean."""
    decl = tmp_path / "decl.py"
    decl.write_text("from jax.sharding import PartitionSpec as P\n"
                    "import jax\n"
                    "SPEC = P(\"i\")\n"
                    "def entry(x):\n"
                    "    from use import f\n"
                    "    return jax.shard_map(f, mesh=None,\n"
                    "                         in_specs=(SPEC,),\n"
                    "                         out_specs=SPEC)(x)\n")
    use = tmp_path / "use.py"
    use.write_text("import jax\n"
                   "def f(x):\n"
                   "    return jax.lax.psum(x, \"i\")\n")
    out = io.StringIO()
    rc = glrunner.run([by_id("collective-consistency")()],
                      ctx=RunContext(roots=[tmp_path],
                                     changed={str(use)}),
                      baseline_path=tmp_path / "none.json", out=out)
    assert rc == 0, out.getvalue()


def test_changed_files_helper_runs():
    from ci.graftlint import changed_files

    got = changed_files("HEAD")
    assert got is None or isinstance(got, set)


def test_changed_lane_budget():
    """The pre-commit lane stays well inside its <5s budget (3x slack
    for loaded CI hosts — the full-run pin uses the same pattern).
    Exit status is not asserted: a dirty development tree may
    legitimately carry findings in changed files."""
    proc = subprocess.run(
        [sys.executable, "-m", "ci.graftlint", "--changed", "HEAD"],
        cwd=str(ROOT), capture_output=True, text=True, timeout=15)
    assert proc.returncode in (0, 1), proc.stdout + proc.stderr


# -- baseline-debt guard ------------------------------------------------------

def test_lint_baseline_guard(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_lint_baseline", ROOT / "ci" / "check_lint_baseline.py")
    guard = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(guard)

    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"version": 1, "passes": {
        "print": [{"path": "a.py", "code": "print", "count": 1}]}}))
    failures, waived = guard.check(bl)
    assert len(failures) == 1 and not waived
    assert guard.main(["x", str(bl)]) == 1

    bl.write_text(json.dumps({"version": 1, "passes": {
        "print": [{"path": "a.py", "code": "print", "count": 1,
                   "waiver": "2026-08: accepted, ISSUE-99"}]}}))
    failures, waived = guard.check(bl)
    assert not failures and len(waived) == 1
    assert guard.main(["x", str(bl)]) == 0

    assert guard.main(["x", str(tmp_path / "missing.json")]) == 0


def test_repo_baseline_is_empty_or_waived():
    """Acceptance pin: baseline debt cannot silently accrete at HEAD."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_lint_baseline2", ROOT / "ci" / "check_lint_baseline.py")
    guard = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(guard)
    failures, _ = guard.check()
    assert not failures, failures


# -- MXNET_LINT_FIXPOINT_DEPTH ------------------------------------------------

DEEP_HELPER_CHAIN = """
    import threading
    class R:
        def __init__(self):
            self._lock = threading.Lock()
            self._state = {}
        def _c(self):
            self._state["k"] = 1
        def _b(self):
            self._c()
        def _a(self):
            self._b()
        def entry(self):
            with self._lock:
                self._a()
        def write(self):
            with self._lock:
                self._state["x"] = 2
"""


def test_fixpoint_depth_env_tunable(tmp_path, monkeypatch):
    """The helper chain _a -> _b -> _c (defined callee-first, so one
    sweep resolves one level) needs 3 fixpoint iterations; the default
    depth (5) proves it lock-held, depth 1 does not."""
    monkeypatch.delenv("MXNET_LINT_FIXPOINT_DEPTH", raising=False)
    res = run_on("lock-discipline", DEEP_HELPER_CHAIN, tmp_path,
                 name="deep_ok.py")
    assert not active(res), [f.message for f in active(res)]

    monkeypatch.setenv("MXNET_LINT_FIXPOINT_DEPTH", "1")
    res = run_on("lock-discipline", DEEP_HELPER_CHAIN, tmp_path,
                 name="deep_shallow.py")
    assert any(f.code == "unlocked-write" for f in active(res)), \
        [f.message for f in res.findings]

    monkeypatch.setenv("MXNET_LINT_FIXPOINT_DEPTH", "notanint")
    from ci.graftlint.dataflow import fixpoint_depth
    assert fixpoint_depth() == 5


# -- regressions for the two defects the v2 passes found ---------------------

def test_server_of_routing_is_hashseed_stable():
    """KVStoreDist._server_of routed string keys by builtin hash():
    per-process PYTHONHASHSEED would send the same key to different
    shard servers from different workers.  Now crc32 — assert the
    routing is a pure function of the key, reproduced in a subprocess
    with a different hash seed."""
    import zlib

    from mxnet_tpu.kvstore import KVStoreDist

    kv = KVStoreDist.__new__(KVStoreDist)
    kv._num_servers = 4
    want = {k: zlib.crc32(k.encode()) % 4
            for k in ("fc1_weight", "conv0_bias", "gamma")}
    got = {k: kv._server_of(k) for k in want}
    assert got == want
    assert kv._server_of(7) == 3  # int keys unchanged: round-robin

    code = ("import sys; sys.path.insert(0, %r); "
            "from mxnet_tpu.kvstore import KVStoreDist; "
            "kv = KVStoreDist.__new__(KVStoreDist); "
            "kv._num_servers = 4; "
            "print([kv._server_of(k) for k in "
            "('fc1_weight', 'conv0_bias', 'gamma')])" % str(ROOT))
    for seed in ("0", "12345"):
        env = dict(os.environ, PYTHONHASHSEED=seed,
                   JAX_PLATFORMS="cpu")
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.strip() == str(list(want.values()))


def test_elastic_iter_restores_rank():
    """ElasticShardIter.load_state_dict dropped the captured 'rank':
    restoring a capture onto a differently-constructed iterator walked
    another rank's shard.  Now the rank round-trips and the restored
    iterator serves the capture's shard."""
    import numpy as np

    from mxnet_tpu.io import ElasticShardIter

    data = np.arange(32, dtype=np.float32).reshape(32, 1)
    it = ElasticShardIter(data=data, batch_size=4, rank=1,
                          ranks=(0, 1), membership_epoch=0)
    state = it.state_dict()
    assert state["rank"] == 1

    other = ElasticShardIter(data=data, batch_size=4, rank=0,
                             ranks=(0, 1), membership_epoch=0)
    other.load_state_dict(state)
    assert other.rank == 1
    b_it = it.next()
    b_other = other.next()
    assert np.array_equal(np.asarray(b_it.index),
                          np.asarray(b_other.index))


# -- ISSUE 15: the sentinel's threads stay lock-discipline clean -------------

def test_sentinel_lock_discipline_clean_no_baseline():
    """The watchdog monitor / supervisor land with ZERO lock-discipline
    baseline entries (and signal-restore stays clean over the fit-scope
    SIGQUIT installer)."""
    targets = [ROOT / "mxnet_tpu" / "sentinel.py",
               ROOT / "tools" / "supervise.py",
               ROOT / "mxnet_tpu" / "module" / "base_module.py"]
    for pass_id in ("lock-discipline", "signal-restore"):
        res = run_pass(by_id(pass_id)(), RunContext(roots=targets))
        assert not active(res), (pass_id,
                                 [f.message for f in active(res)])
    baseline = glbaseline.load()
    blob = json.dumps(baseline.get("passes", {}))
    assert "sentinel" not in blob and "supervise" not in blob, \
        "sentinel/supervisor must carry no baseline debt"


def test_mutation_stripping_watchdog_progress_lock_is_caught(tmp_path):
    """Strip the lock around the watchdog's last-progress timestamp
    (the phase-hook write the monitor thread reads against the
    deadline): lock-discipline must fire — an unlocked write there is
    exactly the torn-read race that turns a healthy job into a false
    hang trip (ISSUE 15 satellite)."""
    pristine = tmp_path / "sentinel_ok.py"
    pristine.write_text((ROOT / "mxnet_tpu" / "sentinel.py").read_text())
    res0 = run_pass(by_id("lock-discipline")(),
                    RunContext(roots=[pristine]))
    assert not active(res0), [f.message for f in active(res0)]

    mutated = _mutated_copy(
        tmp_path, "mxnet_tpu/sentinel.py",
        "        now = time.monotonic()\n"
        "        with self._lock:\n"
        "            self._last_progress = now",
        "        now = time.monotonic()\n"
        "        if True:\n"
        "            self._last_progress = now",
        "sentinel_mut.py")
    res1 = run_pass(by_id("lock-discipline")(),
                    RunContext(roots=[mutated]))
    assert any(f.code == "unlocked-write"
               and "_last_progress" in f.message
               for f in active(res1)), \
        [f.message for f in res1.findings]
