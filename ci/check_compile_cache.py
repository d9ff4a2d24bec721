#!/usr/bin/env python
"""CI cache-effectiveness check: the compile-once contract, enforced.

Runs a small fit + predict workload TWICE, each in a fresh subprocess,
against one ``JAX_COMPILATION_CACHE_DIR`` — a fixed directory under the
checkout, emptied first (the path is part of JAX's cache key).  The first
run is cold (it populates the persistent XLA compile cache); the second run
must perform ZERO XLA compilations — every executable (train step,
fused update, eval forward, predictor buckets) must load from the
cache.  Any persistent-cache miss in the second run means an
executable's cache identity is unstable across processes (nondeterminism
in tracing, an env fingerprint leaking into the program, a cache-key
regression) — exactly the bug class that silently re-introduces cold
warm-up costs in serving and CI, so it fails the build here instead.

Usage: python ci/check_compile_cache.py
Wired into ci/run_tests.sh.  See docs/how_to/perf.md "Compile once".
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

_WORKLOAD = r"""
import json, os, sys
sys.path.insert(0, os.environ["CCCHECK_REPO"])
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import compile_cache

# small but representative: fit (train step + fused update + metric) +
# a standalone Predictor forward (the serving build path)
data = mx.sym.Variable("data")
net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
net = mx.sym.Activation(net, act_type="relu")
net = mx.sym.SoftmaxOutput(
    mx.sym.FullyConnected(net, num_hidden=4, name="fc2"), name="softmax")
rs = np.random.RandomState(0)
x = rs.rand(32, 8).astype(np.float32)
y = rs.randint(0, 4, 32).astype(np.float32)
train = mx.io.NDArrayIter(x, y, batch_size=8, last_batch_handle="discard")
mod = mx.mod.Module(net, context=mx.cpu())
mod.fit(train, optimizer="sgd",
        optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
        num_epoch=1)
pred = mx.predict.Predictor(net.tojson(), None, {"data": (4, 8)})
pred.set_input("data", np.zeros((4, 8), np.float32))
pred.forward()
pred.get_output(0)
print("CCCHECK " + json.dumps(compile_cache.stats()), flush=True)
"""


def _run_once(cache_dir, repo_root):
    env = dict(os.environ,
               JAX_COMPILATION_CACHE_DIR=cache_dir,
               JAX_ENABLE_COMPILATION_CACHE="true",
               CCCHECK_REPO=repo_root,
               JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS", "cpu"))
    proc = subprocess.run([sys.executable, "-c", _WORKLOAD], env=env,
                          capture_output=True, text=True, timeout=600)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("CCCHECK ")]
    if proc.returncode != 0 or not lines:
        print("check_compile_cache: workload subprocess failed (rc %d)"
              % proc.returncode)
        print(proc.stdout[-2000:])
        print(proc.stderr[-2000:])
        return None
    return json.loads(lines[-1][len("CCCHECK "):])


def main():
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cache_dir = os.path.join(repo_root, ".jax_cache_probe", "ci")
    shutil.rmtree(cache_dir, ignore_errors=True)
    os.makedirs(cache_dir)
    try:
        cold = _run_once(cache_dir, repo_root)
        if cold is None:
            return 1
        if cold["misses"] == 0:
            print("check_compile_cache: cold run performed no compiles "
                  "(%r) — the check is not exercising the cache" % cold)
            return 1
        warm = _run_once(cache_dir, repo_root)
        if warm is None:
            return 1
        if warm["misses"] != 0 or warm["hits"] == 0:
            print("check_compile_cache: FAIL — second run against a "
                  "populated cache still compiled: %d persistent-cache "
                  "miss(es), %d hit(s) (cold run: %d misses).  An "
                  "executable's cache identity is unstable across "
                  "processes; serving warm-up / CI / resume would pay "
                  "cold compiles again." % (warm["misses"], warm["hits"],
                                            cold["misses"]))
            return 1
        print("check_compile_cache: OK — cold run compiled %d "
              "executable(s), warm run loaded all %d from the cache "
              "(0 compiles, %.2fs compile time saved)"
              % (cold["misses"], warm["hits"],
                 warm.get("compile_time_saved_seconds", 0.0)))
        return 0
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
