"""Test config: force a CPU-only 8-device virtual mesh BEFORE jax initializes.

Mirrors the reference's fake-device fixture strategy (SURVEY §4: multi-device
tests use mx.cpu(0)/mx.cpu(1) contexts without a cluster) — 8 virtual CPU
devices stand in for an 8-chip TPU slice, so sharding/collective paths
compile and run in CI.

The persistent compile cache is on by default for users
(``mxnet_tpu.compile_cache``); the suite keeps it OFF through JAX's own
switch so CPU test programs never land in the checkout's ``.jax_cache``.
Tests that exercise the cache enable it on a ``tmp_path`` themselves, and
the example/worker subprocesses the suite starts inherit the same
environment.
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow'); fault-"
        "injection tests must stay fast enough to NOT need this")


def pytest_sessionfinish(session, exitstatus):
    """Dump real op-invocation counts (OpDef.apply calls) when asked:
    MXNET_OP_COVERAGE_OUT=path pytest tests/ ... writes {op: count}.
    tools/gen_op_census.py consumes the dump so the census coverage
    column counts executions, not word-grep mentions."""
    try:
        from mxnet_tpu.test_utils import dump_op_coverage
    except Exception:
        return
    dump_op_coverage("OpDef.apply call counts from one pytest session")
