#!/bin/sh
# Local CI entry point (the reference's tests/travis/run_test.sh analog):
# byte-compile -> graftlint + baseline guard -> native build -> unit suite
# with its pinned skips -> multichip dryrun -> mesh smoke -> trace smoke ->
# compile-cache check -> (CHAOS=1) kill/resume chaos matrix.
#
#   sh ci/run_tests.sh precommit   # fast lane: diff-scoped lint only
#
set -e
cd "$(dirname "$0")/.."
# pre-commit lane (docs/linting.md "The --changed lane"): lint ONLY the
# *.py files that differ from PRECOMMIT_REV (default HEAD) — per-file
# passes skip unchanged files, interprocedural passes keep whole-tree
# call-graph context but report changed files only.  Budgeted <5s;
# the run exports lint.changed_run_seconds through telemetry.
if [ "${1:-}" = "precommit" ]; then
  python -m ci.graftlint --changed "${PRECOMMIT_REV:-HEAD}" \
    --emit-telemetry
  exit 0
fi
python -m compileall -q mxnet_tpu tools example
# unified static analysis (docs/linting.md): ONE invocation runs every
# graftlint pass — the five migrated syntactic lints (bare-except,
# print, env-docs, host-sync, signal-restore; their ci/check_*.py shims
# were deleted after the deprecation cycle), the dataflow passes
# (tracer-purity, recompile-hazard, donation, lock-discipline), and the
# interprocedural SPMD/distributed-correctness passes
# (collective-consistency, replica-divergence, spec-shape,
# state-protocol) — over mxnet_tpu/, honoring the shared
# '# lint: ok[pass-id] <reason>' suppression grammar and the per-pass
# baselines.  The JSON findings report lands at /tmp/graftlint.json as
# a CI artifact, and per-pass finding counts export through telemetry
# (lint.findings gauges) so lint debt can be tracked.
python -m ci.graftlint --json /tmp/graftlint.json --emit-telemetry
# baseline-debt guard: the ledger must be empty at HEAD unless every
# entry carries a documented waiver: baseline debt cannot silently
# accrete.
python ci/check_lint_baseline.py
if command -v g++ > /dev/null; then
  g++ -O2 -shared -fPIC -std=c++17 -o libmxnet_tpu_native.so \
      src/native.cc -lpthread
fi
# -rs surfaces skip reasons; the expected-skip pin below fails the run
# if a test starts silently skipping for a NEW reason (a silent skip
# can hide a regression behind a green suite)
rc=0
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python -m pytest tests/ -q -rs > /tmp/ci_pytest.log 2>&1 || rc=$?
tail -40 /tmp/ci_pytest.log
[ "$rc" -eq 0 ] || exit "$rc"
# expected skips, pinned by REASON (an allowlist, so a test that starts
# skipping for a NEW reason fails the run).  Legitimate classes: the
# f32-only gamma/gammaln lowerings skip their f64 sweep cases (always,
# pinned to exactly 4 below), and environment-gated tests skip where
# their toolchain piece is absent (perl/gcc/g++/make/cmake/ninja/
# OpenCV dev headers — the native build above already treats g++ as
# optional).
allow='f32-only lowering|needs perl \+ toolchain'
allow="$allow|needs a C(/C\\+\\+|\\+\\+)? toolchain"
allow="$allow|native toolchain unavailable|cmake|ninja|OpenCV|opencv"
unexpected=$(grep '^SKIPPED' /tmp/ci_pytest.log \
  | grep -vcE "$allow" || true)
if [ "$unexpected" -gt 0 ]; then
  echo "CI FAIL: tests skipped for unexpected reasons ($unexpected)"
  grep '^SKIPPED' /tmp/ci_pytest.log || true
  exit 1
fi
# the f64 sweep skips are environment-independent: exactly 4, always
f64_skips=$(grep '^SKIPPED' /tmp/ci_pytest.log \
  | grep 'f32-only lowering' \
  | sed 's/^SKIPPED \[\([0-9]*\)\].*/\1/' \
  | awk '{s+=$1} END {print s+0}')
if [ "${f64_skips:-0}" -ne 4 ]; then
  echo "CI FAIL: expected exactly 4 f32-only-lowering skips," \
       "got ${f64_skips:-0}"
  grep '^SKIPPED' /tmp/ci_pytest.log || true
  exit 1
fi
python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"
# 8-virtual-device mesh smoke (docs/how_to/multi_devices.md "Sharded
# fit"): fit(kvstore='mesh') trains with the in-graph gradient plane +
# ZeRO-sharded updates, is killed mid-epoch, and resumes bit-identically
# from its sharded snapshots — the kvstore='mesh' acceptance, explicit
# even though the full suite above also runs it.
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python -m pytest tests/test_mesh_kvstore.py -q -p no:cacheprovider \
  -k "zero_per_step or shards_optimizer_state or kill_resume"
# trace smoke (docs/observability.md "Distributed tracing & fleet
# aggregation"): MXNET_TRACE=1 over a tiny fit and one HTTP /generate —
# every span tree must be rooted with zero orphans, and GET /trace/<id>
# must serve the request's tree back.
python ci/check_trace_smoke.py
# compile-once effectiveness: a small fit+predict runs twice against a
# temp persistent compile cache; the second run must perform ZERO XLA
# compilations (every executable loads from the cache) — unstable cache
# identities re-introduce cold warm-up costs in serving/CI/resume.
# (also runnable as the orchestrated graftlint pass 'compile-cache')
python ci/check_compile_cache.py
# kill/resume chaos matrix (5x rotating seeds) — opt-in, it multiplies
# suite time: CHAOS=1 sh ci/run_tests.sh
if [ "${CHAOS:-0}" = "1" ]; then
  sh ci/run_chaos.sh
fi
echo "CI OK"
