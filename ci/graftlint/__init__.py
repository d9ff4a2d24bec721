"""graftlint — the unified static-analysis framework for this repo.

One shared AST walker, one suppression grammar (``# lint: ok[pass-id]
<reason>``), one baseline ledger, one output format (human + JSON), and
a pluggable pass registry; ``python -m ci.graftlint`` runs everything
over ``mxnet_tpu/`` in seconds.  See docs/linting.md for the pass
catalog, the suppression grammar, and the baseline workflow.

The five historical ``ci/check_*.py`` lint scripts were removed after
their deprecation cycle (graftlint v2): run the migrated passes with
``--pass bare-except`` / ``print`` / ``env-docs`` / ``host-sync`` /
``signal-restore`` instead.  Legacy suppression comments (``# noqa``,
``# host-sync: ok``) are still honored forever.  ``check_compile_cache``
stays a full script but is also exposed as an orchestrated pass.
"""

from __future__ import annotations

import sys

from .core import Finding, Pass, RunContext, Source  # noqa: F401 re-export
from .passes import ALL_PASSES, DEFAULT_PASSES, by_id  # noqa: F401
from .runner import run, run_pass  # noqa: F401


def changed_files(rev="HEAD", repo=None):
    """Repo-relative ``*.py`` paths differing from ``rev`` (committed,
    staged, or worktree) plus untracked ones — the ``--changed`` lane's
    scope.  Returns None when git is unavailable (the caller falls back
    to a full run rather than silently linting nothing)."""
    import pathlib
    import subprocess

    from .core import REPO

    repo = pathlib.Path(repo) if repo else REPO
    try:
        diff = subprocess.run(
            ["git", "diff", "--name-only", rev, "--", "*.py"],
            cwd=str(repo), capture_output=True, text=True, timeout=30)
        untracked = subprocess.run(
            ["git", "ls-files", "--others", "--exclude-standard",
             "--", "*.py"],
            cwd=str(repo), capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        return None
    if diff.returncode != 0 or untracked.returncode != 0:
        # either listing failing must trigger the full-run fallback —
        # a silently-empty untracked list would let a brand-new file
        # sail through the pre-commit lane unlinted
        return None
    names = set()
    for line in diff.stdout.splitlines() + untracked.stdout.splitlines():
        line = line.strip()
        if line.endswith(".py"):
            names.add(line)
    return names


def main(argv=None):
    """``python -m ci.graftlint`` — see ``--help``."""
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m ci.graftlint",
        description="unified static-analysis runner (docs/linting.md)")
    parser.add_argument("roots", nargs="*",
                        help="files/dirs to scan (default: each pass's "
                             "own roots under the repo)")
    parser.add_argument("--pass", dest="passes", action="append",
                        metavar="ID",
                        help="run only this pass (repeatable); "
                             "the orchestrated pass (compile-cache) "
                             "only runs when named here")
    parser.add_argument("--changed", nargs="?", const="HEAD",
                        metavar="REV",
                        help="diff-scoped fast lane: only report on "
                             "*.py files changed vs REV (default HEAD; "
                             "includes staged/worktree/untracked). "
                             "Per-file passes skip unchanged files; "
                             "interprocedural passes still see the "
                             "whole tree for call-graph context")
    parser.add_argument("--list", action="store_true",
                        help="list passes and exit")
    parser.add_argument("--json", metavar="PATH",
                        help="write the machine-readable findings "
                             "report here (the CI artifact)")
    parser.add_argument("--update-baseline", action="store_true",
                        help="rewrite the baseline ledger from the "
                             "current findings and exit 0")
    parser.add_argument("--prune-baseline", action="store_true",
                        help="drop stale baseline entries (whose "
                             "findings no longer fire)")
    parser.add_argument("--baseline", metavar="PATH",
                        help="baseline ledger path (default: "
                             "ci/graftlint/baseline.json)")
    parser.add_argument("--emit-telemetry", action="store_true",
                        help="export per-pass finding counts through "
                             "mxnet_tpu.telemetry (lint.findings "
                             "gauges; lint.changed_run_seconds for "
                             "--changed runs)")
    args = parser.parse_args(argv)

    if args.list:
        for cls in ALL_PASSES:
            kind = "orchestrated" if cls.orchestrated else (
                "project" if cls.interprocedural else "analysis")
            print("%-22s %-12s %s" % (cls.id, kind, cls.title))  # noqa: CLI output
        return 0

    if args.passes:
        passes = [by_id(p)() for p in args.passes]
    else:
        passes = [cls() for cls in DEFAULT_PASSES]

    changed = None
    if args.changed is not None:
        changed = changed_files(args.changed)
        if changed is None:
            print("graftlint: --changed: git unavailable, falling back "
                  "to a full run")  # noqa: CLI output
        elif not changed:
            print("graftlint: --changed: no *.py changes vs %s — "
                  "nothing to lint" % args.changed)  # noqa: CLI output
            return 0

    from . import baseline as _baseline

    kwargs = {}
    if args.baseline:
        kwargs["baseline_path"] = args.baseline
    else:
        kwargs["baseline_path"] = _baseline.DEFAULT_PATH
    ctx = RunContext(roots=args.roots or None, changed=changed)
    return run(passes, ctx=ctx, json_path=args.json,
               update_baseline=args.update_baseline,
               prune_baseline=args.prune_baseline,
               emit_telemetry=args.emit_telemetry, **kwargs)


if __name__ == "__main__":  # pragma: no cover - exercised via __main__
    sys.exit(main())
