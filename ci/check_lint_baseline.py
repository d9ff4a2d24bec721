#!/usr/bin/env python
"""Fail when graftlint baseline debt accretes without a documented waiver.

The baseline ledger (``ci/graftlint/baseline.json``) exists so a NEW
pass can land before its pre-existing findings are triaged — but nothing
stopped entries from quietly living there forever: ``--update-baseline``
is one command, and a baselined finding never fails the build again.
This guard closes that hole:
at HEAD the ledger must be EMPTY, unless every entry carries a
``waiver`` field saying who accepted the debt and why::

    {"path": "mxnet_tpu/foo.py", "code": "unlocked-write", "count": 1,
     "waiver": "2026-08: pass landed with pre-triage debt; ISSUE-14"}

The waiver string should carry a date plus an issue/ROADMAP pointer.
``--update-baseline`` rewrites the ledger WITHOUT waivers, so refreshing
the baseline forces the waiver conversation to happen again — the
ratchet only tightens (stale entries are already expired by
``--prune-baseline``).

Usage: python ci/check_lint_baseline.py [baseline.json]
Wired into ci/run_tests.sh right after the graftlint run.  Exit 1 when
unwaived entries exist.
"""

from __future__ import annotations

import json
import pathlib
import sys

DEFAULT = pathlib.Path(__file__).resolve().parent / "graftlint" \
    / "baseline.json"


def check(path=DEFAULT):
    """``(failures, waived)`` — baseline entries without / with a
    documented waiver, each as ``(pass_id, entry_dict)``."""
    path = pathlib.Path(path)
    if not path.exists():
        return [], []
    data = json.loads(path.read_text())
    failures, waived = [], []
    for pass_id, entries in sorted(data.get("passes", {}).items()):
        for e in entries:
            (waived if str(e.get("waiver", "")).strip()
             else failures).append((pass_id, e))
    return failures, waived


def _describe(pass_id, entry):
    line = "%s %s [%s] %s x%d" % (
        pass_id, entry.get("path"), entry.get("code"),
        entry.get("detail", "-"), int(entry.get("count", 1)))
    if entry.get("waiver"):
        line += "  WAIVED: %s" % entry["waiver"]
    return line


def main(argv):
    path = argv[1] if len(argv) > 1 else DEFAULT
    failures, waived = check(path)
    for pass_id, entry in waived:
        print("check_lint_baseline: %s" % _describe(pass_id, entry))
    if failures:
        for pass_id, entry in failures:
            print("check_lint_baseline: UNWAIVED %s"
                  % _describe(pass_id, entry))
        print("check_lint_baseline: FAIL — %d baseline entr(ies) with "
              "no documented waiver: fix the finding, suppress it in "
              "source with '# lint: ok[pass-id] reason', or add a "
              "\"waiver\" field (date + issue pointer) to the entry in "
              "%s (see docs/linting.md \"Baselines\")"
              % (len(failures), path))
        return 1
    n = len(waived)
    print("check_lint_baseline: OK — baseline %s"
          % ("empty" if not n else "%d entr(ies), all waived" % n))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
