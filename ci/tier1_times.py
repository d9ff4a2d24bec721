#!/usr/bin/env python
"""Where a tier-1 run's time went, from its junit file.

The driver runs ``tests/`` on six workers with ``--dist loadfile``: a file
is one worker's from its first case to its last, so the run's wall time is
at least its longest file's, and a file cannot end before its longest
case.  With the test-seconds spread evenly a worker's share is
``total / workers``; the two rules (ROADMAP D16) keep every file and every
case well inside it:

- no file over 60% of ``total / workers``;
- no case over 15% of it.

Usage: python ci/tier1_times.py <junit.xml> [workers]   (workers: 6)

Prints the files over 10 s in order of their seconds with the longest case
of each, the cases over 10 s, the total and a worker's share; exit 1 when a rule
is broken, with the files and cases that break it named.
"""

import collections
import sys
import xml.etree.ElementTree as ET

FILE_SHARE, CASE_SHARE = 0.60, 0.15
#: a file and a case are listed from here on, seconds
LISTED_FROM_S = 10.0


def read(path):
    """``[(file, case, seconds)]`` of a junit file's test cases."""
    return [("%s.py" % case.get("classname", "").replace(".", "/"),
             case.get("name"), float(case.get("time", 0.0)))
            for case in ET.parse(path).iter("testcase")]


def report(cases, workers=6):
    """``(lines, broken)``: the table as lines of text, and the lines that
    name what breaks a rule."""
    total = sum(s for _f, _c, s in cases)
    share = total / workers
    files = collections.defaultdict(list)
    for f, c, s in cases:
        files[f].append((s, c))
    lines = ["%d cases, %.1f test-seconds; a worker's share of %d: %.1f s; "
             "a file may take %.1f s, a case %.1f s"
             % (len(cases), total, workers, share, FILE_SHARE * share,
                CASE_SHARE * share), "",
             "| file | cases | seconds | the longest case in it |",
             "|---|---|---|---|"]
    broken, under = [], []
    for f, took in sorted(files.items(), key=lambda kv: -sum(
            s for s, _c in kv[1])):
        seconds, (longest, name) = sum(s for s, _c in took), max(took)
        if seconds > LISTED_FROM_S:
            lines.append("| `%s` | %d | %.1f | `%s` %.1f |"
                         % (f, len(took), seconds, name, longest))
        else:
            under.append(seconds)
        if seconds > FILE_SHARE * share:
            broken.append("file %s: %.1f s, over %.1f" % (
                f, seconds, FILE_SHARE * share))
    lines.append("| %d files under %.0f s | | %.1f | |"
                 % (len(under), LISTED_FROM_S, sum(under)))
    listed = sorted(((s, f, c) for f, c, s in cases if s > LISTED_FROM_S),
                    reverse=True)
    lines += ["", "%d cases over %.0f s, %.1f s of the %.1f:"
              % (len(listed), LISTED_FROM_S, sum(s for s, _f, _c in listed),
                 total)]
    for s, f, c in listed:
        lines.append("%7.1f  %s::%s" % (s, f, c))
        if s > CASE_SHARE * share:
            broken.append("case %s::%s: %.1f s, over %.1f" % (
                f, c, s, CASE_SHARE * share))
    return lines, broken


def main(argv):
    if not 2 <= len(argv) <= 3:
        print(__doc__)
        return 2
    lines, broken = report(read(argv[1]),
                           int(argv[2]) if len(argv) == 3 else 6)
    print("\n".join(lines))
    if broken:
        print("\nbroken:\n" + "\n".join(broken))
    return 1 if broken else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
