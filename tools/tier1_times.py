"""Seconds by test file of one tier-1 run, read from the JUnit XML that the
run's ``--junitxml`` wrote: ``python -m tools.tier1_times /tmp/_t1.xml``.

Prints each file's cases and seconds (every case's ``time``: set-up, call
and teardown), longest first, their sum, the sum over the run's six
workers (no run ends sooner), the run's own seconds and the twenty
longest cases. Exits 1 where a file is over ``FILE_LIMIT_S``: xdist's
``--dist loadfile`` runs a file on one worker, so the longest file is the
run's tail.
"""

import collections
import sys
import xml.etree.ElementTree as ET

FILE_LIMIT_S = 150
WORKERS = 6
LONGEST_CASES = 20


def read(path):
    """``(cases, run_seconds)``: ``cases`` a list of ``(seconds, file,
    case id)``, ``file`` the case's ``classname`` as a path."""
    suite = ET.parse(path).getroot()
    if suite.tag == "testsuites":
        suite = suite[0]
    cases = [
        (
            float(case.get("time", 0)),
            case.get("classname", "").replace(".", "/") + ".py",
            case.get("name", ""),
        )
        for case in suite.iter("testcase")
    ]
    return cases, float(suite.get("time", 0))


def report(path):
    """The table as text, and the files over the limit."""
    cases, run_seconds = read(path)
    by_file = collections.defaultdict(lambda: [0, 0.0])
    for seconds, file, _ in cases:
        by_file[file][0] += 1
        by_file[file][1] += seconds
    rows = sorted(by_file.items(), key=lambda row: -row[1][1])
    total = sum(seconds for seconds, _, _ in cases)
    lines = [f"{'cases':>5} {'seconds':>8}  file"]
    lines += [f"{n:5d} {s:8.1f}  {file}" for file, (n, s) in rows]
    lines += [
        f"{len(cases):5d} {total:8.1f}  sum of {len(rows)} files",
        f"      {total / WORKERS:8.1f}  sum over {WORKERS}",
        f"      {run_seconds:8.1f}  the run's own seconds",
        f"the {LONGEST_CASES} longest cases:",
    ]
    lines += [
        f"      {seconds:8.1f}  {file}::{name}"
        for seconds, file, name in sorted(cases, reverse=True)[:LONGEST_CASES]
    ]
    over = [file for file, (_, s) in rows if s > FILE_LIMIT_S]
    return "\n".join(lines), over


def main(argv):
    if len(argv) != 1:
        sys.exit(__doc__)
    text, over = report(argv[0])
    print(text)
    if over:
        print(f"over {FILE_LIMIT_S} s a file: " + ", ".join(over))
    return 1 if over else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
