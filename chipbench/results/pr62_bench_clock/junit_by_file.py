"""Case-seconds by test file from a pytest junit file (CPU host, never a
device number):

    python3 junit_by_file.py <junit.xml> [<prefix>]

one line a file under `<prefix>` (default `tests/chipbench_tests/`), the
directory's total, the whole run's total, its counts and the cases over
45 s. A case's seconds are its set-up, call and tear-down, as pytest's
junit adds them."""
import collections
import sys
import xml.etree.ElementTree as ET


def main(path, prefix="tests/chipbench_tests/"):
    suite = ET.parse(path).getroot()
    suite = suite if suite.tag == "testsuite" else suite[0]
    by_file = collections.defaultdict(lambda: [0.0, 0])
    long_cases, total = [], 0.0
    for case in suite.iter("testcase"):
        seconds = float(case.get("time", 0))
        name = case.get("classname", "").replace(".", "/") + ".py"
        total += seconds
        by_file[name][0] += seconds
        by_file[name][1] += 1
        if seconds > 45:
            long_cases.append((seconds, f"{name}::{case.get('name')}"))
    counts = {k: int(suite.get(k, 0))
              for k in ("tests", "errors", "failures", "skipped")}
    passed = counts["tests"] - counts["errors"] - counts["failures"] \
        - counts["skipped"]
    print(f"{path}: {counts} passed={passed} wall={suite.get('time')} s "
          f"case-seconds={total:.1f}")
    inside = {k: v for k, v in by_file.items() if k.startswith(prefix)}
    for name, (seconds, n) in sorted(inside.items(), key=lambda kv: -kv[1][0]):
        print(f"{seconds:8.1f} s {n:4d} cases  {name}")
    print(f"{sum(v[0] for v in inside.values()):8.1f} s "
          f"{sum(v[1] for v in inside.values()):4d} cases  {prefix} in all")
    for seconds, name in sorted(long_cases, reverse=True):
        print(f"over 45 s: {seconds:6.1f} s  {name}")


if __name__ == "__main__":
    main(*sys.argv[1:])
