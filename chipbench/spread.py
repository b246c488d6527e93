"""Run one cell several times, as the driver does, and say how far the runs
spread: for every metric the distance between the quartiles over the median.

    python3 -m chipbench.spread --workload <cell> --runs 6 --first-seed 100 \\
        --out chiprun_out/<file>.jsonl [--trace 1]

Each run is the benchmark's own command in a new process with its own seed;
every result line is appended to ``--out``. A bound is set to about five
times the widest spread over the cells and the two sets, never under 1 %.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys

from chipbench import catalog


def spread(values) -> float:
    """The contract's spread: the quartiles as ``statistics.quantiles``
    gives them (numpy's lie closer together: a set of six with one run
    7.6 % off reads 2.17 % here and 0.28 % there, PR 23's set 2)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return (q3 - q1) / abs(median) if median else float("nan")


def spread_without_farthest(values) -> float:
    """What the driver holds against half a bound: the spread with the run
    farthest from the median left out, where that narrows it."""
    median = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - median))
    rest = [v for i, v in enumerate(values) if i != far]
    return min(spread(values), spread(rest)) if len(rest) > 1 \
        else spread(values)


def summarize(lines) -> dict:
    names = sorted({name for line in lines for name in line["metrics"]})
    out = {}
    for name in names:
        values = [line["metrics"][name]["value"] for line in lines
                  if name in line["metrics"]]
        out[name] = {"median": statistics.median(values),
                     "spread": spread(values),
                     "spread_without_farthest":
                         spread_without_farthest(values),
                     "runs": len(values)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--first-seed", type=int, default=100)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    manifest = catalog.load_manifest()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    lines = []
    for i in range(args.runs):
        proc = subprocess.run(
            manifest["command"] + [
                "--workload", args.workload, "--seed",
                str(args.first_seed + i), "--seconds",
                str(manifest["run_seconds"]), "--trace", str(args.trace)],
            cwd=catalog.ROOT, stdout=subprocess.PIPE, text=True)
        if proc.returncode != 0:
            print(f"run {i} exited {proc.returncode}", file=sys.stderr)
            return proc.returncode
        out = proc.stdout.strip().splitlines()
        line = dict(json.loads(out[-1]), seed=args.first_seed + i)
        for earlier in out[:-1]:
            if earlier.startswith("notes: "):
                line["notes"] = json.loads(earlier[len("notes: "):])
        lines.append(line)
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
    print(json.dumps({"workload": args.workload, "correct": all(
        line["correct"] for line in lines), "metrics": summarize(lines)},
        indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
