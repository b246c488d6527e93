"""A PR's chip runs through the benchmark's own command, several in ONE
call of the chip tool. Run from the repo root (the tool copies the
git-ignored .bench_tree/ too):

    python3 benchmarks/chip_cells.py <tag> <name> <cell>:<plan>[:<first seed>] ...

Lines go to chiprun_out/<tag>/<name>/<cell>.jsonl; what a PR keeps of them
it copies to benchmarks/results/<tag>/<name>/. Trees: .bench_tree/change
(`git archive $(git write-tree)`: the files git would commit, nothing else)
and .bench_tree/parent_bench (`git archive` of the parent commit with this
tree's BENCHMARK.json and the directories of its `paths` laid over it, as
the driver lays them). Plans:
  runs<N>     N runs of the change, --trace 0, seeds first, first+1, ...
  quiet<N>    the same with the program's rings off (RAY_TPU_TIMELINE=0
              RAY_TPU_INTERNAL_TELEMETRY=0): what the tracing costs
  onoff<N>    N pairs: a `runs` run, then a `quiet` run, on seeds of
              their own
  traced      the change with --trace 1
  cold        the same with JAX_COMPILATION_CACHE_DIR a fresh directory:
              every program compiled, none loaded
  parent      parent_bench with --trace 0: must fail cleanly where the
              parent cannot run the cell
  ptraced     parent_bench with --trace 1 (an old cell under the new
              benchmark files)
  pair        parent_bench, change on one seed, --trace 0
  abba        parent_bench, change, change, parent_bench on two seeds
  below-<control>   the change through `benchmarks.precision_control
              <control>` (the program below its stated precision: `correct`
              should read false), 5 s of window
Never imports jax."""
import json
import os
import subprocess
import sys
import tempfile
import time

ROOT = os.getcwd()
TAG, NAME = sys.argv[1:3]
OUT = os.path.join(ROOT, "chiprun_out", TAG, NAME)
os.makedirs(OUT, exist_ok=True)
KEPT_NOTES = ("verdicts", "check", "losses", "last_loss", "plan",
              "setup_cache", "clock", "longest_steps", "breakdown",
              "trace_notes")


def left():
    ps = subprocess.run(["ps", "-eo", "pid,stat,cmd"], capture_output=True,
                        text=True).stdout.splitlines()[1:]
    procs = [line for line in ps
             if ("ray_tpu" in line or "chipbench" in line)
             and "chip_cells" not in line
             and int(line.split()[0]) != os.getpid()]
    return {"procs": len(procs),
            "defunct": sum("<defunct>" in line for line in ps)}


QUIET = {"RAY_TPU_TIMELINE": "0", "RAY_TPU_INTERNAL_TELEMETRY": "0"}


def one(cell, side, seed, trace, control=None, quiet=False, cold=False):
    tree = os.path.join(ROOT, ".bench_tree", side)
    command = ["-m", "chipbench.run"] if control is None else \
        ["-m", "benchmarks.precision_control", control]
    t0 = time.time()
    p = subprocess.run(
        [sys.executable, *command, "--workload", cell, "--seed", str(seed),
         "--seconds", "30" if control is None else "5", "--trace", str(trace)],
        cwd=tree, env=dict(os.environ, BENCH_RUN="builder",
                           **(QUIET if quiet else {}),
                           **({"JAX_COMPILATION_CACHE_DIR": tempfile.mkdtemp(
                               prefix="cold_cache_")} if cold else {})),
        capture_output=True, text=True)
    at_return = left()
    time.sleep(5)
    row = {"cell": cell, "side": side, "seed": seed, "traced": bool(trace),
           "rc": p.returncode, "wall_s": round(time.time() - t0 - 5, 1),
           "left_after_run": {"at_return": at_return, "5s_later": left()}}
    if control:
        row["control"] = control
    if quiet:
        row["quiet"] = True
    if cold:
        row["cold"] = True
    lines = [line for line in p.stdout.splitlines() if line.strip()]
    try:
        row.update(json.loads(lines[-1]))
        notes = [line for line in lines if line.startswith("notes: ")]
        if notes:
            n = json.loads(notes[-1][len("notes: "):])
            row["notes"] = {k: n[k] for k in KEPT_NOTES if k in n}
    except Exception as e:
        row["error"] = repr(e)
        row["stdout_tail"] = p.stdout[-1500:]
        row["stderr_tail"] = p.stderr[-2500:]
    with open(os.path.join(OUT, cell + ".jsonl"), "a") as f:
        f.write(json.dumps(row) + "\n")
    errors = (row.get("notes", {}).get("check") or {}).get("errors", {})
    print(cell, side, control or ("quiet" if quiet else ""), seed,
          "traced" if trace else "e2e",
          "rc", p.returncode, "wall", row["wall_s"],
          "correct", row.get("correct"),
          {k: round(v["value"], 4) for k, v in row.get("metrics", {}).items()},
          {k: round(v, 4) for k, v in errors.items()},
          row["left_after_run"], flush=True)
    if p.returncode and side != "parent_bench":
        print(p.stderr[-2500:], flush=True)
    return row


for spec in sys.argv[3:]:
    cell, plan, *rest = spec.split(":")
    seed = int(rest[0]) if rest else 2234036201
    if plan.startswith("runs"):
        for i in range(int(plan[4:])):
            one(cell, "change", seed + i, 0)
    elif plan.startswith("quiet"):
        for i in range(int(plan[5:])):
            one(cell, "change", seed + i, 0, quiet=True)
    elif plan.startswith("onoff"):
        for i in range(int(plan[5:])):
            one(cell, "change", seed + 2 * i, 0)
            one(cell, "change", seed + 2 * i + 1, 0, quiet=True)
    elif plan == "traced":
        one(cell, "change", seed, 1)
    elif plan == "cold":
        one(cell, "change", seed, 1, cold=True)
    elif plan == "parent":
        one(cell, "parent_bench", seed, 0)
    elif plan == "ptraced":
        one(cell, "parent_bench", seed, 1)
    elif plan == "pair":
        one(cell, "parent_bench", seed, 0)
        one(cell, "change", seed, 0)
    elif plan == "abba":
        for side, s in (("parent_bench", seed), ("change", seed),
                        ("change", seed + 1), ("parent_bench", seed + 1)):
            one(cell, side, s, 0)
    elif plan.startswith("below-"):
        one(cell, "change", seed, 0, control=plan[len("below-"):])
    else:
        raise SystemExit(f"unknown plan {plan!r}")
