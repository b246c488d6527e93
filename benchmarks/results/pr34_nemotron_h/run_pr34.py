"""PR 34's chip runs through the benchmark's own command, several in ONE
call. Run from the repo root (the chip tool copies the git-ignored
.bench_tree/ too):
    python3 benchmarks/results/pr34_nemotron_h/run_pr34.py <out-name> <cell>:<plan>[:<first seed>] ...
Trees: .bench_tree/change (`git archive $(git write-tree)`: the files git
would commit, nothing else) and .bench_tree/parent_bench (`git archive` of
the parent commit with this PR's BENCHMARK.json and `paths` laid over it, as
the driver runs the parent in a new cell and in every traced run). Plans:
  runs<N>  N runs of the change, --trace 0, seeds first, first+1, ...
  traced   the change with --trace 1
  parent   parent_bench with --trace 0: must fail cleanly where the parent
           cannot run the cell
  ptraced  parent_bench with --trace 1 (an old cell under the new benchmark files)
  pair     parent_bench, change on one seed, --trace 0
Never imports jax."""
import json, os, subprocess, sys, time

ROOT = os.getcwd()
NAME = sys.argv[1]
OUT = os.path.join(ROOT, "chiprun_out", "pr34", NAME)
os.makedirs(OUT, exist_ok=True)


def left():
    ps = subprocess.run(["ps", "-eo", "pid,stat,cmd"], capture_output=True, text=True).stdout
    me = os.getpid()
    procs = [l for l in ps.splitlines()[1:]
             if ("ray_tpu" in l or "chipbench" in l) and "run_pr34" not in l
             and int(l.split()[0]) != me]
    defunct = [l for l in ps.splitlines()[1:] if "<defunct>" in l]
    return {"procs": len(procs), "defunct": len(defunct)}


def one(cell, side, seed, trace):
    tree = os.path.join(ROOT, ".bench_tree", side)
    t0 = time.time()
    p = subprocess.run([sys.executable, "-m", "chipbench.run", "--workload", cell,
                        "--seed", str(seed), "--seconds", "30", "--trace", str(trace)],
                       cwd=tree, env=dict(os.environ, BENCH_RUN="builder"),
                       capture_output=True, text=True)
    at_return = left()
    time.sleep(5)
    row = {"cell": cell, "side": side, "seed": seed, "traced": bool(trace),
           "rc": p.returncode, "wall_s": round(time.time() - t0 - 5, 1),
           "left_after_run": {"at_return": at_return, "5s_later": left()}}
    lines = [l for l in p.stdout.splitlines() if l.strip()]
    try:
        row.update(json.loads(lines[-1]))
        notes = [l for l in lines if l.startswith("notes: ")]
        if notes:
            n = json.loads(notes[-1][len("notes: "):])
            row["notes"] = {k: n[k] for k in n if k in (
                "verdicts", "check", "losses", "last_loss", "plan", "setup_cache", "clock",
                "longest_steps", "breakdown", "trace_notes")}
    except Exception as e:
        row["error"] = repr(e)
        row["stdout_tail"] = p.stdout[-1500:]
        row["stderr_tail"] = p.stderr[-2500:]
    with open(os.path.join(OUT, cell + ".jsonl"), "a") as f:
        f.write(json.dumps(row) + "\n")
    m = row.get("metrics", {})
    errors = (row.get("notes", {}).get("check") or {}).get("errors", {})
    print(cell, side, seed, "traced" if trace else "e2e", "rc", p.returncode, "wall", row["wall_s"],
          "correct", row.get("correct"), {k: round(v["value"], 4) for k, v in m.items()},
          {k: round(v, 4) for k, v in errors.items()}, row["left_after_run"], flush=True)
    if p.returncode and side != "parent_bench":
        print(p.stderr[-2500:], flush=True)
    return row


for spec in sys.argv[2:]:
    cell, plan, *rest = spec.split(":")
    seed = int(rest[0]) if rest else 2234034201
    if plan.startswith("runs"):
        for i in range(int(plan[4:])):
            one(cell, "change", seed + i, 0)
    elif plan == "traced":
        one(cell, "change", seed, 1)
    elif plan == "parent":
        one(cell, "parent_bench", seed, 0)
    elif plan == "ptraced":
        one(cell, "parent_bench", seed, 1)
    elif plan == "pair":
        one(cell, "parent_bench", seed, 0)
        one(cell, "change", seed, 0)
    else:
        raise SystemExit(f"unknown plan {plan!r}")
