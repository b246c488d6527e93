"""PR 33's chip runs through the benchmark's own command, parent and change
in turn in ONE call. Run from the repo root (copied to .bench_tree/):
    python3 .bench_tree/run_pr33.py <out-name> <cell>:<plan>[:<first seed>] ...
Trees: .bench_tree/parent (`git archive` of the parent commit),
.bench_tree/change (`git archive $(git write-tree)`), and
.bench_tree/parent_bench (the parent with this PR's BENCHMARK.json and
`paths` laid over it, as the driver runs a traced parent). Plans:
  pairs  parent, change, change, parent on two seeds (the first run of a
         side dumps its jit_step), then the dumped programs compared
  pair   parent, change on one seed
  traced parent_bench, change with --trace 1 on one seed
Never imports jax."""
import json, os, subprocess, sys, time

ROOT = os.getcwd()
NAME = sys.argv[1]
OUT = os.path.join(ROOT, "chiprun_out", "pr33", NAME)
os.makedirs(OUT, exist_ok=True)


def left():
    ps = subprocess.run(["ps", "-eo", "pid,stat,cmd"], capture_output=True, text=True).stdout
    me = os.getpid()
    procs = [l for l in ps.splitlines()[1:]
             if ("ray_tpu" in l or "chipbench" in l) and "run_pr33" not in l
             and int(l.split()[0]) != me]
    defunct = [l for l in ps.splitlines()[1:] if "<defunct>" in l]
    return {"procs": len(procs), "defunct": len(defunct)}


def one(cell, side, seed, trace, dump=False):
    tree = os.path.join(ROOT, ".bench_tree", side)
    env = dict(os.environ)
    if dump:
        d = os.path.join(OUT, "hlo", cell, side)
        os.makedirs(d, exist_ok=True)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_dump_to={d} --xla_dump_hlo_as_text"
                            " --xla_dump_hlo_module_re=jit_step").strip()
    t0 = time.time()
    p = subprocess.run([sys.executable, "-m", "chipbench.run", "--workload", cell,
                        "--seed", str(seed), "--seconds", "30", "--trace", str(trace)],
                       cwd=tree, env=env, capture_output=True, text=True)
    at_return = left()
    time.sleep(5)
    row = {"cell": cell, "side": side, "seed": seed, "traced": bool(trace),
           "dumped": bool(dump), "rc": p.returncode, "wall_s": round(time.time() - t0, 1),
           "left_after_run": {"at_return": at_return, "5s_later": left()}}
    lines = [l for l in p.stdout.splitlines() if l.strip()]
    try:
        row.update(json.loads(lines[-1]))
        notes = [l for l in lines if l.startswith("notes: ")]
        if notes:
            n = json.loads(notes[-1][len("notes: "):])
            row["notes"] = {k: n[k] for k in n if k in ("check", "compile", "steps", "longest_steps", "cache", "breakdown", "trace_notes")}
    except Exception as e:
        row["error"] = repr(e)
        row["stdout_tail"] = p.stdout[-2000:]
        row["stderr_tail"] = p.stderr[-3000:]
    with open(os.path.join(OUT, cell + ".jsonl"), "a") as f:
        f.write(json.dumps(row) + "\n")
    m = row.get("metrics", {})
    print(cell, side, seed, "traced" if trace else "e2e", "rc", p.returncode, "correct", row.get("correct"),
          {k: round(v["value"], 4) for k, v in m.items()}, row["left_after_run"], flush=True)
    return row


def compare_programs(cell):
    a, b = (os.path.join(OUT, "hlo", cell, s) for s in ("parent", "change"))
    cmp_ = subprocess.run([sys.executable, os.path.join(ROOT, "benchmarks", "step_hlo_compare.py"), a, b],
                          env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True)
    with open(os.path.join(OUT, "same_program.jsonl"), "a") as f:
        f.write(json.dumps({"cell": cell, "rc": cmp_.returncode, "stdout": cmp_.stdout,
                            "stderr_tail": cmp_.stderr[-1500:]}) + "\n")
    print(cell, "PROGRAM COMPARE rc", cmp_.returncode, cmp_.stdout.strip().splitlines()[-1:], flush=True)
    # keep only the after-optimisation texts, gzipped, to fit what comes back
    for d in (a, b):
        for n in os.listdir(d) if os.path.isdir(d) else ():
            path = os.path.join(d, n)
            if "optimizations" in n and "jit_step" in n and n.endswith(".txt"):
                subprocess.run(["gzip", "-f", path])
            elif os.path.isfile(path):
                os.remove(path)


for spec in sys.argv[2:]:
    cell, plan, *rest = spec.split(":")
    seed = int(rest[0]) if rest else 2233033001
    if plan == "pairs":
        one(cell, "parent", seed, 0, dump=True)
        one(cell, "change", seed, 0, dump=True)
        one(cell, "change", seed + 1, 0)
        one(cell, "parent", seed + 1, 0)
        compare_programs(cell)
    elif plan == "pair":
        one(cell, "parent", seed, 0)
        one(cell, "change", seed, 0)
    elif plan == "riap":
        one(cell, "change", seed, 0)
        one(cell, "parent", seed, 0)
    elif plan == "traced":
        one(cell, "parent_bench", seed, 1)
        one(cell, "change", seed, 1)
    else:
        raise SystemExit(f"unknown plan {plan!r}")
