"""PR 32's chip runs: for each cell, parent and change in turn through the
benchmark's own command, the first run of each side with an XLA dump of the
train step; then a traced run a side; then the dumped programs compared.
Run from the repo root: python3 .bench_tree/run_pr32.py <cell> [<cell> ...]
Trees: .bench_tree/parent, .bench_tree/change. Never imports jax."""
import json, os, subprocess, sys, time

ROOT = os.getcwd()
OUT = os.path.join(ROOT, "chiprun_out", "pr32")
os.makedirs(OUT, exist_ok=True)
SEEDS = {"pair1": 32101, "pair2": 32102, "traced": 32103}


def left():
    ps = subprocess.run(["ps", "-eo", "pid,stat,cmd"], capture_output=True, text=True).stdout
    me = os.getpid()
    procs = [l for l in ps.splitlines()[1:]
             if ("ray_tpu" in l or "chipbench" in l) and "run_pr32" not in l
             and int(l.split()[0]) != me]
    defunct = [l for l in ps.splitlines()[1:] if "<defunct>" in l]
    return {"procs": len(procs), "defunct": len(defunct)}


def one(cell, side, seed, trace, dump):
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
            row["notes"] = {k: n[k] for k in n if k in ("check", "compile", "steps", "longest_steps", "cache")}
    except Exception as e:
        row["error"] = repr(e)
        row["stdout_tail"] = p.stdout[-2000:]
        row["stderr_tail"] = p.stderr[-3000:]
    with open(os.path.join(OUT, cell + ".jsonl"), "a") as f:
        f.write(json.dumps(row) + "\n")
    m = row.get("metrics", {})
    print(cell, side, "traced" if trace else "e2e", "rc", p.returncode, "correct", row.get("correct"),
          {k: round(v["value"], 4) for k, v in m.items()}, row["left_after_run"], flush=True)
    return row


for cell in sys.argv[1:]:
    one(cell, "parent", SEEDS["pair1"], 0, True)
    one(cell, "change", SEEDS["pair1"], 0, True)
    one(cell, "change", SEEDS["pair2"], 0, False)
    one(cell, "parent", SEEDS["pair2"], 0, False)
    one(cell, "parent", SEEDS["traced"], 1, False)
    one(cell, "change", SEEDS["traced"], 1, False)
    a, b = (os.path.join(OUT, "hlo", cell, s) for s in ("parent", "change"))
    for d in (a, b):
        names = sorted(os.listdir(d)) if os.path.isdir(d) else []
        print(cell, d, len(names), "files", names[:12], flush=True)
    cmp_ = subprocess.run([sys.executable, os.path.join(ROOT, "benchmarks", "step_hlo_compare.py"), a, b],
                          env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True)
    verdict = {"cell": cell, "rc": cmp_.returncode, "stdout": cmp_.stdout, "stderr_tail": cmp_.stderr[-1500:]}
    with open(os.path.join(OUT, "same_program.jsonl"), "a") as f:
        f.write(json.dumps(verdict) + "\n")
    print(cell, "PROGRAM COMPARE rc", cmp_.returncode, cmp_.stdout.strip().splitlines()[-1:] , flush=True)
    # keep only the after-optimisation texts, gzipped, to fit what comes back
    for d in (a, b):
        if not os.path.isdir(d):
            continue
        for n in os.listdir(d):
            path = os.path.join(d, n)
            if "optimizations" in n and "jit_step" in n and n.endswith(".txt"):
                subprocess.run(["gzip", "-f", path])
            else:
                os.remove(path) if os.path.isfile(path) else None
