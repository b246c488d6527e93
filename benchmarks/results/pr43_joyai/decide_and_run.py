"""One call: which scales keep every routed layer under its bound on six seeds, then the
benchmark's own runs of the cell with them. Never imports jax."""
import json, os, re, shutil, subprocess, sys, time

START, LIMIT = time.time(), 3440
ROOT = os.getcwd()
OUT = os.path.join(ROOT, "chiprun_out", "pr43_joyai")
os.makedirs(OUT, exist_ok=True)
SEEDS = [3100430101, 3100430104, 3100430105, 3100430106, 3100430107, 3100430108]
BAD = [3100430101, 3100430106, 3100430108]
DRY = os.environ.get("DRY") == "1"
STEPS, BOUND = (3, 256) if DRY else (36, 16384)


def left():
    return LIMIT - (time.time() - START)


def probe(overrides, seeds):
    args = [f"{k}={v}" for k, v in overrides.items()]
    p = subprocess.run([sys.executable, "benchmarks/held_by_layer.py",
                        "joyaiflash5l-b2s8k", str(STEPS), *args, *map(str, seeds)], capture_output=True, text=True)
    rows = []
    for line in p.stdout.splitlines():
        if line.startswith("{"):
            rows.append(json.loads(line))
    if not rows:
        print("probe failed", p.stderr[-3000:], flush=True)
    return rows


def judge(rows):
    """(layer-steps over the bound, the largest share of the bound a layer reached)."""
    over, top = 0, 0.0
    for r in rows:
        layers = [k for k in r if k.startswith("held_")]
        for k in layers:
            top = max(top, max(r[k]) / BOUND)
            over += sum(1 - c for c in r["compact_" + k[5:]])
    return over, round(top, 3)


def show(name, rows):
    for r in rows:
        layers = sorted(k for k in r if k.startswith("held_"))
        print(name, r["seed"], "max held a layer", [max(r[k]) for k in layers],
              "first step over", [next((i for i, c in enumerate(r["compact_" + k[5:]]) if not c), None)
                                  for k in layers],
              "step ms 2/mid/last", r["step_ms"][2], r["step_ms"][len(r["step_ms"]) // 2], r["step_ms"][-1], flush=True)
    print(name, "judge", judge(rows), "seconds left", round(left()), flush=True)


results = {}
rows = probe({}, BAD)
show("filed", rows)
results["filed"] = judge(rows)
CANDIDATES = [("eh4", {"eh_std": 4.0}),
              ("eh4_table2048", {"eh_std": 4.0, "embed_std": 2048.0}),
              ("eh1", {"eh_std": 1.0})]
chosen = None
for name, overrides in CANDIDATES:
    if left() < 2450:       # the runs behind need 40 minutes
        break
    rows = probe(overrides, SEEDS)
    show(name, rows)
    results[name] = judge(rows)
    if rows and results[name][0] == 0 and results[name][1] <= 0.8:
        chosen = name
        break
if chosen is None:
    tried = [n for n, _ in CANDIDATES if n in results]
    chosen = min(tried, key=lambda n: results[n]) if tried else "eh4"
overrides = dict(CANDIDATES)[chosen]
print("chosen", chosen, overrides, results, flush=True)

path = os.path.join(ROOT, ".bench_tree", "change", "ray_tpu", "models", "joyai.py")
text = open(path).read()
old = "embed_std=512.0,\n                       router_std=0.15)"
assert old in text, "the preset's line moved"
new = (f"embed_std={overrides.get('embed_std', 512.0)},\n"
       f"                       router_std=0.15, eh_std={overrides['eh_std']})")
open(path, "w").write(text.replace(old, new))
shutil.copy(path, os.path.join(OUT, "joyai_as_run.py"))
with open(os.path.join(OUT, "decision.json"), "w") as f:
    json.dump({"chosen": chosen, "overrides": overrides, "judged": results}, f)

C = "joyaiflash5l-b2s8k"


def cells(*specs):
    if DRY:
        return print("would run", specs)
    subprocess.run([sys.executable, "benchmarks/chip_cells.py", "pr43_joyai", "final2", *specs])


cells(f"{C}:traced:3100430111")
for seed in range(3100430112, 3100430118):
    if left() > 470:
        cells(f"{C}:runs1:{seed}")
if left() > 520:
    cells(f"{C}:below-e4m3:3100430119")
print("done, seconds left", round(left()), flush=True)
