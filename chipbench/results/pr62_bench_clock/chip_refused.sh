# PR 62, second session (after BENCHMARK_REFUSED.md): the driver's seed of
# `phi4flash6l-b1s8k` run again as the driver ran it, then the look at the
# compared leaves over that seed, PR 58's and eight fresh ones.
#   chiprun --chips 1 --timeout 3000 -- bash chipbench/results/pr62_bench_clock/chip_refused.sh
set -u
root=$(pwd); out=$root/chiprun_out/pr62; mkdir -p $out
python3 -m chipbench.run --workload phi4flash6l-b1s8k --seed 314767261 --seconds 30 --trace 0 \
  > $out/refused_314767261.out 2> $out/refused_314767261.err
echo "refused seed rc=$?"; tail -n 2 $out/refused_314767261.out | cut -c1-6000
python3 chipbench/results/pr62_bench_clock/leaf_look.py phi4flash6l-b1s8k --f32 \
  314767261 2124027345 3100620301 3100620302 3100620303 3100620304 \
  3100620305 3100620306 3100620307 3100620308 \
  > $out/leaf_look.out 2> $out/leaf_look.err
echo "look rc=$?"; tail -n 3 $out/leaf_look.err | cut -c1-1500
python3 - <<'PY'
import json
for line in open("chiprun_out/pr62/leaf_look_phi4flash6l-b1s8k.jsonl"):
    d = json.loads(line)
    for name in ("stated", "attention_f32"):
        r = d.get(name, {})
        if "errors" not in r:
            print(d["run_seed"], name, r); continue
        e = r["errors"]
        top = sorted(((v, k) for k, v in e.items() if k != "loss"), reverse=True)[:4]
        print(d["run_seed"], name, "loss %.2e" % e["loss"], " ".join("%s %.4f" % (k, v) for v, k in top),
              "| lam", {k: [round(x, 3) for x in v] for k, v in d["lambda"].items()}, d["seconds"])
PY
