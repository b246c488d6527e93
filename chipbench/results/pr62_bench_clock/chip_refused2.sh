# PR 62, second session, second call: the lambdas of 400 seeds' draws, the
# look on the drawn and the conditioned parameters (the driver's seed, PR 58's,
# the four of the scan nearest lambda = 1, ten fresh ones; the e4m3 control on
# the conditioned), then the two refused seeds through the benchmark's command
# with `accounting.conditioned` in the comparison.
#   chiprun --chips 1 --timeout 3400 -- bash chipbench/results/pr62_bench_clock/chip_refused2.sh
set -u
root=$(pwd); out=$root/chiprun_out/pr62; mkdir -p $out
python3 chipbench/results/pr62_bench_clock/leaf_look.py phi4flash6l-b1s8k --scan=400 --e4m3 \
  3100620400 314767261 2124027345 3100620311 3100620312 3100620313 3100620314 3100620315 \
  3100620316 3100620317 3100620318 3100620319 3100620320 \
  > $out/leaf_look2.out 2> $out/leaf_look2.err
echo "look rc=$?"; tail -n 3 $out/leaf_look2.err | cut -c1-1500; head -n 1 $out/leaf_look2.out | cut -c1-1500
python3 - <<'PY'
import json
for line in open("chiprun_out/pr62/leaf_look_phi4flash6l-b1s8k.jsonl"):
    d = json.loads(line)
    for name in ("stated", "e4m3"):
        if name not in d:
            continue
        e = d[name]["errors"]
        g = sorted(((v, k) for k, v in e.items() if k != "loss"), reverse=True)
        print(d["run_seed"], d["form"], name, "loss %.2e" % e["loss"],
              " ".join("%s %.4f" % (k, v) for v, k in g[:3]), "least %s %.4f" % (g[-1][1], g[-1][0]),
              "| lam", {k: round(v, 3) for k, v in d["lambda"].items()}, d["seconds"])
PY
run() { # seed trace
  python3 -m chipbench.run --workload phi4flash6l-b1s8k --seed $1 --seconds 30 --trace $2 \
    > $out/conditioned_$1_t$2.out 2> $out/conditioned_$1_t$2.err
  echo "seed=$1 trace=$2 rc=$?"; tail -n 1 $out/conditioned_$1_t$2.out | cut -c1-4000; tail -n 4 $out/conditioned_$1_t$2.err; }
run 314767261 0
run 2124027345 1
