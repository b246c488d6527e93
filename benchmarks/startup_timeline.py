"""A cell's start-up, span by span: the benchmark's own `train_loop` through
`JaxTrainer.fit()` with a short window, and the program's merged timeline
(`ray_tpu.timeline()`, asked from inside the train worker once the loop has
ended) written out and printed — every span of category `startup` and
`compile` between the process's start and the window's, indented under its
parent, and what NO span of the five that `train.startup_unspanned_s` takes
out covers, with the other spans (tasks, actor calls, steps) that lie there.

    python3 benchmarks/startup_timeline.py <cell> <seed> <out.json> [tiny]

On the chip, through the chip tool, from the root of a checkout (`tiny`: the
tests' cell on the CPU, a rehearsal). One process tree; this process never
imports jax. A start-up is not a benchmark run: nothing here is a metric.
"""
import json
import os
import sys
import time

T_START = time.time()

sys.path.insert(0, os.getcwd())

from chipbench import catalog  # noqa: E402
from chipbench.jobs import train_fit  # noqa: E402

COVERED = ("init", "gang_start", "backend_up", "compile::train_state_init",
           "compile::train_step")
WINDOW_S = 3.0


def loop(config):
    """The worker's side: the benchmark's loop, then the timeline."""
    import ray_tpu
    from chipbench.jobs import train_fit    # the worker's own: unpatched

    try:
        train_fit.train_loop(config)
    finally:
        with open(config["timeline_out"], "w") as f:
            json.dump(ray_tpu.timeline(), f)


def uncovered(spans, lo, hi):
    """[(start, end)] of [lo, hi] that no span of `spans` covers."""
    gaps, at = [], lo
    for ev in sorted(spans, key=lambda ev: ev["ts"]):
        start, end = ev["ts"], ev["ts"] + ev["dur"]
        if start > at:
            gaps.append((at, min(start, hi)))
        at = max(at, end)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    return [(a, b) for a, b in gaps if b > a]


def report(events, clock):
    lo = int(clock["process_start"] * 1e6)
    hi = int(clock["window_start"] * 1e6)
    spans = [ev for ev in events if ev.get("ph") == "X"
             and lo <= ev["ts"] < hi]
    by_id = {ev["args"]["id"]: ev for ev in spans}

    def depth(ev):
        n, seen = 0, set()
        while ev["args"].get("parent") in by_id and \
                ev["args"]["id"] not in seen:
            seen.add(ev["args"]["id"])
            ev, n = by_id[ev["args"]["parent"]], n + 1
        return n

    print(f"setup_s {(hi - lo) / 1e6:.3f}  (process_start -> window_start)")
    for ev in sorted(spans, key=lambda ev: ev["ts"]):
        if ev["cat"] in ("startup", "compile"):
            shown = {k: v for k, v in ev["args"].items()
                     if k in ("chips", "backend", "devices", "rank",
                              "persistent_cache", "first_execute_s",
                              "cache_misses_total", "cache_hits_total")}
            print(f"{(ev['ts'] - lo) / 1e6:9.3f} {ev['dur'] / 1e6:8.3f} "
                  f"pid {ev['pid']:<7} {'  ' * depth(ev)}{ev['name']} "
                  f"{shown or ''}")
    print("not covered by " + ", ".join(COVERED) + ":")
    covering = [ev for ev in spans if ev["name"] in COVERED]
    for a, b in uncovered(covering, lo, hi):
        if b - a < 50_000:
            continue
        inside = {}
        for ev in spans:
            if ev["name"] in COVERED or ev["cat"] == "startup" and \
                    ev["name"] in ("fit", "train_fn"):
                continue
            over = min(b, ev["ts"] + ev["dur"]) - max(a, ev["ts"])
            if over > 0:
                key = f"{ev['cat']}:{ev['name']}"
                inside[key] = inside.get(key, 0) + over
        top = sorted(inside.items(), key=lambda kv: -kv[1])[:6]
        print(f"{(a - lo) / 1e6:9.3f} {(b - a) / 1e6:8.3f}  "
              + ", ".join(f"{k} {v / 1e6:.3f}" for k, v in top))


def main():
    name, seed, out = sys.argv[1], int(sys.argv[2]), \
        os.path.abspath(sys.argv[3])
    tiny = sys.argv[4:] == ["tiny"]
    os.makedirs(os.path.dirname(out), exist_ok=True)
    manifest = catalog.load_manifest()
    if tiny:
        manifest = dict(manifest, workloads=[
            {"name": "tiny", "config": "gpt2-tiny", "traffic": "fit-tiny",
             "chips": 1, "why": "rehearsal"}], end_to_end=[])
        name = "tiny"
    cell = catalog.resolve_cell(manifest, name, "end_to_end")
    # the job's parent side as it is (init, tokens, fit, shutdown), with
    # `loop` for the loop it hands the trainer
    train_fit.train_loop = loop
    record = train_fit.run(dict(cell, timeline_out=out), seed=seed,
                           seconds=WINDOW_S, trace=False, t_start=T_START,
                           require_tpu=not tiny)
    assert "jax" not in sys.modules
    with open(out) as f:
        events = json.load(f)
    print(f"{name} seed {seed} device {record['device']} "
          f"setup_cache {record['setup_cache']}")
    report(events, record["clock"])


if __name__ == "__main__":
    main()
