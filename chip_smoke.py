"""Chip smoke: the training main path, end to end, on the local TPU chips.

    python chip_smoke.py                  # all local chips on the dp axis
    python chip_smoke.py --mesh dp=2,tp=2

ray_tpu.init() detects the chips -> JaxTrainer.fit() gang-schedules one
TrainWorker holding all of them -> the worker checks the compiled flash
kernels against the reference, builds the mesh, the train state and the
train step for GPT-2-small at full width (12 layers, d_model 768, vocab
50,304, seq 1,024, bf16, batch 16 per chip, no remat), and takes 2 warm-up
+ 5 timed steps on token batches streamed from a ray_tpu.data shard,
reporting each through session.report.

This process never touches JAX: a chip belongs to one process, and that
process is the train worker. Exit code 0 and a last stdout line
{"ok": true, "device": {...}} mean every phase passed on a TPU; anything
else is a non-zero exit with one line per cause and no result line.
Weights and tokens are random, made from a seed; nothing is measured here
that a benchmark should quote.
"""
import argparse
import glob
import json
import math
import os
import sys
import time

SEED = 0
VOCAB = 50_304   # GPT2Config.vocab_size; importing the model would import jax
SEQ = 1024
BATCH_PER_CHIP = 16
REMAT = False
WARMUP_STEPS = 2
TIMED_STEPS = 5
# compiled kernel vs float32 reference: error / max(1, max|reference|). The
# kernels round probabilities and score gradients to bf16 (8 mantissa bits)
# before the MXU and emit bf16; the reference keeps float32 throughout.
KERNEL_TOL = 2e-2
# sharded first-step loss vs the same global batch on one chip (bf16
# compute, different reduction order)
LOSS_TOL = 1e-2
# largest / smallest per-device bytes_in_use after the steps
BALANCE_FACTOR = 1.25


def make_tokens(n_rows: int, seq: int, vocab: int, seed: int = SEED):
    """Random token rows drawn from 1/16 of the vocabulary: the unigram
    statistics are learnable within a few steps, so a falling loss is a
    signal that the optimizer works and not noise around ln(vocab)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return rng.integers(0, max(2, vocab // 16), size=(n_rows, seq + 1),
                        dtype=np.int32)


# ------------------------------------------------------------ worker side

def _rel_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, dtype=np.float32)
    ref = np.asarray(ref, dtype=np.float32)
    return float(np.max(np.abs(got - ref)) / max(1.0, np.max(np.abs(ref))))


# (B, S, H, KV, D), window: GPT-2's shape (one major block), and heads of
# 128 over four major blocks on grouped KV heads, causal and with a window
# that reaches three of them (the D 128 cells' form, PR 39)
KERNEL_CASES = {
    "b2s1024h12d64": ((2, 1024, 12, 12, 64), None),
    "b1s8192h8kv2d128": ((1, 8192, 8, 2, 128), None),
    "b1s8192h8kv2d128w4096": ((1, 8192, 8, 2, 128), 4096),
}


def check_flash_kernels() -> dict:
    """Compiled flash forward + both backward kernels against
    reference_attention in bf16 at each of `KERNEL_CASES`: the output and
    all three gradients. Returns the four relative errors of each case."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.parallel.ring_attention import reference_attention

    def reference(q, k, v, window):
        group = q.shape[2] // k.shape[2]
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
        return reference_attention(q, k, v, window=window)

    errs = {}
    for case, ((B, S, H, KV, D), window) in KERNEL_CASES.items():
        q, k, v, w = (jax.random.normal(key, (B, S, h, D), jnp.bfloat16)
                      for key, h in zip(
                          jax.random.split(jax.random.PRNGKey(7), 4),
                          (H, KV, KV, H)))

        def out_and_grads(attend):
            def run(q, k, v):
                o, vjp = jax.vjp(attend, q, k, v)
                return (o, *vjp(w))      # w: a non-symmetric cotangent
            return jax.jit(run)(q, k, v)

        got = out_and_grads(functools.partial(flash_attention, window=window))
        # the TPU's default float32 matmul is a single bf16 pass; the
        # reference must not carry the error it is there to expose
        with jax.default_matmul_precision("highest"):
            ref = out_and_grads(functools.partial(reference, window=window))
        errs[case] = {name: _rel_err(g, r) for name, g, r in
                      zip(("o", "dq", "dk", "dv"), got, ref)}
    bad = {f"{case}.{n}": e for case, by_name in errs.items()
           for n, e in by_name.items() if not e <= KERNEL_TOL}
    if bad:
        raise AssertionError(
            f"compiled flash kernels disagree with reference_attention "
            f"beyond {KERNEL_TOL}: {bad}")
    return errs


def flash_operand_report(hlo: str, local_shape: tuple) -> dict:
    """From a compiled (post-partitioning) HLO module: each flash custom
    call's first-operand shape, whether it equals the per-device shard
    shape, and whether an all-gather is among the instructions that feed
    the call (walked back six producers through the module text)."""
    import re

    instr = re.compile(
        r"\s*(?:ROOT\s+)?%([\w.\-]+) = (.+?)\s([a-z][\w\-]*)\((.*)")
    defs = {}      # name -> (result shape text, opcode, operand names, line)
    for line in hlo.splitlines():
        m = instr.match(line)
        if m:
            name, shape, opcode, rest = m.groups()
            operands = re.findall(r"%([\w.\-]+)", rest.split(")", 1)[0])
            defs[name] = (shape, opcode, operands, line.strip())

    def dims(shape_text):
        m = re.match(r"\(?\w+\[([\d,]*)\]", shape_text)
        return tuple(int(d) for d in m.group(1).split(",") if d) if m else ()

    calls, shapes, gathered = [], [], []
    for name, (_, opcode, operands, line) in defs.items():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        calls.append(line[:300])
        shapes.append(dims(defs[operands[0]][0])
                      if operands and operands[0] in defs else ())
        frontier, seen = list(operands), set()
        for _ in range(6):
            nxt = []
            for op in frontier:
                if op in seen or op not in defs:
                    continue
                seen.add(op)
                if defs[op][1].startswith("all-gather"):
                    gathered.append(f"{name} <- {op}")
                nxt += defs[op][2]
            frontier = nxt
    return {"calls": calls, "operand_shapes": shapes,
            "local_shape": tuple(local_shape),
            "all_local": bool(shapes) and all(
                s == tuple(local_shape) for s in shapes),
            "all_gather_feeds": gathered}


def train_loop(config: dict):
    """The smoke's train_loop_per_worker. ``config``: model ("gpt2_small" |
    "gpt2_tiny"), attention, remat, mesh ({axis: size}), batch (global),
    seq, warmup, steps, require_tpu, out_dir."""
    import dataclasses
    import importlib.metadata as md
    import itertools

    import jax
    import numpy as np

    from ray_tpu.air import session
    from ray_tpu.models import gpt2, layers
    from ray_tpu.parallel.compile_watch import configure_compile_cache
    from ray_tpu.parallel.mesh import (
        MeshConfig,
        create_mesh,
        mesh_shape_summary,
    )
    from ray_tpu.parallel.train_step import (
        default_optimizer,
        make_train_state,
        make_train_step,
    )

    cache_dir = configure_compile_cache()
    cache_events = {"hits": 0, "misses": 0}

    def _on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(_on_event)

    devices = jax.local_devices()
    report = {
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(jax.devices())},
        "versions": {"jax": jax.__version__,
                     "jaxlib": md.version("jaxlib"),
                     "libtpu": md.version("libtpu")},
        "compile_cache_dir": cache_dir,
    }
    if config["require_tpu"] and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"train worker's jax.default_backend() is "
            f"{jax.default_backend()!r}, not 'tpu'")

    cfg = dataclasses.replace(getattr(gpt2, config["model"])(),
                              attention=config["attention"],
                              remat=config["remat"])
    n_mesh = math.prod(config["mesh"].values())
    mesh = create_mesh(MeshConfig(**config["mesh"]),
                       devices=devices[:n_mesh])
    report["mesh"] = mesh_shape_summary(mesh)
    report["attention"] = layers.resolve_attention(cfg.attention, mesh)

    t_compile = time.perf_counter()
    if report["attention"] == "flash":
        report["kernel_rel_err"] = check_flash_kernels()

    opt = default_optimizer(1e-3, warmup_steps=2, total_steps=100)
    state = make_train_state(lambda rng: gpt2.init(rng, cfg),
                             jax.random.PRNGKey(SEED), opt, mesh,
                             gpt2.partition_specs(cfg))
    step = make_train_step(lambda p, b: gpt2.loss_fn(p, b, cfg, mesh),
                           opt, mesh)

    batches = session.get_dataset_shard("train").iter_batches(
        batch_size=config["batch"], drop_last=True)
    first = next(batches)
    if n_mesh > 1:
        # the same global batch on ONE chip, forward only, in per-chip
        # sized chunks (equal chunks: the mean of chunk means is the mean)
        p0 = jax.device_put(state.params, devices[0])
        loss0 = jax.jit(lambda p, t: gpt2.loss_fn(p, {"tokens": t}, cfg)[0])
        report["one_chip_first_loss"] = float(np.mean([
            float(loss0(p0, jax.device_put(chunk, devices[0])))
            for chunk in np.split(first, n_mesh)]))
        del p0

    compile_s = 0.0
    losses, t_block, t_fetch = [], [], []
    n_steps = config["warmup"] + config["steps"]
    for i, tokens in enumerate(itertools.islice(
            itertools.chain([first], batches), n_steps)):
        t0 = time.perf_counter()
        state, metrics = step(state, {"tokens": tokens})
        jax.block_until_ready((state, metrics))
        t1 = time.perf_counter()
        loss = float(metrics["loss"])      # host scalar fetch
        t2 = time.perf_counter()
        losses.append(loss)
        if i == 0:
            compile_s = t2 - t_compile     # everything up to the first step
        elif i >= config["warmup"]:
            t_block.append(t1 - t0)
            t_fetch.append(t2 - t0)
        session.report({"step": i, "loss": loss,
                        "grad_norm": float(metrics["grad_norm"])})
    report["cache_events"] = dict(cache_events)

    report.update({
        "losses": losses,
        "seconds": {"compile_and_first_step": round(compile_s, 2),
                    "timed_steps": round(sum(t_fetch), 3)},
        "step_time_block_until_ready_s": float(np.median(t_block)),
        "step_time_scalar_fetch_s": float(np.median(t_fetch)),
    })
    stats = [d.memory_stats() or {} for d in devices[:n_mesh]]
    report["hbm"] = [{k: s.get(k) for k in
                      ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
                     for s in stats]

    lowered = step.lower(state, {"tokens": tokens})
    report["pallas_calls_in_step"] = lowered.as_text().count(
        "@tpu_custom_call")
    if report["attention"] == "flash":
        # the same program again, ahead of time, for its text and its
        # memory plan (a persistent-cache hit, counted after the snapshot
        # of cache_events above)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        report["compiled_step_bytes"] = {
            k: getattr(mem, f"{k}_size_in_bytes")
            for k in ("argument", "output", "alias", "temp")}
        hlo = compiled.as_text()
        # a device's share of the batch, which the tp region of the layer
        # loop runs as two half-batch chains (one kernel call each)
        local_batch = config["batch"] // mesh.shape["dp"]
        chains = gpt2.tp_exchange_plan(cfg, mesh, local_batch).chains
        report["flash_partitioning"] = flash_operand_report(hlo, (
            local_batch // chains * cfg.n_head // mesh.shape["tp"],
            config["seq"], cfg.d_model // cfg.n_head))
        if config["out_dir"]:
            os.makedirs(config["out_dir"], exist_ok=True)
            name = f"train_step_{report['mesh'].replace('=', '')}.hlo.txt"
            with open(os.path.join(config["out_dir"], name), "w") as f:
                f.write(hlo)
    session.report({"smoke_report": report})


# ------------------------------------------------------------ driver side

def check_result(result, config: dict) -> list:
    """Every way the run can have failed, one line each; [] = passed.
    ``fit()`` does not raise on a failed loop — with the default
    FailureConfig it returns Result(error=...) — so the error is the first
    thing looked at."""
    if result.error is not None:
        return [f"train loop failed: {type(result.error).__name__}: "
                f"{result.error}"]
    report = (result.metrics or {}).get("smoke_report")
    if report is None:
        return ["train loop ended without its final report"]
    failures = []
    losses = report["losses"]
    if len(losses) < config["warmup"] + config["steps"]:
        failures.append(f"only {len(losses)} steps ran")
    if not all(math.isfinite(x) for x in losses):
        failures.append(f"non-finite loss: {losses}")
    elif not losses[-1] < losses[0]:
        failures.append(f"loss did not fall: {losses}")
    if "one_chip_first_loss" in report:      # ran on a mesh
        gap = abs(losses[0] - report["one_chip_first_loss"])
        if not gap <= LOSS_TOL:
            failures.append(
                f"first-step loss on mesh {report['mesh']} is {losses[0]}, "
                f"one chip gives {report['one_chip_first_loss']} on the "
                f"same global batch (tolerance {LOSS_TOL})")
        in_use = [h["bytes_in_use"] for h in report["hbm"]]
        if not all(in_use):
            if config["require_tpu"]:
                failures.append(
                    f"memory_stats() gave no bytes_in_use: {in_use}")
        elif max(in_use) > BALANCE_FACTOR * min(in_use):
            failures.append(f"device memory unbalanced: bytes_in_use "
                            f"{in_use} spread beyond {BALANCE_FACTOR}x")
    flash = report.get("flash_partitioning")
    if flash and (not flash["all_local"] or flash["all_gather_feeds"]):
        failures.append(
            f"flash custom calls are not per-device shards: operand shapes "
            f"{flash['operand_shapes']} vs local {flash['local_shape']}, "
            f"all-gather feeds {flash['all_gather_feeds']}")
    if config["require_tpu"]:
        platform = report["device"]["platform"]
        if platform != "tpu":
            failures.append(f"worker backend {platform!r}")
        if report["attention"] != "flash" or \
                report["pallas_calls_in_step"] < 3:
            failures.append(
                f"attention ran as {report['attention']!r} with "
                f"{report['pallas_calls_in_step']} compiled Pallas calls in "
                f"the step (want flash: forward, dq, dk/dv)")
    return failures


def print_report(report: dict):
    d, v = report["device"], report["versions"]
    print(f"device: platform={d['platform']} device_kind={d['kind']!r} "
          f"count={d['count']}  jax={v['jax']} jaxlib={v['jaxlib']} "
          f"libtpu={v['libtpu']}")
    print(f"mesh: {report['mesh']}  attention: {report['attention']}  "
          f"pallas calls in step: {report['pallas_calls_in_step']}")
    print(f"compile cache: {report['compile_cache_dir']}  "
          f"persistent-cache hits={report['cache_events']['hits']} "
          f"misses={report['cache_events']['misses']}")
    if "kernel_rel_err" in report:
        print(f"flash vs reference (rel err, tol {KERNEL_TOL}): "
              f"{report['kernel_rel_err']}")
    s = report["seconds"]
    print(f"seconds: compile+first step {s['compile_and_first_step']}, "
          f"timed steps {s['timed_steps']}")
    print(f"step time (median): block_until_ready "
          f"{report['step_time_block_until_ready_s']:.4f}s, "
          f"host scalar fetch {report['step_time_scalar_fetch_s']:.4f}s")
    print(f"losses: {[round(x, 4) for x in report['losses']]}")
    if "one_chip_first_loss" in report:
        print(f"one-chip first-step loss on the same global batch: "
              f"{report['one_chip_first_loss']:.4f}")
    for i, h in enumerate(report["hbm"]):
        print(f"hbm[{i}]: in_use={h['bytes_in_use']} "
              f"peak={h['peak_bytes_in_use']} limit={h['bytes_limit']}")
    if "compiled_step_bytes" in report:
        print(f"compiled step memory plan (bytes per device): "
              f"{report['compiled_step_bytes']}")
    flash = report.get("flash_partitioning")
    if flash:
        print(f"flash custom-call operands {flash['operand_shapes']} "
              f"local shard {flash['local_shape']} "
              f"all-gather feeds {flash['all_gather_feeds']}")


def _worker_log_tail(session_dir: str, n_bytes: int = 6000) -> str:
    out = []
    for path in sorted(glob.glob(
            os.path.join(session_dir, "logs", "worker-*.err"))):
        with open(path, "rb") as f:
            f.seek(max(0, os.path.getsize(path) - n_bytes))
            tail = f.read().decode(errors="replace").strip()
        if tail:
            out.append(f"--- {path}\n{tail}")
    return "\n".join(out)


def parse_mesh(text: str) -> dict:
    return {axis: int(size) for axis, size in
            (part.split("=") for part in text.split(","))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", type=parse_mesh, default=None,
                    help="axis sizes, e.g. dp=2,tp=2 (default: every local "
                         "chip on dp)")
    ap.add_argument("--out", default=None,
                    help="directory for the full report and, on a mesh, "
                         "the compiled train step's HLO")
    args = ap.parse_args(argv)

    import ray_tpu
    import ray_tpu.data
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train.trainer import JaxTrainer

    ctx = ray_tpu.init()
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        if chips < 1:
            print("chip_smoke: FAILED: ray_tpu.cluster_resources() shows no "
                  "TPU after init(): JAX found no accelerator on this host",
                  file=sys.stderr)
            return 1
        mesh = args.mesh or {"dp": chips}
        n_mesh = math.prod(mesh.values())
        if n_mesh > chips:
            print(f"chip_smoke: FAILED: mesh {mesh} needs {n_mesh} chips, "
                  f"this host has {chips}", file=sys.stderr)
            return 1
        config = {"model": "gpt2_small", "attention": "auto",
                  "remat": REMAT, "mesh": mesh,
                  "batch": BATCH_PER_CHIP * n_mesh, "seq": SEQ,
                  "warmup": WARMUP_STEPS, "steps": TIMED_STEPS,
                  "require_tpu": True,
                  "out_dir": os.path.abspath(args.out) if args.out else None}
        tokens = make_tokens(
            config["batch"] * (WARMUP_STEPS + TIMED_STEPS), SEQ, VOCAB)
        t0 = time.perf_counter()
        result = JaxTrainer(
            train_loop, train_loop_config=config,
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         chips_per_worker=chips),
            datasets={"train": ray_tpu.data.from_numpy(tokens)},
        ).fit()
        wall = time.perf_counter() - t0
        failures = check_result(result, config)
        report = (result.metrics or {}).get("smoke_report")
        if report:
            print_report(report)
            if config["out_dir"]:
                os.makedirs(config["out_dir"], exist_ok=True)
                with open(os.path.join(
                        config["out_dir"],
                        f"chip_smoke_{report['mesh'].replace('=', '')}.json"),
                        "w") as f:
                    json.dump(report, f, indent=1)
        print(f"fit() wall: {wall:.1f}s")
        if "jax" in sys.modules:
            failures.append("the driver process imported jax; the chip "
                            "must belong to the train worker alone")
        if failures:
            for line in failures:
                print(f"chip_smoke: FAILED: {line}", file=sys.stderr)
            print(_worker_log_tail(ctx["session_dir"]), file=sys.stderr)
            return 1
    finally:
        ray_tpu.shutdown()
    print(json.dumps({"ok": True, "device": report["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
