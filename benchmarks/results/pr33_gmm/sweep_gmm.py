"""PR 33 scratch: the cell's own train step (olmoe1l-b2s4k, its state from the
seed, its tokens) compiled once a tiling variant in ONE process on the chip;
per variant the step's time and, from a 3-step profile, the time of each
grouped-matmul call and of the weight converts/copies.
Copy it and a variants file into the git-ignored .bench_tree/ (the chip tool
copies that too), then from the repo root, through the chip tool:
  python3 .bench_tree/sweep_gmm.py <variants-file.json> [out-name]
SWEEP_TINY=1 rehearses the control flow on the CPU at the tiny preset."""
import dataclasses, glob, importlib, json, math, os, re, shutil, sys, time, types
ROOT = os.getcwd(); sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp, numpy as np
from chipbench import catalog, flops, generate, trace_reduce
from ray_tpu.models import layers as L
from ray_tpu.ops import grouped_matmul as gm
from ray_tpu.parallel.mesh import MeshConfig, create_mesh
from ray_tpu.parallel.train_step import default_optimizer, make_train_state, make_train_step

CELL, SEED = "olmoe1l-b2s4k", 2233001177
variants = json.load(open(sys.argv[1]))
out_name = sys.argv[2] if len(sys.argv) > 2 else "sweep"
OUT = os.path.join(ROOT, "chiprun_out", "pr33"); os.makedirs(OUT, exist_ok=True)
TRACE = os.path.join(ROOT, ".chipbench_tmp", "sweep_trace")

VARIANT = {}
_orig_kernels = gm._kernels
def _patched():
    gmm, tgmm = _orig_kernels()
    def g(lhs, rhs, sizes, *, tiling, transpose_rhs=False, **kw):
        k = lhs.shape[1]; n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
        t = VARIANT.get("gmmT" if transpose_rhs else "gmm", {}).get(f"{k}x{n}") or VARIANT.get("gmm", {}).get(f"{k}x{n}")
        return gmm(lhs, rhs, sizes, tiling=tuple(t) if t else tiling, transpose_rhs=transpose_rhs, **kw)
    def t_(lhsT, d, sizes, *, tiling, **kw):
        k, n = lhsT.shape[0], d.shape[1]
        t = VARIANT.get("tgmm", {}).get(f"{k}x{n}")
        return tgmm(lhsT, d, sizes, tiling=tuple(t) if t else tiling, **kw)
    return g, t_
gm._kernels = _patched
# the "ragged" variant: XLA's product on the chip, the op told it runs elsewhere
_where = gm.target.where
gm.target = types.SimpleNamespace(
    where=lambda *a, **kw: ("cpu", 1) if VARIANT.get("ragged") else _where(*a, **kw))

manifest = catalog.load_manifest()
cell = catalog.resolve_cell(manifest, CELL, "end_to_end")
traffic = cell["traffic"]
module_name, preset = cell["model"]["entry"].split(":")
module = importlib.import_module(module_name)
if os.environ.get("SWEEP_TINY"):
    preset = "olmoe_tiny"; traffic = dict(traffic, seq=64, batches=8)
cfg = dataclasses.replace(getattr(module, preset)(), attention=traffic["attention"], remat=traffic["remat"])
devices = jax.local_devices()
print("device", devices[0].device_kind, flush=True)
mesh = create_mesh(MeshConfig(**traffic["mesh"]), devices=devices[:1])
opt = default_optimizer(**traffic["optimizer"])
state = make_train_state(lambda rng: module.init(rng, cfg), jax.random.PRNGKey(SEED), opt, mesh, module.partition_specs(cfg))
rows = generate.token_rows(traffic, flops.padded_vocab(cell["model"]["vocab_size"]), SEED)
B = traffic["batch"]
def batch(i):
    i %= len(rows) // B
    return {"tokens": rows[i * B:(i + 1) * B]}

PAT = re.compile(r"^%?(gmm|tgmm|ragged-dot)")
def weightish(name):
    p = trace_reduce._parse(name)
    if not p: return False
    return bool(re.match(r"^\(?bf16\[(1,)?64,\d+,\d+\]", p[1])) and not PAT.match(name)

counts_fn = jax.jit(lambda p, t: module.forward(p, t[:, :-1], cfg, mesh)[1]["counts"])
def routing(tag):
    c = np.asarray(counts_fn(state.params, batch(n_step)["tokens"]))[0]
    row = {"routing_at": tag, "step": n_step, "counts_sorted": sorted(c.tolist(), reverse=True)[:8] + ["..."] + sorted(c.tolist())[:4],
           "issued_ratio": {tm: round(gm.issued_ratio(c, tm), 4) for tm in (128, 256, 512)}}
    print(json.dumps(row), flush=True)
    with open(os.path.join(OUT, out_name + ".jsonl"), "a") as f:
        f.write(json.dumps(row) + "\n")

n_step = 0
results = []
routing("start")
for v in variants:
    VARIANT.clear(); VARIANT.update(v)
    row = {"variant": v}
    try:
        step = make_train_step(lambda p, b: module.loss_fn(p, b, cfg, mesh), opt, mesh)
        t0 = time.time()
        for _ in range(3):
            state, metrics = step(state, batch(n_step)); n_step += 1
            loss = float(metrics["loss"])
        row["compile_and_3_steps_s"] = round(time.time() - t0, 1)
        spans = []
        for _ in range(12):
            t1 = time.perf_counter()
            state, metrics = step(state, batch(n_step)); n_step += 1
            loss = float(metrics["loss"])
            spans.append(time.perf_counter() - t1)
        row["step_ms_median"] = round(1e3 * float(np.median(spans)), 3)
        row["step_ms_min"] = round(1e3 * min(spans), 3)
        row["loss"] = loss
        row["counts"] = np.asarray(metrics.get("moe_load_max_over_mean", 0)).tolist() if "moe_load_max_over_mean" in metrics else None
        shutil.rmtree(TRACE, ignore_errors=True)
        options = jax.profiler.ProfileOptions(); options.python_tracer_level = 0
        jax.profiler.start_trace(TRACE, profiler_options=options)
        for _ in range(4):
            state, metrics = step(state, batch(n_step)); n_step += 1
            float(metrics["loss"])
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(TRACE, "**", "*.xplane.pb"), recursive=True)
        summary = trace_reduce.reduce_trace(trace_reduce.load_xplane(files[0], ()), ())
        steps = summary["steps"]
        row["device_ms"] = round(1e3 * summary["busy_s"] / steps, 3) if "busy_s" in summary else None
        per_op = summary["per_op_s"]
        calls = {}
        for name, s in per_op.items():
            if PAT.match(name) or weightish(name):
                calls[trace_reduce.short_op_name(name, 80)] = round(1e3 * s / steps, 3)
        row["per_call_ms"] = dict(sorted(calls.items()))
        row["grouped_ms"] = round(sum(t for n, t in calls.items() if re.match(r"(gmm|tgmm|ragged-dot-(?!metadata))", n)), 3)
        row["weight_ops_ms"] = round(sum(t for n, t in calls.items() if not PAT.match(n)), 3)
    except Exception as e:
        row["error"] = f"{type(e).__name__}: {str(e)[:600]}"
    results.append(row)
    if len(results) % 3 == 0:
        routing(f"after variant {len(results)}")
    print(json.dumps(row), flush=True)
    with open(os.path.join(OUT, out_name + ".jsonl"), "a") as f:
        f.write(json.dumps(row) + "\n")
shutil.rmtree(TRACE, ignore_errors=True)
routing("end")
print("== summary")
for r in results:
    print(json.dumps(r["variant"]), r.get("step_ms_median"), r.get("device_ms"), r.get("grouped_ms"), r.get("weight_ops_ms"), r.get("error", "")[:100])
