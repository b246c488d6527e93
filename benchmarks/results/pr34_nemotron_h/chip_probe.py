"""PR 34 scratch, ONE process on the chip (from the repo root, through the
chip tool):  python3 benchmarks/results/pr34_nemotron_h/chip_probe.py <phase>...

  kernels  what had never run on a chip: the flash kernels at S 8,192 / D 128
           with 32 query heads on 2 KV heads against plain attention (o, dq,
           dk, dv), and `ops.ssd` at the cell's shapes against the
           token-by-token recurrence (y and every gradient), with and without
           `three_pass`
  flips    the cell's model, layer by layer, beside the plain reference on
           the same parameters and tokens: the stream's relative error after
           each layer and, at each routed layer, the share of tokens whose
           top-6 differs — with every forward product in three passes (the
           program), with none, with the projections' only
  ragged   a share of the experts (2 of 8 held) on XLA's grouped product,
           where no kernel tile divides the rows: what `lax.ragged_dot`
           leaves in the rows of no group, and `_local_experts` (which masks
           them) against a dense loop over the held experts, output and
           gradients
  check    the benchmark's comparison (fresh parameters and one sequence
           from seed + 1, the cell's loss against the plain reference) with
           the routed leaves of EVERY routed layer picked, on the seeds
           given after the word ("none" among them: single-pass products,
           the precision below the program's): the error of `wg`, two held experts' `w1`
           / `w2` and `shared_w1` by depth
  step     the cell's own train step (state from the seed, its tokens) in a
           bare loop: step time and, from a 3-step profile, the time by
           operation, with three passes (the program) and without

PROBE_TINY=1 rehearses the control flow on the CPU at the tiny preset.
Lines go to chiprun_out/pr34/probe.jsonl."""
import dataclasses, functools, glob, importlib, json, math, os, shutil, sys, time
ROOT = os.getcwd(); sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp, numpy as np
from chipbench import catalog, compare, flops, generate, trace_reduce
from chipbench.references import nemotron_h as ref
from chipbench.readers import trace_ssm
from ray_tpu.models import layers as L, nemotron_h as nh
from ray_tpu.ops import mxu, ssd as ssd_ops
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel.mesh import MeshConfig, create_mesh
from ray_tpu.parallel.train_step import default_optimizer, make_train_state, make_train_step

TINY = bool(os.environ.get("PROBE_TINY"))
CELL, SEED = "nemotronh9l-b1s8k", 2234034001
OUT = os.path.join(ROOT, "chiprun_out", "pr34"); os.makedirs(OUT, exist_ok=True)
TRACE = os.path.join(ROOT, ".chipbench_tmp", "probe_trace")
print("device", jax.devices()[0].device_kind, flush=True)


def emit(row):
    print(json.dumps(row), flush=True)
    with open(os.path.join(OUT, "probe.jsonl"), "a") as f:
        f.write(json.dumps(row) + "\n")


# which products run in three passes: "all" (the program), "none", "proj"
PASSES = {"which": "all"}
_einsum = mxu.einsum
def _patched(eq, x, w, out_dtype, *, cd, three_pass, _ssd=False):
    on = three_pass and (PASSES["which"] == "all" or (PASSES["which"] == "proj" and not _ssd))
    return _einsum(eq, x, w, out_dtype, cd=cd, three_pass=on)
L.mxu = type("m", (), {"einsum": staticmethod(_patched)})
ssd_ops.mxu = type("m", (), {"einsum": staticmethod(functools.partial(_patched, _ssd=True))})

manifest = catalog.load_manifest()
cell = catalog.resolve_cell(manifest, CELL, "end_to_end")
traffic, filed = cell["traffic"], cell["model"]
cfg = nh.nemotron_twotower_30b_a3b_9l()
if TINY:
    cfg = nh.nemotron_h_tiny()
    filed = json.load(open(os.path.join(ROOT, "tests/chipbench_tests/configs/nemotronh-tiny.json")))
    traffic = dict(traffic, seq=64, batches=8)
cfg = dataclasses.replace(cfg, attention="reference" if TINY else "flash", remat=True)
SEQ = traffic["seq"]


def rel(a, b):
    return compare.rel_l2(np.asarray(a, np.float32), np.asarray(b, np.float32))


def kernels():
    H, KV, D, S = (cfg.n_head, cfg.n_kv_head, cfg.head_dim, SEQ)
    ks = jax.random.split(jax.random.PRNGKey(SEED % 2**31), 8)
    q = jax.random.normal(ks[0], (1, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, S, KV, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, S, KV, D), jnp.bfloat16)
    w = jax.random.normal(ks[3], (1, S, H, D), jnp.float32)
    group = H // KV

    def plain(q, k, v):
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
        kh, vh = (jnp.repeat(t, group, axis=2) for t in (k, v))
        causal = jnp.tril(jnp.ones((S, S), bool))

        def head(args):
            qh, kk, vv = args                      # [S, D]
            s = jnp.where(causal, qh @ kk.T / math.sqrt(D), -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ vv
        o = jax.lax.map(jax.checkpoint(head), tuple(
            jnp.moveaxis(t[0], 1, 0) for t in (q, kh, vh)))
        return jnp.moveaxis(o, 0, 1)[None]

    def loss(fn):
        return lambda q, k, v: jnp.sum(w * fn(q, k, v).astype(jnp.float32))

    flash = functools.partial(flash_attention, causal=True, interpret=TINY)
    with jax.default_matmul_precision("highest"):
        o_ref = jax.jit(plain)(q, k, v)
        g_ref = jax.jit(jax.grad(loss(plain), (0, 1, 2)))(q, k, v)
    o = jax.jit(flash)(q, k, v)
    g = jax.jit(jax.grad(loss(flash), (0, 1, 2)))(q, k, v)
    row = {"phase": "kernels", "what": f"flash [1,{S},{H},{D}] on {KV} KV heads vs plain attention (rel L2; max-abs over the largest reference magnitude)",
           "o": [rel(o, o_ref), float(jnp.max(jnp.abs(o.astype(jnp.float32) - o_ref)) / jnp.max(jnp.abs(o_ref)))]}
    for name, a, b in zip(("dq", "dk", "dv"), g, g_ref):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        row[name] = [rel(a, b), float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))]
    emit(row)

    m = cfg.mamba
    x = jax.random.normal(ks[4], (1, S, m.n_heads, m.head_dim))
    dt = jax.nn.softplus(jax.random.normal(ks[5], (1, S, m.n_heads)) - 3.0)
    A = -jnp.exp(jax.random.uniform(ks[6], (m.n_heads,), minval=0.0, maxval=math.log(16.0)))
    B, C = (jax.random.normal(kk, (1, S, m.n_groups, m.d_state)) for kk in jax.random.split(ks[7]))
    Dh = jnp.ones((m.n_heads,))
    wy = jax.random.normal(ks[3], x.shape)
    args = (x, dt, A, B, C, Dh)

    def rec(x, dt, A, B, C, Dh):
        return ref.recurrence(x[0], dt[0], A, B[0], C[0], Dh)[None]
    with jax.default_matmul_precision("highest"):
        y_ref = jax.jit(rec)(*args)
        g_ref = jax.jit(jax.grad(lambda *a: jnp.sum(wy * rec(*a)), range(6)))(*args)
    for three in (True, False):
        fn = functools.partial(ssd_ops.ssd, chunk=m.chunk, three_pass=three)
        PASSES["which"] = "all"
        y = jax.jit(fn)(*args)
        g = jax.jit(jax.grad(lambda *a: jnp.sum(wy * fn(*a)), range(6)))(*args)
        t0 = time.perf_counter()
        for _ in range(5):
            y = jax.jit(fn)(*args)
        y.block_until_ready()
        row = {"phase": "kernels", "what": f"ops.ssd three_pass={three} at x [1,{S},{m.n_heads},{m.head_dim}] vs the recurrence (rel L2)",
               "y": rel(y, y_ref), "forward_ms": 1e3 * (time.perf_counter() - t0) / 5}
        for name, a, b in zip(("x", "dt", "A", "B", "C", "D"), g, g_ref):
            row["d" + name] = rel(a, b)
        emit(row)


def flips():
    params = jax.jit(lambda rng: nh.init(rng, cfg))(jax.random.PRNGKey((SEED + 1) % 2**31))
    tokens = generate.token_rows(dict(traffic, batches=1, batch=1), cfg.vocab_size, SEED + 1)[:, :-1]
    eps = cfg.rms_norm_eps

    @jax.jit
    def chosen(x, layer):
        with jax.default_matmul_precision("highest"):
            s = jax.nn.sigmoid(ref.rms_norm(x, layer["ln"], eps) @ layer["wg"])
            vals, idx = jax.lax.top_k(s + layer["bias"], cfg.top_k + 1)
        return jnp.sort(idx[..., :cfg.top_k], axis=-1), vals[..., cfg.top_k - 1] - vals[..., cfg.top_k]

    def ref_layer(kind):
        name, mixer = ref.MIXERS[kind]
        def fn(x, p):
            with jax.default_matmul_precision("highest"):
                return x + mixer(ref.rms_norm(x, p["ln"], eps), p, filed)
        return jax.jit(fn)

    x0 = jnp.take(params["wte"], tokens, axis=0).astype(jnp.float32)
    streams = {"ref": x0[0]}
    for which in ("all", "none", "proj"):
        streams[which] = x0
    seen = dict.fromkeys(nh.KINDS, 0)
    for depth, kind in enumerate(cfg.pattern):
        layer = jax.tree_util.tree_map(lambda a, i=seen[kind]: a[i], params[nh.KINDS[kind]])
        seen[kind] += 1
        row = {"phase": "flips", "layer": depth, "kind": kind}
        if kind == "E":
            want, gap = chosen(streams["ref"], layer)
            row["median_gap_6th_7th"] = float(jnp.median(gap))
            held = jnp.any(want < cfg.moe.stacked, axis=-1)
            for which in ("all", "none", "proj"):
                got, _ = chosen(streams[which][0], layer)
                differs = jnp.any(got != want, axis=-1)
                # a token whose choice among the HELD experts differs
                held_differs = jnp.any(jnp.where(got < cfg.moe.stacked, got, -1).sort(-1)
                                       != jnp.where(want < cfg.moe.stacked, want, -1).sort(-1), axis=-1)
                row[f"top6_differs_share[{which}]"] = float(jnp.mean(differs))
                row[f"held_choice_differs_of_tokens_with_a_held[{which}]"] = float(
                    jnp.sum(held_differs) / jnp.maximum(jnp.sum(held), 1))
        streams["ref"] = ref_layer(kind)(streams["ref"], layer)
        for which in ("all", "none", "proj"):
            PASSES["which"] = which
            body = jax.jit(functools.partial(
                nh._layer_apply, kind=kind, cfg=cfg, impl=L.resolve_attention(cfg.attention), mesh=None))
            streams[which] = body(streams[which], layer)[0]
            row[f"stream_rel_error[{which}]"] = rel(streams[which][0], streams["ref"])
            if which == "all":
                # a token at a time: the median is what the arithmetic adds, the tail what a flipped choice does
                by_token = (jnp.linalg.norm(streams[which][0] - streams["ref"], axis=-1)
                            / jnp.linalg.norm(streams["ref"], axis=-1))
                row["token_rel_error[all]"] = {q: float(jnp.quantile(by_token, float(q))) for q in ("0.5", "0.9", "0.99")}
        emit(row)
    PASSES["which"] = "all"


def ragged():
    ks = jax.random.split(jax.random.PRNGKey(SEED % 2**31), 5)
    T, D, F, E, held, first, K = 500, 256, 320, 8, 2, 2, 2
    x = jax.random.normal(ks[0], (1, T, D))
    experts = {"w1": 0.1 * jax.random.normal(ks[1], (held, D, F)), "w2": 0.1 * jax.random.normal(ks[2], (held, F, D))}
    gate_idx = jax.random.randint(ks[3], (1, T, K), 0, E)
    gate_vals = jax.random.uniform(ks[4], (1, T, K))
    platform = jax.default_backend()
    assert L.grouped_matmul.kernel_width(T * K, D, F, jnp.bfloat16) is None
    # the bare product: rows sorted by group, the first `held` groups have a matrix
    sizes = jnp.bincount((gate_idx.reshape(-1) - first) % E, length=E).astype(jnp.int32)
    rows = jax.random.normal(ks[0], (T * K, D), jnp.bfloat16)
    bare = jax.jit(lambda r, w, s: jax.lax.ragged_dot(r, w, s[:held], preferred_element_type=jnp.bfloat16))(
        rows, experts["w1"].astype(jnp.bfloat16), sizes).astype(jnp.float32)
    beyond = np.asarray(bare)[int(jnp.sum(sizes[:held])):]
    row = {"phase": "ragged", "platform": platform, "rows": T * K, "rows_of_a_held_group": int(jnp.sum(sizes[:held])),
           "bare_ragged_dot_beyond_the_groups": {"finite_share": float(np.isfinite(beyond).mean()),
                                                  "nonzero_share": float((beyond != 0).mean()),
                                                  "max_abs": float(np.nanmax(np.abs(beyond)))}}

    def system(x, gate_vals, experts):
        return L._local_experts(x, gate_vals, gate_idx, experts, n_experts=E, first=first, cd=jnp.bfloat16,
                                platform=platform, activation="relu2")[0]

    def dense(x, gate_vals, experts):
        with jax.default_matmul_precision("highest"):
            out = 0.0
            for e in range(held):
                y = jnp.square(jax.nn.relu(x @ experts["w1"][e])) @ experts["w2"][e]
                out = out + y * jnp.sum(jnp.where(gate_idx == first + e, gate_vals, 0.0), -1)[..., None]
            return out
    w = jax.random.normal(ks[1], x.shape)
    for name, fn in (("system", system), ("dense", dense)):
        out, vjp = jax.vjp(jax.jit(fn), x, gate_vals, experts)
        row[name] = (out, vjp(w))
    got, want = row.pop("system"), row.pop("dense")
    leaves = dict(zip(("out", "dx", "dgates", "dw1", "dw2"), zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want))))
    row["finite"] = {k: bool(np.isfinite(np.asarray(a, np.float32)).all()) for k, (a, _) in leaves.items()}
    row["rel_l2_vs_dense_f32"] = {k: rel(a, b) for k, (a, b) in leaves.items()}
    emit(row)


def check():
    mesh = create_mesh(MeshConfig(**traffic["mesh"]), devices=jax.local_devices()[:1])
    depths = [d for d, kind in enumerate(cfg.pattern) if kind == "E"]

    def pick(params):
        moe = params["moe"]
        return {"wg": moe["wg"], "w1": moe["w1"][:, :2], "w2": moe["w2"][:, :2], "shared_w1": moe["shared_w1"]}

    def put(params, leaves):
        moe = dict(params["moe"], wg=leaves["wg"], shared_w1=leaves["shared_w1"])
        for k in ("w1", "w2"):
            moe[k] = moe[k].at[:, :2].set(leaves[k])
        return dict(params, moe=moe)

    system = jax.jit(compare.loss_and_grads(lambda p, t: nh.loss_fn(p, {"tokens": t}, cfg, mesh)[0], pick, put))
    plain = jax.jit(compare.loss_and_grads(lambda p, t: ref.loss(p, t, filed), pick, put))
    words = sys.argv[sys.argv.index("check") + 1:]
    # "none" among them: every product in ONE bf16 pass, the precision below the program's
    PASSES["which"] = "none" if "none" in words else "all"
    for seed in [int(a) for a in words if a.isdigit()]:
        params = jax.jit(lambda rng: nh.init(rng, cfg))(jax.random.PRNGKey((seed + 1) % 2**31))
        tokens = generate.token_rows(dict(traffic, batches=1, batch=traffic["check_sequences"]), cfg.vocab_size, seed + 1)
        loss, grads = system(params, tokens)
        with jax.default_matmul_precision("highest"):
            ref_loss, ref_grads = plain(params, tokens[:1])
        row = {"phase": "check", "three_pass": PASSES["which"], "seed": seed, "loss": abs(float(loss) - float(ref_loss)) / abs(float(ref_loss))}
        for name in grads:
            row[name + "_by_depth"] = {str(d): rel(grads[name][i], ref_grads[name][i]) for i, d in enumerate(depths)}
        emit(row)
        del params, grads, ref_grads
    PASSES["which"] = "all"


def step():
    devices = jax.local_devices()
    mesh = create_mesh(MeshConfig(**traffic["mesh"]), devices=devices[:1])
    opt = default_optimizer(**traffic["optimizer"])
    rows = generate.token_rows(traffic, cfg.vocab_size, SEED)
    model = dict(filed)
    sizes = trace_ssm._sizes(model, traffic["batch"] * SEQ)
    for which in (sys.argv[sys.argv.index("step") + 1:] or ["all", "none"]):
        if which not in ("all", "none", "proj"):
            break
        PASSES["which"] = which
        state = make_train_state(lambda rng: nh.init(rng, cfg), jax.random.PRNGKey(SEED % 2**31), opt, mesh, nh.partition_specs(cfg))
        fn = make_train_step(lambda p, b: nh.loss_fn(p, b, cfg, mesh), opt, mesh)
        n = 0
        def batch():
            nonlocal n
            n += 1
            return {"tokens": rows[(n - 1) % len(rows)][None]}
        t0 = time.time()
        held_by_step = []
        for _ in range(3):
            state, metrics = fn(state, batch()); loss0 = float(metrics["loss"]); held_by_step.append(int(metrics["moe_held"]))
        row = {"phase": "step", "three_pass": which, "compile_and_3_steps_s": round(time.time() - t0, 1)}
        spans = []
        for _ in range(8):
            t1 = time.perf_counter()
            state, metrics = fn(state, batch()); loss = float(metrics["loss"])
            spans.append(time.perf_counter() - t1); held_by_step.append(int(metrics["moe_held"]))
        row.update(step_ms_median=1e3 * float(np.median(spans)), step_ms_min=1e3 * min(spans), loss_first=loss0, loss_last=loss,
                   moe_held=int(metrics["moe_held"]), moe_assignments=int(metrics["moe_assignments"]))
        plan = fn.lower(state, batch()).compile().memory_analysis()
        row["plan_gb"] = (plan.argument_size_in_bytes + plan.temp_size_in_bytes) / 1e9
        if not TINY:
            shutil.rmtree(TRACE, ignore_errors=True)
            options = jax.profiler.ProfileOptions(); options.python_tracer_level = 0
            jax.profiler.start_trace(TRACE, profiler_options=options)
            for _ in range(4):
                state, metrics = fn(state, batch()); float(metrics["loss"]); held_by_step.append(int(metrics["moe_held"]))
            jax.profiler.stop_trace()
            files = glob.glob(os.path.join(TRACE, "**", "*.xplane.pb"), recursive=True)
            summary = trace_reduce.reduce_trace(trace_reduce.load_xplane(files[0], ()), ())
            steps = summary["steps"]
            row["traced_steps"] = steps
            row["device_ms"] = 1e3 * summary["busy_s"] / steps
            per_op = summary["per_op_s"]
            scan = sum(s for name, s in per_op.items() if trace_ssm._is_scan(name, sizes))
            mixer = sum(s for name, s in per_op.items() if trace_ssm._is_mixer(name, sizes))
            flash = sum(s for name, s in per_op.items() if flops.flash_call_cost(name))
            row.update(scan_ms=1e3 * scan / steps, mixer_ms=1e3 * mixer / steps, flash_ms=1e3 * flash / steps)
            top = sorted(per_op.items(), key=lambda kv: -kv[1])[:60]
            row["top_ops_ms"] = [[round(1e3 * s / steps, 3), ("S" if trace_ssm._is_scan(name, sizes) else "M" if trace_ssm._is_mixer(name, sizes) else "-"),
                                  trace_reduce.short_op_name(name, 150)] for name, s in top]
            with open(os.path.join(OUT, f"per_op_{which}.json"), "w") as f:
                json.dump({name: s / steps for name, s in per_op.items()}, f)
            shutil.rmtree(TRACE, ignore_errors=True)
        row["moe_held_by_step"] = held_by_step
        emit(row)
        del state, fn


for phase in sys.argv[1:]:
    if phase in ("kernels", "flips", "ragged", "check", "step"):
        t0 = time.time()
        {"kernels": kernels, "flips": flips, "ragged": ragged, "check": check, "step": step}[phase]()
        print(f"== {phase} took {time.time() - t0:.0f}s", flush=True)
