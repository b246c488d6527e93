"""The scan at the cell's layer on the chip, one process: the kernels against
the plain form (values and the seven gradients, relative to the plain form's
largest magnitude) and ms a call by block of tokens, forward alone and forward
+ backward, each kernel by its own name from a device profile:
    python3 kernel_probe.py [32,64,128] [out.jsonl]
`PROBE_TINY=1` rehearses on the CPU (interpreter, 256 channels)."""
import glob, json, os, shutil, sys, time
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp
import numpy as np
from ray_tpu.ops import selective_scan as ss

tiny = bool(os.environ.get("PROBE_TINY"))
blocks = [int(b) for b in (sys.argv[1] if len(sys.argv) > 1 else "64").split(",")]
out_path = sys.argv[2] if len(sys.argv) > 2 else None
B, T, C, N = (1, 64, 256, 16) if tiny else (1, 8192, 5120, 16)
ks = jax.random.split(jax.random.PRNGKey(int(os.environ.get("PROBE_SEED", 0))), 8)
# the layer's own ranges: s behind a SiLU, Δ 1e-3…1e-1, A = −(1…N)
inputs = (jax.nn.silu(jax.random.normal(ks[0], (B, T, C))),
          jax.random.normal(ks[1], (B, T, C)) - 4.0,
          -jnp.broadcast_to(jnp.arange(1.0, N + 1), (C, N)),
          jax.random.normal(ks[2], (B, T, N)),
          jax.random.normal(ks[3], (B, T, N)),
          jnp.ones((C,)), jax.random.normal(ks[4], (C,)) * 0.5)
weights = jax.random.normal(ks[5], (B, T, C))
names = ("s", "dt", "a", "b_in", "c_out", "d_skip", "dt_bias")


def plain(*a):
    saved = ss.target
    ss.target = type("T", (), {"where": staticmethod(
        lambda mesh=None, *, interpret=False: ("cpu", 1))})
    try:
        return ss.selective_scan(*a)
    finally:
        ss.target = saved


def kernel(*a):
    return ss.selective_scan(*a, interpret=tiny)


def grads(fn):
    both = jax.jit(jax.grad(lambda w, *a: jnp.sum(fn(*a) * w),
                            argnums=tuple(range(1, 8))))
    return lambda *a: both(weights, *a)


def ms(fn, *a, n=2 if tiny else 5):
    jax.block_until_ready(fn(*a))
    t = time.perf_counter()
    for _ in range(n):
        r = fn(*a)
    jax.block_until_ready(r)
    return (time.perf_counter() - t) / n * 1e3


def rel(a, b):
    a, b = (np.asarray(x, np.float64) for x in (a, b))
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def by_name(fn, *a):
    """ms a call of each `sscan_*` kernel from a device profile of four
    calls (the reduction keeps whole periods, `summary["steps"]` of them:
    this PR's first two probe calls divided by the three calls they made
    where it kept two, and their `ms_by_name` read 2/3 of the truth)."""
    if tiny:
        return {}
    from chipbench import trace_reduce
    trace = os.path.join(os.getcwd(), "chiprun_out", "sscan_probe_trace")
    shutil.rmtree(trace, ignore_errors=True)
    jax.profiler.start_trace(trace)
    for _ in range(4):
        r = fn(*a)
    jax.block_until_ready(r)
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(trace, "**", "*.xplane.pb"), recursive=True)
    summary = trace_reduce.reduce_trace(trace_reduce.load_xplane(files[0], ()), ())
    shutil.rmtree(trace, ignore_errors=True)
    out, calls = {}, summary["steps"]
    for text, seconds in summary["per_op_s"].items():
        name = text.partition(" = ")[0].strip().lstrip("%")
        if seconds * 1e3 / calls > 0.05:
            out[name] = round(seconds * 1e3 / calls, 3)
    return dict(sorted(out.items(), key=lambda kv: -kv[1])[:12])


lines = []
want, want_g = jax.jit(plain)(*inputs), grads(plain)(*inputs)
line = {"what": "plain", "device": jax.devices()[0].device_kind,
        "fwd_ms": ms(jax.jit(plain), *inputs),
        "fwd_bwd_ms": ms(grads(plain), *inputs)}
print(json.dumps(line), flush=True)
lines.append(line)
for block in blocks:
    ss.BLOCK_TOKENS = block
    if os.environ.get("PROBE_TOKENS_A_BODY"):
        ss.TOKENS_A_BODY = int(os.environ["PROBE_TOKENS_A_BODY"])
    if tiny:
        ss.BLOCK_TILES = 1
    jax.clear_caches()
    try:
        got, got_g = jax.jit(kernel)(*inputs), grads(kernel)(*inputs)
    except Exception as e:
        print("block", block, "REFUSED", str(e)[:1500], flush=True)
        continue
    line = {"what": "kernel", "block": block, "tiles": ss.BLOCK_TILES,
            "tokens_a_body": getattr(ss, "TOKENS_A_BODY", None),
            "fwd_ms": ms(jax.jit(kernel), *inputs),
            "fwd_bwd_ms": ms(grads(kernel), *inputs),
            "rel_y": rel(got, want),
            "rel_grads": {n: rel(a, b) for n, a, b in zip(names, got_g, want_g)},
            "ms_by_name": by_name(grads(kernel), *inputs)}
    print(json.dumps(line), flush=True)
    lines.append(line)
if out_path:
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
