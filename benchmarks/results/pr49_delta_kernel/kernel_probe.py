"""The rule at the cell's layer on the chip, one process: the kernels
against the plain form (values and the five gradients) and ms a call by
block size, forward alone and forward + backward:
    python3 kernel_probe.py [256,512x2,1024] [out.jsonl]
(a block of tokens, `x` the chunks the inner loop's body holds)
`PROBE_TINY=1` rehearses on the CPU (interpreter, one key head)."""
import json, os, sys, time
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp
import numpy as np
from ray_tpu.ops import gated_delta as gd

tiny = bool(os.environ.get("PROBE_TINY"))
blocks = [tuple(int(x) for x in b.split("x")) for b in
          (sys.argv[1] if len(sys.argv) > 1 else "512").split(",")]
out_path = sys.argv[2] if len(sys.argv) > 2 else None
B, T, G, H, K, V, C = (1, 256, 1, 2, 128, 128, 64) if tiny else \
    (2, 8192, 16, 32, 128, 128, 64)
kw = dict(key_heads=G, k_dim=K, chunk=C, normalize=1e-6, interpret=tiny)
ks = jax.random.split(jax.random.PRNGKey(int(os.environ.get("PROBE_SEED", 0))), 4)
qkv = jax.nn.silu(jax.random.normal(ks[0], (B, T, 2 * G * K + H * V)))
g = -jax.nn.softplus(jax.random.normal(ks[1], (B, T, H))) * 0.1
beta = jax.nn.sigmoid(jax.random.normal(ks[2], (B, T, H)))
weights = jax.random.normal(ks[3], (B, T, H, V))


def plain(qkv, g, beta):
    chunked = gd._to_chunks(*gd._split(qkv, g, G, K), g, beta, C)
    return gd._from_chunks(gd._rule(*chunked, jnp.bfloat16, 1e-6), T)


def kernel(qkv, g, beta):
    return gd.gated_delta_packed(qkv, g, beta, **kw)


def grads(fn):
    both = jax.jit(jax.grad(lambda w, *a: jnp.sum(fn(*a) * w),
                            argnums=(1, 2, 3)))
    return lambda *a: both(weights, *a)


def ms(fn, *a, n=3 if tiny else 10):
    jax.block_until_ready(fn(*a))
    t = time.perf_counter()
    for _ in range(n):
        r = fn(*a)
    jax.block_until_ready(r)
    return (time.perf_counter() - t) / n * 1e3


def rel(a, b):
    a, b = (np.asarray(x, np.float64) for x in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


lines = []
want, want_g = jax.jit(plain)(qkv, g, beta), grads(plain)(qkv, g, beta)
line = {"what": "plain", "device": jax.devices()[0].device_kind,
        "fwd_ms": ms(jax.jit(plain), qkv, g, beta),
        "fwd_bwd_ms": ms(grads(plain), qkv, g, beta)}
print(json.dumps(line), flush=True)
lines.append(line)
for block, *unrolled in blocks:
    gd.BLOCK_TOKENS = block
    gd.CHUNKS_UNROLLED = unrolled[0] if unrolled else gd.CHUNKS_UNROLLED
    jax.clear_caches()
    text = jax.jit(kernel).lower(qkv, g, beta).as_text()
    print("backend", jax.default_backend(), "kernel in program:",
          "delta_fwd" in text, flush=True)
    got, got_g = jax.jit(kernel)(qkv, g, beta), grads(kernel)(qkv, g, beta)
    line = {"what": "kernel", "block": block,
            "chunks_unrolled": gd.CHUNKS_UNROLLED,
            "fwd_ms": ms(jax.jit(kernel), qkv, g, beta),
            "fwd_bwd_ms": ms(grads(kernel), qkv, g, beta),
            "rel_o": rel(got, want),
            "rel_grads": [rel(a, b) for a, b in zip(got_g, want_g)]}
    print(json.dumps(line), flush=True)
    lines.append(line)
if out_path:
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
