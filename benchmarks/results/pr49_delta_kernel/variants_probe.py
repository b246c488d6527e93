"""Where the forward kernel's time goes, by taking parts out (the results
are then WRONG: timing only), one process on the chip:
    python3 variants_probe.py [out.jsonl]
`merges_one_pass`: the inverse's block products in one bfloat16 pass
instead of the highest precision; `no_inverse`: ``T = I − A`` (neither the
substitution nor the merges). `PROBE_TINY=1` rehearses on the CPU."""
import json, os, sys, time
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp
from ray_tpu.ops import gated_delta as gd

out_path = sys.argv[1] if len(sys.argv) > 1 else None
tiny = bool(os.environ.get("PROBE_TINY"))
B, T, G, H, K, V, C = (1, 256, 1, 2, 128, 128, 64) if tiny else \
    (2, 8192, 16, 32, 128, 128, 64)
ks = jax.random.split(jax.random.PRNGKey(0), 3)
qkv = jax.nn.silu(jax.random.normal(ks[0], (B, T, 2 * G * K + H * V)))
g = -jax.nn.softplus(jax.random.normal(ks[1], (B, T, H))) * 0.1
beta = jax.nn.sigmoid(jax.random.normal(ks[2], (B, T, H)))


def ms(fn, *a, n=10):
    jax.block_until_ready(fn(*a))
    t = time.perf_counter()
    for _ in range(n):
        r = fn(*a)
    jax.block_until_ready(r)
    return (time.perf_counter() - t) / n * 1e3


full_inverse, exact = gd._inverse_packed, gd._exact


def single_pass(a, b, dims):
    return jax.lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                               dims, preferred_element_type=jnp.float32)


def neither(A, row, col, second, same_head):
    return (row == col).astype(jnp.float32) - A


variants = {
    "full": {},
    "merges_one_pass": {"_exact": single_pass},
    "no_inverse": {"_inverse_packed": neither},
}
for name, patch in variants.items():
    for k, v in patch.items():
        setattr(gd, k, v)
    jax.clear_caches()
    fn = jax.jit(lambda a, b, c: gd.gated_delta_packed(
        a, b, c, key_heads=G, k_dim=K, chunk=C, normalize=1e-6,
        interpret=tiny))
    line = {"variant": name, "fwd_ms": ms(fn, qkv, g, beta)}
    print(json.dumps(line), flush=True)
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(line) + "\n")
    gd._inverse_packed, gd._exact = full_inverse, exact
