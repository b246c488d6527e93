"""Where a chunk's time goes inside `kda_fwd` / `kda_bwd`: the rule alone at
the cell's size with one piece of a chunk's arithmetic taken out at a time
(WRONG results, timing only — the pieces are patched here, the module is as
shipped):

    python3 benchmarks/results/pr59_kda_kernel/ablate.py <out.jsonl> [piece ...]

pieces: base, no_band (the diagonal sub-blocks' element-wise terms and their
pullback), no_inverse (T = A), one_pass_exact (every six-pass product one
pass), no_norm (normalize None). Through the chip tool; `PROBE_TINY=1`
rehearses on the CPU. Since PR 64 the module takes a block's inverses at once
between two loops (`_inverse_many`, which `no_inverse` leaves out);
`PROBE_MODULE=<file>` takes the pieces out of another form of the module (the
parent's one-loop form, whose inverse is `_inverse_packed`)."""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import kda  # noqa: E402

if os.environ.get("PROBE_MODULE"):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "kda_probed", os.environ["PROBE_MODULE"])
    kda = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kda)

TINY = os.environ.get("PROBE_TINY") == "1"
B, T, H, K = (1, 256, 2, 128) if TINY else (2, 8192, 32, 128)
out_file = sys.argv[1]
pieces = sys.argv[2:] or ["base", "no_band", "no_inverse", "one_pass_exact",
                          "no_norm"]
ks = jax.random.split(jax.random.PRNGKey(0), 6)
qkv = jax.random.normal(ks[0], (B, T, 3 * H * K))
g = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, H * K)))
beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
args = (qkv, g, beta)
INVERSE = ("_inverse_many" if hasattr(kda, "_inverse_many")
           else "_inverse_packed")
kept = {name: getattr(kda, name) for name in (
    "_band", "_band_pull", INVERSE, "_exact")}


def clock(fn, runs=5):
    compiled = jax.jit(fn).lower(*args).compile()
    jax.block_until_ready(compiled(*args))
    t0 = time.time()
    for _ in range(runs):
        last = compiled(*args)
    jax.block_until_ready(last)
    return 1e3 * (time.time() - t0) / runs


def patch(piece):
    for name, fn in kept.items():
        setattr(kda, name, fn)
    if piece == "no_band":
        kda._band = lambda qT, kT, cumT: (
            qT[:kda._BASE] + cumT[:kda._BASE], kT[:kda._BASE])
        kda._band_pull = lambda qT, kT, cumT, d_kk, d_qk: (
            qT * d_qk[:1], kT * d_kk[:1], cumT)
    elif piece == "no_inverse":
        setattr(kda, INVERSE, {
            "_inverse_many": lambda ref, masks: None,
            "_inverse_packed": lambda A, *geometry: A}[INVERSE])
    elif piece == "one_pass_exact":
        kda._exact = lambda a, b, dims: jax.lax.dot_general(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims,
            preferred_element_type=jnp.float32)


for piece in pieces:
    patch(piece)
    jax.clear_caches()
    normalize = None if piece == "no_norm" else 1e-6

    def rule(qkv, g, beta):
        return kda.kda_packed(qkv, g, beta, k_dim=K, chunk=64,
                              normalize=normalize, interpret=TINY)

    def gradient(qkv, g, beta):
        return jax.grad(lambda *a: jnp.sum(rule(*a) ** 2),
                        argnums=(0, 1, 2))(qkv, g, beta)

    try:
        row = {"piece": piece, "forward_ms": clock(rule),
               "gradient_ms": clock(gradient)}
    except Exception as e:  # noqa: BLE001
        row = {"piece": piece, "refused": str(e)[:1500]}
    print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(out_file)), exist_ok=True)
    with open(out_file, "a") as f:
        f.write(json.dumps(row) + "\n")
