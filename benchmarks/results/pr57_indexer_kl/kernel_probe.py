"""PR 57's probe, on the chip: the indexer's loss and its gradient at the
cell's size (B 1, S 16,384, 32 heads on 4 KV heads of 128, top-2,048), the
parent's form (`sparse_mean_probs` + the plain `indexer_kl`, from
.bench_tree/parent_bench) against this tree's two kernels — milliseconds a call
on the host's clock around `block_until_ready`, and how far the values lie
apart. One JSON line a variant to `<out>`.

    python3 benchmarks/results/pr57_indexer_kl/kernel_probe.py <out.jsonl> <seed> [variant ...]

Variants: parent, fused (the tree as it is), rolled (the heads' loop not
unrolled), tile256."""
import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.getcwd())
from ray_tpu.ops import flash_attention as fa          # noqa: E402
from ray_tpu.ops import sparse_attention as sa         # noqa: E402

OUT, SEED = sys.argv[1], int(sys.argv[2])
VARIANTS = sys.argv[3:] or ["parent", "fused"]
B, S, H, KV, D, TOPK = 1, 16384, 32, 4, 128, 2048
REPEATS = 10


def parent_module():
    spec = importlib.util.spec_from_file_location(
        "parent_sparse_attention",
        ".bench_tree/parent_bench/ray_tpu/ops/sparse_attention.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def timed(fn, *args):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(REPEATS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / REPEATS * 1e3, out


def main():
    ks = jax.random.split(jax.random.PRNGKey(SEED % (2 ** 31)), 5)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.bfloat16)
    below = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    scores = jnp.where(below, jax.random.normal(ks[3], (B, S, S)), sa.NEG_INF)
    keep = jax.jit(lambda s: sa.select(s, TOPK))(scores)
    _, lse = jax.jit(lambda q, k, v, keep: sa.sparse_attention(
        q, k, v, keep))(q, k, v, keep)
    d = jnp.full((B,), 0.37, jnp.float32)
    want = {}
    os.makedirs(os.path.dirname(OUT) or ".", exist_ok=True)
    for variant in VARIANTS:
        row = {"variant": variant, "seed": SEED,
               "device": jax.devices()[0].device_kind}
        if variant == "parent":
            old = parent_module()
            value = jax.jit(lambda q, k, lse, keep, s: old.indexer_kl(
                s, old.mean_probs(q, k, lse, keep), keep))
            probs = jax.jit(lambda q, k, lse, keep: old.mean_probs(
                q, k, lse, keep))
            row["mean_probs_ms"], p = timed(probs, q, k, lse, keep)
            row["kl_fwd_of_probs_ms"], _ = timed(jax.jit(
                lambda s, p, keep: old.indexer_kl(s, p, keep)), scores, p,
                keep)
            row["kl_bwd_of_probs_ms"], _ = timed(jax.jit(
                lambda s, p, keep: jax.vjp(
                    lambda s: old.indexer_kl(s, p, keep), s)[1](d)[0]),
                scores, p, keep)
            del p
            grad = jax.jit(lambda q, k, lse, keep, s: jax.vjp(
                lambda s: old.indexer_kl(
                    s, old.mean_probs(q, k, lse, keep), keep), s)[1](d)[0])
        else:
            loop, tile = fa._loop, sa.PROB_TILE
            if variant == "rolled":
                fa._loop = jax.lax.fori_loop
            if variant == "tile256":
                sa.PROB_TILE = 256
            value = jax.jit(lambda q, k, lse, keep, s: sa.indexer_loss(
                q, k, lse, keep, s))
            grad = jax.jit(lambda q, k, lse, keep, s: jax.vjp(
                lambda s: sa.indexer_loss(q, k, lse, keep, s), s)[1](d)[0])
        t0 = time.perf_counter()
        row["value_ms"], kl = timed(value, q, k, lse, keep, scores)
        # value-and-gradient: forward, then the backward rule
        row["value_and_grad_ms"], g = timed(grad, q, k, lse, keep, scores)
        row["compile_and_run_s"] = time.perf_counter() - t0
        if variant != "parent":
            fa._loop, sa.PROB_TILE = loop, tile
        row["kl"] = float(kl[0])
        g = np.asarray(g)
        row["grad_abs_max"] = float(np.abs(g).max())
        row["grad_nonzero_where_not_kept"] = int(
            np.count_nonzero(g[np.asarray(keep) == 0]))
        if "kl" in want:
            row["kl_rel_to_first"] = abs(row["kl"] / want["kl"] - 1.0)
            row["grad_max_diff_to_first"] = float(
                np.abs(g - want["grad"]).max())
        else:
            want = {"kl": row["kl"], "grad": g}
        with open(OUT, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)


main()
