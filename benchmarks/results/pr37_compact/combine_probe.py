"""Alone on the chip, at the two share cells' shapes: the bounded combine
(C rows added into [T, D], a token up to K times) as an XLA scatter-add and
as gathers of T·K rows from the C-row buffer under a mask (laid out as
[T, K, D] and summed, or one gather of T rows a slot, accumulated), beside
the parent's whole combine and the plain row gathers.

    python3 benchmarks/results/pr37_compact/combine_probe.py

One process; prints one JSON line a shape and appends it to
chiprun_out/pr37_compact/combine_probe.jsonl. `PROBE_TINY=1` rehearses it
on the CPU at a small size."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

TINY = os.environ.get("PROBE_TINY") == "1"
SHAPES = {  # cell: tokens, top_k, d_model, scored, held, bound
    "nemotronh9l-b1s8k": (8192, 6, 2688, 128, 8, 6144),
    "smallthinker4l-b1s16k": (16384, 6, 2560, 64, 16, 49152),
}
if TINY:
    SHAPES = {"tiny": (256, 2, 128, 8, 2, 256)}


def timed(fn, *args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return round(1e3 * (time.perf_counter() - t0) / n, 4), out


def main():
    os.makedirs("chiprun_out/pr37_compact", exist_ok=True)
    for cell, (T, K, D, E, held, C) in SHAPES.items():
        rng = np.random.default_rng(0)
        R = T * K
        idx = np.stack([rng.permutation(E)[:K] for _ in range(T)])
        key = idx.reshape(R)
        order = np.argsort(key, kind="stable").astype(np.int32)
        inverse = np.zeros(R, np.int32)
        inverse[order] = np.arange(R, dtype=np.int32)
        held_rows = int((key < held).sum())
        assert held_rows <= C, (held_rows, C)
        tok_c = jnp.asarray(order[:C] // K)
        gates = jnp.asarray(rng.random((T, K), np.float32))
        here = jnp.asarray(idx < held)
        pos = jnp.asarray(np.minimum(inverse, C - 1).reshape(T, K))
        w_c = jnp.where(jnp.arange(C) < held_rows,
                        gates.reshape(R)[order[:C]], 0.0)
        y_c = jnp.asarray(rng.standard_normal((C, D), np.float32),
                          jnp.bfloat16)
        y_c = jnp.where((jnp.arange(C) < held_rows)[:, None], y_c, 0)
        y = jnp.zeros((R, D), jnp.bfloat16).at[:C].set(y_c)
        x2 = jnp.asarray(rng.standard_normal((T, D), np.float32),
                         jnp.bfloat16)
        inv, ordr = jnp.asarray(inverse), jnp.asarray(order)

        @jax.jit
        def masked_gather(y_c, pos, here, gates):
            rows = y_c[pos.reshape(-1)].reshape(T, K, D)
            return jnp.sum(jnp.where(
                here[..., None], rows.astype(jnp.float32) * gates[..., None],
                0.0), axis=1)

        @jax.jit
        def gather_then_sum(y_c, pos, here, gates):
            rows = jax.lax.optimization_barrier(y_c[pos.reshape(-1)])
            return jnp.sum(jnp.where(
                here[..., None], rows.reshape(T, K, D).astype(jnp.float32)
                * gates[..., None], 0.0), axis=1)

        @jax.jit
        def by_slot(y_c, pos, here, gates):
            out = jnp.zeros((T, D), jnp.float32)
            for k in range(K):
                out = out + jnp.where(
                    here[:, k, None], y_c[pos[:, k]].astype(jnp.float32)
                    * gates[:, k, None], 0.0)
            return out

        @jax.jit
        def by_slot_whole(y, inv, here, gates):
            inv = inv.reshape(T, K)
            out = jnp.zeros((T, D), jnp.float32)
            for k in range(K):
                out = out + jnp.where(
                    here[:, k, None], y[inv[:, k]].astype(jnp.float32)
                    * gates[:, k, None], 0.0)
            return out

        @jax.jit
        def scatter_add(y_c, tok_c, w_c):
            return jnp.zeros((T, D), jnp.float32).at[tok_c].add(
                y_c.astype(jnp.float32) * w_c[:, None])

        @jax.jit
        def whole(y, inv, here, gates):
            rows = y[inv].reshape(T, K, D)
            return jnp.sum(jnp.where(
                here[..., None], rows.astype(jnp.float32) * gates[..., None],
                0.0), axis=1)

        @jax.jit
        def take_c(x2, tok_c):
            return x2[tok_c]

        @jax.jit
        def take_all(x2, ordr):
            return x2[ordr // K]

        record = {"cell": cell, "device": jax.devices()[0].device_kind,
                  "tokens": T, "rows": R, "bound": C, "held_rows": held_rows,
                  "d_model": D, "ms": {}}
        ref = None
        for name, fn, args in (
                ("whole_combine", whole, (y, inv, here, gates)),
                ("masked_gather", masked_gather, (y_c, pos, here, gates)),
                ("gather_then_sum", gather_then_sum,
                 (y_c, pos, here, gates)),
                ("by_slot", by_slot, (y_c, pos, here, gates)),
                ("by_slot_whole", by_slot_whole, (y, inv, here, gates)),
                ("scatter_add", scatter_add, (y_c, tok_c, w_c)),
                ("take_bound_rows", take_c, (x2, tok_c)),
                ("take_all_rows", take_all, (x2, ordr))):
            ms, out = timed(fn, *args)
            record["ms"][name] = ms
            if name == "whole_combine":
                ref = out
            elif name not in ("take_bound_rows", "take_all_rows"):
                record.setdefault("max_abs_diff_to_whole", {})[name] = float(
                    jnp.max(jnp.abs(out - ref)))
        line = json.dumps(record)
        print(line, flush=True)
        with open("chiprun_out/pr37_compact/combine_probe.jsonl", "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
