"""Kimi Delta Attention's rule ALONE at the cell's size (2 x 8,192 tokens, 32
heads of 128, bf16 products, the norms inside), one process: the plain form
and the kernels (`kda_fwd` / `kda_bwd`) — how near the kernels' output and
every gradient lie to the plain form's, and the milliseconds of the forward
and of the gradient of a scalar of it, the kernels at each block of tokens
given (the module's constant `BLOCK_TOKENS`):

    python3 benchmarks/results/pr59_kda_kernel/rule_probe.py <out.jsonl> [256 512 ...]

(`rule_probe.jsonl`'s lines of the earlier forms carry `unrolled` and `slab`
too: the chunks a loop body held and the key channels the diagonal terms took
at a time, knobs the module no longer has.)

from the repo root, through the chip tool; `PROBE_TINY=1` rehearses on the
CPU at a small size (the kernels in the interpreter); `PROBE_MODULE=<file>`
probes another form of the module from a file — an unshipped one, or the
parent's (`.bench_tree/parent/ray_tpu/ops/kda.py`: PR 64 probed PR 59's
`kda_two_loops.py`, a block's chunks in two loops, this way before it became
the module, and the one-loop module beside it afterwards). PR 58's probe of
the plain form alone: `benchmarks/results/pr58_kimi_linear/rule_probe.py`."""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import kda  # noqa: E402

if os.environ.get("PROBE_MODULE"):
    # another form of the module, from a file (the plain form is its own)
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "kda_probed", os.environ["PROBE_MODULE"])
    kda = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kda)

TINY = os.environ.get("PROBE_TINY") == "1"
B, T, H, K = (1, 256, 2, 128) if TINY else (2, 8192, 32, 128)
out_file = sys.argv[1]
blocks = [int(v) for v in sys.argv[2:]] or [kda.BLOCK_TOKENS]
ks = jax.random.split(jax.random.PRNGKey(0), 6)
qkv = jax.random.normal(ks[0], (B, T, 3 * H * K))
g = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, H * K)))
beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
w = jax.random.normal(ks[5], (B, T, H, K))
args = (qkv, g, beta)


def clock(fn, runs=5):
    t0 = time.time()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.time() - t0
    out = jax.block_until_ready(compiled(*args))
    t0 = time.time()
    for _ in range(runs):
        last = compiled(*args)
    jax.block_until_ready(last)
    plan = compiled.memory_analysis()
    return out, {"ms": 1e3 * (time.time() - t0) / runs,
                 "compile_s": compile_s,
                 "temp_gb": plan.temp_size_in_bytes / 1e9}


def rule(qkv, g, beta):
    return kda.kda_packed(qkv, g, beta, k_dim=K, chunk=64, normalize=1e-6,
                          interpret=TINY and kda._use_kernel is use_kernel)


def gradient(qkv, g, beta):
    return jax.grad(lambda *a: jnp.sum(rule(*a) * w), argnums=(0, 1, 2))(
        qkv, g, beta)


def rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def row_of(form, **more):
    out, forward = clock(rule)
    grads, backward = clock(gradient)
    return (out, grads), {
        "device": jax.devices()[0].device_kind, "shape": [B, T, H, K],
        "form": form, **more, "forward": forward, "gradient": backward}


def write(row):
    print(json.dumps(row), flush=True)
    with open(out_file, "a") as f:
        f.write(json.dumps(row) + "\n")


os.makedirs(os.path.dirname(os.path.abspath(out_file)), exist_ok=True)
use_kernel = kda._use_kernel
kda._use_kernel = lambda *a: False
(plain_out, plain_grads), row = row_of("plain")
write(row)
kda._use_kernel = use_kernel
for block in blocks:
    # (a sweep may pass the budget `_use_kernel` holds the default block to)
    kda.BLOCK_TOKENS, kda.VMEM_BUDGET_BYTES = block, 56 * 1024 * 1024
    jax.clear_caches()
    knobs = {"block": block}
    try:
        (out, grads), row = row_of("kernels", **knobs)
    except Exception as e:  # noqa: BLE001
        write({"form": "kernels", **knobs, "refused": str(e)[:2000]})
        continue
    d_qkv, plain_qkv = grads[0], plain_grads[0]
    parts = {"q": slice(0, H * K), "k": slice(H * K, 2 * H * K),
             "v": slice(2 * H * K, None)}
    row["against_plain"] = {
        "o": rel(out, plain_out),
        **{name: rel(d_qkv[..., at], plain_qkv[..., at])
           for name, at in parts.items()},
        "g": rel(grads[1], plain_grads[1]),
        "beta": rel(grads[2], plain_grads[2]),
        "finite": bool(all(jnp.all(jnp.isfinite(x))
                           for x in (out, *grads)))}
    write(row)
