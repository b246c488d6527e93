"""Where a grid step's time goes in the TWO-LOOP form of `kda_fwd` / `kda_bwd`
(`ray_tpu/ops/kda.py`): each kernel ALONE at the cell's size (2 x 8,192 tokens,
32 heads of 128, bf16 products, the norms inside), with one piece of a block's
work taken out or done another way at a time. Variants other than `base` give
WRONG results unless `right` says otherwise: timing only.

    python3 benchmarks/results/pr64_kda_two_loops/loop_probe.py <out.jsonl> \
        <fwd|both> <block> [variant ...]

variants (`VARIANTS`): base; no_inverse (the block's inverses left out);
substitution_only / merge_only (half of the inverse each); per_chunk (two
loops, but each chunk's inverse taken alone in the second: the loops' split
without the sharing); no_second / no_first (a loop left out). The patched
`_two_loops` here and in the variants' files walk the second loop ONE chunk a
body, whatever `together` the kernels ask for. Through the chip
tool; `PROBE_TINY=1` rehearses on the CPU in the interpreter;
`PROBE_DESCRIBED=1` (with `JAX_PLATFORMS=cpu`) only COMPILES each variant for
a described v5e at the cell's size: what Mosaic refuses shows without a
chip."""
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
DESCRIBED = os.environ.get("PROBE_DESCRIBED") == "1"
B, T, H, K = (1, 512, 2, 128) if TINY else (2, 8192, 32, 128)
C = 64
out_file, which, block = sys.argv[1], sys.argv[2], int(sys.argv[3])
names = sys.argv[4:] or ["base"]
if DESCRIBED:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    CHIP = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
ks = jax.random.split(jax.random.PRNGKey(0), 8)
qkv = jax.random.normal(ks[0], (B, T, 3 * H * K))
g = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, H * K)))
beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
d_out = jax.random.normal(ks[5], (B, T, H * K))
rows = kda._kernel_rows(beta, chunk=C)
kept = {name: getattr(kda, name) for name in (
    "_inverse_many", "_two_loops", "_prepared", "_FORWARD_READS",
    "_fwd_kernel")}


def clock(fn, *args, runs=5):
    t0 = time.time()
    if DESCRIBED:
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=CHIP)
                for a in args]
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.time() - t0
    if DESCRIBED:
        return (None, jnp.zeros((T // C, B, H // 2, 2, K, K))), None, compile_s
    out = jax.block_until_ready(compiled(*args))
    t0 = time.time()
    for _ in range(runs):
        last = compiled(*args)
    jax.block_until_ready(last)
    return out, 1e3 * (time.time() - t0) / runs, compile_s


def _no_inverse(ref, m):
    pass


def _half(substitute: bool, merge: bool):
    """`_inverse_many` with one of its halves left out."""
    from jax.experimental.pallas import tpu as pltpu
    _BASE, _exact, _by_head, _NN = kda._BASE, kda._exact, kda._by_head, kda._NN

    def inverse(ref, m):
        n, chunk, width = ref.shape
        blocks = chunk // _BASE
        lane = jax.lax.broadcasted_iota(jnp.int32, (_BASE, width), 1)
        at_tile, block_of = lane % _BASE, lane % chunk // _BASE
        at = jnp.concatenate([at_tile] * n, axis=0)
        A = [ref[c] for c in range(n)]
        own = jnp.concatenate([
            sum(jnp.where(block_of == b, A[c][b * _BASE:(b + 1) * _BASE], 0.0)
                for b in range(blocks)) for c in range(n)], axis=0)
        Tt = (jax.lax.broadcasted_iota(jnp.int32, (n * _BASE, width), 0)
              % _BASE == at).astype(jnp.float32)
        if substitute:
            for j in range(_BASE - 1):
                factor = jnp.where(at == j, own, 0.0)
                if j:
                    factor = pltpu.roll(factor, width - j, 1)
                for reach in (1, 2, 4, 8):
                    factor = factor + pltpu.roll(factor, reach, 1)
                Tt = Tt - factor * jnp.concatenate([
                    jnp.broadcast_to(Tt[c * _BASE + j:c * _BASE + j + 1],
                                     (_BASE, width)) for c in range(n)],
                    axis=0)
        else:
            Tt = Tt + own
        Ts = [jnp.concatenate([
            jnp.where(block_of == b, Tt[c * _BASE:(c + 1) * _BASE], 0.0)
            for b in range(blocks)], axis=0) for c in range(n)]
        row, col, same_head = m["row"], m["col"], m["same_head"]
        side = _BASE
        while merge and side < chunk:
            def second_rows(x):
                return jnp.concatenate(
                    [x[a:a + side] for a in range(side, chunk, 2 * side)],
                    axis=0)

            def placed(x):
                nothing = jnp.zeros((side, width), jnp.float32)
                return jnp.concatenate(
                    [part for a in range(0, chunk // 2, side)
                     for part in (nothing, x[a:a + side])], axis=0)

            off = ((row // (2 * side) == col // (2 * side))
                   & (row // side != col // side))
            rights = [placed(_exact(second_rows(jnp.where(off, A[c], 0.0)),
                                    _by_head(Ts[c], same_head), _NN))
                      for c in range(n)]
            lowers = [_exact(second_rows(Ts[c]),
                             _by_head(rights[c], same_head), _NN)
                      for c in range(n)]
            Ts = [Ts[c] - placed(lowers[c]) for c in range(n)]
            side *= 2
        for c in range(n):
            ref[c] = Ts[c]

    return inverse


def _loops(first_on=True, second_on=True, inverse="many"):
    """`_two_loops` with a loop left out, or each chunk's inverse taken
    alone inside the second loop (`gated_delta._inverse_packed`)."""
    from ray_tpu.ops.gated_delta import _inverse_packed

    def two_loops(masks, held, tree, prepare, finish, reverse=False,
                      together=1):
        def first(c, _):
            for ref, leaf in zip(held, jax.tree_util.tree_leaves(prepare(c))):
                ref[c] = leaf

        chunks = held[0].shape[0]
        if first_on:
            jax.lax.fori_loop(0, chunks, first, None)
        if inverse == "many":
            kda._inverse_many(
                jax.tree_util.tree_unflatten(tree, held)["A"], masks)

        def second(step, _):
            c = chunks - 1 - step if reverse else step
            p = jax.tree_util.tree_unflatten(tree, [ref[c] for ref in held])
            if inverse == "per_chunk":
                p["A"] = _inverse_packed(
                    p["A"], masks["row"], masks["col"], masks["second"],
                    masks["same_head"])
            finish(c, p, masks)

        if second_on:
            jax.lax.fori_loop(0, chunks, second, None)

    return two_loops


VARIANTS = {
    "base": {},
    "no_inverse": {"_inverse_many": _no_inverse},
    "substitution_only": {"_inverse_many": _half(True, False)},
    "merge_only": {"_inverse_many": _half(False, True)},
    "per_chunk": {"_two_loops": _loops(inverse="per_chunk")},
    "no_second": {"_two_loops": _loops(second_on=False)},
    "no_first": {"_two_loops": _loops(first_on=False)},
    "only_inverse": {"_two_loops": _loops(first_on=False, second_on=False)},
}
if os.environ.get("PROBE_VARIANTS"):
    # more variants from a file that defines `variants(kda) -> dict`
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "more_variants", os.environ["PROBE_VARIANTS"])
    more = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(more)
    VARIANTS.update(more.variants(kda))

kw = dict(k_dim=K, v_dim=K, chunk=C, block=block,
          cd=jnp.dtype(jnp.bfloat16), normalize=1e-6, interpret=TINY)
os.makedirs(os.path.dirname(os.path.abspath(out_file)), exist_ok=True)
base_out = {}
for name in names:
    for attr, fn in kept.items():
        setattr(kda, attr, fn)
    for attr, fn in VARIANTS[name].items():
        setattr(kda, attr, fn)
    jax.clear_caches()
    row = {"variant": name, "block": block,
           "device": jax.devices()[0].device_kind}
    try:
        (out, starts), row["fwd_ms"], row["fwd_compile_s"] = clock(
            lambda a, b, r: kda._kda_fwd(a, b, r, **kw), qkv, g, rows)
        outs = [out]
        if which == "both":
            grads, row["bwd_ms"], row["bwd_compile_s"] = clock(
                lambda a, b, r, s, d: kda._kda_bwd(a, b, r, s, d, **kw),
                qkv, g, rows, base_out.get("starts", starts), d_out)
            outs += list(grads)
        if DESCRIBED:
            pass
        elif name == "base":
            base_out = {"starts": starts, "outs": outs}
        elif "outs" in base_out:
            # how far from `base` (0.0: the same arithmetic)
            row["against_base"] = [float(
                jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                for a, b in zip(outs, base_out["outs"])]
    except Exception as e:  # noqa: BLE001
        row["refused"] = str(e)[:1500]
    print(json.dumps(row), flush=True)
    with open(out_file, "a") as f:
        f.write(json.dumps(row) + "\n")
