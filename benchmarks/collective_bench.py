"""Collective bus-bandwidth harness — BASELINE.md north-star metric #2.

Reference shape: python/ray/util/collective/examples/ (allreduce/p2p
latency + bandwidth scripts run at several payload sizes). Reports
algorithm bandwidth (payload / wall time) and NCCL-convention bus
bandwidth for each (backend, op, size):

    allreduce:      busbw = algbw * 2(n-1)/n
    allgather:      busbw = algbw *  (n-1)/n
    reducescatter:  busbw = algbw *  (n-1)/n

Size semantics follow nccl-tests so backends are comparable: `size` is
the PER-RANK input buffer for allreduce and reducescatter, and the TOTAL
gathered output (per-rank input = size/n) for allgather. algbw = size/t
in all cases.

Backends:
  host       N actor processes, ring/tree collectives over sockets
             (ray_tpu.util.collective "host" backend)
  xla-local  shard_map collectives on the in-process device mesh
             (8 virtual CPU devices under the test env; real chips on
             TPU hosts) — the compiled-program path that rides ICI
  tpu        xla-local on the local TPU chips; exits non-zero when JAX
             finds none. Single-chip worlds are reported with n=1 so
             the degenerate case is explicit

Usage:
  python benchmarks/collective_bench.py --backend host --world 2 \
      --sizes-mb 1 8 64 --repeats 5
  python benchmarks/collective_bench.py --backend xla-local

Each result prints as ONE JSON line; a summary table follows on stderr.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OPS = ("allreduce", "allgather", "reducescatter")


def bus_factor(op: str, n: int) -> float:
    if n <= 1:
        return 1.0
    if op == "allreduce":
        return 2.0 * (n - 1) / n
    return (n - 1) / n


def emit(result: dict):
    print(json.dumps(result), flush=True)


# --------------------------------------------------------------- host backend

# conservative per-format per-hop quantization step, relative to the
# running partial sum's absmax (bf16: half ULP of an 8-bit mantissa;
# int8: half a step of a 127-level block scale)
WIRE_Q = {"bf16": 2.0 ** -8, "int8": 1.0 / 254.0}


def _host_bench_actor_cls():
    import numpy as np

    import ray_tpu
    from ray_tpu.util.collective import CollectiveActorMixin

    @ray_tpu.remote
    class BenchRank(CollectiveActorMixin):
        def wire_error(self, size_bytes: int, fmt: str) -> dict:
            """Measured allreduce error under the active wire format,
            against a locally reconstructed exact (float64) oracle.
            Returns the max-abs error and the DOCUMENTED bound: at most
            `world` quantized hops (world-1 reduce steps + the final
            chunk's own encode), each within q_fmt of the running
            partial's absmax, which is itself bounded by the sum of the
            ranks' input absmaxes."""
            from ray_tpu.util import collective as col

            n = col.get_collective_group_size()
            rank = col.get_rank()
            elems = max(1, size_bytes // 4)
            ins = [np.random.RandomState(1000 + r)
                   .standard_normal(elems).astype(np.float32)
                   for r in range(n)]
            got = np.asarray(col.allreduce(ins[rank])).astype(np.float64)
            exact = np.zeros(elems, np.float64)
            for x in ins:
                exact += x
            err = float(np.abs(got - exact).max())
            absmax_sum = float(sum(np.abs(x).max() for x in ins))
            q = WIRE_Q.get(fmt, 0.0)
            return {"max_abs_err": err,
                    "err_bound": n * q * absmax_sum,
                    "absmax_sum": absmax_sum}

        def bench_async(self, size_bytes: int, repeats: int,
                        window: int) -> list:
            """Per-op wall times of `window` async allreduces submitted
            back-to-back and waited together. window=1 vs the sync
            `bench` rows is the pure handle overhead (submit + issue-
            thread handoff + handle wakeup); larger windows measure the
            pipelined submission path the bucketed-DDP plane rides."""
            from ray_tpu.util import collective as col

            elems = max(1, size_bytes // 4)
            arr = np.ones(elems, dtype=np.float32)
            col.allreduce_async(arr).result(120)       # warmup
            col.barrier()
            out = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                handles = [col.allreduce_async(arr)
                           for _ in range(window)]
                for h in handles:
                    h.result(600)
                out.append((time.perf_counter() - t0) / window)
            return out

        def bench(self, op: str, size_bytes: int, repeats: int) -> list:
            """Returns per-op wall times (seconds), one per repeat —
            the caller derives mean (headline, comparable to earlier
            rounds) plus p50/min (steady-state vs scheduler-outlier
            split on shared boxes)."""
            from ray_tpu.util import collective as col

            n = col.get_collective_group_size()
            elems = max(1, size_bytes // 4)
            if op == "reducescatter":
                # per-rank input = size, divisible into n shards
                elems = max(n, elems - elems % n)
            elif op == "allgather":
                # nccl-tests convention: size = total gathered output,
                # so each rank contributes size/n
                elems = max(1, elems // n)
            arr = np.ones(elems, dtype=np.float32)
            fn = {
                "allreduce": lambda: col.allreduce(arr),
                "allgather": lambda: col.allgather(arr),
                "reducescatter": lambda: col.reducescatter(arr),
            }[op]
            fn()                      # warmup
            col.barrier()             # synchronized start
            out = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                fn()
                out.append(time.perf_counter() - t0)
            return out

    return BenchRank


def run_host(world: int, sizes: list[int], repeats: int,
             extra: dict | None = None,
             wire_fmt: str | None = None) -> list[dict]:
    import ray_tpu
    from ray_tpu.util import collective as col

    ray_tpu.init(num_cpus=max(4, world),
                 object_store_memory=256 * 1024 * 1024)
    try:
        BenchRank = _host_bench_actor_cls()
        actors = [BenchRank.options(num_cpus=0).remote()
                  for _ in range(world)]
        col.create_collective_group(actors, world, list(range(world)),
                                    backend="host")
        out = []
        for op in OPS:
            for size in sizes:
                err_stats = None
                if wire_fmt is not None and op == "allreduce":
                    # measured quantization error + documented bound,
                    # same cluster/knobs as the timed rows (worst rank)
                    errs = ray_tpu.get(
                        [a.wire_error.remote(size, wire_fmt)
                         for a in actors], timeout=600)
                    err_stats = max(errs, key=lambda e: e["max_abs_err"])
                per_rank = ray_tpu.get(
                    [a.bench.remote(op, size, repeats) for a in actors],
                    timeout=1800)
                # slowest rank bounds the op; mean is the headline
                # (comparable to earlier rounds), p50/min expose the
                # scheduler-outlier share on shared dev boxes
                per_op = [max(ts) for ts in zip(*per_rank)]
                dt = sum(per_op) / len(per_op)
                p50 = sorted(per_op)[len(per_op) // 2]
                best = min(per_op)
                bf = bus_factor(op, world)
                algbw = size / dt / 1e9
                out.append({
                    "backend": "host", "op": op, "size_bytes": size,
                    "world": world, "time_s": round(dt, 6),
                    "algbw_GBps": round(algbw, 4),
                    "busbw_GBps": round(algbw * bf, 4),
                    "p50_busbw_GBps": round(size / p50 / 1e9 * bf, 4),
                    "best_busbw_GBps": round(size / best / 1e9 * bf, 4),
                    **({"quant_max_abs_err": err_stats["max_abs_err"],
                        "quant_err_bound": err_stats["err_bound"]}
                       if err_stats else {}),
                    **(extra or {}),
                })
                emit(out[-1])
        return out
    finally:
        ray_tpu.shutdown()


def run_host_sweep(world: int, sizes: list[int], repeats: int,
                   segment_sweep: list[int] | None,
                   pipeline: str | None) -> list[dict]:
    """Host-backend runs across the pipeline knobs. Each configuration
    gets a fresh cluster (the knobs ride env vars that member worker
    processes inherit at spawn), and each row records the knob values so
    the JSON artifact is self-describing."""
    if pipeline is not None:
        os.environ["RAY_TPU_COLLECTIVE_PIPELINE"] = \
            "1" if pipeline == "on" else "0"
    pipe_on = os.environ.get("RAY_TPU_COLLECTIVE_PIPELINE", "1") != "0"
    rows = []
    for seg in (segment_sweep or [None]):
        if seg is not None:
            os.environ["RAY_TPU_COLLECTIVE_SEGMENT_BYTES"] = str(int(seg))
        from ray_tpu._private.config import get_config

        rows += run_host(world, sizes, repeats, extra={
            "pipeline": pipe_on,
            "segment_bytes": int(get_config("collective_segment_bytes")),
        })
    return rows


def run_wire_sweep(world: int, sizes: list[int], repeats: int,
                   wire_dtypes: list[str], keep_shm: bool) -> list[dict]:
    """Host-backend sweep across wire formats, one fresh cluster per
    format, ALWAYS anchored by a same-run `off` baseline. Unless
    --wire-shm is passed, the whole sweep (baseline included) runs with
    the same-node shm transport off: quantization is an INTER-host wire
    feature — in production the intra-host hierarchy keeps same-host
    hops exact, so the socket path is the wire a cross-host deployment
    actually quantizes, and comparing both configs on it is the
    apples-to-apples measurement. Rows record wire_dtype +
    collective_shm so the artifact is self-describing, and allreduce
    rows carry the measured max-abs error against an exact float64
    oracle plus the documented bound (world * q_fmt * sum of per-rank
    input absmaxes)."""
    fmts = list(wire_dtypes)
    if "off" not in fmts:
        fmts.insert(0, "off")
    else:
        fmts.sort(key=lambda f: f != "off")   # baseline first
    if not keep_shm:
        os.environ["RAY_TPU_COLLECTIVE_SHM"] = "0"
    rows = []
    for fmt in fmts:
        os.environ["RAY_TPU_COLLECTIVE_WIRE_DTYPE"] = fmt
        from ray_tpu._private.config import get_config

        rows += run_host(
            world, sizes, repeats,
            extra={
                "wire_dtype": fmt,
                "collective_shm": bool(get_config("collective_shm")),
                "segment_bytes":
                    int(get_config("collective_segment_bytes")),
                "quant_block":
                    int(get_config("collective_quant_block")),
            },
            wire_fmt=fmt)
    baseline = {(r["op"], r["size_bytes"]): r for r in rows
                if r["wire_dtype"] == "off"}
    for r in rows:
        base = baseline.get((r["op"], r["size_bytes"]))
        if base is not None and r["wire_dtype"] != "off":
            r["p50_speedup_vs_off"] = round(
                r["p50_busbw_GBps"] / max(base["p50_busbw_GBps"], 1e-9), 3)
    return rows


def run_async_sweep(world: int, sizes: list[int], repeats: int,
                    windows: list[int] | None = None) -> list[dict]:
    """--async: handle-overhead sweep. For each size: a sync-allreduce
    baseline, then async submissions at each window depth (window=1
    isolates the per-op handle overhead; deeper windows measure the
    pipelined submission path). One cluster for the whole sweep — the
    knobs don't change between rows."""
    import ray_tpu
    from ray_tpu.util import collective as col

    windows = windows or [1, 4]
    ray_tpu.init(num_cpus=max(4, world),
                 object_store_memory=256 * 1024 * 1024)
    try:
        BenchRank = _host_bench_actor_cls()
        actors = [BenchRank.options(num_cpus=0).remote()
                  for _ in range(world)]
        col.create_collective_group(actors, world, list(range(world)),
                                    backend="host")
        rows = []
        for size in sizes:
            per_rank = ray_tpu.get(
                [a.bench.remote("allreduce", size, repeats)
                 for a in actors], timeout=1800)
            sync_ops = [max(ts) for ts in zip(*per_rank)]
            sync_p50 = sorted(sync_ops)[len(sync_ops) // 2]
            rows.append({
                "backend": "host", "op": "allreduce", "mode": "sync",
                "size_bytes": size, "world": world,
                "p50_time_s": round(sync_p50, 6),
                "p50_busbw_GBps": round(
                    size / sync_p50 / 1e9
                    * bus_factor("allreduce", world), 4),
            })
            emit(rows[-1])
            for window in windows:
                per_rank = ray_tpu.get(
                    [a.bench_async.remote(size, repeats, window)
                     for a in actors], timeout=1800)
                per_op = [max(ts) for ts in zip(*per_rank)]
                p50 = sorted(per_op)[len(per_op) // 2]
                row = {
                    "backend": "host", "op": "allreduce",
                    "mode": f"async_w{window}", "size_bytes": size,
                    "world": world, "p50_time_s": round(p50, 6),
                    "p50_busbw_GBps": round(
                        size / p50 / 1e9
                        * bus_factor("allreduce", world), 4),
                }
                if window == 1:
                    row["handle_overhead_us"] = round(
                        (p50 - sync_p50) * 1e6, 1)
                rows.append(row)
                emit(row)
        return rows
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------- xla-local backend

def run_xla_local(sizes: list[int], repeats: int,
                  force_cpu: bool) -> list[dict]:
    if force_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    if force_cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    devices = jax.devices()
    if not force_cpu and devices[0].platform != "tpu":
        raise SystemExit(
            f"collective_bench --backend tpu: JAX found no TPU chip "
            f"(backend is {devices[0].platform!r})")
    n = len(devices)
    mesh = Mesh(devices, ("x",))
    out = []

    def smap(fn, in_specs, out_specs):
        # replication of e.g. tiled all_gather output isn't statically
        # inferred, so the check is off
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def timed(fn, x):
        jax.block_until_ready(fn(x))   # warmup + compile
        t0 = time.perf_counter()
        for _ in range(repeats):
            y = fn(x)
        jax.block_until_ready(y)
        return (time.perf_counter() - t0) / repeats

    for op in OPS:
        for size in sizes:
            if op == "allgather":
                # size = total gathered output; the global array IS the
                # output, each device holds size/n
                elems = max(n, (size // 4) - (size // 4) % n)
            else:
                # size = per-rank input: global array = n * size so each
                # device's shard is the full per-rank buffer
                elems = n * max(1, size // 4)
            x = jnp.ones((elems,), jnp.float32)

            if op == "allreduce":
                f = smap(lambda a: jax.lax.psum(a, "x"),
                         in_specs=P("x"), out_specs=P())
            elif op == "allgather":
                f = smap(lambda a: jax.lax.all_gather(a, "x", tiled=True),
                         in_specs=P("x"), out_specs=P())
            else:  # reducescatter
                f = smap(lambda a: jax.lax.psum_scatter(a, "x", tiled=True),
                         in_specs=P("x"), out_specs=P("x"))
            f = jax.jit(f)
            dt = timed(f, x)
            algbw = size / dt / 1e9
            out.append({
                "backend": "xla", "op": op, "size_bytes": size,
                "world": n, "time_s": round(dt, 6),
                "algbw_GBps": round(algbw, 4),
                "busbw_GBps": round(algbw * bus_factor(op, n), 4),
                "platform": devices[0].platform,
            })
            emit(out[-1])
    return out


def summarize(rows: list[dict]):
    if not rows:
        return
    hdr = f"{'backend':8} {'op':14} {'size':>10} {'n':>3} " \
          f"{'algbw GB/s':>11} {'busbw GB/s':>11}"
    print("\n" + hdr, file=sys.stderr)
    print("-" * len(hdr), file=sys.stderr)
    for r in rows:
        if "algbw_GBps" not in r:          # --async rows: p50-only
            print(f"{r['backend']:8} {r['op'] + ':' + r['mode']:14} "
                  f"{r['size_bytes'] / 2**20:>8.1f}MB {r['world']:>3} "
                  f"{'':>11} {r['p50_busbw_GBps']:>11.3f}",
                  file=sys.stderr)
            continue
        print(f"{r['backend']:8} {r['op']:14} "
              f"{r['size_bytes'] / 2**20:>8.1f}MB {r['world']:>3} "
              f"{r['algbw_GBps']:>11.3f} {r['busbw_GBps']:>11.3f}",
              file=sys.stderr)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="host",
                    choices=["host", "xla-local", "tpu"])
    ap.add_argument("--world", type=int, default=2,
                    help="actor count (host backend)")
    ap.add_argument("--sizes-mb", type=float, nargs="+",
                    default=[1, 8, 64])
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--segment-bytes", type=int, nargs="+", default=None,
                    help="host backend: sweep collective_segment_bytes "
                         "(one fresh cluster per value)")
    ap.add_argument("--pipeline", choices=["on", "off"], default=None,
                    help="host backend: force the pipelined data path "
                         "on/off (default: env/config)")
    ap.add_argument("--wire-dtype", nargs="+", default=None,
                    choices=["off", "bf16", "int8"],
                    help="host backend: sweep block-quantized wire "
                         "formats (a same-run `off` baseline is always "
                         "included; runs the socket wire — the path "
                         "inter-host traffic quantizes — unless "
                         "--wire-shm) and record measured quantization "
                         "error vs an exact oracle")
    ap.add_argument("--wire-shm", action="store_true",
                    help="with --wire-dtype: keep the same-node shm "
                         "segment transport on instead of measuring "
                         "the socket wire")
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="host backend: async handle-overhead sweep — "
                         "sync allreduce baseline vs allreduce_async "
                         "at --async-windows submission depths")
    ap.add_argument("--async-windows", type=int, nargs="+",
                    default=[1, 4],
                    help="submission window depths for --async")
    ap.add_argument("--json-out", default=None,
                    help="write all rows as one machine-readable JSON "
                         "record (busbw artifact, e.g. BENCH_r06.json)")
    args = ap.parse_args(argv)
    sizes = [int(mb * 2**20) for mb in args.sizes_mb]

    if args.async_mode and args.backend != "host":
        ap.error("--async requires --backend host (async handles are a "
                 "host-backend feature)")
    if args.async_mode and args.wire_dtype:
        ap.error("--async and --wire-dtype are separate sweeps — run "
                 "them as two invocations")
    if args.backend == "host" and args.async_mode:
        rows = run_async_sweep(args.world, sizes, args.repeats,
                               args.async_windows)
    elif args.backend == "host" and args.wire_dtype:
        rows = run_wire_sweep(args.world, sizes, args.repeats,
                              args.wire_dtype, args.wire_shm)
    elif args.backend == "host":
        rows = run_host_sweep(args.world, sizes, args.repeats,
                              args.segment_bytes, args.pipeline)
    elif args.backend == "xla-local":
        rows = run_xla_local(sizes, args.repeats, force_cpu=True)
    else:  # tpu
        rows = run_xla_local(sizes, args.repeats, force_cpu=False)
    summarize(rows)
    if args.json_out:
        record = {
            "harness": "benchmarks/collective_bench.py",
            "argv": list(argv) if argv is not None else sys.argv[1:],
            "rows": rows,
        }
        with open(args.json_out, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.json_out} ({len(rows)} rows)",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
