"""Why one compared leaf of a cell reads far over the others on a few seeds
(PR 62, second session: the driver's seed 314767261 of `phi4flash6l-b1s8k`).
`train_fit._reference_check`'s own parameters, tokens and two programs, over
several seeds in ONE process on the chip, with what the verdict's line does
not carry: each leaf's reference norm, the error of `w_qkv` by its q | k | v
columns and of every leaf by its rows' and columns' largest shares, the three
layers' lambda; each seed on the parameters as drawn and as
`accounting.conditioned` hands them to the comparison, there also with the
cell's standing control, every matmul weight rounded to e4m3 (`--e4m3`:
`benchmarks/precision_control.py`'s `_eight_bit`). `--scan=N` first reads
the lambdas alone of the N run seeds from the first one given and adds the
four whose draw lies nearest lambda = 1 to the seeds looked at.

    python3 chipbench/results/pr62_bench_clock/leaf_look.py <cell> [--scan=N] [--e4m3] <run seed> ...

from the repo root, through the chip tool; a line a seed to standard output
and to chiprun_out/pr62/leaf_look_<cell>.jsonl."""
import importlib
import json
import math
import os
import sys
import time

ROOT = os.getcwd()
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import catalog, compare, generate  # noqa: E402
from chipbench.jobs import train_fit  # noqa: E402
from ray_tpu.parallel.compile_watch import configure_compile_cache  # noqa: E402
from ray_tpu.parallel.mesh import MeshConfig, create_mesh  # noqa: E402


def shares(diff, want):
    """The error's square by part, as shares of the whole error's square:
    the largest row, the largest column, and for a fused q | k | v matrix
    its three column blocks with each block's own relative error."""
    sq = np.square(np.asarray(diff, np.float64))
    total = sq.sum()
    out = {}
    if sq.ndim == 2:
        out["top_row_share"] = float(sq.sum(1).max() / total)
        out["top_col_share"] = float(sq.sum(0).max() / total)
        if sq.shape[1] == 5120:               # 2560 q | 1280 k | 1280 v
            w2 = np.square(np.asarray(want, np.float64))
            for name, lo, hi in (("q", 0, 2560), ("k", 2560, 3840),
                                 ("v", 3840, 5120)):
                out[name] = {
                    "share": float(sq[:, lo:hi].sum() / total),
                    "rel": float(math.sqrt(sq[:, lo:hi].sum()
                                           / w2[:, lo:hi].sum())),
                    "ref_norm": float(math.sqrt(w2[:, lo:hi].sum()))}
    return out


def lambdas(params, first_layer: int) -> dict:
    """Layer -> lambda of every differential attention layer."""
    out = {}
    for i, layer in enumerate(params["layers"]):
        m = layer["mixer"]
        if "lambda_q1" in m:
            out[str(i)] = float(
                jnp.exp(jnp.sum(m["lambda_q1"] * m["lambda_k1"]))
                - jnp.exp(jnp.sum(m["lambda_q2"] * m["lambda_k2"]))
                + 0.8 - 0.6 * math.exp(-0.3 * (first_layer + i)))
    return out


def main(cell_name, seeds, scan=0, e4m3=False):
    configure_compile_cache()
    cell = catalog.resolve_cell(catalog.load_manifest(), cell_name,
                                "end_to_end")
    traffic = cell["traffic"]
    module, cfg = train_fit._model(cell)
    devices = jax.local_devices()[:math.prod(traffic["mesh"].values())]
    mesh = create_mesh(MeshConfig(**traffic["mesh"]), devices=devices)
    accounting = importlib.import_module(cell["accounting"])
    reference = importlib.import_module(cell["reference"])
    pick, put = accounting.pick, accounting.put
    shardings = jax.tree_util.tree_map(
        lambda spec: jax.sharding.NamedSharding(mesh, spec),
        module.partition_specs(cfg))
    make = jax.jit(lambda rng: module.init(rng, cfg), out_shardings=shardings)
    out = os.path.join(ROOT, "chiprun_out", "pr62")
    os.makedirs(out, exist_ok=True)
    first = cell["model"].get("first_layer", 0)
    if scan:
        # the lambda leaves alone (the compiler drops the rest of the draw):
        # the seeds whose draw puts some layer's lambda nearest 1
        only = jax.jit(lambda rng: {"layers": [
            {"mixer": {k: v for k, v in layer["mixer"].items()
                       if k.startswith("lambda")}}
            for layer in module.init(rng, cfg)["layers"]]})
        rows = []
        for run_seed in range(seeds[0], seeds[0] + scan):
            lam = lambdas(only(jax.random.PRNGKey(run_seed + 1)), first)
            rows.append((min(abs(1 - v) for v in lam.values()), run_seed,
                         lam))
        rows.sort()
        near = sum(r[0] < 0.02 for r in rows)
        print(json.dumps({"scanned": scan, "from": seeds[0],
                          "within_0.02_of_1": near,
                          "nearest": rows[:8]}), flush=True)
        with open(os.path.join(out, f"lambda_scan_{cell_name}.json"),
                  "w") as f:
            json.dump(rows, f)
        seeds = seeds[1:] + [r[1] for r in rows[:4]]
    ref = jax.jit(compare.loss_and_grads(
        lambda p, t: reference.loss(p, t, cell["model"]), pick, put))
    programs = {"stated": jax.jit(compare.loss_and_grads(
        lambda p, t: module.loss_fn(p, {"tokens": t}, cfg, mesh)[0],
        pick, put))}
    if e4m3:
        # the cell's standing control: every matmul weight rounded to an
        # 8-bit float forward, the gradient straight through
        from benchmarks import precision_control
        programs["e4m3"] = jax.jit(compare.loss_and_grads(
            lambda p, t: module.loss_fn(precision_control._eight_bit(p),
                                        {"tokens": t}, cfg, mesh)[0],
            pick, put))
    for run_seed in seeds:
        seed = run_seed + 1                     # `_reference_check`'s
        drawn = make(jax.random.PRNGKey(seed))
        tokens = generate.token_rows(
            dict(traffic, batches=1, batch=traffic["check_sequences"]),
            cfg.vocab_size, seed)
        for form, params in (("drawn", drawn),
                             ("conditioned", accounting.conditioned(drawn))):
            t0 = time.time()
            with jax.default_matmul_precision("highest"):
                ref_loss, ref_grads = ref(params, tokens)
            ref_grads = {k: np.asarray(v, np.float64)
                         for k, v in ref_grads.items()}
            norms = {k: float(np.linalg.norm(v))
                     for k, v in ref_grads.items()}
            median = float(np.median(list(norms.values())))
            line = {"cell": cell_name, "run_seed": run_seed, "form": form,
                    "reference_loss": float(ref_loss),
                    "reference_norms": norms,
                    "lambda": lambdas(params, first)}
            for name, program in programs.items():
                if form == "drawn" and name != "stated":
                    continue
                loss, grads = program(params, tokens)
                rows = {"loss": abs(float(loss) - float(ref_loss))
                        / abs(float(ref_loss))}
                detail = {}
                for k, want in ref_grads.items():
                    got = np.asarray(grads[k], np.float64)
                    rows[k] = float(np.linalg.norm(got - want) / norms[k])
                    detail[k] = dict(
                        shares(got - want, want),
                        norm_gap=abs(float(np.linalg.norm(got)) - norms[k])
                        / max(norms[k], median))
                line[name] = {"errors": rows, "detail": detail}
            line["seconds"] = round(time.time() - t0, 1)
            text = json.dumps(line)
            print(text, flush=True)
            with open(os.path.join(out, f"leaf_look_{cell_name}.jsonl"),
                      "a") as f:
                f.write(text + "\n")
        del drawn, params, ref_grads


if __name__ == "__main__":
    args = [a for a in sys.argv[2:] if not a.startswith("--")]
    scan = [int(a[7:]) for a in sys.argv if a.startswith("--scan=")]
    main(sys.argv[1], [int(a) for a in args], scan[0] if scan else 0,
         "--e4m3" in sys.argv)
