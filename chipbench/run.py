"""One process, one cell, one run.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout, on the machine that holds the cell's
chips. Prints as its last line the result object the benchmark's contract
fixes; with ``--trace 0`` its metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, and the line also carries ``breakdown``.
Exits non-zero, with no result line, when there is no TPU (or too few
chips), when the chip's kind has no peak on file, when the checkout holds no
program, or when the job fails. There is no way to run this on the CPU.

This process never imports JAX: a chip belongs to one process, and that
process is the job's worker.
"""
import time

T_START = time.time()     # as early as this process can know its own start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from chipbench import catalog  # noqa: E402


def result_line(record: dict) -> dict:
    """The contract's line of a job's record. ``compared`` comes last: the
    verdicts, then each number compared beside its limit (``[number,
    limit]``), the nearest to its limit first, so that a run called not
    correct says by which."""
    line = {k: record[k] for k in
            ("correct", "attempted", "failed", "metrics", "device")}
    if "breakdown" in record:
        line["breakdown"] = record["breakdown"]
    line["compared"] = dict(record["verdicts"], **record["compared"])
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--keep-trace", default=None, metavar="FILE",
                    help="with --trace 1, also copy the profiler's "
                         ".xplane.pb to FILE, to be read by hand")
    args = ap.parse_args(argv)

    if not os.path.isfile(os.path.join(catalog.ROOT, "ray_tpu",
                                       "__init__.py")):
        print(f"chipbench: FAILED: {catalog.ROOT} holds the benchmark but "
              f"not the program (no ray_tpu/)", file=sys.stderr)
        return 1
    manifest = catalog.load_manifest()
    cell = catalog.resolve_cell(
        manifest, args.workload, "per_layer" if args.trace else "end_to_end")
    if args.keep_trace:
        cell["keep_trace"] = os.path.abspath(args.keep_trace)
    job = catalog.load_module(manifest, "jobs", cell["traffic"]["job"])
    try:
        record = job.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), t_start=T_START)
    except job.JobFailed as e:
        print(f"chipbench: FAILED: {e}", file=sys.stderr)
        return 1
    if "jax" in sys.modules:
        print("chipbench: FAILED: the parent process imported jax; the chip "
              "must belong to the worker alone", file=sys.stderr)
        return 1
    wanted = {m["name"] for m in catalog.metrics_of(
        manifest, args.workload, "end_to_end")}
    if not args.trace and set(record["metrics"]) != wanted:
        print(f"chipbench: FAILED: the run gave {sorted(record['metrics'])}"
              f", the cell reports {sorted(wanted)}", file=sys.stderr)
        return 1
    line = result_line(record)
    notes = {k: v for k, v in record.items() if k not in line}
    print("notes: " + json.dumps(notes))
    # what was compared, beside its limit: the last lines of standard error
    for name, value in line["compared"].items():
        print(f"compared {name}: {value}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
