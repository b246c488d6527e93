"""ONE fit of a tests' tiny cell for the cases that read it. A fit through
``train_fit.run`` starts a runtime and a worker, traces and compiles the
tiny model's step, its memory plan and both sides of the comparison:
20–35 s of a worker whatever the window. The `[False]` / `[True]` cases of
a `test_*_tiny_through_the_trainer` pair made it twice; they now read one
group each of ONE traced fit that reports the metrics of both groups (the
untraced path of the job is `test_zz_chipbench_job.py`'s, at the `tiny`
cell), made once a test run (`conftest.once_a_run`)."""
import time

from chipbench import catalog
from chipbench.jobs import train_fit


def traced(manifest: dict, cell: str, *, seed: int,
           seconds: float = 1.0) -> dict:
    """The record of a traced fit of ``cell`` with its per-layer AND its
    end-to-end metrics."""
    resolved = catalog.resolve_cell(manifest, cell, "per_layer")
    resolved["metrics"] += catalog.resolve_cell(
        manifest, cell, "end_to_end")["metrics"]
    return train_fit.run(resolved, seed=seed, seconds=seconds, trace=True,
                         t_start=time.time(), require_tpu=False)


def values_of(record: dict, cell: dict) -> dict:
    """The record's values of the metrics ``cell`` was resolved with: one
    group's, as a run of that group alone reports them."""
    return {m["name"]: record["metrics"][m["name"]]["value"]
            for m in cell["metrics"] if m["name"] in record["metrics"]}
