"""The metrics that split ``setup_s``, rehearsed without the chip: the
benchmark's own entries (``BENCHMARK.json``'s ``program_span`` /
``program_counter`` metrics, read by ``readers/program_span.py`` and
``readers/step_activity.py``) over the tests' tiny cell, through
``train_fit.run`` in a process that, like ``chipbench.run``'s, never
imports JAX. ONE run serves every case."""
import json
import math
import os
import subprocess
import sys

import pytest

from chipbench import catalog
from chipbench.readers import program_span, step_activity
from tests.chipbench_tests import later_cell

# table 6 of the issue that brought them, in the manifest's order
STARTUP_METRICS = (
    "runtime.init_s", "runtime.chip_probe_s", "train.gang_start_s",
    "train.worker_backend_up_s", "train_step.state_init_s",
    "train_step.first_call_s", "train_step.trace_lower_s",
    "train_step.executable_s", "train_step.cache_misses_at_setup",
    "train.startup_unspanned_s", "data.blocked_ms")
# the parts whose union `train.startup_unspanned_s` takes from `setup_s`
SPANNED = ("runtime.init_s", "train.gang_start_s",
           "train.worker_backend_up_s", "train_step.state_init_s",
           "train_step.first_call_s")

_RUN = """
import json, sys, time
t_start = time.time()
from chipbench import catalog
from chipbench.jobs import train_fit
manifest = json.loads(sys.argv[1])
cell = catalog.resolve_cell(manifest, "tiny", "per_layer")
record = train_fit.run(cell, seed=2**31 + 5, seconds=1.5, trace=True,
                       t_start=t_start, require_tpu=False)
record["parent_imported_jax"] = "jax" in sys.modules
print("RECORD " + json.dumps(record))
"""


def _entries(manifest):
    """The manifest's start-up entries, found by name wherever a later
    PR's entries put them."""
    return [m for m in manifest["per_layer"]
            if m["name"] in STARTUP_METRICS]


def _one_run():
    manifest = catalog.load_manifest()
    entries = _entries(manifest)
    tiny = {
        "paths": manifest["paths"],
        "workloads": [{"name": "tiny", "config": "gpt2-tiny",
                       "traffic": "fit-tiny", "chips": 1,
                       "why": "rehearsal"}],
        "per_layer": entries + [
            {"name": "train.fit_startup_s", "unit": "s"},
            {"name": "data.wait_ms", "unit": "ms"}],
    }
    # the chip probe is part of the start-up path: on, as on the chip
    env = dict(os.environ, RAY_TPU_DETECT_CHIPS="1", RAY_TPU_QUIET="1")
    done = subprocess.run(
        [sys.executable, "-c", _RUN, json.dumps(tiny)], env=env,
        cwd=catalog.ROOT, capture_output=True, text=True, timeout=600)
    lines = [line for line in done.stdout.splitlines()
             if line.startswith("RECORD ")]
    assert done.returncode == 0 and lines, done.stderr[-4000:]
    return json.loads(lines[-1][len("RECORD "):])


@pytest.fixture(scope="module")
def record(once_a_run):
    return once_a_run("startup_record", _one_run)


def check_the_manifests_entries(manifest):
    entries = _entries(manifest)
    assert tuple(m["name"] for m in entries) == STARTUP_METRICS
    # appended in one piece, every cell reports them (no `workloads`), all
    # lower
    at = manifest["per_layer"].index(entries[0])
    assert manifest["per_layer"][at:at + len(entries)] == entries
    for m in entries:
        assert "workloads" not in m and m["better"] == "lower"
        assert m["moves"] == ("tokens_per_s_per_chip"
                              if m["name"] == "data.blocked_ms"
                              else "setup_s")
        assert m["layer"] == m["name"].split(".")[0]
    sources = {m["name"]: m["source"] for m in entries}
    assert sources.pop("train_step.cache_misses_at_setup") == \
        "program_counter"
    assert set(sources.values()) == {"program_span"}


def test_the_manifests_entries_are_the_issues_table():
    check_the_manifests_entries(catalog.load_manifest())


def test_a_later_cell_breaks_nothing_here():
    check_the_manifests_entries(
        later_cell.with_a_later_cell(catalog.load_manifest()))


@pytest.mark.parametrize("metric", STARTUP_METRICS)
def test_every_metric_reads_a_number(record, metric):
    value = record["metrics"][metric]["value"]
    assert math.isfinite(value) and value >= 0


def test_the_parts_add_up_to_setup_s(record):
    values = {k: v["value"] for k, v in record["metrics"].items()}
    clock = record["clock"]
    setup_s = clock["window_start"] - clock["process_start"]
    spanned = sum(values[name] for name in SPANNED)
    assert spanned + values["train.startup_unspanned_s"] == \
        pytest.approx(setup_s, abs=1e-3)
    # what the outside clock has always read holds the three phases that
    # end before the train function starts
    assert values["runtime.init_s"] + values["train.gang_start_s"] \
        + values["train.worker_backend_up_s"] \
        <= values["train.fit_startup_s"] + 1e-3
    assert values["runtime.chip_probe_s"] <= values["runtime.init_s"]
    assert values["train_step.trace_lower_s"] \
        + values["train_step.executable_s"] \
        <= values["train_step.first_call_s"] + 1e-3
    # the iterator's own stamp lies inside the benchmark's clock around it
    assert values["data.blocked_ms"] <= values["data.wait_ms"] + 0.05


def test_the_counter_is_the_jobs_own_count(record):
    assert record["metrics"]["train_step.cache_misses_at_setup"]["value"] \
        == record["setup_cache"]["misses"]


def test_the_parent_never_imported_jax(record):
    assert record["parent_imported_jax"] is False
    assert record["device"]["platform"] == "cpu"


def _span(name, ts, dur, span_id, parent=None, run="r", cat="startup",
          **args):
    args.update(id=span_id, run=run)
    if parent:
        args["parent"] = parent
    return {"ph": "X", "cat": cat, "name": name, "ts": int(ts * 1e6),
            "dur": int(dur * 1e6), "args": args}


def _ctx(spans, run="r"):
    by_id = {ev["args"]["id"]: ev for ev in spans}
    return {"clock": {"process_start": 100.0, "window_start": 160.0},
            "program_spans": (spans, by_id, int(160.0 * 1e6))}


def test_the_reader_on_a_hand_made_timeline():
    spans = [
        _span("init", 101, 20, "a"),
        _span("chip_probe", 102, 15, "b", parent="a"),
        _span("compile::train_step", 130, 25, "c", cat="compile",
              cache_misses_total=0),
        _span("trace", 130, 8, "d", parent="c", cat="compile"),
        _span("lower", 138, 2, "e", parent="c", cat="compile"),
        _span("trace", 120, 4, "f", parent="z", cat="compile"),
        # a recompile that runs into the window: cut at its start
        _span("compile::train_step", 158, 9, "g", cat="compile",
              cache_misses_total=3),
    ]
    ctx = _ctx(spans)
    assert program_span.read(ctx, spans=["init"]) == 20
    assert program_span.read(ctx, spans=["init", "chip_probe"]) == 20
    assert program_span.read(ctx, spans=["compile::train_step"]) == 27
    assert program_span.read(ctx, spans=["trace", "lower"],
                             under="compile::train_step") == 10
    assert program_span.read(
        ctx, spans=["init", "compile::train_step"], unspanned=True) == 13
    assert program_span.read(ctx, stamp="cache_misses_total") == 3
    # a span the program lost is an error that names it, never a number
    with pytest.raises(LookupError, match="'gang_start'"):
        program_span.read(ctx, spans=["init", "gang_start"])
    with pytest.raises(LookupError, match="'lower' under 'fit'"):
        program_span.read(ctx, spans=["lower"], under="fit")
    with pytest.raises(LookupError, match="cache_hits_total"):
        program_span.read(ctx, stamp="cache_hits_total")
    # a program from before these spans: nothing to read, nothing raised
    assert program_span.read({"program_spans": None}, spans=["init"]) \
        is None


def test_the_activity_reader_skips_the_warm_ups_step(monkeypatch):
    from ray_tpu._private import step_anatomy

    def act(step_id, seconds, kind="data_wait"):
        return {"step_id": step_id, "kind": kind, "start": 10.0,
                "end": 10.0 + seconds}

    records = {"activities_dropped": 0, "activities": [
        act(1, 9.0), act(2, 0.001), act(2, 0.002), act(3, 0.005),
        act(3, 7.0, kind="compile"), act(4, 0.001), act(5, 3.0)]}
    monkeypatch.setattr(step_anatomy, "local_records", lambda: records)
    ctx = {"counters": {"steps": 4}}
    # steps 2, 3, 4: 3 ms, 5 ms, 1 ms
    assert step_activity.read(ctx, kind="data_wait") == pytest.approx(3.0)
    with pytest.raises(LookupError, match="1 step"):
        step_activity.read({"counters": {"steps": 1}}, kind="data_wait")
    records["activities_dropped"] = 2
    with pytest.raises(LookupError, match="2 record"):
        step_activity.read(ctx, kind="data_wait")
