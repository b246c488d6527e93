"""Step anatomy + cluster flight recorder (PR 11).

Late-alphabet on purpose (tier-1 wall-clock budget; the E2E gang tests
here cost seconds each). Structure:

- pure units: step lifecycle, interval clipping / hidden-vs-exposed
  math, fusion by step_id (clock-skew + pid-collision + out-of-order
  tolerance), the rolling-baseline regression detector, ring-drop
  counters, the serve-batch trace link, the telemetry kill switch;
- overhead guard: what the step-anatomy instrumentation does on the
  host-allreduce hot path and around a real jitted train step, counted
  (records appended, lock acquisitions, knob reads, medians);
- cluster acceptance: a 2-worker train run over the double-buffered
  data feed yields a summarize_steps() report with data work hidden
  under compute and a seeded slow rank named on the critical path; a
  seeded kill_actor gang failure auto-produces a black-box dump with
  the GANG_FAILED event and final collective spans from >= 2 distinct
  processes merged into one loadable chrome timeline.
"""
import collections
import json
import os
import time

import numpy as np
import pytest

from ray_tpu._private import telemetry as _tm
from ray_tpu._private import step_anatomy as sa

pytestmark = pytest.mark.skipif(
    not _tm.ENABLED,
    reason="RAY_TPU_INTERNAL_TELEMETRY=0 disables the plane under test")


@pytest.fixture(autouse=True)
def _clean_anatomy():
    sa.finish()         # close any leaked context BEFORE clearing
    sa.clear()
    yield
    sa.finish()
    sa.clear()


# ------------------------------------------------------------ step context


def test_step_lifecycle_monotonic_ids():
    sa.start(rank=3)
    assert sa.current() == (1, 3)
    sa.record_activity("collective", 0.0, 1.0, blocking=True)
    sa.advance(1)                      # report #1 ends step 1
    assert sa.current() == (2, 3)
    sa.advance(0)                      # stale iteration: still monotonic
    assert sa.current() == (3, 3)
    sa.finish()
    assert sa.current() is None
    rec = sa.local_records()
    assert [s["step_id"] for s in rec["steps"]] == [1, 2, 3]
    assert all(s["rank"] == 3 for s in rec["steps"])
    assert rec["activities"][0]["step_id"] == 1
    # no context: recording is a no-op, not a crash
    sa.record_activity("collective", 0.0, 1.0)
    assert len(sa.local_records()["activities"]) == 1


def test_advance_without_start_is_noop():
    sa.advance()                       # e.g. Tune trainable on the driver
    sa.finish()
    assert sa.local_records()["steps"] == []


def test_step_metric_observed():
    from ray_tpu.util.metrics import registry_snapshot

    sa.start(rank=0)
    sa.advance()
    sa.finish()
    fam = next(m for m in registry_snapshot()
               if m["name"] == "ray_tpu_step_seconds")
    assert any(sum(row["counts"]) >= 2 for row in fam["counts"])


# --------------------------------------------------------------- breakdown


def _step(sid, rank, start, end, **kw):
    return {"step_id": sid, "rank": rank, "node": kw.get("node", "n0"),
            "pid": kw.get("pid", 1), "start": start, "end": end}


def _act(sid, rank, kind, start, end, blocking=True, **kw):
    return {"step_id": sid, "rank": rank, "kind": kind, "start": start,
            "end": end, "blocking": blocking,
            "node": kw.get("node", "n0"), "pid": kw.get("pid", 1)}


def test_hidden_vs_exposed_interval_math():
    """Step [0, 1]: blocking comm [0.1, 0.3] is exposed; background
    produce [0.2, 0.6] hides only where it is NOT covered by exposed
    time ([0.3, 0.6] = 0.3); compute is wall minus exposed."""
    step = _step(1, 0, 0.0, 1.0)
    acts = [_act(1, 0, "collective", 0.1, 0.3),
            _act(1, 0, "data_produce", 0.2, 0.6, blocking=False)]
    br = sa.anatomize_rank_step(step, acts)
    assert br["comm_exposed_s"] == pytest.approx(0.2)
    assert br["data_hidden_s"] == pytest.approx(0.3)
    assert br["compute_s"] == pytest.approx(0.8)
    assert br["overlap_fraction"] == pytest.approx(0.3 / 0.5)


def test_overlapping_blocking_intervals_not_double_counted():
    step = _step(1, 0, 0.0, 1.0)
    acts = [_act(1, 0, "collective", 0.0, 0.4),
            _act(1, 0, "collective", 0.3, 0.5),
            _act(1, 0, "data_wait", 0.45, 0.7)]
    br = sa.anatomize_rank_step(step, acts)
    # per-category totals may overlap each other, but compute uses the
    # UNION of exposed time (0.0-0.7), never going negative
    assert br["comm_exposed_s"] == pytest.approx(0.5)
    assert br["data_wait_s"] == pytest.approx(0.25)
    assert br["compute_s"] == pytest.approx(0.3)


def test_activity_clipped_to_step_window():
    step = _step(2, 0, 10.0, 11.0)
    acts = [_act(2, 0, "collective", 9.5, 10.25),    # straddles start
            _act(2, 0, "collective", 11.5, 12.0)]    # entirely outside
    br = sa.anatomize_rank_step(step, acts)
    assert br["comm_exposed_s"] == pytest.approx(0.25)


def test_fusion_joins_by_step_id_never_wall_clock():
    """Two ranks whose monotonic clocks differ by ~1e6 seconds (two
    hosts, arbitrary boot times / NTP skew): steps still pair by
    step_id, and per-rank phases stay correct because each rank's math
    uses only its own clock."""
    r0 = {"node": "hostA", "pid": 7, "steps_dropped": 0,
          "activities_dropped": 0,
          "steps": [_step(1, 0, 100.0, 100.5, node="hostA", pid=7),
                    _step(2, 0, 100.5, 101.0, node="hostA", pid=7)],
          "activities": [_act(1, 0, "collective", 100.1, 100.2,
                              node="hostA", pid=7)]}
    base = 1_000_000.0
    r1 = {"node": "hostB", "pid": 7, "steps_dropped": 0,
          "activities_dropped": 0,
          "steps": [_step(1, 1, base, base + 0.8, node="hostB", pid=7),
                    _step(2, 1, base + 0.8, base + 1.6, node="hostB",
                          pid=7)],
          "activities": [_act(1, 1, "data_wait", base + 0.1, base + 0.3,
                              node="hostB", pid=7)]}
    fused = sa.fuse([r0, r1])
    assert [s["step_id"] for s in fused["steps"]] == [1, 2]
    s1 = fused["steps"][0]
    assert set(s1["ranks"]) == {0, 1} and s1["complete"]
    assert s1["ranks"][0]["comm_exposed_s"] == pytest.approx(0.1)
    assert s1["ranks"][1]["data_wait_s"] == pytest.approx(0.2)
    # rank 1 is slower by SELF time -> named on the critical path
    assert s1["critical_path"]["rank"] == 1
    assert not fused["incomplete"]


def test_fusion_out_of_order_and_duplicate_exports():
    """Out-of-order record arrival and a duplicated per-process export
    (two collection paths reaching the same process) change nothing."""
    import random

    steps = [_step(i, 0, float(i), i + 1.0) for i in range(1, 6)]
    acts = [_act(i, 0, "collective", i + 0.1, i + 0.4)
            for i in range(1, 6)]
    export = {"node": "n0", "pid": 1, "steps": steps,
              "activities": acts, "steps_dropped": 0,
              "activities_dropped": 0}
    shuffled = dict(export)
    shuffled["steps"] = list(steps)
    shuffled["activities"] = list(acts)
    random.Random(7).shuffle(shuffled["steps"])
    random.Random(8).shuffle(shuffled["activities"])
    a = sa.fuse([export, dict(export)])     # duplicate (node, pid)
    b = sa.fuse([shuffled])
    assert [s["step_id"] for s in a["steps"]] == list(range(1, 6))
    for x, y in zip(a["steps"], b["steps"]):
        assert x["ranks"][0]["comm_exposed_s"] == \
            pytest.approx(y["ranks"][0]["comm_exposed_s"])


def test_fusion_critical_path_names_straggler_despite_equal_walls():
    """Bulk-synchronous gang: the allreduce equalizes wall clocks (the
    fast rank absorbs the straggler's lateness as comm wait), so the
    critical path must rank by SELF time, not wall."""
    exports = []
    for rank, comm in ((0, 0.4), (1, 0.01)):   # rank 1 barely waits
        exports.append({
            "node": f"h{rank}", "pid": 1, "steps_dropped": 0,
            "activities_dropped": 0,
            "steps": [_step(1, rank, 0.0, 1.0, node=f"h{rank}")],
            "activities": [_act(1, rank, "collective", 1.0 - comm, 1.0,
                                node=f"h{rank}")]})
    fused = sa.fuse(exports)
    crit = fused["steps"][0]["critical_path"]
    assert crit["rank"] == 1 and crit["phase"] == "compute_s"


def test_fusion_never_mixes_clock_domains_across_processes():
    """Gang restart: the SAME (step_id, rank) re-reported from a NEW
    process must not have the old process's activities (a foreign
    monotonic clock base) clipped into its step window — activities
    follow their own process's step record exclusively."""
    old = {"node": "n0", "pid": 10, "steps_dropped": 0,
           "activities_dropped": 0,
           "steps": [_step(1, 0, 50.0, 51.0, pid=10)],
           "activities": [_act(1, 0, "collective", 50.2, 50.9, pid=10)]}
    new = {"node": "n0", "pid": 20, "steps_dropped": 0,
           "activities_dropped": 0,
           # restarted process: fresh clock base, same (step_id, rank)
           "steps": [_step(1, 0, 7000.0, 7001.0, pid=20)],
           "activities": [_act(1, 0, "data_wait", 7000.1, 7000.3,
                               pid=20)]}
    fused = sa.fuse([old, new])
    br = fused["steps"][0]["ranks"][0]
    # only the winning (last) process's own activities count
    assert br["data_wait_s"] == pytest.approx(0.2)
    assert br["comm_exposed_s"] == 0.0, (
        "old incarnation's comm leaked into the new step window")


def test_fusion_flags_incomplete_on_drops():
    export = {"node": "n0", "pid": 1, "steps": [_step(1, 0, 0.0, 1.0)],
              "activities": [], "steps_dropped": 3,
              "activities_dropped": 0}
    fused = sa.fuse([export])
    assert fused["incomplete"] and fused["dropped"]["steps"] == 3


def test_fusion_partial_step_not_complete():
    exports = [
        {"node": "a", "pid": 1, "steps_dropped": 0,
         "activities_dropped": 0, "activities": [],
         "steps": [_step(1, 0, 0.0, 1.0, node="a"),
                   _step(2, 0, 1.0, 2.0, node="a")]},
        {"node": "b", "pid": 1, "steps_dropped": 0,
         "activities_dropped": 0, "activities": [],
         "steps": [_step(1, 1, 0.0, 1.1, node="b")]},  # died before 2
    ]
    fused = sa.fuse(exports)
    by_id = {s["step_id"]: s for s in fused["steps"]}
    assert by_id[1]["complete"] and not by_id[2]["complete"]


# ------------------------------------------------------ regression detector


def test_regression_detector_fires_on_p50_drift(monkeypatch):
    from ray_tpu._private import events

    monkeypatch.setenv("RAY_TPU_STEP_REGRESSION_WINDOW", "3")
    monkeypatch.setenv("RAY_TPU_STEP_REGRESSION_MULTIPLE", "2.0")
    events.clear()
    sa._durations.clear()
    for d in [0.01, 0.011, 0.009]:
        sa._check_regression(d)
    assert not [e for e in events.snapshot()
                if e["kind"] == "STEP_REGRESSION"]
    for i, d in enumerate([0.1, 0.11, 0.09]):   # p50 10x the baseline
        sa._check_regression(d, step_id=100 + i, rank=2)
    evs = [e for e in events.snapshot() if e["kind"] == "STEP_REGRESSION"]
    assert len(evs) == 1
    assert evs[0]["p50_recent_s"] == pytest.approx(0.1)
    assert evs[0]["p50_baseline_s"] == pytest.approx(0.01)
    # stamped with the step that COMPLETED the window, and its rank
    assert evs[0]["step_id"] == 102 and evs[0]["rank"] == 2
    assert not sa._durations              # reset: no per-step re-firing
    from ray_tpu.util.metrics import registry_snapshot

    fam = next(m for m in registry_snapshot()
               if m["name"] == "ray_tpu_step_regressions_total")
    assert sum(v["value"] for v in fam["values"]) >= 1


def test_regression_detector_quiet_on_proportionate_noise(monkeypatch):
    from ray_tpu._private import events

    monkeypatch.setenv("RAY_TPU_STEP_REGRESSION_WINDOW", "4")
    events.clear()
    sa._durations.clear()
    for d in [0.01, 0.012, 0.011, 0.013] * 4:
        sa._check_regression(d)
    assert not [e for e in events.snapshot()
                if e["kind"] == "STEP_REGRESSION"]


# ----------------------------------------------------------- ring drops


def test_trace_ring_drop_counted_and_surfaced(monkeypatch):
    from ray_tpu.util import tracing
    from ray_tpu.util.metrics import registry_snapshot

    monkeypatch.setattr(tracing, "_spans",
                        collections.deque(maxlen=4))
    monkeypatch.setattr(tracing, "_dropped", 0)
    tracing.enable()
    try:
        for i in range(7):
            tracing.record_completed_span(f"s{i}", "INTERNAL", i, i + 1)
    finally:
        tracing.disable()
    st = tracing.stats()
    assert st["dropped"] == 3 and st["buffered"] == 4
    marked = tracing.local_spans(with_drop_marker=True)
    marker = [s for s in marked if "__drops__" in s]
    assert len(marker) == 1 and marker[0]["__drops__"] == 3
    assert len([s for s in marked if "__drops__" not in s]) == 4
    fam = next(m for m in registry_snapshot()
               if m["name"] == "ray_tpu_trace_dropped_total")
    assert sum(v["value"] for v in fam["values"]) >= 3


def test_timeline_ring_drop_marker_in_merge(monkeypatch):
    from ray_tpu._private import profiling

    monkeypatch.setattr(profiling, "_events",
                        collections.deque(maxlen=3))
    monkeypatch.setattr(profiling, "_dropped", 0)
    for i in range(5):
        profiling.record_completed_span("t", f"e{i}", float(i), 0.5)
    assert profiling.stats()["dropped"] == 2
    merged = profiling.to_chrome_trace(
        profiling.snapshot(with_drop_marker=True))
    # the marker is a chrome metadata row, sorted to the head
    assert merged[0]["ph"] == "M"
    assert merged[0]["name"] == "ray_tpu_timeline_dropped"
    assert merged[0]["args"]["dropped"] == 2
    assert all(e["ph"] == "X" for e in merged[1:])


def test_pid_collision_remapped_in_merged_timeline():
    """Same pid on two hosts must become two distinct chrome processes
    (chrome://tracing keys by pid alone), with the real identity in
    process_name metadata."""
    from ray_tpu._private import flight_recorder as fr

    snaps = [
        {"node": "hostA", "pid": 4242, "timeline": [
            {"ph": "X", "name": "opA", "pid": 4242, "ts": 10, "dur": 5}]},
        {"node": "hostB", "pid": 4242, "timeline": [
            {"ph": "X", "name": "opB", "pid": 4242, "ts": 3, "dur": 5}]},
    ]
    merged = fr.merged_timeline(snaps)
    names = {e["args"]["name"] for e in merged if e["ph"] == "M"}
    assert names == {"hostA/pid4242", "hostB/pid4242"}
    op_pids = {e["name"]: e["pid"] for e in merged if e["ph"] == "X"}
    assert op_pids["opA"] != op_pids["opB"]
    # sorted by ts: opB (ts 3) precedes opA (ts 10)
    xs = [e["name"] for e in merged if e["ph"] == "X"]
    assert xs == ["opB", "opA"]


def test_merged_timeline_carries_drop_marker():
    from ray_tpu._private import flight_recorder as fr

    snaps = [{"node": "h", "pid": 1, "timeline_dropped": 9,
              "timeline": [{"ph": "X", "name": "op", "pid": 1,
                            "ts": 5, "dur": 1}]}]
    merged = fr.merged_timeline(snaps)
    mark = [e for e in merged
            if e["ph"] == "M" and e["name"] == "ray_tpu_timeline_dropped"]
    assert len(mark) == 1 and mark[0]["args"]["dropped"] == 9
    # remapped to the same chrome process as the spans it qualifies
    op = next(e for e in merged if e.get("name") == "op")
    assert mark[0]["pid"] == op["pid"]


def test_dump_dirs_unique_within_one_second(tmp_path, monkeypatch):
    """Two dumps in the same wall-clock second (retrying gang + manual)
    must land in distinct directories, and the newest is discoverable
    from a FRESH process via the on-disk scan (`ray-tpu blackbox
    last`)."""
    from ray_tpu._private import flight_recorder as fr

    monkeypatch.setenv("RAY_TPU_FLIGHT_RECORDER_DIR", str(tmp_path))
    p1 = fr.dump("reason_a")
    p2 = fr.dump("reason_a")
    assert p1 and p2 and p1 != p2
    assert os.path.isdir(p1) and os.path.isdir(p2)
    latest = fr.find_latest_dump()
    assert latest in (p1, p2)
    assert fr.find_latest_dump(str(tmp_path / "nonexistent")) is None


# --------------------------------------------------------- plane stamping


def test_collective_op_stamped_with_step():
    from ray_tpu._private import profiling
    from ray_tpu.util import collective as col
    from ray_tpu.util.collective.collective import _GroupState, _manager

    class _Noop:
        def allreduce(self, arr, op, seq):
            return arr

    state = _GroupState("zzsa_stamp", 4, 0, "host", _Noop(), None)
    _manager._groups["zzsa_stamp"] = state
    try:
        sa.start(rank=0, step_id=41)
        col.allreduce(np.zeros(8), group_name="zzsa_stamp")
        sa.finish()
        acts = sa.local_records()["activities"]
        mine = [a for a in acts if a["kind"] == "collective"
                and a.get("meta", {}).get("group") == "zzsa_stamp"]
        assert len(mine) == 1
        assert mine[0]["step_id"] == 41 and mine[0]["blocking"]
        span = next(e for e in profiling.snapshot()
                    if e["name"] == "collective::allreduce"
                    and e["args"].get("group") == "zzsa_stamp")
        assert span["args"]["step"] == 41
    finally:
        _manager._groups.pop("zzsa_stamp", None)
        from ray_tpu.util.collective.telemetry import flush_timings

        flush_timings()   # drop buffered records for the fake group


def test_data_wait_stamped_with_step():
    from ray_tpu.data._internal.streaming.iterator import stamp_wait

    def gen():
        for i in range(3):
            time.sleep(0.002)
            yield i

    sa.start(rank=2)
    out = list(stamp_wait(gen(), "zzsa-consumer"))
    sa.finish()
    assert out == [0, 1, 2]
    waits = [a for a in sa.local_records()["activities"]
             if a["kind"] == "data_wait"
             and a.get("meta", {}).get("consumer") == "zzsa-consumer"]
    assert len(waits) == 3
    assert all(w["blocking"] and w["step_id"] == 1 for w in waits)
    assert all(w["end"] > w["start"] for w in waits)


def test_compile_stamped_as_blocking_activity():
    from ray_tpu.parallel.compile_watch import CompiledFunction

    fn = CompiledFunction(lambda x: x * 2, "zzsa_compile")
    sa.start(rank=0, step_id=5)
    fn(np.zeros(4))                    # miss: compile activity
    fn(np.ones(4))                     # hit: no activity
    sa.finish()
    comp = [a for a in sa.local_records()["activities"]
            if a["kind"] == "compile"]
    assert len(comp) == 1
    assert comp[0]["step_id"] == 5 and comp[0]["blocking"]


def test_serve_batch_links_caller_trace():
    """A traced request through @serve.batch shows its batching wait:
    a per-item span under the CALLER's trace, pointing at the shared
    batch-execution span."""
    from ray_tpu.serve import batching
    from ray_tpu.util import tracing

    @batching.batch(max_batch_size=4, batch_wait_timeout_s=0.005)
    def doubler(items):
        return [i * 2 for i in items]

    tracing.clear()
    tracing.enable()
    try:
        with tracing.span("request", "INTERNAL") as req:
            assert doubler(21) == 42
    finally:
        tracing.disable()
    spans = tracing.local_spans()
    item = [s for s in spans if s["name"] == "serve.batch doubler"]
    execs = [s for s in spans
             if s["name"] == "serve.batch_execute doubler"]
    assert len(item) == 1 and len(execs) == 1
    # the item span continues the CALLER's trace under the caller span
    assert item[0]["traceId"] == req["trace_id"]
    assert item[0]["parentSpanId"] == req["span_id"]
    assert item[0]["attributes"]["batch_span"] == execs[0]["spanId"]
    assert item[0]["attributes"]["batch_wait_s"] >= 0
    tracing.clear()


def test_serve_batch_untraced_pays_nothing():
    from ray_tpu.serve import batching
    from ray_tpu.util import tracing

    @batching.batch(max_batch_size=2, batch_wait_timeout_s=0.001)
    def ident(items):
        return list(items)

    tracing.clear()
    assert ident(5) == 5
    assert not [s for s in tracing.local_spans()
                if s["name"].startswith("serve.batch")]


# ------------------------------------------------------------- kill switch


def test_internal_telemetry_kill_switch_disables_everything(monkeypatch):
    """RAY_TPU_INTERNAL_TELEMETRY=0 must turn off step stamping, the
    anatomy rings, AND the flight recorder (snapshot + dump + trigger)."""
    from ray_tpu._private import flight_recorder as fr

    monkeypatch.setattr(_tm, "ENABLED", False)
    sa.start(rank=0)
    assert sa.current() is None           # no context was opened
    sa.record_activity("collective", 0.0, 1.0)
    sa.advance()
    sa.finish()
    assert sa.local_records()["steps"] == []
    assert sa.local_records()["activities"] == []
    assert fr.local_snapshot() == {}
    assert fr.dump("zz_killswitch") is None
    assert fr.trigger_dump("zz_killswitch", force=True) is None


# ---------------------------------------------------------- overhead guard


class _CountingLock:
    """`sa._lock` with its acquisitions counted."""

    def __init__(self, lock):
        self._lock = lock
        self.acquired = 0

    def __enter__(self):
        self.acquired += 1
        return self._lock.__enter__()

    def __exit__(self, *exc):
        return self._lock.__exit__(*exc)


def test_overhead_guard_allreduce_and_train_step(monkeypatch):
    """What the instrumentation DOES on the two hot paths, counted — a
    count reads the same beside five busy neighbours, where a share of a
    0.14 ms step's wall clock did not.

    - allreduce: with a step active one op costs one record appended
      under one acquisition of the ring's lock; with no step active or
      the plane off, none; a full ring drops its oldest, so the stamp
      keeps nothing beyond its own record;
    - train step: one `advance()` is one step record under one lock
      acquisition, the knobs are read once a loop, and the regression
      detector's medians run once a window (the two costs that first
      measured 6.3% of a step); a compiled `make_train_step` step
      stamps one `compile` activity on the call that compiles and none
      after."""
    import statistics
    import tracemalloc

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu._private import config as _config
    from ray_tpu.util import collective as col
    from ray_tpu.util.collective.collective import _GroupState, _manager

    class _Noop:
        def allreduce(self, arr, op, seq):
            return arr

    lock = _CountingLock(sa._lock)
    monkeypatch.setattr(sa, "_lock", lock)
    _manager._groups["zzov_noop"] = _GroupState(
        "zzov_noop", 4, 0, "host", _Noop(), None)
    tiny = np.zeros(16)

    def stamped_by_one_op():
        n0, l0 = len(sa._acts), lock.acquired
        col.allreduce(tiny, group_name="zzov_noop")
        return len(sa._acts) - n0, lock.acquired - l0

    try:
        assert stamped_by_one_op() == (0, 0)        # no step active
        sa.start(rank=0)                            # step ACTIVE
        assert stamped_by_one_op() == (1, 1)
        rec = sa._acts[-1]
        assert (rec["kind"], rec["step_id"], rec["rank"],
                rec["blocking"]) == ("collective", 1, 0, True)
        assert rec["meta"] == {"op": "allreduce", "group": "zzov_noop"}
        monkeypatch.setattr(_tm, "ENABLED", False)
        assert stamped_by_one_op() == (0, 0)        # plane off
        monkeypatch.setattr(_tm, "ENABLED", True)

        # a full ring: every further stamp evicts one record and keeps
        # one, so what this module holds stops growing (the records are
        # traced from the first, or an eviction would free nothing seen)
        m = time.monotonic()
        n = 2_000
        tracemalloc.start()
        try:
            for _ in range(sa._acts.maxlen):
                sa.record_activity("collective", m, m + 1e-6)
            assert len(sa._acts) == sa._acts.maxlen
            dropped0 = sa.local_records()["activities_dropped"]
            only = [tracemalloc.Filter(True, sa.__file__)]
            before = tracemalloc.take_snapshot().filter_traces(only)
            for _ in range(n):
                sa.record_activity("collective", m, m + 1e-6)
            after = tracemalloc.take_snapshot().filter_traces(only)
        finally:
            tracemalloc.stop()
        kept = sum(d.size_diff for d in after.compare_to(before, "filename"))
        assert len(sa._acts) == sa._acts.maxlen
        assert sa.local_records()["activities_dropped"] - dropped0 == n
        # a leaked record a stamp would be ~400 B x 2,000
        assert kept < 16 * 1024, kept
    finally:
        sa.finish()
        _manager._groups.pop("zzov_noop", None)
        from ray_tpu.util.collective.telemetry import flush_timings

        flush_timings()
    sa.clear()

    # ---- train step: what one advance() does, over five windows
    knob_reads, medians = [], []
    real_get, real_median = _config.get_config, statistics.median
    monkeypatch.setattr(
        _config, "get_config",
        lambda name: knob_reads.append(name) or real_get(name))
    monkeypatch.setattr(
        statistics, "median",
        lambda xs: medians.append(1) or real_median(xs))
    sa.start(rank=0)
    window = sa._regression_params()[0]
    steps = 5 * window
    n0, l0 = len(sa._steps), lock.acquired
    for _ in range(steps):
        sa.advance()
    assert len(sa._steps) - n0 == steps
    assert lock.acquired - l0 == steps
    assert sorted(knob_reads) == ["step_regression_multiple",
                                  "step_regression_window"]
    # two medians (baseline, recent) each time a window has filled
    assert 0 < len(medians) <= 2 * (steps // window), len(medians)
    monkeypatch.setattr(_config, "get_config", real_get)
    monkeypatch.setattr(statistics, "median", real_median)

    # ---- a real compiled step inside the active loop
    from ray_tpu.parallel.train_step import (
        default_optimizer,
        make_train_state,
        make_train_step,
    )

    def init_params(rng):
        k1, k2 = jax.random.split(rng)
        return {"w1": jax.random.normal(k1, (64, 128)) * 0.02,
                "w2": jax.random.normal(k2, (128, 8)) * 0.02}

    def loss_fn(params, batch):
        x, y = batch
        h = jnp.tanh(x @ params["w1"])
        logits = h @ params["w2"]
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, {"loss": loss}

    opt = default_optimizer(1e-3)
    state = make_train_state(init_params, jax.random.PRNGKey(0), opt)
    step_fn = make_train_step(loss_fn, opt, donate=False)
    batch = (jnp.ones((32, 64)), jnp.zeros((32,), jnp.int32))

    def compiles_stamped():
        return [a["meta"]["fn"] for a in sa._acts
                if a["kind"] == "compile"]

    assert compiles_stamped() == ["train_state_init"]
    for _ in range(3):
        state, metrics = step_fn(state, batch)
        sa.advance()
    jax.block_until_ready(metrics["loss"])
    assert compiles_stamped() == ["train_state_init", "train_step"]


# ------------------------------------------------------ cluster acceptance


def _overlap_loop(config):
    import time as _t

    import numpy as _np

    from ray_tpu.air import session
    from ray_tpu.util import collective as _col

    rank = session.get_world_rank()
    shard = session.get_dataset_shard("train")
    for batch in shard.iter_batches(batch_size=256, device_put=True):
        # rank 1 is the seeded slow rank: 3x the per-step compute
        _t.sleep(0.06 if rank == 1 else 0.02)
        _col.allreduce(_np.ones(64), "zzsa_gang")
        session.report({"rows": int(len(batch))})


def test_overlap_proof_two_worker_train(ray_start_regular):
    """Acceptance: a 2-worker train run over the double-buffered data
    feed (PR 9) yields a summarize_steps() report whose anatomy shows
    data work hidden under compute (hidden fraction > 0, wait
    consistent with ray_tpu_data_wait_seconds), and the seeded slow
    rank is named on the critical path. Collected BEFORE gang teardown
    (the records live in the worker processes)."""
    ray = ray_start_regular
    from ray_tpu import data
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.experimental.state.api import (
        metrics_summary,
        summarize_steps,
    )
    from ray_tpu.train.backend_executor import BackendExecutor, JaxConfig

    ds = data.from_numpy(np.arange(2048.0), parallelism=8)
    executor = BackendExecutor(
        JaxConfig(group_name="zzsa_gang"),
        ScalingConfig(num_workers=2,
                      resources_per_worker={"CPU": 1})).start()
    try:
        executor.set_dataset_shards("train", ds.split(2))
        executor.start_training(_overlap_loop, {})
        deadline = time.time() + 120
        while True:
            rows = executor.next_results()
            if all(r.get("done") for r in rows):
                assert not any(r.get("error") for r in rows), rows
                break
            assert time.time() < deadline, "train run wedged"
        summary = summarize_steps()
        snaps = {m["name"]: m for m in metrics_summary()}
    finally:
        executor.shutdown()

    complete = [s for s in summary["steps"]
                if s["complete"] and len(s["ranks"]) == 2]
    assert len(complete) >= 3, summary["steps"]
    # --- overlap: the double-buffer producer's work hid under compute
    hidden = sum(br["data_hidden_s"] for s in complete
                 for br in s["ranks"].values())
    assert hidden > 0, "no data work attributed as hidden under compute"
    fracs = [s["overlap_fraction"] for s in complete
             if s["overlap_fraction"] is not None]
    assert fracs and max(fracs) > 0
    # --- data wait consistency with the metric plane: anatomy counts a
    # subset of what the histogram saw (only waits inside active steps)
    anatomy_wait = sum(br["data_wait_s"] for s in summary["steps"]
                      for br in s["ranks"].values())
    fam = snaps.get("ray_tpu_data_wait_seconds", {})
    metric_wait = sum(
        v["value"] for v in fam.get("values", ())
        if str(v["tags"].get("consumer", "")).startswith("train/train/"))
    assert metric_wait > 0, "train consumers never stamped data wait"
    assert anatomy_wait <= metric_wait + 0.25, (anatomy_wait, metric_wait)
    # --- the seeded slow rank is named on the critical path
    crit_ranks = [s["critical_path"]["rank"] for s in complete]
    assert crit_ranks.count(1) > len(crit_ranks) / 2, crit_ranks
    # per-rank rollup agrees: rank 1's compute dominates rank 0's
    assert summary["ranks"][1]["compute_s"] > \
        summary["ranks"][0]["compute_s"]
    # the cluster span collection surfaces drop accounting alongside
    from ray_tpu.util import tracing

    spans = tracing.get_spans()
    assert isinstance(spans.dropped, dict)


def _blackbox_loop(config):
    import numpy as _np

    from ray_tpu.air import session
    from ray_tpu.util import collective as _col

    for step in range(3):
        _col.allreduce(_np.full(8, float(step + 1)), "zzsa_bb")
        session.report({"step": step})


@pytest.mark.chaos
@pytest.mark.fault_injection
def test_blackbox_dump_on_seeded_gang_kill(tmp_path, monkeypatch):
    """Acceptance: a seeded kill_actor gang failure auto-produces a
    black-box dump containing the GANG_FAILED event and the final
    collective spans of >= 2 distinct surviving processes, merged into
    one loadable chrome-timeline file."""
    import ray_tpu
    from ray_tpu._private import flight_recorder as fr
    from ray_tpu.air.config import FailureConfig, RunConfig, ScalingConfig
    from ray_tpu.train import JaxTrainer
    from ray_tpu.train.backend_executor import JaxConfig

    monkeypatch.setenv("RAY_TPU_FLIGHT_RECORDER_DIR", str(tmp_path))
    monkeypatch.setenv("RAY_TPU_FAULT_SEED", "7")
    monkeypatch.setenv("RAY_TPU_FAULT_SCHEDULE",
                       "kill_actor:rank1.next_result:#2")
    monkeypatch.setattr(fr, "_last_auto_dump_ts", 0.0)
    monkeypatch.setattr(fr, "_last_dump_path", None)
    ray_tpu.init(num_cpus=4, object_store_memory=64 * 1024 * 1024)
    try:
        trainer = JaxTrainer(
            _blackbox_loop,
            backend_config=JaxConfig(group_name="zzsa_bb"),
            scaling_config=ScalingConfig(num_workers=3,
                                         resources_per_worker={"CPU": 1}),
            run_config=RunConfig(
                failure_config=FailureConfig(max_failures=1)))
        try:
            trainer.fit()        # the retry gets killed again: may raise
        except Exception:
            pass
        dumps = sorted(d for d in os.listdir(tmp_path)
                       if d.startswith("blackbox_"))
        assert dumps, "gang failure produced no flight-recorder dump"
        # find the (forced) GANG_FAILED dump and hold it to the contract
        best = None
        for d in reversed(dumps):
            ddir = tmp_path / d
            files = [f for f in os.listdir(ddir) if f.endswith(".jsonl")]
            blobs = {f: (ddir / f).read_text() for f in files}
            if any('"GANG_FAILED"' in b for b in blobs.values()):
                best = (ddir, blobs)
                break
        assert best is not None, f"no dump contains GANG_FAILED: {dumps}"
        ddir, blobs = best
        assert len(blobs) >= 2, "dump captured fewer than 2 processes"
        with_col_spans = [
            f for f, b in blobs.items()
            if '"collective::allreduce"' in b]
        assert len(with_col_spans) >= 2, (
            f"final collective spans from <2 processes: {list(blobs)}")
        # merged chrome timeline: loadable, and the collective spans of
        # distinct processes kept distinct (remapped) pids
        timeline = json.loads((ddir / "timeline.json").read_text())
        assert isinstance(timeline, list) and timeline
        col_pids = {e["pid"] for e in timeline
                    if e.get("name") == "collective::allreduce"}
        assert len(col_pids) >= 2, timeline[:5]
        # the dump event itself is in the cluster stream
        from ray_tpu._private import events

        assert any(e["kind"] == "FLIGHT_RECORDER_DUMP"
                   for e in events.snapshot())
    finally:
        ray_tpu.shutdown()


def test_cli_steps_and_blackbox_subcommands(monkeypatch):
    from ray_tpu.scripts import cli

    called = {}
    monkeypatch.setattr(
        cli, "cmd_steps",
        lambda args: called.update(steps=(args.address, args.last)) or 0)
    monkeypatch.setattr(
        cli, "cmd_blackbox",
        lambda args: called.update(bb=(args.action, args.out)) or 0)
    assert cli.main(["steps", "--address", "h:1", "--last", "5"]) == 0
    assert cli.main(["blackbox", "dump", "--out", "/tmp/x"]) == 0
    assert called == {"steps": ("h:1", 5), "bb": ("dump", "/tmp/x")}
