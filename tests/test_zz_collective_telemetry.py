"""Data-plane observability (PR 3): collective-op telemetry, straggler
detection, compile watch, device gauges, seq validation.

Late-alphabet on purpose (tier-1 wall-clock budget; the cluster tests
here cost a few seconds each). Structure:

- pure units: straggler detector on synthetic rank timings, the
  rendezvous-side aggregator, the compile-cache wrapper, device gauges
  from injected probe records;
- overhead guard: instrumented host-path allreduce vs telemetry-off on
  a fake in-process group (op body dominates; <5% budget);
- cluster acceptance: a 4-rank host-backend collective with one
  slow_reply-faulted rank yields correct latency/bytes samples in
  metrics_summary(), a COLLECTIVE_STRAGGLER event naming the slow rank
  in list_cluster_events(), and a collective span linked under the
  submitting task's trace (both tracing and chrome-timeline planes);
- seq desync: a rank with a skewed op counter raises
  CollectiveSeqMismatchError instead of hanging.
"""
import time

import numpy as np
import pytest

from ray_tpu._private import telemetry as _tm

# the whole file exercises the data-plane telemetry; with the plane
# killed there is nothing meaningful to assert (CI runs with the
# default, telemetry on)
pytestmark = pytest.mark.skipif(
    not _tm.ENABLED,
    reason="RAY_TPU_INTERNAL_TELEMETRY=0 disables the plane under test")


# ------------------------------------------------- straggler detector


def _timings(starts, **extra):
    return [{"rank": r, "start": s, "group": "g", "op": "allreduce",
             "seq": 1, **extra} for r, s in enumerate(starts)]


def test_detector_flags_late_rank():
    from ray_tpu.util.collective.telemetry import detect_stragglers

    stragglers, lags, median = detect_stragglers(
        _timings([0.0, 0.002, 0.001, 0.400]),
        multiple=3.0, min_lag_s=0.05)
    assert [r for r, _ in stragglers] == [3]
    assert lags[3] == pytest.approx(0.4)
    assert median == pytest.approx(0.0015)


def test_detector_uniform_group_is_quiet():
    from ray_tpu.util.collective.telemetry import detect_stragglers

    stragglers, _, _ = detect_stragglers(
        _timings([0.0, 0.001, 0.002, 0.0015]),
        multiple=3.0, min_lag_s=0.05)
    assert stragglers == []


def test_detector_multiple_of_median_threshold():
    """A wide-but-proportionate spread stays quiet; shrinking the
    multiple flags the tail — the threshold really is a multiple of the
    leave-one-out median, not an absolute cut."""
    from ray_tpu.util.collective.telemetry import detect_stragglers

    starts = [0.0, 0.1, 0.2, 0.3]    # rank 3: others' median lag = .1
    quiet, _, _ = detect_stragglers(_timings(starts),
                                    multiple=3.0, min_lag_s=0.01)
    assert quiet == []                # .3 == 3 * .1, strictly-greater
    flagged, _, _ = detect_stragglers(_timings(starts),
                                      multiple=2.0, min_lag_s=0.01)
    assert [r for r, _ in flagged] == [3]   # .3 > 2 * .1


def test_detector_two_rank_group_not_blind():
    """Leave-one-out median: with a plain group median a 2-rank group
    could NEVER flag (the laggard's own lag is half the median for any
    multiple >= 2) — the smallest real topology must still detect."""
    from ray_tpu.util.collective.telemetry import detect_stragglers

    flagged, lags, _ = detect_stragglers(_timings([0.0, 10.0]),
                                         multiple=3.0, min_lag_s=0.05)
    assert [r for r, _ in flagged] == [1]
    assert lags[1] == pytest.approx(10.0)
    quiet, _, _ = detect_stragglers(_timings([0.0, 0.01]),
                                    multiple=3.0, min_lag_s=0.05)
    assert quiet == []                # under the floor


def test_detector_floor_suppresses_microjitter():
    """Tight group (median ~ 0): µs-scale jitter must not flag without
    the floor, and must not flag WITH the default floor."""
    from ray_tpu.util.collective.telemetry import detect_stragglers

    starts = [0.0, 1e-6, 2e-6, 2e-4]
    flagged, _, _ = detect_stragglers(_timings(starts),
                                      multiple=3.0, min_lag_s=0.0)
    assert [r for r, _ in flagged] == [3]   # no floor: flagged
    quiet, _, _ = detect_stragglers(_timings(starts),
                                    multiple=3.0, min_lag_s=0.05)
    assert quiet == []                      # 50ms floor: quiet


def test_detector_degenerate_sizes():
    from ray_tpu.util.collective.telemetry import detect_stragglers

    assert detect_stragglers([], multiple=3.0, min_lag_s=0.0) == \
        ([], {}, 0.0)
    assert detect_stragglers(_timings([1.0]), multiple=3.0,
                             min_lag_s=0.0) == ([], {}, 0.0)


def test_aggregator_emits_event_when_all_ranks_reported():
    from ray_tpu._private import events
    from ray_tpu.util.collective.telemetry import GroupTimingAggregator

    events.clear()
    agg = GroupTimingAggregator(world_size=4)
    t0 = 1000.0
    recs = _timings([t0, t0 + 0.001, t0 + 0.002, t0 + 0.9])
    agg.ingest(recs[:2])              # partial: no event yet
    assert not [e for e in events.snapshot()
                if e["kind"] == "COLLECTIVE_STRAGGLER"]
    agg.ingest(recs[2:])              # completes seq 1
    evs = [e for e in events.snapshot()
           if e["kind"] == "COLLECTIVE_STRAGGLER"]
    assert len(evs) == 1
    ev = evs[0]
    assert ev["group"] == "g" and ev["op"] == "allreduce"
    assert ev["ranks"] == [3]
    assert ev["lags_s"]["3"] == pytest.approx(0.9, abs=1e-5)
    assert agg.stragglers_found == 1


def test_aggregator_duplicate_report_is_noop():
    """A duplicated/retried report for an already-evaluated seq must
    neither re-emit the event nor resurrect an unfinishable pending
    slot (which would squat in the bounded table and evict genuinely
    pending seqs)."""
    from ray_tpu._private import events
    from ray_tpu.util.collective.telemetry import GroupTimingAggregator

    events.clear()
    agg = GroupTimingAggregator(world_size=2)
    recs = _timings([0.0, 5.0])
    agg.ingest(recs)
    n_events = len([e for e in events.snapshot()
                    if e["kind"] == "COLLECTIVE_STRAGGLER"])
    assert n_events == 1
    agg.ingest([recs[1]])            # duplicate delivery of rank 1
    assert agg._pending == {}        # not resurrected
    assert len([e for e in events.snapshot()
                if e["kind"] == "COLLECTIVE_STRAGGLER"]) == n_events


def test_aggregator_pending_table_is_bounded():
    from ray_tpu.util.collective import telemetry as ct

    agg = ct.GroupTimingAggregator(world_size=2)
    # 1000 seqs that never complete (only rank 0 reports)
    agg.ingest([{"rank": 0, "start": 0.0, "seq": s, "group": "g",
                 "op": "allreduce"} for s in range(1000)])
    assert len(agg._pending) <= ct._MAX_PENDING_SEQS


# ------------------------------------------------- compile watch


def test_compile_watch_hit_miss_and_events():
    from ray_tpu._private import events
    from ray_tpu.parallel.compile_watch import CompiledFunction
    from ray_tpu.util.metrics import registry_snapshot

    events.clear()
    calls = []
    fn = CompiledFunction(lambda x: calls.append(1) or x.sum(), "cw_test")
    fn(np.zeros((4, 4)))                  # miss
    fn(np.ones((4, 4)))                   # same signature: hit
    fn(np.zeros((8, 4)))                  # new shape: miss
    assert len(calls) == 3
    kinds = [e["kind"] for e in events.snapshot()
             if e.get("fn") == "cw_test"]
    assert kinds == ["COMPILE_BEGIN", "COMPILE_END",
                     "COMPILE_BEGIN", "COMPILE_END"]
    fam = next(m for m in registry_snapshot()
               if m["name"] == "ray_tpu_pjit_cache_total")
    by_result = {v["tags"]["result"]: v["value"] for v in fam["values"]
                 if v["tags"].get("fn") == "cw_test"}
    assert by_result == {"miss": 2.0, "hit": 1.0}
    comp = next(m for m in registry_snapshot()
                if m["name"] == "ray_tpu_pjit_compile_seconds")
    n = sum(sum(row["counts"]) for row in comp["counts"]
            if row["tags"].get("fn") == "cw_test")
    assert n == 2


def test_compile_watch_failed_compile_not_cached():
    from ray_tpu.parallel.compile_watch import CompiledFunction

    boom = [True]

    def fn(x):
        if boom[0]:
            raise RuntimeError("compile exploded")
        return x

    wrapped = CompiledFunction(fn, "cw_fail")
    with pytest.raises(RuntimeError):
        wrapped(np.zeros(3))
    boom[0] = False
    # the retry must re-classify as a miss (key was not cached)
    assert wrapped._seen == set()
    wrapped(np.zeros(3))
    assert len(wrapped._seen) == 1


def test_compile_watch_survives_cloudpickle():
    """make_train_step's return value used to be a bare jax.jit result,
    which cloudpickles across task boundaries — the wrapper must too
    (lock dropped, cache reset: the receiving process recompiles, so a
    fresh cache keeps its hit/miss classification truthful)."""
    import cloudpickle

    from ray_tpu.parallel.compile_watch import CompiledFunction

    fn = CompiledFunction(lambda x: x * 2, "cw_pickle")
    fn(np.zeros(3))
    clone = cloudpickle.loads(cloudpickle.dumps(fn))
    assert clone._name == "cw_pickle"
    assert clone._seen == set()            # fresh cache on the far side
    assert float(clone(np.ones(2))[0]) == 2.0
    assert len(clone._seen) == 1


def test_compile_watch_kill_switch(monkeypatch):
    from ray_tpu._private import telemetry as tm
    from ray_tpu.parallel.compile_watch import CompiledFunction

    monkeypatch.setattr(tm, "ENABLED", False)
    fn = CompiledFunction(lambda x: x, "cw_off")
    fn(np.zeros(2))
    assert fn._seen == set()   # classification skipped entirely


def test_compile_watch_cache_size_path_with_real_jit():
    """Real jitted functions classify via jit's own _cache_size delta
    (O(1) on the hit path — no per-leaf signature rebuild per training
    step), with the same metrics/events as the fallback path."""
    import jax

    from ray_tpu._private import events
    from ray_tpu.parallel.compile_watch import CompiledFunction
    from ray_tpu.util.metrics import registry_snapshot

    events.clear()
    fn = CompiledFunction(jax.jit(lambda x: x + 1), "cw_jit")
    assert getattr(fn._fn, "_cache_size", None) is not None
    fn(np.zeros(4))                  # compile
    fn(np.ones(4))                   # hit
    fn(np.zeros(6))                  # new shape: compile
    # the signature set records only MISSES (error-path classifier:
    # compile failure vs runtime failure of a compiled program) — the
    # hit never touched it
    assert len(fn._seen) == 2
    fam = next(m for m in registry_snapshot()
               if m["name"] == "ray_tpu_pjit_cache_total")
    by_result = {v["tags"]["result"]: v["value"] for v in fam["values"]
                 if v["tags"].get("fn") == "cw_jit"}
    assert by_result == {"miss": 2.0, "hit": 1.0}
    kinds = [e["kind"] for e in events.snapshot()
             if e.get("fn") == "cw_jit"]
    assert kinds == ["COMPILE_BEGIN", "COMPILE_END",
                     "COMPILE_BEGIN", "COMPILE_END"]
    begin = next(e for e in events.snapshot()
                 if e.get("fn") == "cw_jit"
                 and e["kind"] == "COMPILE_BEGIN")
    assert begin["started_at"] <= begin["ts"]   # materialized post hoc
    from ray_tpu._private import profiling

    assert any(e["name"] == "compile::cw_jit"
               for e in profiling.snapshot())


def test_compile_watch_failed_compile_visible_on_cache_size_path():
    """A trace/compile-time failure on the _cache_size path must be as
    visible as on the fallback path: miss counted, COMPILE_END ok=False
    recorded (a crash-looping worker must not show zero compile
    activity)."""
    import jax

    from ray_tpu._private import events
    from ray_tpu.parallel.compile_watch import CompiledFunction

    events.clear()

    def bad(x):
        raise ValueError("explodes during trace")

    fn = CompiledFunction(jax.jit(bad), "cw_jitfail")
    with pytest.raises(ValueError):
        fn(np.zeros(3))
    evs = [e for e in events.snapshot() if e.get("fn") == "cw_jitfail"]
    assert [e["kind"] for e in evs] == ["COMPILE_BEGIN", "COMPILE_END"]
    assert evs[1]["ok"] is False


def test_publish_local_device_gauges_in_process():
    """Owner-side gauge publish: in-process memory_stats from an
    already-imported jax backend, never a subprocess (the path train
    workers use per step — they own the chips, so nothing else may
    probe them). On backends without memory stats it's a clean 0."""
    import jax

    from ray_tpu._private.tpu_probe import publish_local_device_gauges

    jax.devices()
    n = publish_local_device_gauges()
    assert n >= 0
    try:
        has_stats = bool(jax.local_devices()[0].memory_stats())
    except Exception:
        has_stats = False
    if has_stats:
        assert n == len(jax.local_devices())


def test_mesh_build_metric_recorded():
    import jax

    from ray_tpu.parallel.mesh import create_mesh
    from ray_tpu.util.metrics import registry_snapshot

    create_mesh(devices=[jax.devices()[0]], axes={"dp": 1})
    fam = next(m for m in registry_snapshot()
               if m["name"] == "ray_tpu_mesh_build_seconds")
    assert any(row["tags"].get("kind") == "mesh" and sum(row["counts"])
               for row in fam["counts"])


# ------------------------------------------------- device telemetry


def test_publish_device_gauges_from_injected_snapshot():
    from ray_tpu._private.tpu_probe import publish_device_gauges
    from ray_tpu.util.metrics import registry_snapshot

    n = publish_device_gauges(devices=[
        {"id": 7, "platform": "tpu", "kind": "TPU v4",
         "hbm_bytes_in_use": 1 << 30, "hbm_bytes_limit": 32 << 30},
        {"id": 8, "platform": "cpu"},     # CPU fallback: no hbm stats
    ])
    assert n == 2
    fam = next(m for m in registry_snapshot()
               if m["name"] == "ray_tpu_device_hbm_bytes")
    vals = {(v["tags"]["device"], v["tags"]["stat"]): v["value"]
            for v in fam["values"]}
    assert vals[("7", "in_use")] == float(1 << 30)
    assert vals[("7", "limit")] == float(32 << 30)
    assert not any(d == "8" for d, _ in vals)
    # every series carries the producing host: local device ids restart
    # at 0 per host, so a multi-host cluster needs the node tag to not
    # collide last-write-wins
    import os

    assert all(v["tags"]["node"] == os.uname().nodename
               for v in fam["values"])


def test_local_device_identity_shape():
    """jax is already imported by this suite's other tests, so the
    identity must carry platform + device ids; host/pid always."""
    import jax

    from ray_tpu._private.tpu_probe import local_device_identity

    jax.devices()
    info = local_device_identity()
    assert info["host"] and info["pid"]
    assert info["platform"] in ("cpu", "tpu", "gpu")
    assert info["device_count"] >= 1
    assert len(info["device_ids"]) == info["device_count"]


# ------------------------------------------------- overhead guard


def _fake_group(name, impl, world_size=4):
    """An in-process _GroupState over a no-RPC impl. store=None: timing
    records are buffered then dropped by the flusher (no rendezvous
    actor)."""
    from ray_tpu.util.collective.collective import _GroupState, _manager

    state = _GroupState(name, world_size, 0, "host", impl, None)
    _manager._groups[name] = state
    return state


def test_overhead_guard_host_allreduce_under_5pct(monkeypatch):
    """CI satellite: instrumentation on the host-backend allreduce hot
    path stays <5% vs uninstrumented (telemetry off). A direct A/B
    wall-clock ratio on a multi-ms op drowns a ~10µs wrapper in ±5%
    machine noise, so the guard measures the two quantities that make
    up the ratio separately — each is individually stable:

    - the ABSOLUTE per-call instrumentation cost, from a no-op impl
      (on-minus-off isolates the wrapper itself);
    - the hot-path op cost, from an impl doing the deterministic numpy
      work of a small ring step (a LOWER bound on any real collective,
      which also pays peer RPCs).

    Shows up in --durations by design."""
    import statistics

    from ray_tpu._private import telemetry as tm
    from ray_tpu.util import collective as col

    class _Noop:
        def allreduce(self, arr, op, seq):
            return arr

    class _RingStep:
        def allreduce(self, arr, op, seq):
            out = arr
            for _ in range(4):
                out = out + out * 0.5
            return out

    _fake_group("ovh_noop", _Noop())
    _fake_group("ovh_ring", _RingStep())
    tiny = np.zeros(16)
    arr = np.zeros(200_000)

    def per_call(group, payload, n=60):
        samples = []
        for _ in range(n):
            t0 = time.perf_counter()
            col.allreduce(payload, group_name=group)
            samples.append(time.perf_counter() - t0)
        return statistics.median(samples)

    try:
        for g, p in (("ovh_noop", tiny), ("ovh_ring", arr)):
            col.allreduce(p, group_name=g)        # warm both paths
        rounds_on, rounds_off, op_rounds = [], [], []
        for _ in range(5):
            monkeypatch.setattr(tm, "ENABLED", False)
            rounds_off.append(per_call("ovh_noop", tiny))
            op_rounds.append(per_call("ovh_ring", arr, n=20))
            monkeypatch.setattr(tm, "ENABLED", True)
            rounds_on.append(per_call("ovh_noop", tiny))
        overhead = max(0.0, min(rounds_on) - min(rounds_off))
        op_cost = min(op_rounds)
        assert overhead < 0.05 * op_cost, (
            f"instrumentation adds {overhead * 1e6:.1f}µs/op — "
            f"{overhead / op_cost * 100:.1f}% of a {op_cost * 1e3:.2f}ms "
            f"host ring step (budget: 5%)")
    finally:
        from ray_tpu.util.collective.collective import _manager

        _manager._groups.pop("ovh_noop", None)
        _manager._groups.pop("ovh_ring", None)
        from ray_tpu.util.collective.telemetry import flush_timings

        flush_timings()   # drop buffered records for the dead groups


# ------------------------------------------------- cluster acceptance


def test_collective_telemetry_end_to_end(ray_start_regular):
    """Acceptance: 4-rank host-backend collective with one
    slow_reply-faulted rank →
    - correct latency/bytes samples in metrics_summary(),
    - COLLECTIVE_STRAGGLER event naming the slow rank,
    - collective span linked under the submitting task's trace,
    - collective span on the chrome timeline (both clock planes),
    - summarize_collectives() folds all of it."""
    ray = ray_start_regular
    from ray_tpu._private import fault_injection
    from ray_tpu.experimental.state.api import (
        list_cluster_events,
        metrics_summary,
        summarize_collectives,
    )
    from ray_tpu.util import collective as col
    from ray_tpu.util import tracing

    tracing.enable()
    try:
        from ray_tpu.util.collective import CollectiveActorMixin

        @ray.remote
        class Rank(CollectiveActorMixin):
            def go(self, value, straggle=False):
                from ray_tpu.util import collective as c

                if straggle:
                    # arrival delayed by a slow_reply-faulted control
                    # RPC (the in-process GCS stalls kv_get replies)
                    from ray_tpu._private.worker_runtime import (
                        current_worker,
                    )

                    try:
                        current_worker().gcs.call(
                            "kv_get", ns="straggle", key=b"x")
                    except Exception:
                        pass
                arr = np.full(1024, float(value))      # 8192 bytes
                return float(c.allreduce(arr, group_name="zzg")[0])

        n = 4
        actors = [Rank.options(num_cpus=0).remote() for _ in range(n)]
        col.create_collective_group(actors, n, list(range(n)),
                                    backend="host", group_name="zzg")
        fault_injection.install(11, "slow_reply:*.kv_get:p1:600")
        try:
            out = ray.get(
                [a.go.remote(i + 1, straggle=(i == 3))
                 for i, a in enumerate(actors)], timeout=120)
        finally:
            fault_injection.uninstall()
        assert out == [10.0] * n

        # --- metrics: 4 latency samples + 4 * 8192 payload bytes
        deadline = time.time() + 30
        while True:
            snaps = {m["name"]: m for m in metrics_summary()}
            lat = snaps.get("ray_tpu_collective_latency_seconds")
            rows = [r for r in (lat or {}).get("counts", ())
                    if r["tags"] == {"op": "allreduce", "backend": "host",
                                     "group": "zzg"}]
            if rows and sum(sum(r["counts"]) for r in rows) >= n:
                break
            assert time.time() < deadline, (lat, "latency samples late")
            time.sleep(0.5)
        assert sum(sum(r["counts"]) for r in rows) == n
        byt = snaps["ray_tpu_collective_bytes_total"]
        moved = sum(v["value"] for v in byt["values"]
                    if v["tags"] == {"op": "allreduce", "backend": "host",
                                     "group": "zzg"})
        assert moved == n * 1024 * 8

        # --- straggler event names the faulted rank
        deadline = time.time() + 30
        while True:
            evs = [e for e in list_cluster_events(
                       filters=[("kind", "=", "COLLECTIVE_STRAGGLER")])
                   if e.get("group") == "zzg"]
            if any(3 in e.get("ranks", ()) for e in evs):
                break
            assert time.time() < deadline, (
                f"no COLLECTIVE_STRAGGLER naming rank 3 within budget: "
                f"{evs}")
            time.sleep(0.5)
        ev = next(e for e in evs if 3 in e.get("ranks", ()))
        assert ev["op"] == "allreduce"
        assert float(ev["lags_s"]["3"]) > 0.3     # ~600ms injected

        # --- tracing: collective span joins the submitting task trace
        spans = tracing.get_spans()
        col_spans = [s for s in spans if s["name"] == "collective "
                     "allreduce" and s["attributes"].get("group") == "zzg"]
        assert len(col_spans) >= n
        submit_traces = {s["traceId"] for s in spans
                         if s["name"].startswith("submit ")}
        for s in col_spans:
            assert s["parentSpanId"], s
            assert s["traceId"] in submit_traces, (
                "collective span not linked under a submitted task's "
                "trace")

        # --- chrome timeline carries the same op (µs clock plane)
        trace = ray.timeline()
        tl = [e for e in trace if e["name"] == "collective::allreduce"
              and e.get("args", {}).get("group") == "zzg"]
        assert len(tl) >= n
        assert all(e["ph"] == "X" and e["dur"] >= 0 for e in tl)

        # --- the rollup folds ops + stragglers + (any) compile rows
        summary = summarize_collectives()
        row = next(r for r in summary["ops"]
                   if r["group"] == "zzg" and r["op"] == "allreduce")
        assert row["backend"] == "host"
        assert row["count"] == n
        assert row["bytes"] == moved
        assert row["mean_s"] > 0
        assert any(3 in e.get("ranks", ()) for e in summary["stragglers"])
    finally:
        tracing.disable()
        tracing.clear()


def test_seq_desync_raises_mismatch_not_hang(ray_start_regular):
    """Satellite: a rank whose op counter desynced (here: one bumped
    seq) used to hang until the op timeout or mis-pair payloads; now
    the rank that observes the NEWER peer seq raises
    CollectiveSeqMismatchError fast (its peer, seeing only an older
    seq, falls back to the bounded watchdog timeout)."""
    import os as _os

    _os.environ["RAY_TPU_COLLECTIVE_OP_TIMEOUT_S"] = "5"
    ray = ray_start_regular
    from ray_tpu.util import collective as col
    from ray_tpu.util.collective import CollectiveActorMixin

    @ray.remote
    class Rank(CollectiveActorMixin):
        def desync(self):
            from ray_tpu.util.collective.collective import _manager

            _manager.get("zzseq").next_seq()    # counter now skewed
            return True

        def go(self, value):
            from ray_tpu.util import collective as c

            return float(c.allreduce(np.full(4, float(value)),
                                     group_name="zzseq")[0])

    try:
        actors = [Rank.options(num_cpus=0).remote() for _ in range(2)]
        col.create_collective_group(actors, 2, [0, 1], backend="host",
                                    group_name="zzseq")
        ray.get(actors[1].desync.remote(), timeout=30)
        t0 = time.time()
        refs = [a.go.remote(1) for a in actors]
        # rank 0 observes rank 1's NEWER seq: immediate mismatch error
        with pytest.raises(Exception) as ei:
            ray.get(refs[0], timeout=60)
        assert "sequence mismatch" in str(ei.value)
        assert time.time() - t0 < 4    # beat even the 5s watchdog
        # rank 1 only sees an OLDER seq (ambiguous): bounded timeout,
        # annotated with the desync hint
        with pytest.raises(Exception) as ei2:
            ray.get(refs[1], timeout=60)
        assert "timed out" in str(ei2.value)
        assert "older seq" in str(ei2.value)
    finally:
        _os.environ.pop("RAY_TPU_COLLECTIVE_OP_TIMEOUT_S", None)


def test_col_take_seq_validation_unit(ray_start_regular):
    """Direct mailbox-level check of the mismatch rule: exact key wins
    even next to a stale same-channel message; a lone different-seq
    message raises."""
    from ray_tpu import exceptions as exc
    from ray_tpu._private.worker_runtime import current_worker

    w = current_worker()
    chan = ("zzu", "ar")
    w.col_push_local(chan + (2, 0, 1), b"seq2")
    w.col_push_local(chan + (5, 0, 1), b"seq5")
    # exact key present: returned, the pipelined seq5 untouched
    assert w.col_take(chan + (2, 0, 1), timeout=5, seq_pos=2) == b"seq2"
    # a NEWER same-channel seq waiting proves desync (in-order
    # delivery: our seq-4 message would already have arrived)
    with pytest.raises(exc.CollectiveSeqMismatchError) as ei:
        w.col_take(chan + (4, 0, 1), timeout=5, seq_pos=2)
    assert "expects seq 4" in str(ei.value)
    # an OLDER same-channel seq is ambiguous (redelivered dup vs
    # restarted peer): no mismatch — timeout, annotated with the hint
    chan2 = ("zzu2", "ar")
    w.col_push_local(chan2 + (1, 0, 1), b"stale-dup")
    with pytest.raises(TimeoutError) as ti:
        w.col_take(chan2 + (6, 0, 1), timeout=0.3, seq_pos=2)
    assert "older seq [1]" in str(ti.value)
    # a message from a DIFFERENT src is a different channel — neither
    # mismatch nor hint
    chan3 = ("zzu3", "ar")
    w.col_push_local(chan3 + (9, 0, 7), b"other-src")
    with pytest.raises(TimeoutError) as ti:
        w.col_take(chan3 + (8, 0, 1), timeout=0.3, seq_pos=2)
    assert "older seq" not in str(ti.value)


def test_destroy_purges_mailbox_for_reincarnation(ray_start_regular):
    """A payload from a dead group incarnation (e.g. landed after an op
    timeout) must not masquerade as a NEWER seq to a re-created group
    under the same name — destroy purges this process's mailbox."""
    from ray_tpu._private.worker_runtime import current_worker
    from ray_tpu.util.collective.collective import _GroupState, _manager

    w = current_worker()
    w.col_push_local(("zzpurge", "ar", 7, 0, 1), b"old-incarnation")
    w.col_push_local(("zzother", "ar", 7, 0, 1), b"unrelated")

    class _Impl:
        def close(self):
            pass

    _manager._groups["zzpurge"] = _GroupState("zzpurge", 2, 0, "host",
                                              _Impl(), None)
    assert _manager.destroy("zzpurge") is True
    # the dead incarnation's message is gone: a fresh seq-1 wait times
    # out instead of raising a phantom mismatch...
    with pytest.raises(TimeoutError):
        w.col_take(("zzpurge", "ar", 1, 0, 1), timeout=0.3, seq_pos=2)
    # ...and other groups' mail is untouched
    assert w.col_take(("zzother", "ar", 7, 0, 1), timeout=1) == \
        b"unrelated"


def test_list_cluster_events_limit_zero(ray_start_regular):
    from ray_tpu._private import events
    from ray_tpu.experimental.state.api import list_cluster_events

    events.record("zz_limit_probe")
    assert list_cluster_events(limit=0) == []
    assert len(list_cluster_events(limit=1)) == 1


def test_cli_has_collectives_subcommand(monkeypatch):
    """Parse-level smoke: `ray-tpu collectives --address h:1` routes to
    cmd_collectives with the address wired through (main() builds its
    parser per call, so patching the module-level handler intercepts)."""
    from ray_tpu.scripts import cli

    called = {}
    monkeypatch.setattr(
        cli, "cmd_collectives",
        lambda args: called.update(address=args.address) or 0)
    assert cli.main(["collectives", "--address", "h:1"]) == 0
    assert called == {"address": "h:1"}
