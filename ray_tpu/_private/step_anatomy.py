"""Step anatomy: per-step, per-rank attribution of train-loop wall clock.

The telemetry planes this framework grew (collective spans + rank
timings, data-wait stamps, compile events, chrome-timeline spans,
tracing spans) each answer their own question, but none of them can
answer the one the ROADMAP's overlap arc hangs on: *for step N, where
did the wall clock go on each rank, and how much of the auxiliary work
was actually hidden under compute?* ("Exploring the limits of
Concurrency in ML Training on Google TPUs", arXiv:2011.03641 — overlap
fraction is the metric that decides whether pipelining paid off.)

This module is the join key and the fusion. The train loop stamps a
monotonically increasing ``step_id`` into a process-global step context
(``start``/``advance``/``finish`` — wired into the Train worker and
``session.report``); every instrumented plane that runs while a step is
active appends a small *activity record* (``record_activity``) tagged
with that step id:

- ``collective``    one collective op (util/collective/telemetry.py);
  blocking when issued on the step's own thread, background when a
  helper thread ran it (a future async-bucketed DDP records these);
- ``data_wait``     consumer-blocked time for one batch (streaming
  iterator) — always exposed;
- ``data_produce``  the double-buffer producer thread's batch
  conversion + device_put dispatch — background by construction, the
  part of ingest that hides under compute;
- ``compile``       a pjit trace+compile (parallel/compile_watch.py).

Records carry intervals on the **producing process's own monotonic
clock**. Fusion NEVER joins by wall-clock windows: records fuse by
``step_id`` (and phases are computed per rank from that rank's own
clock), so NTP skew between hosts cannot smear attribution — the only
cross-rank comparisons are durations.

Per (step, rank) the fusion yields: ``compute_s`` (step wall minus all
exposed aux), ``comm_exposed_s`` / ``comm_hidden_s``, ``data_wait_s`` /
``data_hidden_s``, ``compile_s``, ``other_s``, and an
``overlap_fraction`` = hidden / (hidden + exposed). Per step it names
the cross-rank critical path: the slowest rank and the phase that
dominated it. A rolling-baseline regression detector watches p50 step
time and emits a ``STEP_REGRESSION`` cluster event plus
``ray_tpu_step_regressions_total`` when the recent p50 drifts beyond
``step_regression_multiple`` x the prior window's p50.

Everything is behind ``RAY_TPU_INTERNAL_TELEMETRY=0`` (checked live on
every entry point); with the plane off, the hot paths pay one bool.
With it on, a collective op pays one tuple read + one lock'd append —
see the <5% guard in tests/test_zz_step_anatomy.py.
"""
from __future__ import annotations

import collections
import os
import statistics
import threading
import time

from ray_tpu._private import telemetry as _tm

_MAX_STEPS = 2048          # per-process step-record ring (drop-oldest)
_MAX_ACTIVITIES = 16384    # per-process activity ring (drop-oldest)

# cached per process (workers are spawned, never forked) — same
# rationale as events.py/profiling.py
_PID = os.getpid()
_NODE = os.uname().nodename

_lock = threading.Lock()
_steps: collections.deque = collections.deque(maxlen=_MAX_STEPS)
_acts: collections.deque = collections.deque(maxlen=_MAX_ACTIVITIES)
_steps_dropped = 0
_acts_dropped = 0
_seq = 0

# the active step, swapped atomically as one tuple so hot-path readers
# (collective ops, data stamps — possibly on other threads) never see a
# half-updated context: (step_id, rank, t0_monotonic, t0_wall)
_cur: tuple | None = None
_cur_thread: int | None = None    # ident of the thread driving the loop

# regression detector state (per process; the train thread owns it).
# The window/multiple knobs are cached once per loop (invalidated by
# start()/clear()): a live os.environ read per step is measurable
# against the per-step overhead budget.
_durations: collections.deque = collections.deque()
_regressions = 0
_reg_params: tuple | None = None


def _regression_params() -> tuple:
    global _reg_params
    params = _reg_params
    if params is None:
        from ray_tpu._private.config import get_config

        params = _reg_params = (
            int(get_config("step_regression_window")),
            float(get_config("step_regression_multiple")))
    return params


def _enabled() -> bool:
    # read the module attribute live (not a from-import) so the
    # RAY_TPU_INTERNAL_TELEMETRY kill switch and test monkeypatching of
    # telemetry.ENABLED govern this plane too
    return _tm.ENABLED


def current() -> tuple | None:
    """(step_id, rank) of the active step, or None. One attribute read —
    safe on hot paths."""
    cur = _cur
    if cur is None:
        return None
    return (cur[0], cur[1])


def current_step_id():
    cur = _cur
    return None if cur is None else cur[0]


def start(rank: int = 0, step_id: int = 1):
    """Begin step anatomy for this process's train loop: step ``step_id``
    is active from now until ``advance``/``finish``. Called by the Train
    worker right before the user's train function runs."""
    global _cur, _cur_thread, _reg_params
    if not _enabled():
        return
    _cur = (int(step_id), int(rank), time.monotonic(), time.time())
    _cur_thread = threading.get_ident()
    _durations.clear()
    _reg_params = None      # re-read the knobs once per loop


def advance(step_id: int | None = None):
    """End the active step (recording its span) and begin the next.
    ``session.report`` calls this once per iteration, which makes the
    interval between reports the step and the report's iteration number
    the step id. No-op when no step is active (report outside a train
    loop, e.g. Tune function trainables on the driver)."""
    global _cur
    cur = _cur
    if cur is None or not _enabled():
        return
    now_m, now_w = time.monotonic(), time.time()
    sid, rank, t0_m, t0_w = cur
    _record_step(sid, rank, t0_m, now_m, t0_w, now_w)
    nxt = int(step_id) + 1 if step_id is not None else sid + 1
    # keep ids monotonically increasing even if a caller hands back a
    # stale iteration number (a resumed gang restarts its session
    # counter; the anatomy ring must never reuse a live id)
    if nxt <= sid:
        nxt = sid + 1
    _cur = (nxt, rank, now_m, now_w)
    _check_regression(now_m - t0_m, sid, rank)


def finish():
    """End step anatomy (train function returned/raised): records the
    final partial step and clears the context."""
    global _cur, _cur_thread
    cur = _cur
    _cur = None
    _cur_thread = None
    if cur is None or not _enabled():
        return
    sid, rank, t0_m, t0_w = cur
    _record_step(sid, rank, t0_m, time.monotonic(), t0_w, time.time())


def _record_step(sid, rank, start_m, end_m, start_w, end_w):
    global _steps_dropped, _seq
    dur = max(0.0, end_m - start_m)
    with _lock:
        _seq += 1
        if len(_steps) == _steps.maxlen:
            _steps_dropped += 1
        _steps.append({"step_id": sid, "rank": rank, "node": _NODE,
                       "pid": _PID, "seq": _seq, "start": start_m,
                       "end": end_m, "wall_start": start_w,
                       "wall_end": end_w})
    _tm.observe("ray_tpu_step_seconds", dur)
    try:
        from ray_tpu._private import profiling as _prof

        _prof.record_completed_span("step", f"step::{sid}", start_w, dur,
                                    {"step": sid, "rank": rank})
    except Exception:
        pass


def record_activity(kind: str, start_m: float, end_m: float,
                    blocking: bool = True, **meta):
    """Attribute one interval of auxiliary work to the active step.
    ``start_m``/``end_m`` are time.monotonic() on THIS process. No-op
    (one tuple read) when no step is active or the plane is off."""
    global _acts_dropped, _seq
    cur = _cur
    if cur is None or not _enabled():
        return
    rec = {"step_id": cur[0], "rank": cur[1], "node": _NODE, "pid": _PID,
           "kind": kind, "start": start_m, "end": end_m,
           "blocking": bool(blocking)}
    if meta:
        rec["meta"] = meta
    with _lock:
        _seq += 1
        rec["seq"] = _seq
        if len(_acts) == _acts.maxlen:
            _acts_dropped += 1
        _acts.append(rec)


def _check_regression(dur_s: float, step_id: int | None = None,
                      rank: int | None = None):
    """Rolling-baseline p50 drift detector, amortized to stay off the
    per-step budget: durations accumulate cheaply (one append); the
    median comparison runs only when a full window of NEW steps has
    arrived since the last evaluation (cost ~1/window per step — the
    per-step overhead guard in tests/test_zz_step_anatomy.py is why).
    Fires when p50(last window) > multiple * p50(window before it);
    after firing the history resets, so one sustained slowdown emits
    one event per re-filled window, not one per step. After a quiet
    evaluation the baseline rolls forward by one window."""
    global _regressions
    _durations.append(dur_s)
    window, multiple = _regression_params()
    if window <= 0:
        _durations.clear()
        return
    if len(_durations) < 2 * window:
        return
    hist = list(_durations)[-2 * window:]
    base = statistics.median(hist[:window])
    recent = statistics.median(hist[window:])
    if base <= 0 or recent <= multiple * base:
        # quiet: keep only the recent window as the next baseline
        recent_hist = hist[window:]
        _durations.clear()
        _durations.extend(recent_hist)
        return
    _regressions += 1
    from ray_tpu._private import events as _events

    # step_id is the step that COMPLETED the regressed window (advance
    # has already opened the next one by the time this runs) — the id
    # an operator should look up in summarize_steps()
    _events.record("STEP_REGRESSION", rank=rank, step_id=step_id,
                   p50_recent_s=round(recent, 6),
                   p50_baseline_s=round(base, 6),
                   multiple=multiple, window=window)
    _tm.counter_inc("ray_tpu_step_regressions_total")
    _durations.clear()


def local_records() -> dict:
    """This process's step + activity records (each a copy), plus drop
    counts so a fused report can flag incomplete windows instead of
    silently reporting wrong attribution."""
    with _lock:
        return {"node": _NODE, "pid": _PID,
                "steps": [dict(s) for s in _steps],
                "activities": [dict(a) for a in _acts],
                "steps_dropped": _steps_dropped,
                "activities_dropped": _acts_dropped}


def clear():
    global _steps_dropped, _acts_dropped, _regressions, _reg_params
    with _lock:
        _steps.clear()
        _acts.clear()
        _steps_dropped = 0
        _acts_dropped = 0
    _durations.clear()
    _regressions = 0
    _reg_params = None


# ------------------------------------------------------------------ fusion
#
# Pure functions over exported record sets — usable post-hoc on a flight
# recorder dump as well as live through summarize_steps().


def _merge(intervals: list[tuple]) -> list[tuple]:
    """Union of [s, e) intervals as a sorted disjoint list."""
    out: list[list] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _total(intervals: list[tuple]) -> float:
    return sum(e - s for s, e in intervals)


def _subtract(intervals: list[tuple], cover: list[tuple]) -> float:
    """Total length of ``intervals`` (disjoint, sorted) not covered by
    ``cover`` (disjoint, sorted)."""
    total = 0.0
    ci = 0
    for s, e in intervals:
        pos = s
        while pos < e:
            while ci < len(cover) and cover[ci][1] <= pos:
                ci += 1
            if ci == len(cover) or cover[ci][0] >= e:
                total += e - pos
                break
            cs, ce = cover[ci]
            if cs > pos:
                total += cs - pos
            pos = max(pos, ce)
    return total


_EXPOSED_KINDS = {"collective": "comm_exposed_s", "data_wait":
                  "data_wait_s", "compile": "compile_s",
                  # pipeline-parallel schedule stall: wall clock a stage
                  # spent parked waiting for an upstream activation /
                  # downstream gradient / in-flight-window credit (the
                  # train/pipeline loop stamps these). Kept distinct
                  # from generic comm so the measured per-stage bubble
                  # fraction can be checked against (P-1)/(M+P-1)
                  # schedule theory.
                  "pipeline_bubble": "bubble_s"}
_HIDDEN_KINDS = {"collective": "comm_hidden_s", "data_produce":
                 "data_hidden_s"}


def anatomize_rank_step(step: dict, acts: list[dict]) -> dict:
    """Phase breakdown for one rank's one step from that rank's own
    records (single clock domain). Exposed time = union of blocking
    intervals; hidden time = background intervals minus their overlap
    with exposed time (work genuinely riding under compute); compute =
    wall - exposed."""
    s0, s1 = step["start"], step["end"]
    wall = max(0.0, s1 - s0)
    clip = lambda a: (max(s0, a["start"]), min(s1, a["end"]))  # noqa: E731
    exposed_by: dict[str, list] = {}
    hidden_by: dict[str, list] = {}
    for a in acts:
        iv = clip(a)
        if iv[1] <= iv[0]:
            continue
        if a.get("blocking", True):
            key = _EXPOSED_KINDS.get(a["kind"], "other_s")
            exposed_by.setdefault(key, []).append(iv)
        else:
            key = _HIDDEN_KINDS.get(a["kind"], "other_hidden_s")
            hidden_by.setdefault(key, []).append(iv)
    exposed_union = _merge([iv for ivs in exposed_by.values()
                            for iv in ivs])
    out = {"wall_s": wall, "comm_exposed_s": 0.0, "comm_hidden_s": 0.0,
           "data_wait_s": 0.0, "data_hidden_s": 0.0, "compile_s": 0.0,
           "bubble_s": 0.0, "other_s": 0.0, "other_hidden_s": 0.0}
    for key, ivs in exposed_by.items():
        out[key] = _total(_merge(ivs))
    if out["bubble_s"] and out["comm_exposed_s"]:
        # a pipeline schedule stall IS a blocking recv, so the same wall
        # interval arrives under both kinds (the collective op records
        # itself, and the pipeline loop stamps the stall). Keep the two
        # phases DISJOINT: bubble owns the stall, comm_exposed keeps
        # only communication that wasn't a schedule stall — otherwise
        # the per-rank phases sum past wall_s and comm stops measuring
        # the network.
        out["comm_exposed_s"] = _subtract(
            _merge(exposed_by["comm_exposed_s"]),
            _merge(exposed_by["bubble_s"]))
    for key, ivs in hidden_by.items():
        out[key] = _subtract(_merge(ivs), exposed_union)
    exposed_total = _total(exposed_union)
    # overlap accounting uses the UNION of all background intervals
    # minus exposed time, NEVER the sum of the per-kind values: two
    # concurrent async grad buckets (or a background bucket riding
    # under a data_produce window) cover the same wall clock once, and
    # a per-kind sum would double-count it — with enough concurrent
    # comm, "hidden" would exceed the step wall. The per-kind fields
    # above stay as attribution (they may legitimately overlap each
    # other); the fraction is computed from real wall-clock coverage.
    hidden_total = _subtract(
        _merge([iv for ivs in hidden_by.values() for iv in ivs]),
        exposed_union)
    out["compute_s"] = max(0.0, wall - exposed_total)
    out["overlap_fraction"] = (
        hidden_total / (hidden_total + exposed_total)
        if (hidden_total + exposed_total) > 0 else None)
    return out


_SELF_PHASES = ("compute_s", "data_wait_s", "compile_s", "other_s")


def _self_time(br: dict) -> float:
    """A rank's non-communication time in a step. In a bulk-synchronous
    gang the collective EQUALIZES wall clocks (fast ranks absorb the
    straggler's lateness as comm wait), so raw wall time cannot name
    the straggler — the rank the others waited on is the one with the
    most wall clock spent NOT communicating."""
    return max(0.0, br["wall_s"] - br["comm_exposed_s"])


def fuse(exports: list[dict]) -> dict:
    """Fuse per-process record exports into per-step anatomy. Joining is
    by ``step_id`` exactly — never by wall-clock windows — so records
    from hosts with skewed clocks still pair correctly. Returns::

        {"steps": [{"step_id", "ranks": {rank: breakdown},
                    "critical_path": {"rank", "phase", "wall_s"},
                    "overlap_fraction"}],
         "ranks": {rank: rollup}, "incomplete": bool,
         "dropped": {"steps": n, "activities": n}}
    """
    # dedup by (node, pid): the driver answers both locally and through
    # a raylet fan-out in in-process clusters — keep the richer export
    by_proc: dict[tuple, dict] = {}
    for ex in exports:
        if not ex:
            continue
        key = (ex.get("node"), ex.get("pid"))
        old = by_proc.get(key)
        if old is None or len(ex.get("steps", ())) > len(
                old.get("steps", ())):
            by_proc[key] = ex
    steps_by_id: dict[int, dict[int, dict]] = {}
    # activities keyed by (step_id, rank, node, pid): a gang restart
    # re-reports the same (step_id, rank) from a NEW process, and
    # interval math may only ever mix records from ONE process (one
    # monotonic clock domain) — the phase breakdown below pairs each
    # step record with activities from ITS OWN process exclusively
    acts_by: dict[tuple, list] = {}
    dropped = {"steps": 0, "activities": 0}
    for ex in by_proc.values():
        dropped["steps"] += int(ex.get("steps_dropped", 0))
        dropped["activities"] += int(ex.get("activities_dropped", 0))
        for s in ex.get("steps", ()):
            # a rank may re-report a step id after a gang restart:
            # last writer wins, and its activities follow it via the
            # (node, pid) part of the activity key
            steps_by_id.setdefault(int(s["step_id"]), {})[
                int(s["rank"])] = s
        for a in ex.get("activities", ()):
            acts_by.setdefault((int(a["step_id"]), int(a["rank"]),
                                a.get("node"), a.get("pid")),
                               []).append(a)
    all_ranks = {r for per in steps_by_id.values() for r in per}
    out_steps = []
    rank_roll: dict[int, dict] = {}
    for sid in sorted(steps_by_id):
        per_rank = {}
        for rank, srec in sorted(steps_by_id[sid].items()):
            br = anatomize_rank_step(
                srec, acts_by.get((sid, rank, srec.get("node"),
                                   srec.get("pid")), []))
            per_rank[rank] = br
            roll = rank_roll.setdefault(rank, collections.Counter())
            for k, v in br.items():
                if isinstance(v, (int, float)) and v is not None:
                    roll[k] += v
            roll["steps"] += 1
        crit_rank = max(per_rank,
                        key=lambda r: _self_time(per_rank[r]))
        crit = per_rank[crit_rank]
        phase = max(_SELF_PHASES, key=lambda p: crit.get(p, 0.0))
        fracs = [br["overlap_fraction"] for br in per_rank.values()
                 if br["overlap_fraction"] is not None]
        out_steps.append({
            "step_id": sid, "ranks": per_rank,
            "complete": set(per_rank) == all_ranks,
            "critical_path": {"rank": crit_rank, "phase": phase,
                              "wall_s": crit["wall_s"],
                              "self_s": _self_time(crit)},
            "overlap_fraction": (sum(fracs) / len(fracs)
                                 if fracs else None),
        })
    ranks = {}
    for rank, roll in sorted(rank_roll.items()):
        n = roll.pop("steps", 0) or 1
        roll.pop("overlap_fraction", None)
        ranks[rank] = {**{k: roll.get(k, 0.0) for k in
                          ("wall_s", "compute_s", "comm_exposed_s",
                           "comm_hidden_s", "data_wait_s",
                           "data_hidden_s", "compile_s", "bubble_s",
                           "other_s")},
                       "steps": n,
                       "mean_step_s": roll.get("wall_s", 0.0) / n}
    return {"steps": out_steps, "ranks": ranks,
            "incomplete": bool(dropped["steps"] or dropped["activities"]),
            "dropped": dropped}
