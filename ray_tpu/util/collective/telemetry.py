"""Data-plane telemetry for collective ops.

PR 2 made the control plane observable; this module does the same for
the part the paper cares about — the collective layer. Three planes,
all behind the single ``RAY_TPU_INTERNAL_TELEMETRY=0`` kill switch:

- metrics: every op records ``ray_tpu_collective_latency_seconds`` and
  ``ray_tpu_collective_bytes_total`` tagged (op, backend, group) into
  the internal CATALOG (_private/telemetry.py), so ``metrics_summary()``
  / the dashboard's /metrics see per-op latency histograms and payload
  throughput with no extra wiring;
- spans: each op emits a span into BOTH the chrome-trace timeline
  (_private/profiling.py, µs ``ts``/``dur``) and util/tracing
  (``*TimeUnixNano``) — the tracing span inherits the executing task's
  context, so a collective issued inside a remote task shows up as a
  child of that task's trace (satellite: both clocks, no unit bugs);
- rank timings: each rank's (group, seq, op, start, end) record is
  buffered locally and flushed by a background thread to the group's
  rendezvous actor — the one process that sees every rank — where
  ``GroupTimingAggregator`` runs the straggler detector per completed
  (group, seq) and emits a ``COLLECTIVE_STRAGGLER`` cluster event
  naming the late ranks (2011.03641's observation: per-step stragglers
  dominate scaling behavior; the ICI-aware scheduler needs this signal).

Hot-path budget: with telemetry disabled an op pays one attribute read.
Enabled, it pays two span appends, one histogram observe, one counter
inc, and one lock'd list append (~10µs) — the flush RPC never runs on
the op path (see the <5% overhead guard in
tests/test_zz_collective_telemetry.py).

Clock caveat: rank timings use ``time.time()`` on each member host, so
cross-host straggler lags include NTP-level clock skew (ms-scale) —
fine for the >= tens-of-ms lags the detector's floor targets, not for
µs-scale ICI asymmetry.
"""
from __future__ import annotations

import collections
import statistics
import threading
import time

from ray_tpu._private import events as _events
from ray_tpu._private import profiling as _prof
from ray_tpu._private import telemetry as _tm

# flush the local timing buffer early once it holds this many records
# (the timer normally fires first; this bounds memory under op storms)
_FLUSH_HIGH_WATER = 64
_MAX_PENDING_SEQS = 256      # aggregator: completed-seq working set bound


def payload_nbytes(tensor) -> int:
    """Payload size of one rank's input/output (numpy and jax arrays
    both expose .nbytes) — accounted bytes are payload, not wire bytes
    (a ring allreduce moves ~2x payload per rank; keeping the metric
    algorithm-independent makes it comparable across backends)."""
    n = getattr(tensor, "nbytes", None)
    if n is not None:
        try:
            return int(n)
        except (TypeError, ValueError):
            return 0
    return 0


def run_op(g, op: str, seq, body, payload=None,
           measure_result: bool = False):
    """Execute one collective op body under full data-plane telemetry.

    `g` is the _GroupState; `seq` is the group op sequence (None for
    p2p ops, which have per-channel numbering and no full-group timing
    record). Byte accounting comes from `payload` (the op's input
    array) or, with `measure_result=True`, from the return value
    (recv: the payload is only known afterwards) — sized HERE, after
    the kill-switch check, so a disabled op pays only the bool."""
    if not _tm.ENABLED:
        return body()
    from ray_tpu._private import step_anatomy as _sa
    from ray_tpu.util import tracing

    nbytes = payload_nbytes(payload) if payload is not None else 0
    tags = {"op": op, "backend": g.backend, "group": g.name}
    # the active train step (if any): stamped into both span planes and
    # the rank-timing record, and an activity interval goes to the
    # step-anatomy ring so per-step comm attribution fuses by step_id
    # instead of wall-clock windows. One tuple read when inactive.
    step = _sa.current()
    step_id = step[0] if step is not None else None
    start = time.time()
    t0 = time.perf_counter()
    mono0 = time.monotonic()
    with _prof.record_span("collective", f"collective::{op}",
                           {"group": g.name, "backend": g.backend,
                            "seq": seq, "bytes": nbytes,
                            "step": step_id}):
        with tracing.span(f"collective {op}", "INTERNAL",
                          attributes={"group": g.name,
                                      "backend": g.backend, "seq": seq,
                                      "step": step_id}):
            result = body()
    dur = time.perf_counter() - t0
    if step is not None:
        # blocking iff the op ran on the thread driving the step loop
        # (today's synchronous collectives always do; a future async
        # bucketed-DDP flusher records background comm here)
        _sa.record_activity(
            "collective", mono0, mono0 + dur,
            blocking=threading.get_ident() == _sa._cur_thread,
            op=op, group=g.name)
    if measure_result:
        nbytes = payload_nbytes(result)
    _tm.observe("ray_tpu_collective_latency_seconds", dur, tags=tags)
    if nbytes:
        _tm.counter_inc("ray_tpu_collective_bytes_total", float(nbytes),
                        tags=tags)
    if seq is not None and g.world_size > 1:
        _reporter.add({"group": g.name, "op": op, "seq": int(seq),
                       "rank": g.rank, "world_size": g.world_size,
                       "start": start, "end": start + dur,
                       "bytes": nbytes, "step": step_id})
    return result


# --------------------------------------------------------------- reporting


class _TimingReporter:
    """Per-process buffer of rank-timing records, flushed OFF the op
    path by a daemon thread to each group's rendezvous actor (the
    flush is a fire-and-forget actor call; a dead/destroyed group just
    drops its batch)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._buf: list[dict] = []
        self._wake = threading.Event()
        self._thread: threading.Thread | None = None

    def add(self, record: dict):
        with self._lock:
            self._buf.append(record)
            n = len(self._buf)
            # (re)start on demand: the loop quiesces itself once the
            # buffer is drained and every group is gone
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._loop, daemon=True,
                    name="collective-timing-flush")
                self._thread.start()
        if n >= _FLUSH_HIGH_WATER:
            self._wake.set()

    def _loop(self):
        from ray_tpu._private.config import get_config
        from ray_tpu.util.collective import collective as _col

        while True:
            self._wake.wait(
                timeout=float(get_config("collective_timing_flush_s")))
            self._wake.clear()
            self.flush()
            # quiesce instead of waking 4x/s forever in a process whose
            # collective life is over; add() restarts the thread
            with self._lock:
                done = not self._buf and not _col._manager._groups
                if done:
                    self._thread = None
            if done:
                return

    def flush(self) -> int:
        """Ship buffered records to their groups' rendezvous actors.
        Synchronously callable (tests; group teardown). Returns the
        number of records handed off or dropped."""
        with self._lock:
            buf, self._buf = self._buf, []
        if not buf:
            return 0
        by_group: dict[str, list] = {}
        for r in buf:
            by_group.setdefault(r["group"], []).append(r)
        from ray_tpu.util.collective import collective as _col

        for gname, recs in by_group.items():
            state = _col._manager._groups.get(gname)
            store = getattr(state, "store", None)
            if store is None:
                continue   # group destroyed / never had a rendezvous
            try:
                store.report_timings.remote(recs)
            except Exception:
                pass       # telemetry must never fail the data plane
        return len(buf)


_reporter = _TimingReporter()


def flush_timings():
    """Force-flush this process's buffered rank timings (tests)."""
    _reporter.flush()


# --------------------------------------------------------------- detection


def detect_stragglers(timings: list[dict], multiple: float | None = None,
                      min_lag_s: float | None = None):
    """Flag ranks whose arrival lag exceeds a configurable multiple of
    the group median.

    `timings`: one record per rank with at least {"rank", "start"}.
    A rank's lag is its op start time minus the earliest rank's start;
    rank r is flagged when ``lag_r > max(multiple * median(lags of the
    OTHER ranks), min_lag_s)`` (strictly greater). The leave-one-out
    median matters: an extreme straggler must not raise the bar it is
    judged against — with a plain group median a 2-rank group could
    never flag anything (the laggard's own lag IS half the median), and
    one huge lag in a small group masks itself. The floor keeps a tight
    group (median ~ 0) from flagging µs-scale jitter. Returns
    (stragglers, lags, median_lag) where stragglers is a list of
    (rank, lag_s) sorted by lag desc and median_lag is the full-group
    median (reported in the event for context).
    """
    from ray_tpu._private.config import get_config

    if multiple is None:
        multiple = float(get_config("collective_straggler_multiple"))
    if min_lag_s is None:
        min_lag_s = float(get_config("collective_straggler_min_lag_s"))
    starts = {int(r["rank"]): float(r["start"]) for r in timings}
    if len(starts) < 2:
        return [], {}, 0.0
    t0 = min(starts.values())
    lags = {rank: s - t0 for rank, s in starts.items()}
    median = statistics.median(lags.values())
    # leave-one-out medians from one sort: removing sorted index i
    # leaves m = n-1 values whose median is index math, not a re-sort
    pairs = sorted(lags.items(), key=lambda kv: kv[1])
    vals = [lag for _, lag in pairs]
    n = len(vals)
    m = n - 1

    def _median_excluding(i: int) -> float:
        def at(j: int) -> float:            # j-th of the remaining m
            return vals[j] if j < i else vals[j + 1]
        if m % 2:
            return at(m // 2)
        return 0.5 * (at(m // 2 - 1) + at(m // 2))

    stragglers = []
    for i, (rank, lag) in enumerate(pairs):
        if lag > max(multiple * _median_excluding(i), min_lag_s):
            stragglers.append((rank, lag))
    stragglers.sort(key=lambda p: -p[1])
    return stragglers, lags, median


class GroupTimingAggregator:
    """Lives inside a group's rendezvous actor: accumulates per-(seq)
    rank-timing records and, once every rank has reported a seq, runs
    the straggler detector and emits a COLLECTIVE_STRAGGLER cluster
    event (the actor's own event ring rides the normal events_snapshot
    fan-out into list_cluster_events). Bounded: at most
    ``_MAX_PENDING_SEQS`` incomplete seqs are kept (drop-oldest — a
    rank that never reports must not grow the table forever)."""

    def __init__(self, world_size: int):
        self.world_size = world_size
        self._pending: dict[int, dict[int, dict]] = {}
        self._order: collections.deque = collections.deque()
        # completed seqs (bounded): a duplicated/retried report for an
        # already-evaluated seq must be a no-op, not resurrect a slot
        # that can never complete again
        self._done: collections.deque = collections.deque()
        self._done_set: set = set()
        self._lock = threading.Lock()
        self.stragglers_found = 0

    def ingest(self, records: list[dict]):
        complete = []
        with self._lock:
            for r in records:
                seq = int(r["seq"])
                if seq in self._done_set:
                    continue
                slot = self._pending.get(seq)
                if slot is None:
                    slot = self._pending[seq] = {}
                    self._order.append(seq)
                    while len(self._order) > _MAX_PENDING_SEQS:
                        self._pending.pop(self._order.popleft(), None)
                slot[int(r["rank"])] = r
                if len(slot) == self.world_size:
                    self._pending.pop(seq, None)
                    if len(self._done) >= _MAX_PENDING_SEQS:
                        self._done_set.discard(self._done.popleft())
                    self._done.append(seq)
                    self._done_set.add(seq)
                    complete.append((seq, slot))
        for seq, slot in complete:
            self._evaluate(seq, slot)

    def _evaluate(self, seq: int, slot: dict[int, dict]):
        recs = list(slot.values())
        stragglers, lags, median = detect_stragglers(recs)
        if not stragglers:
            return
        self.stragglers_found += len(stragglers)
        group = recs[0].get("group")
        op = recs[0].get("op")
        # op_seq, not seq: the event ring reserves `seq` for its own
        # per-process dedup counter
        _events.record("COLLECTIVE_STRAGGLER", group=group, op=op,
                       op_seq=seq, ranks=[rank for rank, _ in stragglers],
                       lags_s={str(rank): round(lag, 6)
                               for rank, lag in stragglers},
                       median_lag_s=round(median, 6),
                       world_size=self.world_size)
        _tm.counter_inc("ray_tpu_collective_stragglers_total",
                        float(len(stragglers)),
                        tags={"group": str(group), "op": str(op)})
