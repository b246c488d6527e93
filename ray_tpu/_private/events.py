"""Bounded per-process structured event log for the runtime core.

Reference: Ray's task events + GCS cluster events (task state
transitions with per-state timestamps flow from workers through the
agent into the dashboard/state API; `ray list cluster-events`). Here
every process keeps a bounded ring of structured events; the state API
(`ray_tpu.experimental.state.api.list_cluster_events`) unions the
driver's ring with the GCS process's and every raylet's (which fans out
over its workers, like `rpc_metrics_snapshot`), dedups by
(node, pid, seq) and returns one time-ordered stream.

Event kinds recorded by the runtime:

- ``task_state``   — task lifecycle transitions with timestamps:
                     SUBMITTED (owner, at submit) → LEASE_GRANTED
                     (owner, at dispatch onto a leased worker) →
                     RUNNING (executor) → FINISHED/FAILED (executor or
                     owner), plus RESUBMITTED on dispatch failure /
                     worker death retry. `summarize_tasks()` derives the
                     queue/scheduling/execution latency breakdown from
                     these.
- ``actor_state``  — REGISTERED/ALIVE/RESTARTING/DEAD (GCS process).
- ``node_state``   — ALIVE/DEAD with reason (GCS process).
- ``retry_budget_exhausted`` — the process-wide retry budget drained
                     and a retry was refused (_private/retry.py).
- ``fault_injected`` — a fault-injection rule fired
                     (_private/fault_injection.py): action, method,
                     per-method call number.
- ``COLLECTIVE_STRAGGLER`` — ranks arrived at a collective op late
                     (group rendezvous actor, util/collective/
                     telemetry.py): group, op, seq, ranks, lags.
- ``COMPILE_BEGIN`` / ``COMPILE_END`` — an instrumented jitted
                     function hit a compile-cache miss
                     (parallel/compile_watch.py): fn, duration.
- ``train_step``   — a Train worker streamed a step report
                     (train/worker_group.py): rank, iteration, device
                     identity.
- ``train_group``  — a Train worker gang came up
                     (train/backend_executor.py): per-worker device
                     identities.
- ``GANG_FAILED`` / ``GANG_RESTARTED`` / ``train_gang_retry`` — elastic
                     gang fault tolerance (train/trainer.py): a gang
                     attempt failed (dead ranks, failure counts), a
                     rebuilt gang resumed from checkpoint, and the
                     per-retry backoff draw.
- ``COLLECTIVE_GROUP_POISONED`` — a collective group was poisoned on
                     member death (util/collective/collective.py):
                     group, dead ranks, reason, incarnation epoch.
- ``REPLICA_STARTED`` / ``REPLICA_DIED`` / ``REPLICA_DRAINED`` — Serve
                     replica lifecycle (serve/_private/controller.py):
                     deployment, replica_id; DIED carries the detection
                     source (``death_feed`` / ``health`` / ``init``),
                     DRAINED whether the drain completed gracefully.
- ``SERVE_SCALED``   — an autoscale decision applied after hysteresis
                     (controller): deployment, direction, from/to
                     replica counts, the demand signal.
- ``REQUEST_SHED``   — Serve admission control rejected a request
                     (serve/_private/router.py): deployment, queue
                     occupancy/capacity, the retry-after hint, and
                     whether replicas were draining (the hint then
                     reflects the grace window remaining).
- ``SERVE_APP_REGISTERED`` — a Serve app was deployed as a first-class
                     job-plane tenant (serve/_private/controller.py):
                     app, job, priority, quota.
- ``SERVE_CAPACITY_PLACED`` — a replica's capacity gang turned CREATED
                     in the job plane (controller): deployment,
                     replica_id, job, the spike-to-placed wait.
- ``SERVE_REPLICA_WARNED`` — a preempt_warning landed on a replica's
                     capacity gang (controller): deployment,
                     replica_id, job, reason (``preempted`` external /
                     ``scale_down`` self-requested), grace remaining —
                     the replica drains inside the window and routers
                     drop it from selection.
- ``STEP_REGRESSION`` — the step-anatomy rolling-baseline detector
                     fired (_private/step_anatomy.py): rank, step_id,
                     recent/baseline p50 step time, the knobbed
                     multiple.
- ``FLIGHT_RECORDER_DUMP`` — a black-box dump directory was written
                     (_private/flight_recorder.py): trigger reason,
                     dump path, number of processes captured.
- ``NODE_BATCH_DEAD`` — a coalesced node-death batch (>=
                     ``gcs_death_batch_min`` deaths inside the coalesce
                     window — a rack loss or seeded mass kill) was
                     swept and fanned out as ONE broadcast
                     (_private/gcs.py): node_ids, count, reasons.
- ``JOB_REGISTERED`` — a named job joined the multi-tenant scheduling
                     plane (_private/gcs.py): job, priority, quota.
- ``PREEMPTION_WARNED`` — a higher-priority placement group could not
                     place and the GCS picked this victim: pg_id, job,
                     the grace window, the preemptor — the Train plane
                     cuts a checkpoint inside the window
                     (_private/gcs.py).
- ``PREEMPTION_FIRED`` — the grace window elapsed and the victim's
                     bundles were reclaimed; the victim re-queued
                     PENDING to resume when capacity returns
                     (_private/gcs.py): pg_id, job, preemptor.
- ``PREEMPTION_CANCELED`` — the grace window elapsed but the preemptor
                     no longer needed the capacity (placed elsewhere,
                     removed, or now placeable as-is): the victim kept
                     its bundles (_private/gcs.py): pg_id, job,
                     preemptor.
- ``PIPELINE_GANG_STARTED`` — a multi-slice MPMD pipeline gang came up
                     (train/pipeline/trainer.py): group, stage count,
                     ranks per stage, microbatches, schedule, and the
                     per-stage slice placement reported by the
                     SPREAD_ACROSS_SLICES scheduler.
- ``STORE_LEAK``   — the memory-anatomy leak sweep classified a live
                     store object as orphaned
                     (_private/memory_anatomy.py): the full provenance
                     record (oid, category, nbytes, creator pid,
                     group/epoch/rank) plus the reason
                     (``owner_dead`` / ``group_destroyed`` /
                     ``epoch_stale``). Emitted once per object.
- ``PUBSUB_RESYNC`` — a long-poll subscriber detected a feed gap
                     (mailbox overflow / publisher GC) and reconverged
                     from the channel's state snapshot
                     (_private/pubsub.py): channels, seq floor,
                     per-subscriber resync count.
- ``CHECKPOINT_COMMITTED`` — rank 0 durably renamed a sharded-checkpoint
                     generation's MANIFEST.json after every rank acked
                     its shard write (train/sharded_checkpoint.py):
                     step, world, path, total shard bytes. Before this
                     event the generation does not exist as far as
                     restore is concerned.
- ``CHECKPOINT_QUARANTINED`` — restore-side verification renamed a
                     bad/torn generation out of sight and fell back to
                     the next older one: path, reason (``torn`` /
                     ``digest_mismatch`` / ``size_mismatch`` /
                     ``shard_missing`` / ``plan_mismatch``) and the
                     offending shard file when one is identifiable.
- ``CHECKPOINT_RESHARDED`` — a gang restored a generation saved at a
                     DIFFERENT world size, re-slicing the saved shards
                     onto the new shard map by index math over the
                     bucket plan: path, step, world_saved, world_now.

Design constraints match the metrics plane: recording is one lock +
deque append (no allocation beyond the event dict), the ring is bounded
(drop-oldest, counted), and ``RAY_TPU_INTERNAL_TELEMETRY=0`` turns the
whole plane off.
"""
from __future__ import annotations

import collections
import os
import sys
import threading
import time

# One kill-switch for the internal telemetry plane (shared with
# _private/telemetry.py): latency-critical deployments drop the
# per-event lock+append and the per-RPC histogram observe together.
ENABLED = os.environ.get("RAY_TPU_INTERNAL_TELEMETRY", "1") != "0"

_MAX_EVENTS = int(os.environ.get("RAY_TPU_EVENT_LOG_SIZE", "4096"))

TASK_STATES = ("SUBMITTED", "LEASE_GRANTED", "RUNNING", "FINISHED",
               "FAILED", "RESUBMITTED")

_lock = threading.Lock()
_events: collections.deque = collections.deque(maxlen=_MAX_EVENTS)
_seq = 0
_dropped = 0
# cached per process: workers are spawned (fresh interpreters), never forked
_PID = os.getpid()
_NODE = os.uname().nodename


def _role() -> str:
    """This process's cluster role, reusing the fault plane's tag (gcs /
    raylet / worker / driver) without importing it into the module graph."""
    fi = sys.modules.get("ray_tpu._private.fault_injection")
    if fi is None:
        return "driver"
    role = fi.get_role()
    return "driver" if role == "*" else role


def record(kind: str, **fields):
    """Append one structured event. Never raises; ~1µs when enabled.

    The envelope keys (ts/seq/pid/node/role/kind) are reserved and WIN
    over same-named caller fields: `seq` is the (node, pid, seq) dedup
    key `list_cluster_events` relies on — a caller shadowing it would
    make its events silently vanish as "duplicates" of unrelated ones
    (this bit the collective straggler events; carry domain sequence
    numbers under another name, e.g. ``op_seq``)."""
    global _seq, _dropped
    if not ENABLED:
        return
    with _lock:
        _seq += 1
        dropped = len(_events) == _events.maxlen
        if dropped:
            _dropped += 1
        _events.append({**fields,
                        "ts": time.time(), "seq": _seq, "pid": _PID,
                        "node": _NODE, "role": _role(), "kind": kind})
    if dropped:
        # rare (ring full) — counted into /metrics so silent loss of the
        # event stream's head is itself observable
        try:
            from ray_tpu._private import telemetry as _tm

            _tm.counter_inc("ray_tpu_events_dropped_total")
        except Exception:
            pass


def task_event(task_id, state: str, **extra):
    """Record one task state transition (`kind="task_state"`)."""
    if not ENABLED:
        return
    record("task_state",
           task_id=task_id.hex() if isinstance(task_id, bytes) else task_id,
           state=state, **extra)


def snapshot() -> list[dict]:
    """This process's events, oldest first (each a copy — callers and the
    RPC pickle path must not alias the live ring entries)."""
    with _lock:
        return [dict(e) for e in _events]


def clear():
    global _dropped
    with _lock:
        _events.clear()
        _dropped = 0


def stats() -> dict:
    with _lock:
        return {"recorded": _seq, "buffered": len(_events),
                "dropped": _dropped, "capacity": _events.maxlen}
