"""Core worker — the in-process runtime linked into every worker and driver.

Analog of the reference's CoreWorker
(/root/reference/src/ray/core_worker/core_worker.h:227): task submission with
lease-based scheduling and worker pipelining (direct_task_transport.h:57),
actor creation/submission with per-handle ordering
(direct_actor_task_submitter.h), Put/Get against the node's shared-memory
store plus an in-process memory store for small results
(store_provider/memory_store/), and the execution loop on the worker side
(core_worker.cc:2188 RunTaskExecutionLoop → here an RPC server receiving
pushed tasks).
"""
from __future__ import annotations

import collections
import functools
import itertools
import hashlib
import os
import queue
import threading
import time
import traceback
import uuid
from concurrent.futures import Future as PyFuture

from ray_tpu import exceptions as exc
from ray_tpu._private import events as _events
from ray_tpu._private import fault_injection as _fi
from ray_tpu._private import memory_anatomy as _ma
from ray_tpu._private import profiling as _profiling
from ray_tpu._private import serialization as ser
from ray_tpu._private.object_ref import ObjectRef, ReferenceCounter
from ray_tpu._private.protocol import ConnectionLost, RpcClient, RpcServer
from ray_tpu._private.store_client import StoreClient

# Results below this size return inline in the task reply and live in the
# owner's memory store (reference: small returns go to the owner's in-process
# store, core_worker.cc "return inlined"); larger go to the shm store.
INLINE_RESULT_LIMIT = 100 * 1024
# Max tasks pipelined onto one leased worker before requesting another lease
# (reference pipelines to leased workers in OnWorkerIdle,
# direct_task_transport.cc:174).
def _pipeline_depth() -> int:
    from ray_tpu._private.config import get_config

    return int(get_config("max_tasks_in_flight_per_worker"))


def _lease_soft_cap(worker=None) -> int:
    """Soft bound on leases per scheduling key. Scales with CLUSTER CPU
    capacity (reference: per-node worker_pool soft limits sum to cluster
    capacity), not this process's core count — a laptop driver submitting
    to a 100-core cluster must not throttle it. Cached with a TTL on the
    worker; config `lease_soft_cap` / env RAY_TPU_LEASE_SOFT_CAP
    overrides (0 = auto)."""
    from ray_tpu._private.config import get_config

    configured = int(get_config("lease_soft_cap"))
    if configured > 0:
        return configured
    cluster = worker._cluster_cpu_total() if worker is not None else 0
    return max(4, 2 * (os.cpu_count() or 1), int(2 * cluster))


class _PendingValue:
    __slots__ = ("event", "data", "error")

    def __init__(self):
        self.event = threading.Event()
        self.data = None


class FifoSemaphore:
    """Counting semaphore granting slots in enqueue order.

    threading.Semaphore wakes waiters in unspecified order, which would let
    actor call m3 run before m2 even at max_concurrency=1; grant order here
    follows enqueue order, which the per-caller seq gate makes equal to
    submission order (reference: actor_scheduling_queue.h runs client-side
    sequence numbers in order; concurrency groups bound parallelism)."""

    def __init__(self, n: int):
        self._n = max(1, n)
        self._lock = threading.Lock()
        self._active = 0
        self._waiters: "collections.deque[threading.Event]" = \
            collections.deque()

    def enqueue(self):
        """Reserve a place in line without blocking. Returns a ticket to pass
        to wait(); None means the slot was granted immediately."""
        with self._lock:
            if self._active < self._n and not self._waiters:
                self._active += 1
                return None
            ev = threading.Event()
            self._waiters.append(ev)
            return ev

    def wait(self, ticket):
        if ticket is not None:
            ticket.wait()

    def release(self):
        with self._lock:
            if self._waiters:
                # hand the slot to the next in line (active count unchanged)
                self._waiters.popleft().set()
            else:
                self._active -= 1

    def cancel(self, ticket):
        """Back out of the line (task aborted before running)."""
        if ticket is None:
            self.release()
            return
        with self._lock:
            try:
                self._waiters.remove(ticket)
                return
            except ValueError:
                pass  # already granted by a release() — give the slot back
        self.release()


class MemoryStore:
    """Owner-side store for small/inlined results (futures until resolved)."""

    def __init__(self):
        self._values: dict[bytes, _PendingValue] = {}
        self._lock = threading.Lock()

    def entry(self, object_id: bytes) -> _PendingValue:
        with self._lock:
            entry = self._values.get(object_id)
            if entry is None:
                entry = _PendingValue()
                self._values[object_id] = entry
            return entry

    def put(self, object_id: bytes, data: bytes):
        entry = self.entry(object_id)   # ONE lock round, not two
        entry.data = data
        entry.event.set()

    def get_nowait(self, object_id: bytes):
        with self._lock:
            entry = self._values.get(object_id)
        if entry is not None and entry.event.is_set():
            return entry.data
        return None

    def contains_resolved(self, object_id: bytes) -> bool:
        return self.get_nowait(object_id) is not None

    def free(self, object_id: bytes):
        with self._lock:
            self._values.pop(object_id, None)

    def __len__(self):
        return len(self._values)


def _derive_item_id(gen_id: bytes, index: int) -> bytes:
    """Deterministic id for item `index` of a dynamic-returns stream:
    re-executing the producer (lineage reconstruction) regenerates the
    same ids, so existing borrowed refs resolve against the new run."""
    return hashlib.blake2b(gen_id + index.to_bytes(8, "big"),
                           digest_size=16).digest()


class _GenStream:
    """Owner-side record of one dynamic-returns task's item stream.

    The executor announces each yielded item as it is produced
    (rpc_generator_item); the final task reply carries the item count
    (success) or the error payload. Iterators (_gen_next) wait here.
    Reference: the streaming-generator return path in
    python/ray/_raylet.pyx:168 + core_worker task_manager's
    dynamic_return_ids.
    """

    __slots__ = ("items", "total", "error", "cond", "closed")

    def __init__(self):
        self.items: dict[int, bytes] = {}   # index -> object id
        self.total: int | None = None       # known once the task finishes
        self.error: bytes | None = None     # serialize_error payload
        self.closed = False                 # consumer closed early
        self.cond = threading.Condition()

    def add(self, index: int, rid: bytes):
        with self.cond:
            self.items[index] = rid
            self.cond.notify_all()

    def finish(self, total: int):
        with self.cond:
            if self.total is None:
                self.total = total
            self.cond.notify_all()

    def fail(self, error_data: bytes):
        with self.cond:
            if self.error is None:
                self.error = error_data
            self.cond.notify_all()


class _LeasedWorker:
    def __init__(self, grant: dict, client: RpcClient):
        self.lease_id = grant["lease_id"]
        self.worker_id = grant["worker_id"]
        self.addr = tuple(grant["worker_addr"])
        self.node_id = grant["node_id"]
        self.client = client
        self.in_flight = 0
        self.dead = False


class _SchedulingKeyQueue:
    """One background submitter per (function, resources, strategy): acquires
    leases, pipelines tasks onto them, retries on worker death."""

    def __init__(self, worker: "CoreWorker", key, resources: dict,
                 strategy: dict | None):
        self.worker = worker
        self.key = key
        self.resources = resources
        self.strategy = strategy
        self.tasks: queue.Queue = queue.Queue()
        self.leased: list[_LeasedWorker] = []
        self._lock = threading.Lock()
        self._wakeup = threading.Event()
        self._lease_pending = False       # one in-flight lease request max
        self._dispatching = False         # dispatch thread holds a popped spec
        self._lease_error: BaseException | None = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=f"submit-{key[0][:8].hex() if isinstance(key[0], bytes) else key[0]}")
        self._thread.start()

    def submit(self, spec: dict):
        # Fast path: a leased worker with a free pipeline slot takes the
        # push straight from the submitting thread — no dispatch-thread
        # handoff (queue put + wake + get costs ~50µs of the sync-task
        # budget on the 1-core box). Fairness: the shortcut only fires
        # when nothing is waiting in the queue AND the dispatch thread is
        # not holding a popped spec it is still trying to place (that
        # spec is invisible to qsize(); without the flag a stream of
        # fast-path submits could starve it of freed slots).
        if self.tasks.qsize() == 0 and not self._dispatching \
                and not spec.get("_cancelled"):
            lw = self._pick_worker()
            if lw is not None:
                self._last_dispatch = time.monotonic()
                if self._push(lw, spec):
                    return
        self.tasks.put(spec)
        self._wakeup.set()

    def _run(self):
        """Dispatch loop. NEVER blocks on lease acquisition — a granted lease
        can only be returned from this loop, so blocking here while leases
        idle would deadlock the raylet's resource accounting (the reference
        has the same constraint: lease requests are async callbacks in
        direct_task_transport.cc, dispatch happens in OnWorkerIdle)."""
        while not self.worker.stopped:
            try:
                spec = self.tasks.get(timeout=1.0)
            except queue.Empty:
                self._maybe_return_leases()
                continue
            self._dispatching = True
            dispatched = False
            while not dispatched and not self.worker.stopped:
                if spec.get("_cancelled"):
                    self.worker._fail_task(spec, exc.TaskCancelledError(
                        spec.get("task_desc", "task")))
                    dispatched = True
                    continue
                lw = self._pick_worker()
                if lw is not None:
                    self._last_dispatch = time.monotonic()
                    dispatched = self._push(lw, spec)
                    continue
                if not self._may_grow():
                    # at the soft lease cap with live dispatches — wait for
                    # an in-flight slot instead of growing the fleet
                    self._wakeup.wait(timeout=0.05)
                    self._wakeup.clear()
                    continue
                err = self._maybe_request_lease()
                if err is not None:
                    self.worker._fail_task(spec, err)
                    # the same error condemns everything queued behind it
                    while True:
                        try:
                            pending = self.tasks.get_nowait()
                        except queue.Empty:
                            break
                        self.worker._fail_task(pending, err)
                    dispatched = True
                    continue
                self._wakeup.wait(timeout=0.05)
                self._wakeup.clear()
            self._dispatching = False

    def _pick_worker(self):
        # Depth-1 unless there's real QUEUE pressure: with a short queue,
        # distinct leases maximize cluster parallelism; with a long queue,
        # pipelining depth 2 hides push RTT (execution on the worker is
        # serial either way — a lease represents ONE task's worth of
        # resources). Deliberately NOT counting in-flight work as
        # pressure: queue depth signals the caller is out-running
        # dispatch (pipelining helps), while in-flight-only signals work
        # that may be BLOCKED — stacking a task behind a blocked one on a
        # serial worker deadlocks rendezvous patterns (4 tasks gating on
        # each other inside an actor, test_runtime_fixes). The fleet
        # ratchet this used to cause is bounded by _may_grow instead.
        depth = _pipeline_depth() if self.tasks.qsize() > 2 else 1
        with self._lock:
            alive = [lw for lw in self.leased if not lw.dead]
            self.leased = alive
            candidates = [lw for lw in alive if lw.in_flight < depth]
            if candidates:
                lw = min(candidates, key=lambda w: w.in_flight)
                lw.in_flight += 1
                return lw
            return None

    def _may_grow(self) -> bool:
        """Soft cap on leases per scheduling key: beyond it, prefer waiting
        for an in-flight slot over spawning another worker — one worker
        process per queued zero-cpu task thrashes small hosts (observed:
        18 workers on 1 core). The cap is SOFT for liveness: if nothing
        has dispatched for a second (e.g. every leased worker is blocked
        inside a nested `get`), growth resumes — the reference keeps the
        same escape via worker-pool soft limits + blocked-on-get CPU
        release (worker_pool.h num_workers_soft_limit)."""
        with self._lock:
            n = len(self.leased)
        if n < _lease_soft_cap(self.worker):
            return True
        return time.monotonic() - getattr(self, "_last_dispatch", 0.0) > 1.0

    def _maybe_request_lease(self):
        """Kick off an async lease request if none is in flight. Returns a
        terminal error if the last request failed, else None."""
        with self._lock:
            if self._lease_error is not None:
                err, self._lease_error = self._lease_error, None
                return err
            if self._lease_pending:
                return None
            self._lease_pending = True
        threading.Thread(target=self._lease_request_thread,
                         daemon=True).start()
        return None

    def _lease_request_thread(self):
        try:
            grant = self.worker.request_lease(self.resources, self.strategy)
            client = RpcClient(tuple(grant["worker_addr"]), timeout=None)
            lw = _LeasedWorker(grant, client)
            self._lease_timeouts = 0
            self._lease_conn_failures = 0
            with self._lock:
                self.leased.append(lw)
        except ConnectionLost:
            # Transient: the raylet we were talking to (or spilled to) died
            # mid-request. The cluster view heals within a heartbeat —
            # back off and let the dispatch loop re-request instead of
            # condemning every queued task (chaos-test finding). Pause
            # shape comes from the unified policy (full jitter over
            # consecutive failures) so a fleet of queues doesn't
            # re-request in lockstep.
            from ray_tpu._private.retry import RetryPolicy

            self._lease_timeouts = 0
            self._lease_conn_failures = getattr(
                self, "_lease_conn_failures", 0) + 1
            time.sleep(RetryPolicy(base_backoff_s=0.2, max_backoff_s=2.0)
                       .backoff(self._lease_conn_failures))
        except TimeoutError as e:
            # A full 300s raylet queue timeout is retried (capacity may be
            # coming: autoscaler, chaos replacement) — but not forever: two
            # consecutive exhausted waits mean the demand is going nowhere
            # (e.g. a typo'd resource name) and the tasks should fail
            # loudly rather than hang silently.
            self._lease_timeouts = getattr(self, "_lease_timeouts", 0) + 1
            if self._lease_timeouts >= 2:
                with self._lock:
                    self._lease_error = exc.RayError(
                        f"no capacity for {self.resources} after "
                        f"{self._lease_timeouts} full lease-queue waits: "
                        f"{e}")
        except Exception as e:  # noqa: BLE001
            with self._lock:
                self._lease_error = e
        finally:
            with self._lock:
                self._lease_pending = False
            self._wakeup.set()

    def _push(self, lw: _LeasedWorker, spec: dict) -> bool:
        # LEASE_GRANTED marks the end of this task's queue wait: it is
        # leaving the scheduling queue for a leased worker's pipeline.
        _events.task_event(spec["task_id"], "LEASE_GRANTED",
                           node_id=lw.node_id, worker_id=lw.worker_id,
                           desc=spec.get("task_desc"))
        try:
            fut = lw.client.call_async("push_task", spec=self.worker._strip_spec(spec))
        except ConnectionLost:
            # The task never left this process — the lease was stale (its
            # worker died with a removed node). Requeue WITHOUT charging
            # retries_left: the retry budget is for attempts that may have
            # executed (side effects), not for dispatch failures. Charging
            # here made a task bounce across N stale leases after a node
            # death and exhaust its budget without ever running (chaos
            # suite). Reference: lease invalidation re-requests, it does
            # not count as a task attempt.
            with self._lock:
                lw.dead = True
                lw.in_flight -= 1
            _events.task_event(spec["task_id"], "RESUBMITTED",
                               reason="dispatch connection lost",
                               desc=spec.get("task_desc"))
            self.submit(spec)
            return True
        # Reply lands as a callback on the client's reader/pump thread —
        # no parked thread per in-flight task (the reference's reply path
        # is a ClientCallManager completion-queue callback the same way).
        # _handle_task_reply/_task_done are non-blocking; the death path
        # may make short RPCs on OTHER connections, which is safe there.
        fut.add_done_callback(lambda value: self._on_reply(lw, spec, value))
        return True

    def _on_reply(self, lw: _LeasedWorker, spec: dict, value):
        from ray_tpu._private.protocol import _RemoteError

        if isinstance(value, _RemoteError):
            if isinstance(value.exc, ConnectionLost):
                self._on_worker_death(lw, spec)
            else:
                self.worker._fail_task(spec, value.exc)
                self._task_done(lw)
            return
        self.worker._handle_task_reply(spec, value, lw.node_id)
        self._task_done(lw)

    def _task_done(self, lw: _LeasedWorker):
        with self._lock:
            lw.in_flight -= 1
        self._wakeup.set()

    def _on_worker_death(self, lw: _LeasedWorker, spec: dict):
        with self._lock:
            lw.dead = True
        if spec.get("_cancelled"):
            self.worker._fail_task(spec, exc.TaskCancelledError(
                spec.get("task_desc", "task")))
            return
        retries = spec.get("retries_left", 0)
        if retries > 0:
            spec["retries_left"] = retries - 1
            _events.task_event(spec["task_id"], "RESUBMITTED",
                               reason="worker died",
                               retries_left=spec["retries_left"],
                               desc=spec.get("task_desc"))
            self.submit(spec)
        else:
            self.worker._fail_task(spec, self.worker._worker_death_error(
                lw.worker_id))

    def _maybe_return_leases(self):
        """Return idle leases so the raylet can free resources."""
        to_return = []
        with self._lock:
            keep = []
            for lw in self.leased:
                if lw.in_flight == 0 and self.tasks.empty():
                    to_return.append(lw)
                else:
                    keep.append(lw)
            self.leased = keep
        for lw in to_return:
            self.worker.return_lease(lw)


class _ActorQueue:
    """Client-side submission queue for one actor handle: preserves order,
    handles RESTARTING/DEAD transitions (reference:
    direct_actor_task_submitter.h sequential submit queue)."""

    def __init__(self, worker: "CoreWorker", actor_id: bytes, meta: dict):
        self.worker = worker
        self.actor_id = actor_id
        self.meta = meta
        self.seq = 0
        self.epoch = 0   # bumped on reconnect; scopes seq for the receiver
        self.client: RpcClient | None = None
        self.addr = None
        self._lock = threading.RLock()

    def _on_connection_lost(self):
        with self._lock:
            self.client = None
            self.epoch += 1
            self.seq = 0

    def _connect(self, timeout: float = 60.0):
        """Resolve the actor address (waiting through RESTARTING) and open a
        connection.

        MUST NOT hold self._lock while polling: assign_seq() runs on the
        caller's thread for every handle.method.remote(), and a submit
        thread camped on the lock here (up to 60s while the actor is
        pending) would block the caller — in Tune this deadlocked the
        driver's poll loop against a queued trial actor whose resources
        only free when the poll loop runs. The lock guards only the client
        field handoff.

        A PENDING_CREATION actor does not count against the timeout: like
        the reference (tasks buffer until the actor schedules,
        direct_actor_task_submitter.h), creation may legitimately wait
        behind resource availability for arbitrarily long."""
        with self._lock:
            if self.client is not None:
                if not self.client.closed:
                    return self.client
                # stale connection: new epoch so the replacement actor's
                # receiver doesn't wait for seqs lost with the old process
                self._on_connection_lost()
        deadline = time.time() + timeout
        poll = 0.05
        while True:
            synthetic = False
            try:
                info = self.worker.gcs.call("get_actor",
                                            actor_id=self.actor_id)
            except TimeoutError:
                # GCS overloaded (e.g. hundreds of actors creating at
                # once): a transient RPC timeout is not a verdict on the
                # actor — back off and re-poll instead of killing this
                # submit thread (which would strand its queued call).
                # SYNTHETIC pending: must not extend the deadline, or a
                # permanently-dead GCS would spin this thread forever.
                info = {"state": "PENDING_CREATION", "addr": None}
                synthetic = True
            if info is None:
                raise exc.ActorDiedError(self.actor_id.hex(),
                                         "actor not found")
            if info["state"] == "DEAD":
                raise exc.ActorDiedError(self.actor_id.hex(),
                                         info.get("death_cause") or "dead")
            if info["state"] == "ALIVE" and info["addr"]:
                try:
                    c = RpcClient(tuple(info["addr"]), timeout=None)
                except ConnectionLost:
                    c = None  # raced a death; loop
                if c is not None:
                    with self._lock:
                        if self.client is not None and \
                                not self.client.closed:
                            c.close()  # another submit thread won the race
                            return self.client
                        self.client = c
                        self.addr = tuple(info["addr"])
                        return c
            if info["state"] == "PENDING_CREATION" and not synthetic:
                deadline = time.time() + timeout   # not a failure: queued
            elif time.time() > deadline:
                raise exc.GetTimeoutError(
                    f"actor {self.actor_id.hex()} not ready in {timeout}s")
            time.sleep(poll)
            # with N pending handles this loop is N pollers against one
            # GCS; constant 50 ms polling melted it at N=400 — back off
            from ray_tpu._private.config import get_config

            poll = min(poll * 1.5,
                       float(get_config("actor_resolution_poll_max_s")))

    def assign_seq(self, spec: dict):
        """Must be called in program submission order (caller thread)."""
        with self._lock:
            spec["seq"] = self.seq
            spec["caller_epoch"] = self.epoch
            self.seq += 1

    def submit(self, spec: dict):
        max_retries = spec.get("retries_left", 0)
        if "seq" not in spec:
            self.assign_seq(spec)
        attempt = 0
        while True:
            try:
                client = self._connect()
                with self._lock:
                    if spec.get("caller_epoch") != self.epoch:
                        spec.pop("seq", None)
                        self.assign_seq(spec)
                fut = client.call_async("push_task",
                                        spec=self.worker._strip_spec(spec))
            except (exc.RayTpuError, ValueError, RuntimeError) as e:
                # actor resolved to DEAD / never became ready — resolve the
                # return futures instead of letting this thread die silently
                self.worker._fail_task(spec, e)
                return
            except ConnectionLost:
                self._on_connection_lost()
                spec.pop("seq", None)
                self.assign_seq(spec)
                attempt += 1
                if attempt > max_retries + 1:
                    self.worker._fail_task(spec, exc.ActorUnavailableError(
                        f"actor {self.actor_id.hex()} unavailable"))
                    return
                continue
            # reply runs as a reader/pump-thread callback (no parked thread
            # per in-flight call); the rare failure paths hop to fresh
            # threads because they block (GCS lookup, resubmit)
            fut.add_done_callback(lambda value: self._on_reply(spec, value))
            return

    def _on_reply(self, spec, value):
        from ray_tpu._private.protocol import _RemoteError

        if isinstance(value, _RemoteError):
            if isinstance(value.exc, ConnectionLost):
                self._on_connection_lost()
                retries = spec.get("retries_left", 0)
                if retries > 0:
                    spec["retries_left"] = retries - 1
                    spec.pop("seq", None)   # re-sequenced in the new epoch
                    threading.Thread(target=self.submit, args=(spec,),
                                     daemon=True).start()
                else:
                    threading.Thread(target=self._fail_dead, args=(spec,),
                                     daemon=True).start()
            else:
                self.worker._fail_task(spec, value.exc)
            return
        self.worker._handle_task_reply(spec, value, None)

    def _fail_dead(self, spec):
        # Distinguish died vs restarting for the error type.
        try:
            info = self.worker.gcs.call("get_actor",
                                        actor_id=self.actor_id)
        except ConnectionLost:
            info = None
        reason = (info or {}).get("death_cause") or "connection lost"
        self.worker._fail_task(
            spec, exc.ActorDiedError(self.actor_id.hex(), reason))


# sentinel: a pooled data-plane socket died mid-request — retry once fresh
_RETRY_FRESH = object()


class CoreWorker:
    """One per process (driver or worker)."""

    def __init__(self, gcs_addr, raylet_addr, mode: str,
                 store_name: str | None = None, spill_dir: str | None = None,
                 worker_id: str | None = None, job_id: int | None = None):
        self.mode = mode                      # "driver" | "worker"
        # tag the process for role-scoped fault-injection rules (weak:
        # in-process test clusters keep the subprocess entrypoint's tag)
        from ray_tpu._private import fault_injection

        fault_injection.set_role(mode, weak=True)
        self.worker_id = worker_id or uuid.uuid4().hex[:16]
        self.stopped = False
        # id mint: random 8-byte process prefix + counter. Ids need
        # uniqueness, not unpredictability, and os.urandom is a syscall
        # (~16µs) paid twice per task on the submit hot path.
        self._id_prefix = os.urandom(8)
        self._id_counter = itertools.count(1)
        self.memory_store = MemoryStore()
        self.reference_counter = ReferenceCounter(
            on_zero=self._on_local_refs_zero)
        self._owned: set[bytes] = set()      # ids this process owns
        self._arg_pins: dict[bytes, int] = {}  # in-flight task-arg pins
        self._deferred_free: set[bytes] = set()
        self._actor_concurrency = FifoSemaphore(1)
        self._func_cache: dict[bytes, object] = {}
        self._sched_queues: dict[tuple, _SchedulingKeyQueue] = {}
        self._actor_queues: dict[bytes, _ActorQueue] = {}
        self._task_futures: dict[bytes, PyFuture] = {}
        self._ref_to_task: dict[bytes, tuple] = {}  # rid -> (spec, queue)
        self._gen_streams: dict[bytes, _GenStream] = {}  # gen_id -> stream
        # rid -> (frame bytes, inlinable?) for small resolved args
        # (invalidated on ref-zero with the other per-object state)
        self._inline_frame_cache: dict[bytes, tuple] = {}
        # executor-side twin: rid -> deserialized value for inlined arg
        # frames. Only IMMUTABLE values enter (numpy arrays are marked
        # read-only first — the store's own zero-copy semantics), so
        # sharing one object across tasks is safe. Objects are immutable
        # by id, so entries never go stale; a size cap bounds memory.
        self._inlined_value_cache: dict[bytes, object] = {}
        # Lineage for object reconstruction (reference:
        # core_worker/object_recovery_manager.h:30 + task_manager.h:93-110
        # lineage pinning): completed normal-task specs are retained, keyed
        # by task_id, while any of their return objects is still referenced,
        # so a sealed-then-lost object can be recomputed by re-executing its
        # creating task. Arg pins are held for the lineage's lifetime.
        self._lineage_specs: dict[bytes, tuple] = {}   # task_id -> (spec, q)
        self._lineage_index: dict[bytes, bytes] = {}   # rid -> task_id
        self._lineage_live: dict[bytes, int] = {}      # task_id -> live rids
        self._lineage_bytes = 0
        self._lineage_order: collections.deque = collections.deque()
        # PullManager-lite admission control (reference: pull_manager.h:48):
        # bounds the total bytes of concurrently in-flight remote pulls.
        self._pull_lock = threading.Condition()
        self._pull_inflight_bytes = 0
        self._lock = threading.RLock()
        # __del__-driven frees are deferred to this queue (GC-reentrancy
        # safety — see _on_local_refs_zero)
        self._free_queue: queue.SimpleQueue = queue.SimpleQueue()
        self._free_thread = threading.Thread(
            target=self._free_loop, daemon=True, name="ref-reaper")
        self._free_thread.start()

        # Actor-side state (populated by become_actor)
        self.actor_id: bytes | None = None
        self._actor_instance = None
        self._actor_spec = None
        self._exec_queue: queue.Queue | None = None
        self._exec_threads: list[threading.Thread] = []
        self._async_loop = None
        self._cancelled: set[bytes] = set()
        self._current_task_id = None
        self._current_task_thread = None
        self._next_seq_to_run: dict[str, int] = {}
        self._seq_cond = threading.Condition()
        self._col_mailbox: dict[tuple, object] = {}
        self._col_cond = threading.Condition()
        # gang fault tolerance (see col_set_epoch / col_poison_local):
        # group -> current incarnation epoch, and group -> poison record
        self._col_epochs: dict[str, int] = {}
        self._col_poison: dict[str, tuple[tuple, str]] = {}
        self._ready = threading.Event()
        # Normal tasks execute serially: the lease under which tasks are
        # pushed accounts for exactly one task's resources at a time
        # (pipelined pushes queue here, hiding RTT, not stacking execution).
        self._normal_exec_lock = threading.Lock()
        # main-thread task loop (serve_task_loop) plumbing
        self._main_jobs: queue.Queue = queue.Queue()
        self._main_loop_running = False
        self._main_loop_started = threading.Event()
        # pooled connections to object owners (borrowed-value fetches)
        self._owner_clients: dict[tuple, RpcClient] = {}
        self._owner_client_lock = threading.Lock()

        # Connect out only after all execution state exists: registering with
        # the raylet makes us leasable, and a task can be pushed the moment
        # that happens.
        # Self-healing: GCS table ops are idempotent, so calls retry
        # across a GCS restart instead of surfacing ConnectionLost to
        # the driver (reference: gcs_rpc_client.h reconnection)
        from ray_tpu._private.protocol import ReconnectingRpcClient

        self.gcs = ReconnectingRpcClient(tuple(gcs_addr),
                                         on_push=self._on_gcs_push)
        self._server = RpcServer(self).start()
        self.addr = self._server.addr
        self.raylet = RpcClient(tuple(raylet_addr), timeout=None)
        reg = self.raylet.call("register_worker", worker_id=self.worker_id,
                               addr=self.addr, pid=os.getpid())
        self.node_id = reg["node_id"]
        # the raylet's `worker_spawn` span, for this process's
        # `worker_boot` (None: not spawned by the raylet, a driver)
        self.spawn_span = reg.get("spawn_span")
        # Owner-based object directory (reference:
        # src/ray/object_manager/ownership_based_object_directory.h:1 — the
        # OWNER of an object tracks which nodes hold copies; borrowers and
        # the owner itself resolve locations here, with ZERO GCS round
        # trips on the pull path). _my_node is the snapshot shape handed to
        # owners when this node announces a copy.
        self._my_node = reg.get("node") or {"NodeID": self.node_id}
        self._dir_lock = threading.Lock()
        self._obj_locations: dict[bytes, dict[str, dict]] = {}
        self._obj_sizes: dict[bytes, int] = {}
        self.store = StoreClient(store_name or reg["store_name"],
                                 spill_dir=spill_dir or reg["spill_dir"])
        # provenance leak sweep over this process's store traffic
        # (memory_anatomy; no-op under RAY_TPU_INTERNAL_TELEMETRY=0)
        _ma.start_periodic_sweep(self)
        self.job_id = job_id if job_id is not None else (
            self.gcs.call("next_job_id") if mode == "driver" else 0)
        self._ready.set()

    # ------------------------------------------------------------------ utils

    def _new_id(self) -> bytes:
        """16-byte unique id (process-random prefix + counter) — the id
        mint for tasks/objects/actors; see __init__ for why not urandom."""
        return self._id_prefix + next(self._id_counter).to_bytes(8, "big")

    def _on_gcs_push(self, payload):
        pass  # subscriptions are registered lazily where needed

    def _strip_spec(self, spec: dict) -> dict:
        for k in spec:
            if k[0] == "_":
                return {k: v for k, v in spec.items()
                        if not k.startswith("_")}
        return spec   # nothing local: ship as-is (no dict rebuild)

    def _cluster_cpu_total(self) -> float:
        """Sum of CPU across alive nodes, cached for 10 s (feeds the
        per-key lease soft cap — growth decisions tolerate staleness)."""
        now = time.monotonic()
        cached = getattr(self, "_cluster_cpu_cache", None)
        if cached is not None and now - cached[0] < 10.0:
            return cached[1]
        total = 0.0
        try:
            for n in self.gcs.call("get_nodes", timeout=5.0):
                if n.get("Alive"):
                    total += float(n.get("Resources", {}).get("CPU", 0))
        except Exception:
            if cached is not None:
                return cached[1]
        self._cluster_cpu_cache = (now, total)
        return total

    # -------------------------------------------------------- runtime envs

    def _normalize_runtime_env(self, runtime_env: dict | None):
        """Driver-side normalization: local paths (working_dir, py_modules
        dirs, pip sdist dirs/wheel files) are packaged and uploaded to GCS
        KV once, so the spec carries only content keys that any node can
        materialize (reference: runtime_env/packaging.py). Without this,
        a spec naming /home/me/mylib would only work on nodes sharing the
        driver's filesystem. Uploads are content-addressed AND memoized
        per local path for 10 s, so a submit loop doesn't re-zip the tree
        per task."""
        if not runtime_env:
            return None
        runtime_env = dict(runtime_env)
        wd = runtime_env.get("working_dir")
        if wd and not wd.startswith("pkg-"):
            runtime_env["working_dir"] = self._upload_env_path(wd)
        if runtime_env.get("py_modules"):
            # keep_name: a py_module's directory name IS its import name
            runtime_env["py_modules"] = [
                self._upload_env_path(m, keep_name=True)
                if os.path.exists(str(m)) else m
                for m in runtime_env["py_modules"]]
        if runtime_env.get("pip"):
            runtime_env["pip"] = [
                self._upload_env_path(r) if os.path.exists(str(r)) else r
                for r in runtime_env["pip"]]
        return runtime_env

    def _upload_env_path(self, path: str, keep_name: bool = False) -> str:
        path = os.path.abspath(str(path))
        cache = getattr(self, "_env_upload_cache", None)
        if cache is None:
            cache = self._env_upload_cache = {}
        hit = cache.get((path, keep_name))
        if hit is not None and time.monotonic() - hit[0] < 10.0:
            return hit[1]
        if os.path.isdir(path):
            from ray_tpu._private.runtime_env import upload_working_dir

            key = upload_working_dir(self.gcs.call, path)
            if keep_name:
                key = f"{key}/{os.path.basename(path)}"
        else:
            with open(path, "rb") as f:
                data = f.read()
            key = "blob-" + hashlib.sha256(data).hexdigest()[:24]
            if self.gcs.call("kv_get", ns="packages",
                             key=key.encode()) is None:
                self.gcs.call("kv_put", ns="packages", key=key.encode(),
                              value=data)
            key = f"{key}/{os.path.basename(path)}"
        cache[(path, keep_name)] = (time.monotonic(), key)
        return key

    def _apply_runtime_env(self, runtime_env: dict | None):
        """Make `runtime_env` current in THIS process before running its
        task: pip/py_modules site dirs prepend sys.path, env_vars overlay
        os.environ, working_dir materializes and becomes cwd. A worker
        keeps its env between tasks (the scheduling key separates envs,
        so swaps happen only when the raylet reuses an idle worker across
        keys); swapping reverts the previous overlay (incl. cwd) first.
        Failure-safe: all fallible resolution happens BEFORE any state
        mutates, and a failed apply leaves the worker env-less (key None)
        so the next task re-applies from scratch rather than trusting a
        half-applied overlay. Design delta vs the reference's
        dedicated-worker-per-env: modules already imported from a
        previous env stay cached in sys.modules."""
        import sys as _sys

        key = _freeze(runtime_env)
        if key == getattr(self, "_env_applied_key", None):
            return
        # ---- resolve the NEW env fully before touching process state
        paths, uri, cache = [], None, None
        runtime_env = runtime_env or {}
        pip = runtime_env.get("pip")
        py_modules = runtime_env.get("py_modules")
        if pip or py_modules:
            from ray_tpu._private.runtime_env_pip import node_env_cache

            cache = node_env_cache()
            pip = [self._localize_env_entry(e) for e in (pip or [])]
            py_modules = [self._localize_env_entry(m)
                          for m in (py_modules or [])]
            info = cache.get_or_create(pip=pip, py_modules=py_modules)
            uri = info["uri"]
            paths.extend(info["site_dirs"])
        wd = runtime_env.get("working_dir")
        wd_path = None
        if wd:
            wd_path = self._localize_env_entry(wd)
            paths.append(wd_path)
        # ---- point of no return: revert old overlay, install new
        for p in getattr(self, "_env_paths", ()):
            try:
                _sys.path.remove(p)
            except ValueError:
                pass
        for k, old in getattr(self, "_env_vars_prev", {}).items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old
        if getattr(self, "_env_orig_cwd", None):
            try:
                os.chdir(self._env_orig_cwd)
            except OSError:
                pass
            self._env_orig_cwd = None
        prev_uri = getattr(self, "_env_uri", None)
        self._env_paths = ()
        self._env_vars_prev = {}
        self._env_uri = None
        self._env_applied_key = None
        vars_prev = {}
        for k, v in (runtime_env.get("env_vars") or {}).items():
            vars_prev[k] = os.environ.get(k)
            os.environ[str(k)] = str(v)
        if wd_path:
            try:
                self._env_orig_cwd = os.getcwd()
            except OSError:
                self._env_orig_cwd = None
            try:
                os.chdir(wd_path)
            except OSError:
                pass
        _sys.path[:0] = paths
        if uri is not None:
            cache.acquire(uri)
        if prev_uri:
            from ray_tpu._private.runtime_env_pip import node_env_cache

            node_env_cache().release(prev_uri)
        self._env_paths = paths
        self._env_vars_prev = vars_prev
        self._env_uri = uri
        self._env_applied_key = key

    def _localize_env_entry(self, entry: str) -> str:
        """Turn a runtime-env entry into a path valid on THIS node:
        content keys (pkg-/blob-, uploaded by the driver's normalization)
        materialize from GCS KV into the node's package cache; anything
        else (package names, URLs, paths that exist locally) passes
        through."""
        if not isinstance(entry, str):
            return entry
        dest_root = os.path.join("/tmp/ray_tpu", "pkg_cache")
        if entry.startswith("pkg-"):
            from ray_tpu._private.runtime_env import materialize_working_dir

            os.makedirs(dest_root, exist_ok=True)
            key, _, name = entry.partition("/")
            extracted = materialize_working_dir(self.gcs.call, key,
                                                dest_root)
            if not name:
                return extracted
            # "pkg-<hash>/<name>": the packaged tree must surface under
            # its ORIGINAL directory name (a py_module's dir name is its
            # import name; zipping strips it)
            named_root = os.path.join(dest_root, key + ".named")
            target = os.path.join(named_root, name)
            if not os.path.exists(target):
                os.makedirs(named_root, exist_ok=True)
                try:
                    os.symlink(extracted, target)
                except OSError:
                    pass   # raced another worker: target now exists
            return target
        if entry.startswith("blob-"):
            # "blob-<hash>/<basename>": a single file (e.g. a wheel) —
            # materialized under its REAL basename because pip parses
            # name/version out of wheel filenames
            key, _, basename = entry.partition("/")
            blob_dir = os.path.join(dest_root, key)
            os.makedirs(blob_dir, exist_ok=True)
            path = os.path.join(blob_dir, basename or "blob.bin")
            if not os.path.exists(path):
                data = self.gcs.call("kv_get", ns="packages",
                                     key=key.encode())
                if data is None:
                    raise ValueError(f"package {entry!r} not found in GCS")
                tmp = path + f".tmp{os.getpid()}"
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, path)
            return path
        return entry

    def _worker_death_error(self, worker_id: str):
        """Error for a task whose executing worker died. The raylet records
        OOM kills in GCS KV *before* delivering SIGKILL (raylet.py
        _on_memory_pressure), so by the time the owner observes the dropped
        connection the verdict is already readable — an OOM death surfaces
        as a retriable OutOfMemoryError naming the culprit, anything else
        as WorkerCrashedError."""
        try:
            blob = self.gcs.call("kv_get", ns="oom_kill",
                                 key=worker_id.encode(), timeout=5.0)
        except Exception:
            blob = None
        if blob:
            return exc.OutOfMemoryError(
                blob.decode() if isinstance(blob, bytes) else str(blob))
        return exc.WorkerCrashedError(
            f"worker {worker_id} died executing task")

    # ---------------------------------------------------------------- put/get

    def put(self, value) -> ObjectRef:
        # parts path: out-of-band buffers copy straight into the shm
        # segment (or stream to the spill file) — no assembled
        # intermediate frame (one full copy saved per big array)
        parts = ser.serialize_parts(value)
        object_id = self._new_id()
        with _ma.default_tag("task_arg", owner=self.worker_id):
            size = self.store.put_parts(object_id, parts)
        # we own it: record the location in OUR directory — no RPC at all
        self._loc_add(object_id, self._my_node, size)
        self._owned.add(object_id)
        ref = ObjectRef(object_id, self.addr, self)
        return ref

    # ---- distributed release (simplified owner-based protocol; reference:
    # src/ray/core_worker/reference_count.h). The owner frees an object when
    # its own local Python refs hit zero and no in-flight task of this
    # process uses it as an argument. v1 limitation vs the reference's full
    # borrower protocol: a remote process that stashes a deserialized ref
    # beyond its task's lifetime does not extend the object's life.

    def _on_local_refs_zero(self, object_id: bytes):
        """Called from ObjectRef.__del__ — which the GC can run at ANY
        bytecode boundary, including while this thread holds the memory
        store lock or self._lock. Taking any lock here can self-deadlock
        (observed: GC fired inside submit_task's memory_store.entry() and
        the free path re-acquired the store's non-reentrant lock). So:
        only enqueue; the reaper thread does the real work."""
        if self.stopped:
            return
        self._free_queue.put(object_id)

    def _free_loop(self):
        while True:
            object_id = self._free_queue.get()
            if object_id is None or self.stopped:
                return
            try:
                with self._lock:
                    if self._arg_pins.get(object_id):
                        self._deferred_free.add(object_id)
                        continue
                self._free_object(object_id)
            except Exception:
                pass

    def _free_object(self, object_id: bytes):
        self.memory_store.free(object_id)
        to_unpin = None
        with self._lock:
            task_entry = self._ref_to_task.pop(object_id, None)
            gen_stream = self._gen_streams.pop(object_id, None)
            self._inline_frame_cache.pop(object_id, None)
            owned = object_id in self._owned
            self._owned.discard(object_id)
            tid = self._lineage_index.pop(object_id, None)
            if tid is not None:
                self._lineage_live[tid] -= 1
                if self._lineage_live[tid] <= 0:
                    to_unpin = self._drop_lineage_locked(tid)
        if to_unpin is not None:
            self._unpin_args(to_unpin)
        if gen_stream is not None:
            # The generator itself is gone: release stream items nobody
            # ever took a Python ref on (closed early / dropped
            # uniterated) — their refcount is 0 so on_zero can never fire
            # for them. Items the consumer DID take refs on free through
            # the normal refcount path when those refs die. If the
            # producer is still running, cancel it here (we are on the
            # reaper thread, where blocking pushes are allowed —
            # ObjectRefGenerator.__del__ itself must never touch locks
            # or the network, matching _on_local_refs_zero's contract).
            with gen_stream.cond:
                unfinished = (gen_stream.total is None
                              and gen_stream.error is None)
                gen_stream.closed = True
                item_ids = list(gen_stream.items.values())
                gen_stream.cond.notify_all()
            if unfinished and task_entry is not None:
                self._cancel_spec(*task_entry, force=False)
            for rid in item_ids:
                if self.reference_counter.count(rid) == 0:
                    self._free_object(rid)
        if owned:
            # we are the directory: hand the GCS the holder list so it can
            # fan the delete out to those raylets (node connections live
            # there), then drop our entries
            with self._dir_lock:
                holders = list(self._obj_locations.pop(object_id, {}))
                size = self._obj_sizes.pop(object_id, None)   # always pop
                had_copy = bool(holders) or size is not None
            if not had_copy:
                return   # inline-only result: nothing anywhere to delete,
                         # and the per-task free push + GCS handler round
                         # is pure hot-path overhead (profiled round 5)
            try:
                self.gcs.push("free_objects", object_ids=[object_id],
                              locations={object_id: holders})
            except Exception:
                # the free is one-way and now LOST — the object strands
                # on its holder nodes until the leak sweep names it
                _ma.LEDGER.note_free_dropped("owner_push")

    # ------------------------------------------------ lineage reconstruction
    # Reference: object_recovery_manager.h:30 (re-execute the creating task
    # when all copies are lost) with task_manager.h-style lineage pinning.

    def _retain_lineage(self, spec: dict):
        from ray_tpu._private.config import get_config

        cap = int(get_config("max_lineage_bytes"))
        tid = spec["task_id"]
        cost = len(spec.get("args", b"")) + 512
        retained = False
        evicted: list[dict] = []
        with self._lock:
            if tid in self._lineage_specs:     # reconstruction round-trip:
                return                         # already retained, pins held
            live = [r for r in spec["return_ids"] if r in self._owned]
            if (live and spec.get("_queue") is not None and cost <= cap
                    and spec.get("reconstructions_left", 0) > 0):
                self._lineage_specs[tid] = (spec, spec["_queue"])
                self._lineage_live[tid] = len(live)
                for rid in live:
                    self._lineage_index[rid] = tid
                self._lineage_bytes += cost
                self._lineage_order.append(tid)
                retained = True
                while (self._lineage_bytes > cap
                        and len(self._lineage_order) > 1):
                    old_tid = self._lineage_order.popleft()
                    dropped = self._drop_lineage_locked(old_tid)
                    if dropped is not None:
                        evicted.append(dropped)
                # Compact stale tids (dropped via _free_object) so the
                # deque stays O(live lineage), not O(tasks ever submitted).
                if len(self._lineage_order) > 2 * len(self._lineage_specs) + 64:
                    self._lineage_order = collections.deque(
                        t for t in self._lineage_order
                        if t in self._lineage_specs)
        if not retained:
            self._unpin_args(spec)
        for old in evicted:
            self._unpin_args(old)

    def _drop_lineage_locked(self, tid: bytes):
        """Remove a lineage spec (caller holds self._lock). Returns the spec
        whose arg pins should be released, or None."""
        entry = self._lineage_specs.pop(tid, None)
        self._lineage_live.pop(tid, None)
        if entry is None:
            return None
        spec, _q = entry
        for rid in spec["return_ids"]:
            if self._lineage_index.get(rid) == tid:
                del self._lineage_index[rid]
        self._lineage_bytes -= len(spec.get("args", b"")) + 512
        return spec

    def _maybe_reconstruct(self, object_id: bytes) -> bool:
        """If we own lineage for a lost object, re-submit its creating task.
        Returns True when a reconstruction is in flight (caller should keep
        polling), False when the loss is unrecoverable."""
        with self._lock:
            tid = self._lineage_index.get(object_id)
            if tid is None:
                return False
            spec, q = self._lineage_specs[tid]
            if any(rid in self._ref_to_task for rid in spec["return_ids"]):
                return True    # a reconstruction is already in flight
            if spec.get("reconstructions_left", 0) <= 0:
                return False
            spec["reconstructions_left"] -= 1
            for rid in spec["return_ids"]:
                self._ref_to_task[rid] = (spec, q)
        q.submit(spec)
        return True

    def _pin_args(self, spec: dict, args=None, kwargs=None, *, refs=None,
                  skip=None):
        if refs is None:
            if not args and not kwargs:
                return
            refs = ser.contained_refs((args, kwargs))
        ids = [r.id for r in refs
               if skip is None or r.id not in skip]
        if not ids:
            return
        spec["_arg_ids"] = ids   # stripped before the wire (leading _)
        with self._lock:
            for oid in ids:
                self._arg_pins[oid] = self._arg_pins.get(oid, 0) + 1

    def _unpin_args(self, spec: dict):
        to_free = []
        with self._lock:
            for oid in spec.get("_arg_ids", ()):
                n = self._arg_pins.get(oid, 0) - 1
                if n <= 0:
                    self._arg_pins.pop(oid, None)
                    if oid in self._deferred_free and \
                            self.reference_counter.count(oid) == 0:
                        self._deferred_free.discard(oid)
                        to_free.append(oid)
                else:
                    self._arg_pins[oid] = n
        for oid in to_free:
            self._free_object(oid)

    def get(self, refs, timeout: float | None = None):
        single = isinstance(refs, ObjectRef)
        if single:
            refs = [refs]
        deadline = None if timeout is None else time.time() + timeout
        out = []
        for ref in refs:
            remaining = None if deadline is None else max(
                0.0, deadline - time.time())
            value, raised = self._get_one(ref, remaining)
            if raised and isinstance(value, BaseException):
                raise value
            out.append(value)
        return out[0] if single else out

    def _get_one(self, ref: ObjectRef, timeout: float | None):
        # Only payloads shipped by serialize_error (the task raised) re-raise
        # at get(); a task returning an exception object is a normal value
        # (reference parity: only RayTaskError wrappers re-raise).
        data = self._fetch_bytes(ref, timeout)
        value, meta = ser.deserialize(data, self, with_meta=True)
        return value, meta.get("raised", False)

    def _fetch_bytes(self, ref: ObjectRef, timeout: float | None):
        deadline = None if timeout is None else time.time() + timeout
        poll = 0.001
        while True:
            # 1. owner memory store (we own it or borrowed+cached)
            data = self.memory_store.get_nowait(ref.id)
            if data is not None:
                return data
            # While OUR producing task is still in flight, nothing below
            # can hit: the result announces through the task reply (inline
            # → memory store; stored → directory record), so probing the
            # shm store (a C-lock + spill-stat round, ~100µs on the dev
            # box) or the directory every poll is pure hot-path waste.
            # Skip straight to the wait; the reply or a poll tick re-runs
            # the full path once the task is done.
            in_flight = ref.id in self._ref_to_task
            if not in_flight:
                # 2. local shm store
                buf = self.store.get(ref.id)
                if buf is not None:
                    try:
                        if hasattr(buf, "view"):
                            # spill-backed host buffer (possibly an
                            # mmap): zero-copy view, safe past release
                            return buf.view()
                        return buf.to_bytes()
                    finally:
                        buf.release()
            # 3. resolve through the OWNER-BASED directory — zero GCS calls
            # (reference: ownership_based_object_directory.h).
            we_own = not ref.owner_addr or tuple(ref.owner_addr) == self.addr
            if in_flight:
                pass          # wait below; the reply resolves everything
            elif we_own:
                # we are the owner: our table is the directory
                nodes, created_size = self._loc_snapshot(ref.id)
                for node in nodes:
                    if node["NodeID"] == self.node_id:
                        continue
                    data = self._pull_remote(ref.id, node)
                    if data is not None:
                        return data
                    # the copy is gone with its node — drop the location
                    self._loc_remove(ref.id, node["NodeID"])
                # Sealed once, zero copies left, no producing task in
                # flight → recovery is OUR job (reference:
                # ObjectRecoveryManager runs in the owner's core worker):
                # re-execute the creating task if we hold lineage, else
                # the loss is permanent.
                remote = [n for n in nodes
                          if n["NodeID"] != self.node_id]
                if created_size and not remote \
                        and ref.id not in self._ref_to_task:
                    if not self._maybe_reconstruct(ref.id):
                        raise exc.ObjectLostError(ref.hex())
            else:
                # borrower: ONE owner round trip resolves value (inline),
                # holder nodes ("at" → data-plane pull inside _ask_owner),
                # pending, or lost.
                data = self._ask_owner(ref, deadline)
                if data is not None:
                    # borrower-side cache: repeat gets of this ref skip the
                    # owner round trip. Small values ride the heap memory
                    # store (freed by the same ref-zero path as owned
                    # entries); big ones go to the shm store like remote
                    # pulls, so they stay under shm accounting.
                    from ray_tpu._private.config import get_config

                    if len(data) <= int(get_config(
                            "inline_object_max_size_bytes")):
                        self.memory_store.put(ref.id, data)
                        # put-then-check closes the race with the ref
                        # reaper: if the last local ref died first, the
                        # reaper's free already ran — undo our insert
                        if self.reference_counter.count(ref.id) == 0:
                            self.memory_store.free(ref.id)
                    elif not self.store.contains(ref.id):
                        # (an "at" pull already cached+announced; don't
                        # double-insert)
                        self._cache_local(ref.id, data, ref.owner_addr)
                    return data
            if deadline is not None and time.time() > deadline:
                raise exc.GetTimeoutError(
                    f"get() timed out waiting for {ref.hex()}")
            # The object may simply not be created yet (pending task): if we
            # are the owner, wait on the memory-store future.
            entry = self.memory_store.entry(ref.id)
            wait_t = poll if deadline is None else min(
                poll, max(0.0, deadline - time.time()))
            entry.event.wait(wait_t)
            poll = min(poll * 2, 0.1)

    def _pull_remote(self, object_id: bytes, node_snapshot: dict,
                     owner_addr=None):
        """Chunked node-to-node pull with admission control.

        Reference: PullManager (pull_manager.h:48) bounds in-flight pull
        bytes; PushManager (push_manager.h:29) moves objects as chunks. A
        large object crosses the network in `object_transfer_chunk_bytes`
        frames instead of one pickle frame, and the total bytes being
        pulled concurrently by this worker is capped. owner_addr names the
        object's owner so the cached copy gets announced to its directory
        (None/self → we are the owner)."""
        from ray_tpu._private.config import get_config

        host = node_snapshot["NodeManagerAddress"]
        chunk = int(get_config("object_transfer_chunk_bytes"))
        data = None
        # fast path: the remote raylet's native (C++) data server streams
        # the bytes straight out of its shm segment, GIL-free
        data_port = node_snapshot.get("object_data_port")
        cached = False
        if data_port:
            data, cached = self._pull_native(object_id, (host, data_port),
                                             chunk, owner_addr)
        if data is None:
            data = self._pull_rpc(
                object_id, (host, node_snapshot["NodeManagerPort"]), chunk)
        if data is None:
            return None
        # Cache locally for future gets (reference: pulled chunks land in
        # local plasma) — unless the native path already received the
        # bytes straight into the store and announced the location.
        if not cached:
            self._cache_local(object_id, data, owner_addr)
        return data

    def _cache_local(self, object_id: bytes, data: bytes, owner_addr=None):
        """Cache fetched bytes in the local shm store and register the new
        location with the owner (best-effort; a full store skips the
        cache)."""
        try:
            self.store.put(object_id, data)
            self._announce_copy(object_id, len(data), owner_addr)
        except Exception:
            pass

    def _data_sock_checkout(self, addr, fresh: bool = False):
        """Persistent-connection pool for the native data plane (one
        in-flight request per socket; concurrent pulls each check out
        their own). fresh=True bypasses AND drains the pool for this addr
        — used by the retry after a pooled socket died, since its siblings
        are likely dead too (server restart)."""
        import socket as _socket

        lock = self.__dict__.setdefault("_data_sock_lock",
                                        threading.Lock())
        pool = self.__dict__.setdefault("_data_sock_pool", {})
        with lock:
            socks = pool.get(addr)
            if fresh and socks:
                for s in socks:
                    try:
                        s.close()
                    except OSError:
                        pass
                socks.clear()
            elif socks:
                return socks.pop(), True
        # short connect probe: an unreachable (firewalled) data port must
        # fail over to the RPC plane in seconds, not minutes
        sock = _socket.create_connection(addr, timeout=5.0)
        sock.settimeout(120.0)
        sock.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
        return sock, False

    def _data_sock_checkin(self, addr, sock):
        with self._data_sock_lock:
            socks = self._data_sock_pool.setdefault(addr, [])
            if len(socks) < 4:
                socks.append(sock)
                return
        try:
            sock.close()
        except OSError:
            pass

    def _pull_native(self, object_id: bytes, addr, chunk: int,
                     owner_addr=None):
        """Fetch via the remote store's C++ data server
        (src/store/data_server.cc). Protocol: 32-byte request (id, offset,
        max_len) -> 16-byte header (total_size, payload_len) + payload.
        A pooled (possibly stale) connection gets one retry on a fresh
        socket before giving up."""
        result = self._pull_native_once(object_id, addr, chunk, owner_addr)
        if result is _RETRY_FRESH:
            result = self._pull_native_once(object_id, addr, chunk,
                                            owner_addr, fresh=True)
        if result is _RETRY_FRESH or result is None:
            return None, False
        return result   # (data, cached_in_local_store)

    def _pull_native_once(self, object_id: bytes, addr, chunk: int,
                          owner_addr=None, fresh: bool = False):
        import struct as _struct

        missing = (1 << 64) - 1
        admitted = 0
        sock = None
        pooled = False
        ok = False
        data = None       # heap fallback buffer
        shm_view = None   # zero-copy receive target in the local store
        try:
            sock, pooled = self._data_sock_checkout(addr, fresh=fresh)

            def read_into(view):
                got = 0
                n = len(view)
                while got < n:
                    r = sock.recv_into(view[got:], n - got)
                    if r == 0:
                        raise ConnectionError("data server closed")
                    got += r

            header = bytearray(16)
            size = None
            offset = 0
            while size is None or offset < size:
                sock.sendall(object_id + _struct.pack("<QQ", offset, chunk))
                read_into(memoryview(header))
                total, n = _struct.unpack("<QQ", header)
                if total == missing:
                    ok = True            # healthy conversation, no object
                    if shm_view is not None:
                        # a mid-pull eviction remotely must not leak the
                        # local create reservation (an unsealed entry is
                        # never evictable and poisons the id forever)
                        self.store.abort(object_id)
                    return None
                if size is None:
                    size = total
                    admitted = size
                    self._admit_pull(size)
                    # receive STRAIGHT into the local store's segment —
                    # the old path recv'd into a heap bytearray and then
                    # copied into shm (VERDICT round-3 weak #7). Fall
                    # back to heap when the store is full (spill path)
                    # or the object is already local.
                    try:
                        buf = self.store.create(object_id, size)
                        if buf is not None:
                            shm_view = memoryview(buf).cast("B")
                    except Exception:
                        shm_view = None
                    if shm_view is None:
                        data = bytearray(size)
                    if size == 0:
                        break
                if n == 0:
                    ok = True
                    if shm_view is not None:
                        self.store.abort(object_id)
                    return None          # evicted/shrunk mid-pull
                target = shm_view if shm_view is not None else \
                    memoryview(data)
                read_into(target[offset:offset + n])
                offset += n
            ok = True
            if shm_view is not None:
                # copy out BEFORE seal: sealing makes the entry
                # immediately evictable, and losing a fully-received
                # object to a concurrent eviction would force a full
                # re-download over the slow RPC plane
                payload = bytes(shm_view)
                self.store.seal(object_id)
                self._announce_copy(object_id, size, owner_addr)
                return payload, True
            return (bytes(data), False) if data is not None else None
        except Exception:
            if shm_view is not None:
                try:
                    self.store.abort(object_id)
                except Exception:
                    pass
            # a dead pooled socket deserves one retry on a fresh one
            return _RETRY_FRESH if pooled else None
        finally:
            if admitted:
                self._release_pull(admitted)
            if sock is not None:
                if ok:
                    self._data_sock_checkin(addr, sock)
                else:
                    try:
                        sock.close()
                    except OSError:
                        pass

    def _pull_rpc(self, object_id: bytes, chunk_addr, chunk: int):
        """Fallback chunk fetch over the Python RPC plane. Chunk reads
        are pure (retry-safe), so transient connection loss or a timed-
        out chunk reconnects and resumes AT THE CURRENT OFFSET under the
        unified policy instead of abandoning the whole pull (and with it
        possibly the object's only reachable copy)."""
        from ray_tpu._private.retry import RetryPolicy

        # few, fast attempts: a holder that refuses twice is usually
        # DEAD (node removal), and the caller already falls back to
        # other replicas / the owner poll — don't stall that failover
        policy = RetryPolicy(max_attempts=3, base_backoff_s=0.05,
                             max_backoff_s=0.5, deadline_s=240.0,
                             attempt_timeout_s=120.0)
        clientbox = [None]

        def fetch(offset, attempt_timeout):
            if clientbox[0] is None or clientbox[0].closed:
                # retry=1: re-dialing a refused connect is the POLICY's
                # job here; stacking the constructor's own retry loop
                # under it would triple every failover pause
                clientbox[0] = RpcClient(chunk_addr, timeout=120.0,
                                         retry=1)
            return clientbox[0].call("fetch_object_chunk",
                                     object_id=object_id, offset=offset,
                                     length=chunk, timeout=attempt_timeout)

        admitted = 0
        try:
            first = policy.run(lambda t: fetch(0, t),
                               method="fetch_object_chunk",
                               retry_on=(ConnectionLost, TimeoutError))
            if first is None:
                return None
            size = first["size"]
            admitted = size
            self._admit_pull(size)
            data = bytearray(first["data"])
            while len(data) < size:
                part = policy.run(lambda t: fetch(len(data), t),
                                  method="fetch_object_chunk",
                                  retry_on=(ConnectionLost, TimeoutError))
                if part is None:   # evicted mid-pull
                    return None
                data += part["data"]
            return bytes(data)
        except (ConnectionLost, Exception):  # noqa: BLE001
            return None
        finally:
            if admitted:
                self._release_pull(admitted)
            if clientbox[0] is not None:
                clientbox[0].close()

    def _admit_pull(self, nbytes: int):
        """Block until the pull fits the in-flight budget (always admit when
        nothing else is in flight, so an object larger than the budget can
        still be fetched — same escape hatch as the reference's PullManager)."""
        from ray_tpu._private.config import get_config

        cap = int(get_config("pull_max_inflight_bytes"))
        with self._pull_lock:
            while (self._pull_inflight_bytes > 0
                    and self._pull_inflight_bytes + nbytes > cap):
                self._pull_lock.wait(0.5)
            self._pull_inflight_bytes += nbytes

    def _release_pull(self, nbytes: int):
        with self._pull_lock:
            self._pull_inflight_bytes = max(
                0, self._pull_inflight_bytes - nbytes)
            self._pull_lock.notify_all()

    def _owner_client(self, addr: tuple) -> RpcClient:
        """Pooled connection to an object owner (one multiplexed client per
        owner; a fresh TCP connect per borrowed get was the dominant cost
        of ref-arg tasks in ray_perf). The connect happens OUTSIDE the pool
        lock so one unreachable owner can't stall fetches to healthy ones;
        a losing racer's client is closed, the winner's pooled."""
        with self._owner_client_lock:
            client = self._owner_clients.get(addr)
            if client is not None and not client.closed:
                # LRU reorder: eviction takes the front, so keep hot
                # clients at the back
                self._owner_clients.pop(addr)
                self._owner_clients[addr] = client
                return client
        fresh = RpcClient(addr, timeout=30.0, retry=1)
        with self._owner_client_lock:
            current = self._owner_clients.get(addr)
            if current is not None and not current.closed:
                winner = current
            else:
                # bounded pool: evict the LEAST-RECENTLY-USED entry beyond
                # the cap (checkouts reorder to the back). An evicted
                # client with calls still in flight is left open — its
                # reader thread ends with the connection; closing it would
                # abort healthy calls.
                while len(self._owner_clients) >= 16:
                    oldest = next(iter(self._owner_clients))
                    old = self._owner_clients.pop(oldest)
                    if not old._pending:
                        try:
                            old.close()
                        except Exception:
                            pass
                self._owner_clients[addr] = fresh
                return fresh
        try:
            fresh.close()
        except Exception:
            pass
        return winner

    def _drop_owner_client(self, addr: tuple, client: RpcClient):
        """Evict `client` from the pool — identity-checked, so a healthy
        replacement pooled by another thread is never closed by mistake."""
        with self._owner_client_lock:
            if self._owner_clients.get(addr) is client:
                self._owner_clients.pop(addr, None)
        try:
            client.close()
        except Exception:
            pass

    def _ask_owner(self, ref: ObjectRef, deadline):
        addr = tuple(ref.owner_addr)
        # one retry on a fresh connection: ConnectionLost/timeouts on a
        # POOLED client usually mean the cached socket went stale (owner
        # restart, idle NAT drop), not that the object is gone
        for attempt in range(2):
            try:
                client = self._owner_client(addr)
            except ConnectionLost:
                if attempt == 0:
                    continue
                raise exc.ObjectLostError(ref.hex()) from None
            try:
                reply = client.call("get_owned_value", object_id=ref.id,
                                    timeout=6.0)
                if isinstance(reply, dict) and "status" in reply:
                    if reply["status"] == "lost":
                        raise exc.ObjectLostError(ref.hex())
                    if reply["status"] == "at":
                        # big value: pull over the data plane from a holder
                        # node instead of this pickle channel
                        for node in reply.get("nodes", ()):
                            if node["NodeID"] == self.node_id:
                                # our own cached copy is gone (local store
                                # already missed before we got here) —
                                # retract it or the owner's directory never
                                # drains and lost-detection never fires
                                try:
                                    client.push("object_location_removed",
                                                object_id=ref.id,
                                                node_id=node["NodeID"])
                                except Exception:
                                    pass
                                continue
                            data = self._pull_remote(ref.id, node,
                                                     owner_addr=addr)
                            if data is not None:
                                return data
                            # stale location (holder died): tell the owner
                            try:
                                client.push("object_location_removed",
                                            object_id=ref.id,
                                            node_id=node["NodeID"])
                            except Exception:
                                pass
                        return None   # caller keeps polling; owner recovers
                    return reply.get("data")
                return reply
            except TimeoutError:
                # Possibly half-open: evict from the pool NOW (the next
                # fetch reconnects within one round), but only CLOSE the
                # socket if no other thread has calls in flight on it —
                # closing would abort their healthy calls; an orphaned
                # client dies with its connection.
                with self._owner_client_lock:
                    if self._owner_clients.get(addr) is client:
                        self._owner_clients.pop(addr, None)
                if not client._pending:
                    try:
                        client.close()
                    except Exception:
                        pass
                return None
            except ConnectionLost:
                self._drop_owner_client(addr, client)
                if attempt == 0:
                    continue
                raise exc.ObjectLostError(ref.hex()) from None
        return None

    def rpc_profile_events(self, conn):
        from ray_tpu._private import profiling

        # drop marker included: a merged timeline must surface ring
        # eviction instead of presenting the window as complete
        return profiling.snapshot(with_drop_marker=True)

    def rpc_trace_spans(self, conn):
        from ray_tpu.util import tracing

        return tracing.local_spans(with_drop_marker=True)

    def rpc_metrics_snapshot(self, conn):
        from ray_tpu.util import metrics

        return metrics.registry_snapshot()

    def rpc_events_snapshot(self, conn):
        return _events.snapshot()

    def rpc_step_records(self, conn):
        """This process's step-anatomy export (steps + activities +
        drop counts) for summarize_steps()'s cluster fan-out."""
        from ray_tpu._private import step_anatomy

        return [step_anatomy.local_records()]

    def rpc_blackbox_snapshot(self, conn):
        """This process's flight-recorder window (recent spans/events/
        steps/metrics) for a cluster black-box dump."""
        from ray_tpu._private import flight_recorder

        snap = flight_recorder.local_snapshot()
        return [snap] if snap else []

    def rpc_memory_snapshot(self, conn):
        """This process's memory-anatomy ledger (sweep + snapshot) for
        summarize_memory()'s cluster fan-out."""
        snap = _ma.local_snapshot(top_k=10, window_s=None)
        snap["node"] = self.node_id
        return [snap]

    # ------------------------------------------- owner-based object directory
    # Reference: ownership_based_object_directory.h:1 — the owning worker is
    # the source of truth for which nodes hold copies of its objects. Nodes
    # that create a copy (task return, pull-cache) announce to the OWNER;
    # readers resolve through the owner. The GCS keeps no per-get role.

    def _loc_add(self, object_id: bytes, node: dict, size: int = 0):
        with self._dir_lock:
            self._obj_locations.setdefault(
                object_id, {})[node["NodeID"]] = dict(node)
            if size:
                self._obj_sizes[object_id] = size

    def _loc_remove(self, object_id: bytes, node_id: str):
        with self._dir_lock:
            locs = self._obj_locations.get(object_id)
            if locs:
                locs.pop(node_id, None)

    def _loc_snapshot(self, object_id: bytes):
        """(nodes, size) for an owned object — size>0 means a copy was
        sealed somewhere at some point (the was-created signal that arms
        lost-object detection once nodes drains to empty)."""
        with self._dir_lock:
            nodes = [dict(n)
                     for n in self._obj_locations.get(object_id, {}).values()]
            return nodes, self._obj_sizes.get(object_id, 0)

    def _announce_copy(self, object_id: bytes, size: int, owner_addr):
        """This node now holds a sealed copy: register it with the object's
        owner (ourselves → table write; remote → one-way push)."""
        if not owner_addr or tuple(owner_addr) == self.addr:
            self._loc_add(object_id, self._my_node, size)
            return
        try:
            self._owner_client(tuple(owner_addr)).push(
                "object_location_added", object_id=object_id,
                node=self._my_node, size=size)
        except Exception:
            pass   # owner gone: the copy is orphaned; raylet LRU reclaims

    def rpc_object_location_added(self, conn, object_id: bytes, node: dict,
                                  size: int = 0):
        self._loc_add(object_id, node, size)

    def rpc_object_location_removed(self, conn, object_id: bytes,
                                    node_id: str):
        self._loc_remove(object_id, node_id)

    def rpc_locate_object(self, conn, object_id: bytes):
        """Non-blocking readiness+location probe (wait()/_is_ready path).
        INLINE: dict lookups and a shm-index probe only."""
        ready = (self.memory_store.contains_resolved(object_id)
                 or self.store.contains(object_id))
        nodes, size = self._loc_snapshot(object_id)
        return {"ready": ready or bool(nodes), "nodes": nodes, "size": size}

    def rpc_get_owned_value(self, conn, object_id: bytes):
        """Serve a value we own to a borrower. Blocks briefly if the task
        producing it hasn't finished. Small values ride the reply inline;
        big ones return the holder nodes ("at") so the borrower pulls over
        the zero-copy data plane instead of this pickle channel. If every
        copy of a sealed value died, the owner is the one holding lineage —
        kick reconstruction here so borrowers recover too (reference:
        recovery runs in the owner's core worker,
        object_recovery_manager.h)."""
        from ray_tpu._private.config import get_config

        inline_max = int(get_config("inline_object_max_size_bytes"))
        entry = self.memory_store.entry(object_id)
        if entry.event.wait(0.5):
            return {"status": "ok", "data": entry.data}
        buf = self.store.get(object_id)
        if buf is not None:
            try:
                size = len(buf)
                if size <= inline_max:
                    return {"status": "ok", "data": buf.to_bytes()}
            finally:
                buf.release()
            nodes, _ = self._loc_snapshot(object_id)
            nodes = ([dict(self._my_node)]
                     + [n for n in nodes if n["NodeID"] != self.node_id])
            return {"status": "at", "nodes": nodes, "size": size}
        nodes, size = self._loc_snapshot(object_id)
        nodes = [n for n in nodes if n["NodeID"] != self.node_id]
        if nodes:
            return {"status": "at", "nodes": nodes, "size": size}
        if size and object_id not in self._ref_to_task:
            # sealed once, zero live copies → lost unless lineage recovers it
            if not self._maybe_reconstruct(object_id):
                return {"status": "lost"}
        if entry.event.wait(3.0):
            return {"status": "ok", "data": entry.data}
        # pending: task still running / reconstruction in flight
        return {"status": "pending"}

    def wait(self, refs, num_returns=1, timeout=None, fetch_local=True):
        if num_returns > len(refs):
            raise ValueError("num_returns > len(refs)")
        deadline = None if timeout is None else time.time() + timeout
        ready: list[ObjectRef] = []
        pending = list(refs)
        poll = 0.001
        while len(ready) < num_returns:
            still = []
            for ref in pending:
                if self._is_ready(ref):
                    ready.append(ref)
                else:
                    still.append(ref)
            pending = still
            if len(ready) >= num_returns:
                break
            if deadline is not None and time.time() >= deadline:
                break
            time.sleep(poll)
            poll = min(poll * 2, 0.05)
        # preserve input order
        ready_set = {r.id for r in ready}
        ordered_ready = [r for r in refs if r.id in ready_set]
        ordered_pending = [r for r in refs if r.id not in ready_set]
        return ordered_ready, ordered_pending

    def _is_ready(self, ref: ObjectRef) -> bool:
        if self.memory_store.contains_resolved(ref.id):
            return True
        if self.store.contains(ref.id):
            return True
        if not ref.owner_addr or tuple(ref.owner_addr) == self.addr:
            with self._dir_lock:
                return bool(self._obj_locations.get(ref.id))
        try:
            reply = self._owner_client(tuple(ref.owner_addr)).call(
                "locate_object", object_id=ref.id, timeout=5.0)
            return bool(reply.get("ready"))
        except Exception:
            return False   # owner unreachable → not fetchable either

    def as_future(self, ref: ObjectRef) -> PyFuture:
        fut = PyFuture()

        def _wait():
            try:
                fut.set_result(self.get(ref))
            except BaseException as e:  # noqa: BLE001
                fut.set_exception(e)

        threading.Thread(target=_wait, daemon=True).start()
        return fut

    # ------------------------------------------------------------ submission

    def register_function(self, fn) -> bytes:
        blob = ser.dumps_function(fn)
        func_hash = hashlib.sha1(blob).digest()
        if func_hash not in self._func_cache:
            self.gcs.call("kv_put", ns="funcs", key=func_hash, value=blob,
                          overwrite=False)
            self._func_cache[func_hash] = fn
        return func_hash

    def _load_function(self, func_hash: bytes):
        fn = self._func_cache.get(func_hash)
        if fn is None:
            blob = self.gcs.call("kv_get", ns="funcs", key=func_hash)
            if blob is None:
                raise RuntimeError("function not found in GCS function table")
            fn = ser.loads_function(blob)
            self._func_cache[func_hash] = fn
        return fn

    def submit_task(self, func_hash: bytes, args, kwargs, *, num_returns=1,
                    resources=None, strategy=None, max_retries=0,
                    runtime_env=None, task_desc="task",
                    inline_exec=False) -> list[ObjectRef]:
        # {} is a legitimate request (num_cpus=0: schedule anywhere, consume
        # nothing); only None means "default 1 CPU".
        resources = {"CPU": 1.0} if resources is None else dict(resources)
        runtime_env = self._normalize_runtime_env(runtime_env)
        dynamic = num_returns in ("dynamic", "streaming")
        return_ids = [self._new_id()
                      for _ in range(1 if dynamic else num_returns)]
        inlined = None
        arg_refs = ()
        if args or kwargs:
            args, kwargs, inlined = self._inline_small_args(args, kwargs)
            args_blob = ser.serialize((args, kwargs))
            arg_refs = ser.contained_refs((args, kwargs))   # walked ONCE
        else:
            args_blob = ser.serialize_empty_args()   # constant, cached
        spec = {
            "task_id": self._new_id(),
            "func_hash": func_hash,
            "args": args_blob,
            "return_ids": return_ids,
            "owner_addr": self.addr,
            "retries_left": max_retries,
            # budget for re-executing this task after its sealed result is
            # lost (node death). Reference semantics: reconstruction rides
            # the retry budget — max_retries=0 tasks are never re-executed
            # (their loss raises ObjectLostError, see _fetch_bytes).
            "reconstructions_left": max_retries,
            "task_desc": task_desc,
            "job_id": self.job_id,
        }
        if inlined:
            spec["inlined"] = inlined
        if runtime_env:
            spec["runtime_env"] = runtime_env
        if dynamic:
            spec["dynamic_returns"] = True
            with self._lock:
                self._gen_streams[return_ids[0]] = _GenStream()
        if inline_exec and not runtime_env and not dynamic and \
                all(r.id in (inlined or ()) for r in arg_refs):
            # Only pump-safe if no arg resolution can block: a ref that
            # survived small-arg inlining would make the pump fetch it
            # (possibly a cross-node transfer) mid-dispatch. Such tasks
            # silently take the main-loop path instead. (Refs nested deep
            # inside opaque objects can still slip through — the option's
            # contract says don't do that.)
            spec["inline_exec"] = True
        from ray_tpu.util import tracing

        from ray_tpu._private.task_spec import validate_task_spec

        validate_task_spec(spec)
        _events.task_event(spec["task_id"], "SUBMITTED", desc=task_desc)
        _carry_cause(spec)
        with tracing.submit_span(spec, task_desc):
            # refs whose bytes ride the spec need no pin: the task no
            # longer depends on the object outliving the submission
            self._pin_args(spec, refs=arg_refs, skip=inlined)
            self._owned.update(return_ids)
            refs = [ObjectRef(rid, self.addr, self) for rid in return_ids]
            for rid in return_ids:
                self.memory_store.entry(rid)  # pre-create pending futures
            # runtime_env joins the scheduling key: workers apply an env
            # once and keep it (reference: envs bind to dedicated
            # workers), so different envs must not share leases
            key = (func_hash, tuple(sorted(resources.items())),
                   _freeze(strategy), _freeze(runtime_env))
            with self._lock:
                q = self._sched_queues.get(key)
                if q is None:
                    q = _SchedulingKeyQueue(self, key, resources, strategy)
                    self._sched_queues[key] = q
                for rid in return_ids:
                    self._ref_to_task[rid] = (spec, q)
            q.submit(spec)
        return refs

    def _inline_small_args(self, args, kwargs):
        """Attach the serialized bytes of small, locally-resolved
        top-level ObjectRef args to the spec (reference:
        transport/dependency_resolver.h — the local dependency resolver
        inlines small args into the TaskSpec, sparing the executor an
        owner round trip per task). The refs STAY in the arg tree and
        the bytes ride out-of-band in spec["inlined"]: the producer
        never deserializes-then-reserializes the value per submit (the
        old form cost a full pickle round per task for a repeated
        ref-arg — profiled round 5), and the executor deserializes the
        attached frame exactly once. Error payloads are never inlined:
        getting them must raise on the executor."""
        from ray_tpu._private.config import get_config

        limit = int(get_config("inline_object_max_size_bytes"))
        inlined: dict[bytes, bytes] = {}

        def maybe(v):
            if not isinstance(v, ObjectRef):
                return v
            cached = self._inline_frame_cache.get(v.id)
            if cached is not None:
                data, ok = cached
                if ok:
                    inlined[v.id] = data
                return v
            data = self.memory_store.get_nowait(v.id)
            if data is None:
                buf = self.store.get(v.id)     # put() objects live in shm
                if buf is not None:
                    try:
                        if len(buf) <= limit:
                            data = buf.to_bytes()
                            # heap-cache: repeat submits of the same
                            # small ref must not pay a shm probe each
                            # (C lock + spill stat). Freed by ref-zero.
                            if self.reference_counter.count(v.id) > 0:
                                self.memory_store.put(v.id, data)
                    finally:
                        buf.release()
            if data is None or len(data) > limit:
                return v
            # one-time verdict: error payloads must NOT inline (the
            # executor's get must raise). Cached so repeat submits skip
            # the meta parse.
            try:
                _value, meta = ser.deserialize(data, self, with_meta=True)
                ok = not meta.get("raised")
            except Exception:
                ok = False
            data = bytes(data) if not isinstance(data, bytes) else data
            if self.reference_counter.count(v.id) > 0:
                self._inline_frame_cache[v.id] = (data, ok)
            if ok:
                inlined[v.id] = data
            return v

        args = [maybe(a) for a in args]
        kwargs = {k: maybe(v) for k, v in kwargs.items()}
        return args, kwargs, inlined

    def cancel_task(self, ref: ObjectRef, force: bool = False):
        """Best-effort cancel of the normal task producing `ref` (reference:
        CoreWorker::CancelTask). Queued → dropped before dispatch; running →
        flagged, force additionally interrupts the executing thread."""
        with self._lock:
            entry = self._ref_to_task.get(ref.id)
        if entry is None:
            return False
        return self._cancel_spec(*entry, force=force)

    def _cancel_spec(self, spec: dict, q, force: bool = False) -> bool:
        spec["_cancelled"] = True
        if q is None:
            # dynamic-returns actor task: route the cancel through the
            # actor connection (flag-only; the drain loop between yields
            # honors it)
            with self._lock:
                aq = self._actor_queues.get(spec.get("actor_id"))
            client = aq.client if aq is not None else None
            if client is not None:
                try:
                    client.push("cancel_task", task_id=spec["task_id"],
                                force=force)
                except Exception:
                    pass
            return True
        for lw in list(q.leased):
            try:
                lw.client.push("cancel_task", task_id=spec["task_id"],
                               force=force)
            except Exception:
                pass
        return True

    def request_lease(self, resources, strategy, max_spillbacks: int = 16):
        """Walk the spillback chain until granted (reference:
        direct_task_transport RequestNewWorkerIfNeeded + spillback replies)."""
        from ray_tpu._private.task_spec import validate_lease_request

        if strategy is None or "job" not in strategy:
            # multi-tenant label: leases inherit this process's current
            # job so raylet-side quota throttling and the GCS's per-job
            # usage gossip see plain task/actor work, not just PGs
            from ray_tpu.util import jobs as _jobs

            job = _jobs.current_job()
            if job:
                strategy = dict(strategy or {})
                strategy["job"] = job
        # producer-side shape check: a typo'd resource/strategy key fails
        # here, not as an ignored kwarg inside a remote raylet
        validate_lease_request(resources, strategy)
        target = self.raylet
        opened = None
        try:
            for hop in range(max_spillbacks + 1):
                # Saturated cluster: every node keeps redirecting to some
                # other busy node. After max_spillbacks hops, stop bouncing
                # and queue on the current raylet until resources free.
                if hop == max_spillbacks:
                    strategy = dict(strategy or {})
                    strategy["no_spill"] = True
                reply = target.call("request_worker_lease",
                                    resources=resources, strategy=strategy,
                                    lessee=(self.worker_id, self.addr),
                                    timeout=330.0)
                if "granted" in reply:
                    return reply["granted"]
                addr = tuple(reply["spillback"])
                if opened is not None:
                    opened.close()
                opened = RpcClient(addr, timeout=None)
                target = opened
            raise RuntimeError(
                "lease not granted after queueing on a saturated cluster")
        finally:
            # the grant reply carries everything we need (worker addr,
            # node id); the raylet connection is not kept
            if opened is not None:
                opened.close()

    def return_lease(self, lw: _LeasedWorker):
        try:
            if lw.node_id == self.node_id:
                self.raylet.push("return_worker", lease_id=lw.lease_id)
            else:
                # O(1) single-node lookup: returning one spillback lease
                # used to pull the WHOLE node table (O(cluster) payload
                # per return — at 100 nodes, the soak's dominant driver
                # → GCS traffic)
                addr = self.gcs.call("get_node_addr", node_id=lw.node_id)
                if addr is not None:
                    c = RpcClient(tuple(addr), timeout=10.0)
                    try:
                        c.push("return_worker", lease_id=lw.lease_id)
                    finally:
                        c.close()
        except (ConnectionLost, Exception):  # noqa: BLE001
            pass
        finally:
            try:
                lw.client.close()
            except Exception:
                pass

    def _fail_task(self, spec: dict, error: BaseException):
        _events.task_event(spec["task_id"], "FAILED",
                           error=type(error).__name__,
                           desc=spec.get("task_desc"))
        data = ser.serialize_error(error, spec.get("task_desc", "task"))
        if spec.get("dynamic_returns"):
            self._finalize_gen(spec, None, error=data)
        for rid in spec["return_ids"]:
            self.memory_store.put(rid, data)
            with self._lock:
                self._ref_to_task.pop(rid, None)
        # A failed reconstruction arrives here with the spec still retained
        # as lineage. Pins were taken once at submit and are NOT released at
        # retain time, so: drop the lineage bookkeeping (no unpin of its
        # own), then unpin exactly once.
        with self._lock:
            self._drop_lineage_locked(spec["task_id"])
        self._unpin_args(spec)

    def _handle_task_reply(self, spec: dict, reply: dict, node_id):
        q = None
        with self._lock:
            for rid in spec["return_ids"]:
                entry = self._ref_to_task.pop(rid, None)
                if entry is not None:
                    q = entry[1]
        spec["_queue"] = q   # stripped before the wire (leading _)
        if reply.get("cancelled"):
            self._fail_task(spec, exc.TaskCancelledError(
                spec.get("task_desc", "task")))   # _fail_task unpins args
            return
        # Successful completion: keep the spec as lineage (arg pins held)
        # so a lost result can be recomputed; unpin happens at lineage drop.
        if spec.get("dynamic_returns"):
            # BEFORE lineage retention: extends return_ids with the item
            # ids so reconstruction covers every streamed object
            self._finalize_gen(spec, reply)
        if spec.get("reconstructions_left", 0) > 0 or \
                spec["task_id"] in self._lineage_specs:
            # second clause: a reconstruction that just spent its LAST
            # budget unit replies here with the spec already retained —
            # _retain_lineage's in-table guard must run, not an unpin
            # (the pins belong to the lineage entry)
            self._retain_lineage(spec)
        else:
            self._unpin_args(spec)   # never retained: release arg pins now
        results = reply.get("results", {})
        for rid, data in results.items():
            # fire-and-forget: if every ref was dropped while the task was in
            # flight, storing the result would resurrect an unfreeable object
            if self.reference_counter.count(rid) > 0 or rid in self._owned:
                self.memory_store.put(rid, data)
        # returns listed in reply["stored"] live in the executor node's shm
        # store — record them in OUR directory (we own them); _fetch_bytes
        # and borrower queries resolve through it
        exec_node = reply.get("node")
        if exec_node:
            sizes = reply.get("stored_sizes", {})
            for rid in reply.get("stored", ()):
                self._loc_add(rid, exec_node, sizes.get(rid, 0))

    # --------------------------------------------------------------- actors

    def create_actor(self, class_hash: bytes, args, kwargs, *, options):
        actor_id = self._new_id()
        spec = {
            "class_hash": class_hash,
            "class_name": options.get("class_name", "Actor"),
            "args": ser.serialize((args, kwargs)),
            "resources": options.get("resources", {"CPU": 1.0}),
            "strategy": options.get("strategy"),
            "max_restarts": options.get("max_restarts", 0),
            "max_task_retries": options.get("max_task_retries", 0),
            "max_concurrency": options.get("max_concurrency", 1),
            "concurrency_groups": options.get("concurrency_groups") or {},
            "name": options.get("name"),
            "namespace": options.get("namespace", "default"),
            "lifetime": options.get("lifetime"),
            "get_if_exists": options.get("get_if_exists", False),
            "owner_addr": self.addr,
            "job_id": self.job_id,
            "runtime_env": self._normalize_runtime_env(
                options.get("runtime_env")),
        }
        _carry_cause(spec)
        reg = self.gcs.call("register_actor", actor_id=actor_id, spec=spec)
        if reg.get("existing"):
            return bytes.fromhex(reg["existing"]["ActorID"]), True
        import pickle

        self.gcs.call("kv_put", ns="actor_spec", key=actor_id,
                      value=pickle.dumps(spec))
        # Fire creation asynchronously — actor handles are usable immediately;
        # method calls block on ALIVE state.
        threading.Thread(target=self._drive_actor_creation,
                         args=(actor_id, spec), daemon=True).start()
        return actor_id, False

    def _drive_actor_creation(self, actor_id: bytes, spec: dict):
        try:
            target = self.raylet
            opened = None
            for hop in range(17):
                if hop == 16:
                    # saturated cluster: stop bouncing, queue on the current
                    # raylet (same escape valve as the lease path)
                    spec = dict(spec)
                    spec["strategy"] = dict(spec.get("strategy") or {})
                    spec["strategy"]["no_spill"] = True
                from ray_tpu._private.config import get_config

                reply = target.call(
                    "create_actor", actor_id=actor_id, spec=spec,
                    timeout=float(get_config(
                        "actor_creation_rpc_timeout_s")))
                if "granted" in reply:
                    if opened is not None:
                        opened.close()
                    return
                addr = tuple(reply["spillback"])
                if opened is not None:
                    opened.close()
                opened = target = RpcClient(addr, timeout=None)
            raise RuntimeError("actor creation spillback loop")
        except Exception as e:  # noqa: BLE001
            try:
                self.gcs.call_once("actor_failed", actor_id=actor_id,
                              reason=f"creation failed: {e}")
            except ConnectionLost:
                pass

    def submit_actor_task(self, actor_id: bytes, method_name: str, args,
                          kwargs, *, num_returns=1, max_task_retries=0,
                          task_desc=""):
        dynamic = num_returns in ("dynamic", "streaming")
        return_ids = [self._new_id()
                      for _ in range(1 if dynamic else num_returns)]
        spec = {
            "task_id": self._new_id(),
            "actor_id": actor_id,
            "method_name": method_name,
            "args": ser.serialize((args, kwargs)),
            "return_ids": return_ids,
            "owner_addr": self.addr,
            "caller_id": self.worker_id,
            "retries_left": max_task_retries,
            "task_desc": task_desc or f"actor method {method_name}",
            "job_id": self.job_id,
        }
        if dynamic:
            spec["dynamic_returns"] = True
            with self._lock:
                self._gen_streams[return_ids[0]] = _GenStream()
                # registered so _close_gen → cancel_task can find the
                # spec; q is None (actor path has no scheduling queue)
                self._ref_to_task[return_ids[0]] = (spec, None)
        from ray_tpu.util import tracing

        from ray_tpu._private.task_spec import validate_task_spec

        validate_task_spec(spec, actor=True)
        _carry_cause(spec)
        with tracing.submit_span(spec, spec["task_desc"]):
            self._pin_args(spec, args, kwargs)
            self._owned.update(return_ids)
            refs = [ObjectRef(rid, self.addr, self) for rid in return_ids]
            for rid in return_ids:
                self.memory_store.entry(rid)
            with self._lock:
                q = self._actor_queues.get(actor_id)
                if q is None:
                    q = _ActorQueue(self, actor_id, {})
                    self._actor_queues[actor_id] = q
            q.assign_seq(spec)   # in submission order, before going async
            threading.Thread(target=q.submit, args=(spec,),
                             daemon=True).start()
        return refs

    # ----------------------------------------------------- execution (worker)

    def _start_executor(self, n_threads: int):
        self._exec_queue = queue.Queue()
        for i in range(n_threads):
            t = threading.Thread(target=self._exec_loop, daemon=True,
                                 name=f"exec-{i}")
            t.start()
            self._exec_threads.append(t)

    # Hot-path dispatch policy for this process's RpcServer: push_task is
    # handled INLINE on the transport's reader/pump thread (it never
    # blocks — see rpc_push_task) and replies are DEFERRED (sent by
    # whichever thread finishes the task), so a task in flight parks no
    # dispatch thread. This is the split the reference gets from its C++
    # core worker: compiled transport + completion callbacks,
    # interpreter only for execution (core_worker.cc:2188).
    # ping is inline for LIVENESS, not speed: raylets probe lessees with
    # a short deadline (_gc_remote_lessee_leases), and a ping that must
    # win a GIL slot for a fresh dispatch thread under load can miss it —
    # the raylet then "reclaims" a live driver's leases, killing its
    # workers mid-task (observed as WorkerCrashedError storms in the
    # chaos suite).
    INLINE_RPC = frozenset({"push_task", "ping", "task_state",
                            "locate_object", "generator_item"})
    DEFERRED_RPC = frozenset({"push_task"})

    def rpc_push_task(self, conn, seq, spec: dict):
        """Runs inline on the transport pump — MUST NOT block. Normal
        tasks enqueue straight to the main-thread task loop (reference:
        core_worker.cc:2188 RunTaskExecutionLoop is the worker main
        thread; thread-hostile native libraries — pyarrow submodule
        imports — make main-thread execution load-bearing, see CI
        segfault note in serve_task_loop's history). Actor tasks and the
        rare pre-ready window hop to a thread because they gate on seq
        order / concurrency slots / startup events."""
        from ray_tpu._private.protocol import NO_REPLY

        if (spec.get("actor_id") is None and self._ready.is_set()
                and self._main_loop_running):
            if spec.get("inline_exec") and \
                    self._normal_exec_lock.acquire(blocking=False):
                # Caller declared the task pump-safe (never blocks, no
                # thread-hostile native imports): run it RIGHT HERE and
                # skip the main-thread queue handoff + wake entirely.
                # Non-blocking acquire: if the main loop is mid-task we
                # fall through to the queue rather than stall the pump.
                # interruptible=False: a force-cancel KeyboardInterrupt
                # aimed at this THREAD could detonate in the transport
                # reader loop after the task returns; inline tasks are
                # cancel-by-flag only (they are short by contract).
                from ray_tpu._private.protocol import _RemoteError

                try:
                    result = self._exec_task_body(spec,
                                                  interruptible=False)
                except BaseException as e:  # noqa: BLE001
                    result = _RemoteError(e)
                finally:
                    self._normal_exec_lock.release()
                conn.reply(seq, result)
                return NO_REPLY
            self._main_jobs.put(
                (spec, lambda result: conn.reply(seq, result)))
            return NO_REPLY
        threading.Thread(target=self._push_task_thread,
                         args=(conn, seq, spec), daemon=True).start()
        return NO_REPLY

    def _push_task_thread(self, conn, seq, spec: dict):
        from ray_tpu._private.protocol import _RemoteError

        try:
            result = self._push_task_blocking(conn, spec)
        except BaseException as e:  # noqa: BLE001 — ship errors back
            result = _RemoteError(e)
        conn.reply(seq, result)

    def _push_task_blocking(self, conn, spec: dict):
        self._ready.wait(30.0)
        if spec.get("actor_id") is not None and self.actor_id is not None:
            return self._execute_actor_task(spec, conn)
        if self.mode == "worker":
            # a lease can arrive between __init__ registering us and
            # worker_main entering the loop — wait out that window so the
            # FIRST task (likeliest to do native imports) isn't the one
            # that lands on a dispatch thread
            self._main_loop_started.wait(10.0)
        if self._main_loop_running:
            from ray_tpu._private.protocol import _Future

            fut = _Future()
            self._main_jobs.put((spec, fut.set))
            return fut.result(timeout=None)
        return self._execute_normal_task(spec)

    def serve_task_loop(self):
        """Run normal-task execution on the calling thread (the worker
        process's main thread). Each job is (spec, done) where done
        delivers the result — directly to the requester's connection for
        inline-dispatched tasks. Returns when the raylet connection dies."""
        import queue as _q

        self._main_loop_running = True
        self._main_loop_started.set()
        try:
            while not self.stopped:
                try:
                    spec, done = self._main_jobs.get(timeout=0.5)
                except _q.Empty:
                    if self.raylet.closed:
                        return
                    continue
                try:
                    done(self._execute_normal_task(spec))
                except BaseException as e:  # noqa: BLE001 — never wedge
                    from ray_tpu._private.protocol import _RemoteError

                    done(_RemoteError(e))
        finally:
            self._main_loop_running = False

    def _resolve_args(self, spec):
        blob = spec["args"]
        if blob == ser.serialize_empty_args():
            return (), {}        # constant no-arg frame: skip the parse
        inlined = spec.get("inlined")
        args, kwargs = ser.deserialize(blob, self)

        def resolve(v):
            if not isinstance(v, ObjectRef):
                return v
            if inlined is not None:
                data = inlined.get(v.id)
                if data is not None:
                    cached = self._inlined_value_cache.get(v.id)
                    if cached is not None:
                        return cached
                    value = ser.deserialize(data, self)
                    import numpy as _np

                    if isinstance(value, _np.ndarray):
                        value.setflags(write=False)   # plasma semantics
                        cacheable = True
                    else:
                        cacheable = isinstance(
                            value, (int, float, bool, str, bytes,
                                    type(None)))
                    if cacheable:
                        if len(self._inlined_value_cache) > 1024:
                            self._inlined_value_cache.clear()
                        self._inlined_value_cache[v.id] = value
                    return value
            return self.get(v)

        args = [resolve(a) for a in args]
        kwargs = {k: resolve(v) for k, v in kwargs.items()}
        return args, kwargs

    def _execute_normal_task(self, spec: dict) -> dict:
        task_id = spec["task_id"]
        if task_id in self._cancelled:
            self._cancelled.discard(task_id)
            return {"cancelled": True}
        with self._normal_exec_lock:
            return self._exec_task_body(spec)

    def _exec_task_body(self, spec: dict, interruptible: bool = True) -> dict:
        """Execution core; caller holds _normal_exec_lock (main loop via
        _execute_normal_task, or the pump's non-blocking inline_exec
        acquire). interruptible=False leaves _current_task_thread unset so
        force-cancel never aims an async exception at the transport pump."""
        task_id = spec["task_id"]
        if task_id in self._cancelled:       # cancelled while queued here
            self._cancelled.discard(task_id)
            return {"cancelled": True}
        self._current_task_id = task_id
        self._current_task_desc = spec.get("task_desc")
        self._current_task_thread = \
            threading.get_ident() if interruptible else None
        self._current_task_started = time.time()   # OOM victim ranking
        _events.task_event(task_id, "RUNNING",
                           desc=spec.get("task_desc"))
        import contextlib

        from ray_tpu._private.profiling import record_span

        try:
            from ray_tpu.util import tracing

            # skip the span generator entirely when no trace context
            # arrived and tracing is off here — two context managers per
            # task are measurable on the sync hot path
            trace_ctx, cause = _split_trace_ctx(spec)
            if trace_ctx is None and not tracing.is_enabled():
                trace_cm = contextlib.nullcontext()
            else:
                trace_cm = tracing.span(
                    f"execute {spec.get('task_desc', 'task')}",
                    "CONSUMER", trace_ctx,
                    {"task_id": task_id.hex()})
            with record_span("task", spec.get("task_desc", "task"),
                             {"task_id": task_id.hex()}, **cause), trace_cm:
                if "runtime_env" in spec or \
                        getattr(self, "_env_applied_key", None) is not None:
                    # the second clause REVERTS a previous task's overlay
                    # (env_vars/cwd/sys.path + pip-cache refcount) when
                    # this env-less task reuses the worker
                    self._apply_runtime_env(spec.get("runtime_env"))
                fn = self._load_function(spec["func_hash"])
                args, kwargs = self._resolve_args(spec)
                result = fn(*args, **kwargs)
            out = self._package_results(spec, result)
            _events.task_event(task_id, "FINISHED",
                               desc=spec.get("task_desc"))
            return out
        except BaseException as e:  # noqa: BLE001
            _events.task_event(task_id, "FAILED",
                               error=type(e).__name__,
                               desc=spec.get("task_desc"))
            return self._package_error(spec, e)
        finally:
            self._current_task_id = None
            self._current_task_desc = None
            self._current_task_thread = None
            self._current_task_started = None

    def rpc_task_state(self, conn):
        """Non-blocking probe of what this worker is running (inline —
        the raylet's OOM victim ranking queries it under memory
        pressure; the lease grant time it would otherwise use is the age
        of the LEASE, not of the current task)."""
        tid = getattr(self, "_current_task_id", None)
        return {"task_started_at": getattr(self, "_current_task_started",
                                           None),
                "task_id": tid.hex() if tid else None,
                "task_desc": getattr(self, "_current_task_desc", None)}

    def _execute_actor_task(self, spec: dict, conn=None) -> dict:
        # Per-caller ordering: DISPATCH tasks in seq order for each caller
        # (reference: actor_scheduling_queue.h client-side sequence numbers).
        # The gate orders entry into the FIFO concurrency semaphore, so
        # max_concurrency=1 executes strictly in submission order while
        # max_concurrency>1 pipelines without reordering starts. There is no
        # wall-clock skip-ahead: a successor waits however long its
        # predecessor runs; it only skips when the caller's connection is
        # dead (the predecessor can no longer arrive, and replies would go
        # nowhere anyway — advisor finding on the old 60s deadline).
        caller = f"{spec.get('caller_id', '')}:{spec.get('caller_epoch', 0)}"
        seq = spec.get("seq", 0)
        with self._seq_cond:
            while seq > self._next_seq_to_run.get(caller, 0):
                if conn is not None and not conn.alive:
                    break
                self._seq_cond.wait(timeout=0.5)
            # Resolve the gate INSIDE the seq block: if the lookup fails
            # (undeclared group — normally caught at creation time, api.py
            # _validate_concurrency_groups), the seq must still be consumed
            # or every later call from this caller wedges in the wait loop
            # above (advisor finding, round 3).
            gate_error = None
            try:
                sem = self._actor_semaphore_for(spec["method_name"])
                ticket = sem.enqueue()
            except ValueError as e:
                gate_error = e
            cur = self._next_seq_to_run.get(caller, 0)
            if seq >= cur:
                self._next_seq_to_run[caller] = seq + 1
            self._seq_cond.notify_all()
        if gate_error is not None:
            return self._package_error(spec, gate_error)
        return self._run_actor_method(spec, ticket, sem)

    def _actor_semaphore_for(self, method_name: str) -> FifoSemaphore:
        """The concurrency gate for a method: its declared group's, else
        the actor-wide default (reference: concurrency_group_manager.h)."""
        method = getattr(self._actor_instance, method_name, None)
        group = getattr(method, "__ray_concurrency_group__", None)
        if group is not None:
            sem = (getattr(self, "_actor_groups", None) or {}).get(group)
            if sem is None:
                # a misspelled/undeclared group silently serializing
                # through the default gate would be undebuggable — fail the
                # call instead (the reference validates at definition time)
                raise ValueError(
                    f"method {method_name!r} declares concurrency group "
                    f"{group!r}, but the actor was created with groups "
                    f"{sorted((getattr(self, '_actor_groups', None) or {}))}")
            return sem
        return self._actor_concurrency

    def _run_actor_method(self, spec: dict, ticket=None, sem=None) -> dict:
        import asyncio
        import inspect

        method_name = spec["method_name"]
        sem = sem if sem is not None else self._actor_concurrency
        acquired = False
        try:
            if method_name == "__ray_terminate__":
                threading.Thread(target=self._graceful_exit,
                                 daemon=True).start()
                return self._package_results(spec, None)
            method = getattr(self._actor_instance, method_name)
            # Actor-method dispatch is a fault-injection boundary too:
            # actor calls ride the deferred push_task RPC (replies are
            # written asynchronously), so the transport's on_reply hook
            # never sees them — consult the injector here with the ACTOR
            # method name. This is what lets a seeded schedule like
            # `kill_actor:rank1.next_result:#2` kill one deterministic
            # gang member mid-training (the rank-death chaos the gang-FT
            # tests replay), and lets slow_reply model a stalling actor.
            inj = _fi.ACTIVE
            if inj is not None:
                stall = inj.on_reply(method_name)
                if stall:
                    time.sleep(stall)
            args, kwargs = self._resolve_args(spec)
            # concurrency gate: the method's group semaphore (or the
            # actor-wide default, 1 slot) admits executions in dispatch
            # order (reference: concurrency_group_manager.h).
            sem.wait(ticket)
            acquired = True
            _events.task_event(spec["task_id"], "RUNNING",
                               desc=spec.get("task_desc"),
                               actor_id=(self.actor_id.hex()
                                         if self.actor_id else None))
            from ray_tpu._private.profiling import record_span

            from ray_tpu.util import tracing

            trace_ctx, cause = _split_trace_ctx(spec)
            try:
                with record_span(
                        "actor_task",
                        spec.get("task_desc", f"actor.{method_name}"),
                        {"actor_id": (self.actor_id.hex()
                                      if self.actor_id else "")},
                        **cause), \
                     tracing.span(
                         f"execute {spec.get('task_desc', method_name)}",
                         "CONSUMER", trace_ctx,
                         {"task_id": spec["task_id"].hex()}):
                    if inspect.iscoroutinefunction(method):
                        fut = asyncio.run_coroutine_threadsafe(
                            method(*args, **kwargs),
                            self._ensure_async_loop())
                        result = fut.result()
                    else:
                        result = method(*args, **kwargs)
                    if spec.get("dynamic_returns"):
                        # drain INSIDE the concurrency slot: the generator
                        # body is actor code and must not overlap the next
                        # call at max_concurrency=1
                        result = self._package_results(spec, result)
            finally:
                sem.release()
            if spec.get("dynamic_returns"):
                _events.task_event(spec["task_id"], "FINISHED",
                                   desc=spec.get("task_desc"))
                return result
            # package BEFORE recording FINISHED (matching the plain-task
            # path): an unserializable result must yield FAILED alone,
            # not a FINISHED→FAILED pair for one task
            out = self._package_results(spec, result)
            _events.task_event(spec["task_id"], "FINISHED",
                               desc=spec.get("task_desc"))
            return out
        except BaseException as e:  # noqa: BLE001
            _events.task_event(spec["task_id"], "FAILED",
                               error=type(e).__name__,
                               desc=spec.get("task_desc"))
            return self._package_error(spec, e)
        finally:
            if not acquired:
                sem.cancel(ticket)

    def _ensure_async_loop(self):
        import asyncio

        if self._async_loop is None:
            loop = asyncio.new_event_loop()
            threading.Thread(target=loop.run_forever, daemon=True,
                             name="actor-async-loop").start()
            self._async_loop = loop
        return self._async_loop

    def _package_results(self, spec: dict, result) -> dict:
        if spec.get("dynamic_returns"):
            return self._package_generator(spec, result)
        num_returns = len(spec["return_ids"])
        if num_returns == 1:
            values = [result]
        elif num_returns == 0:
            values = []
        else:
            values = list(result)
            if len(values) != num_returns:
                return self._package_error(spec, ValueError(
                    f"task declared num_returns={num_returns} but returned "
                    f"{len(values)} values"))
        inline: dict[bytes, bytes] = {}
        stored: list[bytes] = []
        sizes: dict[bytes, int] = {}
        for rid, value in zip(spec["return_ids"], values):
            if value is None:
                inline[rid] = ser.serialize_none()   # cached frame
                continue
            parts = ser.serialize_parts(value)
            size = ser.parts_size(parts)
            if size <= INLINE_RESULT_LIMIT:
                inline[rid] = ser.assemble_parts(parts)
            else:
                # parts stream straight into the segment/spill file —
                # no assembled intermediate copy for big returns
                with _ma.default_tag("task_return",
                                     owner=spec.get("task_id",
                                                    b"").hex()[:16]):
                    self.store.put_parts(rid, parts)
                stored.append(rid)
                sizes[rid] = size
        # The task REPLY doubles as the location announcement: the owner
        # records (rid → this node) in its directory on receipt — no
        # directory RPC at all on the return path. (node omitted when
        # nothing was stored: it's reply-size dead weight per task.)
        if not stored:
            return {"results": inline, "stored": stored}
        return {"results": inline, "stored": stored, "stored_sizes": sizes,
                "node": self._my_node}

    def _package_generator(self, spec: dict, result) -> dict:
        """Drain a dynamic-returns task's iterator, announcing each item
        to the owner AS IT IS PRODUCED so a streaming consumer can start
        before the task finishes (reference: _raylet.pyx:168
        ObjectRefGenerator; streaming-generator item pushes in
        task_manager's HandleReportGeneratorItemReturns).

        Item ids derive deterministically from (gen_id, index) so a
        lineage re-execution regenerates the SAME ids and announcements
        land idempotently. Announcements are pipelined call_asyncs; the
        final reply waits for their acks, so by the time the owner sees
        the task reply every item it carries is already registered."""
        from ray_tpu._private.object_ref import ObjectRefGenerator

        gen_id = spec["return_ids"][0]
        owner = spec.get("owner_addr")
        local = not owner or tuple(owner) == self.addr
        rids: list[bytes] = []
        stored: list[bytes] = []
        sizes: dict[bytes, int] = {}
        acks = []
        error = None
        try:
            iterator = iter(result)
        except TypeError:
            return self._package_error(spec, TypeError(
                f"num_returns='dynamic' task returned non-iterable "
                f"{type(result).__name__}"))
        while True:
            if spec["task_id"] in self._cancelled:
                self._cancelled.discard(spec["task_id"])
                self._await_gen_acks(acks)
                return {"cancelled": True}
            try:
                value = next(iterator)
            except StopIteration:
                break
            except BaseException as e:  # noqa: BLE001 — partial stream
                error = e
                break
            index = len(rids)
            rid = _derive_item_id(gen_id, index)
            item_parts = ser.serialize_parts(value)
            size = ser.parts_size(item_parts)
            item = {"gen_id": gen_id, "index": index, "object_id": rid}
            if size <= INLINE_RESULT_LIMIT:
                item["data"] = ser.assemble_parts(item_parts)
            else:
                with _ma.default_tag("task_return",
                                     owner=spec.get("task_id",
                                                    b"").hex()[:16]):
                    self.store.put_parts(rid, item_parts)
                stored.append(rid)
                sizes[rid] = size
                item["node"] = self._my_node
                item["size"] = size
            if local:
                self._gen_item_local(**item)
            else:
                try:
                    acks.append(self._owner_client(tuple(owner))
                                .call_async("generator_item", **item))
                except Exception:
                    pass   # owner gone: the reply path will fail too
            rids.append(rid)
        self._await_gen_acks(acks)
        if error is not None:
            # partial stream: the owner already holds items 0..n-1; the
            # reply's error payload finalizes the stream so iteration
            # yields the produced prefix, then raises
            return self._package_error(spec, error)
        gen = ObjectRefGenerator(gen_id, owner, rids)
        reply = {"results": {gen_id: ser.serialize(gen)},
                 "stored": stored, "gen_count": len(rids)}
        if stored:
            reply["stored_sizes"] = sizes
            reply["node"] = self._my_node
        return reply

    @staticmethod
    def _await_gen_acks(acks):
        for fut in acks:
            try:
                fut.result(timeout=30.0)
            except Exception:
                pass   # owner died mid-stream; reply delivery fails too

    def _gen_item_local(self, gen_id: bytes, index: int, object_id: bytes,
                        data: bytes | None = None, node: dict | None = None,
                        size: int = 0):
        """Owner-side registration of one generator item (also the
        executor fast path when the owner is this process)."""
        # Atomic with _free_object's stream pop (one lock): a late item
        # racing the generator's release must either land before the
        # cleanup snapshot or not register at all — registering after it
        # would leak the object for the life of the worker.
        with self._lock:
            stream = self._gen_streams.get(gen_id)
            if stream is None:
                return   # generator already freed: drop late items
            self._owned.add(object_id)
            if data is not None:
                self.memory_store.put(object_id, data)
            elif node is not None:
                self._loc_add(object_id, node, size)
            stream.add(index, object_id)

    def rpc_generator_item(self, conn, gen_id: bytes, index: int,
                           object_id: bytes, data: bytes | None = None,
                           node: dict | None = None, size: int = 0):
        """INLINE: dict inserts + a condition notify only."""
        self._gen_item_local(gen_id, index, object_id, data, node, size)
        return True

    # ---- owner-side stream consumption (ObjectRefGenerator backing) -------

    def _gen_next(self, gen_id: bytes, index: int,
                  timeout: float | None = None):
        """Block until item `index` of the stream exists; returns its
        object id, None past the end, or raises the task's error once
        the produced prefix is consumed."""
        with self._lock:
            stream = self._gen_streams.get(gen_id)
        if stream is None:
            raise exc.RayError(f"unknown generator {gen_id.hex()}")
        deadline = None if timeout is None else time.time() + timeout
        with stream.cond:
            while True:
                rid = stream.items.get(index)
                if rid is not None:
                    return rid
                if stream.total is not None and index >= stream.total:
                    return None
                if stream.error is not None:
                    value, _meta = ser.deserialize(stream.error, self,
                                                   with_meta=True)
                    raise value
                if stream.closed:
                    return None
                wait_t = 0.5 if deadline is None else min(
                    0.5, max(0.0, deadline - time.time()))
                if deadline is not None and time.time() > deadline:
                    raise exc.GetTimeoutError(
                        f"generator item {index} not produced in time")
                stream.cond.wait(wait_t)

    def _gen_total(self, gen_id: bytes):
        with self._lock:
            stream = self._gen_streams.get(gen_id)
        return None if stream is None else stream.total

    def _close_gen(self, gen_ref):
        """Consumer closed a streaming generator early: cancel the
        producer and wake any blocked iterators."""
        with self._lock:
            stream = self._gen_streams.get(gen_ref.id)
        if stream is None:
            return
        with stream.cond:
            already_done = stream.total is not None or \
                stream.error is not None
            stream.closed = True
            stream.cond.notify_all()
        if not already_done:
            try:
                self.cancel_task(gen_ref, force=False)
            except Exception:
                pass

    def _finalize_gen(self, spec: dict, reply: dict | None,
                      error: BaseException | bytes | None = None):
        """Resolve a dynamic task's stream from its final reply (count on
        success, error payload on failure/cancel). On success the item
        ids join the spec's return_ids so lineage reconstruction covers
        them (re-execution re-derives the same ids)."""
        gen_id = spec["return_ids"][0]
        with self._lock:
            stream = self._gen_streams.get(gen_id)
        if stream is None:
            return
        if error is not None:
            data = error if isinstance(
                error, (bytes, bytearray, memoryview)) else \
                ser.serialize_error(error, spec.get("task_desc", "task"))
            stream.fail(data)
            return
        count = reply.get("gen_count")
        if count is None:    # task failed: results[gen_id] is the error
            stream.fail(reply.get("results", {}).get(gen_id))
            return
        item_ids = [_derive_item_id(gen_id, i) for i in range(count)]
        self._owned.update(item_ids)
        if spec.get("_gen_finalized") is None:
            spec["_gen_finalized"] = True
            spec["return_ids"] = list(spec["return_ids"]) + item_ids
        # Backfill any index whose announcement got lost with a dropped
        # owner connection: the ids re-derive, so the consumer still gets
        # its ref; if the item was inline its data died with the push, so
        # resolve it to ObjectLostError — a loud get() failure instead of
        # _gen_next blocking forever on a hole in the stream.
        with stream.cond:
            missing = [(i, rid) for i, rid in enumerate(item_ids)
                       if i not in stream.items]
            for i, rid in missing:
                stream.items[i] = rid
        for _i, rid in missing:
            if not self.memory_store.contains_resolved(rid):
                nodes, _size = self._loc_snapshot(rid)
                if not nodes:
                    self.memory_store.put(rid, ser.serialize_error(
                        exc.ObjectLostError(rid.hex()),
                        spec.get("task_desc", "task")))
        stream.finish(count)

    def _package_error(self, spec: dict, error: BaseException) -> dict:
        if isinstance(error, KeyboardInterrupt):
            return {"cancelled": True}
        data = ser.serialize_error(error, spec.get("task_desc", "task"))
        return {"results": {rid: data for rid in spec["return_ids"]},
                "stored": []}

    def _exec_loop(self):
        while not self.stopped:
            time.sleep(1)  # tasks execute in RPC handler threads (v1)

    # -- become an actor ------------------------------------------------------

    def rpc_become_actor(self, conn, actor_id: bytes, spec: dict,
                         timeout: float = 60.0):
        self._ready.wait(30.0)
        self.actor_id = actor_id
        self._actor_spec = spec
        self._actor_concurrency = FifoSemaphore(
            max(1, int(spec.get("max_concurrency", 1) or 1)))
        # named concurrency groups: independent FIFO gates per group
        # (reference: transport/concurrency_group_manager.h — methods
        # declared in a group don't contend with the default group)
        self._actor_groups = {
            name: FifoSemaphore(max(1, int(n)))
            for name, n in (spec.get("concurrency_groups") or {}).items()
        }
        try:
            self._apply_runtime_env(spec.get("runtime_env"))
        except BaseException as e:  # noqa: BLE001 — env setup is fatal
            self.gcs.call_once("actor_failed", actor_id=actor_id,
                          reason=f"runtime_env setup failed: {e}")
            raise
        cls = self._load_function(spec["class_hash"])
        args, kwargs = ser.deserialize(spec["args"], self)
        args = [self.get(a) if isinstance(a, ObjectRef) else a for a in args]
        kwargs = {k: self.get(v) if isinstance(v, ObjectRef) else v
                  for k, v in kwargs.items()}
        try:
            self._actor_instance = cls(*args, **kwargs)
        except BaseException as e:  # noqa: BLE001
            self.gcs.call_once("actor_failed", actor_id=actor_id,
                          reason=f"__init__ raised: "
                                 f"{type(e).__name__}: {e}")
            raise
        self.gcs.call("actor_started", actor_id=actor_id, addr=self.addr,
                      node_id=self.node_id)
        return True

    def _graceful_exit(self):
        time.sleep(0.1)
        try:
            self.gcs.call("actor_exited", actor_id=self.actor_id)
        except ConnectionLost:
            pass
        os._exit(0)

    def rpc_exit_worker(self, conn):
        os._exit(0)

    def rpc_cancel_task(self, conn, task_id: bytes, force: bool = False):
        self._cancelled.add(task_id)
        if self._current_task_id == task_id:
            if force:
                # A blocking C call (sleep, IO, XLA) can't be interrupted by
                # an async exception — kill the worker, as the reference does
                # for force-cancel (core_worker.cc HandleCancelTask).
                os._exit(137)
            ident = self._current_task_thread
            if ident is not None:
                import ctypes

                ctypes.pythonapi.PyThreadState_SetAsyncExc(
                    ctypes.c_long(ident), ctypes.py_object(KeyboardInterrupt))
        return True

    # ---------------------------------------------- collective p2p mailbox
    # Direct worker-to-worker data plane for ray_tpu.util.collective's host
    # backend: ring/tree collectives push chunks straight between member
    # processes instead of funnelling every tensor through one rendezvous
    # actor (the reference's gloo backend is likewise peer-to-peer,
    # gloo_collective_group.py; the named actor only rendezvouses metadata).
    # Two ingest paths: rpc_col_push (legacy sync request/reply, payload
    # pickled in the control frame) and rpc_col_push_frame (pipelined
    # one-way PUSH_OOB, payload as a zero-copy OobFrame drawn from the
    # per-(group, nbytes) receive-buffer pool below).

    def col_push_local(self, key: tuple, data):
        with self._col_cond:
            # stale check must happen under the same lock col_set_epoch
            # sweeps under — checked outside, a frame could pass the check
            # concurrently with the sweep and then park AFTER it, stranding
            # its backing shm segment past the reclaim the sweep promised
            if self._col_stale_epoch(key):
                stale = True
            else:
                # traffic from a live incarnation: park it for col_take
                stale = False
                old = self._col_mailbox.get(key)
                self._col_mailbox[key] = data
                self._col_cond.notify_all()
        if stale:
            # traffic from a previous incarnation of this group (the full
            # key carries the incarnation epoch at slot 1): a rebuilt gang
            # must never consume a dead gang's frames — reject instead of
            # parking it where it could masquerade as this epoch's payload
            self._note_stale_epoch(key)
            self._discard_col_msg(data)
            return
        if old is not None and old is not data:
            # a redelivered duplicate (fault plane `dup`, peer retry)
            # overwrote a message nobody consumed — reclaim its backing
            self._discard_col_msg(old, replacement=data)

    def _col_stale_epoch(self, key: tuple) -> bool:
        """True when `key` belongs to an OLDER incarnation of its group
        than the one this process last joined. Only a strictly older
        epoch is rejected: a NEWER one means a peer already joined the
        next incarnation this process hasn't rejoined yet — parking that
        frame is harmless (col_set_epoch's purge or group destroy sweeps
        it if this process never catches up)."""
        if len(key) < 2 or not isinstance(key[1], int):
            return False
        cur = self._col_epochs.get(key[0])
        return cur is not None and key[1] < cur

    def _note_stale_epoch(self, key: tuple):
        from ray_tpu._private import telemetry as _tm

        if _tm.ENABLED:
            try:
                _tm.counter_inc("ray_tpu_collective_stale_epoch_total",
                                tags={"group": str(key[0])})
            except Exception:
                pass

    def col_set_epoch(self, group: str, epoch: int):
        """Register this process's current incarnation epoch for one
        collective group (called at group join). Frames/shm notifies
        stamped with an older epoch are rejected at ingest from now on;
        anything the dead incarnation already parked here — mailbox
        entries AND stranded shm segments (their 4-byte epoch tag rides
        the object id, see col_oid_prefix) — is swept immediately, so a
        rebuilt gang under the same name starts from clean state even
        when the previous gang died too abruptly to destroy itself."""
        with self._col_cond:
            prev = self._col_epochs.get(group)
            self._col_epochs[group] = epoch
            if prev is not None and epoch < prev:
                # never move backwards (a late joiner re-announcing an
                # older incarnation must not resurrect swept traffic)
                self._col_epochs[group] = prev
                return
            self._col_poison.pop(group, None)   # new incarnation: clean
            stale = [k for k in self._col_mailbox
                     if k and k[0] == group and len(k) > 1
                     and isinstance(k[1], int) and k[1] < epoch]
            dropped = [self._col_mailbox.pop(k) for k in stale]
        for msg in dropped:
            self._note_stale_epoch((group, 0))
            self._discard_col_msg(msg)
        # sweep the dead epochs' stranded shm segments: group-prefixed
        # oids whose epoch tag differs from the new epoch's
        try:
            prefix = col_oid_prefix(group)
            tag = col_epoch_tag(epoch)
            for oid, _size in self.store.list_objects():
                if oid.startswith(prefix) and oid[6:10] != tag:
                    self.store.delete_ephemeral(oid)
        except Exception:
            pass

    def col_poison_local(self, group: str, dead_ranks, reason: str,
                         epoch: int | None = None):
        """Poison one collective group in this process: every pending
        col_take wakes and raises CollectiveGroupError immediately, and
        future takes fail the same way until the group is destroyed or
        rejoined under a new epoch. Idempotent; first record wins (it
        names the original dead rank). An epoch-stamped poison from an
        incarnation this process has already left is ignored — a stale
        HostGroup's on_close handler firing after a rejoin would
        otherwise kill the healthy successor gang."""
        with self._col_cond:
            if epoch is not None:
                cur = self._col_epochs.get(group)
                if cur is not None and epoch < cur:
                    return False
            if group in self._col_poison:
                return False
            self._col_poison[group] = (tuple(dead_ranks), str(reason))
            self._col_cond.notify_all()
        from ray_tpu._private import telemetry as _tm

        if _tm.ENABLED:
            try:
                _tm.counter_inc("ray_tpu_collective_groups_poisoned_total",
                                tags={"group": group})
            except Exception:
                pass
        return True

    def rpc_col_poison(self, conn, group: str, dead_ranks, reason: str,
                       epoch: int | None = None):
        """Group-poison ingest (pushed by the group's rendezvous actor on
        member death, or by a member that directly observed a peer's
        connection drop). The epoch guard lives in col_poison_local,
        under the mailbox lock."""
        self.col_poison_local(group, tuple(dead_ranks), reason,
                              epoch=epoch)
        return True

    def col_poisoned(self, group: str):
        """(dead_ranks, reason) if `group` is poisoned in this process."""
        with self._col_cond:
            return self._col_poison.get(group)

    def _discard_col_msg(self, msg, replacement=None):
        """Reclaim an unconsumed mailbox message's backing resource: a
        transport frame's pooled buffer, or a shm segment's store
        object. A duplicate-delivered shm ref (fault plane `dup`) is a
        DISTINCT ColShmRef wrapping the SAME object — deleting the old
        ref's object would tear the store out from under the surviving
        one, so same-oid replacements skip the delete."""
        if isinstance(msg, ColShmRef):
            if isinstance(replacement, ColShmRef) \
                    and replacement.oid == msg.oid:
                return
            try:
                self.store.delete_ephemeral(msg.oid)
            except Exception:
                pass
        else:
            _release_col_msg(msg)

    def col_purge(self, group: str) -> int:
        """Drop every mailbox entry belonging to one collective group
        (keys lead with the group name). Called on group destroy: a
        stale message from a dead incarnation (e.g. a peer's payload
        that landed after an op timeout) would otherwise trip the next
        incarnation's seq validation as a phantom NEWER seq."""
        with self._col_cond:
            stale = [k for k in self._col_mailbox if k and k[0] == group]
            dropped = [self._col_mailbox.pop(k) for k in stale]
            self._col_poison.pop(group, None)
            self._col_epochs.pop(group, None)
        for msg in dropped:
            self._discard_col_msg(msg)
        COL_RECV_POOL.purge(group)
        # sweep STRANDED shm segments too: a dropped col_push_shm notify
        # (or a receiver that died first) leaves the object in the store
        # with no mailbox ref anywhere — reachable only via its group-
        # tagged id prefix
        try:
            prefix = col_oid_prefix(group)
            for oid, _size in self.store.list_objects():
                if oid.startswith(prefix):
                    self.store.delete_ephemeral(oid)
        except Exception:
            pass
        return len(stale)

    def rpc_col_push(self, conn, key: tuple, data):
        self.col_push_local(tuple(key), data)
        return True

    def rpc_col_push_frame(self, conn, key: tuple, frame):
        """PUSH_OOB ingest (runs inline on the transport reader/pump —
        a mailbox store, never blocks). `frame` is the transport's
        OobFrame; the taker deserializes the view in place and releases
        the buffer back to the pool."""
        self.col_push_local(tuple(key), frame)

    def rpc_col_push_shm(self, conn, key: tuple, oid: bytes, nbytes: int):
        """Same-node segment hand-off: the payload already sits in the
        node's shared-memory store under `oid` (the sender put it
        there); only this tiny reference crosses the socket. The taker
        maps the object zero-copy and deletes it once consumed."""
        self.col_push_local(tuple(key), ColShmRef(oid, nbytes))

    def rpc_col_meta(self, conn):
        """Peer identity for the collective data plane: ranks with the
        same node_id share this node's shm store, so segments can move
        as store references instead of socket bytes."""
        return {"node_id": self.node_id}

    def col_take(self, key: tuple, timeout: float = 300.0,
                 seq_pos: int | None = None):
        """Blocking take of one collective message.

        ``seq_pos`` (index of the op sequence number within ``key``)
        arms receiver-side sequence validation: if a message for the
        SAME channel (identical key except the seq slot) carrying a
        NEWER seq shows up while ours never does, the group's op
        ordering has desynchronized — raise a clear mismatch error
        immediately instead of hanging until the watchdog timeout or
        silently pairing wrong payloads. Only a newer seq is proof:
        per-peer delivery is in-order, so a newer message implies ours
        would already have arrived. An OLDER same-channel seq is
        ambiguous (a redelivered duplicate — e.g. the fault plane's
        ``dup`` action — looks identical to a restarted peer), so it
        never raises; it only annotates the eventual timeout. The exact
        key is always preferred when present."""
        key = tuple(key)

        def _same_channel(k):
            return (len(k) == len(key) and k[:seq_pos] == key[:seq_pos]
                    and k[seq_pos + 1:] == key[seq_pos + 1:]
                    and k[seq_pos] != key[seq_pos])

        def _newer(k):
            return _same_channel(k) and k[seq_pos] > key[seq_pos]

        group = key[0] if key else None

        def _ready():
            if group in self._col_poison:
                return True
            if key in self._col_mailbox:
                return True
            return seq_pos is not None and any(
                _newer(k) for k in self._col_mailbox)

        with self._col_cond:
            ok = self._col_cond.wait_for(_ready, timeout=timeout)
            poison = self._col_poison.get(group)
            if poison is not None:
                # a member died: fail fast with the culprit named instead
                # of hanging out the rest of the op timeout (the group is
                # unusable until it is destroyed and rebuilt)
                dead_ranks, reason = poison
                raise exc.CollectiveGroupError(str(group), dead_ranks,
                                               reason)
            if not ok:
                hint = ""
                if seq_pos is not None:
                    stale = sorted(k[seq_pos] for k in self._col_mailbox
                                   if _same_channel(k))
                    if stale:
                        hint = (f" (same-channel messages with older seq "
                                f"{stale} are waiting — a restarted peer "
                                f"resets its op counters)")
                raise TimeoutError(
                    f"collective recv timed out on {key}{hint}")
            if key in self._col_mailbox:
                return self._col_mailbox.pop(key)
            newer = sorted(k[seq_pos] for k in self._col_mailbox
                           if _newer(k))
            raise exc.CollectiveSeqMismatchError(
                f"collective sequence mismatch on channel "
                f"{key[:seq_pos] + key[seq_pos + 1:]}: this rank expects "
                f"seq {key[seq_pos]} but the peer already sent seq "
                f"{newer} — the group's op ordering has desynchronized "
                f"(every rank must issue collective calls in the same "
                f"order; a restarted member resets its counters)")

    def rpc_ping(self, conn):
        return "pong"

    def rpc_actor_state(self, conn):
        return {"actor_id": self.actor_id.hex() if self.actor_id else None,
                "num_pending": self._exec_queue.qsize()
                if self._exec_queue else 0}

    # --------------------------------------------------------------- shutdown

    def shutdown(self):
        self.stopped = True
        _ma.stop_periodic_sweep()
        self._free_queue.put(None)   # unblock the ref reaper
        self.reference_counter.shutdown()   # and the refcount drainer
        self._server.stop()
        with self._owner_client_lock:
            owner_clients = list(self._owner_clients.values())
            self._owner_clients.clear()
        for c in (self.gcs, self.raylet, *owner_clients):
            try:
                c.close()
            except Exception:
                pass
        try:
            self.store.close()
        except Exception:
            pass


class ColShmRef:
    """Mailbox marker for a collective segment parked in the node's shm
    store (see rpc_col_push_shm)."""

    __slots__ = ("oid", "nbytes")

    def __init__(self, oid: bytes, nbytes: int):
        self.oid = oid
        self.nbytes = nbytes


def col_oid_prefix(group: str) -> bytes:
    """6-byte object-id prefix tagging one group's shm segments, so a
    stranded segment (its notify dropped / receiver died before the
    take) is findable: group destroy sweeps the node store for this
    prefix and deletes leftovers — without it, an untagged orphan would
    occupy the bounded segment until eviction pressure."""
    return b"\xc0" + hashlib.blake2b(group.encode(),
                                     digest_size=5).digest()


def col_epoch_tag(epoch: int) -> bytes:
    """4-byte incarnation-epoch tag following the group prefix in a
    collective shm object id (layout: group-prefix(6) + epoch(4) +
    rank(2) + counter(4) — 16 bytes). Lets col_set_epoch sweep a DEAD incarnation's
    stranded segments — including incarnations this process never knew —
    by deleting group-prefixed objects whose tag differs from the live
    epoch's, without ever touching the live epoch's in-flight segments."""
    return (int(epoch) % (1 << 32)).to_bytes(4, "big")


def _release_col_msg(msg):
    release = getattr(msg, "release", None)
    if release is not None:
        try:
            release()
        except Exception:
            pass


class _ColBufferPool:
    """Receive-buffer pool for the pipelined collective data path,
    keyed (group, nbytes). The transport's PUSH_OOB reader acquires a
    buffer per incoming segment; the host backend's take side releases
    it after reducing — steady-state allreduce cycles the same few
    buffers with zero per-step allocations. Bounded per key and in
    total so a burst (or a leak) degrades to plain allocation instead
    of growing forever; purge(group) drops a destroyed group's buffers.
    Process-wide (in-process test clusters share it), like the
    transports themselves."""

    MAX_PER_KEY = 8
    MAX_TOTAL_BYTES = 256 * 1024 * 1024

    def __init__(self):
        self._lock = threading.Lock()
        self._free: dict[tuple, list] = {}
        self._bytes = 0

    def acquire(self, key: tuple, nbytes: int):
        with self._lock:
            bucket = self._free.get(key)
            if bucket:
                self._bytes -= nbytes
                return bucket.pop()
        return bytearray(nbytes)

    def release(self, key: tuple, buf):
        nbytes = len(buf)
        with self._lock:
            bucket = self._free.setdefault(key, [])
            if (len(bucket) < self.MAX_PER_KEY
                    and self._bytes + nbytes <= self.MAX_TOTAL_BYTES):
                bucket.append(buf)
                self._bytes += nbytes

    def purge(self, group: str):
        with self._lock:
            for key in [k for k in self._free if k[0] == group]:
                self._bytes -= sum(len(b) for b in self._free.pop(key))

    def stats(self) -> dict:
        with self._lock:
            return {"keys": len(self._free), "bytes": self._bytes,
                    "buffers": sum(len(v) for v in self._free.values())}


COL_RECV_POOL = _ColBufferPool()

# Hand the transports the pool: PUSH_OOB bodies tagged with a pool hint
# (the collective group name) are received straight into recycled
# buffers instead of fresh allocations (pure-Python transport; the
# native C core allocates in C and release() no-ops there).
from ray_tpu._private import protocol as _protocol  # noqa: E402

_protocol.set_oob_buffer_pool(COL_RECV_POOL)


def _carry_cause(spec: dict):
    """Put the submitting thread's live timeline span (and its run) into
    the spec's ``trace_ctx`` slot, so that the spans of the execution name
    it as their parent. The slot is util.tracing's; its own keys
    (``trace_id``, ``parent_span_id``) join these only where that tracing
    is on, and nothing here switches it on."""
    cause = _profiling.cause()
    if cause is not None:
        spec["trace_ctx"] = cause


def _split_trace_ctx(spec: dict) -> tuple:
    """A spec's ``trace_ctx`` as (util.tracing's context or None, the
    timeline's ``parent=`` / ``run=``)."""
    ctx = spec.get("trace_ctx")
    if not ctx:
        return None, {}
    cause = ({"parent": ctx["cause"], "run": ctx.get("run")}
             if "cause" in ctx else {})
    return (ctx if "trace_id" in ctx else None), cause


def _freeze(obj):
    if obj is None:
        return None
    if isinstance(obj, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in obj.items()))
    if isinstance(obj, (list, tuple)):
        return tuple(_freeze(v) for v in obj)
    return obj


_current_worker: CoreWorker | None = None
_current_worker_lock = threading.Lock()


def current_worker() -> CoreWorker | None:
    return _current_worker


def set_current_worker(worker: CoreWorker | None):
    global _current_worker
    with _current_worker_lock:
        _current_worker = worker
