"""Public exception types.

Mirrors the reference's error taxonomy (python/ray/exceptions.py in the
reference tree): user-code errors wrap the original traceback, system
errors describe which component died.
"""
from __future__ import annotations


class RayTpuError(Exception):
    """Base class for all framework errors."""


class TaskError(RayTpuError):
    """A task raised an exception; re-raised at `get()` with the remote
    traceback attached. If the original exception pickled cleanly it is
    available as `.cause` (and raised `from` it)."""

    def __init__(self, cause_cls_name: str, traceback_str: str,
                 cause: BaseException | None = None, task_desc: str = ""):
        self.cause_cls_name = cause_cls_name
        self.traceback_str = traceback_str
        self.cause = cause
        self.task_desc = task_desc
        where = f" in {task_desc}" if task_desc else ""
        super().__init__(
            f"{cause_cls_name} raised{where}:\n{traceback_str}")
        if cause is not None:
            self.__cause__ = cause

    def __reduce__(self):
        try:
            import pickle

            pickle.dumps(self.cause)
            cause = self.cause
        except Exception:
            cause = None
        return (type(self), (self.cause_cls_name, self.traceback_str,
                             cause, self.task_desc))


class WorkerCrashedError(RayTpuError):
    """The worker executing the task died unexpectedly (analog of the
    reference's WORKER_DIED error type, common.proto ErrorType)."""


class ActorDiedError(RayTpuError):
    def __init__(self, actor_id_hex: str = "", reason: str = ""):
        self.actor_id_hex = actor_id_hex
        self.reason = reason
        super().__init__(f"Actor {actor_id_hex} died: {reason or 'unknown cause'}")


class ActorUnavailableError(RayTpuError):
    """Actor is restarting; the call may be retried."""


class ObjectLostError(RayTpuError):
    """All copies of an object were lost and reconstruction failed/disabled
    (reference: object_recovery_manager.h)."""

    def __init__(self, object_id_hex: str):
        self.object_id_hex = object_id_hex
        super().__init__(f"Object {object_id_hex} lost and could not be reconstructed")


class ObjectStoreFullError(RayTpuError):
    pass


class OutOfMemoryError(RayTpuError):
    """Raised when the memory monitor kills a task to protect the node
    (reference: memory_monitor.h:88, worker_killing_policy.h:30)."""


class GetTimeoutError(RayTpuError, TimeoutError):
    pass


class TaskCancelledError(RayTpuError):
    pass


class RuntimeEnvSetupError(RayTpuError):
    pass


class PlacementGroupUnschedulableError(RayTpuError):
    pass


class CrossLanguageError(RayTpuError):
    pass


class CollectiveSeqMismatchError(RayTpuError):
    """A collective recv found a message for the same (group, phase,
    step, peer) channel carrying a DIFFERENT op sequence number than
    expected: the group's op ordering has desynchronized (e.g. a rank
    restarted and reset its counters, or ranks issued collectives in
    different orders). Raised instead of the old behavior — hanging
    until the op timeout or silently pairing the wrong payloads."""


class CollectiveGroupError(RayTpuError):
    """The collective group was poisoned: a member rank died (or the
    group was torn down) while ops were pending. Raised by pending and
    future collective calls on every surviving rank — naming the dead
    rank(s) — well under the collective op timeout, instead of letting
    each rank hang until its own watchdog fires. The group is unusable;
    recovery is a gang restart (destroy + re-create the group, which
    mints a new incarnation epoch so stale traffic is fenced off)."""

    def __init__(self, group: str, dead_ranks=(), reason: str = ""):
        self.group = group
        self.dead_ranks = tuple(sorted(set(int(r) for r in dead_ranks)))
        self.reason = reason
        ranks = (f" (dead ranks: {list(self.dead_ranks)})"
                 if self.dead_ranks else "")
        super().__init__(
            f"collective group {group!r} poisoned{ranks}: "
            f"{reason or 'member death'}")

    def __reduce__(self):
        return (type(self), (self.group, self.dead_ranks, self.reason))


class TrainWorkerGroupError(RayTpuError):
    """One or more workers of a training gang failed. ``errors`` maps
    world rank -> the exception that rank's call raised; ``dead_ranks``
    names the ranks whose worker actor died (as opposed to raising a
    user-code error). Raised by ``WorkerGroup.execute`` so one dead
    worker's failure is attributed per rank instead of poisoning the
    whole gang result with a generic timeout."""

    def __init__(self, errors: dict | None = None, dead_ranks=(),
                 message: str = ""):
        self.errors = dict(errors or {})
        self.dead_ranks = tuple(sorted(set(int(r) for r in dead_ranks)))
        summary = ", ".join(
            f"rank {r}: {type(e).__name__}: {e}" if not isinstance(e, str)
            else f"rank {r}: {e}"
            for r, e in sorted(self.errors.items()))
        super().__init__(
            message or f"training worker group failure "
                       f"(dead ranks: {list(self.dead_ranks)}) — {summary}")

    def __reduce__(self):
        # per-rank causes may not pickle; degrade them to strings
        errs = {}
        import pickle

        for r, e in self.errors.items():
            try:
                pickle.dumps(e)
                errs[r] = e
            except Exception:
                errs[r] = f"{type(e).__name__}: {e}"
        return (type(self), (errs, self.dead_ranks, str(self)))


class TpuBackendError(RayTpuError):
    """A train worker whose lease holds ``TPU`` chips found its JAX
    backend on another platform. With ``JAX_PLATFORMS`` unset JAX falls
    back to the CPU with only a warning when libtpu cannot take the
    chips (held by another process, driver missing); training on that
    silently would bill a TPU host for CPU steps, so the worker fails
    the gang instead."""


class JobQuotaError(RayTpuError, ValueError):
    """A job-registry operation carried an invalid quota/priority shape
    (negative amounts, non-numeric values, unknown job on update). Raised
    at the GCS admission boundary so a mis-specified tenant fails at
    registration, not as a silently never-scheduling placement group."""


class TrainPreemptedError(TrainWorkerGroupError):
    """The training gang's placement group was preempted by a
    higher-priority job (multi-tenant control plane). This is graceful
    degradation, not a failure: the victim received a PREEMPTION warning
    with a grace window to cut a checkpoint, the GCS reclaimed its
    bundles, and ``fit()`` tears the gang down through the elastic-FT
    path and re-queues it — WITHOUT charging a
    ``FailureConfig.max_failures`` token — to resume from the latest
    checkpoint when capacity returns."""


class ServeConfigError(RayTpuError, ValueError):
    """A Serve DeploymentConfig / AutoscalingConfig carried an invalid
    value (num_replicas <= 0, min_replicas > max_replicas, negative
    timeouts/periods, ...). Raised at CONSTRUCTION — a bad config must
    fail where the operator wrote it, not as a deep runtime failure
    three actors later. Subclasses ValueError so generic config-
    validation handlers keep working."""


class ServeOverloadedError(RayTpuError):
    """Admission control shed this request: every replica of the
    deployment is at ``max_ongoing_requests`` and the router's bounded
    queue (``max_queued_requests`` per replica) is full. The request was
    REJECTED, not queued — callers should back off ``retry_after_s``
    and retry; the HTTP proxy maps this to 503 + a Retry-After header.
    Shedding with a typed error is the production-serve contract: an
    unbounded queue converts overload into unbounded latency for every
    caller instead of fast feedback for the marginal one.

    ``draining`` distinguishes a capacity storm from a load blip: True
    means replicas are preemption-warned / drain-scheduled and
    ``retry_after_s`` hints the grace window remaining (back off past
    the storm), not the static queue-depth heuristic."""

    def __init__(self, deployment_id: str = "", queued: int = 0,
                 retry_after_s: float = 1.0, draining: bool = False):
        self.deployment_id = deployment_id
        self.queued = queued
        self.retry_after_s = retry_after_s
        self.draining = draining
        super().__init__(
            f"deployment {deployment_id!r} is overloaded: all replicas at "
            f"max_ongoing_requests and {queued} requests already queued"
            + (" (replicas draining under preemption warning)"
               if draining else "")
            + f"; retry after {retry_after_s:.2f}s")

    def __reduce__(self):
        return (type(self), (self.deployment_id, self.queued,
                             self.retry_after_s, self.draining))


class ReplicaDrainingError(RayTpuError):
    """A Serve replica refused a request because it is draining (the
    controller told it to shut down gracefully). Raised replica-side and
    caught by the handle layer, which transparently re-dispatches the
    request to a surviving replica — a scale-down or rolling update must
    not lose accepted requests that raced the routing-table update."""

    def __init__(self, replica_id: str = ""):
        self.replica_id = replica_id
        super().__init__(f"replica {replica_id!r} is draining; "
                         f"re-dispatch to another replica")

    def __reduce__(self):
        return (type(self), (self.replica_id,))


class RaySystemError(RayTpuError):
    """An internal framework component failed (narrow subclass — catching it
    must NOT swallow user-code TaskErrors, matching reference semantics)."""


# Reference-API-compatible aliases (python/ray/exceptions.py names) so users
# migrating from the reference find the names they expect.
RayError = RayTpuError
RayTaskError = TaskError
RayActorError = ActorDiedError
