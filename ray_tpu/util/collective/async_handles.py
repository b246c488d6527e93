"""Async op handles for the collective plane.

The ROADMAP's overlap arc ("Exploring the limits of Concurrency in ML
Training on Google TPUs", arXiv:2011.03641) needs a primitive the
synchronous collective API cannot express: *start* a collective now,
*finish* it later, and do useful work in between. This module is that
primitive, shaped like Ray's own async object-ref model
(arXiv:1712.05889): an op submission returns a ``CollectiveHandle``
future with ``wait(timeout)`` / ``poll()`` / ``result()``.

Execution model — one **issue thread per group** (``IssueQueue``):

- Submissions enqueue (FIFO) with their group op-seq already assigned
  on the caller's thread, so the per-group sequence order every rank
  must agree on (the standard collective contract) is fixed at submit
  time, not at execution time.
- The issue thread executes ops strictly in submission order, one at a
  time — at most one op per group is ever on the wire from this rank,
  exactly like the synchronous API, so the mailbox seq validation and
  the receive-buffer pool see the same traffic shape they always did.
- Synchronous ops on a group with async ops in flight first ``drain()``
  the queue (the module API in ``collective.py`` does this), keeping
  mixed sync/async call sites ordered without any new contract.

Because the op body runs on the issue thread — NOT the thread driving
the train loop — the step-anatomy plane records its comm interval as
*background* for free (``telemetry.run_op`` stamps ``blocking`` iff the
op ran on the loop's own thread; the hook PR 11 left ready). A caller
that blocks in ``wait()`` while a step is active records that wait as
an *exposed* comm interval, so hidden/exposed attribution stays honest:
comm is hidden only where nobody was blocked on it.

Failure semantics compose with the gang-FT plane (PR 5): a poisoned
group fails the IN-FLIGHT op fast (its ``col_take`` raises
``CollectiveGroupError`` the moment the poison lands), and the issue
loop then fails every still-QUEUED handle with the same error
immediately — pending handles surface the gang failure within the
poison-latency bound instead of serially burning op timeouts. Group
destroy (``close``) fails queued handles the same way.

Lock discipline (RTL107 covers this module): handle completion state
flips ONLY under the issue queue's condition, waiters park in
``wait_for`` under it, and the op body itself always runs with the
condition released.
"""
from __future__ import annotations

import collections
import threading

from ray_tpu._private import telemetry as _tm


def _default_timeout() -> float:
    from ray_tpu._private.config import get_config

    return float(get_config("collective_op_timeout_s"))


class CollectiveHandle:
    """Future for one asynchronously issued collective op.

    Completion state is guarded by the owning group's issue condition
    (shared with the queue — one lock protects the whole issue-thread
    state). ``poll()`` is a single flag read; ``wait``/``result`` park
    on the condition until the issue thread finishes the op.
    """

    __slots__ = ("group", "op", "seq", "_cond", "_done", "_result",
                 "_error", "done_at")

    def __init__(self, group: str, op: str, seq, cond):
        self.group = group
        self.op = op
        self.seq = seq
        self._cond = cond
        self._done = False
        self._result = None
        self._error = None
        # time.perf_counter() stamp of COMPLETION (set by _finish):
        # latency consumers must measure launch→done_at, not
        # launch→harvest — a caller that parks on other work before
        # result() would otherwise inflate the op's apparent duration
        self.done_at: float | None = None

    def poll(self) -> bool:
        """True once the op finished (successfully or not). Never
        blocks — one attribute read, safe on hot paths."""
        return self._done

    def wait(self, timeout: float | None = None):
        """Block until the op completes; raise its error if it failed
        (e.g. ``CollectiveGroupError`` when the gang was poisoned while
        this op was pending) or ``TimeoutError`` after ``timeout``
        seconds (default: the collective op timeout). While a
        step-anatomy step is active, a wait that actually blocked is
        recorded as an EXPOSED comm interval — the part of background
        comm the caller could not hide."""
        if not self._done:
            if timeout is None:
                timeout = _default_timeout()
            stamp = _tm.ENABLED
            if stamp:
                import time as _time

                from ray_tpu._private import step_anatomy as _sa

                t0 = _time.monotonic()
            with self._cond:
                ok = self._cond.wait_for(lambda: self._done,
                                         timeout=timeout)
            if stamp:
                t1 = _time.monotonic()
                if t1 > t0:
                    # blocking iff THIS is the thread driving the step
                    # loop — the same rule run_op applies. A helper
                    # thread harvesting handles while the loop computes
                    # must not inflate comm_exposed (the loop was never
                    # blocked); its wait stays background.
                    _sa.record_activity(
                        "collective", t0, t1,
                        blocking=threading.get_ident() == _sa._cur_thread,
                        op=f"{self.op}_wait", group=self.group)
            if not ok:
                raise TimeoutError(
                    f"collective {self.op} (group {self.group!r}, seq "
                    f"{self.seq}) did not complete within {timeout}s")
        if self._error is not None:
            raise self._error
        return True

    def result(self, timeout: float | None = None):
        """``wait()`` and return the op's value."""
        self.wait(timeout)
        return self._result

    # -- issue-thread side -------------------------------------------------

    def _finish(self, result=None, error=None):
        """Complete the handle (issue thread / queue teardown only).
        Must be called with the condition RELEASED — it takes it."""
        import time as _time

        with self._cond:
            self._result = result
            self._error = error
            self.done_at = _time.perf_counter()
            self._done = True
            self._cond.notify_all()


class IssueQueue:
    """Per-group background issue thread: executes submitted collective
    op thunks strictly in submission order. The thread is started
    lazily on the first submission (sync-only groups never pay for it)
    and exits when the queue is closed."""

    def __init__(self, group: str):
        self.group = group
        self._cond = threading.Condition()
        self._queue: collections.deque = collections.deque()
        self._inflight = 0          # queued + executing (gauge source)
        self._thread: threading.Thread | None = None
        self._closed = False

    # -- caller side -------------------------------------------------------

    def submit(self, op: str, seq, thunk) -> CollectiveHandle:
        handle = CollectiveHandle(self.group, op, seq, self._cond)
        with self._cond:
            if self._closed:
                raise RuntimeError(
                    f"collective group {self.group!r} was destroyed; "
                    f"async submission refused")
            self._queue.append((handle, thunk))
            self._inflight += 1
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._loop, daemon=True,
                    name=f"col-issue-{self.group}")
                self._thread.start()
            self._cond.notify_all()
        self._note_inflight()
        return handle

    def drain(self, timeout: float | None = None):
        """Block until every submitted op has completed — the ordering
        barrier synchronous ops take before touching a group with async
        work in flight. Errors stay on their handles (the sync op that
        follows hits the same group state and raises on its own).

        ``timeout`` bounds PROGRESS, not the whole drain: every queued
        op is individually bounded by the op timeout, so a deep healthy
        window must not spuriously fail here — drain only raises when
        no op completes within one timeout window."""
        if self._inflight == 0:
            return
        if timeout is None:
            timeout = _default_timeout()
        with self._cond:
            while self._inflight > 0:
                before = self._inflight
                ok = self._cond.wait_for(
                    lambda: self._inflight == 0
                    or self._inflight < before,
                    timeout=timeout)
                if not ok:
                    raise TimeoutError(
                        f"collective group {self.group!r}: async issue "
                        f"queue made no progress in {timeout}s "
                        f"({self._inflight} ops pending)")

    def pending(self) -> int:
        return self._inflight

    def close(self, reason: str = "collective group destroyed"):
        """Fail every queued handle and stop the issue thread. The op
        currently executing (if any) finishes on its own — its handle
        completes or errors through the normal path."""
        from ray_tpu import exceptions as exc

        drained = []
        with self._cond:
            if self._closed:
                return
            self._closed = True
            while self._queue:
                drained.append(self._queue.popleft()[0])
            self._inflight -= len(drained)
            self._cond.notify_all()
        err = exc.CollectiveGroupError(self.group, (), reason)
        for h in drained:
            h._finish(error=err)
        self._note_inflight()

    # -- issue thread ------------------------------------------------------

    def _loop(self):
        while True:
            with self._cond:
                while not self._queue and not self._closed:
                    self._cond.wait()
                if not self._queue and self._closed:
                    return
                handle, thunk = self._queue.popleft()
            # run with the condition RELEASED: the op blocks on network
            # receives for up to the op timeout, and poll()/submit()
            # must stay responsive meanwhile
            result = error = None
            try:
                result = thunk()
            except BaseException as e:  # noqa: BLE001 — delivered via handle
                error = e
            handle._finish(result, error)
            # drop the locals BEFORE parking again: the thunk closure
            # pins the packed input array and `result` the reduced
            # output — without this an idle group's issue thread
            # retains the last bucket's buffers (MBs) indefinitely
            del handle, thunk, result
            with self._cond:
                self._inflight -= 1
                self._cond.notify_all()
            self._note_inflight()
            if error is not None:
                self._fail_pending_fast(error)
            del error

    def _fail_pending_fast(self, error: BaseException):
        """A poisoned group makes EVERY subsequent op on it fail; once
        one op raises CollectiveGroupError, fail the still-queued
        handles with the same error immediately instead of issuing each
        one to fail in turn — pending handles must surface a gang death
        within the poison-latency bound, not serialized behind it."""
        from ray_tpu import exceptions as exc

        if not isinstance(error, exc.CollectiveGroupError):
            return
        drained = []
        with self._cond:
            while self._queue:
                drained.append(self._queue.popleft()[0])
            self._inflight -= len(drained)
            if drained:
                self._cond.notify_all()
        for h in drained:
            h._finish(error=error)
        if drained:
            self._note_inflight()

    def _note_inflight(self):
        if _tm.ENABLED:
            _tm.gauge_set("ray_tpu_collective_async_inflight_tasks",
                          float(self._inflight),
                          tags={"group": self.group})
