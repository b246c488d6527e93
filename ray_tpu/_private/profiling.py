"""Per-process profiling spans → chrome://tracing timeline.

Reference: src/ray/core_worker/profiling.h (events pushed to GCS, dumped by
`ray timeline`, scripts.py:1757). Here every worker/driver process keeps a
bounded ring of completed spans; `ray_tpu.timeline()` fans out over
raylets → workers, merges, and emits the chrome trace-event JSON format.

A span says what caused it: ``args.id`` is ``<node>:<pid>:<n>`` (a
per-process counter), ``args.parent`` the id of the span that was live on
this thread when it opened (or the ``parent=`` it was given: the causing
span of another thread or process, which rides task and actor specs as
``cause()`` makes it) and ``args.run`` the training run it belongs to,
inherited down the same way. In a process that has imported ``jax`` a live
span is also a ``jax.profiler.TraceAnnotation``, so a device profile taken
around it shows the program's phases beside the device's operations; this
module never imports ``jax`` itself.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import os
import sys
import threading
import time

_MAX_EVENTS = 10_000

_lock = threading.Lock()
_events: collections.deque = collections.deque(maxlen=_MAX_EVENTS)
_dropped = 0

# pids collide across hosts: a merged multi-node timeline needs the
# producing host on every event (tracing spans already carry `node`)
_NODE = os.uname().nodename
# cached: worker processes are spawned (never forked), and getpid is a
# real syscall on this container runtime (~0.3ms — profiled on the
# collective span hot path)
_PID = os.getpid()

# Collection defaults ON (ray_tpu.timeline() works out of the box, like
# the reference's profiling events); RAY_TPU_TIMELINE=0 removes the
# per-task dict+lock cost on latency-critical deployments.
_ENABLED = os.environ.get("RAY_TPU_TIMELINE", "1") != "0"

_ids = itertools.count(1)     # next() is atomic under the GIL
# per thread: the (id, run) of every live span, innermost last
_live = threading.local()
# jax.profiler.TraceAnnotation, once this process has imported jax
_annotation = None


def next_id() -> str:
    """A span id of this process: for a span whose children must know it
    before it is recorded (the raylet hands a spawned worker the id of
    the `worker_spawn` span it completes later)."""
    return f"{_NODE}:{_PID}:{next(_ids)}"


def current() -> tuple | None:
    """``(id, run)`` of this thread's innermost live span, or None."""
    stack = getattr(_live, "stack", None)
    return stack[-1] if stack else None


def cause() -> dict | None:
    """What a task or actor spec carries across a process boundary so
    that the spans of its execution name this thread's live span as their
    parent: ``{"cause": id, "run": run}``, or None outside every span."""
    live = current()
    return {"cause": live[0], "run": live[1]} if live else None


def _annotation_cls():
    """``jax.profiler.TraceAnnotation`` where this process has imported
    ``jax`` (whole: another thread may be in the middle of the import),
    else None. Never the one to import it."""
    global _annotation
    jax = sys.modules.get("jax")
    cls = getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)
    if cls is not None:
        _annotation = cls
    return cls


def _append_event(category, name, start_s, dur_s, extra, span_id,
                  parent, run):
    """Single definition of the chrome-event shape — the live context
    manager and the after-the-fact recorder must never drift apart.
    Appends into a full ring evict the oldest span, COUNTED (metric +
    stats + a drop-marker metadata row in timeline merges) so a fused
    window can flag itself incomplete instead of mis-attributing."""
    global _dropped
    args = dict(extra) if extra else {}
    args["id"] = span_id
    if parent is not None:
        args["parent"] = parent
    if run is not None:
        args["run"] = run
    with _lock:
        dropped = len(_events) == _events.maxlen
        if dropped:
            _dropped += 1
        _events.append({
            "cat": category,
            "name": name,
            "pid": _PID,
            "node": _NODE,
            "tid": threading.get_ident() % 2**31,
            "ts": int(start_s * 1e6),   # µs, chrome format
            "dur": int(dur_s * 1e6),
            "ph": "X",
            "args": args,
        })
    if dropped:
        try:
            from ray_tpu._private import telemetry as _tm

            _tm.counter_inc("ray_tpu_timeline_dropped_total")
        except Exception:
            pass


class _SpanCM:
    """Hand-rolled context manager: ~3µs cheaper per task than the
    generator-based contextlib version, and this runs TWICE per task
    on the execute hot path."""

    __slots__ = ("cat", "name", "extra", "start", "id", "parent", "run",
                 "ann")

    def __init__(self, category, name, extra, parent, run):
        self.cat = category
        self.name = name
        self.extra = extra
        self.parent = parent
        self.run = run

    def __enter__(self):
        stack = getattr(_live, "stack", None)
        if stack is None:
            stack = _live.stack = []
        if stack:
            above, run = stack[-1]
            if self.parent is None:
                self.parent = above
            if self.run is None:
                self.run = run
        self.id = next_id()
        stack.append((self.id, self.run))
        cls = _annotation or _annotation_cls()
        if cls is None:
            self.ann = None
        else:
            self.ann = cls(self.name)
            self.ann.__enter__()
        self.start = time.time()
        return None

    def __exit__(self, *exc):
        dur = time.time() - self.start
        if self.ann is not None:
            self.ann.__exit__(*exc)
        _live.stack.pop()
        _append_event(self.cat, self.name, self.start, dur, self.extra,
                      self.id, self.parent, self.run)
        return False


_NULL_CM = contextlib.nullcontext()


def record_span(category: str, name: str, extra: dict | None = None,
                parent: str | None = None, run: str | None = None):
    """A live span. ``parent`` / ``run`` default to this thread's
    innermost live span's; give them where the cause is elsewhere (a
    spec's ``cause()``, a thread started from a span). ``extra`` may be
    filled while the span is open: it is read when it closes."""
    if not _ENABLED:
        return _NULL_CM
    return _SpanCM(category, name, extra, parent, run)


def record_completed_span(category: str, name: str, start_s: float,
                          dur_s: float, extra: dict | None = None,
                          parent: str | None = None,
                          run: str | None = None,
                          span_id: str | None = None) -> str | None:
    """Append an already-timed span (observers that only learn a span
    happened after the fact — e.g. a compile-cache miss detected by
    cache-size delta). Same event shape as the live context manager;
    ``parent`` / ``run`` default to this thread's innermost live span's.
    Returns the span's id (``span_id`` if one was taken ahead with
    ``next_id``), for children recorded after it."""
    if not _ENABLED:
        return None
    above = current()
    if above is not None:
        parent = above[0] if parent is None else parent
        run = above[1] if run is None else run
    span_id = span_id or next_id()
    _append_event(category, name, start_s, dur_s, extra, span_id, parent,
                  run)
    return span_id


def snapshot(with_drop_marker: bool = False) -> list[dict]:
    """This process's events. ``with_drop_marker=True`` (the RPC /
    timeline-merge path) appends one chrome *metadata* row (``ph: M``)
    carrying the ring's drop count — chrome/Perfetto ignore unknown
    metadata names, and merged timelines surface the loss instead of
    presenting an evicted window as complete."""
    with _lock:
        out = list(_events)
        dropped = _dropped
    if with_drop_marker and dropped:
        out.append({"ph": "M", "name": "ray_tpu_timeline_dropped",
                    "pid": _PID, "node": _NODE, "ts": 0,
                    "args": {"dropped": dropped}})
    return out


def adopt(events: list[dict]):
    """Keep another process's spans in this ring, as they are (their own
    node, pid and ids): a gang's workers are killed when ``fit()`` ends,
    and their share of the run's timeline would die with them."""
    global _dropped
    if not _ENABLED:
        return
    with _lock:
        for ev in events:
            if len(_events) == _events.maxlen:
                _dropped += 1
            _events.append(ev)


def merge(events: list[dict]) -> list[dict]:
    """``events`` without the rows a second path brought again: a raylet
    that shares its driver's process answers with the ring the driver
    already gave. A span is ``(node, pid, id)``, a metadata row ``(node,
    pid, name)``."""
    seen, out = set(), []
    for ev in events:
        key = (ev.get("node"), ev.get("pid"),
               (ev.get("args") or {}).get("id") or ("M", ev.get("name")))
        if key not in seen:
            seen.add(key)
            out.append(ev)
    return out


def stats() -> dict:
    with _lock:
        return {"buffered": len(_events), "dropped": _dropped,
                "capacity": _events.maxlen}


def clear():
    global _dropped
    with _lock:
        _events.clear()
        _dropped = 0


def to_chrome_trace(events: list[dict]) -> list[dict]:
    """Already chrome-shaped; kept as a seam for format evolution.
    Metadata rows (drop markers) sort first — ``ts`` 0."""
    return sorted(events, key=lambda e: e["ts"])
