"""Distributed tracing: spans propagated through task/actor calls.

Reference: python/ray/util/tracing/tracing_helper.py:290 — Ray injects
OpenTelemetry spans through the TaskSpec so a driver's trace continues
inside remote execution (submit span on the caller, execute span on the
worker, linked by parent ids). The OpenTelemetry SDK is not bundled
here, so this module implements the same propagation natively with
W3C-trace-context-shaped ids (128-bit trace id, 64-bit span ids) and
exports OTLP-shaped JSON any collector/Jaeger can ingest — plugging the
real SDK in later is a TracerProvider swap, not a redesign.

Usage::

    from ray_tpu.util import tracing
    tracing.enable()
    ray_tpu.get(f.remote())          # spans recorded on every hop
    spans = tracing.get_spans()      # cluster-wide fan-out
    tracing.export_otlp_json(spans, "trace.json")

Propagation is implicit once a context exists: a worker executing a
traced task records spans (and propagates to nested submissions) even
if it never called enable() itself — exactly the reference's behavior
where the TaskSpec carries the context.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import json
import os
import threading
import time

_MAX_SPANS = 10_000

# cached per process (workers are spawned, not forked): getpid/uname are
# real syscalls on this container runtime — measurable per-span cost
_PID = os.getpid()
_NODE = os.uname().nodename

_lock = threading.Lock()
_spans: collections.deque = collections.deque(maxlen=_MAX_SPANS)
_dropped = 0
_enabled = False

# the active span for THIS logical execution context (task body, driver
# code path); contextvars keep concurrent actor calls separate
_current: contextvars.ContextVar = contextvars.ContextVar(
    "ray_tpu_trace_ctx", default=None)


def enable():
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def is_enabled() -> bool:
    return _enabled or _current.get() is not None


def _new_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


def _append_span(span: dict):
    """Sole writer to the ring: a span pushed into a FULL ring evicts
    the oldest one, and that loss is COUNTED (metric + stats) — fused
    consumers (step anatomy, flight recorder) must be able to flag an
    incomplete window instead of silently reporting wrong attribution."""
    global _dropped
    with _lock:
        dropped = len(_spans) == _spans.maxlen
        if dropped:
            _dropped += 1
        _spans.append(span)
    if dropped:
        try:
            from ray_tpu._private import telemetry as _tm

            _tm.counter_inc("ray_tpu_trace_dropped_total")
        except Exception:
            pass


def current_context() -> dict | None:
    """{"trace_id", "span_id"} of the active span, or None."""
    return _current.get()


def inject_context() -> dict | None:
    """Context to attach to an outgoing task/actor spec. Starts a new
    trace at the root when tracing is enabled and no span is active."""
    ctx = _current.get()
    if ctx is not None:
        return {"trace_id": ctx["trace_id"],
                "parent_span_id": ctx["span_id"]}
    if _enabled:
        return {"trace_id": _new_id(16), "parent_span_id": None}
    return None


@contextlib.contextmanager
def span(name: str, kind: str, ctx: dict | None = None,
         attributes: dict | None = None):
    """Record one span. `ctx` (an injected context) links the span into
    an existing trace; otherwise it continues the current one."""
    if ctx is None:
        inherited = _current.get()
        if inherited is None:
            if not _enabled:
                yield None
                return
            trace_id, parent = _new_id(16), None
        else:
            trace_id, parent = inherited["trace_id"], inherited["span_id"]
    else:
        trace_id = ctx["trace_id"]
        parent = ctx.get("parent_span_id")
    span_id = _new_id(8)
    token = _current.set({"trace_id": trace_id, "span_id": span_id})
    start = time.time_ns()
    try:
        yield {"trace_id": trace_id, "span_id": span_id}
    finally:
        end = time.time_ns()
        _current.reset(token)
        _append_span({
            "traceId": trace_id,
            "spanId": span_id,
            "parentSpanId": parent,
            "name": name,
            "kind": kind,                # "PRODUCER"/"CONSUMER"/...
            "startTimeUnixNano": start,
            "endTimeUnixNano": end,
            "pid": _PID,
            # pids collide across hosts; (node, pid) identifies the
            # producing process cluster-wide
            "node": _NODE,
            "attributes": attributes or {},
        })


def record_completed_span(name: str, kind: str, start_ns: int,
                          end_ns: int, attributes: dict | None = None,
                          ctx: dict | None = None):
    """Append an already-timed span linked under the CURRENT context
    (same linkage rule as span(); no-op when tracing is inactive).
    For observers that only learn a span happened after the fact —
    e.g. a compile-cache miss detected by cache-size delta — so the
    span can't wrap the work as a context manager. An explicit ``ctx``
    (an injected context, e.g. captured at @serve.batch enqueue time on
    the CALLER's thread) overrides the current-context linkage — the
    recording thread's own context is usually the wrong trace there."""
    if ctx is not None:
        trace_id, parent = ctx["trace_id"], ctx.get("parent_span_id")
    else:
        inherited = _current.get()
        if inherited is None:
            if not _enabled:
                return None
            trace_id, parent = _new_id(16), None
        else:
            trace_id, parent = inherited["trace_id"], inherited["span_id"]
    span_id = _new_id(8)
    _append_span({
        "traceId": trace_id,
        "spanId": span_id,
        "parentSpanId": parent,
        "name": name,
        "kind": kind,
        "startTimeUnixNano": int(start_ns),
        "endTimeUnixNano": int(end_ns),
        "pid": _PID,
        "node": _NODE,
        "attributes": attributes or {},
    })
    return {"trace_id": trace_id, "span_id": span_id}


def submit_span(spec: dict, name: str):
    """Context manager for an outgoing task/actor submission: opens the
    PRODUCER span (enclosing the submission work — arg pinning, queue
    handoff — so its duration is meaningful), and injects the context
    into ``spec["trace_ctx"]`` so the remote execute span becomes its
    child. No-op (null context) when tracing is inactive. One helper so
    task and actor submission can't drift apart."""
    ctx = inject_context()
    if ctx is None:
        return contextlib.nullcontext()

    @contextlib.contextmanager
    def _cm():
        with span(f"submit {name}", "PRODUCER", ctx,
                  {"task_id": spec["task_id"].hex()}) as sp:
            # beside the timeline's cause, where the spec carries one
            spec["trace_ctx"] = {**(spec.get("trace_ctx") or {}),
                                 "trace_id": sp["trace_id"],
                                 "parent_span_id": sp["span_id"]}
            yield sp

    return _cm()


def local_spans(with_drop_marker: bool = False) -> list[dict]:
    """This process's spans. ``with_drop_marker=True`` (the RPC path)
    appends one marker entry carrying this process's drop count so
    cluster collection can surface ring overflow; ``get_spans`` strips
    markers back out of the span list."""
    with _lock:
        out = list(_spans)
        dropped = _dropped
    if with_drop_marker and dropped:
        out.append({"spanId": f"__drops__:{_NODE}:{_PID}",
                    "__drops__": dropped, "node": _NODE, "pid": _PID})
    return out


def stats() -> dict:
    with _lock:
        return {"buffered": len(_spans), "dropped": _dropped,
                "capacity": _spans.maxlen}


def clear():
    global _dropped
    with _lock:
        _spans.clear()
        _dropped = 0


class SpanList(list):
    """``get_spans``'s return type: a plain span list, plus ``dropped``
    — {(node, pid): count} of spans each process's ring evicted before
    collection. A non-empty ``dropped`` means the trace window is
    incomplete and fused attribution over it should say so."""

    def __init__(self, spans, dropped):
        super().__init__(spans)
        self.dropped: dict[tuple, int] = dropped

    @property
    def complete(self) -> bool:
        return not self.dropped


def get_spans(address: str | None = None) -> "SpanList":
    """Cluster-wide span collection: driver-local spans plus a fan-out
    over every raylet's workers (the same plumbing as `timeline()`).
    Returns a list subclass whose ``dropped`` maps (node, pid) to the
    spans that process's ring evicted (incomplete-window signal)."""
    out = local_spans(with_drop_marker=True)
    try:
        from ray_tpu.experimental.state.api import _each_raylet, _gcs

        with _gcs(address) as call:
            out.extend(_each_raylet(call, "trace_spans"))
    except Exception:
        # a partial trace must not masquerade as a complete one
        import logging

        logging.getLogger(__name__).warning(
            "cluster span fan-out failed; returning driver-local spans "
            "only", exc_info=True)
    # the driver's own worker also answers the fan-out — dedup by span id
    seen, deduped = set(), []
    drops: dict[tuple, int] = {}
    for s in out:
        if s["spanId"] in seen:
            continue
        seen.add(s["spanId"])
        if "__drops__" in s:
            drops[(s.get("node"), s.get("pid"))] = s["__drops__"]
            continue
        deduped.append(s)
    return SpanList(deduped, drops)


def export_otlp_json(spans: list[dict], path: str) -> str:
    """OTLP/JSON export (the shape `otelcol`'s file receiver and Jaeger's
    OTLP ingestion accept): one resourceSpans entry per producing
    (node, pid) — pid alone collides across hosts."""
    by_proc: dict[tuple, list] = {}
    for s in spans:
        by_proc.setdefault((s.get("node", ""), s.get("pid", 0)),
                           []).append(s)
    doc = {"resourceSpans": [
        {
            "resource": {"attributes": [
                {"key": "service.name",
                 "value": {"stringValue": "ray_tpu"}},
                {"key": "host.name",
                 "value": {"stringValue": node}},
                {"key": "process.pid",
                 "value": {"intValue": pid}},
            ]},
            "scopeSpans": [{
                "scope": {"name": "ray_tpu.util.tracing"},
                "spans": [{
                    "traceId": s["traceId"],
                    "spanId": s["spanId"],
                    **({"parentSpanId": s["parentSpanId"]}
                       if s.get("parentSpanId") else {}),
                    "name": s["name"],
                    "kind": {"PRODUCER": 4, "CONSUMER": 5}.get(
                        s.get("kind", ""), 1),
                    "startTimeUnixNano": str(s["startTimeUnixNano"]),
                    "endTimeUnixNano": str(s["endTimeUnixNano"]),
                    "attributes": [
                        {"key": str(k), "value": {"stringValue": str(v)}}
                        for k, v in (s.get("attributes") or {}).items()],
                } for s in group],
            }],
        } for (node, pid), group in sorted(by_proc.items())
    ]}
    with open(path, "w") as f:
        json.dump(doc, f)
    return path
