"""Cluster flight recorder — the always-on per-process black box.

Reference tier: `ray timeline` + the debug-state dumps operators grab
AFTER something died — except those must be requested while the patient
is still alive. Here every process already keeps bounded rings of its
recent telemetry (chrome-timeline spans, tracing spans, structured
events, step-anatomy records, metric registries); this module is the
window cut + the dump fan-out that turns them into a post-mortem
artifact at the moment of failure:

- ``local_snapshot(window_s)`` — one process's recent telemetry, cut to
  the last ``RAY_TPU_FLIGHT_RECORDER_WINDOW_S`` seconds (spans/events
  older than the window are noise by the time a human reads the dump);
- ``dump(reason)`` — fans out over the GCS and every raylet's workers
  (``blackbox_snapshot`` RPC), writes one timestamped directory with a
  per-process ``<node>_<pid>.jsonl`` plus one merged
  ``timeline.json`` chrome trace (pids remapped to be unique across
  hosts — chrome keys processes by pid alone, and pid 4242 on two nodes
  is two different processes);
- ``trigger_dump(reason)`` — the automatic hook, debounced so a failure
  storm produces one black box, not a disk-filling flurry. Wired into
  the gang-failure path (train/trainer.py ``GANG_FAILED``), the
  driver's gang death monitor (train/backend_executor.py), and
  collective group poisoning (util/collective/collective.py).

Kill switch: ``RAY_TPU_INTERNAL_TELEMETRY=0`` disables snapshots,
dumps, and triggers entirely (the rings it reads are off too).
"""
from __future__ import annotations

import json
import os
import threading
import time

from ray_tpu._private import telemetry as _tm

_WINDOW_KNOB = "RAY_TPU_FLIGHT_RECORDER_WINDOW_S"
_DIR_KNOB = "RAY_TPU_FLIGHT_RECORDER_DIR"
_DEFAULT_WINDOW_S = 120.0
_DEBOUNCE_S = 15.0          # min spacing between AUTO dumps per process

_PID = os.getpid()
_NODE = os.uname().nodename

_lock = threading.Lock()
_last_auto_dump_ts = 0.0
_last_dump_path: str | None = None
_dump_seq = 0     # uniquifies same-second dumps from one process


def enabled() -> bool:
    return _tm.ENABLED


def window_s() -> float:
    try:
        return float(os.environ.get(_WINDOW_KNOB, _DEFAULT_WINDOW_S))
    except ValueError:
        return _DEFAULT_WINDOW_S


def base_dir() -> str:
    configured = os.environ.get(_DIR_KNOB)
    if configured:
        return configured
    import tempfile

    return os.path.join(tempfile.gettempdir(), "ray_tpu", "blackbox")


def last_dump_path() -> str | None:
    """The most recent dump this process wrote (None if none) — the
    conftest failure header and operators start post-mortems here."""
    return _last_dump_path


def find_latest_dump(base: str | None = None) -> str | None:
    """Newest dump directory ON DISK under the base dir. The in-memory
    ``last_dump_path`` is per-process — a fresh CLI process asking
    "where did the last auto-dump land?" must scan instead."""
    base = base or base_dir()
    try:
        dumps = [d for d in os.listdir(base)
                 if d.startswith("blackbox_")]
    except OSError:
        return None
    if not dumps:
        return None
    paths = [os.path.join(base, d) for d in dumps]
    return max(paths, key=lambda p: (os.path.getmtime(p), p))


def local_snapshot(window: float | None = None) -> dict:
    """This process's black box: recent spans/events/steps + a metrics
    snapshot, cut to the window. Cheap (ring copies); safe to call from
    failure paths."""
    if not enabled():
        return {}
    if window is None:
        window = window_s()
    now = time.time()
    cutoff = now - window
    out = {"node": _NODE, "pid": _PID, "ts": now, "window_s": window}
    try:
        from ray_tpu._private import events as _events

        out["events"] = [e for e in _events.snapshot()
                         if e.get("ts", now) >= cutoff]
    except Exception:
        out["events"] = []
    try:
        from ray_tpu._private import profiling as _prof

        cutoff_us = cutoff * 1e6
        out["timeline"] = [e for e in _prof.snapshot()
                           if e.get("ts", 0) + e.get("dur", 0)
                           >= cutoff_us]
        out["timeline_dropped"] = _prof.stats()["dropped"]
    except Exception:
        out["timeline"] = []
    try:
        from ray_tpu.util import tracing

        cutoff_ns = cutoff * 1e9
        out["spans"] = [s for s in tracing.local_spans()
                        if s.get("endTimeUnixNano", 0) >= cutoff_ns]
        out["spans_dropped"] = tracing.stats()["dropped"]
    except Exception:
        out["spans"] = []
    try:
        from ray_tpu._private import step_anatomy as _sa

        out["steps"] = _sa.local_records()
    except Exception:
        out["steps"] = {}
    try:
        from ray_tpu._private.events import _role
        from ray_tpu.util.metrics import registry_snapshot

        out["role"] = _role()
        out["metrics"] = registry_snapshot()
    except Exception:
        out["metrics"] = []
    try:
        from ray_tpu._private import memory_anatomy as _ma

        # ring cut to the dump window: a leak post-mortem reads the
        # put/delete history around the incident, not process lifetime
        out["memory"] = _ma.local_snapshot(top_k=10, window_s=window)
    except Exception:
        out["memory"] = {}
    return out


def _collect(address: str | None) -> list[dict]:
    """This process + the GCS + every raylet's workers. Degrades to
    driver-local when there is no cluster to ask (the black box of the
    one process you have beats no black box)."""
    snaps = [local_snapshot()]
    try:
        from ray_tpu.experimental.state.api import _each_raylet, _gcs

        with _gcs(address) as call:
            try:
                snaps.extend(call("blackbox_snapshot"))
            except Exception:
                pass   # older GCS build: its ring just isn't visible
            snaps.extend(_each_raylet(call, "blackbox_snapshot"))
    except Exception:
        pass
    # dedup by (node, pid): the driver answers locally AND through the
    # fan-out in in-process clusters
    seen: set[tuple] = set()
    out = []
    for s in snaps:
        if not s:
            continue
        key = (s.get("node"), s.get("pid"))
        if key in seen:
            continue
        seen.add(key)
        out.append(s)
    return out


def merged_timeline(snaps: list[dict]) -> list[dict]:
    """One chrome-trace event list over every process's recent spans.
    Pids are remapped to unique ints — chrome://tracing keys processes
    by pid, and pids collide across hosts — with ``process_name``
    metadata rows carrying the real (node, pid) identity. Sorted by
    ``ts`` (arrival order does not matter)."""
    pid_map: dict[tuple, int] = {}
    out: list[dict] = []
    for s in snaps:
        key = (s.get("node"), s.get("pid"))
        if key not in pid_map:
            pid_map[key] = len(pid_map) + 1
            out.append({"ph": "M", "name": "process_name",
                        "pid": pid_map[key], "ts": 0,
                        "args": {"name": f"{key[0]}/pid{key[1]}"}})
        fake = pid_map[key]
        if s.get("timeline_dropped"):
            # a ring that evicted spans must say so IN the merged file
            # a post-mortem reader actually loads, not only in the
            # per-process jsonl header chrome never shows
            out.append({"ph": "M", "name": "ray_tpu_timeline_dropped",
                        "pid": fake, "ts": 0,
                        "args": {"dropped": s["timeline_dropped"]}})
        for e in s.get("timeline", ()):
            e = dict(e)
            e["pid"] = fake
            out.append(e)
    out.sort(key=lambda e: (e.get("ts", 0), e.get("ph") != "M"))
    return out


def dump(reason: str, *, address: str | None = None,
         out_dir: str | None = None) -> str | None:
    """Write one black-box dump directory and return its path:
    ``<base>/blackbox_<utc-stamp>_<reason>/`` with one ``.jsonl`` per
    process (line 1: a header with identity/window/drop counts; then one
    line per event/span/step record tagged with its source table) and a
    merged ``timeline.json`` loadable at chrome://tracing."""
    global _last_dump_path, _dump_seq
    if not enabled():
        return None
    snaps = _collect(address)
    stamp = time.strftime("%Y%m%d_%H%M%S", time.gmtime())
    safe_reason = "".join(c if c.isalnum() or c in "-_" else "_"
                          for c in reason)[:48] or "manual"
    with _lock:
        _dump_seq += 1
        seq = _dump_seq
    # per-process seq in the name: the stamp is 1s-resolution, and two
    # dumps in the same second (retrying gang + manual) must not merge
    # into one directory overwriting each other's files
    path = os.path.join(
        out_dir or base_dir(),
        f"blackbox_{stamp}_{os.getpid()}_{seq}_{safe_reason}")
    os.makedirs(path, exist_ok=True)
    for s in snaps:
        fname = f"{s.get('node', 'node')}_{s.get('pid', 0)}.jsonl"
        with open(os.path.join(path, fname), "w") as f:
            header = {k: s.get(k) for k in
                      ("node", "pid", "role", "ts", "window_s",
                       "timeline_dropped", "spans_dropped")}
            f.write(json.dumps({"table": "header", **header,
                                "reason": reason}) + "\n")
            for table in ("events", "spans", "timeline"):
                for row in s.get(table, ()):
                    f.write(json.dumps({"table": table, **row},
                                       default=str) + "\n")
            steps = s.get("steps") or {}
            for row in steps.get("steps", ()):
                f.write(json.dumps({"table": "step", **row}) + "\n")
            for row in steps.get("activities", ()):
                f.write(json.dumps({"table": "activity", **row}) + "\n")
            f.write(json.dumps({"table": "metrics",
                                "metrics": s.get("metrics", [])},
                               default=str) + "\n")
    with open(os.path.join(path, "timeline.json"), "w") as f:
        json.dump(merged_timeline(snaps), f)
    with open(os.path.join(path, "memory.jsonl"), "w") as f:
        # one line per process: ledger summary row + its recent
        # put/delete ring rows (the leak post-mortem's provenance feed)
        for s in snaps:
            mem = s.get("memory") or {}
            if not mem:
                continue
            summary = {k: v for k, v in mem.items() if k != "ring"}
            f.write(json.dumps({"table": "memory_summary",
                                "node": s.get("node"),
                                "pid": s.get("pid"), **summary},
                               default=str) + "\n")
            for row in mem.get("ring", ()):
                f.write(json.dumps({"table": "memory_ring",
                                    "node": s.get("node"),
                                    "pid": s.get("pid"), **row},
                                   default=str) + "\n")
    with _lock:
        _last_dump_path = path
    from ray_tpu._private import events as _events

    _events.record("FLIGHT_RECORDER_DUMP", reason=reason, path=path,
                   processes=len(snaps))
    _tm.counter_inc("ray_tpu_flight_recorder_dumps_total",
                    tags={"trigger": safe_reason})
    return path


def trigger_dump(reason: str, *, address: str | None = None,
                 background: bool = False,
                 force: bool = False) -> str | None:
    """The automatic failure hook: debounced ``dump`` that never raises
    into the failure path it rides on. ``background=True`` runs the dump
    on a daemon thread (for callbacks that must not block, e.g. the
    pubsub death feed). ``force=True`` skips the debounce — for flagship
    triggers (GANG_FAILED) whose dump must capture state recorded
    moments after a sibling trigger already fired."""
    global _last_auto_dump_ts
    if not enabled():
        return None
    with _lock:
        now = time.monotonic()
        if not force and now - _last_auto_dump_ts < _DEBOUNCE_S:
            return None
        _last_auto_dump_ts = now
    if background:
        threading.Thread(target=lambda: trigger_dump_now(reason, address),
                         daemon=True, name="flight-recorder-dump").start()
        return None
    return trigger_dump_now(reason, address)


def trigger_dump_now(reason: str, address: str | None = None):
    try:
        return dump(reason, address=address)
    except Exception:
        return None   # the black box must never worsen the crash
