"""State API — programmatic cluster observability.

Reference: python/ray/experimental/state/api.py (list_actors/list_tasks/
list_objects/list_nodes/..., StateApiClient) with the aggregation the
reference does in dashboard/state_aggregator.py done client-side here: the
GCS serves cluster tables, raylets serve per-node lease/worker state.

Works connected (inside a driver: uses the current worker's GCS) or
standalone (address="host:port", e.g. from the CLI).
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _gcs(address: str | None):
    """Yield a call(method, **kw) callable for the GCS."""
    if address is None:
        from ray_tpu._private.worker_runtime import current_worker

        w = current_worker()
        if w is not None:
            yield w.gcs.call
            return
        from ray_tpu.scripts.node import CLUSTER_FILE
        import json
        import os

        if not os.path.exists(CLUSTER_FILE):
            raise RuntimeError("not connected and no local cluster file; "
                               "pass address='host:port'")
        with open(CLUSTER_FILE) as f:
            address = json.load(f)["gcs_address"]
    from ray_tpu._private.protocol import RpcClient

    host, port = address.rsplit(":", 1)
    client = RpcClient((host, int(port)), timeout=10.0)
    try:
        yield client.call
    finally:
        client.close()


def _each_raylet(call, method: str) -> list:
    from ray_tpu._private.protocol import RpcClient

    out = []
    for n in call("get_nodes"):
        if not n["Alive"]:
            continue
        try:
            c = RpcClient((n["NodeManagerAddress"], n["NodeManagerPort"]),
                          timeout=5.0)
            try:
                out.extend(c.call(method))
            finally:
                c.close()
        except Exception:
            continue
    return out


_FILTER_OPS = {
    "=": lambda a, b: a == b,
    "!=": lambda a, b: a != b,
    "<": lambda a, b: a is not None and a < b,
    "<=": lambda a, b: a is not None and a <= b,
    ">": lambda a, b: a is not None and a > b,
    ">=": lambda a, b: a is not None and a >= b,
    "contains": lambda a, b: b in (a or ""),
}


def _apply_filters(rows: list[dict], filters, limit) -> list[dict]:
    """Predicate filtering + truncation, the reference's state-API
    filter form (python/ray/experimental/state/api.py — filters are
    (key, op, value) tuples ANDed together; `=` compares after str()
    coercion so CLI-sourced values match ints/bools)."""
    for f in filters or ():
        try:
            key, op, value = f
        except (TypeError, ValueError):
            raise ValueError(
                f"filter must be (key, op, value), got {f!r}") from None
        if op not in _FILTER_OPS:
            raise ValueError(f"unknown filter op {op!r} "
                             f"(one of {sorted(_FILTER_OPS)})")
        pred = _FILTER_OPS[op]
        if op in ("=", "!="):
            rows = [r for r in rows
                    if pred(str(r.get(key)), str(value))]
        else:
            rows = [r for r in rows if pred(r.get(key), value)]
    if limit is not None:
        rows = rows[:limit]
    return rows


def list_nodes(*, address: str | None = None, filters=None,
               limit=None) -> list[dict]:
    with _gcs(address) as call:
        return _apply_filters(call("get_nodes"), filters, limit)


def list_actors(*, address: str | None = None, filters=None,
                limit=None) -> list[dict]:
    with _gcs(address) as call:
        return _apply_filters(call("list_actors"), filters, limit)


def list_placement_groups(*, address: str | None = None, filters=None,
                          limit=None) -> list[dict]:
    with _gcs(address) as call:
        return _apply_filters(call("list_placement_groups"), filters,
                              limit)


def list_objects(*, address: str | None = None, filters=None,
                 limit=None) -> list[dict]:
    """Union of per-node store inventories, merged by object id. Locations
    live with owning workers (owner-based directory), so the cluster-wide
    view is assembled from the raylets' stores rather than a GCS table."""
    with _gcs(address) as call:
        rows = _each_raylet(call, "list_store_objects")
    merged: dict[str, dict] = {}
    for r in rows:
        cur = merged.get(r["ObjectID"])
        if cur is None:
            merged[r["ObjectID"]] = dict(r)
        else:
            cur["Locations"] = sorted(set(cur["Locations"])
                                      | set(r["Locations"]))
            cur["Size"] = max(cur["Size"], r["Size"])
    return _apply_filters(list(merged.values()), filters, limit)


def list_tasks(*, address: str | None = None, filters=None,
               limit=None, detail: bool = False) -> list[dict]:
    """Raylet-level view: one row per active lease (running task slot).
    The reference's task events flow through its dashboard agent; here the
    lease table is the source of truth for what is running where.
    detail=True additionally asks each leased worker what it is running
    (task id/desc/start time — the reference's `ray get tasks <id>`
    tier)."""
    with _gcs(address) as call:
        rows = _each_raylet(call, "list_leases")
    if detail:
        from concurrent.futures import ThreadPoolExecutor

        from ray_tpu._private.protocol import RpcClient

        def probe(r):
            addr = r.get("worker_addr")
            if not addr:
                return
            try:
                c = RpcClient(tuple(addr), timeout=2.0)
                try:
                    r.update(c.call("task_state"))
                finally:
                    c.close()
            except Exception:
                pass

        # concurrent probes: dead workers each cost up to the 2s
        # timeout, which must not stack serially across the cluster
        with ThreadPoolExecutor(max_workers=16) as pool:
            list(pool.map(probe, rows))
    return _apply_filters(rows, filters, limit)


def list_workers(*, address: str | None = None, filters=None,
                 limit=None) -> list[dict]:
    with _gcs(address) as call:
        return _apply_filters(_each_raylet(call, "list_workers"),
                              filters, limit)


# ---- per-entity detail lookups (reference: state api get_* tier) ----------

def get_actor(actor_id: str, *, address: str | None = None) -> dict | None:
    """One actor's full record by hex id."""
    for row in list_actors(address=address):
        if row["ActorID"] == actor_id:
            return row
    return None


def get_node(node_id: str, *, address: str | None = None) -> dict | None:
    for row in list_nodes(address=address):
        if row["NodeID"] == node_id:
            return row
    return None


def get_placement_group(pg_id: str, *,
                        address: str | None = None) -> dict | None:
    for row in list_placement_groups(address=address):
        if row["PlacementGroupID"] == pg_id:
            return row
    return None


def get_task(task_id: str, *, address: str | None = None) -> dict | None:
    """Detail for one RUNNING task by hex id (lease + worker probe)."""
    for row in list_tasks(address=address, detail=True):
        if row.get("task_id") == task_id:
            return row
    return None


def get_objects(object_id: str, *,
                address: str | None = None) -> list[dict]:
    """Every store's view of one object (locations/size/lost)."""
    return [r for r in list_objects(address=address)
            if r["ObjectID"] == object_id]


# ---- summaries (reference: `ray summary` / state_aggregator rollups) ------

def summarize_actors(*, address: str | None = None) -> dict:
    """Counts grouped class -> state (reference: `ray summary actors`)."""
    out: dict[str, dict[str, int]] = {}
    for a in list_actors(address=address):
        by_state = out.setdefault(a.get("ClassName") or "?", {})
        by_state[a["State"]] = by_state.get(a["State"], 0) + 1
    return out


def summarize_tasks(*, address: str | None = None) -> dict:
    """Running work grouped by description (leases + worker probes) plus
    queued demand by shape (reference: `ray summary tasks` groups by
    func_or_class_name and state), plus the per-task queue/scheduling/
    execution latency breakdown derived from the runtime event log:

    - ``queue_s``      SUBMITTED → last LEASE_GRANTED (waiting in the
                       scheduling queue for a leased worker; retries of
                       a failed dispatch accrue here),
    - ``scheduling_s`` LEASE_GRANTED → RUNNING (push + dependency
                       resolution on the executor),
    - ``execution_s``  RUNNING → FINISHED/FAILED (the task body).

    ``tasks`` holds one row per task seen in the event window (bounded
    per-process rings — a long-running cluster only covers recent
    tasks); ``latency`` aggregates count/mean/max per task description.
    """
    running: dict[str, int] = {}
    for t in list_tasks(address=address, detail=True):
        key = t.get("task_desc") or (
            "actor_task" if t.get("is_actor") else "task")
        running[key] = running.get(key, 0) + 1
    queued: dict[str, int] = {}
    with _gcs(address) as call:
        for n in call("get_cluster_load")["nodes"]:
            for shape in n.get("PendingDemand", ()):
                key = ",".join(f"{k}:{v:g}"
                               for k, v in sorted(shape.items()))
                queued[key] = queued.get(key, 0) + 1
    tasks = _task_latency_rows(
        list_cluster_events(address=address,
                            filters=[("kind", "=", "task_state")]))
    latency: dict[str, dict] = {}
    for row in tasks:
        agg = latency.setdefault(row["desc"] or "task", {
            "count": 0, "finished": 0, "failed": 0,
            "queue_s": _PhaseAgg(), "scheduling_s": _PhaseAgg(),
            "execution_s": _PhaseAgg()})
        agg["count"] += 1
        if row["state"] == "FINISHED":
            agg["finished"] += 1
        elif row["state"] == "FAILED":
            agg["failed"] += 1
        for phase in ("queue_s", "scheduling_s", "execution_s"):
            if row.get(phase) is not None:
                agg[phase].add(row[phase])
    for agg in latency.values():
        for phase in ("queue_s", "scheduling_s", "execution_s"):
            agg[phase] = agg[phase].summary()
    return {"running": running, "queued_by_shape": queued,
            "tasks": tasks, "latency": latency}


class _PhaseAgg:
    __slots__ = ("n", "total", "max")

    def __init__(self):
        self.n = 0
        self.total = 0.0
        self.max = 0.0

    def add(self, v: float):
        self.n += 1
        self.total += v
        self.max = max(self.max, v)

    def summary(self) -> dict:
        return {"count": self.n,
                "mean": (self.total / self.n) if self.n else 0.0,
                "max": self.max}


def _task_latency_rows(task_events: list[dict]) -> list[dict]:
    """Fold task_state events into one row per task id. For retried
    tasks the breakdown describes the attempt that reached RUNNING last
    (latest LEASE_GRANTED/RUNNING/terminal timestamps), with `attempts`
    counting dispatches; clock skew across hosts is clamped to >= 0."""
    per_task: dict[str, dict] = {}
    for e in task_events:
        tid = e.get("task_id")
        if tid is None:
            continue
        t = per_task.setdefault(tid, {
            "task_id": tid, "desc": None, "state": None, "attempts": 0,
            "_submitted": None, "_granted": None, "_running": None,
            "_end": None})
        state = e.get("state")
        ts = e.get("ts", 0.0)
        if e.get("desc"):
            t["desc"] = e["desc"]
        if state == "SUBMITTED":
            if t["_submitted"] is None or ts < t["_submitted"]:
                t["_submitted"] = ts
        elif state == "LEASE_GRANTED":
            t["attempts"] += 1
            if t["_granted"] is None or ts > t["_granted"]:
                t["_granted"] = ts
        elif state == "RUNNING":
            if t["_running"] is None or ts > t["_running"]:
                t["_running"] = ts
        elif state in ("FINISHED", "FAILED"):
            if t["_end"] is None or ts > t["_end"]:
                t["_end"] = ts
                t["state"] = state
        if state in ("SUBMITTED", "RESUBMITTED", "LEASE_GRANTED",
                     "RUNNING") and t["state"] not in ("FINISHED",
                                                       "FAILED"):
            t["state"] = state
    rows = []
    for t in per_task.values():
        sub, granted = t.pop("_submitted"), t.pop("_granted")
        run, end = t.pop("_running"), t.pop("_end")
        t["queue_s"] = (max(0.0, granted - sub)
                        if sub is not None and granted is not None
                        else None)
        t["scheduling_s"] = (max(0.0, run - granted)
                             if granted is not None and run is not None
                             else None)
        t["execution_s"] = (max(0.0, end - run)
                            if run is not None and end is not None
                            else None)
        t["submitted_at"] = sub
        rows.append(t)
    rows.sort(key=lambda r: r.get("submitted_at") or 0.0)
    return rows


def list_cluster_events(*, address: str | None = None, filters=None,
                        limit=None) -> list[dict]:
    """The cluster's structured runtime event stream (_private/events.py):
    task state transitions, actor lifecycle, node up/down, retry-budget
    exhaustion, injected faults. Unions this process's ring with the GCS
    process's and every raylet's (which fans out over its workers),
    dedups by (node, pid, seq) — in-process test clusters reach the same
    ring through several paths — and returns events time-ordered."""
    from ray_tpu._private import events as _events

    rows = _events.snapshot()
    with _gcs(address) as call:
        try:
            rows.extend(call("events_snapshot"))
        except Exception:
            pass   # pre-telemetry GCS build: its ring just isn't visible
        rows.extend(_each_raylet(call, "events_snapshot"))
    seen: set[tuple] = set()
    deduped = []
    for r in rows:
        key = (r.get("node"), r.get("pid"), r.get("seq"))
        if key in seen:
            continue
        seen.add(key)
        deduped.append(r)
    deduped.sort(key=lambda r: (r.get("ts", 0.0), r.get("node") or "",
                                r.get("pid") or 0, r.get("seq") or 0))
    rows = _apply_filters(deduped, filters, None)
    if limit is not None:
        # a time-ordered log truncates from the HEAD: keep the recent
        # tail (an operator debugging an incident wants the last N
        # events, not the cluster's first N). limit=0 means zero rows,
        # matching _apply_filters' semantics — rows[-0:] would be all.
        rows = rows[-limit:] if limit else []
    return rows


def summarize_objects(*, address: str | None = None) -> dict:
    """Object-store rollup: counts/bytes total and per node (reference:
    `ray summary objects`)."""
    objs = list_objects(address=address)
    per_node: dict[str, dict] = {}
    for o in objs:
        for node in o["Locations"]:
            agg = per_node.setdefault(node, {"count": 0, "bytes": 0})
            agg["count"] += 1
            agg["bytes"] += o["Size"]
    return {"total_objects": len(objs),
            "total_bytes": sum(o["Size"] for o in objs),
            "lost_objects": sum(1 for o in objs if o.get("Lost")),
            "per_node": per_node}


def summarize_control_plane(*, address: str | None = None) -> dict:
    """Control-plane scale & health rollup (cluster soak, round 12):
    the GCS's table sizes, death-feed fanout/coalescing counters,
    registration-admission throttling, and pubsub subscriber/resync
    state — the numbers `benchmarks/soak_bench.py` soaks and
    `ray-tpu control` prints."""
    with _gcs(address) as call:
        state = call("debug_state")
    return {
        "nodes": {"total": state.get("nodes", 0),
                  "alive": state.get("alive_nodes", 0)},
        "actors": {"total": state.get("actors", 0),
                   "alive": state.get("alive_actors", 0)},
        "placement_groups": state.get("placement_groups", 0),
        "objects_tracked": state.get("objects_tracked", 0),
        "death_feed": {
            "batches": state.get("death_batches", 0),
            "deaths_coalesced": state.get("deaths_coalesced", 0),
            "max_batch": state.get("max_death_batch", 0),
            "last_fanout_s": state.get("last_fanout_s", 0.0),
        },
        "registration": {
            "throttled": state.get("register_throttled", 0),
        },
        "pubsub": {
            "subscribers": state.get("pubsub_subscribers", 0),
            "resyncs_served": state.get("pubsub_resyncs_served", 0),
        },
    }


def summarize_topology(*, address: str | None = None) -> dict:
    """ICI-topology rollup: every TPU slice the raylets report (hosts
    with worker index / coords / chips, aliveness) plus which placement
    groups — and which pipeline STAGES of them — currently occupy each
    slice. The operator face of the SPREAD_ACROSS_SLICES scheduler:
    ``ray-tpu topology`` / dashboard ``/api/topology``."""
    with _gcs(address) as call:
        nodes = call("get_nodes")
        pgs = call("list_placement_groups")
    slice_of_node: dict[str, str] = {}
    slices: dict[str, dict] = {}
    for n in nodes:
        tpu = n.get("tpu") or {}
        if not tpu:
            continue
        sid = str(tpu.get("slice_id", "slice-0"))
        slice_of_node[n["NodeID"]] = sid
        entry = slices.setdefault(sid, {
            "hosts": [], "chips": 0, "alive_hosts": 0,
            "accelerator_type": tpu.get("accelerator_type"),
            "topology": tpu.get("topology")})
        host = {"node_id": n["NodeID"],
                "worker_id": int(tpu.get("worker_id", 0)),
                "hostname": n.get("hostname"),
                "alive": bool(n.get("Alive")),
                "chips": int(tpu.get("chips", 0) or 0)}
        if tpu.get("coords"):
            host["coords"] = tpu["coords"]
        entry["hosts"].append(host)
        entry["chips"] += host["chips"]
        entry["alive_hosts"] += 1 if host["alive"] else 0
    for entry in slices.values():
        entry["hosts"].sort(key=lambda h: h["worker_id"])
    occupants: list[dict] = []
    for pg in pgs:
        if pg.get("State") != "CREATED":
            continue
        labels = pg.get("Stages")
        bundle_nodes = pg.get("BundleNodes") or []
        if labels is None:
            labels = list(range(len(bundle_nodes)))
        stage_slices: dict[str, list] = {}
        touched = False
        for lab, nid in zip(labels, bundle_nodes):
            sid = slice_of_node.get(nid)
            if sid is None:
                continue
            touched = True
            bucket = stage_slices.setdefault(str(lab), [])
            if sid not in bucket:
                bucket.append(sid)
        if not touched:
            continue
        row = {"placement_group_id": pg["PlacementGroupID"],
               "name": pg.get("Name", ""), "job": pg.get("Job", ""),
               "strategy": pg.get("Strategy"),
               "stages": stage_slices}
        occupants.append(row)
        for sids in stage_slices.values():
            for sid in sids:
                occ = slices[sid].setdefault("occupants", [])
                if row["placement_group_id"] not in occ:
                    occ.append(row["placement_group_id"])
    return {"num_slices": len(slices),
            "slices": dict(sorted(slices.items())),
            "placement_groups": occupants}


def summarize_jobs(*, address: str | None = None) -> dict:
    """Multi-tenant rollup (the GCS job table + live usage): one row
    per job — priority, quota, cluster-wide usage (CREATED PG bundles +
    gossiped lease usage), dominant resource share, created/pending PG
    counts, preemption and quota-rejection counters — plus the
    cluster totals the soak asserts against:

    - ``quota_violations``: jobs whose live usage exceeds their quota
      (MUST be empty — quota enforcement is admission-time, so a
      violation means the scheduler placed past a cap);
    - ``preemptions`` / ``quota_rejections``: cluster totals;
    - ``serve_apps``: job → Serve app names for jobs that are Serve
      tenants (best-effort controller query) — the jobs-side half of
      the ``summarize_serve()`` cross-link, so an operator reading a
      preemption counter can see which app's autoscaler drove it.
    """
    with _gcs(address) as call:
        rows = call("list_jobs")
    serve_apps: dict[str, list] = {}
    try:
        import ray_tpu
        from ray_tpu.serve._private.constants import (
            CONTROLLER_NAME,
            SERVE_NAMESPACE,
        )

        if ray_tpu.is_initialized():
            controller = ray_tpu.get_actor(CONTROLLER_NAME,
                                           namespace=SERVE_NAMESPACE)
            apps = ray_tpu.get(controller.get_app_status.remote(),
                               timeout=10)
            for app_name, app in apps.items():
                if app.get("job"):
                    serve_apps.setdefault(app["job"], []).append(app_name)
    except Exception:
        pass
    return {
        "jobs": rows,
        "quota_violations": sorted(r["Job"] for r in rows
                                   if r.get("OverQuota")),
        "preemptions": sum(r.get("Preemptions", 0) for r in rows),
        "quota_rejections": sum(r.get("QuotaRejections", 0)
                                for r in rows),
        "serve_apps": serve_apps,
    }


def cluster_status(*, address: str | None = None) -> str:
    """`ray status` analog (reference: scripts.py:1872): node table +
    resource usage summary."""
    from ray_tpu._private.protocol import RpcClient

    with _gcs(address) as call:
        nodes = call("get_nodes")
        lines = ["======== Cluster status ========"]
        alive = [n for n in nodes if n["Alive"]]
        dead = [n for n in nodes if not n["Alive"]]
        lines.append(f"Nodes: {len(alive)} alive, {len(dead)} dead")
        total: dict = {}
        avail: dict = {}
        for n in alive:
            for k, v in n["Resources"].items():
                total[k] = total.get(k, 0) + v
            try:
                c = RpcClient((n["NodeManagerAddress"],
                               n["NodeManagerPort"]), timeout=5.0)
                try:
                    info = c.call("node_info")
                finally:
                    c.close()
                for k, v in info["resources_available"].items():
                    avail[k] = avail.get(k, 0) + v
            except Exception:
                continue
        lines.append("Resources (used/total):")
        for k in sorted(total):
            used = total[k] - avail.get(k, total[k])
            if k == "memory":
                lines.append(f"  {used / 2**30:.1f}/"
                             f"{total[k] / 2**30:.1f} GiB memory")
            else:
                lines.append(f"  {used:g}/{total[k]:g} {k}")
        for n in alive:
            tpu = n.get("tpu")
            suffix = (f" slice={tpu['slice_id']} worker={tpu['worker_id']}"
                      if tpu else "")
            lines.append(f"  node {n['NodeID'][:12]} "
                         f"{n['NodeManagerAddress']}:{n['NodeManagerPort']}"
                         f"{suffix}")
        return "\n".join(lines)


def memory_summary(*, address: str | None = None) -> str:
    """`ray memory` analog (reference: scripts.py:1822)."""
    objs = list_objects(address=address)
    lines = ["======== Object store ========",
             f"Objects tracked: {len(objs)}"]
    total = sum(o["Size"] for o in objs)
    lost = [o for o in objs if o["Lost"]]
    lines.append(f"Total bytes: {total}")
    if lost:
        lines.append(f"Lost objects: {len(lost)}")
    for o in sorted(objs, key=lambda o: -o["Size"])[:20]:
        lines.append(f"  {o['ObjectID'][:16]}  {o['Size']:>12}  "
                     f"on {len(o['Locations'])} node(s)")
    return "\n".join(lines)


def summarize_memory(*, address: str | None = None,
                     top_k: int = 10) -> dict:
    """Memory-anatomy rollup (PR 18): every process's provenance ledger
    (_private/memory_anatomy.py) fanned out like the other telemetry
    RPCs — this process, the GCS, and each raylet's workers — deduped by
    (node, pid) and folded into:

    - ``categories``     cluster-wide live bytes/objects per provenance
                         category (task_arg/task_return/
                         collective_segment/serve_weights/data_staging/
                         checkpoint/other);
    - ``orphans``        leak-sweep rows (deduped by oid — raylet and
                         worker clients sweep the SAME node store) with
                         full creator provenance + reason;
    - ``dropped_frees``  one-way deletes that never landed, per pipeline
                         stage (owner_push/gcs_fanout/raylet_delete);
    - ``train_state``    per-rank params/grads/opt_state/bucket_inflight
                         bytes (exact, from the deterministic flatten);
    - ``top_owners``     the largest live objects cluster-wide;
    - ``per_process``    the raw per-ledger snapshots (ring omitted).
    """
    from ray_tpu._private import memory_anatomy as _ma

    snaps = [_ma.local_snapshot(top_k=top_k)]
    try:
        from ray_tpu._private.worker_runtime import current_worker

        w = current_worker()
        if w is not None:
            snaps[0].setdefault("node", w.node_id)
    except Exception:
        pass
    with _gcs(address) as call:
        try:
            snaps.extend(call("memory_snapshot"))
        except Exception:
            pass   # pre-memory-anatomy GCS build
        snaps.extend(_each_raylet(call, "memory_snapshot"))
    seen: set[tuple] = set()
    procs = []
    for s in snaps:
        key = (s.get("node"), s.get("pid"))
        if key in seen:
            continue
        seen.add(key)
        procs.append(s)

    categories: dict[str, dict] = {}
    dropped: dict[str, int] = {}
    train_state: dict[str, int] = {}
    orphan_by_oid: dict[str, dict] = {}
    owners: list[dict] = []
    for s in procs:
        for cat, v in (s.get("categories") or {}).items():
            agg = categories.setdefault(cat, {"bytes": 0, "objects": 0})
            agg["bytes"] += int(v.get("bytes", 0))
            agg["objects"] += int(v.get("objects", 0))
        for stage, n in (s.get("dropped_frees") or {}).items():
            dropped[stage] = dropped.get(stage, 0) + int(n)
        # per-rank state: each rank process reports its own rows — a
        # later report for the same (kind, rank) supersedes, not adds
        train_state.update(s.get("train_state") or {})
        for row in s.get("orphans") or ():
            orphan_by_oid.setdefault(row.get("oid"), row)
        for row in s.get("top_owners") or ():
            owners.append(dict(row, node=s.get("node")))
    owners.sort(key=lambda r: -(r.get("nbytes") or 0))
    orphans = sorted(orphan_by_oid.values(),
                     key=lambda r: -(r.get("nbytes") or 0))
    return {
        "categories": dict(sorted(categories.items())),
        "live_bytes": sum(c["bytes"] for c in categories.values()),
        "live_objects": sum(c["objects"] for c in categories.values()),
        "orphans": orphans,
        "orphan_bytes": sum(int(r.get("nbytes") or 0) for r in orphans),
        "dropped_frees": dropped,
        "train_state": dict(sorted(train_state.items())),
        "top_owners": owners[:top_k],
        "per_process": [{k: v for k, v in s.items() if k != "ring"}
                        for s in procs],
    }


def _fold_sums(snaps: dict, name: str) -> dict:
    """{sorted-tag-items: value} for one metric family out of a
    ``metrics_summary`` snapshot dict (Counter/Gauge values, Histogram
    observation sums) — the shared fold under every summarize_*."""
    fam = snaps.get(name)
    if not fam:
        return {}
    return {tuple(sorted(v["tags"].items())): v["value"]
            for v in fam.get("values", [])}


def _fold_counts(snaps: dict, name: str) -> dict:
    """{sorted-tag-items: total observation count} for one Histogram
    family out of a ``metrics_summary`` snapshot dict."""
    fam = snaps.get(name)
    if not fam:
        return {}
    return {tuple(sorted(row["tags"].items())): sum(row["counts"])
            for row in fam.get("counts", [])}


def summarize_collectives(*, address: str | None = None) -> dict:
    """Data-plane rollup (reference tier: `ray summary` — but over the
    collective/compile/device telemetry this framework's PR 3 adds).
    Reuses the PR 2 snapshot/aggregation RPCs — everything here is a
    fold over ``metrics_summary()`` plus the cluster event stream, so
    it works connected or standalone exactly like the other summaries:

    - ``ops``        one row per (group, backend, op): call count,
                     total/mean latency, payload bytes moved;
    - ``stragglers`` the COLLECTIVE_STRAGGLER events (group, op, seq,
                     late ranks with their lags);
    - ``compile``    per-fn pjit compile time + cache hit/miss counts
                     (parallel/compile_watch.py);
    - ``devices``    per-device HBM gauges (limits from the raylet's chip
                     probe, live in-use from the owning train workers).
    """
    snaps = {m["name"]: m for m in metrics_summary(address=address)}

    def _sums(name):
        return _fold_sums(snaps, name)

    def _counts(name):
        return _fold_counts(snaps, name)

    ops: dict[tuple, dict] = {}
    lat_sums = _sums("ray_tpu_collective_latency_seconds")
    for key, count in _counts("ray_tpu_collective_latency_seconds").items():
        tags = dict(key)
        total = lat_sums.get(key, 0.0)
        ops[key] = {"group": tags.get("group"),
                    "backend": tags.get("backend"), "op": tags.get("op"),
                    "count": int(count), "total_s": total,
                    "mean_s": (total / count) if count else 0.0,
                    "bytes": 0.0}
    for key, value in _sums("ray_tpu_collective_bytes_total").items():
        tags = dict(key)
        row = ops.setdefault(key, {
            "group": tags.get("group"), "backend": tags.get("backend"),
            "op": tags.get("op"), "count": 0, "total_s": 0.0,
            "mean_s": 0.0, "bytes": 0.0})
        row["bytes"] = value

    compile_fns: dict[str, dict] = {}
    comp_sums = _sums("ray_tpu_pjit_compile_seconds")
    for key, count in _counts("ray_tpu_pjit_compile_seconds").items():
        fn = dict(key).get("fn") or "?"
        total = comp_sums.get(key, 0.0)
        compile_fns[fn] = {"compiles": int(count), "total_s": total,
                           "mean_s": (total / count) if count else 0.0,
                           "cache_hits": 0, "cache_misses": 0}
    for key, value in _sums("ray_tpu_pjit_cache_total").items():
        tags = dict(key)
        fn = tags.get("fn") or "?"
        row = compile_fns.setdefault(fn, {
            "compiles": 0, "total_s": 0.0, "mean_s": 0.0,
            "cache_hits": 0, "cache_misses": 0})
        if tags.get("result") == "hit":
            row["cache_hits"] = int(value)
        elif tags.get("result") == "miss":
            row["cache_misses"] = int(value)

    devices: dict[tuple, dict] = {}
    for key, value in _sums("ray_tpu_device_hbm_bytes").items():
        tags = dict(key)
        # keyed by (node, device): local device ids restart at 0 on
        # every host, so the hostname disambiguates multi-host clusters
        dev = devices.setdefault(
            (tags.get("node"), tags.get("device"), tags.get("platform")),
            {"node": tags.get("node"), "device": tags.get("device"),
             "platform": tags.get("platform")})
        if tags.get("stat") == "in_use":
            dev["hbm_bytes_in_use"] = value
        elif tags.get("stat") == "limit":
            dev["hbm_bytes_limit"] = value

    stragglers = list_cluster_events(
        address=address, filters=[("kind", "=", "COLLECTIVE_STRAGGLER")])
    return {
        "ops": sorted(ops.values(),
                      key=lambda r: (r["group"] or "", r["op"] or "")),
        "stragglers": stragglers,
        "compile": compile_fns,
        "devices": [devices[k] for k in sorted(devices,
                                               key=lambda k: str(k))],
    }


def summarize_data(*, address: str | None = None) -> dict:
    """Streaming-data-plane rollup (folded from the metric catalog like
    ``summarize_collectives``): one row per dataset consumer with its
    batch count, total/mean data-wait, the live prefetch-buffer depth,
    and block counts by origin (local vs remote pulls). The headline
    ingest-health signal is ``mean_wait_s`` against the consumer's step
    time — the ROADMAP's "data wait per step < 5%" acceptance."""
    snaps = {m["name"]: m for m in metrics_summary(address=address)}

    def _sums(name):
        return _fold_sums(snaps, name)

    def _counts(name):
        return _fold_counts(snaps, name)

    consumers: dict[str, dict] = {}

    def _row(consumer):
        return consumers.setdefault(consumer, {
            "consumer": consumer, "batches": 0, "wait_total_s": 0.0,
            "mean_wait_s": 0.0, "prefetch_depth": 0.0,
            "blocks_local": 0, "blocks_remote": 0})

    wait_sums = _sums("ray_tpu_data_wait_seconds")
    for key, count in _counts("ray_tpu_data_wait_seconds").items():
        row = _row(dict(key).get("consumer") or "?")
        total = wait_sums.get(key, 0.0)
        row["batches"] = int(count)
        row["wait_total_s"] = total
        row["mean_wait_s"] = (total / count) if count else 0.0
    for key, value in _sums("ray_tpu_data_prefetch_depth_blocks").items():
        _row(dict(key).get("consumer") or "?")["prefetch_depth"] = value
    for key, value in _sums("ray_tpu_data_blocks_total").items():
        tags = dict(key)
        row = _row(tags.get("consumer") or "?")
        if tags.get("source") == "local":
            row["blocks_local"] = int(value)
        elif tags.get("source") == "remote":
            row["blocks_remote"] = int(value)

    return {"consumers": sorted(consumers.values(),
                                key=lambda r: r["consumer"])}


def summarize_steps(*, address: str | None = None,
                    last: int | None = None) -> dict:
    """Step-anatomy rollup: per-step, per-rank wall-clock attribution
    fused ACROSS the cluster by ``step_id`` (never by wall-clock
    windows — _private/step_anatomy.py). Collects every process's step
    + activity records (driver-local plus a raylet→worker fan-out,
    like the other telemetry RPCs) and returns::

        {"steps": [{"step_id", "ranks": {rank: {wall_s, compute_s,
                     comm_exposed_s, comm_hidden_s, data_wait_s,
                     data_hidden_s, compile_s, other_s,
                     overlap_fraction}},
                    "critical_path": {"rank", "phase", "wall_s"},
                    "overlap_fraction", "complete"}],
         "ranks": per-rank rollups, "regressions": STEP_REGRESSION
         events, "incomplete": ring-eviction flag, "dropped": counts}

    ``last`` keeps only the most recent N steps (post-fusion).
    ``overlap_fraction`` is hidden / (hidden + exposed) auxiliary time —
    the 2011.03641 metric that says whether pipelining paid off;
    ``critical_path`` names the rank and phase that bounded each step.
    """
    from ray_tpu._private import step_anatomy

    exports = [step_anatomy.local_records()]
    with _gcs(address) as call:
        exports.extend(_each_raylet(call, "step_records"))
    fused = step_anatomy.fuse(exports)
    if last is not None:
        fused["steps"] = fused["steps"][-last:] if last else []
    try:
        fused["regressions"] = list_cluster_events(
            address=address, filters=[("kind", "=", "STEP_REGRESSION")])
    except Exception:
        fused["regressions"] = []
    return fused


def summarize_serve(*, address: str | None = None) -> dict:
    """Serving-plane rollup (reference tier: `serve status` + the serve
    dashboard page — but folded from this framework's metric catalog and
    event stream, like ``summarize_collectives``):

    - ``applications``  controller-reported app/deployment/replica FSM
                        status (empty when Serve isn't running);
    - ``requests``      per-deployment completed/error counts, latency
                        totals, sheds, failovers, live queue depth;
    - ``batching``      per-batch-fn executed batch count, mean batch
                        size, mean padded slots (shape-bucket waste);
    - ``events``        replica lifecycle + scaling + shed + tenancy
                        events (REPLICA_STARTED/DIED/DRAINED,
                        SERVE_SCALED, REQUEST_SHED, SERVE_APP_REGISTERED,
                        SERVE_CAPACITY_PLACED, SERVE_REPLICA_WARNED).

    Tenant apps (deployed with ``serve.run(..., job=...)``) carry a
    ``tenancy`` block joined from the GCS job table (the same rows
    ``summarize_jobs()`` reports) for the
    app's job: priority, quota, live usage, dominant share, and the
    preemption / quota-rejection counters — the Serve-side view of the
    same plane the training jobs contend in.
    """
    applications: dict = {}
    try:
        import ray_tpu
        from ray_tpu.serve._private.constants import (
            CONTROLLER_NAME,
            SERVE_NAMESPACE,
        )

        if ray_tpu.is_initialized():
            controller = ray_tpu.get_actor(CONTROLLER_NAME,
                                           namespace=SERVE_NAMESPACE)
            applications = ray_tpu.get(
                controller.get_app_status.remote(), timeout=10)
    except Exception:
        applications = {}
    if any(app.get("job") for app in applications.values()):
        # Straight to the GCS job table: summarize_jobs() would repeat
        # the controller get_app_status RPC made above (its serve_apps
        # cross-link) — doubling controller round-trips per call.
        try:
            with _gcs(address) as call:
                job_rows = {r["Job"]: r for r in call("list_jobs")}
        except Exception:
            job_rows = {}
        for app in applications.values():
            job = app.get("job")
            if job and job in job_rows:
                r = job_rows[job]
                app["tenancy"] = {
                    "priority": r.get("Priority"),
                    "quota": r.get("Quota"),
                    "usage": r.get("Usage"),
                    "dominant_share": r.get("DominantShare"),
                    "preemptions": r.get("Preemptions"),
                    "quota_rejections": r.get("QuotaRejections"),
                    "over_quota": r.get("OverQuota"),
                }

    snaps = {m["name"]: m for m in metrics_summary(address=address)}

    def _sums(name):
        return _fold_sums(snaps, name)

    def _counts(name):
        return _fold_counts(snaps, name)

    requests: dict[str, dict] = {}

    def _dep_row(dep):
        return requests.setdefault(dep, {
            "ok": 0, "error": 0, "latency_total_s": 0.0, "mean_latency_s":
            0.0, "shed": 0, "failovers": 0, "queue_depth": 0.0})

    for key, value in _sums("ray_tpu_serve_requests_total").items():
        tags = dict(key)
        row = _dep_row(tags.get("deployment") or "?")
        if tags.get("result") in ("ok", "error"):
            row[tags["result"]] = int(value)
    lat_sums = _sums("ray_tpu_serve_request_latency_seconds")
    for key, count in _counts("ray_tpu_serve_request_latency_seconds"
                              ).items():
        row = _dep_row(dict(key).get("deployment") or "?")
        total = lat_sums.get(key, 0.0)
        row["latency_total_s"] = total
        row["mean_latency_s"] = (total / count) if count else 0.0
    for key, value in _sums("ray_tpu_serve_shed_total").items():
        _dep_row(dict(key).get("deployment") or "?")["shed"] = int(value)
    for key, value in _sums("ray_tpu_serve_failovers_total").items():
        _dep_row(dict(key).get("deployment") or "?")["failovers"] = \
            int(value)
    for key, value in _sums("ray_tpu_serve_queue_depth_tasks").items():
        # one series per (deployment, role): sum roles for total demand
        _dep_row(dict(key).get("deployment") or "?")["queue_depth"] += value

    batching: dict[str, dict] = {}
    size_sums = _sums("ray_tpu_serve_batch_size_tasks")
    for key, count in _counts("ray_tpu_serve_batch_size_tasks").items():
        fn = dict(key).get("fn") or "?"
        total = size_sums.get(key, 0.0)
        batching[fn] = {"batches": int(count),
                        "mean_batch_size": (total / count) if count else 0.0,
                        "mean_pad_waste": 0.0}
    pad_sums = _sums("ray_tpu_serve_batch_pad_waste_tasks")
    for key, count in _counts("ray_tpu_serve_batch_pad_waste_tasks").items():
        fn = dict(key).get("fn") or "?"
        row = batching.setdefault(fn, {"batches": int(count),
                                       "mean_batch_size": 0.0,
                                       "mean_pad_waste": 0.0})
        total = pad_sums.get(key, 0.0)
        row["mean_pad_waste"] = (total / count) if count else 0.0

    serve_kinds = {"REPLICA_STARTED", "REPLICA_DIED", "REPLICA_DRAINED",
                   "SERVE_SCALED", "REQUEST_SHED", "SERVE_APP_REGISTERED",
                   "SERVE_CAPACITY_PLACED", "SERVE_REPLICA_WARNED"}
    events = [e for e in list_cluster_events(address=address)
              if e.get("kind") in serve_kinds]
    return {"applications": applications, "requests": requests,
            "batching": batching, "events": events}


def metrics_summary(*, address: str | None = None,
                    prometheus: bool = False):
    """Aggregate metrics (user Counter/Gauge/Histogram plus the runtime's
    internal catalog, _private/telemetry.py) across every process: this
    one, the GCS, and each raylet's workers. Snapshots are merged into
    one family per metric name (counters/histograms sum per tag set,
    gauges keep the last collected value; processes reachable via two
    collection paths are deduped by (node, pid)). prometheus=True
    renders the text exposition format (reference: the dashboard agent's
    Prometheus endpoint, reporter_agent.py:296)."""
    from ray_tpu.util.metrics import (
        aggregate_snapshots,
        prometheus_text,
        registry_snapshot,
    )

    with _gcs(address) as call:
        snaps = registry_snapshot()           # this process too
        try:
            snaps.extend(call("metrics_snapshot"))   # the GCS process
        except Exception:
            pass
        snaps.extend(_each_raylet(call, "metrics_snapshot"))
    snaps = aggregate_snapshots(snaps)
    if prometheus:
        return prometheus_text(snaps)
    return snaps


