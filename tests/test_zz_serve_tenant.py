"""Serve as a first-class tenant (late-alphabet; past the tier-1
timeout horizon by design).

Covers PR 16 end to end: the controller's per-replica capacity gangs in
the PR 13 job plane (slot-tag-named, job-labeled, readiness gated on
CREATED), preemption warnings draining replicas inside the grace window,
scale-down riding the SAME warning machinery (self-preempt narrowed by
``pg_name``, gang removed pre-fire), the drain-aware shed contract
(``ServeOverloadedError.draining`` + the router broadcast's ``draining``
deadlines), the fault DSL's slot-tag composition
(``preempt_job:<app-job>.serve_tick``), and the capacity round trip: a
Serve demand spike preempts a training gang through the plane and hands
the capacity back when the spike passes.

Sim-level tests drive the REAL ``_DeploymentState`` FSM (reconcile /
autoscale / capacity poll run unmodified) against the harness GCS via
``sim_serve_deployment_cls``; the E2E runs a real single-node cluster
like tests/test_zz_multitenant.py.
"""
import os
import pickle
import time

import pytest

pytestmark = []


class _Conn:
    """Stub RpcServer connection for direct GCS handler calls."""

    _n = 0

    def __init__(self):
        _Conn._n += 1
        self.id = f"stubconn{_Conn._n}"
        self.meta = {}
        self.alive = True

    def push(self, *a, **k):
        pass


def _fresh(ev0: int, kind: str) -> list:
    """Events of ``kind`` recorded after sequence floor ``ev0`` (the
    ring is process-global — earlier tests leave events behind)."""
    from ray_tpu._private import events

    return [e for e in events.snapshot()
            if e["seq"] > ev0 and e["kind"] == kind]


def _wait(predicate, cluster, timeout_s=15.0, ticks=2):
    """Drive sim ticks until ``predicate()`` holds (gossip at the tick
    boundary is what re-drives the GCS's event-driven pending queue)."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        cluster.run_ticks(ticks)
    return predicate()


# ----------------------------------------------------- capacity-gated start

def test_capacity_gated_start_and_slot_tagged_gang(monkeypatch):
    """A tenant replica only turns RUNNING once its capacity gang is
    CREATED, and the gang is slot-tag-named + job-labeled in the plane
    (the addressable identity chaos schedules and self-preemption use).
    """
    monkeypatch.setenv("RAY_TPU_GCS_PREEMPT_GRACE_S", "0.5")
    from ray_tpu._private import events
    from ray_tpu._private.sim_cluster import SimCluster
    from ray_tpu.serve._private.constants import slot_tag

    ev0 = events.stats()["recorded"]
    cluster = SimCluster(n_nodes=2, tick_interval=0.05).start()
    try:
        app = cluster.add_serve_app(
            "gate", "svc-gate", base_rate=200, service_rate=400,
            min_replicas=1, max_replicas=2, capacity_cpu=2.0)
        assert _wait(lambda: app.live_replicas() == 1, cluster), \
            "replica never turned RUNNING"
        (r,) = app.ds.replicas
        assert r.state == "RUNNING" and r.pg_created
        snap = cluster.gcs_call("get_placement_group",
                                pg_id=r.capacity_pg_id)
        assert snap["State"] == "CREATED"
        assert snap["Name"] == slot_tag(app.dep_id, r.slot)
        assert snap["Job"] == "svc-gate"
        placed = _fresh(ev0, "SERVE_CAPACITY_PLACED")
        assert placed and placed[0]["job"] == "svc-gate"
        assert placed[0]["wait_s"] >= 0.0
    finally:
        cluster.stop()


# ------------------------------------------- scale-down through the warning

def test_scale_down_drains_through_warning(monkeypatch):
    """Autoscaled scale-down self-preempts the victim slot's gang: the
    drain rides the preemption-warning machinery (SERVE_REPLICA_WARNED
    reason=scale_down), completes inside the grace window, and the gang
    is removed PRE-fire — zero PREEMPTION_FIRED for the whole cycle."""
    monkeypatch.setenv("RAY_TPU_GCS_PREEMPT_GRACE_S", "1.0")
    from ray_tpu._private import events
    from ray_tpu._private.sim_cluster import SimCluster

    ev0 = events.stats()["recorded"]
    cluster = SimCluster(n_nodes=3, tick_interval=0.05).start()
    try:
        app = cluster.add_serve_app(
            "sd", "svc-sd", base_rate=900, service_rate=400,
            min_replicas=1, max_replicas=2, capacity_cpu=2.0)
        # demand ~900/tick vs target 400/replica → autoscale to 2
        assert _wait(lambda: app.live_replicas() == 2, cluster), \
            "never scaled up to 2 replicas"
        up_gangs = {r.capacity_pg_id for r in app.ds.replicas}
        assert len(up_gangs) == 2
        # the spike passes: backlog drains, desired falls to 1, and the
        # downscale-delay hysteresis hands one replica to the drain path
        app.base_rate = 50
        assert _wait(lambda: (app.live_replicas() == 1
                              and len(app.ds.replicas) == 1), cluster,
                     timeout_s=20.0), "never scaled back down to 1"
        warned = _fresh(ev0, "SERVE_REPLICA_WARNED")
        assert any(e["reason"] == "scale_down" for e in warned), warned
        assert _fresh(ev0, "PREEMPTION_FIRED") == [], \
            "scale-down drain outlived the grace window"
        kept = {r.capacity_pg_id for r in app.ds.replicas}
        (removed,) = up_gangs - kept
        gone = cluster.gcs_call("get_placement_group", pg_id=removed)
        assert gone is None or gone["State"] == "REMOVED", gone
        jobs = {r["Job"]: r for r in cluster.gcs_call("list_jobs")}
        assert jobs["svc-sd"]["Preemptions"] == 0
        # the accepted backlog was fully served through the drain
        assert app.accepted - app.served - app._queued == 0
    finally:
        cluster.stop()


# ------------------------------------------------- the capacity round trip

def test_capacity_round_trip_spike_preempts_training_then_returns(
        monkeypatch):
    """The tentpole acceptance at sim scale: a demand spike on a
    high-priority Serve tenant claims capacity THROUGH the job plane —
    exactly one training gang is preempted (warning → grace → fire) —
    and when the spike drains, scale-down rides the warning machinery,
    the slot gang is removed pre-fire, and the fired training gang
    resumes CREATED. No flight-recorder dump anywhere in the cycle."""
    monkeypatch.setenv("RAY_TPU_GCS_PREEMPT_GRACE_S", "0.5")
    from ray_tpu._private import events
    from ray_tpu._private.sim_cluster import SimCluster

    ev0 = events.stats()["recorded"]
    cluster = SimCluster(n_nodes=2, tick_interval=0.05).start()
    try:
        def _state(pg_id):
            snap = cluster.gcs_call("get_placement_group", pg_id=pg_id)
            return snap["State"] if snap else "GONE"

        # the app first, on a free cluster: the startup backlog (nothing
        # serves until slot0 places) transiently over-scales, so let it
        # settle to 1 steady replica before packing the training tenants
        app = cluster.add_serve_app(
            "rt", "svc-rt", priority=10, base_rate=100, service_rate=400,
            min_replicas=1, max_replicas=2, capacity_cpu=2.0)
        assert _wait(lambda: (app.live_replicas() == 1
                              and len(app.ds.replicas) == 1
                              and app._queued == 0), cluster,
                     timeout_s=20.0), "app never settled at 1 replica"
        # 8 CPUs total: serve slot0 (2) + 3 training gangs x 2 = full.
        # The spike's second slot MUST claim capacity through the plane.
        cluster.register_job("rt-train", priority=0)
        train = [cluster.create_job_pg("rt-train", n_bundles=1, cpu=2.0)
                 for _ in range(3)]
        assert _wait(lambda: all(_state(p) == "CREATED" for p in train),
                     cluster), "training gangs never placed"
        # age the commits past the GCS's commit-reflection grace (fresh
        # bundles are conservatively double-counted against gossiped
        # availability for ~1.5s, which would over-warn victims)
        cluster.run_ticks(44)
        ev1 = events.stats()["recorded"]

        app.base_rate = 1100          # the spike: desired replicas → 2
        assert _wait(lambda: app.live_replicas() == 2, cluster,
                     timeout_s=20.0), "spike capacity never placed"
        fired = _fresh(ev1, "PREEMPTION_FIRED")
        assert len(fired) == 1 and fired[0]["job"] == "rt-train", fired
        assert sum(_state(p) == "PENDING" for p in train) == 1

        app.base_rate = 50            # the spike passes
        assert _wait(lambda: (app.live_replicas() == 1
                              and all(_state(p) == "CREATED"
                                      for p in train)), cluster,
                     timeout_s=25.0), "training gang never resumed"
        assert any(e["reason"] == "scale_down"
                   for e in _fresh(ev1, "SERVE_REPLICA_WARNED"))
        assert len(_fresh(ev1, "PREEMPTION_FIRED")) == 1, \
            "scale-down fired instead of draining"
        assert _fresh(ev0, "FLIGHT_RECORDER_DUMP") == []
        jobs = {r["Job"]: r for r in cluster.gcs_call("list_jobs")}
        assert jobs["svc-rt"]["Preemptions"] == 0
        assert jobs["rt-train"]["Preemptions"] == 1
        assert app.accepted - app.served - app._queued == 0
    finally:
        cluster.stop()


# --------------------------------------------- fault DSL slot composition

def _chaos_run(seed: int) -> dict:
    """One seeded storm against a tenant app: an app-job-scoped
    ``preempt_job`` rule fans out over the fixed slot range, warning
    every slot's gang simultaneously on the %7 ticks."""
    from ray_tpu._private import fault_injection as fi
    from ray_tpu._private.sim_cluster import SimCluster

    os.environ["RAY_TPU_GCS_PREEMPT_GRACE_S"] = "0.5"
    # 600ms grace: the controller's worst-case reaction is the 0.25s
    # capacity-poll cadence plus two reconcile ticks, so graces under
    # ~0.4s fire before any controller could have drained
    fi.install(seed, "preempt_job:svc-chaos.serve_tick:%7:600")
    cluster = SimCluster(n_nodes=3, tick_interval=0.05).start()
    try:
        app = cluster.add_serve_app(
            "cz", "svc-chaos", base_rate=700, service_rate=400,
            min_replicas=2, max_replicas=3, capacity_cpu=2.0)
        cluster.run_ticks(80)
        out = app.finalize()
        jobs = {r["Job"]: r for r in cluster.gcs_call("list_jobs")}
        return {
            "journal": cluster.journal_text(),
            "lost": out["lost"],
            "served": out["served"],
            "slot_firings": sum("preempt_slot" in ln
                                for ln in cluster.journal),
            "serve_fires": jobs["svc-chaos"]["Preemptions"],
        }
    finally:
        cluster.stop()
        fi.uninstall()
        del os.environ["RAY_TPU_GCS_PREEMPT_GRACE_S"]


@pytest.mark.fault_injection
def test_slot_tag_chaos_composition_deterministic():
    """Satellite: the `preempt_job:<app-job>` schedule composes through
    slot tags — per-(slot, method) counters fire all slots on the same
    tick, warned replicas drain with ZERO lost accepted requests and
    zero serve-side fires, and the journal is byte-identical across two
    runs of the same seed."""
    a = _chaos_run(7)
    assert a["slot_firings"] > 0, "%7 schedule never fired a slot"
    assert a["lost"] == 0, "storm drains lost accepted requests"
    assert a["served"] > 0
    assert a["serve_fires"] == 0, "a warned slot outlived its grace"
    b = _chaos_run(7)
    assert a["journal"] == b["journal"], "chaos journal not reproducible"


# ------------------------------------------------- drain-aware shed contract

def test_shed_error_carries_drain_hint():
    """Satellite: ``ServeOverloadedError`` distinguishes a capacity
    storm (draining=True, retry-after = grace remaining) from a load
    blip, and the distinction survives the pickle boundary replicas
    ship errors across."""
    from ray_tpu.exceptions import ServeOverloadedError

    e = ServeOverloadedError("app#main", queued=7, retry_after_s=2.5,
                             draining=True)
    assert e.draining is True and e.retry_after_s == 2.5 and e.queued == 7
    assert "draining" in str(e)
    e2 = pickle.loads(pickle.dumps(e))
    assert (e2.deployment_id, e2.queued, e2.retry_after_s, e2.draining) \
        == ("app#main", 7, 2.5, True)
    blip = ServeOverloadedError("app#main", queued=3)
    assert blip.draining is False and "draining" not in str(blip)


class _RecordingHost:
    """LongPollHost stand-in capturing the latest broadcast per key."""

    def __init__(self):
        self.values = {}

    def notify_changed(self, key, value):
        self.values[key] = value

    def drop_key(self, key):
        self.values.pop(key, None)


def test_warning_reaches_router_broadcast():
    """An external preempt warning on a replica's gang leaves the
    replica set and lands in the broadcast's ``draining`` list with the
    grace deadline (the router's proactive-drop + retry-after source);
    the drain completes pre-fire so the warning never becomes a fire."""
    from ray_tpu._private import events
    from ray_tpu._private.gcs import GcsServer
    from ray_tpu._private.sim_cluster import sim_serve_deployment_cls
    from ray_tpu.serve._private.constants import (deployment_id,
                                                  replicas_key, slot_tag)

    server = GcsServer(port=0).start()
    try:
        def gcs_call(method, **kw):
            return getattr(server, "rpc_" + method)(_Conn(), **kw)

        server.rpc_register_node(_Conn(), node_id="n1",
                                 addr=("127.0.0.1", 1),
                                 resources={"CPU": 4.0}, meta={})
        gcs_call("register_job", name="bh", quota=None, priority=5)
        dep_id = deployment_id("bh", "main")
        host = _RecordingHost()
        spec = {"name": "main", "user_callable": None, "version": "1",
                "config": {"num_replicas": 1, "max_ongoing_requests": 8,
                           "max_queued_requests": 100,
                           "graceful_shutdown_timeout_s": 1.0,
                           "health_check_period_s": 3600.0,
                           "ray_actor_options": {"num_cpus": 1.0}}}
        # Hold drains open until the test releases them: the sim stub
        # drains instantly, which collapses detect → drain → reap into
        # one reconcile and makes the draining broadcast zero-width.
        drain_gate = {"open": False}

        class _GatedDrain(sim_serve_deployment_cls()):
            def _check_drained(self, r):
                return drain_gate["open"]

            def _begin_stop(self, r, deadline_s=None):
                # the sim stub expires the drain deadline instantly;
                # honor the grace window so the gate actually holds
                super()._begin_stop(r, deadline_s)
                r.drain_deadline = time.monotonic() + (deadline_s or 1.0)

        ds = _GatedDrain(dep_id, spec, host, job="bh", gcs_call=gcs_call)

        def spin(pred, timeout_s=5.0):
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                ds.reconcile()
                if pred():
                    return True
                time.sleep(0.05)
            return pred()

        assert spin(lambda: any(r.state == "RUNNING"
                                for r in ds.replicas))
        rkey = replicas_key(dep_id)
        assert len(host.values[rkey]["replicas"]) == 1
        assert host.values[rkey]["draining"] == []
        old_pg = ds.replicas[0].capacity_pg_id
        ev0 = events.stats()["recorded"]
        victim = gcs_call("preempt_job", name="bh", grace_s=1.0,
                          pg_name=slot_tag(dep_id, 0))
        assert victim is not None
        assert spin(lambda: bool(_fresh(ev0, "SERVE_REPLICA_WARNED")))
        warned = _fresh(ev0, "SERVE_REPLICA_WARNED")
        assert warned[0]["reason"] == "preempted"
        b = host.values[rkey]
        assert b["replicas"] == [], "warned replica still in rotation"
        assert len(b["draining"]) == 1
        assert b["draining"][0]["deadline_ts"] > time.time()
        # release the drain: the reap removes the gang pre-fire; the
        # replacement comes up on a FRESH gang; sleeping past the
        # grace window proves the removed gang's fire was no-opped
        drain_gate["open"] = True
        assert spin(lambda: any(r.state == "RUNNING" and not r.warned
                                for r in ds.replicas))
        gone = gcs_call("get_placement_group", pg_id=old_pg)
        assert gone is None or gone["State"] == "REMOVED", gone
        time.sleep(1.1)
        assert _fresh(ev0, "PREEMPTION_FIRED") == [], \
            "pre-fire gang removal did not cancel the fire"
    finally:
        server.stop()


def test_preemption_reprieve_when_preemptor_leaves(monkeypatch):
    """Tentpole hardening: a warned victim whose preemptor stops
    needing the capacity inside the grace window (here the pending
    gang is removed — the spike evaporated) is reprieved at fire
    time: PREEMPTION_CANCELED, the victim keeps its bundles, and no
    fire is recorded."""
    from ray_tpu._private import events
    from ray_tpu._private.gcs import GcsServer

    monkeypatch.setenv("RAY_TPU_GCS_PREEMPT_GRACE_S", "0.6")
    server = GcsServer(port=0).start()
    try:
        def gcs_call(method, **kw):
            return getattr(server, "rpc_" + method)(_Conn(), **kw)

        server.rpc_register_node(_Conn(), node_id="n1",
                                 addr=("127.0.0.1", 1),
                                 resources={"CPU": 4.0}, meta={})
        gcs_call("register_job", name="lo", quota=None, priority=0)
        gcs_call("register_job", name="hi", quota=None, priority=10)
        lo_id, hi_id = b"\x01" * 16, b"\x02" * 16
        gcs_call("create_placement_group", pg_id=lo_id,
                 bundles=[{"CPU": 4.0}], strategy="PACK", name="lo-g",
                 job="lo")
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if gcs_call("get_placement_group",
                        pg_id=lo_id)["State"] == "CREATED":
                break
            time.sleep(0.02)
        ev0 = events.stats()["recorded"]
        gcs_call("create_placement_group", pg_id=hi_id,
                 bundles=[{"CPU": 4.0}], strategy="PACK", name="hi-g",
                 job="hi")
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            gcs_call("get_placement_group", pg_id=hi_id)  # re-drives queue
            if _fresh(ev0, "PREEMPTION_WARNED"):
                break
            time.sleep(0.02)
        warned = _fresh(ev0, "PREEMPTION_WARNED")
        assert warned and warned[0]["job"] == "lo"
        # the demand evaporates inside the grace window
        gcs_call("remove_placement_group", pg_id=hi_id)
        time.sleep(0.8)   # past the grace: the armed fire must cancel
        canceled = _fresh(ev0, "PREEMPTION_CANCELED")
        assert len(canceled) == 1 and canceled[0]["job"] == "lo"
        assert _fresh(ev0, "PREEMPTION_FIRED") == [], \
            "victim fired for a preemptor that no longer exists"
        snap = gcs_call("get_placement_group", pg_id=lo_id)
        assert snap["State"] == "CREATED"
        assert snap["PreemptDeadline"] is None
        jobs = {r["Job"]: r for r in gcs_call("list_jobs")}
        assert jobs["lo"]["Preemptions"] == 0
    finally:
        server.stop()


# ----------------------------------------------------------- runtime E2E

@pytest.fixture
def serve_rt(monkeypatch):
    """Single-node runtime with a short preemption grace window; tears
    the Serve instance down after (detached actors outlive tests)."""
    monkeypatch.setenv("RAY_TPU_GCS_PREEMPT_GRACE_S", "1.0")
    try:
        import ray_tpu

        ray_tpu.init(num_cpus=4, object_store_memory=64 * 1024 * 1024)
    except (ImportError, ModuleNotFoundError) as e:
        pytest.skip(f"runtime not built yet: {e}")
    yield ray_tpu
    from ray_tpu import serve

    try:
        serve.shutdown()
    except Exception:
        pass
    ray_tpu.shutdown()


class _EchoTenant:
    def __call__(self, x):
        return f"echo:{x}"


@pytest.mark.chaos
def test_serve_tenant_preempts_training_and_returns_capacity_e2e(serve_rt):
    """The tentpole E2E on the real runtime: a tenant app whose replica
    capacity cannot place preempts a lower-priority training gang
    through the job plane (exactly one fire, no GANG_FAILED, no
    flight-recorder dump), serves traffic while holding the capacity,
    shows up on both sides of the jobs↔serve state-API cross-link, and
    hands the capacity back on delete — the training gang resumes."""
    ray = serve_rt
    from ray_tpu import serve
    from ray_tpu._private import events
    from ray_tpu.experimental.state.api import summarize_jobs, summarize_serve
    from ray_tpu.util import jobs
    from ray_tpu.util.placement_group import placement_group

    ev0 = events.stats()["recorded"]
    jobs.register_job("svcE2E-train", priority=0)
    pg = placement_group([{"CPU": 4.0}], strategy="PACK",
                         job="svcE2E-train")
    assert pg.wait(timeout_seconds=15.0), "training gang never placed"

    serve.start(http_options={"host": "127.0.0.1", "port": 0})     # its own
    dep = serve.deployment(_EchoTenant)
    handle = serve.run(dep.bind(), name="echo_app", route_prefix=None,
                       job="svcE2E", job_priority=10, _timeout_s=90.0)
    # the replica's capacity gang could not place on the full node: it
    # preempted the training gang (grace → fire) through the plane
    fired = _fresh(ev0, "PREEMPTION_FIRED")
    assert len(fired) == 1 and fired[0]["job"] == "svcE2E-train", fired
    assert _fresh(ev0, "GANG_FAILED") == []
    assert _fresh(ev0, "FLIGHT_RECORDER_DUMP") == []
    # the app actually serves while holding tenant capacity
    assert handle.remote("hi").result(timeout_s=30.0) == "echo:hi"
    # cross-links: the jobs side names the app; the serve side carries
    # the tenancy block joined from the job row
    sj = summarize_jobs()
    assert "echo_app" in sj["serve_apps"].get("svcE2E", []), sj["serve_apps"]
    assert sj["quota_violations"] == []
    ten = summarize_serve()["applications"]["echo_app"].get("tenancy")
    assert ten and ten["priority"] == 10
    # the spike passes: deleting the app drains the replica, removes the
    # capacity gang, and the fired training gang re-places
    serve.delete("echo_app")
    assert pg.wait(timeout_seconds=30.0), "training gang never resumed"
    assert len(_fresh(ev0, "PREEMPTION_FIRED")) == 1
    rows = {r["Job"]: r for r in summarize_jobs()["jobs"]}
    assert rows["svcE2E-train"]["Preemptions"] == 1
    assert rows["svcE2E"]["Preemptions"] == 0


# --------------------------------------------------- death-feed capacity leak

def test_death_feed_releases_capacity_gang():
    """Review pin: a replica crash delivered via the GCS death feed must
    release the replica's capacity gang exactly like _kill/_drop — the
    fast path used to drop the replica from the list only, leaking a
    CREATED, job-labeled, quota-counted gang per crash (and the
    replacement's slot-tag name then collided with the zombie's)."""
    from ray_tpu.serve._private.controller import (
        RUNNING,
        _DeploymentState,
        _Replica,
    )
    from ray_tpu.serve._private.long_poll import LongPollHost

    calls = []
    ds = _DeploymentState(
        "app#d", {"name": "d", "user_callable": object, "config": {}},
        LongPollHost(), job="svc-leak",
        gcs_call=lambda method, **kw: calls.append((method, kw)))

    class _H:
        _actor_id = b"\xab" * 8

    r = _Replica("d#r0", "actor0", _H(), ready_ref=None, slot=0)
    r.state = RUNNING
    r.capacity_pg_id = b"\x01" * 16
    ds.replicas = [r]

    assert ds.on_actor_death(_H._actor_id.hex())
    assert ds.replicas == []
    assert ("remove_placement_group", {"pg_id": b"\x01" * 16}) in calls, \
        "death-feed drop leaked the replica's capacity gang"
    assert r.capacity_pg_id is None
