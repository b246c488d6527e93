"""Raylet — the per-node manager.

TPU-native analog of the reference's raylet (/root/reference/src/ray/raylet/
node_manager.h): owns this node's shared-memory object store segment, a pool
of worker processes (worker_pool.h:152), and the local half of the two-level
scheduler — lease requests are granted locally when resources fit, spilled
back to another node otherwise (the hybrid policy of
scheduling/policy/hybrid_scheduling_policy.h:24-47: pack onto the local node
below a utilization threshold, then spread).

Differences from the reference, by design:
- the object store is a mapped library, not a forked daemon, so "starting
  plasma" is just creating the segment;
- GCS holds the authoritative cluster resource view (the RaySyncer gossip is
  replaced by raylets reporting load on heartbeat);
- TPU chips are a first-class resource: the raylet detects locally attached
  chips via jax and advertises them as "TPU" alongside "CPU"/"memory".
"""
from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time
import uuid

from ray_tpu._private import profiling as _prof
from ray_tpu._private.protocol import ConnectionLost, RpcClient, RpcServer
from ray_tpu._private.store_client import StoreClient

_LEASE_QUEUE_POLL = 0.02
# how long `Raylet.stop` waits for a child it sent SIGKILL to be reaped
_REAP_TIMEOUT_S = 60.0


def _chip_detection_enabled() -> bool:
    # On by default on real deployments; off under tests (a jax-importing
    # probe subprocess per in-process raylet is slow, and every virtual
    # node of a test cluster would advertise the same local chips).
    default = "0" if os.environ.get("RAY_TPU_TESTING") == "1" else "1"
    return os.environ.get("RAY_TPU_DETECT_CHIPS", default) == "1"


def _probe_local_chips() -> dict | None:
    """The one chip probe of this process (memoized), or None when
    detection is off or found no chips. A SUBPROCESS, because the raylet
    shares the driver's process and must not own the chips it leases to
    workers; synchronous, so the chips are free again before
    ``ray_tpu.init()`` returns and a worker can ask for them."""
    if not _chip_detection_enabled():
        return None
    from ray_tpu._private import tpu_probe
    from ray_tpu._private.config import get_config

    timeout_s = float(get_config("chip_probe_timeout_s"))
    if tpu_probe.probed():
        return tpu_probe.probe_chips(timeout_s=timeout_s)
    # the span is the subprocess: a JAX start that takes the chips, counts
    # them and lets them go, all before `init()` returns
    args = {"timeout_s": timeout_s}
    with _prof.record_span("startup", "chip_probe", args):
        found = tpu_probe.probe_chips(timeout_s=timeout_s)
        args["chips"] = (found or {}).get("chips", 0)
    return found


def detect_tpu_topology() -> dict | None:
    """Structured TPU topology for this host (the ICI-aware scheduler's
    input; reference role: the flat `resources: {"TPU": n}` of
    autoscaler/gcp/tpu.yaml:29, which loses slice/coord structure).

    Sources: the TPU runtime env (TPU_ACCELERATOR_TYPE / TPU_TOPOLOGY /
    TPU_WORKER_ID / TPU_NAME are set on GCE/GKE TPU VMs) plus jax device
    coords when available. Returns None off-TPU.
    """
    env = os.environ
    info: dict = {}
    if env.get("TPU_ACCELERATOR_TYPE"):
        info["accelerator_type"] = env["TPU_ACCELERATOR_TYPE"]
    if env.get("TPU_TOPOLOGY"):
        info["topology"] = env["TPU_TOPOLOGY"]
    if env.get("TPU_WORKER_ID") is not None and env.get("TPU_WORKER_ID") != "":
        try:
            info["worker_id"] = int(env["TPU_WORKER_ID"])
        except ValueError:
            pass
    slice_id = env.get("TPU_NAME") or env.get("TPU_SLICE_ID")
    if slice_id:
        info["slice_id"] = slice_id
    for k, v in (_probe_local_chips() or {}).items():
        if k != "devices":              # per-device records feed the gauges
            info.setdefault(k, v)       # env-derived identity wins
    if not info:
        return None
    info.setdefault("slice_id", "slice-0")
    info.setdefault("worker_id", 0)
    return info


def detect_resources(num_cpus=None, num_tpus=None, memory=None,
                     resources=None) -> dict:
    out = dict(resources or {})
    out["CPU"] = float(num_cpus if num_cpus is not None else os.cpu_count() or 1)
    if num_tpus is None:
        num_tpus = (_probe_local_chips() or {}).get("chips", 0)
    if num_tpus:
        out["TPU"] = float(num_tpus)
    if memory is None:
        try:
            memory = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
        except (ValueError, OSError):
            memory = 8 << 30
    out["memory"] = float(memory)
    return out


class WorkerHandle:
    def __init__(self, proc: subprocess.Popen, worker_id: str):
        self.proc = proc
        self.worker_id = worker_id
        self.addr = None            # set when the worker registers
        self.registered = threading.Event()
        self.idle_since = time.time()
        self.assigned_lease = None  # lease_id when leased out
        self.is_actor = False
        self.actor_id = None
        # id of the `worker_spawn` span, taken ahead: the worker learns it
        # at registration, the span is recorded once it has registered
        self.spawn_span = _prof.next_id() if proc is not None else None


class Lease:
    def __init__(self, lease_id: str, resources: dict, worker: WorkerHandle,
                 lessee: tuple | None = None, job: str | None = None):
        self.lease_id = lease_id
        self.resources = resources
        self.worker = worker
        self.granted_at = time.time()   # OOM victim ranking (newest first)
        # (worker_id, addr) of the requesting core worker: leases die with
        # their lessee (reference: leases are tied to the lease client's
        # connection; a dead lessee's resources must be reclaimed)
        self.lessee_id = lessee[0] if lessee else None
        self.lessee_addr = tuple(lessee[1]) if lessee else None
        # multi-tenant label: per-job lease usage is gossiped to the GCS
        # (quota accounting) and over-quota jobs are throttled at grant
        self.job = job or None


class Raylet:
    def __init__(self, gcs_addr, node_id: str | None = None,
                 host: str = "127.0.0.1", port: int = 0,
                 resources: dict | None = None,
                 store_size: int = 256 * 1024 * 1024,
                 session_dir: str | None = None,
                 tpu_topology: dict | None = None):
        self.node_id = node_id or uuid.uuid4().hex[:16]
        self.gcs_addr = tuple(gcs_addr)
        self.resources_total = dict(resources or detect_resources())
        # structured TPU info for the ICI-aware PG scheduler; tests inject
        # fake slices, real deployments auto-detect
        self.tpu_topology = (tpu_topology if tpu_topology is not None
                             else detect_tpu_topology())
        if tpu_topology is None:
            # real chips (not test-injected topology): seed the per-device
            # HBM limit gauges from the probe that already ran. Live
            # in-use numbers come from the owning train workers.
            from ray_tpu._private.tpu_probe import publish_device_gauges

            publish_device_gauges(
                (_probe_local_chips() or {}).get("devices") or [])
        self.resources_avail = dict(self.resources_total)
        self.session_dir = session_dir or os.path.join(
            "/tmp/ray_tpu", f"session_{os.getpid()}")
        os.makedirs(self.session_dir, exist_ok=True)
        self.store_name = f"rtpu-{self.node_id[:12]}"
        self.spill_dir = os.path.join(self.session_dir,
                                      f"spill_{self.node_id[:8]}")
        self.store = StoreClient(self.store_name, create=True,
                                 size=store_size, spill_dir=self.spill_dir)
        # native (C++) chunk server: remote pulls stream object bytes out
        # of the mmap'd segment GIL-free (src/store/data_server.cc)
        try:
            self.data_port = self.store.start_data_server()
        except Exception:
            self.data_port = None
        self._lock = threading.RLock()
        self._workers: dict[str, WorkerHandle] = {}    # worker_id -> handle
        # every child not yet reaped, whether or not a handle still names
        # it (a killed or disconnected worker leaves `_workers` while its
        # process is still dying): `stop()` waits for all of these
        self._procs: list[subprocess.Popen] = []
        self._starting = 0          # `Popen`s in flight
        self._idle: list[WorkerHandle] = []
        self._leases: dict[str, Lease] = {}
        self._pending: list[dict] = []                 # queued lease requests
        self._pg_reserved: dict[tuple, dict] = {}      # (pg_id,bundle) -> res
        # resource shapes of requests currently queued on this node — the
        # autoscaler's demand signal (reference: LoadMetrics resource_load)
        self._queued_demand: list[dict] = []
        # jobs the GCS currently reports over quota (`jobs` channel):
        # lease grants for these queue until the throttle clears.
        # Replaced wholesale per quota push, never grown per id.
        self._job_throttle: frozenset[str] = frozenset()
        self._stopped = False

        # Monitors are CONSTRUCTED before the RPC server starts: the
        # moment the server is up (and register_node lands), a remote
        # driver can send request_lease → _spawn_worker, which needs
        # logs_dir/_log_monitor. Their threads start only after the GCS
        # connection exists (their publish/kill hooks ride it).
        from ray_tpu._private.log_monitor import LogMonitor
        from ray_tpu._private.memory_monitor import MemoryMonitor

        self.logs_dir = os.path.join(self.session_dir, "logs")
        os.makedirs(self.logs_dir, exist_ok=True)
        # Worker log capture → GCS pubsub → driver console (reference:
        # _private/log_monitor.py as a thread instead of a process).
        from ray_tpu._private.config import get_config

        self._log_monitor = LogMonitor(
            lambda ch, msg: self._gcs.push("publish", channel=ch,
                                           message=msg),
            node_id=self.node_id,
            interval_s=get_config("log_monitor_interval_ms") / 1000.0)
        # OOM protection: poll node memory; above the threshold kill the
        # newest-task worker with a retriable OutOfMemoryError instead of
        # letting the kernel OOM-killer take the node (reference:
        # common/memory_monitor.h:88 + raylet/worker_killing_policy.h:30).
        self._oom_reasons: dict[str, str] = {}   # worker_id -> message
        self._mem_monitor = MemoryMonitor(self._on_memory_pressure)
        # worker-pool spawn state — must exist before the server starts
        # accepting lease requests (they reach _spawn_worker)
        self._idle_cap = int(get_config("idle_worker_cap"))
        self._prestart_target = min(
            int(self.resources_total.get("CPU", 1)), self._idle_cap,
            int(get_config("prestart_workers")))
        self._spawning = 0
        startup_conc = int(get_config("max_startup_concurrency"))
        if startup_conc <= 0:
            startup_conc = os.cpu_count() or 2
        self._spawn_gate = threading.BoundedSemaphore(max(2, startup_conc))

        self._server = RpcServer(self, host, port).start()
        self.addr = self._server.addr
        # Self-healing GCS channel: survives a GCS restart by
        # re-registering this node and re-announcing its live actors
        # (reference: node_manager.cc:1179 HandleNotifyGCSRestart)
        from ray_tpu._private.protocol import ReconnectingRpcClient

        self._gcs = ReconnectingRpcClient(
            self.gcs_addr, on_push=self._on_gcs_push,
            on_reconnect=self._replay_gcs_registration)
        self._replay_gcs_registration(self._gcs)
        self._reaper = threading.Thread(target=self._reap_loop, daemon=True,
                                        name=f"raylet-reap-{self.node_id[:6]}")
        self._reaper.start()
        self._log_monitor.start()
        self._mem_monitor.start()
        # Warm pool: prestart workers so the first leases don't eat Python
        # startup latency, and REFILL toward this watermark whenever the
        # pool is drawn down (reference: worker_pool.h PrestartWorkers +
        # idle-pool maintenance) — on-demand cold spawns under load cost
        # ~300ms each of lease-grant latency (profiled round 4).
        if self._prestart_target > 0:
            self._maybe_refill()

    def _replay_gcs_registration(self, gcs):
        """Initial registration AND the reconnect replay: (re-)register
        this node, re-subscribe, and re-announce actors still running
        here so a restarted GCS repopulates its actor table with live
        addresses instead of restarting healthy actors."""
        gcs.call("register_node", node_id=self.node_id, addr=self.addr,
                 resources=self.resources_total,
                 meta={"store_name": self.store_name,
                       "spill_dir": self.spill_dir,
                       "session_dir": self.session_dir,
                       "hostname": os.uname().nodename,
                       "pid": os.getpid(),
                       "object_data_port": self.data_port,
                       "tpu": self.tpu_topology})
        gcs.call("subscribe", channels=["placement_groups", "jobs"])
        try:
            # seed the over-quota view: the jobs channel is
            # publish-on-change, so a fresh (or re-registering) node
            # can't wait for the next transition to learn the CURRENT
            # set. Best-effort — a miss degrades to unthrottled grants
            # until the next change push, never fails registration.
            self._job_throttle = frozenset(
                gcs.call("get_job_throttle"))
        except Exception:
            pass
        with self._lock:
            live = [(h.actor_id, h.addr)
                    for h in self._workers.values()
                    if h.is_actor and h.actor_id and h.addr
                    and h.proc is not None and h.proc.poll() is None]
        # Failures here MUST propagate: the replay only runs on
        # reconnect, and a swallowed actor_started would leave the actor
        # out of the GCS's re-announce set — the recovery reconcile
        # would then restart a healthy actor (split-brain). Raising
        # aborts this reconnect; the next 600ms report tick retries the
        # whole replay.
        for actor_id, addr in live:
            gcs.call("actor_started", actor_id=actor_id, addr=addr,
                     node_id=self.node_id)

    def _maybe_refill(self):
        """Top the idle pool back up to the prestart watermark in the
        background (never blocks a grant)."""
        if self._stopped:
            return
        with self._lock:
            deficit = (self._prestart_target - len(self._idle)
                       - self._spawning)
            if deficit <= 0:
                return
            self._spawning += deficit
        threading.Thread(target=self._refill, args=(deficit,),
                         daemon=True).start()

    def _refill(self, n: int):
        try:
            handles = [self._spawn_worker() for _ in range(n)]
            for h in handles:
                if h.registered.wait(30.0) and h.proc.poll() is None:
                    with self._lock:
                        if (h.assigned_lease is None
                                and h not in self._idle
                                and len(self._idle) < self._idle_cap):
                            self._idle.append(h)
                        elif h.assigned_lease is None:
                            # pool refilled concurrently (returned leases
                            # beat us): a worker neither idle nor leased
                            # would be an orphan process — kill it
                            self._kill_worker(h)
        except Exception:
            pass   # raylet stopping mid-refill
        finally:
            with self._lock:
                self._spawning -= n

    # ---- GCS pushes ---------------------------------------------------------

    def _on_gcs_push(self, payload):
        """Runs on the GCS RpcClient's reader thread — must NEVER issue a
        synchronous call back over the same connection (the reply could not
        be read). Handlers are either local-only or spawn a thread."""
        method, kwargs = payload
        if method == "free_objects":
            for oid in kwargs["object_ids"]:
                try:
                    self.store.delete(oid)
                except Exception:
                    # last hop of the one-way free pipeline lost: the
                    # object strands in this node's store until the
                    # leak sweep names it — count the drop
                    try:
                        from ray_tpu._private import memory_anatomy

                        memory_anatomy.LEDGER.note_free_dropped(
                            "raylet_delete")
                    except Exception:
                        pass
        elif method == "recreate_actor":
            threading.Thread(target=self._restart_actor,
                             args=(kwargs["actor_id"],), daemon=True).start()
        elif method == "pubsub" and kwargs.get("channel") == "placement_groups":
            msg = kwargs["message"]
            if msg["event"] == "created":
                self._reserve_pg_bundles(msg["pg_id"], msg["bundle_nodes"],
                                         msg["bundles"])
            elif msg["event"] == "removed":
                self._release_pg_bundles(msg["pg_id"])
        elif method == "pubsub" and kwargs.get("channel") == "jobs":
            msg = kwargs["message"]
            if msg.get("event") == "quota":
                # cluster-wide quota view (eventually consistent by one
                # gossip round); queued lease grants re-check it per poll
                self._job_throttle = frozenset(msg.get("over", ()))

    def _reserve_pg_bundles(self, pg_id: bytes, bundle_nodes: list[str],
                            bundles: list[dict]):
        with self._lock:
            for i, (bundle, nid) in enumerate(zip(bundles, bundle_nodes)):
                key = (pg_id, i)
                if nid == self.node_id and key not in self._pg_reserved:
                    for k, v in bundle.items():
                        self.resources_avail[k] = \
                            self.resources_avail.get(k, 0) - v
                    self._pg_reserved[key] = dict(bundle)

    def _release_pg_bundles(self, pg_id: bytes):
        with self._lock:
            for key in [k for k in self._pg_reserved if k[0] == pg_id]:
                for res, v in self._pg_reserved.pop(key).items():
                    self.resources_avail[res] = \
                        self.resources_avail.get(res, 0) + v
        self._pump_pending()

    # ---- worker pool (reference: raylet/worker_pool.h) ----------------------

    def _spawn_worker(self, cause: dict | None = None) -> WorkerHandle:
        """``cause``: the ``trace_ctx`` of the spec this worker is spawned
        for (an actor's creation), None for the pool's own refill. The
        `worker_spawn` span (Popen → the worker registered) names it as
        its parent; the worker is told the span's id when it registers
        and hangs its `worker_boot` under it."""
        if self._stopped:
            raise RuntimeError("raylet is stopped")
        # Bound concurrent process STARTUPS (reference: worker_pool.h
        # maximum_startup_concurrency = num_cpus): 400 actors creating at
        # once means 400 interpreters importing simultaneously on however
        # many cores exist — everything times out. The gate is held from
        # fork until the worker registers (or 30 s), so at most gate-width
        # workers are mid-startup; callers keep their own registered.wait.
        self._spawn_gate.acquire()
        started = time.time()
        try:
            handle = self._spawn_worker_inner()
        except BaseException:
            self._spawn_gate.release()
            raise

        def _release_when_up():
            try:
                up = handle.registered.wait(30.0)
            finally:
                self._spawn_gate.release()
            cause_ = cause or {}
            _prof.record_completed_span(
                "startup", "worker_spawn", started, time.time() - started,
                {"worker_id": handle.worker_id, "registered": up},
                parent=cause_.get("cause"), run=cause_.get("run"),
                span_id=handle.spawn_span)

        threading.Thread(target=_release_when_up, daemon=True).start()
        return handle

    def _spawn_worker_inner(self) -> WorkerHandle:
        worker_id = uuid.uuid4().hex[:16]
        env = dict(os.environ)
        env["RAY_TPU_WORKER_ID"] = worker_id
        env["RAY_TPU_RAYLET_ADDR"] = f"{self.addr[0]}:{self.addr[1]}"
        env["RAY_TPU_GCS_ADDR"] = f"{self.gcs_addr[0]}:{self.gcs_addr[1]}"
        env["RAY_TPU_STORE_NAME"] = self.store_name
        env["RAY_TPU_SPILL_DIR"] = self.spill_dir
        env["RAY_TPU_NODE_ID"] = self.node_id
        # driver's init(system_config=...) overrides reach workers as env
        # (config keys consumed worker-side would otherwise silently keep
        # their defaults there)
        from ray_tpu._private.config import GlobalConfig

        env.update(GlobalConfig.system_override_env())
        # Make ray_tpu importable from anywhere.
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        if repo_root not in parts:
            parts.insert(0, repo_root)
        env["PYTHONPATH"] = os.pathsep.join(parts)
        # Workers log to per-worker files in the session dir (reference:
        # workers write session_latest/logs/worker-*.out/.err, tailed by
        # the log monitor); the raylet's LogMonitor streams new lines to
        # the driver over pubsub.
        out_path = os.path.join(self.logs_dir, f"worker-{worker_id}.out")
        err_path = os.path.join(self.logs_dir, f"worker-{worker_id}.err")
        with self._lock:
            if self._stopped:
                raise RuntimeError("raylet is stopped")
            self._starting += 1     # `stop()` waits for this Popen
        proc = None
        try:
            with open(out_path, "ab") as out_f, \
                    open(err_path, "ab") as err_f:
                proc = subprocess.Popen(
                    [sys.executable, "-m", "ray_tpu._private.worker_main"],
                    env=env, cwd=os.getcwd(),
                    stdout=out_f, stderr=err_f)
            handle = WorkerHandle(proc, worker_id)
        finally:
            with self._lock:
                self._starting -= 1
                if proc is not None:
                    self._procs = [p for p in self._procs
                                   if p.poll() is None]
                    self._procs.append(proc)
                    self._workers[worker_id] = handle
        self._log_monitor.track(worker_id, proc.pid, out_path, err_path)
        return handle

    def _pop_worker(self, timeout: float | None = None,
                    cause: dict | None = None) -> WorkerHandle:
        if timeout is None:
            from ray_tpu._private.config import get_config

            timeout = float(get_config("worker_register_timeout_s"))
        with self._lock:
            while self._idle:
                handle = self._idle.pop()
                if handle.proc.poll() is None:
                    break
            else:
                handle = None
        if handle is not None:
            self._maybe_refill()   # keep the next burst warm
            return handle
        handle = self._spawn_worker(cause)
        self._maybe_refill()
        if not handle.registered.wait(timeout):
            raise TimeoutError(
                f"worker {handle.worker_id} failed to register in {timeout}s")
        return handle

    def rpc_register_worker(self, conn, worker_id: str, addr, pid: int):
        with self._lock:
            handle = self._workers.get(worker_id)
            if handle is None:      # externally started (driver) — track it
                handle = WorkerHandle(None, worker_id)
                self._workers[worker_id] = handle
            handle.addr = tuple(addr)
            conn.meta["worker_id"] = worker_id
        handle.registered.set()
        # `node` is the snapshot shape _pull_remote consumes — workers hand
        # it to object OWNERS when announcing copies (owner-based directory)
        return {"node_id": self.node_id, "store_name": self.store_name,
                "spill_dir": self.spill_dir,
                "spawn_span": handle.spawn_span,
                "node": {"NodeID": self.node_id,
                         "NodeManagerAddress": self.addr[0],
                         "NodeManagerPort": self.addr[1],
                         "object_data_port": self.data_port}}

    def on_disconnect(self, conn):
        worker_id = conn.meta.get("worker_id")
        if worker_id:
            self._on_worker_exit(worker_id)

    def _reap_loop(self):
        ticks = 0
        while not self._stopped:
            time.sleep(0.2)
            ticks += 1
            dead = []
            with self._lock:
                for wid, h in self._workers.items():
                    if h.proc is not None and h.proc.poll() is not None:
                        dead.append(wid)
            for wid in dead:
                self._on_worker_exit(wid)
            if ticks % 25 == 0:   # every ~5s: GC leases of remote lessees
                self._gc_remote_lessee_leases()
                self._reap_idle_workers()
            if ticks % 3 == 0:    # ~600ms: resource view → GCS (the
                # RaySyncer-gossip analog; the PG scheduler packs against
                # this instead of node totals)
                try:
                    with self._lock:
                        avail = dict(self.resources_avail)
                        demand = [dict(d) for d in self._queued_demand]
                        busy = len(self._leases) + sum(
                            1 for w in self._workers.values() if w.is_actor)
                        job_busy: dict[str, dict] = {}
                        for lease in self._leases.values():
                            if lease.job:
                                agg = job_busy.setdefault(lease.job, {})
                                for k, v in lease.resources.items():
                                    agg[k] = agg.get(k, 0.0) + v
                    from ray_tpu._private import telemetry as _tm

                    _tm.gauge_set("ray_tpu_scheduler_queue_tasks",
                                  len(demand),
                                  tags={"node_id": self.node_id})
                    self._gcs.push("report_resources",
                                   node_id=self.node_id, available=avail,
                                   pending_demand=demand, busy=busy,
                                   job_busy=job_busy)
                except Exception:
                    pass

    def _on_memory_pressure(self, used: int, total: int):
        """Kill one worker to relieve node memory pressure. Victim choice
        is newest-task-first (memory_monitor.pick_victim); the kill reason
        is recorded in GCS KV *before* the SIGKILL so the task's owner —
        observing the dropped connection — can surface OutOfMemoryError
        instead of a generic WorkerCrashedError."""
        from ray_tpu._private.memory_monitor import pick_victim, process_rss

        with self._lock:
            cands = []
            for h in self._workers.values():
                if h.proc is None or h.proc.poll() is not None:
                    continue
                started = None
                if h.assigned_lease:
                    lease = self._leases.get(h.assigned_lease)
                    started = lease.granted_at if lease else None
                cands.append({"pid": h.proc.pid, "task_started_at": started,
                              "worker_id": h.worker_id, "addr": h.addr,
                              "handle": h})
        # Leases outlive tasks (they pipeline many), so the grant time
        # ranks by LEASE age. Ask each candidate what it is actually
        # running — task_state answers inline, so this stays fast even
        # under pressure. Actors keep the lease (creation) time: killing
        # an old actor loses state, and newest-first already deprioritizes
        # them. Probe failures fall back to the lease age.
        for c in cands:
            if c["addr"] is None or c["handle"].is_actor:
                continue
            try:
                client = RpcClient(tuple(c["addr"]), timeout=1.0, retry=1)
                try:
                    state = client.call("task_state", timeout=1.0)
                finally:
                    client.close()
                c["task_started_at"] = state.get("task_started_at")
            except Exception:
                pass
        victim = pick_victim(cands)
        if victim is None:
            return
        rss = process_rss(victim["pid"])
        msg = (f"Worker {victim['worker_id']} (pid {victim['pid']}) on node "
               f"{self.node_id} was killed due to the node running low on "
               f"memory: worker RSS {rss / 2**30:.2f} GB, node usage "
               f"{used / 2**30:.2f}/{total / 2**30:.2f} GB above threshold "
               f"{self._mem_monitor.threshold:.0%}. The task is retriable; "
               f"reduce its memory footprint or lower task parallelism.")
        self._oom_reasons[victim["worker_id"]] = msg
        try:
            self._gcs.call("kv_put", ns="oom_kill",
                           key=victim["worker_id"].encode(),
                           value=msg.encode(), timeout=5.0)
        except Exception:
            pass   # owners fall back to WorkerCrashedError
        try:
            os.kill(victim["pid"], signal.SIGKILL)
        except OSError:
            pass

    def _release_leases_of_lessee(self, lessee_id: str):
        with self._lock:
            doomed = [lease for lease in self._leases.values()
                      if lease.lessee_id == lessee_id]
            for lease in doomed:
                self._leases.pop(lease.lease_id, None)
                self._give_back(lease.resources)
                worker = lease.worker
                worker.assigned_lease = None
                # The dead lessee may have left a task mid-execution on this
                # worker; it is not safely reusable — kill it (reference
                # kills leased workers when the lease client disconnects).
                self._kill_worker(worker)

    def _reap_idle_workers(self):
        """Reap idle workers past `worker_pool_idle_timeout_s`, keeping
        the prestart watermark warm (reference: worker_pool.h
        TryKillingIdleWorkers — idle processes beyond the pool target
        are returned to the OS instead of lingering forever)."""
        from ray_tpu._private.config import get_config

        timeout_s = float(get_config("worker_pool_idle_timeout_s"))
        if timeout_s <= 0:
            return
        now = time.time()
        doomed = []
        with self._lock:
            keep = []
            for h in self._idle:
                if (len(self._idle) - len(doomed) > self._prestart_target
                        and now - h.idle_since > timeout_s):
                    doomed.append(h)
                else:
                    keep.append(h)
            if doomed:
                self._idle = keep
                for h in doomed:
                    self._kill_worker(h)

    def _gc_remote_lessee_leases(self):
        """Leases whose lessee lives on another node (spillback grants) are
        not covered by local worker reaping — ping the lessee and reclaim on
        failure."""
        with self._lock:
            remote = [(lease.lessee_id, lease.lessee_addr)
                      for lease in self._leases.values()
                      if lease.lessee_addr is not None
                      and lease.lessee_id not in self._workers]
        for lessee_id, addr in {(i, a) for i, a in remote}:
            # Reclaiming a LIVE lessee's leases kills its workers mid-task,
            # so this probe errs toward patience: the lessee answers ping
            # inline on its transport pump (no GIL-bound dispatch thread),
            # but a loaded single-core host can still stall a reply for
            # seconds — probe twice with generous timeouts before the
            # verdict.
            alive = False
            for _ in range(2):
                try:
                    client = RpcClient(addr, timeout=5.0, retry=1)
                    try:
                        client.call("ping", timeout=5.0)
                        alive = True
                        break
                    finally:
                        client.close()
                except Exception:
                    time.sleep(0.2)
            if not alive:
                self._release_leases_of_lessee(lessee_id)

    def _on_worker_exit(self, worker_id: str):
        with self._lock:
            handle = self._workers.pop(worker_id, None)
            if handle is None:
                return
            if handle in self._idle:
                self._idle.remove(handle)
            lease = None
            if handle.assigned_lease:
                lease = self._leases.pop(handle.assigned_lease, None)
            if lease:
                self._give_back(lease.resources)
        if not handle.is_actor:
            # retire the OOM-kill attribution for non-actor victims —
            # only the actor death path consumed it, so every task-worker
            # OOM kill leaked one reason string per worker id (RTL106
            # class: keyed by worker id, no removal on this death path)
            self._oom_reasons.pop(worker_id, None)
        # Leases this worker REQUESTED (as lessee) die with it: its
        # submission queues can never return them.
        self._release_leases_of_lessee(worker_id)
        self._log_monitor.mark_dead(worker_id)
        if handle.is_actor and handle.actor_id is not None:
            self._handle_actor_death(handle)
        self._pump_pending()

    def _handle_actor_death(self, handle: WorkerHandle):
        if self._stopped:
            # Node teardown: GCS sees our disconnect and re-drives restarts
            # on a surviving node — restarting here would race the shutdown.
            return
        reason = (self._oom_reasons.pop(handle.worker_id, None)
                  or "worker process died")
        try:
            decision = self._gcs.call_once("actor_failed",
                                      actor_id=handle.actor_id,
                                      reason=reason)
        except ConnectionLost:
            return
        if decision and decision.get("restart"):
            spec_key = handle.actor_id
            threading.Thread(
                target=self._restart_actor, args=(spec_key,),
                daemon=True).start()

    def _restart_actor(self, actor_id: bytes):
        if self._stopped:
            return
        blob = self._gcs.call("kv_get", ns="actor_spec", key=actor_id)
        if blob is None:
            return
        import pickle

        spec = pickle.loads(blob)
        try:
            self._create_actor_locally(actor_id, spec)
        except Exception:
            try:
                self._gcs.call_once("actor_failed", actor_id=actor_id,
                               reason="restart failed")
            except ConnectionLost:
                pass

    # ---- scheduling / leasing ----------------------------------------------

    def _fits(self, resources: dict) -> bool:
        return all(self.resources_avail.get(k, 0) + 1e-9 >= v
                   for k, v in resources.items())

    def _take(self, resources: dict):
        for k, v in resources.items():
            self.resources_avail[k] = self.resources_avail.get(k, 0) - v

    def _give_back(self, resources: dict):
        for k, v in resources.items():
            self.resources_avail[k] = self.resources_avail.get(k, 0) + v

    def _pick_spillback(self, resources: dict):
        """Pick an alive node whose totals fit the request, from a briefly
        cached GCS view (every queued lease/actor waiter re-checks spillback
        twice a second — one shared snapshot serves them all)."""
        now = time.time()
        cached = getattr(self, "_nodes_cache", None)
        if cached is not None and now - cached[0] < 0.5:
            nodes = cached[1]
        else:
            try:
                nodes = self._gcs.call("get_nodes")
            except ConnectionLost:
                return None
            self._nodes_cache = (now, nodes)
        best = None
        for n in nodes:
            if not n["Alive"] or n["NodeID"] == self.node_id:
                continue
            total = n["Resources"]
            if all(total.get(k, 0) >= v for k, v in resources.items()):
                if best is None:
                    best = n
        if best is None:
            return None
        return (best["NodeManagerAddress"], best["NodeManagerPort"])

    def rpc_request_worker_lease(self, conn, resources: dict,
                                 strategy: dict | None = None,
                                 grant_or_reject: bool = False,
                                 lessee: tuple | None = None):
        """Returns {"granted": {...}} | {"spillback": addr} | queues until
        resources free (long-poll: the reply is sent when granted)."""
        t0 = time.monotonic()
        strategy = strategy or {}
        job = strategy.get("job")
        # Placement-group leases consume the reserved bundle resources —
        # their job's quota was already enforced at PG admission (the
        # all-or-nothing gang check), so no second gate here.
        pg_id = strategy.get("placement_group_id")
        if pg_id is not None:
            return self._pg_lease(pg_id, strategy.get("bundle_index", -1),
                                  resources, lessee)
        node_hint = strategy.get("node_id")
        if node_hint and node_hint != self.node_id:
            target = self._node_addr(node_hint)
            if target is None:
                if not strategy.get("soft", False):
                    raise ValueError(f"node {node_hint} not found/alive")
            else:
                return {"spillback": target}
        spread = strategy.get("spread", False)
        if spread and not strategy.get("no_spill"):
            # SPREAD policy: coin-flip toward a remote capable node first
            # (reference: scheduling/policy/spread_scheduling_policy).
            target = self._pick_spillback(resources)
            if target is not None and os.urandom(1)[0] < 128:
                return {"spillback": target}
        # zero-resource leases (utility tasks like the PG-ready waiter)
        # consume nothing — parking them on the quota throttle would
        # hang control work without protecting any capacity
        consumes = any(v > 0 for v in resources.values())
        throttled = job is not None and consumes \
            and job in self._job_throttle
        if throttled:
            # lease-grant quota enforcement: the job is over its
            # cluster-wide quota — queue (don't grant, don't bounce
            # around the cluster) until the GCS clears the throttle
            from ray_tpu._private import telemetry as _tm

            if _tm.ENABLED:
                _tm.counter_inc("ray_tpu_quota_rejections_total",
                                tags={"job": job})
        elif self._try_reserve(resources):
            return self._observe_grant(t0,
                                       self._grant(resources, lessee, job))
        # no_spill: the caller exhausted its spillback hops on a saturated
        # cluster — queue here instead of bouncing (the reference keeps the
        # request in ClusterTaskManager's queue in this state).
        if not throttled and not strategy.get("no_spill"):
            target = self._pick_spillback(resources)
            if target is not None:
                return {"spillback": target}
        # Queue until local resources free up (reference: lease request stays
        # in ClusterTaskManager queue). Block this handler thread.
        deadline = time.time() + 300.0
        with self._lock:
            self._queued_demand.append(resources)
        try:
            warned = False
            next_spill_check = time.time() + 0.5
            while time.time() < deadline:
                if self._stopped:
                    raise ConnectionLost("raylet shutting down")
                if job is not None and consumes \
                        and job in self._job_throttle:
                    time.sleep(_LEASE_QUEUE_POLL)
                    continue   # quota throttle: park without reserving
                if self._try_reserve(resources):
                    return self._observe_grant(
                        t0, self._grant(resources, lessee, job))
                # Re-evaluate spillback while queued: a node that joined
                # (autoscaler, chaos replacement) after we started waiting
                # may be able to serve this request right now.
                if (not strategy.get("no_spill")
                        and time.time() >= next_spill_check):
                    target = self._pick_spillback(resources)
                    if target is not None:
                        return {"spillback": target}
                    next_spill_check = time.time() + 0.5
                if not self._feasible(resources) and not warned:
                    # Reference semantics: infeasible work stays PENDING
                    # (with a warning) rather than failing — the queued
                    # shape is the autoscaler's scale-up signal, and chaos
                    # recovery transiently empties resource types.
                    warned = True
                    print(f"[raylet {self.node_id[:8]}] warning: request "
                          f"{resources} is currently infeasible; waiting "
                          f"for capacity (autoscaler signal)", flush=True)
                time.sleep(_LEASE_QUEUE_POLL)
            raise TimeoutError(f"lease request {resources} timed out")
        finally:
            with self._lock:
                try:
                    self._queued_demand.remove(resources)
                except ValueError:
                    pass

    def _observe_grant(self, t0: float, reply: dict) -> dict:
        """Record the lease-grant latency (request arrival → local grant;
        spillbacks never reach here — they are another node's grant)."""
        from ray_tpu._private import telemetry as _tm

        if _tm.ENABLED:
            _tm.observe("ray_tpu_lease_grant_latency_seconds",
                        time.monotonic() - t0,
                        tags={"node_id": self.node_id})
        return reply

    def _try_reserve(self, resources: dict) -> bool:
        with self._lock:
            if self._fits(resources):
                self._take(resources)
                return True
            return False

    def _feasible(self, resources: dict) -> bool:
        if all(self.resources_total.get(k, 0) >= v
               for k, v in resources.items()):
            return True
        try:
            nodes = self._gcs.call("get_nodes")
        except ConnectionLost:
            return True
        return any(
            n["Alive"] and all(n["Resources"].get(k, 0) >= v
                               for k, v in resources.items())
            for n in nodes)

    def _grant(self, resources: dict, lessee: tuple | None = None,
               job: str | None = None) -> dict:
        """Resources must already be reserved via _try_reserve. Runs outside
        _lock because _pop_worker may block on worker registration."""
        try:
            worker = self._pop_worker()
        except Exception:
            with self._lock:
                self._give_back(resources)
            raise
        lease_id = uuid.uuid4().hex
        lease = Lease(lease_id, resources, worker, lessee, job)
        worker.assigned_lease = lease_id
        with self._lock:
            self._leases[lease_id] = lease
        grant = {"lease_id": lease_id,
                 "worker_id": worker.worker_id,
                 "worker_addr": worker.addr,
                 "node_id": self.node_id}
        # producer-side shape check: the lessee reads exactly these keys
        from ray_tpu._private.task_spec import validate_lease_grant

        validate_lease_grant(grant)
        return {"granted": grant}

    def _pg_lease(self, pg_id: bytes, bundle_index: int, resources: dict,
                  lessee: tuple | None = None):
        pg = self._gcs.call("get_placement_group", pg_id=pg_id)
        if pg is None or pg["State"] != "CREATED":
            raise ValueError(f"placement group {pg_id.hex()} not ready")
        nodes = pg["BundleNodes"]
        if bundle_index == -1:
            candidates = [n for n in nodes if n == self.node_id] or nodes
            target_node = candidates[0]
        else:
            target_node = nodes[bundle_index]
        if target_node != self.node_id:
            addr = self._node_addr(target_node)
            if addr is None:
                raise ValueError("placement group node died")
            return {"spillback": addr}
        return self._grant({}, lessee)  # bundle resources were pre-reserved

    def _node_addr(self, node_id: str):
        """Resolve one node's raylet address. Rides the O(1)
        ``get_node_addr`` RPC — the old full-table pull paid an
        O(cluster) payload per PG-target/spillback resolution, which at
        100 nodes made this the dominant GCS read traffic (soak
        round 12)."""
        try:
            addr = self._gcs.call("get_node_addr", node_id=node_id)
        except ConnectionLost:
            return None
        return tuple(addr) if addr else None

    def rpc_return_worker(self, conn, lease_id: str,
                          dispose: bool = False):
        with self._lock:
            lease = self._leases.pop(lease_id, None)
            if lease is None:
                return False
            self._give_back(lease.resources)
            worker = lease.worker
            worker.assigned_lease = None
            if dispose or len(self._idle) >= self._idle_cap:
                self._kill_worker(worker)
            elif worker.proc is not None and worker.proc.poll() is None:
                worker.idle_since = time.time()
                self._idle.append(worker)
        self._pump_pending()
        return True

    def _pump_pending(self):
        pass  # lease queue is handled by blocking handler threads

    def _kill_worker(self, worker: WorkerHandle):
        self._workers.pop(worker.worker_id, None)
        if worker.proc is not None and worker.proc.poll() is None:
            try:
                worker.proc.terminate()
            except OSError:
                pass

    # ---- actors -------------------------------------------------------------

    def rpc_create_actor(self, conn, actor_id: bytes, spec: dict):
        """Create the actor on this node or spill back. The spec's class blob
        lives in GCS KV under ns=actor_spec (function-table analog)."""
        resources = spec.get("resources", {"CPU": 1.0})
        strategy = spec.get("strategy") or {}
        pg_id = strategy.get("placement_group_id")
        if pg_id is not None:
            # A PENDING group just means its resources are currently held
            # (e.g. by other gang-scheduled trials): queue until the GCS
            # reserves the bundles, like the plain-resource path queues.
            deadline = time.time() + 300.0
            poll = _LEASE_QUEUE_POLL
            while True:
                pg = self._gcs.call("get_placement_group", pg_id=pg_id)
                if pg is None or pg["State"] == "REMOVED":
                    raise ValueError("placement group removed")
                if pg["State"] == "CREATED":
                    break
                if time.time() > deadline:
                    raise TimeoutError(
                        "placement group not ready within 300s")
                time.sleep(poll)
                poll = min(poll * 1.5, 0.5)   # back off: dozens of queued
                # creations at 50 polls/s each would hammer the GCS
            idx = strategy.get("bundle_index", -1)
            target = (pg["BundleNodes"][idx] if idx >= 0
                      else next((n for n in pg["BundleNodes"]
                                 if n == self.node_id),
                                pg["BundleNodes"][0]))
            if target != self.node_id:
                addr = self._node_addr(target)
                if addr is None:
                    raise ValueError("placement group node died")
                return {"spillback": addr}
            return self._create_actor_locally(actor_id, spec, reserved={})
        node_hint = strategy.get("node_id")
        if node_hint and node_hint != self.node_id:
            addr = self._node_addr(node_hint)
            if addr is None and not strategy.get("soft", False):
                raise ValueError(f"node {node_hint} not found/alive")
            if addr is not None:
                return {"spillback": addr}
        if self._try_reserve(resources):
            return self._create_actor_locally(actor_id, spec,
                                              reserved=resources)
        if not strategy.get("no_spill"):
            target = self._pick_spillback(resources)
            if target is not None:
                return {"spillback": target}
        # queue locally until feasible
        deadline = time.time() + 300.0
        with self._lock:
            self._queued_demand.append(resources)
        try:
            next_spill_check = time.time() + 0.5
            while time.time() < deadline:
                if self._stopped:
                    raise ConnectionLost("raylet shutting down")
                if self._try_reserve(resources):
                    return self._create_actor_locally(actor_id, spec,
                                                      reserved=resources)
                if not strategy.get("no_spill") and \
                        time.time() >= next_spill_check:
                    target = self._pick_spillback(resources)
                    if target is not None:
                        return {"spillback": target}
                    next_spill_check = time.time() + 0.5
                time.sleep(_LEASE_QUEUE_POLL)
            raise TimeoutError(
                "actor creation timed out waiting for resources")
        finally:
            with self._lock:
                try:
                    self._queued_demand.remove(resources)
                except ValueError:
                    pass

    def _create_actor_locally(self, actor_id: bytes, spec: dict,
                              reserved: dict | None = None):
        """`reserved` are resources already taken via _try_reserve; pass {}
        for placement-group bundles (pre-reserved at bundle commit)."""
        if reserved is None:
            resources = spec.get("resources", {"CPU": 1.0})
            deadline = time.time() + 300.0
            while not self._try_reserve(resources):
                if time.time() > deadline:
                    raise TimeoutError("actor restart resource wait")
                time.sleep(_LEASE_QUEUE_POLL)
            reserved = resources
        resources = reserved
        worker = None
        try:
            worker = self._pop_worker(cause=spec.get("trace_ctx"))
            worker.is_actor = True
            worker.actor_id = actor_id
            lease_id = uuid.uuid4().hex
            lease = Lease(lease_id, resources, worker)
            worker.assigned_lease = lease_id
            with self._lock:
                self._leases[lease_id] = lease
            # Tell the worker to become this actor.
            client = RpcClient(worker.addr, timeout=60.0)
            try:
                from ray_tpu._private.config import get_config

                # Under a creation storm on a starved core a worker's
                # become_actor (class-blob fetch + import) legitimately
                # waits behind dozens of peers, so this scales with the
                # storm-sized driver budget — but at 3/4 of it, leaving
                # the driver's outer create_actor call margin to receive
                # our reply (equal budgets would let the driver give up
                # and mark the actor failed moments before the raylet
                # succeeds, leaking the bound worker).
                outer = float(get_config("actor_creation_rpc_timeout_s"))
                client.call("become_actor", actor_id=actor_id, spec=spec,
                            timeout=0.75 * outer)
            finally:
                client.close()
            self._log_monitor.set_actor_name(
                worker.worker_id,
                spec.get("name") or spec.get("class_name"))
        except BaseException:
            # Failed creation must not leak the reservation (or the worker —
            # a half-initialized actor process is not reusable). If the
            # worker died mid-creation, _on_worker_exit may have already
            # popped the lease and returned the resources — only give back
            # when we pop the lease ourselves (or never registered one).
            with self._lock:
                if worker is None or worker.assigned_lease is None:
                    self._give_back(resources)
                elif self._leases.pop(worker.assigned_lease,
                                      None) is not None:
                    self._give_back(resources)
            if worker is not None:
                worker.is_actor = False
                with self._lock:
                    self._kill_worker(worker)
            raise
        return {"granted": {"worker_id": worker.worker_id,
                            "worker_addr": worker.addr,
                            "node_id": self.node_id,
                            "lease_id": lease_id}}

    def rpc_kill_actor(self, conn, actor_id: bytes, no_restart: bool = True):
        with self._lock:
            handle = next((h for h in self._workers.values()
                           if h.actor_id == actor_id), None)
        if handle is None:
            return False
        if no_restart:
            handle.is_actor = False   # suppress restart path
            try:
                self._gcs.call("actor_exited", actor_id=actor_id)
            except ConnectionLost:
                pass
        if handle.proc is not None:
            try:
                handle.proc.send_signal(signal.SIGKILL)
            except OSError:
                pass
        else:
            # actor hosted in an external process (driver) — push a kill rpc
            try:
                c = RpcClient(handle.addr, timeout=5.0)
                c.push("exit_worker")
                c.close()
            except ConnectionLost:
                pass
        return True

    # ---- object plane -------------------------------------------------------

    def rpc_fetch_object(self, conn, object_id: bytes):
        """Whole-object pull (kept for small objects / compatibility)."""
        buf = self.store.get(object_id)
        if buf is None:
            return None
        try:
            return buf.to_bytes()
        finally:
            buf.release()

    def rpc_fetch_object_chunk(self, conn, object_id: bytes, offset: int,
                               length: int):
        """Chunked pull (reference: ObjectManager chunked gRPC transfer,
        object_manager.h + push_manager.h:29). Returns {"size", "data"} or
        None if the object isn't here (pullers retry elsewhere)."""
        buf = self.store.get(object_id)
        if buf is None:
            return None
        try:
            mv = buf.memoryview()
            return {"size": len(mv), "data": bytes(mv[offset:offset + length])}
        finally:
            buf.release()

    def rpc_store_stats(self, conn):
        return self.store.stats()

    def rpc_list_store_objects(self, conn):
        """Per-node object inventory (`ray-tpu memory` source). Under the
        owner-based directory there is no central location table — the
        state API unions these per-node rows instead."""
        return [{"ObjectID": oid.hex(), "Size": size,
                 "Locations": [self.node_id], "Lost": False}
                for oid, size in self.store.list_objects()]

    def rpc_node_info(self, conn):
        with self._lock:
            return {
                "node_id": self.node_id,
                "resources_total": dict(self.resources_total),
                "resources_available": dict(self.resources_avail),
                "num_workers": len(self._workers),
                "num_idle": len(self._idle),
                "num_leases": len(self._leases),
            }

    def rpc_list_leases(self, conn):
        """Active leases = the raylet-level view of running work (state API
        `list tasks` source; reference: NodeManagerService GetNodeStats)."""
        with self._lock:
            return [{
                "lease_id": lease.lease_id,
                "node_id": self.node_id,
                "resources": dict(lease.resources),
                "worker_id": lease.worker.worker_id,
                "worker_pid": lease.worker.proc.pid,
                "worker_addr": lease.worker.addr,
                "is_actor": lease.worker.is_actor,
            } for lease in self._leases.values()]

    def rpc_list_workers(self, conn):
        with self._lock:
            return [{
                "worker_id": w.worker_id,
                "node_id": self.node_id,
                "pid": w.proc.pid,
                "state": ("actor" if w.is_actor
                          else "leased" if w.assigned_lease else "idle"),
                "actor_id": w.actor_id.hex() if w.actor_id else None,
            } for w in self._workers.values()]

    def _fanout_workers(self, method: str) -> list:
        """Collect per-worker state (profiling spans, metrics) from every
        registered worker process on this node."""
        from ray_tpu._private.protocol import RpcClient

        with self._lock:
            addrs = [w.addr for w in self._workers.values()
                     if w.addr is not None]
        out = []
        for addr in addrs:
            try:
                c = RpcClient(tuple(addr), timeout=5.0)
                try:
                    out.extend(c.call(method))
                finally:
                    c.close()
            except Exception:
                continue
        return out

    def rpc_profile_events(self, conn):
        """This node's timeline: the raylet process's own ring (the chip
        probe and every `worker_spawn` are recorded HERE) with every
        registered worker's. Where the raylet shares the driver's
        process, the driver's worker answers with the same ring:
        `profiling.merge` keeps one of each."""
        return _prof.merge(_prof.snapshot(with_drop_marker=True)
                           + self._fanout_workers("profile_events"))

    def rpc_trace_spans(self, conn):
        return self._fanout_workers("trace_spans")

    def rpc_metrics_snapshot(self, conn):
        """This node's metrics: the raylet process's own registry (the
        scheduler gauges/histograms live HERE) plus every registered
        worker's. aggregate_snapshots dedups by (node, pid) when the
        raylet shares a process with the driver (in-process clusters)."""
        from ray_tpu.util.metrics import registry_snapshot

        return registry_snapshot() + self._fanout_workers(
            "metrics_snapshot")

    def rpc_events_snapshot(self, conn):
        """This node's structured runtime events: the raylet process's own
        ring plus every registered worker's (the state API dedups by
        (node, pid, seq) — in-process clusters share a pid with the
        driver)."""
        from ray_tpu._private import events as _events

        return _events.snapshot() + self._fanout_workers("events_snapshot")

    def rpc_step_records(self, conn):
        """Step-anatomy exports from every registered worker on this
        node (the raylet itself runs no train loop — its own export
        would always be empty)."""
        return self._fanout_workers("step_records")

    def rpc_blackbox_snapshot(self, conn):
        """Flight-recorder windows: the raylet process's own black box
        (its event ring and metrics matter in a post-mortem) plus every
        registered worker's. The dump path dedups by (node, pid)."""
        from ray_tpu._private import flight_recorder

        snap = flight_recorder.local_snapshot()
        own = [snap] if snap else []
        return own + self._fanout_workers("blackbox_snapshot")

    def rpc_memory_snapshot(self, conn):
        """Memory-anatomy ledgers: the raylet process's own (its store
        deletes and dropped frees count HERE) plus every registered
        worker's. summarize_memory dedups by (node, pid)."""
        from ray_tpu._private import memory_anatomy

        snap = memory_anatomy.local_snapshot(top_k=10)
        snap["node"] = self.node_id
        return [snap] + self._fanout_workers("memory_snapshot")

    def rpc_ping(self, conn):
        return "pong"

    def rpc_dump_stacks(self, conn, wait_s: float = 0.6):
        """`ray stack` analog (reference: scripts.py `ray stack` shells
        out to py-spy on every worker): workers register faulthandler on
        SIGUSR1 (worker_main), so signaling them makes each dump every
        thread's Python stack into its own stderr log; this collects the
        fresh tails. No py-spy dependency — the dumps come from the
        interpreter itself."""
        with self._lock:
            targets = [(h.worker_id, h.proc.pid)
                       for h in self._workers.values()
                       if h.proc is not None and h.proc.poll() is None]
        marks = {}
        for worker_id, _pid in targets:
            err = os.path.join(self.logs_dir, f"worker-{worker_id}.err")
            try:
                marks[worker_id] = os.path.getsize(err)
            except OSError:
                # no file yet — mark its CURRENT end once it appears, so
                # historical stderr is never mistaken for the dump
                marks[worker_id] = None
        for _worker_id, pid in targets:
            try:
                os.kill(pid, signal.SIGUSR1)
            except OSError:
                pass
        out = {}
        deadline = time.monotonic() + max(wait_s, 0.1)
        pending = dict(targets)
        while pending and time.monotonic() < deadline:
            time.sleep(0.1)
            for worker_id, pid in list(pending.items()):
                err = os.path.join(self.logs_dir,
                                   f"worker-{worker_id}.err")
                mark = marks[worker_id]
                try:
                    size = os.path.getsize(err)
                except OSError:
                    continue
                if mark is None:
                    marks[worker_id] = mark = size
                    continue
                if size <= mark:
                    continue
                with open(err, "rb") as f:
                    f.seek(mark)
                    dump = f.read().decode(errors="replace")
                out[worker_id] = {"pid": pid, "node_id": self.node_id,
                                  "stack": dump[-100_000:]}
                del pending[worker_id]
        for worker_id, pid in pending.items():   # no dump in time
            out[worker_id] = {"pid": pid, "node_id": self.node_id,
                              "stack": ""}
        return out

    def rpc_physical_stats(self, conn):
        """Reporter-agent sample for this node (reference:
        dashboard/modules/reporter/reporter_agent.py:296 — here the
        raylet plays the per-node agent; the dashboard fans this out at
        /api/reporter)."""
        from ray_tpu.dashboard.reporter import collect_stats

        with self._lock:
            pids = [h.proc.pid for h in self._workers.values()
                    if h.proc is not None and h.proc.poll() is None]
        stats = collect_stats(pids)
        stats["node_id"] = self.node_id
        return stats

    # ---- lifecycle ----------------------------------------------------------

    def stop(self, kill_workers: bool = True):
        """With `kill_workers`, returns only when every process this raylet
        ever spawned is dead AND reaped — also those no handle names any
        more (an actor `rpc_kill_actor` sent SIGKILL, a worker whose
        connection dropped first) and one a refill thread was starting."""
        while True:
            with self._lock:
                self._stopped = True    # no `Popen` starts from now on,
                if not self._starting:  # and those in flight have registered
                    procs = list(self._procs)
                    break
            time.sleep(0.01)
        self._mem_monitor.stop()
        try:
            self._log_monitor.stop()   # final drain rides the live GCS conn
        except Exception:
            pass
        # Drop the GCS connection first: node-death handling (including actor
        # failover to surviving nodes) starts before local worker reaping can
        # misreport deaths as per-worker failures.
        try:
            self._gcs.close()
        except Exception:
            pass
        if kill_workers:
            for proc in procs:
                if proc.poll() is None:
                    try:
                        proc.terminate()
                    except OSError:
                        pass
            # 2 s in all to exit on SIGTERM, then SIGKILL and as long as the
            # kernel takes to tear the process down (one that mapped four
            # chips' HBM takes its time)
            deadline = time.time() + 2.0
            for proc in procs:
                try:
                    proc.wait(max(0.05, deadline - time.time()))
                except subprocess.TimeoutExpired:
                    try:
                        proc.kill()
                    except OSError:
                        pass
            deadline = time.time() + _REAP_TIMEOUT_S
            for proc in procs:
                try:
                    proc.wait(max(0.05, deadline - time.time()))
                except subprocess.TimeoutExpired:
                    print(f"raylet: worker pid {proc.pid} not reaped "
                          f"{_REAP_TIMEOUT_S:.0f} s after SIGKILL",
                          file=sys.stderr, flush=True)
        self._server.stop()
        try:
            self.store.close()
        except Exception:
            pass


def main():  # pragma: no cover - exercised as a subprocess
    """`python -m ray_tpu._private.raylet` with env-provided config."""
    gcs_host, gcs_port = os.environ["RAY_TPU_GCS_ADDR"].split(":")
    resources = None
    if os.environ.get("RAY_TPU_RESOURCES"):
        import json

        resources = json.loads(os.environ["RAY_TPU_RESOURCES"])
    raylet = Raylet(
        (gcs_host, int(gcs_port)),
        node_id=os.environ.get("RAY_TPU_NODE_ID"),
        port=int(os.environ.get("RAY_TPU_RAYLET_PORT", "0")),
        resources=resources,
        store_size=int(os.environ.get("RAY_TPU_STORE_SIZE",
                                      str(256 * 1024 * 1024))),
        session_dir=os.environ.get("RAY_TPU_SESSION_DIR"),
    )
    print(f"RAYLET_READY {raylet.addr[0]}:{raylet.addr[1]} {raylet.node_id}",
          flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        raylet.stop()


if __name__ == "__main__":  # pragma: no cover
    main()
