"""BackendExecutor — gang-schedules the worker group, runs backend setup,
drives the training loop (reference:
python/ray/train/_internal/backend_executor.py:42 — _create_placement_group
:137, start_training :314).
"""
from __future__ import annotations

import os
import threading
import time

import ray_tpu
from ray_tpu import exceptions as exc
from ray_tpu._private import profiling as _prof
from ray_tpu.air.config import ScalingConfig
from ray_tpu.train.worker_group import WorkerGroup
from ray_tpu.util.placement_group import (
    placement_group,
    remove_placement_group,
)


class _GangDeathMonitor:
    """Driver-side fast rank-death detector: subscribes to the GCS
    actor-lifecycle feed for the gang's worker actors so a rank death
    surfaces within seconds — as a named TrainWorkerGroupError listing
    the dead rank(s) — instead of whenever the next per-worker RPC
    happens to fail. Kill switch: RAY_TPU_TRAIN_DEATH_MONITOR=0
    (config `train_death_monitor`). Detection degrades gracefully to
    per-rank RPC failure attribution when off or unavailable."""

    def __init__(self, worker_group: WorkerGroup):
        self._rank_of = {w._actor_id: rank
                         for rank, w in enumerate(worker_group.workers)}
        self._lock = threading.Lock()
        self._dead: dict[int, str] = {}      # rank -> reason
        self._watch = None
        from ray_tpu._private.config import get_config

        if not get_config("train_death_monitor"):
            return
        try:
            from ray_tpu._private.pubsub import watch_actor_deaths

            self._watch = watch_actor_deaths(self._on_death)
        except Exception:
            pass   # detection degrades to per-rank RPC attribution

    def _on_death(self, actor_id, reason: str):
        rank = self._rank_of.get(actor_id)
        if rank is None:
            return
        with self._lock:
            self._dead.setdefault(rank, reason)
        # black box while the body is warm: the dump fan-out runs off
        # the pubsub callback thread (background), debounced so a
        # multi-rank death burst produces one dump
        try:
            from ray_tpu._private import flight_recorder as _fr

            _fr.trigger_dump("actor_death", background=True)
        except Exception:
            pass

    def dead_ranks(self) -> dict[int, str]:
        with self._lock:
            return dict(self._dead)

    def active(self) -> bool:
        """True only while the GCS subscription is live — callers should
        not pay an abort-check poll loop for a monitor that can never
        learn anything (kill switch off, or the subscribe failed)."""
        return self._watch is not None

    def stop(self):
        watch, self._watch = self._watch, None
        if watch is not None:
            watch.stop()


class _PreemptionMonitor:
    """Driver-side preemption-notice handler (multi-tenant control
    plane): subscribes to the GCS `pg_state` channel for the gang's
    placement group. On the PREEMPTION WARNING it pushes the notice to
    every rank (``TrainWorker.notify_preemption`` →
    ``session.preemption_warned()``) so the train loop can cut a
    checkpoint inside the grace window; when the preemption FIRES (the
    GCS reclaimed the bundles) it flips ``fired``, which
    ``next_results``'s abort check turns into ``TrainPreemptedError`` —
    the graceful teardown-requeue-resume path, not a failure. Rides
    PR 12's snapshot-resync so a missed feed message cannot hide a
    preemption."""

    def __init__(self, pg_id: bytes):
        self._pg_id = pg_id
        self._lock = threading.Lock()
        self._warned: dict | None = None
        self._fired = False
        self._notify = None          # set by attach(): notify_cb(grace_s)
        self._watch = None
        # CREATED observed for our pg — BackendExecutor.start hands
        # this to PlacementGroup.wait so the gang-schedule wait rides
        # THIS subscription instead of opening a second one per start
        self._created = threading.Event()
        try:
            from ray_tpu._private.api import _require_worker
            from ray_tpu._private.pubsub import watch_channel

            self._watch = watch_channel(
                "pg_state", self._on_msg, _require_worker().gcs.addr,
                poll_timeout=2.0)
        except Exception:
            pass   # degraded: preemption then surfaces as PG loss

    def attach(self, notify_cb):
        """``notify_cb(grace_s)`` fans the warning out to the workers
        (set once the worker group exists). A warning that arrived in
        the window between CREATED and attach is REPLAYED — dropping it
        would leave the ranks without their checkpoint-then-yield
        notice, defeating the grace window."""
        with self._lock:
            self._notify = notify_cb
            pending = dict(self._warned) if self._warned else None
        if pending is not None:
            try:
                notify_cb(pending["grace_s"])
            except Exception:
                pass

    def created_event(self) -> "threading.Event":
        return self._created

    def _on_msg(self, msg):
        if not isinstance(msg, dict):
            return
        if msg.get("event") == "resync":
            for row in (msg.get("snapshot") or ()):
                if isinstance(row, dict) and row.get("pg_id") == self._pg_id:
                    if row.get("state") == "CREATED":
                        self._created.set()
                    # a still-live deadline means we may have missed the
                    # warning push; `preempted_at` set means the FIRE
                    # itself was missed (stamped only by
                    # _fire_preemption — a PENDING/RESCHEDULING row
                    # alone could be a node-death reschedule, which
                    # must charge the failure budget, not requeue free)
                    if row.get("preempt_deadline"):
                        # the deadline is an epoch stamp: hand the loop
                        # the REMAINING window, not 0.0 — first-warning
                        # -wins would otherwise pin grace_s at zero and
                        # a cooperative loop would skip a checkpoint it
                        # had seconds to cut
                        self._handle_warning({"grace_s": max(
                            0.0, row["preempt_deadline"] - time.time())})
                    if row.get("preempted_at"):
                        self._handle_fired()
            return
        if msg.get("pg_id") != self._pg_id:
            return
        if msg.get("event") == "state" and msg.get("state") == "CREATED":
            self._created.set()
        elif msg.get("event") == "preempt_warning":
            self._handle_warning(msg)
        elif msg.get("event") == "state" and msg.get("state") == "PREEMPTED":
            self._handle_fired()

    def _handle_warning(self, msg):
        with self._lock:
            if self._warned is not None:
                return
            self._warned = {"grace_s": float(msg.get("grace_s") or 0.0)}
            notify = self._notify
        if notify is not None:
            try:
                notify(self._warned["grace_s"])
            except Exception:
                pass   # dying ranks can't take the notice; fire covers it


    def _handle_fired(self):
        with self._lock:
            self._fired = True

    def warned(self) -> dict | None:
        with self._lock:
            return dict(self._warned) if self._warned else None

    def fired(self) -> bool:
        with self._lock:
            return self._fired

    def active(self) -> bool:
        """True only while the pg_state subscription is live."""
        return self._watch is not None

    def stop(self):
        watch, self._watch = self._watch, None
        if watch is not None:
            watch.stop()


class Backend:
    """Pluggable per-framework setup (reference: train/backend.py Backend /
    BackendConfig — e.g. _TorchBackend sets up the process group,
    train/torch/config.py:123)."""

    def on_start(self, worker_group: WorkerGroup,
                 scaling: ScalingConfig):
        pass

    def on_shutdown(self, worker_group: WorkerGroup):
        pass


class JaxBackend(Backend):
    """TPU-native data-parallel backend.

    Two regimes (both covered by this one backend):
    - single-host gang (CI / one TPU host): workers form a host-relay
      collective group ("host" backend) for gradient allreduce — the analog
      of the reference wiring torch DDP over gloo.
    - multi-host TPU pod: one worker per host; each calls
      jax.distributed.initialize(coordinator, num_processes, process_id) so
      the workers jointly own the global device mesh and pjit compiles to
      ICI collectives. Enabled via JaxConfig(distributed=True).
    """

    def __init__(self, config: "JaxConfig"):
        self.config = config

    def on_start(self, worker_group, scaling):
        from ray_tpu.util import collective as col

        world = len(worker_group)
        group_name = self.config.group_name
        col.create_collective_group(
            [w for w in worker_group.workers], world, list(range(world)),
            backend=self.config.collective_backend, group_name=group_name)
        if self.config.distributed:
            # rank 0's host becomes the jax.distributed coordinator; the
            # port is negotiated on that host (a fixed default like
            # 127.0.0.1:9876 collides on real pods — advisor finding)
            coordinator = self.config.coordinator_address
            if coordinator is None:
                coordinator = worker_group.execute_single(
                    0, "free_coordinator_address")

            def _init_jax_distributed(rank, world_size, coordinator):
                import jax

                if not jax.distributed.is_initialized():
                    jax.distributed.initialize(
                        coordinator_address=coordinator,
                        num_processes=world_size, process_id=rank)
                return True

            worker_group.execute(
                "run_setup",
                (_init_jax_distributed, (coordinator,), {}))

    def on_shutdown(self, worker_group):
        # Tear the group down on every member: drops the per-process state
        # (mailbox purge + stranded-shm sweep + poison clear) and kills
        # the rendezvous actor so the next incarnation under this group
        # name starts clean (advisor finding: the actor used to leak).
        # Surviving ranks answer fast; dead ranks resolve quickly as
        # ActorDiedError — the timeout only bounds pathological hangs so
        # a gang teardown can never wedge the restart loop.
        try:
            worker_group.execute("destroy_collective",
                                 self.config.group_name, timeout=60.0)
        except Exception:
            pass


class JaxConfig:
    """(reference analog: train/torch/config.py TorchConfig)"""

    def __init__(self, distributed: bool = False,
                 coordinator_address: str | None = None,
                 group_name: str = "train_dp",
                 collective_backend: str = "host"):
        self.distributed = distributed
        self.coordinator_address = coordinator_address
        self.group_name = group_name
        self.collective_backend = collective_backend

    def backend_cls(self):
        return JaxBackend(self)


class BackendExecutor:
    def __init__(self, backend_config: JaxConfig,
                 scaling: ScalingConfig):
        self.backend_config = backend_config
        self.scaling = scaling
        self.worker_group: WorkerGroup | None = None
        self.pg = None

    def start(self):
        with _prof.record_span("startup", "gang_start"):
            return self._start()

    def _start(self):
        bundles = self.scaling.as_placement_group_bundles()
        self.pg = placement_group(
            bundles, strategy=self.scaling.placement_strategy,
            job=getattr(self.scaling, "job", None),
            bundle_stages=getattr(self.scaling, "bundle_stages", None))
        # subscribe BEFORE waiting: a warning can only arrive once the
        # PG is CREATED, and the monitor must already be listening then.
        # The gang-schedule wait below rides THIS subscription (its
        # created_event) instead of opening a second pg_state
        # connection per start.
        self._preempt = _PreemptionMonitor(self.pg.id)
        try:
            with _prof.record_span("startup", "pg_wait"):
                ok = self.pg.wait(
                    120.0,
                    _created_event=(self._preempt.created_event()
                                    if self._preempt.active() else None))
            if not ok:
                remove_placement_group(self.pg)
                self.pg = None
                from ray_tpu.exceptions import (
                    PlacementGroupUnschedulableError,
                )

                # typed so fit() can tell "still waiting for capacity
                # after a preemption requeue" (keep waiting, no budget
                # charge) from a real gang failure
                raise PlacementGroupUnschedulableError(
                    f"could not gang-schedule {len(bundles)} training "
                    f"bundles {bundles}: insufficient cluster resources")
            # the actors' creation is asynchronous: the span is their
            # specs' cause (`worker_spawn` hangs under it), not their wait
            with _prof.record_span("startup", "worker_group_start"):
                self.worker_group = WorkerGroup(
                    self.scaling.num_workers,
                    self.scaling.worker_resources(),
                    placement_group=self.pg)
            # checkpoint-then-yield fan-out: the warning reaches every
            # rank's session so the train loop can checkpoint in the
            # grace window (fire-and-forget refs: a rank that can't
            # take the notice is torn down when the fire lands anyway);
            # attach replays a warning that landed before this point
            self._preempt.attach(lambda grace_s: [
                w.notify_preemption.remote(grace_s)
                for w in self.worker_group.workers])
            self.backend = self.backend_config.backend_cls()
            # the first call the gang answers: holds the wait for every
            # worker to be spawned, booted and made an actor
            with _prof.record_span("startup", "backend_on_start"):
                self.backend.on_start(self.worker_group, self.scaling)
        except BaseException:
            # a failure ANYWHERE in startup must release the monitor's
            # dedicated GCS connection + poll thread — a crash-looping
            # gang otherwise leaks one per retry (review finding)
            self._preempt.stop()
            raise
        self._monitor = _GangDeathMonitor(self.worker_group)
        self.worker_devices = self._record_group_devices()
        return self

    def _record_group_devices(self):
        """Gather per-worker device identities after backend setup (the
        collective/jax.distributed init just ran, so jax is loaded where
        it will be used) and record one train_group cluster event — the
        gang's rank -> device map, the join key between step events and
        the physical topology. Skipped entirely under the telemetry
        kill-switch; never fails startup."""
        from ray_tpu._private import events as _events

        if not _events.ENABLED:
            return None
        try:
            devices = self.worker_group.execute("device_identity",
                                                timeout=60.0)
        except Exception:
            return None
        _events.record("train_group",
                       num_workers=len(self.worker_group),
                       devices=devices)
        return devices

    def set_dataset_shards(self, name: str, shards: list):
        for worker, shard in zip(self.worker_group.workers, shards):
            ray_tpu.get(worker.set_dataset_shard.remote(name, shard))

    def start_training(self, train_fn, config):
        self._ckpt_root = (config or {}).get("_checkpoint_dir")
        self.worker_group.execute("start_training", train_fn, config)

    def checkpoint_resume_hint(self) -> dict | None:
        """Newest committed sharded generation under this run's root —
        what a gang restart will actually resume from. None when the
        run has no sharded root or no committed generation yet."""
        root = getattr(self, "_ckpt_root", None)
        if not root or not os.path.isdir(root):
            return None
        try:
            from ray_tpu.train.sharded_checkpoint import (
                summarize_checkpoints,
            )
            # cheap scan: manifest presence only, no shard re-hash —
            # this runs on the failure path and must never stall it
            for gen in summarize_checkpoints(root, digests=False):
                if gen.get("status") == "committed":
                    return {"step": gen.get("step"),
                            "path": gen.get("path"),
                            "world": gen.get("world")}
        except Exception:
            return None
        return None

    def next_results(self, timeout: float | None = None):
        """One row of results across the gang (or done/error markers).

        Blocks as long as the train functions run: the per-worker
        next_result only returns when a report arrives or the function
        ends, so a driver-side deadline would spuriously kill long steps
        (first-step XLA compile, big evals). Pass a timeout only to bound
        a run you are willing to abandon.

        A rank death surfaces here as TrainWorkerGroupError: the death
        monitor's pubsub knowledge is polled WHILE the gang call blocks
        (abort_check — a death interrupts the wait within seconds even
        if the transport never surfaces it), per-rank attribution comes
        from WorkerGroup.execute, and anything the monitor learned is
        merged into the raised error's dead_ranks.

        A FIRED preemption (the GCS reclaimed the gang's bundles after
        the grace window) surfaces as TrainPreemptedError through the
        same abort path — fit() treats it as a graceful requeue, not a
        failure."""
        monitor = getattr(self, "_monitor", None)
        pm = getattr(self, "_preempt", None)
        if pm is not None and pm.fired():
            raise self._preempted_error()
        death_check = (monitor.dead_ranks
                       if monitor is not None and monitor.active()
                       else None)
        abort_check = None
        if death_check is not None or pm is not None:
            def abort_check():
                known = dict(death_check()) if death_check else {}
                if pm is not None and pm.fired():
                    for rank in range(len(self.worker_group)):
                        known.setdefault(rank, "placement group preempted")
                return known
        try:
            rows = self.worker_group.execute(
                "next_result", timeout=timeout, abort_check=abort_check)
        except exc.TrainWorkerGroupError as e:
            if pm is not None and pm.fired():
                raise self._preempted_error() from e
            if monitor is not None:
                known = monitor.dead_ranks()
                if set(known) - set(e.dead_ranks):
                    for r, reason in known.items():
                        e.errors.setdefault(
                            r, exc.ActorDiedError("", reason))
                    raise exc.TrainWorkerGroupError(
                        e.errors,
                        set(e.dead_ranks) | set(known)) from e
            raise
        return rows

    def _preempted_error(self) -> "exc.TrainPreemptedError":
        pg_hex = self.pg.id.hex() if self.pg is not None else "?"
        return exc.TrainPreemptedError(
            message=f"training gang preempted: placement group {pg_hex} "
                    f"was reclaimed by a higher-priority job (graceful "
                    f"requeue — resumes from the latest checkpoint when "
                    f"capacity returns)")

    def shutdown(self):
        pm = getattr(self, "_preempt", None)
        if pm is not None:
            pm.stop()
            self._preempt = None
        monitor = getattr(self, "_monitor", None)
        if monitor is not None:
            monitor.stop()
            self._monitor = None
        if self.worker_group is not None:
            if getattr(self, "backend", None) is not None:
                self.backend.on_shutdown(self.worker_group)
            # the workers' rings die with them: keep their share of the
            # run's start-up, so that `timeline()` after `fit()` still
            # resolves every parent (dead ranks answer fast, with an
            # error; the timeout only bounds a hung one)
            try:
                for spans in self.worker_group.execute("run_spans",
                                                       timeout=5.0):
                    _prof.adopt(spans)
            except Exception:
                pass
            self.worker_group.shutdown()
            self.worker_group = None
        if self.pg is not None:
            try:
                remove_placement_group(self.pg)
            except Exception:
                pass
            self.pg = None
