"""Gang of training worker actors (reference:
python/ray/train/_internal/worker_group.py:92).

Each worker hosts the user's train function in a thread and streams
session.report results back through `next_result` calls. Workers are
plain actors; gang placement comes from the BackendExecutor's placement
group.
"""
from __future__ import annotations

import os
import threading
import time

import ray_tpu
from ray_tpu._private import api as _api
from ray_tpu._private import profiling as _prof


class TrainWorker:
    """Actor body for one training worker."""

    def __init__(self, world_rank: int, world_size: int,
                 num_tpus: float = 0):
        from ray_tpu._private import fault_injection as _fi
        from ray_tpu.air import session as _session

        self.world_rank = world_rank
        self.world_size = world_size
        self.num_tpus = num_tpus     # TPU chips this worker's lease holds
        self.session = _session._Session(world_rank, world_size)
        self._thread = None
        self._device_identity = None
        # tag this process with its gang rank so rank-scoped chaos rules
        # (e.g. `kill_actor:rank1.next_result:#2`) target exactly one
        # member deterministically
        _fi.add_tag(f"rank{world_rank}")

    def device_identity(self) -> dict:
        """This worker's device identity (host/pid always; platform and
        device ids once the train function has imported jax). Resolved
        lazily and re-resolved until jax shows up, so the first report
        AFTER the backend initialized carries the real device info."""
        if (self._device_identity is None
                or self._device_identity.get("platform") is None):
            from ray_tpu._private.tpu_probe import local_device_identity

            self._device_identity = local_device_identity()
        return self._device_identity

    def _require_tpu_backend(self):
        """A worker granted TPU chips computes on them or fails the gang
        (``TpuBackendError``). A process whose JAX_PLATFORMS names other
        platforms only was pinned there on purpose — CPU dry runs with
        injected TPU resources — and is left alone."""
        from ray_tpu import exceptions as exc

        # the worker's own JAX start: the import, and the backend taking
        # the chips. A worker left alone leaves the span empty (backend
        # None): JAX then starts wherever the train function first asks
        args = {"backend": None, "devices": 0}
        with _prof.record_span("startup", "backend_up", args):
            pinned = os.environ.get("JAX_PLATFORMS", "")
            if not self.num_tpus or (
                    pinned and "tpu" not in pinned.split(",")):
                return
            import jax

            backend = args["backend"] = jax.default_backend()
            args["devices"] = jax.local_device_count()
        if backend != "tpu":
            raise exc.TpuBackendError(
                f"train worker rank {self.world_rank} holds "
                f"{self.num_tpus:g} TPU chip(s) but its JAX backend is "
                f"{backend!r}: libtpu could not take the chips (another "
                f"process may hold them)")

    def run_spans(self) -> list:
        """This process's start-up and compile spans and the spans they
        name as parents (the call that started the train function), for
        the driver to keep (`BackendExecutor.shutdown`): the process is
        about to be killed."""
        events = [ev for ev in _prof.snapshot() if ev.get("ph") == "X"]
        kept = [ev for ev in events
                if ev["cat"] in ("startup", "compile")]
        parents = {ev["args"].get("parent") for ev in kept}
        return kept + [ev for ev in events
                       if ev["cat"] not in ("startup", "compile")
                       and ev["args"]["id"] in parents]

    def setup_collective_group(self, world_size, rank, backend, group_name):
        from ray_tpu.util import collective as col

        col.init_collective_group(world_size, rank, backend, group_name)
        return rank

    def run_setup(self, setup_fn_and_args):
        """Backend hook (e.g. jax.distributed.initialize)."""
        fn, args, kwargs = setup_fn_and_args
        return fn(self.world_rank, self.world_size, *args, **kwargs)

    def free_coordinator_address(self):
        """A jax.distributed coordinator endpoint on THIS worker's host
        (port negotiated here instead of a collision-prone fixed default)."""
        import socket

        s = socket.socket()
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
        host = socket.gethostbyname(socket.gethostname())
        return f"{host}:{port}"

    def destroy_collective(self, group_name: str):
        from ray_tpu.util import collective as col

        return col.destroy_collective_group(group_name)

    def set_dataset_shard(self, name, shard):
        # Tag the shard with a per-rank consumer label so the streaming
        # data plane's telemetry (`ray_tpu_data_wait_seconds{consumer}`)
        # attributes data wait to the gang member it stalls — the
        # per-step "input gates the train step" signal.
        if hasattr(shard, "iter_batches"):
            try:
                shard._consumer = f"train/{name}/rank{self.world_rank}"
            except Exception:
                pass   # exotic shard types (plain lists) have no attrs
        self.session.dataset_shards[name] = shard

    def start_training(self, train_fn, config):
        from ray_tpu.air import session as _session

        if config is not None and "_resume_checkpoint" in config:
            # gang restart / resume_from_checkpoint: surfaced through
            # session.get_checkpoint() so the train loop can restore
            self.session.resume_checkpoint = config.pop(
                "_resume_checkpoint")
        if config is not None and "_checkpoint_dir" in config:
            # sharded-checkpoint generation root (trainer storage_path):
            # surfaced through session.get_checkpoint_dir() so
            # train.sharded_checkpoint save/restore need no path plumbing
            self.session.checkpoint_dir = config.pop("_checkpoint_dir")
        _session._set_session(self.session)
        # this call's span (its parent: the driver's span that made the
        # call) is the train function's cause; the function's own thread
        # starts with an empty stack
        above = _prof.current() or (None, None)

        def _run():
            from ray_tpu._private import step_anatomy

            try:
                with _prof.record_span("startup", "train_fn",
                                       {"rank": self.world_rank},
                                       parent=above[0], run=above[1]):
                    self._require_tpu_backend()
                    # step 1 opens when the train function starts, AFTER
                    # the worker's JAX start (`backend_up` says where
                    # that time went: `step::1` and the first sample of
                    # `ray_tpu_step_seconds` do not hold it); each
                    # session.report advances it (iteration == step_id),
                    # so every collective/data/compile interval recorded
                    # by this gang member fuses by step, not by
                    # wall-clock windows
                    step_anatomy.start(rank=self.world_rank)
                    train_fn(config) if config is not None else train_fn()
            except BaseException as e:  # noqa: BLE001
                self.session.error = e
            finally:
                step_anatomy.finish()
                self.session.finished.set()

        self._thread = threading.Thread(target=_run, daemon=True,
                                        name="train-fn")
        self._thread.start()
        return True

    def next_result(self, timeout: float = 300.0):
        """Blocks for the next session.report() payload; returns
        {"done": True, "error": ...} when the function finishes.

        `timeout` only bounds the wait once the train thread is no longer
        alive: while the user function is still running it may legitimately
        go far longer than any fixed budget between reports (first-step XLA
        compiles, large eval passes), and killing the run for that would be
        spurious (advisor finding on the old hard 300s deadline)."""
        import queue as _q

        dead_deadline = None
        while True:
            try:
                row = self.session.results.get(timeout=0.1)
            except _q.Empty:
                if self.session.finished.is_set() and \
                        self.session.results.empty():
                    err = self.session.error
                    return {"done": True,
                            "error": err if err is None else
                            _stringify_error(err)}
                if self._thread is None or not self._thread.is_alive():
                    # measure against a monotonic deadline: counting 0.1s
                    # per Empty undercounts under load (each get() may
                    # block longer than its timeout), letting the
                    # deadline drift arbitrarily late
                    now = time.monotonic()
                    if dead_deadline is None:
                        dead_deadline = now + timeout
                    elif now >= dead_deadline:
                        raise TimeoutError(
                            "train thread gone without reporting a result")
            else:
                self._record_step_event(row)
                return row

    def _record_step_event(self, row: dict):
        """Tag one streamed step report with this worker's device
        identity (data-plane observability: which chip produced which
        step). Never fails the report path."""
        from ray_tpu._private import events as _events

        if not _events.ENABLED:
            return
        try:
            _events.record("train_step", rank=self.world_rank,
                           iteration=row.get("iteration"),
                           device=self.device_identity())
            # this process OWNS the jax backend, so it is the one place
            # live HBM gauges can come from without contending for the
            # chips (the raylet's subprocess probe can't run while
            # training holds them)
            from ray_tpu._private.tpu_probe import (
                publish_local_device_gauges,
            )

            publish_local_device_gauges()
        except Exception:
            pass

    def notify_preemption(self, grace_s: float):
        """Driver push on a PREEMPTION warning: surface it to the train
        loop through ``session.preemption_warned()`` so a cooperative
        loop checkpoints inside the grace window (checkpoint-then-yield)
        instead of losing everything since its last natural
        checkpoint."""
        self.session.preempt_notice = {"grace_s": float(grace_s),
                                       "warned_at": time.time()}
        return True

    def shutdown(self):
        return True


def _stringify_error(err: BaseException):
    # ship original if picklable, else a summary
    import pickle

    try:
        pickle.dumps(err)
        return err
    except Exception:
        return RuntimeError(f"{type(err).__name__}: {err}")


class WorkerGroup:
    def __init__(self, num_workers: int, resources_per_worker: dict,
                 placement_group=None):
        remote_cls = ray_tpu.remote(TrainWorker)
        self.workers = []
        for rank in range(num_workers):
            opts = dict(resources_per_worker)
            kwargs = {
                "num_cpus": opts.pop("CPU", 1),
                "resources": opts or None,
                # Gang members must NEVER be silently actor-restarted by
                # the raylet mid-incarnation: a restarted rank has fresh
                # collective counters and no session state, which
                # corrupts the group. Restarts are a GANG-level decision
                # (fit()'s FailureConfig loop tears down and rebuilds
                # everything from the latest checkpoint).
                "max_restarts": 0,
            }
            if "TPU" in (resources_per_worker or {}):
                kwargs["num_tpus"] = resources_per_worker["TPU"]
                kwargs["resources"] = {
                    k: v for k, v in (kwargs["resources"] or {}).items()
                    if k != "TPU"} or None
            if placement_group is not None:
                from ray_tpu.util.scheduling_strategies import (
                    PlacementGroupSchedulingStrategy,
                )

                kwargs["scheduling_strategy"] = \
                    PlacementGroupSchedulingStrategy(
                        placement_group=placement_group,
                        placement_group_bundle_index=rank)
            self.workers.append(
                remote_cls.options(**kwargs).remote(
                    rank, num_workers, kwargs.get("num_tpus", 0)))

    def __len__(self):
        return len(self.workers)

    # how often a gang-blocking execute consults abort_check while a
    # ref is still unresolved (the death monitor's fast-fail cadence)
    ABORT_POLL_S = 1.0

    def execute(self, method_name: str, *args, timeout=None,
                abort_check=None, **kwargs):
        """Run one method on every worker; results in gang (rank) order.

        Failures are attributed PER RANK: one dead worker no longer
        poisons the whole gang's result with whichever exception its
        `get` happened to raise first — every rank's ref is resolved,
        and the aggregate surfaces as TrainWorkerGroupError carrying
        {rank: error} plus the subset of ranks whose actor died.

        `abort_check` (optional, () -> {rank: reason}) is polled while a
        ref is pending: the moment it reports dead ranks the whole call
        raises, even if the RPC layer never surfaces the death (e.g. a
        partition where no TCP reset arrives) — this is how the gang
        death monitor's pubsub knowledge interrupts a blocked gang call
        within seconds instead of waiting out the transport."""
        from ray_tpu import exceptions as exc

        refs = [getattr(w, method_name).remote(*args, **kwargs)
                for w in self.workers]
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        results: list = [None] * len(refs)
        errors: dict[int, BaseException] = {}
        dead: list[int] = []

        def _resolve():
            for rank, ref in enumerate(refs):
                remaining = (None if deadline is None
                             else max(0.0, deadline - time.monotonic()))
                try:
                    results[rank] = ray_tpu.get(ref, timeout=remaining)
                except (exc.ActorDiedError, exc.ActorUnavailableError,
                        exc.WorkerCrashedError) as e:
                    errors[rank] = e
                    dead.append(rank)
                except Exception as e:  # noqa: BLE001 — per rank
                    errors[rank] = e

        if abort_check is None:
            _resolve()
        else:
            # Resolve on a waiter thread so the gang call blocks in ONE
            # get per rank: re-entering get(timeout=1.0) in a loop would
            # re-run its store/directory probe rounds (and reset its
            # poll escalation) every tick for the whole training run.
            # The main thread polls only in-process state — abort_check
            # is a lock-guarded dict copy, done.wait a futex.
            done = threading.Event()

            def _run():
                try:
                    _resolve()
                finally:
                    done.set()

            # daemon + abandoned on abort: teardown kills the gang's
            # workers (no_restart), which fails the pending get and
            # lets the waiter exit
            threading.Thread(target=_run, daemon=True,
                             name="gang-execute-waiter").start()
            while not done.wait(self.ABORT_POLL_S):
                known = abort_check()
                if known:
                    errs = dict(errors)
                    for r, reason in known.items():
                        errs.setdefault(
                            r, exc.ActorDiedError("", str(reason)))
                    raise exc.TrainWorkerGroupError(
                        errs, sorted(set(dead) | set(known)))
        if errors:
            raise exc.TrainWorkerGroupError(errors, dead)
        return results

    def execute_single(self, rank: int, method_name: str, *args, **kwargs):
        return ray_tpu.get(
            getattr(self.workers[rank], method_name).remote(*args, **kwargs))

    def shutdown(self):
        # no_restart suppresses any raylet-side restart race: a gang
        # teardown must leave zero members behind to leak stale frames
        # into the next incarnation
        for w in self.workers:
            try:
                ray_tpu.kill(w, no_restart=True)
            except Exception:
                pass
        self.workers = []
