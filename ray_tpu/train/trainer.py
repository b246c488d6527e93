"""Trainers (reference: python/ray/train/base_trainer.py:38 BaseTrainer.fit
:339; data_parallel_trainer.py:55 DataParallelTrainer).

JaxTrainer is the flagship: gang-schedules a worker per TPU host, wires the
data-parallel backend, streams results/checkpoints, returns a Result. The
reference wraps trainers in Tune trainables; here fit() drives the
BackendExecutor directly, and the Tune layer wraps Trainer the same way when
sweeping.
"""
from __future__ import annotations

import os
import time

from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.air.config import RunConfig, ScalingConfig
from ray_tpu.air.result import Result
from ray_tpu.train.backend_executor import BackendExecutor, JaxConfig


class BaseTrainer:
    def __init__(self, *, scaling_config: ScalingConfig | None = None,
                 run_config: RunConfig | None = None,
                 datasets: dict | None = None,
                 resume_from_checkpoint: Checkpoint | None = None):
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.datasets = datasets or {}
        self.resume_from_checkpoint = resume_from_checkpoint

    def fit(self) -> Result:
        raise NotImplementedError

    def as_trainable(self):
        """Adapter for the Tune layer: a function trainable running one
        fit() per trial config (reference: base_trainer.py:369)."""
        trainer = self

        def _trainable(config):
            from ray_tpu.air import session

            t = trainer.with_updated_config(config)
            result = t.fit()
            if result.error is not None:
                raise result.error
            session.report(result.metrics, checkpoint=result.checkpoint)

        return _trainable

    def with_updated_config(self, config: dict) -> "BaseTrainer":
        return self


class DataParallelTrainer(BaseTrainer):
    """(reference: data_parallel_trainer.py:55) Runs `train_loop_per_worker`
    on every worker of the gang; workers cooperate via the collective group
    (host backend) or a shared jax mesh (distributed mode)."""

    def __init__(self, train_loop_per_worker, *,
                 train_loop_config: dict | None = None,
                 backend_config: JaxConfig | None = None,
                 scaling_config: ScalingConfig | None = None,
                 run_config: RunConfig | None = None,
                 datasets: dict | None = None,
                 resume_from_checkpoint: Checkpoint | None = None):
        super().__init__(scaling_config=scaling_config,
                         run_config=run_config, datasets=datasets,
                         resume_from_checkpoint=resume_from_checkpoint)
        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = dict(train_loop_config or {})
        self.backend_config = backend_config or JaxConfig()

    def with_updated_config(self, config: dict) -> "DataParallelTrainer":
        merged = {**self.train_loop_config, **config}
        return type(self)(
            self.train_loop_per_worker, train_loop_config=merged,
            backend_config=self.backend_config,
            scaling_config=self.scaling_config, run_config=self.run_config,
            datasets=self.datasets,
            resume_from_checkpoint=self.resume_from_checkpoint)

    def fit(self) -> Result:
        """Run training with gang-level fault tolerance.

        A failed attempt — a dead rank (TrainWorkerGroupError), a
        poisoned collective group (CollectiveGroupError in survivors),
        or any worker exception — tears the gang down cleanly (destroy
        the collective group, kill the workers with restarts suppressed,
        release the placement group), then rebuilds it and RESUMES the
        train loop from the latest successfully persisted checkpoint of
        the failed attempt (surfaced to workers via
        session.get_checkpoint()), up to FailureConfig.max_failures
        times. Exhausting the budget re-raises the last failure.

        Retry pacing reuses the unified control-plane policy
        (_private/retry.py): full-jitter exponential backoff, and each
        gang retry draws one token from the process-wide retry budget so
        restart storms surface through the budget-exhaustion event."""
        from ray_tpu._private import profiling

        # the run's root span: every span of this run, in the driver, the
        # raylet and the workers, carries its `run` (it rides the specs
        # with the causing span's id)
        run = f"{self.run_config.name or 'train'}-{os.urandom(4).hex()}"
        with profiling.record_span("startup", "fit", run=run):
            return self._fit()

    def _fit(self) -> Result:
        from ray_tpu._private import events as _events
        from ray_tpu._private import telemetry as _tm
        from ray_tpu._private.retry import RetryPolicy, default_budget

        fc = self.run_config.failure_config
        max_failures = fc.max_failures
        # non-Jax backends (TorchConfig) have no group name; the metric
        # tag falls back to the trainer run name
        group = getattr(self.backend_config, "group_name", None) \
            or self.run_config.name or "train"
        # gang restarts are heavyweight (teardown + reschedule + rebuild):
        # a larger base than the RPC default, same full-jitter shape.
        # Only backoff() is consulted — the retry budget here is
        # FailureConfig.max_failures (checked below), not the policy's
        # attempt cap
        from ray_tpu import exceptions as exc

        policy = RetryPolicy(base_backoff_s=0.5, max_backoff_s=10.0)
        attempt = 0
        preempt_requeues = 0
        self._group = group
        self._resume_ckpt = self.resume_from_checkpoint
        self._latest_checkpoint = None
        self._latest_iteration = None
        while True:
            self._attempt = attempt + 1
            try:
                return self._fit_once()
            except Exception as e:
                # GANG_FAILED event + flight-recorder dump were recorded
                # inside _fit_once, BEFORE its finally tore the gang
                # down — a post-teardown dump would capture only idle
                # pool workers, not the survivors' final spans
                preempted = isinstance(e, exc.TrainPreemptedError)
                if preempted:
                    # graceful degradation, not failure: a preempted
                    # gang re-queues and resumes from its checkpoint
                    # WITHOUT burning a max_failures token — the victim
                    # of another tenant's scale-up must not exhaust its
                    # own failure budget. The GCS's PREEMPTION_* events
                    # carry the audit trail.
                    preempt_requeues += 1
                    self._requeue_wait = True
                elif isinstance(e, exc.PlacementGroupUnschedulableError) \
                        and getattr(self, "_requeue_wait", False):
                    # the re-queued gang timed out WAITING for the
                    # preemptor to release capacity — still the
                    # preemption, not a new failure: keep waiting (the
                    # contract is "resumes when capacity returns", and
                    # charging the budget here would kill a preempted
                    # run whose preemptor merely outlives a few
                    # 120s placement windows)
                    preempt_requeues += 1
                else:
                    self._requeue_wait = False
                    attempt += 1
                    if max_failures != -1 and attempt > max_failures:
                        raise
                if getattr(fc, "restore_from_latest_checkpoint", True) \
                        and self._latest_checkpoint is not None:
                    self._resume_ckpt = self._latest_checkpoint
                # retry-budget event on every gang retry: take() records
                # budget exhaustion as a cluster event; the retry itself
                # proceeds regardless (failing training over an RPC-storm
                # budget would punish the victim)
                budget_ok = default_budget().take()
                _events.record("train_gang_retry", group=group,
                               attempt=attempt,
                               max_failures=max_failures,
                               budget_ok=budget_ok,
                               preempted=preempted,
                               preempt_requeues=preempt_requeues,
                               resume_iteration=self._latest_iteration)
                time.sleep(policy.backoff(max(1, attempt)))
                _tm.counter_inc("ray_tpu_train_gang_restarts_total",
                                tags={"group": group})
                _events.record("GANG_RESTARTED", group=group,
                               attempt=attempt,
                               preempted=preempted,
                               resume_iteration=self._latest_iteration)

    def _fit_once(self) -> Result:
        from ray_tpu._private import events as _events

        executor = None
        try:
            executor = BackendExecutor(self.backend_config,
                                       self.scaling_config).start()
            # the gang placed: a LATER unschedulable error is a fresh
            # capacity problem, not the preemption's requeue wait
            self._requeue_wait = False
            self._setup_datasets(executor)
            config = dict(self.train_loop_config)
            resume = getattr(self, "_resume_ckpt", None) \
                or self.resume_from_checkpoint
            if resume is not None:
                config["_resume_checkpoint"] = resume
            if self.run_config.storage_path:
                # generation root for sharded checkpoints: a sibling of
                # the rank-0 checkpoint_* dirs (which _drive's pruning
                # scans by prefix — gen_* dirs are invisible to it)
                config["_checkpoint_dir"] = os.path.join(
                    self.run_config.storage_path,
                    self.run_config.name or "train_run", "sharded")
            executor.start_training(self.train_loop_per_worker, config)
            return self._drive(executor)
        except Exception as e:
            from ray_tpu import exceptions as exc

            if isinstance(e, exc.TrainPreemptedError) or (
                    isinstance(e, exc.PlacementGroupUnschedulableError)
                    and getattr(self, "_requeue_wait", False)):
                # graceful preemption — including the requeued gang
                # timing out WAITING for the preemptor's capacity — is
                # NOT a failure: no GANG_FAILED, no flight-recorder
                # dump (a preemptor holding capacity for minutes would
                # otherwise force a full-cluster dump per 120s wait
                # cycle). The GCS's PREEMPTION_WARNED/PREEMPTION_FIRED
                # events are the audit trail, and the black box must
                # stay armed for real incidents.
                raise
            # The gang's surviving workers are STILL ALIVE here (the
            # finally below is what tears them down): record the
            # failure and cut the cluster black box now, so the dump
            # captures the survivors' final collective spans and step
            # records instead of post-teardown idle pool workers.
            # force ONLY on the first attempt: the death monitor's own
            # trigger may have fired moments earlier, BEFORE this
            # GANG_FAILED event existed, and the flagship dump must not
            # be debounced into missing it — but a crash-looping gang
            # retrying every backoff must not write one full cluster
            # dump per attempt (later attempts ride the 15s debounce).
            dead = sorted(getattr(e, "dead_ranks", ()) or ())
            attempt = getattr(self, "_attempt", 1)
            # what the restart will resume from: the newest COMMITTED
            # sharded generation (a torn one left by the crash is
            # invisible to restore and must not be advertised here)
            resume_hint = None
            if executor is not None:
                resume_hint = executor.checkpoint_resume_hint()
            _events.record("GANG_FAILED", group=self._group,
                           attempt=attempt, dead_ranks=list(dead),
                           resume_step=(resume_hint or {}).get("step"),
                           error=f"{type(e).__name__}: {e}")
            from ray_tpu._private import flight_recorder as _fr

            _fr.trigger_dump("GANG_FAILED", force=attempt == 1)
            raise
        finally:
            if executor is not None:
                executor.shutdown()

    def _setup_datasets(self, executor):
        for name, ds in self.datasets.items():
            shards = self._shard_dataset(ds, self.scaling_config.num_workers)
            executor.set_dataset_shards(name, shards)

    @staticmethod
    def _shard_dataset(ds, n: int):
        # ray_tpu.data Dataset → split; plain lists/arrays → even chunks
        if hasattr(ds, "split"):
            return ds.split(n)
        size = len(ds)
        chunk = (size + n - 1) // n
        return [ds[i * chunk:(i + 1) * chunk] for i in range(n)]

    def _drive(self, executor) -> Result:
        history: list[dict] = []
        final_checkpoint = None
        storage = self.run_config.storage_path
        ckpt_dir = None
        if storage:
            ckpt_dir = os.path.join(
                storage, self.run_config.name or "train_run")
            os.makedirs(ckpt_dir, exist_ok=True)
        kept: list[str] = []
        num_keep = self.run_config.checkpoint_config.num_to_keep
        if ckpt_dir:
            # re-seed the pruning window from disk: _drive runs once per
            # gang attempt, and without this a failed attempt's dirs fall
            # out of the window forever — each restart would strand up to
            # num_to_keep dirs and the run's disk use grows unboundedly
            kept = sorted(
                os.path.join(ckpt_dir, d) for d in os.listdir(ckpt_dir)
                if d.startswith("checkpoint_"))
        # Drive until RANK 0's stream ends. Workers report at different
        # cadences (e.g. HF callbacks report only on the world-zero
        # process), so a faster worker's completion sentinel must not
        # truncate rank 0's remaining reports — a finished worker's
        # next_result just keeps answering "done", making extra rounds
        # harmless.
        errors: dict[int, BaseException] = {}
        retryable = self.run_config.failure_config.max_failures != 0
        while True:
            rows = executor.next_results()
            rank0_done = False
            for rank, r in enumerate(rows):   # rows arrive in gang order
                if r.get("done"):
                    if r.get("error"):
                        errors.setdefault(rank, r["error"])
                    if rank == 0:
                        rank0_done = True
                    continue
                if rank != 0:
                    continue
                history.append(r["metrics"])
                if r.get("checkpoint") is not None:
                    final_checkpoint = r["checkpoint"]
                    if ckpt_dir:
                        path = os.path.join(
                            ckpt_dir, f"checkpoint_{r['iteration']:06d}")
                        final_checkpoint.to_directory(path)
                        if path in kept:
                            # session iteration counters restart per
                            # attempt, so a resumed gang re-uses dir
                            # names — treat the rewrite as newest, never
                            # as a prune candidate for itself
                            kept.remove(path)
                        kept.append(path)
                        if num_keep and len(kept) > num_keep:
                            import shutil

                            shutil.rmtree(kept.pop(0),
                                          ignore_errors=True)
                    # remembered across attempts: a gang restart resumes
                    # from here ("successfully persisted" = written to
                    # storage when storage is configured, else the last
                    # checkpoint streamed off the workers)
                    self._latest_checkpoint = final_checkpoint
                    self._latest_iteration = r.get("iteration")
            if errors:
                if retryable:
                    # hand the failure to fit()'s gang-restart loop with
                    # per-rank attribution (FailureConfig.max_failures
                    # != 0 opted into restart-from-checkpoint semantics)
                    from ray_tpu import exceptions as exc

                    raise exc.TrainWorkerGroupError(errors)
                first = errors[min(errors)]
                return Result(
                    metrics=history[-1] if history else {},
                    checkpoint=final_checkpoint,
                    error=first, metrics_history=history,
                    path=ckpt_dir)
            if rank0_done:
                break
        return Result(metrics=history[-1] if history else {},
                      checkpoint=final_checkpoint,
                      metrics_history=history, path=ckpt_dir)


class JaxTrainer(DataParallelTrainer):
    """The canonical TPU trainer (the reference's TorchTrainer analog,
    train/torch/torch_trainer.py). Alias with jax-specific defaults."""
