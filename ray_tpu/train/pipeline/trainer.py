"""Multi-slice MPMD pipeline training (the workload half of the
SPREAD_ACROSS_SLICES scheduler).

``PipelineTrainer`` partitions a model into P explicit stages, places
one Train sub-gang per TPU slice (stage-labeled placement-group bundles
under the SPREAD_ACROSS_SLICES strategy), and runs an actor-level
GPipe/1F1B microbatch schedule: activations and activation-gradients
flow stage-to-stage over the host send/recv plane (the PR 4 one-way
fast path), intra-stage data parallelism rides a per-stage collective
group, and the inter-stage hop optionally travels bf16/int8 (the
classic half-width activation wire — ``PipelineConfig.wire_dtype``).

The fault story composes from the existing planes rather than adding a
new one: a dead stage rank poisons the gang's collective group (PR 5),
pending sends/recvs on every OTHER stage raise ``CollectiveGroupError``
within milliseconds instead of wedging their schedule windows, and
``fit()``'s FailureConfig loop tears the whole pipeline down and
resumes it from the latest checkpoint (which carries EVERY stage's
params — rank 0 assembles them from a per-step gather). Preemption
warnings (PR 13) reach every rank's session and force a checkpoint at
the next step boundary inside the grace window.

Observability: each stage stamps its schedule stalls as
``pipeline_bubble`` step-anatomy activities and the
``ray_tpu_pipeline_*`` metrics, so ``summarize_steps()`` reports a
measured per-stage bubble fraction directly comparable to the
``(P-1)/(M+P-1)`` schedule theory (``schedule.py``).

``reference_run`` executes the identical math single-process — the
bit-for-bit loss oracle the E2E suite checks the distributed run
against (same float op order: forwards in microbatch order, backwards
accumulating in microbatch order, one fused ``lr/M`` update multiply).
"""
from __future__ import annotations

import time

import numpy as np

from ray_tpu.air.config import RunConfig, ScalingConfig
from ray_tpu.train.pipeline import schedule as _sched
from ray_tpu.train.pipeline.stage import (
    Stage,
    mse_loss,
    sgd_update,
    synth_microbatch,
)
from ray_tpu.train.trainer import DataParallelTrainer


class PipelineConfig:
    """Knobs of the actor-level pipeline schedule.

    - ``num_microbatches`` (M): microbatches per optimizer step — the
      bubble lever ((P-1)/(M+P-1)).
    - ``schedule``: "gpipe" (all-forward-then-all-backward) or "1f1b"
      (bounded activation memory, same bubble).
    - ``inflight_window``: GPipe ack window — how many un-acked
      activations a stage may post downstream before parking for a
      credit; None reads config ``pipeline_inflight_window`` (0 =
      unbounded). 1F1B's warmup depth is its inherent bound.
    - ``wire_dtype``: "bf16"/"int8" quantizes the inter-stage
      ACTIVATION hop (gradients stay exact unless ``quantize_grads``);
      None reads config ``pipeline_wire_dtype`` (default off = the
      bit-exact path the loss oracle requires).
    - ``checkpoint_every``: cut a full-pipeline checkpoint every k
      steps (0 = only at the final step and on preemption warnings).
    """

    def __init__(self, num_microbatches: int = 4, schedule: str = "gpipe",
                 inflight_window: int | None = None,
                 wire_dtype: str | None = None,
                 quantize_grads: bool | None = None,
                 checkpoint_every: int = 0,
                 group_name: str = "pipeline"):
        if schedule not in _sched.SCHEDULES:
            raise ValueError(f"schedule must be one of {_sched.SCHEDULES}, "
                             f"got {schedule!r}")
        if num_microbatches < 1:
            raise ValueError("num_microbatches must be >= 1")
        if wire_dtype is not None:
            # fail a typo'd format HERE, at construction on the driver —
            # not in a remote worker's first send, where FailureConfig
            # would burn its whole retry budget on a deterministic
            # config error (None is NOT normalized away: it means
            # "defer to the pipeline_wire_dtype config default")
            from ray_tpu.util.collective import wire as _wire

            _wire.normalize_format(wire_dtype)
        self.num_microbatches = int(num_microbatches)
        self.schedule = schedule
        self.inflight_window = inflight_window
        self.wire_dtype = wire_dtype
        self.quantize_grads = quantize_grads
        self.checkpoint_every = int(checkpoint_every)
        self.group_name = group_name


def _resolve_wire(wire_dtype):
    from ray_tpu.util.collective import wire as _wire

    if wire_dtype is None:
        from ray_tpu._private.config import get_config

        wire_dtype = get_config("pipeline_wire_dtype")
    return _wire.normalize_format(wire_dtype)


def _pipeline_worker_loop(config: dict):
    """One gang member's schedule executor (runs as the Train worker's
    train function; global rank r = stage r // R, stage-rank r % R)."""
    from ray_tpu._private import fault_injection as _fi
    from ray_tpu._private import telemetry as _tm
    from ray_tpu._private.config import get_config
    from ray_tpu.air import session
    from ray_tpu.air.checkpoint import Checkpoint
    from ray_tpu._private import step_anatomy
    from ray_tpu.util import collective as col

    spec = config["_pipeline_spec"]
    rank = session.get_world_rank()
    num_stages = int(spec["num_stages"])
    ranks_per = int(spec["ranks_per_stage"])
    microbatches = int(spec["num_microbatches"])
    stage_idx, stage_rank = divmod(rank, ranks_per)
    # chaos scoping: seeded schedules like
    # `kill_actor:stage1-rank0.next_result:#2` land on exactly one
    # deterministic pipeline position
    _fi.add_tag(f"stage{stage_idx}-rank{stage_rank}")
    stage: Stage = spec["stages"][stage_idx]
    group = spec["group_name"]
    lr = float(spec["learning_rate"])
    loss_fn = mse_loss if spec["loss"] == "mse" else spec["loss"]
    wire = _resolve_wire(spec["wire_dtype"])
    quant_grads = spec["quantize_grads"]
    if quant_grads is None:
        quant_grads = bool(get_config("pipeline_quantize_grads"))
    window = spec["inflight_window"]
    if window is None:
        window = int(get_config("pipeline_inflight_window"))
    # the ack credit protocol assumes GPipe's phase split (all acks
    # precede all grads on the down->up channel); 1F1B's warmup depth
    # already bounds in-flight, so the window only arms under gpipe
    window = int(window) if spec["schedule"] == "gpipe" else 0

    params = stage.init_params(
        np.random.default_rng(int(spec["seed"]) + stage_idx))
    start_step = 0
    ckpt = session.get_checkpoint()
    if ckpt is not None:
        state = ckpt.to_dict()
        start_step = int(state["step"]) + 1
        params = [np.asarray(p, np.float32).copy()
                  for p in state["stage_params"][stage_idx]]

    stage_group = None
    if ranks_per > 1:
        # intra-stage data-parallel subgroup (grad allreduce rides the
        # normal pipelined ring inside the stage's slice)
        stage_group = f"{group}:stage{stage_idx}"
        col.init_collective_group(ranks_per, stage_rank, "host",
                                  stage_group)
    up = rank - ranks_per if stage_idx > 0 else None
    down = rank + ranks_per if stage_idx < num_stages - 1 else None
    actions = _sched.build_schedule(spec["schedule"], stage_idx,
                                    num_stages, microbatches)

    shard = session.get_dataset_shard(spec["dataset_name"]) \
        if stage_idx == 0 else None
    batch_iter = None
    if shard is not None and hasattr(shard, "iter_batches"):
        # streaming data plane feeds stage 0: one bounded-prefetch
        # iterator across the whole run (epoch semantics belong to the
        # dataset; the loop just keeps pulling microbatches)
        def _batches():
            while True:
                for b in shard.iter_batches(
                        batch_size=int(spec["microbatch_size"])):
                    yield b

        batch_iter = _batches()

    def _next_microbatch(step: int, mb: int):
        if batch_iter is not None:
            b = next(batch_iter)
            return (np.asarray(b["x"], np.float32),
                    np.asarray(b["y"], np.float32))
        return synth_microbatch(int(spec["seed"]) + stage_rank, step, mb,
                                int(spec["microbatch_size"]),
                                stage.in_dim or 1,
                                int(spec["out_dim"]))

    tags = {"group": group, "stage": str(stage_idx)}
    _ACK = np.zeros(1, np.int8)

    for step in range(start_step, int(spec["num_steps"])):
        step_t0 = time.monotonic()
        bubble = 0.0

        def _stalled(fn):
            """Run one blocking schedule wait, stamping it as bubble
            time (step-anatomy `pipeline_bubble` + the step total)."""
            nonlocal bubble
            t0 = time.monotonic()
            out = fn()
            t1 = time.monotonic()
            bubble += t1 - t0
            step_anatomy.record_activity("pipeline_bubble", t0, t1,
                                         stage=stage_idx)
            return out

        grads = [np.zeros_like(p) for p in params]
        caches: dict[int, object] = {}
        pending_gy: dict[int, np.ndarray] = {}
        loss_sum = 0.0
        sent = acked = 0
        drained = False
        for kind, mb in actions:
            if kind == "fwd":
                if up is None:
                    x, y = _next_microbatch(step, mb)
                else:
                    x = _stalled(lambda: col.recv(up, group))
                    y = col.recv(up, group)
                out, ctx = stage.forward(params, x)
                caches[mb] = ctx
                if down is not None:
                    if window and sent - acked >= window:
                        _stalled(lambda: col.recv(down, group))
                        acked += 1
                    col.send(out, down, group, wire_dtype=wire)
                    col.send(y, down, group)
                    sent += 1
                else:
                    loss, gy = loss_fn(out, y)
                    loss_sum += float(loss)
                    pending_gy[mb] = gy
                if up is not None and window:
                    col.send(_ACK, up, group)
            else:  # bwd
                if down is not None and window and not drained:
                    # GPipe phase boundary: the down->up channel holds
                    # the remaining fwd-phase ack credits ahead of the
                    # first gradient — drain them in order
                    for _ in range(sent - acked):
                        _stalled(lambda: col.recv(down, group))
                        acked += 1
                    drained = True
                if down is not None:
                    gy = _stalled(lambda: col.recv(down, group))
                else:
                    gy = pending_gy.pop(mb)
                gx, g = stage.backward(params, caches.pop(mb), gy)
                for i in range(len(grads)):
                    grads[i] += g[i]
                if up is not None:
                    col.send(gx, up, group,
                             wire_dtype=wire if quant_grads else None)
        if stage_group is not None:
            grads = [np.asarray(col.allreduce(g, stage_group))
                     for g in grads]
            if down is None:
                loss_sum = float(np.asarray(col.allreduce(
                    np.array([loss_sum], np.float64), stage_group))[0]
                    ) / ranks_per
        sgd_update(params, grads, lr,
                   1.0 / (microbatches * ranks_per))

        # ---- step-end consensus round: loss to rank 0, checkpoint
        # decision, preemption notice. One SMALL allgather keeps every
        # rank's collective order identical (the decision must be
        # uniform — a rank checkpointing alone would desync the group);
        # the actual params then move POINT-TO-POINT, each stage's once
        # straight to rank 0 — an allgather would broadcast the whole
        # model to every rank (O(world x model bytes) on the very
        # inter-slice links the pipeline exists to relieve).
        scheduled = bool(spec["checkpoint_every"]) and \
            (step + 1) % spec["checkpoint_every"] == 0
        final = step == int(spec["num_steps"]) - 1
        row = {"stage": stage_idx,
               "loss_sum": loss_sum if (down is None and stage_rank == 0)
               else None,
               "warned": session.preemption_warned() is not None}
        summary = col.allgather_object(row, group)
        want_ckpt = scheduled or final or any(r["warned"] for r in summary)
        stage_params = None
        if want_ckpt:
            import pickle as _pickle

            from ray_tpu._private import step_anatomy as _sa

            # checkpoint assembly is a step-loop stall: attribute it in
            # the same anatomy lane the sharded writer uses, so "why was
            # step k slow" answers "checkpoint", not "mystery bubble"
            _asm_t0 = time.monotonic()
            if rank == 0:
                stage_params = {0: [np.array(p) for p in params]}
                for s in range(1, num_stages):
                    blob = np.asarray(col.recv(s * ranks_per, group))
                    stage_params[s] = _pickle.loads(blob.tobytes())
            elif stage_rank == 0:
                col.send(np.frombuffer(_pickle.dumps(
                    [np.array(p) for p in params]), np.uint8), 0, group)
            try:
                _sa.record_activity("checkpoint", _asm_t0,
                                    time.monotonic(), blocking=True,
                                    phase="assemble", step=step)
            except Exception:
                pass

        step_wall = time.monotonic() - step_t0
        if _tm.ENABLED:
            _tm.observe("ray_tpu_pipeline_bubble_seconds", bubble,
                        tags=tags)
            _tm.observe("ray_tpu_pipeline_step_seconds", step_wall,
                        tags=tags)
            _tm.counter_inc("ray_tpu_pipeline_microbatches_total",
                            float(microbatches),
                            tags={**tags, "phase": "fwd"})
            _tm.counter_inc("ray_tpu_pipeline_microbatches_total",
                            float(microbatches),
                            tags={**tags, "phase": "bwd"})
        metrics = {"step": step, "stage": stage_idx,
                   "bubble_s": round(bubble, 6),
                   "step_wall_s": round(step_wall, 6),
                   "bubble_fraction": (round(bubble / step_wall, 6)
                                       if step_wall > 0 else 0.0)}
        checkpoint = None
        if rank == 0:
            metrics["loss"] = next(
                r["loss_sum"] for r in summary
                if r["loss_sum"] is not None) / microbatches
            if want_ckpt:
                checkpoint = Checkpoint.from_dict(
                    {"step": step, "stage_params": stage_params})
        session.report(metrics, checkpoint=checkpoint)

    if stage_group is not None:
        # drop the per-stage subgroup so its rendezvous actor doesn't
        # outlive the gang (the main group is destroyed by the backend's
        # on_shutdown; subgroups are this loop's to clean up)
        try:
            col.destroy_collective_group(stage_group)
        except Exception:
            pass


class PipelineTrainer(DataParallelTrainer):
    """Stage-partitioned MPMD pipeline training over one gang of
    P x ranks_per_stage workers, placed one stage per TPU slice.

    ``stages`` is the partitioned model (one ``Stage`` per pipeline
    stage); data enters at stage 0 (a ``datasets={"train": ...}`` shard
    through the streaming data plane, or the built-in deterministic
    synthetic feed), the loss lives on the last stage, and rank 0
    streams per-step metrics + full-pipeline checkpoints back through
    the normal Train result path — so FailureConfig gang restarts,
    preemption requeues and Tune wrapping all behave exactly as for a
    data-parallel gang."""

    def __init__(self, stages: list, *,
                 loss="mse", learning_rate: float = 0.05,
                 num_steps: int = 4, microbatch_size: int = 8,
                 seed: int = 0,
                 pipeline_config: PipelineConfig | None = None,
                 ranks_per_stage: int = 1,
                 resources_per_worker: dict | None = None,
                 placement_strategy: str = "SPREAD_ACROSS_SLICES",
                 dataset_name: str = "train",
                 run_config: RunConfig | None = None,
                 datasets: dict | None = None,
                 job: str | None = None,
                 resume_from_checkpoint=None):
        if not stages:
            raise ValueError("need at least one pipeline stage")
        pc = pipeline_config or PipelineConfig()
        num_stages = len(stages)
        num_workers = num_stages * ranks_per_stage
        self.pipeline_config = pc
        self.num_stages = num_stages
        self.ranks_per_stage = int(ranks_per_stage)
        spec = {
            "stages": list(stages),
            "num_stages": num_stages,
            "ranks_per_stage": int(ranks_per_stage),
            "num_microbatches": pc.num_microbatches,
            "schedule": pc.schedule,
            "inflight_window": pc.inflight_window,
            "wire_dtype": pc.wire_dtype,
            "quantize_grads": pc.quantize_grads,
            "checkpoint_every": pc.checkpoint_every,
            "group_name": pc.group_name,
            "learning_rate": float(learning_rate),
            "loss": loss,
            "num_steps": int(num_steps),
            "microbatch_size": int(microbatch_size),
            "out_dim": int(getattr(stages[-1], "out_dim", 1) or 1),
            "seed": int(seed),
            "dataset_name": dataset_name,
        }
        from ray_tpu.train.backend_executor import JaxConfig

        scaling = ScalingConfig(
            num_workers=num_workers,
            resources_per_worker=dict(resources_per_worker or {"CPU": 1}),
            placement_strategy=placement_strategy,
            bundle_stages=([i // ranks_per_stage
                            for i in range(num_workers)]
                           if placement_strategy == "SPREAD_ACROSS_SLICES"
                           else None),
            job=job)
        super().__init__(
            _pipeline_worker_loop,
            train_loop_config={"_pipeline_spec": spec},
            backend_config=JaxConfig(group_name=pc.group_name,
                                     collective_backend="host"),
            scaling_config=scaling, run_config=run_config,
            datasets=datasets,
            resume_from_checkpoint=resume_from_checkpoint)

    def _setup_datasets(self, executor):
        # only stage 0's ranks consume input: shard across the stage's
        # data-parallel width, not the whole gang; later stages receive
        # activations, not batches
        r = self.ranks_per_stage
        for name, ds in self.datasets.items():
            shards = list(self._shard_dataset(ds, r))
            shards += [None] * (self.num_stages * r - r)
            executor.set_dataset_shards(name, shards)

    def _drive(self, executor):
        self._record_gang_event(executor)
        return super()._drive(executor)

    def _record_gang_event(self, executor):
        """PIPELINE_GANG_STARTED with the stage -> slice placement the
        SPREAD_ACROSS_SLICES scheduler chose (driver-side: the PG is
        CREATED by the time _drive runs). Never fails training."""
        from ray_tpu._private import events as _events

        if not _events.ENABLED:
            return
        try:
            from ray_tpu._private import api as _api

            worker = _api._require_worker()
            snap = worker.gcs.call("get_placement_group",
                                   pg_id=executor.pg.id)
            nodes = {n["NodeID"]: n for n in worker.gcs.call("get_nodes")}
            labels = snap.get("Stages") or \
                list(range(len(snap["BundleNodes"])))
            stage_slices: dict = {}
            for lab, nid in zip(labels, snap["BundleNodes"]):
                tpu = (nodes.get(nid) or {}).get("tpu") or {}
                stage_slices.setdefault(str(lab), set()).add(
                    str(tpu.get("slice_id")))
            pc = self.pipeline_config
            _events.record(
                "PIPELINE_GANG_STARTED", group=pc.group_name,
                num_stages=self.num_stages,
                ranks_per_stage=self.ranks_per_stage,
                microbatches=pc.num_microbatches, schedule=pc.schedule,
                stage_slices={k: sorted(v)
                              for k, v in stage_slices.items()})
        except Exception:
            pass


def reference_run(stages: list, *, num_steps: int, num_microbatches: int,
                  microbatch_size: int, learning_rate: float,
                  seed: int = 0, loss="mse") -> dict:
    """Single-process oracle executing the pipeline's EXACT math —
    same init rngs, same synthetic feed, same float op order (forwards
    and loss accumulation in microbatch order, per-stage gradient
    accumulation in microbatch order, one fused ``lr/M`` update
    multiply) — so a distributed run with the exact wire must match its
    per-step losses and final params bit for bit, per seed."""
    loss_fn = mse_loss if loss == "mse" else loss
    params = [st.init_params(np.random.default_rng(seed + i))
              for i, st in enumerate(stages)]
    in_dim = stages[0].in_dim or 1
    out_dim = int(getattr(stages[-1], "out_dim", 1) or 1)
    m = int(num_microbatches)
    losses = []
    for step in range(int(num_steps)):
        grads = [[np.zeros_like(p) for p in ps] for ps in params]
        caches, gys = [], []
        loss_sum = 0.0
        for mb in range(m):
            x, y = synth_microbatch(seed, step, mb, microbatch_size,
                                    in_dim, out_dim)
            ctxs = []
            h = x
            for st, ps in zip(stages, params):
                h, ctx = st.forward(ps, h)
                ctxs.append(ctx)
            step_loss, gy = loss_fn(h, y)
            loss_sum += float(step_loss)
            caches.append(ctxs)
            gys.append(gy)
        for mb in range(m):
            gy = gys[mb]
            for si in reversed(range(len(stages))):
                gx, g = stages[si].backward(params[si], caches[mb][si], gy)
                for i in range(len(grads[si])):
                    grads[si][i] += g[i]
                gy = gx
        for si in range(len(stages)):
            sgd_update(params[si], grads[si], learning_rate, 1.0 / m)
        losses.append(loss_sum / m)
    return {"losses": losses, "params": params}
