"""Worker-facing training session API (reference: python/ray/air/session.py —
report :41, get_world_rank :220, get_dataset_shard :345).

Inside a training worker, `session.report(metrics, checkpoint=...)` streams
an intermediate result back to the trainer; rank/size accessors describe the
worker's place in the gang. The active session is process-global state set
by the train worker actor before the user function runs.
"""
from __future__ import annotations

import queue
import threading

from ray_tpu._private import step_anatomy


class _Session:
    def __init__(self, world_rank: int, world_size: int, local_rank: int = 0,
                 dataset_shards: dict | None = None, trial_info=None):
        self.world_rank = world_rank
        self.world_size = world_size
        self.local_rank = local_rank
        self.dataset_shards = dataset_shards or {}
        self.trial_info = trial_info
        self.results: queue.Queue = queue.Queue()
        self.finished = threading.Event()
        self.error: BaseException | None = None
        self.iteration = 0
        # set by TrainWorker.notify_preemption when the gang's placement
        # group receives a PREEMPTION warning: {"grace_s", "warned_at"}
        self.preempt_notice: dict | None = None

    def report(self, metrics: dict, checkpoint=None):
        self.iteration += 1
        # step-anatomy boundary: the interval between reports IS the
        # step, and the report's iteration number its monotonically
        # increasing step_id. No-op outside an instrumented train loop
        # (e.g. Tune function trainables reporting on the driver).
        step_anatomy.advance(self.iteration)
        self.results.put({"metrics": dict(metrics),
                          "checkpoint": checkpoint,
                          "iteration": self.iteration,
                          "world_rank": self.world_rank})


_active: _Session | None = None
_lock = threading.Lock()


def _set_session(sess: _Session | None):
    global _active
    with _lock:
        _active = sess


def _get_session() -> _Session:
    if _active is None:
        raise RuntimeError(
            "session API used outside a training worker — these functions "
            "only work inside a train_loop_per_worker")
    return _active


def report(metrics: dict, *, checkpoint=None):
    _get_session().report(metrics, checkpoint)


def get_world_rank() -> int:
    return _get_session().world_rank


def get_world_size() -> int:
    return _get_session().world_size


def get_local_rank() -> int:
    return _get_session().local_rank


def get_dataset_shard(dataset_name: str = "train"):
    return _get_session().dataset_shards.get(dataset_name)


def get_checkpoint():
    """Starting checkpoint when resuming (Tune restore / PBT exploit)."""
    return getattr(_get_session(), "resume_checkpoint", None)


def get_checkpoint_dir() -> str | None:
    """The sharded-checkpoint generation root for this training run
    (``<storage_path>/<name>/sharded``, plumbed by the trainer), or
    ``None`` outside a trainer run. ``train.sharded_checkpoint``'s
    save/restore default their ``root`` to this, so a train loop can
    call them with no path plumbing of its own."""
    return getattr(_get_session(), "checkpoint_dir", None)


def preemption_warned() -> dict | None:
    """Non-None once this gang's placement group received a PREEMPTION
    warning from the multi-tenant scheduler: a higher-priority job will
    reclaim its bundles after the grace window. A cooperative train
    loop checks this between steps and cuts a checkpoint (via
    ``report(..., checkpoint=...)``) inside the window — the driver
    then tears the gang down gracefully and resumes it from that
    checkpoint when capacity returns. Returns
    ``{"grace_s": float, "warned_at": epoch_s}``."""
    return _get_session().preempt_notice


def get_trial_name() -> str:
    info = _get_session().trial_info
    return info.get("name", "") if info else ""
