"""Worker process entry point — forked by the raylet's worker pool.

Analog of the reference's default_worker.py
(/root/reference/python/ray/_private/workers/default_worker.py): connect the
core worker to this node's raylet/GCS/store, then serve the task execution
loop until the raylet (or an actor kill) terminates us.
"""
from __future__ import annotations

import os
import signal
import sys
import time

_T_PROCESS = time.time()    # as early as this process knows its own start


def main():
    import faulthandler

    faulthandler.register(signal.SIGUSR1, all_threads=True)  # `ray stack`
    faulthandler.enable()   # SIGSEGV/SIGABRT dump to stderr (worker logs)
    from ray_tpu._private import fault_injection

    fault_injection.set_role("worker")
    gcs_host, gcs_port = os.environ["RAY_TPU_GCS_ADDR"].split(":")
    raylet_host, raylet_port = os.environ["RAY_TPU_RAYLET_ADDR"].split(":")

    from ray_tpu._private.protocol import ConnectionLost
    from ray_tpu._private.worker_runtime import CoreWorker, set_current_worker

    try:
        worker = CoreWorker(
            gcs_addr=(gcs_host, int(gcs_port)),
            raylet_addr=(raylet_host, int(raylet_port)),
            mode="worker",
            store_name=os.environ.get("RAY_TPU_STORE_NAME"),
            spill_dir=os.environ.get("RAY_TPU_SPILL_DIR"),
            worker_id=os.environ.get("RAY_TPU_WORKER_ID"),
            job_id=0,
        )
    except ConnectionLost:
        # Cluster shut down while we were starting (e.g. a prestarted worker
        # racing teardown) — exit quietly.
        return 0
    set_current_worker(worker)

    profile_dir = os.environ.get("RAY_TPU_WORKER_PROFILE")
    prof = None
    if profile_dir:
        import cProfile

        prof = cProfile.Profile()

    def _dump_profile():
        if prof is not None:
            try:
                os.makedirs(profile_dir, exist_ok=True)
                prof.dump_stats(os.path.join(
                    profile_dir, f"worker-{os.getpid()}.prof"))
            except Exception:
                pass

    def _term(signum, frame):
        worker.stopped = True
        _dump_profile()
        os._exit(0)

    signal.signal(signal.SIGTERM, _term)

    # Liveness watchdog: the main thread may be stuck inside a hung task
    # when the raylet dies — this thread preserves the old guarantee that
    # a dead node's workers exit within ~0.5s regardless.
    import threading

    def _watchdog():
        while True:
            time.sleep(0.5)
            if worker.raylet.closed:
                print("[worker] raylet connection closed; exiting",
                      file=sys.stderr, flush=True)
                os._exit(1)

    threading.Thread(target=_watchdog, daemon=True,
                     name="raylet-watchdog").start()

    # Serve normal-task execution on THIS (main) thread — the reference's
    # RunTaskExecutionLoop (core_worker.cc:2188). Some native libraries
    # (pyarrow submodule init) are unreliable on short-lived dispatch
    # threads; the main thread is always safe. Returns when the raylet
    # connection drops — the node is gone.
    # interpreter start → connected, registered and about to take the first
    # call, under the raylet's `worker_spawn`
    from ray_tpu._private import profiling

    profiling.record_completed_span(
        "startup", "worker_boot", _T_PROCESS, time.time() - _T_PROCESS,
        {"worker_id": worker.worker_id}, parent=worker.spawn_span)
    if prof is not None:
        # Perf diagnosis aid (RAY_TPU_WORKER_PROFILE=dir): cProfile the
        # main task loop — where normal-task execution happens — and dump
        # per-pid stats at exit (including SIGTERM, see _term).
        try:
            prof.runcall(worker.serve_task_loop)
        finally:
            _dump_profile()
        os._exit(1)
    worker.serve_task_loop()
    os._exit(1)


if __name__ == "__main__":
    sys.exit(main())
