"""`ray_tpu.shutdown()` means "no child of mine is alive": when it returns,
every process the raylet ever spawned is dead AND reaped — one that cannot
answer SIGTERM (as a process deep in the TPU runtime's teardown cannot), one
`kill()` sent SIGKILL a moment before, and one a refill thread was starting
while `stop()` ran. The house rule "after every chip run nothing of
`ray_tpu` is left running" (ROADMAP.md) rests on this.
"""
import os
import signal
import subprocess
import threading
import time

import pytest

import ray_tpu
from ray_tpu._private import api


@pytest.fixture
def spawned(monkeypatch):
    """The pid of every process started while the test runs."""
    pids = []

    class Recording(subprocess.Popen):
        started = threading.Event()
        hold = None     # an Event: `Popen` returns only once it is set

        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            pids.append(self.pid)
            self.started.set()
            if self.hold is not None:
                self.hold.wait(30)

    monkeypatch.setattr(subprocess, "Popen", Recording)
    yield pids, Recording
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    for pid in pids:    # a failed test leaves nothing behind either
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


def _assert_gone_and_reaped(pids):
    assert pids
    for pid in pids:
        assert not os.path.exists(f"/proc/{pid}"), (
            pid, open(f"/proc/{pid}/stat").read().split()[2])
        with pytest.raises(ProcessLookupError):
            os.kill(pid, 0)


@ray_tpu.remote
class _Actor:
    def pid(self):
        return os.getpid()


@pytest.mark.parametrize("how", ["stopped", "killed_just_before"])
def test_shutdown_returns_with_every_worker_reaped(spawned, how):
    pids, _ = spawned
    ray_tpu.init(num_cpus=2)
    actor = _Actor.remote()
    pid = ray_tpu.get(actor.pid.remote())
    assert pid in pids
    if how == "stopped":
        # SIGTERM stays pending on a stopped process: only SIGKILL ends it
        os.kill(pid, signal.SIGSTOP)
    else:
        # the actor's connection drops before its process is gone, and with
        # it the raylet's handle: `stop()` must know the process all the same
        ray_tpu.kill(actor)
    ray_tpu.shutdown()
    _assert_gone_and_reaped(pids)


def test_a_refill_racing_stop_leaves_no_worker(spawned):
    pids, recording = spawned
    ray_tpu.init(num_cpus=2)
    raylet = api._global_node.raylet
    ray_tpu.get(_Actor.remote().pid.remote())
    # a refill whose Popen is in flight while `stop()` runs: its child is
    # born after `stop()` set `_stopped`, whatever list `stop()` took before
    recording.hold = threading.Event()
    recording.started.clear()
    with raylet._lock:
        raylet._prestart_target = len(raylet._idle) + raylet._spawning + 1
    before = len(pids)
    raylet._maybe_refill()
    assert recording.started.wait(30)
    down = threading.Thread(target=ray_tpu.shutdown)
    down.start()
    deadline = time.time() + 30
    while not raylet._stopped and time.time() < deadline:
        time.sleep(0.01)
    time.sleep(0.2)     # `stop()` is past the point where it lists children
    recording.hold.set()
    down.join(120)
    assert not down.is_alive()
    assert len(pids) == before + 1
    _assert_gone_and_reaped(pids)
