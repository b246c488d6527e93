"""Start-up on one timeline: the ring's ids, parents and runs
(`_private/profiling.py`), the compile span split where the compile
happens (`parallel/compile_watch.py`), and one run's start-up from `init`
to the first step in `ray_tpu.timeline()` — driver, raylet and train
worker, parents resolving across the three — after a one-worker `fit()`
on the CPU. The cluster-level cases share ONE cluster and ONE `fit()`,
across the workers of a parallel run too."""
import os
import threading
import time

import pytest

from ray_tpu._private import profiling, telemetry

# every span of the start-up path, by the names the readers hold it to
STARTUP_SPANS = {
    "init", "gcs_start", "raylet_start", "chip_probe", "fit", "gang_start",
    "pg_wait", "worker_group_start", "backend_on_start", "worker_spawn",
    "worker_boot", "backend_up", "train_fn"}


@pytest.fixture
def ring():
    """The process's ring, emptied for one test and put back."""
    with profiling._lock:
        kept, dropped = list(profiling._events), profiling._dropped
    profiling.clear()
    yield profiling
    profiling.clear()
    with profiling._lock:
        profiling._events.extend(kept)
        profiling._dropped = dropped


def _by_name(events):
    return {ev["name"]: ev for ev in events}


# ------------------------------------------------------------- the ring

def test_a_span_carries_its_id_its_parent_and_its_run(ring):
    with ring.record_span("t", "outer", run="run-1"):
        assert ring.cause() == {"cause": ring.current()[0], "run": "run-1"}
        with ring.record_span("t", "inner", {"k": 1}):
            done = ring.record_completed_span("t", "done", 1.0, 2.0)
        ring.record_completed_span("t", "given", 1.0, 2.0, parent="n:1:9",
                                   run="other")
    assert ring.current() is None and ring.cause() is None
    spans = _by_name(ring.snapshot())
    prefix = f"{profiling._NODE}:{profiling._PID}:"
    ids = [ev["args"]["id"] for ev in spans.values()]
    assert all(i.startswith(prefix) for i in ids)
    assert len(set(ids)) == 4
    assert "parent" not in spans["outer"]["args"]
    assert spans["inner"]["args"]["parent"] == spans["outer"]["args"]["id"]
    assert spans["inner"]["args"]["k"] == 1
    assert spans["done"]["args"]["parent"] == spans["inner"]["args"]["id"]
    assert spans["done"]["args"]["id"] == done
    assert spans["given"]["args"]["parent"] == "n:1:9"
    assert {spans[n]["args"]["run"] for n in ("outer", "inner", "done")} \
        == {"run-1"}
    assert spans["given"]["args"]["run"] == "other"


def test_a_thread_starts_with_no_parent_and_takes_one_given(ring):
    def work(parent, run):
        with ring.record_span("t", "in_thread", parent=parent, run=run):
            pass

    with ring.record_span("t", "outer", run="r"):
        above = ring.current()
        for args in ((None, None), above):
            thread = threading.Thread(target=work, args=args)
            thread.start()
            thread.join()
    first, second = [ev for ev in ring.snapshot()
                     if ev["name"] == "in_thread"]
    assert "parent" not in first["args"] and "run" not in first["args"]
    assert second["args"]["parent"] == above[0]
    assert second["args"]["run"] == "r"


def test_an_id_taken_ahead_is_the_completed_spans(ring):
    ahead = ring.next_id()
    ring.record_completed_span("t", "child", 2.0, 1.0, parent=ahead)
    assert ring.record_completed_span("t", "late", 1.0, 3.0,
                                      span_id=ahead) == ahead
    spans = _by_name(ring.snapshot())
    assert spans["child"]["args"]["parent"] == spans["late"]["args"]["id"]


def test_a_full_ring_drops_counted_and_merge_keeps_one_of_each(
        ring, monkeypatch):
    import collections

    monkeypatch.setattr(profiling, "_events", collections.deque(maxlen=3))
    for i in range(5):
        ring.record_completed_span("t", f"s{i}", float(i), 1.0)
    assert ring.stats() == {"buffered": 3, "dropped": 2, "capacity": 3}
    events = ring.snapshot(with_drop_marker=True)
    assert events[-1]["ph"] == "M" and events[-1]["args"]["dropped"] == 2
    # a second path that brings the same rows again: spans by id, the
    # drop marker by name
    assert ring.merge(events + events) == events
    foreign = [dict(ev, pid=ev["pid"] + 1) for ev in events]
    assert len(ring.merge(events + foreign)) == 2 * len(events)
    ring.adopt(foreign[:1])
    assert ring.stats()["dropped"] == 3


def test_the_ring_off_records_nothing(ring, monkeypatch):
    monkeypatch.setattr(profiling, "_ENABLED", False)
    with ring.record_span("t", "off"):
        assert ring.current() is None
    assert ring.record_completed_span("t", "off", 1.0, 1.0) is None
    ring.adopt([{"name": "foreign"}])
    assert ring.snapshot() == []


def test_a_live_span_is_a_trace_annotation_once_jax_is_imported(
        ring, monkeypatch):
    import jax

    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(profiling, "_annotation", None)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with ring.record_span("t", "outer"):
        with ring.record_span("t", "inner"):
            pass
    assert seen == [("enter", "outer"), ("enter", "inner"),
                    ("exit", "inner"), ("exit", "outer")]
    monkeypatch.setattr(profiling, "_annotation", None)


def test_profiling_never_imports_jax():
    import subprocess
    import sys

    code = ("import sys\n"
            "from ray_tpu._private import profiling\n"
            "with profiling.record_span('t', 'x'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules\n"
            "assert profiling.snapshot()[0]['args']['id']\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


# ------------------------------------------------- the compile span split

@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compilation cache in a directory of the test's,
    taking every compile however short."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {name: getattr(jax.config, name) for name in names}
    for name, value in zip(names, (str(tmp_path), True, 0.0, -1)):
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    yield
    for name, value in was.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def _family(events, name):
    """(the one span `name`, its children by name)."""
    (parent,) = [ev for ev in events if ev["name"] == name]
    children = {}
    for ev in events:
        if ev["args"].get("parent") == parent["args"]["id"]:
            children.setdefault(ev["name"], []).append(ev)
    return parent, children


def test_a_miss_is_split_and_a_hit_yields_nothing(ring, persistent_cache):
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel import compile_watch

    def f(x):
        return jnp.tanh(x @ x.T).sum()

    x = jnp.ones((64, 64))
    cold = compile_watch.CompiledFunction(jax.jit(f), "split_cold")
    assert compile_watch._listening
    before = dict(compile_watch._cache_totals)
    misses = telemetry_counter("ray_tpu_compile_cache_misses_total",
                               "split_cold")
    ring.clear()
    cold(x)
    parent, children = _family(ring.snapshot(), "compile::split_cold")
    assert parent["cat"] == "compile"
    assert parent["args"]["persistent_cache"] == "miss"
    assert set(children) == {"trace", "lower", "backend_compile"}
    lo, hi = parent["ts"], parent["ts"] + parent["dur"]
    for (child,) in children.values():
        assert child["cat"] == "compile" and child["dur"] >= 0
        # each with its own start, inside the call (to the clocks' 1 ms)
        assert lo - 1000 <= child["ts"] <= child["ts"] + child["dur"] \
            <= hi + 1000
    assert children["trace"][0]["ts"] <= children["lower"][0]["ts"] \
        <= children["backend_compile"][0]["ts"]
    assert 0 <= parent["args"]["first_execute_s"] <= parent["dur"] / 1e6
    assert compile_watch._cache_totals["miss"] == before["miss"] + 1
    assert parent["args"]["cache_misses_total"] == before["miss"] + 1
    assert telemetry_counter("ray_tpu_compile_cache_misses_total",
                             "split_cold") == misses + 1

    ring.clear()
    cold(x)                                 # jit's own cache: a hit
    assert ring.snapshot() == []

    # the same program in a process-fresh jit: the persistent cache's
    jax.clear_caches()
    warm = compile_watch.CompiledFunction(jax.jit(f), "split_warm")
    ring.clear()
    warm(x)
    parent, children = _family(ring.snapshot(), "compile::split_warm")
    assert parent["args"]["persistent_cache"] == "hit"
    assert set(children) == {"trace", "lower", "backend_compile",
                             "cache_load"}
    (load,), (executable,) = (children["cache_load"],
                              children["backend_compile"])
    # the load is what `backend_compile` is on a hit
    assert executable["ts"] - 1000 <= load["ts"] and load["dur"] \
        <= executable["dur"] + 1000
    assert parent["args"]["cache_hits_total"] == before["hit"] + 1


def telemetry_counter(name, fn):
    from ray_tpu.util.metrics import registry_snapshot

    total = 0.0
    for metric in registry_snapshot():
        if metric["name"] == name:
            total += sum(row["value"] for row in metric["values"]
                         if row["tags"].get("fn") == fn)
    return total


def test_a_compile_outside_every_wrapper_is_one_span(ring,
                                                     persistent_cache):
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel import compile_watch

    compile_watch._listen()
    with ring.record_span("t", "around"):
        jax.jit(lambda x: jnp.cos(x) * 3.0)(jnp.ones((32,)))
    spans = ring.snapshot()
    around, children = _family(spans, "around")
    assert set(children) == {"backend_compile"}
    assert children["backend_compile"][-1]["args"]["persistent_cache"] \
        == "miss"
    assert not [ev for ev in spans if ev["name"].startswith("compile::")]


def test_telemetry_off_registers_nothing_and_records_nothing(
        ring, monkeypatch):
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel import compile_watch

    registered = []
    monkeypatch.setattr(telemetry, "ENABLED", False)
    monkeypatch.setattr(compile_watch, "_listening", False)
    monkeypatch.setattr(compile_watch, "_calls", threading.local())
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        registered.append)
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        registered.append)
    compile_watch.configure_compile_cache()
    fn = compile_watch.CompiledFunction(jax.jit(lambda x: x * 5.0 - 1.0),
                                        "split_off")
    assert not hasattr(compile_watch._calls, "booked")
    fn(jnp.ones((16,)))
    assert registered == [] and not compile_watch._listening
    assert not hasattr(compile_watch._calls, "booked")
    assert not [ev for ev in ring.snapshot()
                if ev["name"].startswith("compile::")]


# --------------------------------------- one cluster, one fit(), one run

def _loop(config):
    import jax
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu.air import session
    from ray_tpu.parallel import compile_watch
    from ray_tpu.util import tracing

    step = compile_watch.CompiledFunction(
        jax.jit(lambda x: (x @ x.T).sum()), "train_step")
    x = jnp.ones((32, 32))
    for _ in range(3):
        session.report({"loss": float(step(x)),
                        "tracing": tracing.is_enabled()})
    # what the benchmark's readers see: the merged timeline, asked from
    # inside the train worker while the run's own spans are still open
    inside = ray_tpu.timeline()
    session.report({"tracing": tracing.is_enabled(),
                    "inside": sorted({ev["name"] for ev in inside
                                      if ev.get("cat") in ("startup",
                                                           "compile")}),
                    "step_1": [ev for ev in inside
                               if ev["name"] == "step::1"],
                    "spans_recorded": len(tracing.local_spans())})


def _one_fit():
    """One local cluster with its chip probe ON (the probe's span is on
    the start-up path) and one one-worker `fit()` on it; what the cases
    below look at, as plain data."""
    import ray_tpu
    from ray_tpu._private import api, tpu_probe
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train.trainer import JaxTrainer
    from ray_tpu.util import tracing

    was_env = os.environ.get("RAY_TPU_DETECT_CHIPS")
    was_probe = list(tpu_probe._chip_probe_cache)
    os.environ["RAY_TPU_DETECT_CHIPS"] = "1"
    del tpu_probe._chip_probe_cache[:]
    t_start = int(time.time() * 1e6)
    ray_tpu.init(num_cpus=4)
    try:
        result = JaxTrainer(
            _loop, scaling_config=ScalingConfig(num_workers=1)).fit()
        assert result.error is None, result.error
        return {
            "history": result.metrics_history, "driver_pid": os.getpid(),
            "timeline": [ev for ev in ray_tpu.timeline()
                         if ev["ts"] >= t_start],
            # the raylet's own answer, as `timeline()` is handed it
            "raylet": [ev for ev in
                       api._global_node.raylet.rpc_profile_events(None)
                       if ev["ts"] >= t_start],
            "tracing": [tracing.is_enabled(), len(tracing.local_spans())],
        }
    finally:
        ray_tpu.shutdown()
        tpu_probe._chip_probe_cache[:] = was_probe
        if was_env is None:
            del os.environ["RAY_TPU_DETECT_CHIPS"]
        else:
            os.environ["RAY_TPU_DETECT_CHIPS"] = was_env


@pytest.fixture(scope="module")
def run(once_a_run):
    return once_a_run("startup_spans_fit", _one_fit)


def test_the_timeline_after_fit_holds_the_start_up_path(run):
    spans = [ev for ev in run["timeline"] if ev.get("ph") == "X"]
    names = {ev["name"] for ev in spans if ev["cat"] == "startup"}
    assert STARTUP_SPANS <= names, STARTUP_SPANS - names
    assert {"compile::train_step", "trace", "lower", "backend_compile"} \
        <= {ev["name"] for ev in spans if ev["cat"] == "compile"}
    # from three processes at least: driver (with its raylet), the gang's
    # worker, and the pool's
    assert len({ev["pid"] for ev in spans if ev["cat"] == "startup"}) >= 3


def test_every_parent_resolves_across_the_processes(run):
    spans = [ev for ev in run["timeline"] if ev.get("ph") == "X"]
    by_id = {ev["args"]["id"]: ev for ev in spans}
    assert len(by_id) == len(spans)
    for ev in spans:
        parent = ev["args"].get("parent")
        assert parent is None or parent in by_id, (ev["name"], parent)

    def parent_of(ev):
        return by_id.get(ev["args"].get("parent"), {"name": None,
                                                    "pid": None})

    def one(name, **where):
        (ev,) = [ev for ev in spans if ev["name"] == name and all(
            ev["args"].get(k) == v for k, v in where.items())]
        return ev

    fit = one("fit")
    run_id = fit["args"]["run"]
    for name, parent in (
            ("gcs_start", "init"), ("raylet_start", "init"),
            ("chip_probe", "raylet_start"), ("gang_start", "fit"),
            ("pg_wait", "gang_start"),
            ("worker_group_start", "gang_start"),
            ("backend_on_start", "gang_start"),
            ("backend_up", "train_fn"),
            ("compile::train_step", "train_fn")):
        assert parent_of(one(name))["name"] == parent, name
    assert "parent" not in one("init")["args"] and "parent" not in \
        fit["args"]
    # the gang's worker: spawned for the actor `worker_group_start` made,
    # booted under that spawn, and its train function started by a call
    # that `fit` made, each in another process than its parent
    spawn = one("worker_spawn", run=run_id)
    assert parent_of(spawn)["name"] == "worker_group_start"
    train_fn = one("train_fn")
    (boot,) = [ev for ev in spans if ev["name"] == "worker_boot"
               and ev["pid"] == train_fn["pid"]]
    assert parent_of(boot) is spawn and boot["pid"] != spawn["pid"]
    call = parent_of(train_fn)
    assert call["cat"] == "actor_task" and call["pid"] == train_fn["pid"]
    assert parent_of(call) is fit and fit["pid"] != call["pid"]
    # the run's id came down the same way
    for name in ("gang_start", "backend_on_start", "train_fn",
                 "backend_up", "compile::train_step", "trace"):
        assert one(name)["args"]["run"] == run_id, name
    assert train_fn["args"]["rank"] == 0
    assert one("chip_probe")["args"]["chips"] == 0
    assert one("backend_up")["args"]["backend"] is None   # pinned to cpu


def test_the_step_context_opens_after_the_backend_is_up(run):
    spans = _by_name(ev for ev in run["timeline"] if ev.get("ph") == "X")
    (step_1,) = run["history"][-1]["step_1"]
    up = spans["backend_up"]
    assert step_1["pid"] == up["pid"]
    assert step_1["ts"] >= up["ts"] + up["dur"] >= spans["train_fn"]["ts"]


def test_the_worker_sees_the_run_while_it_runs(run):
    inside = set(run["history"][-1]["inside"])
    # its own live spans (`fit`, `train_fn`) are not recorded yet
    assert STARTUP_SPANS - {"fit", "train_fn"} <= inside
    assert {"compile::train_step", "backend_compile"} <= inside


def test_util_tracing_stayed_off(run):
    assert run["tracing"] == [False, 0]
    history = run["history"]
    assert [row["tracing"] for row in history] == [False] * 4
    assert history[-1]["spans_recorded"] == 0


def test_the_raylet_answers_for_itself_and_once(run):
    events = [ev for ev in run["raylet"] if ev.get("ph") == "X"]
    # what only the raylet's own process recorded is there ...
    names = {ev["name"] for ev in events
             if ev["pid"] == run["driver_pid"]}
    assert {"chip_probe", "worker_spawn", "raylet_start"} <= names
    # ... once, though the driver's worker answers with the same ring
    keys = [(ev["node"], ev["pid"], ev["args"]["id"]) for ev in events]
    assert len(keys) == len(set(keys))
    merged = [(ev["node"], ev["pid"], ev["args"]["id"])
              for ev in run["timeline"] if ev.get("ph") == "X"]
    assert len(merged) == len(set(merged))
