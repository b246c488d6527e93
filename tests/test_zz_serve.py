"""Production Serve plane (late-alphabet; sequenced after the tier-1
timeout horizon by design — keep each test fast).

Covers the PR 6 tentpole at unit + E2E scale: config validation at
construction (named ``ServeConfigError``), shape-aware batching against
a recompile-count oracle (the compile_watch classification the batcher
shares with the training step), ``@serve.batch`` fan-out hardening
(per-caller exception clones, call-shape rejection), router
power-of-two-choices distribution + bounded-queue admission control
(typed ``ServeOverloadedError`` + ``REQUEST_SHED``), autoscale
hysteresis (a scale proposal must SUSTAIN for the configured delay),
drain semantics (``ReplicaDrainingError`` → transparent re-dispatch),
zero-copy same-node weight sharing over the shm store, and a seeded
``kill_actor`` replica death → sub-second failover with zero lost
accepted requests (the PR 5 fault DSL riding the ``serve-<dep>``
process tags replicas register at construction).
"""
import os
import threading
import time

import numpy as np
import pytest

pytestmark = [pytest.mark.serve]


# ------------------------------------------------------------- pure units

def test_config_validation_named_errors():
    """Bad values fail at CONSTRUCTION with a named error, not as a deep
    controller-side failure three actors later."""
    from ray_tpu.exceptions import ServeConfigError
    from ray_tpu.serve.config import AutoscalingConfig, DeploymentConfig

    for bad in (dict(num_replicas=0), dict(num_replicas=-3),
                dict(max_ongoing_requests=0),
                dict(max_queued_requests=-1),
                dict(graceful_shutdown_timeout_s=-0.5),
                dict(health_check_period_s=-1),
                dict(health_check_timeout_s=-2)):
        with pytest.raises(ServeConfigError):
            DeploymentConfig(**bad)
    for bad in (dict(min_replicas=3, max_replicas=2),
                dict(min_replicas=-1),
                dict(max_replicas=0),
                dict(target_ongoing_requests=0),
                dict(target_ongoing_requests=-1.0),
                dict(upscale_delay_s=-0.1),
                dict(downscale_delay_s=-0.1),
                dict(metrics_interval_s=-1),
                dict(smoothing_factor=0)):
        with pytest.raises(ServeConfigError):
            AutoscalingConfig(**bad)
    # subclasses ValueError: generic config-validation handlers keep
    # working
    with pytest.raises(ValueError):
        DeploymentConfig(num_replicas=0)
    # defaults are valid
    DeploymentConfig()
    AutoscalingConfig()


def test_options_validates_at_call_site():
    """.options(...) round-trips through __post_init__, so the operator
    sees the error where they wrote the value, pre-deploy."""
    import ray_tpu.serve as serve
    from ray_tpu.exceptions import ServeConfigError

    @serve.deployment
    def f(x):
        return x

    with pytest.raises(ServeConfigError):
        f.options(num_replicas=0)
    with pytest.raises(ServeConfigError):
        f.options(max_ongoing_requests=-1)
    with pytest.raises(ServeConfigError):
        f.options(autoscaling_config={"min_replicas": 5, "max_replicas": 2})
    # valid options still produce an immutable copy
    g = f.options(num_replicas=3)
    assert g.config.num_replicas == 3 and f.config.num_replicas == 1

    # user_config is OPAQUE: .options() and to_dict() must ship the
    # operator's object itself, not an asdict()-mangled deep copy
    class MyCfg:
        lr = 0.1

    cfg_obj = MyCfg()
    h = f.options(user_config=cfg_obj)
    assert h.config.user_config is cfg_obj
    assert h.config.to_dict()["user_config"] is cfg_obj


def test_autoscale_desired_replicas_math():
    from ray_tpu.serve.config import AutoscalingConfig

    ac = AutoscalingConfig(min_replicas=1, max_replicas=8,
                           target_ongoing_requests=2.0)
    # per-replica load 4 = 2x target → double
    assert ac.desired_replicas(2, 8.0) == 4
    # at target: hold
    assert ac.desired_replicas(4, 8.0) == 4
    # clamp to bounds
    assert ac.desired_replicas(4, 1000.0) == 8
    assert ac.desired_replicas(4, 0.0) == 1
    # no running replicas: come up at the floor
    assert ac.desired_replicas(0, 0.0) == 1


def test_autoscale_hysteresis_sustain_before_scale():
    """A scale proposal only moves the target after it SUSTAINS for the
    configured up/downscale delay — blips don't scale."""
    from ray_tpu.serve._private.controller import RUNNING, _DeploymentState
    from ray_tpu.serve._private.long_poll import LongPollHost

    spec = {"name": "m", "user_callable": object, "config": {
        "autoscaling_config": {
            "min_replicas": 1, "max_replicas": 4,
            "target_ongoing_requests": 1.0,
            "upscale_delay_s": 0.15, "downscale_delay_s": 0.15,
            "metrics_interval_s": 3600.0}}}
    ds = _DeploymentState("app#m", spec, LongPollHost())

    class _R:
        state = RUNNING
        num_ongoing = 0.0
        warned = False
        drain_requested = False

    ds.replicas = [_R()]
    ds._last_metrics_poll = time.monotonic()   # suppress replica polling
    assert ds.target_num == 1

    # demand for 4 replicas appears (handle-side router metric)
    ds.handle_metrics["r1"] = (6.0, time.monotonic())
    ds._autoscale()
    assert ds.target_num == 1, "scaled on an unsustained proposal"
    time.sleep(0.2)
    ds.handle_metrics["r1"] = (6.0, time.monotonic())
    ds._autoscale()
    assert ds.target_num == 4, "sustained upscale proposal did not apply"

    # demand vanishes: downscale also waits out its delay
    ds.handle_metrics["r1"] = (0.0, time.monotonic())
    ds._autoscale()
    assert ds.target_num == 4
    time.sleep(0.2)
    ds.handle_metrics["r1"] = (0.0, time.monotonic())
    ds._autoscale()
    assert ds.target_num == 1

    # a proposal that CHANGES resets the clock (4 → idle blip → 4)
    ds.handle_metrics["r1"] = (6.0, time.monotonic())
    ds._autoscale()
    ds.handle_metrics["r1"] = (0.0, time.monotonic())
    ds._autoscale()                      # different proposal: clock resets
    ds.handle_metrics["r1"] = (6.0, time.monotonic())
    ds._autoscale()
    assert ds.target_num == 1, "flapping proposals must not scale"


def test_bucket_sizes_and_padding(monkeypatch):
    from ray_tpu.serve.batching import _Batcher, default_bucket_sizes

    assert default_bucket_sizes(8) == (1, 2, 4, 8)
    assert default_bucket_sizes(6) == (1, 2, 4, 6)   # max always included
    assert default_bucket_sizes(1) == (1,)

    b = _Batcher(lambda xs: xs, 6, 0.01)
    assert b.bucket_sizes == (1, 2, 4, 6)
    items, pad = b._pad_to_bucket([10, 20, 30])
    # padded by replicating the LAST REAL item, never a sentinel
    assert items == [10, 20, 30, 30] and pad == 1
    items, pad = b._pad_to_bucket([5])
    assert items == [5] and pad == 0
    items, pad = b._pad_to_bucket([1, 2, 3, 4, 5])
    assert len(items) == 6 and pad == 1

    # explicit buckets are honored (and max_batch_size appended if absent)
    b2 = _Batcher(lambda xs: xs, 8, 0.01, bucket_sizes=(3, 5))
    assert b2.bucket_sizes == (3, 5, 8)

    # a bucket above max_batch_size would pad batches past the bound the
    # wrapped function was sized for: rejected at decoration time
    from ray_tpu.serve.batching import batch

    with pytest.raises(ValueError, match="batch_size_buckets"):
        batch(max_batch_size=8, batch_size_buckets=[16])(lambda xs: xs)
    with pytest.raises(ValueError, match="batch_size_buckets"):
        batch(max_batch_size=8, batch_size_buckets=[0, 4])(lambda xs: xs)
    with pytest.raises(ValueError, match="max_batch_size"):
        batch(max_batch_size=0)(lambda xs: xs)

    # kill switch restores the legacy pad-free batcher
    monkeypatch.setenv("RAY_TPU_SERVE_SHAPE_BUCKETS", "0")
    b3 = _Batcher(lambda xs: xs, 8, 0.01)
    assert b3.bucket_sizes is None
    items, pad = b3._pad_to_bucket([1, 2, 3])
    assert items == [1, 2, 3] and pad == 0


def test_shape_bucketing_recompile_oracle(monkeypatch):
    """THE shape-aware acceptance proof at unit scale: a mixed
    batch-size traffic stream through the bucketing batcher converges to
    ZERO new pjit-cache misses once each bucket has compiled (4 buckets
    → 4 misses, flat afterwards), while the legacy
    ``RAY_TPU_SERVE_SHAPE_BUCKETS=0`` path keeps recompiling — one miss
    per distinct raw batch size, still climbing deep into the stream."""
    from ray_tpu.serve.batching import _Batcher
    from ray_tpu.util.metrics import registry_snapshot

    def misses(name):
        fam = next((m for m in registry_snapshot()
                    if m["name"] == "ray_tpu_pjit_cache_total"), None)
        if fam is None:
            return 0.0
        return sum(v["value"] for v in fam["values"]
                   if v["tags"].get("fn") == f"serve_batch::{name}"
                   and v["tags"].get("result") == "miss")

    traffic = [3, 1, 5, 2, 7, 4, 8, 6, 3, 5, 7, 1, 6, 2, 8, 4]

    def replay(name):
        b = _Batcher(lambda xs: [x.sum() for x in xs], 8, 0.01, name=name)
        assert misses(name) == 0.0
        history = []
        for n in traffic:
            items, _ = b._pad_to_bucket([np.zeros((4, 2))] * n)
            b._fn(items)           # classified exactly like the loop does
            history.append(misses(name))
        return history

    bucketed = replay("zz_oracle_bucketed")
    # warmup: sizes 3,1,5,2 touch buckets 4,1,8,2 — all four compiled
    assert bucketed[3] == 4.0
    # converged: no new compile for the rest of the stream
    assert bucketed[-1] == 4.0, f"bucketed batcher kept recompiling: " \
                                f"{bucketed}"

    monkeypatch.setenv("RAY_TPU_SERVE_SHAPE_BUCKETS", "0")
    legacy = replay("zz_oracle_legacy")
    # every distinct raw size is a fresh signature: 8 sizes → 8 misses,
    # the 8th landing at index 7 — recompiling long after the bucketed
    # path went flat
    assert legacy[-1] == 8.0
    assert legacy[7] > bucketed[7]


def test_batch_per_caller_exception_isolation():
    """Each caller of a failed batch gets ITS OWN exception object — one
    caller's handler mutating __cause__/__context__ must not corrupt
    what the batch's other callers observe."""
    from ray_tpu.serve.batching import batch

    @batch(max_batch_size=4, batch_wait_timeout_s=0.1)
    def boom(items):
        raise ValueError("batch exploded")

    errs = [None] * 3
    barrier = threading.Barrier(3)

    def call(i):
        barrier.wait()
        try:
            boom(i)
        except ValueError as e:
            errs[i] = e

    threads = [threading.Thread(target=call, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(isinstance(e, ValueError) for e in errs), errs
    assert len({id(e) for e in errs}) == 3, "callers shared one exception"
    # one caller re-raising `from` another error rewrites __cause__ —
    # the others must not see it
    cause = RuntimeError("caller 0's local context")
    errs[0].__cause__ = cause
    assert errs[1].__cause__ is not cause
    assert errs[2].__cause__ is not cause
    # the clones still agree on what failed
    assert {str(e) for e in errs} == {"batch exploded"}


def test_batch_call_shape_rejection():
    """kwargs / wrong arity get one clear message, not a bare TypeError
    arity mismatch from deep inside the batcher — on both the free-
    function and bound-method paths."""
    from ray_tpu.serve.batching import batch

    @batch
    def f(items):
        return items

    with pytest.raises(TypeError, match="keyword"):
        f(1, mode="fast")
    with pytest.raises(TypeError, match="exactly one request"):
        f(1, 2)
    with pytest.raises(TypeError, match="exactly one request"):
        f()

    class M:
        @batch
        def g(self, items):
            return [x + 1 for x in items]

    m = M()
    with pytest.raises(TypeError, match="keyword"):
        m.g(1, extra=2)
    with pytest.raises(TypeError, match="exactly one request"):
        m.g()
    assert m.g(41) == 42   # the good path still works after rejections


def test_batch_wrapper_pickle_roundtrip():
    """The wrapper ships inside deployment specs (a class attribute of
    the user class): it must cloudpickle with its live batcher thread
    and creation lock stripped, and rebuild them lazily on arrival."""
    import cloudpickle

    from ray_tpu.serve.batching import batch

    @batch(max_batch_size=4, batch_wait_timeout_s=0.01)
    def double_all(items):
        return [x * 2 for x in items]

    assert double_all(21) == 42      # live batcher thread now exists
    w2 = cloudpickle.loads(cloudpickle.dumps(double_all))
    assert w2(5) == 10
    assert w2._batch_size_buckets == double_all._batch_size_buckets


def test_replica_drain_refuses_new_work():
    """A draining replica rejects new requests with the typed error the
    handle layer re-dispatches on — scale-down must not lose accepted
    requests that raced the routing update."""
    from ray_tpu.exceptions import ReplicaDrainingError
    from ray_tpu.serve._private.replica import ReplicaActor

    class M:
        def __call__(self, x):
            return x + 1

    r = ReplicaActor("zzapp#m", "zzapp#m#abc", M, (), {})
    assert r.handle_request("__call__", (1,), {}) == 2
    assert r.prepare_for_shutdown(timeout_s=0.2) is True
    with pytest.raises(ReplicaDrainingError):
        r.handle_request("__call__", (1,), {})
    # draining replicas report their residual work to the autoscaler
    assert r.get_metrics()["num_ongoing_requests"] == 0


# ------------------------------------------------------------ runtime E2E

def test_router_distribution_admission_and_summary(ray_start_regular):
    """p2c routing spreads load across replicas; admission control sheds
    (typed error + retry-after + REQUEST_SHED event) instead of queueing
    without bound; the state API folds it all into one rollup."""
    import ray_tpu.serve as serve
    from ray_tpu._private import events
    from ray_tpu.exceptions import ServeOverloadedError

    @serve.deployment(num_replicas=2, max_ongoing_requests=2,
                      max_queued_requests=4)
    class Who:
        def __call__(self, _):
            import os as _os

            return _os.getpid()

    try:
        serve.start(http_options={"host": "127.0.0.1", "port": 0})     # a port of its own
        h = serve.run(Who.bind(), name="zzwho", route_prefix=None)
        pids = {h.remote(i).result(timeout_s=10) for i in range(16)}
        assert len(pids) == 2, f"p2c never reached one replica: {pids}"

        @serve.deployment(num_replicas=1, max_ongoing_requests=1,
                          max_queued_requests=0)
        class Slow:
            def __call__(self, _):
                time.sleep(1.0)
                return "done"

        h2 = serve.run(Slow.bind(), name="zzslow", route_prefix=None)
        r1 = h2.remote(0)            # occupies the only slot
        time.sleep(0.2)
        with pytest.raises(ServeOverloadedError) as ei:
            h2.remote(1)             # saturated + zero queue → shed NOW
        assert ei.value.retry_after_s > 0
        assert "zzslow" in str(ei.value)
        assert any(e["kind"] == "REQUEST_SHED"
                   and e.get("deployment") == "zzslow#Slow"
                   for e in events.snapshot())
        assert r1.result(timeout_s=10) == "done"   # the accepted one runs

        from ray_tpu.experimental.state.api import summarize_serve

        s = summarize_serve()
        assert s["applications"]["zzwho"]["status"] == "RUNNING"
        row = s["requests"]["zzwho#Who"]
        assert row["ok"] >= 16 and row["mean_latency_s"] > 0
        assert s["requests"]["zzslow#Slow"]["shed"] >= 1
        assert any(e["kind"] == "REQUEST_SHED" for e in s["events"])
    finally:
        serve.shutdown()


def test_drain_redispatch_no_lost_requests(ray_start_regular):
    """A request that lands on a draining replica is transparently
    re-dispatched to a survivor. Regression: ReplicaDrainingError is a
    RayError, so serialize_error ships it UNWRAPPED and ray_tpu.get
    re-raises the raw type — a handler matching only the TaskError
    wrapper never fires and the caller sees the drain error (a lost
    accepted request)."""
    import ray_tpu
    import ray_tpu.serve as serve

    @serve.deployment(num_replicas=2, max_ongoing_requests=4)
    class Two:
        def __call__(self, x):
            return x + 1

    try:
        serve.start(http_options={"host": "127.0.0.1", "port": 0})     # a port of its own
        h = serve.run(Two.bind(), name="zzdrain", route_prefix=None)
        h.remote(0).result(timeout_s=10)   # force router creation
        from ray_tpu.serve.handle import _get_router

        router = _get_router("zzdrain#Two")
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and router.num_replicas() < 2:
            time.sleep(0.05)
        # drain one replica BEHIND the controller's back: the router
        # keeps routing to it, so requests race the (never-coming)
        # broadcast — exactly the scale-down window
        rid = next(iter(router._replicas))
        victim = ray_tpu.get_actor(f"SERVE_REPLICA::{rid}",
                                   namespace="serve")
        assert ray_tpu.get(victim.prepare_for_shutdown.remote(0.1),
                           timeout=10)
        responses = [h.remote(i) for i in range(10)]
        results = [r.result(timeout_s=15) for r in responses]
        assert results == [i + 1 for i in range(10)], \
            "drain lost or corrupted accepted requests"
        failovers = sum(r.num_failovers for r in responses)
        assert failovers >= 1, "no request ever hit the drainer?"
        # the first rejection evicted the drainer from selection
        assert rid not in router._replicas
        # repeat result() replays the settled value without re-running
        # the request (metrics/retries are once per request)
        assert responses[0].result() == 1
    finally:
        serve.shutdown()


def test_shared_weights_zero_copy_same_node(ray_start_regular):
    """N same-node replicas of one model cost ONE host copy: the first
    loader publishes through the shm store's put_ephemeral path, later
    replicas map the sealed segment zero-copy (read-only views) and
    never run their loader."""
    ray = ray_start_regular

    class Replica:
        def load(self, marker):
            import numpy as _np

            import ray_tpu.serve as serve

            calls = []

            def loader():
                calls.append(1)
                return {"w": _np.arange(8, dtype=_np.float32) * marker,
                        "meta": f"from-{marker}"}

            v = serve.shared_weights("zzserve:wtest", loader)
            return {"loader_ran": len(calls), "w": v["w"].tolist(),
                    "writable": bool(v["w"].flags.writeable),
                    "meta": v["meta"]}

        def release(self):
            import ray_tpu.serve as serve

            return serve.release_shared_weights("zzserve:wtest",
                                                delete=True)

    a = ray.remote(Replica).options(num_cpus=0).remote()
    b = ray.remote(Replica).options(num_cpus=0).remote()
    first = ray.get(a.load.remote(1))
    second = ray.get(b.load.remote(999))   # poison loader: must not run
    assert first["loader_ran"] == 1
    assert second["loader_ran"] == 0, "second replica re-ran the loader"
    assert second["w"] == first["w"] == list(range(8))
    assert second["meta"] == "from-1"
    # zero-copy views over the shared segment are read-only
    assert first["writable"] is False and second["writable"] is False
    assert ray.get(a.release.remote()) is True


@pytest.mark.chaos
@pytest.mark.fault_injection
def test_seeded_replica_kill_subsecond_failover():
    """Deterministic chaos: every replica process of the deployment is
    killed (os._exit via the seeded ``kill_actor`` DSL) at its 3rd
    ``handle_request`` dispatch — so kills keep landing as the
    controller back-fills capacity. Every accepted request must still
    succeed (zero lost, all correct), recovery stays bounded even when
    BOTH replicas die back-to-back (full capacity rebuild), and the
    death feed's traffic-shed latency — the millisecond-failover claim
    — is then measured directly on a live replica kill."""
    import ray_tpu

    os.environ["RAY_TPU_FAULT_SEED"] = "11"
    os.environ["RAY_TPU_FAULT_SCHEDULE"] = \
        "kill_actor:serve-zzchaos-Victim.handle_request:#3"
    try:
        ray_tpu.init(num_cpus=4, object_store_memory=64 * 1024 * 1024)
        import ray_tpu.serve as serve
        from ray_tpu.util.metrics import registry_snapshot

        @serve.deployment(num_replicas=2, max_ongoing_requests=4)
        class Victim:
            def __call__(self, x):
                return x * 3

        try:
            serve.start(http_options={"host": "127.0.0.1", "port": 0})     # a port of its own
            h = serve.run(Victim.bind(), name="zzchaos", route_prefix=None)
            results, durations = [], []
            for i in range(12):
                t0 = time.monotonic()
                results.append(h.remote(i).result(timeout_s=20))
                durations.append(time.monotonic() - t0)
            # zero lost accepted requests, all correct
            assert results == [i * 3 for i in range(12)]
            # at least one request rode a killed replica and failed over
            fam = next((m for m in registry_snapshot()
                        if m["name"] == "ray_tpu_serve_failovers_total"),
                       None)
            failovers = sum(
                v["value"] for v in (fam["values"] if fam else [])
                if v["tags"].get("deployment") == "zzchaos#Victim")
            assert failovers >= 1, "schedule never landed a kill"
            # unaffected requests stay fast; even a request that rode a
            # kill cascade into a from-zero capacity rebuild (both
            # replicas dead → controller starts a replacement) recovers
            # within a bounded window, not an op-timeout
            durations.sort()
            # typical median ~60-120 ms; headroom for shared-cgroup
            # stalls (the precise numbers live in BENCH_r07.json)
            assert durations[len(durations) // 2] < 0.6, durations
            assert durations[-1] < 8.0, \
                f"recovery unbounded: {durations[-1]:.3f}s"

            # --- direct millisecond-failover measurement -------------
            # Kill a live replica and time the GCS-death-feed path:
            # the router must flag it (new traffic sheds, in-flight
            # re-dispatches) in well under a second — this, not the
            # capacity rebuild above, is the failover latency claim.
            from ray_tpu.serve.handle import _get_router

            router = _get_router("zzchaos#Victim")
            assert router.has_death_watch(), \
                "router degraded to long-poll-only updates"
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and not router.num_replicas():
                time.sleep(0.05)       # wait out the rebuild from the loop
            rid = next(iter(router._replicas))
            victim = ray_tpu.get_actor(f"SERVE_REPLICA::{rid}",
                                       namespace="serve")
            t0 = time.monotonic()
            ray_tpu.kill(victim)
            while not router.replica_dead(rid):
                assert time.monotonic() - t0 < 5.0, \
                    "death feed never reached the router"
                time.sleep(0.002)
            shed_latency = time.monotonic() - t0
            # typically tens of ms (death feed publish latency); the
            # bound is generous for cgroup stalls but still 10x under
            # the health-check period this path exists to beat
            assert shed_latency < 1.5, \
                f"death→shed took {shed_latency:.3f}s"
            # traffic still flows (survivor + controller back-fill)
            assert h.remote(100).result(timeout_s=20) == 300
        finally:
            serve.shutdown()
    finally:
        os.environ.pop("RAY_TPU_FAULT_SEED", None)
        os.environ.pop("RAY_TPU_FAULT_SCHEDULE", None)
        ray_tpu.shutdown()
