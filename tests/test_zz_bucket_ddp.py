"""Async collective handles + bucketed DDP gradient sync (late-alphabet
on purpose: the gang tests here cost seconds each).

Covers the tentpole's two halves and their acceptance criteria:

- pure units: deterministic bucket planning + pack/unpack round trip,
  and the step-anatomy interval-union fix (a background bucket that
  completes inside another bucket's exposed wait window must not be
  double-counted);
- async handle semantics on a live 2-rank group: wait/poll/result,
  bitwise equality with the sync path, submission-order preservation
  across mixed sync/async call sites, out-of-order waits;
- the determinism contract: bucketed-on vs RAY_TPU_TRAIN_BUCKET_DDP=0
  produce rank-byte-identical synced grads AND final params per seed
  at world 2 (pairwise IEEE adds are commutative, so bucket boundaries
  cannot change results);
- composition: the int8 quantized wire (PR 8) applies per bucket
  unchanged (rank-identical, error inside the documented bound);
- chaos: a member killed with bucketed allreduces in flight surfaces
  as CollectiveGroupError from handle.wait() within the poison-latency
  bound (queued handles too, no serialized op timeouts), leaving zero
  stranded shm segments; a seeded dropped frame surfaces as a timeout,
  never a hang;
- cluster acceptance: a 2-worker gang on a REAL make_ddp_train_step loop
  (jitted grad step -> ddp.sync_gradients -> jitted apply) yields a
  summarize_steps() report with comm_hidden > 0 and
  overlap_fraction > 0, and both ranks end byte-identical.
"""
import os
import time

import numpy as np
import pytest

GROUP = "zzbd"


# ------------------------------------------------------------------- units


def test_bucket_plan_deterministic_and_size_targeted():
    from ray_tpu.parallel import sharding as sh

    tree = {
        "w1": np.zeros((100, 100), np.float32),     # 40 KB
        "b1": np.zeros(100, np.float32),            # 400 B
        "w2": np.zeros((50, 100), np.float32),      # 20 KB
        "ints": np.zeros(64, np.int64),             # distinct dtype
        "scalar": np.float32(1.0),
    }
    leaves, treedef = sh.flatten_tree(tree)
    plan = sh.plan_buckets(leaves, 24 * 1024)
    assert plan == sh.plan_buckets(leaves, 24 * 1024)   # deterministic
    # dtype purity + full coverage, order preserved within a bucket
    seen = []
    for bucket in plan:
        dtypes = {str(np.asarray(leaves[i]).dtype) for i in bucket}
        assert len(dtypes) == 1, dtypes
        assert bucket == sorted(bucket)
        seen += bucket
    assert sorted(seen) == list(range(len(leaves)))
    # size targeting: multi-leaf buckets stay under the target unless a
    # single leaf alone exceeds it (never split)
    for bucket in plan:
        nbytes = sum(int(np.asarray(leaves[i]).nbytes) for i in bucket)
        if len(bucket) > 1:
            assert nbytes <= 24 * 1024
    # the 40 KB leaf exceeds the target -> its own bucket
    big = [b for b in plan if any(
        np.asarray(leaves[i]).nbytes > 24 * 1024 for i in b)]
    assert all(len(b) == 1 for b in big) and big
    # pack/unpack round trip is the identity
    out = [None] * len(leaves)
    for bucket in plan:
        sh.unpack_bucket(sh.pack_bucket(leaves, bucket), leaves, bucket,
                         out)
    rt = sh.unflatten_tree(treedef, out)
    for k in tree:
        assert np.asarray(rt[k]).tobytes() == \
            np.asarray(tree[k]).tobytes(), k


def test_hidden_union_not_double_counted_for_concurrent_comm():
    """The satellite fix pin: two concurrent background buckets cover
    the same wall clock ONCE, and a background bucket that completes
    inside another bucket's exposed wait() window is hidden only where
    no one was blocked. Per-kind fields may overlap each other (they
    are attribution); overlap_fraction must use real coverage."""
    from ray_tpu._private import step_anatomy as sa

    step = {"step_id": 1, "rank": 0, "node": "n0", "pid": 1,
            "start": 0.0, "end": 1.0}
    acts = [
        # bucket A's allreduce, background on the issue thread
        {"step_id": 1, "rank": 0, "node": "n0", "pid": 1,
         "kind": "collective", "start": 0.0, "end": 0.5,
         "blocking": False},
        # bucket B overlaps A (it queued behind it; spans overlap once
        # submit+issue stamps both) and completes INSIDE the exposed
        # wait window below
        {"step_id": 1, "rank": 0, "node": "n0", "pid": 1,
         "kind": "collective", "start": 0.2, "end": 0.45,
         "blocking": False},
        # the caller blocked in handle.wait() for [0.4, 0.6]
        {"step_id": 1, "rank": 0, "node": "n0", "pid": 1,
         "kind": "collective", "start": 0.4, "end": 0.6,
         "blocking": True},
    ]
    br = sa.anatomize_rank_step(step, acts)
    # union of background = [0, 0.5]; minus exposed [0.4, 0.6] -> 0.4.
    # A per-record sum would claim 0.5 + 0.25 - overlap bugs.
    assert br["comm_hidden_s"] == pytest.approx(0.4)
    assert br["comm_exposed_s"] == pytest.approx(0.2)
    assert br["overlap_fraction"] == pytest.approx(0.4 / 0.6)
    # cross-kind double count: background comm + background data over
    # the same interval must not sum past the wall clock
    acts2 = [
        {"step_id": 1, "rank": 0, "node": "n0", "pid": 1,
         "kind": "collective", "start": 0.0, "end": 0.8,
         "blocking": False},
        {"step_id": 1, "rank": 0, "node": "n0", "pid": 1,
         "kind": "data_produce", "start": 0.0, "end": 0.8,
         "blocking": False},
    ]
    br2 = sa.anatomize_rank_step(step, acts2)
    # attribution fields overlap by design...
    assert br2["comm_hidden_s"] == pytest.approx(0.8)
    assert br2["data_hidden_s"] == pytest.approx(0.8)
    # ...but the fraction uses the union: hidden coverage is 0.8 of an
    # otherwise-free second, not 1.6
    assert br2["overlap_fraction"] == pytest.approx(1.0)


# --------------------------------------------------------------- live group


def _rank_cls(ray):
    @ray.remote
    class Rank:
        def configure(self, env):
            os.environ.update({k: str(v) for k, v in env.items()})
            return True

        def join(self, world, rank, name):
            from ray_tpu.util import collective as col

            col.init_collective_group(world, rank, "host", name)
            return rank

        def async_vs_sync(self, rank, name):
            """Async results must be bitwise identical to sync results
            on the same inputs, seq order preserved across a mixed
            async/sync call site, waits in arbitrary order."""
            from ray_tpu.util import collective as col

            rng = np.random.RandomState(7 + rank)
            a = rng.standard_normal(4096).astype(np.float32)
            b = rng.standard_normal(333).astype(np.float64)
            c = np.arange(64, dtype=np.int64) * (rank + 1)
            h1 = col.allreduce_async(a, name)
            h2 = col.allreduce_async(b, name)
            done_before = h1.poll(), h2.poll()
            s = col.allreduce(c, name)          # sync: drains the queue
            h3 = col.reducescatter_async(a, name)
            # wait out of order: h2 then h1
            r2 = h2.result(60)
            r1 = h1.result(60)
            r3 = h3.result(60)
            assert h1.poll() and h2.poll() and h3.poll()
            return {"r1": r1, "r2": r2, "s": np.asarray(s), "r3": r3,
                    "done_before": done_before}

        def sync_oracle(self, rank, name):
            from ray_tpu.util import collective as col

            rng = np.random.RandomState(7 + rank)
            a = rng.standard_normal(4096).astype(np.float32)
            b = rng.standard_normal(333).astype(np.float64)
            return {"a": np.asarray(col.allreduce(a, name)),
                    "b": np.asarray(col.allreduce(b, name)),
                    "rs": np.asarray(col.reducescatter(a, name))}

        def train_numpy(self, rank, name, bucketed, steps=4):
            """Tiny numpy SGD loop: grads synced via ddp, params
            updated identically on every rank. Returns the final
            params' raw bytes — the on/off + cross-rank identity
            oracle."""
            os.environ["RAY_TPU_TRAIN_BUCKET_DDP"] = \
                "1" if bucketed else "0"
            from ray_tpu.train import ddp

            rng = np.random.RandomState(1234)      # same init everywhere
            params = {"w1": rng.standard_normal((96, 64))
                      .astype(np.float32),
                      "b1": rng.standard_normal(64).astype(np.float32),
                      "w2": rng.standard_normal((64, 11))
                      .astype(np.float32)}
            for step in range(steps):
                grng = np.random.RandomState(100 * step + rank)
                grads = {k: grng.standard_normal(v.shape)
                         .astype(np.float32) for k, v in params.items()}
                synced = ddp.sync_gradients(grads, name,
                                            bucket_bytes=8192)
                for k in params:
                    params[k] = params[k] - \
                        np.float32(0.01) * np.asarray(synced[k])
            return {k: v.tobytes() for k, v in params.items()}

        def bucket_metrics(self):
            from ray_tpu.util.metrics import registry_snapshot

            out = {}
            for fam in registry_snapshot():
                if fam["name"] in (
                        "ray_tpu_collective_async_inflight_tasks",
                        "ray_tpu_train_buckets_total"):
                    out[fam["name"]] = fam
            return out

        def quantized_bucketed(self, rank, name):
            """int8 wire per bucket: results rank-identical, error
            inside the documented bound vs a float64 oracle."""
            os.environ["RAY_TPU_COLLECTIVE_WIRE_DTYPE"] = "int8"
            os.environ["RAY_TPU_TRAIN_BUCKET_DDP"] = "1"
            try:
                from ray_tpu.train import ddp

                ins = [np.random.RandomState(500 + r)
                       .standard_normal(20000).astype(np.float32)
                       for r in range(2)]
                out = ddp.sync_gradients({"g": ins[rank]}, name,
                                         bucket_bytes=16384)
                got = np.asarray(out["g"])
                exact = ins[0].astype(np.float64) + \
                    ins[1].astype(np.float64)
                err = float(np.abs(got.astype(np.float64) - exact).max())
                bound = 2 * (1.0 / 254.0) * float(
                    sum(np.abs(x).max() for x in ins))
                return {"bytes": got.tobytes(), "err": err,
                        "bound": bound}
            finally:
                os.environ["RAY_TPU_COLLECTIVE_WIRE_DTYPE"] = "off"

        def launch_pending(self, rank, name, count=4):
            """Submit `count` async allreduces and park (rank 1 never
            calls, so they stay pending) — the chaos target."""
            from ray_tpu.util import collective as col

            self._handles = [
                col.allreduce_async(np.full(70000, float(rank + 1),
                                            np.float32), name)
                for _ in range(count)]
            return True

        def wait_pending(self, which, timeout):
            t0 = time.monotonic()
            try:
                self._handles[which].wait(timeout)
                return {"ok": True, "latency": time.monotonic() - t0}
            except BaseException as e:  # noqa: BLE001
                return {"ok": False, "latency": time.monotonic() - t0,
                        "type": type(e).__name__, "msg": str(e)}

        def chaos(self, seed, schedule):
            from ray_tpu._private import fault_injection as fi

            fi.install(seed, schedule)
            return True

        def segment_objects(self, name):
            from ray_tpu._private.worker_runtime import (col_oid_prefix,
                                                         current_worker)

            prefix = col_oid_prefix(name)
            return sum(1 for oid, _ in
                       current_worker().store.list_objects()
                       if oid.startswith(prefix))

        def destroy(self, name):
            from ray_tpu.util import collective as col

            col.destroy_collective_group(name)
            return True

    return Rank


def _world(ray, n, name, env=None):
    Rank = _rank_cls(ray)
    actors = [Rank.options(num_cpus=0).remote() for _ in range(n)]
    merged = {"RAY_TPU_TRAIN_BUCKET_DDP": "1"}
    merged.update(env or {})
    ray.get([a.configure.remote(merged) for a in actors])
    ray.get([a.join.remote(n, i, name) for i, a in enumerate(actors)],
            timeout=120)
    return actors


def test_async_handles_match_sync_bitwise(ray_start_regular):
    ray = ray_start_regular
    name = GROUP + "_async"
    actors = _world(ray, 2, name)
    try:
        got = ray.get([a.async_vs_sync.remote(i, name)
                       for i, a in enumerate(actors)], timeout=120)
        oracle = ray.get([a.sync_oracle.remote(i, name)
                          for i, a in enumerate(actors)], timeout=120)
        for rank in range(2):
            g, o = got[rank], oracle[rank]
            assert np.asarray(g["r1"]).tobytes() == o["a"].tobytes()
            assert np.asarray(g["r2"]).tobytes() == o["b"].tobytes()
            assert np.asarray(g["r3"]).tobytes() == o["rs"].tobytes()
            # the interleaved sync op saw both async ops' contributions
            # drained first and its own result correct
            assert np.array_equal(g["s"], np.arange(64) * 3)
        # metrics plane: the inflight gauge + bucket counter exist
        fams = ray.get(actors[0].bucket_metrics.remote())
        assert "ray_tpu_collective_async_inflight_tasks" in fams
    finally:
        ray.get([a.destroy.remote(name) for a in actors], timeout=30)


def test_bucketed_on_off_final_params_identical(ray_start_regular):
    """Acceptance: bucketed-on vs bucketed-off produce rank-byte-
    identical final params per seed at world 2 (one pairwise IEEE add
    per element — commutative, so bucket boundaries can't change
    bits), and both ranks always agree with each other."""
    ray = ray_start_regular
    name = GROUP + "_id"
    actors = _world(ray, 2, name)
    try:
        on = ray.get([a.train_numpy.remote(i, name, True)
                      for i, a in enumerate(actors)], timeout=120)
        off = ray.get([a.train_numpy.remote(i, name, False)
                       for i, a in enumerate(actors)], timeout=120)
        for k in on[0]:
            assert on[0][k] == on[1][k], f"rank divergence (on) {k}"
            assert off[0][k] == off[1][k], f"rank divergence (off) {k}"
            assert on[0][k] == off[0][k], f"on/off divergence {k}"
        # the bucketed runs actually bucketed (several buckets per sync)
        fams = ray.get(actors[0].bucket_metrics.remote())
        total = sum(v["value"] for v in
                    fams["ray_tpu_train_buckets_total"]["values"])
        assert total >= 8, fams
    finally:
        ray.get([a.destroy.remote(name) for a in actors], timeout=30)


def test_quantized_wire_applies_per_bucket(ray_start_regular):
    ray = ray_start_regular
    name = GROUP + "_q"
    # quantization is an inter-host wire feature; force the socket path
    # so the int8 codec actually runs (same choice as BENCH_r08)
    actors = _world(ray, 2, name, env={"RAY_TPU_COLLECTIVE_SHM": "0"})
    try:
        got = ray.get([a.quantized_bucketed.remote(i, name)
                       for i, a in enumerate(actors)], timeout=120)
        assert got[0]["bytes"] == got[1]["bytes"], "ranks diverged"
        assert 0 < got[0]["err"] <= got[0]["bound"], got[0]
    finally:
        ray.get([a.destroy.remote(name) for a in actors], timeout=30)


@pytest.mark.chaos
def test_poison_fails_pending_handles_fast(ray_start_regular):
    """A member dies with bucketed allreduces IN FLIGHT: the surviving
    rank's pending handles — the one on the wire AND the queued ones —
    all surface CollectiveGroupError within the poison-latency bound
    (nowhere near one op timeout each), and group teardown leaves zero
    stranded shm segments."""
    ray = ray_start_regular
    from ray_tpu.exceptions import CollectiveGroupError  # noqa: F401

    name = GROUP + "_poison"
    actors = _world(ray, 2, name,
                    env={"RAY_TPU_COLLECTIVE_OP_TIMEOUT_S": "120"})
    ray.get(actors[0].launch_pending.remote(0, name, 4), timeout=30)
    time.sleep(0.5)          # let the issue thread put op #1 on the wire
    t0 = time.monotonic()
    ray.kill(actors[1], no_restart=True)
    outcomes = [ray.get(actors[0].wait_pending.remote(i, 90),
                        timeout=120) for i in range(4)]
    total = time.monotonic() - t0
    for out in outcomes:
        assert not out["ok"], out
        assert out["type"] == "CollectiveGroupError", out
    # all four handles failed in far less than ONE 120s op timeout —
    # the queued ones were failed in a batch, not issued serially
    assert total < 30, f"pending handles took {total:.1f}s to fail"
    assert ray.get(actors[0].destroy.remote(name), timeout=30)
    assert ray.get(actors[0].segment_objects.remote(name),
                   timeout=30) == 0
    ray.kill(actors[0], no_restart=True)


@pytest.mark.chaos
@pytest.mark.fault_injection
def test_dropped_frame_times_out_not_hangs(ray_start_regular):
    """A seeded dropped segment during an async bucketed allreduce
    surfaces as a timeout on the handle (the wire's failure detector of
    last resort), never a hang."""
    ray = ray_start_regular
    name = GROUP + "_drop"
    actors = _world(ray, 2, name,
                    env={"RAY_TPU_COLLECTIVE_OP_TIMEOUT_S": "6",
                         "RAY_TPU_COLLECTIVE_SHM": "0"})
    try:
        ray.get([a.chaos.remote(0, "drop:*.col_push_frame:#1")
                 for a in actors], timeout=30)
        ray.get([a.launch_pending.remote(i, name, 1)
                 for i, a in enumerate(actors)], timeout=30)
        t0 = time.monotonic()
        outs = ray.get([a.wait_pending.remote(0, 30) for a in actors],
                       timeout=90)
        elapsed = time.monotonic() - t0
        assert any(not o["ok"] for o in outs), outs
        for o in outs:
            if not o["ok"]:
                assert o["type"] == "TimeoutError", o
        assert elapsed < 45, f"drop took {elapsed:.1f}s to surface"
    finally:
        try:
            ray.get([a.destroy.remote(name) for a in actors],
                    timeout=30)
        except Exception:
            pass


# ------------------------------------------------------ cluster acceptance


def _bucketed_train_loop(config):
    import jax
    import jax.numpy as jnp
    import numpy as _np
    import optax

    from ray_tpu.air import session
    from ray_tpu.parallel.train_step import make_train_state
    from ray_tpu.train import ddp

    rank = session.get_world_rank()

    def init_params(rng):
        k1, k2 = jax.random.split(rng)
        return {"w1": jax.random.normal(k1, (192, 256)) * 0.02,
                "w2": jax.random.normal(k2, (256, 8)) * 0.02}

    def loss_fn(params, batch):
        x, y = batch
        h = jnp.tanh(x @ params["w1"])
        logits = h @ params["w2"]
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, {"loss": loss}

    opt = optax.sgd(0.05)
    state = make_train_state(init_params, jax.random.PRNGKey(0), opt)
    step_fn = ddp.make_ddp_train_step(
        loss_fn, opt,
        lambda g: ddp.sync_gradients(
            g, "zzbd_gang", average=True, bucket_bytes=64 * 1024),
        donate=False)
    for step in range(6):
        srng = _np.random.RandomState(1000 * rank + step)
        batch = (jnp.asarray(srng.standard_normal((32, 192))
                             .astype(_np.float32)),
                 jnp.asarray(srng.randint(0, 8, 32)))
        state, metrics = step_fn(state, batch)
        session.report({"loss": float(metrics["loss"])})
    blob = b"".join(_np.asarray(v).tobytes()
                    for _, v in sorted(state.params.items()))
    import hashlib

    session.report({"digest": hashlib.sha256(blob).hexdigest()})


def test_overlap_proof_bucketed_train(ray_start_regular):
    """Acceptance: a 2-worker gang running a REAL ddp.make_ddp_train_step loop
    over ddp.sync_gradients shows background bucket comm
    genuinely hidden under the step (comm_hidden > 0 with
    overlap_fraction > 0 in the fused step-anatomy report), and both
    ranks' final params are byte-identical."""
    ray = ray_start_regular
    from ray_tpu._private import telemetry as _tm
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.experimental.state.api import summarize_steps
    from ray_tpu.train.backend_executor import BackendExecutor, JaxConfig

    if not _tm.ENABLED:
        pytest.skip("telemetry plane disabled")
    executor = BackendExecutor(
        JaxConfig(group_name="zzbd_gang"),
        ScalingConfig(num_workers=2,
                      resources_per_worker={"CPU": 1})).start()
    digests = {}
    try:
        executor.start_training(_bucketed_train_loop, {})
        deadline = time.time() + 180
        while True:
            rows = executor.next_results()
            for rank, r in enumerate(rows):
                if not r.get("done") and "digest" in r.get("metrics", {}):
                    digests[rank] = r["metrics"]["digest"]
            if all(r.get("done") for r in rows):
                assert not any(r.get("error") for r in rows), rows
                break
            assert time.time() < deadline, "train run wedged"
        summary = summarize_steps()
    finally:
        executor.shutdown()

    assert digests.get(0) and digests[0] == digests.get(1), digests
    complete = [s for s in summary["steps"]
                if s["complete"] and len(s["ranks"]) == 2]
    assert len(complete) >= 3, summary["steps"]
    hidden = sum(br["comm_hidden_s"] for s in complete
                 for br in s["ranks"].values())
    assert hidden > 0, \
        "no bucket comm was attributed as hidden under the step"
    fracs = [s["overlap_fraction"] for s in complete
             if s["overlap_fraction"] is not None]
    assert fracs and max(fracs) > 0
    # the waits the loop DID pay are exposed comm, not compute — the
    # honest-accounting half of the acceptance
    exposed = sum(br["comm_exposed_s"] for s in complete
                  for br in s["ranks"].values())
    assert exposed >= 0
