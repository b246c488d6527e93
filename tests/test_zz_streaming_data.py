"""Streaming data plane (ray_tpu/data/_internal/streaming/): bounded-
memory pull-based ingest, backpressure, locality-ordered prefetch,
device-put double buffering, task-side re-blocking and the collective
shuffle exchange.

Late-alphabet by design: the tier-1 duration guard keeps early files
fast; this whole suite stays well inside the per-file budget.
"""
import threading
import time

import numpy as np
import pytest


@pytest.fixture
def ds_env(ray_start_regular):
    yield ray_start_regular


def _collect(ds, **kw):
    return list(ds.iter_batches(**kw))


# ------------------------------------------------------------------ oracle


def test_bounded_memory_peak_le_budget(ds_env, monkeypatch):
    """Streaming a dataset 6x larger than the prefetch budget never
    holds more than `budget` blocks buffered/in flight at once."""
    from ray_tpu import data
    from ray_tpu.data._internal.streaming import last_executor

    monkeypatch.setenv("RAY_TPU_DATA_PREFETCH_BLOCKS", "2")
    ds = data.from_numpy(np.arange(12_000.0), parallelism=12)
    batches = _collect(ds, batch_size=1000)
    assert sum(len(b) for b in batches) == 12_000
    ex = last_executor()
    st = ex.stats()
    assert st["budget"] == 2
    assert st["peak_buffered_blocks"] <= 2, st
    assert st["consumed"] == 12


def test_backpressure_parks_producer(ds_env, monkeypatch):
    """A slow consumer stops the fetchers: while batch k is being
    'trained on', the executor never runs ahead of the budget window."""
    from ray_tpu import data
    from ray_tpu.data._internal.streaming import last_executor

    monkeypatch.setenv("RAY_TPU_DATA_PREFETCH_BLOCKS", "3")
    ds = data.from_numpy(np.arange(10_000.0), parallelism=10)
    it = ds.iter_batches(batch_size=1000)
    seen = 0
    for batch in it:
        seen += 1
        time.sleep(0.02)   # slow consumer
        ex = last_executor()
        st = ex.stats()
        # fetched-but-unconsumed work is bounded by the budget at every
        # step of the slow consumption, not just at the end
        assert st["peak_buffered_blocks"] <= 3, (seen, st)
    assert seen == 10


def _slices(n_rows: int, batch_size: int, drop_last: bool):
    """Plain slicing of the concatenated rows: what a batch stream is."""
    stops = range(0, n_rows, batch_size)
    out = [(lo, min(lo + batch_size, n_rows)) for lo in stops]
    if drop_last and out and out[-1][1] - out[-1][0] < batch_size:
        out.pop()
    return out


@pytest.mark.parametrize("source,kwargs", [
    ("plain", dict(batch_size=64)),
    ("plain", dict(batch_size=64, drop_last=True)),
    ("plain", dict(batch_size=1000)),       # one short batch
    ("cols", dict(batch_size=77)),
], ids=["b64", "b64_drop_last", "one_short_batch", "dict_columns_b77"])
def test_batches_are_slices_of_the_rows(ds_env, source, kwargs):
    """Across block and batch-size boundaries, dict columns and
    drop_last, batch k is rows [k*B, (k+1)*B) of the dataset."""
    from ray_tpu import data

    if source == "plain":
        rows = {"v": np.arange(500.0)}
        ds = data.from_numpy(rows["v"], parallelism=7)
    else:
        rows = {"x": np.arange(300.0), "y": np.arange(300) % 5}
        ds = data.from_items(
            [{"x": float(i), "y": i % 5} for i in range(300)],
            parallelism=4)
    n = len(next(iter(rows.values())))
    batches = _collect(ds, **kwargs)
    want = _slices(n, kwargs["batch_size"], kwargs.get("drop_last", False))
    assert len(batches) == len(want)
    for batch, (lo, hi) in zip(batches, want):
        got = batch if isinstance(batch, dict) else {"v": batch}
        assert sorted(got) == sorted(rows)
        for k, col in rows.items():
            assert got[k].dtype == col.dtype, (k, got[k].dtype)
            np.testing.assert_array_equal(got[k], col[lo:hi])


# ------------------------------------------------------- pipeline windows


def test_pipeline_carries_remainder_across_windows(ds_env):
    """70 rows in 10 blocks of 7, windows of 3 blocks (21 rows): the old
    per-window batching yielded a short batch at every window edge; now
    only the final batch may be short."""
    from ray_tpu import data

    pipe = data.from_numpy(np.arange(70.0), parallelism=10).window(
        blocks_per_window=3)
    sizes = [len(b) for b in pipe.iter_batches(batch_size=10)]
    assert sizes == [10] * 7
    # 75 rows: final remainder of 5 honors drop_last
    pipe = data.from_numpy(np.arange(75.0), parallelism=10).window(
        blocks_per_window=3)
    sizes = [len(b) for b in pipe.iter_batches(batch_size=10)]
    assert sizes == [10] * 7 + [5]
    pipe = data.from_numpy(np.arange(75.0), parallelism=10).window(
        blocks_per_window=3)
    sizes = [len(b)
             for b in pipe.iter_batches(batch_size=10, drop_last=True)]
    assert sizes == [10] * 7


def test_pipeline_batches_are_slices_of_the_windows_rows(ds_env):
    """A mapped pipeline's batches are slices of its windows' rows laid
    end to end: 113 rows in windows of 2 blocks, batches of 25."""
    from ray_tpu import data

    pipe = data.from_numpy(np.arange(113.0), parallelism=6).window(
        blocks_per_window=2).map_batches(lambda a: a * 3)
    batches = list(pipe.iter_batches(batch_size=25))
    want = _slices(113, 25, False)
    assert len(batches) == len(want) == 5
    rows = np.arange(113.0) * 3
    for batch, (lo, hi) in zip(batches, want):
        assert batch.dtype == rows.dtype
        np.testing.assert_array_equal(batch, rows[lo:hi])


# ------------------------------------------------------------- locality


def test_locality_preference_orders_pulls(ds_env, monkeypatch):
    """Within the prefetch window, same-node blocks are pulled before
    remote ones; delivery order stays dataset order."""
    from ray_tpu.data._internal.streaming.executor import StreamingExecutor

    n = 8
    local = {0, 2, 4, 6}
    fetched = []

    class _FakeRef:
        def __init__(self, i):
            self.i = i

    ex = StreamingExecutor(iter([_FakeRef(i) for i in range(n)]),
                           budget=n, consumer="loctest", fetch_threads=1)
    monkeypatch.setattr(ex, "_is_local", lambda ref: ref.i in local)

    def fake_fetch(ref):
        fetched.append(ref.i)
        from ray_tpu.data._internal.streaming.executor import _Slot

        from ray_tpu._private import serialization as ser

        return _Slot(data=bytes(ser.serialize(ref.i))), (
            "local" if ref.i in local else "remote")

    monkeypatch.setattr(ex, "_fetch_one", fake_fetch)
    out = list(ex.iter_blocks())
    assert out == list(range(n))              # delivery: dataset order
    # pulls: all locals of the initial window before any remote
    first_half = fetched[: len(local)]
    assert set(first_half) == local, fetched
    st = ex.stats()
    assert st["blocks_local"] == 4 and st["blocks_remote"] == 4


def test_blocks_counted_local_on_single_node(ds_env):
    from ray_tpu import data
    from ray_tpu.data._internal.streaming import last_executor

    ds = data.from_numpy(np.arange(600.0), parallelism=6)
    list(ds.iter_batches(batch_size=100))
    st = last_executor().stats()
    assert st["blocks_local"] == 6 and st["blocks_remote"] == 0


# ------------------------------------------------------------- chaos


def test_dropped_block_fetch_retries_not_hang(ds_env):
    """A seeded chaos schedule dropping the first two block fetches is
    absorbed by the unified retry policy — iteration completes with the
    right rows and the injector trace shows the drops fired."""
    from ray_tpu import data
    from ray_tpu._private import fault_injection as fi

    ds = data.from_numpy(np.arange(200.0), parallelism=4)
    inj = fi.install(7, "drop:*.data_block_fetch:#1,2")
    try:
        t0 = time.monotonic()
        batches = list(ds.iter_batches(batch_size=50))
        elapsed = time.monotonic() - t0
    finally:
        fi.uninstall()
    assert sum(len(b) for b in batches) == 200
    np.testing.assert_array_equal(np.concatenate(batches),
                                  np.arange(200.0))
    drops = [e for e in inj.trace()
             if e[0] == "drop" and e[2] == "data_block_fetch"]
    assert len(drops) == 2, inj.trace()
    assert elapsed < 30, "retry path must not degenerate into a hang"


# -------------------------------------------------- task-side re-blocking


def test_reblock_ops_never_materialize_on_driver(ds_env, monkeypatch):
    """repartition / zip / uneven split re-block via remote tasks: the
    driver never calls take_all() mid-op."""
    from ray_tpu import data
    from ray_tpu.data.dataset import Dataset

    ds = data.from_numpy(np.arange(100.0), parallelism=4)
    other = data.from_items([f"s{i}" for i in range(100)], parallelism=4)

    def boom(self):
        raise AssertionError("driver-side take_all during re-block op")

    monkeypatch.setattr(Dataset, "take_all", boom)
    rep = ds.repartition(3)
    zipped = ds.zip(other)
    shards = ds.split(3)          # 4 blocks % 3 != 0 → uneven path
    monkeypatch.undo()

    assert rep.num_blocks == 3
    assert rep.take_all() == list(np.arange(100.0))
    rows = zipped.take_all()
    assert len(rows) == 100
    assert rows[5] == (5.0, "s5")
    got = sorted(float(v) for s in shards for v in s.take_all())
    assert got == list(np.arange(100.0))
    # legacy chunking: ceil(100/3)=34 → 34/34/32
    assert [len(s.take_all()) for s in shards] == [34, 34, 32]


def test_repartition_matches_legacy_content(ds_env):
    from ray_tpu import data

    rows = [{"a": float(i), "b": i % 7} for i in range(90)]
    ds = data.from_items(rows, parallelism=5).map(
        lambda r: {"a": r["a"] * 2, "b": r["b"]})
    rep = ds.repartition(4)
    assert rep.num_blocks == 4
    out = rep.take_all()
    assert [float(r["a"]) for r in out] == [i * 2.0 for i in range(90)]


# ------------------------------------------------------ collective shuffle


def test_collective_shuffle_matches_task_shuffle(ds_env, monkeypatch):
    """The all-to-all over the host-collective plane produces the exact
    rows of the task-based exchange for the same seed."""
    from ray_tpu import data

    ds = data.from_numpy(np.arange(80.0), parallelism=2)
    task_rows = ds.random_shuffle(seed=11).take_all()

    monkeypatch.setenv("RAY_TPU_DATA_SHUFFLE_COLLECTIVE", "1")
    col_rows = ds.random_shuffle(seed=11).take_all()
    assert col_rows == task_rows
    assert sorted(col_rows) == list(np.arange(80.0))
    assert col_rows != list(np.arange(80.0))


# ---------------------------------------------------------- device path


def test_device_put_double_buffered(ds_env):
    import jax

    from ray_tpu import data

    ds = data.from_numpy(np.arange(256.0), parallelism=4)
    batches = list(ds.iter_batches(batch_size=64, device_put=True))
    assert len(batches) == 4
    assert all(isinstance(b, jax.Array) for b in batches)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(b) for b in batches]),
        np.arange(256.0))


# ----------------------------------------------------- staging + summary


def test_ephemeral_staging_roundtrip_and_cleanup(ds_env):
    """Heap-held fetched bytes re-stage into the shm store via
    put_ephemeral and are deleted at consume — no stranded staging
    objects afterwards."""
    from ray_tpu._private.worker_runtime import current_worker
    from ray_tpu.data._internal.streaming.executor import (
        _STAGE_PREFIX,
        StreamingExecutor,
    )

    w = current_worker()
    ex = StreamingExecutor(iter([]), consumer="stagetest")
    payload = b"z" * (64 * 1024)
    slot = ex._stage(w, payload)
    assert slot.pin is not None and slot.stage_id is not None
    assert bytes(slot.view()) == payload
    slot.release(w.store)
    strays = [oid for oid, _ in w.store.list_objects()
              if oid.startswith(_STAGE_PREFIX)]
    assert not strays


def test_summarize_data_and_wait_metric(ds_env):
    from ray_tpu import data
    from ray_tpu.experimental.state.api import summarize_data

    ds = data.from_numpy(np.arange(900.0), parallelism=3)
    ds._consumer = "zz-summary-test"
    n = len(list(ds.iter_batches(batch_size=100)))
    rows = {r["consumer"]: r
            for r in summarize_data()["consumers"]}
    row = rows.get("zz-summary-test")
    assert row is not None, rows
    assert row["batches"] == n == 9
    assert row["wait_total_s"] >= 0.0
    assert row["blocks_local"] == 3 and row["blocks_remote"] == 0


def test_train_shard_consumer_tagging(ds_env):
    """Train's dataset feed stamps per-rank consumer labels so data
    wait is attributable to the gang member it stalls."""
    from ray_tpu import data
    from ray_tpu.train.worker_group import TrainWorker

    tw = TrainWorker(world_rank=1, world_size=2)
    shard = data.from_numpy(np.arange(10.0), parallelism=1)
    tw.set_dataset_shard("train", shard)
    assert tw.session.dataset_shards["train"]._consumer == \
        "train/train/rank1"


def test_executor_close_releases_on_abandon(ds_env, monkeypatch):
    """Abandoning iteration mid-stream (take-style early exit) shuts the
    executor down and releases buffered slots."""
    from ray_tpu import data
    from ray_tpu.data._internal.streaming import last_executor

    monkeypatch.setenv("RAY_TPU_DATA_PREFETCH_BLOCKS", "4")
    ds = data.from_numpy(np.arange(5000.0), parallelism=10)
    it = ds.iter_batches(batch_size=500)
    next(it)
    it.close()
    ex = last_executor()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and not ex._closed:
        time.sleep(0.01)
    assert ex._closed
    assert not ex._buffer
    # fetch threads drain promptly after close
    for t in ex._threads:
        t.join(timeout=5)
    assert not any(t.is_alive() for t in ex._threads)
