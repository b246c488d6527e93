"""Batch assembly over a block stream + the device-put double buffer.

One batching loop serves Dataset and DatasetPipeline, so a pipeline
carries its batch remainder across window boundaries (only the final batch
may be short, honoring ``drop_last``).

With ``device_put=True`` the stream double-buffers: a producer
thread assembles batch k+1 (block fetch is already overlapped by the
executor) and dispatches its ``jax.device_put`` while the caller consumes
batch k, so the host→HBM transfer rides under the train step.

Every yielded batch stamps ``ray_tpu_data_wait_seconds{consumer}`` — the
wall time the consumer was blocked waiting for that batch, the "input
gates the step" signal the ROADMAP's <5% data-wait acceptance is measured
by. Off under ``RAY_TPU_INTERNAL_TELEMETRY=0``.
"""
from __future__ import annotations

import queue as _queue
import threading
import time

# the step-anatomy stamps below cost one tuple read per batch when no
# train step is active
from ray_tpu._private import step_anatomy as _sa
from ray_tpu._private import telemetry as _tm
from ray_tpu.data import block as B
from ray_tpu.data._internal.streaming.executor import StreamingExecutor


def iter_batch_blocks(blocks, batch_size: int, drop_last: bool):
    """Slice a block stream into batch-sized blocks: numpy views + one
    concat per batch, zero per-row Python for columnar blocks."""
    pending: list = []       # partial blocks carried across block refs
    pending_n = 0
    for blk in blocks:
        pending.append(blk)
        pending_n += B.num_rows(blk)
        while pending_n >= batch_size:
            take, taken = [], 0
            while taken < batch_size:
                head = pending[0]
                hn = B.num_rows(head)
                need = batch_size - taken
                if hn <= need:
                    take.append(head)
                    taken += hn
                    pending.pop(0)
                else:
                    take.append(B.slice_block(head, 0, need))
                    pending[0] = B.slice_block(head, need, hn)
                    taken += need
            pending_n -= batch_size
            yield (B.concat_blocks(take) if len(take) > 1 else take[0])
    if pending_n and not drop_last:
        yield B.concat_blocks(pending)


def make_to_batch(batch_format: str, device_put: bool):
    def to_batch(blk):
        if batch_format == "numpy":
            batch = B.to_numpy_batch(blk)
        else:
            batch = B.to_rows(blk)
        if device_put:
            import jax

            batch = jax.device_put(batch)
        return batch

    return to_batch


def stamp_wait(gen, consumer: str):
    """Wrap a batch generator, observing the consumer-blocked time per
    batch (production time of each __next__). When a train step is
    active, the same interval goes to the step-anatomy ring as an
    EXPOSED ``data_wait`` activity — the input-gated share of that
    step, joined by step_id."""
    while True:
        t0 = time.perf_counter()
        m0 = time.monotonic()
        try:
            batch = next(gen)
        except StopIteration:
            return
        wait = time.perf_counter() - t0
        _tm.observe("ray_tpu_data_wait_seconds", wait,
                    tags={"consumer": consumer})
        _sa.record_activity("data_wait", m0, m0 + wait, blocking=True,
                            consumer=consumer)
        yield batch


def _double_buffered(batch_blocks, to_batch):
    """Producer thread converts (slice + device_put dispatch) batch k+1
    while the caller consumes batch k. Queue depth 2 = one batch in the
    caller's hands, one converted and waiting, one being converted."""
    q: _queue.Queue = _queue.Queue(maxsize=2)
    stop = threading.Event()

    def produce():
        try:
            for bb in batch_blocks:
                m0 = time.monotonic()
                item = ("ok", to_batch(bb))
                # background by construction: this thread's conversion
                # + device_put dispatch is the ingest work that HIDES
                # under the caller's train step — step anatomy reports
                # it as data_hidden (overlap proof for the data plane)
                _sa.record_activity("data_produce", m0, time.monotonic(),
                                    blocking=False)
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.2)
                        break
                    except _queue.Full:
                        continue
                if stop.is_set():
                    return
            while not stop.is_set():
                try:
                    q.put(("end", None), timeout=0.2)
                    return
                except _queue.Full:
                    continue
        except BaseException as e:  # noqa: BLE001 — re-raised by consumer
            while not stop.is_set():
                try:
                    q.put(("err", e), timeout=0.2)
                    return
                except _queue.Full:
                    continue

    t = threading.Thread(target=produce, daemon=True,
                         name="data-stream-device-put")
    t.start()
    try:
        while True:
            try:
                kind, payload = q.get(timeout=1.0)
            except _queue.Empty:
                if not t.is_alive():
                    return   # producer died without a sentinel
                continue
            if kind == "end":
                return
            if kind == "err":
                raise payload
            yield payload
    finally:
        # The producer OWNS batch_blocks (closing a generator that is
        # executing in another thread raises); stop just flips the flag —
        # the producer exits at its next put, and the caller's executor
        # close unblocks a producer parked inside a block wait.
        stop.set()


def stream_items(ds):
    """(stages, ref) sources for one Dataset, drawn lazily so the
    executor submits map-stage tasks on demand. ActorPoolStrategy
    datasets keep their eager pool materialization (the pool is sized
    from the block count up front) and stream the resulting refs."""
    from ray_tpu.data.dataset import _ActorPoolStrategy

    compute = getattr(ds, "_compute", None)
    if ds._stages and isinstance(compute, _ActorPoolStrategy):
        for ref in ds._materialized_refs():
            yield (None, ref)
        return
    stages = ds._stages
    for ref in ds._block_refs:
        yield (stages, ref)


def _make_submit():
    from ray_tpu.data.dataset import _get_chain_task

    def submit(item):
        stages, ref = item
        if stages:
            return _get_chain_task().remote(stages, ref)
        return ref

    return submit


def _stream_batches(owner, items, *, batch_size: int, batch_format: str,
                    device_put: bool, drop_last: bool):
    """Batches over one executor's block stream, each stamped with the
    time `owner`'s consumer waited for it."""
    consumer = getattr(owner, "_consumer", None) or "default"
    to_batch = make_to_batch(batch_format, device_put)
    ex = StreamingExecutor(items, _make_submit(), consumer=consumer)
    batch_blocks = iter_batch_blocks(ex.iter_blocks(), batch_size,
                                     drop_last)
    if device_put:
        gen = _double_buffered(batch_blocks, to_batch)
    else:
        gen = (to_batch(bb) for bb in batch_blocks)
    try:
        yield from stamp_wait(gen, consumer)
    finally:
        ex.close()


def dataset_iter_batches(ds, **batching):
    """``Dataset.iter_batches``."""
    return _stream_batches(ds, stream_items(ds), **batching)


def pipeline_iter_batches(pipe, **batching):
    """``DatasetPipeline.iter_batches``: one batch stream over ALL
    windows, carrying the remainder across window boundaries. One
    executor runs over the concatenated window sources, so window i+1's
    tasks submit while window i is consumed, bounded by the same
    budget."""
    items = (item for w in pipe._window_iter() for item in stream_items(w))
    return _stream_batches(pipe, items, **batching)
