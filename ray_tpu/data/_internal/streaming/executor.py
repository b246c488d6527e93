"""Pull-based streaming block executor with a bounded in-flight budget.

Reference: python/ray/data/_internal/execution/streaming_executor.py —
operators pull blocks on demand and a resource budget bounds how much of
the dataset is materialized at once. Here the unit is one block:

- **Bounded in-flight budget.** At most ``RAY_TPU_DATA_PREFETCH_BLOCKS``
  (default 4) blocks per consumer are alive between the consumer's read
  position and the furthest submitted map task — buffered blocks, fetches
  in flight, and submitted-but-unfetched tasks all count. Map-stage tasks
  are submitted lazily as the consumer advances (the legacy path submits
  one task per block up front), so a dataset much larger than host RAM
  streams at bounded memory.
- **Per-consumer backpressure.** Fetch workers park on the executor's
  condition when the consumer's buffer is full and wake when the consumer
  drains a slot — a slow train step stops the producers instead of
  growing an unbounded queue.
- **Shm-staged prefetch (zero-copy).** A prefetched block is held as a
  `PinnedBuffer` view into the node's shared-memory object store whenever
  the bytes are there (task results and `ray_tpu.put` blocks always are);
  borrower-inline bytes that arrive on the heap are re-staged into the
  store via the PR 4 ``put_ephemeral`` path. Either way the prefetch
  buffer holds store-accounted pins, not heap copies — deserialization
  happens once, at consume time, exactly like the legacy get path.
- **Locality-aware pull ordering.** Within the prefetch window, blocks
  that already have a local copy are pulled first (they complete
  instantly into the buffer) while remote blocks start their pulls in
  dataset order — delivery order to the consumer is always dataset
  order, so streaming output is bit-identical to the legacy path.
- **Fault tolerance.** Each block fetch runs under the unified
  `_private/retry.py` policy (method ``data_block_fetch``, registered
  retry-safe: it is a pure read); the seeded fault-injection plane is
  consulted at the same boundary so chaos schedules like
  ``drop:data_block_fetch:#2`` exercise the retry path deterministically.

Telemetry (all off under ``RAY_TPU_INTERNAL_TELEMETRY=0``):
``ray_tpu_data_blocks_total{consumer,source=local|remote}`` and the
``ray_tpu_data_prefetch_depth_blocks{consumer}`` gauge live here;
``ray_tpu_data_wait_seconds{consumer}`` is stamped by the batch iterator
(`iterator.py`).
"""
from __future__ import annotations

import os
import threading
import time

from ray_tpu._private import fault_injection as _fi
from ray_tpu._private import telemetry as _tm

DEFAULT_PREFETCH_BLOCKS = 4

# Heap-held fetched bytes at least this big are re-staged into the shm
# store (put_ephemeral) so the prefetch buffer stays store-accounted.
STAGE_MIN_BYTES = 32 * 1024

_STAGE_PREFIX = b"dstrm"


def prefetch_budget() -> int:
    try:
        v = int(os.environ.get("RAY_TPU_DATA_PREFETCH_BLOCKS",
                               str(DEFAULT_PREFETCH_BLOCKS)))
    except ValueError:
        v = DEFAULT_PREFETCH_BLOCKS
    return max(1, v)


_last_executor: "StreamingExecutor | None" = None


def last_executor() -> "StreamingExecutor | None":
    """The most recently constructed executor in this process (tests and
    the data-wait summary introspect its stats). A strong reference is
    deliberate: a closed executor holds no buffers, and the weakref
    would die with the generator chain the moment iteration finishes."""
    return _last_executor


class DataFetchDropped(Exception):
    """A block fetch was dropped by the fault-injection plane (chaos
    schedules with method ``data_block_fetch``) — transient by contract,
    retried by the executor's RetryPolicy."""


def _mint_stage_id() -> bytes:
    return _STAGE_PREFIX + os.urandom(16 - len(_STAGE_PREFIX))


_NO_VALUE = object()


class _Slot:
    """One fetched block parked in the prefetch buffer: raw heap bytes,
    a pinned zero-copy view into the shm store (optionally an ephemeral
    staging object this executor minted and must delete), or — on the
    no-core-worker fallback (ray:// client mode) — an already-
    deserialized value."""

    __slots__ = ("data", "pin", "stage_id", "error", "value")

    def __init__(self, data=None, pin=None, stage_id=None, error=None,
                 value=_NO_VALUE):
        self.data = data
        self.pin = pin
        self.stage_id = stage_id
        self.error = error
        self.value = value

    def view(self):
        return self.pin.memoryview() if self.pin is not None else self.data

    def release(self, store=None):
        if self.pin is not None:
            try:
                self.pin.release()
            except Exception:
                pass
            self.pin = None
        if self.stage_id is not None and store is not None:
            try:
                store.delete_ephemeral(self.stage_id)
            except Exception:
                pass
            self.stage_id = None
        self.data = None


class StreamingExecutor:
    """Stream blocks, in order, from an iterable of block sources.

    ``items`` yields opaque sources (possibly an infinite generator — a
    looping DatasetPipeline); ``submit(source) -> ObjectRef`` turns one
    into a block ref, submitting its map-stage task on demand. Blocks
    are delivered to exactly one consumer via :meth:`iter_blocks`.
    """

    def __init__(self, items, submit=None, *, budget: int | None = None,
                 consumer: str = "default", fetch_threads: int = 2):
        global _last_executor
        self._items = iter(items)
        self._submit = submit if submit is not None else (lambda s: s)
        self._budget = budget if budget is not None else prefetch_budget()
        self._budget = max(1, int(self._budget))
        self.consumer = consumer
        self._cond = threading.Condition()
        # index spaces: [0, _next_claim) claimed from the iterator,
        # [0, _next_yield) delivered to the consumer. Live indices are
        # always within [_next_yield, _next_yield + budget).
        self._next_claim = 0
        self._next_yield = 0
        self._pending: dict[int, object] = {}   # idx -> block ref
        self._inflight: set[int] = set()
        self._buffer: dict[int, _Slot] = {}
        self._exhausted = False
        self._closed = False
        self._started = False
        # observability / test oracles
        self.peak_buffered_blocks = 0
        self.blocks_local = 0
        self.blocks_remote = 0
        self.fetch_order: list[int] = []
        n_threads = max(1, min(int(fetch_threads), self._budget))
        self._threads = [
            threading.Thread(target=self._fetch_loop, daemon=True,
                             name=f"data-stream-fetch-{i}")
            for i in range(n_threads)
        ]
        _last_executor = self

    # ------------------------------------------------------------ plumbing

    def _worker(self):
        from ray_tpu._private.worker_runtime import current_worker

        return current_worker()

    def _note_peak_locked(self):
        live = len(self._buffer) + len(self._inflight) + len(self._pending)
        if live > self.peak_buffered_blocks:
            self.peak_buffered_blocks = live

    def _refill(self):
        """Claim sources from the item iterator up to the budget window
        and submit their map tasks (submission is non-blocking). Called
        at start and every time the consumer frees a slot, so task
        submission never waits behind a blocked fetch."""
        while True:
            with self._cond:
                if (self._closed or self._exhausted
                        or self._next_claim
                        >= self._next_yield + self._budget):
                    return
                idx = self._next_claim
                try:
                    source = next(self._items)
                except StopIteration:
                    self._exhausted = True
                    self._cond.notify_all()
                    return
                self._next_claim += 1
            # submit OUTSIDE the lock: task submission touches the lease
            # pipeline and must not serialize the consumer/fetchers
            try:
                ref = self._submit(source)
                err = None
            except BaseException as e:  # noqa: BLE001 — delivered in order
                ref, err = None, e
            with self._cond:
                if err is not None:
                    self._buffer[idx] = _Slot(error=err)
                else:
                    self._pending[idx] = ref
                self._note_peak_locked()
                self._cond.notify_all()

    def _is_local(self, ref) -> bool:
        """Does this node already hold the bytes (no network pull)?"""
        try:
            w = self._worker()
            if w.memory_store.get_nowait(ref.id) is not None:
                return True
            if ref.id in w._ref_to_task:
                return False   # still producing: not fetchable yet
            return w.store.contains(ref.id)
        except Exception:
            return False

    def _pick(self) -> tuple[int, object] | None:
        """Choose the next pending index to fetch: same-node blocks
        first (they fill the buffer instantly), remote blocks in dataset
        order otherwise. Locality probes run outside the lock."""
        with self._cond:
            candidates = sorted(self._pending)
        if not candidates:
            return None
        choice = None
        for idx in candidates:
            with self._cond:
                ref = self._pending.get(idx)
            if ref is None:
                continue
            if self._is_local(ref):
                choice = idx
                break
            if choice is None:
                choice = idx   # lowest remote index as the fallback
        if choice is None:
            return None
        with self._cond:
            ref = self._pending.pop(choice, None)
            if ref is None:
                return None   # raced another fetcher
            self._inflight.add(choice)
            return choice, ref

    def _fetch_loop(self):
        while True:
            with self._cond:
                if self._closed:
                    return
                done = (self._exhausted and not self._pending
                        and not self._inflight and not self._buffer
                        and self._next_yield >= self._next_claim)
                if done:
                    self._cond.notify_all()
                    return
                has_work = bool(self._pending)
                if not has_work:
                    self._cond.wait(0.2)
                    continue
            picked = self._pick()
            if picked is None:
                continue
            idx, ref = picked
            try:
                slot, source = self._fetch_one(ref)
            except BaseException as e:  # noqa: BLE001 — surfaced in order
                slot, source = _Slot(error=e), None
            with self._cond:
                self._inflight.discard(idx)
                if self._closed:
                    slot.release(self._store_or_none())
                    return
                self._buffer[idx] = slot
                if source == "local":
                    self.blocks_local += 1
                elif source == "remote":
                    self.blocks_remote += 1
                self.fetch_order.append(idx)
                self._note_peak_locked()
                depth = len(self._buffer)
                self._cond.notify_all()
            if source is not None:
                _tm.counter_inc("ray_tpu_data_blocks_total",
                                tags={"consumer": self.consumer,
                                      "source": source})
                _tm.gauge_set("ray_tpu_data_prefetch_depth_blocks", depth,
                              tags={"consumer": self.consumer})

    def _store_or_none(self):
        try:
            return self._worker().store
        except Exception:
            return None

    # ------------------------------------------------------------ fetching

    def _fetch_one(self, ref) -> tuple[_Slot, str]:
        """Materialize one block's serialized bytes locally, under the
        unified retry policy. Returns (slot, "local"|"remote")."""
        from ray_tpu._private.retry import RetryPolicy

        policy = RetryPolicy.from_config()
        return policy.run(
            lambda timeout: self._fetch_once(ref, timeout),
            method="data_block_fetch",
            retry_on=(DataFetchDropped, TimeoutError, ConnectionError,
                      OSError))

    def _fetch_once(self, ref, timeout) -> tuple[_Slot, str]:
        if _fi.ACTIVE is not None:
            plan = _fi.ACTIVE.on_send("data_block_fetch")
            if plan is not None:
                if plan.delay_s:
                    time.sleep(plan.delay_s)
                if plan.drop or plan.disconnect:
                    raise DataFetchDropped(
                        f"injected drop fetching block {ref.hex()}")
        w = self._worker()
        if w is None:
            # no core worker in this process (ray:// client mode): the
            # proxied get is the only fetch path — no staging, no pins
            import ray_tpu

            return _Slot(value=ray_tpu.get(ref, timeout=timeout)), "remote"
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            data = w.memory_store.get_nowait(ref.id)
            if data is not None:
                return self._stage(w, data), "local"
            if ref.id not in w._ref_to_task:
                buf = w.store.get(ref.id)
                if buf is not None:
                    if hasattr(buf, "view"):
                        # spill-backed host buffer: its memoryview keeps
                        # the backing alive, nothing to pin
                        return _Slot(data=buf.view()), "local"
                    return _Slot(pin=buf), "local"
                # not on this node: one bounded remote resolution round
                remaining = (None if deadline is None
                             else max(0.1, deadline - time.monotonic()))
                data = w._fetch_bytes(ref, remaining)
                # the pull caches big objects into local shm — prefer a
                # pinned zero-copy view over the heap copy it returned
                buf = w.store.get(ref.id)
                if buf is not None and not hasattr(buf, "view"):
                    return _Slot(pin=buf), "remote"
                return self._stage(w, data), "remote"
            # our own producing task is still running: wait on the owner
            # memory-store future like _fetch_bytes does
            entry = w.memory_store.entry(ref.id)
            entry.event.wait(0.05)
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(
                    f"streaming fetch timed out for block {ref.hex()}")

    def _stage(self, w, data) -> _Slot:
        """Heap bytes → shm-staged pin via put_ephemeral when big enough
        (bounded heap while buffered; zero-copy view back out). Store
        pressure falls back to holding the heap bytes."""
        try:
            if len(data) >= STAGE_MIN_BYTES:
                from ray_tpu._private import memory_anatomy as _ma

                stage_id = _mint_stage_id()
                with _ma.tagged("data_staging", owner=self.consumer):
                    w.store.put_ephemeral(stage_id, [data])
                pin = w.store.get(stage_id)
                if pin is not None and not hasattr(pin, "view"):
                    return _Slot(pin=pin, stage_id=stage_id)
                w.store.delete_ephemeral(stage_id)
        except Exception:
            pass
        return _Slot(data=data)

    # ----------------------------------------------------------- consuming

    def start(self):
        if self._started:
            return self
        self._started = True
        self._refill()
        for t in self._threads:
            t.start()
        return self

    def iter_blocks(self):
        """Yield deserialized blocks in dataset order. Closing the
        generator (or exhausting it) releases every buffered pin."""
        from ray_tpu._private import serialization as ser

        self.start()
        try:
            while True:
                with self._cond:
                    while True:
                        slot = self._buffer.pop(self._next_yield, None)
                        if slot is not None:
                            self._next_yield += 1
                            self._cond.notify_all()
                            break
                        if (self._exhausted and not self._pending
                                and not self._inflight
                                and self._next_yield >= self._next_claim):
                            return
                        if self._closed:
                            return
                        self._cond.wait(0.5)
                _tm.gauge_set("ray_tpu_data_prefetch_depth_blocks",
                              len(self._buffer),
                              tags={"consumer": self.consumer})
                # refill NOW (not after the yield): the freed budget slot
                # starts its fetch while the caller is still computing on
                # the previous batch
                self._refill()
                if slot.error is not None:
                    err = slot.error
                    slot.release(self._store_or_none())
                    raise err
                if slot.value is not _NO_VALUE:
                    yield slot.value
                    continue
                try:
                    # one copy out of the pinned store view, exactly like
                    # the legacy get path (deserialize may keep zero-copy
                    # numpy views of the input, so the input must outlive
                    # the block — heap bytes do, a released pin may not)
                    view = slot.view()
                    data = bytes(view) if slot.pin is not None else view
                finally:
                    slot.release(self._store_or_none())
                value, meta = ser.deserialize(data, self._worker(),
                                              with_meta=True)
                if meta.get("raised") and isinstance(value, BaseException):
                    raise value
                yield value
        finally:
            self.close()

    def close(self):
        with self._cond:
            if self._closed:
                return
            self._closed = True
            slots = list(self._buffer.values())
            self._buffer.clear()
            self._pending.clear()
            self._cond.notify_all()
        store = self._store_or_none()
        for slot in slots:
            slot.release(store)

    def stats(self) -> dict:
        with self._cond:
            return {
                "consumer": self.consumer,
                "budget": self._budget,
                "peak_buffered_blocks": self.peak_buffered_blocks,
                "blocks_local": self.blocks_local,
                "blocks_remote": self.blocks_remote,
                "consumed": self._next_yield,
                "buffered": len(self._buffer),
            }

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
