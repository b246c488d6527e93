"""DatasetPipeline — windowed, pipelined dataset execution.

Reference: python/ray/data/dataset_pipeline.py +
_internal/pipeline_executor.py. A pipeline is an ordered list of Dataset
windows; transforms apply per-window lazily, and consumption overlaps
stage execution: while window i's batches are being consumed, window i+1's
stage tasks are already submitted (its block refs are futures resolving in
the background). On TPU this composes with
`iter_batches(device_put=True)`'s batch lookahead: disk → host transform →
HBM all run concurrently.
"""
from __future__ import annotations

import itertools


class DatasetPipeline:
    def __init__(self, windows: list, loop: bool = False):
        self._windows = list(windows)
        self._loop = loop

    # ------------------------------------------------------------ transforms
    def _per_window(self, method: str, *args, **kwargs) -> "DatasetPipeline":
        return DatasetPipeline(
            [getattr(w, method)(*args, **kwargs) for w in self._windows],
            loop=self._loop)

    def map(self, fn):
        return self._per_window("map", fn)

    def map_batches(self, fn, **kw):
        return self._per_window("map_batches", fn, **kw)

    def filter(self, fn):
        return self._per_window("filter", fn)

    def flat_map(self, fn):
        return self._per_window("flat_map", fn)

    def random_shuffle_each_window(self, *, seed=None):
        return DatasetPipeline(
            [w.random_shuffle(seed=seed) for w in self._windows],
            loop=self._loop)

    def repeat(self, times: int | None = None) -> "DatasetPipeline":
        if times is None:
            return DatasetPipeline(self._windows, loop=True)
        return DatasetPipeline(self._windows * times, loop=False)

    # ----------------------------------------------------------- consumption
    def _window_iter(self):
        if self._loop:
            return itertools.cycle(self._windows)
        return iter(self._windows)

    def iter_datasets(self):
        """Yield materialized windows with one-window lookahead: the next
        window's stage tasks are submitted (async) before the current
        window is handed to the consumer."""
        it = self._window_iter()
        try:
            current = next(it).materialize()
        except StopIteration:
            return
        for upcoming in it:
            upcoming = upcoming.materialize()   # submits tasks, no blocking
            yield current
            current = upcoming
        yield current

    def iter_batches(self, *, batch_size: int = 256,
                     batch_format: str = "numpy",
                     device_put: bool = False, drop_last: bool = False):
        """One batch stream over ALL windows: the batch remainder is
        carried across window boundaries, so only the FINAL batch may be
        short (honoring ``drop_last``) — per-window batching used to emit
        a partial batch at every window edge. One bounded-prefetch
        executor runs across windows, so window i+1's stage tasks
        execute while window i's batches are consumed."""
        from ray_tpu.data._internal.streaming import iterator as _si

        return _si.pipeline_iter_batches(
            self, batch_size=batch_size, batch_format=batch_format,
            device_put=device_put, drop_last=drop_last)

    def iter_rows(self):
        for ds in self.iter_datasets():
            yield from ds.iter_rows()

    def take(self, limit: int = 20) -> list:
        out = []
        for row in self.iter_rows():
            out.append(row)
            if len(out) >= limit:
                break
        return out

    def count(self) -> int:
        if self._loop:
            raise ValueError("count() on an infinite (repeat()) pipeline")
        return sum(ds.count() for ds in self._windows)

    def num_windows(self) -> int:
        return len(self._windows)

    def __repr__(self):
        return (f"DatasetPipeline(windows={len(self._windows)}, "
                f"loop={self._loop})")
