"""Distributed Dataset — columnar blocks as object-store refs, lazy plan.

Reference: python/ray/data/dataset.py:138 (Dataset), data/block.py (Block),
_internal/plan.py:46 (ExecutionPlan + Stage), _internal/compute.py:58,173
(TaskPoolStrategy / ActorPoolStrategy), _internal/push_based_shuffle.py,
_internal/sort.py.

Design: a Dataset is a list of block refs plus a chain of not-yet-executed
stages. Blocks are columnar (np.ndarray or dict[str, np.ndarray] — see
data/block.py) with list-of-rows as the ragged-data fallback; map-like
stages fuse and execute one task per block, shuffle partitions blocks with
vectorized numpy index math (no per-row Python on the hot path). TPU-native
additions: `iter_batches(..., device_put=True)` slices batches straight out
of columnar blocks and prefetches the next batch to the chip while the
current one is consumed — the host→HBM feed pipeline that replaces the
reference's `to_torch` pin-memory path. `window()` gives the pipelined
execution of the reference's DatasetPipeline (data/dataset_pipeline.py).
"""
from __future__ import annotations

import builtins
import os
import random as _random

import numpy as np

import ray_tpu
from ray_tpu.data import block as B


def _exec_chain(stages, block):
    for fn in stages:
        block = fn(block)
    return block


_chain_task = None


def _get_chain_task():
    global _chain_task
    if _chain_task is None:
        _chain_task = ray_tpu.remote(_exec_chain)
    return _chain_task


def _write_block(stages, block, write_one, out_path):
    # Runs on the WORKER: create the directory there too — driver and
    # worker need a shared filesystem for distributed writes (same
    # assumption as the reference's local-filesystem datasource; use a
    # network mount for multi-host clusters).
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    write_one(_exec_chain(stages, block), out_path)
    return out_path


_write_task = None


def _get_write_task():
    global _write_task
    if _write_task is None:
        _write_task = ray_tpu.remote(_write_block)
    return _write_task


def _column_values(block, on) -> np.ndarray:
    """Extract the numeric column/values from a block, validating that
    `on` matches the block shape (silently ignoring a bogus column name
    would produce plausible-looking nonsense)."""
    if isinstance(block, dict):
        if on is None:
            raise ValueError(
                f"dataset has named columns {sorted(block)}; pass on=...")
        return np.asarray(block[on], dtype=np.float64)
    if isinstance(block, np.ndarray):
        if on is not None:
            raise ValueError(
                f"on={on!r} given but the dataset has plain values, "
                f"not named columns")
        return block.astype(np.float64, copy=False)
    rows = _rows(block)
    if rows and isinstance(rows[0], dict):
        if on is None:
            raise ValueError(
                f"dataset has named columns {sorted(rows[0])}; pass on=...")
        return np.asarray([row[on] for row in rows], dtype=np.float64)
    if on is not None:
        raise ValueError(
            f"on={on!r} given but the dataset has plain values, "
            f"not named columns")
    return np.asarray(rows, dtype=np.float64)


def _agg_block(stages, block, on):
    """(count, sum, min, max, mean, M2) for one block's column/values —
    M2 = sum((x-mean)^2), so variance merges with Chan's algorithm
    instead of the cancellation-prone sum-of-squares; None for an empty
    block."""
    vals = _column_values(_exec_chain(stages, block), on)
    if vals.size == 0:
        return None
    mean = float(vals.mean())
    return (int(vals.size), float(vals.sum()), float(vals.min()),
            float(vals.max()), mean, float(np.square(vals - mean).sum()))


_agg_task = None


def _get_agg_task():
    global _agg_task
    if _agg_task is None:
        _agg_task = ray_tpu.remote(_agg_block)
    return _agg_task


class _ActorPoolStrategy:
    """(reference: compute.py:173 ActorPoolStrategy) map stages run on a
    pool of long-lived actors — amortizes heavyweight per-process state
    (e.g. a compiled jax program or loaded model) across blocks.

    With min_size < max_size the pool is sized to the workload when the
    dataset materializes: min(max_size, max(min_size, n_blocks)) actors —
    a small job doesn't pay for max_size actor startups, a large one is
    capped (the work-bound sizing of the reference's autoscaling pool;
    mid-execution scale-up is not implemented)."""

    def __init__(self, size: int | None = None, *, min_size: int = 2,
                 max_size: int | None = None):
        if size is not None:
            min_size = max_size = size
        if max_size is not None and max_size < min_size:
            raise ValueError(
                f"max_size={max_size} < min_size={min_size}")
        self.min_size = max(1, min_size)
        self.max_size = max_size or self.min_size

    @property
    def size(self):
        return self.max_size


def ActorPoolStrategy(size: int | None = None, *, min_size: int = 2,
                      max_size: int | None = None):
    return _ActorPoolStrategy(size, min_size=min_size, max_size=max_size)


class _BlockWorker:
    """Actor body for ActorPoolStrategy."""

    def apply(self, stages, block):
        return _exec_chain(stages, block)


class Dataset:
    def __init__(self, block_refs: list, stages: list | None = None,
                 compute=None):
        self._block_refs = list(block_refs)
        self._stages = list(stages or [])
        self._compute = compute   # default strategy for materialize()
        # Objects that must outlive this dataset's in-flight tasks but are
        # referenced only inside pickled closures (invisible to the
        # owner-based ref counter) — e.g. BatchPredictor's checkpoint ref.
        # Every Dataset derived from this one (via _derive) carries them
        # (advisor finding).
        self._keep_alive: tuple = ()

    def _pin(self, obj) -> "Dataset":
        self._keep_alive = self._keep_alive + (obj,)
        return self

    def _derive(self, block_refs, stages=None, compute=None,
                extra_pins=()) -> "Dataset":
        """Construct a Dataset downstream of this one, carrying the pins:
        the new blocks may be futures of tasks whose closures still need
        the pinned objects."""
        out = Dataset(block_refs, stages, compute=compute)
        out._keep_alive = self._keep_alive + tuple(extra_pins)
        return out

    # ------------------------------------------------------------ plan

    def _with_stage(self, fn, compute=None) -> "Dataset":
        return self._derive(self._block_refs, self._stages + [fn],
                            compute=compute or self._compute)

    def materialize(self, compute=None) -> "Dataset":
        """Execute pending stages: one task per block (TaskPoolStrategy) or
        a round-robin actor pool (ActorPoolStrategy)."""
        if not self._stages:
            return self
        stages = self._stages
        compute = compute if compute is not None else self._compute
        if isinstance(compute, _ActorPoolStrategy):
            worker_cls = ray_tpu.remote(_BlockWorker)
            n_blocks = len(self._block_refs)
            # work-bound sizing within [min_size, max_size]
            n_actors = min(compute.max_size,
                           max(compute.min_size, n_blocks))
            pool = [worker_cls.remote()
                    for _ in builtins.range(n_actors)]
            refs = [
                pool[i % len(pool)].apply.remote(stages, ref)
                for i, ref in enumerate(self._block_refs)
            ]
        else:
            task = _get_chain_task()
            refs = [task.remote(stages, ref) for ref in self._block_refs]
        return self._derive(refs)

    def _materialized_refs(self, compute=None):
        return self.materialize(compute)._block_refs

    def blocks(self) -> list:
        return [ray_tpu.get(r) for r in self._materialized_refs()]

    @property
    def num_blocks(self) -> int:
        return len(self._block_refs)

    # ------------------------------------------------------- transforms

    def map(self, fn) -> "Dataset":
        return self._with_stage(
            lambda block: B.columnarize([fn(row) for row in _rows(block)]))

    def flat_map(self, fn) -> "Dataset":
        return self._with_stage(
            lambda block: B.columnarize(
                [out for row in _rows(block) for out in fn(row)]))

    def filter(self, fn) -> "Dataset":
        return self._with_stage(
            lambda block: B.columnarize(
                [row for row in _rows(block) if fn(row)]))

    def map_batches(self, fn, *, batch_format: str = "auto",
                    compute=None) -> "Dataset":
        """fn: block -> block (numpy array in → numpy array out when the
        block is an array; list otherwise). `compute=ActorPoolStrategy(...)`
        runs this (and later) stages on a long-lived actor pool when the
        dataset materializes (reference: dataset.py:322 map_batches)."""
        return self._with_stage(fn, compute=compute)

    def repartition(self, num_blocks: int) -> "Dataset":
        """Re-block via remote tasks over block refs — the driver only
        sees per-block row counts, never rows (the old implementation
        pulled the whole dataset through ``take_all()``)."""
        from ray_tpu.data._internal.streaming import reblock

        refs = self._materialized_refs()
        return self._derive(reblock.repartition_refs(refs, num_blocks))

    def random_shuffle(self, *, seed: int | None = None) -> "Dataset":
        """Push-based two-stage shuffle (reference:
        _internal/push_based_shuffle.py): map tasks split each block into
        N random partitions; reduce tasks concatenate partition i of every
        block. All intermediate partitions live in the object store.
        Columnar blocks partition with one numpy permutation + array
        indexing per block — no per-row Python.

        With ``RAY_TPU_DATA_SHUFFLE_COLLECTIVE=1`` the partition
        exchange instead rides the pipelined host-collective plane (an
        actor gang doing the all-to-all over one-way segment frames);
        identical rows per seed, falls back here on any failure."""
        if not self._block_refs:
            return self   # zero-block dataset: nothing to permute
        n = max(1, self.num_blocks)
        seed_base = seed if seed is not None else _random.randrange(2**31)

        from ray_tpu.data._internal.streaming import shuffle as _shuf

        if _shuf.shuffle_collective_enabled() and n >= 2:
            try:
                refs = _shuf.shuffle_via_collective(self, seed_base)
                if refs is not None:
                    return self._derive(refs)
            except Exception:
                pass   # gang/exchange failure: task-based path below

        @ray_tpu.remote(num_returns=n)
        def shuffle_map(stages, block, block_idx):
            block = _exec_chain(stages, block)
            rows_n = B.num_rows(block)
            rng = np.random.default_rng(seed_base + block_idx)
            perm = rng.permutation(rows_n)
            parts = [B.take_indices(block, idx)
                     for idx in np.array_split(perm, n)]
            return tuple(parts) if n > 1 else parts[0]

        @ray_tpu.remote
        def shuffle_reduce(reduce_idx, *parts):
            merged = B.concat_blocks(list(parts))
            rng = np.random.default_rng((seed_base ^ 0x5EED) + reduce_idx)
            return B.take_indices(merged, rng.permutation(B.num_rows(merged)))

        stages = self._stages
        part_refs = [shuffle_map.remote(stages, ref, i)
                     for i, ref in enumerate(self._block_refs)]
        if n == 1:
            part_refs = [[r] for r in part_refs]
        reduced = [
            shuffle_reduce.remote(
                i, *[part_refs[b][i] for b in builtins.range(n)])
            for i in builtins.range(n)
        ]
        return self._derive(reduced)

    def sort(self, key=None, descending: bool = False) -> "Dataset":
        """Sample-partition-sort (reference: _internal/sort.py): sample
        boundaries, range-partition blocks, sort each range."""
        keyfn = key if callable(key) else (
            (lambda row: row[key]) if key is not None else (lambda row: row))
        n = max(1, self.num_blocks)
        refs = self._materialized_refs()
        if n == 1:
            block = ray_tpu.get(refs[0])
            rows = sorted(_rows(block), key=keyfn, reverse=descending)
            return from_items(rows, parallelism=1)
        # boundary sampling on the driver (small sample per block)
        samples = []
        for ref in refs:
            rows = _rows(ray_tpu.get(ref))
            step = max(1, len(rows) // 8)
            samples.extend(keyfn(r) for r in rows[::step])
        samples.sort()
        bounds = [samples[int(len(samples) * (i + 1) / n)]
                  for i in builtins.range(n - 1)] if samples else []

        @ray_tpu.remote(num_returns=n)
        def range_partition(block):
            import bisect

            parts = [[] for _ in builtins.range(n)]
            for row in _rows(block):
                parts[bisect.bisect_left(bounds, keyfn(row))].append(row)
            return tuple(parts)

        @ray_tpu.remote
        def sort_merge(*parts):
            rows = [row for part in parts for row in part]
            return sorted(rows, key=keyfn, reverse=descending)

        part_refs = [range_partition.remote(ref) for ref in refs]
        ordered = [
            sort_merge.remote(*[part_refs[b][i] for b in builtins.range(n)])
            for i in builtins.range(n)
        ]
        if descending:
            ordered = ordered[::-1]
        return self._derive(ordered)

    def union(self, other: "Dataset") -> "Dataset":
        return self._derive(self._materialized_refs()
                            + other._materialized_refs(),
                            extra_pins=other._keep_alive)

    def zip(self, other: "Dataset") -> "Dataset":
        """Pair rows of two datasets (truncating to the shorter) via
        remote zip tasks over both sides' block refs — rows never land
        on the driver."""
        from ray_tpu.data._internal.streaming import reblock

        refs = reblock.zip_refs(self._materialized_refs(),
                                other._materialized_refs(),
                                self.num_blocks)
        return self._derive(refs, extra_pins=other._keep_alive)

    def split(self, n: int, *, equal: bool = True) -> list["Dataset"]:
        """Shard for per-worker consumption (reference: dataset.py split;
        used by Train's dataset_spec). The uneven case re-blocks with
        remote slice/concat tasks instead of driver ``take_all()``."""
        from ray_tpu.data._internal.streaming import reblock

        refs = self._materialized_refs()
        if len(refs) >= n and len(refs) % n == 0:
            per = len(refs) // n
            return [self._derive(refs[i * per:(i + 1) * per])
                    for i in builtins.range(n)]
        return [self._derive(shard)
                for shard in reblock.split_refs_uneven(refs, n)]

    def groupby(self, key) -> "GroupedDataset":
        return GroupedDataset(self, key)

    def window(self, *, blocks_per_window: int = 2) -> "DatasetPipeline":
        """Windowed pipelined execution (reference:
        data/dataset_pipeline.py): stages of window i+1 execute while
        window i is consumed."""
        from ray_tpu.data.dataset_pipeline import DatasetPipeline

        windows = []
        refs = self._block_refs
        for i in builtins.range(0, len(refs), blocks_per_window):
            windows.append(self._derive(refs[i:i + blocks_per_window],
                                        self._stages))
        return DatasetPipeline(windows)

    def repeat(self, times: int | None = None) -> "DatasetPipeline":
        """Epoch loop as a pipeline (reference: dataset.py repeat)."""
        from ray_tpu.data.dataset_pipeline import DatasetPipeline

        if times is None:
            return DatasetPipeline([self], loop=True)
        return DatasetPipeline([self] * times, loop=False)

    # ------------------------------------------------------ consumption

    def take(self, limit: int = 20) -> list:
        out = []
        for ref in self._materialized_refs():
            out.extend(_rows(ray_tpu.get(ref)))
            if len(out) >= limit:
                return out[:limit]
        return out

    def take_all(self) -> list:
        out = []
        for block in self.blocks():
            out.extend(_rows(block))
        return out

    def count(self) -> int:
        counter = ray_tpu.remote(lambda stages, b: len(_rows(
            _exec_chain(stages, b))))
        return sum(ray_tpu.get([counter.remote(self._stages, r)
                                for r in self._block_refs]))

    def show(self, limit: int = 20):
        for row in self.take(limit):
            print(row)

    def schema(self):
        first = self.take(1)
        if not first:
            return None
        row = first[0]
        if isinstance(row, dict):
            return {k: type(v).__name__ for k, v in row.items()}
        return type(row).__name__

    def iter_rows(self):
        for ref in self._materialized_refs():
            yield from _rows(ray_tpu.get(ref))

    def iter_batches(self, *, batch_size: int = 256,
                     batch_format: str = "numpy",
                     device_put: bool = False, drop_last: bool = False):
        """Batched streaming iteration (data/_internal/streaming/): map
        tasks run on demand under a bounded prefetch budget
        (``RAY_TPU_DATA_PREFETCH_BLOCKS``), blocks stage zero-copy in the
        shm store with per-consumer backpressure, and with device_put a
        double-buffer thread overlaps fetch + slice + ``jax.device_put``
        of batch k+1 with the caller consuming batch k (the TPU host→HBM
        feed pipeline). Per-batch consumer wait lands in
        ``ray_tpu_data_wait_seconds{consumer}``."""
        from ray_tpu.data._internal.streaming import iterator as _si

        return _si.dataset_iter_batches(
            self, batch_size=batch_size, batch_format=batch_format,
            device_put=device_put, drop_last=drop_last)

    def to_numpy(self) -> np.ndarray:
        return _rows_to_numpy(self.take_all())

    def to_pandas(self):
        import pandas as pd

        rows = self.take_all()
        if rows and isinstance(rows[0], dict):
            return pd.DataFrame(rows)
        return pd.DataFrame({"value": rows})

    def to_arrow(self):
        """Materialize as a single pyarrow Table (reference:
        dataset.py to_arrow_refs)."""
        import pyarrow as pa

        rows = self.take_all()
        if rows and isinstance(rows[0], dict):
            # from_pylist unions keys across rows (missing values → null),
            # matching to_pandas()'s NaN-fill behavior
            return pa.Table.from_pylist(rows)
        return pa.table({"value": rows})

    def iter_torch_batches(self, *, batch_size: int = 256,
                           drop_last: bool = False, device=None,
                           dtypes=None):
        """Batched iteration yielding torch tensors (reference:
        dataset.py iter_torch_batches / to_torch at :2770 — the pin-memory
        GPU feed; on this framework the TPU path is
        ``iter_batches(device_put=True)``, torch output serves CPU-side
        models and interop)."""
        import torch

        for batch in self.iter_batches(batch_size=batch_size,
                                       batch_format="numpy",
                                       drop_last=drop_last):
            def convert(a):
                t = torch.as_tensor(np.ascontiguousarray(a))
                if dtypes is not None:
                    t = t.to(dtypes)
                if device is not None:
                    t = t.to(device)
                return t

            if isinstance(batch, dict):
                yield {k: convert(v) for k, v in batch.items()}
            else:
                yield convert(batch)

    def to_torch(self, *, label_column: str | None = None,
                 batch_size: int = 256, drop_last: bool = False):
        """Iterable of (features, label) torch pairs when label_column is
        given, else an iterable of feature tensors/dicts (reference:
        dataset.py to_torch)."""
        for batch in self.iter_torch_batches(batch_size=batch_size,
                                             drop_last=drop_last):
            if label_column is None:
                yield batch
            else:
                if not isinstance(batch, dict):
                    raise ValueError(
                        "label_column requires dict (columnar) rows; this "
                        "dataset yields plain arrays")
                label = batch.pop(label_column)
                yield batch, label

    def to_random_access_dataset(self, key: str, *,
                                 num_workers: int = 2):
        """Distributed key→row point-lookup index over this dataset
        (reference: random_access_dataset.py:23): sorted by `key`,
        partitioned across serving actors, O(log n) gets."""
        from ray_tpu.data.random_access import RandomAccessDataset

        return RandomAccessDataset(self, key, num_workers=num_workers)

    def to_tf(self, *, feature_columns=None, label_columns=None,
              batch_size: int = 256, drop_last: bool = False):
        """tf.data.Dataset over this dataset's batches (reference:
        dataset.py:2959 to_tf). Columnar batches become (features,
        labels) tensor tuples when label_columns is given, else feature
        dicts; shapes/dtypes are inferred from the first batch so
        tf.data gets a full output_signature (None leading dim)."""
        import tensorflow as tf

        # Infer the signature from the first batch WITHOUT recomputing
        # it: the partially-consumed iterator continues on the first
        # epoch, later epochs iterate fresh.
        it0 = self.iter_batches(batch_size=batch_size,
                                batch_format="numpy",
                                drop_last=drop_last)
        first_batch = next(iter(it0), None)
        if first_batch is None:
            raise ValueError("to_tf on an empty dataset")
        first = self._tf_split(first_batch, feature_columns,
                               label_columns)
        leftover = [it0]

        def gen():
            if leftover:
                rest = leftover.pop()
                yield first
                for batch in rest:
                    yield self._tf_split(batch, feature_columns,
                                         label_columns)
                return
            for batch in self.iter_batches(batch_size=batch_size,
                                           batch_format="numpy",
                                           drop_last=drop_last):
                yield self._tf_split(batch, feature_columns,
                                     label_columns)

        def sig_of(x):
            if isinstance(x, dict):
                return {k: sig_of(v) for k, v in x.items()}
            return tf.TensorSpec(shape=(None,) + x.shape[1:],
                                 dtype=tf.as_dtype(x.dtype))

        signature = (sig_of(first) if not isinstance(first, tuple)
                     else tuple(sig_of(p) for p in first))
        return tf.data.Dataset.from_generator(
            gen, output_signature=signature)

    @staticmethod
    def _tf_split(batch, feature_columns, label_columns):
        if not isinstance(batch, dict):
            return batch
        if label_columns is None:
            if feature_columns is not None:
                return {k: batch[k] for k in feature_columns}
            return batch
        labels = ({k: batch[k] for k in label_columns}
                  if not isinstance(label_columns, str)
                  else batch[label_columns])
        feats = (feature_columns if feature_columns is not None
                 else [k for k in batch
                       if (k != label_columns
                           if isinstance(label_columns, str)
                           else k not in label_columns)])
        features = {k: batch[k] for k in feats}
        if len(features) == 1:
            features = next(iter(features.values()))
        return features, labels

    def _write_blocks(self, path: str, ext: str, write_one):
        """One output file per block, written by remote tasks (reference:
        data/datasource/file_based_datasource.py write path). One cached
        remote task takes write_one as an argument — the _get_chain_task
        pattern — so repeated write calls reuse a submitter instead of
        registering a fresh closure per call."""
        import os as _os

        _os.makedirs(path, exist_ok=True)
        task = _get_write_task()
        return ray_tpu.get([
            task.remote(self._stages, ref, write_one,
                        _os.path.join(path, f"part-{i:05d}.{ext}"))
            for i, ref in enumerate(self._block_refs)])

    def write_parquet(self, path: str) -> list:
        # pyarrow directly — NOT pandas: constructing a DataFrame (whose
        # Index uses pyarrow-backed strings in this pandas build) on the
        # worker's RPC dispatch threads segfaults intermittently inside
        # pandas/pyarrow; pa.table from numpy columns avoids that path
        def write_one(block, out_path):
            import pyarrow.parquet as pq

            pq.write_table(_block_to_arrow_table(block), out_path)

        return self._write_blocks(path, "parquet", write_one)

    def write_csv(self, path: str) -> list:
        def write_one(block, out_path):
            import pyarrow.csv as pacsv

            pacsv.write_csv(_block_to_arrow_table(block), out_path)

        return self._write_blocks(path, "csv", write_one)

    def write_json(self, path: str) -> list:
        def write_one(block, out_path):
            import json as _json

            def plain(v):
                if isinstance(v, np.ndarray):
                    return v.tolist()
                if isinstance(v, np.generic):
                    return v.item()
                return v

            with open(out_path, "w") as f:
                for row in _rows(block):
                    if isinstance(row, dict):
                        row = {k: plain(v) for k, v in row.items()}
                    else:
                        row = plain(row)
                    f.write(_json.dumps(row) + "\n")

        return self._write_blocks(path, "json", write_one)

    def write_numpy(self, path: str, *, column: str | None = None) -> list:
        """One .npy file per block (reference:
        data/datasource/numpy_datasource.py write path). Columnar blocks
        need `column=` naming which array to save; plain-array blocks
        save directly."""
        def write_one(block, out_path):
            if isinstance(block, dict):
                if column is None:
                    raise ValueError(
                        f"dataset has named columns {sorted(block)}; "
                        f"pass column=...")
                np.save(out_path, np.asarray(block[column]))
            else:
                np.save(out_path, np.asarray(block))

        return self._write_blocks(path, "npy", write_one)

    def _numeric_partials(self, on=None):
        """Per-block (count, sum, min, max, mean, M2) partials via remote
        tasks; merged driver-side with Chan's parallel-variance algorithm
        (reference: dataset.py sum/mean/std over AggregateFn partials)."""
        task = _get_agg_task()
        parts = ray_tpu.get([task.remote(self._stages, ref, on)
                             for ref in self._block_refs])
        parts = [p for p in parts if p is not None]
        if not parts:
            raise ValueError("aggregation over an empty dataset")
        count, total, mn, mx, mean, m2 = parts[0]
        for n_b, tot_b, mn_b, mx_b, mean_b, m2_b in parts[1:]:
            delta = mean_b - mean
            merged = count + n_b
            mean = mean + delta * n_b / merged
            m2 = m2 + m2_b + delta * delta * count * n_b / merged
            count, total = merged, total + tot_b
            mn, mx = min(mn, mn_b), max(mx, mx_b)
        return count, total, mn, mx, mean, m2

    def sum(self, on=None) -> float:  # noqa: A003
        return self._numeric_partials(on)[1]

    def mean(self, on=None) -> float:
        count, total, *_ = self._numeric_partials(on)
        return total / count

    def min(self, on=None) -> float:  # noqa: A003
        return self._numeric_partials(on)[2]

    def max(self, on=None) -> float:  # noqa: A003
        return self._numeric_partials(on)[3]

    def std(self, on=None, ddof: int = 1) -> float:
        count, _, _, _, _, m2 = self._numeric_partials(on)
        if count <= ddof:
            return 0.0
        return float(np.sqrt(m2 / (count - ddof)))

    def stats(self) -> dict:
        sizes = ray_tpu.get([
            _get_chain_task().remote(
                self._stages + [lambda b: len(_rows(b))], r)
            for r in self._block_refs])
        return {"num_blocks": len(sizes), "block_sizes": sizes,
                "num_rows": sum(sizes)}

    def __repr__(self):
        return (f"Dataset(num_blocks={self.num_blocks}, "
                f"pending_stages={len(self._stages)})")


class GroupedDataset:
    """(reference: data/grouped_dataset.py) distributed hash-partition by
    key, then per-group aggregation inside reduce tasks — group data never
    lands on the driver."""

    def __init__(self, ds: Dataset, key):
        self.ds = ds
        self.keyfn = key if callable(key) else (lambda row: row[key])

    def _reduce(self, per_groups_fn) -> Dataset:
        """Two-stage: map tasks hash-partition each block's rows; reduce
        task i groups partition i of every block and applies
        per_groups_fn(groups_dict) -> rows."""
        ds = self.ds
        keyfn = self.keyfn
        n = max(1, ds.num_blocks)

        @ray_tpu.remote(num_returns=n)
        def part_map(stages, blk):
            import zlib

            rows = _rows(_exec_chain(stages, blk))
            parts = [[] for _ in builtins.range(n)]
            for row in rows:
                # stable hash: builtin hash() is salted per process, and the
                # map tasks run in different workers
                h = zlib.crc32(str(keyfn(row)).encode())
                parts[h % n].append(row)
            return tuple(parts) if n > 1 else parts[0]

        @ray_tpu.remote
        def part_reduce(*parts):
            groups: dict = {}
            for part in parts:
                for row in part:
                    groups.setdefault(keyfn(row), []).append(row)
            return B.columnarize(per_groups_fn(groups))

        part_refs = [part_map.remote(ds._stages, ref)
                     for ref in ds._block_refs]
        if n == 1:
            part_refs = [[r] for r in part_refs]
        reduced = [
            part_reduce.remote(*[part_refs[b][i]
                                 for b in builtins.range(n)])
            for i in builtins.range(n)
        ]
        return ds._derive(reduced)

    def count(self) -> Dataset:
        return self._reduce(lambda groups: [
            {"key": k, "count": len(v)} for k, v in groups.items()])

    def aggregate(self, agg_fn) -> Dataset:
        return self._reduce(lambda groups: [
            {"key": k, "value": agg_fn(v)} for k, v in groups.items()])

    def map_groups(self, fn) -> Dataset:
        return self._reduce(lambda groups: [
            out for _, v in groups.items() for out in fn(v)])

    def _column_agg(self, on, combine, out_name: str) -> Dataset:
        """Per-group column aggregation (reference: grouped_dataset.py
        sum/mean/min/max)."""
        def agg(groups):
            out = []
            for k, rows in groups.items():
                if rows and not isinstance(rows[0], dict):
                    raise ValueError(
                        f"on={on!r} given but grouped rows are plain "
                        f"values, not named columns")
                vals = [row[on] for row in rows]
                out.append({"key": k, out_name: combine(vals)})
            return out

        return self._reduce(agg)

    def sum(self, on) -> Dataset:  # noqa: A003
        return self._column_agg(on, lambda v: float(np.sum(v)), f"sum({on})")

    def mean(self, on) -> Dataset:
        return self._column_agg(on, lambda v: float(np.mean(v)),
                                f"mean({on})")

    def min(self, on) -> Dataset:  # noqa: A003
        return self._column_agg(on, lambda v: float(np.min(v)), f"min({on})")

    def max(self, on) -> Dataset:  # noqa: A003
        return self._column_agg(on, lambda v: float(np.max(v)), f"max({on})")


# -------------------------------------------------------------- block utils

def _block_to_arrow_table(block):
    import pyarrow as pa

    def col(a):
        arr = np.asarray(a)
        if arr.ndim > 1:
            return pa.array(arr.tolist())   # nested lists per row
        return pa.array(arr)

    if isinstance(block, dict):
        return pa.table({k: col(v) for k, v in block.items()})
    if isinstance(block, np.ndarray):
        return pa.table({"value": col(block)})
    rows = _rows(block)
    if rows and isinstance(rows[0], dict):
        return pa.Table.from_pylist(rows)
    return pa.table({"value": pa.array(rows)})


def _rows(block) -> list:
    return B.to_rows(block)


def _rows_to_numpy(rows):
    if rows and isinstance(rows[0], dict):
        return {k: np.asarray([r[k] for r in rows]) for k in rows[0]}
    return np.asarray(rows)


# -------------------------------------------------------------- constructors

def from_items(items: list, *, parallelism: int = 8) -> Dataset:
    items = list(items)
    n = max(1, min(parallelism, len(items) or 1))
    chunk = (len(items) + n - 1) // n
    refs = [ray_tpu.put(B.columnarize(items[i * chunk:(i + 1) * chunk]))
            for i in builtins.range(n)]
    return Dataset(refs)


def range(n: int, *, parallelism: int = 8) -> Dataset:  # noqa: A001
    return from_items(list(builtins.range(n)), parallelism=parallelism)


def from_numpy(arr: np.ndarray, *, parallelism: int = 8) -> Dataset:
    chunks = np.array_split(arr, max(1, parallelism))
    return Dataset([ray_tpu.put(c) for c in chunks if len(c)])


def from_pandas(df, *, parallelism: int = 4) -> Dataset:
    n = max(1, parallelism)
    size = (len(df) + n - 1) // n
    refs = [ray_tpu.put(df.iloc[i * size:(i + 1) * size])
            for i in builtins.range(n) if i * size < len(df)]
    return Dataset(refs)


def read_csv(paths, *, parallelism: int = 4,
             chunk_rows: int = 200_000) -> Dataset:
    """Distributed read: one task per file, one block per `chunk_rows`
    rows. The block count per file is unknown until the file is read, so
    each task streams blocks out through ``num_returns="dynamic"``
    (reference: data/read_api.py read tasks produce a dynamic block
    count per file via ObjectRefGenerator, _raylet.pyx:168)."""
    if isinstance(paths, str):
        paths = [paths]

    @ray_tpu.remote(num_returns="dynamic")
    def _read_csv_file(path, rows):
        import pandas as pd

        for chunk in pd.read_csv(path, chunksize=rows):
            yield chunk

    gens = [_read_csv_file.remote(p, chunk_rows) for p in paths]
    refs = []
    for g in gens:
        refs.extend(ray_tpu.get(g))
    return Dataset(refs)


def read_json(paths) -> Dataset:
    import json

    if isinstance(paths, str):
        paths = [paths]
    rows = []
    for p in paths:
        with open(p) as f:
            for line in f:
                line = line.strip()
                if line:
                    rows.append(json.loads(line))
    return from_items(rows)


def read_parquet(paths, *, parallelism: int = 4) -> Dataset:
    """Distributed read: one task per file, one block per row group —
    the block count only exists after the footer is open, which is
    exactly the ``num_returns="dynamic"`` shape (reference:
    data/read_api.py + _raylet.pyx:168)."""
    if isinstance(paths, str):
        paths = [paths]

    @ray_tpu.remote(num_returns="dynamic")
    def _read_parquet_file(path):
        import pyarrow.parquet as pq

        f = pq.ParquetFile(path)
        for rg in builtins.range(f.num_row_groups):
            t = f.read_row_group(rg)
            yield {name: t.column(name).to_numpy(zero_copy_only=False)
                   for name in t.column_names}

    gens = [_read_parquet_file.remote(p) for p in paths]
    refs = []
    for g in gens:
        refs.extend(ray_tpu.get(g))
    return Dataset(refs)


def _chunk_list(items: list, parallelism: int) -> list[list]:
    """Split items into at most `parallelism` contiguous non-empty
    chunks (the shared fan-out shape of the file readers)."""
    n = max(1, min(parallelism, len(items) or 1))
    chunk = (len(items) + n - 1) // n
    return [items[i * chunk:(i + 1) * chunk]
            for i in builtins.range(n) if items[i * chunk:(i + 1) * chunk]]


def read_numpy(paths, *, parallelism: int = 4) -> Dataset:
    """.npy files loaded by remote tasks, one block per file but at
    most `parallelism` tasks (reference:
    data/datasource/numpy_datasource.py)."""
    if isinstance(paths, str):
        paths = [paths]

    @ray_tpu.remote(num_returns="dynamic")
    def _load(batch):
        for p in batch:
            yield np.load(p)

    refs = []
    for gen in [_load.remote(b) for b in _chunk_list(paths, parallelism)]:
        refs.extend(ray_tpu.get(gen))
    return Dataset(refs)


def read_binary_files(paths, *, include_paths: bool = False,
                      parallelism: int = 4) -> Dataset:
    """Raw file bytes, one row per file (reference:
    data/datasource/binary_datasource.py). Rows are {"bytes": ...} (+
    {"path": ...} with include_paths) so downstream map stages see the
    same dict-row shape as other sources."""
    if isinstance(paths, str):
        paths = [paths]

    @ray_tpu.remote
    def _load(batch, with_paths):
        rows = []
        for p in batch:
            with open(p, "rb") as f:
                row = {"bytes": f.read()}
            if with_paths:
                row["path"] = p
            rows.append(row)
        return rows

    refs = [_load.remote(batch, include_paths)
            for batch in _chunk_list(paths, parallelism)]
    return Dataset(refs)


def read_images(paths, *, size: tuple | None = None,
                mode: str | None = None,
                include_paths: bool = False,
                parallelism: int = 4) -> Dataset:
    """Images → numpy arrays, decoded by remote tasks (reference:
    data/datasource/image_datasource.py — PIL decode, optional resize/
    mode convert). Rows are {"image": HxWxC uint8} (+ path)."""
    if isinstance(paths, str):
        paths = [paths]

    @ray_tpu.remote
    def _load(batch, sz, md, with_paths):
        from PIL import Image

        rows = []
        for p in batch:
            img = Image.open(p)
            if md is not None:
                img = img.convert(md)
            if sz is not None:
                img = img.resize(sz)
            row = {"image": np.asarray(img)}
            if with_paths:
                row["path"] = p
            rows.append(row)
        return rows

    refs = [_load.remote(batch, size, mode, include_paths)
            for batch in _chunk_list(paths, parallelism)]
    return Dataset(refs)


def read_text(paths) -> Dataset:
    if isinstance(paths, str):
        paths = [paths]
    rows = []
    for p in paths:
        with open(p) as f:
            rows.extend(line.rstrip("\n") for line in f)
    return from_items(rows)


def from_arrow(tables, *, parallelism: int = 4) -> Dataset:
    """pyarrow Table(s) → Dataset with one block per table (reference:
    data/read_api.py from_arrow). Columns land as numpy arrays — the
    columnar block format — so downstream batches slice without a row
    loop."""
    if not isinstance(tables, (list, tuple)):
        tables = [tables]
    refs = []
    per_table = max(1, parallelism // max(1, len(tables)))
    for t in tables:
        n = len(t)
        if n == 0:
            continue
        k = min(per_table, n)
        size = (n + k - 1) // k
        for start in builtins.range(0, n, size):
            piece = t.slice(start, size)
            cols = {name: piece.column(name).to_numpy(
                        zero_copy_only=False)
                    for name in piece.column_names}
            refs.append(ray_tpu.put(cols))
    return Dataset(refs)


