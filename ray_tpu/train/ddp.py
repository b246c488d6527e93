"""Bucketed data-parallel gradient synchronization over the host
collective plane.

Training on this framework's host-DP path was compute-then-communicate:
run the whole backward, then one synchronous allreduce over the whole
grad pytree — the wire idles during compute, the TPU idles during comm.
This module hides one under the other ("Exploring the limits of
Concurrency in ML Training on Google TPUs", arXiv:2011.03641; the same
shape as torch DDP's gradient buckets / Horovod tensor fusion):

- the grad pytree is flattened in jax's canonical deterministic order
  and partitioned into size-targeted buckets
  (``RAY_TPU_TRAIN_GRAD_BUCKET_BYTES``, ~4 MiB default; planning
  depends only on shapes/dtypes, so every rank derives byte-identical
  buckets — ``parallel/sharding.plan_buckets``);
- each bucket's allreduce launches **asynchronously**
  (``collective.allreduce_async`` → the group's background issue
  thread) as soon as the bucket is packed, so bucket k's comm overlaps
  the device→host fetch + packing of bucket k+1, the unpacking of
  completed buckets, and whatever compute the caller runs before
  ``result()`` — including the next microbatch's forward when used via
  ``sync_gradients_async``;
- ``result()`` waits all handles at the optimizer boundary, stamping
  each bucket's *actually blocked* time (the comm the backward failed
  to hide) into the metric + step-anatomy planes.

Composition: the quantized wire (PR 8) and the intra-host hierarchy
apply per bucket unchanged (each bucket is an ordinary float32-sum
allreduce); a poisoned gang (PR 5) fails every pending handle fast
with ``CollectiveGroupError``.

Determinism contract (pinned in tests/test_zz_bucket_ddp.py): all
ranks always return byte-identical synced grads (the ring/pair
exchange guarantees it per op). Bucketed-on vs the
``RAY_TPU_TRAIN_BUCKET_DDP=0`` kill switch (legacy single synchronous
allreduce over the whole flattened tree) is additionally
**bit-identical at world size 2** on the exact wire: the pairwise
exchange reduces every element as one two-operand IEEE add, which is
commutative, so bucket boundaries cannot change results. At larger
world sizes the ring's per-chunk reduction order depends on chunk
boundaries, so on-vs-off agree within float reassociation rounding
(the same caveat as the collective hierarchy) while staying exactly
rank-consistent either way.
"""
from __future__ import annotations

import time

from ray_tpu._private import memory_anatomy as _ma
from ray_tpu._private import profiling as _prof
from ray_tpu._private import telemetry as _tm


def _get_config(name):
    from ray_tpu._private.config import get_config

    return get_config(name)


class PendingGradSync:
    """In-flight bucketed gradient sync: every bucket's async allreduce
    has been launched; ``result(timeout)`` waits them in launch order,
    unpacks, and returns the synced grad pytree. Work the caller does
    between launch and ``result()`` overlaps ALL of the comm."""

    def __init__(self, group: str, treedef, leaves, launched,
                 world: int, average: bool, rank: int | None = None):
        self._group = group
        self._treedef = treedef
        self._leaves = leaves
        self._launched = launched    # [(indices, handle, t_launch)]
        self._world = world
        self._average = average
        self._rank = rank
        self._result = None
        self._out_leaves: list = [None] * len(leaves)
        self._next = 0               # harvest progress (retry-safe)

    @property
    def num_buckets(self) -> int:
        return len(self._launched)

    def poll(self) -> bool:
        """True once every bucket's allreduce completed."""
        return all(h.poll() for _, h, _ in self._launched)

    def result(self, timeout: float | None = None):
        """Wait every bucket at the optimizer boundary and return the
        synced pytree. Raises ``CollectiveGroupError`` if the gang was
        poisoned while buckets were in flight, ``TimeoutError`` on a
        wire stall (timeout-not-hang; default: the collective op
        timeout per bucket)."""
        if self._result is not None:
            return self._result
        from ray_tpu.parallel import sharding as _sh
        from ray_tpu.util import tracing as _tracing

        out_leaves = self._out_leaves
        tags = {"group": self._group}
        # resume from the first un-harvested bucket: a retry after a
        # failed/timed-out bucket must not re-observe the completed
        # buckets' wait/sync histograms (counts would exceed
        # buckets_total) nor re-unpack them
        while self._next < len(self._launched):
            b = self._next
            indices, handle, t_launch = self._launched[b]
            t0 = time.perf_counter()
            with _prof.record_span("train", f"grad_bucket_wait::{b}",
                                   {"group": self._group, "bucket": b}):
                with _tracing.span(f"grad_bucket_wait {b}", "INTERNAL",
                                   attributes={"group": self._group,
                                               "bucket": b}):
                    flat = handle.result(timeout)
            now = time.perf_counter()
            if _tm.ENABLED and self._rank is not None:
                # bucket landed: it is no longer in flight on the wire
                _ma.LEDGER.add_inflight(self._rank, -float(flat.nbytes))
            if _tm.ENABLED:
                _tm.observe("ray_tpu_train_bucket_wait_seconds",
                            now - t0, tags=tags)
                # launch→COMPLETION (the handle stamps done_at when the
                # op finishes on the issue thread) — NOT launch→harvest:
                # a caller that overlapped long compute before result()
                # must not inflate the bucket's apparent comm time (the
                # overlap-fraction panel divides wait by this)
                _tm.observe("ray_tpu_train_bucket_sync_seconds",
                            (handle.done_at or now) - t_launch,
                            tags=tags)
            if self._average:
                flat = flat / self._world
            _sh.unpack_bucket(flat, self._leaves, indices, out_leaves)
            self._next = b + 1
        self._result = _sh.unflatten_tree(self._treedef, out_leaves)
        # drop the launch-time references (packed buffers, raw grads)
        self._launched = []
        self._leaves = []
        return self._result


class _DoneSync:
    """Kill-switch / degenerate result: the sync already happened."""

    num_buckets = 0

    def __init__(self, result):
        self._result = result

    def poll(self) -> bool:
        return True

    def result(self, timeout: float | None = None):
        return self._result


class PendingShardSync:
    """In-flight sharded (ZeRO-style) gradient sync: every bucket's
    async reducescatter has been launched; each handle resolves to THIS
    rank's contiguous shard of the bucket's reduction. The shard map
    (``parallel/sharding.plan_shard_map``) is derived from shapes +
    dtypes only, so every rank agrees on who owns which ``[lo, hi)``
    slice of each packed bucket — the precondition for each rank to be
    the sole updater of its optimizer-state shard. ``wait_bucket(b)``
    harvests one bucket (the sharded optimizer's per-bucket hook);
    ``result()`` harvests all and returns the per-bucket shard list."""

    mode = "reducescatter"

    def __init__(self, group: str, treedef, leaves, plan, shard_map,
                 launched, world: int, average: bool,
                 rank: int | None = None):
        self._group = group
        self._treedef = treedef
        self._leaves = leaves
        self._plan = plan
        self._shard_map = shard_map
        self._launched = launched    # [(indices, handle, t_launch, nbytes)]
        self._world = world
        self._average = average
        self._rank = rank
        self._shards: list = [None] * len(launched)
        self._next = 0               # harvest progress (retry-safe)

    @property
    def num_buckets(self) -> int:
        return len(self._launched)

    @property
    def shard_map(self):
        return self._shard_map

    def poll(self) -> bool:
        return all(h.poll() for _, h, _, _ in self._launched)

    def _harvest_next(self, timeout: float | None):
        from ray_tpu.util import tracing as _tracing

        b = self._next
        indices, handle, t_launch, nbytes = self._launched[b]
        tags = {"group": self._group}
        t0 = time.perf_counter()
        with _prof.record_span("train", f"grad_bucket_wait::{b}",
                               {"group": self._group, "bucket": b}):
            with _tracing.span(f"grad_bucket_wait {b}", "INTERNAL",
                               attributes={"group": self._group,
                                           "bucket": b}):
                flat = handle.result(timeout)
        now = time.perf_counter()
        if _tm.ENABLED and self._rank is not None:
            _ma.LEDGER.add_inflight(self._rank, -float(nbytes))
        if _tm.ENABLED:
            _tm.observe("ray_tpu_train_bucket_wait_seconds",
                        now - t0, tags=tags)
            _tm.observe("ray_tpu_train_bucket_sync_seconds",
                        (handle.done_at or now) - t_launch, tags=tags)
        if self._average:
            flat = flat / self._world
        self._shards[b] = flat
        self._next = b + 1

    def wait_bucket(self, b: int, timeout: float | None = None):
        """This rank's reduced (or averaged) shard of bucket ``b``;
        harvests in launch order (handles complete FIFO on the issue
        thread, so waiting bucket b implies buckets < b are done)."""
        while self._next <= b:
            self._harvest_next(timeout)
        return self._shards[b]

    def result(self, timeout: float | None = None) -> list:
        """Harvest every bucket; returns the list of this rank's
        per-bucket shard arrays (use ``shard_map`` to locate them in
        the packed buckets)."""
        while self._next < len(self._launched):
            self._harvest_next(timeout)
        self._launched = []
        return self._shards


class _DoneShardSync:
    """Kill-switch / degenerate sharded result: the reducescatters
    already ran synchronously; same surface as PendingShardSync."""

    mode = "reducescatter"

    def __init__(self, shards, shard_map, plan):
        self._shards = shards
        self._shard_map = shard_map
        self._plan = plan

    @property
    def num_buckets(self) -> int:
        return len(self._shards)

    @property
    def shard_map(self):
        return self._shard_map

    def poll(self) -> bool:
        return True

    def wait_bucket(self, b: int, timeout: float | None = None):
        return self._shards[b]

    def result(self, timeout: float | None = None) -> list:
        return self._shards


def _resolve_mode(mode) -> str:
    m = mode if mode is not None else _get_config("train_ddp_mode")
    m = str(m).strip().lower()
    if m not in ("allreduce", "reducescatter"):
        raise ValueError(
            f"train DDP mode {mode!r}: expected 'allreduce' (legacy, "
            f"every rank gets the full synced tree) or 'reducescatter' "
            f"(ZeRO-style, each rank gets its shard of every bucket)")
    return m


def _sync_shards_async(grads, group_name: str, *, average: bool,
                       bucket_bytes: int | None, wire_dtype):
    """The ``mode="reducescatter"`` launch path: one async
    reducescatter per bucket, each handle yielding only this rank's
    shard — roughly half the wire bytes of an allreduce per bucket
    (each element crosses the wire once instead of reduce+broadcast).
    With ``RAY_TPU_TRAIN_BUCKET_DDP=0`` (or a backend without async
    support) the SAME bucket plan runs through synchronous
    reducescatters instead — the shard map must not change with the
    kill switch, or optimizer state sharded over it would be orphaned
    mid-run; only the overlap is given up."""
    from ray_tpu.parallel import sharding as _sh
    from ray_tpu.util import collective as col

    leaves, treedef = _sh.flatten_tree(grads)
    world = col.get_collective_group_size(group_name)
    if bucket_bytes is None:
        bucket_bytes = int(_get_config("train_grad_bucket_bytes"))
    plan = _sh.plan_buckets(leaves, bucket_bytes)
    shard_map = _sh.plan_shard_map(leaves, plan, world)
    rank = None
    tags = {"group": group_name}
    if _tm.ENABLED:
        try:
            rank = col.get_rank(group_name)
        except Exception:
            rank = None
        if rank is not None:
            _ma.LEDGER.note_train_state(
                "grads", rank, float(sum(l.nbytes for l in leaves)))
    wire_of = wire_dtype if callable(wire_dtype) else (
        lambda b, indices: wire_dtype)
    bucketed = bool(_get_config("train_bucket_ddp"))
    if not bucketed or not col.supports_async(group_name):
        shards = []
        for b, indices in enumerate(plan):
            flat = _sh.pack_bucket(leaves, indices)
            if _tm.ENABLED:
                _tm.observe("ray_tpu_train_bucket_bytes",
                            float(flat.nbytes), tags=tags)
                _tm.counter_inc("ray_tpu_train_buckets_total", tags=tags)
            shard = col.reducescatter(flat, group_name)
            if average:
                shard = shard / world
            shards.append(shard)
        return _DoneShardSync(shards, shard_map, plan)
    launched = []
    for b, indices in enumerate(plan):
        with _prof.record_span("train", f"grad_bucket_pack::{b}",
                               {"group": group_name, "bucket": b}):
            flat = _sh.pack_bucket(leaves, indices)
        if _tm.ENABLED:
            _tm.observe("ray_tpu_train_bucket_bytes", float(flat.nbytes),
                        tags=tags)
            _tm.counter_inc("ray_tpu_train_buckets_total", tags=tags)
            if rank is not None:
                _ma.LEDGER.add_inflight(rank, float(flat.nbytes))
        launched.append((indices,
                         col.reducescatter_async(
                             flat, group_name,
                             wire_dtype=wire_of(b, indices)),
                         time.perf_counter(), float(flat.nbytes)))
    return PendingShardSync(group_name, treedef, leaves, plan, shard_map,
                            launched, world, average, rank=rank)


def sync_gradients_async(grads, group_name: str = "train_dp", *,
                         average: bool = False,
                         bucket_bytes: int | None = None,
                         mode: str | None = None,
                         wire_dtype=None):
    """Launch the bucketed gradient sync and return a
    ``PendingGradSync`` immediately — overlap the comm with anything
    (the next microbatch's forward, metrics, logging), then call
    ``.result()`` at the optimizer boundary.

    ``mode`` (default: the ``RAY_TPU_TRAIN_DDP_MODE`` config knob,
    ``allreduce``) selects the sync shape: ``allreduce`` returns the
    full synced tree on every rank; ``reducescatter`` is the ZeRO-style
    sharded sync — the returned ``PendingShardSync`` yields only this
    rank's ``[lo, hi)`` shard of each packed bucket (see
    ``ZeroOptimizer`` for the sharded optimizer riding it).
    ``wire_dtype`` ("bf16"/"int8", or a ``(bucket, indices) -> fmt``
    callable for per-bucket opt-in) quantizes the reducescatter wire;
    it applies to the sharded mode only.

    With ``RAY_TPU_TRAIN_BUCKET_DDP=0`` the legacy path runs instead:
    one synchronous allreduce over the whole flattened tree (one op per
    dtype for mixed-dtype trees), completed before this returns — and
    the sharded mode degrades to synchronous per-bucket reducescatters
    over the unchanged shard map."""
    from ray_tpu.parallel import sharding as _sh
    from ray_tpu.util import collective as col

    mode = _resolve_mode(mode)
    if mode == "reducescatter":
        return _sync_shards_async(grads, group_name, average=average,
                                  bucket_bytes=bucket_bytes,
                                  wire_dtype=wire_dtype)
    if wire_dtype is not None:
        raise ValueError(
            "wire_dtype is a per-bucket opt-in on the reducescatter "
            "path; the allreduce mode composes with the group-wide "
            "RAY_TPU_COLLECTIVE_WIRE_DTYPE knob instead")
    leaves, treedef = _sh.flatten_tree(grads)
    world = col.get_collective_group_size(group_name)
    if not leaves or world == 1:
        # world-1 sum is the identity (and average divides by 1):
        # skip the pack/allreduce/unpack round entirely
        return _DoneSync(grads)
    bucketed = bool(_get_config("train_bucket_ddp"))
    if bucket_bytes is None:
        bucket_bytes = int(_get_config("train_grad_bucket_bytes"))
    if not bucketed or not col.supports_async(group_name):
        # legacy: the whole tree as ONE synchronous allreduce (one
        # per dtype — a bucket must be contiguous in one dtype), the
        # exact pre-bucketing semantics the kill switch promises.
        # Also the degrade path for backends without async support
        # (xla) — the sync allreduce works there, so a grad sync must
        # not fail where the kill-switch path would succeed
        plan = _sh.plan_buckets(leaves, 1 << 62)
        out_leaves: list = [None] * len(leaves)
        for indices in plan:
            flat = col.allreduce(_sh.pack_bucket(leaves, indices),
                                 group_name)
            if average:
                flat = flat / world
            _sh.unpack_bucket(flat, leaves, indices, out_leaves)
        return _DoneSync(_sh.unflatten_tree(treedef, out_leaves))
    plan = _sh.plan_buckets(leaves, bucket_bytes)
    launched = []
    tags = {"group": group_name}
    rank = None
    if _tm.ENABLED:
        try:
            rank = col.get_rank(group_name)
        except Exception:
            rank = None
        if rank is not None:
            # exact by construction: the flatten is deterministic, so
            # this is THE grads footprint the sync moves for this rank
            _ma.LEDGER.note_train_state(
                "grads", rank, float(sum(l.nbytes for l in leaves)))
    for b, indices in enumerate(plan):
        # pack on the caller thread: bucket b's device→host fetch +
        # memcpy runs while buckets < b are already on the wire
        with _prof.record_span("train", f"grad_bucket_pack::{b}",
                               {"group": group_name, "bucket": b}):
            flat = _sh.pack_bucket(leaves, indices)
        if _tm.ENABLED:
            _tm.observe("ray_tpu_train_bucket_bytes", float(flat.nbytes),
                        tags=tags)
            _tm.counter_inc("ray_tpu_train_buckets_total", tags=tags)
            if rank is not None:
                _ma.LEDGER.add_inflight(rank, float(flat.nbytes))
        launched.append((indices, col.allreduce_async(flat, group_name),
                         time.perf_counter()))
    return PendingGradSync(group_name, treedef, leaves, launched, world,
                           average, rank=rank)


def sync_gradients(grads, group_name: str = "train_dp", *,
                   average: bool = False,
                   bucket_bytes: int | None = None,
                   mode: str | None = None,
                   wire_dtype=None):
    """Synchronize one grad pytree across the data-parallel gang and
    return the summed (or averaged) grads — or, in
    ``mode="reducescatter"``, the list of this rank's per-bucket
    shards. Bucketed + async under the hood (see module docstring);
    the pack/unpack of neighboring buckets still overlaps each bucket's
    comm even though this call itself blocks until the sync is done."""
    # timeout=None = the collective op timeout per bucket (the wire's
    # failure detector of last resort) — bounded, never a silent hang
    return sync_gradients_async(
        grads, group_name, average=average, bucket_bytes=bucket_bytes,
        mode=mode, wire_dtype=wire_dtype).result(timeout=None)


# ------------------------------------------------- sharded optimizer (ZeRO)
#
# ZeRO-1/2-style sharded optimizer over the bucket plan: grads arrive
# per-bucket via reducescatter (each rank holds only its [lo, hi) shard
# of every bucket), the optimizer state for that shard lives ONLY on
# its owner rank (O(model/world) state per rank instead of O(model)),
# and updated param shards return via per-bucket ASYNC allgathers that
# ride the issue thread while later buckets are still applying — and
# while the caller runs the next step's work, because the gather
# handles are waited only at first use of the new params.
#
# The shard optimizers here are strictly ELEMENTWISE numpy updates
# (sgd/momentum/adam): applying them per-shard then allgathering is
# exactly the computation legacy mode runs on the full vector, element
# for element — so at world 2, where the pairwise exchange makes
# reducescatter's shard bit-identical to the allreduce result's same
# slice, the final params are bit-identical to legacy allreduce + full
# apply (pinned by test). Optimizers with cross-element coupling
# (global grad-norm clipping, LAMB trust ratios) would need an extra
# scalar sync per step and are deliberately out of scope.


class _SgdShard:
    """Elementwise SGD (+momentum) on one shard; state: momentum only."""

    name = "sgd"

    def __init__(self, lr: float, momentum: float = 0.0):
        self.lr = float(lr)
        self.momentum = float(momentum)
        self.slots = 1 if momentum else 0

    def init(self, nelems: int, dtype):
        import numpy as np

        if not self.momentum:
            return {}
        return {"m": np.zeros(nelems, dtype=dtype)}

    def apply(self, p, g, state, step: int):
        if self.momentum:
            m = state["m"]
            m *= self.momentum
            m += g
            p -= self.lr * m
        else:
            p -= self.lr * g
        return p


class _AdamShard:
    """Elementwise Adam on one shard; state: first + second moments."""

    name = "adam"
    slots = 2

    def __init__(self, lr: float, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8):
        self.lr = float(lr)
        self.b1 = float(b1)
        self.b2 = float(b2)
        self.eps = float(eps)

    def init(self, nelems: int, dtype):
        import numpy as np

        return {"m": np.zeros(nelems, dtype=dtype),
                "v": np.zeros(nelems, dtype=dtype)}

    def apply(self, p, g, state, step: int):
        import numpy as np

        m, v = state["m"], state["v"]
        m *= self.b1
        m += (1.0 - self.b1) * g
        v *= self.b2
        v += (1.0 - self.b2) * (g * g)
        mhat = m / (1.0 - self.b1 ** step)
        vhat = v / (1.0 - self.b2 ** step)
        p -= self.lr * mhat / (np.sqrt(vhat) + self.eps)
        return p


def zero_sgd(lr: float, momentum: float = 0.0) -> _SgdShard:
    """Shard optimizer for :class:`ZeroOptimizer`: elementwise SGD."""
    return _SgdShard(lr, momentum)


def zero_adam(lr: float, b1: float = 0.9, b2: float = 0.999,
              eps: float = 1e-8) -> _AdamShard:
    """Shard optimizer for :class:`ZeroOptimizer`: elementwise Adam."""
    return _AdamShard(lr, b1, b2, eps)


class PendingParams:
    """In-flight sharded apply: every bucket's updated param shard has
    an async allgather on the wire. ``result()`` waits the gathers at
    FIRST USE, reassembles each packed bucket from the per-rank shards,
    and unflattens the new params tree — so the gathers overlap
    whatever the caller runs between the optimizer step and the next
    forward (data loading, metrics, host→device transfer), and step
    anatomy attributes that comm as hidden."""

    def __init__(self, group: str, treedef, leaves, plan, shard_map,
                 gathers, rank: int | None):
        self._group = group
        self._treedef = treedef
        self._leaves = leaves
        self._plan = plan
        self._shard_map = shard_map
        self._gathers = gathers      # [(b, handle, t_launch, nbytes)]
        self._rank = rank
        self._result = None

    @property
    def num_buckets(self) -> int:
        return len(self._gathers)

    def poll(self) -> bool:
        return all(h.poll() for _, h, _, _ in self._gathers)

    def result(self, timeout: float | None = None):
        """The updated params pytree; blocks on any allgather still in
        flight (the residue the overlap window failed to hide)."""
        if self._result is not None:
            return self._result
        import numpy as np

        from ray_tpu.parallel import sharding as _sh

        tags = {"group": self._group}
        out_leaves: list = [None] * len(self._leaves)
        done = [None] * len(self._plan)
        for b, handle, t_launch, nbytes in self._gathers:
            t0 = time.perf_counter()
            parts = handle.result(timeout)
            now = time.perf_counter()
            if _tm.ENABLED:
                _tm.observe("ray_tpu_train_param_gather_wait_seconds",
                            now - t0, tags=tags)
                _tm.observe("ray_tpu_train_param_gather_seconds",
                            (handle.done_at or now) - t_launch,
                            tags=tags)
                if self._rank is not None:
                    _ma.LEDGER.add_inflight(self._rank, -float(nbytes))
            # shard bounds are contiguous in rank order, so the packed
            # bucket is exactly the rank-ordered concatenation
            done[b] = np.concatenate([np.asarray(p).reshape(-1)
                                      for p in parts])
        for b, indices in enumerate(self._plan):
            if done[b] is not None:
                _sh.unpack_bucket(done[b], self._leaves, indices,
                                  out_leaves)
        # leaves the plan never covered (empty tree edge) stay original
        for i, leaf in enumerate(self._leaves):
            if out_leaves[i] is None:
                out_leaves[i] = leaf
        self._result = _sh.unflatten_tree(self._treedef, out_leaves)
        self._gathers = []
        self._leaves = []
        return self._result


class ZeroOptimizer:
    """ZeRO-style sharded optimizer over the DDP bucket plan.

    Each rank owns the ``[lo, hi)`` shard of every packed bucket that
    the deterministic shard map (``parallel/sharding.plan_shard_map``,
    same divmod split as the collective backend's reducescatter)
    assigns it, materializes optimizer state for ONLY that shard, and
    updates only those elements each step — the O(model) replicated
    optimizer state of legacy DDP becomes O(model/world) per rank,
    proven live via the ``ray_tpu_train_state_bytes{kind=opt_state}``
    gauge this class stamps.

    Step pipeline (``step_async``): per bucket, fold the last
    microbatch's grads → launch ``reducescatter_async`` (bucket b's
    wire time hides under bucket b+1's pack), then harvest: wait shard
    b → elementwise apply on the shard → launch ``allgather_async`` of
    the updated param shard — the gather of bucket k rides the issue
    thread under the apply of bucket k+1, and the returned
    :class:`PendingParams` waits the gathers only at first use.
    ``accumulate(grads)`` is the grad-accumulation hook: earlier
    microbatches fold into host accumulators with no comm; the final
    microbatch goes straight to ``step_async`` so each bucket launches
    the moment its fold completes, not at the step boundary.

    ``state_budget_bytes`` (optional) is a hard per-rank cap: state
    materialization raises when this rank's shard state would exceed
    it — the acceptance harness trains models whose REPLICATED state
    breaks the budget that the sharded state fits.
    """

    def __init__(self, opt, group_name: str = "train_dp", *,
                 bucket_bytes: int | None = None, wire_dtype=None,
                 state_budget_bytes: int | None = None,
                 average: bool = False):
        self._opt = opt
        self._group = group_name
        self._bucket_bytes = bucket_bytes
        self._wire = wire_dtype
        self._budget = state_budget_bytes
        self._average = average
        self._plan = None
        self._shard_map = None
        self._sig = None             # (shape, dtype) leaf signature
        self._state: dict = {}       # bucket -> this rank's state dict
        self._acc: list | None = None
        self._step = 0
        self._world = None
        self._rank = None
        self._fingerprint = None     # sharding.plan_fingerprint of plan
        self._pending_state = None   # load_shard_state_dict before plan

    # ------------------------------------------------------------ plan
    def _ensure_plan(self, leaves):
        from ray_tpu.parallel import sharding as _sh
        from ray_tpu.util import collective as col

        sig = tuple((tuple(getattr(l, "shape", ())),
                     str(getattr(l, "dtype", "object"))) for l in leaves)
        if sig == self._sig:
            return
        if self._sig is not None:
            # structure changed mid-run: the shard map (and therefore
            # every rank's state slices) is stale — refuse to guess
            raise ValueError(
                "ZeroOptimizer: param/grad tree structure changed; the "
                "bucket shard map (and the optimizer state sharded "
                "over it) is derived from leaf shapes and cannot be "
                "remapped in place")
        bucket_bytes = self._bucket_bytes
        if bucket_bytes is None:
            bucket_bytes = int(_get_config("train_grad_bucket_bytes"))
        self._world = col.get_collective_group_size(self._group)
        self._rank = col.get_rank(self._group)
        self._plan = _sh.plan_buckets(leaves, bucket_bytes)
        self._shard_map = _sh.plan_shard_map(leaves, self._plan,
                                             self._world)
        self._sig = sig
        self._fingerprint = _sh.plan_fingerprint(leaves, self._plan)
        if self._pending_state is not None:
            self._install_pending_state()

    def _my_bounds(self, b: int):
        return self._shard_map[b]["bounds"][self._rank]

    # ----------------------------------------------------------- state
    def _shard_state(self, b: int) -> dict:
        st = self._state.get(b)
        if st is None:
            lo, hi = self._my_bounds(b)
            st = self._opt.init(hi - lo, self._shard_map[b]["dtype"])
            self._state[b] = st
            self._note_state()
        return st

    def _note_state(self):
        total = self.state_bytes()
        if self._budget is not None and total > self._budget:
            raise RuntimeError(
                f"ZeroOptimizer: this rank's optimizer-state shard "
                f"({int(total)} bytes) exceeds the per-rank budget "
                f"({int(self._budget)} bytes) — raise the budget, "
                f"grow the gang, or use a lighter optimizer")
        if _tm.ENABLED and self._rank is not None:
            _ma.LEDGER.note_train_state("opt_state", self._rank,
                                        float(total))

    def state_bytes(self) -> float:
        """Exact flatten-sum of this rank's materialized shard state —
        the number the opt_state gauge carries."""
        return float(sum(arr.nbytes for st in self._state.values()
                         for arr in st.values()))

    def replicated_state_bytes(self) -> float:
        """What ONE rank would hold if the state were replicated (the
        legacy-DDP footprint): slots × elements × itemsize over the
        whole plan. The world-fold claim is
        ``state_bytes() ≈ replicated_state_bytes() / world``."""
        if self._shard_map is None:
            raise ValueError("ZeroOptimizer: no plan yet (run a step "
                             "or accumulate first)")
        slots = int(getattr(self._opt, "slots", 0))
        return float(sum(e["elems"] * e["dtype"].itemsize * slots
                         for e in self._shard_map))

    @property
    def shard_map(self):
        return self._shard_map

    @property
    def step_count(self) -> int:
        return self._step

    @property
    def plan_fingerprint(self) -> str | None:
        """World-independent identity of the bucket plan (see
        ``parallel/sharding.plan_fingerprint``); ``None`` before the
        first step/accumulate establishes the plan."""
        return self._fingerprint

    # ------------------------------------------- sharded checkpoint I/O
    def shard_state_dict(self) -> dict:
        """This rank's optimizer-state shard for the sharded checkpoint
        plane (``train/sharded_checkpoint.py``): per-bucket slot arrays
        covering ONLY this rank's ``[lo, hi)`` of each packed bucket,
        plus the step counter (adam bias correction depends on it) and
        the plan fingerprint restore must verify. O(model/world) — full
        state never exists on any rank."""
        import numpy as np

        if self._plan is None:
            raise ValueError("ZeroOptimizer: no plan yet (run a step "
                             "or accumulate first)")
        buckets = []
        for b in range(len(self._plan)):
            st = self._shard_state(b)
            buckets.append({k: np.asarray(v) for k, v in st.items()})
        return {"step": self._step,
                "plan_fingerprint": self._fingerprint,
                "world": self._world, "rank": self._rank,
                "buckets": buckets}

    def load_shard_state_dict(self, state: dict):
        """Install a shard-state dict (from :meth:`shard_state_dict`,
        possibly re-sliced onto this world size by the sharded
        checkpoint plane). Before the first step the plan is unknown, so
        the state parks and installs when the plan is established —
        fingerprint and per-bucket lengths are verified then."""
        self._pending_state = dict(state)
        if self._plan is not None:
            self._install_pending_state()

    def _install_pending_state(self):
        pend, self._pending_state = self._pending_state, None
        fp = pend.get("plan_fingerprint")
        if fp is not None and self._fingerprint is not None \
                and fp != self._fingerprint:
            raise ValueError(
                f"ZeroOptimizer: checkpointed plan fingerprint "
                f"{fp[:12]}… does not match this model's "
                f"{self._fingerprint[:12]}… — the saved shards were cut "
                f"over a different leaf signature/bucket plan and "
                f"cannot be re-sliced onto it")
        buckets = pend.get("buckets", [])
        if len(buckets) != len(self._plan):
            raise ValueError(
                f"ZeroOptimizer: checkpoint has {len(buckets)} bucket "
                f"states, plan has {len(self._plan)} buckets")
        for b, st in enumerate(buckets):
            lo, hi = self._my_bounds(b)
            for slot, arr in st.items():
                if int(getattr(arr, "size", -1)) != hi - lo:
                    raise ValueError(
                        f"ZeroOptimizer: bucket {b} slot {slot!r} has "
                        f"{getattr(arr, 'size', None)} elements, this "
                        f"rank's shard is {hi - lo}")
            self._state[b] = dict(st)
        self._step = int(pend.get("step", 0))
        self._note_state()

    # ------------------------------------------------------------ step
    def accumulate(self, grads):
        """Grad-accumulation hook: fold one microbatch's grads into the
        host-side per-bucket accumulators (pack + add; no comm). Feed
        the FINAL microbatch to ``step_async(params, grads=...)``
        instead — its fold interleaves with the bucket launches."""
        from ray_tpu.parallel import sharding as _sh

        leaves, _ = _sh.flatten_tree(grads)
        self._ensure_plan(leaves)
        if self._acc is None:
            self._acc = [None] * len(self._plan)
        for b, indices in enumerate(self._plan):
            flat = _sh.pack_bucket(leaves, indices)
            if self._acc[b] is None:
                self._acc[b] = flat   # pack allocates: safe to own
            else:
                self._acc[b] += flat

    def step_async(self, params, grads=None,
                   timeout: float | None = None) -> PendingParams:
        """One sharded optimizer step. Folds ``grads`` (the last — or
        only — microbatch; optional when ``accumulate`` already folded
        everything), launches the per-bucket reducescatters as each
        bucket's fold completes, applies this rank's shards as they
        land (later buckets' wire time and earlier buckets' gathers
        hide under the apply), and returns a :class:`PendingParams`
        with the allgathers in flight."""
        import numpy as np

        from ray_tpu.parallel import sharding as _sh
        from ray_tpu.util import collective as col

        leaves, treedef = _sh.flatten_tree(params)
        self._ensure_plan(leaves)
        if grads is None and self._acc is None:
            raise ValueError("ZeroOptimizer.step_async: no grads — "
                             "pass grads= or call accumulate() first")
        gleaves = None
        if grads is not None:
            gleaves, _ = _sh.flatten_tree(grads)
        self._step += 1
        tags = {"group": self._group}
        rank = self._rank if _tm.ENABLED else None
        if rank is not None:
            _ma.LEDGER.note_train_state(
                "grads", rank,
                float(sum(l.nbytes for l in (gleaves or leaves))))
        wire_of = self._wire if callable(self._wire) else (
            lambda b, indices: self._wire)
        bucketed = (bool(_get_config("train_bucket_ddp"))
                    and col.supports_async(self._group))
        # launch: fold bucket b, put its reducescatter on the wire,
        # move on to folding b+1 — grads go out as they become final
        launched = []
        for b, indices in enumerate(self._plan):
            flat = None
            if gleaves is not None:
                with _prof.record_span(
                        "train", f"grad_bucket_pack::{b}",
                        {"group": self._group, "bucket": b}):
                    flat = _sh.pack_bucket(gleaves, indices)
                if self._acc is not None and self._acc[b] is not None:
                    flat += self._acc[b]
            else:
                flat = self._acc[b]
            if _tm.ENABLED:
                _tm.observe("ray_tpu_train_bucket_bytes",
                            float(flat.nbytes), tags=tags)
                _tm.counter_inc("ray_tpu_train_buckets_total", tags=tags)
            if bucketed:
                if rank is not None:
                    _ma.LEDGER.add_inflight(rank, float(flat.nbytes))
                launched.append(
                    (indices,
                     col.reducescatter_async(
                         flat, self._group,
                         wire_dtype=wire_of(b, indices)),
                     time.perf_counter(), float(flat.nbytes)))
            else:
                launched.append((indices, flat, None, None))
        self._acc = None
        # harvest: wait shard b, apply, launch its allgather — while
        # this rank runs the apply math, bucket b+1's reducescatter and
        # buckets < b's allgathers proceed on the issue thread
        gathers = []
        for b, (indices, h, t_launch, nbytes) in enumerate(launched):
            lo, hi = self._my_bounds(b)
            with _prof.record_span("train", f"param_shard_pack::{b}",
                                   {"group": self._group, "bucket": b}):
                pflat = _sh.pack_bucket(leaves, indices)
            pshard = np.array(pflat[lo:hi])  # own the slice memory
            if bucketed:
                t0 = time.perf_counter()
                gshard = h.result(timeout)
                now = time.perf_counter()
                if _tm.ENABLED:
                    _tm.observe("ray_tpu_train_bucket_wait_seconds",
                                now - t0, tags=tags)
                    _tm.observe("ray_tpu_train_bucket_sync_seconds",
                                (h.done_at or now) - t_launch, tags=tags)
                    if rank is not None:
                        _ma.LEDGER.add_inflight(rank, -float(nbytes))
            else:
                gshard = col.reducescatter(h, self._group)
            if self._average:
                gshard = gshard / self._world
            st = self._shard_state(b)
            with _prof.record_span("train", f"shard_apply::{b}",
                                   {"group": self._group, "bucket": b}):
                pshard = self._opt.apply(pshard, np.asarray(gshard), st,
                                         self._step)
            bucket_bytes_full = float(
                self._shard_map[b]["elems"]
                * self._shard_map[b]["dtype"].itemsize)
            if bucketed:
                if rank is not None:
                    _ma.LEDGER.add_inflight(rank, bucket_bytes_full)
                gathers.append((b, col.allgather_async(pshard,
                                                       self._group),
                                time.perf_counter(), bucket_bytes_full))
            else:
                parts = col.allgather(pshard, self._group)
                gathers.append((b, _DoneHandle(parts), time.perf_counter(),
                                0.0))
        return PendingParams(self._group, treedef, leaves, self._plan,
                             self._shard_map, gathers, rank)

    def step(self, params, grads=None, timeout: float | None = None):
        """Blocking convenience: ``step_async(...).result()``."""
        return self.step_async(params, grads, timeout).result(timeout)


class _DoneHandle:
    """Completed pseudo-handle for the kill-switch path: the op already
    ran synchronously; PendingParams treats it like a real handle."""

    done_at = None

    def __init__(self, value):
        self._value = value

    def poll(self) -> bool:
        return True

    def result(self, timeout: float | None = None):
        return self._value


# ------------------------------------------------------------ step builders
#
# The host-DP train steps: each gang member owns its local devices and
# gradients cross hosts over the collective plane (the reference's
# torch-DDP shape), not as an XLA psum, so the step is compiled halves
# with the host collective between them. They are built on the compute
# layer's pieces (`parallel/train_step.py`), imported at call time: the
# driver imports this module and must stay off jax.


def make_ddp_train_step(loss_fn, optimizer, grad_sync, mesh=None, *,
                        donate: bool = True):
    """loss_fn(params, batch) -> (scalar_loss, metrics_dict).

    Returns step(state, batch) -> (state, metrics): a jitted grad
    computation, then ``grad_sync`` — a callable
    ``grads_pytree -> synced_grads_pytree``, canonically
    :func:`sync_gradients` — run OUTSIDE the compiled programs, then a
    jitted optimizer apply. The bucketed sync overlaps its comm with
    the unpack/pack work around it."""
    import jax
    import optax

    from ray_tpu.parallel import train_step as ts

    def grad_step(params, batch):
        batch = ts._constrain_batch(batch, mesh, ts.BATCH_SPEC)
        # metrics pass through exactly as loss_fn returned them — the
        # in-mesh step adds only grad_norm, and the two must expose the
        # same metric schema for the same loss_fn
        (_loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return dict(metrics), grads

    def apply_step(state, grads):
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return (
            ts.TrainState(step=state.step + 1, params=params,
                          opt_state=opt_state),
            optax.global_norm(grads),
        )

    grad_fn = ts._jit(grad_step, "train_grad_step")
    apply_fn = ts._jit(apply_step, "train_apply_step",
                       donate_argnums=(0,) if donate else ())

    def step(state, batch):
        metrics, grads = grad_fn(state.params, batch)
        # the hook receives the device grads pytree; the bucketed sync
        # materializes leaves per bucket (np.asarray is the device→host
        # fetch), so later buckets' transfers overlap earlier buckets'
        # allreduce. grad_norm is computed from the SYNCED grads — the
        # quantity the optimizer actually applies.
        synced = grad_sync(grads)
        state, grad_norm = apply_fn(state, synced)
        metrics = dict(metrics)
        metrics["grad_norm"] = grad_norm
        return state, metrics

    return step


def make_zero_train_state(init_params_fn, rng, mesh=None,
                          param_specs=None):
    """The state :func:`make_zero_train_step` steps: params only. The
    optimizer state lives in the :class:`ZeroOptimizer` instead —
    sharded over the bucket plan, materialized per rank, and stamped
    into the ``opt_state`` gauge at shard granularity — so
    ``TrainState.opt_state`` is the empty tuple and this process's
    replicated-state footprint is params only."""
    import optax

    from ray_tpu.parallel.train_step import make_train_state

    # init is all make_train_state asks of an optimizer
    stateless = optax.GradientTransformation(
        init=lambda params: (),
        update=lambda updates, state, params=None: (updates, state))
    return make_train_state(init_params_fn, rng, stateless, mesh,
                            param_specs)


def make_zero_train_step(loss_fn, zero_optimizer: ZeroOptimizer,
                         mesh=None):
    """The ZeRO-sharded host step: a jitted function computes grads
    only; ``zero_optimizer`` reducescatters them, applies this rank's
    shards, and allgathers updated params ASYNC — the returned ``step``
    waits those gathers at the START of the next call (first use), so
    everything between steps overlaps the gather comm. Call
    ``step.finalize(state)`` once after the loop to fold the last
    step's in-flight params into the state. ``metrics["grad_norm"]`` is
    the LOCAL pre-sync norm (the synced grads exist only as shards)."""
    import dataclasses

    import jax
    import optax

    from ray_tpu.parallel import train_step as ts

    def zgrad_step(params, batch):
        batch = ts._constrain_batch(batch, mesh, ts.BATCH_SPEC)
        (_loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return dict(metrics), grads, optax.global_norm(grads)

    zgrad_fn = ts._jit(zgrad_step, "train_grad_step")
    box = {"pending": None}

    def resolve(state):
        pending = box["pending"]
        if pending is None:
            return state
        box["pending"] = None
        # first use of the previous step's params: the allgathers
        # rode the issue thread through everything the caller did
        # since step_async returned; only the residue blocks here.
        # timeout=None defers to the per-op collective deadline so
        # a dead peer surfaces as CollectiveGroupError, not a hang
        return dataclasses.replace(
            state, params=pending.result(timeout=None))

    def step(state, batch):
        state = resolve(state)
        metrics, grads, grad_norm = zgrad_fn(state.params, batch)
        box["pending"] = zero_optimizer.step_async(state.params, grads)
        metrics = dict(metrics)
        metrics["grad_norm"] = grad_norm
        return (
            ts.TrainState(step=state.step + 1, params=state.params,
                          opt_state=state.opt_state),
            metrics,
        )

    step.finalize = resolve
    return step
