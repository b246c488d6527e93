"""Logical-axis sharding rules: model code names dimensions, this module
maps them to mesh axes.

This is the TPU-native replacement for the reference's per-framework
process-group plumbing (train/torch/config.py): instead of wiring NCCL
process groups, models annotate arrays with logical axis names and XLA
inserts the collectives implied by the mapping.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = Union[None, str, Tuple[str, ...]]

# Default logical→mesh rules for transformer LMs. "seq" rides the sp axis
# (sequence/context parallelism); "heads"/"mlp"/"vocab" ride tp; "experts"
# ride ep; "layers" ride pp when pipelining is on; "batch" rides dp.
# These rules place the arrays. They imply the collectives too, except the
# `tp` reductions of a dense block's row-parallel matmuls in gpt2.forward's
# layer loop: the model issues those itself (models/gpt2.py:_tp_blocks,
# explicit neighbour exchanges in per-device code), so do not look for them
# in the partitioner's output.
DEFAULT_RULES: Dict[str, AxisVal] = {
    "batch": "dp",
    "seq": "sp",
    "embed": None,
    "heads": "tp",
    "kv": None,
    "head_dim": None,
    "mlp": "tp",
    "experts": "ep",
    "expert_mlp": "tp",
    "vocab": "tp",
    "stage": "pp",
    "layers": None,
}


def spec(*logical_axes: Optional[str], rules: Optional[Dict[str, AxisVal]] = None) -> P:
    """PartitionSpec from logical axis names, e.g. spec("batch","seq","embed")."""
    rules = rules or DEFAULT_RULES
    out = []
    for ax in logical_axes:
        if ax is None:
            out.append(None)
        else:
            if ax not in rules:
                raise KeyError(f"No sharding rule for logical axis {ax!r}")
            out.append(rules[ax])
    return P(*out)


def named_sharding(
    mesh: Mesh, *logical_axes: Optional[str], rules: Optional[Dict[str, AxisVal]] = None
) -> NamedSharding:
    return NamedSharding(mesh, spec(*logical_axes, rules=rules))


def tree_shard(tree, mesh: Mesh, spec_tree):
    """Device-put a pytree with a matching pytree of PartitionSpecs."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, spec_tree
    )


def constrain(x, mesh: Optional[Mesh], *logical_axes: Optional[str],
              rules=None):
    """In-jit sharding constraint by logical names; without a mesh, x."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec(*logical_axes, rules=rules))
    )


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# --------------------------------------------------- gradient bucket plumbing
#
# Pytree plumbing for the host trainer's bucketed gradient sync:
# flatten a grad pytree in jax's canonical deterministic order, plan
# size-targeted buckets over the leaves, and pack/unpack each bucket as
# one contiguous array the collective plane can move. Planning depends
# ONLY on the tree structure + leaf shapes/dtypes, so every rank of a
# data-parallel gang derives byte-identical buckets locally — the
# precondition for the allreduce results to agree.


def flatten_tree(tree):
    """(leaves, treedef) in jax's canonical flatten order (sorted dict
    keys, registered-pytree field order) — deterministic across ranks
    for identical model structures."""
    import jax

    return jax.tree_util.tree_flatten(tree)


def unflatten_tree(treedef, leaves):
    import jax

    return jax.tree_util.tree_unflatten(treedef, leaves)


def plan_buckets(leaves, bucket_bytes: int) -> list[list[int]]:
    """Partition leaf indices into size-targeted buckets.

    Leaves are grouped by dtype (first-appearance order — a bucket is
    packed into ONE contiguous array, so members must share a dtype)
    and, within each dtype, kept in flatten order and greedily filled
    up to ``bucket_bytes``. A single leaf larger than the target gets
    its own bucket (never split: the collective plane's segmented ring
    already pipelines within one op). Every rank derives the same plan
    from the same tree."""
    bucket_bytes = max(1, int(bucket_bytes))
    by_dtype: dict = {}
    order: list = []
    for i, leaf in enumerate(leaves):
        dt = str(getattr(leaf, "dtype", "object"))
        if dt not in by_dtype:
            by_dtype[dt] = []
            order.append(dt)
        by_dtype[dt].append(i)
    plan: list[list[int]] = []
    for dt in order:
        cur: list[int] = []
        cur_bytes = 0
        for i in by_dtype[dt]:
            nbytes = int(getattr(leaves[i], "nbytes", 0))
            if cur and cur_bytes + nbytes > bucket_bytes:
                plan.append(cur)
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += nbytes
        if cur:
            plan.append(cur)
    return plan


def pack_bucket(leaves, indices):
    """One contiguous 1-D array holding the raveled members of a bucket
    (C order). Materializes device-resident leaves (``np.asarray`` is
    the device→host fetch for jax arrays) member by member, so packing
    bucket k+1 can overlap bucket k's in-flight allreduce."""
    import numpy as np

    total = 0
    for i in indices:
        n = 1
        for d in getattr(leaves[i], "shape", ()):
            n *= int(d)
        total += n
    out = np.empty(total,
                   dtype=np.dtype(getattr(leaves[indices[0]], "dtype",
                                          np.float64)))
    pos = 0
    for i in indices:
        arr = np.asarray(leaves[i]).reshape(-1)
        out[pos:pos + arr.size] = arr
        pos += arr.size
    return out


def unpack_bucket(flat, leaves, indices, out_leaves):
    """Scatter one reduced bucket back into per-leaf arrays (shapes
    taken from the original leaves); writes into ``out_leaves`` at the
    bucket's indices."""
    import numpy as np

    pos = 0
    for i in indices:
        shape = tuple(getattr(leaves[i], "shape", ()))
        n = 1
        for d in shape:
            n *= int(d)
        out_leaves[i] = np.asarray(flat[pos:pos + n]).reshape(shape)
        pos += n


def shard_bounds(total: int, parts: int) -> list:
    """Split ``total`` elements into ``parts`` contiguous ``[lo, hi)``
    chunks; the first ``total % parts`` chunks are one element longer.
    This is the SAME divmod math as the host collective backend's
    ``_split_bounds`` (pinned equal by test): a reducescatter over a
    packed bucket hands rank r exactly elements ``bounds[r]``, so the
    sharded-optimizer map below and the wire layer always agree on
    where a rank's shard of each bucket lives."""
    total = int(total)
    parts = max(1, int(parts))
    base, extra = divmod(total, parts)
    bounds = []
    lo = 0
    for r in range(parts):
        hi = lo + base + (1 if r < extra else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def plan_shard_map(leaves, plan, world: int) -> list:
    """Per-bucket shard map for ZeRO-style sharded DDP: one dict per
    bucket of ``plan`` (from :func:`plan_buckets`) with the bucket's
    packed element count, dtype, and per-rank ``[lo, hi)`` shard bounds
    (``shard_bounds(elems, world)``). Depends ONLY on leaf shapes +
    dtypes + the plan + world size — every rank derives a byte-identical
    map locally, which is the precondition for each rank to own (and be
    the sole updater of) the same optimizer-state shard every step."""
    import numpy as np

    out = []
    for indices in plan:
        elems = 0
        for i in indices:
            n = 1
            for d in getattr(leaves[i], "shape", ()):
                n *= int(d)
            elems += n
        dt = np.dtype(getattr(leaves[indices[0]], "dtype", np.float64))
        out.append({
            "indices": list(indices),
            "elems": elems,
            "dtype": dt,
            "bounds": shard_bounds(elems, world),
        })
    return out


def plan_fingerprint(leaves, plan) -> str:
    """Deterministic sha256 hex digest of the bucket plan's full
    identity: per-leaf (shape, dtype) in flatten order plus the plan's
    bucket membership. Depends ONLY on leaf shapes + dtypes + the plan —
    NOT on world size — so a gang restarting at a different world size
    derives the SAME fingerprint from the same model, which is what
    makes a saved shard set re-sliceable: matching fingerprints mean the
    packed element streams are byte-compatible and restore reduces to
    pure index math (:func:`reslice_spans`)."""
    import hashlib

    h = hashlib.sha256()
    for leaf in leaves:
        shape = tuple(int(d) for d in getattr(leaf, "shape", ()))
        dt = str(getattr(leaf, "dtype", "object"))
        h.update(repr((shape, dt)).encode())
    for indices in plan:
        h.update(repr(tuple(indices)).encode())
    return h.hexdigest()


def reslice_spans(elems: int, old_world: int, new_world: int,
                  new_rank: int) -> list:
    """Pure index math for world-elastic restore of ONE packed bucket:
    which byte-compatible spans of which OLD ranks' shards concatenate
    into NEW rank ``new_rank``'s shard. Returns
    ``[(old_rank, old_lo, old_hi), ...]`` in order, where
    ``[old_lo, old_hi)`` indexes INTO that old rank's saved shard array
    (not the bucket). Both layouts come from :func:`shard_bounds` over
    the same ``elems``, so the concatenated spans are exactly the new
    rank's ``[lo, hi)`` slice of the packed bucket — bit-identical to
    what a same-world save/restore would hand it."""
    new_lo, new_hi = shard_bounds(elems, new_world)[int(new_rank)]
    spans = []
    for old_rank, (old_lo, old_hi) in enumerate(
            shard_bounds(elems, old_world)):
        lo = max(new_lo, old_lo)
        hi = min(new_hi, old_hi)
        if lo < hi:
            spans.append((old_rank, lo - old_lo, hi - old_lo))
    return spans


def axis_size(mesh: Mesh, axis: Optional[str]) -> int:
    if axis is None:
        return 1
    return dict(mesh.shape).get(axis, 1)
