"""Grouped matmul (JAX's Pallas TPU kernels `gmm` / `tgmm`, forward AND
backward): the routed layer's products, each group of rows times its own
matrix, on a kernel that reads the stacked weight as it lies.

`lhs [M, k]` holds the rows in group order, `rhs [G, k, n]` one matrix a
group. The three products of a training step are three calls of two kernels
(`jax.experimental.pallas.ops.tpu.megablox`):

* **forward** `gmm(lhs, rhs)`: grid ``(n tiles, row-tile visits, k tiles)``.
  A row tile that holds the end of one group and the start of the next is
  visited once for each, its store masked to the group's rows;
* **to the rows** `gmm(d, rhs, transpose_rhs=True)`: the same kernel, the
  contraction turned INSIDE it (the weight's block is read as ``[tn, tk]``
  and contracted over its last axis) — so the backward reads the very array
  the forward read, and nothing makes a transposed copy of a weight;
* **to the weights** `tgmm(lhsᵀ, d)`: one ``[k, n]`` accumulator a group,
  the rows of other groups masked out of both operands.

Tiles are a pure function of the shapes (:func:`tile_plan`); how much more
than the needed rows a routing makes the kernel issue is
:func:`issued_ratio`. Precision is that of XLA's own grouped product at
the same dtypes: operands as given, float32 accumulation, the result in
``lhs.dtype``.

Rows that belong to no matrix here (under `ep` the other devices' experts'
rows lie behind the local ones): the kernel does not visit them, and memory
it does not visit is UNWRITTEN. So `sizes` names every row's group —
``sizes [E]`` with E ≥ G and Σ sizes = M, `rhs` the matrices of the first G
— and the kernel's own epilogue zeroes the rows of the other E − G; with
E = G there is nothing to zero and no pass is spent on it.

The kernels compile for the TPU or raise, so `grouped_matmul` takes them
where the call runs on a TPU (`target.where`) and `tile_plan` has tiles for
its shapes, and XLA's own grouped product (`lax.ragged_dot`: the CPU's path
and the tests' reference) everywhere else. ``interpret=True`` (the Pallas
interpreter) is for tests that ask for it.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import target

_LANES = 128
# What one grid step may hold in VMEM (double-buffered blocks, the float32
# accumulator and the float32 copies `tgmm` masks its operands in), of the 16
# MiB a Mosaic kernel gets by default; the rest is the compiler's.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024
# Rows of a row tile, and the widest n tile (and k tile of `tgmm`). Swept on
# the v5e in the step of `olmoe1l-b2s4k`, at its own routing (PERF.md §6,
# PR 33): a group's last row tile is issued whole, so the fewer rows it has
# the less is issued for nothing (1.16–1.25 × the needed at 256 rows over
# that cell's window, 1.33–1.49 × at 512); 128 rows feed the MXU worse than
# they save.
ROW_TILE = 256
_WIDE_TILE = 1024


def _tiles(size: int, cap: int) -> list:
    """The multiples of 128 that divide `size`, none above `cap`."""
    return [t for t in range(_LANES, min(cap, size) + 1, _LANES)
            if size % t == 0]


def vmem_bytes(tm: int, tk: int, tn: int, itemsize: int, *,
               to_weights: bool = False) -> int:
    """VMEM one grid step holds at these tiles. `gmm`: two operand blocks
    and the result's, double-buffered, and a float32 accumulator the
    result's shape. `tgmm` (`to_weights`): the same blocks with the result
    ``[tk, tn]``, and float32 copies of both operand blocks."""
    if to_weights:
        return (2 * (tm * tk + tm * tn + tk * tn) * itemsize + tk * tn * 4
                + (tm * tk + tm * tn) * 4)
    return 2 * (tm * tk + tk * tn + tm * tn) * itemsize + tm * tn * 4


def row_tile(m: int) -> Optional[int]:
    """Rows of a row tile for m rows: `ROW_TILE`, 128 where only that
    divides m, None where neither does. Every product of a routed layer has
    the same rows, so the same row tile."""
    return next((t for t in (ROW_TILE, _LANES) if m % t == 0), None)


def tile_plan(m: int, k: int, n: int, dtype, *,
              to_weights: bool = False) -> Optional[Tuple[int, int, int]]:
    """``(tm, tk, tn)`` for a product of ``[m, k]`` rows with ``[G, k, n]``
    matrices, from the shapes alone: the forward kernel's tiling (the
    product to the rows is the forward kernel on ``(m, n, k)``) or, with
    `to_weights`, that of the product to the weights. None where no tile
    divides the shapes (the kernels want whole tiles of rows and 128-lane
    multiples; the caller then has no kernel to call) — which does not
    depend on the order of k and n, nor on `to_weights`.

    The row tile is `row_tile`'s. Of the k and n tiles that divide k and n
    and fit `VMEM_BUDGET_BYTES`, the pair that covers most of a matrix, the
    deeper one of equals. Forward, the k tile may be the whole contraction:
    the grid then has no k steps, a group's matrix block stays where it is
    while the group's row tiles go by, and the weight is read once a group
    and not once a row tile. To the weights, the ``[tk, tn]`` accumulator
    is what stays: neither is wider than `_WIDE_TILE`.
    """
    tm = row_tile(m)
    if tm is None:
        return None
    itemsize = jnp.dtype(dtype).itemsize
    fitting = [(tk * tn, tk, tn)
               for tk in _tiles(k, _WIDE_TILE if to_weights else k)
               for tn in _tiles(n, _WIDE_TILE)
               if vmem_bytes(tm, tk, tn, itemsize, to_weights=to_weights)
               <= VMEM_BUDGET_BYTES]
    if not fitting:
        return None
    _, tk, tn = max(fitting)
    return tm, tk, tn


def issued_ratio(sizes, tm: int) -> float:
    """Rows the forward kernel issues ÷ rows the routing needs: every group
    visits each row tile it has a row in, whole, so a tile that two groups
    share is issued twice. 1.0 where every group ends on a tile's edge; at
    most ``(M / tm + G − 1) · tm / M`` (`models.layers.moe_plan`). `sizes`
    are concrete (numpy): the groups' row counts in order, from row 0."""
    sizes = np.asarray(sizes, np.int64)
    ends = np.cumsum(sizes)
    starts = ends - sizes
    visits = np.where(sizes > 0, -(-ends // tm) - starts // tm, 0)
    return float(visits.sum() * tm / sizes.sum())


def _kernels():
    # Pallas and Mosaic are a second's import: paid by the program that
    # calls a kernel, not by everyone who asks for a tile plan
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    return gmm, tgmm


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(lhs, rhs, sizes, interpret):
    m, k = lhs.shape
    gmm, _ = _kernels()
    return gmm(
        lhs, rhs, sizes, preferred_element_type=lhs.dtype,
        tiling=tile_plan(m, k, rhs.shape[2], lhs.dtype), interpret=interpret)


def _grouped_fwd(lhs, rhs, sizes, interpret):
    return _grouped(lhs, rhs, sizes, interpret), (lhs, rhs, sizes)


def _grouped_bwd(interpret, res, d):
    lhs, rhs, sizes = res
    (m, k), n = lhs.shape, rhs.shape[2]
    d = d.astype(lhs.dtype)
    gmm, tgmm = _kernels()
    d_lhs = gmm(
        d, rhs, sizes, preferred_element_type=lhs.dtype,
        tiling=tile_plan(m, n, k, lhs.dtype), transpose_rhs=True,
        interpret=interpret)
    # `tgmm` takes the rows as [k, M] and turns them back itself: no
    # transpose is left in the program
    d_rhs = tgmm(
        lhs.T, d, sizes, preferred_element_type=rhs.dtype,
        tiling=tile_plan(m, k, n, lhs.dtype, to_weights=True),
        num_actual_groups=rhs.shape[0], interpret=interpret)
    return d_lhs, d_rhs, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def _ragged(lhs, rhs, sizes):
    """The plain form: `lax.ragged_dot`, the rows of no group zero."""
    held = rhs.shape[0]
    if held == sizes.shape[0]:
        return jax.lax.ragged_dot(lhs, rhs, sizes,
                                  preferred_element_type=lhs.dtype)
    # On a TPU XLA's grouped product leaves the rows of no group UNWRITTEN,
    # in the result and, transposed, in the rows' cotangent: zero both (the
    # mask on `lhs` is, transposed, the mask on its cotangent).
    grouped = (jnp.arange(lhs.shape[0]) < jnp.sum(sizes[:held]))[:, None]
    out = jax.lax.ragged_dot(jnp.where(grouped, lhs, 0), rhs, sizes[:held],
                             preferred_element_type=lhs.dtype)
    return jnp.where(grouped, out, 0)


def kernel_width(rows: int, d_model: int, d_ff: int, dtype,
                 mesh=None) -> Optional[int]:
    """The width at which a layer of experts ``[G, d_model, d_ff]`` (and
    back) over `rows` rows goes through the kernels, for a caller that
    decides once for all of a layer's products: `d_ff` where the call runs
    on a TPU and tiles divide the shapes; its next multiple of 128 where
    tiles divide THAT (the caller zero-pads the experts' compute-dtype
    copies to it: an activation that maps 0 to 0 makes the padding compute
    zeros, add nothing and take no gradient); None where `grouped_matmul`
    is XLA's product whatever the padding. mesh: as there."""
    if target.where(mesh)[0] != "tpu":
        return None
    return next((width for width in (d_ff, -(-d_ff // _LANES) * _LANES)
                 if tile_plan(rows, d_model, width, dtype) is not None), None)


def grouped_matmul(lhs, rhs, sizes, *, mesh=None, interpret: bool = False):
    """lhs [M, k] rows in group order, rhs [G, k, n], sizes [E ≥ G] int32
    with Σ sizes = M -> [M, n] in ``lhs.dtype``: each of the first G groups'
    rows times its own matrix, the rows of the other groups zero.
    Differentiable in `lhs` and `rhs` (rows of the other groups get a zero
    gradient, and give none to a weight).

    mesh: where the call runs (`target.where`; the call itself is
    per-device code, under the caller's `shard_map` where there are several
    devices). On a TPU, where `tile_plan` has tiles for the shapes, JAX's
    Pallas `gmm` / `tgmm` (the weight read as it lies by the forward and by
    the product to the rows); elsewhere `lax.ragged_dot`. `interpret` runs
    the kernels in Pallas's interpreter wherever the process is, and exists
    for tests."""
    if lhs.dtype != rhs.dtype:
        raise ValueError(f"grouped_matmul: lhs is {lhs.dtype}, rhs "
                         f"{rhs.dtype}; cast them to one compute dtype")
    (m, k), n = lhs.shape, rhs.shape[2]
    if (target.where(mesh, interpret=interpret)[0] == "tpu"
            and tile_plan(m, k, n, lhs.dtype) is not None):
        return _grouped(lhs, rhs, sizes.astype(jnp.int32), interpret)
    if interpret:
        raise ValueError(
            f"grouped_matmul: no tiles divide [{m}, {k}] × [G, {k}, {n}]")
    return _ragged(lhs, rhs, sizes)
