"""Fused flash attention (Pallas TPU kernels, forward AND backward).

The hot op of the flagship models. Three kernels — forward, dq, dk/dv (the
standard flash-attention split; each recomputes the probability tile from
the saved logsumexp, so no O(S²) tensor ever reaches HBM) — share one tile
schedule, derived from ``(S, D, dtype)`` — and, where v is another width than
q and k, that width — by :func:`tile_plan`:

* **Grid** ``(B·H, q-major blocks, kv-major blocks)`` (dk/dv: kv-major
  parallel, q-major sequential). A major block is as much of the sequence
  as a stated VMEM budget holds, and at most ``MAJOR_ROWS`` rows — the whole
  head at S = 1,024 — so K/V (or Q/dO) are fetched once per head there and
  no grid point is spent on a block the causal mask empties; with several
  major blocks the index maps clamp to the last needed block, so skipped
  ones are not fetched either.
* **Inner loops** over ``tile_q × tile_k`` score tiles inside the kernel.
  Each kernel has its own tile, looked up in ``_TILES`` under the widths the
  call arrives with (q/k's, v's): a call whose widths are equal — every
  call without ``k_shared`` — gets the default entry, forward 128 × 256, dq
  256 × 256, dk/dv 128 × 128; the latent call at 192 / 128 gets a 256 × 512
  forward tile and a 256 × 256 dk/dv tile (below). The forward and dq walk
  each q tile's kv tiles, dk/dv walks each kv tile's q tiles (on the
  TRANSPOSED score tile ``k·qᵀ``, so that ``pᵀ·dO`` and ``dSᵀ·q`` are plain
  contractions and lse/delta are used as the rows they are stored as). Trip
  counts come from the diagonal (:func:`_kv_tiles`, :func:`_q_tiles`):
  tiles wholly above it are never issued.
* **Every trip count is a Python int, whatever the number of major blocks**
  (PR 39), and every loop over tiles unrolled. What a grid step walks
  depends on where its q-major block lies FROM its kv-major block, not on
  where either lies, and that offset takes a handful of values known when
  the kernel is traced: the diagonal block, a whole block, with a window
  the block its lower edge crosses; non-causal, the last kv block with its
  padded columns or any other. :func:`_grid_cases` lists them, each kernel
  holds one schedule a case under ``pl.when`` on the program ids
  (:func:`_walk`, every row of tiles written out with its own trip
  counts), and one major block is the one case with no condition.
  :func:`static_tile_share` counts, from the same cases, the share of the
  issued tiles such a loop walks (1.0).
* **Masks only where the diagonal is.** Each loop is split: tiles wholly
  below the diagonal run with no iota/compare/select; only the tiles that
  cross it (or, non-causal, the ones holding padded columns) build a mask.
* **Softmax state that never changes layout.** Running max and sum are
  ``[tile_q, 128]`` loop carries (the sum lane-partial: its cross-lane
  reduction happens once per q tile), ``scale`` is folded into the
  stationary operand once per tile row, lse/delta are turned from stored
  rows into columns once per q tile (dq) or never (dk/dv).

* **A window** (``window=W``: query i sees keys i − W < j ≤ i; causal
  only) is a second bound on the same schedule (:func:`_window_kv_tiles`,
  :func:`_window_q_tiles`): tiles wholly before the window are never issued,
  the tiles that cross its lower edge are masked as the diagonal's are, and
  the sequentially walked grid axis shrinks to the major blocks a window
  can reach (:func:`_seq_blocks`), so the others are neither visited nor
  fetched. :func:`window_plan` counts what that keeps, issues and skips.
  These calls are named ``flash_window_fwd`` / ``_dq`` / ``_dkv`` so that
  a trace tells them from the full-causal calls of the same operand shapes,
  which are ``flash_fwd`` / ``flash_dq`` / ``flash_dkv``. ``window=None``
  traces to the program it was before, those names aside.

* **Latent attention** (``k_shared``: q is ``[B, S, H, Dn + Dr]``, k
  ``[B, S, H, Dn]``, ``k_shared`` ``[B, S, Dr]`` ONE head's worth of key
  columns that every head reads behind its own, v ``[B, S, H, Dv]``): the
  kernels take the shared columns as an operand of their own, whose index
  map picks the batch row as :func:`_kv_head` picks a KV head for a group,
  so nothing of ``[B·H, S, Dn + Dr]`` is built for k in HBM and no operand
  is padded to another width. A score tile is the sum of two products
  (q's first Dn columns on k, its last Dr on the shared ones), dq and dk
  are accumulated by those column ranges, and the accumulators that follow
  v (``acc``, ``o``, ``dv``) are Dv wide. The dk/dv kernel writes each
  query head's part of the shared columns' gradient and the heads are
  summed outside, as a KV group's are. These calls are named
  ``flash_latent_fwd`` / ``_dq`` / ``_dkv``. Without ``k_shared`` every
  kernel traces to the program it was. **Their tiles are their own**
  (``_TILES[(192, 128)]``, PR 44): the MXU contracts 128 at a time, so a
  product with the 64 shared columns costs a whole pass for half a pass's
  work, and what a score tile pays around it — q's ``[tile_q, 64]`` slice,
  the turned ``[tile_k, 64]`` key tile — it pays once whatever its size, so
  it is amortised over 256 rows and not 128. The two kernels that walked
  128-row tiles (the forward; dk/dv) fell furthest from their rooflines and
  dq at 256 rows did not, so the forward walks 256 × 512 tiles and dk/dv
  256 × 256, dq 256 × 256 as before (the sweep's figures: ``_TILES``'
  comment). The widths are read from the operands' shapes; nothing else
  selects a tile.

* **Which (q/k, v) width pairs were swept, and which fall to the default.**
  Swept on the chip: equal widths at 64 (PR 26; the default entry, which
  128 and 256 have run at since without a sweep of their own) and (192, 128)
  with shared key columns (PR 44). Every other pair falls to the default —
  among them **(64, 128), differential attention's** (``models.layers.
  apply_diff_attention``, PR 51: q and k 64 wide, v two heads side by side,
  with or without a window; no ``k_shared``): an unswept pair, whose
  ``QKᵀ`` fills half the MXU's depth as the D 64 calls' does, so the
  default's reasoning carries over and its figures do not. It runs the same
  kernels: the accumulators that follow v are as wide as v whatever q is.

Precision is unchanged: operands in the input dtype, f32 scores, f32
softmax statistics and accumulators, ``p``/``dS`` cast to the input dtype
for their matmuls.

The kernels compile for the TPU or raise. ``interpret=True`` (the Pallas
interpreter) is for tests that ask for it; off-TPU product code uses
parallel.ring_attention.reference_attention.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128
# `checkpoint_name`s of the forward kernel's two outputs as the backward
# pass keeps them (o [B, S, H, D], lse [B·H, S] f32). A `jax.checkpoint`
# whose policy saves them (`models.layers.remat`) does not run the forward
# kernel a second time; outside a checkpoint a name lowers to nothing.
RESIDUAL_NAMES = ("flash_o", "flash_lse")
# grid = (batch*heads, parallel blocks, sequentially accumulated blocks)
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))

# What one grid step may hold in VMEM (double-buffered blocks + scratch), of
# the 16 MiB a Mosaic kernel gets by default; the rest is the compiler's.
VMEM_BUDGET_BYTES = 10 * 1024 * 1024
# The most rows of a major block. A kernel holds the code of every schedule
# a grid step can have (`_grid_cases`), and the diagonal block's, every tile
# written out, grows with the square of the block: 20 / 10 / 36 tile bodies
# (forward / dq / dk/dv) at 1,024 rows, 72 / 36 / 136 at the 2,048 the
# budget would hold at D = 128 — and a windowed call holds a second
# triangle. Pallas traces and lowers that code for every call site of every
# process: at 2,048 rows it took 2–3 × the parent's seconds for 9 % less
# kernel time (PR 39: 115.0 ms a step for 126.5 in smallthinker4l-b1s16k).
# At 1,024 rows a K/V block still feeds 1,024 FLOP a byte fetched.
MAJOR_ROWS = 1024
# Score-tile (rows, columns) of each kernel BY THE CALL'S HEAD WIDTHS, (q/k's,
# v's) as `flash_attention` finds them in its operands' shapes; a pair that
# has no entry — every call whose widths are equal — gets the default.
# Default: swept on the v5e at [192, 1024, 64] bf16 (PR 26), and what the
# D 64 and D 128 cells' kernels have run at since: the forward likes a wide
# tile (fewer softmax row statistics per score element), dq a square one,
# dk/dv — three live tiles and two accumulators — the smallest.
# (192, 128), latent attention (128 columns of a head's own + 64 shared, v
# 128), swept at [2, 8192, 32, 192 / 128] bf16 (PR 44: thirteen forms, each
# kernel by name from a device profile,
# benchmarks/results/pr44_tiles_by_width/kernels/): a product with the 64
# shared columns costs the MXU a whole 128-deep pass, and what a score tile
# pays around it — q's [rows, 64] slice, the turned [columns, 64] key tile —
# it pays once whatever its size. The two kernels that walked 128-row tiles
# fell furthest from their rooflines: forward 19.86 ms a call at 128 × 256,
# 13.73 at 256 × 256, 10.99 at 256 × 512 (512 rows or 1,024 columns: no
# better); dk/dv 26.07 at 128 × 128, 19.91 at 128 × 256, 18.55 at 256 × 256;
# dq 17.0–17.2 at every form with 256 rows or more. The equal-width kernels
# move by under 6 % over the same forms (the forward 8.18 → 7.74 at 256 ×
# 512), which is why they keep the default. A grid step holds the same
# blocks in VMEM as before — they are a major block's, not a tile's:
# `vmem_bytes(1024, 192, 2)`, 8.1 MiB of the 10 — and live float32 score
# tiles of 512 KiB for 128 in the forward (s and p), 256 KiB for 64 in dk/dv
# (sᵀ, pᵀ and dSᵀ). The diagonal block writes out FEWER tile bodies:
# forward 20 → 6, dk/dv 36 → 10 (dq 10 as before), so a call site traces
# and lowers no slower. The padded length is a multiple of 512 here.
_TILES = {
    None: {"fwd": (128, 256), "dq": (256, 256), "dkv": (128, 128)},
    (192, 128): {"fwd": (256, 512), "dq": (256, 256), "dkv": (256, 256)},
}


# ------------------------------------------------------------------ tile plan
class TilePlan(NamedTuple):
    tile_q: int   # rows of a score tile (columns of dk/dv's transposed one)
    tile_k: int   # columns of a score tile
    major: int    # rows of a grid block (q and kv alike); divides s_pad
    s_pad: int    # S padded up to a multiple of every tile


class TilePlans(NamedTuple):
    fwd: TilePlan
    dq: TilePlan
    dkv: TilePlan


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def vmem_bytes(major: int, head_dim: int, itemsize: int) -> int:
    """VMEM one grid step of the hungriest kernel holds for a major block:
    six [major, D] operand/result blocks and two [8, major] f32 rows, double-
    buffered by the pipeline, plus the forward's m, l, acc (or the backward's
    two f32 accumulators). The minor dimension occupies whole 128-lane tiles.
    `head_dim`: the widest operand's (latent attention: q's), which bounds
    every block of the call from above."""
    lanes = _round_up(head_dim, _LANES)
    blocks = 2 * (6 * major * lanes * itemsize + 2 * 8 * major * 4)
    scratch = major * (2 * _LANES + lanes) * 4
    return blocks + scratch


def tile_plan(seq_len: int, head_dim: int, dtype,
              block_q: Optional[int] = None,
              block_k: Optional[int] = None, *,
              v_dim: Optional[int] = None) -> TilePlans:
    """The schedule of the three kernels for ``[B·H, seq_len, head_dim]``
    (``head_dim``: q's and the whole key's; ``v_dim``: v's, where it is
    another).

    Tiles are ``_TILES``' entry for the two widths, else its default (128 ·
    2^n: a score tile's lane dimension and the lane dimension of an lse row
    block need multiples of 128), never larger than the sequence rounded up
    to 128. Swept pairs: equal widths at 64 (the default entry) and (192,
    128); every other pair — equal widths at 128 and 256, and (64, 128),
    differential attention's — gets the default unswept; ``block_q``/``block_k`` override the rows/columns of all three
    (tests). The kernels share the padded
    length — the next multiple of the largest tile — and the major block:
    the largest multiple of the tiles that divides the padded length, fits
    ``VMEM_BUDGET_BYTES`` and has at most ``MAJOR_ROWS`` rows. ``MAJOR_ROWS``
    is the limit that binds for bf16 up to head_dim 256 and float32 up to
    128 (1,024 rows take 4.6–8.1 MiB there); the budget binds beyond
    (float32 at 256 and bf16 at 512: 512 rows; float32 at 512: 256).
    """
    cap = _round_up(seq_len, _LANES)
    tiles = {name: (min(_round_up(block_q or tq, _LANES), cap),
                    min(_round_up(block_k or tk, _LANES), cap))
             for name, (tq, tk) in _TILES.get(
                 (head_dim, v_dim or head_dim), _TILES[None]).items()}
    sides = {side for pair in tiles.values() for side in pair}
    step = max(sides)
    if any(step % side for side in sides):
        raise ValueError(f"tiles {tiles} do not nest")
    s_pad = _round_up(seq_len, step)
    itemsize = jnp.dtype(dtype).itemsize
    major = max((m for m in range(step, max(MAJOR_ROWS, step) + 1, step)
                 if s_pad % m == 0
                 and vmem_bytes(m, head_dim, itemsize) <= VMEM_BUDGET_BYTES),
                default=step)
    return TilePlans(**{name: TilePlan(tq, tk, major, s_pad)
                        for name, (tq, tk) in tiles.items()})


def _clamp(x, lo, hi):
    if all(isinstance(a, int) for a in (x, lo, hi)):
        return max(lo, min(x, hi))
    return jnp.clip(x, lo, hi)


def _div(x, d: int):
    """x // d for a Python int or a traced scalar. A traced negative rounds
    toward zero, not down: every caller clamps the quotient at 0."""
    return x // d if isinstance(x, int) else jax.lax.div(x, d)


def _kv_tiles(row0, col0, n_tiles: int, *, plan: TilePlan, causal: bool,
              seq_len: int):
    """For the q tile whose first row is ``row0`` and a kv-major block whose
    first column is ``col0``: ``(n_plain, n_issued)`` — kv tiles
    ``[0, n_plain)`` need no mask, ``[n_plain, n_issued)`` cross the diagonal
    (or hold padded columns), the rest are never issued."""
    tq, tk = plan.tile_q, plan.tile_k
    if causal:
        # issued while the tile's first column <= the q tile's last row;
        # plain while its last column <= the q tile's first row
        n_issued = _clamp(_div(row0 + tq - col0 + tk - 1, tk), 0, n_tiles)
        n_plain = _clamp(_div(row0 - col0 + 1, tk), 0, n_issued)
        return n_plain, n_issued
    if plan.s_pad == seq_len:
        return n_tiles, n_tiles
    return _clamp(_div(seq_len - col0, tk), 0, n_tiles), n_tiles


def _q_tiles(col0, row0, n_tiles: int, *, plan: TilePlan, causal: bool,
             seq_len: int):
    """For the kv tile whose first column is ``col0`` and a q-major block
    whose first row is ``row0``: ``(first, n_masked_end)`` — q tiles
    ``[first, n_masked_end)`` cross the diagonal (or, non-causal, meet padded
    kv rows), ``[n_masked_end, n_tiles)`` need no mask, earlier ones are
    never issued."""
    tq, tk = plan.tile_q, plan.tile_k
    if causal:
        # issued once the tile's last row >= the kv tile's first column;
        # plain once its first row >= the kv tile's last column
        first = _clamp(_div(col0 - row0, tq), 0, n_tiles)
        plain_from = _clamp(_div(col0 + tk - 1 - row0 + tq - 1, tq),
                            first, n_tiles)
        return first, plain_from
    if plan.s_pad == seq_len:
        return 0, 0
    has_padding = col0 + tk > seq_len
    if isinstance(has_padding, bool):
        return 0, (n_tiles if has_padding else 0)
    return 0, jnp.where(has_padding, n_tiles, 0)


def _window_kv_tiles(row0, col0, n_tiles: int, *, plan: TilePlan, window: int):
    """`_kv_tiles` under a causal window of `window` keys: ``(first, edge_end,
    plain_end, n_issued)`` — kv tiles ``[first, edge_end)`` cross the
    window's lower edge, ``[edge_end, plain_end)`` need no mask,
    ``[plain_end, n_issued)`` cross the diagonal; tiles before ``first`` lie
    wholly before the window of the q tile's FIRST row (whose window starts
    earliest) and are never issued. A tile that crosses both edges (a window
    narrower than two tiles) is in the first range: masked tiles apply both
    masks."""
    tq, tk = plan.tile_q, plan.tile_k
    n_plain, n_issued = _kv_tiles(row0, col0, n_tiles, plan=plan, causal=True,
                                  seq_len=plan.s_pad)
    # skipped while the tile's last column < the first row's first key;
    # edge while its first column < the LAST row's first key
    first = _clamp(_div(row0 - window + 1 - col0, tk), 0, n_issued)
    edge_end = _clamp(_div(row0 + tq - window - col0 + tk - 1, tk),
                      first, n_issued)
    return first, edge_end, _clamp(n_plain, edge_end, n_issued), n_issued


def _window_q_tiles(col0, row0, n_tiles: int, *, plan: TilePlan, window: int):
    """`_q_tiles` under a causal window: ``(first, diag_end, plain_end,
    end)`` — q tiles ``[first, diag_end)`` cross the diagonal, ``[diag_end,
    plain_end)`` need no mask, ``[plain_end, end)`` cross the window's lower
    edge; from ``end`` on every row's window starts past the kv tile."""
    tq, tk = plan.tile_q, plan.tile_k
    first, plain_from = _q_tiles(col0, row0, n_tiles, plan=plan, causal=True,
                                 seq_len=plan.s_pad)
    # issued while the tile's first row's first key <= the kv tile's last
    # column; plain while its LAST row's first key <= the first column
    end = _clamp(_div(col0 + tk + window - 1 - row0 + tq - 1, tq),
                 first, n_tiles)
    diag_end = _clamp(plain_from, first, end)
    plain_end = _clamp(_div(col0 + window - row0, tq), diag_end, end)
    return first, diag_end, plain_end, end


def _seq_blocks(plan: TilePlan, window: Optional[int]) -> int:
    """Major blocks on the sequentially walked grid axis: all of them, or
    with a window the most one block's window can reach (a q block its kv
    blocks, a kv block its q blocks: the same count)."""
    n_major = plan.s_pad // plan.major
    if window is None:
        return n_major
    return min(n_major, -(-(window - 1) // plan.major) + 1)


def _first_block(i, n_major: int, n_seq: int, *, trailing: bool):
    """The first of the `n_seq` consecutive major blocks that grid row `i`
    (a program id or a Python int) walks. `trailing` (forward, dq): the kv
    blocks that END at the diagonal block, ``i − n_seq + 1 … i``, shifted up
    where that would start before block 0 (the surplus then lies past the
    diagonal, where the causal bound issues nothing). Otherwise (dk/dv): the
    q blocks that START at it, ``i … i + n_seq − 1``, shifted down at the
    sequence's end likewise."""
    if trailing:
        lo = i - (n_seq - 1)
        return max(lo, 0) if isinstance(i, int) else jnp.maximum(lo, 0)
    hi = n_major - n_seq
    return min(i, hi) if isinstance(i, int) else jnp.minimum(i, hi)


def _step_blocks(plan: TilePlan, window: Optional[int], *, transposed: bool,
                 ids=None):
    """``(q-major block, kv-major block)`` of a grid step — of this one, by
    its program ids, or of step ``ids = (i, j)`` given as Python ints (the
    counters). The forward and dq walk a q block's kv blocks, dk/dv
    (`transposed`) a kv block's q blocks. One major block is (0, 0) and
    reads no program id."""
    n_major = plan.s_pad // plan.major
    if n_major == 1:
        return 0, 0
    i, j = ids or (pl.program_id(1), pl.program_id(2))
    n_seq = _seq_blocks(plan, window)
    if n_seq < n_major:
        j = _first_block(i, n_major, n_seq, trailing=not transposed) + j
    return (j, i) if transposed else (i, j)


def _row_bounds(plan: TilePlan, row0_major: int, col0_major: int, *,
                transposed: bool, causal: bool, seq_len: int,
                window: Optional[int]):
    """What a grid step walks, row of tiles by row of tiles, for a q-major
    block that starts at row `row0_major` and a kv-major block at column
    `col0_major`: a q tile's kv tiles (`transposed`, dk/dv: a kv tile's q
    tiles) as ``(bounds, masked_first)`` for `_phases`."""
    tq, tk, major = plan.tile_q, plan.tile_k, plan.major
    kw = dict(plan=plan, causal=causal, seq_len=seq_len, window=window)
    if transposed:
        return [(_q_bounds(col0_major + ki * tk, row0_major, major // tq,
                           **kw), True) for ki in range(major // tk)]
    return [_kv_bounds(row0_major + qi * tq, col0_major, major // tk, **kw)
            for qi in range(major // tq)]


def _issued(rows) -> int:
    return sum(bounds[-1] - bounds[0] for bounds, _ in rows)


def _masked(rows) -> int:
    """The tiles among them that build a mask (`_phases`' masked ranges)."""
    return sum(hi - lo for bounds, masked_first in rows
               for lo, hi in list(zip(bounds, bounds[1:]))[not masked_first::2])


class _Case(NamedTuple):
    when: object     # True, False, or a traced bool: does this step run it
    row0: int        # the q-major block's first row and the kv-major
    col0: int        # block's first column, as the bounds and masks see them
    rows: list       # `_row_bounds` there


def _grid_cases(plan: TilePlan, q_block, kv_block, *, transposed: bool,
                causal: bool, seq_len: int, window: Optional[int]):
    """The schedules a grid step can have, each with Python-int trip counts,
    and the condition under which a step runs it (`_step_blocks` gives the
    blocks: traced in a kernel of several major blocks, else ints).

    The trip counts depend on where the q-major block lies FROM the kv-major
    block, ``d = q_block − kv_block``, not on where either lies: causal,
    ``d < 0`` issues nothing, ``d = 0`` is the diagonal block, and ``d ≥ 1``
    is a whole block, or with a window whatever its lower edge leaves of
    one (``d < `_seq_blocks```), so a case sees the q block at row
    ``d · major`` and the kv block at column 0. Non-causal only the last kv
    block can hold padded columns. Consecutive offsets that walk the same
    tiles and build no mask (whole blocks) are one case."""
    kw = dict(transposed=transposed, causal=causal, seq_len=seq_len,
              window=window)
    major = plan.major

    def case(when, row0, col0):
        return _Case(when, row0, col0, _row_bounds(plan, row0, col0, **kw))

    if not causal:
        last = plan.s_pad // major - 1
        if plan.s_pad == seq_len or last == 0:
            return [case(True, 0, 0)]
        return [case(kv_block < last, 0, 0),
                case(kv_block == last, 0, last * major)]
    d = q_block - kv_block
    cases, spans = [], []          # spans: the [lo, hi] of d of each case
    for off in range(_seq_blocks(plan, window)):
        new = case(None, off * major, 0)
        if cases and new.rows == cases[-1].rows and not _masked(new.rows):
            spans[-1][1] = off
        elif _issued(new.rows):
            cases.append(new)
            spans.append([off, off])
    return [c._replace(when=(d == lo) if lo == hi else (d >= lo) & (d <= hi))
            for c, (lo, hi) in zip(cases, spans)]


def static_tile_share(plan: TilePlan, seq_len: int,
                      window: Optional[int] = None, *, causal: bool = True,
                      transposed: bool = False) -> float:
    """Of the tiles a kernel issues over a head's grid steps (by the masks'
    own bounds at each step's blocks), the share a loop with a Python-int
    trip count walks: the tiles of the case `_grid_cases` gives the step.
    The sibling of `issued_area_ratio`: 1.0 says every step of the grid
    meets a case and the case issues exactly the step's tiles (0.0 for
    several major blocks before PR 39, whose bounds came from program ids).
    `transposed`: dk/dv's walk."""
    n_major, major = plan.s_pad // plan.major, plan.major
    kw = dict(transposed=transposed, causal=causal, seq_len=seq_len,
              window=window)
    issued = static = 0
    for i in range(n_major):
        for j in range(_seq_blocks(plan, window)):
            q_block, kv_block = _step_blocks(plan, window,
                                             transposed=transposed,
                                             ids=(i, j))
            issued += _issued(_row_bounds(plan, q_block * major,
                                          kv_block * major, **kw))
            static += sum(_issued(case.rows) for case in
                          _grid_cases(plan, q_block, kv_block, **kw)
                          if case.when)
    return static / issued


def issued_area_ratio(plan: TilePlan, seq_len: int,
                      window: Optional[int] = None) -> float:
    """Score elements the causal forward issues ÷ elements the mask keeps:
    the engagement counter of the schedule (1.5 with 512 × 512 blocks at
    S = 1,024; 1.0 would be element granularity). With a window, of the
    windowed schedule and the windowed mask (`window_plan`)."""
    if window is not None:
        counted = window_plan(seq_len, window, plan)
        return counted["issued_area"] / counted["kept_area"]
    issued = 0
    for row0 in range(0, plan.s_pad, plan.tile_q):
        for col0 in range(0, plan.s_pad, plan.major):
            _, n = _kv_tiles(row0, col0, plan.major // plan.tile_k, plan=plan,
                             causal=True, seq_len=seq_len)
            issued += n * plan.tile_q * plan.tile_k
    return issued / (seq_len * (seq_len + 1) / 2)


def window_plan(seq_len: int, window: int, plan: TilePlan) -> dict:
    """What the forward (or dq: the same walk) does under a causal window,
    from the plan alone, by the kernel's own trip counts: `kept_area` score
    elements the mask keeps (``W·S − W(W−1)/2`` for W ≤ S), `issued_area`
    elements of the tiles it issues, `tiles_issued`, `tiles_masked` (those
    that build a mask), `tiles_skipped` (tiles the causal schedule issues
    and the window drops), `grid_steps` a head and `blocks_fetched` (K/V
    major blocks a head; the causal schedule's are S/major·(S/major+1)/2)."""
    n_major, n_tiles = plan.s_pad // plan.major, plan.major // plan.tile_k
    n_seq = _seq_blocks(plan, window)
    issued = masked = causal_issued = 0
    fetched = set()
    for i in range(n_major):
        first_block = max(i - (n_seq - 1), 0)
        for qi in range(plan.major // plan.tile_q):
            row0 = i * plan.major + qi * plan.tile_q
            for j in range(n_major):
                col0 = j * plan.major
                causal_issued += _kv_tiles(row0, col0, n_tiles, plan=plan,
                                           causal=True, seq_len=seq_len)[1]
                first, edge_end, plain_end, end = _window_kv_tiles(
                    row0, col0, n_tiles, plan=plan, window=window)
                if end > first:
                    # the grid walks every block that has a tile to issue
                    assert first_block <= j < first_block + n_seq, (i, j)
                    fetched.add((i, j))
                issued += end - first
                masked += (edge_end - first) + (end - plain_end)
    w = min(window, seq_len)
    return {"kept_area": w * seq_len - w * (w - 1) // 2,
            "issued_area": issued * plan.tile_q * plan.tile_k,
            "tiles_issued": issued, "tiles_masked": masked,
            "tiles_skipped": causal_issued - issued,
            "grid_steps": n_major * n_seq, "blocks_fetched": len(fetched)}


# -------------------------------------------------------------------- helpers
_NT = (((1,), (1,)), ((), ()))   # a · bᵀ
_NN = (((1,), (0,)), ((), ()))   # a · b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _scaled(x, scale):
    """x · scale in x's dtype, via f32 (exact for the power-of-two scales of
    head_dim 64 and 256)."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _columns(refs):
    """The column ranges, as slices, of an array as wide as the key operands
    `refs` together, by their last dimensions in order: all of it where
    there is one (the k operand; with latent attention a head's own columns,
    then the shared ones)."""
    if len(refs) == 1:
        return [slice(None)]
    widths = [r.shape[-1] for r in refs]
    return [slice(sum(widths[:i]), sum(widths[:i + 1]))
            for i in range(len(refs))]


def _split(x, refs):
    """x [rows, ΣD] by `_columns`: x itself where there is one operand."""
    return [x if c == slice(None) else x[:, c] for c in _columns(refs)]


def _score(a_parts, b_parts):
    """Σ a·bᵀ over the column ranges: a score tile (or its transpose)."""
    s = _dot(a_parts[0], b_parts[0], _NT)
    for a, b in zip(a_parts[1:], b_parts[1:]):
        s = s + _dot(a, b, _NT)
    return s


def _lanes(x, n: int):
    """A lane-broadcast [rows, 128] value widened (or cut) to n lanes."""
    if n <= _LANES:
        return x[:, :n]
    return jnp.tile(x, (1, -(-n // _LANES)))[:, :n]


def _lane_partial_sum(x):
    """[rows, n·128] → [rows, 128]: adds the 128-lane column chunks (whole
    vector registers); the cross-lane reduction is left to the caller."""
    out = x[:, :_LANES]
    for c in range(1, x.shape[1] // _LANES):
        out = out + x[:, c * _LANES:(c + 1) * _LANES]
    return out


def _rows_to_cols(rows8):
    """[8, n] (8 identical rows, as lse/delta are stored) → [n, 128]
    lane-broadcast column."""
    return jnp.transpose(jnp.tile(rows8, (_LANES // 8, 1)))


def _cols_to_rows(cols):
    """[n, 128] lane-broadcast column → [8, n] identical rows."""
    return jnp.transpose(cols)[:8]


def _keep(shape, row0, col0, *, causal, seq_len, transposed=False,
          window=None):
    """The mask of a tile that crosses the diagonal (causal) or holds padded
    kv positions (non-causal); with a window, of one that crosses either of
    its edges. ``transposed``: the tile is k·qᵀ, kv on rows."""
    q_dim, k_dim = (1, 0) if transposed else (0, 1)
    kv = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, k_dim)
    if causal:
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
        if window is None:
            return rows >= kv
        return (rows >= kv) & (kv > rows - window)
    return kv < seq_len


def _phases(bounds, body, carry, *, masked_first: bool):
    """fori_loops over [b0, b1), [b1, b2), … with body(i, carry, masked),
    masked and plain ranges in turn."""
    masked = masked_first
    for lo, hi in zip(bounds, bounds[1:]):
        carry = _loop(lo, hi, functools.partial(body, masked=masked), carry)
        masked = not masked
    return carry


def _loop(lo: int, hi: int, body, carry):
    """fori_loop over tiles, unrolled: every trip count of the kernels is a
    Python int (`_grid_cases`), so that the scheduler overlaps one tile's
    softmax with the next tile's matmul — 1.8× on the v5e at D 64 (PR 26),
    2.2× a call at D 128 (PR 39)."""
    return jax.lax.fori_loop(lo, hi, body, carry, unroll=True)


def _ds(i, size: int):
    return pl.ds(pl.multiple_of(i * size, size), size)


def _kv_bounds(row0, col0, n_tiles, *, plan, causal, seq_len, window):
    """The kv-tile ranges of a q tile as `_phases` walks them: (bounds,
    whether the first range is the masked one)."""
    if window is None:
        n_plain, n_issued = _kv_tiles(row0, col0, n_tiles, plan=plan,
                                      causal=causal, seq_len=seq_len)
        return (0, n_plain, n_issued), False
    return _window_kv_tiles(row0, col0, n_tiles, plan=plan,
                            window=window), True


def _q_bounds(col0, row0, n_tiles, *, plan, causal, seq_len, window):
    """The q-tile ranges of a kv tile as `_phases` walks them."""
    if window is None:
        first, plain_from = _q_tiles(col0, row0, n_tiles, plan=plan,
                                     causal=causal, seq_len=seq_len)
        return (first, plain_from, n_tiles)
    return _window_q_tiles(col0, row0, n_tiles, plan=plan, window=window)


def _walk(row, plan: TilePlan, *, transposed: bool, causal: bool,
          seq_len: int, window: Optional[int]):
    """Run ``row(i, case, bounds, masked_first)`` for every row of tiles of
    the case this grid step meets (`_grid_cases`), under `pl.when` where
    the grid has more than one: every row written out with its own trip
    counts. (A whole block's rows are all alike, and ONE loop around one
    row's code holds less of it — and ran the forward 1.9 × slower at
    [28, 16384, 128] on the v5e, for no `setup_s` that showed: PR 39,
    `PERF.md` §6.)"""
    blocks = _step_blocks(plan, window, transposed=transposed)
    for case in _grid_cases(plan, *blocks, transposed=transposed,
                            causal=causal, seq_len=seq_len, window=window):
        def run(case=case):
            for i, (bounds, masked_first) in enumerate(case.rows):
                if bounds[-1] > bounds[0]:
                    row(i, case, bounds, masked_first)
        if case.when is True:
            run()
        elif case.when is not False:
            pl.when(case.when)(run)


# -------------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, *refs, scale, causal, plan, seq_len,
                window=None):
    # behind k: [the shared key columns,] v; o, lse; m, l, acc
    *k_more, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    k_refs = (k_ref, *k_more)
    tq, tk, major = plan.tile_q, plan.tile_k, plan.major
    n_seq = _seq_blocks(plan, window)
    head_dim = v_ref.shape[-1]
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def row(qi, case, bounds, masked_first):
        rows = slice(qi * tq, (qi + 1) * tq)
        q = _split(_scaled(q_ref[0, rows, :], scale), k_refs)

        def body(t, carry, masked):
            m, l, acc = carry               # [tq,128], [tq,128], [tq,D]
            cols = _ds(t, tk)
            s = _score(q, [r[0, cols, :] for r in k_refs])   # [tq, tk]
            if masked:
                s = jnp.where(_keep(s.shape, case.row0 + qi * tq,
                                    case.col0 + t * tk, causal=causal,
                                    seq_len=seq_len, window=window),
                              s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - _lanes(m_new, tk))
            l = alpha * l + _lane_partial_sum(p)
            v = v_ref[0, cols, :]
            acc = acc * _lanes(alpha, head_dim) + _dot(p.astype(v.dtype), v, _NN)
            return m_new, l, acc

        m, l, acc = _phases(
            bounds, body,
            (m_scr[rows, :], l_scr[rows, :], acc_scr[rows, :]),
            masked_first=masked_first)
        m_scr[rows, :] = m
        l_scr[rows, :] = l
        acc_scr[rows, :] = acc

    _walk(row, plan, transposed=False, causal=causal, seq_len=seq_len,
          window=window)

    @pl.when(j == n_seq - 1)
    def _finish():
        for qi in range(major // tq):
            rows = slice(qi * tq, (qi + 1) * tq)
            l = jnp.maximum(jnp.sum(l_scr[rows, :], axis=1, keepdims=True),
                            1e-30)
            o_ref[0, rows, :] = (acc_scr[rows, :] / l).astype(o_ref.dtype)
            # lse is materialized as [BH, q tiles, 8, tile_q] (8 broadcast
            # sublanes) to satisfy the TPU (8, 128) block-tiling constraint.
            lse_ref[0, qi] = _cols_to_rows(m_scr[rows, :] + jnp.log(l))


def _row_spec(plan: TilePlan, index_map):
    """lse/delta rows: [BH, q tiles, 8, tile_q], a major block's tiles."""
    return pl.BlockSpec((1, plan.major // plan.tile_q, 8, plan.tile_q),
                        index_map)


def _kv_head(group: int):
    """Row of K and V, [B·KV, S, D], that row b of q, [B·H, S, D], reads:
    query head i its KV head i // group, so row b // group (H = KV · group).
    With one KV head a query head, b itself."""
    if group == 1:
        return lambda b: b
    return lambda b: b // group


def _kv_index(causal: bool, group: int, n_major: int, n_seq: int):
    """Index map of the forward's and dq's K/V blocks. Causal: kv-major
    blocks past the q block's diagonal are never needed; clamping their
    index keeps the pipeline from fetching them. `n_seq` < `n_major` (a
    window): grid step j is the j-th of the blocks `_first_block` gives."""
    head = _kv_head(group)
    if n_seq < n_major:
        return lambda b, i, j: (head(b), jnp.minimum(
            _first_block(i, n_major, n_seq, trailing=True) + j, i), 0)
    if causal:
        return lambda b, i, j: (head(b), jnp.minimum(j, i), 0)
    return lambda b, i, j: (head(b), j, 0)


def _call_name(kernel: str, window: Optional[int], latent: bool) -> str:
    """`pallas_call`'s `name`, which is the HLO instruction's and so the
    trace event's: `flash_fwd` / `flash_dq` / `flash_dkv`, `flash_window_…`
    where the call has a window and `flash_latent_…` where it has shared key
    columns (never both: `flash_attention`). Without one the instruction is
    named after whatever function encloses the call."""
    if latent:
        return f"flash_latent_{kernel}"
    return f"flash_{kernel}" if window is None else f"flash_window_{kernel}"


def _pad_rows(arrays, s_pad: int):
    """Each [·, S, ·] array zero-padded to `s_pad` rows."""
    return [jnp.pad(x, [(0, 0), (0, s_pad - x.shape[1]), (0, 0)])
            for x in arrays]


def _flash_fwd(q, k, v, shared=(), *, scale, causal, block_q, block_k,
               interpret, window=None):
    """q [BH, S, D], k, v [B·KV, S, D] -> (o [BH,S,D], lse [BH,S]).
    `shared`: none, or the one array [B, S, Dr] of key columns every head
    reads behind its own — q is then D = Dn + Dr wide, k Dn, v and o Dv.

    Sequence lengths that don't divide the tiles are zero-padded up to the
    next multiple; padded KV columns are masked inside the kernel and padded
    Q rows sliced off the output.
    """
    BH, S, D = q.shape
    Dv = v.shape[-1]
    plan = tile_plan(S, D, q.dtype, block_q, block_k, v_dim=Dv).fwd
    S_pad, major = plan.s_pad, plan.major
    if S_pad != S:
        q, k, v, *shared = _pad_rows((q, k, v, *shared), S_pad)
    n_major = S_pad // major
    n_seq = _seq_blocks(plan, window)

    def kv_spec(x):     # row b of q reads row b // (BH / rows of x) of x
        return pl.BlockSpec((1, major, x.shape[-1]), _kv_index(
            causal, BH // x.shape[0], n_major, n_seq))

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, plan=plan,
                          seq_len=S, window=window),
        grid=(BH, n_major, n_seq),
        in_specs=[pl.BlockSpec((1, major, D), lambda b, i, j: (b, i, 0)),
                  *(kv_spec(x) for x in (k, *shared, v))],
        out_specs=[pl.BlockSpec((1, major, Dv), lambda b, i, j: (b, i, 0)),
                   _row_spec(plan, lambda b, i, j: (b, i, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S_pad, Dv), q.dtype),
            jax.ShapeDtypeStruct((BH, S_pad // plan.tile_q, 8, plan.tile_q),
                                 jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((major, _LANES), jnp.float32),
            pltpu.VMEM((major, _LANES), jnp.float32),
            pltpu.VMEM((major, Dv), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=_call_name("fwd", window, bool(shared)),
    )(q, k, *shared, v)
    return o[:, :S], lse[:, :, 0, :].reshape(BH, S_pad)[:, :S]


def _to_bh(x):
    """[B, S, H, D] → [B·H, S, D], the kernels' layout."""
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, shared, heads, scale, causal, block_q, block_k, interpret,
           window):
    """q [B·H, S, D], k, v [B·KV, S, D] → o [B, S, H, D]. `shared`: () or
    (the key columns every head reads [B, S, Dr],), as `_flash_fwd`'s."""
    return _flash_vjp_fwd(q, k, v, shared, heads, scale, causal, block_q,
                          block_k, interpret, window)[0]


def _flash_vjp_fwd(q, k, v, shared, heads, scale, causal, block_q, block_k,
                   interpret, window=None):
    """The backward pass keeps `o` as the model reads it, [B, S, H, D], not
    as the kernel wrote it: [B·H, S, D] with D = 64 is padded to 128 lanes
    in HBM, twice the bytes, and the backward needs `o` only for the row
    sums `delta`, which it takes in either layout."""
    o, lse = _flash_fwd(
        q, k, v, shared, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret, window=window,
    )
    BH, S, D = o.shape
    o = o.reshape(BH // heads, heads, S, D).transpose(0, 2, 1, 3)
    o, lse = (checkpoint_name(x, name)
              for x, name in zip((o, lse), RESIDUAL_NAMES))
    return o, (q, k, v, shared, o, lse)


# ------------------------------------------------------------------- backward
def _bwd_dq_kernel(q_ref, k_ref, *refs, scale, causal, plan, seq_len,
                   window=None):
    # behind k: [the shared key columns,] v, do, lse, delta; dq; its scratch
    *k_more, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs
    k_refs = (k_ref, *k_more)
    parts = _columns(k_refs)
    tq, tk = plan.tile_q, plan.tile_k
    n_seq = _seq_blocks(plan, window)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def row(qi, case, bounds, masked_first):
        rows = slice(qi * tq, (qi + 1) * tq)
        q = _split(_scaled(q_ref[0, rows, :], scale), k_refs)
        do = do_ref[0, rows, :]
        lse = _lanes(_rows_to_cols(lse_ref[0, qi]), tk)       # [tq, tk]
        delta = _lanes(_rows_to_cols(delta_ref[0, qi]), tk)

        def body(t, dq, masked):          # dq: an accumulator a k operand
            cols = _ds(t, tk)
            k = [r[0, cols, :] for r in k_refs]
            s = _score(q, k)                                   # [tq, tk]
            if masked:
                s = jnp.where(_keep(s.shape, case.row0 + qi * tq,
                                    case.col0 + t * tk, causal=causal,
                                    seq_len=seq_len, window=window),
                              s, NEG_INF)
            p = jnp.exp(s - lse)
            dp = _dot(do, v_ref[0, cols, :], _NT)              # do · vᵀ
            ds = (p * (dp - delta)).astype(k[0].dtype)
            return tuple(acc + _dot(ds, part, _NN)             # ds · k
                         for acc, part in zip(dq, k))

        dq = _phases(bounds, body, tuple(dq_scr[rows, c] for c in parts),
                     masked_first=masked_first)
        for c, acc in zip(parts, dq):
            dq_scr[rows, c] = acc

    _walk(row, plan, transposed=False, causal=causal, seq_len=seq_len,
          window=window)

    @pl.when(j == n_seq - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, *refs, scale, causal, plan, seq_len, window=None):
    # n k operands (a head's own columns [, the shared ones]); v, do, lse,
    # delta; a dk a k operand, dv; their scratches: 3·n + 6 behind q
    n = (len(refs) - 6) // 3
    k_refs, (v_ref, do_ref, lse_ref, delta_ref) = refs[:n], refs[n:n + 4]
    dk_refs, dv_ref = refs[n + 4:2 * n + 4], refs[2 * n + 4]
    dk_scrs, dv_scr = refs[2 * n + 5:-1], refs[-1]
    tq, tk = plan.tile_q, plan.tile_k
    n_seq = _seq_blocks(plan, window)
    j = pl.program_id(2)   # kv-major blocks parallel, q-major accumulated

    @pl.when(j == 0)
    def _init():
        for scr in (*dk_scrs, dv_scr):
            scr[...] = jnp.zeros_like(scr)

    def row(ki, case, bounds, masked_first):
        cols = slice(ki * tk, (ki + 1) * tk)
        k = [_scaled(r[0, cols, :], scale) for r in k_refs]
        v = v_ref[0, cols, :]

        def body(t, carry, masked):
            *dk, dv = carry
            rows = _ds(t, tq)
            q = _split(q_ref[0, rows, :], k_refs)
            do = do_ref[0, rows, :]
            st = _score(k, q)                                  # [tk, tq]
            if masked:
                st = jnp.where(_keep(st.shape, case.row0 + t * tq,
                                     case.col0 + ki * tk, causal=causal,
                                     seq_len=seq_len, transposed=True,
                                     window=window),
                               st, NEG_INF)
            pt = jnp.exp(st - lse_ref[0, t, 0:1, :])           # row lse
            dv = dv + _dot(pt.astype(do.dtype), do, _NN)       # pᵀ · do
            dpt = _dot(v, do, _NT)                             # v · doᵀ
            dst = (pt * (dpt - delta_ref[0, t, 0:1, :])).astype(q[0].dtype)
            return (*(acc + _dot(dst, part, _NN)               # dsᵀ · q
                      for acc, part in zip(dk, q)), dv)

        scrs = (*dk_scrs, dv_scr)
        carry = _phases(bounds, body, tuple(scr[cols, :] for scr in scrs),
                        masked_first=masked_first)
        for scr, acc in zip(scrs, carry):
            scr[cols, :] = acc

    _walk(row, plan, transposed=True, causal=causal, seq_len=seq_len,
          window=window)

    @pl.when(j == n_seq - 1)
    def _finish():
        for dk_ref, dk_scr in zip(dk_refs, dk_scrs):
            dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _tile_rows(x, plan: TilePlan):
    """[BH, S_pad] → [BH, q tiles, 8, tile_q]: each q tile's values as 8
    identical rows, so the (8, 128) TPU tile constraint holds for row-vector
    inputs and a tile is picked by a leading index (the same layout the
    forward writes its lse in)."""
    BH, S_pad = x.shape
    x = x.reshape(BH, S_pad // plan.tile_q, 1, plan.tile_q)
    return jnp.broadcast_to(x, (BH, x.shape[1], 8, plan.tile_q))


def _flash_bwd(q, k, v, shared, lse, delta, do, *, scale, causal, block_q,
               block_k, interpret, window=None):
    """Pallas backward: returns (dq [BH, S, D], dk, dv [B·KV, S, D], and the
    gradients of `shared`, as many). `delta` [BH, S]: the row sums of do · o.

    Grouped KV heads: the dq kernel reads each query head's KV head through
    its index map, as the forward does. The dk/dv kernel runs a QUERY head
    a grid row and writes that head's dk and dv, [BH, S, D] in the operands'
    dtype; the group's are summed in float32 outside the kernel. (An
    accumulation over the group inside it would make the group a grid axis
    and its blocks' index maps functions of two program ids: the same
    traffic but for the [BH, S, D] partials, 2 · 2 bytes an element of q.)
    The shared key columns are a group of every head of a batch row: the
    kernel writes each head's part of their gradient, [BH, S, Dr], and the
    heads are summed the same way."""
    BH, S, D = q.shape
    plans = tile_plan(S, D, q.dtype, block_q, block_k, v_dim=v.shape[-1])
    S_pad, major = plans.dq.s_pad, plans.dq.major
    if S_pad != S:
        q, k, v, do, *shared = _pad_rows((q, k, v, do, *shared), S_pad)
        lse = jnp.pad(lse, [(0, 0), (0, S_pad - S)])
        delta = jnp.pad(delta, [(0, 0), (0, S_pad - S)])
    n_major = S_pad // major
    n_seq = _seq_blocks(plans.dq, window)
    kw = dict(scale=scale, causal=causal, seq_len=S, window=window)
    keys = (k, *shared)            # what q's columns are scored against

    def scratch(x):
        return pltpu.VMEM((major, x.shape[-1]), jnp.float32)

    def spec(x, index_map):
        return pl.BlockSpec((1, major, x.shape[-1]), index_map)

    # dq: q-major blocks parallel, kv-major sequential, as the forward
    def kv_spec(x):
        return spec(x, _kv_index(causal, BH // x.shape[0], n_major, n_seq))

    def q_index(b, i, j):
        return (b, i, 0)

    row_q = _row_spec(plans.dq, lambda b, i, j: (b, i, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, plan=plans.dq, **kw),
        grid=(BH, n_major, n_seq),
        in_specs=[spec(q, q_index), *(kv_spec(x) for x in (*keys, v)),
                  spec(do, q_index), row_q, row_q],
        out_specs=[spec(q, q_index)],
        out_shape=[jax.ShapeDtypeStruct((BH, S_pad, D), q.dtype)],
        scratch_shapes=[scratch(q)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=_call_name("dq", window, bool(shared)),
    )(q, *keys, v, do, _tile_rows(lse, plans.dq),
      _tile_rows(delta, plans.dq))[0]

    # dk/dv: grid transposed — kv-major blocks parallel, q-major sequential;
    # causal: q-major blocks before the kv block's diagonal are never needed
    # (a window: nor those past the last row that reaches the kv block)
    if n_seq < n_major:
        def q_block(i, j):
            return jnp.maximum(
                _first_block(i, n_major, n_seq, trailing=False) + j, i)
    elif causal:
        def q_block(i, j):
            return jnp.maximum(j, i)
    else:
        def q_block(i, j):
            return j

    def q_index2(b, i, j):
        return (b, q_block(i, j), 0)

    def kv_spec2(x):
        head = _kv_head(BH // x.shape[0])
        return spec(x, lambda b, i, j: (head(b), i, 0))

    row_q2 = _row_spec(plans.dkv, lambda b, i, j: (b, q_block(i, j), 0, 0))
    *dkeys, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, plan=plans.dkv, **kw),
        grid=(BH, n_major, n_seq),
        in_specs=[spec(q, q_index2), *(kv_spec2(x) for x in (*keys, v)),
                  spec(do, q_index2), row_q2, row_q2],
        out_specs=[spec(x, q_index) for x in (*keys, v)],
        out_shape=[jax.ShapeDtypeStruct((BH, S_pad, x.shape[-1]), x.dtype)
                   for x in (*keys, v)],
        scratch_shapes=[scratch(x) for x in (*keys, v)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=_call_name("dkv", window, bool(shared)),
    )(q, *keys, v, do, _tile_rows(lse, plans.dkv),
      _tile_rows(delta, plans.dkv))

    def over_heads(t, like):    # the heads that read one row of `like`
        if like.shape[0] == BH:
            return t
        return jnp.sum(t.reshape(like.shape[0], BH // like.shape[0], S_pad,
                                 t.shape[-1]).astype(jnp.float32),
                       axis=1).astype(t.dtype)

    return tuple(t[:, :S] for t in (dq, *(
        over_heads(t, like) for t, like in zip((*dkeys, dv), (*keys, v)))))


def _flash_vjp_bwd(heads, scale, causal, block_q, block_k, interpret, window,
                   res, do):
    q, k, v, shared, o, lse = res
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dq, dk, *dshared, dv = _flash_bwd(
        q, k, v, shared, lse, delta.transpose(0, 2, 1).reshape(lse.shape),
        _to_bh(do), scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret, window=window,
    )
    return dq, dk, dv, tuple(dshared)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
    window: Optional[int] = None,
    k_shared: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Flash attention over q [B, S, H, D], k, v [B, S, KV, D] (heads layout
    matching models/layers.apply_attention), KV dividing H: query head i
    reads KV head i // (H // KV). Differentiable via custom VJP.

    ``k_shared`` [B, S, Dr] (latent attention): key columns that every head
    reads behind its own. q is then Dn + Dr wide, k Dn (one KV head a query
    head), v and the result any Dv; the default scale is q's width's. No
    window with it.

    ``window`` (causal only): query i sees the keys i − window < j ≤ i, itself
    and the window − 1 before it. A window that covers the sequence is the
    causal call itself.

    The schedule (score-tile shape, major block, padding) is derived from
    ``(S, D, dtype)`` and v's width by :func:`tile_plan`; ``block_q`` /
    ``block_k`` override the score tile's rows/columns (multiples of 128)
    and exist for tests.
    """
    H, D = q.shape[2:]
    if scale is None:
        scale = 1.0 / (D**0.5)
    if window is not None:
        if not causal or window < 1:
            raise ValueError(f"window={window} needs causal attention and "
                             f"at least the query itself")
        if window >= q.shape[1]:
            window = None
    if k_shared is None:
        shared = ()
    elif window is not None or D != k.shape[-1] + k_shared.shape[-1]:
        raise ValueError(
            f"k_shared {k_shared.shape}: q's width {D} is k's "
            f"{k.shape[-1]} and the shared columns', and a call with them "
            f"has no window (window={window})")
    else:
        shared = (k_shared,)
    return _flash(_to_bh(q), _to_bh(k), _to_bh(v), shared, H, scale, causal,
                  block_q, block_k, interpret, window)
