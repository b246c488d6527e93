"""Kimi Delta Attention's rule (Kimi Linear, arXiv:2510.26692): the gated
delta rule of `ops/gated_delta.py` with the decay a VECTOR a head and token,
one number a KEY CHANNEL, in its chunked form: two Pallas TPU kernels under a
`jax.custom_vjp` where ONE TPU runs it and the tiles divide the shapes, plain
JAX under a `jax.custom_vjp` of its own everywhere else. :func:`kda` is the
entry, :func:`kda_packed` the same on ``[q | k | v]`` in one array.

The recurrence, a head at a time (state ``S`` [K, V], ``S₀ = 0``; ``g_t ≤
0`` in ``ℝᴷ`` the log-decay, ``α_t = exp(g_t)``, ``β_t`` the write strength):

    S̃_t = Diag(α_t) · S_{t−1}
    S_t = S̃_t + β_t · k_t (v_t − S̃_tᵀ k_t)ᵀ              o_t = S_tᵀ q_t

With ``γ`` the running sum of g inside a chunk of ``C`` tokens (inclusive,
per channel) the chunk is the scalar rule's WY form with the decay INSIDE
every contraction over the key channels:

    A = stril(β_i Σ_c k_ic k_jc e^{γ_ic − γ_jc})     T = (I + A)⁻¹
    P = tril(Σ_c q_ic k_jc e^{γ_ic − γ_jc})
    U = T·(β ∘ v)        W = T·(β ∘ e^γ ∘ k)         V' = U − W·S
    o = (e^γ ∘ q)·S + P·V'
    S ← Diag(e^{γ_C})·S + (e^{γ_C − γ} ∘ k)ᵀ·V'

**No exponent above zero is ever taken** (nothing bounds g below, so
``(k ∘ e^γ)(k ∘ e^−γ)ᵀ`` overflows inside a chunk). The scores' rows go in
sub-blocks of `_BASE` = 16. For a sub-block ``I`` whose first row is ``r``
and every column ``j < r``: ``(x_i ∘ e^{γ_i − γ_r}) · (k_j ∘ e^{γ_r −
γ_j})`` — both exponents at most 0 because γ only falls — one product on the
MXU a sub-block, q's and k's rows side by side. On the diagonal sub-blocks
the ``16 × 16 × K`` terms are taken element-wise at ``e^{γ_i − γ_j}``, ``i
≥ j``, masked BEFORE the exponential. ``e^γ``, ``e^{γ_C − γ}`` and
``e^{γ_C}`` are at most 1 as they stand. Both forms do exactly this.

**The plain form** (the CPU, a mesh that splits the batch, widths off the
tiles): ONE `lax.scan` over the chunks that carries the state in float32; a
step makes its chunk's scores, inverse, U and W, then the carry and the
readout. The backward keeps the inputs and every chunk's START state, walks
the chunks in reverse and rebuilds the inside of a chunk (`jax.vjp` of
:func:`_chunk`; the inverse has `gated_delta._inverse`'s rule), so nothing
of ``[C, C]`` or ``[16, 16, K]`` a row outlives a chunk. :func:`kda_plain`
is the same walk differentiated by JAX end to end, both backwards' control
in the tests. Every intermediate of a chunk goes through HBM between
fusions, and the compiler wraps the walk in relayout copies between the
token-major ``[B, T, H·K]`` arrays and ``[chunks, B, H, C, K]`` (413 ms of
the cell's 992 ms step: PERF.md §6, PR 59).

**The kernels** (``kda_fwd``, ``kda_bwd`` in HLO and trace) keep a chunk in
VMEM. Grid (batch, PAIR of heads, block of `BLOCK_TOKENS` tokens), the
blocks of a pair in order — the backward's in reverse — with the two states
``[2, K, V]`` float32 (their cotangents) in VMEM scratch. A grid step walks
its block's chunks in TWO loops (`_two_loops`):

1. *the first* builds of every chunk what reads no state (`_prepared`: γ's
   running sum, the diagonal sub-blocks' terms, the scores against earlier
   sub-blocks, ``A``, ``P``, the decayed q and k, β ∘ v) and keeps it in VMEM
   scratch (`_kept_shapes`: 0.3 MB a chunk for the forward, whose second
   loop reads eight of these arrays, 1.7 MB for the backward, which reads
   all thirty);
2. *between the loops* ``A → (I + A)⁻¹`` of ALL the block's chunks at once
   (`_inverse_many`), in place in the scratch;
3. *the second* takes ``U``, ``W``, the carry over the state and the readout
   a chunk, in the order the state needs (the backward: from the block's last
   chunk to its first, with the cotangents), reading the scratch — the
   forward's four chunks in one loop body, the backward's two a body, so
   that what of the next chunk reads no state fills this chunk's waits.

Why: a chunk's inverse reads no state, and alone it is a chain of waits —
fifteen substitution steps, then four dependent six-pass products — that a
loop body of one chunk sits out: 4.7 of a 14.1 ms forward call and 5.5 of a
28.6 ms backward call with one loop (`benchmarks/results/pr64_kda_two_loops/
ablate_shipped.jsonl`; PR 59's `ablate.jsonl` read the same of its earlier
form), where bodies of two and four chunks were slower. With the tiles of a
block's four chunks one above the other the chain is paid once a block: 1.3
ms of a forward call are left of the 4.7 (`final/ablate_change.jsonl`;
PERF.md §6, PR 64). q, k and v are read out of the conv's ``[q | k |
v]`` ``[B, T, 3·H·K]``, g out of ``[B, T, H·K]`` IN PLACE by block index
maps, o is written as ``[B, T, H·V]``: no split, head reshape or chunked
copy exists (:func:`kda_packed`; :func:`kda` lays its three side by side
for it). β comes as rows ``[B, H / 2, chunks, 8, 128]`` whose lane is (head
of the pair, token) — `_kernel_rows`, plain JAX on a 2 MB array, which JAX
differentiates. Inside a chunk the pair's ``[C, ·]`` arrays lie one above
the other (``[(r, i), ·]``, "stacked": 128 rows for the MXU) and its ``[C,
C]`` arrays side by side along the lanes (``[C, (r, j)]``, "packed"), as
`gated_delta`'s two value heads of a key head, whose `_by_head` and packed
geometry serve here — but each head has q, k and decays of its own:

* γ is a product with a triangle of ones at the highest precision (and its
  pullback the transposed product);
* the diagonal sub-blocks' terms are taken with the key channels on the
  SUBLANES (q, k, γ transposed once a chunk, ``[K, (r, i)]``): for an offset
  ``d = i − j`` a lane roll by d puts ``k_j``, ``γ_j`` under row i, and the
  sum over the channels is a sum of vregs (no cross-lane reduction a pair:
  `_band`); the 2 × 16 bands become the packed arrays by ONE transpose and
  ONE roll whose amount grows by a row (`_bands_to_packed`);
* sums over a row's lanes that do not head the chunk's chain go through the
  MXU (`_over_lanes`: three passes against ones): the XLU, which the
  diagonal terms' rolls keep busy, is what bounds a chunk;
* the rows against earlier sub-blocks are three small products, both heads'
  q and k rows of a sub-block one above the other;
* the backward rebuilds all of that from the chunk's kept START state and
  pulls it back by hand (`_scores_pull`, `_band_pull`: the products'
  cotangents rounded to `compute_dtype` where the plain form's operands'
  are), and writes the cotangents of q, k, v (three arrays, concatenated
  for the conv's backward in plain JAX), g and β's rows.

Written to HBM: o and each chunk's start state, float32 (forward); the five
cotangents (backward) — nothing of ``[C, C]``, ``[16, 16, K]`` or ``[chunks,
…]`` beside the start states. `kda_plan` gives a grid step's VMEM bytes from
shapes alone; `_use_kernel` holds them against `VMEM_BUDGET_BYTES` and
decides between the forms from `target.where`'s answer and the shapes — no
flag, no environment variable. Each kernel sits behind a module-level
`jax.jit` (one trace and one Mosaic lowering a step, whatever the number of
call sites: PERF.md §6, PR 46).

A length the chunk (the kernels: the block) does not divide is padded with
``g = 0``, ``β = 0`` tokens behind the last, which neither decay the state
nor write to it.

Precision, both forms: decays, running sums, the element-wise diagonal
terms, the triangular inverse and the carried state in float32 (the kept
start states too); the products' operands in `compute_dtype` with float32
accumulation, one pass.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import target
from ray_tpu.ops.gated_delta import (
    _BASE, _LANES, _NN, _NT, _TN, _by_head, _check, _exact, _inverse,
    _mm, _one_pass, _own, _packed_columns, _packed_geometry, _unit,
    _whole_blocks)


# ------------------------------------------------------------- the scores
def _scores(q, k, cum, *, cd):
    """q, k, γ [..., C, K] float32 -> ``Σ_c k_ic k_jc e^{γ_ic − γ_jc}`` and
    the same with q's rows, [..., C, C] float32, zero above the diagonal
    (ON it the first is ``|k_i|²``: the caller's mask takes it)."""
    mm = _mm(cd)
    C, K = k.shape[-2:]
    base = min(_BASE, C)
    m = C // base
    lead = k.shape[:-2]

    def blocks(x):
        return x.reshape(*lead, m, base, K)

    qb, kb, cb = blocks(q), blocks(k), blocks(cum)
    first = cb[..., :1, :]                          # γ_r a sub-block
    at = jnp.arange(base)
    below = (at[:, None] >= at[None, :])[..., None]
    # the diagonal sub-blocks: masked BEFORE the exponential
    decay = jnp.exp(jnp.where(
        below, cb[..., :, None, :] - cb[..., None, :, :], -jnp.inf))
    kk = jnp.sum(kb[..., :, None, :] * kb[..., None, :, :] * decay, -1)
    qk = jnp.sum(qb[..., :, None, :] * kb[..., None, :, :] * decay, -1)
    if m == 1:
        return kk[..., 0, :, :], qk[..., 0, :, :]
    # rows against every EARLIER sub-block's columns: q's and k's rows one
    # above the other, both exponents at most 0
    shrink = jnp.exp(cb - first)
    rows = jnp.concatenate([qb * shrink, kb * shrink], axis=-2)
    kk_rows, qk_rows = [kk[..., 0, :, :]], [qk[..., 0, :, :]]
    for i in range(1, m):
        n = i * base
        cols = k[..., :n, :] * jnp.exp(first[..., i, :, :] - cum[..., :n, :])
        both = mm("...ik,...jk->...ij", rows[..., i, :, :], cols)
        qk_rows.append(jnp.concatenate(
            [both[..., :base, :], qk[..., i, :, :]], axis=-1))
        kk_rows.append(jnp.concatenate(
            [both[..., base:, :], kk[..., i, :, :]], axis=-1))

    def whole(rows_of):
        return jnp.concatenate([
            jnp.pad(r, [(0, 0)] * (r.ndim - 1) + [(0, C - r.shape[-1])])
            for r in rows_of], axis=-2)

    return whole(kk_rows), whole(qk_rows)


# ---------------------------------------------------------------- a chunk
def _chunk(state, q, k, v, g, beta, *, cd, normalize):
    """One chunk: state [b, H, K, V] float32; q, k, g [b, H, C, K], v [b, H,
    C, V], β [b, H, C] -> (the state behind the chunk, o [b, H, C, V]
    float32)."""
    mm = _mm(cd)
    C = q.shape[-2]
    if normalize is not None:
        q = _unit(q, normalize) * q.shape[-1] ** -0.5
        k = _unit(k, normalize)
    cum = jnp.cumsum(g, axis=-2)                             # γ, inclusive
    kk, qk = _scores(q, k, cum, cd=cd)
    at = jnp.arange(C)
    T = _inverse(jnp.where(at[:, None] > at[None, :],
                           kk * beta[..., :, None], 0.0))
    grow = jnp.exp(cum)                                      # e^γ
    U = mm("bhij,bhjv->bhiv", T, v * beta[..., None])
    W = mm("bhij,bhjk->bhik", T, k * grow * beta[..., None])
    new = U - mm("bhik,bhkv->bhiv", W, state)                # V'
    out = (mm("bhik,bhkv->bhiv", q * grow, state)
           + mm("bhij,bhjv->bhiv", qk, new))
    last = cum[..., -1:, :]                                  # γ_C
    return (jnp.exp(last[..., 0, :])[..., None] * state
            + mm("bhik,bhiv->bhkv", k * jnp.exp(last - cum), new)), out


def _walk(q, k, v, g, beta, *, cd, normalize):
    """Chunked inputs [chunks, b, H, C, ·] -> (o likewise, every chunk's
    START state [chunks, b, H, K, V] float32)."""
    def step(state, chunk):
        after, out = _chunk(state, *chunk, cd=cd, normalize=normalize)
        return after, (out, state)

    _, b, H, _, K = q.shape
    _, (out, starts) = jax.lax.scan(
        step, jnp.zeros((b, H, K, v.shape[-1]), jnp.float32),
        (q, k, v, g, beta))
    return out, starts


# ------------------------------------------------------- its own backward
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, cd, normalize):
    return _walk(q, k, v, g, beta, cd=cd, normalize=normalize)[0]


def _rule_fwd(q, k, v, g, beta, cd, normalize):
    out, starts = _walk(q, k, v, g, beta, cd=cd, normalize=normalize)
    return out, (q, k, v, g, beta, starts)


def _rule_bwd(cd, normalize, residuals, d_out):
    *inputs, starts = residuals

    def step(d_after, chunk):
        """`d_after`: the cotangent of the state this chunk leaves; the
        chunk's inside is rebuilt from its start state."""
        start, d_o, *chunk_inputs = chunk
        _, pull = jax.vjp(
            functools.partial(_chunk, cd=cd, normalize=normalize),
            start, *chunk_inputs)
        d_start, *d_inputs = pull((d_after, d_o))
        return d_start, tuple(d_inputs)

    _, d_inputs = jax.lax.scan(
        step, jnp.zeros(starts.shape[1:], jnp.float32),
        (starts, d_out, *inputs), reverse=True)
    return d_inputs


_rule.defvjp(_rule_fwd, _rule_bwd)


# ---------------------------------------------------------------- kernels
# heads a grid step holds: their chunks' [C, ·] arrays one above the other
# are the MXU's 128 rows ("stacked", [(r, i), ·]), their [C, C] arrays side
# by side the 128 lanes ("packed", [C, (r, j)]), as `gated_delta`'s two value
# heads of a key head — here two heads with q, k and decays of their own
_PAIR = 2
# tokens a grid step holds: whole chunks, walked by two loops with all their
# inverses taken at once between them, so the block sets how many chunks
# share one chain of waits (`kda_plan`'s `inverses_at_once`: 4). On the chip,
# each kernel alone at the cell's layer (forward / backward, ms;
# `benchmarks/results/pr64_kda_two_loops/loop_probe_{b,c,h}.jsonl`): with
# one chunk a body of the second loop 128 tokens 12.60 / 26.30, 256 11.86 /
# 25.42, 512 as 256 (the first form of `_inverse_many`: 12.52 / 26.03 for
# 12.35 / 25.91); with the forward's second loop in one body 128 tokens
# 11.44, 256 10.50, 512 10.22 — but 512's backward keeps 26 MB of scratch,
# over `VMEM_BUDGET_BYTES`
BLOCK_TOKENS = 256
# what a grid step's double-buffered blocks and the state may take of VMEM
# (`_vmem_bytes`); a chunk's temporaries are the rest of what Mosaic is asked
# for (a v5e core has 128 MiB)
VMEM_BUDGET_BYTES = 16 * 1024 * 1024
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


@functools.lru_cache(maxsize=None)
def _vmem_bytes(block: int, chunk: int, k_dim: int, v_dim: int) -> int:
    """VMEM of a grid step of the backward (the larger of the two): its
    blocks — q, k, g and their cotangents [block, 2·K]; v, o's cotangent and
    v's [block, 2·V]; β's rows and theirs [block / C, 8, 128]; the chunks'
    start states [block / C, 2, K, V] — all float32 and double-buffered; the
    state's cotangent [2, K, V] float32; and what the first loop over the
    block's chunks keeps for the second, once (`_kept_shapes`, in whole
    tiles, the products' operands counted as float32, the widest they come:
    at chunk 64 and K = V = 128 nineteen stacked [128, 128] arrays — q and k
    as they are and transposed, γ transposed, three decays, three columns
    and three reaches of earlier sub-blocks, `P` by head and the four
    operands the products read — six packed [64, 128] — `A`, both scores,
    the three sub-blocks' left operands — and five columns [128, 1], a tile
    of lanes each: 1.77 MB a chunk so counted)."""
    chunks = block // chunk
    blocks = 4 * (6 * block * _PAIR * k_dim + 3 * block * _PAIR * v_dim
                  + 2 * chunks * 8 * _LANES
                  + chunks * _PAIR * k_dim * v_dim)
    kept, _ = _kept_shapes(chunks, chunk, k_dim, v_dim, jnp.dtype("float32"),
                           1e-6, None)
    return (2 * blocks + 4 * _PAIR * k_dim * v_dim
            + 4 * sum(math.prod(held.shape[:-2]) * -(-held.shape[-2] // 8) * 8
                      * -(-held.shape[-1] // _LANES) * _LANES
                      for held in kept))


def kda_plan(tokens: int, heads: int, k_dim: int, v_dim: int,
             chunk: int) -> dict:
    """What the kernels hold for `tokens` tokens of a sequence, from shapes
    alone: the chunks, how many of them a grid step inverts at once between
    its two loops, the start states kept for the backward (float32), and
    `vmem_bytes`, a grid step's blocks, state and kept scratch
    (`_vmem_bytes`), held against `VMEM_BUDGET_BYTES` by `_use_kernel`. (The
    rule's FLOPs and bytes are the model's accounting:
    `accounting/kimi_linear.py`.)"""
    chunks = -(-tokens // chunk)
    return {
        "chunks": chunks, "block_tokens": BLOCK_TOKENS,
        "inverses_at_once": BLOCK_TOKENS // chunk,
        "state_bytes": 4 * chunks * heads * k_dim * v_dim,
        "vmem_bytes": _vmem_bytes(BLOCK_TOKENS, chunk, k_dim, v_dim),
    }


def _use_kernel(platform: str, devices: int, chunk: int, heads: int,
                k_dim: int, v_dim: int) -> bool:
    """Whether the rule goes through the Pallas kernels: on ONE TPU (a mesh
    that splits the batch would need the call under a `shard_map`, which is
    not written) where the tiles divide the shapes — a head's K and V
    columns whole lane tiles, v's first column in ``[q | k | v]`` a whole
    number of a pair's value blocks, the heads whole pairs, the chunk whole
    sub-blocks of `_BASE` and a pair's chunks the 128 lanes of the packed
    ``[C, 2·C]`` arrays (chunk 64), whole chunks a block — and a grid step
    fits the VMEM budget. `platform` and `devices` are `target.where`'s
    answer."""
    return (platform == "tpu" and devices == 1
            and k_dim % _LANES == 0 and v_dim % _LANES == 0
            and heads % _PAIR == 0 and 2 * heads * k_dim % (_PAIR * v_dim) == 0
            and chunk % _BASE == 0 and _PAIR * chunk == _LANES
            and BLOCK_TOKENS % chunk == 0
            and _vmem_bytes(BLOCK_TOKENS, chunk, k_dim, v_dim)
            <= VMEM_BUDGET_BYTES)


def _parts(x):
    """float32 `x` as three bfloat16 parts that add up to it."""
    parts, rest = [], x
    for _ in range(3):
        parts.append(rest.astype(jnp.bfloat16))
        rest = rest - parts[-1].astype(jnp.float32)
    return parts


def _summed(ones, x, dims):
    """The product of a matrix of ones and zeros with float32 `x`, to
    float32's accuracy in THREE passes: x's three bfloat16 parts, each
    against the ones, which bfloat16 holds exactly (the six-pass product
    would multiply three of its passes by the ones' zero remainders)."""
    return sum(_one_pass(ones, part, dims) for part in _parts(x))


def _over_lanes(x):
    """float32 `x` [n, ·] summed over its lanes, the sum on ALL 128 lanes of
    [n, 128]: three passes against ones on the MXU, which has the room, and
    not seven lane rotations a vreg on the XLU, which the rolls of the
    diagonal sub-blocks keep busy."""
    ones = jnp.ones((x.shape[1], _LANES), jnp.bfloat16)
    return sum(_one_pass(part, ones, _NN) for part in _parts(x))


def _stacked(ref, at, width: int):
    """A block's two heads, side by side along the lanes, one above the
    other: ``[C, (r, ·)]`` -> ``[(r, i), ·]``."""
    return jnp.concatenate([ref[at, :width], ref[at, width:]], axis=0)


def _unstack(ref, at, stacked, width: int):
    half = stacked.shape[0] // _PAIR
    ref[at, :width] = stacked[:half]
    ref[at, width:] = stacked[half:]


def _row_of_each_head(x, chunk: int, at: int):
    """Row `at` of each head's chunk on all of that head's rows."""
    return jnp.concatenate([
        jnp.broadcast_to(x[r * chunk + at:r * chunk + at + 1],
                         (chunk, x.shape[1])) for r in range(_PAIR)], axis=0)


def _sub_block(x, at: int, chunk: int):
    """Sub-block `at`'s rows of both heads, ``[(r, 16), ·]``."""
    return jnp.concatenate([
        x[r * chunk + at * _BASE:r * chunk + (at + 1) * _BASE]
        for r in range(_PAIR)], axis=0)


def _band(qT, kT, cumT):
    """The diagonal sub-blocks' scores, element-wise in float32, with the
    key channels on the SUBLANES (qT, kT, γT ``[K, (r, i)]``): for an offset
    ``d = i − j`` inside a sub-block, k and γ rolled by d lanes stand under
    row i's own, the exponent is masked before it is taken, and the sum over
    the channels is a sum of vregs. -> the bands ``[16, (r, i)]`` of ``k_i ·
    k_{i−d}`` and ``q_i · k_{i−d}`` (decayed), offset d in row ``15 − d``."""
    K, L = kT.shape
    within = jax.lax.broadcasted_iota(jnp.int32, (K, L), 1) % _BASE
    sub = jax.lax.broadcasted_iota(jnp.int32, (_BASE, L), 0)
    kk = jnp.zeros((_BASE, L), jnp.float32)
    qk = jnp.zeros((_BASE, L), jnp.float32)
    for d in range(_BASE):
        held = kT if d == 0 else _held(kT, cumT, within, d)[0]
        here = sub == _BASE - 1 - d
        kk = jnp.where(here, jnp.sum(kT * held, axis=0, keepdims=True), kk)
        qk = jnp.where(here, jnp.sum(qT * held, axis=0, keepdims=True), qk)
    return kk, qk


def _held(kT, cumT, within, d: int):
    """``k_{i−d} ∘ e^{γ_i − γ_{i−d}}`` under row i and the decay alone,
    zero where ``i − d`` leaves i's sub-block."""
    decay = jnp.exp(jnp.where(within >= d, cumT - pltpu.roll(cumT, d, 1),
                              -jnp.inf))
    return pltpu.roll(kT, d, 1) * decay, decay


def _band_pull(qT, kT, cumT, d_kk, d_qk):
    """`_band`'s pullback: the bands' cotangents -> those of qT, kT, γT."""
    K, L = kT.shape
    within = jax.lax.broadcasted_iota(jnp.int32, (K, L), 1) % _BASE
    d_q = d_qk[_BASE - 1:] * kT                       # offset 0: q_i · k_i
    d_k = d_qk[_BASE - 1:] * qT
    d_cum = jnp.zeros((K, L), jnp.float32)
    # what column j takes from its rows i = j + d: ONE roll back an offset
    # — k's takes it as it is, γ's times k_j (the held row is k_j under i)
    back = jnp.zeros((K, L), jnp.float32)
    for d in range(1, _BASE):
        a, b = (x[_BASE - 1 - d:_BASE - d] for x in (d_kk, d_qk))
        held, decay = _held(kT, cumT, within, d)
        both = a * kT + b * qT
        d_k = d_k + a * held
        d_q = d_q + b * held
        d_cum = d_cum + both * held
        back = back + pltpu.roll(both * decay, L - d, 1)
    return d_q, d_k + back, d_cum - back * kT


def _bands_to_packed(kk_band, qk_band, second, chunk: int):
    """Two bands ``[16, (r, i)]`` (offset d in row ``15 − d``) -> the two
    packed ``[C, (r, j)]`` arrays with the diagonal sub-blocks filled. The
    bands transposed — row (r, i) holds its 16 entries of the first in
    lanes 0–15 and of the second in lanes 64–79 — and each row rolled by
    ``i − 15 + 64·r`` lanes (a roll whose amount grows by one a row): the
    first band's entries then stand in the row's OWN head's lanes at their
    column j, the second's in the other head's."""
    L = kk_band.shape[1]
    gap = jnp.zeros((chunk - _BASE, L), jnp.float32)
    square = pltpu.roll(
        jnp.transpose(jnp.concatenate([kk_band, gap, qk_band, gap], axis=0)),
        L - (_BASE - 1), 1, stride=1, stride_axis=0)
    return (_own(square, second, chunk),
            pltpu.roll(jnp.where(second, square[:chunk], square[chunk:]),
                       chunk, 1))


def _packed_to_bands(d_kk, d_qk, same_head, chunk: int):
    """`_bands_to_packed` backwards: every row rolled back by its own
    amount (Mosaic has no roll that SHRINKS by a row: seven conditional
    rolls, one a bit of the row's number)."""
    L = d_kk.shape[1]
    square = jnp.where(same_head, jnp.concatenate([d_kk, d_kk], axis=0),
                       jnp.concatenate([pltpu.roll(d_qk, chunk, 1)] * 2,
                                       axis=0))
    at_row = jax.lax.broadcasted_iota(jnp.int32, square.shape, 0)
    bit = 1
    while bit < L:
        square = jnp.where(at_row & bit != 0, pltpu.roll(square, L - bit, 1),
                           square)
        bit *= 2
    bands = jnp.transpose(pltpu.roll(square, _BASE - 1, 1))
    return bands[:_BASE], bands[chunk:chunk + _BASE]


def _masks(chunk: int, k_dim: int) -> dict:
    """Index arrays and masks of a chunk of two heads (constants of a grid
    step): `_packed_geometry`'s of the packed ``[C, 2·C]`` tile and the
    square one, the triangle of ones a head that takes the running sum, a
    stacked row's token, and which half of the lanes is the second head's
    on a sub-block's 16 rows."""
    row, col, second, same_head = _packed_geometry(chunk)
    square = (_PAIR * chunk, _PAIR * chunk)
    below = (jax.lax.broadcasted_iota(jnp.int32, square, 0)
             >= jax.lax.broadcasted_iota(jnp.int32, square, 1))
    return {
        "row": row, "col": col, "second": second, "same_head": same_head,
        "strict": row > col, "lower": row >= col,
        "sums": (same_head & below).astype(jnp.bfloat16),
        "token": jax.lax.broadcasted_iota(
            jnp.int32, (_PAIR * chunk, k_dim), 0) % chunk,
        "second_half": jax.lax.broadcasted_iota(
            jnp.int32, (_BASE, _PAIR * chunk), 1) >= chunk,
    }


def _chunk_parts(q, k, g, rows, m, *, chunk: int, cd, normalize):
    """What both kernels build of a chunk of two heads before they read the
    state or invert anything, in VMEM: q, k, g stacked ``[(r, i), K]``
    float32 as the conv and the gate left them, `rows` the chunk's [8, 128]
    block (β), `m` `_masks`' -> a dict of arrays (and lists of them), among
    them `A`, the strictly lower-triangular matrix to invert, packed."""
    C, K = chunk, q.shape[1]
    second = m["second"]
    raw = {}
    if normalize is not None:
        # (the XLU's sum: this heads the chunk's chain, and a trip through
        # the MXU is the longer wait)
        raw["q_norm"], raw["k_norm"] = (jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + normalize)
            for x in (q, k))
        q, k = q * raw["q_norm"] * K ** -0.5, k * raw["k_norm"]
    # γ: the running sum a head, a product with a triangle of ones
    cum = _summed(m["sums"], g, _NN)
    # column l of the transposed tile is row l mod 8
    beta = jnp.transpose(jnp.concatenate([rows] * (_LANES // 8),
                                         axis=0))[:, :1]
    # ---- the diagonal sub-blocks, the key channels on the sublanes
    qT, kT, cumT = jnp.transpose(q), jnp.transpose(k), jnp.transpose(cum)
    kk, qk = _bands_to_packed(*_band(qT, kT, cumT), second, C)
    # ---- rows against every EARLIER sub-block's columns: q's and k's rows
    # of both heads one above the other, both exponents at most 0
    blocks = cum.reshape(_PAIR * C // _BASE, _BASE, K)
    shrink = jnp.exp(cum - jnp.broadcast_to(
        blocks[:, :1], blocks.shape).reshape(_PAIR * C, K))
    q_rows, k_rows = q * shrink, k * shrink
    nothing = jnp.zeros((_BASE, _PAIR * C), jnp.float32)
    kk_off, qk_off, reaches, cols, lefts = [nothing], [nothing], [], [], []
    for i in range(1, C // _BASE):
        reach = jnp.exp(jnp.where(
            m["token"] < i * _BASE,
            _row_of_each_head(cum, C, i * _BASE) - cum, -jnp.inf))
        reaches.append(reach)
        cols.append(k * reach)
        lefts.append(jnp.concatenate(
            [_sub_block(q_rows, i, C), _sub_block(k_rows, i, C)],
            axis=0).astype(cd))
        both = _one_pass(lefts[-1], cols[-1].astype(cd), _NT)
        qk_off.append(jnp.where(m["second_half"], both[_BASE:2 * _BASE],
                                both[:_BASE]))
        kk_off.append(jnp.where(m["second_half"], both[3 * _BASE:],
                                both[2 * _BASE:3 * _BASE]))
    if C > _BASE:
        kk = kk + jnp.concatenate(kk_off, axis=0)
        qk = qk + jnp.concatenate(qk_off, axis=0)
    grow = jnp.exp(cum)                                      # e^γ
    to_end = jnp.exp(_row_of_each_head(cum, C, C - 1) - cum)
    return {
        **raw, "q": q, "k": k, "qT": qT, "kT": kT, "cumT": cumT,
        "beta": beta, "kk": kk, "qk": qk, "shrink": shrink,
        "reaches": reaches, "cols": cols, "lefts": lefts, "grow": grow,
        "to_end": to_end,
        "A": jnp.where(m["strict"],
                       kk * _packed_columns(beta, second, C), 0.0),
        # e^{γ_C} a key channel, a column a head
        "keep": [jnp.exp(cumT[:, (r + 1) * C - 1:(r + 1) * C])
                 for r in range(_PAIR)],
        "P_by_head": _by_head(qk.astype(cd), m["same_head"]),
        "q_grown": (q * grow).astype(cd),
        "k_end": (k * to_end).astype(cd),
        "k_written": (k * grow * beta).astype(cd),
    }


# what the forward's second loop reads of `_prepared`'s (the backward's: all)
_FORWARD_READS = ("A", "keep", "P_by_head", "q_grown", "k_end", "k_written",
                  "written_v")


def _inverse_many(ref, m):
    """``A -> (I + A)⁻¹`` for EVERY chunk of a block at once, in place: ref
    [n, C, 2·C] float32 in VMEM (two heads' strictly lower-triangular [C, C]
    side by side along the lanes a chunk). `gated_delta._inverse_packed`'s
    arithmetic to the bit — forward substitution on the 16 × 16 diagonal
    blocks as ``[16, (r, b, j)]`` tiles, right-looking, then the blocks
    merged by products at the highest precision — with the n chunks' tiles
    one above the other through the substitution and their products issued
    side by side at each level of the merge: every step of either is a wait
    (a row's broadcast, the MXU's), and n chunks share it. A step's factor,
    ``A``'s column j on all 16 lanes of its block, reads no earlier step: it
    is a product with a block matrix of ones on the MXU (the column's three
    bfloat16 parts, which add up to it exactly), a product a column — not a
    mask, a lane rotation to the block's first lane and four doubling
    rotations on the XLU, 0.5 ms more a call of either kernel, and not all
    fifteen columns in one product, which gains nothing
    (`benchmarks/results/pr64_kda_two_loops/loop_probe_{b,c}.jsonl`)."""
    n, chunk, width = ref.shape
    blocks = chunk // _BASE
    lane = jax.lax.broadcasted_iota(jnp.int32, (_BASE, width), 1)
    at_tile, block = lane % _BASE, lane % chunk // _BASE
    at = jnp.concatenate([at_tile] * n, axis=0)
    A = [ref[c] for c in range(n)]
    own = jnp.concatenate([
        sum(jnp.where(block == b, A[c][b * _BASE:(b + 1) * _BASE], 0.0)
            for b in range(blocks)) for c in range(n)], axis=0)
    square = (width, width)
    ones = (jax.lax.broadcasted_iota(jnp.int32, square, 0) // _BASE
            == jax.lax.broadcasted_iota(jnp.int32, square, 1) // _BASE
            ).astype(jnp.bfloat16)
    rows = n * _BASE
    T = (jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0) % _BASE
         == at).astype(jnp.float32)
    for j in range(_BASE - 1):
        parts = _parts(jnp.where(at == j, own, 0.0))
        spread = _one_pass(jnp.concatenate(parts, axis=0), ones, _NN)
        factor = sum(spread[part * rows:(part + 1) * rows]
                     for part in range(len(parts)))
        T = T - factor * jnp.concatenate([
            jnp.broadcast_to(T[c * _BASE + j:c * _BASE + j + 1],
                             (_BASE, width)) for c in range(n)], axis=0)
    Ts = [jnp.concatenate([
        jnp.where(block == b, T[c * _BASE:(c + 1) * _BASE], 0.0)
        for b in range(blocks)], axis=0) for c in range(n)]
    row, col, same_head = m["row"], m["col"], m["same_head"]
    side = _BASE
    while side < chunk:
        # only the second block of a pair has rows in `A_off`, and so in
        # both products: half the rows go through the MXU
        def second_rows(x):
            return jnp.concatenate(
                [x[at:at + side] for at in range(side, chunk, 2 * side)],
                axis=0)

        def placed(x):
            nothing = jnp.zeros((side, width), jnp.float32)
            return jnp.concatenate(
                [part for at in range(0, chunk // 2, side)
                 for part in (nothing, x[at:at + side])], axis=0)

        off = ((row // (2 * side) == col // (2 * side))
               & (row // side != col // side))
        rights = [placed(_exact(second_rows(jnp.where(off, A[c], 0.0)),
                                _by_head(Ts[c], same_head), _NN))
                  for c in range(n)]
        lowers = [_exact(second_rows(Ts[c]), _by_head(rights[c], same_head),
                         _NN) for c in range(n)]
        Ts = [Ts[c] - placed(lowers[c]) for c in range(n)]
        side *= 2
    for c in range(n):
        ref[c] = Ts[c]


def _scores_pull(p, m, d_kk, d_qk, *, chunk: int, cd):
    """The scores' pullback: the cotangents of `kk` and `qk` (packed; zero
    where the scores are) -> those of the normed q, k and of γ, stacked."""
    C, f32 = chunk, jnp.float32
    q, k, shrink = p["q"], p["k"], p["shrink"]
    second_half = m["second_half"]
    # ---- the diagonal sub-blocks
    d_qT, d_kT, d_cumT = _band_pull(
        p["qT"], p["kT"], p["cumT"],
        *_packed_to_bands(d_kk, d_qk, m["same_head"], C))
    d_q, d_k, d_cum = (jnp.transpose(x) for x in (d_qT, d_kT, d_cumT))
    # ---- the rows against earlier sub-blocks
    if C == _BASE:
        return d_q, d_k, d_cum
    at_row = jax.lax.broadcasted_iota(jnp.int32, q.shape, 0)
    earlier = jax.lax.broadcasted_iota(
        jnp.int32, second_half.shape, 1) % C
    nothing = jnp.zeros((_BASE, q.shape[1]), f32)
    d_q_rows = [[nothing] for _ in range(_PAIR)]
    d_k_rows = [[nothing] for _ in range(_PAIR)]
    for i in range(1, C // _BASE):
        cols, left = p["cols"][i - 1], p["lefts"][i - 1]
        own_q, own_k = (x[i * _BASE:(i + 1) * _BASE] for x in (d_qk, d_kk))
        inside = earlier < i * _BASE
        d_both = jnp.concatenate([
            jnp.where(inside & (second_half == bool(r)), x, 0.0)
            for x in (own_q, own_k) for r in range(_PAIR)],
            axis=0).astype(cd)
        d_left = _one_pass(d_both, cols.astype(cd), _NN).astype(cd)
        d_cols = _one_pass(d_both, left, _TN).astype(cd).astype(f32)
        d_left = d_left.astype(f32)
        for r in range(_PAIR):
            d_q_rows[r].append(d_left[r * _BASE:(r + 1) * _BASE])
            d_k_rows[r].append(
                d_left[(_PAIR + r) * _BASE:(_PAIR + r + 1) * _BASE])
        # cols = k ∘ e^{γ_r − γ_j}, zero from the sub-block's first row on
        d_k = d_k + d_cols * p["reaches"][i - 1]
        reach = d_cols * cols
        d_cum = d_cum - reach
        for r in range(_PAIR):
            d_cum = d_cum + jnp.where(
                at_row == r * C + i * _BASE,
                jnp.sum(reach[r * C:(r + 1) * C], axis=0, keepdims=True),
                0.0)
    d_q_rows, d_k_rows = (
        jnp.concatenate([piece for r in range(_PAIR) for piece in x[r]],
                        axis=0) for x in (d_q_rows, d_k_rows))
    d_q = d_q + d_q_rows * shrink
    d_k = d_k + d_k_rows * shrink
    shrunk = (d_q_rows * q + d_k_rows * k) * shrink
    d_cum = d_cum + shrunk
    for r in range(_PAIR):
        for i in range(1, C // _BASE):
            first = r * C + i * _BASE
            d_cum = d_cum - jnp.where(
                at_row == first,
                jnp.sum(shrunk[first:first + _BASE], axis=0, keepdims=True),
                0.0)
    return d_q, d_k, d_cum


def _prepared(q, k, v, g, rows, *, chunk: int, cd, normalize, reads):
    """A chunk's first stage, which reads neither a state nor an inverse:
    `_chunk_parts`' and β ∘ v as the products read it; `reads`: the keys the
    later stages need (None: all). What it returns is what a grid step keeps
    of each of its chunks in VMEM scratch (`_kept_shapes`)."""
    p = _chunk_parts(q, k, g, rows, _masks(chunk, q.shape[1]), chunk=chunk,
                     cd=cd, normalize=normalize)
    p["written_v"] = (v * p["beta"]).astype(cd)
    return p if reads is None else {key: p[key] for key in reads}


def _kept_shapes(chunks: int, chunk: int, k_dim: int, v_dim: int, cd,
                 normalize, reads):
    """(scratch shapes, tree) of `_prepared`'s result for every chunk of a
    block, each leaf with the chunks leading."""
    def operand(columns):
        return jax.ShapeDtypeStruct((_PAIR * chunk, columns), jnp.float32)

    leaves, tree = jax.tree_util.tree_flatten(jax.eval_shape(
        functools.partial(_prepared, chunk=chunk, cd=cd, normalize=normalize,
                          reads=reads),
        operand(k_dim), operand(k_dim), operand(v_dim), operand(k_dim),
        jax.ShapeDtypeStruct((8, _LANES), jnp.float32)))
    return [pltpu.VMEM((chunks, *leaf.shape), leaf.dtype)
            for leaf in leaves], tree


def _two_loops(masks: dict, kept, tree, prepare, finish, *, together: int,
               reverse=False):
    """A block's chunks twice: `prepare(c)` of each into the scratch `kept`
    (`_kept_shapes`' refs and tree, the chunks leading; `masks`: `_masks`');
    ``A -> (I + A)⁻¹`` of all of them at once (`_inverse_many`); then
    ``finish(c, the chunk's kept parts, the masks)`` in the order the state
    needs (`reverse`: from the last chunk to the first), `together` chunks a
    loop body. The first loop's bodies read no state, so nothing orders
    them but the scratch, and they are bound by what they do: two or four of
    them a body gain nothing. The second loop's are chains of trips through
    the MXU from one state to the next, and what of chunk c + 1 reads no
    state fills chunk c's waits where one body holds both: the forward's
    four bodies in ONE (straight behind the inverses: 11.9 -> 10.5 ms a
    call), the backward's two a body (25.5 -> 24.6; all four: 25.3)
    (`benchmarks/results/pr64_kda_two_loops/loop_probe_{c,g,h}.jsonl`)."""
    def first(c, _):
        for ref, leaf in zip(kept, jax.tree_util.tree_leaves(prepare(c))):
            ref[c] = leaf

    chunks = kept[0].shape[0]
    jax.lax.fori_loop(0, chunks, first, None)
    _inverse_many(jax.tree_util.tree_unflatten(tree, kept)["A"], masks)
    together = min(together, chunks)

    def second(body, _):
        for more in range(together):
            step = body * together + more
            c = chunks - 1 - step if reverse else step
            finish(c, jax.tree_util.tree_unflatten(
                tree, [ref[c] for ref in kept]), masks)

    # (Mosaic unrolls a loop whole or not at all: `together` by hand)
    jax.lax.fori_loop(0, chunks // together, second, None,
                      unroll=together == chunks)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, rows_ref, o_ref, starts_ref,
                state, *kept, chunk: int, cd, normalize, tree):
    @pl.when(pl.program_id(2) == 0)
    def _first_block():
        state[...] = jnp.zeros_like(state)

    C = chunk
    K, V = q_ref.shape[1] // _PAIR, v_ref.shape[1] // _PAIR
    heads = [slice(r * C, (r + 1) * C) for r in range(_PAIR)]

    def tokens(c):
        return pl.ds(pl.multiple_of(c * C, C), C)

    def prepare(c):
        at = tokens(c)
        return _prepared(
            _stacked(q_ref, at, K), _stacked(k_ref, at, K),
            _stacked(v_ref, at, V), _stacked(g_ref, at, K), rows_ref[c],
            chunk=C, cd=cd, normalize=normalize, reads=_FORWARD_READS)

    def carry(c, p, m):
        T_by_head = _by_head(p["A"], m["same_head"]).astype(cd)
        U = _one_pass(T_by_head, p["written_v"], _NN)
        W = _one_pass(T_by_head, p["k_written"], _NN).astype(cd)
        values, reads = [], []
        for r, own in enumerate(heads):
            start = state[r]
            starts_ref[c, r] = start
            # W·S and (e^γ ∘ q)·S in one product
            seen = _one_pass(
                jnp.concatenate([W[own], p["q_grown"][own]], axis=0),
                start.astype(cd), _NN)
            new = (U[own] - seen[:C]).astype(cd)
            state[r] = p["keep"][r] * start + _one_pass(p["k_end"][own], new,
                                                        _TN)
            values.append(new)
            reads.append(seen[C:])
        out = (jnp.concatenate(reads, axis=0)
               + _one_pass(p["P_by_head"], jnp.concatenate(values, axis=0),
                           _NN))
        _unstack(o_ref, tokens(c), out, V)

    _two_loops(_masks(C, K), kept, tree, prepare, carry,
               together=rows_ref.shape[0])


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, rows_ref, starts_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, drows_ref, dstate, *kept,
                chunk: int, cd, normalize, tree):
    @pl.when(pl.program_id(2) == 0)
    def _last_block():
        dstate[...] = jnp.zeros_like(dstate)

    C = chunk
    K, V = q_ref.shape[1] // _PAIR, v_ref.shape[1] // _PAIR
    f32 = jnp.float32
    heads = [slice(r * C, (r + 1) * C) for r in range(_PAIR)]

    def rounded(x):
        """A cotangent that comes back for a `cd` operand: in `cd`."""
        return x.astype(cd).astype(f32)

    def tokens(c):
        return pl.ds(pl.multiple_of(c * C, C), C)

    def prepare(c):
        """The chunk rebuilt as far as nothing reads a state."""
        at = tokens(c)
        return _prepared(
            _stacked(q_ref, at, K), _stacked(k_ref, at, K),
            _stacked(v_ref, at, V), _stacked(g_ref, at, K), rows_ref[c],
            chunk=C, cd=cd, normalize=normalize, reads=None)

    def finish(c, p, m):
        at = tokens(c)
        T, second, same_head = p["A"], m["second"], m["same_head"]
        T_by_head = _by_head(T, same_head).astype(cd)
        U = _one_pass(T_by_head, p["written_v"], _NN)
        W = _one_pass(T_by_head, p["k_written"], _NN).astype(cd)
        # ---- the carry and the readout, backwards: V' rebuilt from the
        # kept start state, the state's cotangent walked on
        d_out = _stacked(do_ref, at, V).astype(cd)
        starts = [starts_ref[c, r] for r in range(_PAIR)]
        starts_cd = [s.astype(cd) for s in starts]
        d_after = [dstate[r] for r in range(_PAIR)]
        d_after_cd = [d.astype(cd) for d in d_after]
        values = jnp.concatenate(
            [(U[own] - _one_pass(W[own], starts_cd[r], _NN)).astype(cd)
             for r, own in enumerate(heads)], axis=0)
        d_values = _one_pass(p["P_by_head"], d_out, _TN)       # Pᵀ·dO
        d_qk = jnp.where(m["lower"], rounded(
            _own(_one_pass(d_out, values, _NT), second, C)), 0.0)
        d_new, d_q_grown, d_W, d_k_end, d_cumT = [], [], [], [], 0.0
        at_lane = jax.lax.broadcasted_iota(jnp.int32, (K, _PAIR * C), 1)
        for r, own in enumerate(heads):
            new = d_values[own] + _one_pass(p["k_end"][own], d_after_cd[r],
                                            _NN)
            both = jnp.concatenate([d_out[own], new.astype(cd)], axis=0)
            # dO·Sᵀ and dV'·Sᵀ in one product
            read = _one_pass(both, starts_cd[r], _NT)
            d_q_grown.append(read[:C])
            d_W.append(-read[C:])
            d_k_end.append(_one_pass(values[own], d_after_cd[r], _NT))
            keep = p["keep"][r]
            # γ at the head's last token, through e^{γ_C}: a column where
            # the key channels are the sublanes
            d_cumT = d_cumT + jnp.where(
                at_lane == (r + 1) * C - 1,
                keep * _over_lanes(d_after[r] * starts[r]), 0.0)
            # (e^γ ∘ q)ᵀ·dO − Wᵀ·dV' in one product
            dstate[r] = keep * d_after[r] + _one_pass(
                jnp.concatenate([p["q_grown"][own], -W[own]], axis=0), both,
                _TN)
            d_new.append(new)
        d_U = jnp.concatenate(d_new, axis=0)                    # float32
        d_q_grown, d_W, d_k_end = (
            rounded(jnp.concatenate(x, axis=0))
            for x in (d_q_grown, d_W, d_k_end))
        # ---- what the products read, backwards
        beta, grow, to_end, q, k = (p[x] for x in (
            "beta", "grow", "to_end", "q", "k"))
        v = _stacked(v_ref, at, V)
        d_q = d_q_grown * grow
        d_grow = d_q_grown * q
        d_k = d_k_end * to_end
        d_to_end = d_k_end * k * to_end
        d_U_cd, d_W_cd = d_U.astype(cd), d_W.astype(cd)
        d_T = (_own(rounded(_one_pass(d_U_cd, p["written_v"], _NT)), second,
                    C)
               + _own(rounded(_one_pass(d_W_cd, p["k_written"], _NT)),
                      second, C))
        d_written_v = rounded(_one_pass(T_by_head, d_U_cd, _TN))
        d_written_k = rounded(_one_pass(T_by_head, d_W_cd, _TN))
        d_v = d_written_v * beta
        d_k = d_k + d_written_k * (grow * beta)
        d_grow = d_grow + d_written_k * k * beta
        d_beta = (_over_lanes(d_written_v * v)
                  + _over_lanes(d_written_k * k * grow))
        # γ_C − γ: the last row of a head takes the others' sum
        d_cum = d_grow * grow - d_to_end + jnp.where(
            m["token"] == C - 1, jnp.concatenate([
                jnp.broadcast_to(jnp.sum(d_to_end[own], axis=0,
                                         keepdims=True), (C, K))
                for own in heads], axis=0), 0.0)
        # the inverse's rule: dA = −Tᵀ·dT·Tᵀ below the diagonal
        T_t = jnp.transpose(_by_head(T, same_head))
        d_A = jnp.where(m["strict"], -_exact(
            _own(T_t, second, C),
            _by_head(_exact(d_T, T_t, _NN), same_head), _NN), 0.0)
        along = d_A * p["kk"]
        d_beta = d_beta + jnp.concatenate(
            [_over_lanes(jnp.where(second, 0.0, along)),
             _over_lanes(jnp.where(second, along, 0.0))], axis=0)
        more_q, more_k, more_cum = _scores_pull(
            p, m, d_A * _packed_columns(beta, second, C), d_qk, chunk=C,
            cd=cd)
        d_q, d_k = d_q + more_q, d_k + more_k
        d_cum = d_cum + more_cum + jnp.transpose(d_cumT)
        if normalize is not None:
            d_q = d_q * K ** -0.5
            d_q, d_k = (
                norm * d - raw * (norm * norm * norm
                                  * _over_lanes(raw * d)[:, :1])
                for raw, norm, d in (
                    (_stacked(q_ref, at, K), p["q_norm"], d_q),
                    (_stacked(k_ref, at, K), p["k_norm"], d_k)))
        _unstack(dq_ref, at, d_q, K)
        _unstack(dk_ref, at, d_k, K)
        _unstack(dv_ref, at, d_v, V)
        # the running sum's pullback: the triangle of ones, transposed
        _unstack(dg_ref, at, _summed(m["sums"], d_cum, _TN), K)
        # β's cotangent as the rows' lanes: one transposed tile
        lane = jax.lax.broadcasted_iota(jnp.int32, (_PAIR * C, _LANES), 1)
        drows_ref[c] = jnp.transpose(jnp.where(lane == 0, d_beta, 0.0))[:8]

    _two_loops(_masks(C, K), kept, tree, prepare, finish, together=2,
               reverse=True)


def _block_specs(block: int, chunk: int, k_dim: int, v_dim: int, offsets,
                 block_of):
    """Block specs of a grid step (batch i, pair of heads p, step s of the
    token axis; `block_of` maps s to the block of tokens): q, k and v ``[b,
    T, ·]`` read a pair's columns `offsets` blocks into their operand
    (`_packed_offsets` in ``[q | k | v]``, zeros in an array of their own),
    g and o ``[b, T, H·K]``, ``[b, T, H·V]`` likewise; β's rows ``[b, H / 2,
    chunks, 8, 128]``; the chunks' start states ``[chunks, b, H / 2, 2, K,
    V]``."""
    q_at, k_at, v_at = offsets
    chunks = block // chunk

    def columns(width, first):
        return pl.BlockSpec((None, block, _PAIR * width),
                            lambda i, p, s: (i, block_of(s), first + p))

    return {
        "q": columns(k_dim, q_at), "k": columns(k_dim, k_at),
        "v": columns(v_dim, v_at), "g": columns(k_dim, 0),
        "o": columns(v_dim, 0),
        "rows": pl.BlockSpec((None, None, chunks, 8, _LANES),
                             lambda i, p, s: (i, p, block_of(s), 0, 0)),
        "starts": pl.BlockSpec(
            (chunks, None, None, _PAIR, k_dim, v_dim),
            lambda i, p, s: (block_of(s), i, p, 0, 0, 0)),
    }


def _packed_offsets(heads: int, k_dim: int, v_dim: int):
    """Where q, k and v begin in ``[q | k | v]``, each in blocks of its own
    width: a pair of heads' 2·K columns for q and k, 2·V for v."""
    pairs = heads // _PAIR
    return 0, pairs, 2 * heads * k_dim // (_PAIR * v_dim)


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT_BYTES)
_STATIC = ("k_dim", "v_dim", "chunk", "block", "cd", "normalize", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _kda_fwd(qkv, g, rows, *, k_dim, v_dim, chunk, block, cd, normalize,
             interpret):
    """qkv: ``[q | k | v]`` [b, T, 2·H·K + H·V] float32 as the conv left
    them, T a multiple of `block`, handed to the kernel three times (an
    operand a block spec: a pair of heads' columns of q, of k and of v are
    read out of it in place); g [b, T, H·K]; rows [b, H / 2, T / C, 8, 128]
    -> o [b, T, H·V] float32 and every chunk's START state [T / C, b, H / 2,
    2, K, V] float32. Five array operands (the backward: seven):
    `flops.flash_call_cost` of the benchmark reads a Mosaic call of three or
    six as a flash kernel."""
    b, T = g.shape[:2]
    pairs = rows.shape[1]
    spec = _block_specs(block, chunk, k_dim, v_dim,
                        _packed_offsets(_PAIR * pairs, k_dim, v_dim),
                        lambda s: s)
    kept, tree = _kept_shapes(block // chunk, chunk, k_dim, v_dim, cd,
                              normalize, _FORWARD_READS)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, cd=cd,
                          normalize=normalize, tree=tree),
        grid=(b, pairs, T // block),
        in_specs=[spec["q"], spec["k"], spec["v"], spec["g"], spec["rows"]],
        out_specs=[spec["o"], spec["starts"]],
        out_shape=[
            jax.ShapeDtypeStruct((b, T, _PAIR * pairs * v_dim), jnp.float32),
            jax.ShapeDtypeStruct(
                (T // chunk, b, pairs, _PAIR, k_dim, v_dim), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_PAIR, k_dim, v_dim), jnp.float32),
                        *kept],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="kda_fwd",
    )(qkv, qkv, qkv, g, rows)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _kda_bwd(qkv, g, rows, starts, d_out, *, k_dim, v_dim, chunk, block, cd,
             normalize, interpret):
    """The operands of :func:`_kda_fwd`, its start states and o's cotangent
    [b, T, H·V] float32 -> the cotangents of q, k [b, T, H·K], v [b, T,
    H·V], g [b, T, H·K] and of β's rows."""
    b, T = g.shape[:2]
    pairs = rows.shape[1]
    last = T // block - 1
    spec = _block_specs(block, chunk, k_dim, v_dim,
                        _packed_offsets(_PAIR * pairs, k_dim, v_dim),
                        lambda s: last - s)
    own = _block_specs(block, chunk, k_dim, v_dim, (0, 0, 0),
                       lambda s: last - s)
    keys = jax.ShapeDtypeStruct((b, T, _PAIR * pairs * k_dim), jnp.float32)
    kept, tree = _kept_shapes(block // chunk, chunk, k_dim, v_dim, cd,
                              normalize, None)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, cd=cd,
                          normalize=normalize, tree=tree),
        grid=(b, pairs, T // block),
        in_specs=[spec["q"], spec["k"], spec["v"], spec["g"], spec["rows"],
                  spec["starts"], spec["o"]],
        out_specs=[own["q"], own["k"], own["v"], spec["g"], spec["rows"]],
        out_shape=[keys, keys,
                   jax.ShapeDtypeStruct((b, T, _PAIR * pairs * v_dim),
                                        jnp.float32),
                   keys, jax.ShapeDtypeStruct(rows.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_PAIR, k_dim, v_dim), jnp.float32),
                        *kept],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="kda_bwd",
    )(qkv, qkv, qkv, g, rows, starts, d_out)


def _kernel_rows(beta, *, chunk: int):
    """β [b, T, H] float32, T whole chunks -> the kernels' per-chunk rows
    [b, H / 2, T / C, 8, 128]: β in row 0, a lane (head of the pair, token of
    the chunk). Plain JAX (a 2 MB array), differentiated by JAX."""
    b, T, H = beta.shape
    n = T // chunk
    lanes = jnp.transpose(
        beta.reshape(b, n, chunk, H // _PAIR, _PAIR), (0, 3, 1, 4, 2)
    ).reshape(b, H // _PAIR, n, 1, _PAIR * chunk)
    return jnp.pad(lanes, [(0, 0)] * 3 + [(0, 7), (0, 0)])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernel_rule(qkv, g, beta, static):
    """qkv: ``[q | k | v]`` as the conv left them [b, T, 2·H·K + H·V], read
    in place; g [b, T, H·K]; β [b, T, H]; all float32 -> o [b, T, H·V]
    float32. `static`: `_static`'s."""
    return _kernel_rule_fwd(qkv, g, beta, static)[0]


def _kernel_rule_fwd(qkv, g, beta, static):
    kw = dict(zip(_STATIC, static))
    whole, g_whole, beta_whole = _whole_blocks(kw["block"], qkv, g, beta)
    out, starts = _kda_fwd(
        whole, g_whole, _kernel_rows(beta_whole, chunk=kw["chunk"]), **kw)
    return out[:, :g.shape[1]], (qkv, g, beta, starts)


def _kernel_rule_bwd(static, residuals, d_out):
    kw = dict(zip(_STATIC, static))
    qkv, g, beta, starts = residuals
    T = g.shape[1]
    whole, g_whole, beta_whole, d_whole = _whole_blocks(
        kw["block"], qkv, g, beta, d_out.astype(jnp.float32))
    rows, back = jax.vjp(
        functools.partial(_kernel_rows, chunk=kw["chunk"]), beta_whole)
    *d_qkv, d_g, d_rows = _kda_bwd(whole, g_whole, rows, starts, d_whole,
                                   **kw)
    (d_beta,) = back(d_rows)
    return (jnp.concatenate(d_qkv, axis=-1)[:, :T], d_g[:, :T],
            d_beta[:, :T])


_kernel_rule.defvjp(_kernel_rule_fwd, _kernel_rule_bwd)


def _static(k_dim, v_dim, chunk, compute_dtype, normalize, interpret):
    """`_STATIC`'s values (the module's block of tokens as it stands when
    the call is traced)."""
    return (k_dim, v_dim, chunk, BLOCK_TOKENS, jnp.dtype(compute_dtype),
            normalize, interpret)


def _kernels_take(mesh, interpret: bool, chunk: int, heads: int, k_dim: int,
                  v_dim: int) -> bool:
    _check(heads, heads, chunk)
    return _use_kernel(*target.where(mesh, interpret=interpret), chunk,
                       heads, k_dim, v_dim)


# ------------------------------------------------------------------ entry
def _to_chunks(q, k, v, g, beta, chunk: int):
    """[B, T, H, ·] -> float32 [chunks, B, H, C, ·] (β [chunks, B, H, C]),
    zero-padded behind the last token to whole chunks: the chunks leading
    (the scan walks them) and heads ahead of a chunk's tokens, so that
    every product is a batched matmul."""
    b, T, H, _ = q.shape
    _check(H, H, chunk)
    pad = -T % chunk

    def lay(a):
        a = jnp.pad(a.astype(jnp.float32),
                    [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        a = a.reshape(b, (T + pad) // chunk, chunk, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    return tuple(lay(a) for a in (q, k, v, g, beta))


def _from_chunks(out, T: int):
    """[chunks, b, H, C, V] -> [b, T, H, V]."""
    n, b, H, C, V = out.shape
    return jnp.moveaxis(jnp.moveaxis(out, 0, 1), 2, 3).reshape(
        b, n * C, H, V)[:, :T]


def kda(q, k, v, g, beta, *, chunk: int = 64, compute_dtype=jnp.bfloat16,
        normalize=None, mesh=None, interpret: bool = False):
    """q, k [B, T, H, K], v [B, T, H, V], g [B, T, H, K] float32 (the
    log-decay a key channel, ≤ 0) and β [B, T, H] -> o [B, T, H, V]
    float32, the state zero before each sequence's first token. Chunked,
    with the backward of this module's docstring.

    normalize: as `gated_delta.gated_delta`'s — None, or an epsilon: each
    head's q and k are L2-normed here, q then times ``K^-½``; the backward
    then keeps the RAW q and k and no normed copy.

    mesh: where the rule runs (`target.where`); that and the shapes decide
    between the kernels and the plain form (`_use_kernel`). The kernels
    read ONE array, so where they are taken the three are laid side by side
    for :func:`kda_packed` (a copy; the layer, whose conv leaves them so,
    calls that entry). The plain form is plain JAX, which XLA partitions
    over a `dp` mesh as any other plain op. `interpret` runs the kernels in
    Pallas's interpreter wherever the process is, and exists for tests."""
    (b, T, H, K), V = q.shape, v.shape[-1]
    if _kernels_take(mesh, interpret, chunk, H, K, V):
        return kda_packed(
            jnp.concatenate([q.reshape(b, T, H * K), k.reshape(b, T, H * K),
                             v.reshape(b, T, H * V)], axis=-1),
            g.reshape(b, T, H * K), beta, k_dim=K, chunk=chunk,
            compute_dtype=compute_dtype, normalize=normalize, mesh=mesh,
            interpret=interpret)
    if interpret:
        raise ValueError(f"kda: no kernel tiling for chunk {chunk}, {H} "
                         f"heads, widths {K}, {V}")
    return _from_chunks(
        _rule(*_to_chunks(q, k, v, g, beta, chunk),
              jnp.dtype(compute_dtype), normalize), T)


def kda_packed(qkv, g, beta, *, k_dim: int, chunk: int = 64,
               compute_dtype=jnp.bfloat16, normalize=None, mesh=None,
               interpret: bool = False):
    """:func:`kda` on ``[q | k | v]`` [B, T, 2·H·K + H·V] as the layer's
    conv leaves them, each part head after head, and g [B, T, H·K] as the
    gate's projection leaves it: the kernels read a pair of heads' columns
    out of both in place, so no slice, head split or chunked copy of them is
    made for their sake; the plain form splits them."""
    b, T, H = beta.shape
    v_dim = (qkv.shape[-1] - 2 * H * k_dim) // H
    if _kernels_take(mesh, interpret, chunk, H, k_dim, v_dim):
        f32 = jnp.float32
        out = _kernel_rule(
            qkv.astype(f32), g.astype(f32), beta.astype(f32),
            _static(k_dim, v_dim, chunk, compute_dtype, normalize,
                    interpret))
        return out.reshape(b, T, H, v_dim)
    q, k, v = jnp.split(qkv, [H * k_dim, 2 * H * k_dim], axis=-1)
    return kda(q.reshape(b, T, H, k_dim), k.reshape(b, T, H, k_dim),
               v.reshape(b, T, H, v_dim), g.reshape(b, T, H, k_dim), beta,
               chunk=chunk, compute_dtype=compute_dtype, normalize=normalize,
               mesh=mesh, interpret=interpret)


def kda_plain(q, k, v, g, beta, *, chunk: int = 64,
              compute_dtype=jnp.bfloat16, normalize=None):
    """:func:`kda` with JAX's own derivative of the walk: the custom
    backward's control."""
    return _from_chunks(
        _walk(*_to_chunks(q, k, v, g, beta, chunk),
              cd=jnp.dtype(compute_dtype), normalize=normalize)[0],
        q.shape[1])
