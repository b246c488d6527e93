"""The gated delta rule (Yang, Kautz & Hatamizadeh, arXiv:2412.06464; the
linear-attention layer of the Qwen3-Next family) in its chunked form: Pallas
TPU kernels under a `jax.custom_vjp` where ONE TPU runs it and the tiles
divide the shapes, plain JAX under a `jax.custom_vjp` of its own everywhere
else. Both backwards keep the states at the chunk boundaries and rebuild
everything inside a chunk. :func:`gated_delta` is the entry, and
:func:`gated_delta_packed` the same on ``[q | k | v]`` in one array.

The recurrence, a value head at a time (state ``S`` [K, V], ``S₀ = 0``;
``g_t ≤ 0`` the log-decay, ``β_t`` the write strength):

    S̃_t = exp(g_t) · S_{t−1}
    S_t = S̃_t + k_t · (β_t · (v_t − S̃_tᵀ k_t))ᵀ          o_t = S_tᵀ q_t

q and k belong to a KEY head that ``H // G`` value heads read (value head h
reads key head ``h // (H // G)``); g, β and v are a value head's. Unlike
Mamba-2's scan (`ops/ssd.py`), whose chunk has a closed form, the delta
rule's correction reads the state the SAME chunk's earlier tokens wrote, so
a chunk of ``C`` tokens first needs the inverse of a unit lower-triangular
``C × C`` matrix (the WY / UT form). With ``γ`` the running sum of g inside
the chunk (inclusive) and ``D_ij = exp(γ_i − γ_j)`` for ``i ≥ j``:

* ``prepare`` (nothing reads the state):
  ``A = stril(diag(β) · k kᵀ ∘ D)``, ``T = (I + A)⁻¹`` (:func:`_inverse`:
  forward substitution on 16 × 16 diagonal blocks, the blocks merged by
  products — float32 throughout), ``U = T·(β ∘ v)``, ``W = T·(β e^γ ∘ k)``,
  ``P = tril(q kᵀ ∘ D)``;
* ``carry`` (the chunks in order, float32 state): ``V' = U − W·S``,
  ``S ← e^{γ_C} S + (e^{γ_C − γ} ∘ k)ᵀ·V'``; kept: every chunk's START state
  ``[chunks, b, G, R, K, V]``;
* ``readout``: ``o = (e^γ ∘ q)·S + P·V'``.

The backward runs the same three stages in reverse: ``V'`` is rebuilt from
the kept start states, the readout's cotangents are four products, the
state's cotangent is carried from the last chunk to the first (``dV' =
Pᵀ·dO + (e^{γ_C − γ} ∘ k)·dS``, ``dS ← (e^γ ∘ q)ᵀ·dO + e^{γ_C} dS −
Wᵀ·dV'``), and `prepare` is pulled back (the inverse has its own rule,
``dA = −Tᵀ·dT·Tᵀ``). So the residuals are the inputs and the start states;
no ``[T, C]`` array outlives a pass. :func:`gated_delta_plain` is the three
stages differentiated by JAX end to end, both backwards' control in the
tests.

A length the chunk does not divide is padded with ``g = 0``, ``β = 0``
tokens behind the last, which neither decay the state nor write to it.

The plain form runs `prepare` and `readout` for all chunks at once and the
carry as a `lax.scan`: every intra-chunk array reaches HBM between the
stages (`delta_plan`'s arithmetic; 230 ms of the cell's 650 ms step, PERF.md
§6, PR 49). The kernels (``delta_fwd``, ``delta_bwd`` in HLO and trace) do
all three in VMEM. Grid (batch, key head, block of `BLOCK_TOKENS` tokens),
the blocks of a head in order — the backward's in reverse — with the state
``[2, K, V]`` float32 (its cotangent) in VMEM scratch, and an inner loop
over the block's chunks. A grid step holds one KEY head and its TWO value
heads: q and k ``[block, K]`` and v ``[block, 2·V]`` are read out of ONE
operand IN PLACE by block index maps (:func:`gated_delta_packed`: out of
the conv's ``[q | k | v]``, so no slice of it is copied for a kernel;
:func:`gated_delta` lays its three side by side for them), the
per-token scalars come as rows ``[chunks, 8, 128]`` whose lane is (value
head, token) — γ, β, γ at the chunk's end: `_kernel_rows`, plain JAX, which
also takes the running sum and which JAX differentiates — and the two heads
share one ``k kᵀ`` / ``q kᵀ``. Inside a chunk the two heads' ``[C, C]``
arrays lie side by side along the 128 lanes (``[C, (r, j)]``, "packed")
and their ``[C, ·]`` arrays one above the other (``[(r, i), ·]``,
"stacked"): a product of a packed with a stacked operand goes through the
MXU once for both heads with the packed one laid out block-diagonally
(`_by_head`). The inverse is `_inverse_packed`: the substitution on all
eight 16 × 16 diagonal blocks as ONE ``[16, 128]`` tile (two vregs), the
merges as whole-tile products at the highest precision over the rows that
have any. Written to HBM: o and each chunk's start state in the compute
dtype (forward); the cotangents of q, k (both value heads' summed), v and
of the rows (backward, which reads its six inputs once) — nothing of
``[T, C]`` or ``[chunks, K, V]`` beside the start states. The decays'
cotangent takes both sums of ONE float32 array ``D ∘ dD`` (`ops/ssd.py`'s
lesson). Left in JAX: the rows and their pullback (2 MB arrays), the pads
of a length no block divides, and the concatenation of the three
cotangents into the conv output's. Each kernel sits behind a module-level
`jax.jit` (one trace and one Mosaic lowering a step, whatever the number of
call sites: PERF.md §6, PR 46).

Precision, both forms: decays, running sums, the triangular inverse and the
carried state in float32; the products' operands in `compute_dtype` with
float32 accumulation, one pass; `exp`, `rsqrt` and the divisions exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import mxu, target

# side of the diagonal blocks inverted by forward substitution; a chunk is
# one such block or a power-of-two number of them
_BASE = 16
_HIGHEST = jax.lax.Precision.HIGHEST


def delta_plan(tokens: int, heads: int, key_heads: int, k_dim: int,
               v_dim: int, chunk: int, itemsize: int = 2) -> dict:
    """What one forward call of :func:`gated_delta` does for `tokens`
    tokens, from shapes alone: the FLOPs of the token-by-token recurrence
    (decay-free: read ``S̃ᵀk``, write the rank-one delta, read ``Sᵀq``: three
    passes over a ``K × V`` state a head and token; what a model's
    accounting counts — chunking, like recomputation, is the
    implementation's), the FLOPs the chunked form issues by stage, the
    bytes a call must move (q, k, v, g, β read and o written once), the
    start states kept for the backward in float32, and `vmem_bytes`: what a
    grid step of the kernels holds in VMEM (`_vmem_bytes`, the backward's:
    the larger), held against `VMEM_BUDGET_BYTES` by `_use_kernel`."""
    chunks = -(-tokens // chunk)
    padded = chunks * chunk
    flops = {
        "scores": 2 * 2 * padded * chunk * key_heads * k_dim,      # kkᵀ, qkᵀ
        "apply_inverse": 2 * padded * chunk * heads * (k_dim + v_dim),
        "carry": 2 * 2 * padded * heads * k_dim * v_dim,
        "readout": 2 * padded * heads * (k_dim * v_dim + chunk * v_dim),
    }
    return {
        "chunks": chunks,
        "flops_recurrence": 6 * tokens * heads * k_dim * v_dim,
        "flops_by_stage": flops,
        "flops": sum(flops.values()),
        "bytes": (2 * tokens * key_heads * k_dim
                  + 2 * tokens * heads * v_dim) * itemsize
        + 2 * tokens * heads * 4,
        "state_bytes": 4 * chunks * heads * k_dim * v_dim,
        "vmem_bytes": _vmem_bytes(BLOCK_TOKENS, chunk, heads // key_heads,
                                  k_dim, v_dim, itemsize),
    }


# -------------------------------------------------- the triangular inverse
def _substitute(A):
    """``(I + A)⁻¹`` for strictly lower-triangular A [..., n, n] by forward
    substitution, a row at a time (row i of the inverse is ``e_i − Σ_{j<i}
    A_ij · row_j``): n steps of multiply-adds, exact float32."""
    n = A.shape[-1]
    eye = jnp.eye(n, dtype=A.dtype)
    rows = []
    for i in range(n):
        row = jnp.broadcast_to(eye[i], A.shape[:-2] + (n,))
        if i:
            row = row - jnp.sum(A[..., i, :i, None] * jnp.stack(rows, -2),
                                axis=-2)
        rows.append(row)
    return jnp.stack(rows, -2)


def _inverse_blocks(A):
    n = A.shape[-1]
    if n <= _BASE:
        return _substitute(A)
    h = n // 2
    # both diagonal halves through one recursion, then the block formula
    # [[a, 0], [c, d]]⁻¹ = [[a⁻¹, 0], [−d⁻¹ c a⁻¹, d⁻¹]]
    a, d = _inverse_blocks(jnp.stack([A[..., :h, :h], A[..., h:, h:]]))
    lower = -jnp.matmul(d, jnp.matmul(A[..., h:, :h], a, precision=_HIGHEST),
                        precision=_HIGHEST)
    return jnp.concatenate([
        jnp.concatenate([a, jnp.zeros_like(a)], -1),
        jnp.concatenate([lower, d], -1)], -2)


@jax.custom_vjp
def _inverse(A):
    """``T = (I + A)⁻¹`` for strictly lower-triangular A [..., C, C],
    float32. Its rule: ``dA = −Tᵀ·dT·Tᵀ`` (the caller's mask keeps the part
    below the diagonal)."""
    return _inverse_blocks(A)


def _inverse_fwd(A):
    T = _inverse_blocks(A)
    return T, T


def _inverse_bwd(T, dT):
    Tt = jnp.swapaxes(T, -1, -2)
    return (-jnp.matmul(Tt, jnp.matmul(dT, Tt, precision=_HIGHEST),
                        precision=_HIGHEST),)


_inverse.defvjp(_inverse_fwd, _inverse_bwd)


# ------------------------------------------------------- the three stages
def _mm(cd):
    return functools.partial(mxu.einsum, out_dtype=jnp.float32, cd=cd,
                             three_pass=False)


def _unit(x, eps: float):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _prepare(q, k, v, g, beta, *, cd, normalize=None):
    """Chunked inputs, the chunks leading and heads ahead of a chunk's
    tokens: q, k [n, b, G, C, K], v [n, b, G, R, C, V], g, β [n, b, G, R, C]
    (float32) -> what the carry and the readout read: ``e^γ ∘ q`` and
    ``e^{γ_C − γ} ∘ k`` [n, b, G, R, C, K], ``P`` [n, b, G, R, C, C], ``U``
    [n, b, G, R, C, V], ``W`` [n, b, G, R, C, K] and the whole chunk's decay
    ``e^{γ_C}`` [n, b, G, R]. `normalize`: :func:`gated_delta`'s."""
    mm = _mm(cd)
    C = q.shape[-2]
    if normalize is not None:
        q = _unit(q, normalize) * q.shape[-1] ** -0.5
        k = _unit(k, normalize)
    cum = jnp.cumsum(g, axis=-1)                             # γ, inclusive
    at = jnp.arange(C)
    below = at[:, None] >= at[None, :]
    # masked BEFORE the exponential: above the diagonal γ_i − γ_j ≥ 0
    decay = jnp.exp(jnp.where(
        below, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    kk = mm("nbgik,nbgjk->nbgij", k, k)[:, :, :, None]
    qk = mm("nbgik,nbgjk->nbgij", q, k)[:, :, :, None]
    T = _inverse(jnp.where(at[:, None] > at[None, :],
                           kk * decay * beta[..., :, None], 0.0))
    grow = jnp.exp(cum)[..., None]                           # e^γ
    keys = k[:, :, :, None]
    U = mm("nbgrij,nbgrjv->nbgriv", T, v * beta[..., None])
    W = mm("nbgrij,nbgrjk->nbgrik", T, keys * (beta[..., None] * grow))
    last = cum[..., -1]                                      # [n,b,G,R]
    to_end = jnp.exp(last[..., None] - cum)[..., None]
    # what only the products read leaves this stage as they read it, in
    # `cd` (and so do the cotangents that come back for it); U is
    # subtracted from in float32
    return ((q[:, :, :, None] * grow).astype(cd), (keys * to_end).astype(cd),
            (qk * decay).astype(cd), U, W.astype(cd), jnp.exp(last))


def _carry(U, W, k_end, total, *, cd):
    """The chunks in order -> every chunk's START state [n, b, G, R, K, V]
    and its corrected values ``V' = U − W·S`` [n, b, G, R, C, V]; the state
    is carried in float32, and both are handed on as the products read
    them: in `cd`."""
    mm = _mm(cd)

    def step(state, chunk):
        u, w, k_to_end, keep = chunk
        new = u - mm("bgrik,bgrkv->bgriv", w, state)
        after = (keep[..., None, None] * state
                 + mm("bgrik,bgriv->bgrkv", k_to_end, new))
        return after, (state.astype(cd), new.astype(cd))

    _, b, G, R, _, V = U.shape
    _, (starts, values) = jax.lax.scan(
        step, jnp.zeros((b, G, R, W.shape[-1], V), jnp.float32),
        (U, W, k_end, total))
    return starts, values


def _readout(q_grown, P, starts, values, *, cd):
    mm = _mm(cd)
    return (mm("nbgrik,nbgrkv->nbgriv", q_grown, starts)
            + mm("nbgrij,nbgrjv->nbgriv", P, values))


def _chunked(q, k, v, g, beta, *, cd, normalize=None):
    q_grown, k_end, P, U, W, total = _prepare(q, k, v, g, beta, cd=cd,
                                              normalize=normalize)
    starts, values = _carry(U, W, k_end, total, cd=cd)
    return _readout(q_grown, P, starts, values, cd=cd), starts


# ------------------------------------------------------ its own backward
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, cd, normalize):
    return _chunked(q, k, v, g, beta, cd=cd, normalize=normalize)[0]


def _rule_fwd(q, k, v, g, beta, cd, normalize):
    out, starts = _chunked(q, k, v, g, beta, cd=cd, normalize=normalize)
    return out, (q, k, v, g, beta, starts)


def _rule_bwd(cd, normalize, residuals, d_out):
    *inputs, starts = residuals
    mm = _mm(cd)
    (q_grown, k_end, P, U, W, total), pull = jax.vjp(
        functools.partial(_prepare, cd=cd, normalize=normalize), *inputs)
    values = U - mm("nbgrik,nbgrkv->nbgriv", W, starts)
    values = values.astype(cd)
    # the readout's cotangents that no state-sized array stands behind
    d_q_grown = mm("nbgriv,nbgrkv->nbgrik", d_out, starts).astype(cd)
    d_P = mm("nbgriv,nbgrjv->nbgrij", d_out, values).astype(cd)
    d_values = mm("nbgrij,nbgriv->nbgrjv", P, d_out)

    def step(d_after, chunk):
        """`d_after`: the cotangent of the state this chunk leaves. Every
        product with a [K, V] array a chunk is made here, so that only the
        start states exist for all chunks at once."""
        d_new, d_o, q_up, w, k_to_end, new, keep, start = chunk
        d_new = d_new + mm("bgrik,bgrkv->bgriv", k_to_end, d_after)
        d_state = (mm("bgrik,bgriv->bgrkv", q_up, d_o)
                   + keep[..., None, None] * d_after
                   - mm("bgrik,bgriv->bgrkv", w, d_new))
        return d_state, (
            d_new, mm("bgriv,bgrkv->bgrik", new, d_after).astype(cd),
            jnp.sum(d_after * start.astype(jnp.float32), axis=(-1, -2)))

    _, (d_values, d_k_end, d_total) = jax.lax.scan(
        step, jnp.zeros(starts.shape[1:], jnp.float32),
        (d_values, d_out, q_grown, W, k_end, values, total, starts),
        reverse=True)
    d_W = -mm("nbgriv,nbgrkv->nbgrik", d_values, starts).astype(cd)
    return pull((d_q_grown, d_k_end, d_P, d_values, d_W, d_total))


_rule.defvjp(_rule_fwd, _rule_bwd)


# ---------------------------------------------------------------- kernels
_LANES = 128
# tokens a grid step holds (whole chunks, walked in order by an inner loop):
# swept on the chip at the cell's layer (PERF.md §6, PR 49)
BLOCK_TOKENS = 512
# chunks of a block the inner loop's body holds: one chunk's products wait
# on its substitution and the next chunk's substitution on nothing, so
# several in a body let the scheduler run them side by side (swept with the
# block)
CHUNKS_UNROLLED = 4
# What a grid step's double-buffered blocks and the state may take of VMEM;
# the kernels ask Mosaic for `_VMEM_LIMIT_BYTES` (a v5e core has 128 MiB),
# a chunk's temporaries being the rest.
VMEM_BUDGET_BYTES = 16 * 1024 * 1024
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))   # a · bᵀ
_NN = (((1,), (0,)), ((), ()))   # a · b
_TN = (((0,), (0,)), ((), ()))   # aᵀ · b
# rows of the per-chunk block [8, 128] the kernels read beside q, k, v: a
# lane is (value head of the key head, token of the chunk)
_ROW_CUM, _ROW_BETA, _ROW_LAST, _ROW_LAST_OF = 0, 1, 2, 3


def _vmem_bytes(block: int, chunk: int, per_key: int, k_dim: int, v_dim: int,
                itemsize: int = 2) -> int:
    """VMEM of a grid step of the backward (the larger of the two): its
    blocks — q, k and their cotangents [block, K]; v, o's cotangent and v's
    [block, r·V], float32; the rows and theirs [block / C, 8, 128]; the
    chunks' start states [block / C, r, K, V] in the compute dtype —
    double-buffered, and the state's cotangent [r, K, V] float32."""
    chunks = block // chunk
    blocks = 4 * (4 * block * k_dim + 3 * block * per_key * v_dim
                  + 2 * chunks * 8 * _LANES)
    blocks += itemsize * chunks * per_key * k_dim * v_dim
    return 2 * blocks + 4 * per_key * k_dim * v_dim


def _use_kernel(platform: str, devices: int, chunk: int, key_heads: int,
                per_key: int, k_dim: int, v_dim: int) -> bool:
    """Whether the rule goes through the Pallas kernels: on ONE TPU (a mesh
    that splits the batch would need the call under a `shard_map`, which is
    not written) where the tiles divide the shapes — a key head's and a
    value head's columns whole lane tiles, v's first column in ``[q | k |
    v]`` a whole number of a key head's value blocks (`_packed_offsets`),
    and the value heads of a key head times the chunk's tokens the 128
    lanes the packed ``[C, r·C]`` arrays fill (two heads at chunk 64) — and
    a grid step fits the VMEM budget. `platform` and `devices` are
    `target.where`'s answer."""
    return (platform == "tpu" and devices == 1
            and k_dim % _LANES == 0 and v_dim % _LANES == 0
            and key_heads * k_dim % v_dim == 0
            and chunk % _BASE == 0 and per_key * chunk == _LANES
            and _vmem_bytes(BLOCK_TOKENS, chunk, per_key, k_dim, v_dim, 4)
            <= VMEM_BUDGET_BYTES)


def _exact(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _one_pass(a, b, dims):
    exact = _HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(a, b, dims, precision=exact,
                               preferred_element_type=jnp.float32)


def _twice(a):
    return jnp.concatenate([a, a], axis=0)


def _by_head(packed, same_head):
    """``[C, (r, j)]`` packed along the lanes -> ``[(r, i), (r', j)]`` with
    head r's ``[C, C]`` on the diagonal and zeros off it: as the left
    operand it applies each head's matrix to that head's rows of a stacked
    ``[(r, j), ·]`` operand, as the right operand each head's matrix to that
    head's lanes of a packed one."""
    return jnp.where(same_head, _twice(packed), jnp.zeros((), packed.dtype))


def _packed_geometry(chunk: int):
    """Index arrays of a packed ``[C, 2·C]`` tile (row i; lane (r, j)) and of
    the square ``[2·C, 2·C]`` one."""
    shape = (chunk, 2 * chunk)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    second = lane >= chunk
    col = jnp.where(second, lane - chunk, lane)
    square = (2 * chunk, 2 * chunk)
    same_head = ((jax.lax.broadcasted_iota(jnp.int32, square, 0) >= chunk)
                 == (jax.lax.broadcasted_iota(jnp.int32, square, 1) >= chunk))
    return row, col, second, same_head


def _packed_columns(column, second, chunk: int):
    """A stacked column ``[(r, i), 1]`` -> packed ``[C, (r, j)]``, head r's
    value of row i on all of its lanes."""
    width = second.shape[1]
    return jnp.where(
        second, jnp.broadcast_to(column[chunk:], (chunk, width)),
        jnp.broadcast_to(column[:chunk], (chunk, width)))


def _inverse_packed(A, row, col, second, same_head):
    """:func:`_inverse` for two heads' strictly lower-triangular ``[C, C]``
    side by side along the lanes, in VMEM and float32. Forward substitution
    on the 16 × 16 diagonal blocks, all of them at once as one ``[16, (r,
    b, j)]`` tile (block b of head r on ITS OWN lanes of the packed tile,
    so compacting and spreading again are masks and no lane moves) and
    right-looking: once row j of a block's inverse is final, every later
    row i takes ``−A_ij`` times it — a step is ``A``'s column j spread over
    its block's 16 lanes (a mask, a lane rotation to the block's first lane
    and four doubling rotations), one multiply-add, and no reduction. Then
    the blocks merged by products at the highest precision, ``T ← T −
    T·(A_off·T)`` with ``A_off`` the level's off-diagonal blocks (block (1,
    0) of every pair is ``−d⁻¹·(c·a⁻¹)``: `_inverse_blocks`' formula for
    all pairs of a level at once)."""
    chunk, width = A.shape
    blocks = chunk // _BASE
    # (a slice of an index array is not what Mosaic lays out: made anew)
    lane = jax.lax.broadcasted_iota(jnp.int32, (_BASE, width), 1)
    at, block = lane % _BASE, lane % chunk // _BASE
    own = sum(jnp.where(block == b, A[b * _BASE:(b + 1) * _BASE], 0.0)
              for b in range(blocks))
    T = (jax.lax.broadcasted_iota(jnp.int32, (_BASE, width), 0)
         == at).astype(jnp.float32)
    for j in range(_BASE - 1):
        factor = jnp.where(at == j, own, 0.0)
        if j:
            factor = pltpu.roll(factor, width - j, 1)
        for reach in (1, 2, 4, 8):
            factor = factor + pltpu.roll(factor, reach, 1)
        T = T - factor * jnp.broadcast_to(T[j:j + 1], T.shape)
    T = jnp.concatenate([jnp.where(block == b, T, 0.0)
                         for b in range(blocks)], axis=0)
    side = _BASE
    while side < chunk:
        # only the second block of a pair has rows in `A_off`, and so in
        # both products: half the rows go through the MXU
        def second_rows(x):
            return jnp.concatenate(
                [x[at:at + side] for at in range(side, chunk, 2 * side)],
                axis=0)

        off = second_rows(jnp.where(
            (row // (2 * side) == col // (2 * side))
            & (row // side != col // side), A, 0.0))
        right = _exact(off, _by_head(T, same_head), _NN)
        nothing = jnp.zeros((side, width), jnp.float32)
        right = jnp.concatenate(
            [part for at in range(0, chunk // 2, side)
             for part in (nothing, right[at:at + side])], axis=0)
        lower = _exact(second_rows(T), _by_head(right, same_head), _NN)
        T = T - jnp.concatenate(
            [part for at in range(0, chunk // 2, side)
             for part in (nothing, lower[at:at + side])], axis=0)
        side *= 2
    return T


def _chunk_parts(q, k, rows, *, chunk: int, cd, normalize):
    """What both kernels build of a chunk before they read the state, in
    VMEM: q, k [C, K] float32 as the conv left them, `rows` the chunk's
    [8, 128] block -> a dict of the normed q and k stacked a value head,
    the per-token columns stacked ``[(r, i), 1]``, the packed decay, scores
    and inverse, and the stacked operands of the carry and the readout."""
    row, col, second, same_head = _packed_geometry(chunk)
    raw = {"q_raw": q, "k_raw": k}
    if normalize is not None:
        scale = q.shape[-1] ** -0.5
        raw["q_norm"], raw["k_norm"] = (jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + normalize)
            for x in (q, k))
        q, k = q * raw["q_norm"] * scale, k * raw["k_norm"]
    # column l of the transposed tile is row l mod 8
    columns = jnp.transpose(jnp.concatenate([rows] * (_LANES // 8), axis=0))
    cum, beta, last = (columns[:, at:at + 1]
                       for at in (_ROW_CUM, _ROW_BETA, _ROW_LAST))
    below = row >= col
    # masked BEFORE the exponential: above the diagonal γ_i − γ_j ≥ 0
    decay = jnp.exp(jnp.where(
        below, _packed_columns(cum, second, chunk)
        - rows[_ROW_CUM:_ROW_CUM + 1], -jnp.inf))
    k_cd, q_cd = k.astype(cd), q.astype(cd)
    keys = _twice(k_cd)
    kk = _one_pass(k_cd, keys, _NT)                       # [C, (r, j)]
    qk = _one_pass(q_cd, keys, _NT)
    beta_packed = _packed_columns(beta, second, chunk)
    A = jnp.where(row > col, kk * decay * beta_packed, 0.0)
    T = _inverse_packed(A, row, col, second, same_head)
    grow, to_end = jnp.exp(cum), jnp.exp(last - cum)
    k_stacked, q_stacked = _twice(k), _twice(q)
    return {
        **raw, "k_cd": k_cd, "q_cd": q_cd, "k_stacked": k_stacked,
        "q_stacked": q_stacked, "beta": beta, "grow": grow, "to_end": to_end,
        "decay": decay, "kk": kk, "qk": qk, "T": T, "same_head": same_head,
        "second": second, "strict": row > col, "beta_packed": beta_packed,
        "T_by_head": _by_head(T, same_head).astype(cd),
        "P_by_head": _by_head((qk * decay).astype(cd), same_head),
        "q_grown": (q_stacked * grow).astype(cd),
        "k_end": (k_stacked * to_end).astype(cd),
        "k_written": k_stacked * (beta * grow),
    }


def _walk(chunks: int, unrolled: int, one_chunk):
    """`one_chunk(c)` for c = 0 … chunks − 1 in order, `unrolled` of them a
    loop body (Mosaic unrolls a loop whole or not at all)."""
    held = unrolled if chunks % unrolled == 0 else 1

    def body(step, _):
        for u in range(held):
            one_chunk(step * held + u)

    jax.lax.fori_loop(0, chunks // held, body, None)


def _fwd_kernel(q_ref, k_ref, v_ref, rows_ref, o_ref, starts_ref, state, *,
                chunk: int, unrolled: int, v_dim: int, cd, normalize):
    @pl.when(pl.program_id(2) == 0)
    def _first_block():
        state[...] = jnp.zeros_like(state)

    C, V = chunk, v_dim

    def one_chunk(c):
        at = pl.ds(pl.multiple_of(c * C, C), C)
        rows = rows_ref[c]
        p = _chunk_parts(q_ref[at, :], k_ref[at, :], rows, chunk=C, cd=cd,
                         normalize=normalize)
        v = jnp.concatenate([v_ref[at, :V], v_ref[at, V:]], axis=0)
        U = _one_pass(p["T_by_head"], (v * p["beta"]).astype(cd), _NN)
        W = _one_pass(p["T_by_head"], p["k_written"].astype(cd),
                      _NN).astype(cd)
        values, reads = [], []
        for r, own in enumerate((slice(0, C), slice(C, 2 * C))):
            start = state[r]
            start_cd = start.astype(cd)
            starts_ref[c, r] = start_cd
            # W·S and (e^γ ∘ q)·S in one product
            seen = _one_pass(
                jnp.concatenate([W[own], p["q_grown"][own]], axis=0),
                start_cd, _NN)
            new = (U[own] - seen[:C]).astype(cd)
            keep = jnp.exp(rows[_ROW_LAST_OF + r:_ROW_LAST_OF + r + 1])
            state[r] = keep * start + _one_pass(p["k_end"][own], new, _TN)
            values.append(new)
            reads.append(seen[C:])
        out = (jnp.concatenate(reads, axis=0)
               + _one_pass(p["P_by_head"], jnp.concatenate(values, axis=0),
                           _NN))
        o_ref[at, :V] = out[:C]
        o_ref[at, V:] = out[C:]

    _walk(rows_ref.shape[0], unrolled, one_chunk)


def _packed_offsets(key_heads: int, k_dim: int, v_dim: int):
    """Where q, k and v begin in ``[q | k | v]``, each in blocks of its own
    width: a key head's K columns for q and k, its two value heads' 2·V for
    v."""
    return 0, key_heads, key_heads * k_dim // v_dim


def _block_specs(block: int, chunk: int, k_dim: int, v_dim: int, offsets,
                 block_of):
    """Block specs of a grid step (batch i, key head g, step s of the token
    axis; `block_of` maps s to the block of tokens): q and k ``[b, T, ·]``
    read a key head's columns `offsets` blocks into their operand
    (`_packed_offsets` in ``[q | k | v]``, zeros in an array of their own),
    v a key head's two value heads', o ``[b, T, H·V]`` likewise; the rows
    ``[b, G, chunks, 8, 128]``; the chunks' start states ``[chunks, b, G, r,
    K, V]``."""
    q_at, k_at, v_at = offsets
    chunks = block // chunk
    return {
        "q": pl.BlockSpec((None, block, k_dim),
                          lambda i, g, s: (i, block_of(s), q_at + g)),
        "k": pl.BlockSpec((None, block, k_dim),
                          lambda i, g, s: (i, block_of(s), k_at + g)),
        "v": pl.BlockSpec((None, block, 2 * v_dim),
                          lambda i, g, s: (i, block_of(s), v_at + g)),
        "o": pl.BlockSpec((None, block, 2 * v_dim),
                          lambda i, g, s: (i, block_of(s), g)),
        "rows": pl.BlockSpec((None, None, chunks, 8, _LANES),
                             lambda i, g, s: (i, g, block_of(s), 0, 0)),
        "starts": pl.BlockSpec((chunks, None, None, 2, k_dim, v_dim),
                               lambda i, g, s: (block_of(s), i, g, 0, 0, 0)),
    }


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT_BYTES)


_STATIC = ("k_dim", "v_dim", "chunk", "block", "unrolled", "cd", "normalize",
           "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _delta_fwd(qkv, rows, *, k_dim, v_dim, chunk, block, unrolled, cd,
               normalize, interpret):
    """qkv: ``[q | k | v]`` [b, T, 2·G·K + H·V] float32 as the conv left
    them, T a multiple of `block`, handed to the kernel three times (an
    operand a block spec: a key head's columns of q, of k and of v are read
    out of it in place); rows [b, G, T / C, 8, 128] -> o [b, T, H·V] float32
    and every chunk's START state [T / C, b, G, r, K, V] in `cd`. Four array
    operands: `flops.flash_call_cost` of the benchmark reads a Mosaic call
    of three or six as a flash kernel."""
    b, T = qkv.shape[:2]
    G = rows.shape[1]
    spec = _block_specs(block, chunk, k_dim, v_dim,
                        _packed_offsets(G, k_dim, v_dim), lambda s: s)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, unrolled=unrolled,
                          v_dim=v_dim, cd=cd, normalize=normalize),
        grid=(b, G, T // block),
        in_specs=[spec["q"], spec["k"], spec["v"], spec["rows"]],
        out_specs=[spec["o"], spec["starts"]],
        out_shape=[
            jax.ShapeDtypeStruct((b, T, G * 2 * v_dim), jnp.float32),
            jax.ShapeDtypeStruct((T // chunk, b, G, 2, k_dim, v_dim), cd)],
        scratch_shapes=[pltpu.VMEM((2, k_dim, v_dim), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="delta_fwd",
    )(qkv, qkv, qkv, rows)


def _own(full, second, chunk: int):
    """``[(r, i), (r', j)]`` -> packed ``[C, (r, j)]``: each head's own
    block of the square tile."""
    return jnp.where(second, full[chunk:], full[:chunk])


def _halves(stacked, chunk: int):
    """The sum over the key head's two value heads of a stacked ``[(r, i),
    ·]`` array."""
    return stacked[:chunk] + stacked[chunk:]


def _by_token(x):
    return jnp.sum(x, axis=-1, keepdims=True)


def _by_token_and_head(packed, second):
    """Packed ``[C, (r, j)]`` summed over j -> stacked ``[(r, i), 1]``."""
    return jnp.concatenate([_by_token(jnp.where(second, 0.0, packed)),
                            _by_token(jnp.where(second, packed, 0.0))],
                           axis=0)


def _bwd_kernel(eps_ref, q_ref, k_ref, v_ref, rows_ref, starts_ref, do_ref,
                dq_ref, dk_ref, dv_ref, drows_ref, dstate, *, chunk: int,
                unrolled: int, v_dim: int, cd, normed: bool):
    @pl.when(pl.program_id(2) == 0)
    def _last_block():
        dstate[...] = jnp.zeros_like(dstate)

    C, V = chunk, v_dim
    chunks = rows_ref.shape[0]
    f32 = jnp.float32
    heads = (slice(0, C), slice(C, 2 * C))

    def rounded(x):
        """A cotangent that comes back for a `cd` operand: in `cd`."""
        return x.astype(cd).astype(f32)

    def one_chunk(step):
        c = chunks - 1 - step
        at = pl.ds(pl.multiple_of(c * C, C), C)
        rows = rows_ref[c]
        p = _chunk_parts(q_ref[at, :], k_ref[at, :], rows, chunk=C, cd=cd,
                         normalize=eps_ref[0, 0] if normed else None)
        second, beta, grow = p["second"], p["beta"], p["grow"]
        decay, T, to_end = p["decay"], p["T"], p["to_end"]
        q_stacked, k_stacked = p["q_stacked"], p["k_stacked"]
        v = jnp.concatenate([v_ref[at, :V], v_ref[at, V:]], axis=0)
        d_out = jnp.concatenate([do_ref[at, :V], do_ref[at, V:]],
                                axis=0).astype(cd)
        written_v = (v * beta).astype(cd)
        written_k = p["k_written"].astype(cd)
        U = _one_pass(p["T_by_head"], written_v, _NN)
        W = _one_pass(p["T_by_head"], written_k, _NN).astype(cd)
        # ---- the carry and the readout, backwards: V' rebuilt from the
        # kept start state, the state's cotangent walked on
        starts = [starts_ref[c, r] for r in range(2)]
        d_after = [dstate[r] for r in range(2)]
        d_after_cd = [d.astype(cd) for d in d_after]
        values = jnp.concatenate(
            [(U[own] - _one_pass(W[own], starts[r], _NN)).astype(cd)
             for r, own in enumerate(heads)], axis=0)
        d_values = _one_pass(p["P_by_head"], d_out, _TN)       # Pᵀ·dO
        d_P = _own(_one_pass(d_out, values, _NT), second, C).astype(cd)
        d_new, d_q_grown, d_W, d_k_end, d_last_of = [], [], [], [], []
        for r, own in enumerate(heads):
            new = d_values[own] + _one_pass(p["k_end"][own], d_after_cd[r],
                                            _NN)
            both = jnp.concatenate([d_out[own], new.astype(cd)], axis=0)
            # dO·Sᵀ and dV'·Sᵀ in one product
            read = _one_pass(both, starts[r], _NT)
            d_q_grown.append(read[:C].astype(cd))
            d_W.append(-read[C:].astype(cd))
            d_k_end.append(_one_pass(values[own], d_after_cd[r],
                                     _NT).astype(cd))
            keep = jnp.exp(rows[_ROW_LAST_OF + r:_ROW_LAST_OF + r + 1])
            d_last_of.append(keep * jnp.sum(
                d_after[r] * starts[r].astype(f32), axis=0, keepdims=True))
            # (e^γ ∘ q)ᵀ·dO − Wᵀ·dV' in one product
            dstate[r] = keep * d_after[r] + _one_pass(
                jnp.concatenate([p["q_grown"][own], -W[own]], axis=0), both,
                _TN)
            d_new.append(new)
        d_U = jnp.concatenate(d_new, axis=0)                    # float32
        d_q_grown, d_W, d_k_end = (
            jnp.concatenate(x, axis=0).astype(f32)
            for x in (d_q_grown, d_W, d_k_end))
        # ---- `prepare`, backwards
        d_q = d_q_grown * grow                                  # stacked
        d_grow = _by_token(d_q_grown * q_stacked)
        d_k = d_k_end * to_end
        d_to_end = _by_token(d_k_end * k_stacked) * to_end
        d_U_cd, d_W_cd = d_U.astype(cd), d_W.astype(cd)
        d_T = (_own(rounded(_one_pass(d_U_cd, written_v, _NT)), second, C)
               + _own(rounded(_one_pass(d_W_cd, written_k, _NT)), second, C))
        d_written_v = rounded(_one_pass(p["T_by_head"], d_U_cd, _TN))
        d_written_k = rounded(_one_pass(p["T_by_head"], d_W_cd, _TN))
        d_v = d_written_v * beta
        d_k = d_k + d_written_k * (beta * grow)
        written = _by_token(d_written_k * k_stacked)
        d_beta = _by_token(d_written_v * v) + written * grow
        d_grow = d_grow + written * beta
        # the inverse's rule: dA = −Tᵀ·dT·Tᵀ below the diagonal
        T_t = jnp.transpose(_by_head(T, p["same_head"]))
        d_A = jnp.where(p["strict"], -_exact(
            _own(T_t, second, C),
            _by_head(_exact(d_T, T_t, _NN), p["same_head"]), _NN), 0.0)
        d_P = d_P.astype(f32)
        d_kk = d_A * decay * p["beta_packed"]
        d_qk = d_P * decay
        # the decay of token j at token i grows with γ_i and shrinks with
        # γ_j: the two sums of ONE float32 array, so that what cancels
        # between them in the running sum's cotangent does (`ops/ssd.py`)
        along = d_A * p["kk"]
        grown = decay * (along * p["beta_packed"] + d_P * p["qk"])
        d_cum = _by_token_and_head(grown, second) + d_grow * grow - d_to_end
        d_beta = d_beta + _by_token_and_head(along * decay, second)
        # the scores: both heads' cotangents against the key head's q and k
        d_scores = jnp.concatenate([d_kk, d_qk], axis=0).astype(cd)
        by_row = rounded(_one_pass(d_scores, _twice(p["k_cd"]), _NN))
        by_col = rounded(_one_pass(
            d_scores, jnp.concatenate([p["k_cd"], p["q_cd"]], axis=0), _TN))
        d_k = _halves(d_k, C) + by_row[:C] + _halves(by_col, C)
        d_q = _halves(d_q, C) + by_row[C:]
        if normed:
            d_q = d_q * p["q_raw"].shape[-1] ** -0.5
            d_q, d_k = (
                norm * d - raw * (norm * norm * norm * _by_token(raw * d))
                for raw, norm, d in ((p["q_raw"], p["q_norm"], d_q),
                                     (p["k_raw"], p["k_norm"], d_k)))
        dq_ref[at, :] = d_q
        dk_ref[at, :] = d_k
        dv_ref[at, :V] = d_v[:C]
        dv_ref[at, V:] = d_v[C:]
        # the per-token columns as the rows' lanes: one transposed tile
        lane = jax.lax.broadcasted_iota(jnp.int32, (2 * C, _LANES), 1)
        as_rows = jnp.transpose(jnp.where(
            lane == _ROW_CUM, d_cum, jnp.where(
                lane == _ROW_BETA, d_beta, jnp.where(
                    lane == _ROW_LAST, d_to_end, 0.0))))[:8]
        sub = jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 0)
        drows_ref[c] = as_rows + jnp.where(
            sub == _ROW_CUM, -jnp.sum(grown, axis=0, keepdims=True),
            jnp.where(sub == _ROW_LAST_OF, d_last_of[0],
                      jnp.where(sub == _ROW_LAST_OF + 1, d_last_of[1], 0.0)))

    _walk(chunks, unrolled, one_chunk)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _delta_bwd(qkv, rows, starts, d_out, *, k_dim, v_dim, chunk, block,
               unrolled, cd, normalize, interpret):
    """The operands of :func:`_delta_fwd`, its start states and o's
    cotangent [b, T, H·V] float32 -> the cotangents of q, k [b, T, G·K]
    (both value heads' summed), v [b, T, H·V] and of the rows. The norm's
    epsilon is an OPERAND, [1, 1] in SMEM: six array operands would read as
    a flash kernel (`_delta_fwd`)."""
    b, T = qkv.shape[:2]
    G = rows.shape[1]
    last = T // block - 1
    spec = _block_specs(block, chunk, k_dim, v_dim,
                        _packed_offsets(G, k_dim, v_dim), lambda s: last - s)
    own = _block_specs(block, chunk, k_dim, v_dim, (0, 0, 0),
                       lambda s: last - s)
    narrow = jax.ShapeDtypeStruct((b, T, G * k_dim), jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, unrolled=unrolled,
                          v_dim=v_dim, cd=cd, normed=normalize is not None),
        grid=(b, G, T // block),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec["q"],
                  spec["k"], spec["v"], spec["rows"], spec["starts"],
                  spec["o"]],
        out_specs=[own["q"], own["k"], own["v"], spec["rows"]],
        out_shape=[narrow, narrow,
                   jax.ShapeDtypeStruct((b, T, G * 2 * v_dim), jnp.float32),
                   jax.ShapeDtypeStruct(rows.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((2, k_dim, v_dim), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="delta_bwd",
    )(jnp.full((1, 1), normalize or 0.0, jnp.float32), qkv, qkv, qkv, rows,
      starts, d_out)


def _kernel_rows(g, beta, *, key_heads: int, chunk: int):
    """g, β [b, T, H] float32, T whole chunks -> the kernels' per-chunk
    rows [b, G, T / C, 8, 128], a lane (value head of the key head, token):
    the running sum γ of g inside the chunk (inclusive), β, γ at the
    chunk's last token, and that a head on all 128 lanes. Plain JAX,
    differentiated by JAX."""
    b, T, H = g.shape
    R, n = H // key_heads, T // chunk

    def lanes(a):                                  # [b, n, C, G, R] -> rows
        return jnp.transpose(a, (0, 3, 1, 4, 2)).reshape(
            b, key_heads, n, R * chunk)

    g = g.reshape(b, n, chunk, key_heads, R)
    # the running sum as a product with a triangle of ones, in float32
    # (HIGHEST: the ones are exact, the sum is float32's), as `ops/ssd.py`
    cum = jnp.einsum("bnjgr,ji->bnigr", g,
                     jnp.triu(jnp.ones((chunk, chunk), jnp.float32)),
                     precision=_HIGHEST)
    last = cum[:, :, -1:]                                  # [b, n, 1, G, R]
    whole = jnp.broadcast_to(
        jnp.transpose(last[:, :, 0], (0, 2, 1, 3))[..., None],
        (b, key_heads, n, R, _LANES))
    rows = jnp.stack([lanes(cum),
                      lanes(beta.reshape(b, n, chunk, key_heads, R)),
                      lanes(jnp.broadcast_to(last, cum.shape))], axis=3)
    return jnp.concatenate(
        [rows, whole,
         jnp.zeros((b, key_heads, n, 8 - 3 - R, _LANES), jnp.float32)],
        axis=3)


# ------------------------------------------------------------------ entry
def _check(heads: int, key_heads: int, chunk: int):
    if heads % key_heads:
        raise ValueError(f"{heads} value heads on {key_heads} key heads: a "
                         f"key head is read by a whole number of value heads")
    if chunk > _BASE and (chunk % _BASE or (chunk // _BASE)
                          & (chunk // _BASE - 1)):
        raise ValueError(f"chunk={chunk}: at most {_BASE}, or {_BASE} times "
                         f"a power of two (the triangular inverse's blocks)")


def _to_chunks(q, k, v, g, beta, chunk: int):
    b, T, G, K = q.shape
    H, V = v.shape[2:]
    _check(H, G, chunk)
    pad = -T % chunk
    f32 = jnp.float32
    q, k, v, g, beta = (
        jnp.pad(a.astype(f32), [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        for a in (q, k, v, g, beta))
    n, R = (T + pad) // chunk, H // G
    # the chunks leading (the carry scans them) and heads ahead of a
    # chunk's tokens: every product a batched matmul

    def lead(a, heads_to: int):
        return jnp.moveaxis(jnp.moveaxis(a, 2, heads_to), 1, 0)

    return (lead(q.reshape(b, n, chunk, G, K), 3),
            lead(k.reshape(b, n, chunk, G, K), 3),
            lead(v.reshape(b, n, chunk, G, R, V), 4),
            lead(g.reshape(b, n, chunk, G, R), 4),
            lead(beta.reshape(b, n, chunk, G, R), 4))


def _from_chunks(out, T: int):
    """[n, b, G, R, C, V] -> [b, T, H, V]."""
    n, b, G, R, C, V = out.shape
    return jnp.moveaxis(jnp.moveaxis(out, 0, 1), 4, 2).reshape(
        b, n * C, G * R, V)[:, :T]


def _split(qkv, g, key_heads: int, k_dim: int):
    """``[q | k | v]`` [b, T, 2·G·K + H·V] -> q, k [b, T, G, K], v [b, T,
    H, V]."""
    b, T, H = g.shape
    wide = key_heads * k_dim
    q, k, v = jnp.split(qkv, [wide, 2 * wide], axis=-1)
    return (q.reshape(b, T, key_heads, k_dim),
            k.reshape(b, T, key_heads, k_dim), v.reshape(b, T, H, -1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernel_rule(qkv, g, beta, static):
    """qkv: ``[q | k | v]`` as the conv left them [b, T, 2·G·K + H·V], read
    in place; g, β [b, T, H]; all float32 -> o [b, T, H·V] float32. `static`:
    `_static`'s."""
    return _kernel_rule_fwd(qkv, g, beta, static)[0]


def _whole_blocks(block: int, *arrays):
    """[b, T, ·] arrays zero-padded behind the last token to whole blocks
    (``g = 0``, ``β = 0`` tokens neither decay the state nor write to it)."""
    pad = -arrays[0].shape[1] % block
    if not pad:
        return arrays
    return tuple(jnp.pad(a, [(0, 0), (0, pad), (0, 0)]) for a in arrays)


def _kernel_rule_fwd(qkv, g, beta, static):
    key_heads, kw = static[0], dict(zip(_STATIC, static[1:]))
    whole, g_whole, beta_whole = _whole_blocks(kw["block"], qkv, g, beta)
    out, starts = _delta_fwd(
        whole, _kernel_rows(g_whole, beta_whole, key_heads=key_heads,
                            chunk=kw["chunk"]), **kw)
    return out[:, :g.shape[1]], (qkv, g, beta, starts)


def _kernel_rule_bwd(static, residuals, d_out):
    key_heads, kw = static[0], dict(zip(_STATIC, static[1:]))
    qkv, g, beta, starts = residuals
    T = g.shape[1]
    whole, g_whole, beta_whole, d_whole = _whole_blocks(
        kw["block"], qkv, g, beta, d_out.astype(jnp.float32))
    # the rows as a function of g and β: JAX differentiates it
    rows, back = jax.vjp(functools.partial(
        _kernel_rows, key_heads=key_heads, chunk=kw["chunk"]),
        g_whole, beta_whole)
    *d_qkv, d_rows = _delta_bwd(whole, rows, starts, d_whole, **kw)
    d_g, d_beta = back(d_rows)
    return (jnp.concatenate(d_qkv, axis=-1)[:, :T], d_g[:, :T],
            d_beta[:, :T])


_kernel_rule.defvjp(_kernel_rule_fwd, _kernel_rule_bwd)


def _static(key_heads, k_dim, v_dim, chunk, compute_dtype, normalize,
            interpret):
    """The kernels' key heads and `_STATIC` keywords (the module's block of
    tokens and chunks a loop body as they stand when the call is traced)."""
    return (key_heads, k_dim, v_dim, chunk, BLOCK_TOKENS, CHUNKS_UNROLLED,
            jnp.dtype(compute_dtype), normalize, interpret)


def _kernels_take(mesh, interpret: bool, chunk: int, key_heads: int,
                  heads: int, k_dim: int, v_dim: int) -> bool:
    _check(heads, key_heads, chunk)
    return _use_kernel(*target.where(mesh, interpret=interpret), chunk,
                       key_heads, heads // key_heads, k_dim, v_dim)


def gated_delta(q, k, v, g, beta, *, chunk: int = 64,
                compute_dtype=jnp.bfloat16, normalize=None, mesh=None,
                interpret: bool = False):
    """q, k [B, T, G, K], v [B, T, H, V], g (log-decay, ≤ 0) and β [B, T,
    H] -> o [B, T, H, V] float32, the state zero before each sequence's
    first token. Chunked, with the backward of this module's docstring.

    normalize: None — q and k are read as they come (normed and scaled by
    the caller) — or an epsilon: each head's q and k are L2-normed here
    (``x · rsqrt(Σx² + ε)``), q then times ``K^-½``, inside `prepare`; the
    backward then keeps the RAW chunked q and k and no normed copy beside
    what the caller's norm would keep.

    mesh: where the rule runs (`target.where`); that and the shapes decide
    between the kernels and the plain form (`_use_kernel`). The kernels
    read ONE array, so where they are taken the three are laid side by side
    for :func:`gated_delta_packed` (a copy; the layer, whose conv leaves
    them so, calls that entry). `interpret` runs the kernels in Pallas's
    interpreter wherever the process is, and exists for tests."""
    (b, T, G, K), (H, V) = q.shape, v.shape[2:]
    if _kernels_take(mesh, interpret, chunk, G, H, K, V):
        return gated_delta_packed(
            jnp.concatenate([q.reshape(b, T, G * K), k.reshape(b, T, G * K),
                             v.reshape(b, T, H * V)], axis=-1),
            g, beta, key_heads=G, k_dim=K, chunk=chunk,
            compute_dtype=compute_dtype, normalize=normalize, mesh=mesh,
            interpret=interpret)
    if interpret:
        raise ValueError(f"gated_delta: no kernel tiling for chunk {chunk}, "
                         f"{H // G} value heads a key head, widths {K}, {V}")
    return _from_chunks(
        _rule(*_to_chunks(q, k, v, g, beta, chunk), compute_dtype, normalize),
        T)


def gated_delta_packed(qkv, g, beta, *, key_heads: int, k_dim: int,
                       chunk: int = 64, compute_dtype=jnp.bfloat16,
                       normalize=None, mesh=None, interpret: bool = False):
    """:func:`gated_delta` on ``[q | k | v]`` [B, T, 2·G·K + H·V] as the
    layer's conv leaves them, each part head after head: the kernels read a
    head's columns out of it in place, so no slice of it is copied for
    their sake; the plain form splits it."""
    b, T, H = g.shape
    v_dim = (qkv.shape[-1] - 2 * key_heads * k_dim) // H
    if _kernels_take(mesh, interpret, chunk, key_heads, H, k_dim, v_dim):
        f32 = jnp.float32
        out = _kernel_rule(
            qkv.astype(f32), g.astype(f32), beta.astype(f32),
            _static(key_heads, k_dim, v_dim, chunk, compute_dtype, normalize,
                    interpret))
        return out.reshape(b, T, H, v_dim)
    return gated_delta(*_split(qkv, g, key_heads, k_dim), g, beta,
                       chunk=chunk, compute_dtype=compute_dtype,
                       normalize=normalize, mesh=mesh, interpret=interpret)


def gated_delta_plain(q, k, v, g, beta, *, chunk: int = 64,
                      compute_dtype=jnp.bfloat16, normalize=None):
    """:func:`gated_delta` with JAX's own derivative of the three stages:
    the custom backward's control."""
    return _from_chunks(
        _chunked(*_to_chunks(q, k, v, g, beta, chunk), cd=compute_dtype,
                 normalize=normalize)[0], q.shape[1])
