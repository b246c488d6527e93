"""State-space scan of Mamba-2 (Dao & Gu, arXiv:2405.21060), the chunked
"state-space dual" form in plain JAX, differentiated by JAX.

The recurrence, a head at a time (state ``H`` [P, N], ``a_t = exp(Δ_t·A)``):

    H_t = a_t · H_{t−1} + Δ_t · x_t · B_tᵀ        y_t = H_t · C_t + D · x_t

``B`` and ``C`` belong to a GROUP of heads (``H // G`` heads share one).
Unrolled over a chunk of ``Q`` tokens it is four stages, each under its own
``jax.named_scope``:

* ``ssd/intra``: inside a chunk, ``y_i += Σ_{j≤i} (C_i·B_j) · exp(Σ_{j<k≤i}
  Δ_k A) · Δ_j x_j``: the ``Q × Q`` scores ``C·Bᵀ`` a group, times the decay
  matrix a head (cumulative log-decay in float32, masked above the
  diagonal BEFORE the exponential), times ``Δ·x``: matmuls of ``Q × Q``, the
  MXU's shape at Q = 128;
* ``ssd/states``: what a chunk adds to the state by its end, ``Σ_j exp(Σ_{j<k}
  Δ_k A) · Δ_j x_j B_jᵀ``, one ``[P, N]`` a head and chunk;
* ``ssd/carry``: the state at each chunk's start, a `lax.scan` over the
  chunks (``H ← decay · H + added``), float32;
* ``ssd/readout``: ``y_i += exp(Σ_{k≤i} Δ_k A) · C_i · H_start``.

No ``[T, T]`` array exists; the largest is the decay matrix, ``T · Q · H``
elements (:func:`ssd_plan`). A length the chunk does not divide is padded
with ``Δ = 0`` tokens, which neither decay the state nor add to it.

Precision: decays, cumulative sums and the carried state in float32; the
four products' operands in `compute_dtype` with float32 accumulation, and
with `three_pass` their forward values to float32 accuracy (`mxu.einsum`),
for a model whose later layers are discontinuous in them. The one caller
(`layers.apply_mamba` for `models/nemotron_h.py`) sets it; off is the
single-pass control that the tests and the chip probe compare with.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops import mxu


def ssd_plan(tokens: int, heads: int, head_dim: int, state: int,
             groups: int, chunk: int, itemsize: int = 2) -> dict:
    """What one forward call of :func:`ssd` does for `tokens` tokens (a
    multiple of `chunk`), from shapes alone: the four products' FLOPs as
    issued (the whole ``Q × Q`` tile, the half above the diagonal too), the
    FLOPs of the token-by-token recurrence (what a model's accounting
    counts: chunking, like recomputation, is the implementation's), the
    elements of the decay matrix, and the bytes a call must move: x, Δ, B, C
    read and y written once."""
    flops = {
        "scores": 2 * tokens * chunk * groups * state,
        "intra": 2 * tokens * chunk * heads * head_dim,
        "states": 2 * tokens * heads * head_dim * state,
        "readout": 2 * tokens * heads * head_dim * state,
    }
    return {
        "chunks": tokens // chunk,
        "flops_by_stage": flops,
        "flops": sum(flops.values()),
        "flops_recurrence": 4 * tokens * heads * head_dim * state,
        "decay_elements": tokens * chunk * heads,
        "bytes": (2 * tokens * heads * head_dim
                  + 2 * tokens * groups * state) * itemsize
        + tokens * heads * 4,
    }


def ssd(x, dt, A, B, C, D, *, chunk: int, compute_dtype=jnp.bfloat16,
        three_pass: bool = False):
    """x [b, T, H, P], dt [b, T, H] (Δ > 0), A [H] (< 0), B, C [b, T, G, N],
    D [H] -> y [b, T, H, P] float32."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    R, Q = H // G, chunk
    pad = -T % Q
    if pad:
        x, dt, B, C = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, B, C))
    nc = (T + pad) // Q
    f32 = jnp.float32

    def mm(eq, lhs, rhs):
        return mxu.einsum(eq, lhs, rhs, f32, cd=compute_dtype,
                          three_pass=three_pass)

    xc = x.astype(f32).reshape(b, nc, Q, G, R, P)
    dtc = dt.astype(f32).reshape(b, nc, Q, G, R)
    Bc = B.astype(f32).reshape(b, nc, Q, G, N)
    Cc = C.astype(f32).reshape(b, nc, Q, G, N)
    # log-decay a step, and its running sum inside each chunk (inclusive)
    cum = jnp.cumsum(dtc * A.astype(f32).reshape(G, R), axis=2)
    xdt = xc * dtc[..., None]                                # [b,c,j,g,r,p]
    with jax.named_scope("ssd/intra"):
        rows = jnp.moveaxis(cum, 2, -1)                      # [b,c,g,r,q]
        below = jnp.tril(jnp.ones((Q, Q), bool))
        decay = jnp.exp(jnp.where(
            below, rows[..., :, None] - rows[..., None, :], -jnp.inf))
        scores = mm("bcign,bcjgn->bcgij", Cc, Bc)
        y = mm("bcgrij,bcjgrp->bcigrp", scores[:, :, :, None] * decay, xdt)
    with jax.named_scope("ssd/states"):
        last = cum[:, :, -1]                                 # [b,c,g,r]
        to_end = jnp.exp(last[:, :, None] - cum)             # [b,c,j,g,r]
        added = mm("bcjgrp,bcjgn->bcgrpn", xdt * to_end[..., None], Bc)
    with jax.named_scope("ssd/carry"):
        def step(state, chunk_in):
            plus, keep = chunk_in
            return keep[..., None, None] * state + plus, state

        _, start = jax.lax.scan(
            step, jnp.zeros((b, G, R, P, N), f32),
            (jnp.moveaxis(added, 1, 0), jnp.moveaxis(jnp.exp(last), 1, 0)))
        start = jnp.moveaxis(start, 0, 1)                    # [b,c,g,r,p,n]
    with jax.named_scope("ssd/readout"):
        y = y + (mm("bcign,bcgrpn->bcigrp", Cc, start)
                 * jnp.exp(cum)[..., None])
    y = y + D.astype(f32).reshape(G, R, 1) * xc
    return y.reshape(b, nc * Q, H, P)[:, :T]
