"""Fused flash attention (Pallas TPU kernels, forward AND backward).

The hot op of the flagship models. Forward is a Pallas kernel: grid over
(batch*heads, Q blocks, KV blocks), online-softmax accumulators held in
VMEM scratch across the sequential KV grid dimension, causal blocks
skipped at block granularity. Backward is two Pallas kernels (the standard
flash-attention split): a dq kernel gridded (BH, Q blocks, KV blocks) and
a dk/dv kernel gridded (BH, KV blocks, Q blocks), each recomputing the
probability block from the saved logsumexp — no O(S²) tensor is ever
materialized in HBM, unlike a naive VJP.

The kernels compile for the TPU or raise. ``interpret=True`` (the Pallas
interpreter) is for tests that ask for it; off-TPU product code uses
parallel.ring_attention.reference_attention.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128
# grid = (batch*heads, parallel blocks, sequentially accumulated blocks)
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *, scale, causal,
    block_q, block_k, seq_len, padded,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    def _compute():
        q = q_ref[0]  # [block_q, D]
        k = k_ref[0]  # [block_k, D]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale  # [block_q, block_k]
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if padded:
            # Mask KV padding columns (inputs padded up to the block size).
            s = jnp.where(cols < seq_len, s, NEG_INF)
        m_prev = m_ref[:, 0]  # [block_q]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, 0] * corr + jnp.sum(p, axis=-1)
        acc_ref[:] = acc_ref[:] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    if causal:
        # Skip KV blocks entirely in the future of this Q block.
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[:, 0]
        o_ref[0] = (acc_ref[:] / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
        # lse is materialized as [BH, 8, S] (8 broadcast sublanes) to satisfy
        # the TPU (8, 128) block-tiling constraint; callers slice [:, 0, :].
        lse = m_ref[:, 0] + jnp.log(jnp.maximum(l, 1e-30))
        lse_ref[0] = jnp.broadcast_to(lse[None, :], (8, lse.shape[0]))


def _flash_fwd(q, k, v, *, scale, causal, block_q, block_k, interpret):
    """q,k,v: [BH, S, D] -> (o [BH,S,D], lse [BH,S]).

    Sequence lengths that don't divide the block size are zero-padded up to
    the next block multiple; padded KV columns are masked inside the kernel
    and padded Q rows sliced off the output.
    """
    BH, S, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    S_pad = -(-S // block_q) * block_q
    S_pad = -(-S_pad // block_k) * block_k
    if S_pad != S:
        pad = [(0, 0), (0, S_pad - S), (0, 0)]
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))
    grid = (BH, S_pad // block_q, S_pad // block_k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        seq_len=S, padded=S_pad != S,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S_pad, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 8, S_pad), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v)
    return o[:, :S], lse[:, 0, :S]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    o, _ = _flash_fwd(
        q, k, v, scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return o


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    o, lse = _flash_fwd(
        q, k, v, scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return o, (q, k, v, o, lse)


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, scale, causal, block_q, block_k, seq_len, padded,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = iq * block_q
    k_start = ik * block_k

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0]      # [block_q]
        delta = delta_ref[0, 0]  # [block_q]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if padded:
            s = jnp.where(cols < seq_len, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                       # [bq, bk]
        dp = jax.lax.dot_general(                           # do @ v^T
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale
        dq_acc[:] += jax.lax.dot_general(                   # ds @ k
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, scale, causal, block_q, block_k, seq_len, padded,
):
    ikb = pl.program_id(1)   # KV block (parallel)
    iqb = pl.program_id(2)   # Q block (sequential accumulation)
    nq = pl.num_programs(2)

    @pl.when(iqb == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = iqb * block_q
    k_start = ikb * block_k

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if padded:
            s = jnp.where(cols < seq_len, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                       # [bq, bk]
        dv_acc[:] += jax.lax.dot_general(                   # p^T @ do
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(                           # do @ v^T
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[:] += jax.lax.dot_general(                   # ds^T @ q
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # Q blocks strictly before this KV block contribute nothing.
        pl.when(q_start + block_q - 1 >= k_start)(_compute)
    else:
        _compute()

    @pl.when(iqb == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _broadcast8(x):
    """[BH, S] → [BH, 8, S] so the (8, 128) TPU tile constraint holds for
    row-vector inputs (same trick the forward uses for its lse output)."""
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], 8, x.shape[1]))


def _flash_bwd(q, k, v, o, lse, do, *, scale, causal, block_q, block_k,
               interpret):
    """Pallas backward: returns (dq, dk, dv), each [BH, S, D]."""
    BH, S, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    S_pad = -(-S // block_q) * block_q
    S_pad = -(-S_pad // block_k) * block_k
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if S_pad != S:
        pad = [(0, 0), (0, S_pad - S), (0, 0)]
        q, k, v, o, do = (jnp.pad(x, pad) for x in (q, k, v, o, do))
        lse = jnp.pad(lse, [(0, 0), (0, S_pad - S)])
        delta = jnp.pad(delta, [(0, 0), (0, S_pad - S)])
    lse8 = _broadcast8(lse)
    delta8 = _broadcast8(delta)
    nq, nk = S_pad // block_q, S_pad // block_k
    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              seq_len=S, padded=S_pad != S)

    qspec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0))
    row_q = pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kw),
        grid=(BH, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, row_q, row_q],
        out_specs=[qspec],
        out_shape=[jax.ShapeDtypeStruct((BH, S_pad, D), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v, do, lse8, delta8)[0]

    # dk/dv: grid transposed — KV blocks parallel, Q blocks sequential
    qspec2 = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, j, 0))
    kspec2 = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, i, 0))
    row_q2 = pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, j))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kw),
        grid=(BH, nk, nq),
        in_specs=[qspec2, kspec2, kspec2, qspec2, row_q2, row_q2],
        out_specs=[kspec2, kspec2],
        out_shape=[jax.ShapeDtypeStruct((BH, S_pad, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, S_pad, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v, do, lse8, delta8)
    return dq[:, :S], dk[:, :S], dv[:, :S]


def _flash_vjp_bwd(scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd(
        q, k, v, o, lse, do, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return dq, dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Flash attention over [B, S, H, D] (heads layout matching
    models/layers.apply_attention). Differentiable via custom VJP."""
    B, S, H, D = q.shape
    if scale is None:
        scale = 1.0 / (D**0.5)

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)

    o = _flash(
        to_bh(q), to_bh(k), to_bh(v), scale, causal, block_q, block_k, interpret
    )
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)
