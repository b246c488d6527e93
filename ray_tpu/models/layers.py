"""Transformer building blocks, pure-JAX pytree style.

Every layer is a (init_fn, apply_fn) pair over plain dict pytrees; sharding
comes from logical-axis annotations resolved by ray_tpu.parallel.sharding.
Compute is bf16 by default with f32 params/accumulators (MXU-native mix).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.parallel import sharding as sh
from ray_tpu.parallel.ring_attention import reference_attention, ring_attention_local

Params = Dict[str, Any]


def _init_dense(key, shape, scale=0.02, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layer_norm(x, scale, bias, eps=1e-5):
    """LayerNorm with a memory-lean custom VJP.

    XLA's autodiff residuals for the naive f32 LN cost ~2 f32 copies of x
    per call; saving (x, mu, rstd) and recomputing x̂ in the backward cut
    GPT-2-small step time measurably on v5e (part of the 0.34→0.42 MFU fix,
    see bench.py history) and, with the lean MLP below, lets batch 16-24
    train without remat on one 16 GiB chip."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _layer_norm_fwd(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x32 - mu) * rstd
    return (y * scale + bias).astype(x.dtype), (x, mu, rstd, scale)


def _layer_norm_bwd(eps, res, dy):
    x, mu, rstd, scale = res
    dy32 = dy.astype(jnp.float32)
    xhat = (x.astype(jnp.float32) - mu) * rstd
    reduce_axes = tuple(range(x.ndim - 1))
    dscale = jnp.sum(dy32 * xhat, axis=reduce_axes)
    dbias = jnp.sum(dy32, axis=reduce_axes)
    t = dy32 * scale
    dx = rstd * (
        t
        - jnp.mean(t, axis=-1, keepdims=True)
        - xhat * jnp.mean(t * xhat, axis=-1, keepdims=True)
    )
    return (dx.astype(x.dtype), dscale.astype(scale.dtype),
            dbias.astype(scale.dtype))


layer_norm.defvjp(_layer_norm_fwd, _layer_norm_bwd)


# ------------------------------------------------- tensor-parallel reduction
def exchange_sum(partial, axis_name: str):
    """Sum `partial` over the manual mesh axis `axis_name` by neighbour
    exchanges: `p + ppermute(p)` at size 2, a ring of size − 1 hops beyond.
    MUST run in per-device code (`jax.shard_map`, `check_vma=False`).

    This is the `tp` reduction of a row-parallel matmul, written as the one
    collective the TPU compiler runs asynchronously: a `collective-permute`
    is a start/done pair with compute scheduled between, where an
    `all-reduce` (what `psum` or the partitioner gives) blocks. The sum is
    taken in the partials' dtype, as the all-reduce took it. Its transpose
    is the same exchange on the cotangent, so the backward pass needs no
    rule of its own. Beyond size 2 each device adds in ring order from its
    own rank, so replicas agree to rounding, not to the bit."""
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    total = moving = partial
    for _ in range(n - 1):
        moving = jax.lax.ppermute(moving, axis_name, perm)
        total = total + moving
    return total


# ---------------------------------------------------------------- attention
def init_attention(key, d_model, n_head, dtype=jnp.float32):
    head_dim = d_model // n_head
    ks = jax.random.split(key, 4)
    return {
        "wq": _init_dense(ks[0], (d_model, n_head, head_dim), dtype=dtype),
        "wk": _init_dense(ks[1], (d_model, n_head, head_dim), dtype=dtype),
        "wv": _init_dense(ks[2], (d_model, n_head, head_dim), dtype=dtype),
        "wo": _init_dense(ks[3], (n_head, head_dim, d_model), dtype=dtype),
    }


ATTENTION_LOGICAL = {
    "wq": ("embed", "heads", "head_dim"),
    "wk": ("embed", "heads", "head_dim"),
    "wv": ("embed", "heads", "head_dim"),
    "wo": ("heads", "head_dim", "embed"),
}


def apply_attention(
    params: Params,
    x: jnp.ndarray,
    *,
    causal: bool = True,
    impl: str = "reference",
    sp_axis: str = "sp",
    compute_dtype=jnp.bfloat16,
    mesh=None,
    reduce=None,
):
    """x: [B, S, D] -> [B, S, D].

    impl: "reference" (plain jnp), "flash" (Pallas TPU kernel),
    "ring" (context-parallel over the ambient mesh's `sp_axis` — callable
    from inside jit with global arrays), "ring_local" (per-shard body;
    requires already running inside shard_map with sp_axis manual).

    mesh: the mesh q/k/v are sharded over, for "flash". A Mosaic kernel
    cannot be partitioned automatically (lowering it on sharded operands
    raises); under shard_map each device runs the kernel on its own batch
    and head shard.

    reduce: set by per-device callers (gpt2's `tp` region), whose params are
    the local head shard: sums the row-parallel partial output over `tp`.
    The kernel then runs on the local shard as it is, with no wrap.
    """
    cd = compute_dtype
    q = jnp.einsum("bsd,dhk->bshk", x.astype(cd), params["wq"].astype(cd))
    k = jnp.einsum("bsd,dhk->bshk", x.astype(cd), params["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", x.astype(cd), params["wv"].astype(cd))
    if impl == "ring":
        from ray_tpu.parallel.ring_attention import ring_attention

        o = ring_attention(q, k, v, None, causal=causal, seq_axis=sp_axis)
    elif impl == "ring_local":
        o = ring_attention_local(q, k, v, axis_name=sp_axis, causal=causal)
    elif impl == "flash":
        from ray_tpu.ops.flash_attention import flash_attention

        attend = functools.partial(flash_attention, causal=causal)
        if mesh is not None:
            io_spec = sh.spec("batch", None, "heads", None)
            attend = jax.shard_map(
                attend, mesh=mesh, in_specs=(io_spec, io_spec, io_spec),
                out_specs=io_spec, check_vma=False)
        o = attend(q, k, v)
    else:
        o = reference_attention(q, k, v, causal=causal)
    out = jnp.einsum("bshk,hkd->bsd", o.astype(cd), params["wo"].astype(cd))
    if reduce is not None:
        out = reduce(out)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- dense MLP
def init_mlp(key, d_model, d_ff, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    return {
        "w1": _init_dense(k1, (d_model, d_ff), dtype=dtype),
        "b1": jnp.zeros((d_ff,), dtype),
        "w2": _init_dense(k2, (d_ff, d_model), dtype=dtype),
        "b2": jnp.zeros((d_model,), dtype),
    }


MLP_LOGICAL = {
    "w1": ("embed", "mlp"),
    "b1": ("mlp",),
    "w2": ("mlp", "embed"),
    "b2": ("embed",),
}


def _mlp_compute(x, w1, b1, w2, b2, cd):
    u = jax.lax.dot_general(
        x.astype(cd), w1.astype(cd), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=cd,
    ) + b1.astype(cd)
    o = jax.lax.dot_general(
        jax.nn.gelu(u), w2.astype(cd), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=cd,
    ) + b2.astype(cd)
    return o, u


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _lean_mlp(x, w1, b1, w2, b2, cd):
    """2-layer GELU MLP with a memory-lean custom VJP: the backward saves
    only (x, w1, w2, u) — u the pre-activation — and recomputes gelu/gelu′
    elementwise. XLA's default VJP keeps ~6 hidden-sized residuals per
    layer, which is what pushed GPT-2-small batch 16 out of HBM without
    remat (measured: the no-remat OOM dump showed six [L,B,S,4D] buffers)."""
    return _mlp_compute(x, w1, b1, w2, b2, cd)[0]


def _lean_mlp_fwd(x, w1, b1, w2, b2, cd):
    o, u = _mlp_compute(x, w1, b1, w2, b2, cd)
    return o, (x, w1, w2, u)


def _lean_mlp_bwd(cd, res, do):
    x, w1, w2, u = res
    do = do.astype(cd)
    g, gvjp = jax.vjp(jax.nn.gelu, u)
    nd = x.ndim - 1
    x2 = x.reshape(-1, x.shape[-1])
    do2 = do.reshape(-1, do.shape[-1])
    g2 = g.reshape(-1, g.shape[-1])
    dg = jax.lax.dot_general(             # do @ w2^T
        do, w2.astype(cd), (((nd,), (1,)), ((), ())),
        preferred_element_type=cd,
    )
    dw2 = jax.lax.dot_general(            # g^T @ do (f32 accum)
        g2, do2, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    du = gvjp(dg)[0]
    du2 = du.reshape(-1, du.shape[-1])
    dw1 = jax.lax.dot_general(            # x^T @ du (f32 accum)
        x2.astype(cd), du2, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dx = jax.lax.dot_general(             # du @ w1^T
        du, w1.astype(cd), (((nd,), (1,)), ((), ())),
        preferred_element_type=cd,
    )
    db1 = jnp.sum(du.astype(jnp.float32), axis=tuple(range(nd)))
    db2 = jnp.sum(do.astype(jnp.float32), axis=tuple(range(nd)))
    return (dx.astype(x.dtype), dw1.astype(w1.dtype), db1.astype(w1.dtype),
            dw2.astype(w2.dtype), db2.astype(w2.dtype))


_lean_mlp.defvjp(_lean_mlp_fwd, _lean_mlp_bwd)


def apply_mlp(params: Params, x, compute_dtype=jnp.bfloat16, reduce=None):
    """reduce: as in apply_attention — w1/b1/w2 are the local `mlp` shard,
    the partial product is summed over `tp` and b2 added once, after."""
    w1, b1, w2, b2 = (params[k] for k in ("w1", "b1", "w2", "b2"))
    if reduce is None:
        out = _lean_mlp(x, w1, b1, w2, b2, compute_dtype)
    else:
        partial = _lean_mlp(x, w1, b1, w2, jnp.zeros_like(b2), compute_dtype)
        out = reduce(partial) + b2.astype(compute_dtype)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- MoE (EP)
@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25


def init_moe(key, d_model, d_ff, cfg: MoEConfig, dtype=jnp.float32):
    kg, k1, k2 = jax.random.split(key, 3)
    E = cfg.n_experts
    return {
        "wg": _init_dense(kg, (d_model, E), dtype=dtype),
        "w1": _init_dense(k1, (E, d_model, d_ff), dtype=dtype),
        "w2": _init_dense(k2, (E, d_ff, d_model), dtype=dtype),
    }


MOE_LOGICAL = {
    "wg": ("embed", None),
    "w1": ("experts", "embed", "expert_mlp"),
    "w2": ("experts", "expert_mlp", "embed"),
}


def apply_moe(params: Params, x, cfg: MoEConfig, compute_dtype=jnp.bfloat16):
    """GShard-style top-k routed MoE with capacity, dense-dispatch einsums.

    Experts (leading E dim of w1/w2) are sharded over the `ep` mesh axis;
    the dispatch/combine einsums below are exactly the contractions XLA
    turns into all_to_all over `ep` when tokens and experts live on
    different devices — expert parallelism without hand-written comms.
    Returns (output [B,S,D], aux_loss scalar).
    """
    cd = compute_dtype
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = max(1, int(cfg.capacity_factor * K * B * S / E))

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), params["wg"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # [B,S,E]
    gate_vals, gate_idx = jax.lax.top_k(probs, K)  # [B,S,K]
    # Renormalize the chosen gates.
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    # Load-balancing auxiliary loss (Switch-style): fraction of tokens per
    # expert × mean router prob per expert.
    me = jnp.mean(probs, axis=(0, 1))  # [E]
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(gate_idx[..., 0], E), axis=1) / S, axis=0
    )  # top-1 token fraction per expert
    aux_loss = E * jnp.sum(me * ce)

    # Position of each (token, k) within its expert's capacity buffer.
    # Positions are assigned over the WHOLE token stream (B*S*K flattened):
    # the dispatch einsum below sums over both b and s, so a slot (e, c)
    # must be unique across the entire batch, not per row.
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # [B,S,K,E]
    flat = onehot.reshape(B * S * K, E)
    pos = jnp.cumsum(flat, axis=0) - 1  # [B*S*K, E]
    pos = pos.reshape(B, S, K, E)
    in_cap = (pos < C) & (onehot > 0)
    # dispatch [B,S,E,C]: 1 where token (b,s) occupies slot c of expert e.
    disp = jnp.sum(
        jax.nn.one_hot(jnp.where(in_cap, pos, -1), C, dtype=cd)
        * onehot.astype(cd)[..., None],
        axis=2,
    )  # sum over K -> [B,S,E,C]
    gates_per_e = jnp.sum(
        gate_vals[..., None].astype(cd) * onehot.astype(cd), axis=2
    )  # [B,S,E]
    combine = disp * gates_per_e[..., None]  # weight by gate prob

    expert_in = jnp.einsum("bsec,bsd->ecd", disp, x.astype(cd))  # a2a over ep
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", expert_in, params["w1"].astype(cd)))
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["w2"].astype(cd))
    out = jnp.einsum("bsec,ecd->bsd", combine, expert_out)  # a2a back
    return out.astype(x.dtype), aux_loss
