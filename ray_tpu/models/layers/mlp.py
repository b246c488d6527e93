"""The two feed-forwards and the gated memory unit. Imports `core` alone
of this package."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.layers import core


def init_mlp(key, d_model, d_ff, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    return {
        "w1": core.init_dense(k1, (d_model, d_ff), dtype=dtype),
        "b1": jnp.zeros((d_ff,), dtype),
        "w2": core.init_dense(k2, (d_ff, d_model), dtype=dtype),
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


def apply_mlp(params: core.Params, x, compute_dtype=jnp.bfloat16, reduce=None):
    """reduce: as in apply_attention — w1/b1/w2 are the local `mlp` shard,
    the partial product is summed over `tp` and b2 added once, after."""
    w1, b1, w2, b2 = (params[k] for k in ("w1", "b1", "w2", "b2"))
    if reduce is None:
        out = _lean_mlp(x, w1, b1, w2, b2, compute_dtype)
    else:
        partial = _lean_mlp(x, w1, b1, w2, jnp.zeros_like(b2), compute_dtype)
        out = reduce(partial) + b2.astype(compute_dtype)
    return out.astype(x.dtype)


def init_gated_mlp(key, d_model, d_ff, dtype=jnp.float32):
    """A dense feed-forward of three matrices and no bias, named as a gated
    expert's (`init_moe`): `w_gate`, `w_up` [d, F], `w_down` [F, d]."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": core.init_dense(k1, (d_model, d_ff), dtype=dtype),
            "w_up": core.init_dense(k3, (d_model, d_ff), dtype=dtype),
            "w_down": core.init_dense(k2, (d_ff, d_model), dtype=dtype)}


GATED_MLP_LOGICAL = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
                     "w_down": ("mlp", "embed")}


def apply_gated_mlp(params: core.Params, x, *, compute_dtype=jnp.bfloat16,
                    three_pass: bool = False):
    """``(silu(x·W_gate) ∘ (x·W_up))·W_down``: the three products on the
    MXU in `compute_dtype`, the gate in float32. In one pass the two wide
    results and the hidden row are `compute_dtype`'s, as `_through_experts`
    holds an expert's; with `three_pass` (as in `apply_attention`) all three
    stay float32, so that the extra passes have something to add to and the
    last product sees the hidden row's own low part."""
    project = core.project(compute_dtype, three_pass)
    wide = jnp.float32 if three_pass else compute_dtype
    gate = project("bsd,df->bsf", x, params["w_gate"], wide)
    up = project("bsd,df->bsf", x, params["w_up"], wide)
    hidden = (jax.nn.silu(gate.astype(jnp.float32))
              * up.astype(jnp.float32)).astype(wide)
    return project("bsf,fd->bsd", hidden, params["w_down"], x.dtype)


# ------------------------------------------------------ gated memory unit
def init_gmu(key, d_model, inner, dtype=jnp.float32):
    """`w_in` [d, inner], `w_out` [inner, d], no bias."""
    k_in, k_out = jax.random.split(key)
    return {"w_in": core.init_dense(k_in, (d_model, inner), dtype=dtype),
            "w_out": core.init_dense(k_out, (inner, d_model), dtype=dtype)}


GMU_LOGICAL = {"w_in": ("embed", None), "w_out": (None, "embed")}


def apply_gmu(params: core.Params, x, memory, *, compute_dtype=jnp.bfloat16):
    """``(SiLU(x·W_in) ⊙ memory)·W_out`` (arXiv:2507.06607): `memory` [B, T,
    inner] an EARLIER layer's scan result (`apply_mamba1`'s second), which
    this layer gates with its own stream and does not recompute. Products
    on the MXU in `compute_dtype`, the gate float32."""
    project = core.project(compute_dtype, False)
    gate = project("btd,de->bte", x, params["w_in"], jnp.float32)
    return project("bte,ed->btd",
                   jax.nn.silu(gate) * memory.astype(jnp.float32),
                   params["w_out"], x.dtype)
