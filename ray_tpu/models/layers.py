"""Transformer building blocks, pure-JAX pytree style.

Every layer is a (init_fn, apply_fn) pair over plain dict pytrees; sharding
comes from logical-axis annotations resolved by ray_tpu.parallel.sharding.
Compute is bf16 by default with f32 params/accumulators (MXU-native mix).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import (
    gated_delta,
    grouped_matmul,
    kda,
    mamba_stages,
    mxu,
    selective_scan,
    sparse_attention,
    ssd,
    target,
)
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
    round 5, before this benchmark) and, with the lean MLP below, lets batch 16-24
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, scale, eps=1e-5):
    """RMSNorm over the last axis, float32 inside, with `layer_norm`'s lean
    VJP: the backward keeps (x, rstd) and recomputes x̂."""
    return _rms_norm_fwd(x, scale, eps)[0]


def _rms_norm_fwd(x, scale, eps=1e-5):
    x32 = x.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rstd * scale).astype(x.dtype), (x, rstd, scale)


def _rms_norm_bwd(eps, res, dy):
    x, rstd, scale = res
    xhat = x.astype(jnp.float32) * rstd
    dy32 = dy.astype(jnp.float32)
    dscale = jnp.sum(dy32 * xhat, axis=tuple(range(x.ndim - 1)))
    t = dy32 * scale
    dx = rstd * (t - xhat * jnp.mean(t * xhat, axis=-1, keepdims=True))
    return dx.astype(x.dtype), dscale.astype(scale.dtype)


rms_norm.defvjp(_rms_norm_fwd, _rms_norm_bwd)


def rms_norm_centred(x, w, eps=1e-5):
    """The zero-centred RMSNorm of the Qwen3-Next family: `rms_norm` at the
    scale ``1 + w``, the leaf `w` drawn at 0."""
    return rms_norm(x, 1.0 + w.astype(jnp.float32), eps)


def rope(x, theta: float = 10000.0, *, interleaved: bool = False,
         positions=None, sections=None):
    """Rotary positions on x [B, S, H, K], float32 inside: pair i of the K/2
    pairs of columns turned by pos · theta^(−2i/K). The pairing is the
    half-split (i, i + K/2) of the public `rotate_half` models, or,
    `interleaved`, the neighbours (2i, 2i + 1) of the models that read a
    head's columns as K/2 complex numbers (`rope_interleave`); either way a
    column stays where it was. All K columns turn: a model that rotates a
    PART of a head (latent attention's `_pe` columns) hands that part alone.

    `positions`: None, the token's index 0..S−1 (not for a sequence that
    `sp` splits); or ONE stream [B, S]; or, with `sections`, SEVERAL [n, B,
    S] (sectioned rotation, `mrope_section`): `sections` (n numbers that add
    to K/2) gives each stream its run of pairs, pair i turning by its OWN
    stream's position — the first ``sections[0]`` pairs by stream 0, the
    next ``sections[1]`` by stream 1, and so on."""
    seq, half = x.shape[1], x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if positions is None:
        angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq
        cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    else:
        pos = jnp.asarray(positions, jnp.float32)
        if sections is not None:
            if sum(sections) != half or len(sections) != pos.shape[0]:
                raise ValueError(
                    f"sections {tuple(sections)} for {pos.shape[0]} streams "
                    f"of positions and {half} pairs of columns")
            stream = jnp.repeat(jnp.arange(len(sections)),
                                jnp.asarray(sections),
                                total_repeat_length=half)
            # [n, B, S] -> [B, S, half]: pair i reads stream[i]
            pos = jnp.take(jnp.moveaxis(pos, 0, -1), stream, axis=-1)
        else:
            pos = pos[..., None]
        angle = pos * inv_freq                               # [B, S, half]
        cos, sin = jnp.cos(angle)[:, :, None, :], jnp.sin(angle)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    if interleaved:
        pairs = x32.reshape(*x.shape[:-1], half, 2)
        a, b = pairs[..., 0], pairs[..., 1]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape).astype(x.dtype)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


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
def init_attention(key, d_model, n_head, dtype=jnp.float32, *,
                   n_kv_head: Optional[int] = None,
                   head_dim: Optional[int] = None, out_gate: bool = False):
    """`n_kv_head` (default `n_head`): K and V heads, each read by
    `n_head // n_kv_head` query heads (query head i by KV head i // group).
    `head_dim` (default `d_model // n_head`): the q width `n_head ·
    head_dim` need not be `d_model`. `out_gate`: `wq` emits, beside a
    head's q, as many columns of an output gate (`apply_attention`)."""
    head_dim = head_dim or d_model // n_head
    kv = n_kv_head or n_head
    ks = jax.random.split(key, 4)
    q_out = head_dim * (2 if out_gate else 1)
    return {
        "wq": _init_dense(ks[0], (d_model, n_head, q_out), dtype=dtype),
        "wk": _init_dense(ks[1], (d_model, kv, head_dim), dtype=dtype),
        "wv": _init_dense(ks[2], (d_model, kv, head_dim), dtype=dtype),
        "wo": _init_dense(ks[3], (n_head, head_dim, d_model), dtype=dtype),
    }


ATTENTION_LOGICAL = {
    "wq": ("embed", "heads", "head_dim"),
    "wk": ("embed", "heads", "head_dim"),
    "wv": ("embed", "heads", "head_dim"),
    "wo": ("heads", "head_dim", "embed"),
}
# fewer KV heads than query heads: the few are held by every `tp` rank
GROUPED_ATTENTION_LOGICAL = dict(ATTENTION_LOGICAL,
                                 wk=("embed", "kv", "head_dim"),
                                 wv=("embed", "kv", "head_dim"))


def resolve_attention(attention: str, mesh=None) -> str:
    """A config's `attention` as `apply_attention`'s `impl`: "auto" is ring
    attention where the mesh splits the sequence, the Pallas kernel where
    the call runs on a TPU (`target.where`), the plain reference
    elsewhere."""
    if attention != "auto":
        return attention
    if mesh is not None and dict(mesh.shape).get("sp", 1) > 1:
        return "ring"
    return "flash" if target.where(mesh)[0] == "tpu" else "reference"


# `checkpoint_name` of the attention sub-layer's output, [B, S, d_model] as
# it is added to the residual stream (after `reduce`).
ATTENTION_OUT = "attention_out"
# `checkpoint_name` of a product whose forward value took three bf16 passes
# (`_project`): three passes to rebuild, so `remat` keeps it.
THREE_PASS_OUT = "three_pass_out"
# `checkpoint_name` of what a routed layer decides once a step: the router's
# float32 logits (six bf16 passes to rebuild), the top-k's choices, and the
# sort of the assignments (`order`, `inverse`, `sizes`): all of it integers
# and one `[tokens, n_experts]` table, and sorts and scatters to rebuild
# (`moe_route`, `_local_experts`; `routing_plan` gives the bytes).
ROUTING = "routing"


def _project(cd, three_pass: bool):
    """A layer's products on the MXU: ``(eq, x, w, out_dtype) -> result``,
    `mxu.einsum` with operands rounded to `cd`. With `three_pass` the result
    carries the name `THREE_PASS_OUT`, which says nothing without a
    checkpoint and under `remat` keeps the result where the backward pass
    reads it (the layer's other results — an out-projection's, which only
    the residual add reads — are no residual and cost nothing)."""
    product = functools.partial(mxu.einsum, cd=cd, three_pass=three_pass)
    if not three_pass:
        return product
    return lambda *args: checkpoint_name(product(*args), THREE_PASS_OUT)


def _flash_on(mesh, grouped: bool, **kw):
    """``(q, k, v) -> o``: the Pallas flash kernels (`kw`:
    `flash_attention`'s) as a layer calls them. A Mosaic kernel cannot be
    partitioned automatically (lowering it on sharded operands raises):
    under a mesh each device runs the kernel on its own batch and head
    shard. `grouped`: fewer KV heads than query heads, which are not split
    with the query heads (a model with them refuses a `tp` that would split
    either)."""
    from ray_tpu.ops.flash_attention import flash_attention

    attend = functools.partial(flash_attention, **kw)
    if mesh is None:
        return attend
    io_spec = sh.spec("batch", None, "heads", None)
    kv_spec = sh.spec("batch", None, "kv", None) if grouped else io_spec
    return jax.shard_map(
        attend, mesh=mesh, in_specs=(io_spec, kv_spec, kv_spec),
        out_specs=io_spec, check_vma=False)


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
    qk_fn=None,
    three_pass: bool = False,
    window: Optional[int] = None,
    out_gate: bool = False,
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

    qk_fn: what a model does to the projected q and k [B, S, H, K] before
    the kernel sees them (a norm, a rotation): (q, k) -> (q, k). It is
    handed the projections' float32 accumulators and its results are
    rounded to the compute dtype once, for the kernel.

    three_pass: the four projections' forward values to float32 accuracy
    (`mxu.einsum`), for a model whose later layers are discontinuous in them.

    window: causal attention over the last `window` keys alone (query i
    sees i − window < j ≤ i), for a model whose layers mix such windows with
    global attention: "flash" (a second bound on the kernels' tile schedule)
    and "reference"; the ring paths know no window and refuse one.

    out_gate: `wq` is `[D, H, 2·K]`, a head's K columns of q beside K of
    a gate; the attention's output is multiplied by ``sigmoid(gate)`` (in
    float32, under the scope `attn_gate`) ahead of `wo`.

    Grouped KV heads (`wk`, `wv` with fewer heads than `wq`): the flash
    kernels read each query head's KV head where it lies; every other path
    repeats K and V to the query heads first (plain XLA, off the TPU).
    """
    if window is not None and impl in ("ring", "ring_local"):
        raise ValueError("ring attention has no window: a layer with one "
                         "cannot run on a mesh that splits the sequence")
    cd = compute_dtype
    project = _project(cd, three_pass)
    # float32 out of the MXU's accumulator where something is still to be
    # done to q and k; the compute dtype's own result type otherwise
    qk_dtype = None if qk_fn is None else jnp.float32
    q = project("bsd,dhk->bshk", x, params["wq"], qk_dtype)
    if out_gate:
        q, gate = jnp.split(q, 2, axis=-1)
    k = project("bsd,dhk->bshk", x, params["wk"], qk_dtype)
    v = project("bsd,dhk->bshk", x, params["wv"], None)
    if qk_fn is not None:
        q, k = (t.astype(cd) for t in qk_fn(q, k))
    group = q.shape[2] // k.shape[2]
    if group > 1 and impl != "flash":
        k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    if impl == "ring":
        from ray_tpu.parallel.ring_attention import ring_attention

        o = ring_attention(q, k, v, None, causal=causal, seq_axis=sp_axis)
    elif impl == "ring_local":
        o = ring_attention_local(q, k, v, axis_name=sp_axis, causal=causal)
    elif impl == "flash":
        o = _flash_on(mesh, group > 1, causal=causal, window=window)(q, k, v)
    else:
        o = reference_attention(q, k, v, causal=causal, window=window)
    if out_gate:
        with jax.named_scope("attn_gate"):
            o = o.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))
    # into the residual stream's dtype straight from the accumulator
    out = project("bshk,hkd->bsd", o.astype(cd), params["wo"], x.dtype)
    if reduce is not None:
        out = reduce(out)
    return checkpoint_name(out, ATTENTION_OUT)


# ------------------------------------------------------- latent attention
@dataclasses.dataclass(frozen=True)
class LatentConfig:
    """Multi-head latent attention's widths (the DeepSeek-V2/V3 family's):
    q and k, v each come from a low-rank projection of the stream; a head's
    q and k are `nope_dim` columns of its own beside `rope_dim` rotated
    ones, and the ROTATED KEY COLUMNS ARE ONE HEAD'S WORTH, shared by all.
    `q_rank` None: q is ONE product of the stream, no low rank and no norm
    (Kimi Linear's `q_lora_rank: null`). `rotate` False: the `rope_dim`
    columns stay plain shared columns, no position on any (its
    `mla_use_nope`)."""
    n_head: int = 32
    q_rank: Optional[int] = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    rope_theta: float = 10000.0
    rope_interleaved: bool = True
    rotate: bool = True

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim


def init_latent_attention(key, d_model, cfg: LatentConfig,
                          dtype=jnp.float32):
    """`wq_a` [d, q_rank] and its norm's scale, `wq_b` [q_rank, H, nope +
    rope]; `wkv_a` [d, kv_rank + rope] (the latent and the shared rotated
    key columns from one product), the latent's norm, `wkv_b` [kv_rank, H,
    nope + v] (a head's k columns, then its v); `wo` [H, v, d]. No bias.
    Without a q rank the first three are ONE leaf `wq` [d, H, nope + rope]."""
    ks = jax.random.split(key, 5)
    H = cfg.n_head
    if cfg.q_rank is None:
        q = {"wq": _init_dense(ks[0], (d_model, H, cfg.qk_dim), dtype=dtype)}
    else:
        q = {"wq_a": _init_dense(ks[0], (d_model, cfg.q_rank), dtype=dtype),
             "q_norm": jnp.ones((cfg.q_rank,), dtype),
             "wq_b": _init_dense(ks[1], (cfg.q_rank, H, cfg.qk_dim),
                                 dtype=dtype)}
    return {
        **q,
        "wkv_a": _init_dense(ks[2], (d_model, cfg.kv_rank + cfg.rope_dim),
                             dtype=dtype),
        "kv_norm": jnp.ones((cfg.kv_rank,), dtype),
        "wkv_b": _init_dense(ks[3], (cfg.kv_rank, H,
                                     cfg.nope_dim + cfg.v_dim), dtype=dtype),
        "wo": _init_dense(ks[4], (H, cfg.v_dim, d_model), dtype=dtype),
    }


# the two low-rank matrices and the shared key columns are whole on every
# `tp` rank; the per-head ones would split over heads (not taken: a model
# with the layer refuses `tp` > 1)
LATENT_ATTENTION_LOGICAL = {
    "wq_a": ("embed", None), "q_norm": (None,),
    "wq_b": (None, "heads", "head_dim"),
    "wkv_a": ("embed", None), "kv_norm": (None,),
    "wkv_b": (None, "heads", "head_dim"),
    "wo": ("heads", "head_dim", "embed"),
}
# the same without a q rank (`LatentConfig.q_rank` None)
LATENT_FULL_Q_LOGICAL = dict(
    {k: v for k, v in LATENT_ATTENTION_LOGICAL.items()
     if k not in ("wq_a", "q_norm", "wq_b")},
    wq=("embed", "heads", "head_dim"))


def apply_latent_attention(params: Params, x, cfg: LatentConfig, *,
                           eps: float = 1e-6, impl: str = "reference",
                           compute_dtype=jnp.bfloat16, mesh=None):
    """x [B, S, d] -> [B, S, d], causal. ``c_q = RMSNorm(x·Wq_a)``,
    ``[q_nope | q_pe] = c_q·Wq_b`` a head; ``[c_kv | k_pe] = x·Wkv_a``,
    ``c_kv = RMSNorm(c_kv)``, ``[k_nope | v] = c_kv·Wkv_b`` a head; q_pe and
    the ONE k_pe rotated (`rope`, the `_pe` columns alone; not with
    `cfg.rotate` False); softmax of
    ``(q_nope·k_nope + q_pe·k_pe) / √(nope + rope)`` over v; ``·W_o``.
    Without a q rank ``q = x·W_q`` a head, one product. The
    five products on the MXU in `compute_dtype`, norms and rotation in
    float32 from the accumulators, all under the scope `latent_proj` but
    the last.

    impl "flash": the Pallas kernels with the shared columns an operand of
    their own (`flash_attention(k_shared=)`: q `[B·H, S, nope + rope]`, k
    `[B·H, S, nope]`, k_pe `[B, S, rope]`, v `[B·H, S, v]`, nothing padded
    or repeated in HBM); "reference": k built whole, plain softmax. mesh: as
    in `apply_attention`."""
    cd, nope = compute_dtype, cfg.nope_dim
    project = _project(cd, False)
    turn = functools.partial(rope, theta=cfg.rope_theta,
                             interleaved=cfg.rope_interleaved) \
        if cfg.rotate else (lambda t: t)
    with jax.named_scope("latent_proj"):
        if cfg.q_rank is None:
            q = project("bsd,dhk->bshk", x, params["wq"], jnp.float32)
        else:
            c_q = rms_norm(project("bsd,dr->bsr", x, params["wq_a"],
                                   jnp.float32), params["q_norm"], eps)
            q = project("bsr,rhk->bshk", c_q, params["wq_b"], jnp.float32)
        q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])],
                            axis=-1).astype(cd)
        ckv = project("bsd,dr->bsr", x, params["wkv_a"], jnp.float32)
        c_kv = rms_norm(ckv[..., :cfg.kv_rank], params["kv_norm"], eps)
        k_pe = turn(ckv[:, :, None, cfg.kv_rank:])[:, :, 0].astype(cd)
        kv = project("bsr,rhk->bshk", c_kv, params["wkv_b"], None)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        if impl != "flash":
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_pe[:, :, None], (*k_nope.shape[:3], cfg.rope_dim))],
                axis=-1)
    if impl == "flash":
        from ray_tpu.ops.flash_attention import flash_attention

        def attend(q, k_nope, v, k_pe):
            return flash_attention(q, k_nope, v, k_shared=k_pe, causal=True)

        if mesh is not None:
            io_spec = sh.spec("batch", None, "heads", None)
            attend = jax.shard_map(
                attend, mesh=mesh, in_specs=(io_spec, io_spec, io_spec,
                                             sh.spec("batch", None, None)),
                out_specs=io_spec, check_vma=False)
        o = attend(q, k_nope, v, k_pe)
    elif impl == "reference":
        o = reference_attention(q, k, v, causal=True)
    else:
        raise ValueError(f"latent attention has no {impl!r} path: the ring "
                         f"paths know one head size")
    out = project("bshk,hkd->bsd", o.astype(cd), params["wo"], x.dtype)
    return checkpoint_name(out, ATTENTION_OUT)


# --------------------------------------------------- differential attention
@dataclasses.dataclass(frozen=True)
class DiffAttnConfig:
    """Differential attention's heads (arXiv:2410.05258, as the SambaY
    family wires it): `n_head` query heads and `n_kv_head` K and V heads of
    `head_dim`, read two by two — pair j's ``q¹, q²`` are query heads 2j and
    2j + 1, KV pair m's ``k¹, k²`` K heads 2m and 2m + 1, and its ``V`` the V
    heads 2m and 2m + 1 side by side, `2·head_dim` wide; query pair j reads
    KV pair ``j // (pairs // kv_pairs)``."""
    n_head: int = 40
    n_kv_head: int = 20
    head_dim: int = 64

    @property
    def pairs(self) -> int:
        return self.n_head // 2

    @property
    def kv_pairs(self) -> int:
        return self.n_kv_head // 2

    @property
    def q_dim(self) -> int:
        return self.n_head * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_head * self.head_dim


def lambda_init(depth: int) -> float:
    """λ's constant part at the layer of PUBLISHED index `depth`."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def init_diff_attention(key, d_model, cfg: DiffAttnConfig, dtype=jnp.float32,
                        *, own_kv: bool = True):
    """The layer's leaves: `w_qkv` [d, q | k | v] with `b_qkv` — or, for a
    layer that reads ANOTHER layer's K and V (`own_kv` false), `w_q` [d, q]
    with `b_q` alone — `w_o` [q, d] with `b_o`, the four λ vectors of
    `head_dim` (normal at 0.1) and the sub-norm's scale `subln`
    [2·head_dim], one for all pairs."""
    k_in, k_out, *k_lam = jax.random.split(key, 6)
    width = cfg.q_dim + (2 * cfg.kv_dim if own_kv else 0)
    name = "qkv" if own_kv else "q"
    lam = {f"lambda_{n}": _init_dense(k, (cfg.head_dim,), 0.1, dtype)
           for n, k in zip(("q1", "k1", "q2", "k2"), k_lam)}
    return {
        f"w_{name}": _init_dense(k_in, (d_model, width), dtype=dtype),
        f"b_{name}": jnp.zeros((width,), dtype),
        "w_o": _init_dense(k_out, (cfg.q_dim, d_model), dtype=dtype),
        "b_o": jnp.zeros((d_model,), dtype),
        **lam, "subln": jnp.ones((2 * cfg.head_dim,), dtype),
    }


# every leaf whole on every `tp` rank (10 KV pairs divide by neither 4 nor
# 8): a model with the layer refuses `tp` > 1
_DIFF_REST = {"w_o": (None, "embed"), "b_o": ("embed",), "subln": (None,),
              **{f"lambda_{n}": (None,) for n in ("q1", "k1", "q2", "k2")}}
DIFF_ATTENTION_LOGICAL = dict(_DIFF_REST, w_qkv=("embed", None),
                              b_qkv=(None,))
DIFF_CROSS_LOGICAL = dict(_DIFF_REST, w_q=("embed", None), b_q=(None,))


def apply_diff_attention(params: Params, x, cfg: DiffAttnConfig, *,
                         depth: int, window: Optional[int] = None, kv=None,
                         impl: str = "reference", compute_dtype=jnp.bfloat16,
                         eps: float = 1e-5, mesh=None):
    """x [B, S, d] -> (out [B, S, d], (k [B, S, KV, K], v [B, S, KV/2, 2K])
    as the kernels read them, for a later layer's `kv`).

    ``a¹_j = softmax(q¹_j k¹ᵀ / √K)·V``, ``a²_j`` likewise from ``q², k²`` —
    TWO attention calls at q/k `head_dim`, v `2·head_dim` (the published
    code's four at `head_dim` take each score twice), under the scope
    `diff_flash`; then, under `diff_combine`, ``λ = exp(λ_q1·λ_k1) −
    exp(λ_q2·λ_k2) + λ_init(depth)``, ``o_j = RMSNorm(a¹_j − λ·a²_j)·subln ·
    (1 − λ_init)`` over a pair's `2·head_dim` columns; ``o·W_o + b_o``.

    kv: another layer's ``(k, v)`` — the layer then projects q alone (`w_q`)
    and hands the same pair on. window: as `apply_attention`'s. impl:
    "flash" or "reference"; the ring paths know one head width and no
    window and are refused. Causal. Projections on the MXU in
    `compute_dtype`, biases added to their float32 accumulators; the
    softmaxes' statistics, λ and the sub-norm float32."""
    if impl not in ("flash", "reference"):
        raise ValueError(f"differential attention has no {impl!r} path")
    B, S, _ = x.shape
    cd, K, f32 = compute_dtype, cfg.head_dim, jnp.float32
    project = _project(cd, False)
    name = "qkv" if kv is None else "q"
    q = (project("bsd,de->bse", x, params["w_" + name], f32)
         + params["b_" + name].astype(f32)).astype(cd)
    if kv is None:
        q, k, v = jnp.split(q, [cfg.q_dim, cfg.q_dim + cfg.kv_dim], axis=-1)
        kv = (k.reshape(B, S, cfg.n_kv_head, K),
              v.reshape(B, S, cfg.kv_pairs, 2 * K))
    k, v = kv
    q = q.reshape(B, S, cfg.pairs, 2, K)
    k = k.reshape(B, S, cfg.kv_pairs, 2, K)
    group = cfg.pairs // cfg.kv_pairs
    with jax.named_scope("diff_flash"):
        if impl == "flash":
            attend = _flash_on(mesh, group > 1, causal=True, window=window)
        else:
            def attend(q_, k_, v_):
                k_, v_ = (jnp.repeat(t, group, axis=2) for t in (k_, v_))
                return reference_attention(q_, k_, v_, causal=True,
                                           window=window)
        a1, a2 = (attend(q[:, :, :, i], k[:, :, :, i], v) for i in (0, 1))
    with jax.named_scope("diff_combine"):
        lam0 = lambda_init(depth)

        def dot(a, b):
            return jnp.sum(params[a].astype(f32) * params[b].astype(f32))

        lam = (jnp.exp(dot("lambda_q1", "lambda_k1"))
               - jnp.exp(dot("lambda_q2", "lambda_k2")) + lam0)
        o = rms_norm(a1.astype(f32) - lam * a2.astype(f32),
                     params["subln"].astype(f32), eps) * (1.0 - lam0)
    out = project("bse,ed->bsd", o.reshape(B, S, cfg.q_dim).astype(cd),
                  params["w_o"], f32) + params["b_o"].astype(f32)
    return checkpoint_name(out.astype(x.dtype), ATTENTION_OUT), kv


# --------------------------------------------------- learned sparse attention
@dataclasses.dataclass(frozen=True)
class SparseConfig:
    """Softmax attention over the keys a learned indexer keeps
    (`ops.sparse_attention`): the main attention's heads, and the indexer's
    (`index_heads` query heads of `index_dim` on ONE key head; `topk` keys
    a query)."""
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    index_heads: int = 16
    index_dim: int = 64
    topk: int = 2048
    rope_theta: float = 1e7
    # pairs of a main head's head_dim / 2 that each stream of positions
    # turns (`rope`'s `sections`); the indexer's columns turn by stream 0
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    eps: float = 1e-6


def init_sparse_attention(key, d_model, cfg: SparseConfig, dtype=jnp.float32):
    """`init_attention`'s four matrices with per-head norm scales for q and
    k, and under `indexer` the indexer's own leaves: `w_q` [d, J, E], `w_k`
    [d, E] behind a LayerNorm (`k_scale`, `k_bias`), `w_w` [d, J]."""
    k_main, kq, kk, kw = jax.random.split(key, 4)
    J, E = cfg.index_heads, cfg.index_dim
    return dict(
        init_attention(k_main, d_model, cfg.n_head, dtype,
                       n_kv_head=cfg.n_kv_head, head_dim=cfg.head_dim),
        q_norm=jnp.ones((cfg.head_dim,), dtype),
        k_norm=jnp.ones((cfg.head_dim,), dtype),
        indexer={"w_q": _init_dense(kq, (d_model, J, E), dtype=dtype),
                 "w_k": _init_dense(kk, (d_model, E), dtype=dtype),
                 "k_scale": jnp.ones((E,), dtype),
                 "k_bias": jnp.zeros((E,), dtype),
                 "w_w": _init_dense(kw, (d_model, J), dtype=dtype)})


SPARSE_ATTENTION_LOGICAL = dict(
    GROUPED_ATTENTION_LOGICAL, q_norm=("head_dim",), k_norm=("head_dim",),
    indexer={"w_q": ("embed", None, None), "w_k": ("embed", None),
             "k_scale": (None,), "k_bias": (None,), "w_w": ("embed", None)})


def _sparse_core(q, k, v, qi, ki, w, *, topk: int, kernel: bool,
                 interpret: bool, compute_dtype):
    """Scores, selection, attention over the selection and the indexer's
    loss for the batch rows at hand: -> (o [B, S, H, D], the indexer's KL
    summed over each row's tokens [B], the pairs each row keeps [B])."""
    with jax.named_scope("indexer_scores"):
        scores = sparse_attention.index_scores(
            qi, ki, w, kernel=kernel, interpret=interpret,
            backward_dtype=compute_dtype)
    with jax.named_scope("select"):
        keep = sparse_attention.select(scores, topk, kernel=kernel,
                                       interpret=interpret)
    with jax.named_scope("sparse_attn"):
        o, lse = sparse_attention.sparse_attention(
            q, k, v, keep, kernel=kernel, interpret=interpret)
    with jax.named_scope("indexer_loss"):
        kl = sparse_attention.indexer_loss(q, k, lse, keep, scores,
                                           kernel=kernel, interpret=interpret)
        kept = jnp.sum(keep, axis=(1, 2), dtype=jnp.int32)
    return o, kl, kept


def apply_sparse_attention(params: Params, x, cfg: SparseConfig, *,
                           positions=None, impl: str = "reference",
                           compute_dtype=jnp.bfloat16, mesh=None,
                           interpret: bool = False):
    """x [B, S, d], the layer's normed input -> (out [B, S, d], (the
    indexer's loss: its KL summed over the tokens, how many query-key pairs
    the layer keeps)).

    Main attention: q, k, v without bias, a per-head RMSNorm on q and k, the
    sectioned rotation (`rope` with `cfg.mrope_section`; `positions` [3, B,
    S], by default the token's index in every stream), grouped KV heads.
    The indexer reads ``stop_gradient(x)``: the trunk's leaves get no
    gradient from its loss, and its own none from anything else (the kept
    set is not differentiated, the attention it is compared with is behind
    a stop_gradient). Its three projections are float32 at the highest
    precision and its scores float32 operands in three bf16 passes
    (`sparse_attention.SCORE_PASSES`): they decide a discontinuous choice,
    as a router's; the scores' backward takes `compute_dtype`'s one pass.

    impl: "flash" (the flash kernels with the kept set as an operand and
    the KL's two kernels) or "reference" (their plain forms: a score a HEAD
    and pair, for small shapes). The ring paths have no kept set."""
    if impl not in ("flash", "reference"):
        raise ValueError(f"sparse attention runs as 'flash' or 'reference', "
                         f"not {impl!r}: a kept set is a whole sequence's")
    cd, f32 = compute_dtype, jnp.float32
    project = _project(cd, False)
    sections = cfg.mrope_section if positions is not None else None

    def turn(t, scale):
        return rope(rms_norm(t, scale.astype(f32), cfg.eps), cfg.rope_theta,
                    positions=positions, sections=sections).astype(cd)

    q = turn(project("bsd,dhk->bshk", x, params["wq"], f32), params["q_norm"])
    k = turn(project("bsd,dhk->bshk", x, params["wk"], f32), params["k_norm"])
    v = project("bsd,dhk->bshk", x, params["wv"], None)

    with jax.named_scope("indexer"):
        ix = params["indexer"]
        a = jax.lax.stop_gradient(x).astype(f32)
        high = functools.partial(jnp.einsum,
                                 precision=jax.lax.Precision.HIGHEST)
        first = None if positions is None else positions[0]
        qi = rope(high("bsd,dje->bsje", a, ix["w_q"].astype(f32)),
                  cfg.rope_theta, positions=first)
        ki = layer_norm(high("bsd,de->bse", a, ix["w_k"].astype(f32)),
                        ix["k_scale"].astype(f32), ix["k_bias"].astype(f32),
                        cfg.eps)
        ki = rope(ki[:, :, None, :], cfg.rope_theta, positions=first)[:, :, 0]
        w = high("bsd,dj->bsj", a, ix["w_w"].astype(f32)) * (
            cfg.index_heads ** -0.5 * cfg.index_dim ** -0.5)

    core = functools.partial(_sparse_core, topk=cfg.topk,
                             kernel=impl == "flash", interpret=interpret,
                             compute_dtype=cd)
    if mesh is not None:
        # per-device code, a device its own batch rows: a Mosaic kernel
        # cannot be partitioned automatically, and nothing here crosses rows
        rows = [sh.spec("batch", *([None] * (t.ndim - 1)))
                for t in (q, k, v, qi, ki, w)]
        out = (rows[0], sh.spec("batch"), sh.spec("batch"))
        core = jax.shard_map(core, mesh=mesh, in_specs=tuple(rows),
                             out_specs=out, check_vma=False)
    o, kl, kept = core(q, k, v, qi, ki, w)
    out = project("bshk,hkd->bsd", o.astype(cd), params["wo"], x.dtype)
    return checkpoint_name(out, ATTENTION_OUT), (jnp.sum(kl), jnp.sum(kept))


def remat(body):
    """`jax.checkpoint` for a layer loop's body that keeps, besides the
    block's input, what is dear to recompute — and of that only what the
    backward pass reads:

    * the flash forward kernel's `o` and `lse` (the backward kernels'
      residuals: without them the kernel runs twice a step);
    * the attention sub-layer's output, where the block goes on from it
      (without it the recompute needs the `wo` product and, under `tp`, its
      exchange, only to rebuild the second norm's input);
    * the result of a product whose forward value was brought to float32
      accuracy by three bf16 passes (`_project` with `three_pass`: a mixer's
      in-projection, a feed-forward's first products, q, k and v as they
      are rounded for the kernel): rebuilding it costs three passes where
      the backward's own products cost one, so a kept byte saves three
      times what it saves behind a single-pass product;
    * a routed layer's routing (`ROUTING`): the router's float32 logits,
      a product of six passes, the top-k's chosen experts, and the sorted
      positions of the assignments, their inverse and the groups' sizes —
      a `[tokens, n_experts]` table and integers, whose rebuilding is a
      full sort of every token's scores, an argsort and two scatters
      (`routing_plan`: 4–36 MB a layer where the cells' layers weigh GBs);
    * a sparse attention layer's kept set (`sparse_attention.KEEP_NAME`):
      int8 ``[B, S, S]``, a quarter of ONE float32 score array, whose
      rebuilding is the selection's 46 passes over the indexer's scores;
      and the rows its KL's backward kernel reads
      (`sparse_attention.KL_ROWS_NAME`: each query's log-sum-exp of its kept
      indexer scores and the sum of its mean attention, float32 ``[B, 2,
      S]``, 128 KB a layer at 16,384), without which the recompute runs the
      KL's forward kernel — every head's probabilities over the causal
      area — a second time for two numbers a row.

    Everything else in the block — norms, single-pass q/k/v products, the
    MLP's first product, convs and gates, the scan, the routed experts, the
    router's softmax and statistics — is recomputed. A block that names
    none of these (ring or reference attention has no `o`/`lse`; a model in
    one pass names no product; a dense block routes nothing) keeps what it
    does name. What it costs a layer, from shapes alone:
    `gpt2.remat_saved_plan`, `nemotron_h.remat_saved_plan`, `routing_plan`."""
    from ray_tpu.ops.flash_attention import RESIDUAL_NAMES

    return jax.checkpoint(
        body, policy=jax.checkpoint_policies.save_only_these_names(
            *RESIDUAL_NAMES, ATTENTION_OUT, THREE_PASS_OUT, ROUTING,
            sparse_attention.KEEP_NAME, sparse_attention.KL_ROWS_NAME))


# ------------------------------------------------- depthwise causal conv
def causal_taps(x, w):
    """x [B, T, C] float32, taps w [K, C] -> [B, T, C]: ``Σ_i w_i ∘
    x_{t−(K−1)+i}``, x zero before a sequence's first position — a depthwise
    causal convolution as K shifted products, each sequence of the batch on
    its own. No bias, no activation: the caller's."""
    T, K = x.shape[1], w.shape[0]
    w = w.astype(jnp.float32)
    padded = jnp.pad(x, [(0, 0), (K - 1, 0), (0, 0)])
    return sum(padded[:, i:i + T] * w[i] for i in range(K))


def init_short_conv(key, d_model, taps: int = 3, dtype=jnp.float32):
    """The gated short convolution's leaves: `w_in` [d, b | c | u], the
    depthwise taps `conv_w` [taps, d] (uniform ±taps^-½, a framework's
    default for a depthwise conv, as `init_mamba`'s), `w_out` [d, d]. No
    bias."""
    k_in, k_conv, k_out = jax.random.split(key, 3)
    bound = taps ** -0.5
    return {
        "w_in": _init_dense(k_in, (d_model, 3 * d_model), dtype=dtype),
        "conv_w": jax.random.uniform(
            k_conv, (taps, d_model), minval=-bound,
            maxval=bound).astype(dtype),
        "w_out": _init_dense(k_out, (d_model, d_model), dtype=dtype),
    }


# every leaf whole on every `tp` rank: a model with the operator refuses
# `tp` > 1 (the gates pair channel i of three streams: columns over `tp`
# is the split, not taken yet)
SHORT_CONV_LOGICAL = {"w_in": ("embed", None), "conv_w": (None, None),
                      "w_out": (None, "embed")}


def apply_short_conv(params: Params, x, *, compute_dtype=jnp.bfloat16,
                     three_pass: bool = False):
    """x [B, T, d] -> [B, T, d], the gated short convolution of the LFM2
    family: ``[b | c | u] = x·W_in``; ``v = taps(b ∘ u)`` (`causal_taps`:
    depthwise, causal, no bias, no activation); ``(c ∘ v)·W_out``. The two
    products on the MXU in `compute_dtype` (`three_pass` as in
    `apply_attention`), gates and taps in float32 from the in-projection's
    accumulator, under the scope `gate_conv`: plain JAX, no kernel."""
    project = _project(compute_dtype, three_pass)
    bcu = project("btd,de->bte", x, params["w_in"], jnp.float32)
    with jax.named_scope("gate_conv"):
        b, c, u = jnp.split(bcu, 3, axis=-1)
        y = c * causal_taps(b * u, params["conv_w"])
    return project("bte,ed->btd", y, params["w_out"], x.dtype)


# ------------------------------------------------------------ Mamba-2 mixer
@dataclasses.dataclass(frozen=True)
class MambaConfig:
    n_heads: int = 64
    head_dim: int = 64
    n_groups: int = 8         # B and C are shared by n_heads // n_groups heads
    d_state: int = 128
    d_conv: int = 4
    chunk: int = 128
    # Δ at initialisation: log-uniform in [dt_min, dt_max], floored
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    @property
    def inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:        # x, B and C go through the conv
        return self.inner + 2 * self.n_groups * self.d_state

    @property
    def in_proj(self) -> int:         # [z | xBC | dt]
        return self.inner + self.conv_dim + self.n_heads


def _init_dt_bias(key, n: int, cfg, dtype):
    """`dt_bias` [n] = softplus⁻¹(Δ₀) with Δ₀ log-uniform in [`cfg.dt_min`,
    `cfg.dt_max`], floored."""
    dt0 = jnp.maximum(jnp.exp(
        jax.random.uniform(key, (n,))
        * (jnp.log(cfg.dt_max) - jnp.log(cfg.dt_min)) + jnp.log(cfg.dt_min)),
        cfg.dt_floor)
    return (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype)


def _init_decays(k_dt, k_a, heads: int, cfg, dtype):
    """A recurrent mixer's per-head decay leaves: `dt_bias`
    (`_init_dt_bias`) and `A_log` = log U[1, 16]."""
    return {"dt_bias": _init_dt_bias(k_dt, heads, cfg, dtype),
            "A_log": jnp.log(jax.random.uniform(
                k_a, (heads,), minval=1.0, maxval=16.0)).astype(dtype)}


def init_mamba(key, d_model, cfg: MambaConfig, dtype=jnp.float32):
    """Mamba-2's leaves: `w_in` [d, z | xBC | dt], the depthwise conv
    (`conv_w` [d_conv, conv_dim], uniform ±d_conv^-½ as a framework's
    default; `conv_b`), a head's `dt_bias` = softplus⁻¹(Δ₀), `A_log` =
    log U[1, 16], `D` = 1, the gated norm's scale, `w_out`."""
    k_in, k_out, k_conv, k_dt, k_a = jax.random.split(key, 5)
    bound = cfg.d_conv ** -0.5
    return {
        "w_in": _init_dense(k_in, (d_model, cfg.in_proj), dtype=dtype),
        "conv_w": jax.random.uniform(
            k_conv, (cfg.d_conv, cfg.conv_dim), minval=-bound,
            maxval=bound).astype(dtype),
        "conv_b": jnp.zeros((cfg.conv_dim,), dtype),
        **_init_decays(k_dt, k_a, cfg.n_heads, cfg, dtype),
        "D": jnp.ones((cfg.n_heads,), dtype),
        "norm": jnp.ones((cfg.inner,), dtype),
        "w_out": _init_dense(k_out, (cfg.inner, d_model), dtype=dtype),
    }


# every leaf whole on every `tp` rank: a model with a mixer refuses `tp` > 1
MAMBA_LOGICAL = {
    "w_in": ("embed", None), "conv_w": (None, None), "conv_b": (None,),
    "dt_bias": (None,), "A_log": (None,), "D": (None,), "norm": (None,),
    "w_out": (None, "embed"),
}


def apply_mamba(params: Params, u, cfg: MambaConfig, *,
                compute_dtype=jnp.bfloat16, eps: float = 1e-5,
                three_pass: bool = False, mesh=None):
    """u [B, T, d] -> [B, T, d]: ``[z | xBC | dt] = u·W_in``; ``xBC ←
    SiLU(causal depthwise conv(xBC) + b)``, split into x [T, H, P] and B, C
    [T, G, N]; ``Δ = softplus(dt + dt_bias)``, ``A = −exp(A_log)``; the
    state-space scan (`ops.ssd`); ``y ← RMSNorm_groups(y ⊙ SiLU(z))`` (a norm
    over each of the G groups' share of the inner width, one learned scale);
    ``·W_out``. Conv, softplus, decays and the norm in float32; the two
    projections and the scan's products on the MXU in `compute_dtype`.
    `three_pass` as in `apply_attention`; the one model with a mixer
    (`nemotron_h`) always sets it, and off is the single-pass control its
    tests and chip probe compare with. mesh: as in `apply_attention` — on
    one TPU whose tiles divide the shapes the scan is Pallas kernels
    (`ssd._use_kernel`) and so are the conv and the gate-norm stage
    (`ops.mamba_stages`, each one pass over HBM a direction), elsewhere
    plain JAX."""
    B, T, _ = u.shape
    H, P, G, N = cfg.n_heads, cfg.head_dim, cfg.n_groups, cfg.d_state
    project = _project(compute_dtype, three_pass)
    zxbcdt = project("btd,de->bte", u, params["w_in"], jnp.float32)
    dt = zxbcdt[..., cfg.inner + cfg.conv_dim:]
    # both stages read their columns of `zxbcdt` in place (z the first
    # `inner`, xBC the `conv_dim` after them)
    with jax.named_scope("conv"):
        xbc = mamba_stages.conv_silu(zxbcdt, params["conv_w"],
                                     params["conv_b"], start=cfg.inner,
                                     mesh=mesh)
    x, b_in, c_out = jnp.split(xbc, [cfg.inner, cfg.inner + G * N], axis=-1)
    y = ssd.ssd(
        x.reshape(B, T, H, P),
        jax.nn.softplus(dt + params["dt_bias"].astype(jnp.float32)),
        -jnp.exp(params["A_log"].astype(jnp.float32)),
        b_in.reshape(B, T, G, N), c_out.reshape(B, T, G, N), params["D"],
        chunk=cfg.chunk, compute_dtype=compute_dtype, three_pass=three_pass,
        mesh=mesh)
    with jax.named_scope("gate_norm"):
        y = mamba_stages.gate_norm(y.reshape(B, T, cfg.inner), zxbcdt,
                                   params["norm"], groups=G, eps=eps,
                                   mesh=mesh)
    return project("bte,ed->btd", y, params["w_out"], u.dtype)


# ------------------------------------------------------------ Mamba-1 mixer
@dataclasses.dataclass(frozen=True)
class Mamba1Config:
    """Mamba-1's widths: `inner` channels (``expand · d_model``), a state of
    `d_state` a channel, Δ from a projection of rank `dt_rank`; `chunk` and
    `block` are the scan's walk (`ops.selective_scan`)."""
    inner: int = 5120
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    chunk: int = 32
    block: int = 512
    # Δ at initialisation, as `MambaConfig`'s
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4


def init_mamba1(key, d_model, cfg: Mamba1Config, dtype=jnp.float32):
    """Mamba-1's leaves and customary draws: `w_in` [d, s | z]; the
    depthwise conv (`conv_w` [d_conv, inner] uniform ±d_conv^-½, `conv_b`);
    `w_x` [inner, dt_rank | B | C]; `w_dt` [dt_rank, inner] uniform
    ±dt_rank^-½ with a channel's `dt_bias` (`_init_dt_bias`, as
    `init_mamba`'s); `A_log` = log(1 … d_state) a channel; `D` = 1;
    `w_out`."""
    k_in, k_x, k_dt, k_out, k_conv, k_b = jax.random.split(key, 6)
    bound = cfg.d_conv ** -0.5
    rank = cfg.dt_rank ** -0.5
    return {
        "w_in": _init_dense(k_in, (d_model, 2 * cfg.inner), dtype=dtype),
        "conv_w": jax.random.uniform(
            k_conv, (cfg.d_conv, cfg.inner), minval=-bound,
            maxval=bound).astype(dtype),
        "conv_b": jnp.zeros((cfg.inner,), dtype),
        "w_x": _init_dense(k_x, (cfg.inner, cfg.dt_rank + 2 * cfg.d_state),
                           dtype=dtype),
        "w_dt": jax.random.uniform(
            k_dt, (cfg.dt_rank, cfg.inner), minval=-rank,
            maxval=rank).astype(dtype),
        "dt_bias": _init_dt_bias(k_b, cfg.inner, cfg, dtype),
        "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
            1, cfg.d_state + 1, dtype=jnp.float32)),
            (cfg.inner, cfg.d_state)).astype(dtype),
        "D": jnp.ones((cfg.inner,), dtype),
        "w_out": _init_dense(k_out, (cfg.inner, d_model), dtype=dtype),
    }


# every leaf whole on every `tp` rank: a model with a mixer refuses `tp` > 1
MAMBA1_LOGICAL = {
    "w_in": ("embed", None), "conv_w": (None, None), "conv_b": (None,),
    "w_x": (None, None), "w_dt": (None, None), "dt_bias": (None,),
    "A_log": (None, None), "D": (None,), "w_out": (None, "embed"),
}


def apply_mamba1(params: Params, u, cfg: Mamba1Config, *,
                 compute_dtype=jnp.bfloat16, mesh=None):
    """u [B, T, d] -> (out [B, T, d], y [B, T, inner] float32): ``[s | z] =
    u·W_in``; ``s ← SiLU(causal depthwise conv(s) + b)`` (`ops.mamba_stages.
    conv_silu`, the Mamba-2 mixer's stage, scope `conv`); ``[r | B | C] =
    s·W_x``; ``Δ = softplus(r·W_dt + dt_bias)``, ``A = −exp(A_log)``; the
    selective scan (`ops.selective_scan`, scope `selective_scan`); out ``=
    (y ⊙ SiLU(z))·W_out``. `y`, the scan's result BEFORE the gate, is what a
    gated memory unit of a later layer reads (`apply_gmu`). The four
    projections on the MXU in `compute_dtype`; conv, Δ, decays, state,
    readout and gate float32. mesh: where the conv stage and the scan run
    (each its kernels on one TPU where its tiles divide the shapes, as in
    `apply_mamba`; its plain form everywhere else)."""
    project = _project(compute_dtype, False)
    f32, N = jnp.float32, cfg.d_state
    sz = project("btd,de->bte", u, params["w_in"], f32)
    with jax.named_scope("conv"):
        s = mamba_stages.conv_silu(sz, params["conv_w"], params["conv_b"],
                                   start=0, mesh=mesh)
    rbc = project("bte,ef->btf", s, params["w_x"], f32)
    r, b_in, c_out = jnp.split(rbc, [cfg.dt_rank, cfg.dt_rank + N], axis=-1)
    dt = project("btr,re->bte", r, params["w_dt"], f32)
    y = selective_scan.selective_scan(
        s, dt, -jnp.exp(params["A_log"].astype(f32)), b_in, c_out,
        params["D"], params["dt_bias"], chunk=cfg.chunk, block=cfg.block,
        mesh=mesh)
    gated = y * jax.nn.silu(sz[..., cfg.inner:])
    return project("bte,ed->btd", gated, params["w_out"], u.dtype), y


# ------------------------------------------------------ Gated DeltaNet
@dataclasses.dataclass(frozen=True)
class DeltaConfig:
    """The Gated-DeltaNet layer's widths (the Qwen3-Next family's): q and k
    belong to `n_k_heads` key heads, v, the gate z, the decay and the write
    strength to `n_v_heads` value heads (value head h reads key head
    ``h // (n_v_heads // n_k_heads)``); q, k and v go through a depthwise
    causal conv of `d_conv` taps."""
    n_k_heads: int = 16
    n_v_heads: int = 32
    k_dim: int = 128
    v_dim: int = 128
    d_conv: int = 4
    chunk: int = 64
    # Δ at initialisation, as `MambaConfig`'s
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    @property
    def key_dim(self) -> int:
        return self.n_k_heads * self.k_dim

    @property
    def value_dim(self) -> int:
        return self.n_v_heads * self.v_dim

    @property
    def conv_dim(self) -> int:        # q, k and v go through the conv
        return 2 * self.key_dim + self.value_dim

    @property
    def in_proj(self) -> int:         # [q | k | v | z]
        return self.conv_dim + self.value_dim


def init_gated_delta(key, d_model, cfg: DeltaConfig, dtype=jnp.float32):
    """The layer's leaves: `w_in` [d, q | k | v | z] (each part a head's
    columns after the last head's), `w_ba` [d, b | a] (a value head's write
    strength and decay inputs), the depthwise taps `conv_w` [d_conv, q | k |
    v] (uniform ±d_conv^-½, no bias), a value head's `dt_bias` and `A_log`
    (`_init_decays`, as `init_mamba`'s), the output norm's scale `norm`
    [v_dim], one for all heads, `w_out`."""
    k_in, k_ba, k_out, k_conv, k_dt, k_a = jax.random.split(key, 6)
    bound = cfg.d_conv ** -0.5
    return {
        "w_in": _init_dense(k_in, (d_model, cfg.in_proj), dtype=dtype),
        "w_ba": _init_dense(k_ba, (d_model, 2 * cfg.n_v_heads), dtype=dtype),
        "conv_w": jax.random.uniform(
            k_conv, (cfg.d_conv, cfg.conv_dim), minval=-bound,
            maxval=bound).astype(dtype),
        **_init_decays(k_dt, k_a, cfg.n_v_heads, cfg, dtype),
        "norm": jnp.ones((cfg.v_dim,), dtype),
        "w_out": _init_dense(k_out, (cfg.value_dim, d_model), dtype=dtype),
    }


# every leaf whole on every `tp` rank: a model with the layer refuses `tp` > 1
GATED_DELTA_LOGICAL = {
    "w_in": ("embed", None), "w_ba": ("embed", None), "conv_w": (None, None),
    "dt_bias": (None,), "A_log": (None,), "norm": (None,),
    "w_out": (None, "embed"),
}


def apply_gated_delta(params: Params, u, cfg: DeltaConfig, *,
                      compute_dtype=jnp.bfloat16, eps: float = 1e-6,
                      mesh=None):
    """u [B, T, d] -> [B, T, d]: ``[q | k | v | z] = u·W_in``, ``[b | a] =
    u·W_ba``; ``[q | k | v] ← SiLU(causal depthwise conv([q | k | v]))``, no
    bias (`ops.mamba_stages.conv_silu`, the Mamba mixer's stage); q and k
    L2-normed over a head's columns, q scaled by ``k_dim^-½`` (inside the
    rule: `gated_delta(normalize=)`); ``β =
    sigmoid(b)``, ``g = −exp(A_log) · softplus(a + dt_bias)``; the gated
    delta rule (`ops.gated_delta`); ``y = RMSNorm_head(o) ∘ SiLU(z)`` — the
    norm over a head's `v_dim` columns FIRST, then the gate, the other order
    than `mamba_stages.gate_norm`'s; ``·W_out``. The three projections and
    the rule's products on the MXU in `compute_dtype`; conv, norms, decays
    and gates in float32. Scopes: `delta_proj`, `delta_conv`, `delta_rule`,
    `delta_gate_norm`. mesh: as in `apply_mamba` (the conv stage's and the
    rule's kernels on one TPU whose tiles divide the shapes)."""
    B, T, _ = u.shape
    G, H, K, V = cfg.n_k_heads, cfg.n_v_heads, cfg.k_dim, cfg.v_dim
    project = _project(compute_dtype, False)
    f32 = jnp.float32
    with jax.named_scope("delta_proj"):
        # two products of the one leaf: z is read once, by the gate, and its
        # cotangent is done with before the rule's backward starts, where
        # one `[q | k | v | z]` array and its cotangent live through it
        w_qkv, w_z = jnp.split(params["w_in"], [cfg.conv_dim], axis=-1)
        qkv = project("btd,de->bte", u, w_qkv, f32)
        z = project("btd,de->bte", u, w_z, f32)
        ba = project("btd,de->bte", u, params["w_ba"], f32)
    with jax.named_scope("delta_conv"):
        qkv = mamba_stages.conv_silu(
            qkv, params["conv_w"], jnp.zeros((cfg.conv_dim,), f32),
            start=0, mesh=mesh)
    with jax.named_scope("delta_rule"):
        b, a = jnp.split(ba, 2, axis=-1)
        g = -jnp.exp(params["A_log"].astype(f32)) * jax.nn.softplus(
            a + params["dt_bias"].astype(f32))
        # q and k L2-normed, q scaled, inside the rule: its backward keeps
        # the conv's output and no normed copy beside it; its kernels read
        # each head's columns out of `qkv` in place
        o = gated_delta.gated_delta_packed(
            qkv, g, jax.nn.sigmoid(b), key_heads=G, k_dim=K,
            chunk=cfg.chunk, compute_dtype=compute_dtype, normalize=eps,
            mesh=mesh)
    with jax.named_scope("delta_gate_norm"):
        y = rms_norm(o, params["norm"], eps) * jax.nn.silu(
            z.reshape(B, T, H, V))
    with jax.named_scope("delta_proj"):
        return project("bte,ed->btd", y.reshape(B, T, cfg.value_dim),
                       params["w_out"], u.dtype)


# ------------------------------------------------- Kimi Delta Attention
@dataclasses.dataclass(frozen=True)
class KDAConfig:
    """Kimi Delta Attention's widths (the Kimi Linear family's): `n_heads`
    heads whose q, k (`k_dim`) and v (`v_dim`) go through a depthwise causal
    conv of `d_conv` taps; the forget gate — a log-decay a head, token and
    KEY CHANNEL — and the output gate each come from a low-rank pair of
    `gate_rank`."""
    n_heads: int = 32
    k_dim: int = 128
    v_dim: int = 128
    d_conv: int = 4
    gate_rank: int = 128
    chunk: int = 64
    l2_eps: float = 1e-6              # under q's and k's L2 norms
    # Δ at initialisation, as `MambaConfig`'s
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    @property
    def key_dim(self) -> int:
        return self.n_heads * self.k_dim

    @property
    def value_dim(self) -> int:
        return self.n_heads * self.v_dim

    @property
    def conv_dim(self) -> int:        # [q | k | v]
        return 2 * self.key_dim + self.value_dim

    def n_params(self, d_model: int) -> int:
        return (d_model * self.conv_dim + self.d_conv * self.conv_dim
                + self.gate_rank * (2 * d_model + self.key_dim
                                    + self.value_dim)
                + d_model * self.n_heads + self.n_heads + self.key_dim
                + self.v_dim + self.value_dim * d_model)


def init_kda(key, d_model, cfg: KDAConfig, dtype=jnp.float32):
    """The layer's leaves: `w_qkv` [d, q | k | v] (each part a head's
    columns after the last head's), the depthwise taps `conv_w` [d_conv, q |
    k | v] (uniform ±d_conv^-½, no bias), the forget gate's pair `w_f_down`
    [d, r], `w_f_up` [r, H·K] with `A_log` [H] (log U[1, 16]) and `dt_bias`
    [H·K] (one a channel: `_init_dt_bias`), the write strength's `w_beta`
    [d, H], the output gate's pair `w_g_down`, `w_g_up` [r, H·V], the head
    norm's scale `norm` [v_dim], one for all heads, `w_out`."""
    ks = jax.random.split(key, 10)
    bound, r = cfg.d_conv ** -0.5, cfg.gate_rank
    return {
        "w_qkv": _init_dense(ks[0], (d_model, cfg.conv_dim), dtype=dtype),
        "conv_w": jax.random.uniform(
            ks[1], (cfg.d_conv, cfg.conv_dim), minval=-bound,
            maxval=bound).astype(dtype),
        "w_f_down": _init_dense(ks[2], (d_model, r), dtype=dtype),
        "w_f_up": _init_dense(ks[3], (r, cfg.key_dim), dtype=dtype),
        "dt_bias": _init_dt_bias(ks[4], cfg.key_dim, cfg, dtype),
        "A_log": jnp.log(jax.random.uniform(
            ks[5], (cfg.n_heads,), minval=1.0, maxval=16.0)).astype(dtype),
        "w_beta": _init_dense(ks[6], (d_model, cfg.n_heads), dtype=dtype),
        "w_g_down": _init_dense(ks[7], (d_model, r), dtype=dtype),
        "w_g_up": _init_dense(ks[8], (r, cfg.value_dim), dtype=dtype),
        "norm": jnp.ones((cfg.v_dim,), dtype),
        "w_out": _init_dense(ks[9], (cfg.value_dim, d_model), dtype=dtype),
    }


# every leaf whole on every `tp` rank: a model with the layer refuses `tp` > 1
KDA_LOGICAL = {
    "w_qkv": ("embed", None), "conv_w": (None, None),
    "w_f_down": ("embed", None), "w_f_up": (None, None),
    "dt_bias": (None,), "A_log": (None,), "w_beta": ("embed", None),
    "w_g_down": ("embed", None), "w_g_up": (None, None), "norm": (None,),
    "w_out": (None, "embed"),
}


def apply_kda(params: Params, u, cfg: KDAConfig, *,
              compute_dtype=jnp.bfloat16, eps: float = 1e-6, mesh=None):
    """u [B, T, d] -> [B, T, d]: ``[q | k | v] = SiLU(causal depthwise
    conv(u·W_qkv))``, no bias (`ops.mamba_stages.conv_silu`); q and k
    L2-normed over a head's columns, q scaled by ``k_dim^-½`` (inside the
    rule: `kda(normalize=cfg.l2_eps)`); the forget gate ``g = −exp(A_log) ·
    softplus((u·W_f↓)·W_f↑ + dt_bias)``, a number a head, token and key
    channel; ``β = sigmoid(u·W_β)``; the rule (`ops.kda`); ``y =
    RMSNorm_head(o) ∘ sigmoid((u·W_g↓)·W_g↑)`` — the norm over a head's
    `v_dim` columns first, then the gate, a SIGMOID where
    `apply_gated_delta` has SiLU; ``·W_out``. The projections, both
    low-rank pairs and the rule's products on the MXU in `compute_dtype`;
    conv, norms, decays and gates in float32. Scopes: `kda_proj`,
    `kda_conv`, `kda_rule`, `kda_gate_norm`. mesh: as in
    `apply_gated_delta` — on one TPU whose tiles divide the shapes
    (`kda._use_kernel`) the rule is the kernels `kda_fwd` / `kda_bwd`,
    which read a pair of heads' columns of q, k and v out of the conv's
    ``[q | k | v]``, and of the decay out of the gate's ``[B, T, H·K]``, IN
    PLACE: no split, head reshape or chunked copy of them is made; the plain
    form (the CPU, a mesh that splits the batch, widths off the tiles)
    splits them itself."""
    B, T, _ = u.shape
    H, K, V = cfg.n_heads, cfg.k_dim, cfg.v_dim
    project = _project(compute_dtype, False)
    f32 = jnp.float32

    def low_rank(down, up):
        return project("btr,re->bte", project("btd,dr->btr", u, down, f32),
                       up, f32)

    with jax.named_scope("kda_proj"):
        qkv = project("btd,de->bte", u, params["w_qkv"], f32)
        f = low_rank(params["w_f_down"], params["w_f_up"])
        z = low_rank(params["w_g_down"], params["w_g_up"])
        # the gates' own arithmetic with their projections: `kda_rule`
        # holds the rule and nothing else (what its roofline share counts)
        # a head's rate on its K columns: g stays [B, T, H·K], as the
        # rule's kernels read it
        g = -jnp.repeat(jnp.exp(params["A_log"].astype(f32)), K) \
            * jax.nn.softplus(f + params["dt_bias"].astype(f32))
        beta = jax.nn.sigmoid(project("btd,dh->bth", u, params["w_beta"],
                                      f32))
    with jax.named_scope("kda_conv"):
        qkv = mamba_stages.conv_silu(
            qkv, params["conv_w"], jnp.zeros((cfg.conv_dim,), f32),
            start=0, mesh=mesh)
    with jax.named_scope("kda_rule"):
        o = kda.kda_packed(qkv, g, beta, k_dim=K, chunk=cfg.chunk,
                           compute_dtype=compute_dtype,
                           normalize=cfg.l2_eps, mesh=mesh)
    with jax.named_scope("kda_gate_norm"):
        y = rms_norm(o, params["norm"], eps) * jax.nn.sigmoid(
            z.reshape(B, T, H, V))
    with jax.named_scope("kda_proj"):
        return project("bte,ed->btd", y.reshape(B, T, cfg.value_dim),
                       params["w_out"], u.dtype)


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


def init_gated_mlp(key, d_model, d_ff, dtype=jnp.float32):
    """A dense feed-forward of three matrices and no bias, named as a gated
    expert's (`init_moe`): `w_gate`, `w_up` [d, F], `w_down` [F, d]."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": _init_dense(k1, (d_model, d_ff), dtype=dtype),
            "w_up": _init_dense(k3, (d_model, d_ff), dtype=dtype),
            "w_down": _init_dense(k2, (d_ff, d_model), dtype=dtype)}


GATED_MLP_LOGICAL = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
                     "w_down": ("mlp", "embed")}


def apply_gated_mlp(params: Params, x, *, compute_dtype=jnp.bfloat16,
                    three_pass: bool = False):
    """``(silu(x·W_gate) ∘ (x·W_up))·W_down``: the three products on the
    MXU in `compute_dtype`, the gate in float32. In one pass the two wide
    results and the hidden row are `compute_dtype`'s, as `_through_experts`
    holds an expert's; with `three_pass` (as in `apply_attention`) all three
    stay float32, so that the extra passes have something to add to and the
    last product sees the hidden row's own low part."""
    project = _project(compute_dtype, three_pass)
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
    return {"w_in": _init_dense(k_in, (d_model, inner), dtype=dtype),
            "w_out": _init_dense(k_out, (inner, d_model), dtype=dtype)}


GMU_LOGICAL = {"w_in": ("embed", None), "w_out": (None, "embed")}


def apply_gmu(params: Params, x, memory, *, compute_dtype=jnp.bfloat16):
    """``(SiLU(x·W_in) ⊙ memory)·W_out`` (arXiv:2507.06607): `memory` [B, T,
    inner] an EARLIER layer's scan result (`apply_mamba1`'s second), which
    this layer gates with its own stream and does not recompute. Products
    on the MXU in `compute_dtype`, the gate float32."""
    project = _project(compute_dtype, False)
    gate = project("btd,de->bte", x, params["w_in"], jnp.float32)
    return project("bte,ed->btd",
                   jax.nn.silu(gate) * memory.astype(jnp.float32),
                   params["w_out"], x.dtype)


# ---------------------------------------------------------------- MoE (EP)
@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    # the chosen gates rescaled to sum to 1 (GShard); False uses the router's
    # probabilities as they are (OLMoE)
    norm_topk_prob: bool = True
    # "softmax" over all experts, or "sigmoid": independent scores, the
    # top-k chosen on score + a selection bias (the leaf `bias`, behind a
    # stop_gradient) and weighted by the scores themselves
    score: str = "softmax"
    # what the chosen gates are multiplied by, after any renormalisation
    scale: float = 1.0
    # between the two matrices of the `w1` / `w2` form: "gelu" | "relu2"
    activation: str = "gelu"
    # on the gate of the three-matrix form: "silu" | "relu"
    gate: str = "silu"
    # width of a shared expert every token goes through, its result added
    # once, whole, whatever share of the routed experts is held; 0: none.
    # In the routed experts' form: `w1` / `w2` with their activation
    # (leaves `shared_w1`, `shared_w2`), or, where they are gated, the dense
    # SiLU-gated feed-forward of three matrices (`apply_gated_mlp` on the
    # leaves under `shared`)
    d_shared: int = 0
    # the gated-form shared expert's output times ``sigmoid(x·w_sg)``, a
    # scalar a token (the leaf `w_sg` [d, 1])
    shared_gate: bool = False
    # THE CHIP'S SHARE of a deployment that spreads the experts: the
    # stacked leaves hold `held` experts (None: all `n_experts`), the first
    # of them expert `first` of the `n_experts` the router scores. The
    # layer computes its own experts' part of the result; what the others
    # would add is computed where they live, which may be nowhere.
    held: Optional[int] = None
    first: int = 0

    @property
    def stacked(self) -> int:
        return self.n_experts if self.held is None else self.held


def init_moe(key, d_model, d_ff, cfg: MoEConfig, dtype=jnp.float32,
             gated: bool = False):
    """Router `wg` (as wide as the experts it scores) and the experts held
    here (`cfg.stacked`), stacked over a leading axis: two matrices with an
    activation between (`w1`, `w2`), or, `gated`, three with a gate (SiLU or
    ReLU: `cfg.gate`; `w_gate`, `w_up`, `w_down`). `apply_moe` tells the
    form by the leaves.
    With sigmoid scores the selection `bias` [E], zero; with `d_shared` the
    shared expert in the experts' form: `shared_w1`, `shared_w2`, or,
    `gated`, `init_gated_mlp`'s three under `shared` (and, with
    `shared_gate`, its scalar gate's `w_sg` [d, 1])."""
    kg, k1, k2, k3 = jax.random.split(key, 4)
    E = cfg.stacked
    wide, narrow = (E, d_model, d_ff), (E, d_ff, d_model)
    experts = ({"w_gate": _init_dense(k1, wide, dtype=dtype),
                "w_up": _init_dense(k3, wide, dtype=dtype),
                "w_down": _init_dense(k2, narrow, dtype=dtype)} if gated else
               {"w1": _init_dense(k1, wide, dtype=dtype),
                "w2": _init_dense(k2, narrow, dtype=dtype)})
    params = {"wg": _init_dense(kg, (d_model, cfg.n_experts), dtype=dtype),
              **experts}
    if cfg.score == "sigmoid":
        params["bias"] = jnp.zeros((cfg.n_experts,), dtype)
    if cfg.d_shared:
        k4, k5 = jax.random.split(k3)
        if gated:
            params["shared"] = init_gated_mlp(k4, d_model, cfg.d_shared, dtype)
            if cfg.shared_gate:
                params["w_sg"] = _init_dense(k5, (d_model, 1), dtype=dtype)
            return params
        params["shared_w1"] = _init_dense(k4, (d_model, cfg.d_shared),
                                          dtype=dtype)
        params["shared_w2"] = _init_dense(k5, (cfg.d_shared, d_model),
                                          dtype=dtype)
    return params


_WIDE = ("experts", "embed", "expert_mlp")
_NARROW = ("experts", "expert_mlp", "embed")
MOE_LOGICAL = {"wg": ("embed", None), "w1": _WIDE, "w2": _NARROW}
GATED_MOE_LOGICAL = {"wg": ("embed", None), "w_gate": _WIDE, "w_up": _WIDE,
                     "w_down": _NARROW}
# the leaves some routers and layers have besides
MOE_EXTRA_LOGICAL = {"bias": (None,), "shared_w1": ("embed", "mlp"),
                     "shared_w2": ("mlp", "embed")}
# a shared expert beside GATED experts: the dense form's three leaves
GATED_SHARED_LOGICAL = {"shared": GATED_MLP_LOGICAL}
# and its scalar sigmoid gate's
SHARED_GATE_LOGICAL = {"w_sg": ("embed", None)}

# A share's bounds over the held experts' expected rows: ONE, at twice the
# expectation. It holds the two cells with a share while their routing is
# even or turns away from this chip. Rungs at 4 and 8 times were tried
# (`nemotronh9l-b1s8k`: a deeper layer gives one, two or nearly three of
# every token's six choices to held experts for 3 to 15 steps in four seeds
# of seven — 17, 33, 45 % of the rows on an expectation of 6.25 % — and runs
# whole through them, 462 ms a step for 432): each rung is one more program
# with kernels of its own shapes, ~3 s of that cell's 64 s from start to
# first step, whose bound is a tenth of it (PERF.md §6, PR 37). A bound
# that is not under the rows is none.
_BOUND_FACTORS = (2,)


def assignment_bounds(rows: int, local: int, n_experts: int) -> Tuple[int, ...]:
    """How many of `rows` sorted assignments a device that holds `local` of
    the `n_experts` scored experts works on while its experts' rows fit
    them: their expectation under even routing times each of
    `_BOUND_FACTORS`, rounded up to the grouped kernel's row tile, those
    under `rows`, ascending. From the shapes alone. Empty where every
    scored expert is held or no bound is under `rows`: the layer then has
    the whole path only."""
    if local >= n_experts:
        return ()
    tile = grouped_matmul.row_tile(rows) or grouped_matmul.ROW_TILE
    expected = -(-rows * local // n_experts)
    bounds = {-(-factor * expected // tile) * tile
              for factor in _BOUND_FACTORS}
    return tuple(sorted(b for b in bounds if b < rows))


def moe_plan(tokens: int, d_model: int, d_ff: int, cfg: MoEConfig, *,
             gated: bool, itemsize: int = 2, ep: int = 1) -> dict:
    """What one forward pass of `apply_moe` does on one device, from shapes
    alone (`tokens` there; `ep` devices share the `cfg.stacked` experts the
    leaves hold): the rows gathered, the grouped matmuls' FLOPs needed
    (every assignment through its expert once; a device's share under even
    routing over all `n_experts`) and the most the
    tiled kernel issues under ANY routing (each local expert's group may
    end inside a row tile, which is then visited twice), and the bytes that
    dispatch and combine move. The backward pass is twice the FLOPs (one
    product for the rows, one for the weights) and the same bytes again.
    `bounds`: where the leaves hold fewer experts than the router scores,
    the sorted assignments the layer dispatches, multiplies and combines
    while the held experts' rows fit them — the least that does
    (`assignment_bounds`; empty: all `rows` always); the bytes are those of
    the whole path."""
    rows = tokens * cfg.top_k
    per_row = (3 if gated else 2) * 2 * d_model * d_ff
    tile = grouped_matmul.row_tile(rows) or grouped_matmul.ROW_TILE
    tiles = -(-rows // tile)
    visits = min(tiles + cfg.stacked // ep - 1, 2 * tiles)
    return {
        "rows": rows,
        "bounds": assignment_bounds(rows, cfg.stacked // ep, cfg.n_experts),
        "flops_needed": rows * per_row * cfg.stacked // (cfg.n_experts * ep),
        "flops_issued_max": visits * tile * per_row,
        # each row read from its token and written in expert order
        "dispatch_bytes": 2 * rows * d_model * itemsize,
        # each row read back in token order, a token's K summed into one
        "combine_bytes": (rows + tokens) * d_model * itemsize,
    }


def routing_plan(tokens: int, cfg: MoEConfig) -> dict:
    """Bytes of what one routed layer keeps under `remat` by the name
    `ROUTING`, on one device with `tokens` there, from shapes alone: the
    router's float32 `logits`, the `top_k`'s chosen experts (and, of a
    softmax router, their probabilities as the sort gave them; a sigmoid
    router reads its gates at the choice, which is rebuilt), and the
    assignments' sort: `order`, `inverse`, `sizes`."""
    rows = tokens * cfg.top_k
    return {"logits": tokens * cfg.n_experts * 4,
            "top_k": rows * (8 if cfg.score == "softmax" else 4),
            "order": rows * 4, "inverse": rows * 4,
            "sizes": cfg.n_experts * 4}


@jax.custom_vjp
def _take_assignments(x2, order, inverse):
    """x2 [T, D] -> [T·K, D], row j the token of the j-th assignment in
    expert order (`order`: positions in the token-major [T·K] list; `inverse`
    its inverse permutation). Every token is taken K times, so the
    transpose is a gather too: K rows a token, summed."""
    return x2[order // (order.shape[0] // x2.shape[0])]


def _take_assignments_fwd(x2, order, inverse):
    return _take_assignments(x2, order, inverse), (inverse, x2.shape[0])


def _take_assignments_bwd(res, d):
    inverse, tokens = res
    dx = jnp.sum(d[inverse].reshape(tokens, -1, d.shape[-1])
                 .astype(jnp.float32), axis=1)
    return dx.astype(d.dtype), None, None


_take_assignments.defvjp(_take_assignments_fwd, _take_assignments_bwd)


@jax.custom_vjp
def _permute_rows(y, perm, inverse):
    """y[perm] for a permutation; the transpose is the gather by `inverse`
    (a scatter to XLA, which knows no permutation when it sees one)."""
    return y[perm]


_permute_rows.defvjp(lambda y, perm, inverse: (y[perm], (inverse,)),
                     lambda res, d: (d[res[0]], None, None))


def _sum_prefix(y, inverse, top_k: int, weights=None, mask=None):
    """y [C, D], the first C rows of the sorted order -> [T, D] float32:
    each token's K assignments' rows (row `inverse[i]` of y; those behind
    the prefix, and those `mask` [T, K] leaves out, count zero), times
    `weights` [T, K], summed. One gather of T rows a slot, accumulated:
    nothing of `[T, K, D]` is laid out, and on the TPU that is what the
    whole path's combine spends most of its time on (PERF.md §6, PR 37)."""
    place = inverse.reshape(-1, top_k)
    out = 0.0
    for k in range(top_k):
        rows = y[jnp.minimum(place[:, k], y.shape[0] - 1)].astype(jnp.float32)
        if weights is not None:
            rows = rows * weights[:, k, None]
        keep = place[:, k] < y.shape[0]
        if mask is not None:
            keep = keep & mask[:, k]
        out = out + jnp.where(keep[:, None], rows, 0.0)
    return out


@jax.custom_vjp
def _take_prefix(x2, order, inverse):
    """`_take_assignments` for a prefix of the sorted assignments: x2 [T, D]
    -> [C, D], row j the token of `order[j]` (`order` [C] the first C of the
    sorted positions, `inverse` [T·K] the whole inverse permutation). The
    transpose gathers too: each token's rows out of the C, summed in
    float32 (`_sum_prefix`)."""
    return x2[order // (inverse.shape[0] // x2.shape[0])]


def _take_prefix_fwd(x2, order, inverse):
    return _take_prefix(x2, order, inverse), (inverse, x2.shape[0])


def _take_prefix_bwd(res, d):
    inverse, tokens = res
    with jax.named_scope("dispatch"):   # a backward rule inherits no scope
        dx = _sum_prefix(d, inverse, inverse.shape[0] // tokens)
        return dx.astype(d.dtype), None, None


_take_prefix.defvjp(_take_prefix_fwd, _take_prefix_bwd)


@jax.custom_vjp
def _combine_prefix(y, gate_vals, here, order, inverse):
    """y [C, D], the experts' outputs for the first C of the sorted
    assignments -> [T, D] float32: each token's rows times its gates [T, K]
    in float32, those of experts not `here` [T, K] left out, summed
    (`_sum_prefix`). The transpose works on the C rows alone: each row's
    token's cotangent gathered once, for the row and for its gate."""
    return _sum_prefix(y, inverse, gate_vals.shape[1], gate_vals, here)


def _combine_prefix_fwd(y, gate_vals, here, order, inverse):
    return (_combine_prefix(y, gate_vals, here, order, inverse),
            (y, gate_vals, here, order, inverse))


def _combine_prefix_bwd(res, d):
    y, gate_vals, here, order, inverse = res
    top_k = gate_vals.shape[1]
    with jax.named_scope("combine"):    # a backward rule inherits no scope
        d_rows = d[order // top_k]
        gates = jnp.where(here.reshape(-1)[order],
                          gate_vals.reshape(-1)[order], 0.0)
        # a gate's cotangent, by row; then by token and slot
        d_gates = jnp.sum(y.astype(jnp.float32) * d_rows, axis=-1)
        place = inverse.reshape(-1, top_k)
        d_gates = jnp.where(here & (place < y.shape[0]),
                            d_gates[jnp.minimum(place, y.shape[0] - 1)], 0.0)
        return ((d_rows * gates[:, None]).astype(y.dtype), d_gates, None,
                None, None)


_combine_prefix.defvjp(_combine_prefix_fwd, _combine_prefix_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _one_of(programs, which, args, aux):
    """`programs[which](*args, *aux)`: several programs of one result, one
    chosen on the device. Differentiable in `args`. The backward branches
    on `which` again and differentiates the program that ran from its
    inputs (its forward once more, as under `remat`), so nothing of the
    others is kept, zero-filled or run:
    `lax.switch` under differentiation returns the residuals of EVERY
    branch, the whole path's `[T·K, ·]` arrays among them."""
    return jax.lax.switch(which, programs, *args, *aux)


def _one_of_fwd(programs, which, args, aux):
    return _one_of(programs, which, args, aux), (which, args, aux)


def _one_of_bwd(programs, res, d):
    which, args, aux = res

    def gradient(fn):
        return lambda args, d: jax.vjp(lambda *a: fn(*a, *aux), *args)[1](d)

    # the barrier holds the compiler's conditional code motion off: it
    # sinks what reads a cotangent into every branch, and where that is the
    # zero-padded copy of a whole stack of layers (a slice's transpose) each
    # conditional then returns the stack (4.9 GB more in `nemotronh9l-b1s8k`)
    return (None, jax.lax.optimization_barrier(jax.lax.switch(
        which, [gradient(fn) for fn in programs], args, d)), None)


_one_of.defvjp(_one_of_fwd, _one_of_bwd)


def _relu2(u):
    """relu(u)², in float32."""
    return jnp.square(jax.nn.relu(u.astype(jnp.float32)))


def _local_experts(x, gate_vals, gate_idx, experts, *, n_experts: int,
                   first, cd, mesh=None, activation: str = "gelu",
                   gate: str = "silu"):
    """One device's part of the routed layer: x [b, s, D] its tokens, gates
    [b, s, K] their chosen experts and weights, `experts` the leaves of the
    E_local experts it holds, `first` the id of the first of them, `mesh`
    where this runs (`grouped_matmul.grouped_matmul`'s). Returns
    [b, s, D] float32: for each token the weighted outputs of those of its
    experts that live here (all of them where nothing splits the experts).

    Where E_local < n_experts the local experts' rows are a PREFIX of the
    sorted assignments, and the shapes give bounds on it
    (`assignment_bounds`): while this step's routing keeps the prefix under
    a bound, only that many rows (the least bound that holds them) are
    gathered, multiplied and combined; otherwise all of them are, as where
    every expert is local — the same result either way, no assignment
    dropped. Second result: whether a bounded program ran (int32; None
    where every scored expert is local)."""
    d_model, top_k = x.shape[-1], gate_idx.shape[-1]
    x2 = x.reshape(-1, d_model)
    gate_vals, gate_idx = (g.reshape(-1, top_k) for g in (gate_vals, gate_idx))
    rows = x2.shape[0] * top_k
    local = next(iter(experts.values())).shape[0]
    wide = experts["w_gate" if "w_gate" in experts else "w1"]

    def through(fn, bound=None):
        # decided ONCE for all of a program's products: the width at which
        # they are the op's kernels, None where they are XLA's
        width = grouped_matmul.kernel_width(bound or rows, *wide.shape[1:],
                                            cd, mesh)
        return functools.partial(
            fn, n_experts=n_experts, cd=cd, mesh=mesh, width=width,
            activation=activation, gate=gate, bound=bound)

    with jax.named_scope("dispatch"):
        # this device's experts first, in order; the others' rows behind
        # them. Sort, inverse and sizes carry the name `remat` keeps: the
        # argsort and the two scatters run once a step
        key = (gate_idx.reshape(rows) - first) % n_experts
        order = checkpoint_name(
            jnp.argsort(key, stable=True).astype(jnp.int32), ROUTING)
        inverse = checkpoint_name(jnp.zeros((rows,), jnp.int32).at[order].set(
            jnp.arange(rows, dtype=jnp.int32), unique_indices=True), ROUTING)
        # every row's group, the local experts' first: what lies behind
        # them belongs to no matrix here and comes out of a product zero
        sizes = checkpoint_name(
            jnp.bincount(key, length=n_experts).astype(jnp.int32), ROUTING)
    bounds = assignment_bounds(rows, local, n_experts)
    if not bounds:
        # a share too small for a bound under its rows never runs bounded
        return through(_through_experts)(
            x2, gate_vals, experts, gate_idx, first, order, inverse,
            sizes).reshape(x.shape), (
                           None if local == n_experts else jnp.int32(0))
    # the least bound that holds the local experts' rows; behind the last,
    # the whole path. Each program is a `jit`, so a model's layers (and the
    # forward, its recomputation and the backward of each) trace it once
    over = jnp.sum(jnp.sum(sizes[:local]) > jnp.asarray(bounds, jnp.int32))
    programs = tuple(through(_through_experts_jit, b)
                     for b in bounds + (None,))
    out = _one_of(programs, over, (x2, gate_vals, experts),
                  (gate_idx, jnp.asarray(first, jnp.int32), order, inverse,
                   sizes))
    return out.reshape(x.shape), (over < len(bounds)).astype(jnp.int32)


def _through_experts(x2, gate_vals, experts, gate_idx, first, order, inverse,
                     sizes, *, n_experts: int, cd, mesh, width: Optional[int],
                     activation: str, gate: str, bound: Optional[int] = None):
    """`_local_experts` behind the sort: x2 [T, D], gates [T, K], the
    sorted positions `order`, their inverse and every group's `sizes`
    -> [T, D] float32. `bound`: the local experts' rows lie within the first
    `bound` of the sorted order, and only those are taken, multiplied and
    combined; None: all T·K."""
    (tokens, d_model), top_k = x2.shape, gate_idx.shape[-1]
    local = next(iter(experts.values())).shape[0]
    with jax.named_scope("dispatch"):
        if bound is None:
            taken = _take_assignments(x2.astype(cd), order, inverse)
        else:
            order = order[:bound]
            # what of the prefix lies behind the local experts' rows is one
            # more group of no matrix
            sizes = jnp.append(sizes[:local],
                               bound - jnp.sum(sizes[:local]))
            taken = _take_prefix(x2.astype(cd), order, inverse)
    with jax.named_scope("experts"):
        def product(lhs, name, axis):
            """`axis`: the one of the leaf's that is the experts' width,
            the cast leaf zero-padded there to `width` (every activation
            here maps 0 to 0)."""
            rhs = experts[name].astype(cd)
            pad = width and width - rhs.shape[axis]
            if pad:
                rhs = jnp.pad(rhs, [(0, pad if a == axis else 0)
                                    for a in range(rhs.ndim)])
            return grouped_matmul.grouped_matmul(lhs, rhs, sizes, mesh=mesh)

        if "w_gate" in experts:
            gate_fn = {"silu": jax.nn.silu, "relu": jax.nn.relu}[gate]
            gated = product(taken, "w_gate", 2)
            up = product(taken, "w_up", 2)
            hidden = (gate_fn(gated.astype(jnp.float32))
                      * up.astype(jnp.float32)).astype(cd)
            y = product(hidden, "w_down", 1)
        elif activation == "gelu":
            hidden = jax.nn.gelu(product(taken, "w1", 2))
            y = product(hidden, "w2", 1)
        else:
            hidden = _relu2(product(taken, "w1", 2)).astype(cd)
            y = product(hidden, "w2", 1)
    with jax.named_scope("combine"):
        if bound is None:
            y = _permute_rows(y, inverse, order).reshape(
                tokens, top_k, d_model)
        here = ((gate_idx - first) % n_experts) < local
        if bound is not None:
            return _combine_prefix(y, gate_vals, here, order, inverse)
        weighted = jnp.where(here[..., None], y.astype(jnp.float32)
                             * gate_vals[..., None], 0.0)
        return jnp.sum(weighted, axis=1)


_through_experts_jit = jax.jit(_through_experts, static_argnames=(
    "n_experts", "cd", "mesh", "width", "activation", "gate", "bound"))


@jax.custom_jvp
def _chosen(values, scores, indices):
    """`values`, the top-k of `scores` [..., E] at `indices` [..., K], as a
    function of the scores: the tangent is the scores' at the indices THE
    CALLER HOLDS. `lax.top_k`'s own rule gathers by the indices as the sort
    gave them, which no `checkpoint_name` reaches: a checkpoint that keeps
    the named choice would still sort again for its backward pass."""
    return values


@_chosen.defjvp
def _chosen_jvp(primals, tangents):
    values, _, indices = primals
    # the gather of `lax.top_k`'s own rule, so that a step without a
    # checkpoint stays the program it was
    batch = tuple(range(indices.ndim - 1))
    return values, jax.lax.gather(
        tangents[1], indices[..., None], jax.lax.GatherDimensionNumbers(
            offset_dims=(), collapsed_slice_dims=(len(batch),),
            start_index_map=(len(batch),), operand_batching_dims=batch,
            start_indices_batching_dims=batch), (1,) * indices.ndim)


def _route(logits, bias, cfg: MoEConfig):
    """Router logits [B, S, E] float32 -> (gates [B, S, K], experts [B, S,
    K], the scores the statistics are taken of [B, S, E])."""
    if cfg.score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = (checkpoint_name(a, ROUTING) for a in
                               jax.lax.top_k(jax.lax.stop_gradient(probs),
                                             cfg.top_k))
        gate_vals = _chosen(gate_vals, probs, gate_idx)
        floor = 1e-9
    else:
        # independent scores; chosen on score + bias, weighted by the score
        probs = jax.nn.sigmoid(logits)
        _, gate_idx = jax.lax.top_k(
            probs + jax.lax.stop_gradient(bias.astype(jnp.float32)),
            cfg.top_k)
        gate_idx = checkpoint_name(gate_idx, ROUTING)
        gate_vals = jnp.take_along_axis(probs, gate_idx, axis=-1)
        floor = 1e-20
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, -1, keepdims=True), floor)
    if cfg.scale != 1.0:
        gate_vals = gate_vals * cfg.scale
    return gate_vals, gate_idx, probs


_NOT_ROUTED = ("wg", *MOE_EXTRA_LOGICAL, *GATED_SHARED_LOGICAL,
               *SHARED_GATE_LOGICAL)


def moe_route(params: Params, x, cfg: MoEConfig):
    """The router of `apply_moe` on x [B, S, D]: (gates [B, S, K], experts
    [B, S, K], stats), float32. `apply_moe` routes on its own input; a model
    whose router reads another place (the layer's input, ahead of attention)
    calls this there and hands the result on as `routing`."""
    E, K = cfg.n_experts, cfg.top_k
    S = x.shape[1]
    with jax.named_scope("router"):
        logits = checkpoint_name(jnp.einsum(
            "bsd,de->bse", x.astype(jnp.float32),
            params["wg"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), ROUTING)
        gate_vals, gate_idx, probs = _route(logits, params.get("bias"), cfg)
        # [B, E]: a sequence's assignments by expert
        counts = jnp.sum(jax.nn.one_hot(gate_idx, E, dtype=jnp.int32),
                         axis=(1, 2))
        stats = {
            "load_balance": jnp.mean(E * jnp.sum(
                counts.astype(jnp.float32) / (S * K)
                * jnp.mean(probs, axis=1), axis=-1)),
            "z": jnp.mean(jnp.square(
                jax.scipy.special.logsumexp(logits, axis=-1))),
            "counts": jnp.sum(counts, axis=0),
        }
    return gate_vals, gate_idx, stats


def apply_moe(params: Params, x, cfg: MoEConfig, compute_dtype=jnp.bfloat16,
              mesh=None, three_pass: bool = False, routing=None):
    """Top-k routed experts, dropless: x [B, S, D] -> (y [B, S, D], stats).

    Router and its scores in float32 (`_route`: softmax, or sigmoid with a
    selection bias); `lax.top_k`; the T·K assignments sorted
    by expert (stable), their rows gathered in that order, the experts'
    matrices applied to the ragged groups by grouped matmuls, and each
    token's K outputs weighted by its gates and summed. No capacity: every
    assignment is computed whatever the routing, and all shapes are static
    (`moe_plan` gives them). With `cfg.d_shared` a shared expert's output
    is added for every token, once — times ``sigmoid(x·w_sg)``, float32,
    where the leaves hold `w_sg` (`three_pass`: its forward values to
    float32 accuracy, as in `apply_attention`; set by the one model with a
    `w1` / `w2` shared expert, off is the single-pass control).

    routing: `moe_route`'s result where the router read another tensor than
    the experts' input x; None routes on x.

    `cfg.held` / `cfg.first`: the leaves hold a share of the `n_experts` the
    router scores. The result is then the PART of the layer's output that
    these experts (and the shared one) give; the assignments to the others
    are sorted behind the held ones' and come out of the products zero —
    or, while the held ones' rows stay under a bound the shapes give
    (`moe_plan`'s `bounds`), are not touched at all (`_local_experts`).

    mesh: as in `apply_attention` — the grouped matmul is a Mosaic kernel on
    the TPU (`ops.grouped_matmul` decides, from `target.where(mesh)` and its
    tiles), so dispatch, experts and combine run as per-device code.
    Each device takes its share of the batch and the experts `ep` gives it
    (their `expert_mlp` slice under `tp`), computes its experts' part of
    its tokens' outputs, and the parts are summed over `ep` and `tp`.

    stats (float32 scalars but `counts`): `load_balance` = E · Σ_e f_e · P_e
    with f_e the share of a sequence's S·K assignments that went to expert
    e and P_e its mean router probability, taken a sequence at a time and
    averaged — so that, like the cross-entropy, a batch's value is the mean
    of its sequences' whatever `dp` does with them; `z` = mean
    logsumexp(logits)²; `counts` [E] the batch's assignments by expert;
    and, where a device holds fewer experts than are scored (a share, or
    `ep`), `compact`: the share of the devices on which this step's routing
    kept the held experts' rows under the bound, so that only a bounded
    prefix of the sorted assignments was worked on (`_local_experts`; 1.0
    or 0.0 on one device).
    """
    cd = compute_dtype
    E = cfg.n_experts
    experts = {k: v for k, v in params.items() if k not in _NOT_ROUTED}

    if routing is None:
        routing = moe_route(params, x, cfg)
    gate_vals, gate_idx, stats = routing

    local = functools.partial(_local_experts, n_experts=E, cd=cd, mesh=mesh,
                              activation=cfg.activation, gate=cfg.gate)
    if mesh is None:
        out, compact = local(x, gate_vals, gate_idx, experts, first=cfg.first)
    else:
        def per_device(x, gate_vals, gate_idx, experts):
            held = next(iter(experts.values())).shape[0]
            out, compact = local(
                x, gate_vals, gate_idx, experts,
                first=cfg.first + jax.lax.axis_index("ep") * held)
            out = jax.lax.psum(out, ("ep", "tp"))
            if compact is None:
                return out
            return out, jax.lax.pmean(compact.astype(jnp.float32),
                                      mesh.axis_names)

        logical = GATED_MOE_LOGICAL if "w_gate" in experts else MOE_LOGICAL
        tok = sh.spec("batch", "seq", None)
        # a device has a bound where it holds fewer experts than are scored
        stacked = next(iter(experts.values())).shape[0]
        bounded = stacked // dict(mesh.shape).get("ep", 1) < E
        out = jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(tok, tok, tok,
                      {k: sh.spec(*logical[k]) for k in experts}),
            out_specs=(tok, sh.spec()) if bounded else tok,
            check_vma=False)(x, gate_vals, gate_idx, experts)
        out, compact = out if bounded else (out, None)
    if compact is not None:
        stats = dict(stats, compact=compact.astype(jnp.float32))
    if cfg.d_shared:
        with jax.named_scope("shared_expert"):
            if "shared" in params:
                shared = apply_gated_mlp(
                    params["shared"], x, compute_dtype=cd,
                    three_pass=three_pass).astype(jnp.float32)
                if "w_sg" in params:
                    shared = shared * jax.nn.sigmoid(jnp.einsum(
                        "bsd,do->bso", x.astype(jnp.float32),
                        params["w_sg"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST))
                out = out + shared
            else:
                project = _project(cd, three_pass)
                hidden = _relu2(project("bsd,df->bsf", x,
                                        params["shared_w1"], jnp.float32))
                out = out + project("bsf,fd->bsd", hidden,
                                    params["shared_w2"], jnp.float32)
    return out.astype(x.dtype), stats


# ------------------------------------------------ a model file's two ends
def partition_specs(logical, rules=None):
    """A tree of logical axis names (tuples) as `PartitionSpec`s."""
    return jax.tree_util.tree_map(
        lambda names: sh.spec(*names, rules=rules), logical,
        is_leaf=lambda x: isinstance(x, tuple))


def refuse_tp(mesh, model: str, whole: str):
    """A model whose leaves (`whole`: which) every `tp` rank holds whole
    has no program for a mesh that splits them."""
    if mesh is not None and dict(mesh.shape).get("tp", 1) > 1:
        raise ValueError(
            f"{model}: {whole} are whole on every `tp` rank; a mesh with "
            f"tp > 1 is not supported (dp and ep meshes are)")


def embed(table, tokens, mesh=None):
    """tokens [B, S] -> their rows of `table` [V, d] as the float32 stream
    [B, S, d], split over the mesh as the layers keep it."""
    return sh.constrain(jnp.take(table, tokens, axis=0).astype(jnp.float32),
                        mesh, "batch", "seq", "embed")


def head_logits(x, scale, table, *, eps: float, compute_dtype, mesh=None,
                bias=None):
    """The stream behind the last layer -> logits [B, S, V] float32: an
    RMSNorm — with `bias`, a LayerNorm — and the product with `table` [V,
    d] (an untied head, or the embedding). Nothing behind the last layer is
    discontinuous: the head reads the stream in the compute dtype, as
    `gpt2.unembed` does."""
    x = x.astype(compute_dtype)
    x = (rms_norm(x, scale, eps) if bias is None
         else layer_norm(x, scale, bias, eps))
    logits = jax.lax.dot_general(
        x, table.astype(compute_dtype), (((2,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return sh.constrain(logits, mesh, "batch", "seq", "vocab")


def next_token_loss(logits, targets, mask=None):
    """Mean cross-entropy of `targets` [B, S] under `logits` [B, S, V]; with
    `mask` [B, S] over the positions it keeps (a second prediction whose
    last rows have no target)."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tl = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        return jnp.mean(lse - tl)
    return jnp.sum(jnp.where(mask, lse - tl, 0.0)) / jnp.sum(mask)


def share_loss(forward, params, batch, cfg: MoEConfig):
    """The `loss_fn` of a model that holds a share of its experts: batch
    {"tokens" [B, S+1] int32}, ids of this chip's vocabulary slice;
    `forward(params, tokens)` -> (logits [B, S, V], the routed layers'
    assignments by expert, whether each ran bounded). Mean next-token
    cross-entropy over the slice, and how the routing went
    (`share_metrics`)."""
    tokens, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    logits, counts, compact = forward(params, tokens)
    with jax.named_scope("loss_tail"):
        loss = next_token_loss(logits, targets)
    return loss, share_metrics(loss, counts, compact, tokens=tokens.size,
                               cfg=cfg)


def share_metrics(loss, counts, compact, *, tokens: int, cfg: MoEConfig):
    """A step's metrics where a share of the experts is held: `counts`
    [routed layers, E] the assignments by expert, `compact` [routed layers]
    whether each ran bounded. `moe_assignments` (tokens × top_k × routed
    layers), `moe_held` (those that chose an expert held here; the others'
    outputs are the absent chips') and `moe_compact` (the routed layers
    whose held rows stayed under the share's bound)."""
    return {
        "loss": loss,
        "moe_assignments": jnp.int32(tokens * cfg.top_k * counts.shape[0]),
        "moe_held": jnp.sum(counts[:, cfg.first:cfg.first + cfg.stacked]),
        "moe_compact": jnp.sum(compact),
    }
