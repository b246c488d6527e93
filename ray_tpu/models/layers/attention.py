"""The four attentions: plain (grouped, windowed, gated), latent,
differential and sparse. Imports `core` alone of this package."""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.layers import core
from ray_tpu.ops import sparse_attention, target
from ray_tpu.parallel import sharding as sh
from ray_tpu.parallel.ring_attention import (reference_attention,
                                             ring_attention_local)


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
        "wq": core.init_dense(ks[0], (d_model, n_head, q_out), dtype=dtype),
        "wk": core.init_dense(ks[1], (d_model, kv, head_dim), dtype=dtype),
        "wv": core.init_dense(ks[2], (d_model, kv, head_dim), dtype=dtype),
        "wo": core.init_dense(ks[3], (n_head, head_dim, d_model), dtype=dtype),
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


def apply_attention(
    params: core.Params,
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
    project = core.project(cd, three_pass)
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
        o = core.flash_on(mesh, group > 1, causal=causal,
                          window=window)(q, k, v)
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
    return checkpoint_name(out, core.ATTENTION_OUT)


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
        q = {"wq": core.init_dense(ks[0], (d_model, H, cfg.qk_dim),
                                   dtype=dtype)}
    else:
        q = {"wq_a": core.init_dense(ks[0], (d_model, cfg.q_rank),
                                     dtype=dtype),
             "q_norm": jnp.ones((cfg.q_rank,), dtype),
             "wq_b": core.init_dense(ks[1], (cfg.q_rank, H, cfg.qk_dim),
                                 dtype=dtype)}
    return {
        **q,
        "wkv_a": core.init_dense(ks[2], (d_model, cfg.kv_rank + cfg.rope_dim),
                             dtype=dtype),
        "kv_norm": jnp.ones((cfg.kv_rank,), dtype),
        "wkv_b": core.init_dense(ks[3], (cfg.kv_rank, H,
                                     cfg.nope_dim + cfg.v_dim), dtype=dtype),
        "wo": core.init_dense(ks[4], (H, cfg.v_dim, d_model), dtype=dtype),
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


def apply_latent_attention(params: core.Params, x, cfg: LatentConfig, *,
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
    project = core.project(cd, False)
    turn = functools.partial(core.rope, theta=cfg.rope_theta,
                             interleaved=cfg.rope_interleaved) \
        if cfg.rotate else (lambda t: t)
    with jax.named_scope("latent_proj"):
        if cfg.q_rank is None:
            q = project("bsd,dhk->bshk", x, params["wq"], jnp.float32)
        else:
            c_q = core.rms_norm(project("bsd,dr->bsr", x, params["wq_a"],
                                   jnp.float32), params["q_norm"], eps)
            q = project("bsr,rhk->bshk", c_q, params["wq_b"], jnp.float32)
        q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])],
                            axis=-1).astype(cd)
        ckv = project("bsd,dr->bsr", x, params["wkv_a"], jnp.float32)
        c_kv = core.rms_norm(ckv[..., :cfg.kv_rank], params["kv_norm"], eps)
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
    return checkpoint_name(out, core.ATTENTION_OUT)


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
    lam = {f"lambda_{n}": core.init_dense(k, (cfg.head_dim,), 0.1, dtype)
           for n, k in zip(("q1", "k1", "q2", "k2"), k_lam)}
    return {
        f"w_{name}": core.init_dense(k_in, (d_model, width), dtype=dtype),
        f"b_{name}": jnp.zeros((width,), dtype),
        "w_o": core.init_dense(k_out, (cfg.q_dim, d_model), dtype=dtype),
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


def apply_diff_attention(params: core.Params, x, cfg: DiffAttnConfig, *,
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
    project = core.project(cd, False)
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
            attend = core.flash_on(mesh, group > 1, causal=True,
                                   window=window)
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
        o = core.rms_norm(a1.astype(f32) - lam * a2.astype(f32),
                     params["subln"].astype(f32), eps) * (1.0 - lam0)
    out = project("bse,ed->bsd", o.reshape(B, S, cfg.q_dim).astype(cd),
                  params["w_o"], f32) + params["b_o"].astype(f32)
    return checkpoint_name(out.astype(x.dtype), core.ATTENTION_OUT), kv


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
        indexer={"w_q": core.init_dense(kq, (d_model, J, E), dtype=dtype),
                 "w_k": core.init_dense(kk, (d_model, E), dtype=dtype),
                 "k_scale": jnp.ones((E,), dtype),
                 "k_bias": jnp.zeros((E,), dtype),
                 "w_w": core.init_dense(kw, (d_model, J), dtype=dtype)})


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


def apply_sparse_attention(params: core.Params, x, cfg: SparseConfig, *,
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
    project = core.project(cd, False)
    sections = cfg.mrope_section if positions is not None else None

    def turn(t, scale):
        return core.rope(core.rms_norm(t, scale.astype(f32), cfg.eps),
                         cfg.rope_theta, positions=positions,
                         sections=sections).astype(cd)

    q = turn(project("bsd,dhk->bshk", x, params["wq"], f32), params["q_norm"])
    k = turn(project("bsd,dhk->bshk", x, params["wk"], f32), params["k_norm"])
    v = project("bsd,dhk->bshk", x, params["wv"], None)

    with jax.named_scope("indexer"):
        ix = params["indexer"]
        a = jax.lax.stop_gradient(x).astype(f32)
        high = functools.partial(jnp.einsum,
                                 precision=jax.lax.Precision.HIGHEST)
        first = None if positions is None else positions[0]
        qi = core.rope(high("bsd,dje->bsje", a, ix["w_q"].astype(f32)),
                  cfg.rope_theta, positions=first)
        ki = core.layer_norm(high("bsd,de->bse", a, ix["w_k"].astype(f32)),
                        ix["k_scale"].astype(f32), ix["k_bias"].astype(f32),
                        cfg.eps)
        ki = core.rope(ki[:, :, None, :], cfg.rope_theta,
                       positions=first)[:, :, 0]
        w = high("bsd,dj->bsj", a, ix["w_w"].astype(f32)) * (
            cfg.index_heads ** -0.5 * cfg.index_dim ** -0.5)

    attend = functools.partial(_sparse_core, topk=cfg.topk,
                               kernel=impl == "flash", interpret=interpret,
                               compute_dtype=cd)
    if mesh is not None:
        # per-device code, a device its own batch rows: a Mosaic kernel
        # cannot be partitioned automatically, and nothing here crosses rows
        rows = [sh.spec("batch", *([None] * (t.ndim - 1)))
                for t in (q, k, v, qi, ki, w)]
        out = (rows[0], sh.spec("batch"), sh.spec("batch"))
        attend = jax.shard_map(attend, mesh=mesh, in_specs=tuple(rows),
                               out_specs=out, check_vma=False)
    o, kl, kept = attend(q, k, v, qi, ki, w)
    out = project("bshk,hkd->bsd", o.astype(cd), params["wo"], x.dtype)
    return (checkpoint_name(out, core.ATTENTION_OUT),
            (jnp.sum(kl), jnp.sum(kept)))
