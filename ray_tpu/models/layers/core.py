"""What every layer shares: initialisation, the three norms with their
lean VJPs, rotary positions, the `tp` exchange, a layer's products on the
MXU, the flash kernels under a mesh, the layer loop's checkpoint and the
names it keeps, and a logical tree as `PartitionSpec`s. Imports nothing of
this package."""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import mxu, sparse_attention
from ray_tpu.parallel import sharding as sh

Params = Dict[str, Any]


def init_dense(key, shape, scale=0.02, dtype=jnp.float32):
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
    exchanges. MUST run in per-device code (`jax.shard_map`,
    `check_vma=False`).

    This is the `tp` reduction of a row-parallel matmul, written as the one
    collective the TPU compiler runs asynchronously: a `collective-permute`
    is a start/done pair with compute scheduled between, where an
    `all-reduce` (what `psum` or the partitioner gives) blocks. The sum is
    taken in the partials' dtype, size − 1 adds an element, as the
    all-reduce took it.

    One algorithm whose form follows the ring's length (`exchange_form`, from
    the axis' size and the partial's shape):

    * size 2: `p + ppermute(p)`, one exchange of the whole partial each way.
      Its transpose is the same exchange on the cotangent.
    * beyond, where the partial's rows (everything but the last axis)
      divide by 2 · size: a reduce-scatter and an all-gather on BOTH ring
      directions (`_ring_sum`). The rows are cut into `size` chunks of two
      halves; for size − 1 steps a rank hands the running sum of one
      chunk's first half to rank + 1 and of another chunk's second half to
      rank − 1 and adds its own partial of what arrives, then for size − 1
      steps the finished half-chunks go round the same two ways and are
      written in place. 2 (size − 1) messages of 1 / (2 · size) of the
      partial each way: 1.5 partials leave a device at size 4, 0.75 on
      each directed link, where a ring of whole partials sends 3 on one.
      Every element is summed once, in ring order from its chunk's first
      rank, so replicas agree to the bit. The backward pass is the same
      exchange on the cotangent.
    * beyond, other shapes: size − 1 hops of the whole partial to the next
      rank; each device adds in ring order from its own rank, so replicas
      agree to rounding, not to the bit."""
    n = jax.lax.axis_size(axis_name)
    if exchange_form(n, partial.shape) == "ring_halves":
        return _ring_sum(partial, axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    total = moving = partial
    for _ in range(n - 1):
        moving = jax.lax.ppermute(moving, axis_name, perm)
        total = total + moving
    return total


def exchange_form(size: int, shape) -> str:
    """Which form `exchange_sum` takes over `size` devices for a partial of
    `shape`: "whole" partials round one way (at size 2 the two forms send
    the same bytes a direction and this one takes one step, not two), or
    "ring_halves", half-chunks round both ways."""
    rows = math.prod(shape[:-1])
    return "ring_halves" if size > 2 and rows % (2 * size) == 0 else "whole"


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _ring_sum(partial, axis_name):
    """`exchange_sum`'s reduce-scatter and all-gather of half-chunks. Piece
    2 c + h of the rows is half h of chunk c; half 0 travels to rank + 1,
    half 1 to rank − 1. Which piece a rank handles at a step hangs on its
    rank (`lax.axis_index`), so the pieces are dynamic slices — whose
    transposes would be updates into zeros of the whole partial; the
    backward rule is this function on the cotangent instead, which is what
    the transpose computes."""
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    pieces = partial.reshape(2 * n, -1, partial.shape[-1])
    # (half, permutation, the way the chunk index walks from `rank`)
    ways = [(0, [(i, (i + 1) % n) for i in range(n)], -1),
            (1, [(i, (i - 1) % n) for i in range(n)], +1)]

    def index(half, walk, steps):
        return 2 * ((rank + walk * steps) % n) + half

    moving = [jax.lax.dynamic_index_in_dim(pieces, index(half, walk, 0),
                                           keepdims=False)
              for half, _, walk in ways]
    for step in range(1, n):                             # reduce-scatter
        for half, perm, walk in ways:
            moving[half] = jax.lax.ppermute(
                moving[half], axis_name, perm
            ) + jax.lax.dynamic_index_in_dim(
                pieces, index(half, walk, step), keepdims=False)
    # every read of `pieces` ahead of the first write: the finished
    # half-chunks are then written into the partial's own buffer, where the
    # compiler otherwise copies the whole partial for them
    pieces, moving = jax.lax.optimization_barrier((pieces, moving))
    for step in range(n - 1, 2 * n - 1):                 # all-gather
        for half, perm, walk in ways:
            pieces = jax.lax.dynamic_update_index_in_dim(
                pieces, moving[half], index(half, walk, step), 0)
            if step < 2 * n - 2:
                moving[half] = jax.lax.ppermute(moving[half], axis_name, perm)
    return pieces.reshape(partial.shape)


_ring_sum.defvjp(
    lambda partial, axis_name: (_ring_sum(partial, axis_name), None),
    lambda axis_name, _, cotangent: (_ring_sum(cotangent, axis_name),))


# `checkpoint_name` of the attention sub-layer's output, [B, S, d_model] as
# it is added to the residual stream (after `reduce`).
ATTENTION_OUT = "attention_out"
# `checkpoint_name` of a product whose forward value took three bf16 passes
# (`project`): three passes to rebuild, so `remat` keeps it.
THREE_PASS_OUT = "three_pass_out"
# `checkpoint_name` of what a routed layer decides once a step: the router's
# float32 logits (six bf16 passes to rebuild), the top-k's choices, and the
# sort of the assignments (`order`, `inverse`, `sizes`): all of it integers
# and one `[tokens, n_experts]` table, and sorts and scatters to rebuild
# (`moe.moe_route`, `moe._local_experts`; `moe.routing_plan` gives the bytes).
ROUTING = "routing"


def project(cd, three_pass: bool):
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


def flash_on(mesh, grouped: bool, **kw):
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
      accuracy by three bf16 passes (`project` with `three_pass`: a mixer's
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



def partition_specs(logical, rules=None):
    """A tree of logical axis names (tuples) as `PartitionSpec`s."""
    return jax.tree_util.tree_map(
        lambda names: sh.spec(*names, rules=rules), logical,
        is_leaf=lambda x: isinstance(x, tuple))
