"""A model file's two ends: embedding, head, loss, and the loss and metrics
of a model that holds a share of its experts. Imports `core` and `moe`."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.models.layers import core, moe
from ray_tpu.parallel import sharding as sh


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
    x = (core.rms_norm(x, scale, eps) if bias is None
         else core.layer_norm(x, scale, bias, eps))
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


def share_loss(forward, params, batch, cfg: moe.MoEConfig):
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


def share_metrics(loss, counts, compact, *, tokens: int, cfg: moe.MoEConfig):
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
