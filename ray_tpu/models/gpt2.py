"""GPT-2 family — the flagship model (BASELINE.md north star:
GPT-2-1.5B ≥40% MFU on v5e-64).

Pure-JAX pytree model, TPU-first: bf16 compute / f32 params, einsum-only
(MXU), `lax.scan` over layers (one compiled block), optional remat that
keeps the flash kernel's outputs and the attention sub-layer's (`L.remat`),
sharding by logical axes (parallel/sharding.py) so the same forward runs
dp/tp/sp/ep on any mesh; pipeline-parallel forward via parallel/pipeline.py.

Where the `tp` reductions come from: not from the partitioner. On a mesh
with `tp` > 1 the dense layer loop of `forward` is per-device code
(`_tp_blocks`, one `jax.shard_map` around the scan): the model issues each
block's reductions itself, as neighbour exchanges (`layers.exchange_sum`:
`ppermute` over `tp` + add — one exchange of the whole partial at `tp` 2, a
reduce-scatter and an all-gather of half-chunks on both ring directions
beyond) on two independent half-batch chains, because the TPU compiler runs
a `collective-permute` beside the other chain's matmuls and blocks on an
`all-reduce`. `tp_exchange_plan` counts the messages and their bytes from
shapes. Outside the loop (embedding, vocabulary projection, loss), with
`tp` == 1 and in `forward_pipelined`, collectives are still what the
sharding rules imply; a routed block (`layers.apply_moe`) sums its experts'
parts over `ep` and `tp` itself.

Equivalent reference workload: Ray Train GPT-2 fine-tune
(/root/reference/release/train_tests/, BASELINE.json configs); the model
itself is new — the reference contains no model implementations, it wraps
torch. Architecture follows the public GPT-2 description (learned
positional embeddings, pre-LN blocks, GELU MLP, tied LM head).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models import layers as L
from ray_tpu.parallel import sharding as sh
from ray_tpu.parallel.pipeline import gpipe, microbatch, unmicrobatch


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50304  # 50257 rounded up to a 128 multiple (MXU tiling)
    max_seq: int = 1024
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: int = 0  # 0 -> 4 * d_model
    moe: Optional[L.MoEConfig] = None  # if set, every block's MLP is routed
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # recompute each block in the backward pass from its input and the few
    # values `L.remat` keeps (flash `o`/`lse`, the attention output)
    remat: bool = True
    attention: str = "auto"  # auto | flash | reference | ring
    aux_loss_weight: float = 0.01

    @property
    def ff(self) -> int:
        return self.d_ff or 4 * self.d_model

    @property
    def n_params(self) -> int:
        """Parameter count (for MFU math)."""
        d, f, l, v = self.d_model, self.ff, self.n_layer, self.vocab_size
        per_block = 4 * d * d + (2 * d * f + d + f) + 4 * d  # attn + mlp + lns
        if self.moe:
            per_block += self.moe.n_experts * 2 * d * f - (2 * d * f + d + f)
        return v * d + self.max_seq * d + l * per_block + 2 * d


# Presets (public GPT-2 sizes).
def gpt2_small():
    return GPT2Config(n_layer=12, n_head=12, d_model=768)


def gpt2_medium():
    return GPT2Config(n_layer=24, n_head=16, d_model=1024)


def gpt2_large():
    return GPT2Config(n_layer=36, n_head=20, d_model=1280)


def gpt2_xl():
    """The 1.5B north-star config."""
    return GPT2Config(n_layer=48, n_head=25, d_model=1600)


def gpt2_tiny():
    """Test-sized config."""
    return GPT2Config(
        vocab_size=256, max_seq=128, n_layer=2, n_head=4, d_model=64, remat=False
    )


# ------------------------------------------------------------------ params
def _init_block(key, cfg: GPT2Config):
    k1, k2 = jax.random.split(key)
    block = {
        "ln1": {
            "scale": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "bias": jnp.zeros((cfg.d_model,), cfg.param_dtype),
        },
        "attn": L.init_attention(k1, cfg.d_model, cfg.n_head, cfg.param_dtype),
        "ln2": {
            "scale": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "bias": jnp.zeros((cfg.d_model,), cfg.param_dtype),
        },
    }
    if cfg.moe:
        block["moe"] = L.init_moe(k2, cfg.d_model, cfg.ff, cfg.moe, cfg.param_dtype)
    else:
        block["mlp"] = L.init_mlp(k2, cfg.d_model, cfg.ff, cfg.param_dtype)
    return block


def init(key, cfg: GPT2Config):
    ke, kp, kb = jax.random.split(key, 3)
    blocks = jax.vmap(lambda k: _init_block(k, cfg))(jax.random.split(kb, cfg.n_layer))
    return {
        "wte": (jax.random.normal(ke, (cfg.vocab_size, cfg.d_model)) * 0.02).astype(
            cfg.param_dtype
        ),
        "wpe": (jax.random.normal(kp, (cfg.max_seq, cfg.d_model)) * 0.01).astype(
            cfg.param_dtype
        ),
        "blocks": blocks,
        "ln_f": {
            "scale": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "bias": jnp.zeros((cfg.d_model,), cfg.param_dtype),
        },
    }


def logical_axes(cfg: GPT2Config):
    """Pytree of logical-axis names matching init()'s structure. Stacked
    block leaves get a leading 'layers' axis (mapped to pp only by the
    pipelined path, which re-chunks explicitly)."""
    ln = {"scale": ("embed",), "bias": ("embed",)}
    block = {
        "ln1": ln,
        "attn": dict(L.ATTENTION_LOGICAL),
        "ln2": ln,
    }
    if cfg.moe:
        block["moe"] = dict(L.MOE_LOGICAL)
    else:
        block["mlp"] = dict(L.MLP_LOGICAL)
    block = jax.tree_util.tree_map(
        lambda names: ("layers",) + tuple(names),
        block,
        is_leaf=lambda x: isinstance(x, tuple),
    )
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "blocks": block,
        "ln_f": ln,
    }


def partition_specs(cfg: GPT2Config, rules=None):
    return jax.tree_util.tree_map(
        lambda names: sh.spec(*names, rules=rules),
        logical_axes(cfg),
        is_leaf=lambda x: isinstance(x, tuple),
    )


# ----------------------------------------------------------------- forward
def _block_apply(block, x, cfg: GPT2Config, impl: str, mesh=None,
                 reduce=None):
    """reduce: given by `_tp_blocks`, where `block` is this device's shard
    and x its share of the batch; None wherever the partitioner (or nobody)
    splits the weights."""
    cd = cfg.dtype
    with jax.named_scope("attention"):
        h = L.layer_norm(x, block["ln1"]["scale"], block["ln1"]["bias"])
        x = x + L.apply_attention(block["attn"], h, causal=True, impl=impl,
                                  compute_dtype=cd, mesh=mesh, reduce=reduce)
    with jax.named_scope("mlp"):
        h = L.layer_norm(x, block["ln2"]["scale"], block["ln2"]["bias"])
        if cfg.moe:
            m, stats = L.apply_moe(block["moe"], h, cfg.moe,
                                   compute_dtype=cd, mesh=mesh)
            aux = stats["load_balance"]
        else:
            m = L.apply_mlp(block["mlp"], h, compute_dtype=cd, reduce=reduce)
            aux = jnp.float32(0)
        return x + m, aux


def _tp_size(cfg: GPT2Config, mesh: Optional[Mesh]) -> int:
    """Over how many devices the model itself reduces a block's row-parallel
    outputs: the mesh's `tp` size for a dense stack; 1 (the partitioner
    reduces, or nobody has to) for no mesh and for MoE."""
    if mesh is None or cfg.moe:
        return 1
    return sh.axis_size(mesh, "tp")


def _chains(local_batch: int) -> int:
    """Independent chains a block runs its local batch as: two halves, so
    that one half's exchange is in flight beside the other half's matmuls;
    one where the batch does not halve (the exchange is then exposed)."""
    return 2 if local_batch % 2 == 0 else 1


class ExchangePlan(NamedTuple):
    """`tp_exchange_plan`'s answer, for one device and one training step."""
    messages: int           # `ppermute`s the layer loops issue
    bytes: int              # what they carry out of the device, in all
    chains: int             # independent chains a block runs its batch as
    bytes_a_direction: int  # the most any one directed ring link carries


def tp_exchange_plan(cfg: GPT2Config, mesh: Optional[Mesh], local_batch: int,
                     seq: Optional[int] = None) -> ExchangePlan:
    """(messages, bytes, chains, bytes a ring direction) of one training
    step's layer loops on one device: how often `_tp_blocks` engages and in
    which form, from shapes alone.

    A chain reduces twice a layer forward (attention and MLP outputs) and
    twice backward (their cotangents), with remat or without: the
    checkpoint keeps the reduced attention output (`L.remat`), so the
    recompute issues no exchange for it, and the recomputed MLP output is
    dead code. A reduction of the chain's [batch, seq, d_model] activation
    over `tp` devices takes the form `L.exchange_form` reads from the
    shapes: tp − 1 messages of the whole activation, all to the next rank
    (`tp` 2, where both directed links of the pair carry one; or rows that
    do not divide by 2 · tp), or 4 (tp − 1) messages of 1 / (2 · tp) of it,
    half of them to each neighbour. `seq` is the global sequence length
    (default `cfg.max_seq`)."""
    tp = _tp_size(cfg, mesh)
    if tp == 1:
        return ExchangePlan(0, 0, 1, 0)
    chains = _chains(local_batch)
    reductions = cfg.n_layer * chains * 4
    shape = (local_batch // chains,
             (seq or cfg.max_seq) // sh.axis_size(mesh, "sp"), cfg.d_model)
    size = math.prod(shape) * jnp.dtype(cfg.dtype).itemsize
    whole = L.exchange_form(tp, shape) == "whole"
    messages = reductions * (tp - 1) * (1 if whole else 4)
    carried = messages * (size if whole else size // (2 * tp))
    return ExchangePlan(messages, carried, chains,
                        carried if whole else carried // 2)


def remat_saved_plan(cfg: GPT2Config, mesh: Optional[Mesh], local_batch: int,
                     seq: Optional[int] = None, *, flash: bool = True):
    """{name: bytes} of what one layer's checkpoint keeps on one device
    besides the block's input (`L.remat`), from shapes alone: the attention
    sub-layer's output, whole on every `tp` device, and, where the flash
    kernels run (`flash`), their `o` and float32 `lse` over the device's
    heads. Times `cfg.n_layer` it is what remat no longer saves of the
    step's memory. `seq` as in `tp_exchange_plan`."""
    sp, tp = (1, 1) if mesh is None else (sh.axis_size(mesh, "sp"),
                                          sh.axis_size(mesh, "tp"))
    rows = local_batch * ((seq or cfg.max_seq) // sp)
    width = cfg.d_model // tp       # the device's heads × head_dim
    item = jnp.dtype(cfg.dtype).itemsize
    plan = {L.ATTENTION_OUT: rows * cfg.d_model * item}
    if flash:
        from ray_tpu.ops.flash_attention import RESIDUAL_NAMES

        o, lse = RESIDUAL_NAMES
        plan[o] = rows * width * item
        plan[lse] = rows * (cfg.n_head // tp) * 4
    return plan


def _tp_blocks(blocks, x, cfg: GPT2Config, impl: str, mesh: Mesh):
    """The layer loop where `tp` > 1, as per-device code over the whole mesh.

    Each device holds its head and MLP shard of every block and its share of
    the batch (and, under `sp`, of the sequence). The reduction after each
    row-parallel matmul (`wo`, `w2`) is `L.exchange_sum` — issued here, not
    implied by the sharding rules — and the local batch runs as two
    independent half-batch chains, so the compiler has the other half's
    matmuls and flash call to run while a half's exchange is in flight.
    Column-parallel inputs need nothing forward. Backward is the same
    exchange on each reduced output's cotangent (JAX's own transpose of the
    whole-partial form, `_ring_sum`'s rule beyond `tp` 2), so a
    device carries its share of the residual stream's cotangent, and the
    shares (and the gradients of what `tp` replicates) are summed once, at
    the region's edge, with the `dp` sum of the stacked weight gradients.
    Under remat the checkpoint keeps each chain's reduced attention output
    (`L.remat`), so what the backward pass recomputes holds no exchange."""
    reduce = functools.partial(L.exchange_sum, axis_name="tp")
    if impl == "ring":
        impl = "ring_local"    # `sp` is manual here too

    def local(blocks, x):
        chains = tuple(jnp.split(x, _chains(x.shape[0])))

        def body(chains, block):
            return tuple(_block_apply(block, c, cfg, impl, reduce=reduce)[0]
                         for c in chains), None

        if cfg.remat:
            body = L.remat(body)
        chains, _ = jax.lax.scan(body, chains, blocks)
        return jnp.concatenate(chains)

    x_spec = sh.spec("batch", "seq", "embed")
    with jax.named_scope("blocks"):
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(partition_specs(cfg)["blocks"], x_spec),
            out_specs=x_spec, check_vma=False)(blocks, x)


def embed(params, tokens, cfg: GPT2Config):
    S = tokens.shape[1]
    with jax.named_scope("embed"):
        x = jnp.take(params["wte"], tokens, axis=0) + params["wpe"][:S]
        return x.astype(cfg.dtype)


def unembed(params, x, cfg: GPT2Config):
    """Vocab projection in bf16 with f32 MXU accumulation. The earlier f32
    einsum + log_softmax loss tail cost ~100ms/step at batch 16 on v5e (vs
    34ms this way, measured) — the f32 [B,S,V] matmul runs far off MXU peak
    and log_softmax materializes a second 3.3 GB tensor."""
    with jax.named_scope("loss_tail"):
        x = L.layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
        return jax.lax.dot_general(
            x.astype(cfg.dtype), params["wte"].astype(cfg.dtype),
            (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )


def forward(params, tokens, cfg: GPT2Config, mesh: Optional[Mesh] = None):
    """tokens [B, S] -> (logits [B, S, V] f32, moe aux loss scalar)."""
    impl = L.resolve_attention(cfg.attention, mesh)
    x = embed(params, tokens, cfg)
    if mesh is not None:
        x = sh.constrain(x, mesh, "batch", "seq", "embed")

    if _tp_size(cfg, mesh) > 1:
        x, aux = _tp_blocks(params["blocks"], x, cfg, impl, mesh), jnp.float32(0)
    else:
        def body(carry, block):
            x, aux = carry
            x, a = _block_apply(block, x, cfg, impl, mesh)
            if mesh is not None:
                x = sh.constrain(x, mesh, "batch", "seq", "embed")
            return (x, aux + a), None

        if cfg.remat:
            body = L.remat(body)
        with jax.named_scope("blocks"):
            (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0)),
                                       params["blocks"])
    logits = unembed(params, x, cfg)
    if mesh is not None:
        logits = sh.constrain(logits, mesh, "batch", "seq", "vocab")
    return logits, aux / cfg.n_layer


def forward_pipelined(
    params,
    tokens,
    cfg: GPT2Config,
    mesh: Mesh,
    *,
    n_microbatches: int = 4,
):
    """Pipeline-parallel forward: block stack split into pp stages,
    embedding/unembedding outside the pipeline (they are cheap and
    tp/dp-sharded). Attention inside stages is flash/reference (see
    pipeline.py for the sp+pp limitation)."""
    n_pp = dict(mesh.shape).get("pp", 1)
    if cfg.n_layer % n_pp:
        raise ValueError(f"n_layer={cfg.n_layer} not divisible by pp={n_pp}")
    if cfg.moe is not None:
        # The GPipe carry is activations-only; the MoE aux loss would be
        # silently dropped (router collapse with no signal). Refuse loudly
        # until aux is threaded through the pipeline carry.
        raise NotImplementedError(
            "pipelined forward does not yet propagate the MoE aux loss; "
            "use pp=1 with MoE or a dense (non-MoE) config with pp>1"
        )
    impl = L.resolve_attention(cfg.attention, mesh)
    # pp×sp composition: ONE flat manual region over {pp, sp} with the
    # per-shard ring attention inside stages (a nested sp-shard_map in the
    # pp scan does not differentiate — DuplicateSpecError in transpose).
    if impl == "ring":
        impl = "ring_local"
        manual_axes = ("sp",)
        from jax.sharding import PartitionSpec as _P

        mb_spec = _P(None, None, "sp", None)   # [M, B_mb, S, D]
    elif dict(mesh.shape).get("sp", 1) > 1:
        raise ValueError(
            f"attention={cfg.attention!r} attends within one sequence shard "
            f"only; a mesh with sp>1 needs attention='ring' (or 'auto')")
    else:
        manual_axes = ()
        mb_spec = None
    per_stage = cfg.n_layer // n_pp
    staged = jax.tree_util.tree_map(
        lambda leaf: leaf.reshape((n_pp, per_stage) + leaf.shape[1:]),
        params["blocks"],
    )

    def stage_fn(stage_blocks, x):
        def body(x, block):
            y, _ = _block_apply(block, x, cfg, impl)
            return y, None

        if cfg.remat:
            body = L.remat(body)
        x, _ = jax.lax.scan(body, x, stage_blocks)
        return x

    x = embed(params, tokens, cfg)
    x = sh.constrain(x, mesh, "batch", "seq", "embed")
    mb = microbatch(x, n_microbatches)
    with jax.named_scope("blocks"):
        y = gpipe(stage_fn, staged, mb, mesh, manual_axes=manual_axes,
                  mb_spec=mb_spec)
    x = unmicrobatch(y)
    logits = unembed(params, x, cfg)
    return sh.constrain(logits, mesh, "batch", "seq", "vocab"), jnp.float32(0)


def loss_fn(
    params,
    batch,
    cfg: GPT2Config,
    mesh: Optional[Mesh] = None,
    *,
    pipelined: bool = False,
    n_microbatches: int = 4,
) -> Tuple[jnp.ndarray, dict]:
    """batch: {"tokens" [B,S+1] int32}. Next-token cross-entropy."""
    tokens = batch["tokens"][:, :-1]
    targets = batch["tokens"][:, 1:]
    if pipelined:
        logits, aux = forward_pipelined(
            params, tokens, cfg, mesh, n_microbatches=n_microbatches
        )
    else:
        logits, aux = forward(params, tokens, cfg, mesh)
    # -log p(target) = logsumexp(logits) - logits[target]; computed without
    # materializing log_softmax's full [B,S,V] output (HBM-bandwidth win).
    with jax.named_scope("loss_tail"):
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, targets[..., None],
                                 axis=-1)[..., 0]
        loss = jnp.mean(lse - tl)
    total = loss + cfg.aux_loss_weight * aux
    return total, {"loss": loss, "aux_loss": aux, "total_loss": total}
