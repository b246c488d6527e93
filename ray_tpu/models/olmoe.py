"""OLMoE (allenai/OLMoE-1B-7B; Muennighoff et al., arXiv:2409.02060): a
decoder whose every block's feed-forward is 64 small routed experts, 8 a
token, none shared.

Written from the public `olmoe` implementation's equations:

* block: ``x + Attn(RMSNorm(x))``, then ``x + MoE(RMSNorm(x))``; after the
  last block an RMSNorm and an untied head;
* attention: ``q = RMSNorm_q(x·Wq)``, ``k = RMSNorm_k(x·Wk)`` — each norm
  over the WHOLE projection (all heads together, before the split), with
  its own learned scale — ``v = x·Wv``; heads of ``head_dim``; q and k
  rotated (`layers.rope`); causal softmax attention; ``·Wo``;
* routed layer (`layers.apply_moe`): softmax over all experts in float32,
  the top-k with their probabilities as they are (not renormalised), SiLU-
  gated experts, dropless — `MoEConfig` at its defaults but for
  `norm_topk_prob`: softmax scores and no selection bias, scale 1, no
  shared expert, every expert the router scores held here (`held` None;
  the other settings are `models/nemotron_h.py`'s); its nine grouped
  products a step run on JAX's Pallas `gmm` / `tgmm` on a TPU
  (`ops/grouped_matmul.py`; XLA's grouped product elsewhere);
* loss: mean next-token cross-entropy + ``aux_loss_weight`` × load balance
  + ``z_loss_weight`` × router z-loss, both averaged over layers (the
  paper's 0.01 and 0.001).

Same shape as `models/gpt2.py`: a pure pytree model, bf16 matmuls over
float32 parameters, `lax.scan` over stacked blocks with optional remat
(`L.remat`: the flash kernel's outputs and the attention output are kept),
sharding by logical axes — it runs on any `dp` × `ep` mesh the rules give.
Norms, rotation, router, softmax and loss are float32, and so is the
RESIDUAL STREAM, which `gpt2.py` carries in bf16: the router's top-k is a
discontinuous function of it. Every bf16 rounding between the embedding and
the router moves its logits by ~2^-9 of their size, and a token whose 8th
and 9th probabilities lie closer than that is sent to another expert than
float32 arithmetic would send it. On the v5e at the published widths a bf16
stream did that to 4.7 % of the tokens (PERF.md §6, PR 29); the stream in
float32, q and k normed and rotated in float32 from the projections'
accumulators and rounded once, takes the roundings out that cost nothing to
take out (the matmuls' bf16 operands and the flash kernel's stay). Not extended to it: the per-device `tp` region of
`gpt2._tp_blocks` (with `tp` > 1 the attention's reductions are the
partitioner's), `sp` (`layers.rope` counts positions from 0) and the
pipelined forward.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models import layers as L
from ray_tpu.parallel import sharding as sh


@dataclasses.dataclass(frozen=True)
class OlmoeConfig:
    vocab_size: int = 50304
    max_seq: int = 4096
    n_layer: int = 16
    n_head: int = 16
    d_model: int = 2048
    d_expert: int = 1024           # width of one expert
    n_experts: int = 64
    top_k: int = 8
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 0.001
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False     # as `GPT2Config.remat`
    attention: str = "auto"  # auto | flash | reference

    @property
    def moe(self) -> L.MoEConfig:
        return L.MoEConfig(n_experts=self.n_experts, top_k=self.top_k,
                           norm_topk_prob=False)

    @property
    def n_params(self) -> int:
        d = self.d_model
        block = (4 * d * d + 2 * d            # attention, q and k norms
                 + d * self.n_experts + self.n_experts * 3 * d * self.d_expert
                 + 2 * d)                     # the block's two norms
        return 2 * self.vocab_size * d + self.n_layer * block + d


def olmoe_1b_7b():
    """The published model: 6,919,161,856 parameters, 1.28 B used a token."""
    return OlmoeConfig()


def olmoe_1b_7b_1l():
    """One whole layer — all 64 experts — with the embedding and the head at
    their published widths: what one 16 GB chip trains (625,616,896
    parameters, 10.0 GB of float32 parameters, gradients and AdamW state)."""
    return OlmoeConfig(n_layer=1)


def olmoe_tiny():
    """Test-sized."""
    return OlmoeConfig(vocab_size=256, max_seq=128, n_layer=2, n_head=4,
                       d_model=64, d_expert=32, n_experts=8, top_k=2)


# ------------------------------------------------------------------ params
def _init_block(key, cfg: OlmoeConfig):
    k1, k2 = jax.random.split(key)
    d, dtype = cfg.d_model, cfg.param_dtype
    heads = (cfg.n_head, d // cfg.n_head)
    return {
        "ln1": jnp.ones((d,), dtype),
        "attn": dict(L.init_attention(k1, d, cfg.n_head, dtype),
                     q_norm=jnp.ones(heads, dtype),
                     k_norm=jnp.ones(heads, dtype)),
        "ln2": jnp.ones((d,), dtype),
        "moe": L.init_moe(k2, d, cfg.d_expert, cfg.moe, dtype, gated=True),
    }


def init(key, cfg: OlmoeConfig):
    ke, kh, kb = jax.random.split(key, 3)

    def table(k):
        return (jax.random.normal(k, (cfg.vocab_size, cfg.d_model))
                * 0.02).astype(cfg.param_dtype)

    return {
        "wte": table(ke),
        "blocks": jax.vmap(lambda k: _init_block(k, cfg))(
            jax.random.split(kb, cfg.n_layer)),
        "ln_f": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "head": table(kh),
    }


def logical_axes(cfg: OlmoeConfig):
    """Logical axis names matching init()'s tree; stacked block leaves get a
    leading 'layers' axis. The q and k norms' scales are split with the
    heads they scale."""
    block = {
        "ln1": ("embed",),
        "attn": dict(L.ATTENTION_LOGICAL, q_norm=("heads", "head_dim"),
                     k_norm=("heads", "head_dim")),
        "ln2": ("embed",),
        "moe": dict(L.GATED_MOE_LOGICAL),
    }
    block = jax.tree_util.tree_map(
        lambda names: ("layers",) + tuple(names), block,
        is_leaf=lambda x: isinstance(x, tuple))
    return {"wte": ("vocab", "embed"), "blocks": block, "ln_f": ("embed",),
            "head": ("vocab", "embed")}


def partition_specs(cfg: OlmoeConfig, rules=None):
    return L.partition_specs(logical_axes(cfg), rules)


# ----------------------------------------------------------------- forward
def _qk_norm_and_rotate(attn, cfg: OlmoeConfig):
    def whole(x, scale):
        """RMSNorm over all heads' outputs together."""
        flat = x.reshape(x.shape[:2] + (-1,))
        return L.rms_norm(flat, scale.reshape(-1),
                          cfg.rms_norm_eps).reshape(x.shape)

    def fn(q, k):
        return (L.rope(whole(q, attn["q_norm"]), cfg.rope_theta),
                L.rope(whole(k, attn["k_norm"]), cfg.rope_theta))
    return fn


def _block_apply(block, x, cfg: OlmoeConfig, impl: str, mesh=None):
    cd = cfg.dtype
    with jax.named_scope("attention"):
        h = L.rms_norm(x, block["ln1"], cfg.rms_norm_eps)
        x = x + L.apply_attention(
            block["attn"], h, causal=True, impl=impl, compute_dtype=cd,
            mesh=mesh, qk_fn=_qk_norm_and_rotate(block["attn"], cfg),
            three_pass=True)
    h = L.rms_norm(x, block["ln2"], cfg.rms_norm_eps)
    m, stats = L.apply_moe(block["moe"], h, cfg.moe, compute_dtype=cd,
                           mesh=mesh)
    return x + m, stats


def forward(params, tokens, cfg: OlmoeConfig, mesh: Optional[Mesh] = None):
    """tokens [B, S] -> (logits [B, S, V] f32, router stats: `load_balance`
    and `z` averaged over layers, `counts` [L, E])."""
    impl = L.resolve_attention(cfg.attention, mesh)
    with jax.named_scope("embed"):
        x = L.embed(params["wte"], tokens, mesh)

    def body(x, block):
        x, stats = _block_apply(block, x, cfg, impl, mesh)
        return sh.constrain(x, mesh, "batch", "seq", "embed"), stats

    if cfg.remat:
        body = L.remat(body)
    with jax.named_scope("blocks"):
        x, stats = jax.lax.scan(body, x, params["blocks"])
    with jax.named_scope("loss_tail"):
        logits = L.head_logits(x, params["ln_f"], params["head"],
                               eps=cfg.rms_norm_eps, compute_dtype=cfg.dtype,
                               mesh=mesh)
    return logits, {"load_balance": jnp.mean(stats["load_balance"]),
                    "z": jnp.mean(stats["z"]), "counts": stats["counts"]}


def loss_fn(params, batch, cfg: OlmoeConfig,
            mesh: Optional[Mesh] = None) -> Tuple[jnp.ndarray, dict]:
    """batch: {"tokens" [B, S+1] int32}. Next-token cross-entropy plus the
    two router terms. `metrics["loss"]` is the cross-entropy alone; the
    rest says how the routing went: `moe_assignments` (tokens × top_k ×
    layers), `moe_dropped` (assignments no expert computed: none, the layer
    is dropless), `moe_load_max_over_mean` (the busiest expert's rows over
    the mean, the worst layer's)."""
    tokens, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    logits, stats = forward(params, tokens, cfg, mesh)
    with jax.named_scope("loss_tail"):
        loss = L.next_token_loss(logits, targets)
    total = (loss + cfg.aux_loss_weight * stats["load_balance"]
             + cfg.z_loss_weight * stats["z"])
    counts = stats["counts"]
    assignments = tokens.size * cfg.top_k * cfg.n_layer
    return total, {
        "loss": loss, "aux_loss": stats["load_balance"], "z_loss": stats["z"],
        "total_loss": total,
        "moe_assignments": jnp.int32(assignments),
        "moe_dropped": assignments - jnp.sum(counts),
        "moe_load_max_over_mean": jnp.max(
            jnp.max(counts, axis=-1) / jnp.mean(counts.astype(jnp.float32),
                                                axis=-1)),
    }
