"""Keye-VL-2.0 (Kwai-Keye/Keye-VL-2.0-30B-A3B), the language model: a
decoder whose every attention layer is a LEARNED SPARSE ATTENTION — a light
indexer scores every causal query-key pair, each query keeps the 2,048 keys
of the highest score, softmax attention runs over the kept keys alone, and
the indexer learns from the attention it steered through a loss of its own
— beside 32 query heads on 4 KV heads, rotary positions in three sectioned
streams, and 128 small SiLU-gated experts of which a token uses 8.

Written from the model's public ``config.json`` and the published form its
``sa_config`` names (DeepSeek-Sparse-Attention's lightning indexer); x the
float32 residual stream, every norm an RMSNorm at ``rms_norm_eps``, no bias
anywhere, every layer alike:

* ``h = x + Attn(N₁(x))``, ``out = h + moe(N₂(h))``;
* ``Attn`` (`layers.apply_sparse_attention` around `ops.sparse_attention`):
  ``q, k, v = a·W`` (32 heads of 128 on 4 KV heads), a per-head RMSNorm on q
  and on k, rotation in the half-split layout over 64 pairs, pair i turning
  by the position of ITS stream (``mrope_section`` [16, 24, 24]: pairs 0–15
  by stream 0, 16–39 by stream 1, 40–63 by stream 2; `forward`'s
  ``positions`` [3, B, S], by default the token's index in all three);
  the indexer on ``ā = stop_gradient(a)``: ``qI = ā·W_qI`` (16 heads of
  64), ``kI = LayerNorm(ā·W_kI)`` (one head), ``w = ā·W_wI · 16^−½ · 64^−½``,
  qI and kI rotated by stream 0 on all 64 columns, ``I[t, s] = Σ_j w[t, j] ·
  ReLU(qI[t, j]·kI[s])`` for s ≤ t; ``S_t`` the ``min(t + 1, 2048)`` keys
  of the largest ``I[t, ·]`` (ties to the lower index), not differentiated;
  ``o`` the softmax attention at 128^−½ over ``S_t``; ``·W_o``;
* the indexer's loss: ``p[t, ·] = stop_gradient(mean over the 32 heads of
  their attention over S_t)``, ``L_I = mean over layers and tokens of KL(p[t,
  ·] ‖ softmax over S_t of I[t, ·])``; the step's loss is ``L_LM +
  indexer_weight · L_I``. The trunk's leaves get their gradient from
  ``L_LM`` alone, the indexer's from ``L_I`` alone;
* ``moe`` (`layers.apply_moe`): ``softmax(n·W_g)`` in float32 over all 128,
  the 8 largest, their probabilities over their sum; SiLU-gated experts of
  width 768, dropless; no shared expert, no auxiliary loss;
* after the last layer an RMSNorm and an UNTIED head, mean next-token
  cross-entropy.

Parameters are PER-LAYER LEAVES (``params["layers"]`` a list, as
`models/lfm2.py`'s); the chip's share (`held`, `first`) as in
`models/nemotron_h.py`.

Precision: float32 parameters AND residual stream, bf16 matmul operands with
float32 accumulation, one pass; norms, rotation, the router and the
indexer's three projections float32 operands at the highest precision (both
decide a discontinuous choice), the indexer's forward scores float32
operands in THREE bf16 passes (`ops.sparse_attention.SCORE_PASSES`: the kept
set six passes keep, PERF.md §6, PR 56) and their backward one pass as every
other backward product; selection, softmax and both losses float32. Not extended to it: `tp` > 1 (the KV heads' and the indexer's
leaves are whole on every rank), `sp` (a kept set is a whole sequence's) and
the pipelined forward. The vision tower is not built: ``positions`` is where
its three streams would arrive.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models import layers as L
from ray_tpu.parallel import sharding as sh


@dataclasses.dataclass(frozen=True)
class KeyeVL2Config:
    vocab_size: int = 151936
    max_seq: int = 262144
    d_model: int = 2048
    n_layer: int = 48
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    index_heads: int = 16          # sa_config: indexer_num_heads
    index_dim: int = 64            # indexer_head_dim (one key head)
    topk: int = 2048               # keys a query keeps
    indexer_weight: float = 1.0    # of the indexer's loss in the step's
    d_expert: int = 768
    n_experts: int = 128           # what the router scores
    top_k: int = 8
    norm_eps: float = 1e-6
    held: Optional[int] = None     # experts this chip holds (None: all)
    first: int = 0                 # the first of them
    # the table's and the routers' draws at initialisation (`init`); every
    # other matrix 0.02
    embed_std: float = 0.02
    router_std: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False     # as `GPT2Config.remat`
    attention: str = "auto"  # auto | flash | reference

    @property
    def sparse(self) -> L.SparseConfig:
        return L.SparseConfig(
            n_head=self.n_head, n_kv_head=self.n_kv_head,
            head_dim=self.head_dim, index_heads=self.index_heads,
            index_dim=self.index_dim, topk=self.topk,
            rope_theta=self.rope_theta, mrope_section=self.mrope_section,
            eps=self.norm_eps)

    @property
    def moe(self) -> L.MoEConfig:
        return L.MoEConfig(
            n_experts=self.n_experts, top_k=self.top_k, norm_topk_prob=True,
            score="softmax", gate="silu", held=self.held, first=self.first)

    @property
    def n_params(self) -> int:
        d = self.d_model
        q, kv = self.n_head * self.head_dim, self.n_kv_head * self.head_dim
        indexer = (d * self.index_heads * self.index_dim + d * self.index_dim
                   + 2 * self.index_dim + d * self.index_heads)
        layer = (2 * d * q + 2 * d * kv + 2 * self.head_dim + indexer
                 + 2 * d + d * self.n_experts
                 + self.moe.stacked * 3 * d * self.d_expert)
        return 2 * self.vocab_size * d + d + self.n_layer * layer


def keye_vl2_30b_a3b():
    """The published language model: 48 layers, 128 experts a layer, the
    whole vocabulary."""
    return KeyeVL2Config()


def keye_vl2_30b_a3b_4l():
    """One chip's share of the published layers 0–3 where 8 chips share
    each layer: experts 0–15 of the 128 the router scores, the attention and
    its indexer whole, an eighth of the vocabulary (18,992 rows, padded to
    19,072); every width, the indexer's 16 × 64 and its 2,048 keys a query
    as published. 465,718,784 parameters: 7.45 GB of float32 parameters,
    gradients and AdamW state.

    The table and the routers are drawn as `qwen3_next.qwen3_next_80b_a3b_4l`
    draws them and for its reason (independent tokens teach a one-chip share
    to route towards the experts it holds; PERF.md §6, PR 56, has `moe_held`
    by step on this model's seeds)."""
    return KeyeVL2Config(vocab_size=19072, n_layer=4, held=16,
                         embed_std=8192.0, router_std=0.15)


def keye_vl2_tiny():
    """Test-sized: three layers, 4 query heads on 2 KV heads of 16 (8 pairs
    in sections 2 + 3 + 3), an indexer of 2 heads of 8 that keeps 24 keys a
    query, a share of the experts."""
    return KeyeVL2Config(
        vocab_size=256, max_seq=128, d_model=64, n_layer=3, n_head=4,
        n_kv_head=2, head_dim=16, rope_theta=10000.0, mrope_section=(2, 3, 3),
        index_heads=2, index_dim=8, topk=24, d_expert=32, n_experts=16,
        top_k=3, held=4)


# ------------------------------------------------------------------ params
def _init_layer(key, cfg: KeyeVL2Config):
    k_attn, k_ff = jax.random.split(key)
    d, dtype = cfg.d_model, cfg.param_dtype
    ff = L.init_moe(k_ff, d, cfg.d_expert, cfg.moe, dtype, gated=True)
    ff["wg"] = (ff["wg"] * (cfg.router_std / 0.02)).astype(dtype)
    return {"ln_attn": jnp.ones((d,), dtype),
            "attn": L.init_sparse_attention(k_attn, d, cfg.sparse, dtype),
            "ln_ff": jnp.ones((d,), dtype), "ff": ff}


def init(key, cfg: KeyeVL2Config):
    ke, kh, *kl = jax.random.split(key, 2 + cfg.n_layer)
    d, dtype = cfg.d_model, cfg.param_dtype
    return {
        "wte": (jax.random.normal(ke, (cfg.vocab_size, d))
                * cfg.embed_std).astype(dtype),
        "layers": [_init_layer(k, cfg) for k in kl],
        "ln_f": jnp.ones((d,), dtype),
        "head": L.init_dense(kh, (cfg.vocab_size, d), dtype=dtype),
    }


def logical_axes(cfg: KeyeVL2Config):
    """Logical axis names matching init()'s tree, a layer at a time."""
    def layer():
        attn = dict(L.SPARSE_ATTENTION_LOGICAL)
        attn["indexer"] = dict(attn["indexer"])
        return {"ln_attn": ("embed",), "attn": attn, "ln_ff": ("embed",),
                "ff": dict(L.GATED_MOE_LOGICAL)}
    return {"wte": ("vocab", "embed"),
            "layers": [layer() for _ in range(cfg.n_layer)],
            "ln_f": ("embed",), "head": ("vocab", "embed")}


def partition_specs(cfg: KeyeVL2Config, rules=None):
    return L.partition_specs(logical_axes(cfg), rules)


# ----------------------------------------------------------------- forward
def _attn_apply(x, layer, positions, *, cfg: KeyeVL2Config, impl: str,
                mesh=None, interpret: bool = False):
    """A layer's first half: x [B, S, d] float32 -> (``x + Attn(N₁(x))``,
    (the indexer's KL summed over the tokens, the pairs the layer keeps)),
    under ONE scope with its norm and its residual add."""
    with jax.named_scope("attention"):
        out, sparse = L.apply_sparse_attention(
            layer["attn"], L.rms_norm(x, layer["ln_attn"], cfg.norm_eps),
            cfg.sparse, positions=positions, impl=impl,
            compute_dtype=cfg.dtype, mesh=mesh, interpret=interpret)
        return x + out, sparse


def _moe_apply(h, layer, *, cfg: KeyeVL2Config, mesh=None):
    """A layer's second half: h -> (``h + moe(N₂(h))``, (the feed-forward's
    assignments by expert [E], whether its share ran bounded))."""
    with jax.named_scope("moe"):
        out, stats = L.apply_moe(
            layer["ff"], L.rms_norm(h, layer["ln_ff"], cfg.norm_eps),
            cfg.moe, compute_dtype=cfg.dtype, mesh=mesh)
        x = h + out
    return (sh.constrain(x, mesh, "batch", "seq", "embed"),
            (stats["counts"], stats.get("compact", jnp.float32(0))))


def forward(params, tokens, cfg: KeyeVL2Config, mesh: Optional[Mesh] = None,
            positions=None):
    """tokens [B, S] -> (logits [B, S, V] f32 over this chip's slice of the
    vocabulary, the layers' assignments by expert [n_layer, E]).
    `positions` [3, B, S]: the three streams of the sectioned rotation (a
    vision tower's; for text the token's index in all three, the default)."""
    return _forward(params, tokens, cfg, mesh, positions)[:2]


def _forward(params, tokens, cfg: KeyeVL2Config, mesh, positions=None,
             interpret: bool = False):
    """`forward`, and: by layer whether its share of the experts ran
    bounded; the indexer's KL summed over tokens, by layer; the query-key
    pairs kept, by layer."""
    L.refuse_tp(mesh, "keye_vl2", "the KV heads' and the indexer's leaves")
    impl = L.resolve_attention(cfg.attention, mesh)
    with jax.named_scope("embed"):
        x = L.embed(params["wte"], tokens, mesh)
    by_layer = []
    with jax.named_scope("blocks"):
        # each half of a layer its own checkpoint, as `models/qwen3_next.py`:
        # the sparse branch's [S, S] arrays are rebuilt after the
        # feed-forward's backward has run, not held through it
        keep = L.remat if cfg.remat else (lambda body: body)
        for layer in params["layers"]:
            h, sparse = keep(functools.partial(
                _attn_apply, cfg=cfg, impl=impl, mesh=mesh,
                interpret=interpret))(x, layer, positions)
            x, routed = keep(functools.partial(_moe_apply, cfg=cfg,
                                               mesh=mesh))(h, layer)
            by_layer.append((*routed, *sparse))
    with jax.named_scope("loss_tail"):
        logits = L.head_logits(x, params["ln_f"], params["head"],
                               eps=cfg.norm_eps, compute_dtype=cfg.dtype,
                               mesh=mesh)
    return (logits, *(jnp.stack(s) for s in zip(*by_layer)))


def loss_fn(params, batch, cfg: KeyeVL2Config, mesh: Optional[Mesh] = None,
            interpret: bool = False) -> Tuple[jnp.ndarray, dict]:
    """batch {"tokens" [B, S+1] int32 [, "positions" [3, B, S]]} -> (the
    step's loss ``L_LM + indexer_weight · L_I``, metrics): `share_metrics`'
    (``loss`` the step's), and ``lm_loss``, ``indexer_loss`` (the mean over
    layers and tokens of the indexer's KL) and ``sparse_selected`` (the
    query-key pairs kept, all layers)."""
    tokens, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    logits, counts, compact, kl, kept = _forward(
        params, tokens, cfg, mesh, batch.get("positions"), interpret)
    with jax.named_scope("loss_tail"):
        lm_loss = L.next_token_loss(logits, targets)
        indexer_loss = jnp.sum(kl) / (kl.shape[0] * tokens.size)
        loss = lm_loss + cfg.indexer_weight * indexer_loss
    return loss, dict(
        L.share_metrics(loss, counts, compact, tokens=tokens.size,
                        cfg=cfg.moe),
        lm_loss=lm_loss, indexer_loss=indexer_loss,
        sparse_selected=jnp.sum(kept))
