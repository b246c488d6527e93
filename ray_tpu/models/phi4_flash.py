"""Phi-4-mini-flash-reasoning (microsoft/Phi-4-mini-flash-reasoning,
`model_type: phi4flash`): the SambaY decoder-hybrid-decoder of
arXiv:2507.06607 with differential attention (arXiv:2410.05258). A
SELF-DECODER of Mamba-1 mixers alternating with differential attention
behind a window of 512, ended by ONE Mamba-1 layer whose scan result is the
model's MEMORY and ONE full-attention layer whose K and V are the model's
only long cache; then a CROSS-DECODER whose mixers compute neither: a gated
memory unit gates the memory with its own stream, a cross-attention layer
projects q alone and reads the shared K and V. The first model here whose
layers are not independent given the stream.

Written from the model's public ``config.json`` and the family's public
modelling code (x the float32 residual stream; every norm a LayerNorm with
bias at ``layer_norm_eps``; no positional encoding anywhere). Layer i of
the published n = 32: ``h = x + mixer_i(LN(x))``, ``out = h + W₂·(SiLU(g) ⊙
u)`` with ``[g | u] = LN(h)·W₁`` (no bias); after the last layer a LayerNorm
and logits on the TIED table. The mixer by published index (`kind_of`):

* even i ≤ n/2 — Mamba-1 (`layers.apply_mamba1` over `ops.selective_scan`);
  layer n/2 also hands on its scan result before the gate, the memory;
* odd i ≤ n/2 + 1 — differential attention (`layers.apply_diff_attention`),
  window 512 for i < n/2, full causal at i = n/2 + 1, which also hands on
  its k and v;
* even i ≥ n/2 + 2 — a gated memory unit (`layers.apply_gmu`) on the memory;
* odd i ≥ n/2 + 3 — cross attention: the differential form with its own λ
  vectors and sub-norm, q of its own, the handed k and v, full causal.

Parameters are PER-LAYER LEAVES walked by declared kind, as `models/lfm2.py`'s.
A configuration may hold a run of the published layers (`first_layer`,
`n_layer`): a pipeline stage. A run with a cross-decoder layer has to hold
the layer that feeds it.

Under `remat` each layer is one `layers.remat` checkpoint; the memory and
the shared k, v are checkpoints' OUTPUTS and later checkpoints' inputs, so
they are kept once and their cotangents arrive from every reader.

Precision: float32 parameters AND residual stream, bf16 matmul operands with
float32 accumulation, one pass; the scan's decays, state and readout, Δ,
both softmaxes' statistics, λ, the sub-norm, LayerNorms and loss float32.
Not extended to it: `tp` > 1 (10 differential K/V pairs divide by neither 4
nor 8; the mixers' leaves are whole on every rank), `sp` and the ring paths
(one head width, no window; the conv and the scan's state would have to
cross a shard's edge) and the pipelined forward.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models import layers as L
from ray_tpu.parallel import sharding as sh

MAMBA, WINDOW, FULL = "mamba", "window_attention", "full_attention"
GMU, CROSS = "gmu", "cross_attention"


def kind_of(i: int, n: int = 32) -> str:
    """The mixer of published layer i of n."""
    half = n // 2
    if i > half + 1:
        return CROSS if i % 2 else GMU
    if i % 2 == 0:
        return MAMBA
    return WINDOW if i < half else FULL


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    max_seq: int = 262144
    d_model: int = 2560
    n_head: int = 40
    n_kv_head: int = 20
    head_dim: int = 64
    d_ff: int = 10240
    window: int = 512
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    n_published: int = 32          # what `kind_of` and λ_init count in
    first_layer: int = 0           # the published index of the first here
    n_layer: int = 32
    chunk: int = 32                # the scan's walk (`ops.selective_scan`)
    block: int = 512
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False     # as `GPT2Config.remat`
    attention: str = "auto"  # auto | flash | reference

    def __post_init__(self):
        kinds, first = self.layer_types, self.first_layer
        for reader, source, what in ((GMU, self.memory_layer, "memory"),
                                     (CROSS, self.kv_layer, "K and V")):
            if reader in kinds and not (
                    first <= source < first + kinds.index(reader)):
                raise ValueError(
                    f"layers {first}–{first + self.n_layer - 1} hold a "
                    f"{reader} layer and not layer {source}, whose {what} "
                    f"it reads")

    @property
    def depths(self) -> Tuple[int, ...]:
        """The published indices of the layers held here."""
        return tuple(range(self.first_layer, self.first_layer + self.n_layer))

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(kind_of(i, self.n_published) for i in self.depths)

    @property
    def memory_layer(self) -> int:
        return self.n_published // 2

    @property
    def kv_layer(self) -> int:
        return self.n_published // 2 + 1

    @property
    def mamba(self) -> L.Mamba1Config:
        return L.Mamba1Config(
            inner=self.expand * self.d_model, d_state=self.d_state,
            d_conv=self.d_conv, dt_rank=self.dt_rank, chunk=self.chunk,
            block=self.block)

    @property
    def diff(self) -> L.DiffAttnConfig:
        return L.DiffAttnConfig(n_head=self.n_head, n_kv_head=self.n_kv_head,
                                head_dim=self.head_dim)

    @property
    def n_params(self) -> int:
        d, m, a = self.d_model, self.mamba, self.diff
        rest = a.q_dim * d + d + 4 * a.head_dim + 2 * a.head_dim
        attention = d * (a.q_dim + 2 * a.kv_dim) + a.q_dim + 2 * a.kv_dim
        mixer = {
            MAMBA: (d * 2 * m.inner + (m.d_conv + 1) * m.inner
                    + m.inner * (m.dt_rank + 2 * m.d_state)
                    + (m.dt_rank + 1) * m.inner + m.inner * m.d_state
                    + m.inner + m.inner * d),
            WINDOW: attention + rest, FULL: attention + rest,
            GMU: 2 * d * m.inner,
            CROSS: d * a.q_dim + a.q_dim + rest}
        return (self.vocab_size * d + 2 * d          # tied table, last norm
                + sum(mixer[kind] + 3 * d * self.d_ff + 4 * d
                      for kind in self.layer_types))


def phi_4_mini_flash():
    """The published model: 32 layers (9 Mamba, 8 window, 1 full, 7 GMU, 7
    cross), the whole vocabulary: 3,852,562,944 parameters."""
    return Phi4FlashConfig()


def phi_4_mini_flash_6l():
    """One pipeline stage of six whole layers, the published 14–19 — Mamba,
    window, Mamba (the memory), full attention (the K and V), GMU, cross:
    the only six in a row that hold all six kinds — with this chip's eighth
    of the tied table (25,008 rows, padded to 25,088); every width as
    published. 697,299,072 parameters: 11.16 GB of float32 parameters,
    gradients and AdamW state."""
    return Phi4FlashConfig(vocab_size=25088, first_layer=14, n_layer=6)


def phi4_flash_tiny():
    """Test-sized: the published pattern at n = 8 cut to layers 2–9 — Mamba,
    window, Mamba (memory), full (K and V), then TWO gated memory units and
    TWO cross layers, so that both shared results have two readers."""
    return Phi4FlashConfig(
        vocab_size=256, max_seq=128, d_model=64, n_head=8, n_kv_head=4,
        head_dim=8, d_ff=128, window=8, d_state=4, dt_rank=4,
        n_published=8, first_layer=2, n_layer=8, chunk=4, block=16)


# ------------------------------------------------------------------ params
def _init_layer(key, kind: str, cfg: Phi4FlashConfig):
    k_mix, k_ff = jax.random.split(key)
    d, dtype = cfg.d_model, cfg.param_dtype
    if kind == MAMBA:
        mixer = L.init_mamba1(k_mix, d, cfg.mamba, dtype)
    elif kind == GMU:
        mixer = L.init_gmu(k_mix, d, cfg.mamba.inner, dtype)
    else:
        mixer = L.init_diff_attention(k_mix, d, cfg.diff, dtype,
                                      own_kv=kind != CROSS)
    # [g | u] = LN(h)·W₁ as two leaves, `w_gate` and `w_up`
    return {"ln_mix": jnp.ones((d,), dtype), "ln_mix_b": jnp.zeros((d,), dtype),
            "mixer": mixer,
            "ln_ff": jnp.ones((d,), dtype), "ln_ff_b": jnp.zeros((d,), dtype),
            "ff": L.init_gated_mlp(k_ff, d, cfg.d_ff, dtype)}


def init(key, cfg: Phi4FlashConfig):
    ke, *kl = jax.random.split(key, 1 + cfg.n_layer)
    d, dtype = cfg.d_model, cfg.param_dtype
    return {
        "wte": L.init_dense(ke, (cfg.vocab_size, d), dtype=dtype),
        "layers": [_init_layer(k, kind, cfg)
                   for k, kind in zip(kl, cfg.layer_types)],
        "ln_f": jnp.ones((d,), dtype), "ln_f_b": jnp.zeros((d,), dtype),
    }


_MIXER_LOGICAL = {MAMBA: L.MAMBA1_LOGICAL, GMU: L.GMU_LOGICAL,
                  WINDOW: L.DIFF_ATTENTION_LOGICAL,
                  FULL: L.DIFF_ATTENTION_LOGICAL, CROSS: L.DIFF_CROSS_LOGICAL}


def logical_axes(cfg: Phi4FlashConfig):
    """Logical axis names matching init()'s tree, a layer at a time."""
    norm = ("embed",)
    return {"wte": ("vocab", "embed"),
            "layers": [{"ln_mix": norm, "ln_mix_b": norm,
                        "mixer": dict(_MIXER_LOGICAL[kind]),
                        "ln_ff": norm, "ln_ff_b": norm,
                        "ff": dict(L.GATED_MLP_LOGICAL)}
                       for kind in cfg.layer_types],
            "ln_f": norm, "ln_f_b": norm}


def partition_specs(cfg: Phi4FlashConfig, rules=None):
    return L.partition_specs(logical_axes(cfg), rules)


def side_plan(cfg: Phi4FlashConfig, tokens: int) -> dict:
    """Bytes of the two results the cross-decoder shares, for `tokens`
    tokens, as the program holds them: the memory in float32, k and v in
    the compute dtype. Each is kept once, whatever the number of readers."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return {"memory_bytes": 4 * tokens * cfg.mamba.inner,
            "shared_kv_bytes": itemsize * tokens * 2 * cfg.diff.kv_dim}


# ----------------------------------------------------------------- forward
def _layer_apply(x, layer, side, *, kind: str, depth: int,
                 cfg: Phi4FlashConfig, impl: str, mesh=None):
    """One layer: x [B, S, d] float32, `side` what a cross-decoder layer
    reads (the memory for a GMU, (k, v) for a cross layer, None for the
    others) -> (x, what this layer hands on: the memory from layer
    `cfg.memory_layer`, (k, v) from `cfg.kv_layer`, None from the others).
    The mixer's scope holds its norm and its residual add."""
    cd, eps, mixer = cfg.dtype, cfg.norm_eps, layer["mixer"]
    hands = None
    with jax.named_scope({MAMBA: "mamba1", GMU: "gmu"}.get(kind, "attn")):
        n = L.layer_norm(x, layer["ln_mix"], layer["ln_mix_b"], eps)
        if kind == MAMBA:
            out, y = L.apply_mamba1(mixer, n, cfg.mamba, compute_dtype=cd,
                                    mesh=mesh)
            if depth == cfg.memory_layer:
                hands = y
        elif kind == GMU:
            out = L.apply_gmu(mixer, n, side, compute_dtype=cd)
        else:
            # `side` is the shared (k, v) for a cross layer, None otherwise
            with (jax.named_scope("cross_attn") if kind == CROSS
                  else contextlib.nullcontext()):
                out, kv = L.apply_diff_attention(
                    mixer, n, cfg.diff, depth=depth, kv=side, impl=impl,
                    window=cfg.window if kind == WINDOW else None,
                    compute_dtype=cd, eps=eps, mesh=mesh)
            if depth == cfg.kv_layer:
                hands = kv
        h = x + out
    with jax.named_scope("mlp"):
        x = h + L.apply_gated_mlp(
            layer["ff"], L.layer_norm(h, layer["ln_ff"], layer["ln_ff_b"],
                                      eps), compute_dtype=cd)
    return sh.constrain(x, mesh, "batch", "seq", "embed"), hands


def forward(params, tokens, cfg: Phi4FlashConfig,
            mesh: Optional[Mesh] = None):
    """tokens [B, S] -> logits [B, S, V] f32 over this chip's slice of the
    vocabulary."""
    L.refuse_tp(mesh, "phi4_flash", "the differential K/V pairs' and the "
                "mixers' leaves")
    impl = L.resolve_attention(cfg.attention, mesh)
    with jax.named_scope("embed"):
        x = L.embed(params["wte"], tokens, mesh)
    shared = {GMU: None, CROSS: None}
    feeds = {cfg.memory_layer: GMU, cfg.kv_layer: CROSS}
    with jax.named_scope("blocks"):
        for depth, kind, layer in zip(cfg.depths, cfg.layer_types,
                                      params["layers"]):
            body = functools.partial(_layer_apply, kind=kind, depth=depth,
                                     cfg=cfg, impl=impl, mesh=mesh)
            x, hands = (L.remat(body) if cfg.remat else body)(
                x, layer, shared.get(kind))
            if depth in feeds:
                shared[feeds[depth]] = hands
    with jax.named_scope("loss_tail"):
        return L.head_logits(x, params["ln_f"], params["wte"],
                             eps=cfg.norm_eps, compute_dtype=cfg.dtype,
                             mesh=mesh, bias=params["ln_f_b"])


def loss_fn(params, batch, cfg: Phi4FlashConfig,
            mesh: Optional[Mesh] = None) -> Tuple[jnp.ndarray, dict]:
    """batch {"tokens" [B, S+1] int32}, ids of this chip's vocabulary slice
    -> (mean next-token cross-entropy over the slice, metrics: the loss, and
    from shapes alone the bytes of the kept memory and shared K/V,
    `side_plan`)."""
    tokens, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    logits = forward(params, tokens, cfg, mesh)
    with jax.named_scope("loss_tail"):
        loss = L.next_token_loss(logits, targets)
    plan = side_plan(cfg, tokens.size)
    return loss, {"loss": loss,
                  **{k: jnp.float32(v) for k, v in plan.items()}}
