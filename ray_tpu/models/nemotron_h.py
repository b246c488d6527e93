"""Nemotron-H (NVIDIA, arXiv:2504.03624), the hybrid tower that the public
``config.json`` of Nemotron-Labs-TwoTower-30B-A3B-Base declares
(``model_type: nemotron_h``): a stack in which every layer is ONE mixer
behind one pre-norm, ``x ← x + Mixer(RMSNorm(x))``, the mixer's kind read
from a pattern (``hybrid_override_pattern``, 52 long: 23 ``M``, 23 ``E``,
6 ``*``); after the last layer an RMSNorm and an untied head. Trained here
with the causal next-token loss. The second, denoising tower that the model
card describes has no key in that config and is NOT built (the benchmark's
configuration file says so under ``assumed``).

The three kinds, written from the config's widths and the family's public
equations:

* ``M``, Mamba-2 (Dao & Gu, arXiv:2405.21060; `layers.apply_mamba`):
  ``[z | xBC | dt] = h·W_in``; ``xBC ← SiLU(causal depthwise conv₄(xBC) +
  b)``, split into ``x [T, 64, 64]`` and ``B, C [T, 8, 128]`` (a group
  serves 8 heads); ``Δ = softplus(dt + dt_bias)``, not clamped; a head's
  ``a_t = exp(Δ_t·A)``, ``A = −exp(A_log)``; ``H_t = a_t·H_{t−1} +
  Δ_t·x_t·B_tᵀ``, ``y_t = H_t·C_t + D·x_t`` (`ops.ssd`, chunks of 128);
  ``y ← RMSNorm_groups(y ⊙ SiLU(z))`` over each of the 8 groups of 512;
  ``·W_out``. No projection bias, a conv bias.
* ``*``, attention (`layers.apply_attention`): 32 query heads of 128 on
  2 KV heads (query head i reads KV head i // 16; the q width 4,096 is not
  the hidden 2,688), causal softmax at scale 128^−½, no bias, no QK-norm
  and NO ROTARY EMBEDDING: positions come from the state-space layers.
* ``E``, routed feed-forward (`layers.apply_moe`): ``s = sigmoid(h·Wg)`` in
  float32; chosen = top-6 of ``s + b`` (``b`` a leaf at zero behind a
  stop_gradient: it stays zero); weights ``s[chosen] / (Σ + 1e-20) × 2.5``;
  an expert is ``W_down·relu(W_up·h)²`` (leaves `w1`, `w2`; no gate, no
  bias); plus a shared expert of the same form, every token; no auxiliary
  loss.

Parameters are stacked BY KIND (``mamba [n_M, …]``, ``moe [n_E, …]``, ``attn
[n_*, …]``); `forward` walks the pattern and takes the next slice of its
kind, each layer under `L.remat` when ``cfg.remat``. The published pattern
is not periodic, so nothing scans over a period.

The chip's share (`held`, `first`): a deployment that spreads each routed
layer over several chips gives this one `held` of the `n_experts` experts,
the shared expert whole and a slice of the vocabulary. The router scores
all `n_experts` and chooses 6 of them; the layer computes its own experts'
part of the result and the shared expert's; what the absent experts would
add is left out, and that partial result goes on to the next layer.

Same shape as `models/olmoe.py`: a pure pytree model, float32 parameters
AND residual stream (every router's top-6 is discontinuous in it), bf16
matmul operands, sharding by logical axes on any `dp` × `ep` mesh. Norms,
conv, softplus, decays, router and loss are float32, and every product
whose value reaches a later router is brought to float32 accuracy in the
FORWARD pass by two more bf16 passes (`ops.mxu.einsum`'s `three_pass`: the
projections of all three kinds, the shared expert, the scan's four
products); the flash kernels and the held experts' grouped products stay
single bf16 passes, and the backward pass is single-pass throughout. A
three-pass result is what `L.remat` keeps of a layer (`remat_saved_plan`
has the bytes): the passes run once a step, not again ahead of the
backward pass. Not extended to it: `tp` > 1 (the mixers' and the KV heads'
leaves are whole on every rank; `forward` refuses such a mesh), `sp`, the
pipelined forward.
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

PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# a pattern's letters and the stacks they index
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    pattern: str = PUBLISHED_PATTERN
    d_model: int = 2688
    n_head: int = 32
    n_kv_head: int = 2
    head_dim: int = 128
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    d_state: int = 128
    d_conv: int = 4
    chunk: int = 128
    n_experts: int = 128           # what the router scores
    top_k: int = 6
    d_expert: int = 1856
    d_shared: int = 3712
    routed_scale: float = 2.5
    held: Optional[int] = None     # experts this chip holds (None: all)
    first: int = 0                 # the first of them
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False     # as `GPT2Config.remat`
    attention: str = "auto"  # auto | flash | reference

    @property
    def n_layer(self) -> int:
        return len(self.pattern)

    @property
    def mamba(self) -> L.MambaConfig:
        return L.MambaConfig(
            n_heads=self.mamba_heads, head_dim=self.mamba_head_dim,
            n_groups=self.n_groups, d_state=self.d_state, d_conv=self.d_conv,
            chunk=self.chunk)

    @property
    def moe(self) -> L.MoEConfig:
        return L.MoEConfig(
            n_experts=self.n_experts, top_k=self.top_k, norm_topk_prob=True,
            score="sigmoid", scale=self.routed_scale, activation="relu2",
            d_shared=self.d_shared, held=self.held, first=self.first)

    @property
    def n_params(self) -> int:
        d, m = self.d_model, self.mamba
        q = self.n_head * self.head_dim
        per = {
            "M": (d * m.in_proj + (m.d_conv + 1) * m.conv_dim
                  + 3 * m.n_heads + m.inner + m.inner * d),
            "*": 2 * d * q + 2 * d * self.n_kv_head * self.head_dim,
            "E": (d * self.n_experts + self.n_experts
                  + self.moe.stacked * 2 * d * self.d_expert
                  + 2 * d * self.d_shared),
        }
        return (2 * self.vocab_size * d + d
                + sum(per[kind] + d for kind in self.pattern))


def nemotron_twotower_30b_a3b():
    """The tower as published: 52 layers, 128 experts a routed layer, the
    whole vocabulary: 31.6 B parameters, 3.5 B used a token."""
    return NemotronHConfig()


def nemotron_twotower_30b_a3b_9l():
    """One chip's share of the first nine layers (``MEMEM*EME``: 4 Mamba-2,
    4 routed, 1 attention) where 16 chips share each layer: experts 0–7 of
    the 128 the router scores, the shared expert whole, 16,384 rows of the
    vocabulary; every width as published. 666,963,456 parameters: 10.67 GB
    of float32 parameters, gradients and AdamW state."""
    return NemotronHConfig(vocab_size=16384, pattern=PUBLISHED_PATTERN[:9],
                           held=8)


def nemotron_h_tiny():
    """Test-sized: every kind of layer, grouped KV heads, two B/C groups, a
    share of the experts."""
    return NemotronHConfig(
        vocab_size=256, pattern="ME*ME", d_model=64, n_head=4, n_kv_head=2,
        head_dim=16, mamba_heads=8, mamba_head_dim=8, n_groups=2, d_state=16,
        chunk=16, n_experts=16, top_k=3, d_expert=32, d_shared=48, held=4)


# ------------------------------------------------------------------ params
def _init_layer(kind: str, key, cfg: NemotronHConfig):
    d, dtype = cfg.d_model, cfg.param_dtype
    if kind == "M":
        mixer = L.init_mamba(key, d, cfg.mamba, dtype)
    elif kind == "*":
        mixer = L.init_attention(key, d, cfg.n_head, dtype,
                                 n_kv_head=cfg.n_kv_head,
                                 head_dim=cfg.head_dim)
    else:
        mixer = L.init_moe(key, d, cfg.d_expert, cfg.moe, dtype)
    return dict(mixer, ln=jnp.ones((d,), dtype))


def init(key, cfg: NemotronHConfig):
    ke, kh, *kinds = jax.random.split(key, 2 + len(KINDS))

    def table(k):
        return (jax.random.normal(k, (cfg.vocab_size, cfg.d_model))
                * 0.02).astype(cfg.param_dtype)

    params = {"wte": table(ke), "head": table(kh),
              "ln_f": jnp.ones((cfg.d_model,), cfg.param_dtype)}
    for (kind, name), k in zip(KINDS.items(), kinds):
        params[name] = jax.vmap(
            functools.partial(_init_layer, kind, cfg=cfg))(
                jax.random.split(k, cfg.pattern.count(kind)))
    return params


def logical_axes(cfg: NemotronHConfig):
    """Logical axis names matching init()'s tree; the stacks' leaves get a
    leading 'layers' axis."""
    kinds = {
        "mamba": L.MAMBA_LOGICAL,
        "attn": L.GROUPED_ATTENTION_LOGICAL,
        "moe": dict(L.MOE_LOGICAL, **L.MOE_EXTRA_LOGICAL),
    }
    stacks = jax.tree_util.tree_map(
        lambda names: ("layers",) + tuple(names),
        {name: dict(axes, ln=("embed",)) for name, axes in kinds.items()},
        is_leaf=lambda x: isinstance(x, tuple))
    return dict(stacks, wte=("vocab", "embed"), ln_f=("embed",),
                head=("vocab", "embed"))


def partition_specs(cfg: NemotronHConfig, rules=None):
    return L.partition_specs(logical_axes(cfg), rules)


def remat_saved_plan(cfg: NemotronHConfig, local_batch: int, seq: int, *,
                     flash: bool = True):
    """{kind: {name: bytes}} of what ONE layer of each kind keeps on one
    device under `L.remat` besides its input, from shapes alone (as
    `gpt2.remat_saved_plan`): the results of the three-pass products the
    backward pass reads — the mixer's in-projection and the shared
    expert's first product in float32, q, k and v in the compute dtype, as
    they are rounded for the kernel — and, where the flash kernels run
    (`flash`), their `o` and float32 `lse`. Not in it, because no backward
    reads them: the out-projections' results, the shared expert's second
    product, `wo`'s (a layer is one mixer: nothing in it goes on from the
    attention output). A routed layer keeps its routing besides
    (`L.ROUTING`, `L.routing_plan`: the router's `[tokens, n_experts]`
    product, float32 at `highest` precision outside `_project`, the chosen
    experts and the assignments' sort: decided once a step).
    The step's total is each kind's sum times the pattern's count of it."""
    rows = local_batch * seq
    item = jnp.dtype(cfg.dtype).itemsize
    q, kv = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
    plan = {"M": {L.THREE_PASS_OUT: rows * cfg.mamba.in_proj * 4},
            "E": {L.THREE_PASS_OUT: rows * cfg.d_shared * 4,
                  L.ROUTING: sum(L.routing_plan(rows, cfg.moe).values())},
            "*": {L.THREE_PASS_OUT: rows * (q + 2 * kv) * item}}
    if flash:
        from ray_tpu.ops.flash_attention import RESIDUAL_NAMES

        o, lse = RESIDUAL_NAMES
        plan["*"].update({o: rows * q * item, lse: rows * cfg.n_head * 4})
    return plan


# ----------------------------------------------------------------- forward
def _layer_apply(x, layer, *, kind: str, cfg: NemotronHConfig, impl: str,
                 mesh=None):
    """One layer: x [B, S, d] float32 -> (x, the routed layer's assignments
    by expert [E] and whether its share ran bounded; None for the other
    kinds)."""
    h = L.rms_norm(x, layer["ln"], cfg.rms_norm_eps)
    mixer = {k: v for k, v in layer.items() if k != "ln"}
    routed = None
    with jax.named_scope(KINDS[kind]):
        if kind == "M":
            out = L.apply_mamba(mixer, h, cfg.mamba, compute_dtype=cfg.dtype,
                                eps=cfg.rms_norm_eps, three_pass=True,
                                mesh=mesh)
        elif kind == "*":
            out = L.apply_attention(mixer, h, causal=True, impl=impl,
                                    compute_dtype=cfg.dtype, mesh=mesh,
                                    three_pass=True)
        else:
            out, stats = L.apply_moe(mixer, h, cfg.moe,
                                     compute_dtype=cfg.dtype, mesh=mesh,
                                     three_pass=True)
            routed = stats["counts"], stats.get("compact", jnp.float32(0))
    x = x + out
    return sh.constrain(x, mesh, "batch", "seq", "embed"), routed


def forward(params, tokens, cfg: NemotronHConfig,
            mesh: Optional[Mesh] = None):
    """tokens [B, S] -> (logits [B, S, V] f32 over this chip's slice of the
    vocabulary, the routed layers' assignments by expert [n_E, E])."""
    return _forward(params, tokens, cfg, mesh)[:2]


def _forward(params, tokens, cfg: NemotronHConfig, mesh):
    """`forward`, and by routed layer [n_E] whether its share of the
    experts ran on a bounded prefix of the assignments (`apply_moe`'s
    `compact`)."""
    L.refuse_tp(mesh, "nemotron_h",
                "the Mamba mixers' and the KV heads' leaves")
    impl = L.resolve_attention(cfg.attention, mesh)
    with jax.named_scope("embed"):
        x = L.embed(params["wte"], tokens, mesh)
    by_layer = []
    with jax.named_scope("blocks"):
        for depth, kind in enumerate(cfg.pattern):
            nth = cfg.pattern[:depth].count(kind)     # of its kind's stack
            layer = jax.tree_util.tree_map(lambda a: a[nth],
                                           params[KINDS[kind]])
            body = functools.partial(_layer_apply, kind=kind, cfg=cfg,
                                     impl=impl, mesh=mesh)
            if cfg.remat:
                body = L.remat(body)
            x, routed = body(x, layer)
            if routed is not None:
                by_layer.append(routed)
    with jax.named_scope("loss_tail"):
        logits = L.head_logits(x, params["ln_f"], params["head"],
                               eps=cfg.rms_norm_eps, compute_dtype=cfg.dtype,
                               mesh=mesh)
    return (logits, *(jnp.stack(s) for s in zip(*by_layer)))


def loss_fn(params, batch, cfg: NemotronHConfig,
            mesh: Optional[Mesh] = None) -> Tuple[jnp.ndarray, dict]:
    """`layers.share_loss` of this model: the cross-entropy, and how the
    routed layers' routing went."""
    return L.share_loss(functools.partial(_forward, cfg=cfg, mesh=mesh),
                        params, batch, cfg.moe)
