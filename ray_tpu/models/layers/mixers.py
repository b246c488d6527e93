"""The five token mixers: short conv, the two state-space mixers, gated
delta and Kimi Delta Attention. Imports `core` alone of this package."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ray_tpu.models.layers import core
from ray_tpu.ops import gated_delta, kda, mamba_stages, selective_scan, ssd


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
        "w_in": core.init_dense(k_in, (d_model, 3 * d_model), dtype=dtype),
        "conv_w": jax.random.uniform(
            k_conv, (taps, d_model), minval=-bound,
            maxval=bound).astype(dtype),
        "w_out": core.init_dense(k_out, (d_model, d_model), dtype=dtype),
    }


# every leaf whole on every `tp` rank: a model with the operator refuses
# `tp` > 1 (the gates pair channel i of three streams: columns over `tp`
# is the split, not taken yet)
SHORT_CONV_LOGICAL = {"w_in": ("embed", None), "conv_w": (None, None),
                      "w_out": (None, "embed")}


def apply_short_conv(params: core.Params, x, *, compute_dtype=jnp.bfloat16,
                     three_pass: bool = False):
    """x [B, T, d] -> [B, T, d], the gated short convolution of the LFM2
    family: ``[b | c | u] = x·W_in``; ``v = taps(b ∘ u)`` (`causal_taps`:
    depthwise, causal, no bias, no activation); ``(c ∘ v)·W_out``. The two
    products on the MXU in `compute_dtype` (`three_pass` as in
    `apply_attention`), gates and taps in float32 from the in-projection's
    accumulator, under the scope `gate_conv`: plain JAX, no kernel."""
    project = core.project(compute_dtype, three_pass)
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
        "w_in": core.init_dense(k_in, (d_model, cfg.in_proj), dtype=dtype),
        "conv_w": jax.random.uniform(
            k_conv, (cfg.d_conv, cfg.conv_dim), minval=-bound,
            maxval=bound).astype(dtype),
        "conv_b": jnp.zeros((cfg.conv_dim,), dtype),
        **_init_decays(k_dt, k_a, cfg.n_heads, cfg, dtype),
        "D": jnp.ones((cfg.n_heads,), dtype),
        "norm": jnp.ones((cfg.inner,), dtype),
        "w_out": core.init_dense(k_out, (cfg.inner, d_model), dtype=dtype),
    }


# every leaf whole on every `tp` rank: a model with a mixer refuses `tp` > 1
MAMBA_LOGICAL = {
    "w_in": ("embed", None), "conv_w": (None, None), "conv_b": (None,),
    "dt_bias": (None,), "A_log": (None,), "D": (None,), "norm": (None,),
    "w_out": (None, "embed"),
}


def apply_mamba(params: core.Params, u, cfg: MambaConfig, *,
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
    project = core.project(compute_dtype, three_pass)
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
        "w_in": core.init_dense(k_in, (d_model, 2 * cfg.inner), dtype=dtype),
        "conv_w": jax.random.uniform(
            k_conv, (cfg.d_conv, cfg.inner), minval=-bound,
            maxval=bound).astype(dtype),
        "conv_b": jnp.zeros((cfg.inner,), dtype),
        "w_x": core.init_dense(k_x, (cfg.inner, cfg.dt_rank + 2 * cfg.d_state),
                           dtype=dtype),
        "w_dt": jax.random.uniform(
            k_dt, (cfg.dt_rank, cfg.inner), minval=-rank,
            maxval=rank).astype(dtype),
        "dt_bias": _init_dt_bias(k_b, cfg.inner, cfg, dtype),
        "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
            1, cfg.d_state + 1, dtype=jnp.float32)),
            (cfg.inner, cfg.d_state)).astype(dtype),
        "D": jnp.ones((cfg.inner,), dtype),
        "w_out": core.init_dense(k_out, (cfg.inner, d_model), dtype=dtype),
    }


# every leaf whole on every `tp` rank: a model with a mixer refuses `tp` > 1
MAMBA1_LOGICAL = {
    "w_in": ("embed", None), "conv_w": (None, None), "conv_b": (None,),
    "w_x": (None, None), "w_dt": (None, None), "dt_bias": (None,),
    "A_log": (None, None), "D": (None,), "w_out": (None, "embed"),
}


def apply_mamba1(params: core.Params, u, cfg: Mamba1Config, *,
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
    project = core.project(compute_dtype, False)
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
        "w_in": core.init_dense(k_in, (d_model, cfg.in_proj), dtype=dtype),
        "w_ba": core.init_dense(k_ba, (d_model, 2 * cfg.n_v_heads),
                                dtype=dtype),
        "conv_w": jax.random.uniform(
            k_conv, (cfg.d_conv, cfg.conv_dim), minval=-bound,
            maxval=bound).astype(dtype),
        **_init_decays(k_dt, k_a, cfg.n_v_heads, cfg, dtype),
        "norm": jnp.ones((cfg.v_dim,), dtype),
        "w_out": core.init_dense(k_out, (cfg.value_dim, d_model), dtype=dtype),
    }


# every leaf whole on every `tp` rank: a model with the layer refuses `tp` > 1
GATED_DELTA_LOGICAL = {
    "w_in": ("embed", None), "w_ba": ("embed", None), "conv_w": (None, None),
    "dt_bias": (None,), "A_log": (None,), "norm": (None,),
    "w_out": (None, "embed"),
}


def apply_gated_delta(params: core.Params, u, cfg: DeltaConfig, *,
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
    project = core.project(compute_dtype, False)
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
        y = core.rms_norm(o, params["norm"], eps) * jax.nn.silu(
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
        "w_qkv": core.init_dense(ks[0], (d_model, cfg.conv_dim), dtype=dtype),
        "conv_w": jax.random.uniform(
            ks[1], (cfg.d_conv, cfg.conv_dim), minval=-bound,
            maxval=bound).astype(dtype),
        "w_f_down": core.init_dense(ks[2], (d_model, r), dtype=dtype),
        "w_f_up": core.init_dense(ks[3], (r, cfg.key_dim), dtype=dtype),
        "dt_bias": _init_dt_bias(ks[4], cfg.key_dim, cfg, dtype),
        "A_log": jnp.log(jax.random.uniform(
            ks[5], (cfg.n_heads,), minval=1.0, maxval=16.0)).astype(dtype),
        "w_beta": core.init_dense(ks[6], (d_model, cfg.n_heads), dtype=dtype),
        "w_g_down": core.init_dense(ks[7], (d_model, r), dtype=dtype),
        "w_g_up": core.init_dense(ks[8], (r, cfg.value_dim), dtype=dtype),
        "norm": jnp.ones((cfg.v_dim,), dtype),
        "w_out": core.init_dense(ks[9], (cfg.value_dim, d_model), dtype=dtype),
    }


# every leaf whole on every `tp` rank: a model with the layer refuses `tp` > 1
KDA_LOGICAL = {
    "w_qkv": ("embed", None), "conv_w": (None, None),
    "w_f_down": ("embed", None), "w_f_up": (None, None),
    "dt_bias": (None,), "A_log": (None,), "w_beta": ("embed", None),
    "w_g_down": ("embed", None), "w_g_up": (None, None), "norm": (None,),
    "w_out": (None, "embed"),
}


def apply_kda(params: core.Params, u, cfg: KDAConfig, *,
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
    project = core.project(compute_dtype, False)
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
        y = core.rms_norm(o, params["norm"], eps) * jax.nn.sigmoid(
            z.reshape(B, T, H, V))
    with jax.named_scope("kda_proj"):
        return project("bte,ed->btd", y.reshape(B, T, cfg.value_dim),
                       params["w_out"], u.dtype)
