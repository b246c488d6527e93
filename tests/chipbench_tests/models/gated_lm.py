"""A fixture, never a cell: a test-sized language model that is NOT
GPT-2-shaped, standing where a program's model stands (the ``entry`` of
``configs/gated-tiny.json``). RMSNorm, rotary positions (no learned ones),
a gated MLP, an untied output head; sizes under the public keys of today's
models. It imports nothing from ``ray_tpu.models``: what the ``train_fit``
job needs of a model is this module's ``init``, ``partition_specs``,
``loss_fn`` and a preset, and nothing of GPT-2's.

Parameters are float32 and stacked over layers; matmuls run in
``cfg.dtype`` (bfloat16) with float32 accumulation, norms, rotation,
softmax and the loss in float32.
"""
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec


@dataclasses.dataclass(frozen=True)
class GatedConfig:
    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 2
    num_attention_heads: int = 4
    intermediate_size: int = 160
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False
    attention: str = "reference"      # the only implementation there is

    @property
    def n_params(self) -> int:
        d, ff = self.hidden_size, self.intermediate_size
        layer = 4 * d * d + 3 * d * ff + 2 * d
        return 2 * self.vocab_size * d + self.num_hidden_layers * layer + d


def tiny():
    return GatedConfig()


def init(rng, cfg: GatedConfig):
    d, ff, n = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    keys = iter(jax.random.split(rng, 9))

    def normal(*shape):
        return 0.02 * jax.random.normal(next(keys), shape, jnp.float32)

    return {
        "embed": normal(cfg.vocab_size, d),
        "layers": {
            "norm1": jnp.ones((n, d)), "norm2": jnp.ones((n, d)),
            "wq": normal(n, d, d), "wk": normal(n, d, d),
            "wv": normal(n, d, d), "wo": normal(n, d, d),
            "gate": normal(n, d, ff), "up": normal(n, d, ff),
            "down": normal(n, ff, d)},
        "norm_f": jnp.ones((d,)),
        "head": normal(d, cfg.vocab_size),
    }


def partition_specs(cfg: GatedConfig):
    """Replicated: the fixture runs on a mesh with data parallelism only."""
    shapes = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
    return jax.tree_util.tree_map(lambda _: PartitionSpec(), shapes)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotate(x, theta):
    """x [B, S, H, K]: each pair (x[..., i], x[..., i + K/2]) turned by
    position · theta^(-2i/K)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _layer(x, p, cfg: GatedConfig):
    def mm(a, w):
        return jnp.matmul(a.astype(cfg.dtype), w.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)

    batch, seq, d = x.shape
    heads = cfg.num_attention_heads
    h = _rms(x, p["norm1"], cfg.rms_norm_eps)
    q, k, v = (mm(h, p[w]).reshape(batch, seq, heads, d // heads)
               for w in ("wq", "wk", "wv"))
    q, k = _rotate(q, cfg.rope_theta), _rotate(k, cfg.rope_theta)
    scores = jnp.einsum("bqhk,bshk->bhqs", q.astype(cfg.dtype),
                        k.astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    scores = scores / (d // heads) ** 0.5
    scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores,
                       -jnp.inf)
    attended = jnp.einsum("bhqs,bshk->bqhk",
                          jax.nn.softmax(scores, -1).astype(cfg.dtype),
                          v.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)
    x = x + mm(attended.reshape(batch, seq, d), p["wo"])
    h = _rms(x, p["norm2"], cfg.rms_norm_eps)
    return x + mm(jax.nn.silu(mm(h, p["gate"])) * mm(h, p["up"]), p["down"])


def loss_fn(params, batch, cfg: GatedConfig, mesh=None):
    """batch {"tokens" [B, S+1]} -> (mean next-token cross-entropy,
    {"loss"})."""
    if cfg.attention != "reference":
        raise ValueError(f"gated_lm has no attention {cfg.attention!r}")
    tokens, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]

    def body(x, p):
        return _layer(x, p, cfg), None

    x, _ = jax.lax.scan(jax.checkpoint(body) if cfg.remat else body,
                        params["embed"][tokens], params["layers"])
    logits = jnp.matmul(
        _rms(x, params["norm_f"], cfg.rms_norm_eps).astype(cfg.dtype),
        params["head"].astype(cfg.dtype), preferred_element_type=jnp.float32)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    loss = jnp.mean(jax.scipy.special.logsumexp(logits, -1) - picked)
    return loss, {"loss": loss}
