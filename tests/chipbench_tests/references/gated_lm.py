"""The fixture architecture of ``models/gated_lm.py``, plainly: float32
``jax.numpy``, one layer after another in a Python loop, the rotation
written with complex numbers. It reads the fixture's parameter tree
(``embed`` [V, d], ``head`` [d, V], ``norm_f`` [d], and ``layers`` stacked
over layers: ``norm1``, ``norm2`` [d]; ``wq``, ``wk``, ``wv``, ``wo``
[d, d]; ``gate``, ``up`` [d, ff]; ``down`` [ff, d]) and imports nothing
from the model. What no leaf's shape gives it reads from the
configuration file it is handed (``configs/gated-tiny.json``):
``num_attention_heads``, ``rope_theta``, ``rms_norm_eps``.
"""
import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                        + eps) * scale


def rotary(x, theta):
    """x [B, S, H, K]: the pair (x_i, x_{i+K/2}) is a complex number,
    turned by position · theta^(-2i/K)."""
    half = x.shape[-1] // 2
    position = jnp.arange(x.shape[1])[:, None, None]
    angle = position * theta ** (-jnp.arange(half) / half)
    turned = (x[..., :half] + 1j * x[..., half:]) * jnp.exp(1j * angle)
    return jnp.concatenate([turned.real, turned.imag], -1)


def layer(x, p, config):
    batch, seq, d = x.shape
    heads, eps = config["num_attention_heads"], config["rms_norm_eps"]
    h = rms_norm(x, p["norm1"], eps)
    q, k, v = ((h @ p[w]).reshape(batch, seq, heads, -1)
               for w in ("wq", "wk", "wv"))
    q, k = rotary(q, config["rope_theta"]), rotary(k, config["rope_theta"])
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / (d // heads) ** 0.5
    scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores,
                       -jnp.inf)
    attended = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(scores, -1), v)
    x = x + attended.reshape(batch, seq, d) @ p["wo"]
    h = rms_norm(x, p["norm2"], eps)
    gate = h @ p["gate"]
    return x + (gate * jax.nn.sigmoid(gate) * (h @ p["up"])) @ p["down"]


def loss(params, tokens, config):
    """tokens [B, S+1]: mean next-token cross-entropy over B·S positions."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    x = params["embed"][tokens[:, :-1]]
    for i in range(params["layers"]["wq"].shape[0]):
        x = layer(x, jax.tree_util.tree_map(lambda a: a[i],
                                            params["layers"]), config)
    logp = jax.nn.log_softmax(
        rms_norm(x, params["norm_f"], config["rms_norm_eps"])
        @ params["head"], -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))
