"""GPT-2, plainly: the forward pass and the next-token loss in
``jax.numpy`` and float32, written from the published description (Radford
et al. 2019; the ``GPT2Model`` of the released code) and importing nothing
from the program. Gradients are ``jax.grad`` of this function.

It reads the system's parameter tree as it stands: ``wte`` [V, d] (tied
output head), ``wpe`` [positions, d], ``ln_f``, and ``blocks`` with every
leaf stacked over layers: ``ln1``, ``ln2`` {scale, bias}; ``attn`` {wq, wk,
wv [d, H, K], wo [H, K, d]}; ``mlp`` {w1 [d, ff], b1, w2 [ff, d], b2}.

``loss(params, tokens, config)`` is what every reference offers: ``config``
is the configuration file as the cell runs it, and whatever no leaf's
shape gives is read from it and from nowhere else. Here that is
``layer_norm_epsilon`` alone (the heads are ``wq``'s middle axis); for
another architecture it is the head counts, the rotary base, the experts
a token, a window.

Departures of the SYSTEM from the published model, which this reference
follows so that the two compute the same function: the attention
projections carry no bias (the published model has them), and the
embedding has 50,304 rows (50,257 padded to a multiple of 128; the extra
rows take part in the softmax like any other).

Callers on a TPU wrap the call in ``jax.default_matmul_precision("highest")``:
a float32 matmul there is otherwise a single bf16 pass. ``lax.scan`` walks
the stacked layers and ``jax.checkpoint`` keeps only each layer's input for
the backward pass; neither changes what is computed, only how much memory
a 36-layer model's float32 score matrices take.
"""
import math

import jax
import jax.numpy as jnp


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu(x):
    """The tanh form GPT-2 was trained with (``gelu_new``)."""
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, eps):
    seq, head_dim = x.shape[1], p["attn"]["wq"].shape[-1]
    h = layer_norm(x, p["ln1"], eps)
    q = jnp.einsum("bsd,dhk->bhsk", h, p["attn"]["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", h, p["attn"]["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", h, p["attn"]["wv"])
    scores = jnp.einsum("bhqk,bhsk->bhqs", q, k) / math.sqrt(head_dim)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attended = jnp.einsum("bhqs,bhsk->bhqk",
                          jax.nn.softmax(scores, axis=-1), v)
    x = x + jnp.einsum("bhsk,hkd->bsd", attended, p["attn"]["wo"])
    h = layer_norm(x, p["ln2"], eps)
    h = gelu(h @ p["mlp"]["w1"] + p["mlp"]["b1"])
    return x + h @ p["mlp"]["w2"] + p["mlp"]["b2"]


def logits(params, tokens, config):
    """tokens [B, S] int -> [B, S, V] float32."""
    eps = config["layer_norm_epsilon"]
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    x = params["wte"][tokens] + params["wpe"][:tokens.shape[1]]
    x, _ = jax.lax.scan(
        jax.checkpoint(lambda x, p: (block(x, p, eps), None)), x,
        params["blocks"])
    return layer_norm(x, params["ln_f"], eps) @ params["wte"].T


def loss(params, tokens, config):
    """tokens [B, S+1]: mean next-token cross-entropy over B·S positions."""
    logp = jax.nn.log_softmax(logits(params, tokens[:, :-1], config),
                              axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)
