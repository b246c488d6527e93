"""OLMoE, plainly: the forward pass and the training loss in ``jax.numpy``
and float32, written from the public ``olmoe`` implementation's equations
(allenai/OLMoE-1B-7B; Muennighoff et al., arXiv:2409.02060) and importing
nothing from the program. Gradients are ``jax.grad`` of this function.

It reads the system's parameter tree as it stands: ``wte`` [V, d], ``head``
[V, d] (untied), ``ln_f`` [d], and ``blocks`` with every leaf stacked over
layers: ``ln1``, ``ln2`` [d]; ``attn`` {wq, wk, wv [d, H, K], wo [H, K, d],
q_norm, k_norm [H, K]}; ``moe`` {wg [d, E], w_gate, w_up [E, d, F], w_down
[E, F, d]}. What no leaf's shape gives is read from ``config``, the
configuration file as the cell runs it: ``rms_norm_eps``, ``rope_theta``,
``num_experts_per_tok`` and the two weights of the router's loss terms,
``router_aux_loss_coef`` and ``router_z_loss_coef`` (the heads are ``wq``'s
middle axis; ``num_attention_heads`` is checked against it).

The equations:

* block: x + Attn(RMSNorm(x)), then x + MoE(RMSNorm(x)); a last RMSNorm and
  the head;
* attention: q = RMSNorm_q(h·Wq), k = RMSNorm_k(h·Wk), each norm over the
  whole d-wide projection before the split into heads; q and k rotated in
  half-split pairs (i, i + K/2) by pos · theta^(−2i/K); causal softmax
  attention at scale K^−½;
* routed layer: p = softmax(h·Wg) over all experts; the
  ``num_experts_per_tok`` largest, p used as it is (``norm_topk_prob``
  false); y = Σ_chosen p_e · W_down_e(silu(W_gate_e h) ⊙ W_up_e h). Computed
  the slow obvious way: EVERY expert on EVERY token, one expert after the
  other, the result masked by whether the token chose it. No sort, no
  gather, no capacity;
* loss: mean next-token cross-entropy + aux · E · Σ_e f_e · P_e (f_e the
  share of ONE SEQUENCE's assignments that went to expert e, P_e the mean
  of p_e over that sequence; mean over sequences and layers) + z · mean
  logsumexp(h·Wg)².

The load-balance term is a product of two means, so over which tokens they
are taken is part of the definition. The public implementations take them
over whatever one device holds in a micro-step; the system takes them a
sequence at a time, which no layout changes, and so does this reference.
Every term is then a mean over sequences, and ``chipbench/compare.py``,
which hands this function one sequence at a time and averages, computes the
same loss as a call on the whole batch.

Callers on a TPU wrap the call in ``jax.default_matmul_precision("highest")``.
``lax.scan`` over layers and experts and ``jax.checkpoint`` change what is
held in memory, not what is computed.
"""
import math

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def rotate(x, theta):
    """x [B, H, S, K]."""
    seq, half = x.shape[2], x.shape[3] // 2
    angle = (jnp.arange(seq, dtype=jnp.float32)[:, None]
             * theta ** (-jnp.arange(half, dtype=jnp.float32) / half))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(h, p, config):
    d, heads, head_dim = p["wq"].shape
    assert heads == config["num_attention_heads"]
    eps, seq = config["rms_norm_eps"], h.shape[1]

    def project(w, norm):
        flat = h @ w.reshape(d, heads * head_dim)
        if norm is not None:
            flat = rms_norm(flat, norm.reshape(-1), eps)
        return flat.reshape(h.shape[0], seq, heads, head_dim).transpose(
            0, 2, 1, 3)

    q = rotate(project(p["wq"], p["q_norm"]), config["rope_theta"])
    k = rotate(project(p["wk"], p["k_norm"]), config["rope_theta"])
    v = project(p["wv"], None)
    scores = jnp.einsum("bhqk,bhsk->bhqs", q, k) / math.sqrt(head_dim)
    scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores,
                       -jnp.inf)
    attended = jnp.einsum("bhqs,bhsk->bhqk",
                          jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bhsk,hkd->bsd", attended, p["wo"])


def routed(h, p, config):
    """h [B, S, d] -> (y, load balance, z)."""
    top_k, n_experts = config["num_experts_per_tok"], p["wg"].shape[1]
    logits = h @ p["wg"]
    probs = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(probs, top_k)
    mask = jnp.sum(jax.nn.one_hot(chosen, n_experts), axis=-2)   # [B,S,E]
    weight = probs * mask

    def one_expert(y, e):
        w_gate, w_up, w_down, w = e
        out = (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down
        return y + out * w[..., None], None

    y, _ = jax.lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(h),
        (p["w_gate"], p["w_up"], p["w_down"], jnp.moveaxis(weight, -1, 0)))
    share = jnp.sum(mask, axis=1) / (mask.shape[1] * top_k)        # [B,E]
    balance = jnp.mean(n_experts * jnp.sum(
        share * jnp.mean(probs, axis=1), axis=-1))
    z = jnp.mean(jnp.square(jax.scipy.special.logsumexp(logits, axis=-1)))
    return y, balance, z


def block(x, p, config):
    eps = config["rms_norm_eps"]
    x = x + attention(rms_norm(x, p["ln1"], eps), p["attn"], config)
    y, balance, z = routed(rms_norm(x, p["ln2"], eps), p["moe"], config)
    return x + y, (balance, z)


def loss(params, tokens, config):
    """tokens [B, S+1]: mean next-token cross-entropy over B·S positions
    plus the two router terms."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x, (balance, z) = jax.lax.scan(
        jax.checkpoint(lambda x, p: block(x, p, config)),
        params["wte"][inputs], params["blocks"])
    logits = rms_norm(x, params["ln_f"], config["rms_norm_eps"]) \
        @ params["head"].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    cross_entropy = -jnp.mean(
        jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return (cross_entropy
            + config["router_aux_loss_coef"] * jnp.mean(balance)
            + config["router_z_loss_coef"] * jnp.mean(z))
