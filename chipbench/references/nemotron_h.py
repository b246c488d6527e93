"""The Nemotron-H tower, plainly: the forward pass and the training loss in
``jax.numpy`` and float32, written from the equations of the family's
public description (NVIDIA, arXiv:2504.03624; Mamba-2: Dao & Gu,
arXiv:2405.21060) and importing nothing from the program. Gradients are
``jax.grad`` of this function.

It reads the system's parameter tree as it stands: ``wte`` [V, d], ``head``
[V, d] (untied), ``ln_f`` [d], and three stacks, every leaf with a leading
axis over the layers of its kind, each with its pre-norm's scale ``ln``:
``mamba`` {w_in [d, z | xBC | dt], conv_w [4, xBC], conv_b, dt_bias, A_log,
D [heads], norm [inner], w_out [inner, d]}; ``attn`` {wq [d, H, K], wk, wv
[d, KV, K], wo [H, K, d]}; ``moe`` {wg [d, E], bias [E], w1 [held, d, F], w2
[held, F, d], shared_w1 [d, Fs], shared_w2 [Fs, d]}. What no leaf's shape
gives is read from ``config``, the configuration file as the cell runs it:
``pattern`` (the kinds of the layers, in order), ``layer_norm_epsilon``,
``mamba_head_dim``, ``n_groups``, ``ssm_state_size``,
``num_experts_per_tok``, ``routed_scaling_factor`` and, under
``deployment``, ``first_expert``: the id, among the experts the router
scores, of the first one the stack holds.

The equations (every layer ``x + Mixer(RMSNorm(x))``; a last RMSNorm and the
head; mean next-token cross-entropy):

* ``M``: ``[z | xBC | dt] = h·W_in``; ``xBC ← SiLU(conv(xBC) + b)``, the
  conv causal and depthwise over 4 tokens; ``Δ = softplus(dt + dt_bias)``;
  a head's state ``H_t = exp(Δ_t·A)·H_{t−1} + Δ_t·x_t·B_tᵀ``, ``y_t =
  H_t·C_t + D·x_t`` with ``A = −exp(A_log)`` — THE RECURRENCE ITSELF, one
  token after the other in a ``lax.scan``: no chunks, no dual form;
  ``RMSNorm`` over each group's share of ``y ⊙ SiLU(z)``; ``·W_out``;
* ``*``: causal softmax attention at scale K^−½, query head i on KV head
  i // (H / KV), no positions;
* ``E``: ``s = sigmoid(h·Wg)``; the top-k of ``s + bias``; weights
  ``s / (Σ_chosen s + 1e-20) × scale``; ``y = Σ_chosen-and-held w_e ·
  W2_e·relu(W1_e·h)² + the shared expert``. Computed the slow obvious way:
  EVERY held expert on EVERY token, one expert after the other, the result
  masked by whether the token chose it. Experts the stack does not hold add
  nothing: the same share the system is given.

Callers on a TPU wrap the call in ``jax.default_matmul_precision("highest")``.
``lax.scan`` over heads and experts, the scan over segments of tokens and
``jax.checkpoint`` change what is held in memory, not what is computed.
"""
import math

import jax
import jax.numpy as jnp

# tokens whose states one checkpointed segment of the recurrence recomputes
SEGMENT = 128


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def recurrence(x, dt, a_head, b_in, c_out, d_head):
    """One sequence: x [T, H, P], dt [T, H], b_in, c_out [T, G, N] -> y
    [T, H, P]."""
    tokens, heads, _ = x.shape
    per_group = heads // b_in.shape[1]
    pad = -tokens % SEGMENT
    # a token with Δ = 0 neither decays the state nor adds to it
    x, dt, b_in, c_out = (
        jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1)).reshape(
            (-1, SEGMENT) + t.shape[1:]) for t in (x, dt, b_in, c_out))

    def token(state, t):
        x_t, dt_t, b_t, c_t = t
        b_t, c_t = (jnp.repeat(g, per_group, axis=0) for g in (b_t, c_t))
        state = (jnp.exp(dt_t * a_head)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t) \
            + d_head[:, None] * x_t

    def segment(state, ts):
        return jax.lax.scan(token, state, ts)

    state = jnp.zeros(x.shape[2:] + (b_in.shape[-1],), jnp.float32)
    _, y = jax.lax.scan(jax.checkpoint(segment), state,
                        (x, dt, b_in, c_out))
    return y.reshape((-1,) + y.shape[2:])[:tokens]


def mamba(h, p, config):
    """h [S, d] -> [S, d]."""
    head_dim, groups = config["mamba_head_dim"], config["n_groups"]
    state = config["ssm_state_size"]
    heads = p["A_log"].shape[0]
    inner = heads * head_dim
    seq = h.shape[0]
    z, xbc, dt = jnp.split(h @ p["w_in"], [inner, 2 * inner
                                           + 2 * groups * state], axis=-1)
    width = p["conv_w"].shape[0]
    padded = jnp.pad(xbc, [(width - 1, 0), (0, 0)])
    xbc = jax.nn.silu(sum(padded[i:i + seq] * p["conv_w"][i]
                          for i in range(width)) + p["conv_b"])
    x, b_in, c_out = jnp.split(xbc, [inner, inner + groups * state], axis=-1)
    y = recurrence(x.reshape(seq, heads, head_dim),
                   jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
                   b_in.reshape(seq, groups, state),
                   c_out.reshape(seq, groups, state), p["D"])
    y = (y.reshape(seq, inner) * jax.nn.silu(z)).reshape(seq, groups, -1)
    y = y / jnp.sqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                     + config["layer_norm_epsilon"])
    return (y.reshape(seq, inner) * p["norm"]) @ p["w_out"]


def attention(h, p, config):
    """h [S, d] -> [S, d]; one head's [S, S] scores at a time."""
    seq = h.shape[0]
    heads, head_dim = p["wq"].shape[1:]
    group = heads // p["wk"].shape[1]
    assert heads == config["num_attention_heads"]
    q = jnp.einsum("sd,dhk->hsk", h, p["wq"])
    k, v = (jnp.repeat(jnp.einsum("sd,dhk->hsk", h, p[w]), group, axis=0)
            for w in ("wk", "wv"))
    causal = jnp.tril(jnp.ones((seq, seq), bool))

    def one_head(_, qkv):
        q, k, v = qkv
        scores = jnp.where(causal, q @ k.T / math.sqrt(head_dim), -jnp.inf)
        return None, jax.nn.softmax(scores, axis=-1) @ v

    _, attended = jax.lax.scan(jax.checkpoint(one_head), None, (q, k, v))
    return jnp.einsum("hsk,hkd->sd", attended, p["wo"])


def relu2(u):
    return jnp.square(jax.nn.relu(u))


def routed(h, p, config):
    """h [S, d] -> [S, d]: the held experts' part and the shared expert."""
    scores = jax.nn.sigmoid(h @ p["wg"])
    _, chosen = jax.lax.top_k(scores + p["bias"],
                              config["num_experts_per_tok"])
    mask = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1]), axis=-2)
    weight = (scores * mask / (jnp.sum(scores * mask, axis=-1, keepdims=True)
                               + 1e-20) * config["routed_scaling_factor"])
    first = config["deployment"]["first_expert"]
    held = p["w1"].shape[0]

    def one_expert(y, e):
        w1, w2, w = e
        return y + (relu2(h @ w1) @ w2) * w[:, None], None

    y, _ = jax.lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(h),
        (p["w1"], p["w2"], weight[:, first:first + held].T))
    return y + relu2(h @ p["shared_w1"]) @ p["shared_w2"]


MIXERS = {"M": ("mamba", mamba), "E": ("moe", routed), "*": ("attn",
                                                             attention)}


def sequence_loss(params, tokens, config):
    """tokens [S+1] -> the sequence's mean next-token cross-entropy."""
    inputs, targets = tokens[:-1], tokens[1:]
    eps = config["layer_norm_epsilon"]
    x = params["wte"][inputs]
    pattern = config["pattern"]
    for depth, kind in enumerate(pattern):
        name, mixer = MIXERS[kind]
        nth = pattern[:depth].count(kind)
        p = jax.tree_util.tree_map(lambda a: a[nth], params[name])
        x = jax.checkpoint(
            lambda x, p, mixer=mixer: x + mixer(
                rms_norm(x, p["ln"], eps), p, config))(x, p)
    logits = rms_norm(x, params["ln_f"], eps) @ params["head"].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def loss(params, tokens, config):
    """tokens [B, S+1]: mean next-token cross-entropy over B·S positions."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    return jnp.mean(jax.vmap(
        lambda row: sequence_loss(params, row, config))(tokens))
