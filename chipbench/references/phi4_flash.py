"""Phi-4-mini-flash-reasoning (microsoft/Phi-4-mini-flash-reasoning, the
SambaY decoder-hybrid-decoder of arXiv:2507.06607 with the differential
attention of arXiv:2410.05258), plainly: the forward pass and the training
loss in ``jax.numpy`` and float32, written from the model's public
``config.json`` and the family's public modelling code, importing nothing
from the program. Gradients are ``jax.grad`` of this function.

It reads the system's parameter tree as it stands: ``wte`` [V, d] (tied: the
embedding and the head), ``ln_f``, ``ln_f_b`` [d]; ``layers``, a list with
one tree a layer: ``ln_mix``, ``ln_mix_b``, ``ln_ff``, ``ln_ff_b`` [d]; ``ff``
= {w_gate, w_up [d, F], w_down [F, d]}; ``mixer`` one of — told by its keys —
a Mamba-1 mixer's {w_in [d, s | z], conv_w [taps, C], conv_b [C], w_x [C, r |
B | C], w_dt [r, C], dt_bias [C], A_log [C, N], D [C], w_out [C, d]}; an
attention layer's {w_qkv [d, q | k | v], b_qkv, w_o [q, d], b_o, lambda_q1,
lambda_k1, lambda_q2, lambda_k2 [K], subln [2K]}; a cross layer's, the same
with {w_q [d, q], b_q} for w_qkv; a gated memory unit's {w_in [d, C], w_out
[C, d]}. What no leaf's shape gives is read from ``config``, the
configuration file as the cell runs it: ``layer_norm_eps``,
``num_attention_heads``, ``num_key_value_heads``, ``sliding_window``,
``num_hidden_layers`` (the published n, which places the two decoders) and
``first_layer`` (the published index of the tree's first layer, 0 where the
file has none).

The equations, layer i (published index) on the stream x [S, d], ``LN`` a
LayerNorm with bias:

* ``h = x + mixer_i(LN(x))``; ``out = h + (silu(n·W_gate) ∘ (n·W_up))·W_down``,
  ``n = LN(h)``;
* even i ≤ n/2, Mamba-1: ``[s | z] = m·W_in``; ``s ← silu(Σ_j taps_j ∘
  s_{t−3+j} + b)``, zero before the first token; ``[r | B | C] = s·W_x``; ``Δ
  = softplus(r·W_dt + dt_bias)``; ``A = −exp(A_log)``; THE RECURRENCE, token
  by token, state H [C, N] from zero: ``H ← exp(Δ_t ⊗ 1 ∘ A) ∘ H + (Δ_t ∘
  s_t) ⊗ B_t``, ``y_t = H·C_t + D ∘ s_t``; ``(y ∘ silu(z))·W_out``. Layer
  n/2's ``y`` is the MEMORY;
* odd i ≤ n/2 + 1, differential attention: ``[q | k | v] = m·W_qkv + b`` as
  H / KV / KV heads of K = d / H; pair j's ``q¹, q²`` = query heads 2j, 2j +
  1; KV pair m's ``k¹, k²`` = K heads 2m, 2m + 1 and ``V`` = V heads 2m, 2m +
  1 side by side; pair j reads KV pair ``j // (H / KV)``; ``a¹ =
  softmax(q¹k¹ᵀ/√K)·V``, ``a²`` from ``q², k²`` — scores kept where j ≤ i
  and, for i < n/2, ``j > i − sliding_window``: an explicit mask, a block
  of queries at a time; ``λ = exp(λ_q1·λ_k1) − exp(λ_q2·λ_k2) + λ_init``,
  ``λ_init = 0.8 − 0.6·exp(−0.3·i)``; ``o = (a¹ − λa²) / rms_2K(·) · subln ·
  (1 − λ_init)``; ``o·W_o + b_o``. Layer n/2 + 1's k and v are the SHARED
  ones;
* even i ≥ n/2 + 2, a gated memory unit: ``(silu(m·W_in) ∘ memory)·W_out``;
* odd i ≥ n/2 + 3, cross attention: ``q = m·W_q + b`` alone, the shared k
  and v, the differential form with this layer's λ vectors and sub-norm,
  full causal;
* a last LN, logits ``·wteᵀ``, the mean cross-entropy of ``t[1..S]``.

Departures from the published description: none in the mathematics. The
published code runs four attention calls at K a layer (``a¹`` and ``a²`` each
on V's two halves); the two halves side by side are the same numbers. The
feed-forward's fused ``[g | u]`` matrix is two leaves.

Callers on a TPU wrap the call in ``jax.default_matmul_precision("highest")``.
``lax.scan`` over tokens, pairs and query blocks and ``jax.checkpoint``
change what is held in memory, not what is computed.
"""
import math

import jax
import jax.numpy as jnp

# queries whose [block, S] scores are held at once
QUERY_BLOCK = 2048
# tokens of the recurrence whose states are held at once in the backward
TOKEN_BLOCK = 128


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def recurrence(s, delta, a, b_in, c_out):
    """s, Δ [S, C], a [C, N], B, C [S, N] -> y [S, C] (without the skip):
    the selective scan one token after the other."""
    seq = s.shape[0]

    def token(state, now):
        s_t, delta_t, b_t, c_t = now
        state = (jnp.exp(delta_t[:, None] * a) * state
                 + (delta_t * s_t)[:, None] * b_t[None, :])
        return state, jnp.sum(state * c_t[None, :], axis=-1)

    def block(state, chunk):
        return jax.lax.scan(token, state, chunk)

    pad = -seq % TOKEN_BLOCK
    blocks = [jnp.pad(x, [(0, pad), (0, 0)]).reshape(
        -1, TOKEN_BLOCK, x.shape[-1]) for x in (s, delta, b_in, c_out)]
    _, out = jax.lax.scan(jax.checkpoint(block), jnp.zeros(a.shape),
                          tuple(blocks))
    return out.reshape(-1, s.shape[-1])[:seq]


def mamba(m, p):
    """m [S, d] -> (the mixer's output [S, d], y [S, C] before the gate)."""
    seq, inner = m.shape[0], p["conv_w"].shape[1]
    states, rank = p["A_log"].shape[1], p["w_dt"].shape[0]
    mixed = m @ p["w_in"]
    s, z = mixed[:, :inner], mixed[:, inner:]
    taps = p["conv_w"].shape[0]
    before = jnp.pad(s, [(taps - 1, 0), (0, 0)])
    s = jax.nn.silu(sum(before[i:i + seq] * p["conv_w"][i]
                        for i in range(taps)) + p["conv_b"])
    rbc = s @ p["w_x"]
    delta = jax.nn.softplus(rbc[:, :rank] @ p["w_dt"] + p["dt_bias"])
    y = recurrence(s, delta, -jnp.exp(p["A_log"]), rbc[:, rank:rank + states],
                   rbc[:, rank + states:]) + p["D"] * s
    return (y * jax.nn.silu(z)) @ p["w_out"], y


def softmax_attention(q, k, v, window):
    """q, k [P, S, K], v [P, S, W] -> [P, S, W]: causal softmax attention a
    row of P at a time, one [block, S] table of scores at a time; `window`:
    None, or the keys a query sees, itself among them."""
    seq, width = q.shape[1:]
    block = min(QUERY_BLOCK, seq)
    pad = -seq % block
    rows = jnp.arange(seq + pad).reshape(-1, block)
    cols = jnp.arange(seq)

    def one_row(_, qkv):
        q, k, v = qkv

        def one_block(_, qi):
            q_block, i = qi
            keep = cols[None, :] <= i[:, None]
            if window is not None:
                keep &= cols[None, :] > i[:, None] - window
            scores = jnp.where(keep, q_block @ k.T / math.sqrt(width),
                               -jnp.inf)
            return None, jax.nn.softmax(scores, axis=-1) @ v

        q = jnp.pad(q, [(0, pad), (0, 0)]).reshape(-1, block, width)
        _, out = jax.lax.scan(jax.checkpoint(one_block), None, (q, rows))
        return None, out.reshape(-1, v.shape[-1])[:seq]

    return jax.lax.scan(one_row, None, (q, k, v))[1]


def diff_attention(m, p, *, depth, window, kv, config):
    """m [S, d] -> (the layer's output [S, d], (k [S, KV, K], v [S, KV,
    K])); `kv`: None, or another layer's."""
    seq, eps = m.shape[0], config["layer_norm_eps"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    width = p["w_o"].shape[0] // heads
    if kv is None:
        qkv = m @ p["w_qkv"] + p["b_qkv"]
        q = qkv[:, :heads * width]
        k = qkv[:, heads * width:(heads + kv_heads) * width]
        v = qkv[:, (heads + kv_heads) * width:]
        kv = (k.reshape(seq, kv_heads, width), v.reshape(seq, kv_heads, width))
    else:
        q = m @ p["w_q"] + p["b_q"]
    k, v = kv
    # pairs: [S, heads / 2, 2, K]; V's two heads of a pair side by side
    q = q.reshape(seq, heads // 2, 2, width)
    k = k.reshape(seq, kv_heads // 2, 2, width)
    v = v.reshape(seq, kv_heads // 2, 2 * width)
    # a KV pair's k and V for each of the query pairs that read it
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))
    v = v.transpose(1, 0, 2)
    a1, a2 = (softmax_attention(q[:, :, i].transpose(1, 0, 2),
                                k[:, :, i].transpose(1, 0, 2), v, window)
              for i in (0, 1))
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0)
    o = a1 - lam * a2                                   # [pairs, S, 2K]
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                     + eps) * p["subln"] * (1.0 - lam0)
    return o.transpose(1, 0, 2).reshape(seq, -1) @ p["w_o"] + p["b_o"], kv


def gated(n, p):
    return (jax.nn.silu(n @ p["w_gate"]) * (n @ p["w_up"])) @ p["w_down"]


def layer(x, p, memory, kv, *, depth, config):
    """One layer -> (the stream, the memory, the shared (k, v)), the last
    two as they stand behind this layer."""
    eps, half = config["layer_norm_eps"], config["num_hidden_layers"] // 2
    m = layer_norm(x, p["ln_mix"], p["ln_mix_b"], eps)
    mixer = p["mixer"]
    if "w_x" in mixer:
        out, y = mamba(m, mixer)
        if depth == half:
            memory = y
    elif "w_qkv" in mixer:
        out, own = diff_attention(
            m, mixer, depth=depth, kv=None, config=config,
            window=config["sliding_window"] if depth < half else None)
        if depth == half + 1:
            kv = own
    elif "w_q" in mixer:
        out, _ = diff_attention(m, mixer, depth=depth, window=None, kv=kv,
                                config=config)
    else:
        out = (jax.nn.silu(m @ mixer["w_in"]) * memory) @ mixer["w_out"]
    h = x + out
    return (h + gated(layer_norm(h, p["ln_ff"], p["ln_ff_b"], eps), p["ff"]),
            memory, kv)


def sequence_loss(params, tokens, config):
    """tokens [S+1] -> the sequence's mean next-token cross-entropy."""
    x = params["wte"][tokens[:-1]]
    memory = kv = None
    for depth, p in enumerate(params["layers"], config.get("first_layer", 0)):
        run = jax.checkpoint(
            lambda x, p, memory, kv, depth=depth: layer(
                x, p, memory, kv, depth=depth, config=config))
        x, memory, kv = run(x, p, memory, kv)
    logits = layer_norm(x, params["ln_f"], params["ln_f_b"],
                        config["layer_norm_eps"]) @ params["wte"].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


def loss(params, tokens, config):
    """tokens [B, S+1]: the mean over the B sequences."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    return jnp.mean(jax.vmap(
        lambda row: sequence_loss(params, row, config))(tokens))
