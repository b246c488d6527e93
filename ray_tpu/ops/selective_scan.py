"""Mamba-1's selective scan: a state of `[channels, d_state]` a sequence,
every ELEMENT of it decayed by its own ``exp(Δ_t[c]·A[c, n])`` a token —

    H_t[c, n] = exp(Δ_t[c]·A[c, n])·H_{t−1}[c, n] + Δ_t[c]·B_t[n]·s_t[c]
    y_t[c]    = Σ_n C_t[n]·H_t[c, n] + D[c]·s_t[c],   Δ = softplus(dt + dt_bias)

— which `ops/ssd.py` cannot compute: Mamba-2's decay is one scalar a head,
which is what turns a chunk into a `Q × Q` matrix product; here nothing
factors, the work is element-wise on the state (VPU and EUP, no MXU) and a
naive form holds `[T, channels, d_state]` float32 arrays, 2.7 GB each at
8,192 tokens of 5,120 channels and 16 states.

:func:`selective_scan` is the entry, as :func:`ssd.ssd` is the other scan's.
**There is no kernel yet**: the plain form below runs wherever the call is
traced, partitioned by XLA under a mesh. (When one is written the entry
takes `mesh=` and `ops/target.py`'s rule: the kernel where
`target.where(mesh)` says one TPU and its tiles divide the shapes, this
form everywhere else.)

The plain form never holds a `[T, channels, d_state]` array:

* the sequence is walked in BLOCKS of `block` tokens by a `lax.scan` that
  carries the state `[d_state, channels]` in float32, and each block's body
  is a `jax.checkpoint`: the backward pass keeps a block's inputs and its
  START STATE, and rebuilds what lies inside a block — the states a token,
  the decays — from them, one block at a time (`[block, d_state,
  channels]`, a sixteenth of the whole at 512 of 8,192 tokens);
* inside a block the recurrence runs CHUNK-PARALLEL: the block's `block /
  chunk` chunks side by side, `chunk` sequential steps on `[chunks, d_state,
  channels]` arrays from a zero state a chunk (a step's arrays are then
  MBs, not the 320 KB of one token's state, and `chunk` + `block / chunk`
  loop iterations stand where `block` would); the chunks' end states are
  chained by the chunks' total decays (``exp(A·ΣΔ)``, a short scan), and
  what a chunk's start state adds to its tokens' readout is one fused
  product, ``Σ_n C_t[n]·exp(A[c, n]·cs_t[c])·H_start[c, n]`` with `cs` the
  running sum of Δ inside the chunk. Every factor is a decay in (0, 1]:
  nothing is divided by one.

The state is held `[d_state, channels]`, channels along the lanes. All of it
float32: decays, state, readout, Δ and its softplus. The readout is a
multiply and a sum over `d_state`, not a product on the MXU. Differentiated
by JAX (the chunk steps' `lax.scan` inside the block's checkpoint).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def scan_plan(tokens: int, channels: int, d_state: int, *, chunk: int,
              block: int) -> dict:
    """Bytes of the plain form's arrays, float32, from shapes alone: the
    `naive` `[tokens, channels, d_state]` array no form here holds; what a
    step of the chunk-parallel walk holds (`step`, `[block / chunk, d_state,
    channels]`); one of the arrays a block's backward rebuilds (`block`);
    the start states the backward keeps (`kept`, one a block)."""
    state = 4 * channels * d_state
    block = min(block, -(-tokens // chunk) * chunk)
    return {"naive": tokens * state, "step": block // chunk * state,
            "block": block * state, "kept": -(-tokens // block) * state}


def _block(h0, xs, a_t, d_skip, *, chunk: int):
    """One block of one sequence. h0 [N, C] the state before it; xs = (s, Δ
    [T_b, C], B, C [T_b, N]); a_t = A transposed, [N, C]. -> (the state
    behind the block, y [T_b, C])."""
    s, delta, b_in, c_out = xs
    (tokens, channels), states = s.shape, a_t.shape[0]
    lanes = tokens // chunk                      # chunks side by side

    def steps(x):       # [T_b, W] -> [chunk, lanes, W]: step q of every chunk
        return x.reshape(lanes, chunk, x.shape[-1]).swapaxes(0, 1)

    def local(h, step):
        # h [lanes, N, C]: every chunk's state, from zero at its start
        ds, dl, b, c = step
        h = (jnp.exp(dl[:, None, :] * a_t) * h
             + (dl * ds)[:, None, :] * b[:, :, None])
        return h, jnp.sum(c[:, :, None] * h, axis=1)

    ends, y_local = jax.lax.scan(
        local, jnp.zeros((lanes, states, channels), jnp.float32),
        tuple(steps(x) for x in (s, delta, b_in, c_out)))

    # the running sum of Δ inside each chunk, and each chunk's total decay
    run = jnp.cumsum(delta.reshape(lanes, chunk, channels), axis=1)
    total = jnp.exp(run[:, -1, None, :] * a_t)               # [lanes, N, C]

    def chain(h, chunk_):
        decay, end = chunk_
        return decay * h + end, h                # a chunk's START state out

    h_end, starts = jax.lax.scan(chain, h0, (total, ends))
    # what the start states add to the readout, one fused pass
    carried = jnp.sum(
        c_out.reshape(lanes, chunk, states, 1)
        * jnp.exp(run[:, :, None, :] * a_t) * starts[:, None], axis=2)
    y = (y_local.swapaxes(0, 1) + carried).reshape(tokens, channels)
    return h_end, y + d_skip * s


def _one_sequence(s, delta, a_t, b_in, c_out, d_skip, *, chunk, block):
    tokens, channels = s.shape
    block = min(block, -(-tokens // chunk) * chunk)
    padded = -(-tokens // block) * block
    # a padded token has Δ = 0: decay 1, nothing written — the state stands
    xs = tuple(jnp.pad(x, [(0, padded - tokens), (0, 0)]).reshape(
        padded // block, block, x.shape[-1])
        for x in (s, delta, b_in, c_out))
    body = jax.checkpoint(
        lambda h, x: _block(h, x, a_t, d_skip, chunk=chunk))
    _, y = jax.lax.scan(
        body, jnp.zeros((a_t.shape[0], channels), jnp.float32), xs)
    return y.reshape(padded, channels)[:tokens]


def selective_scan(s, dt, a, b_in, c_out, d_skip, dt_bias, *, chunk: int = 32,
                   block: int = 512):
    """s, dt [B, T, C]; a [C, N] (negative: ``−exp(A_log)``); b_in, c_out
    [B, T, N]; d_skip, dt_bias [C] -> y [B, T, C] float32, the recurrence of
    the module's docstring with ``Δ = softplus(dt + dt_bias)``, a zero state
    before each sequence's first token, each sequence of the batch on its
    own. Differentiable in all seven.

    chunk, block: the plain form's walk (sequential steps a chunk; tokens a
    checkpointed block, a multiple of `chunk`); any T: the tail is padded
    with tokens that leave the state as it is."""
    if block % chunk:
        raise ValueError(f"block {block} is no multiple of chunk {chunk}")
    f32 = jnp.float32
    with jax.named_scope("selective_scan"):
        delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        one = jax.vmap(
            lambda s_, dl, b, c: _one_sequence(
                s_, dl, a.astype(f32).T, b, c, d_skip.astype(f32),
                chunk=chunk, block=block))
        return one(s.astype(f32), delta, b_in.astype(f32),
                   c_out.astype(f32))
