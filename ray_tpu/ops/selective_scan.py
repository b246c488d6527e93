"""Mamba-1's selective scan: a state of `[channels, d_state]` a sequence,
every ELEMENT of it decayed by its own ``exp(Δ_t[c]·A[c, n])`` a token —

    H_t[c, n] = exp(Δ_t[c]·A[c, n])·H_{t−1}[c, n] + Δ_t[c]·B_t[n]·s_t[c]
    y_t[c]    = Σ_n C_t[n]·H_t[c, n] + D[c]·s_t[c],   Δ = softplus(dt + dt_bias)

— which `ops/ssd.py` cannot compute: Mamba-2's decay is one scalar a head,
which is what turns a chunk into a `Q × Q` matrix product; here nothing
factors, the work is element-wise on the state (VPU and EUP, no MXU) and a
naive form holds `[T, channels, d_state]` float32 arrays, 2.7 GB each at
8,192 tokens of 5,120 channels and 16 states.

:func:`selective_scan` is the entry, as :func:`ssd.ssd` is the other scan's,
and has TWO FORMS under the one scope `selective_scan`: two Pallas TPU
kernels under a `jax.custom_vjp` where `target.where(mesh)` says ONE TPU,
the channels are whole lane tiles, the states whole sublane tiles and a grid
step fits `VMEM_BUDGET_BYTES` (`_kernel_tiles`); the plain form everywhere
else — the CPU, a mesh of several devices (partitioned by XLA), the tiny
presets. Both float32 throughout — Δ and its softplus, decays, state,
readout, cotangents — with the exact `exp`; nothing is ever divided by a
decay (every factor is in (0, 1]); a length no block divides is padded with
Δ = 0 tokens, which leave the state as it is.

**The kernels** (`sscan_fwd`, `sscan_bwd` in HLO and trace; each behind a
module-level `jax.jit`: one trace and one Mosaic lowering a step whatever
the number of call sites). Grid (batch, block of channels, block of
`BLOCK_TOKENS` tokens), a channel block's token blocks in order — the
backward's in reverse. A channel block is `BLOCK_TILES` = 8 lane tiles, so
that ONE `(8, 128)` vector holds a token's 1,024 channels and `d_state` of
them the block's state, which lives in VMEM scratch from a sequence's first
block to its last and never reaches HBM a token. s, dt and y `[B, T, C]`
are handed over as the `(8, 128)` tiles the TPU already holds them in
(`_tiled`: `[B, T / 8, C / 128 · 8, 128]`, a bitcast in HBM once the
row-major layout is pinned, `_row_major` — left to itself XLA chose other
layouts for the projections' results and copied each array twice), and a
token's channels are sublane r of eight neighbouring tiles: ONE strided
load or store (`_token`). B_t[n] and C_t[n] are scalars from SMEM against
whole vectors — no lane broadcast; the readout's sum over n is 15 vector
adds — no sublane reduction. A block's tokens are a LOOP that carries the
state, every operation on all `d_state` of its vectors at once (a token's
`d_state` updates are independent chains for the scheduler),
`TOKENS_A_BODY` tokens a body. The body is all the host traces and lowers,
and the host's seconds before the first step are the cell's `setup_s`: a
body of eight tokens with every state's update written out cost it 30 s
(PERF.md §6, PR 52). What reaches HBM: s, dt, B, C read ONCE a pass and y
written once (the softplus and its pullback are in the kernels; dt_bias a
row);
forward residuals are the inputs and each token block's START state
(`[T / 64, 16, 5120]` float32, 42 MB a layer at the cell's shapes).
The backward rebuilds a block's states from its start state into VMEM
(`[block + 1, d_state, tiles, 128]`, 4.3 MB), then walks the tokens in
reverse with the state's cotangent in scratch, and writes the cotangents of
s and dt `[T, C]`, of A (summed in its output block over a channel block's
token blocks, a sequence's `[d_state, C]`) and of B and C as partial sums a
channel block `[C / 1024, B, T, N]`: a token's products are stored as
sublane r of a state's eight tiles (the strided store again), so adding
the tiles leaves `[8 tokens, 128]` and ONE lane reduction serves eight
tokens. Left in JAX: the padding, the sums of the partial sums over channel
blocks and of A's cotangent over the batch, D's and dt_bias's cotangents
(each one fused pass over a `[T, C]` array).

**The plain form** never holds a `[T, channels, d_state]` array either:

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
  running sum of Δ inside the chunk.

It holds the state `[d_state, channels]`, channels along the lanes; its
readout is a multiply and a sum over `d_state`, not a product on the MXU;
it is differentiated by JAX (the chunk steps' `lax.scan` inside the block's
checkpoint), reads and writes state-sized arrays in HBM 32 times a block
(129 ms of the cell's 516 ms step against the kernels' 16: PERF.md §6,
PR 52), and is the tests' control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, PartitionSpec

from ray_tpu.ops import target


def scan_plan(tokens: int, channels: int, d_state: int, *, chunk: int,
              block: int) -> dict:
    """Bytes of the two forms' arrays, float32, from shapes alone: the
    `naive` `[tokens, channels, d_state]` array no form here holds; the
    plain form's — what a step of the chunk-parallel walk holds (`step`,
    `[block / chunk, d_state, channels]`), one of the arrays a block's
    backward rebuilds (`block`), the start states the backward keeps
    (`kept`, one a block) — and the kernels': the start states their
    backward keeps (`starts`, one a `BLOCK_TOKENS`) and what a grid step
    holds in VMEM (`vmem_bytes`: `_vmem_bytes` at the channel block one TPU
    would take, 0 where the channels are no whole lane tiles)."""
    state = 4 * channels * d_state
    block = min(block, -(-tokens // chunk) * chunk)
    tiles = math.gcd(channels // _LANES, BLOCK_TILES)
    return {"naive": tokens * state, "step": block // chunk * state,
            "block": block * state, "kept": -(-tokens // block) * state,
            "starts": -(-tokens // BLOCK_TOKENS) * state,
            "vmem_bytes": (0 if channels % _LANES else
                           _vmem_bytes(BLOCK_TOKENS, tiles, d_state))}


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


# ---------------------------------------------------------------- kernels
_LANES, _SUBLANES = 128, 8
# tokens a grid step holds, whole groups of eight: the backward keeps every
# token's state of a block in VMEM (`d_state` vregs a token and channel
# block), so the block is short; swept on the chip at the cell's layer
# (PERF.md §6, PR 52)
BLOCK_TOKENS = 64
# lane tiles of channels a grid step holds, at most: at 8 a token's channels
# are ONE `(8, 128)` vreg and the state `d_state` of them
BLOCK_TILES = 8
# What a grid step's double-buffered blocks and its scratch may take of
# VMEM (`_vmem_bytes`); the kernels ask Mosaic for `_VMEM_LIMIT_BYTES` (a
# v5e core has 128 MiB), the compiler's own temporaries being the rest.
VMEM_BUDGET_BYTES = 16 * 1024 * 1024
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024
# tokens a body of the loops that walk a block forwards holds (1, 2, 4 or 8:
# a group of eight is whole bodies): the next token's loads, softplus and
# decays then do not wait for this token's chains. The body is what the host
# traces and lowers; swept with the block (PERF.md §6, PR 52): the step's six
# calls 18.5 / 16.8 / 16.0 ms at 2 / 4 / 8 for ~0.2 s of host time each
TOKENS_A_BODY = 8


def _vmem_bytes(block: int, tiles: int, d_state: int) -> int:
    """VMEM of a grid step of the backward (the larger of the two), float32:
    what it moves, double-buffered — s, dt and y's cotangent in, the
    cotangents of s and dt out (a token's channels of the block `tile`
    bytes); the partial sums of B's and C's cotangents `[block, d_state]`, a
    row padded to the lanes; A, the block's start state and A's cotangent
    (`state` bytes each); D and dt_bias — and what it keeps: the state
    before the block and behind each of its tokens, the state's cotangent,
    Δ and Δ·s a token, and eight tokens' products for the two sums."""
    tile = 4 * tiles * _LANES
    state = d_state * tile
    moved = (5 * block * tile + 2 * block * 4 * _LANES + 3 * state
             + 2 * tile)
    kept = ((block + 1) * state + state + 2 * block * tile
            + 2 * _SUBLANES * state)
    return 2 * moved + kept


def _kernel_tiles(platform: str, devices: int, channels: int,
                  d_state: int) -> int:
    """The lane tiles of channels a grid step of the kernels holds, or 0
    where the scan takes the plain form: the kernels run on ONE TPU (a mesh
    that splits the batch would need the call under a `shard_map`, which is
    not written) where the channels are whole lane tiles and the states
    whole sublane tiles — the largest number of lane tiles up to
    `BLOCK_TILES` that divides the channels' — and a grid step fits the
    VMEM budget. `platform` and `devices` are `target.where`'s answer."""
    if (platform != "tpu" or devices != 1 or channels % _LANES
            or d_state % _SUBLANES):
        return 0
    tiles = math.gcd(channels // _LANES, BLOCK_TILES)
    fits = _vmem_bytes(BLOCK_TOKENS, tiles, d_state) <= VMEM_BUDGET_BYTES
    return tiles if fits else 0


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _token(r: int, tiles: int):
    """Rows r, r + 8, … of a `_tiled` block's `[tiles · 8, 128]`: sublane r
    of each of its tiles, token r of the eight's channels — ONE strided load
    or store and a dense `[tiles, 128]` vector (an index on the sublane axis
    of a `[tiles, 8, 128]` block gives `tiles` vectors of one row each, and
    every operation on them an eighth of its lanes: 9.9 against 1.4 ms a
    forward call, PERF.md §6, PR 52)."""
    return pl.ds(r, tiles, stride=_SUBLANES)


def _at(t, tiles: int):
    """Token t of a `_tiled` block `[block / 8, tiles · 8, 128]`: its group
    of eight and, in it, its rows (`_token`)."""
    return t >> 3, _token(t & (_SUBLANES - 1), tiles)


def _scalars(ref, t, shape):
    """Token t's scalars of an SMEM block `[block, N]`, each on all of a
    `shape` vector: `[N, *shape]`."""
    return jnp.concatenate([jnp.full((1,) + shape, ref[t, n])
                            for n in range(ref.shape[1])])


def _walk(tokens: int, unrolled: int, one_token, carried):
    """`carried = one_token(t, carried)` for t = 0 … tokens − 1 in order,
    `unrolled` of them a loop body: the next token's loads, softplus and
    decays do not wait for this token's chains."""
    def body(step, carried):
        for u in range(unrolled):
            carried = one_token(step * unrolled + u, carried)
        return carried

    return jax.lax.fori_loop(0, tokens // unrolled, body, carried)


def _fwd_kernel(b_ref, c_ref, s_ref, dt_ref, a_ref, skip_ref, bias_ref,
                y_ref, starts_ref, state, *, unrolled: int):
    """A grid step: `block` tokens of one channel block. s, dt, y `[block /
    8, tiles · 8, 128]` (`_tiled`: a token's channels are sublane r of
    `tiles` tiles, read and written as ONE strided vector); A `[N, tiles,
    128]`; D, dt_bias `[tiles, 128]`; B, C `[block, N]` in SMEM, a scalar a
    token and state; `state` `[N, tiles, 128]` the scratch carried from
    block to block. A loop over the block's tokens that carries the state,
    every operation on all N of its vectors at once: a token's N updates
    are independent chains for the scheduler, and the loop's body — all the
    host traces and lowers — is a few dozen operations a token (the host's
    seconds are the cell's `setup_s`: a body of eight tokens with every
    state's update written out cost it 30 s, PERF.md §6, PR 52)."""
    @pl.when(pl.program_id(2) == 0)
    def _first_block():
        state[...] = jnp.zeros_like(state)

    starts_ref[...] = state[...]
    tiles = a_ref.shape[1]
    a, skip, bias = a_ref[...], skip_ref[...], bias_ref[...]

    def token(t, h):
        at = _at(t, tiles)
        s = s_ref[at]
        delta = _softplus(dt_ref[at] + bias)
        h = (jnp.exp(delta * a) * h
             + (delta * s) * _scalars(b_ref, t, s.shape))
        y_ref[at] = skip * s + jnp.sum(
            h * _scalars(c_ref, t, s.shape), axis=0)
        return h

    state[...] = _walk(s_ref.shape[0] * _SUBLANES, unrolled, token,
                       state[...])


def _bwd_kernel(b_ref, c_ref, s_ref, dt_ref, a_ref, skip_ref, bias_ref,
                starts_ref, dy_ref, ds_ref, ddt_ref, db_ref, dc_ref, da_ref,
                d_state, held, deltas, writes, b_parts, c_parts, *,
                unrolled: int):
    """A grid step: the forward's operands of one block, its start state and
    y's cotangent; the blocks of a channel block LAST FIRST. The block's
    states are rebuilt into `held` `[block + 1, N, tiles, 128]` (Δ and Δ·s
    into `deltas`, `writes`), then the tokens are walked in reverse with the
    state's cotangent carried by the loop and, from block to block, in
    `d_state`; A's cotangent is summed in its output block, which stays in
    VMEM for a channel block's blocks. The two sums over channels, B's and
    C's cotangents, are taken eight tokens at a time: a token's products are
    stored as sublane r of `tiles` tiles (`b_parts`, `c_parts` `[N, tiles ·
    8, 128]`), so that adding a state's tiles leaves `[8 tokens, 128]` and
    ONE lane reduction serves eight tokens."""
    @pl.when(pl.program_id(2) == 0)
    def _last_block():
        d_state[...] = jnp.zeros_like(d_state)
        da_ref[...] = jnp.zeros_like(da_ref)

    (states, tiles), groups = a_ref.shape[:2], s_ref.shape[0]
    a, skip, bias = a_ref[...], skip_ref[...], bias_ref[...]
    held[0] = starts_ref[...]

    def rebuild(t, h):
        at = _at(t, tiles)
        delta = _softplus(dt_ref[at] + bias)
        written = delta * s_ref[at]
        deltas[t], writes[t] = delta, written
        h = (jnp.exp(delta * a) * h
             + written * _scalars(b_ref, t, written.shape))
        held[t + 1] = h
        return h

    _walk(groups * _SUBLANES, unrolled, rebuild, starts_ref[...])

    def group(step, dh):
        first = (groups - 1 - step) * _SUBLANES

        def token(back, dh):
            t = first + _SUBLANES - 1 - back
            at = _at(t, tiles)
            own = at[1]
            dy, delta, written = dy_ref[at], deltas[t], writes[t]
            dh = dh + dy * _scalars(c_ref, t, dy.shape)
            c_parts[:, own, :] = dy * held[t + 1]
            b_parts[:, own, :] = dh * written
            d_written = jnp.sum(
                dh * _scalars(b_ref, t, dy.shape), axis=0)
            dh = dh * jnp.exp(delta * a)
            through = dh * held[t]
            da_ref[...] += through * delta
            ds_ref[at] = d_written * delta + skip * dy
            ddt_ref[at] = (
                (jnp.sum(through * a, axis=0) + d_written * s_ref[at])
                * jax.nn.sigmoid(dt_ref[at] + bias))
            return dh

        dh = jax.lax.fori_loop(0, _SUBLANES, token, dh)
        # (these loads wait for the strided stores above, a third of a
        # call; taken a group late out of a second scratch they do not —
        # measured, and left out for its code: PERF.md §7)
        rows = pl.ds(pl.multiple_of(first, _SUBLANES), _SUBLANES)
        for parts, out in ((b_parts, db_ref), (c_parts, dc_ref)):
            by_lane = jnp.sum(parts[...].reshape(
                states, tiles, _SUBLANES, _LANES), axis=1)
            by_token = jnp.sum(by_lane, axis=2, keepdims=True)
            for n in range(states):
                out[rows, n:n + 1] = by_token[n]
        return dh

    d_state[...] = jax.lax.fori_loop(0, groups, group, d_state[...])


def _row_major(x):
    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def _tiled(x):
    """[B, T, C] -> [B, T / 8, C / 128 · 8, 128], row (tile, sublane): the
    `(8, 128)` tiles of the array as the TPU holds it in HBM, tile after
    tile — a bitcast there (PERF.md §6, PR 52), and what lets a kernel read
    a token's channels as sublane r of neighbouring tiles (`_token`)."""
    b, tokens, channels = x.shape
    x = _row_major(x)
    return x.reshape(b, tokens // _SUBLANES, _SUBLANES, channels // _LANES,
                     _LANES).transpose(0, 1, 3, 2, 4).reshape(
                         b, tokens // _SUBLANES, -1, _LANES)


def _untiled(x):
    b, groups, rows, _ = x.shape
    return _row_major(x.reshape(
        b, groups, rows // _SUBLANES, _SUBLANES, _LANES).transpose(
            0, 1, 3, 2, 4).reshape(b, groups * _SUBLANES,
                                   rows // _SUBLANES * _LANES))


def _specs(block: int, tiles: int, states: int, block_of):
    """Block specs of a grid step (batch i, channel block j, step k of the
    token axis; `block_of` maps k to the block of tokens)."""
    return {
        "scalars": pl.BlockSpec((None, block, states),
                                lambda i, j, k: (i, block_of(k), 0),
                                memory_space=pltpu.SMEM),
        "tokens": pl.BlockSpec(
            (None, block // _SUBLANES, tiles * _SUBLANES, _LANES),
            lambda i, j, k: (i, block_of(k), j, 0)),
        "a": pl.BlockSpec((None, states, tiles, _LANES),
                          lambda i, j, k: (j, 0, 0, 0)),
        "channels": pl.BlockSpec((None, tiles, _LANES),
                                 lambda i, j, k: (j, 0, 0)),
        "starts": pl.BlockSpec((None, None, None, states, tiles, _LANES),
                               lambda i, j, k: (i, block_of(k), j, 0, 0, 0)),
        "by_state": pl.BlockSpec((None, None, block, states),
                                 lambda i, j, k: (j, i, block_of(k), 0)),
        "da": pl.BlockSpec((None, None, states, tiles, _LANES),
                           lambda i, j, k: (i, j, 0, 0, 0)),
    }


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT_BYTES)
_STATIC = ("block", "tiles", "unrolled", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _sscan_fwd(b_in, c_out, s, dt, a, d_skip, dt_bias, *, block, tiles,
               unrolled, interpret):
    """b_in, c_out [B, T, N]; s, dt `_tiled` [B, T / 8, C / 128 · 8, 128], T
    whole blocks; a [C / 128 / tiles, N, tiles, 128]; d_skip, dt_bias [C /
    128 / tiles, tiles, 128], all float32 -> y as s and every block's START
    state [B, T / block, C / 128 / tiles, N, tiles, 128]. Seven array
    operands: `flops.flash_call_cost` of the benchmark reads a Mosaic call
    of three or six as a flash kernel."""
    batch, groups = s.shape[:2]
    lane_tiles = s.shape[2] // _SUBLANES
    states, blocks = a.shape[1], groups * _SUBLANES // block
    spec = _specs(block, tiles, states, lambda k: k)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, unrolled=unrolled),
        grid=(batch, lane_tiles // tiles, blocks),
        in_specs=[spec["scalars"], spec["scalars"], spec["tokens"],
                  spec["tokens"], spec["a"], spec["channels"],
                  spec["channels"]],
        out_specs=[spec["tokens"], spec["starts"]],
        out_shape=[
            jax.ShapeDtypeStruct(s.shape, jnp.float32),
            jax.ShapeDtypeStruct((batch, blocks) + a.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM(a.shape[1:], jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="sscan_fwd",
    )(b_in, c_out, s, dt, a, d_skip, dt_bias)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _sscan_bwd(b_in, c_out, s, dt, a, d_skip, dt_bias, starts, dy, *, block,
               tiles, unrolled, interpret):
    """The operands of :func:`_sscan_fwd`, its start states and y's
    cotangent (as s) -> the cotangents of s and of dt (as s), of b_in and
    c_out as partial sums a channel block [C / 128 / tiles, B, T, N], and
    of a, a sequence's [B, C / 128 / tiles, N, tiles, 128]. Nine array
    operands (`_sscan_fwd`)."""
    batch, groups = s.shape[:2]
    lane_tiles = s.shape[2] // _SUBLANES
    states, tokens = a.shape[1], groups * _SUBLANES
    last = tokens // block - 1
    spec = _specs(block, tiles, states, lambda k: last - k)
    by_state = jax.ShapeDtypeStruct(
        (lane_tiles // tiles, batch, tokens, states), jnp.float32)
    state = a.shape[1:]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, unrolled=unrolled),
        grid=(batch, lane_tiles // tiles, last + 1),
        in_specs=[spec["scalars"], spec["scalars"], spec["tokens"],
                  spec["tokens"], spec["a"], spec["channels"],
                  spec["channels"], spec["starts"], spec["tokens"]],
        out_specs=[spec["tokens"], spec["tokens"], spec["by_state"],
                   spec["by_state"], spec["da"]],
        out_shape=[
            jax.ShapeDtypeStruct(s.shape, jnp.float32),
            jax.ShapeDtypeStruct(s.shape, jnp.float32), by_state, by_state,
            jax.ShapeDtypeStruct((batch,) + a.shape, jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM(state, jnp.float32),
            pltpu.VMEM((block + 1,) + state, jnp.float32),
            pltpu.VMEM((block,) + state[1:], jnp.float32),
            pltpu.VMEM((block,) + state[1:], jnp.float32),
            pltpu.VMEM((state[0], tiles * _SUBLANES, _LANES), jnp.float32),
            pltpu.VMEM((state[0], tiles * _SUBLANES, _LANES), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="sscan_bwd",
    )(b_in, c_out, s, dt, a, d_skip, dt_bias, starts, dy)


def _operands(s, dt, a, b_in, c_out, d_skip, dt_bias, block: int,
              tiles: int):
    """The kernels' operands of the scan's inputs, float32 [B, T, ·]: the
    length padded to whole blocks with tokens that leave the state as it is
    (s, B, C zero; dt so far below zero that its softplus IS zero), s and dt
    tiled, A transposed and, like D and dt_bias, cut to channel blocks."""
    tokens, channels = s.shape[1:]
    pad = [(0, 0), (0, -tokens % block), (0, 0)]
    width = tiles * _LANES

    def by_block(x):               # [..., C] -> [C / width, ..., tiles, 128]
        x = x.reshape(x.shape[:-1] + (channels // width, tiles, _LANES))
        return jnp.moveaxis(x, -3, 0)

    return (jnp.pad(b_in, pad), jnp.pad(c_out, pad), _tiled(jnp.pad(s, pad)),
            _tiled(jnp.pad(dt, pad, constant_values=-1e30)), by_block(a.T),
            by_block(d_skip), by_block(dt_bias))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _kernel_scan(s, dt, a, b_in, c_out, d_skip, dt_bias, static):
    """The scan through the kernels, every input float32. `static`: the
    mesh (or None) and the values of `_STATIC`."""
    return _kernel_scan_fwd(s, dt, a, b_in, c_out, d_skip, dt_bias,
                            static)[0]


def _kernel_scan_fwd(s, dt, a, b_in, c_out, d_skip, dt_bias, static):
    kw = dict(zip(_STATIC, static[1:]))
    y, starts = _sscan_fwd(*_operands(
        s, dt, a, b_in, c_out, d_skip, dt_bias, kw["block"], kw["tiles"]),
        **kw)
    return (_untiled(y)[:, :s.shape[1]],
            (s, dt, a, b_in, c_out, d_skip, dt_bias, starts))


def _kernel_scan_bwd(static, residuals, dy):
    mesh, kw = static[0], dict(zip(_STATIC, static[1:]))
    *inputs, starts = residuals
    s, dt, a = inputs[:3]
    tokens = s.shape[1]
    dy = dy.astype(jnp.float32)
    if mesh is not None:
        # one layer's cotangent reaches its rule typed by the mesh and the
        # next layer's does not, and a jit is traced — its kernel lowered —
        # again for every type it meets: all of them the mesh's (the
        # kernels run on one device: the constraint constrains nothing)
        dy = jax.lax.with_sharding_constraint(
            dy, NamedSharding(mesh, PartitionSpec()))
    whole = jnp.pad(dy, [(0, 0), (0, -tokens % kw["block"]), (0, 0)])
    ds, ddt, db, dc, da = _sscan_bwd(
        *_operands(*inputs, kw["block"], kw["tiles"]), starts, _tiled(whole),
        **kw)
    ds, ddt = _untiled(ds)[:, :tokens], _untiled(ddt)[:, :tokens]
    # [B, C / width, N, tiles, 128] -> [C, N], the sequences' summed
    da = jnp.moveaxis(jnp.sum(da, axis=0), 1, 0).reshape(a.shape[::-1]).T
    return (ds, ddt, da, jnp.sum(db, axis=0)[:, :tokens],
            jnp.sum(dc, axis=0)[:, :tokens], jnp.sum(dy * s, axis=(0, 1)),
            jnp.sum(ddt, axis=(0, 1)))


_kernel_scan.defvjp(_kernel_scan_fwd, _kernel_scan_bwd)


# ------------------------------------------------------------------ entry
def selective_scan(s, dt, a, b_in, c_out, d_skip, dt_bias, *, chunk: int = 32,
                   block: int = 512, mesh=None, interpret: bool = False):
    """s, dt [B, T, C]; a [C, N] (negative: ``−exp(A_log)``); b_in, c_out
    [B, T, N]; d_skip, dt_bias [C] -> y [B, T, C] float32, the recurrence of
    the module's docstring with ``Δ = softplus(dt + dt_bias)``, a zero state
    before each sequence's first token, each sequence of the batch on its
    own. Differentiable in all seven.

    mesh: where the scan runs (`target.where`); that and the shapes decide
    between the kernels and the plain form (`_kernel_tiles`). `interpret`
    runs the kernels in Pallas's interpreter wherever the process is, and
    exists for tests.

    chunk, block: the plain form's walk (sequential steps a chunk; tokens a
    checkpointed block, a multiple of `chunk`); any T: the tail is padded
    with tokens that leave the state as it is."""
    if block % chunk:
        raise ValueError(f"block {block} is no multiple of chunk {chunk}")
    f32 = jnp.float32
    channels, states = a.shape
    tiles = _kernel_tiles(*target.where(mesh, interpret=interpret), channels,
                          states)
    if interpret and not tiles:
        raise ValueError(f"selective_scan: no kernel tiling for {channels} "
                         f"channels of {states} states")
    with jax.named_scope("selective_scan"):
        if tiles:
            return _kernel_scan(
                *(x.astype(f32)
                  for x in (s, dt, a, b_in, c_out, d_skip, dt_bias)),
                (mesh, BLOCK_TOKENS, tiles, TOKENS_A_BODY, interpret))
        delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        one = jax.vmap(
            lambda s_, dl, b, c: _one_sequence(
                s_, dl, a.astype(f32).T, b, c, d_skip.astype(f32),
                chunk=chunk, block=block))
        return one(s.astype(f32), delta, b_in.astype(f32),
                   c_out.astype(f32))
