"""`ray_tpu.ops.selective_scan`, both forms — the chunk-parallel,
block-checkpointed plain form and the two Pallas kernels in the interpreter
— against Mamba-1's recurrence written out token by token in float32
(values and the gradients of all seven inputs), at lengths the walk's blocks
divide and do not, with the state carried across chunks and across blocks;
which form a call takes and that the plain form's bytes are the parent's;
what each form holds in memory, from the plain form's jaxpr and from
`scan_plan` by hand; the kernels' operand counts against the benchmark's
flash reader."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import selective_scan as ss

INPUTS = ("s", "dt", "a", "b_in", "c_out", "d_skip", "dt_bias")


def recurrence(s, dt, a, b_in, c_out, d_skip, dt_bias):
    """One token after the other: the state [C, N] from zero."""
    delta = jax.nn.softplus(dt + dt_bias)

    def one(s, delta, b_in, c_out):
        def token(h, now):
            s_t, delta_t, b_t, c_t = now
            h = (jnp.exp(delta_t[:, None] * a) * h
                 + (delta_t * s_t)[:, None] * b_t[None])
            return h, jnp.sum(h * c_t[None], axis=-1) + d_skip * s_t
        return jax.lax.scan(token, jnp.zeros(a.shape),
                            (s, delta, b_in, c_out))[1]
    return jax.vmap(one)(s, delta, b_in, c_out)


def _inputs(T, B=2, C=24, N=4, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    args = (jax.random.normal(k[0], (B, T, C)),
            jax.random.normal(k[1], (B, T, C)),
            -jnp.exp(jax.random.normal(k[2], (C, N))),
            jax.random.normal(k[3], (B, T, N)),
            jax.random.normal(k[4], (B, T, N)),
            jax.random.normal(k[5], (C,)), jax.random.normal(k[6], (C,)))
    return args, jax.random.normal(k[7], (B, T, C))


# (tokens, chunk, block): both divide; neither the chunk nor the block
# divides the length (a padded tail); one block that is longer than the
# sequence; shorter than a chunk
WALKS = [(32, 4, 8), (37, 4, 8), (37, 8, 64), (3, 4, 8)]
# the kernels' shapes: two lane tiles of channels in two channel blocks, 16
# states, token blocks of 8 (`small_blocks`), one sequence — every kernel
# case the SAME padded shapes, so that one worker interprets each kernel
# once (~25 s; the jits behind the kernels keep the program)
KERNEL = dict(B=1, C=256, N=16)
# (form, tokens, chunk, block): the kernels at two token blocks and at a
# length no block divides
FORMS = [("plain",) + walk for walk in WALKS] + [
    ("kernel", 16, None, None), ("kernel", 13, None, None)]
# Against the recurrence: the plain form's limits are the parent's. The
# kernels add a token's 16 states in another order than the recurrence's one
# `sum`, take B's and C's cotangents over channels by tile and then by lane,
# and their softplus is written out (`max + log1p(exp(−|x|))`): float32
# rounding in another order, measured at ≤ 3e-7 of a gradient's largest
# entry here — their limit is ten times that, which is the plain form's.
LIMITS = {"plain": dict(total=2e-6, y=2e-5, grad=3e-6),
          "kernel": dict(total=2e-6, y=2e-5, grad=3e-6)}


@pytest.fixture
def small_blocks(monkeypatch):
    """Token blocks of 8 and channel blocks of one lane tile: the module's
    constants reach the kernels' jits as static values at each call."""
    monkeypatch.setattr(ss, "BLOCK_TOKENS", 8)
    monkeypatch.setattr(ss, "BLOCK_TILES", 1)


def _weighted(fn, w, jit=True):
    """(the output, its sum weighted by w) and the latter's gradients in
    all seven inputs; jitted, or op by op so that the kernels' own jits are
    met again by the next case."""
    def run(*args):
        y = fn(*args)
        return jnp.sum(y * w), y
    both = jax.value_and_grad(run, argnums=tuple(range(7)), has_aux=True)
    return jax.jit(both) if jit else both


def _scan(form, chunk=None, block=None):
    if form == "kernel":
        return functools.partial(ss.selective_scan, interpret=True)
    return functools.partial(ss.selective_scan, chunk=chunk, block=block)


@pytest.mark.parametrize("form, T, chunk, block", FORMS)
def test_values_and_all_seven_gradients(form, T, chunk, block, small_blocks):
    args, w = _inputs(T, **(KERNEL if form == "kernel" else {}))
    (got, y), got_grads = _weighted(_scan(form, chunk, block), w,
                                    jit=form == "plain")(*args)
    (want, y_want), want_grads = _weighted(recurrence, w)(*args)
    limit = LIMITS[form]
    np.testing.assert_allclose(got, want, rtol=limit["total"])
    np.testing.assert_allclose(y, y_want, atol=limit["y"])
    for name, g, r in zip(INPUTS, got_grads, want_grads):
        assert float(jnp.max(jnp.abs(g - r))) <= limit["grad"] * float(
            jnp.max(jnp.abs(r))), name


@pytest.mark.parametrize("form", ["kernel", "plain"])
def test_the_state_is_carried_across_chunks_and_blocks(form, small_blocks):
    """Slow decays, so that a token is felt far behind it: what the first
    chunk wrote reaches the last block's tokens, whatever the walk (the
    kernels': two blocks of 8 tokens, the state carried in their scratch)."""
    T = 16 if form == "kernel" else 48
    (s, dt, a, b_in, c_out, d_skip, bias), _ = _inputs(
        T, **(KERNEL if form == "kernel" else {}))
    a, bias = a * 0.01, bias - 3.0
    def run(s_, **walk):
        return jax.jit(lambda s_: ss.selective_scan(
            s_, dt, a, b_in, c_out, d_skip, bias, **walk))(s_)
    def walked(s_):
        if form == "kernel":
            return _scan(form)(s_, dt, a, b_in, c_out, d_skip, bias)
        return run(s_, chunk=4, block=8)
    one_block = run(s, chunk=T, block=T)
    np.testing.assert_allclose(walked(s), one_block, atol=2e-5)
    without = walked(s.at[:, :4].set(0.0))
    # the skip term D·s reaches a token's own output only
    assert float(jnp.max(jnp.abs((one_block - without)[:, T - 8:]))) > 1e-2


def _parents_plain_form(s, dt, a, b_in, c_out, d_skip, dt_bias, chunk=32,
                        block=512):
    """The parent's `selective_scan`, word for word."""
    f32 = jnp.float32
    delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    one = jax.vmap(
        lambda s_, dl, b, c: ss._one_sequence(
            s_, dl, a.astype(f32).T, b, c, d_skip.astype(f32),
            chunk=chunk, block=block))
    return one(s.astype(f32), delta, b_in.astype(f32), c_out.astype(f32))


@pytest.mark.parametrize("where", ["off_the_tiles_on_a_tpu",
                                   "a_two_device_mesh", "the_cpu"])
def test_off_the_kernels_path_the_plain_forms_bytes(where, runs_on):
    """Shapes the tiles do not divide (the tiny presets' 24 channels of 4
    states) even where `where` says one TPU, a mesh of two devices and the
    CPU at shapes the tiles DO divide: no kernel in the jaxpr, and the
    parent's plain form to the byte."""
    mesh = None
    if where == "off_the_tiles_on_a_tpu":
        runs_on("tpu")
        args, _ = _inputs(40)
    else:
        args, _ = _inputs(40, **KERNEL)
        if where == "a_two_device_mesh":
            mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("dp",))
    scan = functools.partial(ss.selective_scan, chunk=8, block=16, mesh=mesh)
    assert "pallas_call" not in str(jax.make_jaxpr(scan)(*args))
    np.testing.assert_array_equal(
        jax.jit(scan)(*args),
        jax.jit(functools.partial(_parents_plain_form, chunk=8,
                                  block=16))(*args))
    with pytest.raises(ValueError, match="no kernel tiling"):
        ss.selective_scan(*_inputs(8)[0], interpret=True)


def test_the_cells_shapes_on_one_tpu_are_the_kernels(runs_on):
    runs_on("tpu")
    args, _ = _inputs(64, B=1, C=1024, N=16)
    grad = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ss.selective_scan(*a)),
        argnums=tuple(range(7))))(*args))
    assert grad.count("name=sscan_fwd") == 1
    assert grad.count("name=sscan_bwd") == 1
    assert ss._kernel_tiles("tpu", 1, 5120, 16) == 8
    # a grid step over the budget, two devices, another platform: plain
    assert ss._kernel_tiles("tpu", 1, 5120, 64) == 0
    assert ss._kernel_tiles("tpu", 2, 5120, 16) == 0
    assert ss._kernel_tiles("cpu", 1, 5120, 16) == 0


def test_the_benchmarks_flash_reader_passes_the_kernels_by():
    """At the cell's shapes: `chipbench.flops.flash_call_cost` reads any
    Mosaic call of three or six array operands as a flash kernel;
    `sscan_fwd` has seven and `sscan_bwd` nine, and neither is taken for
    one."""
    from chipbench import flops
    from tests.test_ssd_kernels import _event_text

    B, T, C, N, block, tiles = 1, 8192, 5120, 16, 64, 8
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    inputs = (f32((B, T, C)), f32((B, T, C)), f32((C, N)), f32((B, T, N)),
              f32((B, T, N)), f32((C,)), f32((C,)))
    operands = jax.eval_shape(
        lambda *a: ss._operands(*a, block, tiles), *inputs)
    kw = dict(block=block, tiles=tiles, unrolled=2, interpret=False)
    calls = {
        "sscan_fwd": jax.make_jaxpr(
            lambda *a: ss._sscan_fwd(*a, **kw))(*operands),
        "sscan_bwd": jax.make_jaxpr(
            lambda *a: ss._sscan_bwd(*a, **kw))(
                *operands,
                f32((B, T // block, C // 128 // tiles, N, tiles, 128)),
                operands[2]),
    }
    counts = {}
    for name, jaxpr in calls.items():
        (eqn,) = [e for e in jaxpr.eqns[-1].params["jaxpr"].eqns
                  if e.primitive.name == "pallas_call"]
        assert eqn.params["name"] == name
        assert flops.flash_call_cost(_event_text(name, eqn)) is None, name
        counts[name] = len(eqn.invars)
    assert counts == {"sscan_fwd": 7, "sscan_bwd": 9}


def test_a_block_is_a_multiple_of_the_chunk():
    args, _ = _inputs(16)
    with pytest.raises(ValueError, match="no multiple of chunk"):
        ss.selective_scan(*args, chunk=8, block=12)


def _largest(jaxpr) -> int:
    """Elements of the largest array a jaxpr, or any jaxpr inside it,
    holds."""
    most = 0
    for eqn in jaxpr.eqns:
        most = max([most] + [v.aval.size for v in eqn.outvars
                             if hasattr(v.aval, "size")])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            most = max(most, _largest(sub))
    return most


def test_no_array_of_every_tokens_state_is_ever_held():
    """Forward and backward of 256 tokens in blocks of 64: the largest
    array is one block's states (twice an input's `[T, C]` here), never
    `[T, C, N]`."""
    T, C, N = 256, 16, 8
    args, w = _inputs(T, B=1, C=C, N=N)
    grad = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ss.selective_scan(*a, chunk=8, block=64) * w),
        argnums=tuple(range(7))))(*args)
    assert T * C < _largest(grad.jaxpr) <= 64 * C * N < T * C * N


def test_scan_plan_by_hand():
    """The cell's 8,192 tokens of 5,120 channels and 16 states: 2.68 GB
    for the array no form holds; a block of 512 tokens 168 MB, a step of
    16 chunks 5.2 MB, sixteen start states 5.2 MB."""
    plan = ss.scan_plan(8192, 5120, 16, chunk=32, block=512)
    # the kernels' grid step, 64 tokens of 8 lane tiles: a token's channels
    # 4,096 bytes, a state 65,536; moved, twice over: five [64 tokens] blocks
    # 1,310,720, two [64, 16 -> 128] 65,536, three states 196,608, two rows
    # 8,192; kept: 65 states 4,259,840, the state's cotangent 65,536, Δ and
    # Δ·s 524,288, eight tokens' products twice 1,048,576
    assert plan == {"naive": 8192 * 5120 * 16 * 4, "step": 16 * 327680,
                    "block": 512 * 327680, "kept": 16 * 327680,
                    "starts": 128 * 327680,
                    "vmem_bytes": 2 * 1_581_056 + 5_898_240}
    assert plan["naive"] == 2_684_354_560 and plan["block"] == 167_772_160
    assert plan["vmem_bytes"] == 9_060_352 <= ss.VMEM_BUDGET_BYTES
    assert plan["starts"] == 41_943_040
    # channels that are no whole lane tiles have no grid step
    assert ss.scan_plan(64, 24, 4, chunk=8, block=16)["vmem_bytes"] == 0
    # a sequence shorter than a block is one block of whole chunks
    assert ss.scan_plan(40, 8, 2, chunk=16, block=64)["block"] == 48 * 64
