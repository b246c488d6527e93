"""`ops.ssd`, the chunked state-space scan, on the CPU at small sizes: forward
and every gradient against the token-by-token recurrence written out here,
at chunk sizes that do and do not divide the sequence, in float32 and as a
cell runs it; `ssd_plan` against the products a real call holds."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare
from ray_tpu.ops import ssd as ssd_ops

HEADS, HEAD_DIM, GROUPS, STATE = 4, 8, 2, 16


def recurrence(x, dt, A, B, C, D):
    """H_t = exp(Δ_t A) H_{t−1} + Δ_t x_t B_tᵀ, y_t = H_t C_t + D x_t, one
    token after the other. Shapes as `ssd`'s."""
    b, _, H, P = x.shape
    G, N = B.shape[2:]
    Bh, Ch = (jnp.repeat(t, H // G, axis=2) for t in (B, C))   # [b,T,H,N]

    def step(state, t):
        x_t, dt_t, B_t, C_t = t
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, C_t) \
            + D[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((b, H, P, N)),
                        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, Bh, Ch)))
    return jnp.moveaxis(y, 0, 1)


def _inputs(seq, batch=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return {
        "x": jax.random.normal(ks[0], (batch, seq, HEADS, HEAD_DIM)),
        "dt": jax.nn.softplus(jax.random.normal(ks[1], (batch, seq, HEADS))),
        "A": -jnp.exp(jax.random.uniform(ks[2], (HEADS,), minval=0.0,
                                         maxval=math.log(16.0))),
        "B": jax.random.normal(ks[3], (batch, seq, GROUPS, STATE)),
        "C": jax.random.normal(ks[4], (batch, seq, GROUPS, STATE)),
        "D": 1.0 + 0.1 * jax.random.normal(ks[5], (HEADS,)),
    }, jax.random.normal(ks[6], (batch, seq, HEADS, HEAD_DIM))


@pytest.mark.parametrize("seq, chunk", [(64, 16), (64, 64), (50, 16),
                                        (7, 16), (33, 32)])
def test_chunked_scan_is_the_recurrence_in_float32(seq, chunk):
    """Same function, two programs: float32 rounding alone separates them
    (the decays multiply along a chunk in one and add as logs in the
    other), so 2e-5 on the output and on every gradient."""
    inputs, weight = _inputs(seq)

    def chunked(inputs):
        return jnp.sum(weight * ssd_ops.ssd(**inputs, chunk=chunk,
                                            compute_dtype=jnp.float32))

    def plain(inputs):
        return jnp.sum(weight * recurrence(**inputs))

    got = jax.jit(lambda i: ssd_ops.ssd(
        **i, chunk=chunk, compute_dtype=jnp.float32))(inputs)
    assert got.shape == inputs["x"].shape and got.dtype == jnp.float32
    assert compare.rel_l2(got, jax.jit(lambda i: recurrence(**i))(inputs)) \
        <= 2e-5
    grads = jax.jit(jax.grad(chunked))(inputs)
    want = jax.jit(jax.grad(plain))(inputs)
    for name in want:
        assert compare.rel_l2(grads[name], want[name]) <= 2e-5, name


@pytest.mark.parametrize("three_pass, bound", [(False, 2e-2), (True, 1e-4)])
def test_bf16_products_and_three_passes(three_pass, bound):
    """As a cell runs it: operands of the four products rounded to bf16
    (2^-9 a value) — and, with `three_pass`, the forward value brought back
    to float32's (measured 4e-6 / 6e-3 here), the gradients staying the
    single product's."""
    inputs, weight = _inputs(96, seed=3)
    want = jax.jit(lambda i: recurrence(**i))(inputs)
    got = jax.jit(lambda i: ssd_ops.ssd(**i, chunk=32,
                                        three_pass=three_pass))(inputs)
    assert compare.rel_l2(got, want) <= bound
    grads = jax.jit(jax.grad(lambda i: jnp.sum(weight * ssd_ops.ssd(
        **i, chunk=32, three_pass=three_pass))))(inputs)
    ref = jax.jit(jax.grad(
        lambda i: jnp.sum(weight * recurrence(**i))))(inputs)
    for name in ref:
        assert compare.rel_l2(grads[name], ref[name]) <= 2e-2, name
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads.values())


def test_a_long_decay_underflows_to_zero_and_not_to_nan():
    """Δ·A of −16 a step over a chunk of 64: exp(−1024) is 0 in float32,
    and the masked half of the decay matrix never sees exp(+1024)."""
    inputs, weight = _inputs(128, seed=5)
    inputs["dt"] = jnp.ones_like(inputs["dt"])
    inputs["A"] = jnp.full((HEADS,), -16.0)
    got, grads = jax.jit(jax.value_and_grad(lambda i: jnp.sum(
        weight * ssd_ops.ssd(**i, chunk=64,
                             compute_dtype=jnp.float32))))(inputs)
    assert np.isfinite(float(got))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads.values())
    assert compare.rel_l2(
        ssd_ops.ssd(**inputs, chunk=64, compute_dtype=jnp.float32),
        recurrence(**inputs)) <= 2e-5


def test_ssd_plan_counts_what_a_real_call_does():
    seq, chunk, batch = 64, 16, 2
    inputs, _ = _inputs(seq, batch)
    jaxpr = jax.make_jaxpr(
        lambda i: ssd_ops.ssd(**i, chunk=chunk))(inputs)

    def products(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from products(sub)

    def flops(eqn):
        (contract, _), _ = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        return 2 * math.prod(eqn.outvars[0].aval.shape) * math.prod(
            lhs[d] for d in contract)

    plan = ssd_ops.ssd_plan(batch * seq, HEADS, HEAD_DIM, STATE, GROUPS,
                            chunk)
    found = sorted(flops(e) for e in products(jaxpr.jaxpr))
    assert found == sorted(plan["flops_by_stage"].values())
    assert plan["flops"] == sum(found)
    assert plan["chunks"] == 8
    # the largest array of a call is the decay matrix, never [T, T]
    biggest = max(math.prod(v.aval.shape) for e in jaxpr.jaxpr.eqns
                  for v in e.outvars)
    assert biggest == plan["decay_elements"] == 128 * 16 * HEADS
    # at the benchmark cell's shapes: 8,192 tokens, 64 heads of 64, state
    # 128, 8 groups, chunks of 128
    cell = ssd_ops.ssd_plan(8192, 64, 64, 128, 8, 128)
    assert cell["chunks"] == 64
    assert cell["flops_by_stage"] == {
        "scores": 2_147_483_648, "intra": 8_589_934_592,
        "states": 8_589_934_592, "readout": 8_589_934_592}
    assert cell["flops_recurrence"] == 8192 * 4 * 64 * 64 * 128
    assert cell["decay_elements"] == 67_108_864
    assert cell["bytes"] == (2 * 8192 * 4096 + 2 * 8192 * 1024) * 2 \
        + 8192 * 64 * 4
