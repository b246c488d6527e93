"""`ray_tpu.ops.selective_scan`: the chunk-parallel, block-checkpointed
plain form against Mamba-1's recurrence written out token by token in
float32 (values and the gradients of all seven inputs), at lengths the chunk
and the block divide and do not, with the state carried across chunks and
across blocks; what the form holds in memory, from its jaxpr and from
`scan_plan` by hand. There is no kernel yet, so nothing runs in the
interpreter."""
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


def _weighted(fn, w):
    """(the output, its sum weighted by w) and the latter's gradients in
    all seven inputs, jitted."""
    def run(*args):
        y = fn(*args)
        return jnp.sum(y * w), y
    return jax.jit(jax.value_and_grad(run, argnums=tuple(range(7)),
                                      has_aux=True))


@pytest.mark.parametrize("T, chunk, block", WALKS)
def test_values_and_all_seven_gradients(T, chunk, block):
    args, w = _inputs(T)
    (got, y), got_grads = _weighted(
        lambda *a: ss.selective_scan(*a, chunk=chunk, block=block), w)(*args)
    (want, y_want), want_grads = _weighted(recurrence, w)(*args)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(y, y_want, atol=2e-5)
    for name, g, r in zip(INPUTS, got_grads, want_grads):
        assert float(jnp.max(jnp.abs(g - r))) <= 3e-6 * float(
            jnp.max(jnp.abs(r))), name


def test_the_state_is_carried_across_chunks_and_blocks():
    """Slow decays, so that a token is felt far behind it: what the first
    chunk wrote reaches the last block's tokens, whatever the walk."""
    (s, dt, a, b_in, c_out, d_skip, bias), _ = _inputs(48)
    a, bias = a * 0.01, bias - 3.0
    def run(s_, **walk):
        return jax.jit(lambda s_: ss.selective_scan(
            s_, dt, a, b_in, c_out, d_skip, bias, **walk))(s_)
    one_block = run(s, chunk=48, block=48)
    np.testing.assert_allclose(run(s, chunk=4, block=8), one_block,
                               atol=2e-5)
    without = run(s.at[:, :4].set(0.0), chunk=4, block=8)
    # the skip term D·s reaches a token's own output only
    assert float(jnp.max(jnp.abs((one_block - without)[:, 40:]))) > 1e-2


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
    assert plan == {"naive": 8192 * 5120 * 16 * 4, "step": 16 * 327680,
                    "block": 512 * 327680, "kept": 16 * 327680}
    assert plan["naive"] == 2_684_354_560 and plan["block"] == 167_772_160
    # a sequence shorter than a block is one block of whole chunks
    assert ss.scan_plan(40, 8, 2, chunk=16, block=64)["block"] == 48 * 64
