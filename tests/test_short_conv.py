"""The gated short convolution (`layers.apply_short_conv`) and the function
it shares with the Mamba-2 mixer (`layers.causal_taps`), on the CPU: against
a direct depthwise convolution, a batch of two whose second sequence does
not see the first, gradients against finite differences, the mixer's conv
through the shared function, and the dense gated feed-forward that came with
them (`layers.apply_gated_mlp`)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare
from ray_tpu.models import layers as L


def _direct_conv(x, w):
    """x [B, T, C], taps w [K, C]: XLA's own depthwise convolution, K − 1
    zeros ahead of every sequence, one group a channel."""
    taps, width = w.shape
    return jax.lax.conv_general_dilated(
        x, w[:, None, :], window_strides=(1,), padding=[(taps - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=width,
        precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_causal_taps_is_a_depthwise_causal_convolution(taps):
    kx, kw = jax.random.split(jax.random.PRNGKey(taps))
    x = jax.random.normal(kx, (2, 37, 24))
    w = jax.random.normal(kw, (taps, 24))
    np.testing.assert_allclose(L.causal_taps(x, w), _direct_conv(x, w),
                               rtol=1e-5, atol=1e-6)
    # tap K − 1 is the position itself, tap 0 the one K − 1 before it
    np.testing.assert_allclose(L.causal_taps(x, w)[:, 0], x[:, 0] * w[-1],
                               rtol=1e-6)


def _operator(seed=0, d=32, taps=3):
    params = L.init_short_conv(jax.random.PRNGKey(seed), d, taps)
    # 0.02-sized projections make an output of 1e-4: weigh them up so that
    # a wrong split or a wrong tap order is far outside the tolerance
    params = dict(params, w_in=params["w_in"] * 20, w_out=params["w_out"] * 20)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 29, d))
    return params, x


def test_the_operators_leaves():
    params = L.init_short_conv(jax.random.PRNGKey(0), 2048)
    assert {k: v.shape for k, v in params.items()} == {
        "w_in": (2048, 6144), "conv_w": (3, 2048), "w_out": (2048, 2048)}
    assert set(L.SHORT_CONV_LOGICAL) == set(params)
    assert sum(v.size for v in params.values()) == 16_783_360
    assert float(jnp.max(jnp.abs(params["conv_w"]))) <= 3 ** -0.5
    assert float(jnp.std(params["w_in"])) == pytest.approx(0.02, rel=0.02)


def test_apply_short_conv_against_a_direct_convolution():
    """[b | c | u] in THAT order; the conv over b ∘ u; c gates its result."""
    params, x = _operator()
    got = L.apply_short_conv(params, x, compute_dtype=jnp.float32)
    b, c, u = jnp.split(x @ params["w_in"], 3, axis=-1)
    want = (c * _direct_conv(b * u, params["conv_w"])) @ params["w_out"]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # another order of the three streams is another operator
    swapped = (b * _direct_conv(c * u, params["conv_w"])) @ params["w_out"]
    assert compare.rel_l2(swapped, want) > 0.5


def test_sequence_two_does_not_see_sequence_one():
    """The first outputs of the batch's second sequence are those of that
    sequence alone, whatever the first sequence holds; and every output is
    causal: a later position moves no earlier one."""
    params, x = _operator(seed=3)
    run = functools.partial(L.apply_short_conv, params,
                            compute_dtype=jnp.float32)
    both = run(x)
    # (another batch size is another blocking of the products: to rounding)
    np.testing.assert_allclose(both[1], run(x[1:])[0], rtol=1e-5, atol=1e-5)
    other_first = run(x.at[0].set(x[0] * -3.0 + 1.0))
    np.testing.assert_array_equal(both[1], other_first[1])
    assert not np.allclose(both[0], other_first[0])
    later = run(x.at[:, 10:].set(0.0))
    np.testing.assert_array_equal(both[:, :10], later[:, :10])
    assert not np.allclose(both[:, 10:13], later[:, 10:13])


@pytest.mark.parametrize("leaf", ["x", "w_in", "conv_w", "w_out"])
def test_gradients_against_finite_differences(leaf):
    """Central differences along a random direction, in float32 (gates and
    taps are float32 whatever comes in): the operator is a cubic of its
    input and quadratic or linear in each leaf, so a step of 1e-2 leaves a
    truncation error of 1e-4 and a rounding error below it."""
    params, x = _operator(seed=5, d=8)
    x = x[:, :11]
    probe = jax.random.normal(jax.random.PRNGKey(9), (2, 11, 8))

    def scalar(value):
        p, inp = (params, value) if leaf == "x" else (
            dict(params, **{leaf: value}), x)
        return jnp.sum(probe * L.apply_short_conv(
            p, inp, compute_dtype=jnp.float32))

    scalar = jax.jit(scalar)
    at = x if leaf == "x" else params[leaf]
    grad = jax.jit(jax.grad(scalar))(at)
    direction = jax.random.normal(jax.random.PRNGKey(11), at.shape)
    eps = 1e-2
    numeric = (scalar(at + eps * direction)
               - scalar(at - eps * direction)) / (2 * eps)
    assert float(jnp.sum(grad * direction)) == pytest.approx(
        float(numeric), rel=2e-3)


def test_the_gates_and_taps_are_float32_from_the_accumulator():
    """bf16 products, float32 between them: the in-projection's result is
    not rounded to bf16 before the gates (a top-k behind this operator is
    discontinuous in it), and `three_pass` brings the products themselves
    to float32 accuracy."""
    params, x = _operator(seed=7, d=64)
    exact = L.apply_short_conv(params, x, compute_dtype=jnp.float32)
    one = L.apply_short_conv(params, x, compute_dtype=jnp.bfloat16)
    three = L.apply_short_conv(params, x, compute_dtype=jnp.bfloat16,
                               three_pass=True)
    assert one.dtype == three.dtype == jnp.float32
    assert 1e-4 < compare.rel_l2(one, exact) < 2e-2
    assert compare.rel_l2(three, exact) < compare.rel_l2(one, exact) / 20
    jaxpr = str(jax.make_jaxpr(lambda x: L.apply_short_conv(
        params, x, compute_dtype=jnp.bfloat16))(x))
    assert "preferred_element_type=float32" in jaxpr


def test_the_mixers_conv_is_the_direct_convolution(monkeypatch):
    """`apply_mamba`'s depthwise conv (4 taps, a bias, SiLU) has a path of
    its own since PR 46 (`ops.mamba_stages.conv_silu`: kernels on a TPU, K
    shifted products as `causal_taps` elsewhere): the mixer's output with
    its plain stage replaced by the direct convolution is the same."""
    from ray_tpu.ops import mamba_stages

    cfg = L.MambaConfig(n_heads=4, head_dim=8, n_groups=2, d_state=16,
                        chunk=16)
    params = L.init_mamba(jax.random.PRNGKey(0), 32, cfg)
    params = dict(params, conv_b=params["conv_b"] + 0.1)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32))

    def mixer():    # a fresh function: what is patched below is traced anew
        return jax.jit(lambda p, u: L.apply_mamba(
            p, u, cfg, compute_dtype=jnp.float32))(params, u)
    got = mixer()
    calls = []

    def direct(x, w, b):
        calls.append((x.shape, w.shape, b.shape))
        return jax.nn.silu(_direct_conv(x, w.astype(jnp.float32)) + b)

    monkeypatch.setattr(mamba_stages, "_conv_plain", direct)
    want = mixer()
    assert calls == [((2, 32, cfg.conv_dim), (4, cfg.conv_dim),
                      (cfg.conv_dim,))]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("three_pass", [False, True])
def test_the_dense_gated_feed_forward(three_pass):
    """``(silu(x·W_gate) ∘ (x·W_up))·W_down``, no bias: the three-matrix form
    outside a routed layer."""
    params = L.init_gated_mlp(jax.random.PRNGKey(0), 64, 96)
    assert {k: v.shape for k, v in params.items()} == {
        "w_gate": (64, 96), "w_up": (64, 96), "w_down": (96, 64)}
    assert set(L.GATED_MLP_LOGICAL) == set(params)
    params = jax.tree_util.tree_map(lambda a: a * 10, params)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 17, 64))
    want = (jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])) \
        @ params["w_down"]
    exact = L.apply_gated_mlp(params, x, compute_dtype=jnp.float32,
                              three_pass=three_pass)
    np.testing.assert_allclose(exact, want, rtol=2e-5, atol=2e-6)
    rounded = L.apply_gated_mlp(params, x, compute_dtype=jnp.bfloat16,
                                three_pass=three_pass)
    assert rounded.dtype == jnp.float32
    assert compare.rel_l2(rounded, want) < 2e-2
    grads = jax.jit(jax.grad(lambda p: jnp.sum(L.apply_gated_mlp(
        p, x, compute_dtype=jnp.float32, three_pass=three_pass) ** 2)))(params)
    want_grads = jax.jit(jax.grad(lambda p: jnp.sum((
        (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"]))
        @ p["w_down"]) ** 2)))(params)
    for k in params:
        np.testing.assert_allclose(grads[k], want_grads[k], rtol=1e-4,
                                   atol=1e-5)
