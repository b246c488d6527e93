"""The chip's SHARE of a routed layer's experts (`MoEConfig.held` /
`.first`) on the CPU: the shares add up to the uncut layer of the plain
reference; the cell's routed layer at the published widths; a share on the
Pallas kernels at a padded width (interpreter) and on XLA's grouped product,
whose unwritten rows are masked."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare
from chipbench.references import nemotron_h as reference
from ray_tpu.models import layers as L
from ray_tpu.ops import grouped_matmul
from ray_tpu.models import nemotron_h
from tests.test_nemotron_h import FILED, TINY


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 chips: each share's `apply_moe` (4 experts, the
    router over all 16, its `first`) gives its experts' part plus the
    shared expert. The four parts, the shared expert counted once, are the
    plain reference's output for the WHOLE layer — and no single share is."""
    whole = dataclasses.replace(TINY.moe, held=None)
    params = L.init_moe(jax.random.PRNGKey(4), TINY.d_model, TINY.d_expert,
                        whole)
    params["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (16,))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, TINY.d_model))
    filed = dict(FILED, deployment={"first_expert": 0})
    want = jax.vmap(lambda h: reference.routed(h, params, filed))(x)
    shared = reference.relu2(x @ params["shared_w1"]) @ params["shared_w2"]
    parts = []
    for first in range(0, 16, 4):
        share = dict(params, w1=params["w1"][first:first + 4],
                     w2=params["w2"][first:first + 4])
        cfg = dataclasses.replace(whole, held=4, first=first)
        out, stats = L.apply_moe(share, x, cfg, compute_dtype=jnp.float32)
        assert int(jnp.sum(stats["counts"])) == 2 * 24 * TINY.top_k
        # the reference, given the same share, gives the same part
        np.testing.assert_allclose(out, jax.vmap(lambda h: reference.routed(
            h, share, dict(FILED, deployment={"first_expert": first})))(x),
            rtol=2e-5, atol=2e-6)
        parts.append(out - shared)
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=2e-5,
                               atol=2e-6)
    assert compare.rel_l2(parts[0] + shared, want) > 0.1
    # and the plan counts a share's products at its expectation
    plan = L.moe_plan(48, TINY.d_model, TINY.d_expert,
                      dataclasses.replace(whole, held=4), gated=False)
    assert plan["rows"] == 48 * 3
    assert plan["flops_needed"] == 48 * 3 * 4 * 64 * 32 * 4 // 16
    assert L.moe_plan(48, 64, 32, whole, gated=False)["flops_needed"] == \
        48 * 3 * 4 * 64 * 32


def test_the_cells_routed_layer_at_the_published_widths(runs_on):
    """8,192 tokens choose 6 of 128: 49,152 rows, of which the 8 held
    experts see 3,072 at their expectation; 1,856 is no multiple of 128, so
    the Pallas kernels run at 1,920 on zero-padded copies of the weights."""
    moe = nemotron_h.nemotron_twotower_30b_a3b_9l().moe
    plan = L.moe_plan(8192, 2688, 1856, moe, gated=False)
    assert plan["rows"] == 49_152
    assert plan["flops_needed"] == 3_072 * 2 * 2 * 2688 * 1856
    width = functools.partial(grouped_matmul.kernel_width,
                              dtype=jnp.bfloat16)
    assert width(49_152, 2688, 1856) is None
    runs_on("tpu")
    assert grouped_matmul.tile_plan(49_152, 2688, 1856, jnp.bfloat16) is None
    assert width(49_152, 2688, 1856) == 1920
    assert width(65_536, 2048, 1024) == 1024
    assert width(100, 2688, 1856) is None


def test_a_share_on_the_pallas_kernels_at_a_padded_width(monkeypatch,
                                                         runs_on):
    """What the cell's routed layer runs on a TPU, here in the Pallas
    interpreter: 2 of 8 experts held (`first` 2), relu², an expert width
    (160) that no tile divides, so the kernels run at 256 on zero-padded
    weights. Output and every gradient are the XLA path's; the tokens'
    rows for the absent experts come out zero and pass no gradient."""
    calls = []

    def interpreted(lhs, rhs, sizes, mesh=None):
        calls.append((lhs.shape, rhs.shape, sizes.shape))
        return kernel(lhs, rhs, sizes, interpret=True)

    kernel = grouped_matmul.grouped_matmul
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (2, 64, 128))
    experts = {"w1": 0.1 * jax.random.normal(ks[1], (2, 128, 160)),
               "w2": 0.1 * jax.random.normal(ks[2], (2, 160, 128))}
    gate_idx = jax.random.randint(ks[3], (2, 64, 2), 0, 8)
    gate_vals = jax.random.uniform(ks[4], (2, 64, 2))
    share = L.MoEConfig(n_experts=8, top_k=2, held=2, first=2,
                        activation="relu2")

    def part(platform):
        runs_on(platform)
        monkeypatch.setattr(grouped_matmul, "grouped_matmul",
                            interpreted if platform == "tpu" else kernel)

        def fn(x, gate_vals, experts):
            return L.apply_moe(
                experts, x, share, compute_dtype=jnp.float32,
                routing=(gate_vals, gate_idx, {}))[0]
        out, vjp = jax.vjp(fn, x, gate_vals, experts)
        return out, vjp(jnp.ones_like(out))

    got, got_grads = part("tpu")
    assert grouped_matmul.kernel_width(256, 128, 160, jnp.float32) == 256
    assert calls and {c[1] for c in calls} == {(2, 128, 256), (2, 256, 128)}
    want, want_grads = part("cpu")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    elsewhere = ~np.isin(np.asarray(gate_idx), (2, 3)).any(axis=-1)
    assert elsewhere.any() and not np.asarray(got)[elsewhere].any()


def test_a_share_on_xlas_grouped_product_masks_the_rows_of_no_group(
        monkeypatch, runs_on):
    """On a TPU `lax.ragged_dot` given fewer matrices than groups leaves
    the rows of no group UNWRITTEN, in its result and in the cotangent of
    its rows (PR 34's chip probe: a NaN loss). Here a stand-in that writes
    NaN there, both ways: a share's output and gradients stay those of the
    honest product."""
    ragged_dot = jax.lax.ragged_dot

    def spoiled(rows, mats, sizes):
        return jnp.where((jnp.arange(rows.shape[0])
                          < jnp.sum(sizes))[:, None], rows, jnp.nan)

    @jax.custom_vjp
    def unwritten(lhs, rhs, sizes):
        return spoiled(ragged_dot(lhs, rhs, sizes), rhs, sizes)

    def fwd(lhs, rhs, sizes):
        out, vjp = jax.vjp(lambda a, b: ragged_dot(a, b, sizes), lhs, rhs)
        return spoiled(out, rhs, sizes), (vjp, sizes)

    def bwd(res, d):
        vjp, sizes = res
        d_lhs, d_rhs = vjp(d)
        return spoiled(d_lhs, None, sizes), d_rhs, None

    unwritten.defvjp(fwd, bwd)
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    x = jax.random.normal(ks[0], (2, 50, 128))
    experts = {"w1": 0.1 * jax.random.normal(ks[1], (2, 128, 160)),
               "w2": 0.1 * jax.random.normal(ks[2], (2, 160, 128))}
    gate_idx = jax.random.randint(ks[3], (2, 50, 2), 0, 8)
    gate_vals = jax.random.uniform(ks[4], (2, 50, 2))
    share = L.MoEConfig(n_experts=8, top_k=2, held=2, first=2,
                        activation="relu2")
    # no tile divides 100 rows: XLA's product, on a TPU too
    runs_on("tpu")
    assert grouped_matmul.kernel_width(100, 128, 160, jnp.float32) is None

    def part():
        def fn(x, gate_vals, experts):
            return L.apply_moe(
                experts, x, share, compute_dtype=jnp.float32,
                routing=(gate_vals, gate_idx, {}))[0]
        out, vjp = jax.vjp(fn, x, gate_vals, experts)
        return out, vjp(jnp.ones_like(out))

    want = part()
    monkeypatch.setattr(
        jax.lax, "ragged_dot",
        lambda lhs, rhs, sizes, **_: unwritten(lhs, rhs, sizes))
    got = part()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("routing,compact", [("even", 2), ("onto_the_held", 0)])
def test_moe_compact_counts_the_layers_under_the_bound(routing, compact):
    """128 tokens choose 3 of 16, 4 held: 384 rows, 96 expected here, a
    bound of 256. An even routing keeps both routed layers under it; a
    selection bias that sends every token to held experts puts all 384 rows
    on them, over the bound, and both layers take the whole path — the loss
    is the plain reference's either way."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    assert L.moe_plan(128, cfg.d_model, cfg.d_expert, cfg.moe,
                      gated=False)["bounds"] == (256,)
    params = nemotron_h.init(jax.random.PRNGKey(0), cfg)
    if routing == "onto_the_held":
        params["moe"]["bias"] = params["moe"]["bias"].at[:, :cfg.held].set(9.)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0,
                                cfg.vocab_size)
    loss, metrics = jax.jit(lambda p: nemotron_h.loss_fn(
        p, {"tokens": tokens}, cfg))(params)
    assert float(metrics["moe_compact"]) == compact
    held = int(metrics["moe_held"])
    assert held == 2 * 384 if compact == 0 else 0 < held <= 2 * 256
    assert float(loss) == pytest.approx(
        float(jax.jit(lambda p: reference.loss(p, tokens, FILED))(params)),
        rel=2e-6)
