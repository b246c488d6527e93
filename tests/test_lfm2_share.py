"""The chip's SHARE of LFM2's experts (`MoEConfig.held` / `.first` on the
SiLU-gated three-matrix form behind a sigmoid-and-bias router) on the CPU:
the eight shares' parts of a routed layer, added, are the uncut reference's
layer — the cell's own deployment, 64 experts over 8 chips, 4 a token, at
test widths; and the cell's routed layer at the published widths."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare
from chipbench.references import lfm2_moe as reference
from ray_tpu.models import layers as L
from ray_tpu.ops import grouped_matmul
from ray_tpu.models import lfm2
from tests.test_lfm2 import FILED, TINY

# the published routing at test widths: 64 experts, 4 a token
WIDE = dataclasses.replace(TINY, dtype=jnp.float32, n_experts=64, top_k=4,
                           held=None)
WIDE_FILED = dict(FILED, num_experts_per_tok=4)
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


@pytest.mark.parametrize("depth", [2, 4], ids=["conv", "full_attention"])
def test_the_eight_shares_parts_add_up_to_the_uncut_layer(depth):
    """Each chip runs the WHOLE layer on its share (8 experts, the router
    over all 64 with the whole bias, its `first`): the stream behind the
    operator, which every chip computes alike, plus its experts' part. The
    eight parts, the stream behind the operator counted once, are the plain
    reference's output for the layer with all 64 experts — and no single
    share is."""
    cfg = WIDE
    kind = cfg.layer_types[depth]
    layer = lfm2.init(jax.random.PRNGKey(4), cfg)["layers"][depth]
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(5), (64,))
    layer = dict(layer, ln_op=layer["ln_op"] * 1.3, ln_ff=layer["ln_ff"] * 0.8,
                 ff=dict(layer["ff"], bias=bias))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 48, cfg.d_model))

    def filed(first):
        return dict(WIDE_FILED, deployment={"first_expert": first})

    want = jax.vmap(lambda row: reference.layer(row, layer,
                                                config=filed(0)))(x)

    def run(ff, held, first):
        out, (counts, _) = lfm2._layer_apply(
            x, dict(layer, ff=ff), kind=kind, dense=False, impl="reference",
            cfg=dataclasses.replace(cfg, held=held, first=first))
        assert int(jnp.sum(counts)) == 2 * 48 * cfg.top_k
        return out

    ff = layer["ff"]
    # no expert's down-projection: what is left is the stream behind the
    # operator, h
    behind_op = run(dict(ff, w_down=0 * ff["w_down"]), None, 0)
    np.testing.assert_allclose(run(ff, None, 0), want, rtol=2e-5, atol=2e-6)
    parts = []
    for first in range(0, 64, 8):
        share = dict(ff, **{k: ff[k][first:first + 8] for k in EXPERT_LEAVES})
        out = run(share, 8, first)
        # the reference, given the same share, gives the same layer
        np.testing.assert_allclose(out, jax.vmap(lambda row: reference.layer(
            row, dict(layer, ff=share), config=filed(first)))(x),
            rtol=2e-5, atol=2e-6)
        parts.append(out - behind_op)
    np.testing.assert_allclose(sum(parts) + behind_op, want, rtol=2e-5,
                               atol=2e-6)
    routed = want - behind_op
    for part in parts:
        assert compare.rel_l2(part, routed) > 0.5


def test_the_cells_routed_layer_at_the_published_widths(runs_on):
    """16,384 tokens choose 4 of 64: 65,536 rows a layer, of which the 8
    held experts see 8,192 at their expectation (1,024 each, an eighth of
    their deployment's 8,192), worked on within a bound of 16,384; 1,536 is
    a multiple of 128, so the Pallas kernels run at the published width
    with no padding."""
    moe = lfm2.lfm2_24b_a2b_5l().moe
    assert (moe.n_experts, moe.stacked, moe.first, moe.top_k, moe.gate,
            moe.score, moe.norm_topk_prob, moe.scale, moe.d_shared) == (
        64, 8, 0, 4, "silu", "sigmoid", True, 1.0, 0)
    plan = L.moe_plan(16384, 2048, 1536, moe, gated=True)
    assert plan["rows"] == 65_536
    assert plan["bounds"] == (16_384,)
    assert plan["flops_needed"] == 8_192 * 3 * 2 * 2048 * 1536
    assert 8_192 // 8 == 1024 == 8 * 16384 * 4 // 64 // 8
    assert grouped_matmul.kernel_width(16_384, 2048, 1536,
                                       jnp.bfloat16) is None
    runs_on("tpu")
    assert grouped_matmul.kernel_width(16_384, 2048, 1536,
                                       jnp.bfloat16) == 1536


@pytest.mark.parametrize("routing,compact", [("even", 4), ("onto_the_held", 0)])
def test_moe_compact_counts_the_routed_layers_under_the_bound(routing,
                                                              compact):
    """128 tokens choose 3 of 16, 4 held: 384 rows, 96 expected here, a
    bound of 256. An even routing keeps all four routed layers under it;
    selection biases that lift the held experts over every other put all
    384 rows on them, over the bound, and every layer takes the whole path
    — the loss is the plain reference's either way, and the dense layers
    count in neither number."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    assert L.moe_plan(128, cfg.d_model, cfg.d_expert, cfg.moe,
                      gated=True)["bounds"] == (256,)
    params = lfm2.init(jax.random.PRNGKey(0), cfg)
    if routing == "onto_the_held":
        for layer in params["layers"][cfg.n_dense:]:
            layer["ff"]["bias"] = layer["ff"]["bias"].at[:cfg.held].set(2.0)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0,
                                cfg.vocab_size)
    loss, metrics = jax.jit(lambda p: lfm2.loss_fn(
        p, {"tokens": tokens}, cfg))(params)
    assert int(metrics["moe_assignments"]) == 128 * 3 * 4
    assert float(metrics["moe_compact"]) == compact
    held = int(metrics["moe_held"])
    assert held == 4 * 384 if compact == 0 else 0 < held <= 4 * 256
    assert float(loss) == pytest.approx(
        float(jax.jit(lambda p: reference.loss(p, tokens, FILED))(params)),
        rel=2e-6)
