"""The chip's SHARE of SmallThinker's experts (`MoEConfig.held` / `.first`
on the three-matrix form with a ReLU gate, routed from the layer's input)
on the CPU: the four shares' parts of a layer, added, are the uncut
reference's layer; the cell's routed layer at the published widths; a share
of ReLU-gated experts on the Pallas kernels (interpreter)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare
from chipbench.references import smallthinker as reference
from ray_tpu.models import layers as L
from ray_tpu.ops import grouped_matmul
from ray_tpu.models import smallthinker
from tests.test_smallthinker import FILED, TINY


@pytest.mark.parametrize("kind", [(0, 0), (1, 1)], ids=["global", "window"])
def test_the_four_shares_parts_add_up_to_the_uncut_layer(kind):
    """16 experts over 4 chips. Each chip runs the WHOLE layer on its share
    (4 experts, the router over all 16 on the layer's input, its `first`):
    the stream behind attention, which every chip computes alike, plus its
    experts' part. The four parts, the stream behind attention counted once,
    are the plain reference's output for the layer with all 16 experts —
    and no single share is."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32, held=None)
    block = jax.tree_util.tree_map(
        lambda a: a[0], smallthinker.init(jax.random.PRNGKey(4),
                                          cfg)["blocks"])
    block = dict(block, ln1=block["ln1"] * 1.3, ln2=block["ln2"] * 0.8)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 48, cfg.d_model))
    filed = dict(FILED, deployment={"first_expert": 0})
    windowed, rotated = kind
    want = jax.vmap(lambda row: reference.layer(
        row, block, rotated=bool(rotated), windowed=bool(windowed),
        config=filed))(x)

    def layer(moe, held, first):
        out, (counts, _) = smallthinker._block_apply(
            x, dict(block, moe=moe), kind=kind, impl="reference",
            cfg=dataclasses.replace(cfg, held=held, first=first))
        assert int(jnp.sum(counts)) == 2 * 48 * cfg.top_k
        return out

    moe = block["moe"]
    # no expert's down-projection: what is left is the stream behind attention
    behind_attention = layer(dict(moe, w_down=0 * moe["w_down"]), None, 0)
    np.testing.assert_allclose(layer(moe, None, 0), want, rtol=2e-5,
                               atol=2e-6)
    parts = []
    for first in range(0, 16, 4):
        share = dict(moe, **{k: moe[k][first:first + 4]
                             for k in ("w_gate", "w_up", "w_down")})
        out = layer(share, 4, first)
        # the reference, given the same share, gives the same layer
        np.testing.assert_allclose(out, jax.vmap(lambda row: reference.layer(
            row, dict(block, moe=share), rotated=bool(rotated),
            windowed=bool(windowed),
            config=dict(FILED, deployment={"first_expert": first})))(x),
            rtol=2e-5, atol=2e-6)
        parts.append(out - behind_attention)
    np.testing.assert_allclose(sum(parts) + behind_attention, want,
                               rtol=2e-5, atol=2e-6)
    routed = want - behind_attention
    for part in parts:
        assert compare.rel_l2(part, routed) > 0.3


def test_the_cells_routed_layer_at_the_published_widths(runs_on):
    """16,384 tokens choose 6 of 64: 98,304 rows a layer, of which the 16
    held experts see 24,576 at their expectation (1,536 each, a quarter of
    their deployment's 6,144); 768 is a multiple of 128, so the Pallas
    kernels run at the published width with no padding."""
    moe = smallthinker.smallthinker_21b_a3b_4l().moe
    assert (moe.n_experts, moe.stacked, moe.top_k, moe.gate, moe.score,
            moe.norm_topk_prob) == (64, 16, 6, "relu", "softmax", True)
    plan = L.moe_plan(16384, 2560, 768, moe, gated=True)
    assert plan["rows"] == 98_304
    assert plan["flops_needed"] == 24_576 * 3 * 2 * 2560 * 768
    assert 24_576 // 16 == 1536 == 4 * 16384 * 6 // 64 // 4
    assert grouped_matmul.kernel_width(98_304, 2560, 768, jnp.bfloat16) is None
    runs_on("tpu")
    assert grouped_matmul.kernel_width(98_304, 2560, 768, jnp.bfloat16) == 768


@pytest.mark.parametrize("gate", ["relu", "silu"])
def test_a_share_of_gated_experts_on_the_pallas_kernels(gate, monkeypatch,
                                                        runs_on):
    """What the cell's routed layer runs on a TPU, here in the Pallas
    interpreter: 2 of 8 gated experts held (`first` 2). Output and every
    gradient are the XLA path's; the tokens' rows for the absent experts
    come out zero and pass no gradient; and the ReLU gate is not the SiLU
    one."""
    calls = []

    def interpreted(lhs, rhs, sizes, mesh=None):
        calls.append((lhs.shape, rhs.shape, sizes.shape))
        return kernel(lhs, rhs, sizes, interpret=True)

    kernel = grouped_matmul.grouped_matmul
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(ks[0], (2, 64, 128))
    experts = {"w_gate": 0.1 * jax.random.normal(ks[1], (2, 128, 128)),
               "w_up": 0.1 * jax.random.normal(ks[5], (2, 128, 128)),
               "w_down": 0.1 * jax.random.normal(ks[2], (2, 128, 128))}
    gate_idx = jax.random.randint(ks[3], (2, 64, 2), 0, 8)
    gate_vals = jax.random.uniform(ks[4], (2, 64, 2))

    def part(platform, gate=gate):
        runs_on(platform)
        monkeypatch.setattr(grouped_matmul, "grouped_matmul",
                            interpreted if platform == "tpu" else kernel)

        def fn(x, gate_vals, experts):
            return L.apply_moe(
                experts, x,
                L.MoEConfig(n_experts=8, top_k=2, held=2, first=2, gate=gate),
                compute_dtype=jnp.float32,
                routing=(gate_vals, gate_idx, {}))[0]
        out, vjp = jax.vjp(fn, x, gate_vals, experts)
        return out, vjp(jnp.ones_like(out))

    got, got_grads = part("tpu")
    assert len(calls) >= 3 and {c[1] for c in calls} == {(2, 128, 128)}
    want, want_grads = part("cpu")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    elsewhere = ~np.isin(np.asarray(gate_idx), (2, 3)).any(axis=-1)
    assert elsewhere.any() and not np.asarray(got)[elsewhere].any()
    other = part("cpu", {"relu": "silu", "silu": "relu"}[gate])[0]
    assert compare.rel_l2(other, want) > 0.1
    # the gate written out, a dense loop over the two held experts
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[gate]
    dense = sum(
        (act(x @ experts["w_gate"][e]) * (x @ experts["w_up"][e]))
        @ experts["w_down"][e]
        * jnp.sum(jnp.where(gate_idx == 2 + e, gate_vals, 0.0),
                  -1)[..., None] for e in range(2))
    np.testing.assert_allclose(want, dense, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("routing,compact", [("even", 4), ("onto_the_held", 0)])
def test_moe_compact_counts_the_layers_under_the_bound(routing, compact):
    """128 tokens choose 3 of 16, 4 held: 384 rows, 96 expected here, a
    bound of 256. An even routing keeps all four layers under it; a stream
    and routers that send every token to held experts put all 384 rows on
    them, over the bound, and every layer takes the whole path — the loss is
    the plain reference's either way."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    assert L.moe_plan(128, cfg.d_model, cfg.d_expert, cfg.moe,
                      gated=True)["bounds"] == (256,)
    params = smallthinker.init(jax.random.PRNGKey(0), cfg)
    if routing == "onto_the_held":
        # every token's first coordinate large, the held experts' logits it
        params["wte"] = params["wte"].at[:, 0].set(1e3)
        wg = params["blocks"]["moe"]["wg"]
        params["blocks"]["moe"]["wg"] = wg.at[:, 0, :cfg.held].set(1.0)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0,
                                cfg.vocab_size)
    loss, metrics = jax.jit(lambda p: smallthinker.loss_fn(
        p, {"tokens": tokens}, cfg))(params)
    assert float(metrics["moe_compact"]) == compact
    held = int(metrics["moe_held"])
    assert held == 4 * 384 if compact == 0 else 0 < held <= 4 * 256
    assert float(loss) == pytest.approx(
        float(jax.jit(lambda p: reference.loss(p, tokens, FILED))(params)),
        rel=2e-6)
