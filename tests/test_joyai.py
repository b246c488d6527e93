"""`ray_tpu.models.joyai` (JoyAI-LLM-Flash: latent attention, a gated shared
expert beside a sigmoid-and-bias router over a share of the experts, a
multi-token-prediction module in the loss) at test sizes on the CPU: the
system's `loss_fn` against the benchmark's plain reference, the second
loss's weight at zero against the module-less model, the share test of the
model-configs guide, and the kernel path through the Pallas interpreter."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.accounting import joyai_flash as accounting
from chipbench.references import joyai_flash as reference
from ray_tpu.models import joyai
from ray_tpu.models import layers as L
from ray_tpu.parallel.mesh import MeshConfig, create_mesh
from tests import test_model_checks as checks

FILED = checks.filed("joyai-tiny")
LEAVES = ("head", "wq_b", "wkv_b", "wkv_a", "wo", "wg", "w_gate", "w_down",
          "shared_w_gate", "eh_proj")


@functools.cache
def _setup(seed=3, **fields):
    cfg = dataclasses.replace(joyai.joyai_tiny(), dtype=jnp.float32, **fields)
    params = joyai.init(jax.random.PRNGKey(seed), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, 41), 0, 16)
    return cfg, params, tokens


@functools.cache
def _reference(weight):
    """The reference's half of the comparison: remat is not its business."""
    _, params, tokens = _setup(mtp_weight=weight)
    filed = dict(FILED, mtp_loss_weight=weight)
    return checks.reference_side(lambda p, t: reference.loss(p, t, filed),
                                 accounting, params, tokens)


@pytest.mark.parametrize("weight", [0.3, 0.0])
@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_picked_gradients_against_the_reference(weight, remat):
    """`L_main + λ L_mtp` and the gradients the benchmark compares, in
    float32 against `chipbench/references/joyai_flash.py` (the module run
    on the S − 1 rows that have a second target, where the program runs S
    and masks the last)."""
    cfg, params, tokens = _setup(mtp_weight=weight, remat=remat)
    assert accounting.ran_sizes(cfg) == accounting.filed_sizes(
        dict(FILED, mtp_loss_weight=weight))
    out = checks.compared(
        lambda p, t: joyai.loss_fn(p, {"tokens": t}, cfg)[0], accounting,
        params, tokens, _reference(weight))
    assert set(out["errors"]) == {"loss"} | {"grad_" + k for k in LEAVES}
    if weight == 0.0:
        # nothing of the second loss reaches its own matrix
        assert out["reference_grad_norms"]["eh_proj"] == 0.0
        assert out["errors"].pop("grad_eh_proj") != 0.0    # 0 / 0
    assert max(out["errors"].values()) < 5e-6, out["errors"]
    assert out["reference_loss"] > 5.0


def test_the_second_loss_at_weight_zero_is_the_module_less_model():
    """λ = 0: the loss is L_main and the trunk's, the embedding's and the
    head's gradients are those of the model without the module; at λ = 0.3
    the module's gradient reaches all three."""
    cfg, params, tokens = _setup(mtp_weight=0.0)
    bare_cfg = dataclasses.replace(cfg, n_mtp=0)
    bare = {k: v for k, v in params.items() if k != "mtp"}
    assert jax.tree_util.tree_structure(bare) == jax.tree_util.tree_structure(
        joyai.init(jax.random.PRNGKey(0), bare_cfg))

    def grads(cfg, params):
        return checks.loss_and_grads(
            lambda p: joyai.loss_fn(p, {"tokens": tokens}, cfg), params,
            has_aux=True)
    (loss0, metrics0), g0 = grads(cfg, params)
    (loss_bare, _), g_bare = grads(bare_cfg, bare)
    assert float(loss0) == float(loss_bare) == float(metrics0["loss_main"])
    mtp0 = g0.pop("mtp")
    assert not any(np.asarray(x).any()
                   for x in jax.tree_util.tree_leaves(mtp0))
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g_bare)):
        np.testing.assert_allclose(a, b, atol=1e-7)
    (loss, metrics), g = grads(dataclasses.replace(cfg, mtp_weight=0.3),
                               params)
    np.testing.assert_allclose(
        loss, metrics["loss_main"] + 0.3 * metrics["loss_mtp"], rtol=1e-6)
    for name in ("wte", "head", "ln_f"):
        moved = np.abs(np.asarray(g[name] - g0[name])).max()
        assert (moved > 1e-6) == (name != "ln_f"), name   # its own last norm
    assert np.abs(np.asarray(g["layers"][0]["attn"]["wq_a"]
                             - g0["layers"][0]["attn"]["wq_a"])).max() > 1e-7
    assert np.asarray(g["mtp"]["eh_proj"]).any()
    # three routed layers are counted: two of the trunk, the module's
    assert int(metrics["moe_assignments"]) == 2 * 40 * 3 * 3


def test_the_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's share test: the routed layer with ALL 16
    experts equals the sum of the four shares' results (experts 0–3, 4–7,
    8–11, 12–15, each with the same router over all 16), the shared expert
    — whole in every share — counted once."""
    whole_cfg = L.MoEConfig(n_experts=16, top_k=3, score="sigmoid",
                            scale=2.5, gate="silu", d_shared=32)
    whole = L.init_moe(jax.random.PRNGKey(5), 64, 32, whole_cfg, gated=True)
    assert set(whole) == {"wg", "bias", "w_gate", "w_up", "w_down", "shared"}
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, 64))
    uncut, stats = L.apply_moe(whole, x, whole_cfg,
                               compute_dtype=jnp.float32)
    shared = L.apply_gated_mlp(whole["shared"], x, compute_dtype=jnp.float32)
    total, held = 0.0, 0
    for rank in range(4):
        cfg = dataclasses.replace(whole_cfg, held=4, first=4 * rank)
        part = dict(whole, **{k: whole[k][4 * rank:4 * rank + 4]
                              for k in ("w_gate", "w_up", "w_down")})
        out, part_stats = L.apply_moe(part, x, cfg, compute_dtype=jnp.float32)
        np.testing.assert_array_equal(part_stats["counts"], stats["counts"])
        total = total + (out - shared)
        held += int(jnp.sum(stats["counts"][4 * rank:4 * rank + 4]))
    assert held == 2 * 40 * 3
    np.testing.assert_allclose(total + shared, uncut, atol=2e-6)
    # and the shared expert is no small part of it
    assert float(jnp.abs(shared).mean()) > 0.1 * float(jnp.abs(uncut).mean())


def test_the_kernel_path_equals_the_reference_path(interpreted):
    """`impl` "flash" through the Pallas interpreter at q/k 32 and v 16:
    loss and every gradient as the plain-softmax path gives them, with and
    without remat; under remat a step holds THREE kernel calls a latent
    layer (the trunk's three and the module's one), not four."""
    cfg, params, tokens = _setup()

    def run(attention, remat):
        c = dataclasses.replace(cfg, attention=attention, remat=remat)
        return checks.loss_and_grads(
            lambda p: joyai.loss_fn(p, {"tokens": tokens}, c)[0], params)
    want = run("reference", False)
    for remat in (False, True):
        got = run("flash", remat)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                        jax.tree_util.tree_leaves(want[1])):
            np.testing.assert_allclose(a, b, atol=5e-6)
    c = dataclasses.replace(cfg, attention="flash", remat=True)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: joyai.loss_fn(p, {"tokens": tokens}, c)[0]))(params))
    assert text.count("name=flash_latent_fwd") == 4
    assert text.count("name=flash_latent_dq") == 4
    assert text.count("name=flash_latent_dkv") == 4


def test_the_presets_and_what_the_model_refuses():
    cut, whole = joyai.joyai_llm_flash_5l(), joyai.joyai_llm_flash()
    assert cut.n_params == 680_834_304
    assert whole.n_params == 50_190_491_648
    shapes = jax.eval_shape(lambda: joyai.init(jax.random.PRNGKey(0), cut))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(shapes)) == cut.n_params
    layer = shapes["layers"][1]
    assert layer["attn"]["wq_b"].shape == (1536, 32, 192)
    assert layer["attn"]["wkv_a"].shape == (2048, 576)
    assert layer["attn"]["wkv_b"].shape == (512, 32, 256)
    assert layer["ff"]["w_gate"].shape == (16, 2048, 768)
    assert layer["ff"]["wg"].shape == (2048, 256)
    assert layer["ff"]["shared"]["w_down"].shape == (768, 2048)
    assert shapes["layers"][0]["ff"]["w_gate"].shape == (2048, 7168)
    assert shapes["mtp"]["eh_proj"].shape == (4096, 2048)
    assert shapes["head"].shape == shapes["wte"].shape == (16256, 2048)
    specs = joyai.partition_specs(cut)
    assert jax.tree_util.tree_structure(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)) \
        == jax.tree_util.tree_structure(shapes)
    with pytest.raises(ValueError, match="one prediction depth"):
        dataclasses.replace(cut, n_mtp=2)
    cfg, params, tokens = _setup()
    # the three draws the cell's preset sets apart from 0.02
    assert (cut.embed_std, cut.router_std, cut.eh_std) == (2048.0, 0.15, 4.0)
    drawn = joyai.init(jax.random.PRNGKey(0), dataclasses.replace(
        cfg, embed_std=8.0, router_std=0.15, eh_std=4.0))
    for leaf, std in ((drawn["wte"], 8.0), (drawn["mtp"]["eh_proj"], 4.0),
                      (drawn["layers"][1]["ff"]["wg"], 0.15),
                      (drawn["mtp"]["layer"]["ff"]["wg"], 0.15),
                      (drawn["head"], 0.02)):
        np.testing.assert_allclose(np.std(np.asarray(leaf)), std, rtol=0.1)
    mesh = create_mesh(MeshConfig(dp=1, tp=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="tp > 1 is not supported"):
        joyai.loss_fn(params, {"tokens": tokens}, cfg, mesh)


def test_a_dp_mesh_gives_the_unsharded_loss(interpreted):
    cfg, params, tokens = _setup()
    want = joyai.loss_fn(params, {"tokens": tokens}, cfg)[0]
    mesh = create_mesh(MeshConfig(dp=2), devices=jax.devices()[:2])
    for attention in ("reference", "flash"):
        c = dataclasses.replace(cfg, attention=attention)
        got = jax.jit(lambda p, t, c=c: joyai.loss_fn(
            p, {"tokens": t}, c, mesh)[0])(params, tokens)
        np.testing.assert_allclose(got, want, rtol=2e-6)
