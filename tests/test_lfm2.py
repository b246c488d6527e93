"""LFM2-MoE (models/lfm2.py) on the CPU, at the tiny preset: against the
plain float32 reference the benchmark holds it to
(chipbench/references/lfm2_moe.py: the conv as three shifted adds, an
explicit causal mask, every held expert on every token, the published
``+ 1e-6``), loss and EVERY gradient leaf, through `make_train_step`, and
the cell's arithmetic under the benchmark's own limits; the per-head norm
ahead of the rotation; the sigmoid-and-bias top-k; the presets' counts. The
chip's share of the experts is test_lfm2_share.py's, the operator alone
test_short_conv.py's."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare
from chipbench.accounting import lfm2_moe as accounting
from chipbench.references import lfm2_moe as reference
from ray_tpu.models import layers as L
from ray_tpu.models import lfm2
from tests import test_model_checks as checks

TINY = dataclasses.replace(lfm2.lfm2_tiny(), attention="reference")
FILED = checks.filed("lfm2-tiny")
SEQ = 64        # 2 × 64 tokens choose 3 of 16: 384 rows, a bound of 256


def _params(cfg, seed=0):
    """Fresh parameters with every norm's scale (the two per-head ones
    too) and the selection bias moved off their initial values, so that a
    norm in the wrong place or a bias that reached a gate shows."""
    def amount(key, _):
        if key.startswith("ln_") or key in ("q_norm", "k_norm"):
            return 0.3
        return 0.05 * (key == "bias")
    return checks.moved_off(lfm2.init(jax.random.PRNGKey(seed), cfg),
                            seed + 1, amount)


def _tokens(cfg, seq=SEQ, **kw):
    return checks.token_ids(cfg.vocab_size, seq=seq, **kw)


def test_presets_count_the_published_parameters():
    cut = lfm2.lfm2_24b_a2b_5l()
    assert cut.n_params == 469_285_248
    assert cut.layer_types == ("conv", "full_attention", "conv", "conv",
                               "conv")
    assert (cut.n_layer, cut.n_dense, cut.moe.stacked, cut.vocab_size) == (
        5, 1, 8, 8192)
    whole = lfm2.lfm2_24b_a2b()
    assert (whole.n_layer, whole.n_dense, whole.moe.stacked) == (40, 2, 64)
    assert whole.layer_types.count("conv") == 30
    assert whole.layer_types[:7] == ("conv", "conv", "full_attention", "conv",
                                     "conv", "conv", "full_attention")
    assert whole.layer_types[1:6] == cut.layer_types
    assert whole.n_params == 23_843_661_440          # the row's "24B"
    params = jax.eval_shape(lambda: lfm2.init(jax.random.PRNGKey(0), TINY))
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) == \
        TINY.n_params == accounting.params(FILED)
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(lfm2.partition_specs(TINY))
    # both mixers under both feed-forwards
    assert {(kind, depth < TINY.n_dense)
            for depth, kind in enumerate(TINY.layer_types)} == {
        ("conv", True), ("full_attention", True), ("conv", False),
        ("full_attention", False)}
    with pytest.raises(ValueError, match="layer_types"):
        lfm2.Lfm2Config(layer_types=("conv", "window"))


def test_the_table_and_the_last_norm_alone_are_drawn_apart():
    """The cell's preset draws the table at 128 and the last norm's scale at
    0.02 / 128 (why: its docstring) and takes no extra pass; every other
    leaf is what any preset draws, and the logits stand where 0.02 and 1 put
    them, the head being the table."""
    cut, whole = lfm2.lfm2_24b_a2b_5l(), lfm2.lfm2_24b_a2b()
    assert (cut.embed_std, cut.final_norm, cut.three_pass) == (
        128.0, 0.02 / 128.0, False)
    assert (whole.embed_std, whole.final_norm, whole.three_pass) == (
        0.02, 1.0, True) == (TINY.embed_std, TINY.final_norm, TINY.three_pass)
    plain = lfm2.init(jax.random.PRNGKey(3), TINY)
    wide = lfm2.init(jax.random.PRNGKey(3), dataclasses.replace(
        TINY, embed_std=128.0, final_norm=0.02 / 128.0))
    assert float(jnp.std(wide["wte"])) == pytest.approx(128.0, rel=0.05)
    assert jnp.allclose(wide["wte"], plain["wte"] * 6400.0, rtol=1e-5)
    assert bool(jnp.all(wide["ln_f"] == jnp.float32(0.02 / 128.0)))
    assert bool(jnp.all(plain["ln_f"] == 1.0))
    rest = [dict(tree, wte=0.0, ln_f=0.0) for tree in (plain, wide)]
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(
        *map(jax.tree_util.tree_leaves, rest)))
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 8, TINY.d_model))
    logits = [L.head_logits(x, tree["ln_f"], tree["wte"], eps=TINY.norm_eps,
                            compute_dtype=jnp.float32)
              for tree in (plain, wide)]
    np.testing.assert_allclose(logits[1], logits[0], rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_gradient_match_the_reference_in_float32(remat):
    """Tolerances: float32 both sides, sums in another order (2e-6 on the
    loss, 3e-5 relative L2 on a leaf); the program's ``max(Σ, 1e-20)`` under
    the gates against the reference's published ``Σ + 1e-6`` is 5e-7 of a
    gate and inside both."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32, remat=remat)
    params, tokens = _params(cfg), _tokens(cfg)
    # the selection bias is behind a stop_gradient: zero both sides
    (_, metrics), grads, want_grads = checks.against_reference(
        lambda p: lfm2.loss_fn(p, {"tokens": tokens}, cfg),
        lambda p: reference.loss(p, tokens, FILED), params,
        loss_rtol=2e-6, grad_tol=3e-5, skip=("['bias']",), has_aux=True)
    assert jax.tree_util.tree_structure(grads) == \
        jax.tree_util.tree_structure(params)
    for layer, want_layer in zip(grads["layers"], want_grads["layers"]):
        if "bias" in layer["ff"]:
            assert not layer["ff"]["bias"].any()
            assert not want_layer["ff"]["bias"].any()
    assert int(metrics["moe_assignments"]) == 2 * SEQ * 3 * 4
    assert 0 < int(metrics["moe_held"]) < int(metrics["moe_assignments"])


def test_through_make_train_step_the_loss_is_the_references_and_falls():
    """The normal path: `make_train_state` / `make_train_step` on a one-
    device mesh, as `JaxTrainer.fit()`'s loop builds them."""
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.train_step import (
        default_optimizer,
        make_train_state,
        make_train_step,
    )

    cfg = dataclasses.replace(TINY, remat=True)
    mesh = create_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    opt = default_optimizer(learning_rate=1e-3, warmup_steps=2,
                            total_steps=100)
    state = make_train_state(lambda rng: lfm2.init(rng, cfg),
                             jax.random.PRNGKey(0), opt, mesh,
                             lfm2.partition_specs(cfg))
    step = make_train_step(lambda p, b: lfm2.loss_fn(p, b, cfg, mesh), opt,
                           mesh)
    tokens = _tokens(cfg, seed=3) % 16
    want = float(reference.loss(state.params, tokens, FILED))
    losses = []
    for _ in range(8):
        state, metrics = step(state, {"tokens": tokens})
        losses.append(float(metrics["loss"]))
    assert losses[0] == pytest.approx(want, rel=compare.LOSS_RTOL)
    assert losses[-1] < losses[0] - 0.5
    assert {"loss", "moe_assignments", "moe_held", "moe_compact"} <= \
        set(metrics)


@functools.cache
def _reference(seed):
    params, tokens = _params(TINY, seed), _tokens(TINY, seed=seed + 1)
    return params, tokens, checks.picked(
        lambda p, t: reference.loss(p, t, FILED), accounting, params, tokens)


@functools.cache
def _compared(cfg, seed):
    """Once a process: two cases read `(TINY under remat, 0)`."""
    params, tokens, (want, want_grads) = _reference(seed)
    loss, grads = checks.picked(
        lambda p, t: lfm2.loss_fn(p, {"tokens": t}, cfg)[0], accounting,
        params, tokens)
    return abs(float(loss) - float(want)) / float(want), checks.rel(
        grads, want_grads)


@pytest.mark.parametrize("seed", [0, 2])
def test_bf16_with_remat_is_within_the_benchmarks_bounds(seed):
    """The cell's arithmetic (bf16 products, three passes ahead of the
    routers, remat) on the compared leaves, under `chipbench/compare.py`'s
    own limits — and far under them: no token's top-3 differs from the
    reference's, so the routed leaves read what bf16 products give."""
    assert TINY.three_pass
    loss, errors = _compared(dataclasses.replace(TINY, remat=True), seed)
    assert loss <= compare.LOSS_RTOL
    assert set(errors) == {"wte", "w_in", "conv_w", "q_norm", "wv", "wg",
                           "w_gate", "w_down"}
    for name, err in errors.items():
        assert err <= compare.GRAD_RTOL / 4, (name, err)


def test_one_pass_ahead_of_a_router_moves_a_tokens_choice():
    """What `three_pass` is for: with every product ahead of the first
    router in ONE bf16 pass, a token of this seed chooses another expert
    than float32 arithmetic would, between a held and an absent one, and the
    first routed layer's leaves read several times the rounding (its router
    over the comparison's limit); the leaves ahead of it hardly move."""
    cfg = dataclasses.replace(TINY, remat=True)
    three = _compared(cfg, 0)[1]
    one = _compared(dataclasses.replace(cfg, three_pass=False), 0)[1]
    for name in ("wg", "w_gate", "w_down"):
        assert one[name] > 5 * three[name], (name, one[name], three[name])
    assert one["wg"] > compare.GRAD_RTOL
    for name in ("wte", "w_in", "conv_w", "q_norm", "wv"):
        assert one[name] < 2 * three[name], (name, one[name], three[name])


def test_the_per_head_norm_comes_ahead_of_the_rotation():
    """q and k each through an RMSNorm over THE HEAD's width (one scale of
    `head_dim` for all heads), then rotated: not a norm over the whole
    projected width (OLMoE's), not behind the rotation's pairing (a scale
    that differs across a pair does not commute with turning it)."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    layer = _params(cfg, seed=2)["layers"][1]
    assert layer["op"]["q_norm"].shape == (cfg.head_dim,)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, cfg.d_model))
    seen = {}

    def attention(params, a, *, qk_fn, **kw):
        q = jnp.einsum("bsd,dhk->bshk", a, params["wq"])
        k = jnp.einsum("bsd,dhk->bshk", a, params["wk"])
        seen["q"], seen["k"] = qk_fn(q, k)
        seen["raw"] = q, k
        return jnp.zeros_like(a)

    real = L.apply_attention
    L.apply_attention = attention
    try:
        lfm2._layer_apply(x, layer, kind="full_attention", dense=True,
                          cfg=cfg, impl="reference")
    finally:
        L.apply_attention = real
    q, k = seen["raw"]
    eps = cfg.norm_eps

    def normed(t, scale):
        return t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + eps) * scale

    for got, raw, scale in ((seen["q"], q, layer["op"]["q_norm"]),
                            (seen["k"], k, layer["op"]["k_norm"])):
        want = L.rope(normed(raw, scale), cfg.rope_theta)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        behind = normed(L.rope(raw, cfg.rope_theta), scale)
        assert compare.rel_l2(behind, want) > 0.05
        flat = raw.reshape(raw.shape[:2] + (-1,))
        whole = (flat / jnp.sqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                 + eps)).reshape(raw.shape) * scale
        assert compare.rel_l2(L.rope(whole, cfg.rope_theta), want) > 0.05
    # and the whole layer is the reference's
    got = lfm2._layer_apply(x, layer, kind="full_attention", dense=True,
                            cfg=cfg, impl="reference")[0]
    want = jax.vmap(lambda row: reference.layer(row, layer, config=FILED))(x)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("norm", [True, False])
def test_a_bias_changes_the_choice_and_not_the_gate(norm):
    """Sigmoid scores; the top-k on score + bias; the gates the chosen
    SCORES (over their sum where `norm_topk_prob`), the bias nowhere in
    them and no gradient into it."""
    cfg = L.MoEConfig(n_experts=16, top_k=4, norm_topk_prob=norm,
                      score="sigmoid")
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 16))
    scores = jax.nn.sigmoid(logits)
    none = jnp.zeros((16,))

    def route(bias):
        # `moe_route` on logits handed in whole: x · I at HIGHEST is x
        return L.moe_route({"wg": jnp.eye(16), "bias": bias}, logits, cfg)
    np.testing.assert_array_equal(
        jnp.einsum("bsd,de->bse", logits, jnp.eye(16),
                   precision=jax.lax.Precision.HIGHEST), logits)
    gates0, chosen0, _ = route(none)
    # a bias that lifts the four weakest experts over every other
    weakest = jnp.argsort(jnp.mean(scores, axis=(0, 1)))[:4]
    bias = none.at[weakest].set(2.0)
    gates, chosen, _ = route(bias)
    assert not np.array_equal(np.sort(chosen0, -1), np.sort(chosen, -1))
    assert set(np.unique(chosen)) == set(np.asarray(weakest))
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    want = picked / jnp.sum(picked, -1, keepdims=True) if norm else picked
    np.testing.assert_allclose(gates, want, rtol=1e-6)
    # the published form of the same gates: Σ + 1e-6 under them
    if norm:
        published = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-6)
        assert compare.rel_l2(gates, published) < 2e-6
    grad = jax.grad(lambda b: jnp.sum(route(b)[0] ** 2))(bias)
    assert not grad.any()
    # the model's own routed layer: its config and its bias leaf
    moe = TINY.moe
    assert (moe.score, moe.norm_topk_prob, moe.scale, moe.gate, moe.top_k,
            moe.n_experts, moe.stacked) == ("sigmoid", True, 1.0, "silu", 3,
                                            16, 4)
    layer = lfm2.init(jax.random.PRNGKey(0), TINY)["layers"][2]
    assert layer["ff"]["bias"].shape == (16,) and not layer["ff"]["bias"].any()


def test_a_mesh_that_splits_the_leaves_is_refused():
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(dp=1, tp=2), devices=jax.devices()[:2])
    params = jax.eval_shape(lambda: lfm2.init(jax.random.PRNGKey(0), TINY))
    with pytest.raises(ValueError, match="tp > 1 is not supported"):
        jax.eval_shape(lambda p: lfm2.loss_fn(
            p, {"tokens": jnp.zeros((2, 17), jnp.int32)}, TINY, mesh), params)
