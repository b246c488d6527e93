"""`ray_tpu.models.kimi_linear` (Kimi Linear: Kimi Delta Attention layers
among latent-attention layers without a q rank or positions, a leading dense
feed-forward, a sigmoid router over a share of the experts) at test sizes on
the CPU: the system's `loss_fn` against the benchmark's plain reference
(whose rule is the token-by-token recurrence) in float32 and with bf16
products, the share test of the model-configs guide, the new layers each
against the reference's lines, JoyAI's latent call held to the program it
was, the scopes the metrics read, the presets and what the model refuses."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from chipbench import compare
from chipbench.accounting import kimi_linear as accounting
from chipbench.references import kimi_linear as reference
from ray_tpu.models import joyai, kimi_linear
from ray_tpu.models import layers as L
from ray_tpu.parallel.mesh import MeshConfig, create_mesh
from ray_tpu.parallel.ring_attention import reference_attention
from tests import test_model_checks as checks
from tests.test_zz_tp_overlap import _walk

FILED = checks.filed("kimi-linear-tiny")
LEAVES = ("wte", "head", "w_f_down", "A_log", "dt_bias", "w_qkv", "wq",
          "wkv_b", "wg", "w_gate", "w_down")


@functools.cache
def _setup(seed=3, **fields):
    cfg = dataclasses.replace(kimi_linear.kimi_linear_tiny(), **fields)
    params = kimi_linear.init(jax.random.PRNGKey(seed), cfg)
    # norms off their initial one, so that one applied in the wrong place
    # shows
    params = checks.moved_off(
        params, seed, lambda key, a: 0.1 if "norm" in key or "ln_" in key
        else 0)
    return cfg, params, checks.token_ids(16, seq=40, seed=seed + 1)


def _system(cfg):
    return lambda p, t: kimi_linear.loss_fn(p, {"tokens": t}, cfg)[0]


# the KDA layer with the dense feed-forward, then the latent layer with the
# routed one: every KIND of leaf the benchmark compares, two layers to
# compile (the tiny preset's three go through the trainer in
# `tests/chipbench_tests/` and through every leaf's gradient below)
_TWO = kimi_linear.kimi_linear_tiny().layer_types[:2]
FILED_TWO = dict(FILED, layers=2)


def _reference(once_a_run):
    """The reference's half of the comparison, made once a run: remat and
    the products' dtype are not its business."""
    _, params, tokens = _setup(layer_types=_TWO)
    return checks.once(
        once_a_run, "kimi_linear_tiny_reference_side",
        lambda: checks.reference_side(
            lambda p, t: reference.loss(p, t, FILED_TWO), accounting, params,
            tokens), accounting.pick(params))


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_picked_gradients_against_the_reference(remat, once_a_run):
    """The loss and the gradients the benchmark compares, in float32 against
    `chipbench/references/kimi_linear.py`: the chunked rule with its own
    backward against the recurrence differentiated by JAX."""
    cfg, params, tokens = _setup(remat=remat, dtype=jnp.float32,
                                 layer_types=_TWO)
    assert accounting.ran_sizes(cfg) == accounting.filed_sizes(FILED_TWO)
    assert accounting.ran_sizes(kimi_linear.kimi_linear_tiny()) == \
        accounting.filed_sizes(FILED)
    out = checks.compared(_system(cfg), accounting, params, tokens,
                          _reference(once_a_run))
    assert set(out["errors"]) == {"loss"} | {"grad_" + k for k in LEAVES}
    assert max(out["errors"].values()) < 3e-5, out["errors"]
    assert out["reference_loss"] > 5.0


def test_bf16_products_stay_inside_the_benchmarks_limits(once_a_run):
    """The preset's own precision — bf16 operands, one pass — against the
    float32 reference: inside `compare`'s limits, and not by being exact.
    And THE COMPARISON CATCHES EIGHT-BIT WEIGHTS: the same compiled program
    on every matmul weight rounded to e4m3 (`benchmarks/precision_control`'s
    control is straight-through: the gradient AT the rounded weights), the
    precision below the stated one, is outside the limits on several
    compared leaves."""
    from benchmarks import precision_control

    cfg, params, tokens = _setup(remat=True, layer_types=_TWO)
    assert cfg.dtype == jnp.bfloat16
    want = _reference(once_a_run)
    system = jax.jit(compare.loss_and_grads(_system(cfg), accounting.pick,
                                            accounting.put))

    def errors(p):
        loss, grads = system(p, tokens)
        return dict({f"grad_{k}": compare.rel_l2(grads[k], want[1][k])
                     for k in want[1]},
                    loss=abs(float(loss) - want[0]) / abs(want[0]))
    stated = errors(params)
    assert stated.pop("loss") < compare.LOSS_RTOL
    assert max(stated.values()) < compare.GRAD_RTOL, stated
    assert min(stated.values()) > 1e-4, stated
    eight = errors(precision_control._eight_bit(params))
    over = [k for k, v in eight.items()
            if k != "loss" and v > compare.GRAD_RTOL]
    assert len(over) >= 3, eight


def test_every_leafs_gradient_against_the_reference():
    cfg, params, tokens = _setup(seed=5, remat=True, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, got, want = checks.against_reference(
            lambda p: _system(cfg)(p, tokens),
            lambda p: reference.loss(p, tokens, FILED), params,
            loss_rtol=1e-5, grad_tol=1e-4,
            # at zero behind a stop_gradient, in both
            skip=("['bias']",))
    # tables, last norm; a layer's two norms; 11 leaves a KDA layer, 5 the
    # latent one; 3 the dense feed-forward, 8 a routed one (its shared
    # expert's three among them)
    assert len(jax.tree_util.tree_leaves(got)) == \
        3 + 3 * 2 + 2 * 11 + 5 + 3 + 2 * 8
    for layer in got["layers"][1:]:
        assert not np.any(np.asarray(layer["ff"]["bias"]))


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's share test: the reference's routed layer
    given ALL 8 experts equals the sum of what the four shares' layers give
    (2 experts each, every share with the same sigmoid router over all 8),
    the shared expert — whole in every share — counted ONCE."""
    whole_cfg = L.MoEConfig(n_experts=8, top_k=3, score="sigmoid",
                            scale=2.446, gate="silu", d_shared=32)
    whole = L.init_moe(jax.random.PRNGKey(5), 64, 32, whole_cfg, gated=True)
    whole["wg"] = whole["wg"] * 7.5
    assert set(whole) == {"wg", "bias", "w_gate", "w_up", "w_down", "shared"}
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, 64))
    config = dict(FILED, deployment={"first_expert": 0})
    with jax.default_matmul_precision("highest"):
        uncut = jax.vmap(lambda n: reference.routed(n, whole, config=config))(x)
        shared = L.apply_gated_mlp(whole["shared"], x,
                                   compute_dtype=jnp.float32)
        total, held = 0.0, 0
        for rank in range(4):
            cfg = dataclasses.replace(whole_cfg, held=2, first=2 * rank)
            part = dict(whole, **{k: whole[k][2 * rank:2 * rank + 2]
                                  for k in ("w_gate", "w_up", "w_down")})
            out, stats = L.apply_moe(part, x, cfg, compute_dtype=jnp.float32)
            total = total + (out - shared)
            held += int(jnp.sum(stats["counts"][2 * rank:2 * rank + 2]))
    assert held == 2 * 40 * 3
    np.testing.assert_allclose(total + shared, uncut, atol=5e-6)
    # the shared expert is no small part of it
    assert float(jnp.abs(shared).mean()) > 0.1 * float(jnp.abs(uncut).mean())


def test_the_kda_layer_against_the_references_lines():
    """`apply_kda` — one fused projection, the conv stage, both low-rank
    gates, the rule, the sigmoid-gated head norm — against the reference's
    layer, whose rule is the recurrence."""
    cfg = kimi_linear.kimi_linear_tiny()
    params = checks.moved_off(
        L.init_kda(jax.random.PRNGKey(0), 64, cfg.kda), 1,
        lambda key, a: 0.1 if key == "norm" else 0)
    assert {k: v.shape for k, v in params.items()} == {
        "w_qkv": (64, 192), "conv_w": (4, 192), "w_f_down": (64, 8),
        "w_f_up": (8, 64), "dt_bias": (64,), "A_log": (4,),
        "w_beta": (64, 4), "w_g_down": (64, 8), "w_g_up": (8, 64),
        "norm": (16,), "w_out": (64, 64)}
    assert sum(v.size for v in params.values()) == cfg.kda.n_params(64)
    assert set(L.KDA_LOGICAL) == set(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 64))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(functools.partial(
            L.apply_kda, cfg=cfg.kda, compute_dtype=jnp.float32,
            eps=FILED["rms_norm_eps"]))(params, x)
        want = jax.jit(jax.vmap(
            lambda m: reference.kda_layer(m, params, config=FILED)))(x)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_latent_attention_without_rank_and_rotation():
    """`LatentConfig(q_rank=None, rotate=False)`: q one product, one leaf;
    the 8 shared key columns plain — against the reference's layer, and
    another function than the rotated one."""
    cfg = kimi_linear.kimi_linear_tiny().latent
    params = L.init_latent_attention(jax.random.PRNGKey(0), 64, cfg)
    assert {k: v.shape for k, v in params.items()} == {
        "wq": (64, 4, 32), "wkv_a": (64, 40), "kv_norm": (32,),
        "wkv_b": (32, 4, 40), "wo": (4, 16, 64)}
    assert set(L.LATENT_FULL_Q_LOGICAL) == set(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 64))
    kw = dict(eps=FILED["rms_norm_eps"], compute_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(functools.partial(
            L.apply_latent_attention, cfg=cfg, **kw))(params, x)
        want = jax.jit(jax.vmap(
            lambda m: reference.attention(m, params, config=FILED)))(x)
        turned = jax.jit(functools.partial(
            L.apply_latent_attention,
            cfg=dataclasses.replace(cfg, rotate=True), **kw))(params, x)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert float(jnp.abs(turned - got).max()) > 1e-4


def _parents_latent_attention(params, x, cfg, *, eps, impl, compute_dtype):
    """`apply_latent_attention`'s reference path as the parent commit had
    it, before `q_rank` could be None and `rotate` False."""
    cd, nope = compute_dtype, cfg.nope_dim
    project = L.project(cd, False)
    turn = functools.partial(L.rope, theta=cfg.rope_theta,
                             interleaved=cfg.rope_interleaved)
    with jax.named_scope("latent_proj"):
        c_q = L.rms_norm(project("bsd,dr->bsr", x, params["wq_a"],
                                 jnp.float32), params["q_norm"], eps)
        q = project("bsr,rhk->bshk", c_q, params["wq_b"], jnp.float32)
        q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])],
                            axis=-1).astype(cd)
        ckv = project("bsd,dr->bsr", x, params["wkv_a"], jnp.float32)
        c_kv = L.rms_norm(ckv[..., :cfg.kv_rank], params["kv_norm"], eps)
        k_pe = turn(ckv[:, :, None, cfg.kv_rank:])[:, :, 0].astype(cd)
        kv = project("bsr,rhk->bshk", c_kv, params["wkv_b"], None)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_pe[:, :, None], (*k_nope.shape[:3], cfg.rope_dim))], axis=-1)
    o = reference_attention(q, k, v, causal=True)
    out = project("bshk,hkd->bsd", o.astype(cd), params["wo"], x.dtype)
    return checkpoint_name(out, L.ATTENTION_OUT)


def test_joyais_latent_call_traces_to_the_program_it_was():
    cfg = joyai.joyai_tiny().latent
    assert cfg.q_rank == 48 and cfg.rotate
    params = L.init_latent_attention(jax.random.PRNGKey(0), 64, cfg)
    assert set(params) == set(L.LATENT_ATTENTION_LOGICAL)
    x = jnp.zeros((2, 40, 64))
    kw = dict(eps=1e-6, impl="reference", compute_dtype=jnp.bfloat16)
    now = jax.make_jaxpr(functools.partial(
        L.apply_latent_attention, cfg=cfg, **kw))(params, x)
    was = jax.make_jaxpr(functools.partial(
        _parents_latent_attention, cfg=cfg, **kw))(params, x)
    assert str(now) == str(was)


def test_the_scopes_the_metrics_read():
    """Every equation of a KDA layer's half under `kda` and one of its four
    inner scopes, the latent layer's under `attn`, the feed-forwards under
    `mlp` / `moe`: nothing the new layers add is unscoped."""
    cfg, params, tokens = _setup()
    forward = jax.make_jaxpr(lambda p: _system(cfg)(p, tokens))(params)
    stacks = {str(eqn.source_info.name_stack)
              for eqn, _, _ in _walk(forward.jaxpr)}
    for scope in ("kda/kda_proj", "kda/kda_conv", "kda/kda_rule",
                  "kda/kda_gate_norm", "attn/latent_proj", "mlp", "moe",
                  "embed", "loss_tail"):
        assert any(scope in s for s in stacks), scope
    # the layer loop's own equations (a sub-jaxpr's stacks are relative to
    # its call, which carries the scope): each under a half's scope, a KDA
    # half's under `kda` alone (its norm and residual add) or one of the
    # four inner scopes
    in_blocks = [(str(eqn.source_info.name_stack).split("/"), eqn.primitive)
                 for eqn, _, _ in _walk(forward.jaxpr)
                 if str(eqn.source_info.name_stack).startswith("blocks")]
    assert len(in_blocks) > 100
    for parts, _ in in_blocks:
        assert len(parts) > 1 and parts[1] in ("kda", "attn", "mlp", "moe"), parts
        if parts[1] == "kda" and len(parts) > 2:
            assert parts[2] in ("kda_proj", "kda_conv", "kda_rule",
                                "kda_gate_norm"), parts


def test_the_presets_and_what_the_model_refuses():
    whole = kimi_linear.kimi_linear_48b_a3b()
    assert whole.layer_types.count("mla") == 7 and whole.n_layer == 27
    assert [i + 1 for i, k in enumerate(whole.layer_types) if k == "mla"] \
        == [4, 8, 12, 16, 20, 24, 27]
    assert whole.n_params == 49_122_681_728
    cut = kimi_linear.kimi_linear_48b_a3b_5l()
    assert cut.layer_types == ("kda", "kda", "kda", "mla", "kda")
    assert cut.n_params == 602_434_432
    assert (cut.moe.stacked, cut.moe.n_experts, cut.moe.top_k,
            cut.moe.scale, cut.moe.score) == (8, 256, 8, 2.446, "sigmoid")
    assert (cut.latent.q_rank, cut.latent.rotate, cut.latent.qk_dim) == \
        (None, False, 192)
    tiny, params, _ = _setup()
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) == \
        tiny.n_params
    specs = kimi_linear.partition_specs(tiny)
    assert jax.tree_util.tree_structure(specs) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda a: 0, params))
    with pytest.raises(ValueError, match="a layer is 'kda' or 'mla'"):
        dataclasses.replace(tiny, layer_types=("kda", "linear_attention"))
    mesh = create_mesh(MeshConfig(dp=1, tp=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="tp > 1 is not supported"):
        kimi_linear.loss_fn(params, {"tokens": jnp.zeros((2, 9), jnp.int32)},
                            tiny, mesh)
