"""`ray_tpu.models.qwen3_next` (Qwen3-Next: Gated-DeltaNet layers among
gated softmax-attention layers, a sigmoid-gated shared expert beside a
softmax router over a share of the experts) at test sizes on the CPU: the
system's `loss_fn` against the benchmark's plain reference (whose delta rule
is the token-by-token recurrence), the share test of the model-configs
guide, the layer's new pieces each against a hand-written line, the presets
and what the model refuses."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.accounting import qwen3_next as accounting
from chipbench.references import qwen3_next as reference
from ray_tpu.models import layers as L
from ray_tpu.models import qwen3_next
from ray_tpu.parallel.mesh import MeshConfig, create_mesh
from ray_tpu.parallel.ring_attention import reference_attention
from tests import test_model_checks as checks

FILED = checks.filed("qwen3-next-tiny")
LEAVES = ("head", "w_in", "w_out", "A_log", "dt_bias", "conv_w", "wq",
          "k_norm", "wg", "w_gate", "w_down", "shared_w_gate", "w_sg")


@functools.cache
def _setup(seed=3, **fields):
    cfg = dataclasses.replace(qwen3_next.qwen3_next_tiny(),
                              dtype=jnp.float32, **fields)
    params = qwen3_next.init(jax.random.PRNGKey(seed), cfg)
    # norms off their initial zero, so that `1 + w` is not `1`
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(len(str(path))), a.shape)
        if "norm" in str(path[-1]) or "ln_" in str(path[-1]) else a, params)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, 41), 0, 16)
    return cfg, params, tokens


@functools.cache
def _reference():
    """The reference's half of the comparison: remat is not its business."""
    _, params, tokens = _setup()
    return checks.reference_side(lambda p, t: reference.loss(p, t, FILED),
                                 accounting, params, tokens)


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_picked_gradients_against_the_reference(remat):
    """The loss and the gradients the benchmark compares, in float32 against
    `chipbench/references/qwen3_next.py`: the chunked rule with its own
    backward against the recurrence differentiated by JAX."""
    cfg, params, tokens = _setup(remat=remat)
    assert accounting.ran_sizes(cfg) == accounting.filed_sizes(FILED)
    out = checks.compared(
        lambda p, t: qwen3_next.loss_fn(p, {"tokens": t}, cfg)[0], accounting,
        params, tokens, _reference())
    assert set(out["errors"]) == {"loss"} | {"grad_" + k for k in LEAVES}
    assert max(out["errors"].values()) < 2e-5, out["errors"]
    assert out["reference_loss"] > 5.0


@functools.cache
def _every_leaf_of_the_reference():
    _, params, tokens = _setup(seed=5)
    with jax.default_matmul_precision("highest"):
        return checks.loss_and_grads(
            lambda p: reference.loss(p, tokens, FILED), params)[1]


@pytest.mark.parametrize("remat", [False, True])
def test_every_leafs_gradient_against_the_reference(remat):
    cfg, params, tokens = _setup(seed=5, remat=remat)
    with jax.default_matmul_precision("highest"):
        _, got = checks.loss_and_grads(lambda p: qwen3_next.loss_fn(
            p, {"tokens": tokens}, cfg)[0], params)
        want = _every_leaf_of_the_reference()
    assert len(jax.tree_util.tree_leaves(got)) == 3 + 4 * 17 + 16
    checks.assert_close(got, want, 5e-5)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's share test: the routed layer with ALL 16
    experts equals the sum of the sixteen shares' results (one expert each,
    every share with the same softmax router over all 16), the gated shared
    expert — whole in every share — counted ONCE."""
    whole_cfg = L.MoEConfig(n_experts=16, top_k=3, score="softmax",
                            gate="silu", d_shared=32, shared_gate=True)
    whole = L.init_moe(jax.random.PRNGKey(5), 64, 32, whole_cfg, gated=True)
    assert set(whole) == {"wg", "w_gate", "w_up", "w_down", "shared", "w_sg"}
    assert whole["w_sg"].shape == (64, 1)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, 64))
    uncut, stats = L.apply_moe(whole, x, whole_cfg,
                               compute_dtype=jnp.float32)
    shared = jax.nn.sigmoid(x @ whole["w_sg"]) * L.apply_gated_mlp(
        whole["shared"], x, compute_dtype=jnp.float32)
    total, held = 0.0, 0
    for rank in range(16):
        cfg = dataclasses.replace(whole_cfg, held=1, first=rank)
        part = dict(whole, **{k: whole[k][rank:rank + 1]
                              for k in ("w_gate", "w_up", "w_down")})
        out, part_stats = L.apply_moe(part, x, cfg, compute_dtype=jnp.float32)
        np.testing.assert_array_equal(part_stats["counts"], stats["counts"])
        total = total + (out - shared)
        held += int(stats["counts"][rank])
    assert held == 2 * 40 * 3
    np.testing.assert_allclose(total + shared, uncut, atol=2e-6)
    # the shared expert is no small part of it, and its gate no constant
    assert float(jnp.abs(shared).mean()) > 0.1 * float(jnp.abs(uncut).mean())
    gate = jax.nn.sigmoid(x @ whole["w_sg"])
    assert float(gate.std()) > 0.01 and 0.3 < float(gate.mean()) < 0.7


def test_the_output_gate_against_a_hand_written_line():
    """`out_gate`: a head's second half of `wq`'s columns, through a
    sigmoid, times the attention's output ahead of `wo`."""
    params = L.init_attention(jax.random.PRNGKey(0), 64, 4, n_kv_head=2,
                              head_dim=16, out_gate=True)
    assert params["wq"].shape == (64, 4, 32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64))
    got = L.apply_attention(params, x, compute_dtype=jnp.float32,
                            out_gate=True)
    q_gate = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    q, gate = q_gate[..., :16], q_gate[..., 16:]
    k, v = (jnp.repeat(jnp.einsum("bsd,dhk->bshk", x, params[w]), 2, axis=2)
            for w in ("wk", "wv"))
    o = reference_attention(q, k, v, causal=True) * jax.nn.sigmoid(gate)
    np.testing.assert_allclose(
        got, jnp.einsum("bshk,hkd->bsd", o, params["wo"]), atol=1e-6)
    # and without the gate the layer is what it was
    plain = dict(params, wq=params["wq"][..., :16])
    np.testing.assert_allclose(
        L.apply_attention(plain, x, compute_dtype=jnp.float32),
        jnp.einsum("bshk,hkd->bsd", reference_attention(q, k, v, causal=True),
                   params["wo"]), atol=1e-6)


def test_the_rotated_columns_against_a_hand_written_line():
    """The first `rotary_dim` columns of a head turn, column i with column i
    + rotary_dim/2 by pos · θ^(−2i/rotary_dim); the others stay."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 9, 2, 32))
    rotary, theta = 8, 10000.0
    got = jnp.concatenate([L.rope(x[..., :rotary], theta), x[..., rotary:]],
                          axis=-1)
    np.testing.assert_array_equal(got[..., rotary:], x[..., rotary:])
    for pos in (0, 4, 8):
        for i in range(rotary // 2):
            angle = pos * theta ** (-2 * i / rotary)
            a, b = x[0, pos, :, i], x[0, pos, :, i + rotary // 2]
            np.testing.assert_allclose(
                got[0, pos, :, i], a * np.cos(angle) - b * np.sin(angle),
                atol=1e-6)
            np.testing.assert_allclose(
                got[0, pos, :, i + rotary // 2],
                b * np.cos(angle) + a * np.sin(angle), atol=1e-6)
    np.testing.assert_allclose(
        reference.rotate_front(jnp.moveaxis(x[0], 0, 1), rotary, theta),
        jnp.moveaxis(got[0], 0, 1), atol=1e-6)


def test_the_zero_centred_norm_against_a_hand_written_line():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 16)) * 3.0
    w = 0.2 * jax.random.normal(jax.random.PRNGKey(4), (16,))
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * (1 + w)
    np.testing.assert_allclose(L.rms_norm_centred(x, w, 1e-6), want,
                               atol=1e-6)
    # drawn at 0 it is the plain norm at scale 1, and w has a gradient
    np.testing.assert_allclose(L.rms_norm_centred(x, jnp.zeros(16), 1e-6),
                               L.rms_norm(x, jnp.ones(16), 1e-6), atol=1e-7)
    got = jax.grad(lambda w: jnp.sum(L.rms_norm_centred(x, w, 1e-6) ** 2))(w)
    want = jax.grad(lambda w: jnp.sum((x / jnp.sqrt(jnp.mean(
        x * x, -1, keepdims=True) + 1e-6) * (1 + w)) ** 2))(w)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_the_delta_layer_norms_then_gates():
    """`apply_gated_delta` against the reference's layer alone: the conv
    without bias, L2-normed q and k, the head norm BEFORE the gate."""
    delta = L.DeltaConfig(n_k_heads=2, n_v_heads=4, k_dim=16, v_dim=16,
                          chunk=16)
    params = L.init_gated_delta(jax.random.PRNGKey(0), 64, delta)
    assert {k: v.shape for k, v in params.items()} == {
        "w_in": (64, 192), "w_ba": (64, 8), "conv_w": (4, 128),
        "dt_bias": (4,), "A_log": (4,), "norm": (16,), "w_out": (64, 64)}
    params["norm"] = params["norm"] + 0.3 * jax.random.normal(
        jax.random.PRNGKey(1), (16,))
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 40, 64))
    got = jax.jit(lambda p, u: L.apply_gated_delta(
        p, u, delta, compute_dtype=jnp.float32))(params, u)
    want = jnp.stack([reference.delta_layer(row, params, config=FILED)
                      for row in u])
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_the_kernel_path_equals_the_reference_path(interpreted):
    """`impl` "flash" through the Pallas interpreter at head size 32 on two
    KV heads: loss and every gradient as the plain-softmax path gives them;
    under remat a step holds THREE kernel calls for the one attention
    layer, not four."""
    cfg, params, tokens = _setup()

    def run(attention, remat):
        c = dataclasses.replace(cfg, attention=attention, remat=remat)
        return jax.jit(jax.value_and_grad(
            lambda p: qwen3_next.loss_fn(p, {"tokens": tokens}, c)[0]))(params)
    want = run("reference", False)
    for remat in (False, True):
        got = run("flash", remat)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                        jax.tree_util.tree_leaves(want[1])):
            np.testing.assert_allclose(a, b, atol=5e-6)
    c = dataclasses.replace(cfg, attention="flash", remat=True)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: qwen3_next.loss_fn(p, {"tokens": tokens}, c)[0]))(params))
    assert text.count("name=flash_fwd") == 1
    assert text.count("name=flash_dq") == 1
    assert text.count("name=flash_dkv") == 1


def test_each_half_of_a_layer_is_a_checkpoint_of_its_own():
    """Under remat a delta layer's half keeps its arguments and nothing of
    the rule (whose three stages run again for the backward: three scans a
    delta layer with the checkpoint, two without); the routed half its
    arguments and its routing (`L.ROUTING`: `L.routing_plan`'s bytes)."""
    from jax._src.ad_checkpoint import saved_residuals

    cfg, params, tokens = _setup()
    delta_layers = cfg.layer_types.count("linear_attention")

    def scans(remat):
        c = dataclasses.replace(cfg, remat=remat)
        return str(jax.make_jaxpr(jax.grad(lambda p: qwen3_next.loss_fn(
            p, {"tokens": tokens}, c)[0]))(params)).count("= scan[")
    assert (scans(False), scans(True)) == (2 * delta_layers, 3 * delta_layers)
    x = jnp.zeros((2, 40, cfg.d_model), jnp.float32)
    for body, routing in (
            (functools.partial(qwen3_next._mixer_apply,
                               kind="linear_attention", cfg=cfg,
                               impl="reference"), {}),
            (functools.partial(qwen3_next._moe_apply, cfg=cfg),
             L.routing_plan(2 * 40, cfg.moe))):
        kept = [a for a, why in saved_residuals(
            L.remat(body), x, params["layers"][0])
            if not why.startswith(("from the argument", "from a constant"))]
        assert sum(a.size * a.dtype.itemsize for a in kept) \
            == sum(routing.values()), kept


def test_the_rules_kernels_in_the_step_lowered_for_a_tpu(monkeypatch):
    """The step of three delta layers and an attention layer under remat,
    lowered FOR A TPU (no compile, nothing run) at head widths the rule's
    tiles divide: `delta_fwd` at six sites (forward and recompute) and
    `delta_bwd` at three, every one under the scope `delta_rule` (a
    `custom_vjp`'s backward rule inherits its caller's scopes), the kernels
    named for the trace, and no loop left under `delta_rule` (the plain
    form's carry is a `while`)."""
    import re
    import types

    from ray_tpu.ops import gated_delta as gd
    from ray_tpu.parallel.compile_watch import parse_op_name

    # the rule alone: the conv stage and the routed products keep this host's
    monkeypatch.setattr(gd, "target", types.SimpleNamespace(
        where=lambda mesh=None, *, interpret=False: ("tpu", 1)))

    def lowered(**fields):
        cfg = dataclasses.replace(qwen3_next.qwen3_next_tiny(), remat=True,
                                  layer_types=qwen3_next.layer_types(4),
                                  **fields)
        params = jax.eval_shape(
            lambda: qwen3_next.init(jax.random.PRNGKey(0), cfg))
        text = jax.jit(jax.grad(
            lambda p, t: qwen3_next.loss_fn(p, {"tokens": t}, cfg)[0])).trace(
                params, jax.ShapeDtypeStruct((1, 129), jnp.int32)).lower(
                    lowering_platforms=("tpu",)).as_text(debug_info=True)
        return text, dict(re.findall(r'^(#loc\d+) = loc\("([^"]+)"', text,
                                     re.M))

    text, names = lowered(n_k_heads=1, n_v_heads=2, k_dim=128, v_dim=128,
                          chunk=64)
    sites = {}
    for callee, loc in re.findall(
            r"call @(_delta_(?:fwd|bwd))(?:_\d+)?\(.*loc\((#loc\d+)\)", text):
        scopes, phase = parse_op_name(names[loc] + "/call")
        assert "delta_rule" in scopes and "gdn" in scopes, names[loc]
        sites.setdefault(callee, []).append(phase)
    assert {k: sorted(v) for k, v in sites.items()} == {
        "_delta_fwd": ["forward"] * 3 + ["recompute"] * 3,
        "_delta_bwd": ["backward"] * 3}
    kernels = re.findall(r'custom_call @tpu_custom_call.*loc\((#loc\d+)\)',
                         text)
    assert sorted({names[loc] for loc in kernels}) == [
        "delta_bwd/pallas_call", "delta_fwd/pallas_call"]

    def loops(names):
        return [n for n in names.values()
                if "delta_rule" in n and "while" in n]
    assert not loops(names)
    # the tiny preset's own widths take the plain form, whose carry loops
    text, names = lowered()
    assert "tpu_custom_call" not in text and loops(names)


def test_the_presets_and_what_the_model_refuses():
    cut, whole = (qwen3_next.qwen3_next_80b_a3b_4l(),
                  qwen3_next.qwen3_next_80b_a3b())
    assert cut.n_params == 625_994_816
    assert whole.n_params == 79_674_391_296
    assert cut.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert whole.layer_types.count("full_attention") == 12
    assert whole.layer_types[:8] == cut.layer_types * 2
    shapes = jax.eval_shape(
        lambda: qwen3_next.init(jax.random.PRNGKey(0), cut))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(shapes)) == cut.n_params
    delta, attn = shapes["layers"][0], shapes["layers"][3]
    assert delta["mixer"]["w_in"].shape == (2048, 12288)
    assert delta["mixer"]["w_ba"].shape == (2048, 64)
    assert delta["mixer"]["conv_w"].shape == (4, 8192)
    assert delta["mixer"]["w_out"].shape == (4096, 2048)
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        delta["mixer"])) == 33_718_464
    assert attn["mixer"]["wq"].shape == (2048, 16, 512)
    assert attn["mixer"]["wk"].shape == (2048, 2, 256)
    assert attn["mixer"]["k_norm"].shape == (256,)
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        attn["mixer"])) == 27_263_488
    for layer in (delta, attn):
        assert layer["ff"]["w_gate"].shape == (32, 2048, 512)
        assert layer["ff"]["wg"].shape == (2048, 512)
        assert layer["ff"]["shared"]["w_down"].shape == (512, 2048)
        assert layer["ff"]["w_sg"].shape == (2048, 1)
    assert shapes["head"].shape == shapes["wte"].shape == (19072, 2048)
    specs = qwen3_next.partition_specs(cut)
    assert jax.tree_util.tree_structure(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)) \
        == jax.tree_util.tree_structure(shapes)
    with pytest.raises(ValueError, match="layer_types holds"):
        dataclasses.replace(cut, layer_types=("conv",))
    # the draws the cell's preset sets apart from 0.02, and the norms at 0
    assert (cut.embed_std, cut.router_std) == (8192.0, 0.15)
    assert (whole.embed_std, whole.router_std) == (0.02, 0.02)
    tiny = qwen3_next.qwen3_next_tiny()
    drawn = qwen3_next.init(jax.random.PRNGKey(0), dataclasses.replace(
        tiny, embed_std=8.0, router_std=0.15))
    for leaf, std in ((drawn["wte"], 8.0),
                      (drawn["layers"][1]["ff"]["wg"], 0.15),
                      (drawn["layers"][3]["mixer"]["wq"], 0.02),
                      (drawn["head"], 0.02)):
        np.testing.assert_allclose(np.std(np.asarray(leaf)), std, rtol=0.1)
    for zero in (drawn["ln_f"], drawn["layers"][0]["ln_mix"],
                 drawn["layers"][3]["mixer"]["q_norm"]):
        assert not np.asarray(zero).any()
    assert np.asarray(drawn["layers"][0]["mixer"]["norm"] == 1).all()
    cfg, params, tokens = _setup()
    mesh = create_mesh(MeshConfig(dp=1, tp=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="tp > 1 is not supported"):
        qwen3_next.loss_fn(params, {"tokens": tokens}, cfg, mesh)


def test_a_dp_mesh_gives_the_unsharded_loss(interpreted):
    cfg, params, tokens = _setup()
    want = jax.jit(lambda p, t: qwen3_next.loss_fn(
        p, {"tokens": t}, cfg)[0])(params, tokens)
    mesh = create_mesh(MeshConfig(dp=2), devices=jax.devices()[:2])
    for attention in ("reference", "flash"):
        c = dataclasses.replace(cfg, attention=attention)
        got = jax.jit(lambda p, t, c=c: qwen3_next.loss_fn(
            p, {"tokens": t}, c, mesh)[0])(params, tokens)
        np.testing.assert_allclose(got, want, rtol=2e-6)


def test_the_steps_metrics_count_every_layer():
    cfg, params, tokens = _setup()
    _, metrics = jax.jit(lambda p, t: qwen3_next.loss_fn(
        p, {"tokens": t}, cfg))(params, tokens)
    # five routed layers, 2 · 40 tokens, 3 a token
    assert int(metrics["moe_assignments"]) == 5 * 2 * 40 * 3
    assert 0 < int(metrics["moe_held"]) < int(metrics["moe_assignments"])
    assert 0 <= int(metrics["moe_compact"]) <= 5
    logits, counts = jax.jit(lambda p, t: qwen3_next.forward(
        p, t, cfg))(params, tokens[:, :-1])
    assert logits.shape == (2, 40, 256) and counts.shape == (5, 16)
    assert int(counts.sum()) == 5 * 2 * 40 * 3
