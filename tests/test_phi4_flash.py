"""`ray_tpu.models.phi4_flash` (Phi-4-mini-flash-reasoning, SambaY) against
the plain reference `chipbench/references/phi4_flash.py` at test sizes on
the CPU: loss and EVERY leaf's gradient on the 8-layer tiny model — two gated
memory units on one memory, two cross layers on one K/V pair, so both shared
results' cotangents arrive from two readers — with remat on and off;
differential attention with a window inside the sequence, one that covers
it, and another layer's K/V; the flash kernels at q/k 64, v 128 under a
window in the interpreter; the layer pattern and the parameter counts by
hand; what the model refuses; the 8-bit control of `chipbench/compare.py`
through the benchmark's own comparison."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import phi4_flash as reference
from ray_tpu.models import layers as L
from ray_tpu.models import phi4_flash as model
from tests import test_model_checks as checks

CONFIG = {"layer_norm_eps": 1e-5, "num_attention_heads": 8,
          "num_key_value_heads": 4, "sliding_window": 8,
          "num_hidden_layers": 8, "first_layer": 2}


@pytest.fixture(autouse=True)
def exact_products():
    """float32 products to float32 accuracy, for this file's tests alone."""
    with jax.default_matmul_precision("highest"):
        yield


def _noised(tree, seed=1, scale=0.05):
    """Every leaf moved off its draw: biases and norms off 0 and 1. The
    noise is numpy's: a draw a leaf shape through JAX compiles for each."""
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(np.asarray(a) + scale * rng.standard_normal(
            a.shape), a.dtype), tree)


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(model.phi4_flash_tiny(), dtype=jnp.float32)
    params = _noised(model.init(jax.random.PRNGKey(0), cfg))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 41), 0, 256)
    return cfg, params, tokens


@pytest.fixture(scope="module")
def tiny_reference(tiny, once_a_run):
    """The reference's loss and every leaf's gradient on `tiny`: the dear
    part, asked for by the tests that compare with it alone, and made once
    a run (float32 survives the workers' shared JSON digit for digit)."""
    _, params, tokens = tiny

    def make():
        with jax.default_matmul_precision("highest"):
            loss, grads = checks.loss_and_grads(
                lambda p: reference.loss(p, tokens, CONFIG), params)
        return float(loss), [np.asarray(g).tolist()
                             for g in jax.tree_util.tree_leaves(grads)]
    loss, leaves = once_a_run("phi4_flash_tiny_reference", make)
    return jnp.float32(loss), jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params),
        [jnp.asarray(g, jnp.float32) for g in leaves])


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_gradient_against_the_reference(tiny, tiny_reference,
                                                       remat):
    (cfg, params, tokens), (want, want_grads) = tiny, tiny_reference
    cfg = dataclasses.replace(cfg, remat=remat)
    assert cfg.layer_types == (
        model.MAMBA, model.WINDOW, model.MAMBA, model.FULL, model.GMU,
        model.CROSS, model.GMU, model.CROSS)
    (got, metrics), grads = checks.loss_and_grads(
        lambda p: model.loss_fn(p, {"tokens": tokens}, cfg), params,
        has_aux=True)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    # 80 tokens: the memory [80, 128] float32, k and v [80, 32] each
    assert float(metrics["memory_bytes"]) == 4 * 80 * 128
    assert float(metrics["shared_kv_bytes"]) == 4 * 80 * 2 * 32
    # no leaf's reference gradient is zero: 0 / 0 would fail
    checks.assert_close(grads, want_grads, 5e-5)


@pytest.mark.parametrize("case", ["window_inside", "window_covers",
                                  "given_kv"])
def test_differential_attention_against_the_masked_softmax(case):
    """One layer on its own: `layers.apply_diff_attention` beside the
    reference's explicit masked scores, and the pair it hands on."""
    cfg = L.DiffAttnConfig(n_head=8, n_kv_head=4, head_dim=8)
    own = case != "given_kv"
    params = _noised(L.init_diff_attention(
        jax.random.PRNGKey(3), 64, cfg, own_kv=own), seed=4, scale=0.2)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64))
    window = {"window_inside": 5, "window_covers": 24, "given_kv": None}[case]
    kv = ref_kv = None
    if not own:
        k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 24, 4, 8))
                for i in (6, 7))
        kv, ref_kv = (k, v.reshape(1, 24, 2, 16)), (k[0], v[0])
    out, handed = jax.jit(lambda p, x, kv: L.apply_diff_attention(
        p, x, cfg, depth=17, window=window, kv=kv, impl="reference",
        compute_dtype=jnp.float32))(params, x, kv)
    want, want_kv = jax.jit(lambda p, x, kv: reference.diff_attention(
        x, p, depth=17, window=window, kv=kv, config=dict(CONFIG)))(
            params, x[0], ref_kv)
    np.testing.assert_allclose(out[0], want, atol=2e-5)
    np.testing.assert_allclose(handed[0][0], want_kv[0], atol=1e-5)
    np.testing.assert_allclose(handed[1][0].reshape(24, 4, 8), want_kv[1],
                               atol=1e-5)
    with pytest.raises(ValueError, match="no 'ring' path"):
        L.apply_diff_attention(params, x, cfg, depth=17, kv=kv, impl="ring")


def test_flash_at_64_and_128_under_a_window_in_the_interpreter():
    """The differential call's shapes: q and k 64 wide, v 128, two query
    heads a KV head, a window inside the sequence; values and the three
    gradients against the plain attention."""
    from ray_tpu.ops.flash_attention import flash_attention, tile_plan
    from ray_tpu.parallel.ring_attention import reference_attention

    k = jax.random.split(jax.random.PRNGKey(8), 4)
    q = jax.random.normal(k[0], (1, 256, 2, 64))
    key = jax.random.normal(k[1], (1, 256, 1, 64))
    v = jax.random.normal(k[2], (1, 256, 1, 128))
    w = jax.random.normal(k[3], (1, 256, 2, 128))
    # no entry of its own: the default tiles (`tile_plan`'s docstring)
    assert tile_plan(256, 64, jnp.float32, v_dim=128) == \
        tile_plan(256, 64, jnp.float32)

    def plain(q, key, v):
        return reference_attention(q, jnp.repeat(key, 2, axis=2),
                                   jnp.repeat(v, 2, axis=2), causal=True,
                                   window=100)
    got, got_grads = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(flash_attention(
            *a, causal=True, window=100, interpret=True) * w),
        argnums=(0, 1, 2)))(q, key, v)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(plain(*a) * w), argnums=(0, 1, 2)))(q, key, v)
    assert float(got) == pytest.approx(float(want), rel=1e-4, abs=1e-3)
    for g, r in zip(got_grads, want_grads):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, atol=2e-4)


def test_the_pattern_and_the_counts_by_hand():
    kinds = [model.kind_of(i) for i in range(32)]
    assert [kinds.count(k) for k in (model.MAMBA, model.WINDOW, model.FULL,
                                     model.GMU, model.CROSS)] == [9, 8, 1, 7,
                                                                  7]
    assert kinds[14:20] == [model.MAMBA, model.WINDOW, model.MAMBA,
                            model.FULL, model.GMU, model.CROSS]
    full, cut = model.phi_4_mini_flash(), model.phi_4_mini_flash_6l()
    assert (full.memory_layer, full.kv_layer) == (16, 17)
    assert full.n_params == 3_852_562_944
    assert cut.n_params == 697_299_072 and cut.depths == tuple(range(14, 20))
    assert (cut.mamba.inner, cut.mamba.dt_rank, cut.diff.pairs,
            cut.diff.kv_pairs) == (5120, 160, 20, 10)
    assert L.lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), model.phi4_flash_tiny()))
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) == \
        model.phi4_flash_tiny().n_params
    specs = model.partition_specs(model.phi4_flash_tiny())
    assert jax.tree_util.tree_structure(specs, is_leaf=lambda s: isinstance(
        s, jax.sharding.PartitionSpec)) == jax.tree_util.tree_structure(
            shapes)
    assert model.side_plan(cut, 8192) == {
        "memory_bytes": 8192 * 5120 * 4, "shared_kv_bytes": 8192 * 2560 * 2}


@pytest.mark.parametrize("first, n_layer, missing", [(17, 3, "memory"),
                                                     (18, 2, "memory"),
                                                     (19, 1, "K and V")])
def test_a_run_of_layers_holds_what_its_cross_decoder_reads(first, n_layer,
                                                            missing):
    with pytest.raises(ValueError, match=missing):
        model.Phi4FlashConfig(first_layer=first, n_layer=n_layer)
    model.Phi4FlashConfig(first_layer=0, n_layer=16)    # no reader: fine


def test_the_scans_kernels_in_the_step_lowered_for_a_tpu(monkeypatch):
    """The step of the tiny pattern's two Mamba-1 layers under remat, lowered
    FOR A TPU (no compile, nothing run) at widths the scan's tiles divide
    (128 channels, 8 states): `sscan_fwd` at four sites (forward and
    recompute) and `sscan_bwd` at two, every one under the scope
    `selective_scan` inside `mamba1` (a `custom_vjp`'s backward rule
    inherits its caller's scopes) in its phase, the kernels named for the
    trace, and no loop left under `selective_scan` (the plain form's walk is
    `while`s)."""
    import re
    import types

    from ray_tpu.ops import selective_scan as ss
    from ray_tpu.parallel.compile_watch import parse_op_name

    # the scan alone: the conv stage and flash keep this host's
    monkeypatch.setattr(ss, "target", types.SimpleNamespace(
        where=lambda mesh=None, *, interpret=False: ("tpu", 1)))

    def lowered(**fields):
        cfg = dataclasses.replace(model.phi4_flash_tiny(), remat=True,
                                  **fields)
        params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), cfg))
        text = jax.jit(jax.grad(
            lambda p, t: model.loss_fn(p, {"tokens": t}, cfg)[0])).trace(
                params, jax.ShapeDtypeStruct((1, 129), jnp.int32)).lower(
                    lowering_platforms=("tpu",)).as_text(debug_info=True)
        return text, dict(re.findall(r'^(#loc\d+) = loc\("([^"]+)"', text,
                                     re.M))

    text, names = lowered(d_state=8)
    sites = {}
    for callee, loc in re.findall(
            r"call @(_sscan_(?:fwd|bwd))(?:_\d+)?\(.*loc\((#loc\d+)\)", text):
        scopes, phase = parse_op_name(names[loc] + "/call")
        assert "selective_scan" in scopes and "mamba1" in scopes, names[loc]
        sites.setdefault(callee, []).append(phase)
    assert {k: sorted(v) for k, v in sites.items()} == {
        "_sscan_fwd": ["forward"] * 2 + ["recompute"] * 2,
        "_sscan_bwd": ["backward"] * 2}
    kernels = re.findall(r'custom_call @tpu_custom_call.*loc\((#loc\d+)\)',
                         text)
    assert sorted({names[loc] for loc in kernels}) == [
        "sscan_bwd/pallas_call", "sscan_fwd/pallas_call"]

    def loops(names):
        return [n for n in names.values()
                if "selective_scan" in n and "while" in n]
    assert not loops(names)
    # the tiny preset's own 4 states take the plain form, whose walk loops
    text, names = lowered()
    assert "tpu_custom_call" not in text and loops(names)


def test_a_mesh_that_splits_heads_is_refused():
    """Refused from the mesh alone, before anything is computed."""
    cfg = model.phi4_flash_tiny()
    devices = np.array(jax.devices()[:2]).reshape(1, 2)
    mesh = jax.sharding.Mesh(devices, ("dp", "tp"))
    with pytest.raises(ValueError, match="tp > 1 is not supported"):
        jax.eval_shape(
            lambda key: model.forward(model.init(key, cfg),
                                      jnp.zeros((2, 40), jnp.int32), cfg,
                                      mesh), jax.random.PRNGKey(0))


def test_the_comparison_catches_eight_bit_weights(tiny, tiny_reference):
    """`chipbench/compare.py`'s limits on the tiny model with bf16 operands
    and every matmul weight rounded to e4m3, the precision below the stated
    one: outside them on several compared leaves. (The stated precision
    within them: `test_zz_chipbench_phi4_flash.py`, through the job.)"""
    from benchmarks import precision_control
    from chipbench import compare
    from chipbench.accounting import phi4_flash as accounting

    (cfg, params, tokens), (_, want_grads) = tiny, tiny_reference
    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    want_grads = accounting.pick(want_grads)

    def system(leaves):
        p = precision_control._eight_bit(accounting.put(params, leaves))
        return model.loss_fn(p, {"tokens": tokens}, cfg)[0]
    grads = jax.jit(jax.grad(system))(accounting.pick(params))
    eight = {k: compare.rel_l2(grads[k], want_grads[k]) for k in grads}
    over = [k for k, v in eight.items() if v > compare.GRAD_RTOL]
    assert len(over) >= 3, eight
