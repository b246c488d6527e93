"""`ray_tpu.models.keye_vl2` (Keye-VL-2.0's language model: a learned sparse
attention — indexer, top-k selection, attention over the kept keys, the
indexer's own loss — beside sectioned rotary positions and a share of the
experts) at test sizes on the CPU: the system's `loss_fn` against the
benchmark's plain reference, the kept set against `jax.lax.top_k`, a short
sequence against plain causal attention, three different position streams,
the two disjoint gradient flows, the share test of the model-configs guide,
the kernels through the interpreter, the 8-bit control, the presets and what
the model refuses."""
import dataclasses
import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare
from chipbench.accounting import keye_vl2 as accounting
from chipbench.references import keye_vl2 as reference
from ray_tpu.models import keye_vl2
from ray_tpu.models import layers as L
from ray_tpu.ops import sparse_attention as sa
from tests import test_model_checks as checks

FILED = checks.filed("keyevl2-tiny")
LEAVES = ("wte", "head", "wq", "wk", "wo", "w_qI", "w_kI", "w_wI", "wg",
          "w_gate", "w_down")
SEQ = 40          # the tiny preset keeps 24 keys a query: 16 rows select


def _off_their_draw(key, a):
    """Norm scales off 1 and the indexer's bias off 0."""
    return 0.1 if ("norm" in key or key.startswith("ln_")
                   or key in ("k_scale", "k_bias")) else 0


@functools.cache
def _setup(seed=3, **fields):
    cfg = dataclasses.replace(keye_vl2.keye_vl2_tiny(), dtype=jnp.float32,
                              **fields)
    params = checks.moved_off(keye_vl2.init(jax.random.PRNGKey(seed), cfg),
                              seed, _off_their_draw)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, SEQ + 1),
                                0, 16)
    return cfg, params, tokens


def _reference(once_a_run):
    """The reference's half of the comparison, made once a run: remat and
    the products' dtype are not its business."""
    _, params, tokens = _setup()
    return checks.once(
        once_a_run, "keye_vl2_tiny_reference_side",
        lambda: checks.reference_side(
            lambda p, t: reference.loss(p, t, FILED), accounting, params,
            tokens), accounting.pick(params))


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_picked_gradients_against_the_reference(remat, once_a_run):
    """The loss (cross-entropy AND the indexer's KL) and the gradients the
    benchmark compares, in float32 against
    `chipbench/references/keye_vl2.py`: bisection against `lax.top_k`, the
    hand-written gradient of the KL against JAX's."""
    cfg, params, tokens = _setup(remat=remat)
    assert accounting.ran_sizes(cfg) == accounting.filed_sizes(FILED)
    out = checks.compared(
        lambda p, t: keye_vl2.loss_fn(p, {"tokens": t}, cfg)[0], accounting,
        params, tokens, _reference(once_a_run))
    assert set(out["errors"]) == {"loss"} | {"grad_" + k for k in LEAVES}
    assert max(out["errors"].values()) < 2e-5, out["errors"]
    assert out["reference_loss"] > 5.0


def _every_leaf_of_the_reference(once_a_run):
    """Every leaf's gradient under the reference, once a run: the two
    cases' yardstick."""
    _, params, tokens = _setup(seed=5)

    def make():
        with jax.default_matmul_precision("highest"):
            return checks.loss_and_grads(
                lambda p: reference.loss(p, tokens, FILED), params)
    return checks.once(once_a_run, "keye_vl2_tiny_every_leaf", make,
                       params)[1]


@pytest.mark.parametrize("remat", [False, True])
def test_every_leafs_gradient_against_the_reference(remat, once_a_run):
    cfg, params, tokens = _setup(seed=5, remat=remat)
    with jax.default_matmul_precision("highest"):
        (_, metrics), got = checks.loss_and_grads(lambda p: keye_vl2.loss_fn(
            p, {"tokens": tokens}, cfg), params, has_aux=True)
    want = _every_leaf_of_the_reference(once_a_run)
    # table, head, last norm; a layer: 2 norms, 6 of attention, 5 of the
    # indexer, 4 of the feed-forward
    assert len(jax.tree_util.tree_leaves(got)) == 3 + 3 * 17
    checks.assert_close(got, want, 5e-5)
    # every row keeps min(t + 1, 24) keys, in every layer and sequence
    kept = 24 * 25 // 2 + (SEQ - 24) * 24
    assert int(metrics["sparse_selected"]) == 2 * 3 * kept
    assert accounting.kept_pairs(FILED, SEQ) == kept
    assert float(metrics["indexer_loss"]) > 0
    assert float(metrics["loss"]) == pytest.approx(
        float(metrics["lm_loss"]) + float(metrics["indexer_loss"]), rel=1e-6)


@functools.cache
def _in_bf16():
    """The model with bf16 operands, compiled as the comparison compiles
    it: the two cases below run ONE program."""
    cfg = dataclasses.replace(_setup()[0], dtype=jnp.bfloat16)
    return checks.picked_program(
        lambda p, t: keye_vl2.loss_fn(p, {"tokens": t}, cfg)[0], accounting)


def test_bf16_products_stay_inside_the_benchmarks_limits(once_a_run):
    """With bf16 operands the compared leaves read inside
    `chipbench/compare.py`'s limits (what a cell's `correct` holds the chip
    to), the indexer's in float32 whatever the compute dtype."""
    _, params, tokens = _setup()
    out = checks.held_to(_in_bf16()(params, tokens), _reference(once_a_run))
    assert out["within"], out["errors"]
    assert max(out["errors"].values()) > 1e-4     # and bf16 is not float32


def test_the_comparison_catches_eight_bit_weights(once_a_run):
    """Every matmul weight rounded to e4m3, the precision below the stated
    one: outside the limits on several compared leaves. The bf16 case's
    compiled program AT the rounded weights: the control is
    straight-through, so that is its gradient."""
    from benchmarks import precision_control

    _, params, tokens = _setup()
    _, want_grads = _reference(once_a_run)
    _, grads = _in_bf16()(jax.jit(precision_control._eight_bit)(params),
                          tokens)
    eight = {k: compare.rel_l2(grads[k], want_grads[k]) for k in grads}
    over = [k for k, v in eight.items() if v > compare.GRAD_RTOL]
    assert len(over) >= 3, eight


def _layer_input(seed=0, batch=2, seq=SEQ):
    cfg, params, _ = _setup()
    x = jax.random.normal(jax.random.PRNGKey(seed), (batch, seq, cfg.d_model))
    return cfg, params["layers"][0]["attn"], x


def test_a_sequence_no_longer_than_topk_is_plain_causal_attention():
    """Every causal key is kept while t + 1 <= topk: the layer's output is
    `apply_attention(impl="reference")`'s with the same norm and rotation,
    and the kept set is the whole causal triangle."""
    cfg, attn, x = _layer_input(seq=24)
    sparse = cfg.sparse
    layer = jax.jit(functools.partial(L.apply_sparse_attention, cfg=sparse,
                                      compute_dtype=jnp.float32))
    got, (kl, kept) = layer(attn, x)

    def qk_fn(q, k):
        return tuple(L.rope(L.rms_norm(t, s, sparse.eps), sparse.rope_theta)
                     for t, s in ((q, attn["q_norm"]), (k, attn["k_norm"])))
    want = jax.jit(functools.partial(
        L.apply_attention, impl="reference", qk_fn=qk_fn,
        compute_dtype=jnp.float32))(attn, x)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert int(kept) == 2 * 24 * 25 // 2
    assert float(kl) > 0
    # one key more and the last row has to choose
    longer = layer(attn, jnp.concatenate([x, x[:, :1]], axis=1))[1][1]
    assert int(longer) == 2 * (24 * 25 // 2 + 24)


def _indexer_scores(attn, x, sparse):
    """The layer's indexer, written out, [B, S, S]."""
    a = x
    qi = L.rope(jnp.einsum("bsd,dje->bsje", a, attn["indexer"]["w_q"]),
                sparse.rope_theta)
    ki = jnp.einsum("bsd,de->bse", a, attn["indexer"]["w_k"])
    mu = ki.mean(-1, keepdims=True)
    ki = ((ki - mu) / jnp.sqrt(((ki - mu) ** 2).mean(-1, keepdims=True)
                               + sparse.eps) * attn["indexer"]["k_scale"]
          + attn["indexer"]["k_bias"])
    ki = L.rope(ki[:, :, None], sparse.rope_theta)[:, :, 0]
    w = jnp.einsum("bsd,dj->bsj", a, attn["indexer"]["w_w"]) \
        / np.sqrt(sparse.index_heads * sparse.index_dim)
    r = jnp.einsum("btje,bse->btjs", qi, ki)
    return jnp.sum(jax.nn.relu(r) * w[..., None], axis=2)


@functools.cache
def _kept_set():
    """``(attn, x) -> keep``: the layer compiled (once for both cases), the
    set it hands to `sa.sparse_attention` caught in the trace and returned."""
    sparse = _setup()[0].sparse
    plain = sa.sparse_attention

    def layer(attn, x):
        seen = []

        def catch(q, k, v, keep, **kw):
            seen.append(keep)
            return plain(q, k, v, keep, **kw)
        with mock.patch.object(sa, "sparse_attention", catch):
            L.apply_sparse_attention(attn, x, sparse,
                                     compute_dtype=jnp.float32)
        keep, = seen
        return keep
    return jax.jit(layer)


@pytest.mark.parametrize("ties", [False, True])
def test_the_kept_set_is_lax_top_ks(ties):
    """The set the layer attends over, caught on its way into the attention,
    against `jax.lax.top_k` of the indexer's scores written out: row t's
    first min(t + 1, 24) indices. With `ties`, the indexer's head weights
    are zero, so every score is 0.0: the lowest indices are kept."""
    cfg, attn, x = _layer_input(seed=1)
    if ties:
        attn = dict(attn, indexer=dict(
            attn["indexer"], w_w=jnp.zeros_like(attn["indexer"]["w_w"])))
    keep = _kept_set()(attn, x)
    scores = jax.jit(functools.partial(_indexer_scores, sparse=cfg.sparse))(
        attn, x)
    below = np.tril(np.ones((SEQ, SEQ), bool))
    scores = jnp.where(below, scores, -jnp.inf)
    _, chosen = jax.lax.top_k(scores, 24)
    want = np.zeros((2, SEQ, SEQ), np.int8)
    for b in range(2):
        for t in range(SEQ):
            want[b, t, np.asarray(chosen[b, t, :min(t + 1, 24)])] = 1
    if ties:
        assert not np.asarray(jnp.where(below, scores, 0.0)).any()
        assert (want[0, -1, :24] == 1).all()
    np.testing.assert_array_equal(keep, want)
    np.testing.assert_array_equal(keep.sum(-1)[0],
                                  np.minimum(np.arange(SEQ) + 1, 24))


def test_three_different_position_streams_against_the_reference():
    """`positions` [3, B, S] with three DIFFERENT streams (a grid's, as a
    vision tower would give): the loss and every gradient against the
    reference's pair-by-pair sectioned rotation; and the rotation alone,
    pair i by its own stream."""
    cfg, params, tokens = _setup(seed=7)
    key = jax.random.PRNGKey(11)
    positions = jnp.stack([
        jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ)),
        jax.random.randint(key, (2, SEQ), 0, 9),
        jnp.broadcast_to(jnp.arange(SEQ) // 5, (2, SEQ))])
    with jax.default_matmul_precision("highest"):
        mixed, _, _ = checks.against_reference(
            lambda p: keye_vl2.loss_fn(
                p, {"tokens": tokens, "positions": positions}, cfg)[0],
            lambda p: reference.loss(p, tokens, FILED, positions), params,
            loss_rtol=2e-6, grad_tol=5e-5)
        # the streams matter: text positions give another loss
        text = jax.jit(lambda p: keye_vl2.loss_fn(
            p, {"tokens": tokens}, cfg)[0])(params)
    assert abs(float(text) - float(mixed)) > 1e-4
    # by hand: 8 pairs in sections 2 + 3 + 3 at theta 10,000
    x = jax.random.normal(key, (2, SEQ, 3, 16))
    got = L.rope(x, 10000.0, positions=positions, sections=(2, 3, 3))
    for i in range(8):
        stream = 0 if i < 2 else 1 if i < 5 else 2
        angle = positions[stream][..., None] * 10000.0 ** (-i / 8)
        a, b = x[..., i], x[..., i + 8]
        np.testing.assert_allclose(
            got[..., i], a * np.cos(angle) - b * np.sin(angle), atol=1e-5)
        np.testing.assert_allclose(
            got[..., i + 8], b * np.cos(angle) + a * np.sin(angle), atol=1e-5)
    # equal streams are the one-stream rotation, which is the default's
    same = jnp.broadcast_to(jnp.arange(SEQ), (3, 2, SEQ))
    np.testing.assert_allclose(
        L.rope(x, 10000.0, positions=same, sections=(2, 3, 3)),
        L.rope(x, 10000.0), atol=1e-6)
    np.testing.assert_allclose(L.rope(x, 10000.0, positions=same[0]),
                               L.rope(x, 10000.0), atol=1e-6)
    with pytest.raises(ValueError, match="sections"):
        L.rope(x, 10000.0, positions=same, sections=(2, 3, 4))


def test_the_two_gradient_flows_are_disjoint():
    """The trunk's leaves get nothing from the indexer's loss, the indexer's
    nothing from the cross-entropy: each term's gradient is exactly zero on
    the other's leaves and not on its own."""
    cfg, params, tokens = _setup()

    def term(name):
        return jax.jit(jax.grad(lambda p: keye_vl2.loss_fn(
            p, {"tokens": tokens}, cfg)[1][name]))(params)
    from_lm, from_indexer = term("lm_loss"), term("indexer_loss")
    for path, g in jax.tree_util.tree_leaves_with_path(from_lm):
        inside = "indexer" in jax.tree_util.keystr(path)
        assert bool(np.asarray(g).any()) != inside, path
    for path, g in jax.tree_util.tree_leaves_with_path(from_indexer):
        inside = "indexer" in jax.tree_util.keystr(path)
        assert bool(np.asarray(g).any()) == inside, path


def test_the_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's share test: the routed layer with ALL 16
    experts equals the sum of the shares' results — 8 chips, two experts
    each, every share with the same softmax router over all 16; there is no
    shared expert to count once."""
    whole_cfg = L.MoEConfig(n_experts=16, top_k=3, score="softmax",
                            gate="silu")
    whole = L.init_moe(jax.random.PRNGKey(5), 64, 32, whole_cfg, gated=True)
    assert set(whole) == {"wg", "w_gate", "w_up", "w_down"}
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, 64))
    uncut, stats = L.apply_moe(whole, x, whole_cfg,
                               compute_dtype=jnp.float32)
    total, held = 0.0, 0
    for rank in range(8):
        cfg = dataclasses.replace(whole_cfg, held=2, first=2 * rank)
        part = dict(whole, **{k: whole[k][2 * rank:2 * rank + 2]
                              for k in ("w_gate", "w_up", "w_down")})
        out, part_stats = L.apply_moe(part, x, cfg, compute_dtype=jnp.float32)
        np.testing.assert_array_equal(part_stats["counts"], stats["counts"])
        total = total + out
        held += int(stats["counts"][2 * rank:2 * rank + 2].sum())
    assert held == 2 * 40 * 3
    np.testing.assert_allclose(total, uncut, atol=2e-6)
    # and the model's preset is such a share
    cfg = keye_vl2.keye_vl2_tiny()
    assert (cfg.moe.stacked, cfg.moe.n_experts, cfg.moe.first) == (4, 16, 0)


@functools.cache
def _on_one_device():
    """Loss and every gradient on the `reference` path, one device, no
    remat: the kernel path's yardstick and the dp mesh's."""
    cfg, params, tokens = _setup()
    cfg = dataclasses.replace(cfg, attention="reference")
    return jax.jit(jax.value_and_grad(lambda p: keye_vl2.loss_fn(
        p, {"tokens": tokens}, cfg)[0]))(params)


def test_the_kernel_path_equals_the_reference_path(monkeypatch):
    """`attention` "flash" through the Pallas interpreter — the indexer's
    score kernels, the selection kernel, the flash kernels with the kept
    set, the KL's two kernels — against the plain forms: the loss and every
    gradient; under remat neither a layer's flash forward kernel nor its
    KL's is run again, and the heads' mean attention is no call of its own.
    (The forward scores at the highest precision here: at the module's three
    passes a pair at a row's threshold may change sides.)"""
    monkeypatch.setattr(sa, "SCORE_PASSES", 6)
    cfg, params, tokens = _setup()

    c = dataclasses.replace(cfg, attention="flash", remat=True)
    want = _on_one_device()
    got = jax.jit(jax.value_and_grad(lambda p: keye_vl2.loss_fn(
        p, {"tokens": tokens}, c, None, True)[0]))(params)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=5e-6)
    text = str(jax.make_jaxpr(jax.grad(lambda p: keye_vl2.loss_fn(
        p, {"tokens": tokens}, c, None, True)[0]))(params))
    layers = cfg.n_layer
    for name, calls in (("flash_sparse_fwd", 1), ("flash_sparse_dq", 1),
                        ("flash_sparse_dkv", 1), ("sparse_select", 1),
                        ("indexer_scores_fwd", 2), ("indexer_scores_dq", 1),
                        ("indexer_scores_dk", 1), ("indexer_kl_fwd", 1),
                        ("indexer_kl_bwd", 1), ("sparse_mean_probs", 0)):
        assert text.count(f"name={name}") == calls * layers, name


def test_the_presets_and_what_the_model_refuses():
    cut, whole = keye_vl2.keye_vl2_30b_a3b_4l(), keye_vl2.keye_vl2_30b_a3b()
    assert cut.n_params == 465_718_784
    assert whole.n_params == 48 * (18_874_368 + 4_352 + 262_144 + 2_261_120
                                   + 128 * 4_718_592) \
        + 2 * 151_936 * 2048 + 2048
    shapes = jax.eval_shape(lambda: keye_vl2.init(jax.random.PRNGKey(0), cut))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(shapes)) == cut.n_params
    layer = shapes["layers"][0]
    assert layer["attn"]["wq"].shape == (2048, 32, 128)
    assert layer["attn"]["wk"].shape == (2048, 4, 128)
    assert layer["attn"]["indexer"]["w_q"].shape == (2048, 16, 64)
    assert layer["attn"]["indexer"]["w_k"].shape == (2048, 64)
    assert layer["attn"]["indexer"]["w_w"].shape == (2048, 16)
    assert layer["ff"]["wg"].shape == (2048, 128)
    assert layer["ff"]["w_gate"].shape == (16, 2048, 768)
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        layer["attn"]["indexer"])) == 2_261_120
    specs = keye_vl2.partition_specs(cut)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, shapes)) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda s: 0, specs,
            is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)))
    devices = np.array(jax.devices()[:2]).reshape(1, 2)
    mesh = jax.sharding.Mesh(devices, ("dp", "tp"))
    tiny = keye_vl2.keye_vl2_tiny()
    with pytest.raises(ValueError, match="tp > 1 is not supported"):
        jax.eval_shape(
            lambda key: keye_vl2.forward(keye_vl2.init(key, tiny),
                                         jnp.zeros((2, 40), jnp.int32), tiny,
                                         mesh), jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="'flash' or 'reference'"):
        L.apply_sparse_attention({}, jnp.zeros((1, 8, 64)), tiny.sparse,
                                 impl="ring")


def test_on_a_dp_mesh_each_device_selects_its_own_rows():
    """Two sequences over dp = 2: the sparse branch is per-device code, and
    the loss and gradients are the unsharded ones."""
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh

    cfg, params, tokens = _setup()
    mesh = create_mesh(MeshConfig(dp=2), devices=jax.devices()[:2])
    want = _on_one_device()
    got = jax.jit(jax.value_and_grad(lambda p: keye_vl2.loss_fn(
        p, {"tokens": tokens}, cfg, mesh)[0]))(params)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=5e-6)
