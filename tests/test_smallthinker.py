"""SmallThinker (models/smallthinker.py) on the CPU, at the tiny preset:
against the plain float32 reference the benchmark holds it to
(chipbench/references/smallthinker.py: explicit window masks, rotation by
layout, the router on the layer's input, every held expert on every token),
loss, every gradient, and the cell's arithmetic under the benchmark's own
limits. Where the router reads and which layers rotate or have a window is
test_smallthinker_layout.py's, the program's paths test_smallthinker_mesh.py's,
the chip's share of the experts test_smallthinker_share.py's."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from chipbench import compare
from chipbench.accounting import smallthinker as accounting
from chipbench.references import smallthinker as reference
from ray_tpu.models import smallthinker
from tests import test_model_checks as checks

# two periods of the layout, as the preset has them, and one
DEEP = dataclasses.replace(smallthinker.smallthinker_tiny(),
                           attention="reference")
TINY = dataclasses.replace(DEEP, window_layout=(0, 1, 1, 1),
                           rope_layout=(0, 1, 1, 1))
FILED = checks.filed("smallthinker-tiny")
SEQ = 64        # longer than the tiny window of 24: the window bites


def _params(cfg, seed=0, scale=1.0):
    """Fresh parameters with every norm's scale moved off its initial 1, so
    that a norm applied in the wrong place (ahead of the router) shows;
    `scale` times the blocks' matrices, where a test wants the layers to
    weigh more in the loss than 0.02-sized weights let them."""
    norms = ("ln1", "ln2", "ln_f")
    params = checks.moved_off(
        smallthinker.init(jax.random.PRNGKey(seed), cfg), seed + 1,
        lambda key, _: 0.3 * (key in norms))
    return dict(params, blocks=jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in norms else a * scale,
        params["blocks"]))


def _tokens(cfg, seq=SEQ, **kw):
    return checks.token_ids(cfg.vocab_size, seq=seq, **kw)


def test_presets_count_the_published_parameters():
    cut = smallthinker.smallthinker_21b_a3b_4l()
    assert cut.n_params == 656_693_760
    assert (cut.n_layer, cut.period, cut.moe.stacked, cut.vocab_size) == (
        4, 4, 16, 38016)
    assert cut.kinds == ((0, 0), (1, 1), (1, 1), (1, 1))
    assert (DEEP.n_layer, DEEP.period, TINY.n_layer) == (8, 4, 4)
    whole = smallthinker.smallthinker_21b_a3b()
    assert (whole.n_layer, whole.period, whole.moe.stacked) == (52, 4, 64)
    assert sum(whole.window_layout) == 39 == sum(whole.rope_layout)
    assert whole.n_params == 21_506_562_560
    params = jax.eval_shape(lambda: smallthinker.init(jax.random.PRNGKey(0),
                                                      DEEP))
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) == \
        DEEP.n_params
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(smallthinker.partition_specs(DEEP))


def test_the_embedding_table_alone_is_drawn_at_embed_std():
    """The cell's preset draws the table at 32 (why: its docstring); the
    head and every block matrix stay at 0.02 whatever the table's draw."""
    assert smallthinker.smallthinker_21b_a3b_4l().embed_std == 32.0
    assert smallthinker.smallthinker_21b_a3b().embed_std == 0.02 == \
        TINY.embed_std
    plain = smallthinker.init(jax.random.PRNGKey(3), TINY)
    wide = smallthinker.init(jax.random.PRNGKey(3),
                             dataclasses.replace(TINY, embed_std=32.0))
    assert float(jnp.std(wide["wte"])) == pytest.approx(32.0, rel=0.05)
    assert jnp.allclose(wide["wte"], plain["wte"] * 1600.0, rtol=1e-5)
    rest = [dict(tree, wte=0.0) for tree in (plain, wide)]
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(
        *map(jax.tree_util.tree_leaves, rest)))


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_gradient_match_the_reference_in_float32(remat):
    cfg = dataclasses.replace(TINY, dtype=jnp.float32, remat=remat)
    params, tokens = _params(cfg), _tokens(cfg)
    (_, metrics), _, _ = checks.against_reference(
        lambda p: smallthinker.loss_fn(p, {"tokens": tokens}, cfg),
        lambda p: reference.loss(p, tokens, FILED), params,
        loss_rtol=2e-6, grad_tol=2e-5, has_aux=True)
    assert int(metrics["moe_assignments"]) == 2 * SEQ * 3 * 4
    assert 0 < int(metrics["moe_held"]) < int(metrics["moe_assignments"])


def test_bf16_with_remat_is_within_the_benchmarks_bounds():
    """The cell's arithmetic (bf16 products, every one a single pass, remat)
    on the compared leaves, under `chipbench/compare.py`'s own limits."""
    cfg = dataclasses.replace(DEEP, remat=True)
    params, tokens = _params(cfg), _tokens(cfg)
    loss, grads = checks.picked(
        lambda p, t: smallthinker.loss_fn(p, {"tokens": t}, cfg)[0],
        accounting, params, tokens)
    want, want_grads = checks.picked(
        lambda p, t: reference.loss(p, t, FILED), accounting, params, tokens)
    assert abs(float(loss) - float(want)) / float(want) <= compare.LOSS_RTOL
    assert set(grads) == {"head", "wq_global", "wv_global", "wq_window",
                          "wv_window", "wg", "w_gate", "w_down"}
    checks.assert_close(grads, want_grads, compare.GRAD_RTOL)
