"""What is SmallThinker's own (models/smallthinker.py), on the CPU at the
tiny preset: where the router reads (the layer's input as it is), which
layers rotate and which have a window, the model on the flash kernels in
the Pallas interpreter, and the meshes it runs on and refuses. Agreement
with the plain reference is test_smallthinker.py's, the chip's share of the
experts test_smallthinker_share.py's: three files, each inside the
conftest's per-file budget when the whole suite loads the machine."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import smallthinker as reference
from ray_tpu.models import layers as L
from ray_tpu.models import smallthinker
from tests.test_smallthinker import FILED, SEQ, TINY, _params, _tokens


def _loss(params, tokens, cfg):
    return float(jax.jit(lambda p: smallthinker.loss_fn(
        p, {"tokens": tokens}, cfg)[0])(params))


def _filed_loss(params, tokens):
    # a fresh function each time: `reference.layer` as it is patched now
    return float(jax.jit(lambda p: reference.loss(p, tokens, FILED))(params))


@functools.cache
def _weighty():
    """Parameters whose layers weigh in the loss, tokens, and the filed
    reference's loss on them: five cases' yardstick, once a process (the
    layouts are no part of the parameters)."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    params, tokens = _params(cfg, scale=4.0), _tokens(cfg)
    return params, tokens, _filed_loss(params, tokens)


def test_the_router_reads_the_layers_input_as_it_is(monkeypatch):
    """`moe_route` is handed the stream x itself: the embedding in layer 0,
    each layer's output in the next — not its RMSNorm, not the stream
    behind attention."""
    seen = []
    route = L.moe_route
    monkeypatch.setattr(L, "moe_route", lambda params, x, cfg: (
        seen.append(x), route(params, x, cfg))[1])
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    params, tokens = _params(cfg), _tokens(cfg, batch=1)
    outs = []
    apply = smallthinker._block_apply
    monkeypatch.setattr(smallthinker, "_block_apply", lambda x, block, **kw: (
        outs.append(apply(x, block, **kw)), outs[-1])[1])
    with jax.disable_jit():
        smallthinker.forward(params, tokens[:, :-1], cfg)
    assert len(seen) == cfg.n_layer
    np.testing.assert_array_equal(seen[0], params["wte"][tokens[:, :-1]])
    for layer in range(1, cfg.n_layer):
        np.testing.assert_array_equal(seen[layer], outs[layer - 1][0])


@pytest.mark.parametrize("leaf", ["ln1", "attn"])
def test_a_layers_routing_ignores_its_own_norm_and_attention(leaf):
    """Behaviour, not plumbing: scale layer 0's input norm, or replace its
    attention weights, and layer 0's assignments by expert stay to the
    token what they were (a router fed the normalised or the post-attention
    stream would move them) while layer 1's, which reads what layer 0 wrote,
    do move."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    params, tokens = _params(cfg), _tokens(cfg)[:, :-1]
    blocks = params["blocks"]
    fresh = jax.random.PRNGKey(11)
    if leaf == "ln1":
        changed = dict(blocks, ln1=blocks["ln1"].at[0].mul(
            1 + jax.random.uniform(fresh, (cfg.d_model,))))
    else:
        other = L.init_attention(fresh, cfg.d_model, cfg.n_head,
                                 n_kv_head=cfg.n_kv_head,
                                 head_dim=cfg.head_dim)
        changed = dict(blocks, attn={k: v.at[0].set(3 * other[k])
                                     for k, v in blocks["attn"].items()})
    _, before = smallthinker.forward(params, tokens, cfg)
    _, after = smallthinker.forward(dict(params, blocks=changed), tokens, cfg)
    np.testing.assert_array_equal(before[0], after[0])
    assert (np.asarray(before[1]) != np.asarray(after[1])).any()


def test_the_reference_with_the_router_elsewhere_is_another_model():
    """The same parameters through the reference with its router moved
    behind the norm, or behind attention: a loss the model does not have."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    params, tokens, filed = _weighty()
    loss = _loss(params, tokens, cfg)
    layer = reference.layer

    def moved(where):
        def wrong(x, p, *, rotated, windowed, config):
            eps = config["rms_norm_eps"]
            a = reference.rms_norm(x, p["ln1"], eps)
            h = x + reference.attention(
                a, p["attn"], rotated=rotated, theta=config["rope_theta"],
                window=config["sliding_window_size"] if windowed else None)
            logits = {"normed": a, "post_attention": h}[where] @ \
                p["moe"]["wg"]
            return h + reference.routed(
                reference.rms_norm(h, p["ln2"], eps), logits, p["moe"],
                top_k=config["moe_num_active_primary_experts"],
                first=config["deployment"]["first_expert"])
        return wrong
    assert loss == pytest.approx(filed, rel=2e-6)
    for where in ("normed", "post_attention"):
        reference.layer = moved(where)
        try:
            other = _filed_loss(params, tokens)
        finally:
            reference.layer = layer
        assert abs(other - loss) / loss > 2e-5, where


def test_global_layers_do_not_rotate_and_window_layers_do(monkeypatch):
    calls = []
    attend = L.apply_attention

    def recorded(params, x, **kw):
        calls.append((kw["window"], kw["qk_fn"] is not None,
                      kw.get("three_pass", False), kw["causal"]))
        return attend(params, x, **kw)
    monkeypatch.setattr(L, "apply_attention", recorded)
    rotations = []
    rope = L.rope
    monkeypatch.setattr(L, "rope", lambda x, theta: (
        rotations.append(theta), rope(x, theta))[1])
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    smallthinker.forward(_params(cfg), _tokens(cfg)[:, :-1], cfg)
    # one trace of the scan's body: one period of four layers, every
    # projection ONE pass (the stated precision)
    assert calls == [(None, False, False, True)] + \
        [(24, True, False, True)] * 3
    assert rotations == [1.5e6] * 6            # q and k of the three


@pytest.mark.parametrize("wrong", ["global_rotates", "window_does_not",
                                   "window_is_global", "global_is_window"])
def test_a_layout_applied_wrongly_is_another_model(wrong):
    """The program run with one layout bent against the reference run with
    the filed one: the rotation and the window are not decorations."""
    rope, window = {
        "global_rotates": ((1, 1, 1, 1), (0, 1, 1, 1)),
        "window_does_not": ((0, 0, 1, 1), (0, 1, 1, 1)),
        "window_is_global": ((0, 1, 1, 1), (0, 0, 1, 1)),
        "global_is_window": ((0, 1, 1, 1), (1, 1, 1, 1)),
    }[wrong]
    cfg = dataclasses.replace(TINY, dtype=jnp.float32, rope_layout=rope,
                              window_layout=window)
    params, tokens, want = _weighty()
    loss = _loss(params, tokens, cfg)
    assert abs(loss - want) / want > 2e-5


def test_a_window_as_long_as_the_sequence_is_global_attention():
    cfg = dataclasses.replace(TINY, dtype=jnp.float32, window=SEQ)
    params, tokens = _params(cfg), _tokens(cfg)
    every = dataclasses.replace(cfg, window_layout=(0,) * 4)
    assert _loss(params, tokens, cfg) == pytest.approx(
        _loss(params, tokens, every), rel=1e-6)
