"""The Nemotron-H tower (models/nemotron_h.py) on the CPU, at the tiny
preset: against the plain float32 reference the benchmark holds it to
(chipbench/references/nemotron_h.py: the recurrence itself, full-softmax
attention, every held expert on every token), and the flash kernels with
grouped KV heads in interpret mode. The chip's share of the experts is
test_nemotron_h_share.py's, the virtual `dp` and `ep` meshes
test_nemotron_h_mesh.py's: three files, each inside the conftest's
per-file budget when the whole suite loads the machine."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare
from chipbench.accounting import nemotron_h as accounting
from chipbench.references import nemotron_h as reference
from ray_tpu.models import nemotron_h
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel.ring_attention import reference_attention
from tests import test_model_checks as checks

TINY = dataclasses.replace(nemotron_h.nemotron_h_tiny(),
                           attention="reference")
FILED = checks.filed("nemotronh-tiny")


def _params(cfg, seed=0):
    """Fresh parameters with every norm's scale and the selection bias
    moved off their initial 1 and 0, so that one applied in the wrong place
    shows."""
    return checks.moved_off(
        nemotron_h.init(jax.random.PRNGKey(seed), cfg), seed + 1,
        lambda key, _: 0.1 * (key in ("ln", "ln_f", "norm", "bias")))


def _tokens(cfg, seq=40, **kw):
    return checks.token_ids(cfg.vocab_size, seq=seq, **kw)


def test_presets_count_the_published_parameters():
    cut = nemotron_h.nemotron_twotower_30b_a3b_9l()
    assert cut.n_params == 666_963_456
    assert (cut.pattern, cut.moe.stacked, cut.vocab_size) == (
        "MEMEM*EME", 8, 16384)
    whole = nemotron_h.nemotron_twotower_30b_a3b()
    assert whole.n_params == 31_577_940_288          # the row's "30B"
    assert [whole.pattern.count(k) for k in "ME*"] == [23, 23, 6]
    assert whole.pattern.startswith(cut.pattern)
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda: nemotron_h.init(jax.random.PRNGKey(0), TINY)))
    assert sum(math.prod(a.shape) for a in leaves) == TINY.n_params
    assert accounting.ran_sizes(TINY) == accounting.filed_sizes(FILED)


def test_loss_and_every_gradient_match_the_reference_in_float32():
    """Same arithmetic, two programs — the chunked scan against the
    recurrence, sorted grouped products against every expert on every
    token: float32 rounding alone separates them (measured 1e-6), so 1e-5.
    The selection bias gets no gradient from either."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    params, tokens = _params(cfg), _tokens(cfg)
    _, grads, want_grads = checks.against_reference(
        lambda p: nemotron_h.loss_fn(p, {"tokens": tokens}, cfg)[0],
        lambda p: reference.loss(p, tokens, FILED), params,
        loss_rtol=2e-6, grad_tol=1e-5, skip=("['bias']",))
    for stack in (grads, want_grads):
        assert not np.asarray(stack["moe"]["bias"]).any()


def test_bf16_with_remat_is_within_the_benchmarks_bounds():
    """As the cell runs it: bf16 operands, every layer under the remat
    policy, against the float32 reference, on the leaves `accounting.pick`
    names, inside `compare`'s bounds (loss 3e-4, gradients 8e-2)."""
    cfg = dataclasses.replace(TINY, remat=True)
    params, tokens = _params(cfg), _tokens(cfg)
    check = compare.compare(
        lambda p, t: nemotron_h.loss_fn(p, {"tokens": t}, cfg)[0],
        lambda p, t: reference.loss(p, t, FILED), params, tokens,
        jax.devices()[0], pick=accounting.pick, put=accounting.put)
    assert set(check["errors"]) == {"loss"} | {"grad_" + k for k in (
        "head", "wq", "wv", "w_in", "A_log", "dt_bias", "w_out", "wg", "w1",
        "w2", "shared_w1")}
    assert check["within"], check["errors"]


@pytest.mark.parametrize("heads, kv_heads", [(4, 4), (4, 1), (16, 1),
                                             (8, 2)])
def test_flash_with_grouped_kv_heads_against_plain_attention(heads,
                                                             kv_heads):
    """Interpret mode, float32: o and all three gradients, K and V handed
    to the kernels as [B·KV, S, D] — query head i reads KV head i // group
    through the index maps, dk and dv are summed over the group. S 200 is
    padded to the tiles; groups of 1, 4, 16 and two KV heads of 4."""
    ks = jax.random.split(jax.random.PRNGKey(heads), 4)
    batch, seq, dim = 2, 200, 32
    q = jax.random.normal(ks[0], (batch, seq, heads, dim))
    k, v = (jax.random.normal(key, (batch, seq, kv_heads, dim))
            for key in ks[1:3])
    weight = jax.random.normal(ks[3], q.shape)
    group = heads // kv_heads

    def plain(q, k, v):
        return jnp.sum(weight * reference_attention(
            q, jnp.repeat(k, group, 2), jnp.repeat(v, group, 2),
            causal=True))

    def flash(q, k, v):
        return jnp.sum(weight * flash_attention(q, k, v, causal=True,
                                                interpret=True))

    want, want_grads = jax.jit(jax.value_and_grad(plain, (0, 1, 2)))(q, k, v)
    got, grads = jax.jit(jax.value_and_grad(flash, (0, 1, 2)))(q, k, v)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(grads, want_grads):
        assert a.shape == b.shape
        assert compare.rel_l2(a, b) <= 1e-5


# ------------------------------------------------ the scan's Pallas kernels
# the tiny preset with mixers whose shapes the kernels' tiles divide (eight
# heads of 64 in one group, state 128, chunks of 128)
KERNEL_TINY = dataclasses.replace(TINY, mamba_heads=8, mamba_head_dim=64,
                                  n_groups=1, d_state=128, chunk=128)


def _ssd_calls(jaxpr) -> list:
    """Names of the scan's `pallas_call`s in a jaxpr, nested ones too."""
    from tests.test_zz_tp_overlap import _walk
    return [eqn.params["name"] for eqn, *_ in _walk(jaxpr)
            if eqn.primitive.name == "pallas_call"
            and eqn.params["name"].startswith("ssd_")]


def _as_on_a_tpu(monkeypatch):
    from ray_tpu.ops import ssd as ssd_ops
    use = ssd_ops._use_kernel
    monkeypatch.setattr(ssd_ops, "_use_kernel",
                        lambda platform, *rest: use("tpu", *rest))


def test_the_scan_engages_its_kernels_under_remat(monkeypatch):
    """The counter that says the kernels engage: in the gradient of
    `loss_fn` under the remat policy every mixer runs the forward kernel
    twice (the step's forward, the backward's recomputation) and the
    backward kernel once; on this backend, and at shapes no tile divides,
    none."""
    def calls(cfg):
        params = jax.eval_shape(
            lambda: nemotron_h.init(jax.random.PRNGKey(0), cfg))
        tokens = jax.ShapeDtypeStruct((1, 131), jnp.int32)
        return _ssd_calls(jax.make_jaxpr(jax.grad(
            lambda p, t: nemotron_h.loss_fn(p, {"tokens": t}, cfg)[0]))(
                params, tokens).jaxpr)

    cfg = dataclasses.replace(KERNEL_TINY, remat=True)
    assert calls(cfg) == []                     # the CPU: the plain form
    _as_on_a_tpu(monkeypatch)
    mixers = cfg.pattern.count("M")
    found = calls(cfg)
    assert len(found) == 3 * mixers
    assert sorted(found) == ["ssd_bwd"] * mixers + ["ssd_fwd"] * 2 * mixers
    assert len(calls(dataclasses.replace(cfg, remat=False))) == 2 * mixers
    assert calls(dataclasses.replace(TINY, remat=True)) == []


@pytest.mark.parametrize("checkpoint", ["policy", "keeps_nothing"])
def test_remat_rebuilds_the_scan_and_not_the_in_projection(monkeypatch,
                                                           checkpoint):
    """What `layers.remat` keeps of a mixer: the in-projection's three-pass
    result, not the scan's y. So the gradient under remat holds the forward
    kernel twice a mixer (the benchmark's `kernels.ssd_roofline` counts
    three executions a layer) and the in-projection's three passes ONCE; a
    checkpoint that keeps nothing holds them twice."""
    from ray_tpu.models import layers
    from tests.test_zz_remat_policy import _products_with
    if checkpoint == "keeps_nothing":
        monkeypatch.setattr(layers, "remat", jax.checkpoint)
    _as_on_a_tpu(monkeypatch)
    cfg = dataclasses.replace(KERNEL_TINY, remat=True)
    params = jax.eval_shape(
        lambda: nemotron_h.init(jax.random.PRNGKey(0), cfg))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, t: nemotron_h.loss_fn(p, {"tokens": t}, cfg)[0]))(
            params, jax.ShapeDtypeStruct((1, 131), jnp.int32)).jaxpr
    passes, _ = _products_with(jaxpr, (cfg.d_model, cfg.mamba.in_proj))
    mixers = cfg.pattern.count("M")
    assert _ssd_calls(jaxpr).count("ssd_fwd") == 2 * mixers
    assert passes == (3 if checkpoint == "policy" else 6) * mixers


def test_the_model_on_the_kernels_is_the_model_on_the_plain_form(
        monkeypatch):
    """Float32, the kernels in the interpreter, under the remat policy:
    loss and every gradient against the same model on the plain form — the
    reshapes at the call, the padding of 130 tokens to two chunks and the
    running sums' cotangents agree (A_log through a chunk-long running sum
    of differences: 5e-4, as in test_ssd_kernels.py)."""
    import functools

    from ray_tpu.ops import ssd as ssd_ops
    cfg = dataclasses.replace(KERNEL_TINY, dtype=jnp.float32, remat=True)
    params, tokens = _params(cfg), _tokens(cfg, batch=1, seq=130)
    def run():
        return checks.loss_and_grads(
            lambda p: nemotron_h.loss_fn(p, {"tokens": tokens}, cfg)[0],
            params)
    want, want_grads = run()
    monkeypatch.setattr(ssd_ops, "ssd",
                        functools.partial(ssd_ops.ssd, interpret=True))
    got, grads = run()
    assert abs(float(got) - float(want)) <= 2e-6 * abs(float(want))
    # the selection bias: no gradient, 0 / 0
    checks.assert_close(grads, want_grads, 5e-4, skip=("['bias']",))
    checks.assert_close(grads, want_grads, 2e-5,
                        skip=("['bias']", "['A_log']"))
