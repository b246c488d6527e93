"""SmallThinker (models/smallthinker.py) as a program, on the CPU at the
tiny preset: the scan over whole periods of the layout, the model on the
flash kernels in the Pallas interpreter, the meshes it runs on and refuses,
and the normal training path. (Four files, each inside the conftest's
per-file budget when the whole suite loads the machine: agreement with the
reference is test_smallthinker.py's, the layouts test_smallthinker_layout.py's,
the share test_smallthinker_share.py's.)"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from chipbench.references import smallthinker as reference
from ray_tpu.models import smallthinker
from ray_tpu.ops import flash_attention as fa
from ray_tpu.parallel import sharding as sh
from ray_tpu.parallel.mesh import MeshConfig, create_mesh
from ray_tpu.parallel.train_step import (
    default_optimizer,
    make_train_state,
    make_train_step,
)
from tests import test_model_checks as checks
from tests.test_smallthinker import FILED, TINY, _params, _tokens

@pytest.mark.parametrize("layouts, period", [
    (((0, 1, 1, 1) * 2, (0, 1, 1, 1) * 2), 4),
    (((0, 1), (1, 1)), 2),                   # kinds differ by rotation alone
    (((0, 1, 1, 0, 1), (0, 1, 1, 0, 1)), 5),    # no period: one pass
])
def test_the_scan_runs_over_whole_periods_of_the_layout(layouts, period):
    cfg = dataclasses.replace(TINY, window_layout=layouts[0],
                              rope_layout=layouts[1], dtype=jnp.float32)
    assert cfg.period == period
    params, tokens = _params(cfg), _tokens(cfg, batch=1)
    filed = dict(FILED, sliding_window_layout=list(layouts[0]),
                 rope_layout=list(layouts[1]))
    loss = jax.jit(lambda p: smallthinker.loss_fn(
        p, {"tokens": tokens}, cfg)[0])(params)
    assert float(loss) == pytest.approx(float(jax.jit(
        lambda p: reference.loss(p, tokens, filed))(params)), rel=2e-6)
    with pytest.raises(ValueError, match="one entry a layer each"):
        dataclasses.replace(TINY, rope_layout=(0, 1))


# ------------------------------------------------------------- the kernels

KERNEL_TINY = dataclasses.replace(
    TINY, d_model=128, n_head=2, n_kv_head=1, head_dim=64, d_expert=128,
    window=96, window_layout=(0, 1), rope_layout=(0, 1), dtype=jnp.float32,
    attention="flash", remat=True)


def test_the_model_on_the_kernels_is_the_model_on_the_plain_form(monkeypatch):
    """The step a TPU runs, here in the Pallas interpreter: flash with and
    without a window, each layer under `L.remat` (three calls a layer: the
    forward is not run twice), against reference attention; the windowed
    calls by their names, the global layer's without one."""
    original = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention", lambda *a, **kw: original(
        *a, **dict(kw, interpret=True, block_q=128, block_k=128)))
    params, tokens = _params(KERNEL_TINY), _tokens(KERNEL_TINY, seq=256)

    def grad_of(cfg):
        return jax.value_and_grad(
            lambda p: smallthinker.loss_fn(p, {"tokens": tokens}, cfg)[0])
    loss, grads = jax.jit(grad_of(KERNEL_TINY))(params)
    want, want_grads = jax.jit(grad_of(dataclasses.replace(
        KERNEL_TINY, attention="reference")))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    checks.assert_close(grads, want_grads, 5e-4)
    from tests.test_flash_window import _calls
    names = [name for name, _ in _calls(
        jax.make_jaxpr(grad_of(KERNEL_TINY))(params).jaxpr, [])]
    assert len(names) == 6
    assert sorted(n for n in names if n and "window" in n) == [
        "flash_window_dkv", "flash_window_dq", "flash_window_fwd"]


# ------------------------------------------------------------------ meshes

@pytest.mark.parametrize("axes", [{"dp": 1, "ep": 2}], ids=["ep2"])
def test_model_on_a_mesh_agrees_with_one_device(axes):
    """The HELD experts over `ep` (a deployment's `ep` within the chip's
    share: each device its part of the held ones); batch over `dp` is
    `test_trains_through_the_normal_path`'s."""
    import math
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    params, tokens = _params(cfg), _tokens(cfg, batch=4, seq=40)
    want, want_grads = checks.loss_and_grads(
        lambda p: smallthinker.loss_fn(p, {"tokens": tokens}, cfg)[0], params)
    n = math.prod(axes.values())
    mesh = create_mesh(MeshConfig(**axes), devices=jax.devices()[:n])
    sharded = sh.tree_shard(params, mesh, smallthinker.partition_specs(cfg))
    with jax.set_mesh(mesh):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: smallthinker.loss_fn(p, {"tokens": tokens}, cfg,
                                           mesh)[0]))(sharded)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    checks.assert_close(grads, want_grads, 1e-5)


def test_a_mesh_that_splits_heads_or_sequence_is_refused():
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    params, tokens = _params(cfg), _tokens(cfg, batch=2, seq=40)
    mesh = create_mesh(MeshConfig(dp=1, tp=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="tp > 1 is not supported"):
        smallthinker.forward(params, tokens[:, :-1], cfg, mesh)
    mesh = create_mesh(MeshConfig(dp=1, sp=2), devices=jax.devices()[:2])
    with jax.set_mesh(mesh), pytest.raises(
            ValueError, match="ring attention has no window"):
        smallthinker.forward(params, tokens[:, :-1],
                             dataclasses.replace(cfg, attention="auto"), mesh)
def test_trains_through_the_normal_path():
    """`make_train_state` / `make_train_step` on a dp=2 mesh, as
    `JaxTrainer` workers call them: the loss falls on a fixed batch and the
    share's counters come back with it."""
    mesh = create_mesh(MeshConfig(dp=2), devices=jax.devices()[:2])
    opt = default_optimizer(1e-2, warmup_steps=1, total_steps=50)
    with jax.set_mesh(mesh):
        state = make_train_state(lambda rng: smallthinker.init(rng, TINY),
                                 jax.random.PRNGKey(0), opt, mesh,
                                 smallthinker.partition_specs(TINY))
        step = make_train_step(
            lambda p, b: smallthinker.loss_fn(p, b, TINY, mesh), opt, mesh)
        batch = {"tokens": _tokens(TINY, batch=4, seq=32)}
        losses = []
        for _ in range(6):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert int(metrics["moe_assignments"]) == 4 * 32 * 3 * 4
    assert 0 <= int(metrics["moe_held"]) <= int(metrics["moe_assignments"])
