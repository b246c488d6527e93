"""The Nemotron-H tower on virtual-device meshes (CPU, tiny preset): batch
over `dp`, the held experts over `ep`, against one device; `tp` > 1 is
refused."""
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import nemotron_h
from ray_tpu.parallel import sharding as sh
from ray_tpu.parallel.mesh import MeshConfig, create_mesh
from tests import test_model_checks as checks
from tests.test_nemotron_h import TINY, _params, _tokens


@functools.cache
def _on_one_device():
    """What the three meshes are held to, made once a process."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    params, tokens = _params(cfg), _tokens(cfg, batch=4, seq=32)
    return cfg, params, tokens, *checks.loss_and_grads(
        lambda p: nemotron_h.loss_fn(p, {"tokens": tokens}, cfg)[0], params)


@pytest.mark.parametrize("axes", [{"dp": 2}, {"dp": 1, "ep": 2},
                                  {"dp": 2, "ep": 2}],
                         ids=["dp2", "ep2", "dp2_ep2"])
def test_model_on_a_mesh_agrees_with_one_device(axes):
    """Batch over `dp`, the held experts over `ep`: loss and every
    gradient as on one device, to float32 rounding."""
    cfg, params, tokens, want, want_grads = _on_one_device()
    n = math.prod(axes.values())
    mesh = create_mesh(MeshConfig(**axes), devices=jax.devices()[:n])
    sharded = sh.tree_shard(params, mesh, nemotron_h.partition_specs(cfg))
    with jax.set_mesh(mesh):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: nemotron_h.loss_fn(p, {"tokens": tokens}, cfg,
                                         mesh)[0]))(sharded)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    checks.assert_close(grads, want_grads, 1e-5, skip=("['bias']",))


def test_a_tp_mesh_is_refused():
    mesh = create_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    params = jax.eval_shape(
        lambda: nemotron_h.init(jax.random.PRNGKey(0), TINY))
    with pytest.raises(ValueError, match="tp > 1 is not supported"):
        jax.eval_shape(lambda p: nemotron_h.loss_fn(
            p, {"tokens": jnp.zeros((2, 9), jnp.int32)}, TINY, mesh), params)
