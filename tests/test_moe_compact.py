"""A share that compacts its rows (`layers.moe._local_experts`, PR 37): where
the leaves hold fewer experts than the router scores, the layer works on a
bounded prefix of the sorted assignments — the least of its bounds that
holds the held experts' rows — and on all of them where none does: the
same result either way.

This is the `moe` seam's own test: it imports `ray_tpu.models.layers.moe`
by path, because what it patches (`_BOUND_FACTORS`, `assignment_bounds`,
`checkpoint_name`) are globals that `moe.py`'s own code reads — set on the
package they would reach nothing."""
import dataclasses
import hashlib
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import layers as L
from ray_tpu.models.layers import moe
from ray_tpu.ops import grouped_matmul
from ray_tpu.parallel import sharding as sh
from ray_tpu.parallel.mesh import MeshConfig, create_mesh

D, F, T, K, E = 64, 32, 512, 2, 16
# 2 × and 4 × (1,024 rows · 2 held of 16), whole row tiles. The layer has
# ONE rung today (`_BOUND_FACTORS`: what a rung costs before the first step
# allows no more); the tests hold the ladder itself to two, so that the rung
# a later PR adds is already held to the whole path.
BOUNDS = (256, 512)


@pytest.fixture(autouse=True)
def two_rungs(monkeypatch):
    monkeypatch.setattr(moe, "_BOUND_FACTORS", (2, 4))
FORMS = {"relu2": dict(activation="relu2"), "relu_gated": dict(gate="relu"),
         "silu_gated": dict(gate="silu")}


def _config(form, held=2, first=3):
    return L.MoEConfig(n_experts=E, top_k=K, held=held, first=first,
                       **FORMS[form])


def _params(cfg, form, key=0):
    return L.init_moe(jax.random.PRNGKey(key), D, F, cfg,
                      gated=form != "relu2")


def _forced(held_rows, cfg, local=None):
    """[1, T, K] experts, `held_rows` of the T·K assignments on the
    `local` (default: all held) experts from `cfg.first`, a token's K
    distinct."""
    local = cfg.held if local is None else local
    held = [(cfg.first + i) % E for i in range(local)]
    others = [e for e in range(E) if e not in
              [(cfg.first + i) % E for i in range(cfg.held)]]
    idx = np.array([[others[(t + 3 * k) % len(others)] for k in range(K)]
                    for t in range(T)])
    for n in range(held_rows):
        t, k = n % T, n // T
        idx[t, k] = held[(t + k) % len(held)]
    return jnp.asarray(idx[None], jnp.int32)


def _routing(params, x, idx):
    """A router whose choice is given and whose gates are not: softmax
    scores of x · wg at the forced experts, so `wg` and x get a gradient
    through the gates."""
    probs = jax.nn.softmax(jnp.einsum("bsd,de->bse", x, params["wg"]), -1)
    counts = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.int32), axis=(0, 1, 2))
    return jnp.take_along_axis(probs, idx, -1), idx, {"counts": counts}


def _value_and_grads(params, x, cfg, idx, mesh=None):
    def f(params, x):
        out, stats = L.apply_moe(params, x, cfg, compute_dtype=jnp.float32,
                                 mesh=mesh, routing=_routing(params, x, idx))
        weights = jnp.cos(jnp.arange(out.size, dtype=jnp.float32))
        return jnp.sum(out * weights.reshape(out.shape)), (out, stats)
    (_, (out, stats)), grads = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1), has_aux=True))(params, x)
    return out, stats, grads


def _whole(*args, **kwargs):
    """The same call with the bound switched off: the parent's path."""
    with mock.patch.object(moe, "assignment_bounds", lambda *a: ()):
        return _value_and_grads(*args, **kwargs)


def _assert_same(got, want):
    out, _, grads = got
    want_out, _, want_grads = want
    np.testing.assert_allclose(out, want_out, rtol=1e-6, atol=1e-7)
    flat = jax.tree_util.tree_leaves_with_path(want_grads)
    assert {jax.tree_util.keystr(p).split("'")[1] for p, _ in flat[:-1]} == \
        set(want_grads[0])          # wg and every expert leaf, then x
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.max(jnp.abs(b))) > 0 or "bias" in str(path)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("held_rows,compact", [
    (100, 1), (BOUNDS[0], 1), (BOUNDS[0] + 1, 1), (BOUNDS[1], 1),
    (BOUNDS[1] + 1, 0), (0, 1)],
    ids=["under", "at_the_bound", "one_over_the_first", "at_the_last_bound",
         "one_over_the_last", "none_held"])
def test_the_bounded_path_is_the_whole_path(form, held_rows, compact):
    """Output and the gradients to x, the gates (through them `wg`) and
    every expert leaf, whatever the routing: under a bound and exactly at
    it that bounded program runs, one row over the first the second does,
    one row over the last the whole one (no assignment is dropped), and a
    routing that reaches no held expert gives zeros from the bounded
    program too. `first` is 3."""
    cfg = _config(form)
    params = _params(cfg, form)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, T, D))
    idx = _forced(held_rows, cfg)
    got = _value_and_grads(params, x, cfg, idx)
    want = _whole(params, x, cfg, idx)
    assert float(got[1]["compact"]) == compact
    assert float(want[1]["compact"]) == 0
    if held_rows:
        _assert_same(got, want)
    else:
        assert not np.any(got[0]) and not np.any(want[0])
        for a, b in zip(*(jax.tree_util.tree_leaves(g[2])
                          for g in (got, want))):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("form", ["relu2", "relu_gated"])
def test_the_bounded_path_with_the_layers_own_router(form):
    """No `routing=`: `apply_moe` routes on its own input (top-k over
    softmax scores, all 16 experts), the held two see about an eighth of
    the rows, under the first bound of a quarter."""
    cfg = _config(form, first=0)
    params = _params(cfg, form)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, T // 2, D))

    def run():
        def f(params, x):
            out, stats = L.apply_moe(params, x, cfg,
                                     compute_dtype=jnp.float32)
            return jnp.sum(jnp.sin(out)), (out, stats)
        (_, (out, stats)), grads = jax.jit(jax.value_and_grad(
            f, argnums=(0, 1), has_aux=True))(params, x)
        return out, stats, grads
    got = run()
    with mock.patch.object(moe, "assignment_bounds", lambda *a: ()):
        want = run()
    held = int(jnp.sum(got[1]["counts"][:2]))
    assert 0 < held <= BOUNDS[0] and float(got[1]["compact"]) == 1
    _assert_same(got, want)


@pytest.mark.parametrize("held_rows,compact", [(400, 1.0), (600, 0.5)],
                         ids=["both_devices_under", "one_device_over"])
def test_the_bounded_path_under_an_ep_mesh(held_rows, compact):
    """Four held experts over `ep=2`: each device has two, its own `first`
    and its own count of rows — with 600 rows on device 0's experts and
    none on device 1's, device 0 takes the whole path and device 1 a
    bounded one, and the sum is the one-device result."""
    cfg = _config("relu2", held=4)
    params = _params(cfg, "relu2")
    x = jax.random.normal(jax.random.PRNGKey(3), (1, T, D))
    idx = _forced(held_rows, cfg, local=4 if held_rows == 400 else 2)
    want = _whole(params, x, cfg, idx)
    mesh = create_mesh(MeshConfig(ep=2), devices=jax.devices()[:2])
    specs = {k: sh.spec(*L.MOE_LOGICAL[k]) for k in params}
    with jax.set_mesh(mesh):
        got = jax.jit(lambda p, x: _value_and_grads(p, x, cfg, idx, mesh))(
            sh.tree_shard(params, mesh, specs), x)
    assert float(got[1]["compact"]) == compact
    _assert_same(got, want)


def test_the_plans_bounds_are_the_layers_and_multiples_of_the_row_tile(
        monkeypatch):
    """`moe_plan` gives the bounds `_local_experts` branches on (here seen
    in the traced program: each bounded branch's products have that many
    rows), from the shapes alone; `nemotronh9l-b1s8k`'s is an eighth of its
    rows, `smallthinker4l-b1s16k`'s a half."""
    cfg = _config("relu2")
    plan = L.moe_plan(T, D, F, cfg, gated=False)
    assert plan["rows"] == T * K and plan["bounds"] == BOUNDS
    assert L.moe_plan(T // 2, D, F, cfg, gated=False)["bounds"] == (256,)
    params = _params(cfg, "relu2")
    text = str(jax.make_jaxpr(lambda p, x: L.apply_moe(
        p, x, cfg, compute_dtype=jnp.float32)[0])(
            params, jnp.zeros((1, T, D))))
    for rows in BOUNDS + (T * K,):
        assert rows % grouped_matmul.row_tile(T * K) == 0
        assert f"f32[{rows},{F}]" in text
    monkeypatch.undo()              # the cells' bounds, as the layer has them
    for tokens, d, f, share, rows, bound in (
            (8192, 2688, 1856, L.MoEConfig(n_experts=128, top_k=6, held=8),
             49_152, 6_144),
            (16_384, 2560, 768, L.MoEConfig(n_experts=64, top_k=6, held=16),
             98_304, 49_152)):
        plan = L.moe_plan(tokens, d, f, share, gated=False)
        assert (plan["rows"], plan["bounds"]) == (rows, (bound,))
        assert bound % grouped_matmul.row_tile(rows) == 0
        assert grouped_matmul.tile_plan(bound, d, -(-f // 128) * 128,
                                        jnp.bfloat16) is not None
    monkeypatch.setattr(moe, "_BOUND_FACTORS", (2, 4))
    # every expert held, or shared out over `ep` by halves: no bound
    whole = dataclasses.replace(cfg, held=None)
    assert L.moe_plan(T, D, F, whole, gated=False)["bounds"] == ()
    assert L.moe_plan(T, D, F, whole, gated=False, ep=2)["bounds"] == ()
    assert L.moe_plan(T, D, F, whole, gated=False, ep=4)["bounds"] == (512,)
    assert L.moe_plan(T, D, F, whole, gated=False, ep=8)["bounds"] == BOUNDS
    assert L.assignment_bounds(48, 2, 16) == ()    # a tile is over 48 rows


# sha256 (first 16 digits) of the jaxpr of `apply_moe`, forward + backward,
# with every expert held and no mesh, as THE PARENT OF PR 37 traced it
# (commit 6d357e1, JAX 0.9.0; addresses and source line numbers stripped):
# the bound is a Python-level branch on shapes, and where the leaves hold
# every scored expert it adds or moves no operation. The digests are of that
# text with a dtype's two spellings made one (`<class 'jax.numpy.bfloat16'>`
# as `bfloat16`: since PR 47 the plain grouped product lives in
# `ops.grouped_matmul` and hands `ragged_dot` its operand's dtype where the
# layer handed the scalar type — the same operation, printed otherwise) and
# without the `checkpoint_name`s of the routing (PR 50: `name` equations
# that say nothing outside a checkpoint and lower to nothing,
# `tests/test_zz_remat_policy.py::test_names_lower_to_nothing_without_remat`).
# RE-TAKEN AT PR 50 (they were 4a9c779829774eec and 99fd950b997c3eed): both
# forms route by softmax, and `_route` now hands `lax.top_k` the
# probabilities behind a `stop_gradient` and reads the gates' tangent by
# `_chosen` — top_k's own gather, by the indices the caller holds — so the
# text differs in those lines (and in the names of every variable behind
# them) and in nothing else; the COMPILED step of the cell that runs this
# path is the parent's to the sha256
# (`benchmarks/results/pr50_routing_kept/same_program.txt`, `olmoe1l-b2s4k`).
PARENT_JAXPR = {"relu2": "0b5757f09468b930", "silu_gated": "9f386453f66f486b"}


def _digest(form, monkeypatch):
    monkeypatch.setattr(moe, "checkpoint_name", lambda x, name: x)
    cfg = dataclasses.replace(_config(form), held=None, first=0)
    params = _params(cfg, form)
    x = jnp.zeros((1, T, D))

    def f(params, x):
        return jnp.sum(L.apply_moe(params, x, cfg)[0])
    text = str(jax.make_jaxpr(jax.grad(f, (0, 1)))(params, x))
    text = re.sub(r"\.py:\d+", ".py", re.sub(r"0x[0-9a-f]+", "0x", text))
    text = re.sub(r"<class 'jax\.numpy\.(\w+)'>", r"\1", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("form", sorted(PARENT_JAXPR))
def test_every_expert_held_traces_to_the_parents_jaxpr(form, monkeypatch):
    assert _digest(form, monkeypatch) == PARENT_JAXPR[form]
