"""The `tp` reductions of the layer loops, issued by the model
(`models/gpt2.py:_tp_blocks`): two half-batch chains a block, each
reduction a neighbour exchange (`ppermute` over `tp` + add) in per-device
code. Same mathematics as the unsharded model, the exchanges in the count
`tp_exchange_plan` gives, and nothing of it where `tp` is 1.

CPU virtual devices; what the TPU compiler schedules between an exchange's
start and its done is read from a compile for a described chip by hand
(PERF.md §6), not here.
"""
import contextlib
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import gpt2
from ray_tpu.models import layers as L
from ray_tpu.parallel.mesh import MeshConfig, create_mesh

SEQ = 32


def _mesh(axes):
    n = math.prod(axes.values())
    return create_mesh(MeshConfig(**axes), devices=jax.devices()[:n])


def _setup(batch, **overrides):
    cfg = dataclasses.replace(gpt2.gpt2_tiny(), dtype=jnp.float32,
                              **overrides)
    params = gpt2.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, SEQ + 1), 0,
                                cfg.vocab_size)
    return cfg, params, tokens


def _loss_and_grads(cfg, mesh):
    return jax.value_and_grad(
        lambda p, t: gpt2.loss_fn(p, {"tokens": t}, cfg, mesh)[0])


def _walk(jaxpr, times=1, in_loop=False):
    """Every equation with how often a step runs it and whether a scan
    (a layer loop) holds it."""
    for eqn in jaxpr.eqns:
        yield eqn, times, in_loop
        scan = eqn.primitive.name == "scan"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(
                        sub, times * (eqn.params["length"] if scan else 1),
                        in_loop or scan)


def _tp_collectives(cfg, mesh, params, tokens):
    """(ppermutes over tp a step, [(in a layer loop, rank) of each psum
    over tp]) in the jaxpr of loss and gradients."""
    jaxpr = jax.make_jaxpr(_loss_and_grads(cfg, mesh))(params, tokens).jaxpr
    exchanges, sums = 0, []
    for eqn, times, in_loop in _walk(jaxpr):
        name = eqn.primitive.name
        if name == "ppermute" and "tp" in str(eqn.params["axis_name"]):
            exchanges += times
        elif name.startswith("psum") and "tp" in str(eqn.params.get("axes")):
            sums += [(in_loop, v.aval.ndim) for v in eqn.invars]
    return exchanges, sums


CASES = {
    "dp2_tp2": ({"dp": 2, "tp": 2}, 8, {}),
    "tp4_ring": ({"tp": 4}, 4, {}),
    "dp2_tp2_remat": ({"dp": 2, "tp": 2}, 8, {"remat": True}),
    "dp2_tp2_odd_local_batch": ({"dp": 2, "tp": 2}, 6, {"remat": True}),
    "dp2_sp2_tp2_ring_attention": ({"dp": 2, "sp": 2, "tp": 2}, 8,
                                   {"remat": True}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_tp_stack_matches_unsharded_and_exchanges_as_planned(case):
    axes, batch, overrides = CASES[case]
    cfg, params, tokens = _setup(batch, **overrides)
    mesh = _mesh(axes)
    local_batch = batch // axes.get("dp", 1)

    want, want_grads = _loss_and_grads(cfg, None)(params, tokens)
    with jax.set_mesh(mesh):
        sharded = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params,
            gpt2.partition_specs(cfg))
        got, got_grads = jax.jit(_loss_and_grads(cfg, mesh))(sharded, tokens)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    errors = jax.tree_util.tree_map(
        lambda g, w: float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)),
        got_grads, want_grads)
    for path, err in jax.tree_util.tree_leaves_with_path(errors):
        assert err <= 1e-5, (jax.tree_util.keystr(path), err)

    exchanges, size, chains = gpt2.tp_exchange_plan(cfg, mesh, local_batch,
                                                    seq=SEQ)
    assert chains == (1 if local_batch % 2 else 2)
    # with remat too: the checkpoint keeps the reduced attention output, so
    # the backward pass recomputes no exchange (jaxpr and plan agree below)
    assert exchanges == cfg.n_layer * chains * 4 * (axes["tp"] - 1)
    assert size == exchanges * (local_batch // chains) * (
        SEQ // axes.get("sp", 1)) * cfg.d_model * 4
    counted, sums = _tp_collectives(cfg, mesh, params, tokens)
    assert counted == exchanges
    # no activation is summed over tp by psum inside the layer loops; the one
    # outside is the region's edge (the stack input's cotangent shares)
    assert [s for s in sums if s[0] and s[1] >= 3] == []
    assert len([s for s in sums if s[1] >= 3]) <= 1


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_compiled_loops_hold_four_exchanges_a_layer_and_chain(remat):
    """The compiled 2 x 2 step, one body a layer loop: two exchanges a chain
    in the forward body and two in the backward one, under remat as
    without (a checkpoint with no policy put a third in the backward's)."""
    axes = {"dp": 2, "tp": 2}
    cfg, params, tokens = _setup(8, remat=remat)
    mesh = _mesh(axes)
    with jax.set_mesh(mesh):
        sharded = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params,
            gpt2.partition_specs(cfg))
        hlo = jax.jit(_loss_and_grads(cfg, mesh)).lower(
            sharded, tokens).compile().as_text()
    exchanges, _, chains = gpt2.tp_exchange_plan(cfg, mesh, 4, seq=SEQ)
    assert chains == 2 and exchanges == cfg.n_layer * chains * 4
    permutes = [line for line in hlo.splitlines()
                if " collective-permute(" in line
                or " collective-permute-start(" in line]
    assert len(permutes) == exchanges // cfg.n_layer


NO_TP = {
    "no_mesh": (None, {}),
    "dp4": ({"dp": 4}, {}),
    "dp2_sp2": ({"dp": 2, "sp": 2}, {"remat": True}),
    # a routed block sums its experts' parts itself (layers.apply_moe), by
    # psum over ep and tp, not by the dense loop's exchanges
    "dp2_tp2_moe": ({"dp": 2, "tp": 2},
                    {"moe": L.MoEConfig(n_experts=4, top_k=2)}),
}


@pytest.mark.parametrize("case", list(NO_TP))
def test_no_exchange_where_the_model_does_not_reduce(case):
    axes, overrides = NO_TP[case]
    cfg, params, tokens = _setup(8, **overrides)
    mesh = _mesh(axes) if axes else None
    assert gpt2.tp_exchange_plan(cfg, mesh, 4) == (0, 0, 1)
    with jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        counted, _ = _tp_collectives(cfg, mesh, params, tokens)
        text = str(jax.make_jaxpr(_loss_and_grads(cfg, mesh))(params, tokens))
    assert counted == 0
    if "sp" not in (axes or {}):        # ring attention rotates k/v over sp
        assert "ppermute" not in text


def test_tp_exchange_plan_at_the_four_chip_cells_shapes():
    """gpt2l-dp2tp2: gpt2-large under remat, 32 x 1,024 over dp=2 x tp=2:
    36 layers x 2 chains x 4 exchanges of one bf16 [8, 1024, 1280]."""
    cfg = dataclasses.replace(gpt2.gpt2_large(), remat=True)
    mesh = _mesh({"dp": 2, "tp": 2})
    one = 8 * 1024 * 1280 * 2
    assert one == 20_971_520
    assert gpt2.tp_exchange_plan(cfg, mesh, 16) == (288, 288 * one, 2)
    assert gpt2.tp_exchange_plan(cfg, _mesh({"dp": 4}), 16) == (0, 0, 1)
    assert gpt2.tp_exchange_plan(cfg, None, 16) == (0, 0, 1)
    # one chain where the local batch does not halve: half the exchanges,
    # each twice the size
    assert gpt2.tp_exchange_plan(cfg, mesh, 1, seq=1024) == (
        144, 144 * 1024 * 1280 * 2, 1)
    assert gpt2.tp_exchange_plan(
        dataclasses.replace(cfg, remat=False), _mesh({"tp": 4}), 16)[0] \
        == 36 * 2 * 4 * 3


@pytest.mark.parametrize("size", [2, 4])
def test_exchange_sum_is_the_sum_over_the_axis(size):
    mesh = _mesh({"tp": size})
    x = jax.random.normal(jax.random.PRNGKey(0), (size, 3, 8, 16))

    def both(p):
        return L.exchange_sum(p, "tp"), jax.lax.psum(p, "tp")

    got, want = jax.jit(jax.shard_map(
        both, mesh=mesh, in_specs=P("tp"), out_specs=(P("tp"), P("tp")),
        check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(jnp.sum(x, axis=0)), rtol=1e-6,
        atol=1e-6)
