"""The `tp` reductions of the layer loops, issued by the model
(`models/gpt2.py:_tp_blocks`): two half-batch chains a block, each
reduction neighbour exchanges (`ppermute` over `tp` + add) in per-device
code: one exchange of the whole partial at `tp` 2, a reduce-scatter and an
all-gather of half-chunks on both ring directions beyond
(`layers.exchange_sum`). Same mathematics as the unsharded model, the
messages and bytes `tp_exchange_plan` gives, and nothing of it where `tp`
is 1.

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


def _walk(jaxpr, times=1, loop=None):
    """Every equation with how often a step runs it and which scan (a layer
    loop: the forward's or the backward's) holds it, None outside one."""
    for eqn in jaxpr.eqns:
        yield eqn, times, loop
        scan = eqn.primitive.name == "scan"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(
                        sub, times * (eqn.params["length"] if scan else 1),
                        id(eqn) if scan else loop)


def _tp_collectives(cfg, mesh, params, tokens):
    """((ppermutes over tp a step, their bytes, their bytes on the busier
    ring direction taken loop by loop), [(in a layer loop, rank) of each
    psum over tp]) in the jaxpr of loss and gradients."""
    jaxpr = jax.make_jaxpr(_loss_and_grads(cfg, mesh))(params, tokens).jaxpr
    messages, ways, sums = 0, {}, []
    for eqn, times, loop in _walk(jaxpr):
        name = eqn.primitive.name
        if name == "ppermute" and "tp" in str(eqn.params["axis_name"]):
            (aval,) = (v.aval for v in eqn.invars)
            source, target = eqn.params["perm"][0]
            up = (target - source) % mesh.shape["tp"] == 1
            messages += times
            way = ways.setdefault(loop, {True: 0, False: 0})
            way[up] += times * aval.size * aval.dtype.itemsize
        elif name.startswith("psum") and "tp" in str(eqn.params.get("axes")):
            sums += [(loop is not None, v.aval.ndim) for v in eqn.invars]
    return (messages, sum(sum(way.values()) for way in ways.values()),
            sum(max(way.values()) for way in ways.values())), sums


CASES = {
    "dp2_tp2": ({"dp": 2, "tp": 2}, 8, {}),
    "tp4_ring": ({"tp": 4}, 4, {}),
    # a chain is ONE sequence, as in `gpt2l-tp4`'s reference check: the rows
    # are what is cut, so it takes the timed step's path
    "tp4_remat_one_sequence_a_chain": ({"tp": 4}, 2, {"remat": True}),
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

    tp = axes["tp"]
    plan = gpt2.tp_exchange_plan(cfg, mesh, local_batch, seq=SEQ)
    chains = 1 if local_batch % 2 else 2
    # with remat too: the checkpoint keeps the reduced attention output, so
    # the backward pass recomputes no exchange (jaxpr and plan agree below)
    reductions = cfg.n_layer * chains * 4
    one = (local_batch // chains) * (SEQ // axes.get("sp", 1)) \
        * cfg.d_model * 4
    if tp == 2:     # one exchange of the whole partial, as before PR 61
        assert plan == (reductions, reductions * one, chains,
                        reductions * one)
    else:           # 2 (tp - 1) messages of one / (2 tp) each way
        assert plan == (reductions * 4 * (tp - 1),
                        reductions * 2 * (tp - 1) * one // tp, chains,
                        reductions * (tp - 1) * one // tp)
    # forward and backward loops alike: the backward is the same exchange
    counted, sums = _tp_collectives(cfg, mesh, params, tokens)
    assert counted == (plan.messages, plan.bytes, plan.bytes_a_direction)
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
    exchanges, _, chains, _ = gpt2.tp_exchange_plan(cfg, mesh, 4, seq=SEQ)
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
    assert gpt2.tp_exchange_plan(cfg, mesh, 4) == (0, 0, 1, 0)
    with jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        counted, _ = _tp_collectives(cfg, mesh, params, tokens)
        text = str(jax.make_jaxpr(_loss_and_grads(cfg, mesh))(params, tokens))
    assert counted == (0, 0, 0)
    if "sp" not in (axes or {}):        # ring attention rotates k/v over sp
        assert "ppermute" not in text


def test_tp_exchange_plan_at_the_four_chip_cells_shapes():
    """gpt2-large under remat, one bf16 [8, 1024, 1280] a chain and
    reduction (A), 36 layers x 2 chains x 4 reductions. `gpt2l-dp2tp2`, 32 x
    1,024 over dp=2 x tp=2: one exchange of A each. `gpt2l-tp4`, 16 x 1,024
    over tp=4: 12 messages of A / 8 each, six to either neighbour."""
    cfg = dataclasses.replace(gpt2.gpt2_large(), remat=True)
    mesh = _mesh({"dp": 2, "tp": 2})
    one = 8 * 1024 * 1280 * 2
    assert one == 20_971_520
    assert gpt2.tp_exchange_plan(cfg, mesh, 16) == (288, 288 * one, 2,
                                                    288 * one)
    assert gpt2.tp_exchange_plan(cfg, _mesh({"dp": 4}), 16) == (0, 0, 1, 0)
    assert gpt2.tp_exchange_plan(cfg, None, 16) == (0, 0, 1, 0)
    # one chain where the local batch does not halve: half the exchanges,
    # each twice the size
    assert gpt2.tp_exchange_plan(cfg, mesh, 1, seq=1024)[:3] == (
        144, 144 * 1024 * 1280 * 2, 1)
    tp4 = _mesh({"tp": 4})
    plan = gpt2.tp_exchange_plan(cfg, tp4, 16)
    assert plan == (288 * 12, 288 * 12 * one // 8, 2, 288 * 6 * one // 8)
    assert plan == (3_456, 9_059_696_640, 2, 4_529_848_320)
    # ... where a ring of whole activations sent 18.1 GB, all of it one way
    assert 288 * 3 * one == 18_119_393_280
    assert gpt2.tp_exchange_plan(
        dataclasses.replace(cfg, remat=False), tp4, 16)[0] == 36 * 2 * 4 * 12
    # the reference check's two sequences, one a chain: the same form
    assert gpt2.tp_exchange_plan(cfg, tp4, 2) == (
        3_456, 3_456 * one // 64, 2, 1_728 * one // 64)
    # rows that do not divide by 2 tp: the ring of whole activations
    assert gpt2.tp_exchange_plan(cfg, tp4, 2, seq=1023) == (
        864, 864 * 1023 * 1280 * 2, 2, 864 * 1023 * 1280 * 2)


def _over_tp(mesh, fn, *arrays):
    """`fn` as per-device code on arrays [size, ...], one slice a rank."""
    specs = tuple(P("tp") for _ in arrays)
    return jax.jit(jax.shard_map(
        lambda *local: fn(*(a[0] for a in local))[None], mesh=mesh,
        in_specs=specs, out_specs=P("tp"), check_vma=False))(*arrays)


SUMS = {
    "2": (2, (3, 8, 16), jnp.float32, "whole"),
    "4": (4, (3, 8, 16), jnp.float32, "ring_halves"),
    "3": (3, (3, 8, 16), jnp.float32, "ring_halves"),
    "8": (8, (2, 8, 16), jnp.float32, "ring_halves"),
    "2_bf16": (2, (3, 8, 16), jnp.bfloat16, "whole"),
    "4_bf16": (4, (3, 8, 16), jnp.bfloat16, "ring_halves"),
    "8_bf16": (8, (2, 8, 16), jnp.bfloat16, "ring_halves"),
    # 21 and 24 rows do not divide by 2 x 4 and 2 x 8
    "4_rows_do_not_divide": (4, (3, 7, 16), jnp.float32, "whole"),
    "8_rows_do_not_divide_bf16": (8, (3, 8, 16), jnp.bfloat16, "whole"),
    # one sequence a chain, `gpt2l-tp4`'s reference check: the rows are cut
    "4_one_sequence": (4, (1, 32, 16), jnp.float32, "ring_halves"),
    "4_one_sequence_bf16": (4, (1, 32, 16), jnp.bfloat16, "ring_halves"),
}


@pytest.mark.parametrize("case", list(SUMS))
def test_exchange_sum_is_the_sum_over_the_axis(case):
    size, shape, dtype, form = SUMS[case]
    assert L.exchange_form(size, shape) == form
    mesh = _mesh({"tp": size})
    x = jax.random.normal(jax.random.PRNGKey(0), (size, *shape)).astype(dtype)
    weight = jax.random.normal(jax.random.PRNGKey(1), x.shape).astype(dtype)
    forms = {"exchange": lambda p: L.exchange_sum(p, "tp"),
             "psum": lambda p: jax.lax.psum(p, "tp")}

    got, want = (np.asarray(_over_tp(mesh, fn, x), np.float32)
                 for fn in forms.values())
    exact = np.sum(np.asarray(x, np.float32), axis=0)
    # size - 1 adds an element in the partials' dtype, in whatever order
    bound = (size - 1) * float(jnp.finfo(dtype).eps) * np.sum(
        np.abs(np.asarray(x, np.float32)), axis=0) + 1e-6
    assert (np.abs(got - exact) <= bound).all()
    assert (np.abs(got - want) <= 2 * bound).all()
    if form == "ring_halves" or size == 2:
        # each element summed once, on one rank: replicas agree to the bit
        assert all((got[rank] == got[0]).all() for rank in range(size))

    # messages as `tp_exchange_plan` counts them a reduction
    text = str(jax.make_jaxpr(lambda x: _over_tp(mesh, forms["exchange"],
                                                 x))(x))
    assert text.count("ppermute") == (
        4 * (size - 1) if form == "ring_halves" else size - 1)
    assert "psum" not in text

    def grad_through(fn):
        return np.asarray(jax.grad(lambda x: jnp.sum(_over_tp(
            mesh, lambda p, w: fn(p) * w, x, weight).astype(jnp.float32)))(x),
            np.float32)

    got, want = (grad_through(fn) for fn in forms.values())
    scale = (size - 1) * float(jnp.finfo(dtype).eps) * np.sum(
        np.abs(np.asarray(weight, np.float32)), axis=0) + 1e-6
    assert (np.abs(got - want) <= 2 * scale).all()
    if form == "ring_halves":
        assert all((got[rank] == got[0]).all() for rank in range(size))


def test_exchange_sum_at_size_two_is_one_exchange_of_the_whole_partial():
    """`tp` 2 is the control (`gpt2l-dp2tp2`): the jaxpr it had before the
    ring got a second form, forward and backward."""
    mesh = _mesh({"tp": 2})
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 8, 16), jnp.bfloat16)

    def as_it_was(p):
        return p + jax.lax.ppermute(p, "tp", [(0, 1), (1, 0)])

    def program(fn):
        return str(jax.make_jaxpr(jax.value_and_grad(lambda x: jnp.sum(
            _over_tp(mesh, fn, x).astype(jnp.float32))))(x))

    assert program(lambda p: L.exchange_sum(p, "tp")) == program(as_it_was)
