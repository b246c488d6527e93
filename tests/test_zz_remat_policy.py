"""What the layer loops' checkpoint keeps (`models/layers.py:remat`): the
flash forward kernel's `o` and `lse` and the attention sub-layer's output,
besides the block's input. So a training step runs the forward kernel once
a layer, not twice, and recomputes neither the `wo` product nor, under
`tp`, its exchange (`tests/test_zz_tp_overlap.py` counts those); the bytes
kept are `gpt2.remat_saved_plan`'s; no number changes; and where remat is
off the names lower to nothing.

CPU virtual devices, the Pallas kernels through the interpreter
(`interpret=True`: `force_tpu_interpret_mode` has effects a checkpoint
refuses). What the kept values are worth on the chip is in PERF.md §6.
"""
import contextlib
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax._src.ad_checkpoint import saved_residuals
from jax.sharding import NamedSharding

from ray_tpu.models import gpt2, olmoe
from ray_tpu.models import layers as L
from ray_tpu.ops import flash_attention as fa
from tests.test_zz_tp_overlap import _mesh as _mesh_of, _walk

SEQ = 128


@pytest.fixture
def interpreted(monkeypatch):
    """`apply_attention(impl="flash")` through the Pallas interpreter."""
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))


def _mesh(axes):
    return _mesh_of(axes) if axes else None


def _on(mesh):
    return jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext()


def _gpt2(batch, **overrides):
    cfg = dataclasses.replace(gpt2.gpt2_tiny(), dtype=jnp.float32,
                              **overrides)
    params = gpt2.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, SEQ + 1), 0,
                                cfg.vocab_size)
    return cfg, params, tokens


def _loss_and_grads(module, cfg, mesh, **kw):
    return jax.value_and_grad(
        lambda p, t: module.loss_fn(p, {"tokens": t}, cfg, mesh, **kw)[0])


# ------------------------------------------ the forward kernel runs once
KERNEL_CASES = {
    "no_mesh": {},                          # the kernel called as it is
    "dp2_wrapped": {"dp": 2},               # under `attend`'s shard_map
    "dp2_tp2_two_chains": {"dp": 2, "tp": 2},   # in `_tp_blocks`' region
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_three_kernel_calls_a_layer_and_chain_under_remat(interpreted, case):
    axes = KERNEL_CASES[case]
    mesh = _mesh(axes)
    calls = {}
    for remat in (False, True):
        cfg, params, tokens = _gpt2(8, attention="flash", remat=remat)
        with _on(mesh):
            jaxpr = jax.make_jaxpr(_loss_and_grads(gpt2, cfg, mesh))(
                params, tokens).jaxpr
        calls[remat] = sum(times for eqn, times, _ in _walk(jaxpr)
                           if eqn.primitive.name == "pallas_call")
    chains = 2 if axes.get("tp", 1) > 1 else 1
    # forward, dq, dk/dv: a bare `jax.checkpoint` made it four
    assert calls[True] == cfg.n_layer * chains * 3
    assert calls[True] == calls[False]


# ------------------- in which loop the kernel and the `wo` product run
def _olmoe(remat=True, **overrides):
    cfg = dataclasses.replace(olmoe.olmoe_tiny(), dtype=jnp.float32,
                              remat=remat, **overrides)
    params = olmoe.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, SEQ + 1), 0,
                                cfg.vocab_size)
    return olmoe, cfg, params, tokens


# one head of 64: the width the kernels' tile plan starts at
OLMOE_FLASH = {"attention": "flash", "n_head": 1}


def _layer_loops(jaxpr, n_layer):
    """(forward, backward) bodies of the step's layer loops: the outermost
    scans over the blocks, the backward one running in reverse."""
    def scans(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan" \
                    and eqn.params["length"] == n_layer:
                yield eqn
                continue
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) \
                        else [value]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from scans(sub)

    forward, backward = scans(jaxpr)
    assert not forward.params["reverse"] and backward.params["reverse"]
    return forward.params["jaxpr"].jaxpr, backward.params["jaxpr"].jaxpr


def _count(body, cfg):
    """(pallas_calls, `wo` products) in one layer loop's body: the product
    is the only one that contracts heads and head_dim with a [H, K, D]."""
    wo = (cfg.n_head, cfg.d_model // cfg.n_head, cfg.d_model)
    kernels = products = 0
    for eqn, times, _ in _walk(body):
        if eqn.primitive.name == "pallas_call":
            kernels += times
        elif eqn.primitive.name == "dot_general" \
                and eqn.params["dimension_numbers"][0] == ((2, 3), (0, 1)):
            shape = eqn.invars[1].aval.shape
            products += times * (shape[-3:] == wo or shape[-3:] == (
                wo[0] // 2, *wo[1:]))      # half the heads on a `tp` device
    return kernels, products


LOOP_CASES = {
    "gpt2_no_mesh": ({}, lambda remat: (
        gpt2, *_gpt2(4, attention="flash", remat=remat))),
    "gpt2_dp2_tp2_two_chains": ({"dp": 2, "tp": 2}, lambda remat: (
        gpt2, *_gpt2(8, attention="flash", remat=remat))),
    "olmoe_no_mesh": ({}, lambda remat: _olmoe(remat, **OLMOE_FLASH)),
}


@pytest.mark.parametrize("case", list(LOOP_CASES))
def test_backward_loop_holds_no_forward_kernel_and_no_wo_product(
        interpreted, monkeypatch, case):
    axes, make = LOOP_CASES[case]
    mesh = _mesh(axes)
    chains = 2 if axes.get("tp", 1) > 1 else 1

    def loops(remat):
        module, cfg, params, tokens = make(remat)
        with _on(mesh):
            jaxpr = jax.make_jaxpr(_loss_and_grads(module, cfg, mesh))(
                params, tokens).jaxpr
        forward, backward = _layer_loops(jaxpr, cfg.n_layer)
        return _count(forward, cfg), _count(backward, cfg)

    (kernels, products), backward = plain = loops(remat=False)
    # forward: the kernel once and the product (OLMoE's in several passes,
    # `three_pass`); backward: dq and dk/dv, and only transposes of products
    assert kernels == chains and products >= chains
    assert backward == (2 * chains, 0)
    assert loops(remat=True) == plain
    # what a checkpoint with no policy recomputes, and this count can see
    monkeypatch.setattr(L, "remat", jax.checkpoint)
    assert loops(remat=True) == ((kernels, products),
                                 (3 * chains, products))


# ------------------------------------------------- what is kept, in bytes
@pytest.mark.parametrize("impl", ["flash", "reference"])
def test_saved_residuals_are_the_input_and_the_named_values(interpreted, impl):
    batch = 4
    cfg = dataclasses.replace(gpt2.gpt2_tiny(), attention=impl, remat=True)
    params = gpt2.init(jax.random.PRNGKey(0), cfg)
    block = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    x = jnp.zeros((batch, SEQ, cfg.d_model), cfg.dtype)
    body = L.remat(lambda x, block: gpt2._block_apply(block, x, cfg, impl)[0])
    kept = [aval for aval, what in saved_residuals(body, x, block)
            if not what.startswith("from the argument")]
    plan = gpt2.remat_saved_plan(cfg, None, batch, SEQ, flash=impl == "flash")
    assert set(plan) == {L.ATTENTION_OUT, *(
        fa.RESIDUAL_NAMES if impl == "flash" else ())}
    assert sorted(a.size * a.dtype.itemsize for a in kept) \
        == sorted(plan.values())
    if impl == "flash":
        o, lse = fa.RESIDUAL_NAMES
        head_dim = cfg.d_model // cfg.n_head
        # o as the model reads it, dense in HBM; lse as the kernels do
        assert {(a.shape, str(a.dtype)) for a in kept} == {
            ((batch, SEQ, cfg.n_head, head_dim), "bfloat16"),
            ((batch * cfg.n_head, SEQ), "float32"),
            ((batch, SEQ, cfg.d_model), "bfloat16")}
        assert plan[o] == plan[L.ATTENTION_OUT] == batch * SEQ * cfg.d_model * 2
        assert plan[lse] == batch * cfg.n_head * SEQ * 4


def test_remat_saved_plan_at_the_remat_cells_shapes():
    """gpt2m-b16-remat: 16 x 1,024 on one chip; gpt2l-dp2tp2: 16 x 1,024 a
    `dp` shard, 10 of 20 heads a `tp` device, the output whole on each."""
    o, lse = fa.RESIDUAL_NAMES
    medium = dataclasses.replace(gpt2.gpt2_medium(), remat=True)
    assert gpt2.remat_saved_plan(medium, None, 16) == {
        L.ATTENTION_OUT: 33_554_432, o: 33_554_432, lse: 1_048_576}
    large = dataclasses.replace(gpt2.gpt2_large(), remat=True)
    mesh = _mesh({"dp": 2, "tp": 2})
    plan = gpt2.remat_saved_plan(large, mesh, 16)
    assert plan == {L.ATTENTION_OUT: 41_943_040, o: 20_971_520, lse: 655_360}
    assert large.n_layer * sum(plan.values()) == 2_288_517_120
    # a sequence split over `sp` runs ring attention: no kernel, no o/lse
    assert gpt2.remat_saved_plan(large, _mesh({"dp": 2, "sp": 2}), 16,
                                 flash=False) == {L.ATTENTION_OUT: 20_971_520}


# ------------------------------------------------- the same numbers
SAME_NUMBERS = {
    "gpt2_no_mesh": ({}, lambda: (gpt2, *_gpt2(4, attention="flash"), {})),
    "gpt2_dp2": ({"dp": 2}, lambda: (gpt2, *_gpt2(4, attention="flash"), {})),
    "gpt2_dp2_tp2": ({"dp": 2, "tp": 2},
                     lambda: (gpt2, *_gpt2(8, attention="flash"), {})),
    "gpt2_pipelined": ({"dp": 2, "pp": 2}, lambda: (
        gpt2, *_gpt2(8), {"pipelined": True, "n_microbatches": 2})),
    "olmoe_tiny": ({}, lambda: (*_olmoe(attention="reference"), {})),
    "olmoe_tiny_flash": ({}, lambda: (*_olmoe(**OLMOE_FLASH), {})),
}


@pytest.mark.parametrize("against", ["no_remat", "bare_checkpoint"])
@pytest.mark.parametrize("case", list(SAME_NUMBERS))
def test_remat_changes_no_number(interpreted, monkeypatch, case, against):
    """Loss and gradients with `L.remat` against those with remat off, and
    against those of a `jax.checkpoint` that keeps nothing."""
    axes, make = SAME_NUMBERS[case]
    module, cfg, params, tokens, kw = make()
    mesh = _mesh(axes)

    def run(remat):
        return jax.jit(_loss_and_grads(
            module, dataclasses.replace(cfg, remat=remat), mesh, **kw))(
                params, tokens)

    with _on(mesh):
        if mesh is not None:
            params = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                params, module.partition_specs(cfg))
        got, got_grads = run(remat=True)
        if against == "bare_checkpoint":
            monkeypatch.setattr(L, "remat", jax.checkpoint)
        want, want_grads = run(remat=against == "bare_checkpoint")
    assert abs(float(got) - float(want)) <= 1e-6 * abs(float(want))
    errors = jax.tree_util.tree_map(
        lambda g, w: float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)),
        got_grads, want_grads)
    for path, err in jax.tree_util.tree_leaves_with_path(errors):
        assert err <= 1e-6, (jax.tree_util.keystr(path), err)


# ------------------------------------- nothing where remat is off
def test_names_lower_to_nothing_without_remat(interpreted, monkeypatch):
    cfg, params, tokens = _gpt2(4, attention="flash", remat=False)

    def lowered():
        # a fresh function each time: nothing traced before is reused
        f = _loss_and_grads(gpt2, cfg, None)
        text = jax.jit(f).lower(params, tokens).as_text()
        # private functions are numbered by a counter of the process
        return (str(jax.make_jaxpr(f)(params, tokens)),
                re.sub(r"@(\w+?)_\d+\b", r"@\1", text))

    named_jaxpr, named = lowered()
    for module in (L, fa):
        monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)
    bare_jaxpr, bare = lowered()
    for name in (*fa.RESIDUAL_NAMES, L.ATTENTION_OUT):
        assert f"name={name}" in named_jaxpr
        assert f"name={name}" not in bare_jaxpr
    assert named == bare
