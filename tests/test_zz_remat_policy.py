"""What the layer loops' checkpoint keeps (`models/layers/core.py:remat`): the
flash forward kernel's `o` and `lse`, the attention sub-layer's output, the
results of the products whose forward value took three bf16 passes and a
routed layer's routing (the router's logits, the chosen experts, the
assignments' sort), besides the block's input. So a training step runs the
forward kernel once a layer, not twice, recomputes neither the `wo` product
nor, under `tp`, its exchange (`tests/test_zz_tp_overlap.py` counts
those), runs a three-pass product's passes once, and sorts and scatters a
routed layer's assignments once; the bytes kept are
`gpt2.remat_saved_plan`'s, `nemotron_h.remat_saved_plan`'s and
`layers.routing_plan`'s; no number changes; and where remat is off the
names lower to nothing.

CPU virtual devices, the Pallas kernels through the interpreter
(`interpret=True`: `force_tpu_interpret_mode` has effects a checkpoint
refuses). What the kept values are worth on the chip is in PERF.md §6.
"""
import contextlib
import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax._src.ad_checkpoint import saved_residuals
from jax.sharding import NamedSharding

from ray_tpu.models import (gpt2, joyai, lfm2, nemotron_h, olmoe, qwen3_next,
                            smallthinker)
from ray_tpu.models import layers as L
from ray_tpu.models.layers import attention, core, moe
from ray_tpu.ops import flash_attention as fa
from tests.test_zz_tp_overlap import _mesh as _mesh_of, _walk

SEQ = 128


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
def _tiny(module, preset, batch, **fields):
    cfg = dataclasses.replace(preset(), **fields)
    params = module.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, SEQ + 1), 0,
                                cfg.vocab_size)
    return module, cfg, params, tokens


def _olmoe(remat=True, **overrides):
    return _tiny(olmoe, olmoe.olmoe_tiny, 4, dtype=jnp.float32, remat=remat,
                 **overrides)


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


# ------------------------- what a three-pass product's layer keeps
# grouped KV heads of 64, the width the kernels' tile plan starts at
NEMOTRON_FLASH = {"attention": "flash", "n_head": 2, "n_kv_head": 1,
                  "head_dim": 64}


def _nemotron(remat=True, **overrides):
    return _tiny(nemotron_h, nemotron_h.nemotron_h_tiny, 2, remat=remat,
                 **{"attention": "reference", **overrides})


def _lfm2(remat=True, **overrides):
    return _tiny(lfm2, lfm2.lfm2_tiny, 2, remat=remat,
                 **{"attention": "reference", "three_pass": True,
                    **overrides})


def _smallthinker(remat=True, **overrides):
    return _tiny(smallthinker, smallthinker.smallthinker_tiny, 2, remat=remat,
                 **{"attention": "reference", **overrides})


def _kept(body, x, layer):
    """(shape, dtype) of what a checkpointed layer saves besides its
    arguments and constants (a share's bound: one int32), sorted."""
    return sorted((a.shape, str(a.dtype))
                  for a, what in saved_residuals(body, x, layer)
                  if not what.startswith(("from the argument",
                                          "from a constant")))


def _bytes(kept):
    return sum(math.prod(shape) * jnp.dtype(dtype).itemsize
               for shape, dtype in kept)


def _routing_kept(batch, seq, moe):
    """(shape, dtype) of what a routed layer keeps by the name `L.ROUTING`
    with `batch` x `seq` tokens on the device: the router's logits, the
    top-k's choice — with a softmax router's probabilities as the sort gave
    them; a sigmoid router's choice counts twice, because
    `take_along_axis`'s jit hands its indices through to its tangent's
    gather as a result of its own (one buffer to the compiler) — and the
    assignments' sorted positions, their inverse and the groups' sizes."""
    chosen = (batch, seq, moe.top_k)
    rows = batch * seq * moe.top_k
    return [((batch, seq, moe.n_experts), "float32"), (chosen, "int32"),
            (chosen, "float32" if moe.score == "softmax" else "int32"),
            ((rows,), "int32"), ((rows,), "int32"),
            ((moe.n_experts,), "int32")]


def _handed_through(batch, seq, moe):
    """Bytes `_routing_kept` lists twice (a sigmoid router's choice)."""
    return 0 if moe.score == "softmax" else batch * seq * moe.top_k * 4


@pytest.mark.parametrize("kind", ["M", "E", "*", "*-flash"])
def test_a_nemotron_layer_keeps_its_input_and_the_planned_values(
        interpreted, kind):
    kind, _, flash = kind.partition("-")
    _, cfg, params, _ = _nemotron(**(NEMOTRON_FLASH if flash else {}))
    batch, impl = 2, cfg.attention
    layer = jax.tree_util.tree_map(lambda a: a[0],
                                   params[nemotron_h.KINDS[kind]])
    x = jnp.zeros((batch, SEQ, cfg.d_model), jnp.float32)
    kept = _kept(L.remat(functools.partial(
        nemotron_h._layer_apply, kind=kind, cfg=cfg, impl=impl)), x, layer)
    q, kv = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
    want = {
        "M": [((batch, SEQ, cfg.mamba.in_proj), "float32")],
        "E": [((batch, SEQ, cfg.d_shared), "float32"),
              *_routing_kept(batch, SEQ, cfg.moe)],
        # q, k, v as they are rounded for the kernel
        "*": [((batch, SEQ, cfg.n_head, cfg.head_dim), "bfloat16"),
              ((batch, SEQ, cfg.n_kv_head, cfg.head_dim), "bfloat16"),
              ((batch, SEQ, cfg.n_kv_head, cfg.head_dim), "bfloat16")],
    }[kind]
    if flash:       # o as the model reads it, lse as the kernels do
        want += [((batch, SEQ, cfg.n_head, cfg.head_dim), "bfloat16"),
                 ((batch * cfg.n_head, SEQ), "float32")]
    assert kept == sorted(want)
    # no out-projection's result, no second product's: [B, S, d_model]
    assert all(shape != x.shape for shape, _ in kept)
    plan = nemotron_h.remat_saved_plan(cfg, batch, SEQ, flash=bool(flash))
    assert set(plan) == set(nemotron_h.KINDS)
    assert set(plan[kind]) == {L.THREE_PASS_OUT, *(
        fa.RESIDUAL_NAMES if flash else ()), *(
        [L.ROUTING] if kind == "E" else [])}
    assert sum(plan[kind].values()) == _bytes(kept) - (
        _handed_through(batch, SEQ, cfg.moe) if kind == "E" else 0)
    if kind == "*":
        assert plan[kind][L.THREE_PASS_OUT] == batch * SEQ * (q + 2 * kv) * 2


def test_an_lfm2_layer_in_three_passes_keeps_by_the_same_rule():
    """A conv operator and a dense feed-forward in ONE checkpointed layer:
    `bcu`, the operator's output (the feed-forward goes on from it, as a
    GPT-2 block from its attention output), gate and up; not `w_down`'s
    result. In one pass, the cell's program: the input alone."""
    _, cfg, params, _ = _lfm2()
    depth = next(i for i, kind in enumerate(cfg.layer_types)
                 if kind == lfm2.CONV and i < cfg.n_dense)
    x = jnp.zeros((2, SEQ, cfg.d_model), jnp.float32)

    def kept(cfg):
        return _kept(L.remat(functools.partial(
            lfm2._layer_apply, kind=lfm2.CONV, dense=True, cfg=cfg,
            impl="reference")), x, params["layers"][depth])

    rows = (2, SEQ)
    assert kept(cfg) == sorted([
        ((*rows, 3 * cfg.d_model), "float32"), ((*rows, cfg.d_model), "float32"),
        ((*rows, cfg.d_ff), "float32"), ((*rows, cfg.d_ff), "float32")])
    assert kept(dataclasses.replace(cfg, three_pass=False)) == []


def _products_with(jaxpr, weight):
    """(products of the form x·W with W of this shape, every product with
    an operand or a result as wide as W's second axis) in a jaxpr, nested
    ones too."""
    forward = every = 0
    for eqn, times, _ in _walk(jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
        every += times * any(weight[1] in shape for shape in shapes)
        forward += times * (shapes[1] == weight
                            and shapes[2][-1] == weight[1])
    return forward, every


def test_the_backward_holds_no_three_pass_product_again(monkeypatch):
    """The mixer's in-projection and the shared expert's two products (no
    other array is as wide as either): three passes forward and the
    backward's own two products each, with the policy as without remat; a
    checkpoint that keeps nothing runs the in-projection's and the shared
    expert's first three passes again (its second product nobody reads)."""
    def products(remat):
        module, cfg, params, tokens = _nemotron(remat)
        jaxpr = jax.make_jaxpr(_loss_and_grads(module, cfg, None))(
            params, tokens).jaxpr
        return {name: _products_with(jaxpr, (cfg.d_model, width))
                for name, width in (("w_in", cfg.mamba.in_proj),
                                    ("shared_w1", cfg.d_shared))}, cfg

    plain, cfg = products(remat=False)
    mixers, routed = cfg.pattern.count("M"), cfg.pattern.count("E")
    assert plain == {"w_in": (3 * mixers, 5 * mixers),
                     "shared_w1": (3 * routed, 10 * routed)}
    assert products(remat=True)[0] == plain
    monkeypatch.setattr(L, "remat", jax.checkpoint)
    assert products(remat=True)[0] == {
        "w_in": (6 * mixers, 8 * mixers),
        "shared_w1": (6 * routed, 13 * routed)}


def test_three_pass_plan_at_the_nemotron_cells_shapes():
    """nemotronh9l-b1s8k: 1 x 8,192, four mixers, four routed layers, one
    attention layer of 32 query heads on 2 KV heads of 128."""
    o, lse = fa.RESIDUAL_NAMES
    cfg = nemotron_h.nemotron_twotower_30b_a3b_9l()
    plan = nemotron_h.remat_saved_plan(cfg, 1, 8192)
    assert plan == {
        "M": {L.THREE_PASS_OUT: 337_641_472},
        # logits [8192, 128] float32; 49,152 choices, sorted positions and
        # their inverse, int32; 128 sizes
        "E": {L.THREE_PASS_OUT: 121_634_816,
              L.ROUTING: 4_194_304 + 3 * 196_608 + 512},
        "*": {L.THREE_PASS_OUT: 75_497_472, o: 67_108_864, lse: 1_048_576}}
    by_name = {}
    for kind in cfg.pattern:
        for name, size in plan[kind].items():
            by_name[name] = by_name.get(name, 0) + size
    assert by_name == {
        L.THREE_PASS_OUT: 4 * 337_641_472 + 4 * 121_634_816 + 75_497_472,
        L.ROUTING: 4 * 4_784_640, o: 67_108_864, lse: 1_048_576}
    assert by_name[L.THREE_PASS_OUT] == 1_912_602_624


# ------------------------------------- what a routed layer keeps: its routing
D_MODEL, D_EXPERT = 64, 32
ROUTERS = {
    # softmax, the probabilities as they are (OLMoE)
    "softmax": L.MoEConfig(n_experts=8, top_k=2, norm_topk_prob=False),
    # softmax renormalised beside a gated shared expert (Qwen3-Next)
    "softmax_renormalised_shared": L.MoEConfig(
        n_experts=8, top_k=2, gate="silu", d_shared=48, shared_gate=True),
    # sigmoid scores chosen on score + bias, rescaled (Nemotron, LFM2, JoyAI)
    "sigmoid_bias": L.MoEConfig(n_experts=8, top_k=2, score="sigmoid",
                                scale=2.5, gate="silu"),
}


def _routed(router, held, three_pass=False):
    """(layer body, x, leaves, cfg): `apply_moe` alone, gated experts, the
    leaves holding `held` of the eight scored experts (None: all)."""
    cfg = dataclasses.replace(ROUTERS[router], held=held)
    params = L.init_moe(jax.random.PRNGKey(0), D_MODEL, D_EXPERT, cfg,
                        gated=True)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, D_MODEL))

    def body(x, params):
        return L.apply_moe(params, x, cfg, three_pass=three_pass)[0]

    return body, x, params, cfg


@pytest.mark.parametrize("held", [None, 2], ids=["every_expert", "a_share"])
@pytest.mark.parametrize("router", list(ROUTERS))
def test_a_routed_layer_keeps_its_input_the_old_names_and_its_routing(
        router, held):
    """Logits, choice and sort by the name `L.ROUTING`, with the bytes
    `L.routing_plan` gives; a shared expert in three passes keeps its
    products as it did (gate and up, and the last one's result, which its
    scalar gate's gradient reads); nothing else (no gathered row, no
    expert's product, no gate)."""
    three_pass = bool(ROUTERS[router].d_shared)
    body, x, params, cfg = _routed(router, held, three_pass)
    kept = _kept(L.remat(body), x, params)
    wide = [((2, SEQ, cfg.d_shared), "float32")] * 2 \
        + [(x.shape, "float32")] if three_pass else []
    assert kept == sorted(_routing_kept(2, SEQ, cfg) + wide)
    plan = L.routing_plan(2 * SEQ, cfg)
    assert list(plan) == ["logits", "top_k", "order", "inverse", "sizes"]
    assert sum(plan.values()) == _bytes(kept) - _bytes(wide) \
        - _handed_through(2, SEQ, cfg)
    # a bare checkpoint keeps the input alone
    assert _kept(jax.checkpoint(body), x, params) == []


def _routing_work(jaxpr, cfg, tokens):
    """How often a step sorts (the top-k's sort of every token's scores,
    the argsort of the assignments), scatter-adds into `[E]` (the
    bincount), scatters into `[T·K]` (the inverse permutation) and
    multiplies by the router's `wg` [D, E]."""
    found = dict.fromkeys(("sorts", "into_E", "into_TK", "by_wg"), 0)
    for eqn, times, _ in _walk(jaxpr):
        name, out = eqn.primitive.name, eqn.outvars[0].aval.shape
        if name in ("sort", "top_k"):
            found["sorts"] += times
        elif name == "scatter-add" and out == (cfg.n_experts,):
            found["into_E"] += times
        elif name == "scatter" and out == (tokens * cfg.top_k,):
            found["into_TK"] += times
        elif name == "dot_general" \
                and eqn.invars[1].aval.shape == (D_MODEL, cfg.n_experts):
            found["by_wg"] += times
    return found


@pytest.mark.parametrize("held", [None, 2], ids=["every_expert", "a_share"])
@pytest.mark.parametrize("router", list(ROUTERS))
def test_the_backward_sorts_and_scatters_no_assignment_again(router, held):
    """Loss and gradients of a routed layer: the top-k and the argsort, the
    bincount, the inverse's scatter once; the router's product forward and
    for `wg`'s gradient (x's reads the transpose) — with the policy as
    without remat. A checkpoint that keeps nothing does each once more."""
    body, x, params, cfg = _routed(router, held)

    def work(wrap):
        return _routing_work(jax.make_jaxpr(jax.grad(
            lambda x, p: jnp.sum(wrap(body)(x, p)), argnums=(0, 1)))(
                x, params).jaxpr, cfg, 2 * SEQ)

    plain = work(lambda body: body)
    assert plain == {"sorts": 2, "into_E": 1, "into_TK": 1, "by_wg": 2}
    assert work(L.remat) == plain
    assert work(jax.checkpoint) == {"sorts": 4, "into_E": 2, "into_TK": 2,
                                    "by_wg": 3}


def test_routing_plan_at_the_routed_remat_cells_shapes():
    """A routed layer's kept routing in the five cells that checkpoint
    one, bytes a layer: E 64–512, 49,152–163,840 assignments."""
    cells = {
        "qwen3next4l-b2s8k": (qwen3_next.qwen3_next_80b_a3b_4l, 16_384),
        "joyaiflash5l-b2s8k": (joyai.joyai_llm_flash_5l, 16_384),
        "nemotronh9l-b1s8k": (nemotron_h.nemotron_twotower_30b_a3b_9l, 8_192),
        "lfm2moe5l-b2s8k": (lfm2.lfm2_24b_a2b_5l, 16_384),
        "smallthinker4l-b1s16k": (smallthinker.smallthinker_21b_a3b_4l,
                                  16_384),
    }
    plans = {cell: L.routing_plan(tokens, preset().moe)
             for cell, (preset, tokens) in cells.items()}
    assert plans["qwen3next4l-b2s8k"] == {      # softmax, 10 of 512
        "logits": 33_554_432, "top_k": 1_310_720, "order": 655_360,
        "inverse": 655_360, "sizes": 2_048}
    assert {cell: sum(plan.values()) for cell, plan in plans.items()} == {
        "qwen3next4l-b2s8k": 36_177_920,
        "joyaiflash5l-b2s8k": 18_351_104,       # sigmoid, 8 of 256
        "nemotronh9l-b1s8k": 4_784_640,         # sigmoid, 6 of 128
        "lfm2moe5l-b2s8k": 4_980_992,           # sigmoid, 4 of 64
        "smallthinker4l-b1s16k": 5_767_424}     # softmax, 6 of 64


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
    "nemotron_tiny": ({}, lambda: (*_nemotron(), {})),
    "nemotron_tiny_flash": ({}, lambda: (*_nemotron(**NEMOTRON_FLASH), {})),
    "lfm2_tiny_three_pass": ({}, lambda: (*_lfm2(), {})),
    # a softmax router on a share, routed on the layer's input: the gates'
    # gradient by the kept choice
    "smallthinker_tiny_routed_share": ({}, lambda: (*_smallthinker(), {})),
}


@functools.cache
def _with_the_policy(case):
    """`case`'s mesh, its compiled step by `remat`, and what the step gives
    under `L.remat`: once a process, for both comparisons of the case."""
    axes, make = SAME_NUMBERS[case]
    module, cfg, params, tokens, kw = make()
    mesh = _mesh(axes)
    if mesh is not None:
        params = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, module.partition_specs(cfg))

    def run(remat):
        with _on(mesh):
            # a fresh function each time: traced under what is patched now
            return jax.jit(_loss_and_grads(
                module, dataclasses.replace(cfg, remat=remat), mesh, **kw))(
                    params, tokens)
    return run, run(remat=True)


@pytest.mark.parametrize("against", ["no_remat", "bare_checkpoint"])
@pytest.mark.parametrize("case", list(SAME_NUMBERS))
def test_remat_changes_no_number(interpreted, monkeypatch, case, against):
    """Loss and gradients with `L.remat` against those with remat off, and
    against those of a `jax.checkpoint` that keeps nothing."""
    run, (got, got_grads) = _with_the_policy(case)
    if against == "bare_checkpoint":
        monkeypatch.setattr(L, "remat", jax.checkpoint)
    want, want_grads = run(remat=against == "bare_checkpoint")
    assert abs(float(got) - float(want)) <= 1e-6 * abs(float(want))
    errors = jax.tree_util.tree_map(
        lambda g, w: float(jnp.linalg.norm(g - w)
                           / jnp.maximum(jnp.linalg.norm(w), 1e-30)),
        got_grads, want_grads)
    for path, err in jax.tree_util.tree_leaves_with_path(errors):
        assert err <= 1e-6, (jax.tree_util.keystr(path), err)


# ------------------------------------- nothing where remat is off
NAMED = {
    "gpt2": (lambda: (gpt2, *_gpt2(4, attention="flash", remat=False)),
             (*fa.RESIDUAL_NAMES, L.ATTENTION_OUT)),
    # attention in three passes with remat off: the cell olmoe1l-b2s4k
    "olmoe_three_pass": (lambda: _olmoe(False, **OLMOE_FLASH),
                         (*fa.RESIDUAL_NAMES, L.ATTENTION_OUT,
                          L.THREE_PASS_OUT, L.ROUTING)),
}


@pytest.mark.parametrize("case", list(NAMED))
def test_names_lower_to_nothing_without_remat(interpreted, monkeypatch, case):
    make, names = NAMED[case]
    module, cfg, params, tokens = make()

    def lowered():
        # a fresh function each time: nothing traced before is reused
        f = _loss_and_grads(module, cfg, None)
        text = jax.jit(f).lower(params, tokens).as_text()
        # private functions are numbered by a counter of the process
        return (str(jax.make_jaxpr(f)(params, tokens)),
                re.sub(r"@(\w+?)_\d+\b", r"@\1", text))

    named_jaxpr, named = lowered()
    # every module that names a value reads its own global: a holder left
    # out shows as its name still in `bare_jaxpr`
    for holder in (core, attention, moe, fa):
        monkeypatch.setattr(holder, "checkpoint_name", lambda x, name: x)
    bare_jaxpr, bare = lowered()
    for name in names:
        assert f"name={name}" in named_jaxpr
        assert f"name={name}" not in bare_jaxpr
    assert named == bare
