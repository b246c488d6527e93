"""Device time by the program's own names (`compile_watch.scope_table`):
the tiny GPT-2 step with and without remat, the tiny Nemotron-H step, the
tiny JoyAI step (a prediction module whose layer keeps the trunk's scope
names inside `mtp`) and the tiny Qwen3-Next step (the delta layer's four
stages inside `gdn`, the output gate inside `attn`), compiled through
`make_train_step` on the CPU, and the parser on hand-written `op_name`s.

The persistent compile cache is off for this file: its key leaves metadata
out, so a step compiled before a scope existed would be loaded with the
old `op_name`s — the staleness `scope_table` answers with None, shown here
on purpose by one test and kept away from the others."""
import dataclasses
import gc
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu._private import telemetry
from ray_tpu.models import gpt2, joyai, nemotron_h, qwen3_next
from ray_tpu.parallel import compile_watch
from ray_tpu.parallel.compile_watch import CompiledFunction, parse_op_name
from ray_tpu.parallel.train_step import (
    default_optimizer,
    make_train_state,
    make_train_step,
)

# every scope each model declares, itself or through `layers` / `ops.ssd`
GPT2_SCOPES = {"embed", "blocks", "attention", "mlp", "loss_tail",
               "optimizer"}
NEMOTRON_SCOPES = {"embed", "blocks", "mamba", "conv", "gate_norm", "ssd",
                   "intra", "states", "carry", "readout", "attn", "moe",
                   "router", "dispatch", "experts", "combine",
                   "shared_expert", "loss_tail", "optimizer"}
JOYAI_SCOPES = {"embed", "blocks", "attention", "latent_proj", "mlp", "moe",
                "router", "dispatch", "experts", "combine", "shared_expert",
                "mtp", "loss_tail", "optimizer"}
QWEN3_NEXT_SCOPES = {"embed", "blocks", "gdn", "delta_proj", "delta_conv",
                     "delta_rule", "delta_gate_norm", "attn", "attn_gate",
                     "moe", "router", "dispatch", "experts", "combine",
                     "shared_expert", "loss_tail", "optimizer"}
STEPS = {
    "gpt2": (gpt2, gpt2.gpt2_tiny, False, GPT2_SCOPES),
    "gpt2-remat": (gpt2, gpt2.gpt2_tiny, True, GPT2_SCOPES),
    "nemotron-h-remat": (nemotron_h, nemotron_h.nemotron_h_tiny, True,
                         NEMOTRON_SCOPES),
    "joyai-remat": (joyai, joyai.joyai_tiny, True, JOYAI_SCOPES),
    "qwen3-next-remat": (qwen3_next, qwen3_next.qwen3_next_tiny, True,
                         QWEN3_NEXT_SCOPES),
}


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


_BUILT = {}


def _built(case):
    """(the step, its table, its compiled text) of one case, built when a
    test first asks for it and once a process: one real step run, ONE
    compile. The table is built from what the compile MISS left behind;
    the text comes from the arguments themselves, lowered before the call
    donates the state and compiled after it, when the executable is
    already in JAX's in-process cache (as the table's own is)."""
    if case not in _BUILT:
        module, preset, remat, _ = STEPS[case]
        cfg = dataclasses.replace(preset(), remat=remat)
        opt = default_optimizer()
        state = make_train_state(lambda rng: module.init(rng, cfg),
                                 jax.random.PRNGKey(0), opt)
        step = make_train_step(
            lambda p, b: module.loss_fn(p, b, cfg), opt)
        tokens = jnp.zeros((2, 65), jnp.int32)
        lowered = step.lower(state, {"tokens": tokens})
        step(state, {"tokens": tokens})
        _BUILT[case] = (step, step.scope_table(),
                        lowered.compile().as_text())
    return _BUILT[case]


def _every_scope_the_model_declares_is_in_the_table(case, step, table,
                                                    text):
    found = {name for scopes, _ in table.values() for name in scopes}
    assert STEPS[case][3] <= found, STEPS[case][3] - found
    # and nothing of JAX's own structure got through as a scope
    assert not found & {"jit", "jvp", "transpose", "checkpoint", "while",
                        "body", "cond", "closed_call",
                        "rematted_computation", "step"}
    assert not any(re.match(r"branch_\d+", name) for name in found)


def _the_four_phases_and_no_recompute_without_remat(case, step, table,
                                                    text):
    phases = {phase for _, phase in table.values()}
    want = {"forward", "backward", "optimizer"}
    assert phases == (want | {"recompute"} if STEPS[case][2] else want)
    # the optimizer's phase is the optimizer's scope, and only it
    for scopes, phase in table.values():
        assert (phase == "optimizer") == ("optimizer" in scopes)


def _computations(text):
    """{computation name: its ROOT instruction's opcode} and every
    instruction of the text as (name, opcode, called computation)."""
    roots, instructions, inside = {}, [], None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            inside = head.group(1)
            continue
        m = re.match(r"^\s*(ROOT )?%?([\w.\-]+) = .*?\s([\w\-]+)\(", line)
        if not m:
            continue
        if m.group(1):
            roots[inside] = m.group(3)
        calls = re.search(r"calls=%?([\w.\-]+)", line)
        instructions.append((m.group(2), m.group(3),
                             calls.group(1) if calls else None))
    return roots, instructions


def _every_matmul_lies_under_a_scope(case, step, table, text):
    roots, instructions = _computations(text)
    matmul = {"dot", "convolution"}
    seen = 0
    for name, opcode, calls in instructions:
        if opcode in matmul or (opcode == "fusion"
                                and roots.get(calls) in matmul):
            seen += 1
            assert table[name][0], (name, opcode)
    assert seen >= 10


def _the_table_is_of_the_program_that_ran(case, step, table, text):
    """Lowering the kept abstract arguments (the state was donated by
    then) gives the text that lowering the arguments themselves gave."""
    assert table == compile_watch.scope_table_of(text)
    assert step.scope_table() is table      # built once


CHECKS = {check.__name__.lstrip("_"): check for check in (
    _every_scope_the_model_declares_is_in_the_table,
    _the_four_phases_and_no_recompute_without_remat,
    _every_matmul_lies_under_a_scope,
    _the_table_is_of_the_program_that_ran)}


# the upper decorator varies fastest: a case's four checks are collected
# side by side, so that the workers of a parallel run, which are handed
# neighbouring tests, build few cases each
@pytest.mark.parametrize("check", sorted(CHECKS))
@pytest.mark.parametrize("case", sorted(STEPS))
def test_the_steps_table(case, check):
    CHECKS[check](case, *_built(case))


OP_NAMES = {
    "forward, a scope inside jvp(), a jit() dropped":
        ("jit(step)/jvp(mamba)/conv/jit(silu)/logistic",
         ("mamba", "conv"), "forward"),
    "backward, the scope inside transpose(jvp())":
        ("jit(step)/transpose(jvp(loss_tail))/dot_general",
         ("loss_tail",), "backward"),
    "recompute, an einsum's own scope dropped":
        ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
         "moe/router/bsd,de->bse/dot_general", ("moe", "router"),
         "recompute"),
    "backward of a checkpointed block is not its recomputation":
        ("jit(step)/transpose(jvp(jvp()))/checkpoint/mamba/gate_norm/mul",
         ("mamba", "gate_norm"), "backward"),
    "a switch's branch and a custom_vjp rule's own vjp":
        ("jit(step)/transpose(jvp(jvp()))/checkpoint/moe/cond/branch_0_fun/"
         "transpose(jvp(jit(_through_experts)))/experts/mul",
         ("moe", "experts"), "backward"),
    "a scan's while/body/closed_call":
        ("jit(step)/jvp(blocks)/while/body/closed_call/attention/"
         "bsd,dhk->bshk/dot_general", ("blocks", "attention"), "forward"),
    "the loop's own slices have the loop's scope":
        ("jit(step)/transpose(jvp(blocks))/while/body/dynamic_update_slice",
         ("blocks",), "backward"),
    "the optimizer":
        ("jit(step)/optimizer/jit(clip)/max", ("optimizer",), "optimizer"),
    "a scope with a slash in it, wrapped":
        ("jit(step)/jvp(ssd/intra)/mul", ("ssd", "intra"), "forward"),
    "shard_map":
        ("jit(step)/jvp(blocks)/shard_map/while/body/closed_call/mlp/"
         "dot_general", ("blocks", "mlp"), "forward"),
    "a loop's predicate":
        ("jit(step)/jvp(blocks)/while/body_pred/lt", ("blocks",), "forward"),
    "no scope at all": ("jit(step)/jvp()/slice", (), "forward"),
    "a transposed instruction outside every scope":
        ("jit(step)/transpose(jvp())/add_any", (), "backward"),
    "what the compiler named itself": ("reduce_sum", (), "forward"),
}


@pytest.mark.parametrize("kind", sorted(OP_NAMES))
def test_parse_op_name(kind):
    op_name, scopes, phase = OP_NAMES[kind]
    assert parse_op_name(op_name) == (scopes, phase)


HAND_WRITTEN = """HloModule jit_step

%fused_computation.7 (param_0.1: f32[8,16]) -> f32[128] {
  %param_0.1 = f32[8,16]{1,0} parameter(0)
  %mul.3 = f32[8,16]{1,0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(step)/transpose(jvp(loss_tail))/mul" stack_frame_id=4}
  ROOT %bitcast.9 = f32[128]{0} bitcast(%mul.3)
}

%fused_computation.8 (param_0.2: f32[128]) -> f32[128] {
  %param_0.2 = f32[128]{0} parameter(0)
  ROOT %scatter.1 = f32[128]{0} negate(%param_0.2)
}

ENTRY %main.1 (p: f32[8,16]) -> f32[128] {
  %p = f32[8,16]{1,0} parameter(0), metadata={op_name="state.params[\\'w\\']"}
  %multiply_bitcast_fusion = f32[128]{0} fusion(%p), kind=kLoop, calls=%fused_computation.7, backend_config={"flag_configs":[]}
  %fusion.2 = f32[128]{0} fusion(%multiply_bitcast_fusion), kind=kCustom, calls=%fused_computation.8
  %copy.5 = f32[128]{0} copy(%fusion.2)
  ROOT %fusion.3 = f32[128]{0} fusion(%copy.5), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(step)/optimizer/add"}
}
"""


def test_a_fusion_without_a_name_takes_the_last_inside_it():
    table = compile_watch.scope_table_of(HAND_WRITTEN)
    # the compiler wrapped the fused root in a bitcast of its own
    assert table["multiply_bitcast_fusion"] == (("loss_tail",), "backward")
    assert table["mul.3"] == (("loss_tail",), "backward")
    assert table["fusion.3"] == (("optimizer",), "optimizer")
    # nothing inside to read, and a copy the compiler made: not in the table
    assert "fusion.2" not in table and "copy.5" not in table
    assert table["p"] == ((), "forward")    # an argument's name: no scope
    # without the optimizer's instruction the same text is no table
    assert compile_watch.scope_table_of(
        HAND_WRITTEN.replace("/optimizer/", "/")) is None


def test_no_table_for_a_program_without_the_optimizer_scope():
    def f(x):
        with jax.named_scope("attention"):
            return jnp.sum(jnp.sin(x) * 2.0)

    fn = CompiledFunction(jax.jit(jax.grad(f)), "scope_table_none")
    assert fn.scope_table() is None         # nothing compiled yet
    fn(jnp.ones((8, 8)))
    assert fn._abstract is not None
    assert fn.scope_table() is None
    # the same text with the step's scope on one instruction is a table
    text = fn.lower(jnp.ones((8, 8))).compile().as_text()
    assert compile_watch.scope_table_of(text) is None
    assert "attention" in text
    scoped = text.replace('op_name="jit(f)/', 'op_name="jit(f)/optimizer/', 1)
    assert compile_watch.scope_table_of(scoped) is not None


def test_a_hit_records_nothing_and_a_new_miss_replaces_the_table():
    def f(x):
        with jax.named_scope("optimizer"):
            return x * 2.0 + 1.0

    fn = CompiledFunction(jax.jit(f), "scope_table_hit")
    fn(jnp.ones((4,)))
    kept, table = fn._abstract, fn.scope_table()
    assert table is not None
    (leaf,), _ = kept
    assert isinstance(leaf, jax.ShapeDtypeStruct) and leaf.shape == (4,)
    fn(jnp.zeros((4,)))                     # a hit
    assert fn._abstract is kept and fn.scope_table() is table
    fn(jnp.ones((8,)))                      # a miss: the newest call's
    assert fn._abstract is not kept
    assert fn._abstract[0][0].shape == (8,)
    assert fn.scope_table() is not table


def test_nothing_is_kept_with_telemetry_off(monkeypatch):
    monkeypatch.setattr(telemetry, "ENABLED", False)

    def f(x):
        with jax.named_scope("optimizer"):
            return x + 1.0

    fn = CompiledFunction(jax.jit(f), "scope_table_off")
    fn(jnp.ones((4,)))
    assert fn._abstract is None and fn.scope_table() is None


def test_compiled_finds_the_newest_live_function_of_a_name():
    assert compile_watch.compiled("scope_table_nobody") is None
    first = CompiledFunction(jax.jit(lambda x: x), "scope_table_named")
    second = CompiledFunction(jax.jit(lambda x: x), "scope_table_named")
    other = CompiledFunction(jax.jit(lambda x: x), "scope_table_other")
    assert compile_watch.compiled("scope_table_named") is second
    assert compile_watch.compiled("scope_table_other") is other
    del second
    gc.collect()
    assert compile_watch.compiled("scope_table_named") is first
    step = make_train_step(lambda p, b: (jnp.sum(p), {}), default_optimizer())
    assert compile_watch.compiled("train_step") is step
