"""A cell's run with the program computing BELOW the precision its
configuration states, through the benchmark's own command, so that the
harness's comparison itself says whether `correct` would catch it:

    python3 -m benchmarks.precision_control <control> \\
        --workload <cell> --seed <n> --seconds <s> --trace 0

from the root of a checkout, on the machine with the chip. Everything behind
<control> is `chipbench.run`'s; the cell is resolved as ever and only its
configuration's `entry` is pointed here, so the worker builds the same
preset, state and step and calls THIS module's `loss_fn`, in the timed steps
and in the comparison alike. The result line is the benchmark's: a control
that the comparison catches prints `"correct": false`, with the errors
beside their limits in the `notes:` line (`check.errors`).

Controls (for `ray_tpu.models.smallthinker`, PR 36, `ray_tpu.models.lfm2`,
PR 40, `ray_tpu.models.joyai`, PR 43, `ray_tpu.models.qwen3_next`, PR 48,
`ray_tpu.models.phi4_flash`, PR 51, `ray_tpu.models.keye_vl2`, PR 56, and
`ray_tpu.models.kimi_linear`, PR 58: `_PROGRAMS`; a preset's module is
the one whose name, or whose entry in `_PRESETS`, its own starts with):
  e4m3         every matmul weight (attention's four projections, latent
               attention's five or differential attention's fused two, a
               conv operator's, a delta layer's or a Mamba-1 mixer's in-, x-,
               dt- and out-projections, a KDA layer's seven (both low-rank
               pairs among them), a gated memory unit's two, a dense, a shared or
               an expert's three matrices, a prediction module's `eh_proj`,
               the head — the table where it is tied) rounded to an 8-bit float
               forward, the gradient straight through: `chipbench/compare.py`'s
               standing control, the nearest precision below bf16 products
  bf16_stream  the residual stream rounded to bf16 at every layer's input,
               as a model that carries it in the compute dtype would
  bf16_state   (a program that runs `ops.kda`'s rule) the state the rule
               carries from chunk to chunk rounded to bf16 behind every
               chunk, as a rule that keeps it in the compute dtype would.
               The rounding patches `kda._chunk`, which the kernels `kda_fwd`
               / `kda_bwd` never call: the control takes the rule's PLAIN
               form for its run wherever it runs (and says so, `PLAIN_FORM`),
               so its `correct` is the rounded carry's and its speed is not
               the cell's
  one_pass     (a preset with `three_pass`) every product ahead of a router
               in ONE bf16 pass: what the extra passes buy the comparison
  stated       no change: the wrapper alone, which must read as the cell does

The parent process never imports JAX (the chip is the worker's); this
module's top level imports nothing of the program.
"""
import contextlib
import dataclasses
import functools
import importlib
import sys

CONTROLS = ("e4m3", "bf16_stream", "bf16_state", "one_pass", "stated")
PLAIN_FORM = ("precision_control: bf16_state runs `ops.kda`'s rule in its "
              "PLAIN form (the kernels kda_fwd / kda_bwd never call "
              "`kda._chunk`, which the control rounds): `correct` is the "
              "rounded carry's, the speed is the plain form's")
# program module -> (its config class, the function that applies one layer
# to the stream: `(x, layer's leaves, **static)`)
_PROGRAMS = {
    "ray_tpu.models.smallthinker": ("SmallThinkerConfig", "_block_apply"),
    "ray_tpu.models.lfm2": ("Lfm2Config", "_layer_apply"),
    "ray_tpu.models.joyai": ("JoyaiConfig", "_layer_apply"),
    # a layer is two checkpoints there; its first half reads the stream
    "ray_tpu.models.qwen3_next": ("Qwen3NextConfig", "_mixer_apply"),
    "ray_tpu.models.phi4_flash": ("Phi4FlashConfig", "_layer_apply"),
    # two checkpoints a layer, as Qwen3-Next's
    "ray_tpu.models.keye_vl2": ("KeyeVL2Config", "_attn_apply"),
    "ray_tpu.models.kimi_linear": ("KimiLinearConfig", "_mixer_apply"),
}
# presets that are not named after their module: prefix -> module
_PRESETS = {"phi_4_mini_flash": "ray_tpu.models.phi4_flash"}
_MATMUL_LEAVES = {"wq", "wk", "wv", "wo", "w_in", "w_out", "w_gate", "w_up",
                  "w_down", "head", "wq_a", "wq_b", "wkv_a", "wkv_b",
                  "eh_proj", "w_ba", "w_qkv", "w_q", "w_o", "w_x", "w_dt",
                  "w_f_down", "w_f_up", "w_g_down", "w_g_up", "w_beta"}


# ---------------------------------------------------------------- worker side
def _module_of(preset: str) -> str:
    """`smallthinker_tiny` -> `ray_tpu.models.smallthinker`."""
    for module in _PROGRAMS:
        if preset.startswith(module.rpartition(".")[2] + "_"):
            return module
    for prefix, module in _PRESETS.items():
        if preset.startswith(prefix):
            return module
    raise AttributeError(preset)


@functools.cache
def _config_class(module: str):
    program = importlib.import_module(module)
    return dataclasses.make_dataclass(
        "ControlConfig", [("control", str, "stated"),
                          ("program", str, module)],
        bases=(getattr(program, _PROGRAMS[module][0]),), frozen=True)


def __getattr__(name):
    """The entry's preset, `<control>__<the program's preset>`: that preset
    with the control written into it."""
    control, _, preset = name.partition("__")
    if control not in CONTROLS or not preset:
        raise AttributeError(name)
    module = _module_of(preset)

    def controlled():
        base = getattr(importlib.import_module(module), preset)()
        if control == "one_pass":
            base = dataclasses.replace(base, three_pass=False)
        return _config_class(module)(control=control, program=module, **{
            f.name: getattr(base, f.name) for f in dataclasses.fields(base)})
    return controlled


def init(rng, cfg):
    return importlib.import_module(cfg.program).init(rng, cfg)


def partition_specs(cfg):
    return importlib.import_module(cfg.program).partition_specs(cfg)


def _e4m3(a):
    """`a` rounded to the nearest float8_e4m3fn value (3 bits of mantissa,
    least normal 2^-6, subnormals in steps of 2^-9, largest 448), by
    ARITHMETIC: the TPU compiler drops a cast there and back
    (`xla_allow_excess_precision` — PR 36's first chip call of this control
    read the program's own errors). Equal to the cast on every value tried
    (`tests/chipbench_tests/test_zz_chipbench_smallthinker.py`)."""
    import jax.numpy as jnp

    a32 = a.astype(jnp.float32)
    _, e = jnp.frexp(a32)                    # |a| = m · 2^e, m in [0.5, 1)
    step = jnp.ldexp(jnp.float32(1), jnp.maximum(e - 1, -6) - 3)
    return jnp.clip(jnp.round(a32 / step) * step, -448.0, 448.0
                    ).astype(a.dtype)


def _eight_bit(params):
    """Every leaf named as a matmul weight, wherever it lies in the tree;
    the embedding table too where it is the head (no `head` leaf)."""
    import jax

    names = _MATMUL_LEAVES | (set() if "head" in params else {"wte"})

    def cast(path, a):  # straight-through: the gradient passes unrounded
        if path[-1].key not in names:
            return a
        return a + jax.lax.stop_gradient(_e4m3(a) - a)
    return jax.tree_util.tree_map_with_path(cast, params)


@contextlib.contextmanager
def _rounded_stream(program, layer_fn: str):
    """Every layer reads the stream rounded to bf16 (`reduce_precision`: a
    cast there and back the TPU compiler may drop)."""
    import jax

    apply = getattr(program, layer_fn)

    def rounded(x, layer, *side, **kw):
        return apply(jax.lax.reduce_precision(x, 8, 7), layer, *side, **kw)
    setattr(program, layer_fn, rounded)
    try:
        yield
    finally:
        setattr(program, layer_fn, apply)


@contextlib.contextmanager
def _rounded_state():
    """`ops.kda`'s rule hands every chunk a state rounded to bf16, forward
    and in its own backward's rebuilding alike — in its PLAIN form, which
    the control takes on the chip too: the kernels carry their state in
    VMEM and never call `kda._chunk`."""
    import jax

    from ray_tpu.ops import kda

    chunk, use_kernel = kda._chunk, kda._use_kernel

    def rounded(state, *inputs, **kw):
        after, out = chunk(state, *inputs, **kw)
        return jax.lax.reduce_precision(after, 8, 7), out
    kda._chunk, kda._use_kernel = rounded, lambda *shape: False
    print(PLAIN_FORM, file=sys.stderr, flush=True)
    try:
        yield
    finally:
        kda._chunk, kda._use_kernel = chunk, use_kernel


def loss_fn(params, batch, cfg, mesh=None):
    program = importlib.import_module(cfg.program)
    if cfg.control == "e4m3":
        params = _eight_bit(params)
    with (_rounded_stream(program, _PROGRAMS[cfg.program][1])
          if cfg.control == "bf16_stream" else _rounded_state()
          if cfg.control == "bf16_state" else contextlib.nullcontext()):
        return program.loss_fn(params, batch, cfg, mesh)


# ---------------------------------------------------------------- parent side
def controlled_entry(model: dict, control: str) -> dict:
    """The configuration with its `entry` pointed at this module."""
    module, preset = model["entry"].split(":")
    if module not in _PROGRAMS:
        raise SystemExit(f"precision_control: no control for {module}")
    return dict(model,
                entry=f"benchmarks.precision_control:{control}__{preset}")


def main(argv) -> int:
    from chipbench import catalog, run

    if not argv or argv[0] not in CONTROLS:
        print(f"usage: precision_control {'|'.join(CONTROLS)} "
              f"<chipbench.run's arguments>", file=sys.stderr)
        return 2
    if argv[0] == "bf16_state":
        print(PLAIN_FORM, flush=True)
    resolve = catalog.resolve_cell

    def resolved(manifest, cell, group, *a, **kw):
        out = resolve(manifest, cell, group, *a, **kw)
        out["model"] = controlled_entry(out["model"], argv[0])
        return out
    catalog.resolve_cell = resolved
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
