"""`ops.mamba_stages`: the mixer's conv and gate-norm stages. The Pallas
kernels in the interpreter, on the CPU at small sizes, against the plain
forms (forward and every gradient, float32); causality and the batch rows'
independence; the predicates that choose the path; `apply_mamba` against
the parent's body; the whole model on the kernels under the remat policy;
how often a kernel's body is traced for a step (the start-up budget); what
`stage_plan` says the calls move; and the calls' event text before the
benchmark's readers that go by shape."""
import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import catalog, compare, flops
from chipbench.readers import trace_held, trace_ssm
from ray_tpu.models import layers, nemotron_h
from ray_tpu.ops import mamba_stages as stages
from ray_tpu.ops import ssd, target
from tests.test_ssd_kernels import _event_text
from tests.test_zz_tp_overlap import _walk

# (batch, tokens, inner, groups, state, taps, conv block, gate tokens): two
# and four token blocks a sequence, one and several channel blocks, the
# cell's four taps and LFM2's three
SHAPES = [
    (2, 512, 64, 2, 16, 4, (8, 256), (128, 256)),
    (1, 512, 128, 1, 64, 4, (128, 128), (256, 128)),
    (2, 256, 32, 4, 8, 3, (16, 128), (128, 128)),
    (1, 384, 64, 2, 32, 2, (64, 128), (128, 384)),
]
KERNEL_TINY = dataclasses.replace(
    nemotron_h.nemotron_h_tiny(), mamba_heads=8, mamba_head_dim=64,
    n_groups=1, d_state=128, chunk=128, attention="reference")


def _pallas_calls(jaxpr, prefix="mamba_"):
    """{name: [its `pallas_call` equations]} in a jaxpr, nested ones too."""
    found = {}
    for eqn, *_ in _walk(jaxpr):
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"].startswith(prefix)):
            found.setdefault(eqn.params["name"], []).append(eqn)
    return found


@pytest.fixture
def blocks(monkeypatch):
    def use(conv, gate):
        monkeypatch.setattr(stages, "CONV_BLOCK", conv)
        monkeypatch.setattr(stages, "GATE_TOKENS", gate)
        jax.clear_caches()      # the jits' cache does not key on the blocks
    yield use
    jax.clear_caches()


def _inputs(batch, tokens, inner, groups, state, taps, seed=0):
    conv = inner + 2 * groups * state
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return {
        "src": jax.random.normal(ks[0], (batch, tokens, inner + conv + 8)),
        "w": jax.random.uniform(ks[1], (taps, conv), minval=-0.5, maxval=0.5),
        "b": 0.1 * jax.random.normal(ks[2], (conv,)),
        "y": jax.random.normal(ks[3], (batch, tokens, inner)),
        "scale": 1.0 + 0.1 * jax.random.normal(ks[4], (inner,)),
    }, (jax.random.normal(ks[5], (batch, tokens, conv)),
        jax.random.normal(ks[6], (batch, tokens, inner)))


def _conv(i, inner, **how):
    return stages.conv_silu(i["src"], i["w"], i["b"], start=inner, **how)


def _gate(i, groups, **how):
    return stages.gate_norm(i["y"], i["src"], i["scale"], groups=groups,
                            eps=1e-5, **how)


def _conv_plain(i, inner):
    conv = i["w"].shape[1]
    return stages._conv_plain(i["src"][..., inner:inner + conv], i["w"],
                              i["b"])


def _gate_plain(i, groups):
    inner = i["y"].shape[2]
    return stages._gate_plain(i["y"], i["src"][..., :inner], i["scale"],
                              groups, 1e-5)


def _value_and_grads(fn, inputs, weight):
    return jax.jit(lambda i: (fn(i), jax.grad(
        lambda i: jnp.sum(weight * fn(i)))(i)))(inputs)


@pytest.mark.parametrize("stage", ["conv", "gate_norm"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[1]}x{s[2]}k{s[5]}")
def test_kernels_are_the_plain_stage_in_float32(stage, shape, blocks):
    """Same arithmetic, two programs: float32 rounding alone separates
    them, in the output and in every gradient — the source's columns (zero
    outside the stage's own), taps, bias; y, z's columns, scale."""
    *sizes, conv_block, gate_tokens = shape
    inner, groups = sizes[2], sizes[3]
    blocks(conv_block, gate_tokens)
    inputs, (d_conv, d_gate) = _inputs(*sizes)
    if stage == "conv":
        got = _value_and_grads(lambda i: _conv(i, inner, interpret=True),
                               inputs, d_conv)
        want = _value_and_grads(lambda i: _conv_plain(i, inner), inputs,
                                d_conv)
        used = ("src", "w", "b")
    else:
        got = _value_and_grads(lambda i: _gate(i, groups, interpret=True),
                               inputs, d_gate)
        want = _value_and_grads(lambda i: _gate_plain(i, groups), inputs,
                                d_gate)
        used = ("y", "src", "scale")
    assert got[0].dtype == jnp.float32 and got[0].shape == want[0].shape
    assert compare.rel_l2(got[0], want[0]) <= 2e-6
    for leaf in used:
        assert got[1][leaf].shape == inputs[leaf].shape
        assert compare.rel_l2(got[1][leaf], want[1][leaf]) <= 5e-6, leaf
    untouched = [leaf for leaf in inputs if leaf not in used]
    assert all(not np.any(got[1][leaf]) for leaf in untouched)


@pytest.mark.parametrize("interpret", [True, False],
                         ids=["kernels", "plain"])
def test_conv_is_causal_from_zeros_and_rows_are_independent(interpret,
                                                            blocks):
    """A sequence's first K − 1 positions see zeros before them, not the
    row before or the block's halo; nothing reads a later token — also
    across a token block's edge — and a batch row reads no other."""
    batch, tokens, inner, groups, state, taps = 2, 512, 64, 2, 16, 4
    blocks((8, 256), (128, 256))
    inputs, _ = _inputs(batch, tokens, inner, groups, state, taps)
    how = {"interpret": True} if interpret else {}
    out = _conv(inputs, inner, **how)
    x = inputs["src"][..., inner:inner + inputs["w"].shape[1]]
    for t in range(taps - 1):               # by hand: the taps that exist
        pre = sum(inputs["w"][taps - 1 - j] * x[:, t - j]
                  for j in range(t + 1)) + inputs["b"]
        np.testing.assert_allclose(out[:, t], jax.nn.silu(pre), rtol=2e-6,
                                   atol=2e-6)
    for at in (5, 255, 256, 300):           # a block's edge is at 256
        moved = dict(inputs, src=inputs["src"].at[0, at].add(1.0))
        other = _conv(moved, inner, **how)
        assert np.array_equal(other[0, :at], out[0, :at])
        assert not np.array_equal(other[0, at], out[0, at])
        assert np.array_equal(other[0, at + taps:], out[0, at + taps:])
        assert np.array_equal(other[1], out[1])
    gated = _gate(inputs, groups, **how)
    moved = dict(inputs, y=inputs["y"].at[1, 7].add(1.0))
    other = _gate(moved, groups, **how)
    assert np.array_equal(other[0], gated[0])
    assert np.array_equal(np.delete(other[1], 7, 0), np.delete(gated[1], 7, 0))


def test_the_backward_reads_no_earlier_cotangent_and_no_other_row(blocks):
    """The conv's backward walks the blocks in reverse with a carry: a
    token's cotangent reaches the K − 1 tokens BEFORE it and none after,
    across a block's edge as inside one, in its own row alone."""
    batch, tokens, inner, groups, state, taps = 2, 512, 64, 2, 16, 4
    blocks((8, 256), (128, 256))
    inputs, (d_conv, _) = _inputs(batch, tokens, inner, groups, state, taps)
    grad = jax.jit(lambda i, d: jax.vjp(
        lambda s: _conv(dict(i, src=s), inner, interpret=True),
        i["src"])[1](d)[0])
    base = grad(inputs, d_conv)
    for at in (3, 255, 256, 258):
        moved = grad(inputs, d_conv.at[0, at].add(1.0))
        changed = np.any(np.asarray(moved != base), axis=-1)
        assert not changed[1].any()
        assert list(np.flatnonzero(changed[0])) == list(
            range(max(at - taps + 1, 0), at + 1))


PREDICATES = [
    # platform, devices, tokens, start, channels, taps -> conv kernel?
    ("conv", ("tpu", 1, 8192, 4096, 6144, 4), True),
    ("conv", ("cpu", 1, 8192, 4096, 6144, 4), False),
    ("conv", ("tpu", 2, 8192, 4096, 6144, 4), False),   # the batch split
    ("conv", ("tpu", 1, 8000, 4096, 6144, 4), False),   # tokens
    ("conv", ("tpu", 1, 8192, 4096, 6100, 4), False),   # channels
    ("conv", ("tpu", 1, 8192, 4000, 6144, 4), False),   # the first column
    ("conv", ("tpu", 1, 8192, 4096, 6144, 1), False),   # no history
    ("conv", ("tpu", 1, 40, 64, 128, 4), False),        # the tiny preset
    # platform, devices, tokens, inner, groups -> gate-norm kernel?
    ("gate", ("tpu", 1, 8192, 4096, 8), True),
    ("gate", ("cpu", 1, 8192, 4096, 8), False),
    ("gate", ("tpu", 4, 8192, 4096, 8), False),
    ("gate", ("tpu", 1, 8200, 4096, 8), False),
    ("gate", ("tpu", 1, 8192, 4100, 8), False),         # groups of 512.5
    ("gate", ("tpu", 1, 8192, 4096, 1024), False),      # groups of 4 rows
    ("gate", ("tpu", 1, 8192, 8 * 8192, 8), False),     # VMEM
    ("gate", ("tpu", 1, 40, 64, 2), False),
]


@pytest.mark.parametrize("stage, args, engages", PREDICATES)
def test_the_path_is_chosen_from_platform_devices_and_shapes(stage, args,
                                                             engages):
    use = (stages._use_conv_kernel if stage == "conv"
           else stages._use_gate_kernel)
    assert use(*args) is engages


def test_entries_fall_back_to_the_plain_form(blocks):
    """On this backend the entries ARE the plain forms (no `pallas_call` in
    the jaxpr, the same bits); a mesh's devices decide as the backend does;
    and the interpreter refuses shapes no block divides instead of falling
    back in silence."""
    batch, tokens, inner, groups, state, taps = 2, 200, 64, 2, 16, 4
    inputs, _ = _inputs(batch, tokens, inner, groups, state, taps)
    for entry, plain, arg in ((_conv, _conv_plain, inner),
                              (_gate, _gate_plain, groups)):
        jaxpr = jax.make_jaxpr(lambda i: entry(i, arg))(inputs).jaxpr
        assert _pallas_calls(jaxpr, "") == {}
        assert np.array_equal(entry(inputs, arg), plain(inputs, arg))
        with pytest.raises(ValueError, match="no kernel tiling"):
            entry(inputs, arg, interpret=True)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("dp",))
    assert target.where(mesh) == ("cpu", 2)
    assert target.where() == ("cpu", 1)
    assert target.where(mesh, interpret=True) == ("tpu", 1)


def _apply_mamba_parent(params, u, cfg, *, compute_dtype, eps, three_pass):
    """`layers.apply_mamba` as the parent commit had it, the two stages
    written out in it."""
    B, T, _ = u.shape
    H, P, G, N = cfg.n_heads, cfg.head_dim, cfg.n_groups, cfg.d_state
    project = layers.project(compute_dtype, three_pass)
    zxbcdt = project("btd,de->bte", u, params["w_in"], jnp.float32)
    z, xbc, dt = jnp.split(zxbcdt, [cfg.inner, cfg.inner + cfg.conv_dim],
                           axis=-1)
    xbc = jax.nn.silu(layers.causal_taps(xbc, params["conv_w"])
                      + params["conv_b"].astype(jnp.float32))
    x, b_in, c_out = jnp.split(xbc, [cfg.inner, cfg.inner + G * N], axis=-1)
    y = ssd.ssd(
        x.reshape(B, T, H, P),
        jax.nn.softplus(dt + params["dt_bias"].astype(jnp.float32)),
        -jnp.exp(params["A_log"].astype(jnp.float32)),
        b_in.reshape(B, T, G, N), c_out.reshape(B, T, G, N), params["D"],
        chunk=cfg.chunk, compute_dtype=compute_dtype, three_pass=three_pass)
    y = (y.reshape(B, T, cfg.inner) * jax.nn.silu(z)).reshape(
        B, T, G, cfg.inner // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    y = y.reshape(B, T, cfg.inner) * params["norm"].astype(jnp.float32)
    return project("bte,ed->btd", y, params["w_out"], u.dtype)


@pytest.mark.parametrize("dtype, three_pass", [(jnp.float32, False),
                                               (jnp.bfloat16, True)])
def test_apply_mamba_is_the_parents_on_the_tiny_preset(dtype, three_pass):
    """The plain path is the parent's program: output and every gradient
    of `apply_mamba` at `nemotron_h_tiny`'s mixer, to the bit."""
    cfg = nemotron_h.nemotron_h_tiny()
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    params = layers.init_mamba(ks[0], cfg.d_model, cfg.mamba)
    params = dict(params,
                  conv_b=0.1 * jax.random.normal(ks[1], params["conv_b"].shape),
                  norm=1.0 + 0.1 * jax.random.normal(ks[2],
                                                     params["norm"].shape))
    u = jax.random.normal(ks[3], (2, 40, cfg.d_model))
    how = dict(compute_dtype=dtype, eps=cfg.rms_norm_eps,
               three_pass=three_pass)
    got = jax.jit(jax.value_and_grad(lambda p, u: jnp.sum(jnp.sin(
        layers.apply_mamba(p, u, cfg.mamba, **how))), argnums=(0, 1)))(
            params, u)
    want = jax.jit(jax.value_and_grad(lambda p, u: jnp.sum(jnp.sin(
        _apply_mamba_parent(p, u, cfg.mamba, **how))), argnums=(0, 1)))(
            params, u)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.array_equal(a, b)


def _interpreted(monkeypatch):
    monkeypatch.setattr(stages, "conv_silu", functools.partial(
        stages.conv_silu, interpret=True))
    monkeypatch.setattr(stages, "gate_norm", functools.partial(
        stages.gate_norm, interpret=True))


def test_the_model_on_the_kernels_is_the_model_on_the_plain_form(
        monkeypatch, blocks):
    """Float32, both stages' kernels in the interpreter, under the remat
    policy: loss and every gradient against the same model on the plain
    stages."""
    cfg = dataclasses.replace(KERNEL_TINY, dtype=jnp.float32, remat=True)
    params = nemotron_h.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 257), 0,
                                cfg.vocab_size)
    loss = lambda p: nemotron_h.loss_fn(p, {"tokens": tokens}, cfg)[0]
    want, want_grads = jax.jit(jax.value_and_grad(loss))(params)
    blocks((128, 128), (128, 256))
    _interpreted(monkeypatch)
    got, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert abs(float(got) - float(want)) <= 2e-6 * abs(float(want))
    for path, err in jax.tree_util.tree_leaves_with_path(
            jax.tree_util.tree_map(compare.rel_l2, grads, want_grads)):
        name = jax.tree_util.keystr(path)
        if not name.endswith("['bias']"):       # no gradient, 0 / 0
            assert err <= 2e-5, (name, err)


@pytest.mark.parametrize("remat", [True, False])
def test_a_step_traces_each_kernel_once_for_all_its_call_sites(
        monkeypatch, blocks, remat):
    """The start-up budget (PERF.md §6, PR 46): in the gradient of
    `loss_fn` every mixer calls each stage's forward kernel twice under the
    remat policy (once without) and its backward once — and every call site
    of a kernel holds THE SAME kernel jaxpr, the jits' trace cache's, so
    that JAX's per-primitive lowering cache makes one Mosaic lowering a
    kernel and not one a site. On this backend, none."""
    cfg = dataclasses.replace(KERNEL_TINY, remat=remat)
    params = jax.eval_shape(
        lambda: nemotron_h.init(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, 257), jnp.int32)

    def calls():
        return _pallas_calls(jax.make_jaxpr(jax.grad(
            lambda p, t: nemotron_h.loss_fn(p, {"tokens": t}, cfg)[0]))(
                params, tokens).jaxpr)

    assert calls() == {}
    blocks((128, 128), (128, 256))
    _interpreted(monkeypatch)
    found = calls()
    mixers, forwards = cfg.pattern.count("M"), 2 if remat else 1
    assert {name: len(eqns) for name, eqns in found.items()} == {
        "mamba_conv_fwd": forwards * mixers, "mamba_conv_bwd": mixers,
        "mamba_gate_fwd": forwards * mixers, "mamba_gate_bwd": mixers}
    for name, eqns in found.items():
        assert len({id(e.params["jaxpr"]) for e in eqns}) == 1, name
        assert len({e.params["grid_mapping"] for e in eqns}) == 1, name


def _cell_calls(blocks):
    """The four calls at `nemotronh9l-b1s8k`'s shapes, traced and not run."""
    blocks(stages.CONV_BLOCK, stages.GATE_TOKENS)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    src, y = f32(1, 8192, 10304), f32(1, 8192, 4096)
    w, b, scale = f32(4, 6144), f32(6144), f32(4096)

    def both(src, y, w, b, scale):
        conv = lambda s, w, b: stages.conv_silu(s, w, b, start=4096,
                                                interpret=True)
        gate = lambda y, s, n: stages.gate_norm(y, s, n, groups=8, eps=1e-5,
                                                interpret=True)
        out, pull = jax.vjp(conv, src, w, b)
        out2, pull2 = jax.vjp(gate, y, src, scale)
        return pull(out), pull2(out2)
    found = _pallas_calls(jax.make_jaxpr(both)(src, y, w, b, scale).jaxpr)
    return {name: eqns[-1] for name, eqns in found.items()}


def _nbytes(avals):
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in avals)


def test_stage_plan_against_the_calls_at_the_cells_shapes(blocks):
    """What `stage_plan` says a call must move is what the kernels' blocks
    cover — the stage's own columns of the source once, the results once —
    to the halos, columns and partials it leaves out (under 7 %); and a
    grid step's blocks fit the budget the predicates hold them to."""
    calls = _cell_calls(blocks)
    plan = stages.stage_plan(8192, 6144, 4096)
    assert plan["conv"] == {"forward": 2 * 201_326_592,
                            "backward": 3 * 201_326_592}
    assert plan["gate_norm"] == {"forward": 3 * 134_217_728,
                                 "backward": 5 * 134_217_728}
    covered = {}
    for name, eqn in calls.items():
        mapping = eqn.params["grid_mapping"]
        steps = int(np.prod(mapping.grid))
        blocks_bytes = [4 * int(np.prod([getattr(d, "block_size", 1)
                                         for d in m.block_shape]))
                        for m in mapping.block_mappings]
        covered[name] = steps * sum(b for b in blocks_bytes
                                    if b == max(blocks_bytes))
        step = 2 * sum(blocks_bytes) + 4 * sum(
            int(np.prod(a.shape)) for a in mapping.scratch_avals)
        assert step <= stages.VMEM_BUDGET_BYTES, name
    assert covered == {"mamba_conv_fwd": plan["conv"]["forward"],
                       "mamba_conv_bwd": plan["conv"]["backward"],
                       "mamba_gate_fwd": plan["gate_norm"]["forward"],
                       "mamba_gate_bwd": plan["gate_norm"]["backward"]}
    rows, block = stages.CONV_BLOCK
    assert stages._conv_vmem_bytes(rows, block) <= stages.VMEM_BUDGET_BYTES


def test_the_benchmarks_readers_see_the_stages_as_the_mixers_not_the_scans(
        blocks):
    """At the cell's shapes, the readers that go by shape: all four calls
    count as the mixers' (the source's width is the in-projection's), none
    as the scan's (two and three axes: `kernels.ssd_roofline` must not
    move), none as a routed layer's, and none is taken for a flash kernel
    (three or six float operands: the calls have four and five)."""
    cell = catalog.resolve_cell(catalog.load_manifest(), "nemotronh9l-b1s8k",
                                "end_to_end")
    model, traffic = cell["model"], cell["traffic"]
    tokens = traffic["batch"] * traffic["seq"]
    scan, routed = (trace_ssm._sizes(model, tokens),
                    trace_held._sizes(model, tokens))
    assert (scan["inner"], scan["conv"], scan["in_proj"], scan["g"]) == (
        4096, 6144, 10304, 8)
    calls = _cell_calls(blocks)
    assert sorted(calls) == ["mamba_conv_bwd", "mamba_conv_fwd",
                             "mamba_gate_bwd", "mamba_gate_fwd"]
    for name, eqn in calls.items():
        text = _event_text(name, eqn)
        assert not trace_ssm._is_scan(text, scan), name
        assert trace_ssm._is_mixer(text, scan), name
        assert not trace_held._is_routed(text, routed), name
        assert flops.flash_call_cost(text) is None, name
        assert all(v.aval.ndim <= 3 for v in eqn.invars + eqn.outvars), name
    assert {name: len(eqn.invars) for name, eqn in calls.items()} == {
        "mamba_conv_fwd": 4, "mamba_conv_bwd": 5,
        "mamba_gate_fwd": 4, "mamba_gate_bwd": 5}
    # the hazard the operands steer clear of: taps and bias as ONE operand
    three = _event_text("mamba_conv_fwd", calls["mamba_conv_fwd"]).replace(
        ", f32[6144,1]{0} %v3", "")
    assert flops.flash_call_cost(three) is not None


def test_the_stages_call_sites_keep_their_scopes_in_every_phase(monkeypatch,
                                                                blocks):
    """The step lowered FOR A TPU (no compile, nothing run), the kernels as
    the chip gets them: every call of a stage's jitted kernel carries the
    model's scopes in its `op_name` — `blocks`, `mamba` and `conv` /
    `gate_norm` — in the forward, the recompute AND the backward phase (a
    `custom_vjp`'s backward rule inherits its caller's scopes and opens
    none), so `ssm.scoped_share` and `train_step.unscoped_share` read the
    kernels as they read the fusions; and the kernel inside is named for
    the trace (`mamba_conv_fwd.N` …)."""
    import re

    from ray_tpu.parallel.compile_watch import parse_op_name

    blocks((128, 128), (128, 128))
    # the stages alone: the scan and the routed products keep this host's
    monkeypatch.setattr(stages, "target", types.SimpleNamespace(
        where=lambda mesh=None, *, interpret=False: ("tpu", 1)))
    cfg = dataclasses.replace(KERNEL_TINY, pattern="ME", remat=True)
    params = jax.eval_shape(
        lambda: nemotron_h.init(jax.random.PRNGKey(0), cfg))
    text = jax.jit(jax.grad(
        lambda p, t: nemotron_h.loss_fn(p, {"tokens": t}, cfg)[0])).trace(
            params, jax.ShapeDtypeStruct((1, 129), jnp.int32)).lower(
                lowering_platforms=("tpu",)).as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]+)"', text, re.M))
    sites = {}
    for callee, loc in re.findall(
            r"call @(_(?:conv|gate)_(?:fwd|bwd))(?:_\d+)?\(.*loc\((#loc\d+)\)",
            text):
        scopes, phase = parse_op_name(names[loc] + "/call")
        stage = "conv" if "conv" in callee else "gate_norm"
        assert scopes[-3:] == ("blocks", "mamba", stage) or \
            scopes[-4:] == ("blocks", "blocks", "mamba", stage), names[loc]
        sites.setdefault(callee, []).append(phase)
    assert {k: sorted(v) for k, v in sites.items()} == {
        "_conv_fwd": ["forward", "recompute"], "_conv_bwd": ["backward"],
        "_gate_fwd": ["forward", "recompute"], "_gate_bwd": ["backward"]}
    kernels = re.findall(r'custom_call @tpu_custom_call.*loc\((#loc\d+)\)',
                         text)
    assert sorted({names[loc] for loc in kernels}) == [
        f"mamba_{k}/pallas_call"
        for k in ("conv_bwd", "conv_fwd", "gate_bwd", "gate_fwd")]
