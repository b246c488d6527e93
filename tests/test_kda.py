"""`ray_tpu.ops.kda`: Kimi Delta Attention's chunked rule against the
recurrence written out token by token (values and the gradients of all five
inputs), at chunks of 16 and 64 and a length no chunk divides, with the
log-decay drawn down to −30 a token and channel (the "no exponent above
zero" rule: finite, and the recurrence's), a decay constant over a head's
channels against the scalar rule of `ops.gated_delta`, the backward of its
own against JAX's derivative of the same walk, and what it refuses. Each
in both FORMS: the plain one, and the Pallas kernels `kda_fwd` / `kda_bwd`
in the interpreter (two heads of 128, blocks of two chunks of 64, the norms
inside; 150 tokens are padded to two blocks, so the state and its cotangent
cross a grid step both ways), the latter also at the steepest published
decay and at blocks of ONE chunk (three grid steps: the two loops of a
single chunk) and of FOUR (the module's: 150 tokens are three chunks and a
chunk of padding in one block, all four inverses taken at once, the
backward's second loop from the fourth chunk to the first); what decides
between the forms; and the kernels' names and scope in the layer's jaxpr."""
import functools

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import gated_delta as gd
from ray_tpu.ops import kda

INPUTS = ("q", "k", "v", "g", "beta")
# the kernels' tiles: a pair of heads, whole lane tiles
KERNEL = dict(b=1, H=2, K=128, V=128)
# the kernel cases' own limit: one interpreted program of ~10 s serves them
KERNEL_LIMIT = pytest.mark.limit(
    120, reason="one interpreted kernel program, compiled once a shape")


@pytest.fixture(autouse=True)
def blocks_of_two_chunks(monkeypatch):
    monkeypatch.setattr(kda, "BLOCK_TOKENS", 128)


@pytest.fixture
def block_of(monkeypatch):
    """The kernels' block of tokens for a case (the module's constant as it
    stands when the call is traced: `_out_and_weighted(block=)` keys the
    compiled program by it)."""
    def patch(block):
        monkeypatch.setattr(kda, "BLOCK_TOKENS", block)
        return block

    return patch


@pytest.fixture(autouse=True)
def exact_products():
    """float32 products to float32 accuracy, for this file's tests alone."""
    with jax.default_matmul_precision("highest"):
        yield


def recurrence(q, k, v, g, beta):
    """The rule one token after the other: q, k, g [b, T, H, K], v [b, T,
    H, V], β [b, T, H] -> o [b, T, H, V]."""
    (b, _, H, K), V = q.shape, v.shape[-1]

    def token(state, now):
        q_t, k_t, v_t, g_t, beta_t = now
        state = state * jnp.exp(g_t)[..., None]             # Diag(α_t)·S
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t,
                                   beta_t[..., None] * (v_t - seen))
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    _, out = jax.lax.scan(token, jnp.zeros((b, H, K, V)), tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)


def _inputs(seed=0, b=2, T=150, H=3, K=16, V=8, decay=1.0, floor=None,
            steepest=False):
    """`decay`: the scale of the log-decays (1e-3 keeps the state, 20
    forgets it within a token); `floor`: log-decays uniform in [floor, 0]
    instead, each channel its own; `steepest`: the published bound, ``A_log
    = log 16`` on softplus inputs of 3 ± 2."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, T, H, K))
    k = jax.random.normal(ks[1], (b, T, H, K))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * K ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, T, H, K))) * decay
    if floor is not None:
        g = jax.random.uniform(ks[3], (b, T, H, K), minval=floor, maxval=0.0)
    if steepest:
        g = -16.0 * jax.nn.softplus(
            3.0 + 2.0 * jax.random.normal(ks[3], (b, T, H, K)))
    return (q, k, jax.random.normal(ks[2], (b, T, H, V)), g,
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, T, H))))


@functools.cache
def _program(fn, kw, block=None):
    """`fn`'s output and the gradients by input of a scalar of it, one
    jitted program a function, keywords and block of tokens (which the
    kernels read off the module when they are traced): the draws of a
    parametrised case meet it compiled."""
    def scalar(weights, *a):
        out = fn(*a, **dict(kw))
        return jnp.sum(out * weights), out

    return jax.jit(jax.grad(scalar, argnums=tuple(range(1, 6)),
                            has_aux=True))


def _out_and_weighted(fn, args, block=None, **kw):
    weights = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    grads, out = _program(fn, tuple(sorted(kw.items())), block)(weights,
                                                                *args)
    return out, grads


@functools.cache
def _rule(chunk):
    return jax.jit(functools.partial(kda.kda, chunk=chunk,
                                     compute_dtype=jnp.float32))


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


_recurrence = jax.jit(recurrence)
DRAWS = {"mild": dict(), "kept": dict(decay=1e-3),
         "forgotten": dict(decay=20.0),
         "down_to_-30": dict(floor=-30.0)}
EPS = 1e-12


def normed_recurrence(q, k, v, g, beta):
    """The recurrence on RAW q and k: what `normalize=EPS` computes."""
    return recurrence(gd._unit(q, EPS) * q.shape[-1] ** -0.5,
                      gd._unit(k, EPS), v, g, beta)


def _raw(args):
    """Inputs the norms inside the rule undo."""
    q, k, *rest = args
    return (3.0 * q, 0.2 * k, *rest)


def _kernel_case(draw, **shape):
    """(raw inputs at the kernels' tiles, `kda`'s keywords): the norms
    inside, float32 products, the interpreter."""
    more = dict(steepest=True) if draw == "steepest" else DRAWS[draw]
    return (_raw(_inputs(**{**KERNEL, **shape, **more})),
            dict(chunk=64, compute_dtype=jnp.float32, normalize=EPS,
                 interpret=True))


# (form, chunk, T, draw, block of tokens): every draw at blocks of two chunks,
# the mild one at blocks of one and of four (`BLOCK_TOKENS` as shipped)
BLOCKS = (64, 128, 256)
KERNEL_DRAWS = [
    *(pytest.param("kernel", 64, 150, draw, 128, marks=KERNEL_LIMIT)
      for draw in (*sorted(DRAWS), "steepest")),
    *(pytest.param("kernel", 64, 150, "mild", block, marks=KERNEL_LIMIT)
      for block in BLOCKS if block != 128)]


@pytest.mark.parametrize("form, chunk, T, draw, block", [
    *(("plain", chunk, T, draw, None) for chunk, T in [(16, 150), (64, 150),
                                                       (64, 128), (16, 7)]
      for draw in sorted(DRAWS)),
    *KERNEL_DRAWS])
def test_values_against_the_recurrence(form, chunk, T, draw, block,
                                       block_of):
    if form == "plain":
        args = _inputs(T=T, **DRAWS[draw])
        got, want = _rule(chunk)(*args), _recurrence(*args)
    else:
        args, kw = _kernel_case(draw, T=T)
        got, _ = _out_and_weighted(kda.kda, args, block_of(block), **kw)
        want, _ = _out_and_weighted(normed_recurrence, args)
    assert got.shape == want.shape == args[2].shape
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _rel(got, want) < 1e-5


# the kernel draws FIRST here and last above: the ten cases that meet the one
# interpreted program (`_program`, compiled once a process) stand in a row,
# and `--dist load` hands a row of cases to one worker more often than not
@pytest.mark.parametrize("form, chunk, T, draw, block", [
    *KERNEL_DRAWS, *(("plain", chunk, 150, draw, None) for chunk in (16, 64)
                     for draw in sorted(DRAWS))])
def test_every_gradient_against_the_recurrence(form, chunk, T, draw, block,
                                               block_of):
    """The kernels: 150 tokens in two blocks of 128 (the padding's ``g =
    0``, ``β = 0`` tokens; the state carried across a grid step forward,
    its cotangent backward), the norms inside, and at `steepest` no
    overflow and no `nan` where a token forgets the state whole; in three
    blocks of one chunk; and in ONE block of four, the last chunk all
    padding, which the backward's second loop walks first."""
    if form == "plain":
        args = _inputs(**DRAWS[draw])
        _, got = _out_and_weighted(kda.kda, args, chunk=chunk,
                                   compute_dtype=jnp.float32)
        _, want = _out_and_weighted(recurrence, args)
    else:
        args, kw = _kernel_case(draw, T=T)
        _, got = _out_and_weighted(kda.kda, args, block_of(block), **kw)
        _, want = _out_and_weighted(normed_recurrence, args)
    for name, a, b in zip(INPUTS, got, want):
        assert a.shape == b.shape
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert _rel(a, b) < 1e-4, (name, _rel(a, b))


@pytest.mark.parametrize("form, chunk, block", [
    ("plain", 16, None), ("plain", 64, None),
    *(pytest.param("kernel", 64, block, marks=KERNEL_LIMIT)
      for block in BLOCKS)])
def test_the_backward_of_its_own_against_jaxs(form, chunk, block, block_of):
    """`kda` (custom_vjp: the chunks' start states kept, a chunk's inside
    rebuilt — by the plain form's `jax.vjp` of a chunk, by `kda_bwd`'s
    own arithmetic) against `kda_plain`, the same walk differentiated by
    JAX."""
    kw = dict(chunk=chunk, compute_dtype=jnp.float32)
    if form == "plain":
        args, near = _inputs(seed=3, T=300), (1e-6, 2e-6)
    else:
        # the kernels sum a chunk's products in tiles of their own
        (args, kernel), near = _kernel_case("mild"), (2e-6, 5e-6)
        kw["normalize"] = kernel["normalize"]
    out, got = _out_and_weighted(
        kda.kda, args,
        **(kw if form == "plain" else dict(kernel, block=block_of(block))))
    plain, want = _out_and_weighted(kda.kda_plain, args, **kw)
    assert _rel(out, plain) < near[0]
    for name, a, b in zip(INPUTS, got, want):
        assert _rel(a, b) < near[1], (name, _rel(a, b))


@pytest.mark.parametrize("chunk", [16, 64])
def test_a_decay_constant_over_the_channels_is_the_scalar_rule(chunk):
    """The scalar rule is the vector rule's special case: `g` the same on
    every channel of a head gives `gated_delta_plain`'s output and its
    gradients (the decay's summed over the channels)."""
    q, k, v, g, beta = _inputs(seed=5)
    one = g[..., 0]
    kw = dict(chunk=chunk, compute_dtype=jnp.float32)
    got, d_got = _out_and_weighted(
        kda.kda, (q, k, v, jnp.broadcast_to(one[..., None], g.shape), beta),
        **kw)
    want, d_want = _out_and_weighted(gd.gated_delta_plain,
                                     (q, k, v, one, beta), **kw)
    assert _rel(got, want) < 2e-6
    d_got = (*d_got[:3], jnp.sum(d_got[3], axis=-1), d_got[4])
    for name, a, b in zip(INPUTS, d_got, d_want):
        assert _rel(a, b) < 1e-5, (name, _rel(a, b))


def test_the_norms_inside_the_rule_and_one_pass_in_bf16():
    """`normalize`: raw q and k L2-normed inside, q times K^-½ — the
    caller's own norm gives the same; and bf16 operands stay within a bf16
    pass of the recurrence."""
    q, k, v, g, beta = _inputs(seed=7)
    raw_q, raw_k = 3.0 * q, 0.2 * k
    got = kda.kda(raw_q, raw_k, v, g, beta, chunk=16,
                  compute_dtype=jnp.float32, normalize=1e-12)
    want = recurrence(q, k, v, g, beta)
    assert _rel(got, want) < 1e-5
    low = kda.kda(q, k, v, g, beta, chunk=64, compute_dtype=jnp.bfloat16)
    assert 1e-4 < _rel(low, want) < 1e-2


@KERNEL_LIMIT
@pytest.mark.parametrize("block", BLOCKS)
def test_bf16_products_in_the_kernels_stay_near_the_plain_forms(block,
                                                                block_of):
    """One pass in bf16: the kernels round the operands the plain form
    rounds, so the two stay within a bf16 pass of each other — output and
    every gradient — whatever the block (what the first loop keeps of a
    chunk's operands is in bf16 then)."""
    args, kw = _kernel_case("mild")
    kw = {**kw, "compute_dtype": jnp.bfloat16}
    out, got = _out_and_weighted(kda.kda, args, block_of(block), **kw)
    kw.pop("interpret")
    plain, want = _out_and_weighted(kda.kda, args, **kw)
    assert _rel(out, plain) < 1e-3
    for name, a, b in zip(INPUTS, got, want):
        assert 0 < _rel(a, b) < 2e-2, (name, _rel(a, b))


@pytest.mark.parametrize("why, shape, kw", [
    ("a head of 64 key columns", dict(K=64), {}),
    ("an odd number of heads", dict(H=3), {}),
    ("chunk 16: a pair's chunks are not the 128 lanes", {}, dict(chunk=16)),
    ("chunk 8, which `_BASE` does not divide", {}, dict(chunk=8)),
    ("a block the chunk does not divide", {}, dict(block=96)),
    ("a mesh of four devices", {}, dict(devices=4)),
    ("the CPU", {}, dict(platform="cpu")),
])
def test_off_the_tiles_or_off_one_tpu_it_is_the_plain_form(
        why, shape, kw, monkeypatch):
    """`_use_kernel`'s table: shapes the kernels' tiles do not divide, a
    mesh of more than one device and another backend than the TPU trace to
    the plain form; the cell's widths on one TPU trace to the kernels."""
    platform, devices = kw.pop("platform", "tpu"), kw.pop("devices", 1)
    monkeypatch.setattr(kda, "BLOCK_TOKENS", kw.pop("block", 128))
    monkeypatch.setattr(kda.target, "where",
                        lambda mesh=None, interpret=False: (platform,
                                                            devices))
    args = _inputs(**{**KERNEL, **dict(T=128), **shape})
    kw = {**dict(chunk=64), **kw}
    text = str(jax.make_jaxpr(lambda *a: kda.kda(*a, **kw))(*args))
    assert "pallas_call" not in text, why
    monkeypatch.setattr(kda.target, "where",
                        lambda mesh=None, interpret=False: ("tpu", 1))
    monkeypatch.setattr(kda, "BLOCK_TOKENS", 128)
    text = str(jax.make_jaxpr(lambda *a: kda.kda(*a, chunk=64))(
        *_inputs(**KERNEL, T=128)))
    assert text.count("name=kda_fwd") == 1


def test_the_plan_and_the_budget_by_hand(monkeypatch):
    """A grid step of the backward at the cell's widths, blocks of 256
    tokens: q, k, g and their cotangents [256, 256], v, o's cotangent and
    v's [256, 256], β's rows and theirs [4, 8, 128], four chunks' start
    states [2, 128, 128], float32 and twice; the state's cotangent once;
    and what the first loop keeps of each of the four chunks for the second,
    once: nineteen stacked [128, 128] arrays, six packed [64, 128] and five
    columns [128, 1], a tile of 128 lanes each. A block of 512 tokens keeps
    eight chunks, which the budget does not hold: the plain form, from
    shapes alone."""
    monkeypatch.setattr(kda, "BLOCK_TOKENS", 256)
    plan = kda.kda_plan(8192, 32, 128, 128, 64)
    assert plan["chunks"] == 128 and plan["block_tokens"] == 256
    assert plan["inverses_at_once"] == 4
    assert plan["state_bytes"] == 4 * 128 * 32 * 128 * 128 == 268_435_456
    blocks = 2 * 4 * (
        9 * 256 * 256 + 2 * 4 * 8 * 128 + 4 * 2 * 128 * 128
    ) + 4 * 2 * 128 * 128
    kept_a_chunk = 4 * (19 * 128 * 128 + 6 * 64 * 128 + 5 * 128 * 128)
    assert blocks == 5_963_776 and kept_a_chunk == 1_769_472
    assert plan["vmem_bytes"] == blocks + 4 * kept_a_chunk == 13_041_664
    assert plan["vmem_bytes"] <= kda.VMEM_BUDGET_BYTES
    assert kda._use_kernel("tpu", 1, 64, 32, 128, 128)
    with monkeypatch.context() as tighter:
        tighter.setattr(kda, "VMEM_BUDGET_BYTES", plan["vmem_bytes"] - 1)
        assert not kda._use_kernel("tpu", 1, 64, 32, 128, 128)
    # eight chunks' scratch: over the budget as it stands
    monkeypatch.setattr(kda, "BLOCK_TOKENS", 512)
    plan = kda.kda_plan(8192, 32, 128, 128, 64)
    assert plan["inverses_at_once"] == 8
    assert plan["vmem_bytes"] == 2 * blocks - 4 * 2 * 128 * 128 \
        + 8 * kept_a_chunk == 25_952_256 > kda.VMEM_BUDGET_BYTES
    assert not kda._use_kernel("tpu", 1, 64, 32, 128, 128)
    # what the kept scratch counts is what the kernels allocate
    kept, _ = kda._kept_shapes(4, 64, 128, 128, jnp.dtype(jnp.float32), 1e-6,
                               None)
    assert len(kept) == 30
    assert sorted({held.shape[1:] for held in kept}) == [
        (64, 128), (128, 1), (128, 128)]


def _pallas_calls(jaxpr, found, outer=""):
    """(kernel name, the scopes around the call: its own name stack behind
    those of the calls — a kernel's `jit`, a checkpoint — that hold it)."""
    for eqn in jaxpr.eqns:
        scope = f"{outer}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"], scope))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, found, scope)
    return found


def test_the_layers_rule_is_the_two_kernels_under_its_scope(runs_on):
    """On one TPU `apply_kda` at the kernels' widths traces to `kda_fwd`
    and `kda_bwd` (the names the trace and the ledger's breakdown list),
    both inside the `kda_rule` scope that `kernels.kda_rule_roofline`
    reads, and splits neither the conv's output nor the decay for them."""
    from ray_tpu.models import layers as L

    runs_on("tpu")
    cfg = L.KDAConfig(n_heads=2, k_dim=128, v_dim=128, gate_rank=16)
    params = L.init_kda(jax.random.PRNGKey(0), 64, cfg)
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 64))
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(L.apply_kda(
        p, u, cfg, compute_dtype=jnp.bfloat16))))(params)
    rule = [(name, scope) for name, scope in _pallas_calls(jaxpr.jaxpr, [])
            if name.startswith("kda_")]
    assert sorted(name for name, _ in rule) == ["kda_bwd", "kda_fwd"]
    assert all("kda_rule" in scope for _, scope in rule), rule


def test_the_rounded_carrys_control_takes_the_plain_form(runs_on, capsys):
    """`benchmarks/precision_control.py`'s `bf16_state` rounds the carry by
    patching `kda._chunk`, which the kernels never call: inside the control
    the rule traces to the plain form WITH the rounding even where the
    kernels would run, and says so; outside it the kernels are back."""
    from benchmarks import precision_control

    runs_on("tpu")
    args = _inputs(**KERNEL, T=128)

    def text():
        return str(jax.make_jaxpr(lambda *a: kda.kda(*a, chunk=64))(*args))

    with precision_control._rounded_state():
        inside = text()
    assert "pallas_call" not in inside and "reduce_precision" in inside
    assert "PLAIN form" in capsys.readouterr().err
    assert "name=kda_fwd" in text() and "reduce_precision" not in text()


def test_a_chunk_the_inverse_cannot_halve_is_refused():
    q, k, v, g, beta = _inputs(T=48)
    with pytest.raises(ValueError, match="power of two"):
        kda.kda(q, k, v, g, beta, chunk=48)
