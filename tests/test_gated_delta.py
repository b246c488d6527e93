"""`ray_tpu.ops.gated_delta`: the chunked gated delta rule against the
recurrence written out token by token (values and the gradients of all five
inputs), at chunks of 16 and 64 and a length no chunk divides, with decays
near 0 and near 1; the backward of its own against JAX's derivative of the
same three stages; the triangular inverse and its rule; key heads read by
several value heads; the plan's arithmetic by hand. Each in both FORMS: the
plain one, and the Pallas kernels in the interpreter (two value heads on one
key head of 128, blocks of two chunks of 64)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_delta as gd

INPUTS = ("q", "k", "v", "g", "beta")
FORMS = ("plain", "kernel")
# the kernels' tiles: two value heads a key head, whole lane tiles; TWO key
# heads, so that a head's columns of q, k and v lie at three different block
# offsets in the one operand the kernels read
KERNEL = dict(b=1, G=2, H=4, K=128, V=128)
# how near the backward of its own (values, gradients) and the grouped call
# lie to their controls: the plain form's limits are the ones it came with
# (it IS its control's arithmetic in another order); the kernels sum a
# chunk's products in tiles of their own
CLOSE = {"plain": (1e-6, 2e-6), "kernel": (2e-6, 3e-6)}


@pytest.fixture(autouse=True)
def blocks_of_two_chunks(monkeypatch):
    monkeypatch.setattr(gd, "BLOCK_TOKENS", 128)


def _form(form, **shape):
    """(`_inputs`' shape, `gated_delta`'s keywords) of a form."""
    if form == "plain":
        return shape, {}
    return {**shape, **KERNEL}, {"interpret": True}


@pytest.fixture(autouse=True)
def exact_products():
    """float32 products to float32 accuracy, for this file's tests alone."""
    with jax.default_matmul_precision("highest"):
        yield


def recurrence(q, k, v, g, beta):
    """The rule one token after the other: q, k [b, T, G, K], v [b, T, H,
    V], g, β [b, T, H] -> o [b, T, H, V]."""
    (b, _, G, K), (H, V) = q.shape, v.shape[2:]
    q, k = (jnp.repeat(t, H // G, axis=2) for t in (q, k))

    def token(state, now):
        q_t, k_t, v_t, g_t, beta_t = now
        state = state * jnp.exp(g_t)[..., None, None]
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t,
                                   beta_t[..., None] * (v_t - seen))
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    _, out = jax.lax.scan(token, jnp.zeros((b, H, K, V)), tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)


def _inputs(seed=0, b=2, T=150, G=2, H=4, K=16, V=8, decay=1.0):
    """`decay`: the scale of the log-decays; 1e-3 keeps the state (decays
    near 1), 20 forgets it within a token (near 0)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, T, G, K))
    k = jax.random.normal(ks[1], (b, T, G, K))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * K ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    return (q, k, jax.random.normal(ks[2], (b, T, H, V)),
            -jax.nn.softplus(jax.random.normal(ks[3], (b, T, H))) * decay,
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, T, H))))


def _out_and_weighted(fn, args, **kw):
    """The rule's output and the gradients by input of a scalar of it, one
    jitted program."""
    weights = jax.random.normal(jax.random.PRNGKey(9),
                                args[2].shape)

    def scalar(*a):
        out = fn(*a, **kw)
        return jnp.sum(out * weights), out

    grads, out = jax.jit(jax.grad(scalar, argnums=tuple(range(5)),
                                  has_aux=True))(*args)
    return out, grads


def _weighted(fn, args, **kw):
    return _out_and_weighted(fn, args, **kw)[1]


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("decay", [1.0, 1e-3, 20.0])
@pytest.mark.parametrize("form, chunk, T", [
    ("plain", 16, 150), ("plain", 64, 150), ("plain", 64, 128),
    ("plain", 16, 7),
    # three blocks, the last one short of a chunk; one block, not whole
    ("kernel", 64, 300), ("kernel", 64, 100)])
def test_values_against_the_recurrence(form, chunk, T, decay):
    shape, kw = _form(form, T=T)
    args = _inputs(decay=decay, **shape)
    got = jax.jit(lambda *a: gd.gated_delta(
        *a, chunk=chunk, compute_dtype=jnp.float32, **kw))(*args)
    want = recurrence(*args)
    assert got.shape == want.shape == args[2].shape
    assert _rel(got, want) < 5e-6


@pytest.mark.parametrize("decay", [1.0, 1e-3, 20.0])
@pytest.mark.parametrize("form, chunk", [("plain", 16), ("plain", 64),
                                         ("kernel", 64)])
def test_every_gradient_against_the_recurrence(form, chunk, decay):
    shape, kw = _form(form)
    args = _inputs(decay=decay, **shape)
    got = _weighted(gd.gated_delta, args, chunk=chunk,
                    compute_dtype=jnp.float32, **kw)
    want = _weighted(recurrence, args)
    for name, a, b in zip(INPUTS, got, want):
        assert _rel(a, b) < 5e-5, (name, _rel(a, b))


@pytest.mark.parametrize("form, chunk", [("plain", 16), ("plain", 64),
                                         ("kernel", 64)])
def test_the_backward_of_its_own_against_jaxs(form, chunk):
    """`gated_delta` (custom_vjp: start states kept, the rest rebuilt; in
    the kernel form `delta_bwd`) against `gated_delta_plain`, the same
    stages differentiated by JAX."""
    shape, kw = _form(form)
    args = _inputs(seed=3, **shape)
    plain = dict(chunk=chunk, compute_dtype=jnp.float32)
    got, got_grads = _out_and_weighted(gd.gated_delta, args, **plain, **kw)
    want, want_grads = _out_and_weighted(gd.gated_delta_plain, args, **plain)
    np.testing.assert_allclose(got, want, atol=CLOSE[form][0])
    for name, a, b in zip(INPUTS, got_grads, want_grads):
        assert _rel(a, b) < CLOSE[form][1], (name, _rel(a, b))


@pytest.mark.parametrize("form", FORMS)
def test_the_backward_keeps_the_start_states_and_the_inputs_alone(form):
    if form == "plain":
        args = _inputs(T=128)
        chunked = gd._to_chunks(*args, 64)
        _, residuals = jax.eval_shape(
            lambda *c: gd._rule_fwd(*c, jnp.float32, None), *chunked)
        assert len(residuals) == 6
        for kept, given in zip(residuals, chunked):
            assert kept.shape == given.shape
        # [chunks, b, G, R, K, V]
        assert residuals[-1].shape == (2, 2, 2, 2, 16, 8)
        return
    # the kernels keep [q | k | v] as the conv left them — ONE array, not
    # padded, not chunked — g, β and the start states
    q, k, v, g, beta = _inputs(T=200, **KERNEL)
    qkv = jnp.concatenate([a.reshape(1, 200, -1) for a in (q, k, v)], -1)
    static = gd._static(2, 128, 128, 64, jnp.float32, None, True)
    out, residuals = gd._kernel_rule_fwd(qkv, g, beta, static)
    kept, kept_g, kept_beta, starts = residuals
    assert kept is qkv and kept_g is g and kept_beta is beta
    # [chunks of the padded length, b, G, R, K, V]
    assert starts.shape == (4, 1, 2, 2, 128, 128)
    assert out.shape == (1, 200, 512)


@pytest.mark.parametrize("n", [8, 16, 32, 64])
def test_the_triangular_inverse_and_its_rule(n):
    A = jnp.tril(jax.random.normal(jax.random.PRNGKey(n), (3, 2, n, n)), -1)
    A = A * 0.3
    T = jax.jit(gd._inverse)(A)
    want = np.linalg.inv(np.eye(n) + np.asarray(A, np.float64))
    np.testing.assert_allclose(T, want, atol=2e-5 * np.abs(want).max())
    assert not np.asarray(jnp.triu(T, 1)).any()
    weights = jax.random.normal(jax.random.PRNGKey(1), A.shape)
    mask = jnp.tril(jnp.ones((n, n), bool), -1)

    def through(inverse):
        return jax.jit(jax.grad(lambda a: jnp.sum(
            inverse(jnp.where(mask, a, 0.0)) * weights)))(A)

    got = through(gd._inverse)
    want = through(lambda a: jnp.linalg.inv(jnp.eye(n) + a))
    assert _rel(got, want) < 1e-4


@pytest.mark.parametrize("form", FORMS)
def test_a_key_head_read_by_two_value_heads_is_the_repeated_call(form):
    """The kernels' two value heads of a key head (packed side by side
    along the lanes) against the plain form on the key head repeated."""
    shape, kw = _form(form, G=2, H=4)
    q, k, v, g, beta = _inputs(**shape)
    chunk = 16 if form == "plain" else 64
    grouped = jax.jit(lambda *a: gd.gated_delta(
        *a, chunk=chunk, compute_dtype=jnp.float32, **kw))(q, k, v, g, beta)
    repeated = jax.jit(lambda *a: gd.gated_delta(
        *a, chunk=chunk, compute_dtype=jnp.float32))(
            jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), v, g, beta)
    np.testing.assert_allclose(grouped, repeated, atol=CLOSE[form][0])


@pytest.mark.parametrize("form", FORMS)
def test_the_norm_inside_the_rule_is_the_callers_norm(form):
    """`normalize=ε`: raw q and k, L2-normed and scaled in `prepare` (in the
    kernels: in VMEM, and its pullback there too), give what the caller's
    own norm ahead of the call gives, values and every gradient; the plain
    backward then keeps the RAW chunked q and k."""
    shape, more = _form(form, T=70)
    _, _, v, g, beta = _inputs(**shape)
    b, _, G, K = shape.get("b", 2), 70, shape.get("G", 2), shape.get("K", 16)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    raw_q = jax.random.normal(ks[0], (b, 70, G, K)) * 3.0
    raw_k = jax.random.normal(ks[1], (b, 70, G, K)) * 0.2
    chunk = 16 if form == "plain" else 64
    kw = dict(chunk=chunk, compute_dtype=jnp.float32, **more)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def outside(q, k, v, g, beta):
        return gd.gated_delta(unit(q) * K ** -0.5, unit(k), v, g, beta, **kw)

    def inside(q, k, v, g, beta):
        return gd.gated_delta(q, k, v, g, beta, normalize=1e-6, **kw)

    args = (raw_q, raw_k, v, g, beta)
    (got, got_grads), (want, want_grads) = (
        _out_and_weighted(inside, args), _out_and_weighted(outside, args))
    np.testing.assert_allclose(got, want, atol=1e-6)
    for name, a, b in zip(INPUTS, got_grads, want_grads):
        assert _rel(a, b) < 5e-6, (name, _rel(a, b))
    if form == "plain":
        np.testing.assert_allclose(
            got, jax.jit(lambda *a: gd.gated_delta_plain(
                *a, normalize=1e-6, chunk=chunk,
                compute_dtype=jnp.float32))(*args), atol=1e-6)
        chunked = gd._to_chunks(*args, 16)
        _, residuals = jax.jit(lambda *c: gd._rule_fwd(
            *c, jnp.float32, 1e-6))(*chunked)
        np.testing.assert_array_equal(residuals[0], chunked[0])


def test_bfloat16_products_stay_near_float32s():
    args = _inputs()
    exact = jax.jit(lambda *a: gd.gated_delta(
        *a, chunk=64, compute_dtype=jnp.float32))(*args)
    rounded = jax.jit(lambda *a: gd.gated_delta(*a, chunk=64))(*args)
    assert rounded.dtype == jnp.float32
    assert 1e-4 < _rel(rounded, exact) < 2e-2
    # and so do the gradients, whose cotangents into `prepare` are rounded
    # to the compute dtype as its outputs are
    for name, a, b in zip(
            INPUTS, _weighted(gd.gated_delta, args, chunk=64),
            _weighted(gd.gated_delta, args, chunk=64,
                      compute_dtype=jnp.float32)):
        assert 1e-4 < _rel(a, b) < 3e-2, (name, _rel(a, b))


@pytest.mark.parametrize("form, chunk, short, T", [
    ("plain", 16, 40, 48), ("kernel", 64, 150, 256)])
def test_padding_tokens_neither_decay_nor_write(form, chunk, short, T):
    """T = 40 at chunk 16 is padded by 8 (the kernels: 150 tokens to two
    blocks of 128): the first outputs are those of the first tokens of a
    longer call, and so are the gradients of a loss on them."""
    shape, more = _form(form, T=T)
    args = _inputs(**shape)
    kw = dict(chunk=chunk, compute_dtype=jnp.float32, **more)
    cut = tuple(a[:, :short] for a in args)
    rule = jax.jit(lambda *a: gd.gated_delta(*a, **kw))
    np.testing.assert_allclose(rule(*cut), rule(*args)[:, :short], atol=1e-6)
    if form == "kernel":
        for name, a, b in zip(INPUTS, _weighted(gd.gated_delta, cut, **kw),
                              _weighted(gd.gated_delta_plain, cut,
                                        chunk=chunk,
                                        compute_dtype=jnp.float32)):
            assert a.shape == b.shape and _rel(a, b) < 3e-6, name


@pytest.mark.parametrize("scale", [0.3, 1.0])
def test_the_inverse_in_the_kernel_is_the_plain_forms(scale):
    """`_inverse_packed` (two heads' [64, 64] side by side along the lanes:
    substitution on the 16 x 16 diagonal blocks as one [16, 128] tile, the
    blocks merged by whole-tile products) against `_inverse`, on random
    strictly lower-triangular matrices with entries up to `scale`."""
    from jax.experimental import pallas as pl
    A = jnp.tril(jax.random.uniform(jax.random.PRNGKey(7), (2, 64, 64),
                                    minval=-scale, maxval=scale), -1)

    def kernel(a_ref, t_ref):
        t_ref[...] = gd._inverse_packed(a_ref[...], *gd._packed_geometry(64))

    packed = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((64, 128), jnp.float32),
        interpret=True)(jnp.concatenate([A[0], A[1]], axis=1))
    got = jnp.stack([packed[:, :64], packed[:, 64:]])
    want = jax.jit(gd._inverse)(A)
    exact = np.linalg.inv(np.eye(64) + np.asarray(A, np.float64))
    top = np.abs(exact).max()
    np.testing.assert_allclose(got, want, atol=1e-5 * top)
    np.testing.assert_allclose(got, exact, atol=2e-5 * top)
    assert not np.asarray(jnp.triu(got, 1)).any()


@pytest.mark.parametrize("why, shape, kw", [
    ("a key head of 64 columns", dict(K=64), {}),
    ("four value heads on a key head", dict(G=1, H=4), {}),
    ("chunk 16", {}, dict(chunk=16)),
    ("a mesh of two devices", {}, dict(devices=2)),
    ("the CPU", {}, dict(platform="cpu")),
])
def test_off_the_tiles_or_off_one_tpu_it_is_the_plain_form(
        why, shape, kw, monkeypatch):
    """Shapes the kernels' tiles do not divide, a mesh of more than one
    device and another backend than the TPU trace to the plain form and
    give its bytes; the cell's shapes on one TPU trace to the kernels."""
    platform, devices = kw.pop("platform", "tpu"), kw.pop("devices", 1)
    monkeypatch.setattr(gd.target, "where",
                        lambda mesh=None, interpret=False: (platform,
                                                            devices))
    args = _inputs(**{**dict(T=128), **KERNEL, **shape})
    kw = {**dict(chunk=64, compute_dtype=jnp.float32), **kw}
    text = str(jax.make_jaxpr(lambda *a: gd.gated_delta(*a, **kw))(*args))
    assert "pallas_call" not in text, why
    monkeypatch.undo()
    np.testing.assert_array_equal(
        jax.jit(lambda *a: gd.gated_delta(*a, **kw))(*args),
        jax.jit(lambda *a: gd._from_chunks(gd._rule(
            *gd._to_chunks(*a, kw["chunk"]), jnp.float32, None), 128))(*args))


def test_the_cells_shapes_on_one_tpu_are_the_kernels(monkeypatch):
    monkeypatch.setattr(gd.target, "where",
                        lambda mesh=None, interpret=False: ("tpu", 1))
    args = _inputs(T=128, **KERNEL)
    grad = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(gd.gated_delta(
        *a, chunk=64, normalize=1e-6)), argnums=(0, 1, 2, 3, 4)))(*args)
    assert str(grad).count("name=delta_fwd") == 1
    assert str(grad).count("name=delta_bwd") == 1


def test_the_benchmarks_flash_reader_passes_the_kernels_by():
    """At the cell's shapes: `chipbench.flops.flash_call_cost` reads any
    Mosaic call of three or six array operands as a flash kernel;
    `delta_fwd` has four and `delta_bwd` seven (the norm's epsilon is its
    seventh, in SMEM), and neither is taken for one."""
    import functools

    from chipbench import flops
    from tests.test_ssd_kernels import _event_text

    B, T, G, H, K, V, C = 2, 8192, 16, 32, 128, 128, 64
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    qkv, rows = f32((B, T, 2 * G * K + H * V)), f32((B, G, T // C, 8, 128))
    kw = dict(k_dim=K, v_dim=V, chunk=C, block=512, unrolled=4,
              cd=jnp.dtype(jnp.bfloat16), normalize=1e-6, interpret=False)
    calls = {
        "delta_fwd": jax.make_jaxpr(lambda a, r: gd._delta_fwd(
            a, r, **kw))(qkv, rows),
        "delta_bwd": jax.make_jaxpr(lambda a, r, s, d: gd._delta_bwd(
            a, r, s, d, **kw))(
                qkv, rows, f32((T // C, B, G, 2, K, V)), f32((B, T, H * V))),
    }
    operands = {}
    for name, jaxpr in calls.items():
        (eqn,) = [e for e in jaxpr.eqns[-1].params["jaxpr"].eqns
                  if e.primitive.name == "pallas_call"]
        assert eqn.params["name"] == name
        assert flops.flash_call_cost(_event_text(name, eqn)) is None, name
        operands[name] = len(eqn.invars)
    assert operands == {"delta_fwd": 4, "delta_bwd": 7}
    # the hazard: the backward without the epsilon's operand
    (eqn,) = [e for e in calls["delta_bwd"].eqns[-1].params["jaxpr"].eqns
              if e.primitive.name == "pallas_call"]
    six = _event_text("delta_bwd", eqn).replace("f32[1,1]{0} %v0, ", "")
    assert flops.flash_call_cost(six) is not None


@pytest.mark.parametrize("chunk", [24, 48, 96])
def test_a_chunk_the_inverse_cannot_halve_is_refused(chunk):
    with pytest.raises(ValueError, match="power of two"):
        gd.gated_delta(*_inputs(T=96), chunk=chunk)


def test_value_heads_must_fill_the_key_heads():
    q, k, v, g, beta = _inputs(G=3, H=4)
    with pytest.raises(ValueError, match="whole number of value heads"):
        gd.gated_delta(q, k, v, g, beta, chunk=16)


def test_the_plan_by_hand(monkeypatch):
    """The cell's layer: 16,384 tokens, 32 value heads on 16 key heads of
    128, chunk 64, the kernels' blocks of 512 tokens."""
    monkeypatch.setattr(gd, "BLOCK_TOKENS", 512)
    plan = gd.delta_plan(16384, 32, 16, 128, 128, 64)
    assert plan["chunks"] == 256
    assert plan["flops_recurrence"] == 6 * 16384 * 32 * 128 * 128 \
        == 51_539_607_552
    assert plan["flops_by_stage"] == {
        "scores": 2 * 2 * 16384 * 64 * 16 * 128,
        "apply_inverse": 2 * 16384 * 64 * 32 * 256,
        "carry": 4 * 16384 * 32 * 128 * 128,
        "readout": 2 * 16384 * 32 * (128 * 128 + 64 * 128)}
    # q and k [T, 2048], v and o [T, 4096] at two bytes, g and β float32
    assert plan["bytes"] == 16384 * (2 * 2048 + 2 * 4096) * 2 \
        + 2 * 16384 * 32 * 4
    assert plan["state_bytes"] == 256 * 32 * 128 * 128 * 4 == 536_870_912
    # a grid step of the backward, 512 tokens of a key head and its two
    # value heads: q, k, dq, dk [512, 128], v, do, dv [512, 256], the rows
    # and theirs [8, 8, 128] in float32, eight chunks' start states [2, 128,
    # 128] in bfloat16, all twice; the state's cotangent once
    assert plan["vmem_bytes"] == 2 * (
        4 * (4 * 512 * 128 + 3 * 512 * 256 + 2 * 8 * 8 * 128)
        + 2 * 8 * 2 * 128 * 128) + 4 * 2 * 128 * 128 == 6_553_600
    assert plan["vmem_bytes"] <= gd.VMEM_BUDGET_BYTES
    padded = gd.delta_plan(100, 4, 2, 16, 8, 16)
    assert padded["chunks"] == 7
    assert padded["flops_recurrence"] == 6 * 100 * 4 * 16 * 8
