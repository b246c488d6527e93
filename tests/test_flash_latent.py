"""LATENT attention in the flash kernels (`ops/flash_attention.py`,
`k_shared=`): q and k wider than v, the rotated key columns ONE shared
operand. The three kernels in the Pallas interpreter against plain attention
over a key built whole, over one and several major blocks; the calls' names
and operand lists (what the benchmark's readers go by); the refusals; and
that a call with one head size is the program it was before."""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import layers as L
from ray_tpu.ops import flash_attention as fa
from ray_tpu.parallel.ring_attention import reference_attention
from tests.test_flash_window import PARENT_JAXPR, _calls, _eqns

NAMES = ("o", "dq", "dk", "dk_shared", "dv")


def _inputs(B, S, H, nope, rope, v_dim):
    ks = jax.random.split(jax.random.PRNGKey(S + H + rope), 5)
    return (jax.random.normal(ks[0], (B, S, H, nope + rope)),
            jax.random.normal(ks[1], (B, S, H, nope)),
            jax.random.normal(ks[2], (B, S, rope)),
            jax.random.normal(ks[3], (B, S, H, v_dim)),
            jax.random.normal(ks[4], (B, S, H, v_dim)))


def _whole_key(k, shared):
    """Every head's key: its own columns, then the shared ones."""
    B, S, H, _ = k.shape
    return jnp.concatenate(
        [k, jnp.broadcast_to(shared[:, :, None], (B, S, H, shared.shape[-1]))],
        axis=-1)


def _both(B, S, H, nope, rope, v_dim, **tiles):
    q, k, shared, v, w = _inputs(B, S, H, nope, rope, v_dim)

    def flash(q, k, shared, v):
        return fa.flash_attention(q, k, v, k_shared=shared, interpret=True,
                                  **tiles)

    def plain(q, k, shared, v):
        return reference_attention(q, _whole_key(k, shared), v)

    out = []
    for fn in (flash, plain):
        o, vjp = jax.vjp(fn, q, k, shared, v)
        dq, dk, dshared, dv = vjp(w)
        out.append((o, dq, dk, dshared, dv))
    return out


@pytest.mark.parametrize("B, S, H, nope, rope, v_dim", [
    (2, 256, 2, 32, 16, 32),      # the cell's proportions: 128 + 64, 128
    (1, 300, 3, 32, 16, 24),      # S no multiple of a tile; v another width
    (2, 128, 4, 24, 8, 16),       # the tiny preset's widths
])
def test_latent_kernels_against_plain_attention(B, S, H, nope, rope, v_dim):
    """Forward and the FOUR gradients (q, a head's own key columns, the
    shared ones summed over the heads that read them, v), scale (nope +
    rope)^-½, in one major block."""
    got, want = _both(B, S, H, nope, rope, v_dim)
    for name, g, r in zip(NAMES, got, want):
        assert g.shape == r.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-4,
                                   err_msg=name)


def test_latent_kernels_over_several_major_blocks(monkeypatch):
    """Four major blocks a side, as the cell's eight: the shared columns'
    index map follows the kv-major block, and the result equals one major
    block's to the bit."""
    one = _both(2, 512, 3, 32, 16, 24, block_q=128, block_k=128)[0]
    monkeypatch.setattr(fa, "VMEM_BUDGET_BYTES", fa.vmem_bytes(128, 48, 4))
    assert fa.tile_plan(512, 48, jnp.float32, 128, 128).dkv.major == 128
    got, want = _both(2, 512, 3, 32, 16, 24, block_q=128, block_k=128)
    for name, g, r, o in zip(NAMES, got, want, one):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-4,
                                   err_msg=name)
        assert np.array_equal(np.asarray(g), np.asarray(o)), name


# ---- tiles by the call's head widths (PR 44): q/k 192, v 128 has its own
LATENT = dict(nope=128, rope=64, v_dim=128)      # the cell's widths


@pytest.mark.parametrize("S, major, s_pad", [
    (512, 512, 512), (1000, 1024, 1024), (1024, 1024, 1024),
    (1100, 512, 1536), (4096, 1024, 4096), (8192, 1024, 8192),
    (16384, 1024, 16384)])
def test_the_latent_widths_have_their_own_tiles(S, major, s_pad):
    """`tile_plan(v_dim=)`: (192, 128) takes `_TILES`' entry of that pair —
    a 256 × 512 forward tile, a 256 × 256 dk/dv tile, dq as it was — for
    bf16 and float32 alike; the sides nest, the kernels share the padded
    length and a major block within `MAJOR_ROWS` and the budget (which
    holds 512 rows of float32 at 192). Widths with no entry (the tiny
    presets' 48 / 24, v as wide as q) get the default's."""
    for dtype, major in ((jnp.bfloat16, major),
                         (jnp.float32, min(major, 512))):
        plans = fa.tile_plan(S, 192, dtype, v_dim=128)
        assert plans == fa.TilePlans(fwd=fa.TilePlan(256, 512, major, s_pad),
                                     dq=fa.TilePlan(256, 256, major, s_pad),
                                     dkv=fa.TilePlan(256, 256, major, s_pad))
        assert major <= fa.MAJOR_ROWS and s_pad % major == 0
        assert fa.vmem_bytes(major, 192, jnp.dtype(dtype).itemsize) \
            <= fa.VMEM_BUDGET_BYTES
        for plan in plans:
            assert major % plan.tile_q == 0 and major % plan.tile_k == 0
        default = fa.tile_plan(S, 192, dtype)
        assert default == fa.tile_plan(S, 192, dtype, v_dim=192) \
            == fa.tile_plan(S, 192, dtype, v_dim=64)
        assert [p[:2] for p in default] == [(128, 256), (256, 256), (128, 128)]
        assert fa.tile_plan(S, 48, dtype, v_dim=24) == fa.tile_plan(S, 48, dtype)
    # the override is still every kernel's, whatever the widths
    assert {p[:2] for p in fa.tile_plan(S, 192, jnp.bfloat16, 128, 128,
                                        v_dim=128)} == {(128, 128)}


def test_the_new_tiles_write_out_fewer_tile_bodies():
    """What the diagonal block of 1,024 rows holds, every tile written out
    (`_walk`): forward 20 → 6, dk/dv 36 → 10, dq 10 as before — so a call
    site traces and lowers no more code than it did."""
    def bodies(plans):
        return [fa._issued(fa._row_bounds(
            plan, 0, 0, transposed=name == "dkv", causal=True,
            seq_len=plan.s_pad, window=None))
            for name, plan in plans._asdict().items()]
    assert bodies(fa.tile_plan(8192, 192, jnp.bfloat16)) == [20, 10, 36]
    assert bodies(fa.tile_plan(8192, 192, jnp.bfloat16, v_dim=128)) == \
        [6, 10, 10]
    for plan in fa.tile_plan(8192, 192, jnp.bfloat16, v_dim=128):
        assert fa.static_tile_share(plan, 8192) == 1.0


@pytest.mark.parametrize("S, H, whole, blocks", [
    (1024, 2, True, 1),      # one major block, as bf16 has at 1,024 rows
    (1024, 1, False, 2),     # float32's own: the budget holds 512 rows
    (1100, 1, False, 3),     # S no multiple of 256: padded to 1,536
], ids=["one_block", "two_blocks", "padded"])
def test_latent_kernels_at_the_new_tiles(monkeypatch, S, H, whole, blocks):
    """The three kernels at the (192, 128) entry's tiles — none overridden,
    none clamped to the sequence — against plain attention: o, dq, dk, the
    shared columns' gradient and dv."""
    if whole:
        monkeypatch.setattr(fa, "VMEM_BUDGET_BYTES",
                            fa.vmem_bytes(1024, 192, 4))
    plans = fa.tile_plan(S, 192, jnp.float32, v_dim=128)
    assert [p[:2] for p in plans] == [(256, 512), (256, 256), (256, 256)]
    assert plans.fwd.s_pad // plans.fwd.major == blocks
    got, want = _both(1, S, H, **LATENT)
    for name, g, r in zip(NAMES, got, want):
        assert g.shape == r.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-4,
                                   err_msg=name)


def test_a_traced_call_takes_the_plan_of_its_own_widths(monkeypatch):
    """Tracing a latent and two equal-width calls, forward and backward:
    each kernel's call asks `tile_plan` for the widths its operands have (q
    and k's, v's), and what it is given is that entry's tiles."""
    asked, plan = {}, fa.tile_plan

    def recording(seq_len, head_dim, dtype, *blocks, v_dim=None):
        plans = plan(seq_len, head_dim, dtype, *blocks, v_dim=v_dim)
        asked[head_dim, v_dim] = tuple(p[:2] for p in plans)
        return plans
    monkeypatch.setattr(fa, "tile_plan", recording)
    q, k, shared, v, _ = _inputs(1, 1024, 1, **LATENT)

    def latent(q, k, shared, v):
        return jnp.sum(fa.flash_attention(q, k, v, k_shared=shared))

    def equal(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v))
    jax.make_jaxpr(jax.grad(latent, (0, 1, 2, 3)))(q, k, shared, v)
    for width in (128, 64):
        x = v[..., :width]
        jax.make_jaxpr(jax.grad(equal, (0, 1, 2)))(x, x, x)
    default = ((128, 256), (256, 256), (128, 128))
    assert asked == {(192, 128): ((256, 512), (256, 256), (256, 256)),
                     (128, 128): default, (64, 64): default}


def test_the_calls_carry_their_names_and_their_own_operand_lists():
    """`flash_latent_fwd` / `_dq` / `_dkv`, with four and seven operands:
    the benchmark's generic reader (`flops.flash_call_cost`: three and six,
    every product at q's width) does not take them for its own, and
    `readers/trace_latent.py` finds them by name. No operand is padded or
    repeated: q at nope + rope, k at nope, the shared columns one row a
    batch row, v and the results at their own widths."""
    q, k, shared, v, w = _inputs(2, 256, 4, 32, 16, 24)

    def f(q, k, shared, v):
        return jnp.sum(w * fa.flash_attention(q, k, v, k_shared=shared))
    jaxpr = jax.make_jaxpr(jax.grad(f, (0, 1, 2, 3)))(q, k, shared, v).jaxpr
    assert sorted(name for name, _ in _calls(jaxpr, [])) == [
        "flash_latent_dkv", "flash_latent_dq", "flash_latent_fwd"]
    shapes = {}
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "pallas_call":
            shapes[eqn.params["name"]] = (
                [x.aval.shape for x in eqn.invars],
                [x.aval.shape for x in eqn.outvars])
    big, row = (8, 256), (8, 2, 8, 128)
    assert shapes["flash_latent_fwd"] == (
        [big + (48,), big + (32,), (2, 256, 16), big + (24,)],
        [big + (24,), row])
    assert shapes["flash_latent_dq"] == (
        [big + (48,), big + (32,), (2, 256, 16), big + (24,), big + (24,),
         (8, 1, 8, 256), (8, 1, 8, 256)], [big + (48,)])
    assert shapes["flash_latent_dkv"] == (
        [big + (48,), big + (32,), (2, 256, 16), big + (24,), big + (24,),
         row, row], [big + (32,), big + (16,), big + (24,)])


def test_what_the_latent_call_refuses():
    q, k, shared, v, _ = _inputs(1, 256, 2, 32, 16, 32)
    with pytest.raises(ValueError, match="no window"):
        fa.flash_attention(q, k, v, k_shared=shared, window=100,
                           interpret=True)
    with pytest.raises(ValueError, match="q's width 48"):
        fa.flash_attention(q, k, v, k_shared=shared[..., :8], interpret=True)


@pytest.mark.parametrize("shape", sorted(PARENT_JAXPR))
def test_one_head_size_traces_to_the_parents_jaxpr(shape):
    """A second head size adds no case to a call that has one: without
    `k_shared` forward and backward trace to the jaxpr PR 39's kernels gave
    (`tests/test_flash_window.py` holds the digests and says how they are
    taken), names aside — `shared` is an empty tuple, the k operands one."""
    S, H, KV, D = shape
    q = jnp.zeros((1, S, H, D), jnp.bfloat16)
    k = jnp.zeros((1, S, KV, D), jnp.bfloat16)

    def f(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, k_shared=None)
                       .astype(jnp.float32))
    text = str(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, k, k))
    text = re.sub(r"\.py:\d+", ".py", re.sub(r"0x[0-9a-f]+", "0x", text))
    assert "flash_latent" not in text
    unnamed = re.sub(r"name=flash_(?:fwd|dq|dkv)\b", "name=None", text)
    assert hashlib.sha256(unnamed.encode()).hexdigest()[:16] == \
        PARENT_JAXPR[shape]


def test_apply_latent_attention_flash_equals_reference(monkeypatch):
    """The layer hands the kernels q whole, k's own columns, the ONE rotated
    head and v, and builds the whole key only off them."""
    cfg = L.LatentConfig(n_head=4, q_rank=48, kv_rank=32, nope_dim=24,
                         rope_dim=8, v_dim=16, rope_theta=1e4)
    params = L.init_latent_attention(jax.random.PRNGKey(1), 64, cfg)
    assert {k: v.shape for k, v in params.items()} == {
        "wq_a": (64, 48), "q_norm": (48,), "wq_b": (48, 4, 32),
        "wkv_a": (64, 40), "kv_norm": (32,), "wkv_b": (32, 4, 40),
        "wo": (4, 16, 64)}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 64))

    def loss(params, impl):
        return jnp.sum(jnp.square(L.apply_latent_attention(
            params, x, cfg, impl=impl, compute_dtype=jnp.float32)))
    grads_of = jax.jit(jax.value_and_grad(loss), static_argnums=1)
    want = grads_of(params, "reference")
    seen = []
    original = fa.flash_attention

    def interpreted(q, k, v, **kw):
        seen.append((q.shape, k.shape, v.shape, kw["k_shared"].shape))
        return original(q, k, v, **dict(kw, interpret=True))
    monkeypatch.setattr(fa, "flash_attention", interpreted)
    got = grads_of(params, "flash")
    assert seen == [((2, 128, 4, 32), (2, 128, 4, 24), (2, 128, 4, 16),
                     (2, 128, 8))]
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for name in params:
        np.testing.assert_allclose(got[1][name], want[1][name], atol=2e-4,
                                   err_msg=name)
    with pytest.raises(ValueError, match="no 'ring' path"):
        L.apply_latent_attention(params, x, cfg, impl="ring")


@pytest.mark.parametrize("width", [8, 64])
def test_interleaved_rotation_against_complex_numbers(width):
    """`rope(interleaved=True)`: columns (2i, 2i + 1) are the real and
    imaginary part of a number multiplied by e^{i · pos · θ^(−2i/K)}; a
    column stays where it was; the half-split pairing is the same turn of
    other pairs; and a PART of a head is rotated by handing that part."""
    theta = 32e6
    x = jax.random.normal(jax.random.PRNGKey(width), (2, 40, 3, width))
    got = np.asarray(L.rope(x, theta, interleaved=True), np.float64)
    z = np.asarray(x, np.float64)[..., 0::2] \
        + 1j * np.asarray(x, np.float64)[..., 1::2]
    angle = np.arange(40)[:, None] * theta ** (
        -2.0 * np.arange(width // 2) / width)
    turned = z * np.exp(1j * angle)[None, :, None, :]
    np.testing.assert_allclose(got[..., 0::2], turned.real, atol=2e-5)
    np.testing.assert_allclose(got[..., 1::2], turned.imag, atol=2e-5)
    # position 0 is not turned, and a turn keeps every pair's length
    np.testing.assert_array_equal(got[:, 0], np.asarray(x[:, 0], np.float64))
    np.testing.assert_allclose(
        got[..., 0::2] ** 2 + got[..., 1::2] ** 2, np.abs(z) ** 2, rtol=1e-4)
    # the half-split pairing turns (i, i + K/2) by the same angles
    halves = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    split = np.asarray(L.rope(halves, theta))
    np.testing.assert_allclose(split[..., :width // 2], got[..., 0::2],
                               atol=2e-5)
    np.testing.assert_allclose(split[..., width // 2:], got[..., 1::2],
                               atol=2e-5)
    # the scores of two rotated rows depend on their distance alone
    a, b = got[0, 5, 0], got[0, 9, 0]
    shifted = np.asarray(L.rope(jnp.roll(x, 7, axis=1), theta,
                                interleaved=True), np.float64)
    np.testing.assert_allclose(shifted[0, 12, 0] @ shifted[0, 16, 0], a @ b,
                               rtol=1e-4, atol=1e-4)
