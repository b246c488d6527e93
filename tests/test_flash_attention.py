"""The flash-attention kernels' tile schedule (ops/flash_attention.py): the
pure plan function, and o / dq / dk / dv against reference_attention through
the Pallas interpreter on CPU, with the derived tiles and the 128 override."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention as fa
from ray_tpu.parallel.ring_attention import reference_attention
from tests.test_flash_window import _calls, _eqns


def _out_and_grads(attend, q, k, v, w):
    o, vjp = jax.vjp(attend, q, k, v)
    return (o, *vjp(w))          # w: a non-symmetric cotangent


def _inputs(S, D, dtype=jnp.float32, heads=1):
    keys = jax.random.split(jax.random.PRNGKey(S * 131 + D), 4)
    return [jax.random.normal(key, (1, S, heads, D), dtype) for key in keys]


@pytest.mark.parametrize("blocks", [None, 128], ids=["derived", "b128"])
@pytest.mark.parametrize("S,D", [(128, 32), (128, 64), (192, 32), (192, 64),
                                 (256, 32), (256, 64), (1024, 64)])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_matches_reference(causal, S, D, blocks):
    """S = 192 is padded; S = 1,024 at D = 64 is the benchmark's shape:
    several kv tiles to a q tile, split into plain and diagonal ones (the
    interpreter makes it the slow case, so D = 32 is left to the short
    ones)."""
    q, k, v, w = _inputs(S, D)
    got = _out_and_grads(
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, block_q=blocks, block_k=blocks,
            interpret=True), q, k, v, w)
    ref = _out_and_grads(
        lambda q, k, v: reference_attention(q, k, v, causal=causal),
        q, k, v, w)
    for name, g, r, atol in zip(("o", "dq", "dk", "dv"), got, ref,
                                (2e-5, 1e-4, 1e-4, 1e-4)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=atol,
                                   err_msg=f"{name} (S={S}, D={D})")


@pytest.mark.parametrize("S,D", [(128, 64), (192, 64), (128, 128),
                                 (192, 128)])
def test_vjp_keeps_o_as_the_model_reads_it(S, D):
    """The backward keeps `o` as [B, S, H, D] (dense in HBM at D = 64, where
    the kernels' [B·H, S, D] is padded to 128 lanes) and takes the row sums
    of do · o in that layout: with several batch rows and heads a wrong
    order of the two would show in every gradient. S = 192 is padded."""
    B, H = 2, 3
    keys = jax.random.split(jax.random.PRNGKey(S + D), 4)
    q, k, v, w = (jax.random.normal(key, (B, S, H, D)) for key in keys)
    o, res = fa._flash_vjp_fwd(*(fa._to_bh(x) for x in (q, k, v)), (), H,
                               D ** -0.5, True, None, None, True)
    assert o.shape == (B, S, H, D) and res[4] is o  # q, k, v, shared (none)
    assert res[5].shape == (B * H, S)           # lse, as the kernels read it
    got = _out_and_grads(
        lambda q, k, v: fa.flash_attention(q, k, v, interpret=True),
        q, k, v, w)
    ref = _out_and_grads(reference_attention, q, k, v, w)
    for name, g, r, atol in zip(("o", "dq", "dk", "dv"), got, ref,
                                (2e-5, 1e-4, 1e-4, 1e-4)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=atol,
                                   err_msg=f"{name} (S={S}, D={D})")


def test_flash_bf16_within_the_chip_smoke_tolerance():
    """bf16 operands, f32 scores and statistics: the relative error
    chip_smoke.py allows the compiled kernels (0.02)."""
    q, k, v, w = _inputs(256, 64, jnp.bfloat16, heads=2)
    got = _out_and_grads(
        lambda q, k, v: fa.flash_attention(q, k, v, interpret=True),
        q, k, v, w)
    ref = _out_and_grads(
        reference_attention, *(x.astype(jnp.float32) for x in (q, k, v, w)))
    for name, g, r in zip(("o", "dq", "dk", "dv"), got, ref):
        g, r = np.asarray(g, np.float32), np.asarray(r)
        err = np.max(np.abs(g - r)) / max(1.0, np.max(np.abs(r)))
        assert err <= 0.02, (name, err)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_several_major_blocks(monkeypatch, causal):
    """A VMEM budget that holds a quarter of the sequence: four q-major and
    four kv-major grid blocks, one schedule of Python-int trip counts for
    each offset between them (`_grid_cases`; traced loop bounds before
    PR 39), clamped index maps; the padded tail (S = 450 of 512) sits in the
    last one."""
    monkeypatch.setattr(fa, "VMEM_BUDGET_BYTES", 1_100_000)
    S, D = 450, 64
    assert fa.tile_plan(S, D, jnp.float32, 128, 128).fwd == \
        fa.TilePlan(128, 128, 128, 512)
    q, k, v, w = _inputs(S, D, heads=2)
    got = _out_and_grads(
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, block_q=128, block_k=128,
            interpret=True), q, k, v, w)
    ref = _out_and_grads(
        lambda q, k, v: reference_attention(q, k, v, causal=causal),
        q, k, v, w)
    for name, g, r in zip(("o", "dq", "dk", "dv"), got, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-4,
                                   err_msg=name)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_head_dim_128_several_major_blocks(monkeypatch, causal):
    """The OLMoE cell's form at a size the interpreter can run: heads of
    128 and more than one major block (three of 128 rows under a budget
    that holds a third of S = 384), so every kernel holds the diagonal
    block's schedule and the whole block's under `pl.when` (traced loops
    before PR 39) and its index maps are clamped, as at [32, 4096, 128] on
    the chip."""
    monkeypatch.setattr(fa, "VMEM_BUDGET_BYTES", 1_100_000)
    S, D = 384, 128
    assert fa.tile_plan(S, D, jnp.float32, 128, 128).dkv == \
        fa.TilePlan(128, 128, 128, 384)
    q, k, v, w = _inputs(S, D, heads=2)
    got = _out_and_grads(
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, block_q=128, block_k=128,
            interpret=True), q, k, v, w)
    ref = _out_and_grads(
        lambda q, k, v: reference_attention(q, k, v, causal=causal),
        q, k, v, w)
    for name, g, r in zip(("o", "dq", "dk", "dv"), got, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-4,
                                   err_msg=name)


def test_tile_plan_at_the_olmoe_cells_shape():
    """[32, 4096, 128] bf16 (olmoe1l-b2s4k): the budget would hold half the
    sequence and `MAJOR_ROWS` holds a quarter (PR 39: the code a kernel
    writes out grows with the square of the block), so each kernel has four
    major blocks a side; the causal schedule issues 6 % (3 % in dk/dv's
    smaller tile) more score elements than the mask keeps, whatever the
    block."""
    plans = fa.tile_plan(4096, 128, jnp.bfloat16)
    assert plans == fa.TilePlans(fwd=fa.TilePlan(128, 256, 1024, 4096),
                                 dq=fa.TilePlan(256, 256, 1024, 4096),
                                 dkv=fa.TilePlan(128, 128, 1024, 4096))
    assert fa.MAJOR_ROWS == 1024
    assert fa.vmem_bytes(2048, 128, 2) <= fa.VMEM_BUDGET_BYTES \
        < fa.vmem_bytes(4096, 128, 2)
    assert fa.issued_area_ratio(plans.fwd, 4096) == pytest.approx(1.0622,
                                                                  abs=1e-4)
    assert fa.issued_area_ratio(plans.dq, 4096) == pytest.approx(1.0622,
                                                                 abs=1e-4)
    assert fa.issued_area_ratio(plans.dkv, 4096) == pytest.approx(1.0310,
                                                                  abs=1e-4)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("D", [32, 64, 128, 256])
def test_equal_widths_keep_the_plans_they_had(D, dtype):
    """A call whose q/k and v widths are equal takes `_TILES`' default, with
    or without `v_dim=`: the plans PR 43's `tile_plan` gave, spelled out —
    forward 128 × 256, dq 256 × 256, dk/dv 128 × 128 (clamped to S rounded
    up to 128), padded to the largest tile, a major block of at most 1,024
    rows (512 for float32 at 256: the budget)."""
    most = 512 if (D, dtype) == (256, jnp.float32) else 1024
    for S, major, s_pad in [(200, 256, 256), (512, 512, 512),
                            (1000, most, 1024), (4096, most, 4096),
                            (8192, most, 8192), (16384, most, 16384)]:
        want = fa.TilePlans(fwd=fa.TilePlan(128, 256, major, s_pad),
                            dq=fa.TilePlan(256, 256, major, s_pad),
                            dkv=fa.TilePlan(128, 128, major, s_pad))
        assert fa.tile_plan(S, D, dtype) == want
        assert fa.tile_plan(S, D, dtype, v_dim=D) == want
    assert fa.tile_plan(100, D, dtype) == fa.TilePlans(
        *[fa.TilePlan(128, 128, 128, 128)] * 3)


@pytest.mark.parametrize("S,D", [(1024, 64), (2048, 64), (4096, 128),
                                 (192, 32)])
def test_tile_plan_shapes(S, D):
    plans = fa.tile_plan(S, D, jnp.bfloat16)
    assert len({(p.major, p.s_pad) for p in plans}) == 1   # shared
    for plan in plans:
        assert plan.s_pad >= S and plan.s_pad - S < max(plan.tile_q,
                                                        plan.tile_k)
        # (8, 128) tiling: a tile side is the lane dimension of a score tile
        # or an lse row block, and a sublane multiple of any dtype
        assert plan.tile_q % 128 == 0 and plan.tile_k % 128 == 0
        assert plan.major % plan.tile_q == 0 and plan.major % plan.tile_k == 0
        assert plan.s_pad % plan.major == 0
        assert fa.vmem_bytes(plan.major, D, 2) <= fa.VMEM_BUDGET_BYTES
        assert plan.major <= fa.MAJOR_ROWS
    if S <= 1024:
        assert plans.fwd.major == plans.fwd.s_pad   # one block: K/V once a head


def test_tile_plan_issued_area():
    """The engagement counter: score elements the causal forward issues over
    those the mask keeps, at the benchmark's S = 1,024."""
    derived = fa.tile_plan(1024, 64, jnp.bfloat16)
    assert fa.issued_area_ratio(derived.fwd, 1024) <= 1.25
    assert fa.issued_area_ratio(derived.dkv, 1024) <= 1.25
    # the fixed 512 x 512 blocks this schedule replaced
    old = fa.tile_plan(1024, 64, jnp.bfloat16, 512, 512).fwd
    assert fa.issued_area_ratio(old, 1024) == pytest.approx(1.5, abs=0.01)


def test_tile_ranges_cover_exactly_the_unmasked_tiles():
    """_kv_tiles / _q_tiles against the mask itself: a tile is issued iff the
    causal mask keeps one of its elements, and runs unmasked iff it keeps
    all of them; both views of the triangle agree."""
    plan = fa.TilePlan(128, 256, 1024, 1024)
    keep = np.tril(np.ones((1024, 1024), bool))
    issued_by_rows = set()
    for qi in range(1024 // plan.tile_q):
        n_plain, n_issued = fa._kv_tiles(qi * plan.tile_q, 0, 4, plan=plan,
                                         causal=True, seq_len=1024)
        for t in range(4):
            tile = keep[qi * 128:(qi + 1) * 128, t * 256:(t + 1) * 256]
            assert (t < n_issued) == bool(tile.any())
            assert (t < n_plain) == bool(tile.all())
            if t < n_issued:
                issued_by_rows.add((qi, t))
    issued_by_cols = set()
    for t in range(4):
        first, plain_from = fa._q_tiles(t * plan.tile_k, 0, 8, plan=plan,
                                        causal=True, seq_len=1024)
        for qi in range(8):
            tile = keep[qi * 128:(qi + 1) * 128, t * 256:(t + 1) * 256]
            assert (qi >= first) == bool(tile.any())
            assert (qi >= plain_from) == bool(tile.all())
            if qi >= first:
                issued_by_cols.add((qi, t))
    assert issued_by_rows == issued_by_cols


@pytest.mark.parametrize("window,names", [
    (None, ["flash_dkv", "flash_dq", "flash_fwd"]),
    (1024, ["flash_dkv", "flash_dq", "flash_fwd"]),   # covers the sequence
    (200, ["flash_window_dkv", "flash_window_dq", "flash_window_fwd"]),
])
def test_the_three_calls_carry_their_names_in_the_jaxpr(window, names):
    """`pallas_call`'s `name` is the HLO instruction's and the trace
    event's: the full-causal calls have theirs, the windowed ones keep
    theirs, and a reader tells the kernels by it."""
    q = jnp.zeros((1, 1024, 2, 64), jnp.bfloat16)

    def f(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, window=window)
                       .astype(jnp.float32))
    jaxpr = jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, q, q).jaxpr
    assert sorted(name for name, _ in _calls(jaxpr, [])) == names


def _loops(jaxpr):
    """Every loop of a jaxpr, kernels' bodies included: a `scan` (a
    Python-int trip count: its length) or a `while` (a traced bound)."""
    return [(eqn.primitive.name, eqn.params.get("length"))
            for eqn in _eqns(jaxpr) if eqn.primitive.name in ("scan", "while")]


@pytest.mark.parametrize("S,H,KV,D,window", [
    (1024, 2, 2, 64, None),       # the GPT-2 cells: one major block
    (4096, 2, 2, 128, None),      # olmoe1l-b2s4k: 4 x 4 major blocks
    (8192, 4, 1, 128, None),      # nemotronh9l-b1s8k: 8 x 8, grouped KV heads
    (16384, 2, 1, 128, None),     # smallthinker4l-b1s16k, the global layer
    (16384, 2, 1, 128, 4096),     # and its window layers: 16 x 5 grid steps
])
def test_every_loop_of_the_kernels_has_a_static_trip_count(S, H, KV, D,
                                                           window):
    """The three kernels as the D 128 cells trace them (PR 39): no loop in
    a `pallas_call`'s body has a traced bound, whatever the number of major
    blocks — a traced one is a `while`, which the TPU scheduler does not
    overlap across trips (1.8 x a tile, PR 26; 2.7 x a call at
    [28, 16384, 128]) — and `static_tile_share`, computed from the same
    cases, reads 1.0."""
    q = jnp.zeros((1, S, H, D), jnp.bfloat16)
    k = jnp.zeros((1, S, KV, D), jnp.bfloat16)

    def f(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, window=window)
                       .astype(jnp.float32))
    jaxpr = jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, k, k).jaxpr
    assert len(_calls(jaxpr, [])) == 3
    loops = _loops(jaxpr)
    assert loops and all(kind == "scan" for kind, _ in loops), \
        [loop for loop in loops if loop[0] != "scan"][:5]
    plans = fa.tile_plan(S, D, jnp.bfloat16)
    assert plans.fwd.s_pad // plans.fwd.major == S // 1024
    for plan, transposed in ((plans.fwd, False), (plans.dq, False),
                             (plans.dkv, True)):
        assert fa.static_tile_share(plan, S, window,
                                    transposed=transposed) == 1.0
        # a walk's trip counts are at most a major block's tiles
        assert max(n for _, n in loops) <= plan.major // 128


def test_static_tile_share_counts_the_cases_not_the_claim(monkeypatch):
    """`static_tile_share` sums what `_grid_cases` gives each grid step
    against what the masks ask of that step: a case that a step does not
    meet, or one with another step's trip counts, shows as a share off 1.0."""
    plan = fa.TilePlan(128, 128, 128, 512)
    assert fa.static_tile_share(plan, 512) == 1.0
    assert fa.static_tile_share(plan, 450, causal=False) == 1.0
    assert fa.static_tile_share(plan, 512, 257, transposed=True) == 1.0
    cases = fa._grid_cases
    monkeypatch.setattr(fa, "_grid_cases", lambda *args, **kw: [
        case for case in cases(*args, **kw) if fa._masked(case.rows)])
    # 4 diagonal tiles of the 10 the causal grid issues
    assert fa.static_tile_share(plan, 512) == pytest.approx(0.4)
