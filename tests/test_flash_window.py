"""The causal WINDOW in the flash kernels (`ops/flash_attention.py`): the
three kernels in the Pallas interpreter against plain masked attention, the
windowed tile schedule against the mask itself, what `window_plan` counts,
and that `window=None` is the program it was before."""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import layers as L
from ray_tpu.ops import flash_attention as fa
from ray_tpu.parallel.ring_attention import reference_attention


def _inputs(S, H, KV, D, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(S + H), 4)
    return (jax.random.normal(ks[0], (1, S, H, D), dtype),
            jax.random.normal(ks[1], (1, S, KV, D), dtype),
            jax.random.normal(ks[2], (1, S, KV, D), dtype),
            jax.random.normal(ks[3], (1, S, H, D), dtype))


def _out_and_grads(fn, q, k, v, w):
    o, vjp = jax.vjp(fn, q, k, v)
    return (o,) + vjp(w)


def _against_plain(S, W, H, KV, D=32, **tiles):
    q, k, v, w = _inputs(S, H, KV, D)
    group = H // KV
    got = _out_and_grads(
        lambda q, k, v: fa.flash_attention(q, k, v, window=W, interpret=True,
                                           **tiles), q, k, v, w)
    want = _out_and_grads(
        lambda q, k, v: reference_attention(
            q, jnp.repeat(k, group, 2), jnp.repeat(v, group, 2), window=W),
        q, k, v, w)
    for name, g, r in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-4,
                                   err_msg=f"{name} S={S} W={W}")


@pytest.mark.parametrize("S, W, H, KV", [
    (512, 200, 7, 1),     # four tiles a side, W no multiple of a tile, GQA 7
    (512, 128, 2, 2),     # W one tile exactly
    (512, 50, 2, 1),      # narrower than a tile: a tile crosses both edges
    (384, 300, 2, 1),     # S no multiple of dq's 256: padded rows and columns
    (512, 1, 2, 2),       # the query itself and nothing else
])
def test_windowed_kernels_against_plain_masked_attention(S, W, H, KV):
    _against_plain(S, W, H, KV, block_q=128, block_k=128)


@pytest.mark.parametrize("W", [100, 257, 600])
def test_windowed_kernels_over_several_major_blocks(monkeypatch, W):
    """The form the cell runs: four major blocks a side, of which a window
    reaches 2, 3 or all 4, so the sequential grid axis is shorter than the
    parallel one, its blocks are `_first_block`'s, and a kernel holds one
    schedule of Python-int trip counts for each block a window reaches
    (`_grid_cases`; traced loop bounds before PR 39)."""
    monkeypatch.setattr(fa, "VMEM_BUDGET_BYTES", 300 * 1024)
    plan = fa.tile_plan(1024, 32, jnp.float32).fwd
    assert plan.major == 256 and plan.s_pad == 1024
    assert fa._seq_blocks(plan, W) == {100: 2, 257: 2, 600: 4}[W]
    _against_plain(1024, W, 2, 1)


def test_a_window_that_covers_the_sequence_is_the_causal_call():
    q, k, v, _ = _inputs(256, 2, 1, 32)
    kw = dict(block_q=128, block_k=128, interpret=True)
    causal = fa.flash_attention(q, k, v, **kw)
    for window in (256, 4096):
        np.testing.assert_array_equal(
            fa.flash_attention(q, k, v, window=window, **kw), causal)
    # and one key short of it is not
    assert np.abs(np.asarray(
        fa.flash_attention(q, k, v, window=255, **kw) - causal)).max() > 0


def test_a_window_needs_a_causal_path_that_knows_it():
    q, k, v, _ = _inputs(128, 2, 2, 32)
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, k, v, causal=False, window=64, interpret=True)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, window=0, interpret=True)
    params = L.init_attention(jax.random.PRNGKey(0), 64, 2)
    x = jnp.zeros((1, 128, 64))
    for impl in ("ring", "ring_local"):
        with pytest.raises(ValueError, match="ring attention has no window"):
            L.apply_attention(params, x, impl=impl, window=64)


@pytest.mark.parametrize("W", [1, 100, 128, 300, 640, 1024])
def test_window_tile_ranges_cover_exactly_the_kept_tiles(W):
    """`_window_kv_tiles` / `_window_q_tiles` against the mask itself: a tile
    is issued iff the mask keeps one of its elements and runs unmasked iff
    it keeps all of them; no tile wholly outside the window is issued, from
    either side of the triangle."""
    plan = fa.TilePlan(128, 256, 1024, 1024)
    i = np.arange(1024)
    keep = (i[None, :] <= i[:, None]) & (i[None, :] > i[:, None] - W)
    by_rows = set()
    for qi in range(8):
        first, edge_end, plain_end, end = fa._window_kv_tiles(
            qi * 128, 0, 4, plan=plan, window=W)
        assert 0 <= first <= edge_end <= plain_end <= end <= 4
        for t in range(4):
            tile = keep[qi * 128:(qi + 1) * 128, t * 256:(t + 1) * 256]
            assert (first <= t < end) == bool(tile.any()), (qi, t)
            assert (edge_end <= t < plain_end) == bool(tile.all()), (qi, t)
            if first <= t < end:
                by_rows.add((qi, t))
    by_cols = set()
    for t in range(4):
        first, diag_end, plain_end, end = fa._window_q_tiles(
            t * 256, 0, 8, plan=plan, window=W)
        assert 0 <= first <= diag_end <= plain_end <= end <= 8
        for qi in range(8):
            tile = keep[qi * 128:(qi + 1) * 128, t * 256:(t + 1) * 256]
            assert (first <= qi < end) == bool(tile.any()), (qi, t)
            assert (diag_end <= qi < plain_end) == bool(tile.all()), (qi, t)
            if first <= qi < end:
                by_cols.add((qi, t))
    assert by_rows == by_cols


def test_window_plan_at_the_smallthinker_cells_shape():
    """[28, 16384, 128] bf16, window 4,096 (smallthinker4l-b1s16k): sixteen
    major blocks a side (`MAJOR_ROWS`, PR 39; eight before) of which a
    window reaches five; what the windowed forward keeps, issues and skips,
    by the kernel's own trip counts."""
    S, W = 16384, 4096
    plans = fa.tile_plan(S, 128, jnp.bfloat16)
    assert plans.fwd == fa.TilePlan(128, 256, 1024, S)
    assert fa._seq_blocks(plans.fwd, W) == 5 == fa._seq_blocks(plans.dkv, W)
    plan = fa.window_plan(S, W, plans.fwd)
    kept = W * S - W * (W - 1) // 2
    assert plan["kept_area"] == kept == 58_722_304
    # 43.7 % of the causal area
    assert kept / (S * (S + 1) / 2) == pytest.approx(0.4375, abs=1e-3)
    # a q tile of 128 rows issues the 17 kv tiles of 256 its rows' windows
    # touch (4,096 + 127 columns, on 256-column boundaries: 17 or 18)
    assert plan["issued_area"] == plan["tiles_issued"] * 128 * 256
    assert 1.0 < plan["issued_area"] / kept < 1.10
    assert fa.issued_area_ratio(plans.fwd, S, W) == \
        plan["issued_area"] / kept
    assert fa.issued_area_ratio(plans.fwd, S, W) == pytest.approx(1.0625,
                                                                  abs=1e-3)
    # every tile a causal call issues is issued or skipped here
    causal_tiles = round(fa.issued_area_ratio(plans.fwd, S)
                         * (S * (S + 1) / 2) / (128 * 256))
    assert plan["tiles_issued"] + plan["tiles_skipped"] == causal_tiles
    assert plan["tiles_skipped"] / causal_tiles == pytest.approx(0.545,
                                                                 abs=5e-3)
    # 16 x 5 grid steps a head where the causal call walks 16 x 16 (ten of
    # the 80 are the first four rows' surplus), and 70 K/V major blocks
    # fetched where it fetches 136
    assert plan["grid_steps"] == 80 and plan["blocks_fetched"] == 70
    # a window that covers everything is the causal schedule
    whole = fa.window_plan(S, S, plans.fwd)
    assert whole["tiles_skipped"] == 0 and whole["grid_steps"] == 256
    assert whole["blocks_fetched"] == 16 * 17 // 2
    assert whole["issued_area"] / whole["kept_area"] == \
        fa.issued_area_ratio(plans.fwd, S)


@pytest.mark.parametrize("S, H, KV, causal, W, major", [
    (512, 2, 2, True, None, 128),   # diagonal and whole blocks
    (450, 2, 2, False, None, 128),  # padded columns in the last kv block only
    (512, 4, 1, True, None, 128),   # a group of four query heads a KV head
    (512, 4, 1, True, 100, 128),    # a window that reaches two major blocks
    (512, 2, 1, True, 257, 128),    # three; row 0's, 1's surplus steps empty
    (512, 2, 2, True, 128, 128),    # its lower edge on a block's boundary
    (512, 2, 1, True, None, 256),   # two rows of tiles a block, two blocks
    (512, 2, 1, True, 300, 256),    # and a window's edge inside a block
], ids=["causal", "full-padded", "group4", "window2", "window3", "edge",
        "rows2", "rows2-window"])
def test_several_major_blocks_are_one_major_block_bitwise(monkeypatch, S, H,
                                                          KV, causal, W,
                                                          major):
    """The schedule of a grid step is a function of where its q-major block
    lies from its kv-major block (`_grid_cases`): the same tiles in the
    same order with the same masks as one major block walks them, the
    carries through float32 scratch in between — so four major blocks a
    side (two of two rows of tiles each) give `o`, `dq`, `dk` and `dv` of
    one bit for bit."""
    q, k, v, w = _inputs(S, H, KV, 32)

    def run():
        return _out_and_grads(
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=causal, window=W, block_q=128, block_k=128,
                interpret=True), q, k, v, w)
    plan = fa.tile_plan(S, 32, jnp.float32, 128, 128).dkv
    assert plan.major == plan.s_pad == 512
    one = run()
    monkeypatch.setattr(fa, "VMEM_BUDGET_BYTES", fa.vmem_bytes(major, 32, 4))
    plan = fa.tile_plan(S, 32, jnp.float32, 128, 128).dkv
    assert plan.major == major and plan.s_pad == 512
    if W is not None:
        assert fa._seq_blocks(plan, W) == {100: 2, 257: 3, 128: 2, 300: 2}[W]
    for name, a, b in zip(("o", "dq", "dk", "dv"), run(), one):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters
    (kernels' bodies, loops' bodies, branches)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else [value]):
                if hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                    yield from _eqns(sub.jaxpr)
                elif hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _calls(jaxpr, found):
    found += [(eqn.params["name"], eqn.params["grid_mapping"].grid)
              for eqn in _eqns(jaxpr) if eqn.primitive.name == "pallas_call"]
    return found


def test_windowed_calls_are_named_and_walk_a_shorter_grid(monkeypatch):
    monkeypatch.setattr(fa, "VMEM_BUDGET_BYTES", 300 * 1024)
    q, k, v, w = _inputs(1024, 2, 1, 32)

    def calls(window):
        def f(q, k, v):
            return jnp.sum(w * fa.flash_attention(q, k, v, window=window))
        return _calls(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, k, v).jaxpr,
                      [])
    assert sorted(calls(200)) == [
        ("flash_window_dkv", (2, 4, 2)), ("flash_window_dq", (2, 4, 2)),
        ("flash_window_fwd", (2, 4, 2))]
    # the full-causal calls carry their own names and walk the whole grid
    assert sorted(calls(None)) == [
        ("flash_dkv", (2, 4, 4)), ("flash_dq", (2, 4, 4)),
        ("flash_fwd", (2, 4, 4))]


# sha256 (first 16 digits) of the jaxpr of forward + backward at `window=None`
# as THE PARENT OF PR 36 traced it (commit 5d66e18, JAX 0.9.0; addresses and
# source line numbers stripped): the windowed schedule is Python-level
# branches, and without a window none of them adds or moves an operation.
# Since PR 38 the three calls carry a name where that trace had none: the
# one difference, taken out before hashing.
# (4096, 4, 2, 128) was "f0c38224bea791bd" until PR 39 moved it BY DESIGN:
# with several major blocks the kernels now hold one schedule of Python-int
# trip counts for each offset between a step's blocks, under `pl.when`, where
# they held loops bounded by program ids, and a major block has at most
# `MAJOR_ROWS` rows (four a side here, two before). One major block, the
# first case, is the trace it was.
PARENT_JAXPR = {(1024, 4, 4, 64): "c40b43bd2c945677",
                (4096, 4, 2, 128): "dc2f8ba272ecf275"}


@pytest.mark.parametrize("shape", sorted(PARENT_JAXPR))
def test_no_window_traces_to_the_parents_jaxpr(shape):
    S, H, KV, D = shape
    q = jnp.zeros((1, S, H, D), jnp.bfloat16)
    k = jnp.zeros((1, S, KV, D), jnp.bfloat16)

    def f(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v).astype(jnp.float32))
    text = str(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, k, k))
    text = re.sub(r"\.py:\d+", ".py", re.sub(r"0x[0-9a-f]+", "0x", text))
    assert len(re.findall(r"name=flash_(?:fwd|dq|dkv)\b", text)) == 3
    unnamed = re.sub(r"name=flash_(?:fwd|dq|dkv)\b", "name=None", text)
    assert hashlib.sha256(unnamed.encode()).hexdigest()[:16] == \
        PARENT_JAXPR[shape]
    # and a window that covers the sequence is that very trace
    def g(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, window=S)
                       .astype(jnp.float32))
    again = str(jax.make_jaxpr(jax.grad(g, (0, 1, 2)))(q, k, k))
    again = re.sub(r"\.py:\d+", ".py", re.sub(r"0x[0-9a-f]+", "0x", again))
    assert again == text


def test_apply_attention_hands_the_window_to_both_paths(monkeypatch):
    params = L.init_attention(jax.random.PRNGKey(1), 64, 4, n_kv_head=2,
                              head_dim=16)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 64))
    plain = L.apply_attention(params, x, impl="reference", window=40,
                              compute_dtype=jnp.float32)
    causal = L.apply_attention(params, x, impl="reference",
                               compute_dtype=jnp.float32)
    # the first 40 queries see every earlier key either way
    np.testing.assert_allclose(plain[:, :40], causal[:, :40], atol=1e-6)
    assert np.abs(np.asarray(plain[:, 40:] - causal[:, 40:])).max() > 1e-4
    original = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention", lambda *a, **kw: original(
        *a, **dict(kw, interpret=True)))
    flash = L.apply_attention(params, x, impl="flash", window=40,
                              compute_dtype=jnp.float32)
    np.testing.assert_allclose(flash, plain, atol=2e-5)
