"""ops/grouped_matmul.py on the CPU: JAX's Pallas `gmm` / `tgmm` in interpret
mode, at shapes the tiles divide, against `lax.ragged_dot` — values and both
gradients — and the two pure functions beside them (`tile_plan`,
`issued_ratio`) against counts made by hand and the kernel's own metadata."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import grouped_matmul as gm

M, K, N, G = 768, 256, 128, 4      # three row tiles of 256
# name -> every row's group; the first G have a matrix
ROUTINGS = {
    "even": [192, 192, 192, 192],
    "skewed": [384, 100, 184, 100],            # one group half the rows
    "empty_groups": [0, 500, 0, 268],
    "one_group_has_all": [0, 0, 768, 0],
    # under `ep`: the other devices' experts' rows lie behind the local ones
    "rows_past_the_last_group": [200, 56, 100, 156, 200, 0, 56],
    "no_local_rows": [0, 0, 0, 0, 768],
}


def _operands(dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(keys[0], (M, K), dtype),
            jax.random.normal(keys[1], (G, K, N), dtype) / 16,
            jax.random.normal(keys[2], (M, N), jnp.float32))


@pytest.mark.parametrize("routing", ROUTINGS)
def test_values_and_both_gradients_are_ragged_dots(routing):
    sizes = jnp.asarray(ROUTINGS[routing], jnp.int32)
    lhs, rhs, weight = _operands()
    local = int(sizes[:G].sum())

    def kernel(lhs, rhs):
        out = gm.grouped_matmul(lhs, rhs, sizes, interpret=True)
        return jnp.sum(out * weight), out

    def plain(lhs, rhs):
        out = jax.lax.ragged_dot(lhs, rhs, sizes[:G])
        return jnp.sum(out * weight), out

    (_, got), got_grads = jax.value_and_grad(kernel, (0, 1), has_aux=True)(
        lhs, rhs)
    (_, want), want_grads = jax.value_and_grad(plain, (0, 1), has_aux=True)(
        lhs, rhs)
    assert got.shape == (M, N) and got.dtype == lhs.dtype
    np.testing.assert_allclose(got, want, atol=2e-5)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, atol=2e-5)
    # rows that belong to no matrix here: zero and finite, and so are their
    # gradients — not whatever the memory held
    for leaf in (got, got_grads[0]):
        assert np.isfinite(np.asarray(leaf)).all()
        assert not np.asarray(leaf[local:]).any()
    # an empty group's matrix gets a zero gradient
    for g in np.flatnonzero(np.asarray(sizes[:G]) == 0):
        assert not np.asarray(got_grads[1][g]).any()


def test_bf16_operands_accumulate_in_float32():
    """As the routed layer calls it: bf16 in, bf16 out, the sums in float32
    — `ragged_dot`'s result at the same dtypes to a rounding of the result."""
    sizes = jnp.asarray(ROUTINGS["skewed"], jnp.int32)
    lhs, rhs, _ = _operands(jnp.bfloat16)
    got = gm.grouped_matmul(lhs, rhs, sizes, interpret=True)
    want = jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want,
                               atol=2 ** -8 * float(jnp.abs(want).max()))
    with pytest.raises(ValueError, match="one compute dtype"):
        gm.grouped_matmul(lhs, rhs.astype(jnp.float32), sizes)


# the layer's products at the cell's shapes: 65,536 rows (8,192 tokens × 8),
# d_model 2,048, expert width 1,024 — (m, k, n, to the weights?)
CELL_SHAPES = {
    "gate_up_forward": (65_536, 2048, 1024, False),
    "down_forward": (65_536, 1024, 2048, False),   # and gate / up to the rows
    "gate_up_to_the_weights": (65_536, 2048, 1024, True),
    "down_to_the_weights": (65_536, 1024, 2048, True),
}


@pytest.mark.parametrize("product", CELL_SHAPES)
def test_tile_plan_is_a_pure_function_whose_tiles_divide_the_cells_shapes(
        product):
    m, k, n, to_weights = CELL_SHAPES[product]
    plan = gm.tile_plan(m, k, n, jnp.bfloat16, to_weights=to_weights)
    assert plan == gm.tile_plan(m, k, n, "bfloat16", to_weights=to_weights)
    tm, tk, tn = plan
    assert tm == gm.ROW_TILE == 256
    assert (m % tm, k % tk, n % tn) == (0, 0, 0)
    assert tk % 128 == 0 and tn % 128 == 0
    assert gm.vmem_bytes(tm, tk, tn, 2, to_weights=to_weights) <= \
        gm.VMEM_BUDGET_BYTES
    # forward the whole contraction is one tile (no k steps: a group's
    # matrix block is fetched once); to the weights the accumulator is
    # what stays, a [1024, 1024] corner of the group's matrix
    assert (tk, tn) == ((1024, 1024) if to_weights else (k, 1024))
    # every product of the layer has the same rows and the same row tile
    assert gm.tile_plan(m, n, k, jnp.bfloat16)[0] == tm
    # float32 operands are twice the bytes: narrower tiles, still dividing
    wide = gm.tile_plan(m, k, n, jnp.float32, to_weights=to_weights)
    assert wide[0] == tm and wide[1] * wide[2] < tk * tn
    assert (k % wide[1], n % wide[2]) == (0, 0)


@pytest.mark.parametrize("m, k, n, want", [
    (768, 256, 128, (256, 256, 128)),      # this file's
    (384, 128, 128, (128, 128, 128)),
    (512, 64, 32, None),                   # test_olmoe's layer: no 128 lanes
    (200, 256, 128, None),                 # no whole tile of rows
    (1024, 192, 128, None),                # 192 is no multiple of 128
])
def test_tile_plan_is_none_where_no_tile_divides(m, k, n, want):
    assert gm.tile_plan(m, k, n, jnp.float32) == want


@pytest.mark.parametrize("sizes, tm, visits", [
    ([256, 256, 256], 256, 3),             # every group ends on an edge
    ([192, 192, 192, 192], 256, 6),        # 0, 0-1, 1-2, 2: six for three
    ([384, 100, 184, 100], 256, 6),
    ([0, 500, 0, 268], 256, 4),            # empty groups visit nothing
    ([1] * 8 + [760], 256, 11),            # eight visits of the first tile
    ([1024] * 64, 512, 128),               # the cell, evenly routed
    ([1000] + [1024] * 62 + [1048], 512, 128 + 63),   # and at its worst
])
def test_issued_ratio_against_hand_counts(sizes, tm, visits):
    assert gm.issued_ratio(sizes, tm) == pytest.approx(
        visits * tm / sum(sizes))
    # never more than `moe_plan`'s bound
    tiles, groups = sum(sizes) // tm, len(sizes)
    assert visits <= min(tiles + groups - 1, groups * tiles)


@pytest.mark.parametrize("routing", ROUTINGS)
def test_issued_ratio_is_what_the_kernels_own_metadata_visits(routing):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata,
    )
    sizes = ROUTINGS[routing]
    tm = gm.tile_plan(M, K, N, jnp.float32)[0]
    _, visits = make_group_metadata(
        group_sizes=jnp.asarray(sizes, jnp.int32), m=M, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=len(sizes),
        visit_empty_groups=False)
    assert gm.issued_ratio(sizes, tm) == pytest.approx(int(visits) * tm / M)
