"""More variants for `loop_probe.py` (`PROBE_VARIANTS=` this file):
first_unroll_2 — the first loop's bodies read no state: two a loop body;
ones_1 — `variants_a.py`'s `ones_spread_1` (a column's spread a product of
its own with a block of ones); ones_1_unroll_2 — both; ones_exact_1 — the
spread as ONE six-pass product of the float32 column with the ones; dma_only
— no loop and no inverse: what a grid step's blocks take to come and go."""
import importlib.util
import os

import jax
import jax.numpy as jnp


def variants(kda):
    spec = importlib.util.spec_from_file_location(
        "variants_a", os.path.join(os.path.dirname(__file__),
                                   "variants_a.py"))
    a = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(a)
    ones_1 = a.variants(kda)["ones_spread_1"]["_inverse_many"]

    def loops(unroll: int, on: bool = True):
        def two_loops(masks, held, tree, prepare, finish, reverse=False,
                          together=1):
            def first(c, _):
                for ref, leaf in zip(held,
                                     jax.tree_util.tree_leaves(prepare(c))):
                    ref[c] = leaf

            if not on:
                return
            chunks = held[0].shape[0]
            def several(i, _):
                # (Mosaic unrolls a loop whole or not at all: by hand)
                for more in range(unroll):
                    first(i * unroll + more, None)

            jax.lax.fori_loop(0, chunks // unroll, several, None)
            kda._inverse_many(
                jax.tree_util.tree_unflatten(tree, held)["A"], masks)

            def second(step, _):
                c = chunks - 1 - step if reverse else step
                finish(c, jax.tree_util.tree_unflatten(
                    tree, [ref[c] for ref in held]), masks)

            jax.lax.fori_loop(0, chunks, second, None)

        return two_loops

    def ones_exact(ref, m):
        """`ones_1` with the spread one six-pass product a column."""
        exact, one_pass, parts = kda._exact, kda._one_pass, kda._parts
        kda._parts = lambda x: [x]
        kda._one_pass = lambda x, ones, dims: exact(
            x, ones.astype(jnp.float32), dims)
        try:
            ones_1(ref, m)
        finally:
            kda._parts, kda._one_pass = parts, one_pass

    return {
        "first_unroll_2": {"_two_loops": loops(2)},
        "first_unroll_4": {"_two_loops": loops(4)},
        "ones_1": {"_inverse_many": ones_1},
        "ones_1_unroll_2": {"_inverse_many": ones_1, "_two_loops": loops(2)},
        "ones_exact_1": {"_inverse_many": ones_exact},
        "dma_only": {"_two_loops": loops(1, on=False)},
    }
