"""Other forms of `_inverse_many` for `loop_probe.py` (`PROBE_VARIANTS=` this
file): all RIGHT (the same inverse), timing and `against_base` both count.

ones_spread: the substitution's column spread — `A`'s column j of every
16 × 16 block on all 16 lanes of its block — as a product with a block
matrix of ones on the MXU (three passes, the column's bfloat16 parts), all
fifteen columns of all the block's chunks in ONE product, in place of a mask,
a lane rotation and four doubling rotations a column on the XLU."""
import jax
import jax.numpy as jnp


def variants(kda):
    _BASE, _exact, _by_head, _NN = kda._BASE, kda._exact, kda._by_head, kda._NN

    def merged(ref, A, T, n, m):
        chunk, width = ref.shape[1:]
        blocks = chunk // _BASE
        lane = jax.lax.broadcasted_iota(jnp.int32, (_BASE, width), 1)
        block = lane % chunk // _BASE
        Ts = [jnp.concatenate([
            jnp.where(block == b, T[c * _BASE:(c + 1) * _BASE], 0.0)
            for b in range(blocks)], axis=0) for c in range(n)]
        row, col, same_head = m["row"], m["col"], m["same_head"]
        side = _BASE
        while side < chunk:
            def second_rows(x):
                return jnp.concatenate(
                    [x[a:a + side] for a in range(side, chunk, 2 * side)],
                    axis=0)

            def placed(x):
                nothing = jnp.zeros((side, width), jnp.float32)
                return jnp.concatenate(
                    [part for a in range(0, chunk // 2, side)
                     for part in (nothing, x[a:a + side])], axis=0)

            off = ((row // (2 * side) == col // (2 * side))
                   & (row // side != col // side))
            rights = [placed(_exact(second_rows(jnp.where(off, A[c], 0.0)),
                                    _by_head(Ts[c], same_head), _NN))
                      for c in range(n)]
            lowers = [_exact(second_rows(Ts[c]),
                             _by_head(rights[c], same_head), _NN)
                      for c in range(n)]
            Ts = [Ts[c] - placed(lowers[c]) for c in range(n)]
            side *= 2
        for c in range(n):
            ref[c] = Ts[c]

    def ones_spread(split: int):
        def inverse(ref, m):
            n, chunk, width = ref.shape
            blocks = chunk // _BASE
            lane = jax.lax.broadcasted_iota(jnp.int32, (_BASE, width), 1)
            at_tile, block = lane % _BASE, lane % chunk // _BASE
            at = jnp.concatenate([at_tile] * n, axis=0)
            A = [ref[c] for c in range(n)]
            own = jnp.concatenate([
                sum(jnp.where(block == b, A[c][b * _BASE:(b + 1) * _BASE],
                              0.0)
                    for b in range(blocks)) for c in range(n)], axis=0)
            square = (width, width)
            ones = (jax.lax.broadcasted_iota(jnp.int32, square, 0) // _BASE
                    == jax.lax.broadcasted_iota(jnp.int32, square, 1)
                    // _BASE).astype(jnp.bfloat16)
            rows = n * _BASE
            columns = list(range(_BASE - 1))
            factors = {}
            for first in range(0, len(columns), split):
                group = columns[first:first + split]
                parts = [part for j in group
                         for part in kda._parts(jnp.where(at == j, own, 0.0))]
                spread = kda._one_pass(jnp.concatenate(parts, axis=0), ones,
                                       _NN)
                a = len(parts) // len(group)
                for i, j in enumerate(group):
                    factors[j] = sum(
                        spread[(a * i + p) * rows:(a * i + p + 1) * rows]
                        for p in range(a))
            T = (jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0) % _BASE
                 == at).astype(jnp.float32)
            for j in columns:
                T = T - factors[j] * jnp.concatenate([
                    jnp.broadcast_to(T[c * _BASE + j:c * _BASE + j + 1],
                                     (_BASE, width)) for c in range(n)],
                    axis=0)
            merged(ref, A, T, n, m)

        return inverse

    return {
        "ones_spread": {"_inverse_many": ones_spread(15)},
        "ones_spread_5": {"_inverse_many": ones_spread(5)},
        "ones_spread_1": {"_inverse_many": ones_spread(1)},
    }
