"""The FORWARD kernel's second loop, other ways (`loop_probe.py ... fwd <block>
...` with `PROBE_VARIANTS=` this file; all RIGHT, `against_base` counts). What
the two loops cost beside one (the rule without its inverses: 11.07 ms a
forward call for the parent's 9.41, `ablate_{change,shipped}.jsonl`) is the
second loop's chain of trips through the MXU with nothing left to fill its
waits, so:

second_unrolled — the second loop's four bodies in one (a chunk's products
    that read no state beside the chunk's before it that waits for one);
uw_between — ``U = T·(β ∘ v)`` and ``W = T·(β ∘ e^γ ∘ k)`` read no state:
    taken for every chunk right behind the inverses, between the loops, and
    kept (U float32), one trip through the MXU less a chunk in the chain;
uw_between_unrolled — both;
second_unrolled_both / second_unrolled_bwd_2 — the BACKWARD's second loop in
    one body too, or two chunks a body (`loop_probe.py ... both ...`)."""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def variants(kda):
    _PAIR, _NN, _TN = kda._PAIR, kda._NN, kda._TN
    _one_pass, _by_head = kda._one_pass, kda._by_head
    prepared, reads = kda._prepared, kda._FORWARD_READS

    def loops(unrolled: bool, between=None, backward: int = 1):
        def two_loops(masks, held, tree, prepare, finish, reverse=False,
                          together=1):
            def first(c, _):
                for ref, leaf in zip(held,
                                     jax.tree_util.tree_leaves(prepare(c))):
                    ref[c] = leaf

            chunks = held[0].shape[0]
            jax.lax.fori_loop(0, chunks, first, None)
            structure = jax.tree_util.tree_unflatten(tree, held)
            kda._inverse_many(structure["A"], masks)
            if between is not None:
                between(structure, masks)

            def second(step, _):
                c = chunks - 1 - step if reverse else step
                finish(c, jax.tree_util.tree_unflatten(
                    tree, [ref[c] for ref in held]), masks)

            if reverse and 1 < backward < chunks:
                # (Mosaic unrolls a loop whole or not at all: by hand)
                def several(i, _):
                    for more in range(backward):
                        second(i * backward + more, None)

                jax.lax.fori_loop(0, chunks // backward, several, None)
                return
            jax.lax.fori_loop(
                0, chunks, second, None,
                unroll=backward >= chunks if reverse else bool(unrolled))

        return two_loops

    def prepared_with_room(q, k, v, g, rows, *, chunk, cd, normalize, reads):
        """`_prepared` with room for U and W among what is kept."""
        if reads is None:
            return prepared(q, k, v, g, rows, chunk=chunk, cd=cd,
                            normalize=normalize, reads=None)
        p = prepared(q, k, v, g, rows, chunk=chunk, cd=cd,
                     normalize=normalize,
                     reads=tuple(r for r in reads if r not in ("U", "W")))
        p["U"] = jnp.zeros(v.shape, jnp.float32)
        p["W"] = jnp.zeros(k.shape, cd)
        return p

    def uw(structure, masks):
        cd = structure["W"].dtype
        for c in range(structure["A"].shape[0]):
            T_by_head = _by_head(structure["A"][c],
                                 masks["same_head"]).astype(cd)
            structure["U"][c] = _one_pass(T_by_head,
                                          structure["written_v"][c], _NN)
            structure["W"][c] = _one_pass(
                T_by_head, structure["k_written"][c], _NN).astype(cd)

    def fwd_kernel(q_ref, k_ref, v_ref, g_ref, rows_ref, o_ref, starts_ref,
                   state, *kept, chunk, cd, normalize, tree):
        """`kda._fwd_kernel` with U and W read from what is kept."""
        @pl.when(pl.program_id(2) == 0)
        def _first_block():
            state[...] = jnp.zeros_like(state)

        C = chunk
        K, V = q_ref.shape[1] // _PAIR, v_ref.shape[1] // _PAIR
        heads = [slice(r * C, (r + 1) * C) for r in range(_PAIR)]

        def tokens(c):
            return pl.ds(pl.multiple_of(c * C, C), C)

        def prepare(c):
            at = tokens(c)
            return kda._prepared(
                kda._stacked(q_ref, at, K), kda._stacked(k_ref, at, K),
                kda._stacked(v_ref, at, V), kda._stacked(g_ref, at, K),
                rows_ref[c], chunk=C, cd=cd, normalize=normalize,
                reads=kda._FORWARD_READS)

        def carry(c, p, m):
            U, W = p["U"], p["W"]
            values, seen_by_q = [], []
            for r, own in enumerate(heads):
                start = state[r]
                starts_ref[c, r] = start
                seen = _one_pass(
                    jnp.concatenate([W[own], p["q_grown"][own]], axis=0),
                    start.astype(cd), _NN)
                new = (U[own] - seen[:C]).astype(cd)
                state[r] = p["keep"][r] * start + _one_pass(
                    p["k_end"][own], new, _TN)
                values.append(new)
                seen_by_q.append(seen[C:])
            out = (jnp.concatenate(seen_by_q, axis=0)
                   + _one_pass(p["P_by_head"],
                               jnp.concatenate(values, axis=0), _NN))
            kda._unstack(o_ref, tokens(c), out, V)

        kda._two_loops(kda._masks(C, K), kept, tree, prepare, carry)

    between = {"_prepared": prepared_with_room,
               "_FORWARD_READS": (*reads, "U", "W"),
               "_fwd_kernel": fwd_kernel}
    return {
        "second_unrolled": {"_two_loops": loops(True)},
        "second_unrolled_both": {"_two_loops": loops(True, backward=4)},
        "second_unrolled_bwd_2": {"_two_loops": loops(True, backward=2)},
        "uw_between": {**between, "_two_loops": loops(False, uw)},
        "uw_between_unrolled": {**between, "_two_loops": loops(True, uw)},
    }
