"""The routed layer: its config, leaves, plans, routing and the experts' own
work. Imports `core`, and `mlp` for the shared expert."""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.layers import core, mlp
from ray_tpu.ops import grouped_matmul
from ray_tpu.parallel import sharding as sh


# ---------------------------------------------------------------- MoE (EP)
@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    # the chosen gates rescaled to sum to 1 (GShard); False uses the router's
    # probabilities as they are (OLMoE)
    norm_topk_prob: bool = True
    # "softmax" over all experts, or "sigmoid": independent scores, the
    # top-k chosen on score + a selection bias (the leaf `bias`, behind a
    # stop_gradient) and weighted by the scores themselves
    score: str = "softmax"
    # what the chosen gates are multiplied by, after any renormalisation
    scale: float = 1.0
    # between the two matrices of the `w1` / `w2` form: "gelu" | "relu2"
    activation: str = "gelu"
    # on the gate of the three-matrix form: "silu" | "relu"
    gate: str = "silu"
    # width of a shared expert every token goes through, its result added
    # once, whole, whatever share of the routed experts is held; 0: none.
    # In the routed experts' form: `w1` / `w2` with their activation
    # (leaves `shared_w1`, `shared_w2`), or, where they are gated, the dense
    # SiLU-gated feed-forward of three matrices (`apply_gated_mlp` on the
    # leaves under `shared`)
    d_shared: int = 0
    # the gated-form shared expert's output times ``sigmoid(x·w_sg)``, a
    # scalar a token (the leaf `w_sg` [d, 1])
    shared_gate: bool = False
    # THE CHIP'S SHARE of a deployment that spreads the experts: the
    # stacked leaves hold `held` experts (None: all `n_experts`), the first
    # of them expert `first` of the `n_experts` the router scores. The
    # layer computes its own experts' part of the result; what the others
    # would add is computed where they live, which may be nowhere.
    held: Optional[int] = None
    first: int = 0

    @property
    def stacked(self) -> int:
        return self.n_experts if self.held is None else self.held


def init_moe(key, d_model, d_ff, cfg: MoEConfig, dtype=jnp.float32,
             gated: bool = False):
    """Router `wg` (as wide as the experts it scores) and the experts held
    here (`cfg.stacked`), stacked over a leading axis: two matrices with an
    activation between (`w1`, `w2`), or, `gated`, three with a gate (SiLU or
    ReLU: `cfg.gate`; `w_gate`, `w_up`, `w_down`). `apply_moe` tells the
    form by the leaves.
    With sigmoid scores the selection `bias` [E], zero; with `d_shared` the
    shared expert in the experts' form: `shared_w1`, `shared_w2`, or,
    `gated`, `init_gated_mlp`'s three under `shared` (and, with
    `shared_gate`, its scalar gate's `w_sg` [d, 1])."""
    kg, k1, k2, k3 = jax.random.split(key, 4)
    E = cfg.stacked
    wide, narrow = (E, d_model, d_ff), (E, d_ff, d_model)
    experts = ({"w_gate": core.init_dense(k1, wide, dtype=dtype),
                "w_up": core.init_dense(k3, wide, dtype=dtype),
                "w_down": core.init_dense(k2, narrow, dtype=dtype)}
               if gated else
               {"w1": core.init_dense(k1, wide, dtype=dtype),
                "w2": core.init_dense(k2, narrow, dtype=dtype)})
    params = {"wg": core.init_dense(kg, (d_model, cfg.n_experts), dtype=dtype),
              **experts}
    if cfg.score == "sigmoid":
        params["bias"] = jnp.zeros((cfg.n_experts,), dtype)
    if cfg.d_shared:
        k4, k5 = jax.random.split(k3)
        if gated:
            params["shared"] = mlp.init_gated_mlp(k4, d_model, cfg.d_shared,
                                                   dtype)
            if cfg.shared_gate:
                params["w_sg"] = core.init_dense(k5, (d_model, 1), dtype=dtype)
            return params
        params["shared_w1"] = core.init_dense(k4, (d_model, cfg.d_shared),
                                          dtype=dtype)
        params["shared_w2"] = core.init_dense(k5, (cfg.d_shared, d_model),
                                          dtype=dtype)
    return params


_WIDE = ("experts", "embed", "expert_mlp")
_NARROW = ("experts", "expert_mlp", "embed")
MOE_LOGICAL = {"wg": ("embed", None), "w1": _WIDE, "w2": _NARROW}
GATED_MOE_LOGICAL = {"wg": ("embed", None), "w_gate": _WIDE, "w_up": _WIDE,
                     "w_down": _NARROW}
# the leaves some routers and layers have besides
MOE_EXTRA_LOGICAL = {"bias": (None,), "shared_w1": ("embed", "mlp"),
                     "shared_w2": ("mlp", "embed")}
# a shared expert beside GATED experts: the dense form's three leaves
GATED_SHARED_LOGICAL = {"shared": mlp.GATED_MLP_LOGICAL}
# and its scalar sigmoid gate's
SHARED_GATE_LOGICAL = {"w_sg": ("embed", None)}

# A share's bounds over the held experts' expected rows: ONE, at twice the
# expectation. It holds the two cells with a share while their routing is
# even or turns away from this chip. Rungs at 4 and 8 times were tried
# (`nemotronh9l-b1s8k`: a deeper layer gives one, two or nearly three of
# every token's six choices to held experts for 3 to 15 steps in four seeds
# of seven — 17, 33, 45 % of the rows on an expectation of 6.25 % — and runs
# whole through them, 462 ms a step for 432): each rung is one more program
# with kernels of its own shapes, ~3 s of that cell's 64 s from start to
# first step, whose bound is a tenth of it (PERF.md §6, PR 37). A bound
# that is not under the rows is none.
_BOUND_FACTORS = (2,)


def assignment_bounds(rows: int, local: int, n_experts: int) -> Tuple[int, ...]:
    """How many of `rows` sorted assignments a device that holds `local` of
    the `n_experts` scored experts works on while its experts' rows fit
    them: their expectation under even routing times each of
    `_BOUND_FACTORS`, rounded up to the grouped kernel's row tile, those
    under `rows`, ascending. From the shapes alone. Empty where every
    scored expert is held or no bound is under `rows`: the layer then has
    the whole path only."""
    if local >= n_experts:
        return ()
    tile = grouped_matmul.row_tile(rows) or grouped_matmul.ROW_TILE
    expected = -(-rows * local // n_experts)
    bounds = {-(-factor * expected // tile) * tile
              for factor in _BOUND_FACTORS}
    return tuple(sorted(b for b in bounds if b < rows))


def moe_plan(tokens: int, d_model: int, d_ff: int, cfg: MoEConfig, *,
             gated: bool, itemsize: int = 2, ep: int = 1) -> dict:
    """What one forward pass of `apply_moe` does on one device, from shapes
    alone (`tokens` there; `ep` devices share the `cfg.stacked` experts the
    leaves hold): the rows gathered, the grouped matmuls' FLOPs needed
    (every assignment through its expert once; a device's share under even
    routing over all `n_experts`) and the most the
    tiled kernel issues under ANY routing (each local expert's group may
    end inside a row tile, which is then visited twice), and the bytes that
    dispatch and combine move. The backward pass is twice the FLOPs (one
    product for the rows, one for the weights) and the same bytes again.
    `bounds`: where the leaves hold fewer experts than the router scores,
    the sorted assignments the layer dispatches, multiplies and combines
    while the held experts' rows fit them — the least that does
    (`assignment_bounds`; empty: all `rows` always); the bytes are those of
    the whole path."""
    rows = tokens * cfg.top_k
    per_row = (3 if gated else 2) * 2 * d_model * d_ff
    tile = grouped_matmul.row_tile(rows) or grouped_matmul.ROW_TILE
    tiles = -(-rows // tile)
    visits = min(tiles + cfg.stacked // ep - 1, 2 * tiles)
    return {
        "rows": rows,
        "bounds": assignment_bounds(rows, cfg.stacked // ep, cfg.n_experts),
        "flops_needed": rows * per_row * cfg.stacked // (cfg.n_experts * ep),
        "flops_issued_max": visits * tile * per_row,
        # each row read from its token and written in expert order
        "dispatch_bytes": 2 * rows * d_model * itemsize,
        # each row read back in token order, a token's K summed into one
        "combine_bytes": (rows + tokens) * d_model * itemsize,
    }


def routing_plan(tokens: int, cfg: MoEConfig) -> dict:
    """Bytes of what one routed layer keeps under `remat` by the name
    `ROUTING`, on one device with `tokens` there, from shapes alone: the
    router's float32 `logits`, the `top_k`'s chosen experts (and, of a
    softmax router, their probabilities as the sort gave them; a sigmoid
    router reads its gates at the choice, which is rebuilt), and the
    assignments' sort: `order`, `inverse`, `sizes`."""
    rows = tokens * cfg.top_k
    return {"logits": tokens * cfg.n_experts * 4,
            "top_k": rows * (8 if cfg.score == "softmax" else 4),
            "order": rows * 4, "inverse": rows * 4,
            "sizes": cfg.n_experts * 4}


@jax.custom_vjp
def _take_assignments(x2, order, inverse):
    """x2 [T, D] -> [T·K, D], row j the token of the j-th assignment in
    expert order (`order`: positions in the token-major [T·K] list; `inverse`
    its inverse permutation). Every token is taken K times, so the
    transpose is a gather too: K rows a token, summed."""
    return x2[order // (order.shape[0] // x2.shape[0])]


def _take_assignments_fwd(x2, order, inverse):
    return _take_assignments(x2, order, inverse), (inverse, x2.shape[0])


def _take_assignments_bwd(res, d):
    inverse, tokens = res
    dx = jnp.sum(d[inverse].reshape(tokens, -1, d.shape[-1])
                 .astype(jnp.float32), axis=1)
    return dx.astype(d.dtype), None, None


_take_assignments.defvjp(_take_assignments_fwd, _take_assignments_bwd)


@jax.custom_vjp
def _permute_rows(y, perm, inverse):
    """y[perm] for a permutation; the transpose is the gather by `inverse`
    (a scatter to XLA, which knows no permutation when it sees one)."""
    return y[perm]


_permute_rows.defvjp(lambda y, perm, inverse: (y[perm], (inverse,)),
                     lambda res, d: (d[res[0]], None, None))


def _sum_prefix(y, inverse, top_k: int, weights=None, mask=None):
    """y [C, D], the first C rows of the sorted order -> [T, D] float32:
    each token's K assignments' rows (row `inverse[i]` of y; those behind
    the prefix, and those `mask` [T, K] leaves out, count zero), times
    `weights` [T, K], summed. One gather of T rows a slot, accumulated:
    nothing of `[T, K, D]` is laid out, and on the TPU that is what the
    whole path's combine spends most of its time on (PERF.md §6, PR 37)."""
    place = inverse.reshape(-1, top_k)
    out = 0.0
    for k in range(top_k):
        rows = y[jnp.minimum(place[:, k], y.shape[0] - 1)].astype(jnp.float32)
        if weights is not None:
            rows = rows * weights[:, k, None]
        keep = place[:, k] < y.shape[0]
        if mask is not None:
            keep = keep & mask[:, k]
        out = out + jnp.where(keep[:, None], rows, 0.0)
    return out


@jax.custom_vjp
def _take_prefix(x2, order, inverse):
    """`_take_assignments` for a prefix of the sorted assignments: x2 [T, D]
    -> [C, D], row j the token of `order[j]` (`order` [C] the first C of the
    sorted positions, `inverse` [T·K] the whole inverse permutation). The
    transpose gathers too: each token's rows out of the C, summed in
    float32 (`_sum_prefix`)."""
    return x2[order // (inverse.shape[0] // x2.shape[0])]


def _take_prefix_fwd(x2, order, inverse):
    return _take_prefix(x2, order, inverse), (inverse, x2.shape[0])


def _take_prefix_bwd(res, d):
    inverse, tokens = res
    with jax.named_scope("dispatch"):   # a backward rule inherits no scope
        dx = _sum_prefix(d, inverse, inverse.shape[0] // tokens)
        return dx.astype(d.dtype), None, None


_take_prefix.defvjp(_take_prefix_fwd, _take_prefix_bwd)


@jax.custom_vjp
def _combine_prefix(y, gate_vals, here, order, inverse):
    """y [C, D], the experts' outputs for the first C of the sorted
    assignments -> [T, D] float32: each token's rows times its gates [T, K]
    in float32, those of experts not `here` [T, K] left out, summed
    (`_sum_prefix`). The transpose works on the C rows alone: each row's
    token's cotangent gathered once, for the row and for its gate."""
    return _sum_prefix(y, inverse, gate_vals.shape[1], gate_vals, here)


def _combine_prefix_fwd(y, gate_vals, here, order, inverse):
    return (_combine_prefix(y, gate_vals, here, order, inverse),
            (y, gate_vals, here, order, inverse))


def _combine_prefix_bwd(res, d):
    y, gate_vals, here, order, inverse = res
    top_k = gate_vals.shape[1]
    with jax.named_scope("combine"):    # a backward rule inherits no scope
        d_rows = d[order // top_k]
        gates = jnp.where(here.reshape(-1)[order],
                          gate_vals.reshape(-1)[order], 0.0)
        # a gate's cotangent, by row; then by token and slot
        d_gates = jnp.sum(y.astype(jnp.float32) * d_rows, axis=-1)
        place = inverse.reshape(-1, top_k)
        d_gates = jnp.where(here & (place < y.shape[0]),
                            d_gates[jnp.minimum(place, y.shape[0] - 1)], 0.0)
        return ((d_rows * gates[:, None]).astype(y.dtype), d_gates, None,
                None, None)


_combine_prefix.defvjp(_combine_prefix_fwd, _combine_prefix_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _one_of(programs, which, args, aux):
    """`programs[which](*args, *aux)`: several programs of one result, one
    chosen on the device. Differentiable in `args`. The backward branches
    on `which` again and differentiates the program that ran from its
    inputs (its forward once more, as under `remat`), so nothing of the
    others is kept, zero-filled or run:
    `lax.switch` under differentiation returns the residuals of EVERY
    branch, the whole path's `[T·K, ·]` arrays among them."""
    return jax.lax.switch(which, programs, *args, *aux)


def _one_of_fwd(programs, which, args, aux):
    return _one_of(programs, which, args, aux), (which, args, aux)


def _one_of_bwd(programs, res, d):
    which, args, aux = res

    def gradient(fn):
        return lambda args, d: jax.vjp(lambda *a: fn(*a, *aux), *args)[1](d)

    # the barrier holds the compiler's conditional code motion off: it
    # sinks what reads a cotangent into every branch, and where that is the
    # zero-padded copy of a whole stack of layers (a slice's transpose) each
    # conditional then returns the stack (4.9 GB more in `nemotronh9l-b1s8k`)
    return (None, jax.lax.optimization_barrier(jax.lax.switch(
        which, [gradient(fn) for fn in programs], args, d)), None)


_one_of.defvjp(_one_of_fwd, _one_of_bwd)


def _relu2(u):
    """relu(u)², in float32."""
    return jnp.square(jax.nn.relu(u.astype(jnp.float32)))


def _local_experts(x, gate_vals, gate_idx, experts, *, n_experts: int,
                   first, cd, mesh=None, activation: str = "gelu",
                   gate: str = "silu"):
    """One device's part of the routed layer: x [b, s, D] its tokens, gates
    [b, s, K] their chosen experts and weights, `experts` the leaves of the
    E_local experts it holds, `first` the id of the first of them, `mesh`
    where this runs (`grouped_matmul.grouped_matmul`'s). Returns
    [b, s, D] float32: for each token the weighted outputs of those of its
    experts that live here (all of them where nothing splits the experts).

    Where E_local < n_experts the local experts' rows are a PREFIX of the
    sorted assignments, and the shapes give bounds on it
    (`assignment_bounds`): while this step's routing keeps the prefix under
    a bound, only that many rows (the least bound that holds them) are
    gathered, multiplied and combined; otherwise all of them are, as where
    every expert is local — the same result either way, no assignment
    dropped. Second result: whether a bounded program ran (int32; None
    where every scored expert is local)."""
    d_model, top_k = x.shape[-1], gate_idx.shape[-1]
    x2 = x.reshape(-1, d_model)
    gate_vals, gate_idx = (g.reshape(-1, top_k) for g in (gate_vals, gate_idx))
    rows = x2.shape[0] * top_k
    local = next(iter(experts.values())).shape[0]
    wide = experts["w_gate" if "w_gate" in experts else "w1"]

    def through(fn, bound=None):
        # decided ONCE for all of a program's products: the width at which
        # they are the op's kernels, None where they are XLA's
        width = grouped_matmul.kernel_width(bound or rows, *wide.shape[1:],
                                            cd, mesh)
        return functools.partial(
            fn, n_experts=n_experts, cd=cd, mesh=mesh, width=width,
            activation=activation, gate=gate, bound=bound)

    with jax.named_scope("dispatch"):
        # this device's experts first, in order; the others' rows behind
        # them. Sort, inverse and sizes carry the name `remat` keeps: the
        # argsort and the two scatters run once a step
        key = (gate_idx.reshape(rows) - first) % n_experts
        order = checkpoint_name(
            jnp.argsort(key, stable=True).astype(jnp.int32), core.ROUTING)
        inverse = checkpoint_name(jnp.zeros((rows,), jnp.int32).at[order].set(
            jnp.arange(rows, dtype=jnp.int32), unique_indices=True),
            core.ROUTING)
        # every row's group, the local experts' first: what lies behind
        # them belongs to no matrix here and comes out of a product zero
        sizes = checkpoint_name(
            jnp.bincount(key, length=n_experts).astype(jnp.int32),
            core.ROUTING)
    bounds = assignment_bounds(rows, local, n_experts)
    if not bounds:
        # a share too small for a bound under its rows never runs bounded
        return through(_through_experts)(
            x2, gate_vals, experts, gate_idx, first, order, inverse,
            sizes).reshape(x.shape), (
                           None if local == n_experts else jnp.int32(0))
    # the least bound that holds the local experts' rows; behind the last,
    # the whole path. Each program is a `jit`, so a model's layers (and the
    # forward, its recomputation and the backward of each) trace it once
    over = jnp.sum(jnp.sum(sizes[:local]) > jnp.asarray(bounds, jnp.int32))
    programs = tuple(through(_through_experts_jit, b)
                     for b in bounds + (None,))
    out = _one_of(programs, over, (x2, gate_vals, experts),
                  (gate_idx, jnp.asarray(first, jnp.int32), order, inverse,
                   sizes))
    return out.reshape(x.shape), (over < len(bounds)).astype(jnp.int32)


def _through_experts(x2, gate_vals, experts, gate_idx, first, order, inverse,
                     sizes, *, n_experts: int, cd, mesh, width: Optional[int],
                     activation: str, gate: str, bound: Optional[int] = None):
    """`_local_experts` behind the sort: x2 [T, D], gates [T, K], the
    sorted positions `order`, their inverse and every group's `sizes`
    -> [T, D] float32. `bound`: the local experts' rows lie within the first
    `bound` of the sorted order, and only those are taken, multiplied and
    combined; None: all T·K."""
    (tokens, d_model), top_k = x2.shape, gate_idx.shape[-1]
    local = next(iter(experts.values())).shape[0]
    with jax.named_scope("dispatch"):
        if bound is None:
            taken = _take_assignments(x2.astype(cd), order, inverse)
        else:
            order = order[:bound]
            # what of the prefix lies behind the local experts' rows is one
            # more group of no matrix
            sizes = jnp.append(sizes[:local],
                               bound - jnp.sum(sizes[:local]))
            taken = _take_prefix(x2.astype(cd), order, inverse)
    with jax.named_scope("experts"):
        def product(lhs, name, axis):
            """`axis`: the one of the leaf's that is the experts' width,
            the cast leaf zero-padded there to `width` (every activation
            here maps 0 to 0)."""
            rhs = experts[name].astype(cd)
            pad = width and width - rhs.shape[axis]
            if pad:
                rhs = jnp.pad(rhs, [(0, pad if a == axis else 0)
                                    for a in range(rhs.ndim)])
            return grouped_matmul.grouped_matmul(lhs, rhs, sizes, mesh=mesh)

        if "w_gate" in experts:
            gate_fn = {"silu": jax.nn.silu, "relu": jax.nn.relu}[gate]
            gated = product(taken, "w_gate", 2)
            up = product(taken, "w_up", 2)
            hidden = (gate_fn(gated.astype(jnp.float32))
                      * up.astype(jnp.float32)).astype(cd)
            y = product(hidden, "w_down", 1)
        elif activation == "gelu":
            hidden = jax.nn.gelu(product(taken, "w1", 2))
            y = product(hidden, "w2", 1)
        else:
            hidden = _relu2(product(taken, "w1", 2)).astype(cd)
            y = product(hidden, "w2", 1)
    with jax.named_scope("combine"):
        if bound is None:
            y = _permute_rows(y, inverse, order).reshape(
                tokens, top_k, d_model)
        here = ((gate_idx - first) % n_experts) < local
        if bound is not None:
            return _combine_prefix(y, gate_vals, here, order, inverse)
        weighted = jnp.where(here[..., None], y.astype(jnp.float32)
                             * gate_vals[..., None], 0.0)
        return jnp.sum(weighted, axis=1)


_through_experts_jit = jax.jit(_through_experts, static_argnames=(
    "n_experts", "cd", "mesh", "width", "activation", "gate", "bound"))


@jax.custom_jvp
def _chosen(values, scores, indices):
    """`values`, the top-k of `scores` [..., E] at `indices` [..., K], as a
    function of the scores: the tangent is the scores' at the indices THE
    CALLER HOLDS. `lax.top_k`'s own rule gathers by the indices as the sort
    gave them, which no `checkpoint_name` reaches: a checkpoint that keeps
    the named choice would still sort again for its backward pass."""
    return values


@_chosen.defjvp
def _chosen_jvp(primals, tangents):
    values, _, indices = primals
    # the gather of `lax.top_k`'s own rule, so that a step without a
    # checkpoint stays the program it was
    batch = tuple(range(indices.ndim - 1))
    return values, jax.lax.gather(
        tangents[1], indices[..., None], jax.lax.GatherDimensionNumbers(
            offset_dims=(), collapsed_slice_dims=(len(batch),),
            start_index_map=(len(batch),), operand_batching_dims=batch,
            start_indices_batching_dims=batch), (1,) * indices.ndim)


def _route(logits, bias, cfg: MoEConfig):
    """Router logits [B, S, E] float32 -> (gates [B, S, K], experts [B, S,
    K], the scores the statistics are taken of [B, S, E])."""
    if cfg.score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = (checkpoint_name(a, core.ROUTING) for a in
                               jax.lax.top_k(jax.lax.stop_gradient(probs),
                                             cfg.top_k))
        gate_vals = _chosen(gate_vals, probs, gate_idx)
        floor = 1e-9
    else:
        # independent scores; chosen on score + bias, weighted by the score
        probs = jax.nn.sigmoid(logits)
        _, gate_idx = jax.lax.top_k(
            probs + jax.lax.stop_gradient(bias.astype(jnp.float32)),
            cfg.top_k)
        gate_idx = checkpoint_name(gate_idx, core.ROUTING)
        gate_vals = jnp.take_along_axis(probs, gate_idx, axis=-1)
        floor = 1e-20
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, -1, keepdims=True), floor)
    if cfg.scale != 1.0:
        gate_vals = gate_vals * cfg.scale
    return gate_vals, gate_idx, probs


_NOT_ROUTED = ("wg", *MOE_EXTRA_LOGICAL, *GATED_SHARED_LOGICAL,
               *SHARED_GATE_LOGICAL)


def moe_route(params: core.Params, x, cfg: MoEConfig):
    """The router of `apply_moe` on x [B, S, D]: (gates [B, S, K], experts
    [B, S, K], stats), float32. `apply_moe` routes on its own input; a model
    whose router reads another place (the layer's input, ahead of attention)
    calls this there and hands the result on as `routing`."""
    E, K = cfg.n_experts, cfg.top_k
    S = x.shape[1]
    with jax.named_scope("router"):
        logits = checkpoint_name(jnp.einsum(
            "bsd,de->bse", x.astype(jnp.float32),
            params["wg"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), core.ROUTING)
        gate_vals, gate_idx, probs = _route(logits, params.get("bias"), cfg)
        # [B, E]: a sequence's assignments by expert
        counts = jnp.sum(jax.nn.one_hot(gate_idx, E, dtype=jnp.int32),
                         axis=(1, 2))
        stats = {
            "load_balance": jnp.mean(E * jnp.sum(
                counts.astype(jnp.float32) / (S * K)
                * jnp.mean(probs, axis=1), axis=-1)),
            "z": jnp.mean(jnp.square(
                jax.scipy.special.logsumexp(logits, axis=-1))),
            "counts": jnp.sum(counts, axis=0),
        }
    return gate_vals, gate_idx, stats


def apply_moe(params: core.Params, x, cfg: MoEConfig,
              compute_dtype=jnp.bfloat16, mesh=None, three_pass: bool = False,
              routing=None):
    """Top-k routed experts, dropless: x [B, S, D] -> (y [B, S, D], stats).

    Router and its scores in float32 (`_route`: softmax, or sigmoid with a
    selection bias); `lax.top_k`; the T·K assignments sorted
    by expert (stable), their rows gathered in that order, the experts'
    matrices applied to the ragged groups by grouped matmuls, and each
    token's K outputs weighted by its gates and summed. No capacity: every
    assignment is computed whatever the routing, and all shapes are static
    (`moe_plan` gives them). With `cfg.d_shared` a shared expert's output
    is added for every token, once — times ``sigmoid(x·w_sg)``, float32,
    where the leaves hold `w_sg` (`three_pass`: its forward values to
    float32 accuracy, as in `apply_attention`; set by the one model with a
    `w1` / `w2` shared expert, off is the single-pass control).

    routing: `moe_route`'s result where the router read another tensor than
    the experts' input x; None routes on x.

    `cfg.held` / `cfg.first`: the leaves hold a share of the `n_experts` the
    router scores. The result is then the PART of the layer's output that
    these experts (and the shared one) give; the assignments to the others
    are sorted behind the held ones' and come out of the products zero —
    or, while the held ones' rows stay under a bound the shapes give
    (`moe_plan`'s `bounds`), are not touched at all (`_local_experts`).

    mesh: as in `apply_attention` — the grouped matmul is a Mosaic kernel on
    the TPU (`ops.grouped_matmul` decides, from `target.where(mesh)` and its
    tiles), so dispatch, experts and combine run as per-device code.
    Each device takes its share of the batch and the experts `ep` gives it
    (their `expert_mlp` slice under `tp`), computes its experts' part of
    its tokens' outputs, and the parts are summed over `ep` and `tp`.

    stats (float32 scalars but `counts`): `load_balance` = E · Σ_e f_e · P_e
    with f_e the share of a sequence's S·K assignments that went to expert
    e and P_e its mean router probability, taken a sequence at a time and
    averaged — so that, like the cross-entropy, a batch's value is the mean
    of its sequences' whatever `dp` does with them; `z` = mean
    logsumexp(logits)²; `counts` [E] the batch's assignments by expert;
    and, where a device holds fewer experts than are scored (a share, or
    `ep`), `compact`: the share of the devices on which this step's routing
    kept the held experts' rows under the bound, so that only a bounded
    prefix of the sorted assignments was worked on (`_local_experts`; 1.0
    or 0.0 on one device).
    """
    cd = compute_dtype
    E = cfg.n_experts
    experts = {k: v for k, v in params.items() if k not in _NOT_ROUTED}

    if routing is None:
        routing = moe_route(params, x, cfg)
    gate_vals, gate_idx, stats = routing

    local = functools.partial(_local_experts, n_experts=E, cd=cd, mesh=mesh,
                              activation=cfg.activation, gate=cfg.gate)
    if mesh is None:
        out, compact = local(x, gate_vals, gate_idx, experts, first=cfg.first)
    else:
        def per_device(x, gate_vals, gate_idx, experts):
            held = next(iter(experts.values())).shape[0]
            out, compact = local(
                x, gate_vals, gate_idx, experts,
                first=cfg.first + jax.lax.axis_index("ep") * held)
            out = jax.lax.psum(out, ("ep", "tp"))
            if compact is None:
                return out
            return out, jax.lax.pmean(compact.astype(jnp.float32),
                                      mesh.axis_names)

        logical = GATED_MOE_LOGICAL if "w_gate" in experts else MOE_LOGICAL
        tok = sh.spec("batch", "seq", None)
        # a device has a bound where it holds fewer experts than are scored
        stacked = next(iter(experts.values())).shape[0]
        bounded = stacked // dict(mesh.shape).get("ep", 1) < E
        out = jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(tok, tok, tok,
                      {k: sh.spec(*logical[k]) for k in experts}),
            out_specs=(tok, sh.spec()) if bounded else tok,
            check_vma=False)(x, gate_vals, gate_idx, experts)
        out, compact = out if bounded else (out, None)
    if compact is not None:
        stats = dict(stats, compact=compact.astype(jnp.float32))
    if cfg.d_shared:
        with jax.named_scope("shared_expert"):
            if "shared" in params:
                shared = mlp.apply_gated_mlp(
                    params["shared"], x, compute_dtype=cd,
                    three_pass=three_pass).astype(jnp.float32)
                if "w_sg" in params:
                    shared = shared * jax.nn.sigmoid(jnp.einsum(
                        "bsd,do->bso", x.astype(jnp.float32),
                        params["w_sg"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST))
                out = out + shared
            else:
                project = core.project(cd, three_pass)
                hidden = _relu2(project("bsd,df->bsf", x,
                                        params["shared_w1"], jnp.float32))
                out = out + project("bsf,fd->bsd", hidden,
                                    params["shared_w2"], jnp.float32)
    return out.astype(x.dtype), stats
