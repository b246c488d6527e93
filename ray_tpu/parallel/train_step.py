"""Sharded training step: the compiled unit every Train worker runs.

Design: a single jitted function over a global mesh — params/opt-state
sharded by the model's logical-axis rules, batch sharded (dp, sp), grads
psum'd implicitly by XLA (dp axis appears in batch but not params), donated
state. The reference's equivalent is the user's torch DDP loop driven by
Ray Train (train/torch/config.py:69 + data_parallel_trainer.py); here the
"backend setup" is just mesh construction — no process groups.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel import sharding as sh
from ray_tpu.parallel.compile_watch import (
    CompiledFunction,
    configure_compile_cache,
)


def _jit(fn, name: str, **jit_kwargs) -> CompiledFunction:
    """Every compiled unit this module hands out: persistent compile
    cache configured before its first compile, compile observability
    (cache hit/miss counters, compile timing, COMPILE_BEGIN/END events)
    around every call."""
    configure_compile_cache()
    return CompiledFunction(jax.jit(fn, **jit_kwargs), name)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    step: jnp.ndarray
    params: Any
    opt_state: Any


def default_optimizer(
    learning_rate: float = 3e-4,
    *,
    warmup_steps: int = 100,
    total_steps: int = 10_000,
    weight_decay: float = 0.1,
    b2: float = 0.95,
    grad_clip: float = 1.0,
) -> optax.GradientTransformation:
    sched = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1)
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(sched, b2=b2, weight_decay=weight_decay),
    )


def make_train_state(
    init_params_fn: Callable[[jax.Array], Any],
    rng: jax.Array,
    optimizer: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    param_specs: Any = None,
) -> TrainState:
    """Initialize params + opt state ON-DEVICE with the right shardings:
    params are sharding-constrained inside the jitted init so large models
    never materialize unsharded; opt-state shardings propagate from params
    (mu/nu are zeros_like(params))."""

    def init_fn(rng):
        params = init_params_fn(rng)
        if mesh is not None and param_specs is not None:
            params = jax.tree_util.tree_map(
                lambda x, s: jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, s)
                ),
                params,
                param_specs,
            )
        opt_state = optimizer.init(params)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=opt_state)

    state = _jit(init_fn, "train_state_init")(rng)
    _note_state_bytes(state)
    return state


def make_zero_train_state(
    init_params_fn: Callable[[jax.Array], Any],
    rng: jax.Array,
    mesh: Optional[Mesh] = None,
    param_specs: Any = None,
) -> TrainState:
    """ZeRO variant of :func:`make_train_state`: no on-device optimizer
    state. The state lives in a ``train.ddp.ZeroOptimizer`` instead —
    sharded over the bucket plan, materialized per rank, and stamped
    into the ``opt_state`` gauge at shard granularity — so
    ``TrainState.opt_state`` is the empty tuple and this process's
    replicated-state footprint is params only."""

    def init_fn(rng):
        params = init_params_fn(rng)
        if mesh is not None and param_specs is not None:
            params = jax.tree_util.tree_map(
                lambda x, s: jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, s)
                ),
                params,
                param_specs,
            )
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=())

    state = _jit(init_fn, "train_state_init")(rng)
    _note_state_bytes(state)
    return state


def _note_state_bytes(state: TrainState):
    """Stamp ``ray_tpu_train_state_bytes{kind=params|opt_state,rank}``
    from the deterministic flatten — the exact resident footprint of the
    state this process just materialized (memory-anatomy plane)."""
    try:
        from ray_tpu._private import memory_anatomy as _ma
        from ray_tpu._private import telemetry as _tm

        if not _tm.ENABLED:
            return
        rank = 0
        try:
            from ray_tpu.util import collective as col

            for g in ("train_dp", "default"):
                if col.is_group_initialized(g):
                    rank = col.get_rank(g)
                    break
        except Exception:
            rank = 0
        for kind, tree in (("params", state.params),
                           ("opt_state", state.opt_state)):
            leaves, _ = sh.flatten_tree(tree)
            _ma.LEDGER.note_train_state(
                kind, rank, sum(int(l.nbytes) for l in leaves))
    except Exception:
        pass


def make_train_step(
    loss_fn: Callable[[Any, Any], tuple],
    optimizer: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    *,
    batch_spec: P = P(("dp",), "sp"),
    donate: bool = True,
    host_grad_sync: Optional[Callable[[Any], Any]] = None,
    host_optimizer: Any = None,
):
    """loss_fn(params, batch) -> (scalar_loss, metrics_dict).

    Returns jitted step(state, batch) -> (state, metrics).

    ``host_grad_sync`` (optional) is the host-DP hook: a callable
    ``grads_pytree -> synced_grads_pytree`` (canonically
    ``ray_tpu.train.ddp.sync_gradients``) run OUTSIDE the compiled
    program, between a jitted grad computation and a jitted optimizer
    apply. This is the regime where each gang member owns its local
    devices and grads cross hosts over the collective plane (the
    reference's torch-DDP shape) instead of an XLA psum — the step
    splits into two compiled functions so the host collective can run
    in the middle, and the bucketed-DDP plane can overlap that comm
    with the unpack/pack work around it.

    ``host_optimizer`` (a ``train.ddp.ZeroOptimizer``; mutually
    exclusive with ``host_grad_sync`` and ``optimizer``-driven apply)
    selects the ZeRO-sharded host path: the jitted function computes
    grads only, the sharded optimizer reducescatters them, applies this
    rank's shards, and allgathers updated params ASYNC — the returned
    ``step`` waits those gathers at the START of the next call (first
    use), so everything between steps overlaps the gather comm. The
    step function exposes ``step.finalize(state)`` — call it once after
    the loop to fold the last step's in-flight params into the state.
    ``metrics["grad_norm"]`` in this mode is the LOCAL pre-sync norm
    (the synced grads exist only as shards).
    """

    def _constrain_batch(batch):
        if mesh is not None:
            batch = jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, batch_spec)
                ),
                batch,
            )
        return batch

    if host_optimizer is not None:
        if host_grad_sync is not None:
            raise ValueError("host_optimizer and host_grad_sync are "
                             "mutually exclusive — the sharded "
                             "optimizer owns the gradient sync")

        def zgrad_step(params, batch):
            batch = _constrain_batch(batch)
            (_loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return dict(metrics), grads, optax.global_norm(grads)

        zgrad_fn = _jit(zgrad_step, "train_grad_step")
        box = {"pending": None}

        def resolve(state: TrainState) -> TrainState:
            pending = box["pending"]
            if pending is None:
                return state
            box["pending"] = None
            # first use of the previous step's params: the allgathers
            # rode the issue thread through everything the caller did
            # since step_async returned; only the residue blocks here.
            # timeout=None defers to the per-op collective deadline so
            # a dead peer surfaces as CollectiveGroupError, not a hang
            return dataclasses.replace(
                state, params=pending.result(timeout=None))

        def step(state: TrainState, batch):
            state = resolve(state)
            metrics, grads, grad_norm = zgrad_fn(state.params, batch)
            box["pending"] = host_optimizer.step_async(state.params,
                                                       grads)
            metrics = dict(metrics)
            metrics["grad_norm"] = grad_norm
            return (
                TrainState(step=state.step + 1, params=state.params,
                           opt_state=state.opt_state),
                metrics,
            )

        step.finalize = resolve
        return step

    if host_grad_sync is None:
        def step(state: TrainState, batch):
            batch = _constrain_batch(batch)
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, batch)
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
            metrics = dict(metrics)
            metrics["grad_norm"] = optax.global_norm(grads)
            return (
                TrainState(step=state.step + 1, params=params,
                           opt_state=opt_state),
                metrics,
            )

        return _jit(step, "train_step",
                    donate_argnums=(0,) if donate else ())

    def grad_step(params, batch):
        batch = _constrain_batch(batch)
        # metrics pass through exactly as loss_fn returned them — the
        # no-hook path adds only grad_norm, and the two modes must
        # expose the same metric schema for the same loss_fn
        (_loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return dict(metrics), grads

    def apply_step(state: TrainState, grads):
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return (
            TrainState(step=state.step + 1, params=params,
                       opt_state=opt_state),
            optax.global_norm(grads),
        )

    grad_fn = _jit(grad_step, "train_grad_step")
    apply_fn = _jit(apply_step, "train_apply_step",
                    donate_argnums=(0,) if donate else ())

    def step(state: TrainState, batch):
        metrics, grads = grad_fn(state.params, batch)
        # the hook receives the device grads pytree; the bucketed sync
        # materializes leaves per bucket (np.asarray is the device→host
        # fetch), so later buckets' transfers overlap earlier buckets'
        # allreduce. grad_norm is computed from the SYNCED grads — the
        # quantity the optimizer actually applies.
        synced = host_grad_sync(grads)
        state, grad_norm = apply_fn(state, synced)
        metrics = dict(metrics)
        metrics["grad_norm"] = grad_norm
        return state, metrics

    return step


def eval_step(loss_fn, mesh: Optional[Mesh] = None, batch_spec: P = P(("dp",), "sp")):
    def step(params, batch):
        if mesh is not None:
            batch = jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, batch_spec)
                ),
                batch,
            )
        _, metrics = loss_fn(params, batch)
        return metrics

    return _jit(step, "eval_step")
