"""LearnerGroup — multi-learner (data-parallel) RL updates over a Mesh.

Reference: rllib/core/learner/learner_group.py — N learner workers each
take a shard of the train batch, compute gradients, and all-reduce
before applying. TPU-first inversion: instead of N processes + NCCL
all-reduce, the whole update is ONE jitted SPMD program over a
jax.sharding.Mesh — the batch shards over the `dp` axis, gradients
psum over ICI inside the compiled step, and parameters stay replicated.
The same program scales from 1 chip to a pod slice by changing the
mesh.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import optax


class LearnerGroup:
    """Data-parallel learner: `update(batch)` runs one SPMD step with
    per-device batch shards and psum'd gradients.

    loss_fn(params, minibatch) -> (loss, aux_dict) — same signature the
    single-learner algorithms use, so any of them can hand its loss
    here to scale out.
    """

    def __init__(self, loss_fn, params, *, lr: float = 3e-4,
                 optimizer=None, devices=None, axis: str = "dp"):
        self.devices = list(devices if devices is not None
                            else jax.devices())
        self.axis = axis
        self.mesh = jax.sharding.Mesh(self.devices, (axis,))
        self.optimizer = optimizer or optax.adam(lr)
        self.params = params
        self.opt_state = self.optimizer.init(params)
        self._loss_fn = loss_fn
        self._step = self._build_step()

    @property
    def num_learners(self) -> int:
        return len(self.devices)

    def _build_step(self):
        from jax.sharding import PartitionSpec as P

        axis = self.axis
        optimizer = self.optimizer
        loss_fn = self._loss_fn

        def per_shard(params, opt_state, shard):
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, shard)
            # gradient all-reduce over ICI — the NCCL ring of the
            # reference's multi-learner, compiled into the step
            grads = jax.lax.pmean(grads, axis)
            loss = jax.lax.pmean(loss, axis)
            aux = jax.tree_util.tree_map(
                lambda v: jax.lax.pmean(v, axis), aux)
            updates, opt_state = optimizer.update(grads, opt_state,
                                                  params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss, aux

        smapped = jax.shard_map(
            per_shard, mesh=self.mesh,
            in_specs=(P(), P(), P(axis)),
            out_specs=(P(), P(), P(), P()),
            check_vma=False)
        return jax.jit(smapped)

    def update(self, batch: dict) -> dict:
        """One data-parallel step over the full batch (leading dim must
        divide the learner count). Returns {"loss": float, **aux}."""
        n = self.num_learners
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        b = next(iter(batch.values())).shape[0]
        if b % n:
            # truncate the ragged tail so shards stay equal (static
            # shapes; the reference's learner group drops remainders
            # the same way)
            batch = {k: v[: b - b % n] for k, v in batch.items()}
        self.params, self.opt_state, loss, aux = self._step(
            self.params, self.opt_state, batch)
        out = {"loss": float(loss), "num_learners": n}
        out.update({k: float(v) for k, v in aux.items()})
        return out
