"""The comparison that decides ``correct``: the system's loss and gradients
against the plain reference, on the same parameters and the same tokens.

Compared are the loss and the gradients of a few leaves. Which leaves, and
how they go back into the tree, is the architecture's to say: ``pick`` and
``put`` come from its ``accounting/<reference>.py``. The tolerances are
the same for every architecture.
"""
import jax
import numpy as np

# The system computes in bf16 (8 bits of mantissa, rounding error 2^-9 a
# value) with float32 accumulation; the reference in float32 throughout.
# Measured on the v5e at full width on freshly made parameters (PR 23, 39
# runs over the three cells): loss within 5.2e-5 relative; gradients
# within 0.037 relative L2, the query weights' the largest (0.026 at
# gpt2-small, 0.037 at gpt2-large; their gradient is fifty times smaller
# than the MLP's beside it) and the others 0.009 to 0.018. The bounds are
# twice the largest bf16 gives and more. An 8-bit float (3 bits of
# mantissa in e4m3) rounds 16 times as coarsely: sixteen times the
# measured errors is 0.15 to 0.6 for the gradients and 8e-4 for the
# loss, outside both (the tests round a tiny model's matmul weights to
# e4m3 and see the comparison fail).
LOSS_RTOL = 3e-4
GRAD_RTOL = 8e-2


def loss_and_grads(loss_fn, pick, put):
    """``loss_fn(params, tokens) -> scalar`` -> a function giving the loss
    and its gradients with respect to the picked leaves alone."""
    def run(params, tokens):
        return jax.value_and_grad(
            lambda leaves: loss_fn(put(params, leaves), tokens))(
                pick(params))
    return run


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def compare(system_fn, reference_fn, params, tokens, device, *, pick,
            put) -> dict:
    """Errors of the system against the reference. ``system_fn`` runs as
    the cell runs it (its mesh, its dtype, its kernels) on all of
    ``tokens``; the reference runs on ``device`` alone, one sequence at a
    time, and its results are averaged (equal-length sequences: the mean
    of means is the mean)."""
    loss, grads = jax.jit(
        loss_and_grads(system_fn, pick, put))(params, tokens)
    params0 = jax.device_put(params, device)
    ref = jax.jit(loss_and_grads(reference_fn, pick, put))
    ref_loss, ref_grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for row in np.asarray(tokens):
            one_loss, one = ref(params0, jax.device_put(row[None], device))
            ref_loss += float(one_loss) / len(tokens)
            one = jax.tree_util.tree_map(
                lambda g: np.asarray(g, np.float64) / len(tokens), one)
            ref_grads = one if ref_grads is None else \
                jax.tree_util.tree_map(np.add, ref_grads, one)
    errors = {"loss": abs(float(loss) - ref_loss) / abs(ref_loss)}
    for name in ref_grads:
        errors[f"grad_{name}"] = rel_l2(grads[name], ref_grads[name])
    return {"system_loss": float(loss), "reference_loss": ref_loss,
            "errors": errors,
            "reference_grad_norms": {k: float(np.linalg.norm(v))
                                     for k, v in ref_grads.items()},
            "within": bool(errors["loss"] <= LOSS_RTOL and all(
                v <= GRAD_RTOL for k, v in errors.items() if k != "loss"))}


def beside_limits(errors: dict) -> dict:
    """``compare``'s errors, each beside the limit it was held to, ``name ->
    [number, limit]``, the nearest to its limit first: what a run prints
    last, so that one called not correct says by which number."""
    beside = {k: [v, LOSS_RTOL if k == "loss" else GRAD_RTOL]
              for k, v in errors.items()}
    return dict(sorted(beside.items(), key=lambda kv: -kv[1][0] / kv[1][1]))
