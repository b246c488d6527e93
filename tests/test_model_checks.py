"""What the model files' "against the reference" cases share, and its own
proof: parameters with chosen leaves moved off their draw, tokens, the loss
and gradients of a model function COMPILED (the whole tree, or the leaves
the benchmark compares, through `chipbench.compare.loss_and_grads` under
`jax.jit` as `compare.compare` runs it), the relative-L2 tree and the
leaf-by-leaf assertion. A model file keeps its configuration, its reference
and its tolerances, and imports these. On a CPU a scanned, rematerialised
model differentiated op by op dispatches every primitive of forward and
backward from Python: four to ten times the compiled seconds under load."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare


def filed(name):
    """A tiny model's configuration as the benchmark's own tests file it."""
    with open(os.path.join(os.path.dirname(__file__), "chipbench_tests",
                           "configs", f"{name}.json")) as f:
        return json.load(f)


def moved_off(params, seed, amount):
    """`params` with normal noise on the leaves `amount(key, leaf)` gives a
    scale for (`key` the leaf's own name in its dict; 0 leaves it): norms
    and biases off their initial 1 and 0, so that one applied in the wrong
    place shows. The draws are `PRNGKey(seed)` split 64 ways, taken in tree
    order by the moved leaves alone."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def moved(path, a):
        scale = amount(path[-1].key, a)
        return a + scale * jax.random.normal(next(keys), a.shape) \
            if scale else a
    return jax.tree_util.tree_map_with_path(moved, params)


def token_ids(vocab_size, batch=2, seq=64, seed=1):
    """`[batch, seq + 1]` token ids: inputs and their shifted targets."""
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                              vocab_size)


def rel(got, want):
    """The tree of relative L2 errors, each in float64."""
    return jax.tree_util.tree_map(compare.rel_l2, got, want)


def loss_and_grads(loss_fn, params, **kw):
    """`loss_fn(params)` and its gradient for every leaf, compiled;
    `has_aux=True` where the function returns `(loss, metrics)`."""
    return jax.jit(jax.value_and_grad(loss_fn, **kw))(params)


def picked_program(loss_fn, accounting):
    """``(params, tokens) -> (loss, gradients of the leaves `accounting.pick`
    names)``, compiled as `compare.compare` compiles its system side: for a
    file to keep where two of its cases run ONE program."""
    return jax.jit(compare.loss_and_grads(loss_fn, accounting.pick,
                                          accounting.put))


def picked(loss_fn, accounting, params, tokens):
    """`picked_program`, made and run once."""
    return picked_program(loss_fn, accounting)(params, tokens)


def assert_close(got, want, tol, skip=()):
    """Every leaf of `got` within `tol` of `want`'s in relative L2, the
    failing leaf named; a leaf whose name ends in one of `skip` is left
    out. NaN (a zero reference gradient) fails."""
    flat = jax.tree_util.tree_leaves_with_path(rel(got, want))
    assert flat
    for path, err in flat:
        name = jax.tree_util.keystr(path)
        if not name.endswith(tuple(skip)):
            assert err <= tol, (name, err)


def against_reference(system, reference, params, *, loss_rtol, grad_tol,
                      skip=(), has_aux=False):
    """The case every model file has: `system(params)`'s loss within
    `loss_rtol` of `reference(params)`'s and every gradient leaf within
    `grad_tol`, both compiled. Returns what `system` returned, its
    gradients and the reference's."""
    out, grads = loss_and_grads(system, params, has_aux=has_aux)
    want, want_grads = loss_and_grads(reference, params)
    loss = out[0] if has_aux else out
    assert abs(float(loss) - float(want)) <= loss_rtol * abs(float(want))
    assert_close(grads, want_grads, grad_tol, skip)
    return out, grads, want_grads


def reference_side(reference_fn, accounting, params, tokens):
    """The reference half of `compare.compare`, for a file whose cases share
    one reference: loss and picked gradients a sequence at a time at the
    highest matmul precision, averaged in float64."""
    ref = jax.jit(compare.loss_and_grads(
        reference_fn, accounting.pick, accounting.put))
    loss, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for row in np.asarray(tokens):
            one_loss, one = ref(params, row[None])
            loss += float(one_loss) / len(tokens)
            one = jax.tree_util.tree_map(
                lambda g: np.asarray(g, np.float64) / len(tokens), one)
            grads = one if grads is None else \
                jax.tree_util.tree_map(np.add, grads, one)
    return loss, grads


def once(once_a_run, name, make, like):
    """`make()` -> (loss, gradients) — a `reference_side`, or every leaf's —
    made ONCE A RUN: the workers of a parallel run, each handed some of a
    file's cases, share the first one's through `once_a_run`'s JSON (a
    float64 survives it digit for digit) instead of compiling the reference
    again. `like`: a tree of the gradients' structure (`accounting.pick`'s,
    or the parameters)."""
    def listed():
        loss, grads = make()
        return float(loss), [np.asarray(g, np.float64).tolist()
                             for g in jax.tree_util.tree_leaves(grads)]
    loss, leaves = once_a_run(name, listed)
    return loss, jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(like),
        [np.asarray(g, np.float64) for g in leaves])


def compared(system_fn, accounting, params, tokens, reference):
    """`compare.compare`'s result from a `reference_side` made before."""
    return held_to(picked(system_fn, accounting, params, tokens), reference)


def held_to(system, reference):
    """`compared` from both sides' (loss, picked gradients)."""
    (loss, grads), (ref_loss, ref_grads) = system, reference
    errors = {"loss": abs(float(loss) - ref_loss) / abs(ref_loss)}
    for name in ref_grads:
        errors[f"grad_{name}"] = compare.rel_l2(grads[name], ref_grads[name])
    return {"system_loss": float(loss), "reference_loss": ref_loss,
            "errors": errors,
            "reference_grad_norms": {k: float(np.linalg.norm(v))
                                     for k, v in ref_grads.items()},
            "within": bool(errors["loss"] <= compare.LOSS_RTOL and all(
                v <= compare.GRAD_RTOL
                for k, v in errors.items() if k != "loss"))}


# ------------------------------------------------------------- its proof
def _tiny():
    """Two scanned, rematerialised layers of an MLP over an embedding."""
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    params = {"wte": jax.random.normal(k[0], (16, 8)),
              "blocks": {"ln": jnp.ones((2, 8)),
                         "w": 0.3 * jax.random.normal(k[1], (2, 8, 8))},
              "head": 0.3 * jax.random.normal(k[2], (8, 16))}

    def loss(params, toks):
        def layer(x, block):
            return x + jnp.tanh(x * block["ln"] @ block["w"]), None
        x, _ = jax.lax.scan(jax.checkpoint(layer), params["wte"][toks[:, :-1]],
                            params["blocks"])
        logp = jax.nn.log_softmax(x @ params["head"])
        return -jnp.mean(jnp.take_along_axis(logp, toks[:, 1:, None], -1))
    return params, loss


class _Leaves:
    pick = staticmethod(lambda params: {
        "head": params["head"], "w": params["blocks"]["w"]})
    put = staticmethod(lambda params, leaves: dict(
        params, head=leaves["head"],
        blocks=dict(params["blocks"], w=leaves["w"])))


def test_the_compiled_gradients_are_the_op_by_op_ones():
    params, loss = _tiny()
    params = moved_off(params, 1, lambda key, a: 0.1 * (key == "ln"))
    assert not np.array_equal(params["blocks"]["ln"], 1.0)
    np.testing.assert_array_equal(params["head"], _tiny()[0]["head"])
    toks = token_ids(16, batch=2, seq=12)
    assert toks.shape == (2, 13)
    want, want_grads = jax.value_and_grad(lambda p: loss(p, toks))(params)
    got, grads = loss_and_grads(lambda p: loss(p, toks), params)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert_close(grads, want_grads, 1e-6)
    (aux, two), aux_grads, ref_grads = against_reference(
        lambda p: (loss(p, toks), 2.0), lambda p: loss(p, toks), params,
        loss_rtol=0.0, grad_tol=0.0, has_aux=True)
    assert float(aux) == float(got) and float(two) == 2.0
    assert_close(aux_grads, grads, 0.0)
    with pytest.raises(AssertionError):
        against_reference(lambda p: 1.001 * loss(p, toks),
                          lambda p: loss(p, toks), params, loss_rtol=1e-4,
                          grad_tol=1.0)
    # the picked leaves: those of the whole tree
    some, some_grads = picked(loss, _Leaves, params, toks)
    assert float(some) == pytest.approx(float(want), rel=1e-6)
    assert set(some_grads) == {"head", "w"}
    assert_close(some_grads, _Leaves.pick(want_grads), 1e-6)


def test_assert_close_names_the_leaf_and_refuses_nan():
    want = {"a": {"ok": np.ones(3), "bias": np.ones(3)}, "b": np.ones(3)}
    got = {"a": {"ok": want["b"] + 1e-7, "bias": 2 * want["b"]},
           "b": want["b"] + 3e-6}
    assert_close(got, want, 1e-5, skip=("['bias']",))
    with pytest.raises(AssertionError, match=r"\['a'\]\['bias'\]"):
        assert_close(got, want, 1e-5)
    with pytest.raises(AssertionError, match=r"\['b'\]"):
        assert_close(got, want, 1e-6, skip=("['bias']",))
    with pytest.raises(AssertionError, match="nan"):
        assert_close({"z": np.zeros(3)}, {"z": np.zeros(3)}, 1e-5)
    with pytest.raises(AssertionError):
        assert_close({}, {}, 1e-5)


def test_compared_from_a_kept_reference_is_compare_compare():
    """The two halves give what the benchmark's own function gives, number
    for number: a file may keep the reference's half between its cases."""
    params, loss = _tiny()
    toks = token_ids(16, batch=3, seq=12)

    def system(p, t):           # another program: bf16 products
        return loss(jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), p), t)
    want = compare.compare(system, loss, params, toks, jax.devices()[0],
                           pick=_Leaves.pick, put=_Leaves.put)
    kept = reference_side(loss, _Leaves, params, toks)
    assert compared(system, _Leaves, params, toks, kept) == want
    assert 0 < want["errors"]["grad_w"] < compare.GRAD_RTOL
