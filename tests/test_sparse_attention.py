"""`ray_tpu.ops.sparse_attention` alone, on the CPU: the selection against
`jax.lax.top_k` (ties included), the indexer's scores against the explicit
form, attention over a kept set — the flash kernels with their `keep` operand
through the Pallas interpreter, forward, dq, dk and dv — against the masked
plain form at a length that is no multiple of a tile, the heads' mean
attention, the indexer's loss with its hand-written gradient, and the two
kernels that fuse the last two (`indexer_loss`) against them."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops import sparse_attention as sa


@functools.partial(jax.jit, static_argnums=1)
def _top_k_mask(scores, topk):
    """The kept set as `jax.lax.top_k` gives it: row t's first
    ``min(t + 1, topk)`` indices."""
    B, S, _ = scores.shape
    n = min(topk, S)
    _, chosen = jax.lax.top_k(scores, n)
    valid = jnp.arange(n)[None, :] < jnp.minimum(jnp.arange(S) + 1,
                                                 topk)[:, None]
    mask = jnp.zeros((B, S, S), jnp.int8)
    rows = jnp.arange(S)[None, :, None]
    return mask.at[jnp.arange(B)[:, None, None], rows, chosen].max(
        jnp.broadcast_to(valid, chosen.shape).astype(jnp.int8))


def _causal(scores):
    S = scores.shape[-1]
    return jnp.where(jnp.arange(S)[None, :] <= jnp.arange(S)[:, None],
                     scores, sa.NEG_INF)


@pytest.mark.parametrize("S, topk", [(40, 12), (40, 64), (130, 33), (7, 1)])
def test_the_selection_is_lax_top_ks(S, topk):
    scores = _causal(jax.random.normal(jax.random.PRNGKey(S), (2, S, S)))
    keep = jax.jit(sa.select, static_argnums=1)(scores, topk)
    assert keep.dtype == jnp.int8
    np.testing.assert_array_equal(keep, _top_k_mask(scores, topk))
    np.testing.assert_array_equal(
        jnp.sum(keep, axis=-1)[0], np.minimum(np.arange(S) + 1, topk))


@pytest.mark.parametrize("values", ["few", "zeros", "one"])
def test_ties_go_to_the_lower_index(values):
    """Scores drawn from a handful of values (every threshold is tied many
    times over), from ±0.0 alone (−0.0 sorts below +0.0) and all alike: the
    set is `top_k`'s."""
    S, topk = 48, 9
    key = jax.random.PRNGKey(3)
    if values == "few":
        scores = jax.random.randint(key, (2, S, S), -2, 3).astype(jnp.float32)
    elif values == "zeros":
        scores = jnp.where(jax.random.bernoulli(key, 0.5, (2, S, S)),
                           0.0, -0.0)
    else:
        scores = jnp.full((2, S, S), 1.5)
    scores = _causal(scores)
    keep = jax.jit(sa.select, static_argnums=1)(scores, topk)
    np.testing.assert_array_equal(keep, _top_k_mask(scores, topk))
    if values == "one":     # all tied: the first `k` keys of every row
        want = np.arange(S)[None, :] < np.minimum(np.arange(S) + 1,
                                                  topk)[:, None]
        np.testing.assert_array_equal(keep[0], want)


def test_the_indexers_scores_against_the_explicit_form():
    """Blocks of queries (three, the last a short one) against one einsum:
    values and the three gradients."""
    B, S, J, E = 2, 37, 3, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    qi = jax.random.normal(ks[0], (B, S, J, E))
    ki = jax.random.normal(ks[1], (B, S, E))
    w = jax.random.normal(ks[2], (B, S, J))
    d = jax.random.normal(ks[3], (B, S, S))
    below = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def plain(qi, ki, w):
        r = jnp.einsum("btje,bse->btjs", qi, ki)
        return jnp.where(below, jnp.sum(jax.nn.relu(r) * w[..., None], 2),
                         sa.NEG_INF)
    blocks = functools.partial(sa.index_scores, rows=16)
    got = jax.jit(blocks)(qi, ki, w)
    np.testing.assert_allclose(got, plain(qi, ki, w), atol=1e-5)
    assert (np.asarray(got)[:, ~np.asarray(below)] == sa.NEG_INF).all()

    def loss(fn):
        return lambda *a: jnp.sum(jnp.where(below, fn(*a) * d, 0.0))
    want = jax.jit(jax.grad(loss(plain), argnums=(0, 1, 2)))(qi, ki, w)
    grads = jax.jit(jax.grad(loss(blocks), argnums=(0, 1, 2)))(qi, ki, w)
    for a, b in zip(grads, want):
        np.testing.assert_allclose(a, b, atol=1e-4)


@functools.partial(jax.jit, static_argnums=0, static_argnames=(
    "H", "KV", "D", "topk", "seed", "B"))
def _operands(S, H=4, KV=2, D=16, topk=24, seed=0, B=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    keep = sa.select(_causal(jax.random.normal(ks[3], (B, S, S))), topk)
    do = jax.random.normal(ks[4], (B, S, H, D))
    return q, k, v, keep, do


def _masked_plain(q, k, v, keep):
    """Softmax attention over the kept keys, written out."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(keep[:, None] != 0, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v), p


@pytest.mark.parametrize("S, topk", [(200, 3), (128, 24)])
def test_the_kernels_with_a_kept_set_against_the_masked_plain_form(S, topk):
    """Forward, dq, dk and dv of the flash kernels with `keep`, through the
    interpreter, at 200 keys (128-wide tiles: the length is no multiple of
    one, the padded rows and columns keep nothing) and at one whole tile;
    rows whose first tile holds no kept key among them."""
    q, k, v, keep, do = _operands(S, topk=topk)
    first_tile = np.asarray(keep)[:, :, :128].sum(-1)
    if S > 128:
        assert (first_tile[:, 128:] == 0).any()   # nothing kept in a tile
    want_o, _ = _masked_plain(q, k, v, keep)
    want = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(_masked_plain(q, k, v, keep)[0] * do),
        argnums=(0, 1, 2)))(q, k, v)

    def run(q, k, v):
        o, lse = sa.sparse_attention(q, k, v, keep, interpret=True)
        return jnp.sum(o * do), (o, lse)
    (_, (o, lse)), grads = jax.jit(jax.value_and_grad(
        run, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    for a, b in zip(grads, want):
        np.testing.assert_allclose(a, b, atol=5e-5)
    # the log-sum-exp is the kept scores'
    group = q.shape[2] // k.shape[2]
    s = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(k, group, 2)) / 4.0
    want_lse = jax.scipy.special.logsumexp(
        jnp.where(keep[:, None] != 0, s, -jnp.inf), axis=-1)
    np.testing.assert_allclose(lse.reshape(want_lse.shape), want_lse,
                               atol=2e-5)
    # and the plain form of the op is the same function
    plain_o, plain_lse = sa.sparse_attention(q, k, v, keep, kernel=False)
    np.testing.assert_allclose(plain_o, want_o, atol=2e-5)
    np.testing.assert_allclose(plain_lse, lse, atol=2e-5)


def test_the_kernel_calls_are_named_for_the_trace():
    q, k, v, keep, do = _operands(128)
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(sa.sparse_attention(
        q, k, v, keep, interpret=True)[0])))(q))
    for name in ("flash_sparse_fwd", "flash_sparse_dq", "flash_sparse_dkv"):
        assert text.count(f"name={name}") == 1, name
    # without a kept set the kernels are the calls they were
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(fa.flash_attention(
        q, k, v, interpret=True))))(q))
    assert "flash_sparse" not in text and "name=flash_fwd" in text


def test_the_heads_mean_attention():
    q, k, v, keep, _ = _operands(200, seed=1)
    _, lse = sa.sparse_attention(q, k, v, keep, kernel=False)
    got = sa.mean_probs(q, k, lse, keep)
    _, p = _masked_plain(q, k, v, keep)
    np.testing.assert_allclose(got, jnp.mean(p, axis=1), atol=2e-6)
    np.testing.assert_allclose(jnp.sum(got, axis=-1), 1.0, atol=1e-5)
    assert (np.asarray(got)[np.asarray(keep) == 0] == 0).all()
    # nothing flows back through it
    grads = jax.jit(jax.grad(lambda q: jnp.sum(sa.mean_probs(
        q, k, lse, keep) ** 2)))(q)
    assert not np.asarray(grads).any()


def _written_out_kl(scores, probs, kept):
    """The KL a batch row from `log_softmax`, for JAX to differentiate."""
    logq = jax.nn.log_softmax(jnp.where(kept, scores, -jnp.inf), axis=-1)
    terms = jnp.where(kept & (probs > 0), probs * (
        jnp.log(jnp.where(probs > 0, probs, 1.0))
        - jnp.where(kept, logq, 0.0)), 0.0)
    return jnp.sum(terms, axis=(1, 2))


# S, B, H, KV, topk: 200 is no multiple of the 128-wide tile, 33 lies under
# one; a KV group of 1, 2 and 4 query heads; rows that keep one key
_ZEROS = 20     # the row of each case that holds exact zeros among its kept p
_FUSED = [(200, 2, 4, 2, 24), (33, 1, 4, 4, 7), (33, 2, 2, 2, 1),
          (130, 2, 4, 1, 40), (256, 1, 4, 2, 200)]


def _fused_case(S, B, H, KV, topk):
    """One case's arrays: the operands, what the fused op makes of them
    through the interpreter (`value`, `rows`, `grad` under a cotangent a
    row), and its oracles (`indexer_kl` of `mean_probs`, the written-out
    form and JAX's gradient of it, the op off the kernel path)."""
    q, k, v, keep, _ = _operands(S, H=H, KV=KV, topk=topk, B=B, seed=S)
    if topk > 1:
        # a query whose heads all look at the first of its kept keys: other
        # kept pairs of that row hold p = 0 exactly (exp underflows). Its
        # scores are ~240, so its p is good to ~1e-4 of itself, no better
        first = jnp.argmax(keep[:, _ZEROS], axis=-1)
        q = q.at[:, _ZEROS].set(60.0 * jnp.repeat(
            k[jnp.arange(B), first], H // KV, axis=1))
    scores = _causal(jax.random.normal(jax.random.PRNGKey(S + 1), (B, S, S)))
    _, lse = sa.sparse_attention(q, k, v, keep, kernel=False)
    weights = jnp.arange(1, B + 1, dtype=jnp.float32) * -1.5
    value, (*_, rows) = sa._kernel_kl_fwd(q, k, lse, keep, scores,
                                          q.shape[-1] ** -0.5, True)
    grad = jax.grad(lambda s: jnp.sum(sa.indexer_loss(
        q, k, lse, keep, s, interpret=True) * weights))(scores)
    probs, kept = sa.mean_probs(q, k, lse, keep), keep != 0
    return dict(
        keep=keep, value=value, rows=rows, grad=grad, probs=probs,
        want=sa.indexer_kl(scores, probs, keep),
        written_out=_written_out_kl(scores, probs, kept),
        plain=sa.indexer_loss(q, k, lse, keep, scores, kernel=False),
        want_lse=jax.scipy.special.logsumexp(
            jnp.where(kept, scores, -jnp.inf), axis=-1),
        want_grad=jax.grad(lambda s: jnp.sum(
            _written_out_kl(s, probs, kept) * weights))(scores))


@pytest.fixture(params=_FUSED, ids=lambda c: "S%d-B%d-H%d-KV%d-top%d" % c)
def fused(request, once_a_run):
    """`_fused_case`, made ONCE A RUN: a case's four tests go to whichever
    workers are free, and each would draw, interpret and differentiate the
    same arrays again (float32 and int8 survive JSON digit for digit). A
    fixture a TEST, not a module: pytest then keeps a test's five cases
    together, so that five workers make five cases, where it would hand
    one case's four tests to four workers that wait for the first."""
    made = once_a_run(
        "sparse_attention_fused_S%d-B%d-H%d-KV%d-top%d" % request.param,
        lambda: {name: np.asarray(a).tolist() for name, a in jax.jit(
            functools.partial(_fused_case, *request.param))().items()})
    return {name: np.asarray(a, np.int8 if name == "keep" else np.float32)
            for name, a in made.items()}


def _but_the_row_of_zeros(a, b, atol):
    """a [B, S, ...] against b at `atol`, the row `_ZEROS` at 1e-3."""
    a, b = np.broadcast_arrays(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(a, b, atol=1e-3)
    np.testing.assert_allclose(np.delete(a, _ZEROS, 1),
                               np.delete(b, _ZEROS, 1), atol=atol)


def test_the_fused_loss_is_the_plain_forms_value(fused):
    f = fused
    assert f["value"].shape == f["want"].shape == (f["keep"].shape[0],)
    np.testing.assert_allclose(f["value"], f["want"], rtol=2e-5)
    np.testing.assert_allclose(f["value"], f["written_out"], rtol=2e-5)
    # the public op off the kernel path is the plain form itself
    np.testing.assert_array_equal(f["plain"], f["want"])


def test_the_fused_loss_keeps_each_rows_log_sum_exp_and_sum(fused):
    f = fused
    B, S = f["keep"].shape[:2]
    assert f["rows"].shape == (B, 2, S)
    np.testing.assert_allclose(f["rows"][:, 0], f["want_lse"], atol=1e-5)
    _but_the_row_of_zeros(f["rows"][:, 1], np.sum(f["probs"], axis=-1),
                          1e-6)
    _but_the_row_of_zeros(f["rows"][:, 1], 1.0, 1e-5)


def test_the_fused_gradient_is_jaxs_of_the_written_out_form(fused):
    f = fused
    kept = f["keep"] != 0
    _but_the_row_of_zeros(f["grad"], f["want_grad"], 1e-6)
    assert np.abs(f["want_grad"]).max() > 0.1 or kept.sum(-1).max() == 1
    # exactly 0 wherever nothing is kept, the padded rows' tiles included
    assert not f["grad"][~kept].any()


def test_the_fused_cases_hold_what_they_are_for(fused):
    f = fused
    kept, probs = f["keep"] != 0, f["probs"]
    per_row = kept.sum(-1)
    assert (per_row >= 1).all()
    if per_row.max() == 1:                  # every row keeps one key: KL of
        np.testing.assert_allclose(f["value"], 0.0, atol=1e-4)  # two deltas
        assert not f["grad"].any()
    else:                                   # a kept pair with p = 0 exactly
        assert ((probs == 0) & kept)[:, _ZEROS].all(0).any()
        assert ((f["grad"] != 0) & kept)[:, _ZEROS].any()
        assert np.isfinite(f["value"]).all()


def test_nothing_flows_back_to_the_attention_through_the_fused_loss():
    q, k, v, keep, _ = _operands(128, seed=3)
    scores = _causal(jax.random.normal(jax.random.PRNGKey(5), (2, 128, 128)))
    _, lse = sa.sparse_attention(q, k, v, keep, kernel=False)
    grads = jax.jit(jax.grad(lambda q, k, lse: jnp.sum(sa.indexer_loss(
        q, k, lse, keep, scores, interpret=True)), argnums=(0, 1, 2)))(
            q, k, lse)
    assert not any(np.asarray(g).any() for g in grads)


def test_under_remat_the_kept_rows_spare_the_forward_kernel():
    """With `KL_ROWS_NAME` among the saved names the gradient holds the
    backward kernel once and the forward kernel once (not again in the
    recompute); without it the forward kernel runs twice."""
    q, k, v, keep, _ = _operands(128, seed=3)
    scores = _causal(jax.random.normal(jax.random.PRNGKey(5), (2, 128, 128)))
    _, lse = sa.sparse_attention(q, k, v, keep, kernel=False)

    def calls(*names):
        body = jax.checkpoint(
            lambda s: jnp.sum(sa.indexer_loss(q, k, lse, keep, s * 1.0,
                                              interpret=True)),
            policy=jax.checkpoint_policies.save_only_these_names(*names))
        text = str(jax.make_jaxpr(jax.grad(body))(scores))
        return (text.count("name=indexer_kl_fwd"),
                text.count("name=indexer_kl_bwd"))
    assert calls(sa.KL_ROWS_NAME) == (1, 1)
    assert calls() == (2, 1)


def test_the_indexers_loss_and_its_gradient():
    """KL(p ‖ softmax over the kept keys of the scores), a batch row, and
    the hand-written gradient against JAX's of the written-out form."""
    B, S, topk = 2, 33, 7
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    scores = _causal(jax.random.normal(ks[0], (B, S, S)))
    keep = sa.select(scores, topk)
    kept = keep != 0
    probs = jax.nn.softmax(jnp.where(
        kept, jax.random.normal(ks[1], (B, S, S)), -jnp.inf), axis=-1)
    probs = probs.at[:, 20, :].set(jax.nn.one_hot(   # a row with zeros in it
        jnp.argmax(keep[:, 20], axis=-1), S))

    def written_out(scores):
        return _written_out_kl(scores, probs, kept)
    got = sa.indexer_kl(scores, probs, keep)
    assert got.shape == (B,) and (np.asarray(got) > 0).all()
    np.testing.assert_allclose(got, written_out(scores), rtol=1e-5)
    weights = jnp.array([1.0, -2.0])
    grad = jax.jit(jax.grad(lambda s: jnp.sum(
        sa.indexer_kl(s, probs, keep) * weights)))(scores)
    want = jax.jit(jax.grad(
        lambda s: jnp.sum(written_out(s) * weights)))(scores)
    np.testing.assert_allclose(grad, want, atol=1e-6)
    assert not np.asarray(grad)[~np.asarray(kept)].any()


@pytest.mark.parametrize("S, topk, values", [
    (200, 33, "normal"), (200, 300, "normal"), (130, 9, "few"),
    (130, 9, "zeros"), (64, 5, "one")])
def test_the_selection_kernel_is_the_plain_selection(S, topk, values):
    """The kernel through the interpreter, chunks of 128 keys (two to a
    row, the length no multiple of one): `jax.lax.top_k`'s set, at random
    scores and where every threshold is tied."""
    key = jax.random.PRNGKey(S + topk)
    scores = {
        "normal": jax.random.normal(key, (2, S, S)),
        "few": jax.random.randint(key, (2, S, S), -2, 3).astype(jnp.float32),
        "zeros": jnp.where(jax.random.bernoulli(key, 0.5, (2, S, S)),
                           0.0, -0.0),
        "one": jnp.full((2, S, S), -1.5)}[values]
    scores = _causal(scores)
    old = sa.SELECT_CHUNK
    sa.SELECT_CHUNK = 128
    try:
        keep = jax.jit(functools.partial(sa.select, topk=topk,
                                         interpret=True))(scores)
    finally:
        sa.SELECT_CHUNK = old
    np.testing.assert_array_equal(keep, _top_k_mask(scores, topk))
    np.testing.assert_array_equal(keep, jax.jit(functools.partial(
        sa.select, topk=topk, kernel=False))(scores))


def test_the_indexers_score_kernels_against_the_plain_form(monkeypatch):
    """Forward and the three cotangents through the interpreter at 300
    tokens (two tiles of 256, the second mostly padding), the forward's
    products at the highest precision (`test_the_score_kernels_passes` has
    the module's three passes)."""
    monkeypatch.setattr(sa, "SCORE_PASSES", 6)
    B, S, J, E = 2, 300, 3, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    qi = jax.random.normal(ks[0], (B, S, J, E))
    ki = jax.random.normal(ks[1], (B, S, E))
    w = jax.random.normal(ks[2], (B, S, J))
    d = jax.random.normal(ks[3], (B, S, S))
    below = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def loss(**kw):
        def run(qi, ki, w):
            scores = sa.index_scores(qi, ki, w, **kw)
            return jnp.sum(jnp.where(below, scores * d, 0.0)), scores
        return jax.jit(jax.value_and_grad(run, argnums=(0, 1, 2),
                                          has_aux=True))
    (_, want), want_grads = loss(kernel=False)(qi, ki, w)
    (_, got), grads = loss(interpret=True)(qi, ki, w)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert (np.asarray(got)[:, ~np.asarray(below)] == sa.NEG_INF).all()
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(a, b, atol=2e-4)


def test_only_the_flash_calls_look_like_flash_calls_to_the_benchmark():
    """`chipbench/flops.py:flash_call_cost` tells a flash kernel by three or
    six float operands: the flash kernels with a kept set have them (the
    kept set is int8), every other kernel here has another number."""
    B, S, H, KV, D, J, E = 1, 128, 4, 2, 16, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k, v = (jax.random.normal(key, (B, S, KV, D)) for key in ks[1:3])
    qi = jax.random.normal(ks[3], (B, S, J, E))
    ki = jax.random.normal(ks[4], (B, S, E))
    w = jax.random.normal(ks[5], (B, S, J))

    def whole(q, k, v, qi, ki, w):
        scores = sa.index_scores(qi, ki, w, interpret=True)
        keep = sa.select(scores, 24, interpret=True)
        o, lse = sa.sparse_attention(q, k, v, keep, interpret=True)
        return jnp.sum(o) + jnp.sum(sa.indexer_loss(q, k, lse, keep, scores,
                                                    interpret=True))
    jaxpr = jax.make_jaxpr(jax.grad(whole, argnums=(0, 3)))(q, k, v, qi, ki, w)
    floats = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"] if "name" in eqn.params else \
                    eqn.params["name_and_src_info"].name
                floats[name] = sum(
                    v.aval.dtype in (jnp.float32, jnp.bfloat16)
                    for v in eqn.invars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    assert floats == {
        "indexer_scores_fwd": 4, "sparse_select": 1, "flash_sparse_fwd": 3,
        "indexer_kl_fwd": 5, "indexer_kl_bwd": 7, "flash_sparse_dq": 6,
        "flash_sparse_dkv": 6,
        "indexer_scores_dq": 4, "indexer_scores_dk": 4}


@pytest.mark.parametrize("passes, tol", [(6, 1e-5), (3, 1e-4), (1, 0.2)])
def test_the_score_kernels_passes(passes, tol, monkeypatch):
    """Forward scores in six, three and one bf16 pass against float32: three
    passes are float32 to 1e-4 of the scores' scale, one is not; and the
    backward in one pass (a bf16 model's) is the float32 gradient to bf16's
    own rounding."""
    B, S, J, E = 1, 128, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    qi = jax.random.normal(ks[0], (B, S, J, E))
    ki = jax.random.normal(ks[1], (B, S, E))
    w = jax.random.normal(ks[2], (B, S, J))
    below = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    want = sa.index_scores(qi, ki, w, kernel=False)
    monkeypatch.setattr(sa, "SCORE_PASSES", passes)
    got = sa.index_scores(qi, ki, w, interpret=True)
    err = float(jnp.max(jnp.abs(jnp.where(below, got - want, 0.0))))
    scale = float(jnp.max(jnp.abs(jnp.where(below, want, 0.0))))
    assert err <= tol * scale
    if passes == 3:
        assert err > 1e-7 * scale        # and they are not six
    if passes == 1:
        assert err > 1e-4 * scale

    def grads(**kw):
        return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.where(
            below, sa.index_scores(*a, **kw), 0.0) ** 2),
            argnums=(0, 1, 2)))(qi, ki, w)
    monkeypatch.setattr(sa, "SCORE_PASSES", 6)
    exact = grads(kernel=False)
    for a, b in zip(grads(interpret=True, backward_dtype=jnp.bfloat16),
                    exact):
        rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert 1e-5 < rel < 2e-2, rel
