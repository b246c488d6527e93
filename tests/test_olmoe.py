"""OLMoE (models/olmoe.py) and the dropless routed layer (layers.apply_moe)
on the CPU, at the tiny preset: against the plain float32 reference the
benchmark holds it to (chipbench/references/olmoe.py), against per-token
oracles written here, and on virtual meshes against one device."""
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare
from chipbench.accounting import olmoe as accounting
from chipbench.references import olmoe as reference
from ray_tpu.models import layers as L
from ray_tpu.models import olmoe
from ray_tpu.ops import grouped_matmul
from ray_tpu.parallel import sharding as sh
from ray_tpu.parallel.mesh import MeshConfig, create_mesh
from ray_tpu.parallel.train_step import (
    default_optimizer,
    make_train_state,
    make_train_step,
)
from tests import test_model_checks as checks

TINY = dataclasses.replace(olmoe.olmoe_tiny(), attention="reference")
# what the reference reads from the configuration file
FILED = {"num_attention_heads": TINY.n_head, "rms_norm_eps": 1e-5,
         "rope_theta": 10000.0, "num_experts_per_tok": TINY.top_k,
         "router_aux_loss_coef": 0.01, "router_z_loss_coef": 0.001}


def _params(cfg, seed=0):
    """Fresh parameters with every norm's scale moved off 1."""
    return checks.moved_off(
        olmoe.init(jax.random.PRNGKey(seed), cfg), seed + 1,
        lambda _, a: 0.1 * (a.ndim <= 3 and float(a.reshape(-1)[0]) == 1.0))


def _tokens(cfg, **kw):
    return checks.token_ids(cfg.vocab_size, **kw)


def test_presets_count_the_published_parameters():
    assert olmoe.olmoe_1b_7b().n_params == 6_919_161_856
    assert olmoe.olmoe_1b_7b_1l().n_params == 625_616_896
    leaves = jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: olmoe.init(jax.random.PRNGKey(0), TINY)))
    assert sum(math.prod(a.shape) for a in leaves) == TINY.n_params


def test_loss_and_every_gradient_match_the_reference_in_float32():
    """Same arithmetic, two programs: float32 rounding alone separates
    them (measured 7e-7), so 1e-5."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    params, tokens = _params(cfg), _tokens(cfg)
    checks.against_reference(
        lambda p: olmoe.loss_fn(p, {"tokens": tokens}, cfg)[0],
        lambda p: reference.loss(p, tokens, FILED), params,
        loss_rtol=1e-6, grad_tol=1e-5)


def test_bf16_is_within_the_benchmarks_bounds():
    """As a cell runs it: bf16 compute against the float32 reference, on
    the leaves `accounting.pick` names, inside `compare`'s bounds (loss
    3e-4, gradients 8e-2). bf16 rounds to 2^-9 a value, which the GPT-2
    cells see as ≤ 0.037 on a gradient; the routed layer adds the tokens
    whose last chosen expert changes with that rounding (below). e4m3
    would be sixteen times coarser and outside both bounds."""
    params, tokens = _params(TINY), _tokens(TINY)
    check = compare.compare(
        lambda p, t: olmoe.loss_fn(p, {"tokens": t}, TINY)[0],
        lambda p, t: reference.loss(p, t, FILED), params, tokens,
        jax.devices()[0], pick=accounting.pick, put=accounting.put)
    assert set(check["errors"]) == {"loss", "grad_head", "grad_wq",
                                    "grad_wv", "grad_wg", "grad_w_gate",
                                    "grad_w_down"}
    assert check["within"], check["errors"]


# ------------------------------------------------------------ routed layer

def _skewed(cfg, d_model, d_ff, gated, seed=3):
    """A router under which expert 0 is the first choice of about half the
    tokens (its logit is 8 × the token's first coordinate)."""
    params = L.init_moe(jax.random.PRNGKey(seed), d_model, d_ff, cfg,
                        gated=gated)
    params["wg"] = params["wg"].at[:, 0].set(0.0).at[0, 0].set(8.0)
    return params


def _per_token_oracle(params, x, cfg):
    """Every token by itself, in float64 numpy: its top-k experts by the
    router's probability, each expert's output, weighted and summed."""
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    x = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    out = np.zeros_like(x)
    chosen = []
    for t, h in enumerate(x):
        logits = h @ p["wg"]
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs, kind="stable")[:cfg.top_k]
        gates = probs[top] / (probs[top].sum() if cfg.norm_topk_prob else 1)
        for e, g in zip(top, gates):
            if "w_gate" in p:
                a = h @ p["w_gate"][e]
                hidden = a / (1 + np.exp(-a)) * (h @ p["w_up"][e])
                out[t] += g * (hidden @ p["w_down"][e])
            else:
                a = h @ p["w1"][e]
                hidden = 0.5 * a * (1 + np.tanh(
                    math.sqrt(2 / math.pi) * (a + 0.044715 * a ** 3)))
                out[t] += g * (hidden @ p["w2"][e])
        chosen.append(top)
    return out, np.array(chosen)


@pytest.mark.parametrize("gated, norm", [(True, False), (False, True)],
                         ids=["olmoe_form", "gpt2_form"])
def test_routed_layer_is_dropless_under_a_skewed_router(gated, norm):
    """One expert takes half the tokens — eight times its even share, far
    past any capacity — and every assignment's output is still there."""
    cfg = L.MoEConfig(n_experts=16, top_k=2, norm_topk_prob=norm)
    params = _skewed(cfg, 32, 24, gated)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 64, 32))
    out, stats = jax.jit(lambda p, x: L.apply_moe(
        p, x, cfg, compute_dtype=jnp.float32))(params, x)
    want, chosen = _per_token_oracle(params, x, cfg)
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 32), want,
                               atol=2e-6)
    counts = np.asarray(stats["counts"])
    assert counts.sum() == 4 * 64 * 2
    np.testing.assert_array_equal(counts,
                                  np.bincount(chosen.reshape(-1), minlength=16))
    assert counts[0] >= 100 and counts[0] >= 3 * np.sort(counts)[-2]
    # the load balance, a sequence at a time, from the oracle's routing
    probs = np.asarray(jax.nn.softmax(x @ params["wg"], axis=-1))
    balance = np.mean([
        16 * np.sum(np.bincount(chosen.reshape(4, 64, 2)[b].reshape(-1),
                                minlength=16) / 128 * probs[b].mean(0))
        for b in range(4)])
    assert float(stats["load_balance"]) == pytest.approx(balance, rel=1e-5)


def test_model_reports_its_routing_and_drops_nothing():
    params = _params(TINY)
    moe = params["blocks"]["moe"]
    params["blocks"]["moe"] = dict(
        moe, wg=moe["wg"].at[:, :, 0].set(0.0).at[:, 0, 0].set(8.0))
    tokens = _tokens(TINY)
    _, metrics = jax.jit(lambda p: olmoe.loss_fn(
        p, {"tokens": tokens}, TINY))(params)
    assert int(metrics["moe_assignments"]) == 2 * 64 * TINY.top_k * 2
    assert int(metrics["moe_dropped"]) == 0
    # 8 experts, 2 a token: the even share is a quarter of the tokens,
    # the skewed expert gets about half of them
    assert float(metrics["moe_load_max_over_mean"]) >= 1.7
    for name in ("loss", "aux_loss", "z_loss", "total_loss"):
        assert np.isfinite(float(metrics[name])), name
    assert float(metrics["total_loss"]) == pytest.approx(
        float(metrics["loss"]) + 0.01 * float(metrics["aux_loss"])
        + 0.001 * float(metrics["z_loss"]), rel=1e-6)


def test_swaps_of_the_last_chosen_expert_under_bf16_and_what_they_cost():
    """The cell's known risk, measured at OLMoE's routing shape (64 experts,
    8 a token, router weights 0.02) and small widths. Top-k is a
    discontinuous function of the router's input: ONE bf16 rounding of that
    input moves a logit by ~2^-9 of its size, and the tokens whose 8th and
    9th probabilities lie closer than that choose another 8th expert than
    the float32 reference does. Such a token's row is missing from one
    expert's gradient and extra in another's. Measured here: 1.3 % of the
    tokens swap, and an expert's gate-matrix gradient then differs by 5.0 %
    (relative L2; 0.25 % with the swapped tokens left out of both sides:
    the rounding itself). The error goes with the square root of the rate.
    On the v5e at the published widths (PERF.md §6, PR 29) a bf16 residual
    stream swapped 4.7 % of the tokens and the compared gradients read
    4.1–7.9 %, all of it swaps (1.2 % under the reference's routing): why
    `models/olmoe.py` carries the stream in float32."""
    cfg = L.MoEConfig(n_experts=64, top_k=8, norm_topk_prob=False)
    params = L.init_moe(jax.random.PRNGKey(5), 256, 64, cfg, gated=True)
    exact = jax.random.normal(jax.random.PRNGKey(6), (1, 4096, 256))
    rounded = exact.astype(jnp.bfloat16).astype(jnp.float32)

    def chosen(x):
        probs = jax.nn.softmax(x[0] @ params["wg"], axis=-1)
        return np.sort(np.asarray(jax.lax.top_k(probs, 8)[1]), axis=-1)

    swapped = np.any(chosen(exact) != chosen(rounded), axis=-1)
    rate = swapped.mean()
    assert 0.005 <= rate <= 0.03, rate

    target = jax.random.normal(jax.random.PRNGKey(7), exact.shape)
    steady = jnp.asarray(~swapped, jnp.float32)

    def expert_grads(x):
        """An expert's gate-matrix gradient of Σ out · target over every
        token, and over the steady ones: one forward, a backward each. Op
        by op on purpose: at these row counts the CPU's grouped products are
        dense by group, and one compiled program holds 7–9 GB of them at
        once where this holds 3.4 (twice as fast alone, slower in the whole
        suite: PERF.md §6, PR 55)."""
        out, back = jax.vjp(lambda w_gate: L.apply_moe(
            dict(params, w_gate=w_gate), x, cfg,
            compute_dtype=jnp.float32)[0], params["w_gate"])
        return [back(target * keep[None, :, None])[0]
                for keep in (jnp.ones(4096), steady)]

    (every_r, steady_r), (every_e, steady_e) = map(expert_grads,
                                                   (rounded, exact))
    with_swaps = compare.rel_l2(every_r, every_e)
    rounding_alone = compare.rel_l2(steady_r, steady_e)
    print(f"swap rate {rate:.4f}, gradient error {with_swaps:.4f} "
          f"(rounding alone {rounding_alone:.4f})")
    assert rounding_alone <= 0.01 < with_swaps <= compare.GRAD_RTOL


def test_moe_plan_counts_what_a_real_call_does(runs_on):
    cfg = L.MoEConfig(n_experts=8, top_k=2, norm_topk_prob=False)
    params = L.init_moe(jax.random.PRNGKey(8), 64, 32, cfg, gated=True)
    x = jnp.zeros((2, 128, 64))
    jaxpr = jax.make_jaxpr(lambda p, x: L.apply_moe(p, x, cfg)[0])(params, x)

    def grouped(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "ragged_dot_general":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from grouped(sub)

    products = list(grouped(jaxpr.jaxpr))
    plan = L.moe_plan(256, 64, 32, cfg, gated=True)
    assert len(products) == 3
    assert {e.invars[0].aval.shape[0] for e in products} == {plan["rows"]}
    assert plan["rows"] == 512
    assert sum(2 * e.invars[0].aval.shape[0] * math.prod(
        e.invars[1].aval.shape[1:]) for e in products) == plan["flops_needed"]
    # at the cell's shapes: 8,192 tokens through 8 of 64 experts
    olmoe_moe = olmoe.olmoe_1b_7b().moe
    plan = L.moe_plan(8192, 2048, 1024, olmoe_moe, gated=True)
    assert plan["rows"] == 65_536
    assert plan["flops_needed"] == 3 * 2 * 65_536 * 2048 * 1024
    assert plan["flops_needed"] * 3 == accounting.grouped_matmul_cost(
        {"num_experts_per_tok": 8, "hidden_size": 2048,
         "intermediate_size": 1024, "num_experts": 64}, 8192)[0]
    # the kernel's row tile is `tile_plan`'s: 65,536 / 512 = 128 row tiles
    # and at most 63 more that two experts share
    tile = grouped_matmul.tile_plan(65_536, 2048, 1024, jnp.bfloat16)[0]
    assert tile == grouped_matmul.row_tile(65_536) == grouped_matmul.ROW_TILE
    # and on a TPU the cell's layer takes the kernels, the layer above not
    runs_on("tpu")
    assert grouped_matmul.kernel_width(65_536, 2048, 1024,
                                       jnp.bfloat16) == 1024
    assert grouped_matmul.kernel_width(512, 64, 32, jnp.bfloat16) is None
    assert 65_536 % tile == 0
    tiles = 65_536 // tile
    assert plan["flops_issued_max"] / plan["flops_needed"] == \
        pytest.approx((tiles + 63) / tiles)
    # which no routing passes: the worst one ends every expert inside a tile
    worst = [1024 - 24] + [1024] * 62 + [1024 + 24]
    assert grouped_matmul.issued_ratio(worst, tile) == pytest.approx(
        (tiles + 63) / tiles)
    assert grouped_matmul.issued_ratio([1024] * 64, tile) == 1.0
    assert plan["dispatch_bytes"] == 2 * 65_536 * 2048 * 2
    assert plan["combine_bytes"] == (65_536 + 8192) * 2048 * 2
    # four devices sharing the experts: each a quarter of the FLOPs
    shared = L.moe_plan(8192, 2048, 1024, olmoe_moe, gated=True, ep=4)
    assert shared["flops_needed"] * 4 == plan["flops_needed"]
    assert shared["flops_issued_max"] == \
        (tiles + 15) * tile * 6 * 2048 * 1024


@pytest.mark.parametrize("gated, norm", [(True, False), (False, True)],
                         ids=["olmoe_form", "gpt2_form"])
def test_local_experts_on_the_pallas_kernels_against_the_oracle(
        gated, norm, monkeypatch, runs_on):
    """What a TPU runs, here in the Pallas interpreter: `_local_experts`
    where `target.where` says TPU, at shapes the tiles divide, as two devices
    that hold four of the eight experts each (`first` 0 and 4). Each
    device's rows for the other's experts lie behind its own and come out
    zero; the two parts sum to every token's full output."""
    calls = []

    def interpreted(lhs, rhs, sizes, mesh=None):
        calls.append((lhs.shape, rhs.shape, sizes.shape))
        return kernel(lhs, rhs, sizes, interpret=True)

    kernel = grouped_matmul.grouped_matmul
    monkeypatch.setattr(grouped_matmul, "grouped_matmul", interpreted)
    cfg = L.MoEConfig(n_experts=8, top_k=2, norm_topk_prob=norm)
    params = _skewed(cfg, 128, 128, gated)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 128))
    probs = jax.nn.softmax(x @ params["wg"], axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, 2)
    if norm:
        gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)
    experts = {k: v for k, v in params.items() if k != "wg"}
    assert grouped_matmul.kernel_width(256, 128, 128, jnp.float32) is None
    runs_on("tpu")
    assert grouped_matmul.kernel_width(256, 128, 128, jnp.float32) == 128
    parts = [
        L.apply_moe(
            {k: v[first:first + 4] for k, v in experts.items()}, x,
            dataclasses.replace(cfg, held=4, first=first),
            compute_dtype=jnp.float32,
            routing=(gate_vals, gate_idx, {}))[0]
        for first in (0, 4)]
    # every product of both devices went through the kernel, with all
    # eight groups' sizes and four matrices
    assert len(calls) == 2 * (3 if gated else 2)
    assert {(c[1][0], c[2]) for c in calls} == {(4, (8,))}
    want, chosen = _per_token_oracle(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(parts[0] + parts[1]).reshape(-1, 128), want, atol=1e-5)
    # a token none of whose experts lives on the first device gets nothing
    # from it: exactly zero
    elsewhere = (chosen >= 4).all(axis=-1)
    assert elsewhere.any() and not elsewhere.all()
    assert not np.asarray(parts[0]).reshape(-1, 128)[elsewhere].any()


# ------------------------------------------------------- the shared parts

def test_rope_against_the_rotation_written_out():
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (2, 12, 3, 16)),
                   np.float64)
    want = np.zeros_like(x)
    for pos in range(12):
        for i in range(8):
            angle = pos * 10000.0 ** (-i / 8)
            a, b = x[:, pos, :, i], x[:, pos, :, i + 8]
            want[:, pos, :, i] = a * math.cos(angle) - b * math.sin(angle)
            want[:, pos, :, i + 8] = b * math.cos(angle) + a * math.sin(angle)
    np.testing.assert_allclose(np.asarray(L.rope(jnp.asarray(x, jnp.float32))),
                               want, atol=1e-5)


def test_qk_norm_is_over_the_whole_projection():
    """Not a head at a time: all heads' outputs share one mean square."""
    cfg = TINY
    attn = {"q_norm": 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(10),
                                                  (4, 16)),
            "k_norm": jnp.ones((4, 16))}
    q = jax.random.normal(jax.random.PRNGKey(11), (2, 8, 4, 16))
    q = q * jnp.arange(1, 5)[None, None, :, None]      # heads differ in size
    got, _ = olmoe._qk_norm_and_rotate(attn, cfg)(q, q)
    flat = np.asarray(q, np.float64).reshape(2, 8, 64)
    normed = flat / np.sqrt(np.mean(flat ** 2, axis=-1, keepdims=True)
                            + cfg.rms_norm_eps)
    normed = normed * np.asarray(attn["q_norm"], np.float64).reshape(-1)
    want = L.rope(jnp.asarray(normed.reshape(2, 8, 4, 16), jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_rms_norm_lean_vjp_is_the_plain_ones():
    x = jax.random.normal(jax.random.PRNGKey(12), (3, 5, 32))
    scale = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(13), (32,))
    w = jax.random.normal(jax.random.PRNGKey(14), x.shape)

    def plain(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + 1e-5) * scale

    got = jax.grad(lambda x, s: jnp.sum(L.rms_norm(x, s, 1e-5) * w),
                   argnums=(0, 1))(x, scale)
    want = jax.grad(lambda x, s: jnp.sum(plain(x, s) * w),
                    argnums=(0, 1))(x, scale)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-5)


# ------------------------------------------------------------------ meshes

@functools.cache
def _on_one_device():
    """What the three meshes are held to, made once a process."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    params, tokens = _params(cfg), _tokens(cfg, batch=4)
    return cfg, params, tokens, *checks.loss_and_grads(
        lambda p: olmoe.loss_fn(p, {"tokens": tokens}, cfg)[0], params)


@pytest.mark.parametrize("axes", [{"dp": 2}, {"dp": 1, "ep": 2},
                                  {"dp": 2, "ep": 2}],
                         ids=["dp2", "ep2", "dp2_ep2"])
def test_model_on_a_mesh_agrees_with_one_device(axes):
    """Batch over `dp`, experts over `ep` (each device computes its
    experts' part of its tokens' outputs, the parts are summed): loss and
    every gradient as on one device, to float32 rounding."""
    cfg, params, tokens, want, want_grads = _on_one_device()
    n = math.prod(axes.values())
    mesh = create_mesh(MeshConfig(**axes), devices=jax.devices()[:n])
    sharded = sh.tree_shard(params, mesh, olmoe.partition_specs(cfg))
    with jax.set_mesh(mesh):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: olmoe.loss_fn(p, {"tokens": tokens}, cfg, mesh)[0]))(
                sharded)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    checks.assert_close(grads, want_grads, 1e-5)


def test_trains_through_the_normal_path():
    """`make_train_state` / `make_train_step` on a dp=2 mesh, as
    `JaxTrainer` workers call them: the loss falls on a fixed batch and the
    routing metrics come back with it."""
    mesh = create_mesh(MeshConfig(dp=2), devices=jax.devices()[:2])
    opt = default_optimizer(1e-2, warmup_steps=1, total_steps=50)
    with jax.set_mesh(mesh):
        state = make_train_state(lambda rng: olmoe.init(rng, TINY),
                                 jax.random.PRNGKey(0), opt, mesh,
                                 olmoe.partition_specs(TINY))
        step = make_train_step(
            lambda p, b: olmoe.loss_fn(p, b, TINY, mesh), opt, mesh)
        batch = {"tokens": _tokens(TINY, batch=4, seq=32)}
        losses = []
        for _ in range(6):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert int(metrics["moe_dropped"]) == 0
    assert int(metrics["moe_assignments"]) == 4 * 32 * 2 * 2
