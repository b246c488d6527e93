"""Tests for the parallelism stack: mesh, ring attention, pipeline, MoE,
flash attention, and the sharded GPT-2 train step — all on the virtual
8-device CPU mesh (conftest.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import gpt2
from ray_tpu.models.layers import MoEConfig, apply_moe, init_moe
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel.mesh import MeshConfig, create_mesh, balanced_factorization
from ray_tpu.parallel.pipeline import (
    gpipe,
    microbatch,
    stack_stage_params,
    unmicrobatch,
)
from ray_tpu.parallel.ring_attention import reference_attention, ring_attention
from ray_tpu.parallel.train_step import (
    default_optimizer,
    make_train_state,
    make_train_step,
)


def test_mesh_construction():
    mesh = create_mesh(MeshConfig(dp=2, sp=2, tp=2))
    assert dict(mesh.shape) == {"dp": 2, "pp": 1, "ep": 1, "sp": 2, "tp": 2}
    mesh = create_mesh(MeshConfig(dp=-1, tp=2))
    assert dict(mesh.shape)["dp"] == 4


def test_balanced_factorization():
    sizes = balanced_factorization(8, ["dp", "pp", "tp"])
    assert np.prod(list(sizes.values())) == 8
    assert all(v >= 2 for v in sizes.values())


def test_ring_attention_matches_reference():
    mesh = create_mesh(MeshConfig(dp=2, sp=2, tp=2))
    k = jax.random.PRNGKey(0)
    B, S, H, D = 4, 32, 4, 16
    q, kk, v = [jax.random.normal(kq, (B, S, H, D)) for kq in jax.random.split(k, 3)]
    spec = NamedSharding(mesh, P("dp", "sp", "tp", None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, kk, v))
    with jax.set_mesh(mesh):
        for causal in (True, False):
            out = ring_attention(qs, ks, vs, mesh, causal=causal)
            ref = reference_attention(q, kk, v, causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_grad():
    mesh = create_mesh(MeshConfig(sp=4, tp=2))
    k = jax.random.PRNGKey(1)
    B, S, H, D = 2, 32, 2, 8
    q, kk, v = [jax.random.normal(kq, (B, S, H, D)) for kq in jax.random.split(k, 3)]
    with jax.set_mesh(mesh):
        g = jax.jit(jax.grad(
            lambda q: jnp.sum(ring_attention(q, kk, v, mesh) ** 2)))(q)
    gref = jax.jit(jax.grad(
        lambda q: jnp.sum(reference_attention(q, kk, v) ** 2)))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref), atol=5e-5)


def test_flash_attention_interpret():
    k = jax.random.PRNGKey(2)
    B, S, H, D = 2, 256, 2, 32
    q, kk, v = [jax.random.normal(kq, (B, S, H, D)) for kq in jax.random.split(k, 3)]
    o = flash_attention(q, kk, v, causal=True, block_q=128, block_k=128,
                        interpret=True)
    ref = reference_attention(q, kk, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-5)
    g = jax.grad(
        lambda q: jnp.sum(flash_attention(
            q, kk, v, block_q=128, block_k=128, interpret=True) ** 2)
    )(q)
    gref = jax.grad(lambda q: jnp.sum(reference_attention(q, kk, v) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref), atol=5e-5)


def test_flash_attention_backward_all_grads():
    """The Pallas backward kernels (dq + dk/dv) against the reference VJP,
    causal and non-causal, including a seq length that doesn't divide the
    block size (exercises the padding/masking paths)."""
    key = jax.random.PRNGKey(3)
    for S, causal in [(256, True), (256, False), (192, True)]:
        B, H, D = 2, 2, 32
        q, kk, v = [jax.random.normal(kq, (B, S, H, D))
                    for kq in jax.random.split(jax.random.fold_in(key, S), 3)]

        def loss_flash(q, kk, v):
            o = flash_attention(q, kk, v, causal=causal,
                                block_q=128, block_k=128, interpret=True)
            return jnp.sum(o * jnp.cos(o))   # non-symmetric cotangents

        def loss_ref(q, kk, v):
            o = reference_attention(q, kk, v, causal=causal)
            return jnp.sum(o * jnp.cos(o))

        grads = jax.grad(loss_flash, argnums=(0, 1, 2))(q, kk, v)
        grefs = jax.grad(loss_ref, argnums=(0, 1, 2))(q, kk, v)
        for g, gref, name in zip(grads, grefs, "q k v".split()):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(gref), atol=1e-4,
                err_msg=f"d{name} mismatch (S={S}, causal={causal})")


def test_flash_attention_sharded_over_mesh():
    """apply_attention(impl="flash", mesh=...) runs the kernel under
    shard_map over the batch (dp) and heads (tp) axes — a Mosaic kernel on
    mesh-sharded operands does not lower otherwise. The product path
    (interpret=False), driven on CPU by forcing the TPU interpreter."""
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.models import layers as L

    mesh = create_mesh(MeshConfig(dp=2, tp=2), devices=jax.devices()[:4])
    p = L.init_attention(jax.random.PRNGKey(0), 64, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 128, 64))

    def loss(impl, mesh):
        return lambda p, x: jnp.sum(L.apply_attention(
            p, x, impl=impl, compute_dtype=jnp.float32, mesh=mesh) ** 2)

    ref, gref = jax.value_and_grad(loss("reference", None))(p, x)
    xs = jax.device_put(x, NamedSharding(mesh, P("dp")))
    with pltpu.force_tpu_interpret_mode():
        out, g = jax.jit(jax.value_and_grad(loss("flash", mesh)))(p, xs)
    np.testing.assert_allclose(float(out), float(ref), rtol=1e-5)
    for name in gref:
        np.testing.assert_allclose(np.asarray(g[name]),
                                   np.asarray(gref[name]), atol=1e-5)


def test_moe_matches_per_token_oracle():
    cfg = MoEConfig(n_experts=4, top_k=2)
    k = jax.random.PRNGKey(3)
    p = init_moe(k, 16, 32, cfg)
    x = jax.random.normal(k, (2, 8, 16))
    out, aux = apply_moe(p, x, cfg, compute_dtype=jnp.float32)
    probs = jax.nn.softmax(jnp.einsum("bsd,de->bse", x, p["wg"]), -1)
    gv, gi = jax.lax.top_k(probs, 2)
    gv = gv / gv.sum(-1, keepdims=True)
    ref = jnp.zeros_like(x)
    for b in range(2):
        for s in range(8):
            acc = sum(
                gv[b, s, j]
                * (jax.nn.gelu(x[b, s] @ p["w1"][gi[b, s, j]]) @ p["w2"][gi[b, s, j]])
                for j in range(2)
            )
            ref = ref.at[b, s].set(acc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    assert float(aux["load_balance"]) > 0
    assert int(aux["counts"].sum()) == 2 * 8 * 2      # dropless


def test_moe_ep_sharded():
    cfg = MoEConfig(n_experts=4, top_k=2)
    k = jax.random.PRNGKey(4)
    p = init_moe(k, 16, 32, cfg)
    x = jax.random.normal(k, (4, 8, 16))
    dense_out, _ = apply_moe(p, x, cfg, compute_dtype=jnp.float32)
    mesh = create_mesh(MeshConfig(dp=2, ep=4))
    xs = jax.device_put(x, NamedSharding(mesh, P("dp")))
    ps = {
        "wg": jax.device_put(p["wg"], NamedSharding(mesh, P())),
        "w1": jax.device_put(p["w1"], NamedSharding(mesh, P("ep"))),
        "w2": jax.device_put(p["w2"], NamedSharding(mesh, P("ep"))),
    }
    out, _ = jax.jit(lambda p, x: apply_moe(
        p, x, cfg, compute_dtype=jnp.float32, mesh=mesh))(ps, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense_out), atol=1e-5)


def test_gpipe_matches_sequential():
    mesh = create_mesh(MeshConfig(dp=2, pp=2, tp=2))
    k = jax.random.PRNGKey(5)
    Ws = [jax.random.normal(kq, (8, 8)) * 0.1 for kq in jax.random.split(k, 2)]
    stacked = stack_stage_params([{"w": Ws[0]}, {"w": Ws[1]}])
    x = jax.random.normal(k, (16, 8))

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    with jax.set_mesh(mesh):
        st = jax.device_put(stacked, NamedSharding(mesh, P("pp")))
        y = gpipe(stage_fn, st, microbatch(x, 4), mesh)
        ref = jnp.tanh(jnp.tanh(x @ Ws[0]) @ Ws[1])
        np.testing.assert_allclose(np.asarray(unmicrobatch(y)), np.asarray(ref), atol=1e-5)
        # gradients flow through the schedule
        g = jax.jit(jax.grad(lambda s: jnp.sum(
            gpipe(stage_fn, s, microbatch(x, 4), mesh) ** 2)))(st)
    assert jax.tree_util.tree_map(lambda a: a.shape, g)["w"] == (2, 8, 8)


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = gpt2.gpt2_tiny()
    params = gpt2.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, cfg.vocab_size)
    return cfg, params, tokens


def test_gpt2_forward_shapes(tiny_setup):
    cfg, params, tokens = tiny_setup
    logits, aux = gpt2.forward(params, tokens[:, :-1], cfg)
    assert logits.shape == (8, 32, cfg.vocab_size)
    assert jnp.isfinite(logits).all()


def test_gpt2_sharded_forward_matches_unsharded(tiny_setup):
    cfg, params, tokens = tiny_setup
    dense_logits, _ = gpt2.forward(params, tokens[:, :-1], cfg)
    mesh = create_mesh(MeshConfig(dp=2, sp=2, tp=2))
    specs = gpt2.partition_specs(cfg)
    with jax.set_mesh(mesh):
        sharded = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
        )
        logits, _ = jax.jit(
            lambda p, t: gpt2.forward(p, t, cfg, mesh)
        )(sharded, tokens[:, :-1])
    # ring attention (sp=2) vs dense attention: same math, but bf16 compute
    # with different accumulation order — tolerance sized for bf16.
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(dense_logits), atol=2e-2
    )


def test_gpt2_pipelined_matches_dense(tiny_setup):
    cfg, params, tokens = tiny_setup
    dense_logits, _ = gpt2.forward(params, tokens[:, :-1], cfg)
    mesh = create_mesh(MeshConfig(dp=2, pp=2, tp=2))
    with jax.set_mesh(mesh):
        logits, _ = jax.jit(
            lambda p, t: gpt2.forward_pipelined(p, t, cfg, mesh, n_microbatches=4)
        )(params, tokens[:, :-1])
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(dense_logits), atol=2e-2
    )


def test_gpt2_pipelined_pp_sp_joint_training(tiny_setup):
    """pp×sp composition (round-3 fix): the pipelined forward with sp>1
    uses ring_local attention inside ONE flat {pp, sp} manual region, and
    — the part that used to DuplicateSpecError — it differentiates.
    Forward AND gradients match the dense single-device oracle."""
    cfg, params, tokens = tiny_setup
    mesh = create_mesh(MeshConfig(dp=2, pp=2, sp=2))

    def oracle_loss(p, t):
        logits, _ = gpt2.forward(p, t, cfg)
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    def pp_sp_loss(p, t):
        logits, _ = gpt2.forward_pipelined(p, t, cfg, mesh,
                                           n_microbatches=4)
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    toks = tokens[:, :-1]
    with jax.set_mesh(mesh):
        loss, grads = jax.jit(jax.value_and_grad(pp_sp_loss))(params, toks)
    oracle, ograds = jax.value_and_grad(oracle_loss)(params, toks)
    np.testing.assert_allclose(float(loss), float(oracle), atol=2e-3)
    flat = jax.tree_util.tree_leaves(grads)
    oflat = jax.tree_util.tree_leaves(ograds)
    for g, og in zip(flat, oflat):
        np.testing.assert_allclose(np.asarray(g), np.asarray(og),
                                   atol=5e-2, rtol=5e-2)


def test_gpt2_moe_forward():
    cfg = gpt2.GPT2Config(
        vocab_size=128,
        max_seq=64,
        n_layer=2,
        n_head=2,
        d_model=32,
        remat=False,
        moe=MoEConfig(n_experts=4, top_k=2),
    )
    params = gpt2.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, cfg.vocab_size)
    loss, metrics = gpt2.loss_fn(params, {"tokens": tokens}, cfg)
    assert jnp.isfinite(loss)
    assert float(metrics["aux_loss"]) > 0


def test_train_step_loss_decreases(tiny_setup):
    cfg, _, tokens = tiny_setup
    mesh = create_mesh(MeshConfig(dp=2, sp=2, tp=2))
    opt = default_optimizer(1e-2, warmup_steps=1, total_steps=50)
    specs = gpt2.partition_specs(cfg)
    with jax.set_mesh(mesh):
        state = make_train_state(
            lambda rng: gpt2.init(rng, cfg), jax.random.PRNGKey(0), opt, mesh, specs
        )
        step = make_train_step(
            lambda p, b: gpt2.loss_fn(p, b, cfg, mesh), opt, mesh
        )
        batch = {"tokens": tokens}
        losses = []
        for _ in range(5):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert int(state.step) == 5


def test_hybrid_mesh_slice_major_dp():
    """Multi-slice hybrid mesh: dp spans the (simulated) slices, inner axes
    stay within a slice; a dp-psum executes correctly over the layout."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import MeshConfig, create_hybrid_mesh

    devices = jax.devices()[:8]
    # simulate 2 slices of 4 chips each
    assignments = [0] * 4 + [1] * 4
    mesh = create_hybrid_mesh(MeshConfig(dp=1, tp=4), dcn_dp=2,
                              devices=devices,
                              slice_assignments=assignments)
    assert dict(mesh.shape) == {"dp": 2, "pp": 1, "ep": 1, "sp": 1, "tp": 4}
    # dp must be slice-major: each dp row holds exactly one slice's devices
    dev_array = np.asarray(mesh.devices)
    row0 = set(d.id for d in dev_array[0].ravel())
    assert row0 == {d.id for d in devices[:4]}, "dp row 0 != slice 0"

    @jax.jit
    def summed(x):
        return jax.shard_map(
            lambda s: jax.lax.psum(s, "dp"),
            mesh=mesh, in_specs=P("dp"), out_specs=P(),
        )(x)

    x = jnp.arange(8.0)
    out = summed(x)
    assert np.allclose(out, np.arange(8.0).reshape(2, 4).sum(0))


def test_hybrid_mesh_rejects_uneven_slices():
    import jax
    import pytest as _pytest

    from ray_tpu.parallel.mesh import create_hybrid_mesh

    devices = jax.devices()[:7]
    with _pytest.raises(ValueError, match="uneven"):
        create_hybrid_mesh(devices=devices,
                           slice_assignments=[0, 0, 0, 0, 1, 1, 1])
