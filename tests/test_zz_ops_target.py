"""The one rule for "kernel or plain form" (`ops/target.py`): what `where`
answers, and that each op with a kernel — flash through `resolve_attention`,
the grouped product, the scan, the mixer's two stages, the gated delta
rule, Mamba-1's selective scan — takes the kernel
where `where` says TPU and its plain form where it says CPU, at shapes its
tiles divide. Tracing only: nothing runs, nothing compiles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import layers as L
from ray_tpu.ops import (
    gated_delta,
    grouped_matmul,
    mamba_stages,
    selective_scan,
    ssd,
    target,
)
from tests.test_zz_tp_overlap import _walk

F32 = jnp.float32


def _shapes(*shapes, dtype=F32):
    return [jax.ShapeDtypeStruct(s, dtype) for s in shapes]


def _flash(x):
    # what a model does with its config's "auto"
    return L.apply_attention(
        L.init_attention(jax.random.PRNGKey(0), 64, 2, F32), x,
        impl=L.resolve_attention("auto"), compute_dtype=F32)


def _scan(x, dt, A, B, C, D):
    return ssd.ssd(x, dt, A, B, C, D, chunk=128)


# (the op as a function of arrays, their shapes, a prefix of its kernels'
# names): shapes every tile of the op divides
OPS = {
    "flash": (_flash, _shapes((1, 256, 64)), "flash_"),
    "grouped_matmul": (
        lambda lhs, rhs, sizes: grouped_matmul.grouped_matmul(lhs, rhs, sizes),
        _shapes((256, 128), (2, 128, 128)) + _shapes((2,), dtype=jnp.int32),
        ""),
    "ssd": (_scan, _shapes((1, 256, 8, 64), (1, 256, 8), (8,),
                           (1, 256, 1, 128), (1, 256, 1, 128), (8,)), "ssd_"),
    "conv_silu": (mamba_stages.conv_silu,
                  _shapes((1, 2048, 256), (4, 256), (256,)), "mamba_conv_"),
    "gate_norm": (
        lambda y, src, scale: mamba_stages.gate_norm(y, src, scale, groups=8,
                                                     eps=1e-5),
        _shapes((1, 1024, 64), (1, 1024, 96), (64,)), "mamba_gate_"),
    # two value heads on a key head of 128, the conv's [q | k | v] whole
    "gated_delta": (
        lambda qkv, g, beta: gated_delta.gated_delta_packed(
            qkv, g, beta, key_heads=1, k_dim=128, normalize=1e-6),
        _shapes((1, 512, 512), (1, 512, 2), (1, 512, 2)), "delta_"),
    # one lane tile of channels, 16 states, a token block
    "selective_scan": (
        selective_scan.selective_scan,
        _shapes((1, 64, 128), (1, 64, 128), (128, 16), (1, 64, 16),
                (1, 64, 16), (128,), (128,)), "sscan_"),
}


def _kernels(op):
    fn, shapes, prefix = OPS[op]
    jaxpr = jax.make_jaxpr(lambda *a: jnp.sum(fn(*a)))(*shapes).jaxpr
    return {eqn.params["name"] for eqn, *_ in _walk(jaxpr)
            if eqn.primitive.name == "pallas_call"
            and str(eqn.params["name"]).startswith(prefix)}


def _where(case):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("dp",))
    return {"no_mesh": target.where(), "a_mesh": target.where(mesh),
            "interpret": target.where(mesh, interpret=True)}[case]


CASES = [("where", "no_mesh", ("cpu", 1)), ("where", "a_mesh", ("cpu", 2)),
         ("where", "interpret", ("tpu", 1))]
CASES += [(op, platform, platform == "tpu")
          for op in OPS for platform in ("tpu", "cpu")]


@pytest.mark.parametrize("what, case, want", CASES,
                         ids=[f"{w}-{c}" for w, c, _ in CASES])
def test_one_rule_decides_kernel_or_plain_form(what, case, want, runs_on):
    if what == "where":
        assert _where(case) == want
        return
    runs_on(case)
    assert bool(_kernels(what)) is want, _kernels(what)
