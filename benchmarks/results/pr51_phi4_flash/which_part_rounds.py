"""Where the compared gradients' error comes from, at the tiny size on the CPU (PR 51, after the review): the
8-layer tiny model with bf16 operands, then with single parts computing in float32, against the float32 reference.
From the repo root: JAX_PLATFORMS=cpu PYTHONPATH=. python3 benchmarks/results/pr51_phi4_flash/which_part_rounds.py"""
import dataclasses, functools, json, sys
import jax, jax.numpy as jnp
from chipbench import compare
from chipbench.accounting import phi4_flash as accounting
from chipbench.references import phi4_flash as reference
from ray_tpu.models import layers as L
from ray_tpu.models import phi4_flash as model
sys.path.insert(0, ".")
import tests.test_phi4_flash as T

cfg32 = dataclasses.replace(model.phi4_flash_tiny(), dtype=jnp.float32)
params = jax.jit(lambda: T._noised(model.init(jax.random.PRNGKey(0), cfg32)))()
tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 41), 0, 256)
with jax.default_matmul_precision("highest"):
    want, want_grads = jax.jit(jax.value_and_grad(lambda p: reference.loss(p, tokens, T.CONFIG)))(params)
want_grads = accounting.pick(want_grads)

def errors(cfg):
    def system(leaves):
        return model.loss_fn(accounting.put(params, leaves), {"tokens": tokens}, cfg)[0]
    got, grads = jax.jit(jax.value_and_grad(system))(accounting.pick(params))
    return {"loss": abs(float(got) - float(want)) / float(want),
            **{k: round(float(compare.rel_l2(grads[k], want_grads[k])), 5) for k in grads}}

bf16 = dataclasses.replace(cfg32, dtype=jnp.bfloat16)
rows = {"stated (bf16 operands)": errors(bf16)}
head = L.head_logits
def head_f32(*a, **k):
    return head(*a, **dict(k, compute_dtype=jnp.float32))
L.head_logits = head_f32
rows["head in float32"] = errors(bf16)
L.head_logits = head
for name, fn in (("feed-forwards in float32", "apply_gated_mlp"), ("mamba mixers in float32", "apply_mamba1"),
                 ("attention in float32", "apply_diff_attention"), ("gmu in float32", "apply_gmu")):
    old = getattr(L, fn)
    setattr(L, fn, functools.wraps(old)(lambda *a, _old=old, **k: _old(*a, **dict(k, compute_dtype=jnp.float32))))
    rows[name] = errors(bf16)
    setattr(L, fn, old)
rows["all float32"] = errors(cfg32)
for name, r in rows.items():
    g = [v for k, v in r.items() if k != "loss"]
    print(f"{name:28s} loss {r['loss']:.2e}  grads min {min(g):.5f} median {sorted(g)[len(g)//2]:.5f} max {max(g):.5f}")
json.dump(rows, open("benchmarks/results/pr51_phi4_flash/which_part_rounds.json", "w"), indent=1)
