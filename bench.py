"""Headline benchmark: GPT-2-small training throughput on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

vs_baseline is measured MFU / 0.40, the BASELINE.md north-star target
(GPT-2 ≥40% MFU; see BASELINE.md "Targets for the TPU-native build").
Runs GPT-2-small @ seq 1024 in bf16 with the Pallas flash-attention kernel,
calling make_train_step directly in this process. It measures the chip or
it fails: no TPU, or a TPU whose peak is not in the table below, is an
error and prints no metric.
"""
import dataclasses
import json
import sys
import time

import jax

# bf16 peak FLOP/s per chip by device kind (Google Cloud TPU documentation).
_PEAK_FLOPS = {
    "v5 lite": 197e12,  # v5e
    "v5e": 197e12,
    "v4": 275e12,
    "v5p": 459e12,
    "v6 lite": 918e12,  # trillium
}


def _peak_flops(device) -> float:
    kind = device.device_kind.lower()
    for key, val in _PEAK_FLOPS.items():
        if key in kind:
            return val
    raise SystemExit(
        f"bench.py: no peak FLOP/s on file for device kind "
        f"{device.device_kind!r}; add it to _PEAK_FLOPS with its source")


def main():
    from ray_tpu.models import gpt2
    from ray_tpu.parallel.compile_watch import configure_compile_cache
    from ray_tpu.parallel.train_step import (
        default_optimizer,
        make_train_state,
        make_train_step,
    )

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"bench.py: JAX found no TPU chip (backend is "
            f"{device.platform!r}); this benchmark measures the chip only")
    peak = _peak_flops(device)
    configure_compile_cache()

    # remat off: with the lean LN/MLP custom VJPs (models/layers.py)
    # batch 16 fits one 16 GiB chip without checkpointing.
    cfg = dataclasses.replace(gpt2.gpt2_small(), remat=False)
    batch, seq, timed_steps = 16, 1024, 20

    opt = default_optimizer(1e-4, warmup_steps=10, total_steps=1000)
    state = make_train_state(lambda rng: gpt2.init(rng, cfg), jax.random.PRNGKey(0), opt)
    step = make_train_step(lambda p, b: gpt2.loss_fn(p, b, cfg), opt)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size)
    batch_data = {"tokens": tokens}

    # Warmup (compile) then timed steps. States chain through donation, so
    # the last step's outputs being ready implies every step ran.
    for _ in range(2):
        state, metrics = step(state, batch_data)
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for _ in range(timed_steps):
        state, metrics = step(state, batch_data)
    jax.block_until_ready(metrics)
    dt = time.perf_counter() - t0

    steps_per_sec = timed_steps / dt
    tokens_per_sec = steps_per_sec * batch * seq
    # fwd+bwd FLOPs/token: 6*N_params + attention (6 * L * S * d_model,
    # causal-halved QK^T+PV fwd+bwd) — the PaLM-appendix accounting.
    flops_per_token = 6 * cfg.n_params + 6 * cfg.n_layer * seq * cfg.d_model
    mfu = tokens_per_sec * flops_per_token / peak

    print(
        json.dumps(
            {
                "metric": "gpt2_small_train_tokens_per_sec_per_chip",
                "value": round(tokens_per_sec, 1),
                "unit": "tokens/s/chip",
                "vs_baseline": round(mfu / 0.40, 4),
                "extra": {
                    "mfu": round(mfu, 4),
                    "steps_per_sec": round(steps_per_sec, 3),
                    "loss": float(metrics["loss"]),
                    "batch": batch,
                    "seq": seq,
                    "n_params": cfg.n_params,
                    "platform": device.platform,
                    "device_kind": device.device_kind,
                    "device_count": len(jax.devices()),
                },
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
