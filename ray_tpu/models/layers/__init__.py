"""Transformer building blocks, pure-JAX pytree style.

Every layer is a (init_fn, apply_fn) pair over plain dict pytrees; sharding
comes from logical-axis annotations resolved by ray_tpu.parallel.sharding.
Compute is bf16 by default with f32 params/accumulators (MXU-native mix).

Six modules, lowest first, a module importing only those before it: `core`
(what every layer shares), `attention`, `mixers`, `mlp`, `moe` (over `mlp`:
the shared expert), `ends`. This file only re-exports their public names,
for `from ray_tpu.models import layers as L`; nothing but a seam's own test
imports a module of it by path. A patch on a name that code INSIDE the
package reads goes on the module that reads it (`core.rope`,
`moe.assignment_bounds`), not here, where it would reach model files alone.
"""
from ray_tpu.models.layers.core import (  # noqa: F401
    ATTENTION_OUT, Params, ROUTING, THREE_PASS_OUT, exchange_form,
    exchange_sum, flash_on, init_dense, layer_norm, partition_specs, project,
    remat, rms_norm, rms_norm_centred, rope,
)
from ray_tpu.models.layers.attention import (  # noqa: F401
    ATTENTION_LOGICAL, DIFF_ATTENTION_LOGICAL, DIFF_CROSS_LOGICAL,
    DiffAttnConfig, GROUPED_ATTENTION_LOGICAL, LATENT_ATTENTION_LOGICAL,
    LATENT_FULL_Q_LOGICAL, LatentConfig, SPARSE_ATTENTION_LOGICAL,
    SparseConfig, apply_attention, apply_diff_attention,
    apply_latent_attention, apply_sparse_attention, init_attention,
    init_diff_attention, init_latent_attention, init_sparse_attention,
    lambda_init, resolve_attention,
)
from ray_tpu.models.layers.mixers import (  # noqa: F401
    DeltaConfig, GATED_DELTA_LOGICAL, KDAConfig, KDA_LOGICAL,
    MAMBA1_LOGICAL, MAMBA_LOGICAL, Mamba1Config, MambaConfig,
    SHORT_CONV_LOGICAL, apply_gated_delta, apply_kda, apply_mamba,
    apply_mamba1, apply_short_conv, causal_taps, init_gated_delta,
    init_kda, init_mamba, init_mamba1, init_short_conv,
)
from ray_tpu.models.layers.mlp import (  # noqa: F401
    GATED_MLP_LOGICAL, GMU_LOGICAL, MLP_LOGICAL, apply_gated_mlp,
    apply_gmu, apply_mlp, init_gated_mlp, init_gmu, init_mlp,
)
from ray_tpu.models.layers.moe import (  # noqa: F401
    GATED_MOE_LOGICAL, GATED_SHARED_LOGICAL, MOE_EXTRA_LOGICAL,
    MOE_LOGICAL, MoEConfig, SHARED_GATE_LOGICAL, apply_moe,
    assignment_bounds, init_moe, moe_plan, moe_route, routing_plan,
)
from ray_tpu.models.layers.ends import (  # noqa: F401
    embed, head_logits, next_token_loss, refuse_tp, share_loss,
    share_metrics,
)
