"""Accounting of the ``nemotron_h`` architecture (the rules are in
``chipbench/accounting/gpt2.py``'s docstring), and from the same shapes what
the Mamba-2 mixers' state-space scan needs, for its roofline share. Nothing
here imports JAX or the program.

What a cell runs is the configuration file's ``pattern`` (its length is
``layers``; ``num_hidden_layers`` and ``hybrid_override_pattern`` beside
them are the published 52 and are never read), ``n_routed_experts`` experts
HELD in each routed layer of the ``published`` number the router scores,
and ``vocab_size`` rows of the vocabulary. A token uses, by kind of layer:
``M`` the two projections (d·(z | xBC | dt) and inner·d) and the
recurrence's own multiply-adds (the state's update and its read-out: 4·H·P·N
FLOPs forward, three times that with the backward; the depthwise conv is no
matmul); ``*`` the four projections at their GQA widths and 6·S·(H·K) under
the causal mask; ``E`` the router, the shared expert and, AT THEIR
EXPECTATION, the held experts: a token chooses ``num_experts_per_tok`` of
the published experts and ``n_routed_experts`` of those are here, so
``top_k · held / scored`` experts a token on average (the rest of its
choices are the absent chips' work, not this chip's). Once: the head over
the slice (untied, so the embedding counts nothing).
"""
from chipbench.flops import padded_vocab

_STACK = {"M": "mamba", "E": "moe", "*": "attn"}


def _widths(config: dict) -> dict:
    heads, head_dim = config["mamba_num_heads"], config["mamba_head_dim"]
    inner = heads * head_dim
    conv = inner + 2 * config["n_groups"] * config["ssm_state_size"]
    return {"inner": inner, "conv": conv, "in_proj": inner + conv + heads,
            "q": config["num_attention_heads"] * config["head_dim"],
            "kv": config["num_key_value_heads"] * config["head_dim"]}


def filed_sizes(config: dict) -> dict:
    return {"pattern": config["pattern"], "layers": config["layers"],
            "hidden_size": config["hidden_size"],
            "num_attention_heads": config["num_attention_heads"],
            "num_key_value_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "mamba_num_heads": config["mamba_num_heads"],
            "mamba_head_dim": config["mamba_head_dim"],
            "n_groups": config["n_groups"],
            "ssm_state_size": config["ssm_state_size"],
            "conv_kernel": config["conv_kernel"],
            "chunk_size": config["chunk_size"],
            "experts_scored": config["published"]["n_routed_experts"],
            "n_routed_experts": config["n_routed_experts"],
            "first_expert": config["deployment"]["first_expert"],
            "num_experts_per_tok": config["num_experts_per_tok"],
            "moe_intermediate_size": config["moe_intermediate_size"],
            "moe_shared_expert_intermediate_size":
                config["moe_shared_expert_intermediate_size"],
            "routed_scaling_factor": config["routed_scaling_factor"],
            "layer_norm_epsilon": config["layer_norm_epsilon"],
            "padded_vocab": padded_vocab(config["vocab_size"]),
            "n_params": params(config)}


def ran_sizes(cfg) -> dict:
    return {"pattern": cfg.pattern, "layers": cfg.n_layer,
            "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_head,
            "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
            "mamba_num_heads": cfg.mamba_heads,
            "mamba_head_dim": cfg.mamba_head_dim, "n_groups": cfg.n_groups,
            "ssm_state_size": cfg.d_state, "conv_kernel": cfg.d_conv,
            "chunk_size": cfg.chunk, "experts_scored": cfg.n_experts,
            "n_routed_experts": cfg.moe.stacked, "first_expert": cfg.first,
            "num_experts_per_tok": cfg.top_k,
            "moe_intermediate_size": cfg.d_expert,
            "moe_shared_expert_intermediate_size": cfg.d_shared,
            "routed_scaling_factor": cfg.routed_scale,
            "layer_norm_epsilon": cfg.rms_norm_eps,
            "padded_vocab": cfg.vocab_size, "n_params": cfg.n_params}


def _matmul_params(config: dict, kind: str, experts: float):
    """Parameters of one layer's matmuls, `experts` routed experts among
    them."""
    d, w = config["hidden_size"], _widths(config)
    if kind == "M":
        return d * w["in_proj"] + w["inner"] * d
    if kind == "*":
        return 2 * d * w["q"] + 2 * d * w["kv"]
    return (d * config["published"]["n_routed_experts"]
            + experts * 2 * d * config["moe_intermediate_size"]
            + 2 * d * config["moe_shared_expert_intermediate_size"])


def params(config: dict) -> int:
    """Every parameter the system trains: embedding and head over the slice
    (untied) and the last norm; a layer: its matmuls (ALL the held experts)
    and its pre-norm; besides, an ``M`` layer its conv (4 + 1 a channel),
    dt_bias, A_log and D a head and the gated norm's scale; an ``E`` layer
    the selection bias."""
    d, w = config["hidden_size"], _widths(config)
    other = {"M": ((config["conv_kernel"] + 1) * w["conv"]
                   + 3 * config["mamba_num_heads"] + w["inner"]),
             "*": 0, "E": config["published"]["n_routed_experts"]}
    return (2 * padded_vocab(config["vocab_size"]) * d + d + sum(
        _matmul_params(config, kind, config["n_routed_experts"])
        + other[kind] + d for kind in config["pattern"]))


def recurrence_flops_per_token(config: dict) -> int:
    """Forward: H_t = a·H + Δx·Bᵀ and y = H·C, a multiply and an add an
    element of the state each."""
    return (4 * config["mamba_num_heads"] * config["mamba_head_dim"]
            * config["ssm_state_size"])


def train_flops_per_token(config: dict, seq: int) -> int:
    pattern = config["pattern"]
    expected = (config["num_experts_per_tok"] * config["n_routed_experts"]
                / config["published"]["n_routed_experts"])
    used = (sum(_matmul_params(config, kind, expected) for kind in pattern)
            + config["hidden_size"] * padded_vocab(config["vocab_size"]))
    return round(6 * used
                 + 6 * pattern.count("*") * seq * _widths(config)["q"]
                 + 3 * pattern.count("M") * recurrence_flops_per_token(config))


def ssd_cost(config: dict, tokens: int, itemsize: int = 2):
    """(FLOPs, bytes) that ONE execution of ONE layer's state-space scan
    needs for `tokens` tokens in its chunked form: the chunk's C·Bᵀ scores
    (2·T·Q·G·N), scores × Δx (2·T·Q·H·P), the chunk states (2·T·H·P·N) and
    the read-out (2·T·H·P·N); x, B, C read and y written once in the compute
    dtype, Δ in float32. A backward execution is taken at the same cost (it
    needs about twice that), so a share of the roofline computed from it
    errs low, never high."""
    heads, head_dim = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, state = config["n_groups"], config["ssm_state_size"]
    chunk = config["chunk_size"]
    flops = (2 * tokens * chunk * (groups * state + heads * head_dim)
             + 4 * tokens * heads * head_dim * state)
    moved = ((2 * tokens * heads * head_dim + 2 * tokens * groups * state)
             * itemsize + tokens * heads * 4)
    return flops, moved


# The compared leaves. Experts are compared two at a time.
_EXPERTS = 2


def _mid(params, kind: str) -> int:
    return params[_STACK[kind]]["ln"].shape[0] // 2


# The routed leaves are compared in the FIRST routed layer. Every router's
# top-k is discontinuous in the stream, and where a share of the experts is
# held a token whose choice differs from the reference's between a held and
# an absent expert gains or loses a whole contribution: measured on the v5e
# (PERF.md §6, PR 34; seven seeds), deeper routed layers read 0.02 to 0.105
# for ``wg``, ``w1`` and ``w2`` by how many of ~2,600 tokens flipped (0.4 to
# 0.8 % do, after one routed layer's single-pass grouped products), which is
# chance and not arithmetic. Ahead of the first routed layer lies one mixer
# in three passes: no token's choice differs there, its leaves read 0.010 to
# 0.017, and their cotangent has come back through every layer behind it.
_ROUTED = 0


def pick(params) -> dict:
    """The head (the loss tail); the middle attention layer's ``wq`` and
    ``wv`` (dq; dk/dv summed over a KV head's group); the middle mixer's
    ``w_in``, ``A_log``, ``dt_bias`` (all three through the scan's
    backward) and ``w_out``; the first routed layer's router ``wg`` (the
    gates' gradient through combine and the renormalisation), its first two
    held experts' ``w1`` and ``w2`` (the grouped matmuls' backward through
    dispatch and combine) and the shared expert's ``shared_w1``."""
    m, a, e = _mid(params, "M"), _mid(params, "*"), _ROUTED
    mamba, attn, moe = params["mamba"], params["attn"], params["moe"]
    return {"head": params["head"],
            "wq": attn["wq"][a], "wv": attn["wv"][a],
            "w_in": mamba["w_in"][m], "A_log": mamba["A_log"][m],
            "dt_bias": mamba["dt_bias"][m], "w_out": mamba["w_out"][m],
            "wg": moe["wg"][e], "w1": moe["w1"][e, :_EXPERTS],
            "w2": moe["w2"][e, :_EXPERTS], "shared_w1": moe["shared_w1"][e]}


def put(params, leaves):
    m, a, e = _mid(params, "M"), _mid(params, "*"), _ROUTED
    mamba, attn, moe = (dict(params[k]) for k in ("mamba", "attn", "moe"))
    for k in ("wq", "wv"):
        attn[k] = attn[k].at[a].set(leaves[k])
    for k in ("w_in", "A_log", "dt_bias", "w_out"):
        mamba[k] = mamba[k].at[m].set(leaves[k])
    for k in ("wg", "shared_w1"):
        moe[k] = moe[k].at[e].set(leaves[k])
    for k in ("w1", "w2"):
        moe[k] = moe[k].at[e, :_EXPERTS].set(leaves[k])
    return dict(params, head=leaves["head"], mamba=mamba, attn=attn, moe=moe)
