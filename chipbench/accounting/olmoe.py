"""Accounting of the ``olmoe`` architecture (the rules are in
``chipbench/accounting/gpt2.py``'s docstring), and from the same shapes what
the routed layer's grouped matmuls need, for their roofline share. Nothing
here imports JAX or the program.

The depth a cell runs is the configuration file's ``layers`` (the catalog
row's own name for it); ``num_hidden_layers`` beside it is the published
16 and is never read. A token uses, a layer: the four attention projections
(4·d²), the router (d·E) and ``num_experts_per_tok`` experts of three d×F
matrices; once: the head (d·V; untied, so the embedding is a lookup and
counts nothing). The norms' scales train and count as parameters, not as
matmul FLOPs.
"""
from chipbench.flops import padded_vocab


def filed_sizes(config: dict) -> dict:
    return {"layers": config["layers"],
            "hidden_size": config["hidden_size"],
            "num_attention_heads": config["num_attention_heads"],
            "num_key_value_heads": config["num_key_value_heads"],
            "intermediate_size": config["intermediate_size"],
            "num_experts": config["num_experts"],
            "num_experts_per_tok": config["num_experts_per_tok"],
            "max_position_embeddings": config["max_position_embeddings"],
            "padded_vocab": padded_vocab(config["vocab_size"]),
            "n_params": params(config)}


def ran_sizes(cfg) -> dict:
    return {"layers": cfg.n_layer, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_head,
            "num_key_value_heads": cfg.n_head,
            "intermediate_size": cfg.d_expert,
            "num_experts": cfg.n_experts,
            "num_experts_per_tok": cfg.top_k,
            "max_position_embeddings": cfg.max_seq,
            "padded_vocab": cfg.vocab_size, "n_params": cfg.n_params}


def _layer_matmul_params(config: dict, experts: int) -> int:
    d = config["hidden_size"]
    return (4 * d * d + d * config["num_experts"]
            + experts * 3 * d * config["intermediate_size"])


def params(config: dict) -> int:
    """Every parameter the system trains: embedding and head (untied), and
    a layer: attention with the q and k norms' scales (2·d), the router,
    ALL the experts, and the block's two norms (2·d); the last norm (d)."""
    d = config["hidden_size"]
    return (2 * padded_vocab(config["vocab_size"]) * d
            + config["layers"] * (
                _layer_matmul_params(config, config["num_experts"]) + 4 * d)
            + d)


def train_flops_per_token(config: dict, seq: int) -> int:
    d = config["hidden_size"]
    used = (config["layers"] * _layer_matmul_params(
        config, config["num_experts_per_tok"])
        + d * padded_vocab(config["vocab_size"]))
    return 6 * used + 6 * config["layers"] * seq * d


def grouped_matmul_cost(config: dict, tokens: int, itemsize: int = 2):
    """(FLOPs, bytes) that the grouped matmuls of ONE routed layer need in
    one training step of ``tokens`` tokens: gate, up and down, each forward,
    backward to its rows and backward to its weights (nine products of
    tokens·top_k rows × d × F). Bytes: every product reads its two operands
    and writes its result once, in the compute dtype — a product with the
    weights reads all E matrices, one to the weights writes them."""
    rows = tokens * config["num_experts_per_tok"]
    d, f = config["hidden_size"], config["intermediate_size"]
    weights = config["num_experts"] * d * f
    flops = 9 * 2 * rows * d * f
    # the three operands of a product, whichever of them is the result
    moved = 9 * (rows * d + rows * f + weights) * itemsize
    return flops, moved


# The compared leaves. Experts are compared four at a time, not all 64: the
# whole [64, d, F] leaf is 1 GB as float64 on the host.
_EXPERTS = 4


def pick(params) -> dict:
    """The head (the loss tail), the middle layer's ``wq`` and ``wv`` (dq
    through the QK-norm and the rotation; dk/dv), its router ``wg`` (both
    router loss terms and the gates' gradient through combine), and the
    gate and down matrices of its first four experts (the grouped matmuls'
    backward through dispatch and combine)."""
    blocks = params["blocks"]
    mid = blocks["ln1"].shape[0] // 2
    return {"head": params["head"],
            "wq": blocks["attn"]["wq"][mid],
            "wv": blocks["attn"]["wv"][mid],
            "wg": blocks["moe"]["wg"][mid],
            "w_gate": blocks["moe"]["w_gate"][mid, :_EXPERTS],
            "w_down": blocks["moe"]["w_down"][mid, :_EXPERTS]}


def put(params, leaves):
    blocks = dict(params["blocks"])
    mid = blocks["ln1"].shape[0] // 2
    attn, moe = dict(blocks["attn"]), dict(blocks["moe"])
    for k in ("wq", "wv"):
        attn[k] = attn[k].at[mid].set(leaves[k])
    moe["wg"] = moe["wg"].at[mid].set(leaves["wg"])
    for k in ("w_gate", "w_down"):
        moe[k] = moe[k].at[mid, :_EXPERTS].set(leaves[k])
    blocks.update(attn=attn, moe=moe)
    return dict(params, head=leaves["head"], blocks=blocks)
