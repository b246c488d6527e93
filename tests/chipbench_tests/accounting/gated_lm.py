"""Accounting of the fixture architecture ``gated_lm`` (the rules are in
``chipbench/accounting/gpt2.py``'s docstring). What differs from GPT-2 in
every function: public key names, a gated MLP of three matrices, no
positions and no biases to count, and a head that is not the embedding, so
the embedding's rows are looked up, never multiplied, and carry no FLOPs.
"""
from chipbench.flops import padded_vocab

_SIZES = ("hidden_size", "num_hidden_layers", "num_attention_heads",
          "intermediate_size")


def filed_sizes(config: dict) -> dict:
    return dict({k: config[k] for k in _SIZES},
                padded_vocab=padded_vocab(config["vocab_size"]),
                n_params=params(config))


def ran_sizes(cfg) -> dict:
    return dict({k: getattr(cfg, k) for k in _SIZES},
                padded_vocab=cfg.vocab_size, n_params=cfg.n_params)


def _matmul_params(config: dict) -> int:
    d, ff = config["hidden_size"], config["intermediate_size"]
    return (d * padded_vocab(config["vocab_size"])
            + config["num_hidden_layers"] * (4 * d * d + 3 * d * ff))


def params(config: dict) -> int:
    d = config["hidden_size"]
    return (_matmul_params(config) + padded_vocab(config["vocab_size"]) * d
            + (2 * config["num_hidden_layers"] + 1) * d)


def train_flops_per_token(config: dict, seq: int) -> int:
    return (6 * _matmul_params(config)
            + 6 * config["num_hidden_layers"] * seq * config["hidden_size"])


_MIDDLE = ("gate", "wq", "wv")


def _mid(params) -> int:
    return params["layers"]["gate"].shape[0] // 2


def pick(params) -> dict:
    """The head and the embedding (untied, so each has a gradient of its
    own), and the middle layer's ``gate`` (the gated MLP), ``wq`` and
    ``wv`` (attention through its queries and through its values)."""
    mid = _mid(params)
    leaves = {k: params["layers"][k][mid] for k in _MIDDLE}
    return dict(leaves, head=params["head"], embed=params["embed"])


def put(params, leaves):
    mid = _mid(params)
    layers = dict(params["layers"])
    for k in _MIDDLE:
        layers[k] = layers[k].at[mid].set(leaves[k])
    return dict(params, head=leaves["head"], embed=leaves["embed"],
                layers=layers)
