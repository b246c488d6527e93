"""Everything the harness knows about the GPT-2 architecture, and the only
place it knows it: which sizes the configuration file states and which the
program's preset runs, the parameters and the FLOPs a token, and the leaves
whose gradients decide ``correct``. The shared files (``flops.py``,
``compare.py``, ``readers/mfu.py``, ``jobs/train_fit.py``) hold no
architecture: they reach this module through the ``reference`` name in the
configuration file, as ``chipbench/accounting/<reference>.py`` under any
directory of ``paths`` (``catalog.resolve_cell``). Nothing here imports JAX
or the program.

An accounting module for another architecture has the five functions the
shared files call, by these rules:

``filed_sizes(config)``, ``ran_sizes(cfg)``: two dicts with the same keys,
one from the configuration file and one from the dataclass the program's
preset returns. ``train_fit`` refuses the run where they differ, so they
list every size the file states, the padded vocabulary (``padded_vocab``)
and the count of all the parameters the system trains (``n_params``; how a
module arrives at it is its own affair, ``params`` below is this one's).

``train_flops_per_token(config, seq)``: the PaLM appendix's accounting.
6 FLOPs a token for each parameter A TOKEN USES in a matmul (forward and
backward): for a routed layer the router and the published top-k experts,
not all of them; an embedding's rows count once where the head is tied (the
lookup is no matmul, the head is), and not at all where it is not. Plus
attention's 6·S·(query heads · head_dim) a layer under a causal mask (QK^T
and PV, forward and backward, halved by the mask); a windowed layer counts
its window in place of S. Recomputed operations never count.

``pick(params)``, ``put(params, leaves)``: the leaves ``compare.compare``
holds the system to, by name, and how they go back into the tree. A choice
covers the embedding or the head, and one leaf behind each kernel and each
kind of layer, half-way up the stack.
"""
from chipbench.flops import padded_vocab


def _ff(config: dict) -> int:
    return config.get("n_inner") or 4 * config["n_embd"]


def filed_sizes(config: dict) -> dict:
    return {"n_layer": config["n_layer"], "n_head": config["n_head"],
            "n_embd": config["n_embd"],
            "n_positions": config["n_positions"], "n_inner": _ff(config),
            "padded_vocab": padded_vocab(config["vocab_size"]),
            "n_params": params(config)}


def ran_sizes(cfg) -> dict:
    return {"n_layer": cfg.n_layer, "n_head": cfg.n_head,
            "n_embd": cfg.d_model, "n_positions": cfg.max_seq,
            "n_inner": cfg.ff, "padded_vocab": cfg.vocab_size,
            "n_params": cfg.n_params}


def params(config: dict) -> int:
    """Parameters the system trains for a GPT-2 configuration file: the
    embedding padded to a multiple of 128 rows and counted once (the head
    is tied), learned positions, and per block the four attention
    projections WITHOUT biases (the system's departure), the MLP with
    biases, and two layer norms."""
    d, n_layer = config["n_embd"], config["n_layer"]
    ff = _ff(config)
    block = 4 * d * d + (2 * d * ff + d + ff) + 4 * d
    return (padded_vocab(config["vocab_size"]) * d
            + config["n_positions"] * d + n_layer * block + 2 * d)


def train_flops_per_token(config: dict, seq: int) -> int:
    """Every parameter is used by every token (6·N, the accounting
    ``bench.py`` used), plus 6·L·S·d_model for causal attention."""
    return (6 * params(config)
            + 6 * config["n_layer"] * seq * config["n_embd"])


def _mid(params) -> int:
    return params["blocks"]["mlp"]["w1"].shape[0] // 2


def pick(params) -> dict:
    """The embedding (which is also the output head, so it sees the loss
    tail and every layer below it), and the middle block's ``mlp.w1``,
    ``attn.wq`` and ``attn.wv`` (which see the MLP's backward pass and all
    three flash kernels, dq through ``wq`` and dk/dv through ``wv``,
    through half the stack)."""
    mid = _mid(params)
    return {"wte": params["wte"],
            "w1": params["blocks"]["mlp"]["w1"][mid],
            "wq": params["blocks"]["attn"]["wq"][mid],
            "wv": params["blocks"]["attn"]["wv"][mid]}


def put(params, leaves):
    mid = _mid(params)
    blocks = dict(params["blocks"])
    blocks["mlp"] = dict(blocks["mlp"],
                         w1=blocks["mlp"]["w1"].at[mid].set(leaves["w1"]))
    blocks["attn"] = dict(blocks["attn"],
                          wq=blocks["attn"]["wq"].at[mid].set(leaves["wq"]),
                          wv=blocks["attn"]["wv"].at[mid].set(leaves["wv"]))
    return dict(params, wte=leaves["wte"], blocks=blocks)
