"""Accounting of the ``phi4_flash`` architecture (Phi-4-mini-flash-reasoning,
SambaY; the rules are in ``chipbench/accounting/gpt2.py``'s docstring), and
from the same shapes what the selective scan and the differential flash
calls must do and move, for their roofline shares. Nothing here imports JAX
or the program.

What a cell runs is the configuration file's ``layers`` published layers
from ``first_layer`` on (``layers`` is the catalog row's own name for the
depth; ``num_hidden_layers`` beside it is the published 32, which places the
two decoders: ``kind_of``) and ``vocab_size`` rows of the TIED table. The
state-space sizes are not in the published ``config.json``; the file states
the family's under ``assumed`` (``mamba_d_state``, ``mamba_d_conv``,
``mamba_expand``, ``mamba_dt_rank``).

A token uses, a layer: its feed-forward's three d×F matrices, and its mixer
— a Mamba-1 mixer's four projections (``W_in`` d·2C, ``W_x`` C·(r + 2N),
``W_dt`` r·C, ``W_out`` C·d) and THE RECURRENCE AT ITS OWN COST, 6·C·N FLOPs
a token forward (the decay's product, the write and the readout's multiply
and add on each element of the state; the exponentials, the conv's taps and
the gates are element-wise and NOT counted); an attention layer's ``W_qkv``
and ``W_o``; a cross layer's ``W_q`` and ``W_o``; a gated memory unit's two
d×C matrices. Once: the head over the slice (tied: the table counts as the
head's matmul, its gather as nothing). The scores are counted AT THE WIDTHS
THE OPERANDS HAVE — ``QKᵀ`` at the head size, ``P·V`` at twice it, two calls
a pair — and AT THE AREA THE MASK KEEPS: ``S(S+1)/2`` query-key pairs a
sequence for the full-causal and the cross layers, ``Σ_t min(t + 1, W)`` for
a window layer.
"""
from chipbench.flops import padded_vocab

MAMBA, WINDOW, FULL = "mamba", "window_attention", "full_attention"
GMU, CROSS = "gmu", "cross_attention"


def kind_of(i: int, n: int) -> str:
    """The mixer of published layer i of n."""
    half = n // 2
    if i > half + 1:
        return CROSS if i % 2 else GMU
    if i % 2 == 0:
        return MAMBA
    return WINDOW if i < half else FULL


def layout(config: dict) -> list:
    """The kinds of the layers the configuration runs."""
    first = config.get("first_layer", 0)
    return [kind_of(i, config["num_hidden_layers"])
            for i in range(first, first + config["layers"])]


def _widths(config: dict):
    """(d, inner channels C, states N, Δ's rank r, conv taps)."""
    ssm = config["assumed"]
    return (config["hidden_size"],
            ssm["mamba_expand"] * config["hidden_size"],
            ssm["mamba_d_state"], ssm["mamba_dt_rank"], ssm["mamba_d_conv"])


def _head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def filed_sizes(config: dict) -> dict:
    _, inner, states, rank, taps = _widths(config)
    return {"layer_types": layout(config),
            "first_layer": config.get("first_layer", 0),
            "num_hidden_layers": config["num_hidden_layers"],
            "hidden_size": config["hidden_size"],
            "intermediate_size": config["intermediate_size"],
            "num_attention_heads": config["num_attention_heads"],
            "num_key_value_heads": config["num_key_value_heads"],
            "head_dim": _head_dim(config),
            "sliding_window": config["sliding_window"],
            "mamba_inner": inner, "mamba_d_state": states,
            "mamba_dt_rank": rank, "mamba_d_conv": taps,
            "layer_norm_eps": config["layer_norm_eps"],
            "max_position_embeddings": config["max_position_embeddings"],
            "tie_word_embeddings": config["tie_word_embeddings"],
            "padded_vocab": padded_vocab(config["vocab_size"]),
            "n_params": params(config)}


def ran_sizes(cfg) -> dict:
    return {"layer_types": list(cfg.layer_types),
            "first_layer": cfg.first_layer,
            "num_hidden_layers": cfg.n_published,
            "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
            "num_attention_heads": cfg.n_head,
            "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
            "sliding_window": cfg.window,
            "mamba_inner": cfg.mamba.inner, "mamba_d_state": cfg.d_state,
            "mamba_dt_rank": cfg.dt_rank, "mamba_d_conv": cfg.d_conv,
            "layer_norm_eps": cfg.norm_eps,
            "max_position_embeddings": cfg.max_seq,
            "tie_word_embeddings": True,     # the program has no other head
            "padded_vocab": cfg.vocab_size, "n_params": cfg.n_params}


def _mixer_matmuls(config: dict) -> dict:
    d, inner, states, rank, _ = _widths(config)
    q = config["num_attention_heads"] * _head_dim(config)
    kv = config["num_key_value_heads"] * _head_dim(config)
    attention = d * (q + 2 * kv) + q * d
    return {MAMBA: (d * 2 * inner + inner * (rank + 2 * states)
                    + rank * inner + inner * d),
            WINDOW: attention, FULL: attention, GMU: 2 * d * inner,
            CROSS: d * q + q * d}


def _matmul_params(config: dict) -> int:
    """Parameters of the matmuls of every layer the configuration runs; the
    table apart."""
    ff = 3 * config["hidden_size"] * config["intermediate_size"]
    mixer = _mixer_matmuls(config)
    return sum(mixer[kind] + ff for kind in layout(config))


def params(config: dict) -> int:
    """Every parameter the system trains: the tied table over the padded
    slice, the last LayerNorm (2·d); every layer's matmuls, two LayerNorms a
    layer (4·d); a Mamba mixer's taps and conv bias, ``dt_bias``, ``A_log``
    and ``D``; an attention or cross layer's biases (its q | k | v or q
    width, and d), its four λ vectors and its sub-norm's scale."""
    d, inner, states, _, taps = _widths(config)
    head = _head_dim(config)
    q = config["num_attention_heads"] * head
    kv = config["num_key_value_heads"] * head
    lam = 4 * head + 2 * head
    small = {MAMBA: (taps + 1) * inner + inner + inner * states + inner,
             WINDOW: q + 2 * kv + d + lam, FULL: q + 2 * kv + d + lam,
             GMU: 0, CROSS: q + d + lam}
    return (padded_vocab(config["vocab_size"]) * d + 2 * d
            + _matmul_params(config)
            + sum(small[kind] + 4 * d for kind in layout(config)))


def kept_pairs(seq: int, window=None) -> int:
    """Query-key pairs a causal mask keeps of a sequence, under a window of
    `window` keys (the query's own among them) or none."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def _score_pairs(config: dict, seq: int) -> int:
    """Kept pairs a sequence, summed over the layers that score."""
    kinds = layout(config)
    return ((kinds.count(FULL) + kinds.count(CROSS)) * kept_pairs(seq)
            + kinds.count(WINDOW) * kept_pairs(seq,
                                               config["sliding_window"]))


def train_flops_per_token(config: dict, seq: int) -> int:
    _, inner, states, _, _ = _widths(config)
    used = (_matmul_params(config)
            + config["hidden_size"] * padded_vocab(config["vocab_size"]))
    # the recurrence: 6·C·N a token forward, twice that backward
    scan = layout(config).count(MAMBA) * 18 * inner * states
    # a kept pair and query head: QKᵀ at K and P·V at 2K, 2 FLOPs a
    # multiply-add, forward; twice that backward
    head = _head_dim(config)
    pair = 3 * 2 * (head + 2 * head) * config["num_attention_heads"]
    return round(6 * used + scan + pair * _score_pairs(config, seq) / seq)


def selective_scan_cost(config: dict, tokens: int, itemsize: int = 4) -> dict:
    """(FLOPs, bytes) that ONE execution of ONE Mamba layer's scan needs on
    `tokens` tokens, ``forward`` and ``backward`` apart — the least any
    implementation does and moves, at the dtypes the configuration states
    (float32 in and out of the scan): forward the recurrence's 6·C·N a
    token, s and Δ [tokens, C] and B, C [tokens, N] read and y [tokens, C]
    written once; backward twice the FLOPs, the same read again with y's
    cotangent, and the four cotangents written. The states between chunks,
    A, D and the bias are the implementation's or three orders smaller."""
    _, inner, states, _, _ = _widths(config)
    ops = 6 * tokens * inner * states
    read = tokens * (2 * inner + 2 * states) * itemsize
    wrote = tokens * inner * itemsize
    return {"forward": (ops, read + wrote),
            "backward": (2 * ops, 2 * read + wrote)}


def diff_flash_cost(config: dict, kernel: str, windowed: bool, batch: int,
                    seq: int, itemsize: int = 2):
    """(FLOPs, bytes) of ONE differential flash call (``a¹`` or ``a²`` of one
    layer) of `kernel` ("fwd", "dq", "dkv") on `batch` sequences of `seq`:
    the products the kernel's algorithm needs AT THE OPERANDS' OWN WIDTHS —
    K for ``QKᵀ``, ``dS·K`` and ``dSᵀ·Q``, 2K for ``P·V``, ``dO·Vᵀ`` and
    ``Pᵀ·dO`` — and AT THE AREA THE MASK KEEPS (`kept_pairs`: under the
    window where `windowed`); every operand read once and every result
    written once, k, v and their cotangents at the KV pairs' own count."""
    head = _head_dim(config)
    heads = batch * config["num_attention_heads"] // 2       # query pairs
    kv_heads = batch * config["num_key_value_heads"] // 2
    pairs = kept_pairs(seq, config["sliding_window"] if windowed else None)
    narrow, wide = {"fwd": (1, 1), "dq": (2, 1), "dkv": (2, 2)}[kernel]
    ops = 2 * heads * pairs * (narrow * head + wide * 2 * head)
    q = heads * seq * head * itemsize
    o = heads * seq * 2 * head * itemsize
    k, v = (kv_heads * seq * w * itemsize for w in (head, 2 * head))
    rows = heads * seq * 4                      # lse, delta: float32
    moved = {"fwd": q + k + v + o + rows,
             "dq": q + k + v + o + 2 * rows + q,
             "dkv": q + k + v + o + 2 * rows + k + v}[kernel]
    return ops, moved


# The compared leaves.
def _places(params) -> dict:
    """The first layer of each kind, and the LAST Mamba layer (the one whose
    scan result later layers read)."""
    def having(key):
        return [i for i, p in enumerate(params["layers"])
                if key in p["mixer"]]

    mamba, attention = having("w_x"), having("w_qkv")
    gmu = set(having("w_in")) - set(mamba)
    return {"mamba": mamba[0], "memory": mamba[-1], "window": attention[0],
            "full": attention[-1], "cross": having("w_q")[0],
            "gmu": min(gmu)}


# name -> (place, path under the layer)
_PICKED = {
    # the first Mamba mixer: the in-projection (back through the gate, the
    # scan's own backward and the conv), the decays (the scan's dA and dΔ
    # through the softplus), Δ's projection and the taps
    "mamba_w_in": ("mamba", "mixer", "w_in"),
    "mamba_A_log": ("mamba", "mixer", "A_log"),
    "mamba_dt_bias": ("mamba", "mixer", "dt_bias"),
    "mamba_w_dt": ("mamba", "mixer", "w_dt"),
    "mamba_conv_w": ("mamba", "mixer", "conv_w"),
    # the memory's layer: B and C's projection, whose cotangent holds what
    # the gated memory unit sends back beside the layer's own gate
    "memory_w_x": ("memory", "mixer", "w_x"),
    "memory_D": ("memory", "mixer", "D"),
    # the window layer: the flash kernels' dq, dk and dv under the window at
    # 64 / 128 through one matrix, and the sub-norm's scale
    "window_w_qkv": ("window", "mixer", "w_qkv"),
    "window_subln": ("window", "mixer", "subln"),
    # the full layer: its k and v's cotangents are its own AND the cross
    # layer's
    "full_w_qkv": ("full", "mixer", "w_qkv"),
    "full_w_o": ("full", "mixer", "w_o"),
    # the cross-decoder's mixers
    "gmu_w_in": ("gmu", "mixer", "w_in"),
    "cross_w_q": ("cross", "mixer", "w_q"),
    # a feed-forward, half-way up
    "ff_w_gate": ("memory", "ff", "w_gate"),
}


def pick(params) -> dict:
    """The tied table (the loss tail and the gather), and one or more
    leaves behind each kernel and each kind of layer (`_PICKED`)."""
    places, layers = _places(params), params["layers"]
    leaves = {name: layers[places[place]][part][leaf]
              for name, (place, part, leaf) in _PICKED.items()}
    return dict(leaves, wte=params["wte"])


def put(params, leaves):
    places = _places(params)
    layers = [dict(layer, mixer=dict(layer["mixer"]), ff=dict(layer["ff"]))
              for layer in params["layers"]]
    for name, (place, part, leaf) in _PICKED.items():
        layers[places[place]][part][leaf] = leaves[name]
    return dict(params, wte=leaves["wte"], layers=layers)


# The draw the comparison is made on, brought to one difficulty.
LAMBDA_DOT_MOST = 0.05


def conditioned(params):
    """Freshly made parameters with every attention layer's two lambda
    products, ``lambda_q1 . lambda_k1`` and ``lambda_q2 . lambda_k2``, held
    to ``LAMBDA_DOT_MOST`` in size: a pair over it is scaled down together,
    by the same factor, and keeps its directions; every other leaf is the
    draw's. Why: ``lambda = exp(q1.k1) - exp(q2.k2) + lambda_init`` with
    ``lambda_init`` 0.79-0.80 here, and the draw's products (normal at 0.08)
    put ``lambda`` within 0.02 of 1 in one of the three layers on about 8 %
    of seeds. There ``a1 - lambda a2`` all but vanishes at a sequence's
    first positions, the sub-norm hands the kernels a cotangent 1 / |1 -
    lambda| times the others' (70-300 times), and what bf16 leaves of
    ``dp - delta``, which cancels exactly at position 0, is ``dq`` and
    ``dk`` of that size: that layer's ``w_qkv`` then reads 0.05-0.23 where
    every other seed reads 0.028, by rounding alone and differently from one
    process to the next (my chip runs, PR 62: PERF.md 6). Bounded, ``lambda``
    stays under ``lambda_init + 0.1``, at most 0.9, on every seed. No JAX
    here: the leaves' own arithmetic."""
    def bounded(mixer):
        if "lambda_q1" not in mixer:
            return mixer
        mixer = dict(mixer)
        for q, k in (("lambda_q1", "lambda_k1"), ("lambda_q2", "lambda_k2")):
            size = abs(float((mixer[q] * mixer[k]).sum()))
            if size > LAMBDA_DOT_MOST:
                scale = (LAMBDA_DOT_MOST / size) ** 0.5
                mixer[q], mixer[k] = mixer[q] * scale, mixer[k] * scale
        return mixer
    return dict(params, layers=[dict(layer, mixer=bounded(layer["mixer"]))
                                for layer in params["layers"]])
