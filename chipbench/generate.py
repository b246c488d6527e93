"""The one traffic generator: a traffic file's parameters and a seed in,
the job's inputs out. A new traffic mix is a new data file, never new code
here."""
import numpy as np


def token_rows(traffic: dict, vocab: int, seed: int) -> np.ndarray:
    """``batches`` × ``batch`` rows of ``seq`` + 1 tokens, drawn uniformly
    from the first ``vocab // vocab_divisor`` ids: with a sixteenth of the
    vocabulary the unigram statistics are learnable within a few steps, so
    a falling loss shows that the optimizer works (the rule
    ``chip_smoke.make_tokens`` set)."""
    rng = np.random.default_rng(seed)
    high = max(2, vocab // traffic["vocab_divisor"])
    return rng.integers(
        0, high, size=(traffic["batches"] * traffic["batch"],
                       traffic["seq"] + 1), dtype=np.int32)
