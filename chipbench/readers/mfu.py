"""Model FLOP/s utilization in percent: tokens a second a chip, times the
model's FLOPs a token (``train_flops_per_token`` of the architecture's
accounting module; recomputation does not count), over the chip's published
bf16 peak."""
import importlib

from chipbench.readers import throughput


def read(ctx):
    per_token = importlib.import_module(
        ctx["accounting"]).train_flops_per_token(ctx["model"],
                                                 ctx["traffic"]["seq"])
    return (100.0 * throughput.read(ctx) * per_token
            / ctx["peaks"]["bf16_flops_per_s"])
