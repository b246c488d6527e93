"""Model FLOP/s utilization in percent: tokens a second a chip, times the
model's FLOPs a token (``flops.train_flops_per_token``; recomputation does
not count), over the chip's published bf16 peak."""
from chipbench import flops
from chipbench.readers import throughput


def read(ctx):
    per_token = flops.train_flops_per_token(ctx["model"],
                                            ctx["traffic"]["seq"])
    return (100.0 * throughput.read(ctx) * per_token
            / ctx["peaks"]["bf16_flops_per_s"])
