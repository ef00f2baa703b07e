"""Model FLOP/s utilisation of the whole step: model FLOPs per token times the
window's tokens per second, over the cell's chips times the bf16 peak.
Remat's recomputation is not counted."""

from .. import flops


def read(ctx):
    per_token = flops.train_flops_per_token(ctx.cell.sizes, ctx.cell.traffic["seq_len"])
    peak = ctx.cell.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * per_token * ctx.tokens_per_s / peak
