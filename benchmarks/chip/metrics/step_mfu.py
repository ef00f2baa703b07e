"""Model FLOP/s utilisation of the whole step: model FLOPs per token, as the
configuration's plain reference counts them (``train_flops_per_token``),
times the window's tokens per second, over the cell's chips times the bf16
peak.  Remat's recomputation is not counted."""

from .. import catalog


def read(ctx):
    cell = ctx.cell
    per_token = catalog.reference(cell.config).train_flops_per_token(
        cell.sizes, cell.traffic["seq_len"])
    peak = cell.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * per_token * ctx.tokens_per_s / peak
