"""The flash-attention forward kernel's share of its roofline.

Its calls are the Mosaic custom-calls whose name holds ``flash``; the trace
names the call after the kernel's jitted wrapper.  Each call is one layer's
causal forward over a chip's rows (remat runs it again in the backward, and
each run counts).  The share is the least time the chip could take for the
calls, by ``flops.flash_fwd`` at the head widths that the configuration's
plain reference gives (``attention_dims``) and the peaks, over the time they
took, averaged over the cell's devices.
"""

from .. import catalog, flops


def is_flash(op) -> bool:
    head, _, rest = op.name.partition(" = ")
    return "flash" in head and " custom-call(" in rest


def read(ctx):
    cell = ctx.cell
    dims = catalog.reference(cell.config).attention_dims(cell.sizes)
    f, b = flops.flash_fwd(cell.traffic["rows_per_chip"], dims, cell.traffic["seq_len"])
    least = flops.roofline_s(f, b, ctx.peaks)
    shares = []
    for plane in ctx.trace.ops:
        seconds, calls = ctx.trace.op_time(plane, is_flash)
        if calls:
            shares.append(100.0 * calls * least / seconds)
    return sum(shares) / len(shares) if shares else None
