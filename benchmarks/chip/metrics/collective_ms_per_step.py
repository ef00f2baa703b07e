"""Device milliseconds per step in collective ops, on the device with the most.

Collective ops are the all-reduce, all-gather, reduce-scatter, all-to-all and
collective-permute instructions, their ``-start`` and ``-done`` halves
included, on the device's synchronous op line.  On the TPU a ``-done`` op is
the wait for the transfer, so this is close to the collective time that no
compute hides.  It cannot see transfers that run under compute, nor the
compute that waits behind a transfer in a fused op.
"""

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")


def is_collective(op) -> bool:
    rest = op.name.partition(" = ")[2]
    return any(f" {k}(" in rest or f" {k}-start(" in rest or f" {k}-done(" in rest
               for k in KINDS)


def read(ctx):
    steps = ctx.window["steps"]
    per = [ctx.trace.op_time(p, is_collective) for p in ctx.trace.ops]
    if not steps or not any(n for _, n in per):
        return None
    return 1e3 * max(s for s, _ in per) / steps
