"""Device milliseconds per step in FMI's collectives, on the device with the
most: every op under the program's ``fmi`` scope, which is all that an FMI
algorithm puts on the device (its transfers and their waits, and the adds,
selects and slices between them), not the collective opcodes alone as
``collective_ms_per_step`` counts."""

from .. import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, lambda path: scopes.under(path, "fmi"), over="max")
