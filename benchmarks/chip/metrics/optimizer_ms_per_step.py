"""Device milliseconds per step in the optimizer: the ops under the
program's ``optimizer`` scope (the clip and AdamW; under ZeRO-1 also its row
views, padding and slices) and not under ``fmi``, whose collectives ZeRO-1
runs inside it, averaged over the cell's devices."""

from .. import scopes


def read(ctx):
    return scopes.ms_per_step(
        ctx, lambda path: scopes.under(path, "optimizer") and not scopes.under(path, "fmi"))
