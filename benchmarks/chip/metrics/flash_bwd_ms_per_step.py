"""Device milliseconds per step in the flash-attention backward: the ops
under the program's ``flash_bwd`` scope (the VJP of the XLA twin, a ``while``
over kv blocks), averaged over the cell's devices."""

from .. import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, lambda path: scopes.under(path, "flash_bwd"))
