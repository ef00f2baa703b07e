"""Device milliseconds per step in the flash-attention backward: the ops
under the program's ``flash_bwd`` scope (the Pallas kernels ``attn_bwd_dkv``
and ``attn_bwd_dq``, and the XLA reduction ``D = rowsum(dO * O)`` that feeds
them), averaged over the cell's devices."""

from .. import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, lambda path: scopes.under(path, "flash_bwd"))
