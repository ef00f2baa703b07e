"""Device milliseconds per step in the head and the loss: the ops under the
program's ``head_loss`` scopes (final norm, the head's matmul, the chunked
cross-entropy and z-loss; forward, backward and remat's recompute),
averaged over the cell's devices."""

from .. import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, lambda path: scopes.under(path, "head_loss"))
