"""Host milliseconds per step spent making the batch and handing it to the
device: the harness's own ``input`` span around the traffic generator and
``jax.device_put``, on the host clock."""


def read(ctx):
    steps = ctx.window["steps"]
    return ctx.spans.total_ms("input") / steps if steps else None
