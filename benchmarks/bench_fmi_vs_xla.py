"""Paper Figure 6: FMI vs the established implementation (MPI there; the
provider-managed XLA collectives here), on every device of this process.

The process that owns the devices does the timing: no child process, so on
a TPU host nothing else needs the chips.  On the CPU, give the process
virtual devices before it starts::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.run --only fmi_vs_xla

Measures jitted wall time per call of our ppermute-built collectives against
jax.lax built-ins — 'our implementation of the collectives is competitive
and the framework does not introduce significant overhead' is the claim
under test.  Rows name the platform they ran on."""

from __future__ import annotations

import time

N = 1 << 16


def _timed(fn, x, mesh, reps=30):
    import jax
    from jax.sharding import PartitionSpec as P

    with jax.set_mesh(mesh):
        g = jax.jit(jax.shard_map(lambda v: fn(v[0])[None], mesh=mesh,
                                  in_specs=P("data", None), out_specs=P("data", None),
                                  axis_names={"data"}))
        out = g(x)
        jax.block_until_ready(out)  # compile+warm
        t0 = time.perf_counter()
        for _ in range(reps):
            out = g(x)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType

    from repro.core import collectives as C
    from repro.core.communicator import Communicator

    n_dev = len(jax.devices())
    if n_dev < 2:
        raise RuntimeError("fmi_vs_xla needs two or more devices in this process "
                           "(on the CPU: XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    mesh = jax.make_mesh((n_dev,), ("data",), axis_types=(AxisType.Auto,))
    comm = Communicator(axes=("data",), sizes=(n_dev,))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(n_dev, N)), jnp.float32)
    cases = [
        ("allreduce/fmi_ring", lambda v: C.allreduce(v, comm, algorithm="ring")),
        ("allreduce/fmi_rd", lambda v: C.allreduce(v, comm, algorithm="recursive_doubling")),
        ("allreduce/fmi_rabenseifner", lambda v: C.allreduce(v, comm, algorithm="rabenseifner")),
        ("allreduce/xla_psum", lambda v: C.allreduce(v, comm, algorithm="xla")),
        ("reduce_scatter/fmi_halving", lambda v: C.reduce_scatter(v, comm, algorithm="recursive_halving")),
        ("reduce_scatter/xla", lambda v: C.reduce_scatter(v, comm, algorithm="xla")),
        ("allgather/fmi_rd", lambda v: C.allgather(v[: N // n_dev], comm, algorithm="recursive_doubling")),
        ("allgather/xla", lambda v: C.allgather(v[: N // n_dev], comm, algorithm="xla")),
        ("scan/fmi_hillis_steele", lambda v: C.scan(v, comm)),
        ("bcast/fmi_binomial", lambda v: C.bcast(v, comm, root=0)),
    ]
    vals = {name: _timed(fn, x, mesh) for name, fn in cases}
    base_of = {"reduce_scatter/fmi_halving": "reduce_scatter/xla",
               "allgather/fmi_rd": "allgather/xla"}
    platform = jax.devices()[0].platform
    rows = []
    for name, us in vals.items():
        base = base_of.get(name)
        if name.startswith("allreduce/") and name != "allreduce/xla_psum":
            base = "allreduce/xla_psum"
        derived = f"vs_provider={us / vals[base]:.2f}x" if base else "provider_baseline"
        rows.append((f"fmi_vs_xla/{name}/{n_dev}dev_{N * 4 // 1024}KB/{platform}", us, derived))
    return rows
