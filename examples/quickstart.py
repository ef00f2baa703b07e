"""FMI quickstart — the paper's §3.5 interface, on a JAX mesh.

    PYTHONPATH=src python examples/quickstart.py

Mirrors the paper's C++/Python snippets: build a communicator, scatter,
allreduce with a custom operator, scan — then ask the model-driven selector
which channel/algorithm/pipeline-depth it would pick, at what price, across
the whole channel registry (direct ici, mediated host broker, sim oracle,
and their hierarchical composites).
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from repro.core import collectives as C
from repro.core.communicator import Communicator
from repro.core.selector import explain, select


def main():
    mesh = jax.make_mesh((8,), ("world",), axis_types=(AxisType.Auto,))
    # "Here, the communicator contains 8 functions; each has a unique id"
    comm = Communicator(axes=("world",), sizes=(8,), name="world")

    def program(x):
        me = jax.lax.axis_index("world")
        # comm.scatter semantics: rank r receives chunk r (paper's snippet
        # asserts recv.get()[0] == my_id — same check below)
        chunk = C.reduce_scatter(
            jnp.arange(8.0), comm, algorithm="recursive_halving"
        ) / 8.0
        # allreduce with a custom operator (paper: "users can provide an
        # arbitrary function object as a reduction operation")
        biggest = C.allreduce(x, comm, op=lambda a, b: jnp.maximum(a, b),
                              algorithm="recursive_doubling")
        # prefix scan across ranks
        ranks = C.scan(jnp.ones((1,)), comm)
        return chunk, biggest, ranks, me

    run = jax.jit(jax.shard_map(
        lambda v: tuple(o[None] for o in program(v[0])),
        mesh=mesh, in_specs=P("world", None),
        out_specs=(P("world", None), P("world", None), P("world", None), P("world")),
        axis_names={"world"},
    ))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 16)), jnp.float32)
    with jax.set_mesh(mesh):
        chunk, biggest, ranks, me = run(x)

    for r in range(8):
        assert int(round(float(chunk[r, 0]))) == r, "scatter: rank r gets chunk r"
        assert int(ranks[r, 0]) == r + 1, "scan: inclusive prefix of ones"
    print("scatter  : rank r received chunk r            OK")
    print("allreduce: custom max operator                OK",
          float(biggest[0, 0]) == float(x.max(0)[0]))
    print("scan     : rank r has prefix count r+1        OK")

    print("\nmodel-driven selection for a 4 MB allreduce over 16 ranks,")
    print("across the channel registry (flat, pipelined, hierarchical):")
    print(explain("allreduce", 4 << 20, 16, channels=("ici", "host", "sim")))
    best = select("allreduce", 4 << 20, 16, channels=("ici", "host", "sim"))
    print(f"\nselected: {best.channel}/{best.algorithm} depth={best.depth}")

    print("\n...and the same exchange on the paper's AWS channels (8 workers):")
    print(explain("allreduce", 1 << 20, 8, channels=("s3", "redis", "direct")))


if __name__ == "__main__":
    main()
