"""Paper case study (§6.4): distributed K-Means with FMI collectives.

LambdaML's K-Means synchronized per-epoch centroid sums through DynamoDB
(sequential leader reduction, base64-serialized items); replacing that with
one FMI allreduce gave the paper its 162x/397x headline.  This example is
the same computation in JAX:

  each worker: assign local points to nearest centroid, build [k, d+1]
  partial sums;  all workers: ONE allreduce;  everyone: new centroids.

Two runnable modes:
  * sim  (default) — P workers on the instrumented software channel
    (arbitrary P, counts rounds/bytes; used by benchmarks/bench_kmeans.py)
  * mesh — real shard_map over 8 host devices, the production code path:
      PYTHONPATH=src python examples/distributed_kmeans.py --mode mesh
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.core import algorithms as A
from repro.core.transport import SimTransport


def _local_stats(points: np.ndarray, cents: np.ndarray) -> np.ndarray:
    """[n, d] points x [k, d] centroids -> [k, d+1] (sums | counts)."""
    d2 = ((points[:, None, :] - cents[None, :, :]) ** 2).sum(-1)
    assign = d2.argmin(1)
    k = cents.shape[0]
    sums = np.zeros((k, points.shape[1] + 1), np.float32)
    for j in range(k):
        m = assign == j
        sums[j, :-1] = points[m].sum(0)
        sums[j, -1] = m.sum()
    return sums


def kmeans_epoch_sim(P: int = 16, n_local: int = 512, d: int = 28, k: int = 10,
                     seed: int = 0):
    """One epoch over P simulated workers; returns (centroids, channel trace)."""
    rng = np.random.default_rng(seed)
    data = [rng.normal(size=(n_local, d)).astype(np.float32) + 0.1 * w
            for w in range(P)]
    cents = rng.normal(size=(k, d)).astype(np.float32)

    stats = np.stack([_local_stats(data[w], cents) for w in range(P)])  # [P,k,d+1]
    t = SimTransport(P)
    total = A.allreduce_recursive_doubling(t, stats.reshape(P, -1), "add")
    total = total[0].reshape(k, d + 1)
    counts = np.maximum(total[:, -1:], 1.0)
    new_cents = total[:, :-1] / counts
    return new_cents, t.trace


def kmeans_mesh(epochs: int = 5, P: int = 8, n_local: int = 2048, d: int = 28,
                k: int = 10):
    """The production path: shard_map over real devices, FMI allreduce."""
    import os

    os.environ.setdefault("XLA_FLAGS", f"--xla_force_host_platform_device_count={P}")
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, PartitionSpec as Pspec

    from repro.core import collectives as C
    from repro.core.communicator import Communicator

    mesh = jax.make_mesh((P,), ("data",), axis_types=(AxisType.Auto,))
    comm = Communicator(axes=("data",), sizes=(P,))
    rng = np.random.default_rng(0)
    pts = jnp.asarray(rng.normal(size=(P * n_local, d)), jnp.float32)
    cents = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)

    def epoch(points, cents):
        d2 = jnp.sum((points[:, None, :] - cents[None, :, :]) ** 2, -1)
        assign = jnp.argmin(d2, 1)
        oh = jax.nn.one_hot(assign, k, dtype=points.dtype)  # [n, k]
        sums = oh.T @ points  # [k, d]
        counts = oh.sum(0)[:, None]
        stats = jnp.concatenate([sums, counts], 1)  # [k, d+1]
        # THE case-study line: one FMI collective replaces the storage round
        stats = C.allreduce(stats, comm, algorithm="auto")
        return stats[:, :-1] / jnp.maximum(stats[:, -1:], 1.0)

    step = jax.jit(jax.shard_map(
        epoch, mesh=mesh, in_specs=(Pspec("data", None), Pspec(None, None)),
        out_specs=Pspec(None, None), axis_names={"data"}, check_vma=False,
    ))
    with jax.set_mesh(mesh):
        for e in range(epochs):
            cents = step(pts, cents)
            inertia = float(jnp.sum(jnp.min(jnp.sum(
                (pts[:, None, :] - cents[None, :, :]) ** 2, -1), 1)))
            print(f"epoch {e}: inertia {inertia:.1f}")
    return np.asarray(cents)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["sim", "mesh"], default="sim")
    ap.add_argument("--workers", type=int, default=16)
    ap.add_argument("--epochs", type=int, default=5)
    args = ap.parse_args()
    if args.mode == "sim":
        cents, trace = kmeans_epoch_sim(P=args.workers)
        print(f"sim: {args.workers} workers, allreduce rounds={trace.rounds}, "
              f"bytes/rank={trace.bytes_per_rank}")
        print("centroid[0][:5] =", np.round(cents[0, :5], 3))
    else:
        kmeans_mesh(epochs=args.epochs)


if __name__ == "__main__":
    main()
