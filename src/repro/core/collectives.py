"""Public jax-level collective API (use inside ``jax.shard_map``).

Every function takes a :class:`~repro.core.communicator.Communicator` and an
``algorithm``:

* ``'auto'``    — model-driven selection (paper §5) from the communicator's
  channel α-β/price models, decided at **trace time** (payload size and
  rank count are static);
* ``'xla'``     — the provider-managed channel: ``jax.lax`` built-ins;
* a named algorithm — explicit choice from
  :data:`repro.core.algorithms.ALGORITHMS` (the paper's direct channel).

Shape handling: latency-class algorithms (recursive doubling, binomial,
scan) run on the payload as-is; bandwidth-class chunked algorithms (ring,
Rabenseifner, halving/doubling) ravel + zero-pad the payload to a multiple
of the communicator size, and un-pad on the way out.

Pipelining: under ``algorithm='auto'`` the selector also chooses a chunk
pipelining depth for the bandwidth-class algorithms (round k+1's send
overlaps round k's reduce); pass ``pipeline=<depth>`` to force it.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..analysis.sanitizer import get_active as _sanitizer
from . import algorithms as A
from .communicator import Communicator
from .selector import select
from .transport import is_pow2 as _is_pow2

CHUNKED_ALLREDUCE = {"ring", "rabenseifner"}

_XLA_OPS = {
    "add": jax.lax.psum,
    "max": jax.lax.pmax,
    "min": jax.lax.pmin,
}


def _nbytes(x) -> int:
    return int(math.prod(x.shape)) * x.dtype.itemsize


def _observe(op: str, x, comm: Communicator) -> None:
    """CommSanitizer hook: append this collective to every rank's op ladder
    (one call covers all ranks — the software channels are lockstep; see
    :meth:`repro.analysis.sanitizer.CommSanitizer.on_collective`)."""
    s = _sanitizer()
    if s is not None:
        s.on_collective(f"{comm.name}@{comm.channel}", op,
                        _nbytes(x) if x is not None else 0, comm.size)


def _resolve(
    op_name: str, x, comm: Communicator, algorithm: str, objective: str,
    t=None,
) -> tuple[str, int]:
    """(algorithm, pipeline depth) for this call — model-driven when 'auto'.

    Explicit names pass through at depth 1; 'auto' asks the selector, which
    prices every (algorithm, depth) candidate on the communicator's channel
    with the α-β(+γ) model and returns the argmin.  On stacked (software)
    transports ``x`` physically carries all P ranks, so the per-rank payload
    the model prices is 1/P of it."""
    if algorithm != "auto":
        return algorithm, 1
    nbytes = _nbytes(x)
    if t is not None and t.stacked:
        nbytes = max(1, nbytes // t.size)
    cand = select(
        op_name,
        nbytes,
        comm.size,
        channels=(comm.channel,),
        objective=objective,
    )
    return cand.algorithm, cand.depth


def _pad_flat(x, P: int, t=None):
    """Ravel + zero-pad the per-rank payload to a multiple of ``P``.

    Inside shard_map (JaxTransport) ``x`` is this rank's local shard; on a
    stacked software transport (Sim/Host) ``x`` physically carries all P
    ranks, so the ravel/pad happens per rank along the trailing axes and the
    rank axis is preserved."""
    if t is not None and t.stacked:
        xp = t.xp
        flat = xp.reshape(xp.asarray(x), (t.size, -1))
        n = flat.shape[1]
        pad = (-n) % P
        if pad:
            flat = xp.concatenate([flat, xp.zeros((t.size, pad), flat.dtype)], axis=1)
        return flat, n
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % P
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat, n


def _unpad(out, n: int, shape, t):
    """Inverse of :func:`_pad_flat` for a full-size result."""
    if t.stacked:
        return t.xp.reshape(out, (t.size, -1))[:, :n].reshape(shape)
    return out.reshape(-1)[:n].reshape(shape)


# ---------------------------------------------------------------------------


def allreduce(x, comm: Communicator, op="add", algorithm="auto", objective="time",
              pipeline: int | None = None):
    """``pipeline``: chunk-streaming depth for the bandwidth-class
    algorithms; None lets the selector pick it from the α-β model (only
    meaningful with ``algorithm='auto'`` or ring/rabenseifner)."""
    _observe("allreduce", x, comm)
    if comm.size == 1:
        return x
    t = comm.transport()
    algorithm, depth = _resolve("allreduce", x, comm, algorithm, objective, t)
    if pipeline is not None:
        depth = int(pipeline)
    if algorithm == "xla":
        if not isinstance(op, str) or op not in _XLA_OPS:
            raise ValueError(f"xla channel supports ops {sorted(_XLA_OPS)}")
        return _XLA_OPS[op](x, comm.axis_arg)
    if algorithm in CHUNKED_ALLREDUCE:
        flat, n = _pad_flat(x, comm.size, t)
        if depth > 1:
            out = A.PIPELINED["allreduce"][algorithm](t, flat, op, depth=depth)
        else:
            out = A.ALGORITHMS["allreduce"][algorithm](t, flat, op)
        return _unpad(out, n, x.shape, t)
    return A.ALGORITHMS["allreduce"][algorithm](t, x, op)


def reduce_scatter(x, comm: Communicator, op="add", algorithm="auto",
                   pipeline: int | None = None, rows: bool = False):
    """Returns this rank's reduced chunk of ``x`` raveled: shape
    ``[ceil(x.size/P)]`` under the natural convention (rank r owns chunk r).

    ``rows=True`` splits ``x`` along its leading axis instead, with no
    ravel: rank r gets row block r, ``[x.shape[0] // P, ...]``, and
    ``x.shape[0]`` must be a multiple of P (mesh transports only).  On the
    TPU a ravel of a matrix is a relayout copy, and at gradient size the
    compiler takes minutes over it."""
    _observe("reduce_scatter", x, comm)
    if comm.size == 1:
        return x if rows else x.reshape(-1)
    t = comm.transport()
    algorithm, depth = _resolve("reduce_scatter", x, comm, algorithm, "time", t)
    if pipeline is not None:
        depth = int(pipeline)
    flat = _row_blocks(x, comm.size, t) if rows else _pad_flat(x, comm.size, t)[0]
    if algorithm == "xla":
        if op != "add":
            raise ValueError("xla reduce_scatter supports add")
        return jax.lax.psum_scatter(x if rows else flat, comm.axis_arg,
                                    scatter_dimension=0, tiled=True)
    if algorithm == "recursive_halving":
        if depth > 1:
            return A.halving_reduce_scatter_pipelined(t, flat, op, depth=depth)
        return A.halving_reduce_scatter(t, flat, op)
    if algorithm == "ring":
        if depth > 1:
            chunk = A.ring_reduce_scatter_pipelined(t, flat, op, depth=depth)
        else:
            chunk = A.ring_reduce_scatter(t, flat, op)
        # normalize ring convention (rank r owns chunk (r+1)%P) -> natural
        P = comm.size
        perm = [(i, (i + 1) % P) for i in range(P)]
        return t.ppermute(chunk, perm)
    raise ValueError(f"unknown reduce_scatter algorithm {algorithm!r}")


def _row_blocks(x, P: int, t):
    """``x [n, ...]`` viewed as ``[P, n // P, ...]``: the row blocks of
    ``rows=True`` collectives."""
    if t.stacked or x.ndim < 2 or x.shape[0] % P:
        raise ValueError(f"rows=True needs a mesh transport, a 2-D or wider x "
                         f"and a leading axis divisible by {P}; got shape "
                         f"{tuple(x.shape)}")
    return x.reshape((P, x.shape[0] // P) + tuple(x.shape[1:]))


def allgather(chunk, comm: Communicator, algorithm="auto", rows: bool = False):
    """Natural convention: rank r contributes chunk r; returns flat
    ``[P * chunk.size]`` (leading concat over ranks; on stacked software
    transports the result is ``[P, P * chunk.size]``).  ``rows=True`` is
    the inverse of ``reduce_scatter(rows=True)``: the row blocks stacked,
    ``[P * chunk.shape[0], ...]``, with no ravel."""
    _observe("allgather", chunk, comm)
    if comm.size == 1:
        return chunk if rows else chunk.reshape(-1)
    if algorithm == "auto":
        # doubling is pow2-only; ring handles any rank count
        algorithm = "recursive_doubling" if _is_pow2(comm.size) else "ring"
    if algorithm == "xla":
        return jax.lax.all_gather(chunk if rows else chunk.reshape(-1),
                                  comm.axis_arg, tiled=True)
    t = comm.transport()
    fn = (
        A.doubling_allgather
        if algorithm == "recursive_doubling"
        else A.allgather_natural_ring
    )
    if rows:
        if t.stacked or chunk.ndim < 2:
            raise ValueError(f"rows=True needs a mesh transport and a 2-D or "
                             f"wider chunk; got shape {tuple(chunk.shape)}")
        return fn(t, chunk).reshape((-1,) + tuple(chunk.shape[1:]))
    if t.stacked:
        out = fn(t, t.xp.reshape(t.xp.asarray(chunk), (t.size, -1)))
        return t.xp.reshape(out, (t.size, -1))
    out = fn(t, chunk.reshape(-1))
    return out.reshape(-1)


def alltoall(x, comm: Communicator, algorithm="auto"):
    """``x``: logical ``[P, c, ...]`` per rank (stacked transports:
    physical ``[P, P, c, ...]``); slot j goes to rank j, returns slot j
    from rank j."""
    _observe("alltoall", x, comm)
    if comm.size == 1:
        return x
    if algorithm == "auto":
        algorithm = "pairwise"
    if algorithm == "xla":
        if x.shape[0] != comm.size:
            raise ValueError(f"leading dim {x.shape[0]} != comm size {comm.size}")
        return jax.lax.all_to_all(x, comm.axis_arg, split_axis=0, concat_axis=0, tiled=False)
    t = comm.transport()
    if t.lshape(x)[0] != comm.size:
        raise ValueError(f"leading dim {t.lshape(x)[0]} != comm size {comm.size}")
    return A.alltoall_pairwise(t, x)


def bcast(x, comm: Communicator, root=0, algorithm="binomial"):
    _observe("bcast", x, comm)
    if comm.size == 1:
        return x
    t = comm.transport()
    return A.bcast_binomial(t, x, root=root)


def reduce(x, comm: Communicator, op="add", root=0, algorithm="binomial"):
    _observe("reduce", x, comm)
    if comm.size == 1:
        return x
    t = comm.transport()
    return A.reduce_binomial(t, x, op=op, root=root)


def scan(x, comm: Communicator, op="add"):
    """Inclusive prefix scan across ranks (Hillis–Steele, ⌈log₂P⌉ rounds)."""
    _observe("scan", x, comm)
    if comm.size == 1:
        return x
    t = comm.transport()
    return A.scan_hillis_steele(t, x, op=op)


def barrier(comm: Communicator):
    """A barrier is also the sanitizer's synchronization point: every
    rank's hashed collective ladder is compared here (and reset)."""
    s = _sanitizer()
    if s is not None:
        s.on_collective(f"{comm.name}@{comm.channel}", "barrier", 0,
                        comm.size)
        s.barrier_check(f"{comm.name}@{comm.channel}", comm.size)
    if comm.size == 1:
        return jnp.ones((1,), jnp.int32)
    t = comm.transport()
    return A.barrier(t)


# ---------------------------------------------------------------------------
# Pytree buckets — gradient-sync entry point used by training
# ---------------------------------------------------------------------------


def allreduce_tree(tree, comm: Communicator, op="add", algorithm="auto",
                   objective="time", mean: bool = False,
                   pipeline: int | None = None,
                   schedule: str = "blocking",
                   bucket_bytes: int | None = None,
                   compute_s: float = 0.0):
    """Allreduce a pytree (e.g. gradients).

    ``schedule='blocking'``: leaves are grouped by dtype, raveled and fused
    into one payload per dtype, reduced with one collective each, then
    split back.  ``schedule='bucketed'``: leaves are fed through a
    :class:`~repro.core.scheduler.CommScheduler` in backward order —
    coalesced into α-β-model-sized buckets (``bucket_bytes`` pins the size;
    None lets ``selector.bucket_plan`` choose it from the total payload and
    the ``compute_s`` overlap window) and issued as nonblocking requests.
    ``mean=True`` divides by the communicator size (data-parallel gradient
    averaging)."""
    if comm.size == 1:
        return tree
    if schedule == "bucketed":
        from .scheduler import CommScheduler

        total = sum(
            int(math.prod(l.shape)) * l.dtype.itemsize
            for l in jax.tree.leaves(tree)
        )
        if comm.transport().stacked:
            total //= comm.size  # planner prices the logical per-rank payload
        sched = CommScheduler(
            comm, op=op, mean=mean, algorithm=algorithm, objective=objective,
            bucket_bytes=bucket_bytes, total_bytes_hint=total,
            compute_s=compute_s,
        )
        return sched.sync_tree(tree)
    if schedule != "blocking":
        raise ValueError(f"unknown schedule {schedule!r}; "
                         "expected 'blocking' or 'bucketed'")
    leaves, treedef = jax.tree.flatten(tree)
    by_dtype: dict[Any, list[int]] = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(leaf.dtype, []).append(i)
    out = list(leaves)
    t = comm.transport()
    for dtype, idxs in by_dtype.items():
        if t.stacked:  # software transports: leaves carry a [P, ...] axis
            flat = t.xp.concatenate(
                [t.xp.reshape(t.xp.asarray(leaves[i]), (t.size, -1)) for i in idxs],
                axis=1,
            )
        else:
            flat = jnp.concatenate([leaves[i].reshape(-1) for i in idxs])
        red = allreduce(flat, comm, op=op, algorithm=algorithm, objective=objective,
                        pipeline=pipeline)
        if mean:
            red = red / comm.size
        off = 0
        for i in idxs:
            if t.stacked:
                n = math.prod(leaves[i].shape) // t.size
                out[i] = red[:, off:off + n].reshape(leaves[i].shape)
            else:
                n = math.prod(leaves[i].shape)
                out[i] = jax.lax.dynamic_slice_in_dim(red, off, n).reshape(leaves[i].shape)
            off += n
    return jax.tree.unflatten(treedef, out)
