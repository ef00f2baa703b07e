"""Channel transports for FMI collectives.

The paper (§3.2) separates *algorithms* (channel-agnostic, operate on a
communicator) from *channels* (the medium moving raw bytes).  We keep that
split: every collective algorithm in :mod:`repro.core.algorithms` is written
once against the :class:`Transport` interface below and runs unchanged on

* :class:`JaxTransport` — the **direct ICI channel**: ``jax.lax.ppermute``
  schedules inside ``jax.shard_map`` (the TPU analogue of the paper's direct
  TCP channel; the mesh plays the role of the hole-punching rendezvous), and
* :class:`SimTransport` — an instrumented software channel that executes all
  ranks in lockstep on stacked numpy arrays.  It supports **arbitrary rank
  counts** (including non-powers-of-two), counts rounds and per-rank bytes,
  and is the oracle for property tests and for validating the α-β cost
  models in :mod:`repro.core.models` (the counted rounds/bytes must match
  the model exactly), and
* :class:`HostTransport` — a **mediated channel**: every message is staged
  through a shared host-memory :class:`HostBroker` (PUT by the sender, GET
  by the receiver), the TPU analogue of the paper's S3/Redis storage
  channels.  Each logical exchange costs two serialized hops, which the
  trace and the ``hops=2`` entry of its :class:`~repro.core.models.ChannelSpec`
  both record.

Nonblocking contract
--------------------
The single communication primitive is split MPI-style into an issue half
and a completion half: ``ppermute_start(x, perm)`` injects the message and
returns a :class:`TransportRequest`; ``request.wait()`` yields the received
payload.  Blocking ``ppermute`` is just ``ppermute_start(...).wait()``.

A message *started while earlier requests are still pending* is pipelined
behind them (chunk-streamed pipelining: round ``k+1``'s send overlaps round
``k``'s reduce).  Pending-issued messages still count toward ``rounds`` and
bytes, but merge into the open **serialized slot** — so
``trace.serial_rounds``/``trace.slot_bytes()`` expose the critical-path
schedule the α-β model prices, while ``trace.rounds`` counts raw messages.
The trace's pending-slot accounting replaces the old ``overlap=`` flag:
overlap is no longer asserted by the caller, it is *observed* from the
issue/wait order of requests.

SPMD convention
---------------
Algorithms are written in SPMD style: one logical program per rank.  A
"logical array" has shape ``[*shape]``.  ``SimTransport`` physically stores
``[P, *shape]`` (leading rank axis) and vectorizes every transport op over
it; ``JaxTransport`` stores exactly ``[*shape]`` per device.  Rank-dependent
control flow is expressed with :meth:`Transport.where` masks and
rank-indexed dynamic slices — never with python ``if`` on the rank.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.sanitizer import get_active as _sanitizer

Perm = Sequence[tuple[int, int]]


class RankFailure(RuntimeError):
    """A transport operation touched a rank that has failed.

    Raised by the software channels when fault injection
    (:meth:`SimTransport.kill`) has marked a participant dead, or when a
    lease-based channel (:class:`~repro.core.rdma.LeaseTransport`) observes
    a lapsed lease.  Carries the failed ``rank`` so the elastic runtime can
    mark it in :class:`~repro.runtime.membership.Membership` and regroup,
    and a ``reason`` tag (``"rank-failure"``, ``"lease-expired"``, ...) the
    elastic controller records as the evidence that drove the heal."""

    def __init__(self, rank: int, message: str | None = None,
                 reason: str = "rank-failure"):
        super().__init__(message or f"rank {rank} failed mid-collective")
        self.rank = rank
        self.reason = reason


def is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def ilog2(n: int) -> int:
    if not is_pow2(n):
        raise ValueError(f"expected a power of two, got {n}")
    return n.bit_length() - 1


class TransportRequest:
    """Handle for one in-flight ``ppermute`` (the transport half of the
    MPI-style nonblocking contract; :mod:`repro.core.requests` builds the
    user-facing :class:`~repro.core.requests.Request` on top of this).

    ``wait()`` returns the received payload and retires the request;
    ``test()`` reports completion without blocking.  On lockstep software
    channels the data movement happens at issue time — what ``wait``
    completes is the *trace accounting* (the pending slot is closed), which
    is exactly the part the α-β model prices.

    ``cancel()`` is the abort half of the elastic-runtime quiesce protocol:
    an in-flight request is retired *without* delivering its payload — the
    channel's ``on_cancel`` hook closes the trace's pending slot (and, on
    mediated channels, discards the staged broker keys so nothing leaks).
    Waiting a cancelled request returns ``None``; the user-facing
    :class:`~repro.core.requests.Request` raises instead."""

    def __init__(self, result, on_wait: Callable | None = None,
                 on_cancel: Callable | None = None):
        self._result = result
        self._on_wait = on_wait
        self._on_cancel = on_cancel
        self._done = on_wait is None
        self.cancelled = False

    def test(self) -> bool:
        return self._done

    def wait(self):
        if not self._done:
            on_wait, self._on_wait = self._on_wait, None
            self._result = on_wait(self._result)
            self._done = True
        return self._result

    def cancel(self) -> bool:
        """Abort the request if still in flight.  Returns True iff this call
        cancelled it (False: already completed — MPI_Cancel semantics)."""
        if self._done:
            if self.cancelled:
                s = _sanitizer()
                if s is not None:
                    s.on_transport_double_cancel(self)
            return False
        on_cancel = self._on_cancel
        self._on_wait = self._on_cancel = None
        self._result = None
        self._done = True
        self.cancelled = True
        if on_cancel is not None:
            on_cancel()
        s = _sanitizer()
        if s is not None:
            s.on_transport_cancel(self)
        return True


class Transport:
    """Abstract SPMD transport — the paper's 'channel' operating on raw memory."""

    size: int
    xp: Any  # numpy-like module
    stacked: bool = False  # True: arrays carry a physical [P, ...] rank axis

    # -- identity ---------------------------------------------------------
    def rank(self):
        raise NotImplementedError

    # -- the single communication primitive --------------------------------
    def ppermute_start(self, x, perm: Perm) -> TransportRequest:
        """Issue one permutation message nonblockingly: rank ``dst`` will
        receive ``x`` from ``src`` for each ``(src, dst)``; ranks that
        receive nothing get zeros (jax.lax.ppermute semantics).  A message
        started while earlier requests are pending pipelines behind them
        (merges into the open serialized slot on instrumented channels; a
        scheduling hint only on hardware channels)."""
        raise NotImplementedError

    def ppermute(self, x, perm: Perm):
        """Blocking permutation: issue + immediately complete (one fresh
        serialized slot per call on the instrumented channels)."""
        return self.ppermute_start(x, perm).wait()

    # -- rank-masked helpers (shape-polymorphic between sim and jax) -------
    def where(self, cond, a, b):
        raise NotImplementedError

    def dynslice(self, x, start, size: int, axis: int = 0):
        """``lax.dynamic_slice_in_dim`` with a possibly rank-dependent start."""
        raise NotImplementedError

    def dynupdate(self, x, update, start, axis: int = 0):
        raise NotImplementedError

    def concat(self, parts, axis: int = 0):
        raise NotImplementedError

    def reshape(self, x, shape: tuple[int, ...]):
        raise NotImplementedError

    def astype(self, x, dtype):
        return x.astype(dtype)

    def zeros(self, shape: tuple[int, ...], dtype):
        raise NotImplementedError

    def ones(self, shape: tuple[int, ...], dtype):
        raise NotImplementedError

    # logical shape (without the stacked rank axis)
    def lshape(self, x) -> tuple[int, ...]:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Direct channel: ppermute inside shard_map
# ---------------------------------------------------------------------------


class JaxTransport(Transport):
    """Direct-channel transport over named mesh axes inside ``shard_map``.

    ``axes`` may be a single axis name or a tuple; the flat rank is row-major
    over the tuple (matches ``jax.lax`` semantics for axis-name tuples).
    """

    xp = jnp

    def __init__(self, axes: str | tuple[str, ...], sizes: int | tuple[int, ...]):
        self.axes = (axes,) if isinstance(axes, str) else tuple(axes)
        sizes = (sizes,) if isinstance(sizes, int) else tuple(sizes)
        if len(sizes) != len(self.axes):
            raise ValueError("axes/sizes length mismatch")
        self.axis_sizes = sizes
        self.size = int(np.prod(sizes))

    def rank(self):
        return jax.lax.axis_index(self.axes if len(self.axes) > 1 else self.axes[0])

    def ppermute_start(self, x, perm: Perm) -> TransportRequest:
        # XLA schedules overlap itself (issue order in the traced graph is
        # the async hint); the request completes immediately.
        axis = self.axes if len(self.axes) > 1 else self.axes[0]
        return TransportRequest(jax.lax.ppermute(x, axis, perm))

    def where(self, cond, a, b):
        return jnp.where(cond, a, b)

    def dynslice(self, x, start, size: int, axis: int = 0):
        return jax.lax.dynamic_slice_in_dim(x, start, size, axis=axis)

    def dynupdate(self, x, update, start, axis: int = 0):
        return jax.lax.dynamic_update_slice_in_dim(x, update, start, axis=axis)

    def concat(self, parts, axis: int = 0):
        return jnp.concatenate(parts, axis=axis)

    def reshape(self, x, shape):
        return jnp.reshape(x, shape)

    def zeros(self, shape, dtype):
        return jnp.zeros(shape, dtype)

    def ones(self, shape, dtype):
        return jnp.ones(shape, dtype)

    def lshape(self, x):
        return tuple(x.shape)


# ---------------------------------------------------------------------------
# Instrumented software channel (testing + cost-model oracle)
# ---------------------------------------------------------------------------


@dataclass
class ChannelTrace:
    """What the α-β model needs: rounds and the max bytes any rank moved.

    ``rounds``/``per_round`` count every message; ``serial_rounds``/
    ``per_slot`` group messages into serialized slots.  Slot membership is
    decided by **pending-slot accounting**: a message *issued* while earlier
    requests are still pending rides in the open slot (its bytes occupy the
    link, but it pays no fresh latency because it was injected while the
    previous message's reduce was still running); a message issued with no
    requests in flight opens a fresh slot.  ``issue``/``complete`` are the
    bookkeeping halves of ``ppermute_start``/``request.wait()``."""

    rounds: int = 0
    bytes_per_rank: int = 0  # max over ranks of bytes *sent* (α-β convention)
    total_bytes: int = 0
    per_round: list = field(default_factory=list)
    serial_rounds: int = 0
    per_slot: list = field(default_factory=list)  # [[bytes, ...], ...]
    pending: int = 0  # requests issued but not yet waited

    def record(self, nbytes: int, participants: int, overlap: bool = False):
        self.rounds += 1
        self.bytes_per_rank += nbytes
        self.total_bytes += nbytes * participants
        self.per_round.append((nbytes, participants))
        if overlap and self.per_slot:
            self.per_slot[-1].append(nbytes)
        else:
            self.serial_rounds += 1
            self.per_slot.append([nbytes])

    def issue(self, nbytes: int, participants: int):
        """Record a nonblockingly-issued message: it merges into the open
        slot iff some earlier request is still pending."""
        self.record(nbytes, participants, overlap=self.pending > 0)
        self.pending += 1

    def complete(self):
        """Retire one pending request (the ``wait`` half)."""
        if self.pending <= 0:
            raise RuntimeError("trace.complete() without a pending request")
        self.pending -= 1

    def slot_bytes(self) -> list:
        """Per serialized slot: total bytes the busiest rank pushed."""
        return [sum(slot) for slot in self.per_slot]

    def time(self, alpha: float, beta: float) -> float:
        """α-β critical-path time: one latency per serialized slot, link
        occupancy for every byte in the slot (overlapped messages stream
        back-to-back behind the first)."""
        return sum(alpha + b * beta for b in self.slot_bytes())


class SimTransport(Transport):
    """All ranks in lockstep on stacked ``[P, *shape]`` numpy arrays.

    Fault injection: :meth:`kill` marks a rank failed (optionally after a
    number of further rounds, to land the failure mid-collective); any
    exchange whose pair list then touches the dead rank raises
    :class:`RankFailure`.  :meth:`revive` clears the mark — the membership
    flap (down-then-up) path of the elastic runtime."""

    xp = np
    stacked = True

    def __init__(self, size: int):
        self.size = int(size)
        self.trace = ChannelTrace()
        self._dead: set[int] = set()
        self._kill_at: dict[int, int] = {}  # rank -> rounds until failure

    # fault injection -------------------------------------------------------
    def kill(self, rank: int, after_rounds: int = 0):
        """Mark ``rank`` failed.  ``after_rounds=k``: the next ``k`` calls to
        :meth:`ppermute_start` still succeed; the failure surfaces on the
        one after that (so a test can land it mid-allreduce)."""
        if not 0 <= rank < self.size:
            raise ValueError(f"rank {rank} outside [0, {self.size})")
        if after_rounds <= 0:
            self._dead.add(rank)
        else:
            self._kill_at[rank] = int(after_rounds)

    def revive(self, rank: int):
        """Clear a failure mark (the rank came back — membership flap)."""
        self._dead.discard(rank)
        self._kill_at.pop(rank, None)

    @property
    def dead(self) -> frozenset:
        return frozenset(self._dead)

    def _check_failures(self, pairs: Perm):
        for r in list(self._kill_at):
            if self._kill_at[r] <= 0:  # grace rounds used up: now it dies
                del self._kill_at[r]
                self._dead.add(r)
            else:
                self._kill_at[r] -= 1
        if self._dead:
            for src, dst in pairs:
                if src in self._dead or dst in self._dead:
                    rank = src if src in self._dead else dst
                    raise RankFailure(rank)

    # stacking helpers ------------------------------------------------------
    def stack(self, per_rank: Sequence[np.ndarray]) -> np.ndarray:
        assert len(per_rank) == self.size
        return np.stack([np.asarray(a) for a in per_rank], axis=0)

    def unstack(self, x: np.ndarray) -> list[np.ndarray]:
        return [x[i] for i in range(self.size)]

    def rank(self):
        return np.arange(self.size)

    def ppermute_start(self, x, perm: Perm) -> TransportRequest:
        # Lockstep semantics: the data moves at issue time (every rank is
        # in this call); wait() closes the trace's pending slot.
        pairs = list(perm)
        self._check_failures(pairs)
        out = np.zeros_like(x)
        max_sent = 0
        itemsize = x.dtype.itemsize
        per_msg = int(np.prod(x.shape[1:])) * itemsize
        for src, dst in pairs:
            out[dst] = x[src]
            max_sent = max(max_sent, per_msg)
        self.trace.issue(max_sent, len(pairs))
        return TransportRequest(out, on_wait=self._finish,
                                on_cancel=self.trace.complete)

    def _finish(self, out):
        self.trace.complete()
        return out

    def _bcast_cond(self, cond, ref):
        cond = np.asarray(cond)
        if cond.ndim == 0:
            return cond
        # [P] -> [P, 1, 1, ...] to broadcast against [P, *shape]
        return cond.reshape((self.size,) + (1,) * (np.ndim(ref) - 1))

    def where(self, cond, a, b):
        a, b = np.asarray(a), np.asarray(b)
        ref = a if a.ndim >= b.ndim else b
        return np.where(self._bcast_cond(cond, ref), a, b)

    def dynslice(self, x, start, size: int, axis: int = 0):
        ax = axis + 1  # skip rank axis
        start = np.broadcast_to(np.asarray(start), (self.size,))
        out = np.stack(
            [np.take(x[i], np.arange(start[i], start[i] + size), axis=axis) for i in range(self.size)]
        )
        del ax
        return out

    def dynupdate(self, x, update, start, axis: int = 0):
        start = np.broadcast_to(np.asarray(start), (self.size,))
        out = np.array(x)
        n = update.shape[axis + 1]
        for i in range(self.size):
            idx = [slice(None)] * (x.ndim - 1)
            idx[axis] = slice(int(start[i]), int(start[i]) + n)
            out[i][tuple(idx)] = update[i]
        return out

    def concat(self, parts, axis: int = 0):
        return np.concatenate(parts, axis=axis + 1)

    def reshape(self, x, shape):
        return np.reshape(x, (self.size,) + tuple(shape))

    def zeros(self, shape, dtype):
        return np.zeros((self.size,) + tuple(shape), dtype)

    def ones(self, shape, dtype):
        return np.ones((self.size,) + tuple(shape), dtype)

    def lshape(self, x):
        return tuple(x.shape[1:])


# ---------------------------------------------------------------------------
# Mediated host channel: PUT/GET through a shared host-memory broker
# ---------------------------------------------------------------------------


@dataclass
class BrokerStats:
    """Operation counts of the host broker (the mediated-channel analogue of
    S3 request counts — what the price model bills)."""

    puts: int = 0
    gets: int = 0
    polls: int = 0  # GET attempts before data was present (pull channel)
    aborts: int = 0  # staged messages discarded by a cancelled exchange
    put_bytes: int = 0
    get_bytes: int = 0
    live_keys: int = 0
    peak_keys: int = 0


class HostBroker:
    """Shared host-memory key-value store backing :class:`HostTransport`.

    The paper's mediated channels (S3/DynamoDB/Redis) move every message
    through a rendezvous store: the sender PUTs under a key both sides can
    derive, the receiver polls and GETs.  This is the same object for the
    TPU setting — a host-RAM staging dict shared by all ranks of one
    process (multi-host deployments would back it with the real host
    interconnect; the interface is what the channel model prices)."""

    def __init__(self):
        self._store: dict[Any, np.ndarray] = {}
        self.stats = BrokerStats()

    def put(self, key, value: np.ndarray):
        if key in self._store:
            raise KeyError(f"broker key collision: {key!r}")
        self._store[key] = np.array(value, copy=True)
        self.stats.puts += 1
        self.stats.put_bytes += value.nbytes
        self.stats.live_keys = len(self._store)
        self.stats.peak_keys = max(self.stats.peak_keys, len(self._store))

    def get(self, key) -> np.ndarray:
        """One poll + one GET (pull semantics: the receiver asks)."""
        self.stats.polls += 1
        value = self._store.pop(key)
        self.stats.gets += 1
        self.stats.get_bytes += value.nbytes
        self.stats.live_keys = len(self._store)
        return value

    def discard(self, key) -> bool:
        """Drop a staged message without downloading it (cancelled exchange:
        no GET is billed, but the abort is counted).  Returns True iff the
        key was present."""
        present = self._store.pop(key, None) is not None
        if present:
            self.stats.aborts += 1
            self.stats.live_keys = len(self._store)
        return present


class HostTransport(SimTransport):
    """Mediated transport: lockstep like :class:`SimTransport`, but every
    exchange stages each message through a :class:`HostBroker` — sender PUT,
    receiver GET — so one logical exchange costs **two serialized hops**.
    The trace records both hops; ``ChannelSpec(hops=2)`` is the matching
    α-β model (every α and β is paid twice: HBM→host, host→HBM).

    Under the nonblocking contract the PUT happens at ``ppermute_start``
    (and merges into the open slot when issued behind pending requests);
    the GET happens at ``wait()`` and always serializes — so a depth-D
    pipelined exchange costs D+1 slots, not 2D, exactly what
    ``models.collective_time_ext`` prices for ``hops=2``."""

    def __init__(self, size: int, broker: HostBroker | None = None):
        super().__init__(size)
        self.broker = broker if broker is not None else HostBroker()
        self._seq = 0  # per-transport round counter namespacing broker keys

    def ppermute_start(self, x, perm: Perm) -> TransportRequest:
        pairs = list(perm)
        self._check_failures(pairs)
        self._seq += 1
        seq = self._seq
        per_msg = int(np.prod(x.shape[1:])) * x.dtype.itemsize
        for src, dst in pairs:  # upload hop (all senders in parallel)
            self.broker.put((id(self), seq, src, dst), x[src])
        sent = per_msg if pairs else 0
        self.trace.issue(sent, len(pairs))  # PUT hop

        def finish(out):
            for src, dst in pairs:  # download hop (all receivers in parallel)
                out[dst] = self.broker.get((id(self), seq, src, dst))
            self.trace.record(sent, len(pairs), overlap=False)  # GET hop
            self.trace.complete()
            return out

        def abort():
            # cancelled before the GET hop: discard the staged uploads so the
            # broker never leaks keys (and never collides on a regroup replay)
            for src, dst in pairs:
                self.broker.discard((id(self), seq, src, dst))
            self.trace.complete()

        return TransportRequest(np.zeros_like(x), on_wait=finish,
                                on_cancel=abort)


# ---------------------------------------------------------------------------
# Reduction operators (paper: "users can provide an arbitrary function
# object as a reduction operation")
# ---------------------------------------------------------------------------

OPS: dict[str, Callable] = {
    "add": lambda a, b: a + b,
    "max": lambda a, b: jnp.maximum(a, b) if isinstance(a, jax.Array) else np.maximum(a, b),
    "min": lambda a, b: jnp.minimum(a, b) if isinstance(a, jax.Array) else np.minimum(a, b),
    "prod": lambda a, b: a * b,
}


def resolve_op(op) -> Callable:
    if callable(op):
        return op
    try:
        return OPS[op]
    except KeyError:
        raise ValueError(f"unknown reduction op {op!r}; known: {sorted(OPS)}") from None
