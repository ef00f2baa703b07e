"""Reduce a profiler trace of the measured window to device time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote: the ops that
ran on each of the cell's devices (the ``XLA Ops`` line of each device plane,
where a ``while`` op spans the ops of its body) and the harness's own host
spans (``bench.*`` annotations), which the TPU trace puts on the same clock.
The window is the stretch from the first host span to the last.  Busy time is
the union of the device's op intervals inside it; what is left is idle, and
each idle gap is labelled by the host span that was open at its middle.  Op
times are those of leaf ops, which contain no other op.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


def union(intervals) -> list:
    """Sorted, merged ``(start, end)`` intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


@dataclasses.dataclass
class Trace:
    ops: dict  # device plane name -> [Op], in start order
    spans: list  # host spans: [(name without prefix, start_ns, end_ns)]

    @property
    def lo(self) -> float:
        return min(a for _, a, _ in self.spans)

    @property
    def hi(self) -> float:
        return max(b for _, _, b in self.spans)

    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def in_window(self, plane: str) -> list:
        """The leaf ops of ``plane`` that overlap the window."""
        return [o for o in leaves(self.ops[plane]) if o.end_ns > self.lo and o.start_ns < self.hi]

    def busy_intervals(self, plane: str) -> list:
        return clip(union((o.start_ns, o.end_ns) for o in self.ops[plane]), self.lo, self.hi)

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        per = [sum(b - a for a, b in self.busy_intervals(p)) for p in self.ops]
        return sum(per) / len(per) / 1e9

    def idle_gaps(self, plane: str) -> list:
        """``(start, end)`` of each stretch of the window with no op running."""
        gaps, t = [], self.lo
        for a, b in self.busy_intervals(plane):
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < self.hi:
            gaps.append((t, self.hi))
        return gaps

    def host_at(self, t: float) -> str:
        """The innermost host span open at ``t``, or ``"no span"``."""
        open_ = [(a, n) for n, a, b in self.spans if a <= t <= b]
        return max(open_)[1] if open_ else "no span"

    def op_time(self, plane: str, select) -> tuple[float, int]:
        """(seconds, count) of the ops on ``plane`` in the window that
        ``select(op)`` picks."""
        ops = [o for o in self.in_window(plane) if select(o)]
        return sum(o.dur_ns for o in ops) / 1e9, len(ops)

    def breakdown(self, top: int = 10) -> dict:
        """The leaf ops that took most time and the longest idle gaps, on
        the first device, each as ``[name, seconds]``."""
        plane = sorted(self.ops, key=_device_number)[0]
        by_name = collections.Counter()
        for o in self.in_window(plane):
            by_name[short_name(o.name)] += o.dur_ns / 1e9
        gaps = sorted(self.idle_gaps(plane), key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[n, s] for n, s in by_name.most_common(top)],
            "idle_gaps": [[self.host_at((a + b) / 2), (b - a) / 1e9] for a, b in gaps],
        }


def leaves(ops: list) -> list:
    """The ops that contain no other op (``ops`` in start order)."""
    out = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt.start_ns >= o.end_ns or nxt.end_ns > o.end_ns:
            out.append(o)
    return out


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(...), kind=kLoop, calls=...`` ->
    ``%fusion.12 fusion``: the instruction and its opcode."""
    head, _, rest = name.partition(" = ")
    depth = 0
    for i, c in enumerate(rest):  # skip the result type, which may be a tuple
        depth += (c in "([{") - (c in ")]}")
        if depth == 0 and c == " ":
            return f"{head} {rest[i + 1:].split('(', 1)[0]}"
    return head


def xplane_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str, n_devices: int) -> Trace:
    """The trace at ``path`` (a file, or a directory the profiler wrote),
    keeping the first ``n_devices`` device planes."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = xplane_file(path)
    pd = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs = [Op(e.name, e.start_ns, e.end_ns) for e in line.events]
                    ops[plane.name] = sorted(evs, key=lambda o: (o.start_ns, -o.end_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name[len(SPAN_PREFIX):], e.start_ns, e.end_ns)
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
    keep = sorted(ops, key=_device_number)[:n_devices]
    if not keep:
        raise ValueError(f"{path}: no device plane with an {OPS_LINE!r} line")
    if not spans:
        raise ValueError(f"{path}: no {SPAN_PREFIX}* host spans")
    return Trace({p: ops[p] for p in keep}, sorted(spans, key=lambda s: s[1]))


def _device_number(plane: str) -> int:
    digits = "".join(c for c in plane.rsplit(":", 1)[-1] if c.isdigit())
    return int(digits or 0)


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""

    trace: Trace
    cell: object
    spans: object
    window: dict
    tokens_per_s: float
    peaks: dict
