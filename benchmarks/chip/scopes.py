"""Device time by the program's named scopes.

An op's scope is JAX's op-name path of its HLO instruction, which the trace
keeps in the op's metadata (:func:`xspace.tf_ops`), e.g.
``jit(local_step)/transpose(jvp())/while/body/closed_call/checkpoint/attention/flash_bwd/exp``.
A transformation wraps the scope it meets first (``jvp(head_loss)``,
``transpose(jvp(attention))``), so :func:`segments` unwraps each segment down
to the names inside; an op is under a scope wherever the scope's segments
stand in its path.

A reader is given the loaded trace (``trace.Context.trace``) and the profile
it was loaded from (``trace.Context.profile``, the directory that the runner
profiled into), and :func:`op_paths` reads the paths from that same
``.xplane.pb``.
"""

from __future__ import annotations

import functools

from . import trace, xspace


def _split(path: str) -> list:
    """``path`` split at each ``/`` outside parentheses."""
    out, depth, start = [], 0, 0
    for i, c in enumerate(path):
        depth += (c == "(") - (c == ")")
        if c == "/" and depth == 0:
            out.append(path[start:i])
            start = i + 1
    out.append(path[start:])
    return out


@functools.lru_cache(maxsize=None)
def segments(path: str) -> tuple:
    """The names in ``path``, each transformation's wrapper taken off:
    ``jit(step)/transpose(jvp(attention))/exp`` -> ``("step", "attention",
    "exp")``; an empty wrapper (``jvp()``) names nothing."""
    out = ()
    for seg in _split(path):
        if seg.endswith(")") and "(" in seg:
            out += segments(seg[seg.index("(") + 1:-1])
        elif seg:
            out += (seg,)
    return out


def under(path: str, name: str) -> bool:
    """Whether the op-name ``path`` lies under the scope ``name`` (which may
    hold ``/``, as ``fmi/reduce_scatter``)."""
    want = tuple(name.split("/"))
    have = segments(path)
    n = len(want)
    return any(have[i:i + n] == want for i in range(len(have) - n + 1))


def attach(t, paths: dict) -> dict:
    """Keep ``paths`` (``{plane: {op name: path}}``) as trace ``t``'s."""
    t.op_paths = paths
    return paths


def op_paths(ctx) -> dict:
    """``{plane: {op name: path}}`` of ``ctx.trace``, read once from the
    profile it was loaded from; ``{}`` where the context names none."""
    t = ctx.trace
    if hasattr(t, "op_paths"):
        return t.op_paths
    if ctx.profile is None:
        return attach(t, {})
    return attach(t, xspace.tf_ops(trace.xplane_file(ctx.profile),
                                   keep=set(t.ops).__contains__))


def ms_per_step(ctx, select, over: str = "mean") -> float | None:
    """Device milliseconds per step of the leaf ops in the window whose
    op-name path ``select(path)`` picks, the ``mean`` over the cell's devices
    or the ``max``; ``None`` where no op is picked."""
    steps = ctx.window["steps"]
    paths = op_paths(ctx)
    per = [ctx.trace.op_time(p, lambda o, of=paths.get(p, {}): select(of.get(o.name, "")))
           for p in ctx.trace.ops]
    if not steps or not any(n for _, n in per):
        return None
    seconds = [s for s, _ in per]
    total = max(seconds) if over == "max" else sum(seconds) / len(seconds)
    return 1e3 * total / steps
