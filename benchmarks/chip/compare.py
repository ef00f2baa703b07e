"""The comparison that decides ``correct``: the program's first steps against
the plain reference's, number by number, each against its own limit.

* ``loss_gap``: over the checked steps, the largest gap between the program's
  loss and the reference's, relative to the reference's.
* ``first_loss_gap``: the same for the first step alone, a forward pass from
  the seed's weights on which no earlier step's round-off has built up.
* ``grad_gap``: over the leaves, the largest gap between the norm of the
  program's first gradient (as its optimizer took it, read from the first
  moments after one step) and the reference's, relative to the larger of the
  reference's norm of that leaf and of the median leaf.
* ``update_gap``: the same for the norm of each leaf's change over the
  checked steps.  Leaves whose reference gradient is under a thousandth of the
  median leaf's move by round-off alone and are left out.

A cell's limits file names the numbers it compares; a number whose sound
readings and whose control's and faults' readings do not separate is left
out of it.
"""

from __future__ import annotations

import math
import statistics

NAMES = ("loss_gap", "first_loss_gap", "grad_gap", "update_gap")
NEGLIGIBLE_GRAD = 1e-3


def _worst_leaf(prog: dict, ref: dict, names) -> tuple[float, str]:
    med = statistics.median(ref[n] for n in names)
    worst = (0.0, "")
    for n in names:
        den = max(ref[n], med)
        gap = abs(prog[n] - ref[n]) / den if den > 0 else abs(prog[n] - ref[n])
        if not math.isfinite(prog[n]):
            gap = math.inf
        worst = max(worst, (gap, n))
    return worst


def gaps(prog, ref) -> dict:
    """Each compared number, with the leaf or step where it was worst."""
    per_step = [(abs(p - r) / abs(r) if math.isfinite(p) else math.inf, k)
                for k, (p, r) in enumerate(zip(prog.losses, ref.losses))]
    loss = max(per_step)
    grad = _worst_leaf(prog.grad_norms, ref.grad_norms, list(ref.grad_norms))
    med = statistics.median(ref.grad_norms.values())
    moved = [n for n, g in ref.grad_norms.items() if g >= NEGLIGIBLE_GRAD * med]
    update = _worst_leaf(prog.change_norms, ref.change_norms, moved)
    return {"loss_gap": loss, "first_loss_gap": per_step[0], "grad_gap": grad,
            "update_gap": update}


def compared(limits: dict) -> list:
    return [n for n in NAMES if n in limits]


def within(g: dict, limits: dict) -> bool:
    return all(g[n][0] <= limits[n] for n in compared(limits))


def report(g: dict, limits: dict, finite_window: bool) -> dict:
    """Short names, each with its number and its limit (and where it was worst)."""
    out = {n: {"value": g[n][0], "limit": limits[n], "at": g[n][1]} for n in compared(limits)}
    out["window_losses_finite"] = {"value": int(finite_window), "limit": 1}
    return out
