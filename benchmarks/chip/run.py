"""Run one benchmark cell on the chips of this machine and print its result.

    python3 benchmarks/chip/run.py --workload yi6b-train-s4k --seed 7 \
        --seconds 10 --trace 0

One cell in one process, driven by the runner of its kind
(``runners/<kind>.py``): set-up (weights from the seed, compile, the checked
first steps), a measured window of ``--seconds``, then the check against the
plain reference.  The last line of standard output is one JSON object; with
``--trace 0`` its metrics are the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics, read from a profiler trace of the window.  The numbers
the check compared, each beside its limit, are the last lines of standard
error and the last key of the result.

Without a TPU, or with fewer chips than the cell asks for, it exits with code
2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.chip import catalog, harness

    cell = catalog.load_cell(args.workload)
    runner = catalog.runner(cell)
    imported = time.perf_counter() - T_START
    try:
        devices = harness.check_devices(cell.chips)
    except (harness.NoChip, KeyError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(f"setup imports={imported:.3f} devices={time.perf_counter() - T_START:.3f}",
          file=sys.stderr)
    result = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             T_START, devices)
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
