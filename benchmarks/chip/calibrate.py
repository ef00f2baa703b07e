"""Read the check's numbers over many seeds, for the control and the faults.

    python3 benchmarks/chip/calibrate.py --workload yi6b-train-s4k \
        --seeds 12 --control-seeds 3 --fault-seeds 3 [--out FILE]

On the cell's own chips and at its own sizes, in one process: for each seed
the program's checked first steps against the plain reference (the sound
readings, whose largest sets a limit's lower end); on the first seeds the
control (the reference computed in float8, put in the program's place) and
each fault of ``faults.py`` that the cell can have (their smallest sets the
upper end).  A state left unchanged reads 1 in the norms by construction; it
is run for what the losses read.  Prints one JSON line per reading and writes them all to ``--out``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    from benchmarks.chip import catalog, compare, faults, harness, traffic
    from benchmarks.chip.runners import lm_train
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = catalog.load_cell(args.workload)
    devices = harness.check_devices(cell.chips)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    names = ["control", "half_batch", "state_unchanged"] + (
        ["no_exchange"] if cell.chips > 1 else [])
    rows = []

    def emit(kind, seed, g, t0):
        row = {"kind": kind, "seed": seed, "s": round(time.perf_counter() - t0, 3),
               **{n: g[n][0] for n in compare.NAMES},
               "at": {n: g[n][1] for n in compare.NAMES}}
        rows.append(row)
        print(json.dumps(row), flush=True)

    run = lm_train.Run(cell, devices)
    broken = {}
    for name in names[1:]:
        with faults.ALL[name]():
            broken[name] = lm_train.Run(cell, devices)
            broken[name].start(seeds[0])  # traced under the fault
            broken[name].first_steps()
            broken[name].free()
    ref = lm_train.default_reference(cell, devices)
    ctl = lm_train.default_reference(cell, devices, "fp8")
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        run.start(seed)
        prog = run.first_steps()
        run.free()
        batches = [traffic.batch(cell.traffic, cell.sizes.vocab, seed, k, cell.chips)
                   for k in range(cell.traffic["checked_steps"])]
        r = ref.run(seed, batches)
        emit("sound", seed, compare.gaps(prog, r), t0)
        if i < args.control_seeds:
            t0 = time.perf_counter()
            emit("control", seed, compare.gaps(ctl.run(seed, batches), r), t0)
        if i < args.fault_seeds:
            for name, b in broken.items():
                t0 = time.perf_counter()
                b.start(seed)
                g = compare.gaps(b.first_steps(), r)
                b.free()
                emit(name, seed, g, t0)
    summary = {"workload": cell.name, "config": cell.config["name"],
               "device_kind": devices[0].device_kind}
    for kind in ["sound"] + names:
        got = [r for r in rows if r["kind"] == kind]
        if got:
            summary[kind] = {n: (min(r[n] for r in got), max(r[n] for r in got))
                             for n in compare.NAMES}
    print(json.dumps(summary), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"rows": rows, "summary": summary}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
