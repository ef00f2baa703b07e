"""Benchmark harness — one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows (one block per benchmark).
Mapping to the paper:

    channels     -> Table 1/2  (channel α-β characterization)
    price        -> Table 3/4  (price of performance; exact reproduction)
    collectives  -> Figure 5   (collective scaling vs workers, per channel)
    fmi_vs_xla   -> Figure 6   (FMI direct algorithms vs provider built-ins)
    overhead     -> Figure 7   (platform overhead: opaque vs locality-aware)
    kmeans       -> Figure 8/9 (distributed K-Means case study: time + cost)
    overlap      -> blocking vs bucketed-overlap gradient sync sweep
                    (docs/nonblocking.md; the PR-3 scheduler claim)
    elastic      -> time-to-recover vs world size and bucket depth
                    (docs/elasticity.md; kill-rank -> quiesce/regroup/reshard)
    serving      -> continuous-batching tokens/s + modeled $/1M tokens vs
                    world and batch (docs/serving.md)
    fleet        -> autoscaled fleet vs fixed fleets: tok/s, p99, shed
                    rate, $/1M tokens vs offered load (docs/fleet.md)
    kernels      -> Pallas kernel throughput vs naive references
    roofline     -> §Roofline reader over the dry-run artifacts
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys

BENCHES = [
    "channels",
    "price",
    "collectives",
    "fmi_vs_xla",
    "overhead",
    "kmeans",
    "overlap",
    "elastic",
    "serving",
    "fleet",
    "kernels",
    "roofline",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None)
    args = ap.parse_args()
    names = [args.only] if args.only else BENCHES
    # the mesh benches (fmi_vs_xla, kmeans) run in this process; on the CPU
    # they need host devices, which must be requested before JAX starts
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        try:
            mod = importlib.import_module(f"benchmarks.bench_{name}")
            for row in mod.run():
                n, us, derived = row
                print(f"{n},{us if us is None else f'{us:.2f}'},{derived}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},ERROR,{type(e).__name__}: {e}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
