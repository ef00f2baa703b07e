"""What every runner shares: the look for chips, the host spans, the device's
memory, and the reading of the per-layer metrics from a trace.

A runner (``runners/<kind>.py``, chosen by the traffic file's ``kind``) drives
one kind of cell; it calls these to report in one form whatever it runs.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import jax

from . import catalog


class NoChip(RuntimeError):
    """The run found no TPU, or fewer chips than the cell asks for."""


def check_devices(chips: int) -> list:
    """The cell's devices; raises :class:`NoChip` without enough TPUs, and
    ``KeyError`` for a device kind that has no published peaks."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devices)}")
    catalog.peaks(devices[0].device_kind)
    return devices[:chips]


@dataclasses.dataclass
class Spans:
    """The harness's own host spans, on the host clock and, when a trace is
    taken, also in the profiler's trace."""

    records: list = dataclasses.field(default_factory=list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        self.records.append((name, t0, time.perf_counter_ns()))

    def total_ms(self, name: str) -> float:
        return sum(b - a for n, a, b in self.records if n == name) / 1e6


def memory_peak_bytes(devices) -> int | None:
    """The peak on the fullest chip.  The TPU allocator counts the buffers
    that live between programs as in use, and reserves a program's
    temporaries apart, so the peak is the sum of the two peaks.  On a TPU v5e
    it matches the compiled step's ``memory_analysis().peak_memory_in_bytes``
    to within 0.5%."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(st["peak_bytes_in_use"] + st.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


def per_layer(cell: catalog.Cell, ctx) -> dict:
    """Each per-layer metric of the cell that its reader finds in ``ctx``."""
    out = {}
    for m in cell.per_layer:
        value = catalog.metric_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
