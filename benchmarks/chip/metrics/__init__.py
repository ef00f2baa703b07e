"""Per-layer metric readers, one file each, found by the metric's name.

Each module has ``read(ctx: trace.Context) -> float | None``.  A reader that
finds nothing to read returns ``None``, and the metric is left out of the
result; it never returns 0 for a share of a roofline or a peak.
"""
