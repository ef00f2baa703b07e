"""The on-chip benchmark of the FMI training path (see ``BENCHMARK.json``).

``run.py`` runs one cell; ``harness.py`` drives the program's step;
``refs/`` holds the plain references, ``metrics/`` the per-layer readers,
``configs/``, ``traffic/`` and ``limits/`` the data of each cell.
"""
