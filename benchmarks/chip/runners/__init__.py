"""One runner per kind of traffic, found by the traffic file's ``kind``.

``runners/<kind>.py`` has ``run_cell(cell, seed, seconds, trace, t_start,
devices) -> dict``, which sets the cell up, measures it and checks it, and
returns the object of the run's last line.  A new kind of cell (serving, a
collective alone) is a new runner here, with its traffic files and its plain
reference; no file that is there needs an edit.
"""
