"""A small copy of a cell, for tests on the CPU."""

import json

from benchmarks.chip import catalog


def cell(workload: str = "yi6b-train-s4k", chips: int | None = None, rows: int = 2,
         seq: int = 128) -> catalog.Cell:
    """``workload`` with its model cut to a few hundred thousand parameters
    (every kind of leaf kept) and its batch to ``rows`` x ``seq``, with the
    cell's own limits."""
    full = catalog.load_cell(workload)
    c = dict(full.config, hidden_size=256, intermediate_size=512, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=1000)
    t = json.loads(json.dumps(full.traffic))
    t.update(rows_per_chip=rows, seq_len=seq)
    return catalog.Cell(name=f"{workload}-tiny", chips=chips or full.chips, config=c,
                        traffic=t, limits=full.limits, end_to_end=full.end_to_end,
                        per_layer=full.per_layer)
