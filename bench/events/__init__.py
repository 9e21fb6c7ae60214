"""Seeded event generators, copied from the program's ``repro.data`` so
that a change there cannot change the benchmark's inputs.

``POOLS`` maps a configuration's ``events`` name to its generator:
``make(n, seed) -> (x [n, T, in] float32, labels [n])``.
"""

from bench.events.quickdraw import quickdraw_dataset
from bench.events.tracks import flavor_tagging_dataset

POOLS = {"flavor_tagging": flavor_tagging_dataset,
         "quickdraw": quickdraw_dataset}
