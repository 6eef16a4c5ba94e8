"""The repository's benchmark: one process, one cell, one run.

``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
from the root of a checkout. ``BENCHMARK.json`` names the cells; everything a
cell needs is found from those names (see ``harness/manifest.py``)."""
