"""Chip benchmark of the serve path: cells, traffic, metric readers.

``python -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the accelerator it is started on.
"""

import sys


def log(msg: str) -> None:
    """A progress line on standard error (standard output is the result)."""
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
