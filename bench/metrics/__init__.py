"""Metric readers, one file per metric, found by the metric's name.

Each ``<name>.py`` declares ``LAYER``, ``UNIT``, ``SOURCE``, ``MOVES`` and
``BETTER`` as ``BENCHMARK.json`` lists them, and ``read(ctx)``, which takes
the metric from a :class:`Context` and returns a number, or None when the
run holds nothing to read (the metric is then left out of the line).
"""

from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from types import ModuleType
from typing import Any, List, Optional

HERE = Path(__file__).resolve().parent
FIELDS = ("LAYER", "UNIT", "SOURCE", "MOVES", "BETTER")


@dataclasses.dataclass
class Step:
    """One call of a jitted serve step, seen from the benchmark's wrapper."""

    kind: str  # "prefill" | "decode"
    t: float  # host time of the dispatch
    flops: int
    nbytes: int
    least_s: float
    device_s: Optional[float] = None  # from the trace, when traced


@dataclasses.dataclass
class Context:
    arch: Any
    peaks: Any
    max_batch: int
    seconds: float
    setup_s: float
    recs: list  # bench.drive.Rec
    w0: float
    w1: float
    end: float  # when the run stopped watching requests
    c0: Any  # bench.drive.Counters at the window's start
    c1: Any  # ... and at its end
    steps: List[Step] = dataclasses.field(default_factory=list)
    trace: Any = None  # bench.trace.Summary of the traced part, if any

    def due_in_window(self):
        return [r for r in self.recs if self.w0 <= r.due < self.w1]

    def traced_steps(self, kind: str) -> List[Step]:
        return [s for s in self.steps if s.kind == kind and s.device_s]


def load(name: str) -> ModuleType:
    path = HERE / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no metric reader {path}")
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in FIELDS if not hasattr(mod, f)]
    if missing or not hasattr(mod, "read"):
        raise ValueError(f"metric reader {path} lacks {missing or ['read']}")
    return mod
