"""Profiler trace of the window's last seconds, reduced to metrics.

What the TPU trace holds (``jax.profiler``, read back with
``ProfileData``): per device a plane ``/device:TPU:<i>`` with the lines
``XLA Modules`` (one event per program execution, with its ``run_id``)
and ``XLA Ops`` (one event per operation executed); and a host plane
``/host:CPU`` whose lines hold the benchmark's ``TraceAnnotation`` spans
and the runtime's ``DoEnqueueProgram`` events, which carry the
``run_id`` of the program they put on the device.  Device and host
timestamps are on clocks that differ by a constant; the offset is taken
as the largest (enqueue - device start) over programs, since no program
starts before it was enqueued.

- busy: the union of the operation intervals, per device, averaged over
  the devices; window: the benchmark's ``bench.traced_window`` span.
- a step's device time: the execution of the first program enqueued after
  its span began (``run_id`` order).
- breakdown: the operations that took most device time (named by the
  step or program that ran them), and the device's idle time by the span
  the host was in while the device waited (summed per span name).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import shutil
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import jax
from jax.profiler import ProfileData, ProfileOptions, TraceAnnotation

WINDOW = "bench.traced_window"
SPAN_PREFIX = "bench."
ENQUEUE = "DoEnqueueProgram"
#: label of idle time during which the host was in no benchmark span
HOST_ENGINE = "engine host code (scheduling, slot and block bookkeeping, transfers)"
TOP = 10
#: control-flow operations whose events span the operations of their body
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Summary:
    t0: float  # traced window on the host's ``time.time()`` clock
    t1: float
    window_s: float
    busy_s: float
    #: device seconds of the program each step span dispatched, by the
    #: span's start (ns on the trace's host clock), in span order
    step_device_s: Dict[int, float]
    #: (span name, start ns) of the step spans inside the window, in order
    step_spans: List[Tuple[str, int]]
    ops: Dict[str, float]
    idle: Dict[str, float]

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        idle = sorted(self.idle.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv: List[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _op_name(text: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _program_name(text: str) -> str:
    """``jit__lambda(1520...)`` -> ``jit__lambda``."""
    return text.split("(", 1)[0]


def reduce(pd: ProfileData, host_t0: float, host_t1: float,
           step_labels: Dict[str, str]) -> Summary:
    """Reduce a loaded trace.  ``host_t0``/``host_t1`` bound the traced
    window on ``time.time()``; ``step_labels`` maps the span names that
    wrap a step call to the label their program gets in the breakdown."""
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    spans, enq = [], {}
    for line in host.lines:
        for e in line.events:
            if e.name.startswith(SPAN_PREFIX):
                spans.append((e.name, int(e.start_ns),
                              int(e.start_ns + e.duration_ns)))
            elif e.name == ENQUEUE:
                rid = dict(e.stats).get("run_id")
                if rid is not None:
                    enq[int(rid)] = int(e.start_ns)
    spans.sort(key=lambda s: s[1])
    win = [s for s in spans if s[0] == WINDOW]
    if not win:
        raise ValueError("trace has no traced-window span")
    lo, hi = win[0][1], win[0][2]
    spans = [s for s in spans if s[0] != WINDOW and lo <= s[1] < hi]
    span_starts = [s[1] for s in spans]

    # each step span -> the first program enqueued after it began
    order = sorted((t, r) for r, t in enq.items())
    times = [t for t, _ in order]
    step_rid, label = {}, {}
    for name, s, _ in spans:
        if name in step_labels:
            j = bisect.bisect_left(times, s)
            if j < len(order):
                step_rid[s] = order[j][1]
                label[order[j][1]] = step_labels[name]

    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    if not devices:
        raise ValueError("trace has no TPU device plane")
    n_dev = len(devices)
    busy_total = 0.0
    ops: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    step_device_s: Dict[int, float] = {}
    for k, dev in enumerate(devices):
        lines = {ln.name: ln for ln in dev.lines}
        mods = []
        for e in lines["XLA Modules"].events:
            rid = dict(e.stats).get("run_id")
            mods.append((int(e.start_ns), int(e.duration_ns),
                         _program_name(e.name), None if rid is None else int(rid)))
        offs = [enq[r] - s for s, _, _, r in mods if r in enq]
        offset = max(offs) if offs else 0
        mods = sorted((s + offset, d, n, r) for s, d, n, r in mods)
        if k == 0:
            dur = {r: d / 1e9 for _, d, _, r in mods}
            step_device_s = {s: dur[r] for s, r in step_rid.items() if r in dur}
        starts = [m[0] for m in mods]
        ivs = []
        for e in lines["XLA Ops"].events:
            s, d = int(e.start_ns) + offset, int(e.duration_ns)
            if not (lo <= s < hi):
                continue
            ivs.append((s, s + d))
            op = _op_name(e.name)
            if op.split(".")[0] in CONTAINERS:
                continue  # its time is its body's ops, counted one by one
            i = bisect.bisect_right(starts, s) - 1
            prog = "?"
            if i >= 0 and s < starts[i] + mods[i][1]:
                prog = label.get(mods[i][3], mods[i][2])
            ops[f"{prog}/{op}"] += d / 1e9 / n_dev
        busy = _clip(_union(ivs), lo, hi)
        busy_total += sum(b - a for a, b in busy) / 1e9
        # idle between busy intervals, named by the host span it fell in
        # (the benchmark's spans on the host thread never nest)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) // 2
            j = bisect.bisect_right(span_starts, mid) - 1
            name = spans[j][0] if j >= 0 and mid < spans[j][2] else HOST_ENGINE
            idle[name] += (b - a) / 1e9 / n_dev
    return Summary(
        t0=host_t0, t1=host_t1, window_s=(hi - lo) / 1e9,
        busy_s=busy_total / n_dev, step_device_s=step_device_s,
        step_spans=[(n, s) for n, s, _ in spans if n in step_labels],
        ops=dict(ops), idle=dict(idle))


class Tracer:
    """Starts and stops ``jax.profiler`` from the feeder's marks and reduces
    what it wrote.  The trace directory is emptied first: one trace per run."""

    def __init__(self, path: Path):
        self.path = Path(path)
        self._span: Optional[TraceAnnotation] = None
        self.host_t0: Optional[float] = None
        self.host_t1: Optional[float] = None

    def start(self, now: float) -> None:
        import time

        shutil.rmtree(self.path, ignore_errors=True)
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.path), profiler_options=opts)
        self._span = TraceAnnotation(WINDOW)
        self._span.__enter__()
        self.host_t0 = time.time()

    def stop(self, now: float) -> None:
        import time

        self.host_t1 = time.time()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self, step_labels: Dict[str, str]) -> Summary:
        files = sorted(glob.glob(os.path.join(self.path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not files:
            raise ValueError(f"no trace under {self.path}")
        return reduce(ProfileData.from_file(files[-1]), self.host_t0,
                      self.host_t1, step_labels)
