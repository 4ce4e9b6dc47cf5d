"""The device's idle time split by what the engine's host loop was doing.

The serve engine marks each scheduling iteration of its continuous drain
with a ``serve.step`` span (``jax.profiler.StepTraceAnnotation``) and the
iteration's phases with flat children in this order: ``serve.hooks``,
``serve.schedule``, ``serve.h2d``, ``serve.dispatch``, ``serve.select``
(holding ``serve.sync``, the one blocking transfer) and ``serve.commit``.

:func:`reduce` cuts every idle interval of the device (the gaps between
the operation intervals, as :func:`bench.trace.reduce` finds them) at the
edges of these spans.  Each piece is named by the innermost ``serve.*``
span covering it: ``serve.sync`` inside ``serve.select``, ``serve.step``
for the iteration's own time between its phases.  A piece that no
``serve.*`` span covers keeps :func:`bench.trace.reduce`'s rule: the
benchmark span holding its midpoint, else :data:`bench.trace.HOST_ENGINE`.
A trace with no ``serve.*`` spans is split exactly as that function splits
it, to the digit.

The trace is read a second time, from the file the run's tracer wrote
(:func:`of`), since the benchmark's :class:`bench.trace.Summary` keeps
neither the host spans nor the device's programs.
"""

from __future__ import annotations

import bisect
import dataclasses
from collections import Counter, defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from jax.profiler import ProfileData

from bench import log
from bench import trace as trace_mod

PREFIX = "serve."
STEP = "serve.step"
DISPATCH = "serve.dispatch"
SYNC = "serve.sync"
#: where ``bench.run`` keeps each cell's trace (one directory per cell)
TRACES = Path(__file__).resolve().parents[1] / ".bench_trace"

Span = Tuple[str, int, int]  # (name, start ns, end ns) on the host clock


@dataclasses.dataclass
class EngineTrace:
    #: the engine's spans that start in the traced window, by start
    spans: List[Span]
    #: device-0 program executions that start in the window, by name
    programs: Dict[str, int]
    #: device idle seconds by engine phase (or benchmark span), as
    #: :attr:`bench.trace.Summary.idle`
    idle: Dict[str, float]

    def host_step_s(self) -> Optional[float]:
        """Mean over the iterations that dispatched a device step of the
        iteration's time less its wait on the device (``serve.sync``)."""
        dispatch = sorted(s for n, s, _ in self.spans if n == DISPATCH)
        sync = sorted((s, e - s) for n, s, e in self.spans if n == SYNC)
        sync_at = [s for s, _ in sync]
        waited = [0]
        for _, d in sync:
            waited.append(waited[-1] + d)
        own = []
        for n, s, e in self.spans:
            if n != STEP:
                continue
            if bisect.bisect_left(dispatch, s) == bisect.bisect_left(dispatch, e):
                continue  # an iteration that found no work
            i, j = bisect.bisect_left(sync_at, s), bisect.bisect_left(sync_at, e)
            own.append((e - s) - (waited[j] - waited[i]))
        return sum(own) / len(own) / 1e9 if own else None

    def programs_per_step(self) -> Optional[float]:
        """Device programs run per device step the engine dispatched."""
        steps = sum(1 for n, _, _ in self.spans if n == DISPATCH)
        return sum(self.programs.values()) / steps if steps else None


def segments(spans: List[Span]) -> List[Span]:
    """Nested spans of one thread -> non-overlapping pieces in time order,
    each named by the innermost span covering it."""
    out: List[Span] = []
    stack: List[Tuple[str, int]] = []  # (name, end) of the open spans
    cur = 0

    def close(t):
        nonlocal cur
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > cur:
                out.append((name, cur, end))
                cur = end

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        close(s)
        if stack:
            e = min(e, stack[-1][1])
            if s > cur:
                out.append((stack[-1][0], cur, s))
        cur = s
        stack.append((name, e))
    close(float("inf"))
    return out


def _pieces(a: int, b: int, segs: List[Span], seg_starts: List[int]):
    """[a, b) cut at the edges of ``segs``: (name or None, start, end)."""
    out = []
    cur = a
    j = max(bisect.bisect_right(seg_starts, a) - 1, 0)
    while j < len(segs) and segs[j][1] < b:
        name, s, e = segs[j]
        j += 1
        if e <= cur:
            continue
        if s > cur:
            out.append((None, cur, s))
            cur = s
        end = min(e, b)
        out.append((name, cur, end))
        cur = end
    if cur < b:
        out.append((None, cur, b))
    return out


def reduce(pd: ProfileData) -> EngineTrace:
    """The engine's spans, device 0's programs and the idle split of a
    loaded trace, over the same window and busy intervals as
    :func:`bench.trace.reduce`."""
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    spans, serve, enq = [], [], {}
    for line in host.lines:
        for e in line.events:
            s, t = int(e.start_ns), int(e.start_ns + e.duration_ns)
            if e.name.startswith(PREFIX):
                serve.append((e.name, s, t))
            elif e.name.startswith(trace_mod.SPAN_PREFIX):
                spans.append((e.name, s, t))
            elif e.name == trace_mod.ENQUEUE:
                rid = dict(e.stats).get("run_id")
                if rid is not None:
                    enq[int(rid)] = s
    spans.sort(key=lambda s: s[1])
    win = [s for s in spans if s[0] == trace_mod.WINDOW]
    if not win:
        raise ValueError("trace has no traced-window span")
    lo, hi = win[0][1], win[0][2]
    spans = [s for s in spans if s[0] != trace_mod.WINDOW and lo <= s[1] < hi]
    span_starts = [s[1] for s in spans]
    serve = sorted((s for s in serve if lo <= s[1] < hi), key=lambda s: s[1])
    segs = segments(serve)
    seg_starts = [s[1] for s in segs]

    def fallback(a, b):
        mid = (a + b) // 2
        j = bisect.bisect_right(span_starts, mid) - 1
        return spans[j][0] if j >= 0 and mid < spans[j][2] else trace_mod.HOST_ENGINE

    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    if not devices:
        raise ValueError("trace has no TPU device plane")
    n_dev = len(devices)
    idle: Dict[str, float] = defaultdict(float)
    programs: Counter = Counter()
    for k, dev in enumerate(devices):
        lines = {ln.name: ln for ln in dev.lines}
        mods = []
        for e in lines["XLA Modules"].events:
            rid = dict(e.stats).get("run_id")
            mods.append((int(e.start_ns), trace_mod._program_name(e.name),
                         None if rid is None else int(rid)))
        offs = [enq[r] - s for s, _, r in mods if r in enq]
        offset = max(offs) if offs else 0
        if k == 0:
            programs.update(n for s, n, _ in mods if lo <= s + offset < hi)
        ivs = []
        for e in lines["XLA Ops"].events:
            s, d = int(e.start_ns) + offset, int(e.duration_ns)
            if lo <= s < hi:
                ivs.append((s, s + d))
        busy = trace_mod._clip(trace_mod._union(ivs), lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            if not segs:  # the same sum, term for term, as bench.trace
                idle[fallback(a, b)] += (b - a) / 1e9 / n_dev
                continue
            for name, s, t in _pieces(a, b, segs, seg_starts):
                idle[name or fallback(s, t)] += (t - s) / 1e9 / n_dev
    return EngineTrace(spans=serve, programs=dict(programs), idle=dict(idle))


def _load(summary) -> Optional[ProfileData]:
    """The trace the run's tracer wrote: the newest file under
    :data:`TRACES` written after the traced window closed whose window
    span lasts ``summary.window_s``."""
    files = []
    for f in TRACES.glob("**/*.xplane.pb"):
        mtime = f.stat().st_mtime
        if mtime >= summary.t1 - 1.0:
            files.append((mtime, f))
    for _, f in sorted(files, reverse=True):
        pd = ProfileData.from_file(str(f))
        host = next((p for p in pd.planes if p.name == "/host:CPU"), None)
        if host is None:
            continue
        for line in host.lines:
            for e in line.events:
                if e.name != trace_mod.WINDOW:
                    continue
                # the window's length as bench.trace.reduce computes it
                lo, hi = int(e.start_ns), int(e.start_ns + e.duration_ns)
                if (hi - lo) / 1e9 == summary.window_s:
                    return pd
    return None


def of(ctx) -> Optional[EngineTrace]:
    """This run's engine trace, or None: an untraced run, or a program
    whose trace holds no ``serve.*`` span.  The first call also names the
    run's :class:`bench.trace.Summary` idle time by the engine's phases, so
    that the breakdown printed with the result line reads the split."""
    summary = ctx.trace
    if summary is None:
        return None
    if not hasattr(summary, "engine"):
        summary.engine = None
        pd = _load(summary)
        if pd is None:
            log(f"no trace file under {TRACES} matches the traced window")
            return None
        et = reduce(pd)
        if et.spans:
            summary.engine = et
            summary.idle = et.idle
    return summary.engine
