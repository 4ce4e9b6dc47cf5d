"""The benchmark's own host spans around the engine's step calls (traced
runs only), and the attribution of device time to them.

The engine has no spans of its own yet, so the benchmark wraps the
engine instance's jitted steps (``_prefill_paged``, ``_decode_paged``) and
its token selection (``_sampler.select``) in
``jax.profiler.TraceAnnotation`` spans, and records what each step was fed
(positions, lengths, which slots yield a token) for the work count.  The
step's device time is the device execution that the call dispatched: the
first program to start on the device after the span began.
"""

from __future__ import annotations

import time
from typing import List

import numpy as np
from jax.profiler import TraceAnnotation

from bench import work
from bench.metrics import Step

PREFILL = "bench.prefill_step"
DECODE = "bench.decode_step"
SELECT = "bench.select_tokens"
HOOK = "bench.traffic_hook"
KIND = {PREFILL: "prefill", DECODE: "decode"}


def _feed(engine, lengths):
    """(positions, lengths, yields) of the live slots before this step."""
    live = engine._live
    pos = np.array(live["positions"], copy=True)
    reqs = live["slot_req"]
    lens = np.where([r is not None for r in reqs], lengths, 0)
    yields = [r is not None and int(p) + int(n) == len(r.prompt) + len(r.generated)
              for r, p, n in zip(reqs, pos, lens)]
    return pos, lens, yields


def instrument(engine, arch, peaks, steps: List[Step]) -> None:
    """Wrap the engine's step calls in spans and append a :class:`Step`
    (work counted from what it was fed) to ``steps`` for each call."""
    prefill, decode, select = (engine._prefill_paged, engine._decode_paged,
                               engine._sampler.select)

    def note(kind, lengths):
        pos, lens, yields = _feed(engine, lengths)
        flops, nbytes = work.step_work(arch, pos.tolist(), lens.tolist(), yields)
        steps.append(Step(kind, time.time(), flops, nbytes,
                          work.least_seconds(flops, nbytes, peaks)))

    def prefill_step(p, tok, cache, pos, bt, lens):
        note("prefill", np.asarray(lens))
        with TraceAnnotation(PREFILL):
            return prefill(p, tok, cache, pos, bt, lens)

    def decode_step(p, tok, cache, pos, bt):
        note("decode", np.ones(tok.shape[0], np.int32))
        with TraceAnnotation(DECODE):
            return decode(p, tok, cache, pos, bt)

    def select_tokens(*a, **kw):
        with TraceAnnotation(SELECT):
            return select(*a, **kw)

    engine._prefill_paged = prefill_step
    engine._decode_paged = decode_step
    engine._sampler.select = select_tokens


def hook_span(feeder):
    def hook(engine, busy):
        with TraceAnnotation(HOOK):
            return feeder(engine, busy)
    return hook


#: the step spans and their programs' names in the breakdown
STEP_LABELS = {PREFILL: "prefill_step", DECODE: "decode_step"}


def attribute(steps: List[Step], summary) -> None:
    """Give each step recorded inside the traced window the device time of
    the program its span dispatched.  Records and spans pair one to one in
    order: the tracer starts and stops between steps, and each record is
    made just before its span opens."""
    traced = [s for s in steps if summary.t0 <= s.t <= summary.t1]
    if len(traced) != len(summary.step_spans):
        raise ValueError(f"{len(traced)} steps recorded in the traced window, "
                         f"{len(summary.step_spans)} step spans in the trace")
    for step, (name, start) in zip(traced, summary.step_spans):
        if KIND[name] != step.kind:
            raise ValueError("trace spans and step records disagree")
        step.device_s = summary.step_device_s.get(start)
