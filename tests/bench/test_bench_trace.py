"""The trace reduction on a small trace recorded on a TPU v5e: three
requests served by qwen3-1.7b cut to 2 layers (64- and 8-wide scan steps,
then decode steps), each step call wrapped in the benchmark's spans.  The
file keeps what the reduction reads (the spans, the runtime's
``DoEnqueueProgram`` events, the device's ``XLA Modules`` and ``XLA Ops``
lines), so that it stays under 1 MB."""

from pathlib import Path

import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import spans, trace

DATA = Path(__file__).resolve().parent / "data" / "serve_trace.xplane.pb"


@pytest.fixture(scope="module")
def pd():
    return ProfileData.from_file(str(DATA))


@pytest.fixture(scope="module")
def summary(pd):
    return trace.reduce(pd, 0.0, 1.0, spans.STEP_LABELS)


def _device_ops(pd):
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in dev.lines if ln.name == "XLA Ops")
    return [(int(e.start_ns), int(e.duration_ns)) for e in line.events]


def test_the_recorded_trace_is_small():
    assert DATA.stat().st_size <= 1 << 20


def test_busy_is_the_union_of_op_intervals(pd, summary):
    assert 0 < summary.busy_s <= summary.window_s
    # an independent count: mark every microsecond some op covers
    ops = _device_ops(pd)
    lo = min(s for s, _ in ops)
    hi = max(s + d for s, d in ops)
    grid = np.zeros((hi - lo) // 1000 + 2, bool)
    for s, d in ops:
        grid[(s - lo) // 1000:(s + d - lo) // 1000 + 1] = True
    covered = grid.sum() * 1e-6
    # the reduction clips to the traced window; the grid rounds outward
    assert summary.busy_s <= covered + 1e-3
    assert summary.busy_s >= 0.9 * covered - 1e-3


def test_idle_is_the_rest_of_the_window(summary):
    idle = sum(summary.idle.values())
    assert idle == pytest.approx(summary.window_s - summary.busy_s, abs=1e-6)


def test_each_step_span_gets_the_program_it_dispatched(summary):
    kinds = [spans.KIND[n] for n, _ in summary.step_spans]
    assert "prefill" in kinds and "decode" in kinds
    assert set(summary.step_device_s) == {s for _, s in summary.step_spans}
    t = {k: [summary.step_device_s[s] for n, s in summary.step_spans
             if spans.KIND[n] == k] for k in ("prefill", "decode")}
    assert all(v > 0 for v in t["prefill"] + t["decode"])
    # a scan of up to 64 cells outlasts a single-token decode step
    assert max(t["prefill"]) > max(t["decode"])
    # no two spans were given the same program
    assert len(set(summary.step_device_s.values())) > 1


def test_breakdown_names_steps_and_host_spans(summary):
    b = summary.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    secs = [v for _, v in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert any(n.startswith(("prefill_step/", "decode_step/"))
               for n, _ in b["device_ops"])
    assert not any(n.split("/")[1].startswith("while") for n, _ in b["device_ops"])
    labels = {n for n, _ in b["idle_gaps"]}
    assert labels <= {spans.PREFILL, spans.DECODE, spans.SELECT, spans.HOOK,
                      trace.HOST_ENGINE}
