"""The device's idle time split by the engine's spans (``bench.host_gaps``),
on hand-made spans and on two small traces recorded on a TPU v5e: the
first from a program without engine spans (which the split must leave
exactly as ``bench.trace.reduce`` names it), the second from the engine
with its spans (``bench.tune trace --layers 2 --seconds 0.205``, a window
that closes while the engine still serves, shrunk by
``bench.trace_sample``)."""

import os
import shutil
import types
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import host_gaps, spans, trace
from bench.metrics import load

DATA = Path(__file__).resolve().parent / "data"
PLAIN = DATA / "serve_trace.xplane.pb"
SPANS = DATA / "serve_trace_spans.xplane.pb"
PHASES = ("serve.hooks", "serve.schedule", "serve.h2d", "serve.dispatch",
          "serve.select", "serve.commit")


def test_nested_spans_become_flat_pieces_named_by_the_innermost():
    spans_ = [("serve.step", 0, 100), ("serve.hooks", 5, 20),
              ("serve.select", 40, 70), ("serve.sync", 50, 60),
              ("serve.commit", 70, 90)]
    assert host_gaps.segments(spans_) == [
        ("serve.step", 0, 5), ("serve.hooks", 5, 20), ("serve.step", 20, 40),
        ("serve.select", 40, 50), ("serve.sync", 50, 60),
        ("serve.select", 60, 70), ("serve.commit", 70, 90),
        ("serve.step", 90, 100)]
    # a child that outlasts its parent is cut at the parent's end
    assert host_gaps.segments([("a", 0, 10), ("b", 5, 15), ("c", 20, 30)]) == [
        ("a", 0, 5), ("b", 5, 10), ("c", 20, 30)]


def test_an_interval_is_cut_at_the_edges_of_the_pieces():
    segs = [("x", 10, 20), ("y", 20, 30), ("z", 50, 60)]
    starts = [s for _, s, _ in segs]
    assert host_gaps._pieces(0, 70, segs, starts) == [
        (None, 0, 10), ("x", 10, 20), ("y", 20, 30), (None, 30, 50),
        ("z", 50, 60), (None, 60, 70)]
    assert host_gaps._pieces(15, 25, segs, starts) == [("x", 15, 20),
                                                       ("y", 20, 25)]
    assert host_gaps._pieces(32, 48, segs, starts) == [(None, 32, 48)]


def test_host_step_time_leaves_out_the_wait_and_idle_iterations():
    et = host_gaps.EngineTrace(spans=[
        ("serve.step", 0, 10_000_000), ("serve.dispatch", 2_000_000, 3_000_000),
        ("serve.sync", 4_000_000, 8_000_000),
        ("serve.step", 10_000_000, 16_000_000),
        ("serve.dispatch", 11_000_000, 12_000_000),
        ("serve.step", 16_000_000, 17_000_000)],  # found no work
        programs={"jit_serve_decode_step": 2, "jit_gather": 4}, idle={})
    assert et.host_step_s() == pytest.approx((6e-3 + 6e-3) / 2)
    assert et.programs_per_step() == 3.0
    empty = host_gaps.EngineTrace(spans=[], programs={}, idle={})
    assert empty.host_step_s() is None and empty.programs_per_step() is None


@pytest.fixture(scope="module")
def plain():
    return ProfileData.from_file(str(PLAIN))


def test_a_trace_without_engine_spans_keeps_the_benchmarks_reduction(plain):
    s = trace.reduce(plain, 0.0, 1.0, spans.STEP_LABELS)
    et = host_gaps.reduce(plain)
    assert et.spans == [] and et.idle == s.idle  # to the digit
    # the reduction of this trace, pinned
    assert s.window_s == 0.168616533 and s.busy_s == 0.133546711
    assert s.idle == {trace.HOST_ENGINE: 0.03506982199999994}
    assert s.step_spans == [
        ("bench.prefill_step", 47599875), ("bench.prefill_step", 163383643),
        ("bench.decode_step", 180786251), ("bench.decode_step", 191667070),
        ("bench.decode_step", 203089139)]
    assert s.step_device_s == {
        47599875: 0.111782298, 163383643: 0.014389921,
        180786251: 0.001974062, 191667070: 0.0019729, 203089139: 0.001972077}
    assert len(s.ops) == 240
    b = s.breakdown()
    assert b["device_ops"][0] == ["prefill_step/convert_bitcast_fusion.6",
                                  0.05937053800000001]
    assert b["idle_gaps"] == [[trace.HOST_ENGINE, 0.03506982199999994]]
    # the parent's programs: both steps and the argmax share one name
    assert et.programs["jit__lambda"] == 10 and sum(et.programs.values()) == 94


def _ctx(summary):
    return types.SimpleNamespace(trace=summary)


def _stage(src, tmp_path, monkeypatch, pd):
    """The trace as a run leaves it: a file under the trace directory,
    written after the traced window closed."""
    monkeypatch.setattr(host_gaps, "TRACES", tmp_path)
    dst = tmp_path / "cell" / "plugins" / "profile" / "t" / "h.xplane.pb"
    dst.parent.mkdir(parents=True)
    shutil.copy(src, dst)
    summary = trace.reduce(pd, 0.0, 1.0, spans.STEP_LABELS)
    summary.t1 = os.path.getmtime(dst) - 0.5
    return summary


def test_readers_leave_out_a_program_without_spans(plain, tmp_path,
                                                   monkeypatch):
    summary = _stage(PLAIN, tmp_path, monkeypatch, plain)
    idle = dict(summary.idle)
    for name in ("host_step_ms", "programs_per_step"):
        assert load(name).read(_ctx(summary)) is None
        assert load(name).read(_ctx(None)) is None
    assert summary.idle == idle


@pytest.fixture(scope="module")
def traced():
    return ProfileData.from_file(str(SPANS))


@pytest.fixture(scope="module")
def engine_trace(traced):
    return host_gaps.reduce(traced)


def test_the_spans_trace_is_small():
    assert SPANS.stat().st_size <= 1 << 20


def test_the_split_covers_the_idle_time(traced, engine_trace):
    s = trace.reduce(traced, 0.0, 1.0, spans.STEP_LABELS)
    idle = sum(engine_trace.idle.values())
    assert idle == pytest.approx(s.window_s - s.busy_s, abs=1e-6)
    named = sum(v for k, v in engine_trace.idle.items()
                if k.startswith(host_gaps.PREFIX))
    assert named >= 0.9 * idle
    assert set(engine_trace.idle) <= {*PHASES, host_gaps.STEP, host_gaps.SYNC,
                                      *spans.KIND, spans.SELECT, spans.HOOK,
                                      trace.HOST_ENGINE}


def test_the_engine_trace_reads_steps_and_programs(traced, engine_trace):
    assert engine_trace.host_step_s() > 0
    # the step, the argmax and the row gather's 16 eager programs
    assert engine_trace.programs_per_step() == 18.0
    names = set(engine_trace.programs)
    assert {"jit_serve_decode_step", "jit_serve_prefill_step",
            "jit_serve_select_greedy"} <= names
    assert "jit__lambda" not in names
    # the benchmark's step spans, now inside serve.dispatch, still get the
    # program each one dispatched
    s = trace.reduce(traced, 0.0, 1.0, spans.STEP_LABELS)
    kinds = [spans.KIND[n] for n, _ in s.step_spans]
    assert kinds == ["prefill"] * 2 + ["decode"] * 3
    assert len(set(s.step_device_s.values())) == 5


def test_readers_name_the_breakdown_by_the_engine_phases(
        traced, engine_trace, tmp_path, monkeypatch):
    summary = _stage(SPANS, tmp_path, monkeypatch, traced)
    ctx = _ctx(summary)
    ms = load("host_step_ms").read(ctx)
    assert ms == pytest.approx(1e3 * engine_trace.host_step_s())
    assert load("programs_per_step").read(ctx) == pytest.approx(
        engine_trace.programs_per_step())
    assert summary.idle == engine_trace.idle
    labels = [k for k, _ in summary.breakdown()["idle_gaps"]]
    assert labels[0].startswith(host_gaps.PREFIX)
