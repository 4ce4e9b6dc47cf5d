"""The serve engine's profiler spans, recorded on the CPU at a tiny size.

Each iteration of the continuous drains is one ``serve.step`` span (with
its ``step_num``) holding the flat phases ``serve.hooks``,
``serve.schedule``, ``serve.h2d``, ``serve.dispatch``, ``serve.select``
and ``serve.commit``, in that order, and ``serve.sync`` (the blocking
token transfer) inside ``serve.select``.  Iterations that admit or finish
requests carry their uids as span metadata.  Tracing changes no served
token."""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import model, weights
from repro.serve.engine import Request, ServeEngine
from tests.bench.tiny import TINY

PHASES = ["serve.hooks", "serve.schedule", "serve.h2d", "serve.dispatch",
          "serve.select", "serve.commit"]
#: prompts of 20, 5 and 11 tokens: 16- and 8-wide scan steps, then decode
PROMPTS = (20, 5, 11)
NEW = 4


@pytest.fixture(scope="module")
def tiny():
    cfg = model.program_config(TINY)
    return cfg, weights.program_params(cfg, 12345)


def _serve(tiny, prefill_chunk, trace_dir=None):
    cfg, params = tiny
    engine = ServeEngine(cfg, params, max_batch=2, max_len=64, block_size=8,
                         prefill_chunk=prefill_chunk, kv_dtype="bf16")
    rng = np.random.default_rng(7)
    for uid, n in enumerate(PROMPTS):
        engine.submit(Request(uid, rng.integers(0, TINY.vocab, n),
                              max_new_tokens=NEW))
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    try:
        done = engine.run_until_drained()
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return engine, {uid: list(r.generated) for uid, r in done.items()}


def _spans(trace_dir):
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    return sorted(((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                    dict(e.stats)) for line in host.lines for e in line.events
                   if e.name.startswith("serve.")), key=lambda s: (s[1], -s[2]))


def _inside(outer, spans):
    return [s for s in spans if outer[1] <= s[1] and s[2] <= outer[2]
            and s is not outer]


@pytest.mark.parametrize("prefill_chunk", [16, 1])
def test_each_iteration_is_a_step_span_of_flat_phases(tiny, tmp_path,
                                                      prefill_chunk):
    engine, _ = _serve(tiny, prefill_chunk, tmp_path)
    spans = _spans(tmp_path)
    steps = [s for s in spans if s[0] == "serve.step"]
    # one span per iteration: every device step, plus the last look at an
    # empty queue
    assert len(steps) == engine.steps + 1
    assert [s[3]["step_num"] for s in steps] == list(range(engine.steps + 1))
    dispatched = 0
    for step in steps:
        inner = _inside(step, spans)
        phases = [s for s in inner if s[0] != "serve.sync"]
        names = [s[0] for s in phases]
        if "serve.dispatch" not in names:  # the drain's last iteration
            assert names == PHASES[:2]
            continue
        dispatched += 1
        assert names == PHASES
        # flat: no phase overlaps the next
        assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
        syncs = [s for s in inner if s[0] == "serve.sync"]
        select = phases[PHASES.index("serve.select")]
        assert len(syncs) == 1 and _inside(select, spans) == syncs
    assert dispatched == engine.steps
    # no span outside an iteration
    assert all(any(st[1] <= s[1] and s[2] <= st[2] for st in steps)
               for s in spans if s[0] != "serve.step")


def test_admissions_and_finishes_carry_uids(tiny, tmp_path):
    _serve(tiny, 16, tmp_path)
    spans = _spans(tmp_path)
    admitted = [str(s[3]["admitted"]).split() for s in spans
                if s[0] == "serve.schedule" and "admitted" in s[3]]
    finished = [str(s[3]["finished"]).split() for s in spans
                if s[0] == "serve.commit" and "finished" in s[3]]
    # two slots: uids 0 and 1 at once, 2 when the first slot frees
    assert admitted == [["0", "1"], ["2"]]
    assert sorted(sum(finished, [])) == ["0", "1", "2"]
    # iterations that admit or finish nothing carry no metadata
    plain = [s for s in spans if s[0] in ("serve.schedule", "serve.commit")
             and not s[3]]
    assert len(plain) > len(admitted) + len(finished)


def test_tracing_changes_no_served_token(tiny, tmp_path):
    _, traced = _serve(tiny, 16, tmp_path)
    _, plain = _serve(tiny, 16)
    # the token-by-token drain serves the same streams (the chunked one
    # hands the row gather the same lengths it hands the scan step)
    _, by_token = _serve(tiny, 1)
    assert traced == plain == by_token
    assert sorted(traced) == [0, 1, 2]
    assert all(len(g) == NEW for g in traced.values())
