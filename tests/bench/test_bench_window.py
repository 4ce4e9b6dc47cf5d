"""Metric arithmetic on synthetic stamps, the feeder's timing, the refusal
of a CPU, and a CPU rehearsal of a whole run at a tiny size."""

import json
import types

import numpy as np
import pytest

from bench import drive, metrics, run, window
from tests.bench.tiny import rehearse


def traffic_spec(i):
    from bench import traffic

    return traffic.Spec(i, 0.0, np.ones(4, np.int32), 1)


def rec(due, stamps, submitted=None, uid=0):
    return drive.Rec(uid, due, due if submitted is None else submitted,
                     types.SimpleNamespace(generated=[], done=False),
                     list(stamps))


def test_rate_is_taken_over_the_whole_window():
    # 30 tokens in the first second of a 10 s window: 3 tokens/s, not 30
    stamps = [[0.01 * i for i in range(30)], [10.5]]
    assert window.out_tok_s(stamps, 0.0, 10.0) == pytest.approx(3.0)


def test_p95_pools_all_gaps_rather_than_medians_of_pieces():
    fast = [0.01 * i for i in range(101)]         # 100 gaps of 10 ms
    slow = [0.0, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0]     # 6 gaps of 1 s
    gaps = window.gaps_in_window([fast, slow], 0.0, 10.0)
    assert len(gaps) == 106
    p95 = window.percentile(gaps, 95)
    assert p95 == pytest.approx(float(np.percentile(gaps, 95)))
    # the mean of per-request p95s would read about 0.5 s
    assert 0.01 < p95 < 1.0
    # a gap counts when its later token falls in the window
    assert window.gaps_in_window([[9.0, 11.0], [-1.0, 0.5]], 0.0, 10.0) == [1.5]


def test_a_request_never_served_ranks_above_every_served_one():
    recs = [rec(1.0, [1.2]), rec(2.0, [2.3]), rec(3.0, [])]
    # served 0.2 and 0.3; the unserved waited only 0.1 by the end, yet ranks last
    assert window.ttft_s(recs, 0.0, 10.0, 3.1, 50) == pytest.approx(0.3)
    assert window.ttft_s(recs, 0.0, 10.0, 3.1, 100) == pytest.approx(0.3)
    recs.append(rec(4.0, []))
    assert window.ttft_s(recs, 0.0, 10.0, 14.0, 100) == pytest.approx(11.0)
    # a stall cannot shorten the median
    assert window.ttft_s(recs, 0.0, 10.0, 14.0, 50) >= 0.3


def test_requests_due_outside_the_window_are_left_out():
    recs = [rec(-1.0, [50.0]), rec(1.0, [1.5]), rec(10.0, [99.0])]
    assert window.ttft_s(recs, 0.0, 10.0, 20.0, 50) == pytest.approx(0.5)


class FakeEngine:
    def __init__(self):
        self.queue, self.steps, self.busy_slot_steps = [], 0, 0
        self.submitted = []

    def submit(self, req):
        self.submitted.append(req)
        self.queue.append(req)


def test_open_loop_times_a_request_from_its_due_time():
    from bench import traffic

    specs = [traffic.Spec(0, 1.0, np.ones(4, np.int32), 2),
             traffic.Spec(1, 2.0, np.ones(4, np.int32), 2)]
    clock = iter([0.0, 1.7, 5.0])
    d = drive.Feeder(specs, loop="open", clients=0, warm_s=100.0, seconds=1.0,
                     tail_s=0.0, clock=lambda: next(clock), sleep=lambda s: None)
    eng = FakeEngine()
    d(eng, True)                  # t=0: traffic starts, nothing due yet
    assert eng.submitted == []
    d(eng, True)                  # t=1.7: the first was due at 1.0
    r = d.recs[0]
    assert (r.due, r.submitted) == (1.0, 1.7)
    eng.submitted[0].generated.append(5)
    d(eng, True)                  # t=5.0: its first token, and the second due at 2.0
    assert r.stamps == [5.0]
    assert window.ttft_s(d.recs, 0.0, 10.0, 5.0, 0) == pytest.approx(4.0)
    assert d.recs[1].due == 2.0


def test_every_seed_offers_the_window_the_same_work():
    from bench import traffic

    mix = dict(loop="open", rate_per_s=0.5, warm_s=20.0, tail_s=10.0,
               prompt=dict(dist="lognormal", median=768, sigma=0.5, lo=256, hi=1792),
               output=dict(dist="uniform", lo=16, hi=64))
    seen = []
    for seed in (1, 2**31 + 11):
        specs = traffic.schedule(mix, seed, 51.0, 1000)
        win = [s for s in specs if 20.0 <= s.at_s < 71.0]
        seen.append((sorted(len(s.prompt) for s in win),
                     sorted(s.max_new for s in win)))
        assert len(win) == 26 and specs[0].at_s == 0.0
        assert all(a.at_s <= b.at_s for a, b in zip(specs, specs[1:]))
    assert seen[0] == seen[1]
    assert traffic.schedule(mix, 5, 51.0, 1000)[3].prompt.tolist() == \
        traffic.schedule(mix, 5, 51.0, 1000)[3].prompt.tolist()


def test_every_block_of_a_closed_loop_pool_holds_the_same_work():
    from bench import traffic

    mix = dict(loop="closed", clients=4, pool=64, block=16,
               prompt=dict(dist="uniform", lo=16, hi=64),
               output=dict(dist="lognormal", median=384, sigma=0.4, lo=256,
                           hi=768))
    blocks = set()
    orders = set()
    for seed in (7, 2**31 + 11):
        specs = traffic.schedule(mix, seed, 51.0, 1000)
        assert len(specs) == 64
        for i in range(0, 64, 16):
            blk = specs[i:i + 16]
            blocks.add((tuple(sorted(len(s.prompt) for s in blk)),
                        tuple(sorted(s.max_new for s in blk))))
            orders.add(tuple(s.max_new for s in blk))
    assert len(blocks) == 1 and len(orders) > 1
    with pytest.raises(ValueError, match="multiple"):
        traffic.schedule(dict(mix, pool=40), 7, 51.0, 1000)


def test_closed_loop_sends_when_the_last_reply_finished():
    from bench import traffic

    specs = [traffic.Spec(i, 0.0, np.ones(4, np.int32), 1) for i in range(3)]
    clock = iter([0.0, 2.0, 3.0])
    d = drive.Feeder(specs, loop="closed", clients=2, warm_s=100.0, seconds=1.0,
                     tail_s=0.0, clock=lambda: next(clock), sleep=lambda s: None)
    eng = FakeEngine()
    d(eng, True)
    assert [r.due for r in d.recs] == [0.0, 0.0]
    eng.submitted[0].generated.append(1)
    eng.submitted[0].done = True
    d(eng, True)                  # t=2: client 0's reply seen; it sends at once
    assert [r.due for r in d.recs] == [0.0, 0.0, 2.0]
    assert d.recs[2].submitted == 2.0


def test_time_spent_writing_the_trace_does_not_cut_the_tail():
    """A request due at the window's end still gets its first token when
    the trace's stop mark takes longer than ``tail_s``."""
    specs = [traffic_spec(i) for i in range(3)]
    now = [0.0]

    def slow_stop(t):
        now[0] += 30.0  # the profiler writes its trace

    d = drive.Feeder(specs, loop="closed", clients=1, warm_s=0.0, seconds=10.0,
                     tail_s=5.0, marks=((10.0, slow_stop),),
                     clock=lambda: now[0], sleep=lambda s: None)
    eng = FakeEngine()
    d(eng, True)                  # t=0: the window opens, uid 0 sent
    eng.submitted[0].generated.append(1)
    eng.submitted[0].done = True
    now[0] = 9.5
    d(eng, True)                  # uid 0 done; uid 1 due at 9.5, in the window
    now[0] = 10.0
    d(eng, True)                  # the stop mark fires and takes 30 s
    now[0] = 41.0                 # 31 s past the window's end, 1 s of tail
    d(eng, True)
    eng.submitted[1].generated.append(1)
    now[0] = 42.0
    with pytest.raises(drive.Stop):
        d(eng, True)              # its first token is stamped, then Stop
    assert d.recs[1].due == 9.5 and d.recs[1].stamps == [42.0]


def test_an_idle_engine_sleeps_until_the_next_due_time():
    from bench import traffic

    specs = [traffic.Spec(0, 3.0, np.ones(4, np.int32), 2),
             traffic.Spec(1, 9.0, np.ones(4, np.int32), 2)]
    now = [0.0]
    slept = []

    def sleep(s):
        slept.append(s)
        now[0] += s

    d = drive.Feeder(specs, loop="open", clients=0, warm_s=100.0, seconds=1.0,
                     tail_s=0.0, clock=lambda: now[0], sleep=sleep)
    eng = FakeEngine()
    d(eng, False)
    assert slept == [3.0] and len(eng.submitted) == 1


def test_a_cpu_is_refused_with_no_result_line(capsys):
    with pytest.raises(run.NoChip):
        run.require_chips(1)
    rc = run.main(["--workload", "qwen3-1.7b.gen", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "no TPU" in out.err


def test_a_device_kind_without_peaks_is_refused():
    from bench import peaks

    with pytest.raises(KeyError):
        peaks.for_device_kind("TPU v4")


def test_every_metric_reader_matches_its_benchmark_entry():
    bench = run.load_benchmark()
    for cell in bench["workloads"]:
        for trace in (False, True):
            specs = run.metric_specs(bench, cell["name"], trace)
            assert specs, (cell["name"], trace)
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    assert names <= {p.stem for p in metrics.HERE.glob("*.py")}


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_cpu_rehearsal_prints_the_contract_line(loop):
    out = rehearse(loop)
    line = json.loads(json.dumps(out))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["metrics"]) == {"out_tok_s", "ttft_p50_ms", "itl_p95_ms",
                                    "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["checks"]) == {"logit_gap", "malformed_answers"}
