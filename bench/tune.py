"""Measurements that set a cell's numbers, each in one process on the chip.

    python3 -m bench.tune --workload W knee --rates 0.2,0.3,0.4 --seconds 60
    python3 -m bench.tune --workload W readings --seeds 3 --control 3 \
        --seconds 51
    python3 -m bench.tune --workload W trace --layers 1 --seconds 1 \
        --out tests/bench/data/serve_trace.xplane.pb

``knee``: an open-loop sweep of arrival rates on one set of weights; for
each rate, the backlog (requests due and not finished) at the window's
start and end, completions per second, and the TTFT median.  The knee is
the highest rate whose backlog does not grow.

``readings``: the numbers the correctness limits are set from.  For each
of ``--seeds`` seeds, one whole run of the cell as ``bench.run`` makes it
(its rate, warm-up and window of ``--seconds``, its sample of finished
requests), with the served tokens read against the reference; on the
first ``--control`` seeds the fp8 control is read on the same positions.

``trace``: three short requests through the benchmark's spans and tracer,
with the configuration cut to ``--layers`` layers, written to ``--out``
(a small recorded trace for the tests).

Each line printed is one JSON object; the last one sums up.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import shutil
import sys
import time
from pathlib import Path

from bench import run as bench_run


def _setup(workload: str):
    bench = bench_run.load_benchmark()
    cell = bench_run.find_cell(bench, workload)
    sys.path.insert(0, str(bench_run.ROOT / "src"))
    devices, peaks = bench_run.require_chips(cell["chips"])
    bench_run.enable_compile_cache()
    from bench import model, traffic

    cfg_file = model.load(cell["config"])
    arch = model.arch(cell["config"], cfg_file)
    return cell, arch, model.engine_settings(cfg_file), traffic.load(cell["traffic"]), devices, peaks


def _engine(arch, settings, seed):
    import jax

    from bench import cell as cell_mod
    from bench import model, weights
    from repro.serve.engine import ServeEngine

    pcfg = model.program_config(arch)
    params = jax.block_until_ready(weights.program_params(arch, seed))
    engine = ServeEngine(pcfg, params, **settings)
    engine.warmup()
    cell_mod.warm_shapes(engine, settings["prefill_chunk"])
    return engine


def _drive(engine, mix, seed, vocab, warm_s, seconds, tail_s, marks=()):
    from bench import drive, traffic

    feeder = drive.Feeder(
        traffic.schedule(dict(mix, warm_s=warm_s, tail_s=tail_s), seed,
                         seconds, vocab),
        loop=mix["loop"], clients=mix.get("clients", 0), warm_s=warm_s,
        seconds=seconds, tail_s=tail_s, marks=marks)
    engine.step_hooks[:] = [feeder]
    engine.completed.clear()
    engine.queue.clear()  # what an earlier stopped drain left queued
    try:
        engine.run_until_drained()
    except drive.Stop:
        pass
    engine.step_hooks.clear()
    return feeder


def _backlog(recs, t):
    return sum(1 for r in recs if r.due <= t and (r.finished is None or r.finished > t))


def knee(args):
    from bench import window

    cell, arch, settings, mix, _, _ = _setup(args.workload)
    engine = _engine(arch, settings, args.seed)
    rows = []
    for rate in [float(x) for x in args.rates.split(",")]:
        d = _drive(engine, dict(mix, rate_per_s=rate), args.seed, arch.vocab,
                   args.warm, args.seconds, 0.0)
        w0, w1 = d.window
        done = [r for r in d.recs if r.finished is not None and w0 <= r.finished < w1]
        row = {"rate_per_s": rate, "backlog_start": _backlog(d.recs, w0),
               "backlog_end": _backlog(d.recs, w1),
               "finished_per_s": len(done) / (w1 - w0),
               "out_tok_s": window.out_tok_s((r.stamps for r in d.recs), w0, w1),
               "ttft_p50_ms": 1e3 * window.ttft_s(d.recs, w0, w1, w1, 50),
               "steps": d.w1.steps - d.w0.steps}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return {"knee": rows}


def readings(args):
    """Whole runs of the cell as ``bench.run`` makes them, one seed after
    another in this process; the first ``--control`` also read the fp8
    control on the same positions."""
    bench = bench_run.load_benchmark()
    cell = bench_run.find_cell(bench, args.workload)
    sys.path.insert(0, str(bench_run.ROOT / "src"))
    devices, peaks = bench_run.require_chips(cell["chips"])
    bench_run.enable_compile_cache()
    from bench import cell as cell_mod

    specs = bench_run.metric_specs(bench, args.workload, False)
    limits = bench_run.load_limits(args.workload)
    out = []
    for i in range(args.seeds):
        seed = args.seed + 7919 * i
        r = cell_mod.run(cell, specs, limits, seed=seed, seconds=args.seconds,
                         trace=False, t_start=time.time(), devices=devices,
                         peaks=peaks, trace_dir=None,
                         control=i < args.control)
        gc.collect()
        row = {"seed": seed, "correct": r["correct"], "checks": r["checks"],
               "control_fp8": r.get("control"),
               "metrics": {k: m["value"] for k, m in r["metrics"].items()}}
        out.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for k in limits:
        got = [r["checks"][k]["value"] for r in out
               if r["checks"][k]["value"] is not None]
        low = [r["control_fp8"][k] for r in out if r["control_fp8"]]
        summary[k] = {"program_max": max(got, default=None),
                      "control_min": min(low, default=None)}
    return {"readings": summary, "seeds": len(out)}


def trace(args):
    """Three short requests (prompts of 70, 5 and 20 tokens, 4 new tokens
    each: 64- and 8-wide scan steps, then decode steps) all due at once,
    traced for ``--seconds``; a last request due after the trace keeps
    the schedule open."""
    import jax
    import numpy as np

    from bench import drive, spans, traffic
    from bench import trace as trace_mod

    cell, arch, settings, mix, _, peaks = _setup(args.workload)
    arch = dataclasses.replace(arch, n_layers=args.layers)
    engine = _engine(arch, settings, args.seed)
    steps = []
    spans.instrument(engine, arch, peaks, steps)
    tracer = trace_mod.Tracer(bench_run.ROOT / ".bench_trace" / "sample")
    rng = np.random.default_rng(args.seed)
    specs = [traffic.Spec(i, 0.0, rng.integers(0, arch.vocab, n).astype(np.int32), 4)
             for i, n in enumerate((70, 5, 20))]
    specs.append(traffic.Spec(3, 3600.0, specs[1].prompt, 4))
    feeder = drive.Feeder(specs, loop="open", clients=0, warm_s=0.0,
                          seconds=args.seconds, tail_s=0.0,
                          marks=((0.0, tracer.start), (args.seconds, tracer.stop)))
    engine.step_hooks[:] = [spans.hook_span(feeder)]
    try:
        engine.run_until_drained()
    except drive.Stop:
        pass
    files = sorted(tracer.path.glob("**/*.xplane.pb"))
    shutil.copy(files[-1], args.out)
    s = tracer.summary(spans.STEP_LABELS)
    spans.attribute(steps, s)
    del feeder, engine
    jax.clear_caches()
    return {"trace": str(args.out), "bytes": Path(args.out).stat().st_size,
            "window_s": s.window_s, "busy_s": s.busy_s,
            "steps": [(st.kind, st.device_s) for st in steps if st.device_s],
            "breakdown": s.breakdown()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2_718_281_828)
    sub = ap.add_subparsers(dest="cmd", required=True)
    k = sub.add_parser("knee")
    k.add_argument("--rates", required=True)
    k.add_argument("--seconds", type=float, default=60.0)
    k.add_argument("--warm", type=float, default=20.0)
    r = sub.add_parser("readings")
    r.add_argument("--seeds", type=int, default=3)
    r.add_argument("--control", type=int, default=3)
    r.add_argument("--seconds", type=float, default=51.0)
    t = sub.add_parser("trace")
    t.add_argument("--layers", type=int, default=1)
    t.add_argument("--seconds", type=float, default=1.0)
    t.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    try:
        result = {"knee": knee, "readings": readings, "trace": trace}[args.cmd](args)
    except bench_run.NoChip as e:
        bench_run.log(f"refused: {e}")
        return 3
    print(json.dumps({"workload": args.workload, **result}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
