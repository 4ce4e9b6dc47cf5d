"""One run of one cell, after the device has been found: set-up, warm
traffic, the measured window, the metrics and the correctness check."""

from __future__ import annotations

import gc
import time
from typing import Optional

import jax
import numpy as np

from bench import check, drive, log, model, spans, traffic, weights
from bench import metrics as M
from bench import trace as trace_mod

#: prompt lengths of the shape warm-up: one drain each, so that the chunked
#: drain meets every scan width it can dispatch (and the 1-wide decode path)
#: with only this request in flight
WARM_PROMPTS = (1, 2, 4, 8, 16, 32, 64)


def warm_shapes(engine, prefill_chunk: int) -> None:
    """Serve one tiny request per dispatch width, one drain at a time, so
    that every program and host-side op the drain can call is compiled
    (or loaded from the cache) before the traffic starts."""
    from repro.serve.engine import Request

    for i, n in enumerate(p for p in WARM_PROMPTS if p <= prefill_chunk):
        engine.submit(Request(uid=-1 - i, prompt=np.ones(n, np.int32),
                              max_new_tokens=2))
        engine.run_until_drained()
    engine.completed.clear()


def _served(feeder, w0: float):
    """Requests the drain finished, first token at or after the window's
    start when there are any such, else all finished ones."""
    done = [r for r in feeder.recs if r.req.done]
    late = [r for r in done if r.stamps and r.stamps[0] >= w0]
    return [check.Served(r.uid, r.req.prompt, list(r.req.generated))
            for r in (late or done)]


def _malformed(feeder, vocab: int) -> int:
    bad = 0
    for r in feeder.recs:
        g = r.req.generated
        if (r.req.done and len(g) != r.req.max_new_tokens) or any(
                not 0 <= t < vocab for t in g):
            bad += 1
    return bad


def run(cell: dict, specs, limits: dict, *, seed: int, seconds: float,
        trace: bool, t_start: float, devices, peaks, trace_dir,
        arch: Optional[model.Arch] = None, mix: Optional[dict] = None,
        settings: Optional[dict] = None, control: bool = False) -> dict:
    """The cell's result line as a dict.  ``arch``, ``mix`` and ``settings``
    replace the cell's own files (a rehearsal at a small size).  With
    ``control`` the fp8 control is also read on the compared positions,
    under ``"control"`` (for ``bench.tune readings``; no run of the
    benchmark reads it)."""
    from repro.serve.engine import ServeEngine

    cfg_file = model.load(cell["config"])
    arch = arch or model.arch(cell["config"], cfg_file)
    settings = settings or model.engine_settings(cfg_file)
    mix = mix or traffic.load(cell["traffic"])
    pcfg = model.program_config(arch)
    weights.check_tree(arch, pcfg)

    params = jax.block_until_ready(weights.program_params(arch, seed))
    engine = ServeEngine(pcfg, params, **settings)
    engine.warmup()
    warm_shapes(engine, settings["prefill_chunk"])
    log(f"warm at {time.time() - t_start:.3f} s")

    steps = []
    marks = ()
    tracer = None
    if trace:
        tracer = trace_mod.Tracer(trace_dir)
        spans.instrument(engine, arch, peaks, steps)
        marks = ((seconds - mix["trace_s"], tracer.start),
                 (seconds, tracer.stop))
    feeder = drive.Feeder(
        traffic.schedule(mix, seed, seconds, arch.vocab), loop=mix["loop"],
        clients=mix.get("clients", 0), warm_s=mix["warm_s"], seconds=seconds,
        tail_s=mix["tail_s"], marks=marks)
    engine.add_step_hook(spans.hook_span(feeder) if trace else feeder)
    try:
        engine.run_until_drained()
    except drive.Stop:
        pass
    end = time.time()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    w0, w1 = feeder.window
    summary = None
    if tracer is not None:
        summary = tracer.summary(spans.STEP_LABELS)
        spans.attribute(steps, summary)
    ctx = M.Context(
        arch=arch, peaks=peaks, max_batch=settings["max_batch"],
        seconds=seconds, setup_s=w0 - t_start, recs=feeder.recs, w0=w0,
        w1=w1, end=end, c0=feeder.w0, c1=feeder.w1, steps=steps,
        trace=summary)
    metrics = {}
    for m, mod in specs:
        v = mod.read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    due = ctx.due_in_window()
    failed = sum(1 for r in due if not r.stamps)
    malformed = _malformed(feeder, arch.vocab)
    picked = check.sample(_served(feeder, w0), mix["check"]["requests"], seed)

    # the program's state goes before the reference runs on the device
    engine.step_hooks.clear()
    del engine, params, feeder, ctx
    gc.collect()
    t_ref = time.time()
    low = None
    if control:
        read, low, n_tok = check.control_readings(arch, seed, picked,
                                                  mix["check"]["seq_len"])
    else:
        read, n_tok = check.served_readings(arch, seed, picked,
                                            mix["check"]["seq_len"])
    log(f"reference: {len(picked)} requests, {n_tok} served tokens, "
        f"{time.time() - t_ref:.3f} s")

    checks = {k: {"value": read.get(k), "limit": v} for k, v in limits.items()}
    checks["malformed_answers"] = {"value": malformed, "limit": 0}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {
        "correct": bool(n_tok > 0 and all(
            c["value"] is not None and c["value"] <= c["limit"]
            for c in checks.values())),
        "attempted": len(due), "failed": failed, "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    if control:
        out["control"] = low
    out["checks"] = checks
    return out

