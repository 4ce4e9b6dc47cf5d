"""Run one cell of ``BENCHMARK.json`` on the accelerator this process sees.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it finds the cell, refuses to run without a TPU whose peaks
it knows, makes the cell's weights on the device from ``--seed``, builds
``ServeEngine`` with the configuration's settings, warms every shape the
traffic uses, runs ``warm_s`` of the cell's traffic and then measures for
``--seconds``.  Afterwards it compares a sample of the served tokens with
the float32 reference (``bench.check``) and prints one JSON line: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window's last
``trace_s`` seconds.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

from bench import log  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CELLS = Path(__file__).resolve().parent / "cells"
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(RuntimeError):
    """No accelerator, too few chips, or no peaks for this device kind."""


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def require_chips(n: int):
    """The devices and their peaks, or NoChip."""
    import jax

    from bench import peaks

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {len(devs)} {devs[0].platform} device(s)")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devs)}")
    try:
        return devs[:n], peaks.for_device_kind(devs[0].device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from None


def enable_compile_cache() -> str:
    """JAX's persistent cache at $JAX_COMPILATION_CACHE_DIR, else at a
    fixed path inside the checkout; every program is kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def metric_specs(bench: dict, workload: str, trace: bool):
    """The cell's metrics for this kind of run, as BENCHMARK.json lists
    them, each with its reader (declarations checked against the entry)."""
    from bench import metrics

    key = "per_layer" if trace else "end_to_end"
    out = []
    for m in bench[key]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        mod = metrics.load(m["name"])
        for field in ("unit", "better", "source", "moves", "layer"):
            if field in m and m[field] != getattr(mod, field.upper()):
                raise ValueError(f"metric {m['name']}: {field} {m[field]!r} "
                                 f"in BENCHMARK.json, "
                                 f"{getattr(mod, field.upper())!r} in its reader")
        out.append((m, mod))
    return out


def load_limits(workload: str) -> dict:
    path = CELLS / f"{workload}.json"
    if not path.is_file():
        raise KeyError(f"no limits for {workload}: {path}")
    return json.loads(path.read_text())["limits"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_benchmark()
    cell = find_cell(bench, args.workload)
    sys.path.insert(0, str(ROOT / "src"))  # the system under test
    try:
        devices, peaks = require_chips(cell["chips"])
    except NoChip as e:
        log(f"refused: {e}")
        return 3
    log(f"compile cache {enable_compile_cache()}")

    from bench import cell as cell_mod

    result = cell_mod.run(
        cell, metric_specs(bench, args.workload, bool(args.trace)),
        load_limits(args.workload), seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START, devices=devices,
        peaks=peaks, trace_dir=TRACE_DIR / args.workload)
    gc.collect()
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
