import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # host devices only: never claim a chip

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell and record cost/memory/collective analysis for §Roofline.

MUST be run as its own process (the three lines above must execute before any
jax import anywhere — including ``from repro...``).  Smoke tests and benches
never import this module, so they see 1 device.

Usage:
    python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all [--jobs 4] [--baseline]
    python -m repro.launch.dryrun --list
"""

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

OUT_DIR = os.environ.get("REPRO_DRYRUN_DIR", "experiments/dryrun")


def run_one(arch: str, shape: str, mesh_kind: str, baseline: bool, out_dir: str) -> dict:
    import jax  # noqa: E402  (after XLA_FLAGS)

    from repro.launch import cells as cells_mod
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    cell = cells_mod.build_cell(arch, shape, mesh, baseline=baseline)
    lowered, compiled = cells_mod.lower_cell(cell, mesh)
    t1 = time.time()
    print(compiled.memory_analysis())  # proves it fits
    print({k: v for k, v in (compiled.cost_analysis() or {}).items()
           if k in ("flops", "bytes accessed")})
    result = cells_mod.analyze_cell(cell, mesh, compiled)
    result["compile_s"] = t1 - t0
    result["baseline"] = baseline
    os.makedirs(out_dir, exist_ok=True)
    # atomic write: --skip-existing trusts file existence, so an interrupted
    # dump must never leave a truncated artifact behind
    path = _cell_artifact(out_dir, arch, shape, mesh_kind, baseline)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=1)
    os.replace(tmp, path)
    print(f"[ok] {arch} x {shape} x {mesh_kind} "
          f"compile={result['compile_s']:.1f}s "
          f"dominant={result['roofline']['dominant']} "
          f"bound={result['roofline']['bound_s']:.4g}s "
          f"mem/dev={result['memory_per_device']['total_gb']:.2f}GB "
          f"class={result['sve']['perf_class']}"
          f"({result['sve']['perf_class_name']})")
    return result


def all_cells():
    # import here so --list works without jax device init side effects
    from repro.configs import cells as cfg_cells

    out = []
    for arch, shape in cfg_cells(include_paper_arch=False):
        for mesh_kind in ("single", "multi"):
            out.append((arch, shape, mesh_kind))
    return out


def _cell_artifact(out_dir: str, arch: str, shape: str, mesh_kind: str,
                   baseline: bool) -> str:
    tag = "base" if baseline else "opt"
    fname = f"{arch}__{shape}__{mesh_kind}__{tag}.json".replace("/", "_")
    return os.path.join(out_dir, fname)


def drive_all(jobs: int, baseline: bool, out_dir: str, mesh_filter=None,
              skip_existing: bool = False) -> int:
    todo = [c for c in all_cells() if mesh_filter is None or c[2] == mesh_filter]
    if skip_existing:
        kept = []
        for c in todo:
            path = _cell_artifact(out_dir, c[0], c[1], c[2], baseline)
            if os.path.exists(path):
                print(f"[skip] {c}: artifact exists ({path})")
            else:
                kept.append(c)
        todo = kept
    procs = {}
    failed, done = [], 0
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", "src")
    while todo or procs:
        while todo and len(procs) < jobs:
            arch, shape, mesh_kind = todo.pop(0)
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                   "--out-dir", out_dir]
            if baseline:
                cmd.append("--baseline")
            logname = os.path.join(out_dir, f"{arch}__{shape}__{mesh_kind}.log")
            os.makedirs(out_dir, exist_ok=True)
            logf = open(logname, "w")
            p = subprocess.Popen(cmd, stdout=logf, stderr=subprocess.STDOUT, env=env)
            procs[p.pid] = (p, (arch, shape, mesh_kind), logf)
        time.sleep(2)
        for pid in list(procs):
            p, cellid, logf = procs[pid]
            if p.poll() is not None:
                logf.close()
                del procs[pid]
                done += 1
                status = "ok" if p.returncode == 0 else "FAIL"
                if p.returncode != 0:
                    failed.append(cellid)
                print(f"[{done}] {status}: {cellid}", flush=True)
    if failed:
        print(f"{len(failed)} FAILED cells: {failed}")
        return 1
    print(f"all {done} cells compiled clean")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--baseline", action="store_true",
                    help="paper-faithful baseline RunConfig instead of optimized")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--jobs", type=int, default=4,
                    help="concurrent compile subprocesses for --all")
    ap.add_argument("--skip-existing", action="store_true",
                    help="with --all: skip cells whose analysis JSON already "
                         "exists in --out-dir (persistent artifact reuse)")
    ap.add_argument("--out-dir", default=OUT_DIR)
    args = ap.parse_args()

    if args.list:
        for c in all_cells():
            print(*c)
        return 0
    if args.all:
        return drive_all(args.jobs, args.baseline, args.out_dir,
                         skip_existing=args.skip_existing)
    try:
        run_one(args.arch, args.shape, args.mesh, args.baseline, args.out_dir)
        return 0
    except Exception:
        traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(main())
