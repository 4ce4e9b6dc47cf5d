#!/usr/bin/env python3
"""Bring-up smoke run on the TPU: the Pallas kernels and qwen3-1.7b serving.

    python chip_smoke.py              # one chip
    python chip_smoke.py --mesh 2x2   # four chips: sharded serving only

One chip: every registered kernel runs compiled (``interpret=False``) at a
real size and is compared with its reference, then qwen3-1.7b at its full
published widths (bf16, random weights from ``--seed``) serves 16 requests
through ``ServeEngine``, and one prompt's prefill-then-decode logits are
compared with ``transformer.forward`` on the same tokens.

``--mesh DxM`` runs the same serving phase over a DxM mesh and, in the same
process, on one unsharded device, and compares the two.

Everything runs in this one process, which holds the chip.  Each phase
prints one line; any failure exits non-zero.  The last line of standard
output is ``{"ok": true, "device": {...}}`` as JAX reports the device.
Without a TPU the script fails before any phase and prints no result.
Times printed here are single unrepeated runs: chip, unbenchmarked.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# real sizes (module constants, so a rehearsal can shrink them)
GEMM_N = 4096                   # bf16 (N, N) @ (N, N)
STREAM_SHAPE = (65536, 1024)    # fp32: 256 MiB per array
SPMV_ROWS = 1 << 17             # square, block-ELL 8 x 128, Zipf rows
JACOBI_N = 4096                 # fp32 (N, N) grid
QC_QUBITS, QC_TARGET = 24, 10   # state vector of 2**24 amplitudes
ATTN = dict(B=8, KV=8, G=2, D=128, S=2048, bs=16, C=64)  # qwen3-1.7b heads
ARCH = "qwen3-1.7b"
SERVE = dict(max_batch=8, max_len=2048, block_size=16, kv_dtype="bf16",
             prefill_chunk=64)
REQUESTS, PROMPT_LO, PROMPT_HI, MAX_NEW = 16, 128, 1024, 32
TF_PROMPT, TF_DECODE = 192, 16  # teacher-forced check: 3 chunks + 16 steps

# Largest |kernel - ref| over the largest |ref|, per output dtype.  bf16
# keeps 8 mantissa bits (relative step 2**-8 = 0.0039); attention also
# rounds its probabilities to bf16 before the PV contraction.
KERNEL_TOL = {"float32": 1e-5, "bfloat16": 2e-2}
# Teacher-forced logits, bf16 engine vs bf16 forward: the same weights and
# tokens through a different order of reductions (paged decode cells vs one
# causal pass) over 28 layers.  Same measure as KERNEL_TOL.
LOGIT_TOL = 5e-2


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def rel_err(out, ref) -> float:
    import numpy as np

    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if out.shape != ref.shape:
        fail(f"shape {out.shape} != reference shape {ref.shape}")
    if not np.isfinite(out).all():
        return float("inf")
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))


def device_phase():
    import jax

    from repro.core import hw

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU found: JAX sees {len(devs)} {devs[0].platform} device(s)")
    kind = devs[0].device_kind
    chip = hw.chip_for_device_kind(kind)
    log("device", platform=devs[0].platform, kind=repr(kind), count=len(devs),
        peaks=chip.name, bf16_tflops=chip.peak("bf16") / 1e12,
        hbm_gbs=chip.hbm_bw / 1e9)
    return devs


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------


def kernel_cases(key):
    """name -> (call, reference, output dtype): one real-size case per
    registered kernel, plus the paged flash-decode kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import registry as R
    from repro.kernels.flash_decode import kernel as fdk, ref as fdr
    from repro.kernels.qc_gate import ref as qcr
    from repro.kernels.spmv import ref as spr

    ks = iter(jax.random.split(key, 32))
    bf, f32 = jnp.bfloat16, jnp.float32
    normal = lambda shape, dt: jax.random.normal(next(ks), shape, dt)  # noqa: E731

    def compiled(ops, *args, **kw):
        return lambda: ops.kernel(*args, **kw)

    n = GEMM_N
    x, y = normal((n, n), bf), normal((n, n), bf)
    a, b = normal(STREAM_SHAPE, f32), normal(STREAM_SHAPE, f32)
    vals, cols, nnz = spr.make_problem(next(ks), SPMV_ROWS, SPMV_ROWS)
    xv = normal((SPMV_ROWS,), f32)
    u = normal((JACOBI_N, JACOBI_N), f32)
    re = normal((1 << QC_QUBITS,), f32) / np.sqrt(2 << QC_QUBITS)
    im = normal((1 << QC_QUBITS,), f32) / np.sqrt(2 << QC_QUBITS)

    B, KV, G, D, S, bs, C = (ATTN[k] for k in ("B", "KV", "G", "D", "S", "bs", "C"))
    nb = S // bs
    rng = np.random.default_rng(0)
    q = normal((B, KV, G, D), bf)
    kc, vc = normal((B, S, KV, D), bf), normal((B, S, KV, D), bf)
    valid = jnp.asarray(rng.integers(1, S + 1, B), jnp.int32)
    k_pool, v_pool = normal((1 + B * nb, bs, KV, D), bf), normal((1 + B * nb, bs, KV, D), bf)
    tables = jnp.asarray(1 + rng.permutation(B * nb).reshape(B, nb), jnp.int32)
    qp = normal((B, C, KV, G, D), bf)
    kn, vn = normal((B, C, KV, D), bf), normal((B, C, KV, D), bf)
    q_start = jnp.asarray(rng.integers(0, S - C + 1, B), jnp.int32)

    def prefill_ref():
        out, kp, vp = fdr.prefill_paged_ref(qp, kn, vn, k_pool, v_pool,
                                            tables, q_start)
        return out, kp[1:], vp[1:]  # block 0 (NULL) is unspecified

    def prefill_run():
        out, kp, vp = R.FLASH_PREFILL.kernel(qp, kn, vn, k_pool, v_pool,
                                             tables, q_start)
        return out, kp[1:], vp[1:]

    cases = {
        "gemm": (compiled(R.GEMM, x, y), lambda: R.GEMM.ref(x, y), bf),
        "stream-copy": (compiled(R.STREAM_COPY, a),
                        lambda: R.STREAM_COPY.ref(a), f32),
        "stream-scale": (compiled(R.STREAM_SCALE, a, 3.0),
                         lambda: R.STREAM_SCALE.ref(a, 3.0), f32),
        "stream-add": (compiled(R.STREAM_ADD, a, b),
                       lambda: R.STREAM_ADD.ref(a, b), f32),
        "stream-triad": (compiled(R.STREAM_TRIAD, a, b, 3.0),
                         lambda: R.STREAM_TRIAD.ref(a, b, 3.0), f32),
        "spmv": (compiled(R.SPMV, vals, cols, nnz, xv),
                 lambda: R.SPMV.ref(vals, cols, nnz, xv), f32),
        "spmv-fixed-width": (compiled(R.SPMV_FIXED, vals, cols, nnz, xv),
                             lambda: R.SPMV_FIXED.ref(vals, cols, nnz, xv), f32),
        "jacobi2d": (compiled(R.JACOBI_STEP, u), lambda: R.JACOBI_STEP.ref(u), f32),
        "qc-gate": (compiled(R.RX_GATE, re, im, qubit=QC_TARGET, theta=0.25),
                    lambda: qcr.rx_ref(re, im, QC_TARGET, 0.25), f32),
        "flash-decode": (compiled(R.FLASH_DECODE, q, kc, vc, valid),
                         lambda: R.FLASH_DECODE.ref(q, kc, vc, valid), bf),
        "flash-prefill": (prefill_run, prefill_ref, bf),
        "flash-decode-paged": (
            lambda: fdk.flash_decode_paged(q, k_pool, v_pool, tables, valid,
                                           interpret=False),
            lambda: fdr.decode_paged_ref(q, k_pool, v_pool, tables, valid), bf),
    }
    missing = set(R.KERNELS) - set(cases)
    if missing:
        fail(f"registered kernels without a chip case: {sorted(missing)}")
    return cases


def kernel_phase(key) -> None:
    import jax
    import jax.numpy as jnp

    for name, (run, ref, dt) in kernel_cases(key).items():
        t0 = time.perf_counter()
        out = jax.block_until_ready(run())
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = jax.block_until_ready(run())
        wall_s = time.perf_counter() - t0
        outs = out if isinstance(out, (tuple, list)) else (out,)
        refs = ref()
        refs = refs if isinstance(refs, (tuple, list)) else (refs,)
        err = max(rel_err(o, r) for o, r in zip(outs, refs))
        tol = KERNEL_TOL[jnp.dtype(dt).name]
        log("kernel", name=name, compiled=True, rel_err=f"{err:.3e}",
            tol=tol, first_call_s=f"{first_s:.4f}",
            wall_s=f"{wall_s:.6f} (chip, unbenchmarked)")
        if not err <= tol:
            fail(f"kernel {name}: rel_err {err:.3e} > {tol}")


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------


def build_model(seed: int):
    """The full config with random bf16 weights, as ``launch.serve --no-smoke``
    builds it."""
    import jax

    import repro.configs as configs
    from repro.train import steps as steps_mod

    cfg = configs.get_config(ARCH)
    params = steps_mod.init_model(jax.random.PRNGKey(seed), cfg)
    return cfg, params


def requests(cfg, seed: int):
    import numpy as np

    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    out = []
    for uid in range(REQUESTS):
        plen = int(rng.integers(PROMPT_LO, PROMPT_HI + 1))
        prompt = rng.integers(0, cfg.vocab, size=plen).astype(np.int32)
        out.append(Request(uid=uid, prompt=prompt, max_new_tokens=MAX_NEW))
    return out


def serve(cfg, params, seed: int, *, mesh=None, label: str = "serve"):
    """Warm up, serve the seeded requests, check every stream; returns
    (engine, {uid: tokens})."""
    import jax

    from repro.serve.engine import ServeEngine

    engine = ServeEngine(cfg, params, mesh=mesh, **SERVE)
    t0 = time.perf_counter()
    engine.warmup()
    compile_s = time.perf_counter() - t0
    reqs = requests(cfg, seed)
    for r in reqs:
        engine.submit(r)
    t0 = time.perf_counter()
    done = engine.run_until_drained()
    wall_s = time.perf_counter() - t0
    streams = {uid: list(r.generated) for uid, r in sorted(done.items())}
    if sorted(streams) != [r.uid for r in reqs]:
        fail(f"{label}: served {sorted(streams)}, submitted {len(reqs)}")
    for uid, toks in streams.items():
        if len(toks) != MAX_NEW or not all(0 <= t < cfg.vocab for t in toks):
            fail(f"{label}: request {uid} returned {len(toks)} ids, "
                 f"range [{min(toks)}, {max(toks)}] for vocab {cfg.vocab}")
    new = sum(len(t) for t in streams.values())
    prompt = sum(len(r.prompt) for r in reqs)
    stats = jax.devices()[0].memory_stats() or {}
    log(label, arch=cfg.name, mesh=engine.mesh_shape or "none",
        requests=len(streams), prompt_tokens=prompt, new_tokens=new,
        fused_steps=engine.steps, compile_s=f"{compile_s:.2f}",
        wall_s=f"{wall_s:.3f}", tok_s=f"{new / wall_s:.2f}",
        prompt_and_new_tok_s=f"{(prompt + new) / wall_s:.2f}",
        peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"),
        timing="chip, unbenchmarked")
    return engine, streams


def engine_logits(engine, tokens, n_prompt: int):
    """Teacher-forced logits from the engine's own jitted steps: the prompt
    in ``prefill_chunk``-wide paged prefill calls, then one paged decode
    step per remaining token, all in slot 0 (the other slots idle)."""
    import numpy as np

    B, C = engine.max_batch, engine.prefill_chunk
    nb = engine.max_len // engine.block_size
    V = engine.cfg.vocab
    cache = engine._new_cache()
    tables = np.zeros((B, nb), np.int32)
    tables[0] = 1 + np.arange(nb)
    pos = np.zeros((B,), np.int32)
    rows = []
    for s in range(0, n_prompt, C):
        w = min(C, n_prompt - s)
        tok = np.zeros((B, C), np.int32)
        tok[0, :w] = tokens[s:s + w]
        lens = np.zeros((B,), np.int32)
        lens[0] = w
        logits, cache = engine._prefill_paged(
            engine.params, engine._dev_tok(tok), cache, engine._dev(pos),
            engine._dev(tables), engine._dev(lens))
        rows.append(np.asarray(logits[0, :w, :V], np.float32))
        pos[0] += w
    for t in tokens[n_prompt:]:
        tok = np.zeros((B, 1), np.int32)
        tok[0, 0] = t
        logits, cache = engine._decode_paged(
            engine.params, engine._dev_tok(tok), cache, engine._dev(pos),
            engine._dev(tables))
        rows.append(np.asarray(logits[0, :, :V], np.float32))
        pos[0] += 1
    return np.concatenate(rows)


def forward_logits(cfg, params, tokens):
    import jax
    import numpy as np

    from repro.models import transformer

    fwd = jax.jit(lambda p, t: transformer.forward(p, cfg, t)[0])
    return np.asarray(fwd(params, tokens[None])[0, :, :cfg.vocab], np.float32)


def teacher_forced(cfg, params, engines, seed: int) -> None:
    """Every engine's paged logits against ``transformer.forward``."""
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    tokens = rng.integers(0, cfg.vocab, TF_PROMPT + TF_DECODE).astype(np.int32)
    ref = forward_logits(cfg, params, tokens)
    for label, engine in engines:
        got = engine_logits(engine, tokens, TF_PROMPT)
        err = rel_err(got, ref)
        agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
        log("teacher-forced", engine=label, positions=len(tokens),
            prefill_positions=TF_PROMPT, decode_positions=TF_DECODE,
            rel_err=f"{err:.3e}", tol=LOGIT_TOL, argmax_agree=f"{agree:.4f}")
        if not err <= LOGIT_TOL:
            fail(f"teacher-forced logits ({label}): rel_err {err:.3e} > "
                 f"{LOGIT_TOL}")


def one_chip(seed: int) -> None:
    import jax

    kernel_phase(jax.random.PRNGKey(seed))
    cfg, params = build_model(seed)
    engine, _ = serve(cfg, params, seed)
    teacher_forced(cfg, params, [("unsharded", engine)], seed)


def mesh_run(spec: str, seed: int) -> None:
    """The serve phase over a DxM mesh, and the same requests on one
    unsharded device of this process, compared."""
    from repro.launch.mesh import make_serve_mesh, parse_mesh

    mesh = make_serve_mesh(*parse_mesh(spec))
    cfg, params = build_model(seed)
    sharded, s_streams = serve(cfg, params, seed, mesh=mesh, label="serve-mesh")
    single, u_streams = serve(cfg, params, seed, label="serve-single")
    for uid in sorted(u_streams):
        a, b = s_streams[uid], u_streams[uid]
        prefix = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                      len(a))
        log("greedy-prefix", uid=uid, agree=f"{prefix}/{len(a)}")
    teacher_forced(cfg, params, [(f"mesh{spec}", sharded),
                                 ("unsharded", single)], seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="run only sharded serving over this mesh (e.g. "
                         "2x2) against one unsharded device")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = device_phase()
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir, from_env = enable_compile_cache()
    log("compile-cache", dir=cache_dir,
        source="JAX_COMPILATION_CACHE_DIR" if from_env else "checkout default")
    t0 = time.perf_counter()
    if args.mesh:
        mesh_run(args.mesh, args.seed)
    else:
        one_chip(args.seed)
    log("done", total_s=f"{time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
