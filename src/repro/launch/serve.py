"""Serving driver: load (or init) a model, run batched requests.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch gpt2-124m \\
        --scheduler continuous --requests 8 --max-new 12

``--scheduler wave`` runs the legacy lockstep scheduler (the golden
baseline); the default continuous scheduler refills slots mid-flight over
the paged KV cache.  ``--prefill-chunk N`` commits up to N prompt tokens
per fused step (chunked prefill) and ``--prefill-budget`` caps the total
prefill tokens admitted per step so decode never stalls behind a long
prompt — both land in the report and the ledger key, so chunked and
token-by-token trajectories stay separate.  ``--kv-dtype bf16|int8``
stores the paged KV pool quantized (per-row fp32 scales for int8) and
``--share-prefixes`` deduplicates identical prompt prefixes onto shared
pool blocks with copy-on-write (``--shared-prefix-len N`` samples traffic
that exercises it); both fork the ledger key (``+kv<dtype>`` /
``+shared``).  ``--draft <arch> --spec-k N`` turns on speculative
decoding (the draft model proposes up to N tokens per slot, one fused
target step verifies them; ledger key gains ``+spec<N>``), and
``--temperature/--top-k/--sample-seed`` select real sampling with
per-request PRNG streams (temperature 0 = greedy, bit-identical to the
pre-sampling engine).  ``--record`` appends the serving metrics (tok/s,
p50/p95 request latency, slot utilization, block dedup ratio) to the perf
trajectory ledger, where ``python -m repro.perf report`` renders them;
``--out`` writes the full machine-readable serve report.
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

import repro.configs as configs
from repro.serve.engine import SCHEDULERS, Request, RequestTooLong, ServeEngine
from repro.train import steps as steps_mod


def build_report(args: argparse.Namespace, engine: ServeEngine,
                 rejections: list = ()) -> dict:
    """Machine-readable serve report (the ledger's serving source)."""
    return {
        "kind": "serve_report",
        "arch": args.arch,
        "scheduler": engine.scheduler,
        "max_batch": engine.max_batch,
        "max_len": engine.max_len,
        "block_size": engine.block_size,
        "prefill_chunk": engine.prefill_chunk,
        "prefill_budget": engine.prefill_budget,
        "kv_dtype": engine.kv_dtype,
        "share_prefixes": engine.share_prefixes,
        "draft": getattr(args, "draft", None),
        "spec_k": engine.spec_k,
        "spec_adaptive": engine.spec_adaptive,
        "mesh": engine.mesh_shape,
        "temperature": engine.temperature,
        "top_k": engine.top_k,
        "sample_seed": engine.sample_seed,
        "rejected": len(rejections),
        "rejections": [{"uid": u, "reason": reason} for u, reason in rejections],
        "stats": engine.stats(),
        "requests": [
            {
                "uid": r.uid,
                "prompt_len": int(len(r.prompt)),
                "new_tokens": len(r.generated),
                "tokens": [int(t) for t in r.generated],
                "latency_s": r.latency_s,
                "ttft_s": r.ttft_s,
            }
            for _, r in sorted(engine.completed.items())
        ],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-124m")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="smoke-sized config (--no-smoke for the real one)")
    ap.add_argument("--scheduler", choices=list(SCHEDULERS),
                    default="continuous")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--prompt-lo", type=int, default=4,
                    help="minimum sampled prompt length")
    ap.add_argument("--prompt-hi", type=int, default=16,
                    help="maximum sampled prompt length (inclusive)")
    ap.add_argument("--prefill-chunk", type=int, default=1,
                    help="commit up to N prompt tokens per fused step "
                         "(1 = token-by-token; continuous scheduler only)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="cap total prefill tokens admitted per step so "
                         "decode slots never stall behind long prompts")
    ap.add_argument("--kv-dtype", choices=["f32", "bf16", "int8"],
                    default="f32",
                    help="paged KV pool storage dtype (quantized paging; "
                         "continuous scheduler only for bf16/int8)")
    ap.add_argument("--share-prefixes", action="store_true",
                    help="deduplicate identical prompt prefixes onto "
                         "shared pool blocks with copy-on-write "
                         "(continuous scheduler only)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="sample all prompts with a common prefix of this "
                         "length (exercises --share-prefixes; 0 = fully "
                         "random prompts)")
    ap.add_argument("--draft", default=None,
                    help="draft-model arch for speculative decoding "
                         "(e.g. gpt2-124m); requires --spec-k >= 1")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="draft tokens proposed per slot per fused target "
                         "step (0 = speculation off)")
    ap.add_argument("--spec-adaptive", action="store_true",
                    help="adapt the per-slot draft width from the trailing "
                         "acceptance EMA, clamped to [0, --spec-k]")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve tensor-parallel over a data-x-model device "
                         "mesh, e.g. 2x2 (use XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N to fake "
                         "N host devices; continuous scheduler only)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the k highest-probability "
                         "tokens (0 = full vocab; needs --temperature > 0)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="base seed of the per-request sampling streams")
    ap.add_argument("--warmup", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="compile the fused step before serving so TTFT "
                         "measures scheduling, not XLA compilation")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="write the serve report JSON here")
    ap.add_argument("--record", action="store_true",
                    help="append serving metrics to the perf ledger")
    args = ap.parse_args(argv)

    mesh = None
    if args.mesh:
        from repro.launch.mesh import (MeshShapeError, make_serve_mesh,
                                       parse_mesh)

        try:
            mesh = make_serve_mesh(*parse_mesh(args.mesh))
        except MeshShapeError as e:
            ap.error(str(e))

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    params = steps_mod.init_model(jax.random.PRNGKey(args.seed), cfg)
    draft_cfg = draft_params = None
    if args.spec_k > 0:
        if not args.draft:
            ap.error("--spec-k requires --draft <arch>")
        draft_cfg = (configs.get_smoke_config(args.draft) if args.smoke
                     else configs.get_config(args.draft))
        # same init seed as the target: --draft <same arch> gives exact
        # self-speculation (acceptance 1.0 at temperature 0), the
        # acceptance-friendly setup CI uses for the fewer-steps assert
        draft_params = steps_mod.init_model(
            jax.random.PRNGKey(args.seed), draft_cfg
        )
    engine = ServeEngine(cfg, params, max_batch=args.max_batch,
                         max_len=args.max_len, scheduler=args.scheduler,
                         block_size=args.block_size,
                         prefill_chunk=args.prefill_chunk,
                         prefill_budget=args.prefill_budget,
                         kv_dtype=args.kv_dtype,
                         share_prefixes=args.share_prefixes,
                         temperature=args.temperature, top_k=args.top_k,
                         sample_seed=args.sample_seed, spec_k=args.spec_k,
                         draft_cfg=draft_cfg, draft_params=draft_params,
                         spec_adaptive=args.spec_adaptive, mesh=mesh)
    if args.warmup:
        engine.warmup()

    rng = np.random.default_rng(args.seed)
    shared_prefix = (
        rng.integers(0, cfg.vocab,
                     size=args.shared_prefix_len).astype(np.int32)
        if args.shared_prefix_len > 0 else None)
    rejections: list = []
    for uid in range(args.requests):
        plen = int(rng.integers(args.prompt_lo, args.prompt_hi + 1))
        prompt = rng.integers(0, cfg.vocab, size=plen).astype(np.int32)
        if shared_prefix is not None:
            prompt = np.concatenate([shared_prefix, prompt])
        try:
            engine.submit(Request(
                uid=uid,
                prompt=prompt,
                max_new_tokens=args.max_new,
            ))
        except RequestTooLong as e:
            # an oversized submission is a counted rejection, not a crash:
            # the remaining requests still get served and reported
            rejections.append((uid, str(e)))
    done = engine.run_until_drained()
    stats = engine.stats()
    print(f"[{args.scheduler}] served {stats['requests']} requests, "
          f"{stats['new_tokens']} tokens, {stats['fused_steps']} fused steps "
          f"in {stats['wall_s']:.2f}s ({stats['tok_s']:.1f} tok/s)")
    print(f"  slot utilization {stats['slot_utilization']:.3f} "
          f"({stats['busy_slot_steps']}/{stats['slot_steps']} slot-steps), "
          f"latency p50 {stats['p50_latency_s']:.3f}s "
          f"p95 {stats['p95_latency_s']:.3f}s, "
          f"ttft p50 {stats['ttft_p50_s']:.3f}s "
          f"p95 {stats['ttft_p95_s']:.3f}s"
          + (f" [prefill chunk {engine.prefill_chunk}"
             + (f", budget {engine.prefill_budget}"
                if engine.prefill_budget else "") + "]"
             if engine.prefill_chunk > 1 else ""))
    if engine.kv_dtype != "f32" or engine.share_prefixes:
        print(f"  kv_dtype {stats['kv_dtype']}, "
              f"prefix sharing {'on' if stats['share_prefixes'] else 'off'}: "
              f"{stats['logical_blocks']} logical / "
              f"{stats['physical_blocks']} physical blocks "
              f"({stats['shared_block_hits']} shared hits, "
              f"{stats['cow_copies']} COW copies, "
              f"dedup {stats['block_dedup_ratio']:.3f})")
    if engine.mesh is not None:
        print(f"  mesh {stats['mesh']} ({stats['mesh_devices']} devices), "
              f"device lane utilization "
              f"{stats['device_lane_utilization']:.3f}")
    if engine.spec_k > 0:
        print(f"  speculative: draft {args.draft} k={engine.spec_k}"
              + (" (adaptive width)" if engine.spec_adaptive else "") + ", "
              f"acceptance {stats['acceptance_rate']:.3f} "
              f"({stats['accepted_tokens']}/{stats['drafted_tokens']} "
              f"drafts accepted, {stats['draft_steps']} draft steps, "
              f"{stats['target_steps']} target steps)")
    if rejections:
        print(f"  rejected {len(rejections)} oversized request(s) at submit:")
        for uid, reason in rejections:
            print(f"    req {uid}: {reason}")
    for uid in sorted(done):
        r = done[uid]
        lat = f"{r.latency_s:.3f}s" if r.latency_s is not None else "n/a"
        print(f"  req {uid}: prompt[{len(r.prompt)}] latency {lat} "
              f"-> {r.generated}")

    report = build_report(args, engine, rejections)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
        print(f"serve report -> {args.out}")
    if args.record:
        from repro.perf.ledger import default_ledger

        run = default_ledger().record_sources(
            serving=report, meta={"argv": " ".join(argv or [])} if argv else None,
        )
        print(f"recorded serving run {run.run_id} (seq {run.seq}) "
              f"-> perf ledger")
    return 0


if __name__ == "__main__":
    # the command line only: tests that call main() leave the cache off
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
