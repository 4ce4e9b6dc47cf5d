"""Small stand-ins for the benchmark's cells, run on the CPU."""

import dataclasses
import time

import jax

from bench import metrics, model, peaks

#: qwen3's layout (GQA, qk-norm) at widths the CPU runs in seconds
TINY = model.Arch("tiny", 2, 64, 4, 2, 16, 128, 250, 256, True, 10000.0,
                  1e-5, False, None)
#: the same with the qwen3 cells' tied head, rope_theta and norm epsilon
TINY_TIED = dataclasses.replace(TINY, name="tiny-tied", tie_embeddings=True,
                                rope_theta=1e6, norm_eps=1e-6)
TINY_MOE = model.Arch("tiny-moe", 3, 64, 4, 4, 16, 128, 250, 256, False,
                      10000.0, 1e-5, False,
                      model.MoE(8, 2, 32, 1, True, True, 1.25))
SETTINGS = dict(max_batch=4, max_len=128, block_size=16, prefill_chunk=16,
                kv_dtype="bf16")
FAKE_PEAKS = peaks.Peaks(bf16_flops=1e12, hbm_bytes_s=1e11, hbm_bytes=10**9)
E2E = ("out_tok_s", "ttft_p50_ms", "itl_p95_ms", "setup_s")


def tiny_mix(loop: str, check_requests: int = 3) -> dict:
    return dict(loop=loop, rate_per_s=20.0, clients=4, pool=400,
                prompt=dict(dist="lognormal", median=20, sigma=0.5, lo=4, hi=60),
                output=dict(dist="uniform", lo=2, hi=8), warm_s=0.5,
                tail_s=5.0, trace_s=0.5,
                check=dict(requests=check_requests, seq_len=80))


def rehearse(loop="open", arch=TINY, seconds=2.0, limit=0.25,
             seed=3_000_000_001, check_requests=3, control=False):
    """One cell run on the CPU at a tiny size: the harness minus its look
    for a chip.  Returns the result line's dict."""
    from bench import cell

    specs = [(dict(name=n, unit=metrics.load(n).UNIT), metrics.load(n))
             for n in E2E]
    return cell.run({"config": "qwen3-1.7b", "traffic": "rag", "chips": 1},
                    specs, {"logit_gap": limit}, seed=seed, seconds=seconds,
                    trace=False, t_start=time.time(), devices=jax.devices(),
                    peaks=FAKE_PEAKS, trace_dir=None, arch=arch,
                    mix=tiny_mix(loop, check_requests), settings=SETTINGS,
                    control=control)
