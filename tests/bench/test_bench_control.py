"""The control at a size a test run holds: the reference put in the
program's place at fp8 (one precision step below the configurations'
bf16) has to come out as not correct, where the bf16 program passes.

On the chip the same comparison runs at each cell's own size
(``python3 -m bench.tune --workload <cell> readings``); the readings and
the limits they set are in PERF.md.  Here, at widths 128, the limits sit
between the same two readings taken on these seeds: served tokens read
at most 0.034 (widest gap) and 5.5% (share not the reference's best);
the fp8 control reads at least 0.26 and 18%.
"""

import dataclasses

import numpy as np
import pytest

from bench import check, model, weights
from repro.serve.engine import Request, ServeEngine

from tests.bench.tiny import TINY_TIED, rehearse

DENSE = model.Arch("d128", 4, 128, 4, 2, 32, 256, 500, 512, True, 10000.0,
                   1e-5, False, None)
DENSE_TIED = dataclasses.replace(DENSE, name="d128-tied", tie_embeddings=True,
                                 rope_theta=1e6, norm_eps=1e-6)
MOE = model.Arch("moe128", 3, 128, 4, 4, 32, 256, 500, 512, False, 10000.0,
                 1e-5, False, model.MoE(16, 4, 64, 1, True, True, 1.25))
SETTINGS = dict(max_batch=4, max_len=128, block_size=16, prefill_chunk=16,
                kv_dtype="bf16")
#: the numbers each cell kind compares (MoE: bf16 routing flips make the
#: widest gap swing, so it compares the share of tokens instead)
LIMITS = {"d128": {"logit_gap": 0.12, "mismatch_pct": 10.0},
          "d128-tied": {"logit_gap": 0.12, "mismatch_pct": 10.0},
          "moe128": {"mismatch_pct": 12.0}}


def _served(arch, seed):
    eng = ServeEngine(model.program_config(arch),
                      weights.program_params(arch, seed), **SETTINGS)
    rng = np.random.default_rng(seed)
    for i in range(16):
        n = int(rng.integers(4, 60))
        eng.submit(Request(uid=i, prompt=rng.integers(0, arch.vocab, n)
                           .astype(np.int32), max_new_tokens=8))
    return [check.Served(u, r.prompt, list(r.generated))
            for u, r in sorted(eng.run_until_drained().items())]


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("arch", [DENSE, DENSE_TIED, MOE],
                         ids=["dense", "dense-tied", "moe"])
def test_fp8_control_fails_where_the_program_passes(arch, seed):
    got, low, n = check.control_readings(arch, seed, _served(arch, seed), 80)
    assert n == 16 * 8
    for k, limit in LIMITS[arch.name].items():
        assert got[k] <= limit < low[k], (k, got[k], low[k])


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_a_whole_run_reads_the_control_beside_the_served_tokens(loop):
    # the harness's own path (bench.tune readings): the run stays correct,
    # the control read on its compared positions does not
    out = rehearse(loop, arch=TINY_TIED, check_requests=10_000, control=True)
    gap = out["checks"]["logit_gap"]
    assert out["correct"] is True
    assert gap["value"] <= gap["limit"] < out["control"]["logit_gap"]
