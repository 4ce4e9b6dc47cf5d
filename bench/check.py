"""The comparison that decides ``correct``: served tokens against the
float32 reference.

Once the window has closed, a sample of the requests the drain finished
(drawn from the seed, the one with the most served tokens always in it)
is run through :mod:`bench.reference`, prompt and served tokens together,
and at each position that produced a served token the reference's best
logit is compared with the reference's logit of the token that was
served.  Greedy tokens from
a bf16 program fall short of the reference's best only where the best
two logits lie within the program's rounding.

The control puts the reference itself in the program's place at fp8
(``quant="fp8"``): at each of the same positions it reads the gap of the
token that the fp8 pass ranks first.

Beside the widest gap a run reads the share of compared positions whose
served token is not the reference's best; each cell's limits file
(``bench/cells/<cell>.json``) names the numbers it compares.
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np

from bench import reference

#: salt that keeps the sample's draw apart from the traffic's
_SAMPLE_SALT = 0x5EED


@dataclasses.dataclass
class Served:
    uid: int
    prompt: np.ndarray
    tokens: List[int]


def sample(served: Sequence[Served], k: int, seed: int) -> List[Served]:
    """``k`` requests: the longest (most served tokens, lowest uid on ties)
    and the rest drawn from the seed."""
    if not served:
        return []
    pool = sorted(served, key=lambda s: s.uid)
    longest = max(pool, key=lambda s: (len(s.tokens), -s.uid))
    rest = [s for s in pool if s is not longest]
    rng = np.random.default_rng([seed, _SAMPLE_SALT])
    pick = rng.permutation(len(rest))[:max(k - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def positions(picked: Sequence[Served], seq_len: int):
    """Token array (N, seq_len) of prompt + served tokens, and the
    (row, column, served id) of every position that produced a token."""
    tokens = np.zeros((len(picked), seq_len), np.int32)
    rows, cols, ids = [], [], []
    for i, s in enumerate(picked):
        seq = np.concatenate([s.prompt, np.asarray(s.tokens[:-1], np.int32)])
        if len(seq) > seq_len:
            raise ValueError(f"request {s.uid}: {len(seq)} tokens > check "
                             f"length {seq_len}")
        tokens[i, :len(seq)] = seq
        p = len(s.prompt)
        for j, t in enumerate(s.tokens):
            rows.append(i)
            cols.append(p - 1 + j)
            ids.append(t)
    return (tokens, np.array(rows, np.int32), np.array(cols, np.int32),
            np.array(ids, np.int64))


def gaps(ref_logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """Per position: reference best logit minus the reference's logit of
    the chosen token (0 where the chosen token is the reference's best)."""
    best = ref_logits.max(axis=-1)
    got = ref_logits[np.arange(len(chosen)), chosen]
    return best - got


def readings(gap: np.ndarray) -> dict:
    """The numbers a run can compare, from the per-position gaps: the
    widest gap, and the share of positions (%) whose token is not the
    reference's best."""
    return {"logit_gap": float(gap.max()),
            "mismatch_pct": 100.0 * float((gap > 0).mean())}


def served_readings(arch, seed: int, picked: Sequence[Served], seq_len: int):
    """(readings of the served tokens, tokens compared); ({}, 0) when
    nothing was served."""
    tokens, rows, cols, ids = positions(picked, seq_len)
    if not len(ids):
        return {}, 0
    ref = reference.logits_at(arch, seed, tokens, rows, cols)
    return readings(gaps(ref, ids)), len(ids)


def control_readings(arch, seed: int, picked: Sequence[Served], seq_len: int):
    """(served readings, fp8-control readings, tokens) on the same
    positions."""
    tokens, rows, cols, ids = positions(picked, seq_len)
    if not len(ids):
        return {}, {}, 0
    ref = reference.logits_at(arch, seed, tokens, rows, cols)
    low = reference.logits_at(arch, seed, tokens, rows, cols, quant="fp8")
    return (readings(gaps(ref, ids)), readings(gaps(ref, low.argmax(axis=-1))),
            len(ids))
