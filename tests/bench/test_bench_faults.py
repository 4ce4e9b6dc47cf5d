"""A whole run at a tiny size with the timed path broken underneath:
``correct`` has to come out false for each fault a serving cell can have.
(The cells are one chip each, so there is no exchange between chips to
leave out.)"""

import jax
import numpy as np
import pytest

from repro.models import transformer
from repro.serve import engine as engine_mod
from repro.serve import sampling

from tests.bench.tiny import TINY, TINY_MOE, TINY_TIED, rehearse


def _broken_steps(monkeypatch, fault):
    """Replace the engine's jitted paged steps by ones that apply
    ``fault(logits, old_cache, new_cache) -> (logits, cache)``."""

    def decode(cfg, block_size, kv_dtype):
        def step(p, t, c, pos, bt):
            logits, new = transformer.decode_step_paged(
                p, cfg, t, c, pos, bt, block_size=block_size, kv_dtype=kv_dtype)
            return fault(logits, c, new)
        return jax.jit(step)

    def prefill(cfg, block_size, kv_dtype):
        def step(p, t, c, pos, bt, lens):
            logits, new = transformer.prefill_step_paged(
                p, cfg, t, c, pos, bt, lens, block_size=block_size,
                kv_dtype=kv_dtype)
            return fault(logits, c, new)
        return jax.jit(step)

    monkeypatch.setattr(engine_mod, "_jit_decode_paged", decode)
    monkeypatch.setattr(engine_mod, "_jit_prefill_paged", prefill)


def state_unchanged(logits, old, new):
    return logits, old


def half_batch_left_out(logits, old, new):
    half = logits.shape[0] // 2
    return logits.at[half:].set(logits[:half]), new


FAULTS = {"state_unchanged": state_unchanged,
          "half_batch_left_out": half_batch_left_out}

#: limits at this size, between the sound path's widest gaps (dense about
#: 0.04, MoE up to 0.43: bf16 routing flips) and the faults' (4 and more)
LIMIT = {TINY.name: 0.25, TINY_TIED.name: 0.25, TINY_MOE.name: 1.0}


@pytest.mark.parametrize("arch", [TINY, TINY_TIED, TINY_MOE],
                         ids=["dense", "dense-tied", "moe"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_step_is_not_correct(monkeypatch, fault, arch):
    _broken_steps(monkeypatch, FAULTS[fault])
    out = rehearse("open", arch=arch, check_requests=10_000,
                   limit=LIMIT[arch.name])
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > out["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("arch", [TINY, TINY_TIED, TINY_MOE],
                         ids=["dense", "dense-tied", "moe"])
def test_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch, arch):
    select = sampling.SlotSampler.select

    def altered(self, rows, reqs=(), **kw):
        out = np.array(select(self, rows, reqs, **kw))
        out[0] = (out[0] + 1) % self.vocab
        return out

    monkeypatch.setattr(sampling.SlotSampler, "select", altered)
    out = rehearse("closed", arch=arch, check_requests=10_000,
                   limit=LIMIT[arch.name])
    assert out["correct"] is False


@pytest.mark.parametrize("arch", [TINY, TINY_TIED, TINY_MOE],
                         ids=["dense", "dense-tied", "moe"])
def test_the_sound_path_is_correct(arch):
    out = rehearse("open", arch=arch, check_requests=10_000,
                   limit=LIMIT[arch.name])
    assert out["correct"] is True
