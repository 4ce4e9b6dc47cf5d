"""Per-slot token selection for the serving engine: greedy or sampled.

One helper replaces the three argmax sites the schedulers used to carry
separately.  :meth:`SlotSampler.select` takes the fused step's logits
``(B, W, vocab_padded)`` plus the slot->request map and returns host
token ids ``(B, W)`` in ONE device transfer — greedy at ``temperature
== 0`` (bit-identical to the old ``jnp.argmax`` sites), temperature /
top-k sampling otherwise.

Sampling is *canonical-stream*: the PRNG key for a token is derived
solely from ``(seed, request.uid, generation_index)`` — never from the
slot, the step count, or the scheduler.  A request therefore owns one
reproducible token stream: re-running the same traffic through a
different scheduler, after a preemption replay, or under speculative
decoding reads the same keys at the same generation indices and (given
bit-identical logits) emits the same tokens.  Speculative decoding
leans on this hardest — the draft model proposes with the SAME keys the
target uses to verify, so at 100% logit agreement every proposal is
accepted, and any rejection re-samples the same index from the same key
on the next step.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation


@functools.lru_cache(maxsize=None)
def _jit_greedy(vocab: int):
    """Argmax over the unpadded vocab for every logit row — exactly the
    expression the schedulers used inline, so temp=0 streams are bitwise
    unchanged by the refactor."""
    def serve_select_greedy(rows):
        return jnp.argmax(rows[..., :vocab], axis=-1)

    return jax.jit(serve_select_greedy)


@functools.lru_cache(maxsize=None)
def _jit_sample(vocab: int, temperature: float, top_k: int, seed: int):
    """Temperature/top-k categorical sampling with per-(uid, index) keys.

    Row ``(b, i)`` is sampled with key ``fold_in(fold_in(key(seed),
    uids[b]), idx0[b] + i)`` — position ``i`` inside the fed window maps
    to generation index ``idx0[b] + i``, which is what makes multi-token
    (speculative) windows read the same stream as one-token decode.
    """
    def serve_select_sample(rows, uids, idx0):
        B, W, _ = rows.shape
        logits = rows[..., :vocab].astype(jnp.float32) / temperature
        if 0 < top_k < vocab:
            kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        base = jax.random.PRNGKey(seed)
        flat_u = jnp.repeat(uids, W)
        flat_i = (
            idx0[:, None] + jnp.arange(W, dtype=jnp.uint32)[None, :]
        ).reshape(-1)
        keys = jax.vmap(
            lambda u, i: jax.random.fold_in(jax.random.fold_in(base, u), i)
        )(flat_u, flat_i)
        toks = jax.vmap(jax.random.categorical)(
            keys, logits.reshape(B * W, vocab)
        )
        return toks.reshape(B, W)

    return jax.jit(serve_select_sample)


class SlotSampler:
    """Token selection policy for one engine: vocab + temperature +
    top-k + seed, with the compiled select function shared across
    engines via the module-level ``lru_cache`` factories."""

    def __init__(self, vocab: int, *, temperature: float = 0.0,
                 top_k: int = 0, seed: int = 0):
        if vocab < 1:
            raise ValueError(f"vocab must be >= 1, got {vocab}")
        if temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0 (0 = greedy), got {temperature}"
            )
        if top_k < 0:
            raise ValueError(
                f"top_k must be >= 0 (0 = full vocab), got {top_k}"
            )
        self.vocab = int(vocab)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.seed = int(seed)
        #: greedy engines skip the uid/index plumbing entirely
        self.greedy = self.temperature == 0.0
        if self.greedy:
            self._fn = _jit_greedy(self.vocab)
        else:
            self._fn = _jit_sample(
                self.vocab, self.temperature, self.top_k, self.seed
            )

    def select(self, rows: jax.Array, reqs: Sequence[Optional[object]] = (),
               *, offset: int = 0) -> np.ndarray:
        """Pick one token per logit row — ``rows`` is ``(B, W, >=vocab)``
        from the fused step, ``reqs`` maps slot -> request (``None`` for
        idle slots; any object with ``.uid`` and ``.generated`` works).

        Row ``(b, i)`` is treated as generation index
        ``len(reqs[b].generated) + offset + i`` of request ``reqs[b]``
        (``offset`` shifts the whole window — draft round ``i`` of
        speculative decoding proposes index ``gi + i`` before anything
        is appended).  Rows of idle/irrelevant slots are selected too
        and simply discarded by the caller; their keys can never collide
        with a live stream's.  Returns ``(B, W)`` int64 host tokens via
        a single device transfer, the one place the serve loop waits on
        the device (span ``serve.sync``).
        """
        if self.greedy:
            ids = self._fn(rows)
        else:
            uids = np.array(
                [0 if r is None else int(r.uid) for r in reqs], np.int64
            ).astype(np.uint32)
            idx0 = np.array(
                [0 if r is None else len(r.generated) + offset for r in reqs],
                np.int64,
            ).astype(np.uint32)
            ids = self._fn(rows, jnp.asarray(uids), jnp.asarray(idx0))
        with TraceAnnotation("serve.sync"):
            return np.asarray(ids)
