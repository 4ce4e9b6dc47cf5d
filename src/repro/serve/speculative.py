"""Speculative decoding over the paged KV cache.

A small *draft* model proposes up to ``k`` tokens per busy slot; the
*target* model then scores all of them in ONE fused call of the engine's
prefill step, with per-slot ragged ``valid_len``: the scan cell chunked
prefill uses (bit-exact against token-by-token decode) or, on
dense-attention targets, the chunk-parallel pass (equal to float
tolerance, the same greedy choices).  The longest
proposal prefix that matches the target's own (canonical-stream, see
:mod:`repro.serve.sampling`) choices is accepted, plus the target's one
correction token; the rejected suffix is undone by rewinding
``positions[slot]`` and decref'ing now-stale tail blocks through the
:class:`~repro.serve.block_pool.BlockPool`.

This is the paper's Eq. 1 economics one level up: the k-wide
verification step is a vector issue, the drafted positions are its
lanes, and :func:`repro.core.metrics.acceptance_rate` is the active-lane
fraction — rejected drafts burn issue slots exactly like predicated-out
SVE lanes.

Why the streams stay identical to the non-speculative engine at any
temperature: both the draft proposals and the target verification read
the SAME per-``(request, generation_index)`` PRNG streams, and the
target's choice at index ``i`` is computed from canonical logits
(bitwise on the scan path, to float tolerance on the chunk path)
whenever the prefix through ``i-1`` was accepted.  Accepted tokens are
therefore exactly the tokens the plain engine would have emitted, and a
rejection merely defers index ``i`` to the next step, where the same
key meets the same canonical logits again.  Speculation changes only
how many fused target steps the stream costs, never its content.

Rewind correctness, per cache kind:

* **Attention blocks** — rows past the rewound position are dead weight
  hidden by the causal position mask; the next verification window
  overwrites them before they can be attended (the chunked-prefill
  argument).  Blocks that lie ENTIRELY past the next write position are
  decref'd back to the pool, and ``note_generated_write`` trimming at
  write time already guarantees no prefix-registry key can alias a
  speculated row.
* **SSM / conv state** — accumulated by every scanned token and NOT
  position-masked, so it cannot be rewound by masking.  The decoder
  snapshots the per-slot state leaves (by reference: jax arrays are
  immutable) before each verification, and on any rejection restores
  the snapshot for the rejected slots and replays just their accepted
  tokens through one extra fused call.  The replay starts from the
  identical pre-verification state and feeds the identical tokens, so
  the recomputed state is bitwise what sequential decode would have
  produced.

The draft model must be attention-only (no recurrent state): its paged
f32 cache shares the target's block tables, pool, and copy-on-write
schedule, so draft-side history management costs nothing beyond the
second cache.
"""

from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LayerKind, ModelConfig
from repro.models import transformer
from repro.serve.block_pool import BlockPool
from repro.serve.sampling import SlotSampler


@functools.lru_cache(maxsize=None)
def _jit_draft_prefill(cfg: ModelConfig, block_size: int):
    """Draft-side fused step: always an f32 paged cache (the draft is
    small — quantizing its cache buys nothing and would perturb
    proposals for zero accounting benefit)."""
    return jax.jit(
        lambda p, t, c, pos, bt, lens: transformer.prefill_step_paged(
            p, cfg, t, c, pos, bt, lens, block_size=block_size,
            kv_dtype="f32",
        )
    )


@functools.lru_cache(maxsize=1)
def _jit_restore_state():
    return jax.jit(transformer.restore_slot_state)


def _draft_param_shardings(params, mesh):
    """Megatron rules applied to the draft's params (same rule table as
    the target — the draft is a plain attention LM)."""
    from repro.distributed import sharding as shard_rules
    return shard_rules.serve_param_shardings(params, mesh)


class SpeculativeDecoder:
    """Draft model + verification drain for one :class:`ServeEngine`.

    Owns everything draft-side (config, params, compiled step, the
    proposal sampler) plus the speculative drain loop; the engine's own
    compiled steps, sampler, and accounting are reused through the
    ``eng`` handle passed to :meth:`drain`.
    """

    #: EMA weight for the trailing per-request acceptance rate; 0.5 adapts
    #: within a couple of verification windows (smoke traces are short)
    _ALPHA = 0.5
    #: additive re-probe rate for a stream whose width collapsed to 0 —
    #: a few plain decode steps later it drafts width >= 1 again, so a
    #: distribution shift is never locked out (deterministic, no RNG)
    _RECOVERY = 0.125

    def __init__(self, draft_cfg: ModelConfig, draft_params, k: int, *,
                 target_cfg: ModelConfig, block_size: int,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 adaptive: bool = False, mesh=None,
                 max_batch: Optional[int] = None,
                 max_len: Optional[int] = None):
        if k < 1:
            raise ValueError(f"spec_k must be >= 1, got {k}")
        if any(kind != LayerKind.ATTN for kind in draft_cfg.superblock):
            raise ValueError(
                "the draft model must be attention-only: recurrent "
                "(SSM/conv) draft state cannot share the rewind-by-"
                f"masking path, got superblock {draft_cfg.superblock}"
            )
        self.cfg = draft_cfg
        self.params = draft_params
        self.k = int(k)
        self.block_size = block_size
        self.adaptive = bool(adaptive)
        self.mesh = mesh
        # uid -> EMA of the trailing acceptance rate; absent = optimistic
        # 1.0 (first window drafts full width, like non-adaptive mode)
        self._accept_ema = {}
        # proposals must be valid token ids for BOTH models, and tokens
        # fed back into the draft are clamped to its vocab below
        self.shared_vocab = min(draft_cfg.vocab, target_cfg.vocab)
        self.sampler = SlotSampler(
            self.shared_vocab, temperature=temperature, top_k=top_k,
            seed=seed,
        )
        if mesh is None:
            self._prefill = _jit_draft_prefill(draft_cfg, block_size)
        else:
            # the draft's fused step gets the same explicit-sharding
            # treatment as the target's (attention-only cfg: only the
            # k/v head-split pool rules fire on its cache)
            from repro.serve.engine import _sharded_jits
            self._prefill = _sharded_jits(
                draft_cfg, int(max_batch), int(max_len), block_size,
                "f32", mesh,
            )["prefill"]
            self.params = jax.device_put(
                draft_params, _draft_param_shardings(draft_params, mesh)
            )
        self._restore = _jit_restore_state()

    def _draft_width(self, uid: int) -> int:
        """Per-slot draft width from the trailing acceptance EMA, clamped
        to [0, spec_k].  Non-adaptive engines always draft full width.

        A rejection-heavy stream shrinks toward 0 (plain decode — no
        drafted lanes burned), a well-predicted one grows back toward
        ``k``; a collapsed stream re-probes via the additive
        ``_RECOVERY`` schedule.  Width only changes how FAR we draft,
        never what verification accepts, so served streams are identical
        to the fixed-width engine's.
        """
        if not self.adaptive:
            return self.k
        ema = self._accept_ema.get(uid, 1.0)
        w = int(round(ema * self.k))
        if w <= 0:
            self._accept_ema[uid] = min(1.0, ema + self._RECOVERY)
        return max(0, min(self.k, w))

    def _note_accept(self, uid: int, accepted: int, drafted: int) -> None:
        """Fold one verification window's acceptance into the uid's EMA."""
        if not self.adaptive or drafted <= 0:
            return
        ema = self._accept_ema.get(uid, 1.0)
        self._accept_ema[uid] = (
            (1.0 - self._ALPHA) * ema + self._ALPHA * accepted / drafted
        )

    def _clamp(self, tokens: np.ndarray) -> np.ndarray:
        """Token ids the draft embeds must lie inside ITS vocab; target
        tokens past it are clamped (the draft's conditioning degrades,
        its proposals just get rejected more — correctness never depends
        on the draft's inputs)."""
        return np.minimum(tokens, self.cfg.vocab - 1)

    def warmup(self, eng) -> None:
        """Compile the draft's 1-wide fused step (called from
        :meth:`ServeEngine.warmup`, which warms the target side)."""
        B = eng.max_batch
        dcache = transformer.init_paged_cache(
            self.cfg, B, eng.max_len, self.block_size, "f32",
            mesh=eng.mesh,
        )
        out = self._prefill(
            self.params, jnp.zeros((B, 1), jnp.int32), dcache,
            jnp.zeros((B,), jnp.int32),
            jnp.zeros((B, eng.max_len // self.block_size), jnp.int32),
            jnp.zeros((B,), jnp.int32),
        )
        jax.block_until_ready(out[0])

    # -- the speculative continuous drain --------------------------------------

    def drain(self, eng, max_steps: Optional[int]) -> None:
        """Continuous drain where generation slots advance up to ``k+1``
        tokens per fused target step.

        Each iteration: (1) slots still consuming known tokens (prompt,
        or a preemption replay) are fed one known token, exactly like
        the plain continuous drain; (2) every *generating* slot gets up
        to ``k`` sequential draft proposals; (3) one fused target call
        verifies every slot's window at once (ragged ``lens``); (4) per
        slot, the accepted prefix plus the target's correction token are
        emitted and the rejected suffix is rewound.  The draft cache is
        kept in sync by feeding it every committed token: draft round 0
        covers each slot's current token, later rounds cover the
        proposals themselves.
        """
        # engine.py never imports this module at definition time (the
        # ServeEngine ctor imports it lazily), so this is one-directional
        from repro.serve.engine import _MAX_IDLE_SPINS

        _dev, _dev_tok = eng._dev, eng._dev_tok  # mesh-aware placement
        restore = eng._restore_state or self._restore
        B, bs, k = eng.max_batch, eng.block_size, self.k
        W = k + 1
        nb_slot = eng.max_len // bs
        cache = eng._new_cache()
        dcache = transformer.init_paged_cache(
            self.cfg, B, eng.max_len, bs, "f32", mesh=eng.mesh
        )
        positions = np.zeros(B, np.int32)
        block_tables = np.zeros((B, nb_slot), np.int32)  # 0 = null block
        pool = BlockPool(1 + B * nb_slot, bs,
                         share_prefixes=eng.share_prefixes)
        slot_req = [None] * B
        tokens = np.zeros((B, 1), np.int32)
        reset_mask = np.zeros(B, bool)
        eng._live = {
            "positions": positions, "block_tables": block_tables,
            "free": pool.free, "pool": pool, "slot_req": slot_req,
            "tokens": tokens,
        }
        idle_spins = 0

        try:
            while True:
                pending = eng._call_hooks(
                    busy=any(r is not None for r in slot_req)
                )
                for b in range(B):
                    if slot_req[b] is None and eng.queue:
                        r = eng.queue.popleft()
                        slot_req[b] = r
                        if r.started_s is None:
                            r.started_s = time.time()
                        positions[b] = 0
                        block_tables[b] = 0
                        tokens[b, 0] = r.prompt[0]
                        reset_mask[b] = True
                if all(r is None for r in slot_req):
                    if not pending:
                        break
                    idle_spins += 1  # hooks promise work; let them deliver
                    if idle_spins > _MAX_IDLE_SPINS:
                        raise RuntimeError(
                            "step hooks report pending work but never submit"
                        )
                    continue
                idle_spins = 0
                # occupancy bound: a verification step advances every busy
                # slot by >= 1 position, but stateful targets may spend one
                # extra replay call per rejected step — hence the factor 2
                budget = (max_steps if max_steps is not None
                          else 2 * eng._submitted_work + B)
                if eng.steps >= budget:
                    raise RuntimeError("serve loop did not drain")

                # -- plan: draft width per slot (0 = known-token feed or
                # nothing left to speculate on) -----------------------------
                spec_w = np.zeros(B, np.int32)
                uids_gen = list(slot_req)  # snapshot for stream indexing
                for b, r in enumerate(slot_req):
                    if r is None:
                        continue
                    t = int(positions[b])
                    n_rem = len(r.prompt) + len(r.generated) - t
                    if n_rem == 1:
                        # generating: draft as far as the token budget,
                        # the slot's cache, and (adaptive mode) the uid's
                        # trailing-acceptance width allow (the window
                        # writes through position t + spec_w, which must
                        # stay < max_len)
                        remaining = r.max_new_tokens - len(r.generated)
                        spec_w[b] = max(
                            0, min(self._draft_width(r.uid),
                                   remaining - 1, eng.max_len - 1 - t)
                        )
                any_spec = bool((spec_w > 0).any())

                # -- map blocks + copy-on-write for every position this
                # step writes (t .. t + spec_w[b]), in BOTH caches ----------
                for b, r in enumerate(slot_req):
                    if r is None:
                        continue
                    t = int(positions[b])
                    hi = t + int(spec_w[b])
                    for j in range(t // bs, hi // bs + 1):
                        if block_tables[b, j] == 0:
                            blk = pool.acquire(r.prompt, j)
                            block_tables[b, j] = blk
                            eng.block_history.setdefault(
                                r.uid, []
                            ).append(blk)
                    gen_from = max(t, len(r.prompt))
                    if gen_from <= hi:
                        for j in range(gen_from // bs, hi // bs + 1):
                            old = int(block_tables[b, j])
                            if pool.refcount_of(old) > 1:
                                new = pool.cow(old)
                                cache = eng._copy_block(
                                    cache, jnp.int32(old), jnp.int32(new)
                                )
                                dcache = eng._copy_block(
                                    dcache, jnp.int32(old), jnp.int32(new)
                                )
                                block_tables[b, j] = new
                                eng.block_history.setdefault(
                                    r.uid, []
                                ).append(new)
                            # speculated rows are generated rows: trim any
                            # registry key claiming them BEFORE they are
                            # written, so a rewound row can never alias a
                            # prefix-shared key
                            pool.note_generated_write(
                                int(block_tables[b, j]),
                                max(gen_from, j * bs) % bs,
                            )
                if eng._has_state and reset_mask.any():
                    cache = eng._reset_slots(cache, _dev(reset_mask))
                reset_mask[:] = False
                eng._note_busy(r is not None for r in slot_req)

                # -- draft phase: sequential 1-wide proposals ----------------
                # round 0 feeds every busy slot's current token (keeping the
                # draft cache in sync even during prompt consumption); round
                # i >= 1 feeds proposal d_i at position t + i for slots wide
                # enough — INCLUDING the final round that commits d_w's row
                # without proposing further, so on full acceptance the draft
                # cache is complete through t + w and the next step never
                # attends an unwritten row.  Proposals for index gi + i are
                # sampled from the same canonical stream the target
                # verifies against.
                drafts = np.zeros((B, k), np.int32)
                d_tokens = np.array(tokens)
                d_lens = np.zeros(B, np.int32)
                rounds = int(spec_w.max())  # proposals needed per slot max
                for i in range(rounds + 1):
                    d_lens[:] = 0
                    for b, r in enumerate(slot_req):
                        if r is None:
                            continue
                        if i == 0:
                            d_lens[b] = 1
                        elif int(spec_w[b]) >= i:
                            d_lens[b] = 1
                            d_tokens[b, 0] = drafts[b, i - 1]
                    dlogits, dcache = self._prefill(
                        self.params, _dev_tok(self._clamp(d_tokens)), dcache,
                        _dev(positions + i), _dev(block_tables),
                        _dev(d_lens),
                    )
                    eng.draft_steps += 1
                    if i < rounds:
                        di = self.sampler.select(
                            dlogits, uids_gen, offset=i
                        )
                        for b in range(B):
                            if int(spec_w[b]) > i:
                                drafts[b, i] = int(di[b, 0])

                # -- verification: one fused target call over every slot's
                # ragged window [x_t, d_1 .. d_{w_b}] -----------------------
                pos0 = positions.copy()
                if any_spec:
                    v_tokens = np.zeros((B, W), np.int32)
                    v_lens = np.zeros(B, np.int32)
                    for b, r in enumerate(slot_req):
                        if r is None:
                            continue
                        w_b = int(spec_w[b])
                        v_tokens[b, 0] = tokens[b, 0]
                        v_tokens[b, 1:1 + w_b] = drafts[b, :w_b]
                        v_lens[b] = 1 + w_b
                    snap = (transformer.slot_state(cache)
                            if eng._has_state else None)
                    logits, cache = eng._prefill_paged(
                        eng.params, _dev_tok(v_tokens), cache,
                        _dev(positions), _dev(block_tables), _dev(v_lens),
                    )
                    eng.steps += 1
                    # row i of slot b is the target's canonical choice for
                    # generation index gi + i — valid wherever the proposal
                    # prefix through i-1 matched
                    y = eng._sampler.select(logits, uids_gen)
                else:
                    logits, cache = eng._decode_paged(
                        eng.params, _dev_tok(tokens), cache,
                        _dev(positions), _dev(block_tables),
                    )
                    eng.steps += 1
                    y = eng._sampler.select(logits, uids_gen)

                # -- acceptance, emission, rewind ----------------------------
                replay_lens = np.zeros(B, np.int32)
                for b, r in enumerate(slot_req):
                    if r is None:
                        continue
                    t = int(positions[b])
                    w_b = int(spec_w[b])
                    if w_b == 0:
                        # plain continuous semantics: consume one known
                        # token or append the single selected one
                        positions[b] = t + 1
                        if t + 1 < len(r.prompt):
                            tokens[b, 0] = r.prompt[t + 1]
                            continue
                        gi = t + 1 - len(r.prompt)
                        if gi < len(r.generated):
                            # preemption replay: already served, feed back
                            tokens[b, 0] = r.generated[gi]
                            continue
                        tok = int(y[b, 0])
                        eng._note_first_token(r)
                        r.generated.append(tok)
                        tokens[b, 0] = tok
                        if (len(r.generated) >= r.max_new_tokens
                                or tok == r.eos_id):
                            self._release_slot(
                                b, slot_req, block_tables, positions,
                                tokens, pool, nb_slot, eng
                            )
                        continue
                    # longest proposal prefix matching the target's choices
                    a = 0
                    while a < w_b and int(drafts[b, a]) == int(y[b, a]):
                        a += 1
                    eng.drafted_tokens += w_b
                    eng.accepted_tokens += a
                    eng.rejected_tokens += w_b - a
                    self._note_accept(r.uid, a, w_b)
                    # emit the accepted prefix plus the correction token,
                    # stopping at EOS / budget exactly like 1-wide decode
                    emitted = 0
                    finished = False
                    for i in range(a + 1):
                        tok = int(y[b, i])
                        eng._note_first_token(r)
                        r.generated.append(tok)
                        emitted += 1
                        if (len(r.generated) >= r.max_new_tokens
                                or tok == r.eos_id):
                            finished = True
                            break
                    positions[b] = t + emitted
                    if finished:
                        self._release_slot(
                            b, slot_req, block_tables, positions, tokens,
                            pool, nb_slot, eng
                        )
                        continue
                    tokens[b, 0] = int(y[b, emitted - 1])
                    # rewind: blocks lying entirely past the next write
                    # position hold only rejected rows — return them (decref,
                    # never free: sharing may keep them alive elsewhere)
                    p = t + emitted
                    for j in range(p // bs + 1, (t + w_b) // bs + 1):
                        if block_tables[b, j] != 0:
                            pool.decref(int(block_tables[b, j]))
                            block_tables[b, j] = 0
                    if eng._has_state and emitted < w_b + 1:
                        replay_lens[b] = emitted

                # -- stateful rewind: restore pre-verification state for
                # rejected slots and replay their accepted tokens -----------
                if eng._has_state and any_spec and replay_lens.any():
                    mask = replay_lens > 0
                    cache = restore(cache, snap, _dev(mask))
                    _, cache = eng._prefill_paged(
                        eng.params, _dev_tok(v_tokens), cache,
                        _dev(pos0), _dev(block_tables), _dev(replay_lens),
                    )
                    eng.steps += 1
        finally:
            eng._absorb_pool(pool)
            eng._live = None

    @staticmethod
    def _release_slot(b, slot_req, block_tables, positions, tokens, pool,
                      nb_slot, eng) -> None:
        """Finish slot ``b``'s request and return its blocks (shared
        blocks survive under their other referents' refcounts)."""
        eng._finish(slot_req[b])
        for j in range(nb_slot):
            if block_tables[b, j] != 0:
                pool.decref(int(block_tables[b, j]))
        block_tables[b] = 0
        positions[b] = 0
        tokens[b, 0] = 0
        slot_req[b] = None
