"""Slot-level continuously-batched serving engine over a paged KV cache.

The production serve path: a fixed set of ``max_batch`` slots advances
through one fused :func:`~repro.models.transformer.decode_step_paged` per
token, and every slot carries its OWN cache position.  When a request
finishes (EOS or token budget) its slot is refilled from the queue on the
very next step and its cache blocks return to a shared pool — finished
slots are masked out and reassigned, never waited on.  This is the paper's
predication insight (Eq. 1: keep the lanes busy) executed at the serving
layer, where a fused decode step is the vector issue and the batch slots
are its lanes; :func:`repro.core.metrics.slot_utilization` reports the
resulting busy-lane fraction.

The KV cache is PAGED: attention caches live in a physical block pool
addressed through per-slot block tables (``block_size`` tokens per block,
block 0 reserved as the null block idle slots write into), so a slot's
logical cache never moves when requests of different lengths come and go,
and blocks freed by one request are immediately reused by the next.
Scheduling state — positions, block tables, the free list — is host-side
numpy ("slot accounting"); only the pools live on device, and the fused
step is compiled exactly once per engine.

``prefill_chunk > 1`` turns on prefill/decode disaggregation: prompts are
committed up to ``prefill_chunk`` tokens per fused call while in-flight
decode slots keep advancing one token per step in the SAME fused call.
On dense-attention configs that call is
:func:`~repro.models.transformer.prefill_chunk_paged`, one pass through
the layers over every chunk row, which agrees with token-by-token decode
to float tolerance and gives the same greedy tokens; MoE, MLA, SSM and
hybrid configs run :func:`~repro.models.transformer.prefill_step_paged`,
a scan over the same per-token cell as decode, so their served streams
stay bit-identical.  ``prefill_budget`` caps the total prefill tokens
admitted per step — decode tokens are never counted against it — so a
long prompt cannot starve decode latency; time-to-first-token
(``ttft_p50_s``/``ttft_p95_s``) is the metric this trades against raw
step count.

``scheduler="wave"`` keeps the legacy lockstep behavior (admit a wave,
run every slot to the wave's horizon) as the golden-equivalence baseline:
both schedulers feed identical per-request token sequences, so greedy
outputs must match token-for-token while the continuous scheduler spends
strictly fewer fused steps on ragged workloads.

**Step hooks** let a traffic harness drive the engine from outside the
drain loop: every scheduling iteration calls each hook with
``hook(engine, busy) -> bool`` (the return value means "I may still
deliver work").  Hooks submit mid-flight arrivals, inject faults
(:meth:`ServeEngine.preempt`, a raised exception simulating device loss),
or just observe.  Preempted requests are requeued with their progress and
*replayed*: already-served tokens are fed back verbatim on resume, so a
preemption can never change the served token stream — the scenario
harness (:mod:`repro.scenarios`) asserts exactly that against a
fault-free golden twin.

**Profiler spans** name what the host is doing while the device waits:
each iteration of the continuous drains is one ``serve.step`` span
(``jax.profiler.StepTraceAnnotation``, with ``step_num``) holding the
flat phases ``serve.hooks``, ``serve.schedule``, ``serve.h2d``,
``serve.dispatch``, ``serve.select`` (around the sampler's blocking
``serve.sync``) and ``serve.commit``, in that order.  Iterations that
admit or finish requests carry their uids as span metadata.  A span
costs well under a microsecond while no profiler is active, so they are
always on.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import LayerKind, ModelConfig
from repro.core import metrics as core_metrics
from repro.models import transformer
from repro.serve.block_pool import BlockPool
from repro.serve.sampling import SlotSampler

SCHEDULERS = ("continuous", "wave")

#: A step hook: called once per scheduling iteration with (engine, busy);
#: returns True while it may still deliver work (keeps the drain alive).
StepHook = Callable[["ServeEngine", bool], bool]

#: Spin cap for a fully idle engine whose hooks keep claiming pending
#: work without ever submitting any — a misbehaving hook, not traffic.
_MAX_IDLE_SPINS = 100_000


def _bucket_width(m: int, cap: int) -> int:
    """Smallest power-of-two >= m, clamped to cap (prefill-step widths are
    bucketed so each width traces once and partial chunks don't pay for
    the full chunk's masked cells)."""
    w = 1
    while w < m:
        w *= 2
    return min(w, cap)


def _uids(reqs) -> str:
    """Request uids as one span-metadata value (the profiler splits
    metadata on commas, so they are joined by spaces)."""
    return " ".join(str(r.uid) for r in reqs)


def _dev(x: np.ndarray) -> jax.Array:
    """Hand a scheduler array to the device WITHOUT aliasing it.

    On the CPU backend ``jnp.asarray`` zero-copies a 64-byte-aligned
    contiguous numpy buffer, so the device computation reads the host
    memory directly — but the drain loops mutate these arrays in place
    immediately after dispatch, and the fused step's cache-commit thunks
    can still be reading them after the logits sync (XLA CPU completes
    outputs independently).  A private copy makes the handoff immune:
    the device may alias the copy, which nothing ever mutates.
    """
    return jnp.asarray(np.array(x, copy=True))


def _dev_placed(sharding: NamedSharding):
    """Mesh-aware `_dev`: hand a scheduler array to every device of the
    mesh under an explicit sharding (replicated for slot accounting,
    slot-over-data for token lanes).  The committed placement matches the
    fused steps' ``in_shardings`` exactly, so dispatch never re-infers or
    re-shards; the private copy keeps the same anti-aliasing contract as
    the single-device path."""

    def put(x: np.ndarray) -> jax.Array:
        return jax.device_put(np.array(x, copy=True), sharding)

    return put


# The jitted steps are named functions, so that a profiler trace shows each
# device program under its own name (``jit_serve_decode_step``, ...), not
# as ``jit__lambda``.


def _decode_step_fn(cfg: ModelConfig, block_size: int, kv_dtype: str):
    def serve_decode_step(p, t, c, pos, bt):
        return transformer.decode_step_paged(
            p, cfg, t, c, pos, bt, block_size=block_size, kv_dtype=kv_dtype
        )

    return serve_decode_step


def _prefill_step_fn(cfg: ModelConfig, block_size: int, kv_dtype: str):
    """The chunk-parallel pass where the config allows it
    (:func:`~repro.models.transformer.chunk_parallel`), else the scan of
    one-token cells."""
    step = (transformer.prefill_chunk_paged
            if transformer.chunk_parallel(cfg)
            else transformer.prefill_step_paged)

    def serve_prefill_step(p, t, c, pos, bt, lens):
        return step(p, cfg, t, c, pos, bt, lens, block_size=block_size,
                    kv_dtype=kv_dtype)

    return serve_prefill_step


@functools.lru_cache(maxsize=None)
def _jit_decode(cfg: ModelConfig):
    """One compiled dense decode step per ModelConfig (configs are frozen
    dataclasses, so engines serving the same config share the trace)."""
    def serve_decode_dense(p, t, c):
        return transformer.decode_step(p, cfg, t, c)

    return jax.jit(serve_decode_dense)


@functools.lru_cache(maxsize=None)
def _jit_decode_paged(cfg: ModelConfig, block_size: int, kv_dtype: str):
    return jax.jit(_decode_step_fn(cfg, block_size, kv_dtype))


@functools.lru_cache(maxsize=None)
def _jit_prefill_paged(cfg: ModelConfig, block_size: int, kv_dtype: str):
    """Fused chunked-prefill step (chunk width is baked into the token
    array's shape, so each (config, block_size, chunk) traces once)."""
    return jax.jit(_prefill_step_fn(cfg, block_size, kv_dtype))


@functools.lru_cache(maxsize=1)
def _jit_reset_slots():
    return jax.jit(transformer.reset_paged_slots)


@functools.lru_cache(maxsize=1)
def _jit_copy_block():
    """COW device copy (src/dst are traced scalars: one trace per cache
    structure serves every copy).  The cache operand is DONATED: the
    copy updates the pool buffers in place instead of rebuilding every
    leaf, so a single-block COW costs O(block), not O(pool), and never
    transiently doubles pool memory.  Safe because both drain loops
    rebind ``cache`` to the result and never touch the old reference."""
    return jax.jit(transformer.copy_paged_block, donate_argnums=0)


@functools.lru_cache(maxsize=None)
def _sharded_jits(cfg: ModelConfig, batch: int, max_len: int,
                  block_size: int, kv_dtype: str, mesh):
    """Mesh-partitioned twins of the paged jit factories.

    One compiled step per (config, batch, max_len, block, kv_dtype, mesh)
    — Mesh is hashable, so engines serving the same shape share traces
    exactly like the single-device factories.  Every step is invoked with
    EXPLICIT ``in_shardings``/``out_shardings``: params follow
    :func:`repro.distributed.sharding.param_shardings` (column/row-parallel
    projections, expert-parallel MoE stacks), the paged cache follows
    :func:`~repro.distributed.sharding.paged_cache_shardings` (head-split
    block pools), tokens follow :func:`~repro.distributed.sharding.batch_spec`
    (slots over the data axes), and all host-side slot accounting
    (positions, block tables, lens, masks) plus the logits output stay
    replicated.  Shapes are derived via ``jax.eval_shape`` — nothing is
    allocated here.
    """
    from repro.distributed import sharding as shard_rules

    p_struct = jax.eval_shape(
        lambda key: transformer.init_lm(key, cfg), jax.random.PRNGKey(0)
    )
    cache_struct = jax.eval_shape(
        lambda: transformer.init_paged_cache(
            cfg, batch, max_len, block_size, kv_dtype
        )
    )
    p_sh = shard_rules.serve_param_shardings(p_struct, mesh)
    cache_sh = shard_rules.paged_cache_shardings(cache_struct, mesh)
    rep = shard_rules.replicated(mesh)
    tok = NamedSharding(mesh, shard_rules.batch_spec(mesh, batch, 2))
    snap_sh = shard_rules.paged_cache_shardings(
        transformer.slot_state(cache_struct), mesh
    )
    decode = jax.jit(
        _decode_step_fn(cfg, block_size, kv_dtype),
        in_shardings=(p_sh, tok, cache_sh, rep, rep),
        out_shardings=(rep, cache_sh),
    )
    prefill = jax.jit(
        _prefill_step_fn(cfg, block_size, kv_dtype),
        in_shardings=(p_sh, tok, cache_sh, rep, rep, rep),
        out_shardings=(rep, cache_sh),
    )
    reset = jax.jit(
        transformer.reset_paged_slots,
        in_shardings=(cache_sh, rep), out_shardings=cache_sh,
    )
    copy = jax.jit(
        transformer.copy_paged_block, donate_argnums=0,
        in_shardings=(cache_sh, rep, rep), out_shardings=cache_sh,
    )
    restore = jax.jit(
        transformer.restore_slot_state,
        in_shardings=(cache_sh, snap_sh, rep), out_shardings=cache_sh,
    )
    return {
        "decode": decode, "prefill": prefill, "reset": reset,
        "copy": copy, "restore": restore, "tok_sharding": tok,
        "rep_sharding": rep,
    }


class RequestTooLong(ValueError):
    """Raised at submit() time when prompt + budget exceed one slot's cache.

    Typed and early on purpose: under the old in-wave ``assert`` a single
    oversized request crashed the whole wave it was batched into.
    """


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 16
    eos_id: int = -1  # -1: never stops early

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32)
        self.generated: List[int] = []
        self.done = False
        self.submitted_s: Optional[float] = None
        self.started_s: Optional[float] = None
        self.first_token_s: Optional[float] = None
        self.finished_s: Optional[float] = None
        # step-clock twins of the wall-clock stamps: fused-step counter at
        # submit and at first token — deterministic given the trace, so
        # the perf gate can hold TTFT tight where wall time is noisy
        self.submitted_step: Optional[int] = None
        self.first_token_step: Optional[int] = None

    @property
    def latency_s(self) -> Optional[float]:
        """Submit -> finish wall time (includes queue wait — the quantity
        continuous batching exists to shrink)."""
        if self.submitted_s is None or self.finished_s is None:
            return None
        return self.finished_s - self.submitted_s

    @property
    def ttft_s(self) -> Optional[float]:
        """Submit -> first generated token (time-to-first-token).  Survives
        preemption: replayed tokens never restamp it."""
        if self.submitted_s is None or self.first_token_s is None:
            return None
        return self.first_token_s - self.submitted_s

    @property
    def ttft_steps(self) -> Optional[int]:
        """Fused steps between submit and first generated token — the
        deterministic TTFT (same trace => same value on any machine)."""
        if self.submitted_step is None or self.first_token_step is None:
            return None
        return self.first_token_step - self.submitted_step


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 4,
                 max_len: int = 256, scheduler: str = "continuous",
                 block_size: int = 16, prefill_chunk: int = 1,
                 prefill_budget: Optional[int] = None,
                 kv_dtype: str = "f32", share_prefixes: bool = False,
                 temperature: float = 0.0, top_k: int = 0,
                 sample_seed: int = 0, spec_k: int = 0,
                 draft_cfg: Optional[ModelConfig] = None,
                 draft_params=None, spec_adaptive: bool = False,
                 mesh=None):
        if scheduler not in SCHEDULERS:
            raise ValueError(f"scheduler must be one of {SCHEDULERS}, "
                             f"got {scheduler!r}")
        if scheduler == "continuous" and max_len % block_size:
            # wave mode uses the dense cache and never touches the pool
            raise ValueError(f"max_len {max_len} must be a multiple of "
                             f"block_size {block_size}")
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if prefill_chunk > 1 and scheduler != "continuous":
            raise ValueError(
                "chunked prefill (prefill_chunk > 1) requires the "
                "continuous scheduler; wave mode replays prompts densely"
            )
        if prefill_budget is not None and prefill_budget < 1:
            raise ValueError(
                f"prefill_budget must be >= 1 (or None), got {prefill_budget}"
            )
        if kv_dtype not in transformer.KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {transformer.KV_DTYPES}, "
                f"got {kv_dtype!r}"
            )
        if kv_dtype != "f32" and scheduler != "continuous":
            raise ValueError(
                "quantized KV blocks require the continuous scheduler; "
                "wave mode serves from the dense unquantized cache"
            )
        if share_prefixes and scheduler != "continuous":
            raise ValueError(
                "prefix sharing requires the continuous scheduler's "
                "paged block pool"
            )
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0 (0 = off), got {spec_k}")
        if spec_k > 0:
            if draft_cfg is None or draft_params is None:
                raise ValueError(
                    "speculative decoding (spec_k > 0) requires a draft "
                    "model: pass draft_cfg and draft_params"
                )
            if scheduler != "continuous":
                raise ValueError(
                    "speculative decoding requires the continuous "
                    "scheduler's paged cache"
                )
            if prefill_chunk > 1:
                raise ValueError(
                    "speculative decoding runs its own multi-token "
                    "verification window; combine it with prefill_chunk=1"
                )
        elif draft_cfg is not None or draft_params is not None:
            raise ValueError(
                "a draft model was provided but spec_k is 0; pass "
                "spec_k >= 1 to enable speculative decoding"
            )
        if spec_adaptive and spec_k == 0:
            raise ValueError(
                "spec_adaptive requires speculative decoding (spec_k >= 1)"
            )
        if mesh is not None:
            if scheduler != "continuous":
                raise ValueError(
                    "mesh serving requires the continuous scheduler; wave "
                    "mode is the single-device golden baseline"
                )
            for ax in ("data", "model"):
                if ax not in mesh.axis_names:
                    raise ValueError(
                        f"serve mesh must carry ('data', 'model') axes, "
                        f"got {mesh.axis_names}"
                    )
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.scheduler = scheduler
        self.block_size = block_size
        self.prefill_chunk = prefill_chunk
        self.prefill_budget = prefill_budget
        self.kv_dtype = kv_dtype
        self.share_prefixes = share_prefixes
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.sample_seed = int(sample_seed)
        self.spec_k = int(spec_k)
        self.queue: Deque[Request] = deque()
        self.completed: Dict[int, Request] = {}
        # slot accounting (Eq. 1 analogue): fused steps are vector issues,
        # slots are lanes, busy_slot_steps counts the useful lane-steps
        self.steps = 0
        self.busy_slot_steps = 0
        self.wall_s = 0.0
        self.preemptions = 0
        # block-pool dedup accounting, accumulated across drains (see
        # repro.serve.block_pool): served vs stored block-spans, prefix
        # hits, and copy-on-write divergences
        self.logical_blocks = 0
        self.physical_blocks = 0
        self.shared_block_hits = 0
        self.cow_copies = 0
        # speculative-decoding accounting (all zero when spec_k == 0, so
        # the ledger schema is identical across +spec forks): exact token
        # counters plus the two step clocks — draft fused calls vs target
        # fused calls (the latter mirrors self.steps)
        self.drafted_tokens = 0
        self.accepted_tokens = 0
        self.rejected_tokens = 0
        self.draft_steps = 0
        #: step hooks (see module docstring): traffic feeders, fault plans
        self.step_hooks: List[StepHook] = []
        #: uid -> physical block ids the request occupied, in allocation
        #: order (pool-reuse introspection; continuous scheduler only)
        self.block_history: Dict[int, List[int]] = {}
        self.mesh = mesh
        self.spec_adaptive = bool(spec_adaptive)
        self._decode = _jit_decode(cfg)
        if mesh is None:
            self._decode_paged = _jit_decode_paged(cfg, block_size, kv_dtype)
            self._prefill_paged = _jit_prefill_paged(cfg, block_size, kv_dtype)
            self._reset_slots = _jit_reset_slots()
            self._copy_block = _jit_copy_block()
            self._restore_state = None
            self._dev = _dev
            self._dev_tok = _dev
        else:
            # tensor-parallel serve path: params are placed once by the
            # Megatron-style rules, every fused step carries explicit
            # in/out shardings, and host arrays are committed replicated
            # (tokens: slot-over-data) so no dispatch ever re-infers
            # placement — sharding is pure placement, never semantics
            from repro.distributed import sharding as shard_rules
            self.params = jax.device_put(
                params, shard_rules.serve_param_shardings(params, mesh)
            )
            sj = _sharded_jits(cfg, max_batch, max_len, block_size,
                               kv_dtype, mesh)
            self._decode_paged = sj["decode"]
            self._prefill_paged = sj["prefill"]
            self._reset_slots = sj["reset"]
            self._copy_block = sj["copy"]
            self._restore_state = sj["restore"]
            rep, tok = sj["rep_sharding"], sj["tok_sharding"]
            self._dev = _dev_placed(rep)
            self._dev_tok = _dev_placed(tok)
        self._has_state = any(k != LayerKind.ATTN for k in cfg.superblock)
        #: which multi-token step ``_prefill_step_fn`` picked: "chunk" (one
        #: pass over the chunk's rows) or "scan" (one-token cells)
        self.prefill_path = ("chunk" if transformer.chunk_parallel(cfg)
                             else "scan")
        # per-device busy-lane accounting (Eq. 1 one level up): the data
        # axis shards the slot lanes across device groups when divisible;
        # otherwise (and with no mesh) there is a single shard and
        # device_lane_utilization degenerates to slot_utilization
        n_data = 1
        if mesh is not None:
            from repro.launch.mesh import axis_size
            n_data = axis_size(mesh, "data")
        self._lane_shards = n_data if max_batch % n_data == 0 else 1
        self._lanes_per_shard = max_batch // self._lane_shards
        self.device_busy_lane_steps = np.zeros(self._lane_shards, np.int64)
        self._sampler = SlotSampler(
            cfg.vocab, temperature=self.temperature, top_k=self.top_k,
            seed=self.sample_seed,
        )
        if self.spec_k > 0:
            # imported here, not at module top: speculative.py reuses this
            # module's jit factories, so the import is one-directional only
            # at definition time
            from repro.serve.speculative import SpeculativeDecoder
            self._spec: Optional[SpeculativeDecoder] = SpeculativeDecoder(
                draft_cfg, draft_params, self.spec_k, target_cfg=cfg,
                block_size=block_size, temperature=self.temperature,
                top_k=self.top_k, seed=self.sample_seed,
                adaptive=self.spec_adaptive, mesh=mesh,
                max_batch=max_batch, max_len=max_len,
            )
        else:
            self._spec = None
        # token-work budget for the drain-loop runaway guard: grows with
        # every submit (and preemption replay), so hook-fed traffic gets
        # the same exact occupancy bound pre-submitted traffic always had
        self._submitted_work = 0
        # live continuous-drain state (positions/tables/free/slots); only
        # non-None while _drain_continuous runs — preempt() needs it
        self._live: Optional[Dict[str, Any]] = None

    # -- bookkeeping -----------------------------------------------------------

    @property
    def total_slot_steps(self) -> int:
        return self.steps * self.max_batch

    @property
    def slot_utilization(self) -> float:
        return core_metrics.slot_utilization(
            self.busy_slot_steps, self.steps, self.max_batch
        )

    @property
    def mesh_shape(self) -> Optional[str]:
        """The mesh as a ``DxM`` string (ledger fork segment), or None
        when serving single-device."""
        if self.mesh is None:
            return None
        from repro.launch.mesh import axis_size
        return (f"{axis_size(self.mesh, 'data')}x"
                f"{axis_size(self.mesh, 'model')}")

    @property
    def device_lane_utilization(self) -> float:
        return core_metrics.device_lane_utilization(
            self.device_busy_lane_steps.tolist(), self.steps,
            self._lanes_per_shard,
        )

    def _note_busy(self, busy_flags) -> None:
        """Fold one fused step's per-slot busy flags into both the global
        busy-lane counter and the per-device-shard counters (slot ``b``
        belongs to data shard ``b // lanes_per_shard``, matching
        `batch_spec`'s contiguous slot-over-data layout)."""
        flags = [bool(f) for f in busy_flags]
        self.busy_slot_steps += sum(flags)
        lps = self._lanes_per_shard
        for s in range(self._lane_shards):
            self.device_busy_lane_steps[s] += sum(
                flags[s * lps:(s + 1) * lps]
            )

    def _new_cache(self):
        """A fresh paged cache, placed by the mesh's pool rules when one
        is active (head-split k/v pools, replicated scale pools)."""
        return transformer.init_paged_cache(
            self.cfg, self.max_batch, self.max_len, self.block_size,
            self.kv_dtype, mesh=self.mesh,
        )

    def submit(self, req: Request) -> None:
        horizon = len(req.prompt) + req.max_new_tokens
        if horizon > self.max_len:
            raise RequestTooLong(
                f"request {req.uid}: prompt[{len(req.prompt)}] + "
                f"max_new_tokens[{req.max_new_tokens}] = {horizon} exceeds "
                f"the per-slot cache ({self.max_len} tokens)"
            )
        if len(req.prompt) == 0:
            raise ValueError(f"request {req.uid}: empty prompt")
        req.submitted_s = time.time()
        req.submitted_step = self.steps
        self._submitted_work += horizon
        self.queue.append(req)

    def add_step_hook(self, hook: StepHook) -> None:
        self.step_hooks.append(hook)

    def warmup(self) -> None:
        """Compile the engine's fused step before any traffic arrives.

        One throwaway call on a dummy cache (all-null block tables: every
        paged write lands in the reserved null block, and the cache is
        discarded), so the jit trace is cached by shape when the drain
        loop makes its first real call.  Without this, the first request's
        TTFT measures XLA compilation, not scheduling — production servers
        warm up for exactly this reason.  No-op on engine counters.
        """
        B = self.max_batch
        if self.scheduler == "wave":
            cache = transformer.init_cache(self.cfg, B, self.max_len)
            out = self._decode(
                self.params, jnp.zeros((B, 1), jnp.int32), cache
            )
            jax.block_until_ready(out[0])
            return
        cache = self._new_cache()
        pos = jnp.zeros((B,), jnp.int32)
        bt = jnp.zeros((B, self.max_len // self.block_size), jnp.int32)
        if self.prefill_chunk > 1:
            # chunked engines dispatch the native decode step plus one
            # prefill trace per power-of-two bucket width — warm every width
            # the drain can hit so no compile lands inside a request
            w = 2
            while True:
                w = min(w, self.prefill_chunk)
                out = self._prefill_paged(
                    self.params, jnp.zeros((B, w), jnp.int32),
                    cache, pos, bt, jnp.zeros((B,), jnp.int32),
                )
                jax.block_until_ready(out[0])
                if w == self.prefill_chunk:
                    break
                w *= 2
        if self._spec is not None:
            # speculative engines dispatch the (k+1)-wide verification
            # step (replay reuses the same trace) and the draft model's
            # 1-wide step — warm both alongside the native decode step
            out = self._prefill_paged(
                self.params, jnp.zeros((B, self.spec_k + 1), jnp.int32),
                cache, pos, bt, jnp.zeros((B,), jnp.int32),
            )
            jax.block_until_ready(out[0])
            self._spec.warmup(self)
        out = self._decode_paged(
            self.params, jnp.zeros((B, 1), jnp.int32), cache, pos, bt
        )
        jax.block_until_ready(out[0])

    def _call_hooks(self, busy: bool) -> bool:
        """Run every step hook; True while any may still deliver work."""
        pending = False
        for hook in self.step_hooks:
            pending = bool(hook(self, busy)) or pending
        return pending

    def _absorb_pool(self, pool: BlockPool) -> None:
        """Fold one drain's block-pool dedup counters into the engine's
        (each ``run_until_drained`` builds a fresh cache and pool)."""
        self.logical_blocks += pool.logical_blocks
        self.physical_blocks += pool.physical_blocks
        self.shared_block_hits += pool.shared_hits
        self.cow_copies += pool.cow_copies

    def _finish(self, req: Request) -> None:
        req.done = True
        if req.finished_s is None:
            req.finished_s = time.time()
        self.completed[req.uid] = req

    def _note_first_token(self, req: Request) -> None:
        if req.first_token_s is None:
            req.first_token_s = time.time()
            req.first_token_step = self.steps  # the call that produced it

    def _admit(self, reset_mask) -> List[Request]:
        """Refill every free slot of the live drain from the queue NOW —
        the lane is re-predicated, not idled until a wave drains.  Each
        admitted slot starts at position 0 with no blocks, is marked for a
        state reset, and is fed its first prompt token (the chunked drain
        rebuilds its whole feed every step).  Returns the admitted
        requests."""
        live = self._live
        slot_req = live["slot_req"]
        admitted = []
        for b in range(self.max_batch):
            if slot_req[b] is None and self.queue:
                r = self.queue.popleft()
                slot_req[b] = r
                if r.started_s is None:
                    r.started_s = time.time()
                live["positions"][b] = 0
                live["block_tables"][b] = 0
                live["tokens"][b, 0] = r.prompt[0]
                reset_mask[b] = True
                admitted.append(r)
        return admitted

    def _free_slot(self, b: int) -> None:
        """Empty slot ``b`` of the live drain.  Its blocks are decref'd,
        never freed: a prefix-shared block may still back another slot's
        cache — it returns to the free list only at refcount 0 (LIFO: the
        next admission reuses this request's blocks first)."""
        live = self._live
        block_tables, pool = live["block_tables"], live["pool"]
        for j in range(block_tables.shape[1]):
            if block_tables[b, j] != 0:
                pool.decref(int(block_tables[b, j]))
        block_tables[b] = 0
        live["positions"][b] = 0
        live["tokens"][b] = 0
        live["slot_req"][b] = None

    def _map_chunk(self, cache, pool: BlockPool, block_tables, b: int,
                   r: Request, t0: int, n_b: int):
        """Map the blocks that slot ``b``'s rows ``t0 .. t0 + n_b - 1`` land
        in (with sharing on, acquire() may return another slot's block
        holding the same exact prompt chain), and copy-on-write any block
        receiving a generated-token row while other slots still reference
        it.  Returns the cache, which a COW copy rebinds."""
        bs = self.block_size
        last = (t0 + n_b - 1) // bs
        for j in range(t0 // bs, last + 1):
            if block_tables[b, j] == 0:
                blk = pool.acquire(r.prompt, j)
                block_tables[b, j] = blk
                self.block_history.setdefault(r.uid, []).append(blk)
        gen_from = max(t0, len(r.prompt))
        if gen_from < t0 + n_b:
            for j in range(gen_from // bs, last + 1):
                old = int(block_tables[b, j])
                if pool.refcount_of(old) > 1:
                    new = pool.cow(old)
                    cache = self._copy_block(
                        cache, jnp.int32(old), jnp.int32(new)
                    )
                    block_tables[b, j] = new
                    self.block_history.setdefault(r.uid, []).append(new)
                # in-place generated rows land from max(gen_from, j*bs)
                # onward in this block: trim any registry key claiming them
                pool.note_generated_write(
                    int(block_tables[b, j]), max(gen_from, j * bs) % bs
                )
        return cache

    def preempt(self, uid: Optional[int] = None) -> Optional[int]:
        """Evict one in-flight request from its slot (continuous only).

        The request is requeued at the FRONT of the queue with its
        ``generated`` tokens intact; on re-admission the engine replays
        prompt + generated through the rebuilt cache and only then starts
        appending, so the served stream is bit-identical to an unfaulted
        run.  Picks ``uid``'s slot, or the deepest busy slot (max cache
        position, lowest slot index on ties).  Returns the preempted uid,
        or None when nothing was preemptible.  Only callable from a step
        hook while the continuous scheduler is draining.
        """
        live = self._live
        if live is None:
            raise RuntimeError(
                "preempt() is only available from a step hook while the "
                "continuous scheduler is draining"
            )
        slot_req, positions = live["slot_req"], live["positions"]
        if uid is not None:
            picks = [b for b, r in enumerate(slot_req)
                     if r is not None and r.uid == uid]
        else:
            picks = sorted(
                (b for b, r in enumerate(slot_req) if r is not None),
                key=lambda b: (-int(positions[b]), b),
            )
        if not picks:
            return None
        b = picks[0]
        req = slot_req[b]
        # replay budget: the resumed run re-spends prompt + generated steps
        self._submitted_work += len(req.prompt) + req.max_new_tokens
        self._free_slot(b)
        self.queue.appendleft(req)
        self.preemptions += 1
        return req.uid

    # -- wave scheduler (legacy lockstep, golden baseline) ---------------------

    def _run_wave(self, wave: List[Request]) -> None:
        B = self.max_batch
        cache = transformer.init_cache(self.cfg, B, self.max_len)
        prompt_len = np.array(
            [len(r.prompt) for r in wave] + [1] * (B - len(wave)), np.int32
        )
        horizon = int(max(
            len(r.prompt) + r.max_new_tokens for r in wave
        ))
        if horizon > self.max_len:  # unreachable: submit() already rejects
            raise RequestTooLong(f"wave horizon {horizon} > {self.max_len}")
        tokens = np.zeros((B, 1), np.int32)
        for s, r in enumerate(wave):
            tokens[s, 0] = r.prompt[0]
            r.started_s = time.time()

        for t in range(horizon - 1):
            self._call_hooks(busy=True)  # arrivals land in the NEXT wave
            self._note_busy(
                [not r.done for r in wave] + [False] * (B - len(wave))
            )
            logits, cache = self._decode(self.params, _dev(tokens), cache)
            self.steps += 1
            slots = list(wave) + [None] * (B - len(wave))
            nxt = self._sampler.select(logits, slots)[:, 0]
            for s, r in enumerate(wave):
                if r.done:
                    continue
                if t + 1 < prompt_len[s]:
                    tokens[s, 0] = r.prompt[t + 1]  # still consuming prompt
                else:
                    tok = int(nxt[s])
                    self._note_first_token(r)
                    r.generated.append(tok)
                    tokens[s, 0] = tok
                    if (len(r.generated) >= r.max_new_tokens or tok == r.eos_id):
                        r.done = True
                        r.finished_s = time.time()
            if all(r.done for r in wave):
                break
        for r in wave:
            self._finish(r)

    def _drain_waves(self, max_waves: int) -> None:
        waves = 0
        idle_spins = 0
        while True:
            pending = self._call_hooks(busy=False)
            if not self.queue:
                if not pending:
                    break
                idle_spins += 1  # hooks promise work; let them deliver
                if idle_spins > _MAX_IDLE_SPINS:
                    raise RuntimeError(
                        "step hooks report pending work but never submit"
                    )
                continue
            idle_spins = 0
            if waves >= max_waves:
                raise RuntimeError("serve loop did not drain")
            wave = [self.queue.popleft()
                    for _ in range(min(self.max_batch, len(self.queue)))]
            self._run_wave(wave)
            waves += 1

    # -- continuous scheduler (per-slot positions, paged blocks) ---------------

    def _drain_continuous(self, max_steps: Optional[int]) -> None:
        B, bs = self.max_batch, self.block_size
        nb_slot = self.max_len // bs
        cache = self._new_cache()
        positions = np.zeros(B, np.int32)
        block_tables = np.zeros((B, nb_slot), np.int32)  # 0 = null block
        pool = BlockPool(1 + B * nb_slot, bs,
                         share_prefixes=self.share_prefixes)
        slot_req: List[Optional[Request]] = [None] * B
        tokens = np.zeros((B, 1), np.int32)
        reset_mask = np.zeros(B, bool)
        self._live = {
            "positions": positions, "block_tables": block_tables,
            "free": pool.free, "pool": pool, "slot_req": slot_req,
            "tokens": tokens,
        }
        idle_spins = 0

        try:
            while True:
                with StepTraceAnnotation("serve.step", step_num=self.steps):
                    with TraceAnnotation("serve.hooks"):
                        pending = self._call_hooks(
                            busy=any(r is not None for r in slot_req)
                        )
                    with TraceAnnotation("serve.schedule") as span:
                        admitted = self._admit(reset_mask)
                        if admitted:
                            span.set_metadata(admitted=_uids(admitted))
                        if all(r is None for r in slot_req):
                            if not pending:
                                break
                            idle_spins += 1  # hooks promise work
                            if idle_spins > _MAX_IDLE_SPINS:
                                raise RuntimeError(
                                    "step hooks report pending work but "
                                    "never submit"
                                )
                            continue
                        idle_spins = 0
                        # exact occupancy bound: a request holds its slot
                        # for at most prompt + max_new - 1 steps (replays
                        # re-budgeted at preemption), so submitted work is
                        # a hard cap
                        budget = (max_steps if max_steps is not None
                                  else self._submitted_work + B)
                        if self.steps >= budget:
                            raise RuntimeError("serve loop did not drain")
                        # map the write block of any slot whose position
                        # entered an unmapped logical block (covers fresh
                        # admissions at 0 too) and copy-on-write a shared
                        # block before a generated-token row diverges it
                        # (prompt rows write through — sharers write
                        # identical bytes)
                        for b, r in enumerate(slot_req):
                            if r is not None:
                                cache = self._map_chunk(
                                    cache, pool, block_tables, b, r,
                                    int(positions[b]), 1)
                        if self._has_state and reset_mask.any():
                            cache = self._reset_slots(
                                cache, self._dev(reset_mask))
                        reset_mask[:] = False
                        self._note_busy(r is not None for r in slot_req)
                    with TraceAnnotation("serve.h2d"):
                        tok_d = self._dev_tok(tokens)
                        pos_d = self._dev(positions)
                        bt_d = self._dev(block_tables)
                    with TraceAnnotation("serve.dispatch"):
                        logits, cache = self._decode_paged(
                            self.params, tok_d, cache, pos_d, bt_d)
                        self.steps += 1
                    with TraceAnnotation("serve.select"):
                        nxt = self._sampler.select(logits, slot_req)[:, 0]
                    with TraceAnnotation("serve.commit") as span:
                        finished = []
                        for b, r in enumerate(slot_req):
                            if r is None:
                                continue
                            t = int(positions[b])
                            positions[b] = t + 1
                            if t + 1 < len(r.prompt):
                                # still consuming prompt
                                tokens[b, 0] = r.prompt[t + 1]
                                continue
                            gi = t + 1 - len(r.prompt)
                            if gi < len(r.generated):
                                # replay after preemption: this token was
                                # already served — feed it back, never
                                # re-append
                                tokens[b, 0] = r.generated[gi]
                                continue
                            tok = int(nxt[b])
                            self._note_first_token(r)
                            r.generated.append(tok)
                            tokens[b, 0] = tok
                            if (len(r.generated) >= r.max_new_tokens
                                    or tok == r.eos_id):
                                self._finish(r)
                                self._free_slot(b)
                                finished.append(r)
                        if finished:
                            span.set_metadata(finished=_uids(finished))
        finally:
            self._absorb_pool(pool)
            self._live = None

    # -- continuous scheduler, chunked prefill (prefill/decode disaggregation) -

    def _drain_continuous_chunked(self, max_steps: Optional[int]) -> None:
        """Continuous drain where prompts are committed ``prefill_chunk``
        tokens per fused call instead of one.

        Every busy slot feeds its *known* tokens (prompt, then any tokens
        already generated — i.e. a preemption replay) in order: a slot at
        position ``t0`` with ``n_rem`` known tokens left receives
        ``n_b = min(chunk, n_rem)`` of them this step.  Decode slots
        (``n_rem == 1``: the fed token is the newest generated one) always
        advance and are never counted against ``prefill_budget``; prefill
        slots share the budget in slot order and stall at ``n_b = 0`` when
        it runs out — that is the disaggregation: decode latency no longer
        queues behind a long prompt, because the prompt's chunks are
        admitted under a per-step token budget alongside every decode
        step.  A slot appends a new token only on the step that consumes
        its last known token, from the logits row of that token; all other
        rows are discarded.  The fused step is the engine's
        ``prefill_path``: the scan over the same per-token cell as decode
        serves streams bit-identical to the token-by-token scheduler, and
        the chunk-parallel pass agrees with it to float tolerance (the
        same greedy tokens).
        """
        B, bs, C = self.max_batch, self.block_size, self.prefill_chunk
        nb_slot = self.max_len // bs
        cache = self._new_cache()
        positions = np.zeros(B, np.int32)
        block_tables = np.zeros((B, nb_slot), np.int32)  # 0 = null block
        pool = BlockPool(1 + B * nb_slot, bs,
                         share_prefixes=self.share_prefixes)
        slot_req: List[Optional[Request]] = [None] * B
        tokens = np.zeros((B, C), np.int32)
        lengths = np.zeros(B, np.int32)
        reset_mask = np.zeros(B, bool)
        self._live = {
            "positions": positions, "block_tables": block_tables,
            "free": pool.free, "pool": pool, "slot_req": slot_req,
            "tokens": tokens,
        }
        idle_spins = 0

        try:
            while True:
                with StepTraceAnnotation("serve.step", step_num=self.steps):
                    with TraceAnnotation("serve.hooks"):
                        pending = self._call_hooks(
                            busy=any(r is not None for r in slot_req)
                        )
                    with TraceAnnotation("serve.schedule") as span:
                        admitted = self._admit(reset_mask)
                        if admitted:
                            span.set_metadata(admitted=_uids(admitted))
                        if all(r is None for r in slot_req):
                            if not pending:
                                break
                            idle_spins += 1  # hooks promise work
                            if idle_spins > _MAX_IDLE_SPINS:
                                raise RuntimeError(
                                    "step hooks report pending work but "
                                    "never submit"
                                )
                            continue
                        idle_spins = 0
                        # same exact occupancy bound as the token-by-token
                        # drain: a chunked step never advances a slot by
                        # less than one token unless budget-stalled, and at
                        # least one slot advances
                        budget = (max_steps if max_steps is not None
                                  else self._submitted_work + B)
                        if self.steps >= budget:
                            raise RuntimeError("serve loop did not drain")
                        # admission: hand each slot its next known tokens
                        # under the per-step prefill budget, and map the
                        # blocks they land in
                        tokens[:] = 0
                        lengths[:] = 0
                        budget_left = (self.prefill_budget
                                       if self.prefill_budget is not None
                                       else B * C)
                        for b, r in enumerate(slot_req):
                            if r is None:
                                continue
                            t0 = int(positions[b])
                            known = len(r.prompt) + len(r.generated)
                            n_rem = known - t0
                            if n_rem <= 1:
                                n_b = 1  # decode: always advances, unbudgeted
                            else:
                                n_b = min(C, n_rem, budget_left)
                                budget_left -= n_b
                            if n_b <= 0:
                                continue  # prefill stalled by budget
                            for c in range(n_b):
                                p = t0 + c
                                tokens[b, c] = (
                                    r.prompt[p] if p < len(r.prompt)
                                    else r.generated[p - len(r.prompt)]
                                )
                            lengths[b] = n_b
                            cache = self._map_chunk(
                                cache, pool, block_tables, b, r, t0, n_b)
                        if self._has_state and reset_mask.any():
                            cache = self._reset_slots(
                                cache, self._dev(reset_mask))
                        reset_mask[:] = False
                        self._note_busy(lengths > 0)
                    # disaggregated dispatch: a step with no prefill chunk
                    # in flight (every busy slot advances exactly 1 token)
                    # runs the native 1-wide decode step — decode never
                    # pays a chunk-wide step; steps that DO carry prefill
                    # run the prefill step sliced to the smallest
                    # power-of-two bucket >= the widest chunk, so partial
                    # chunks don't burn masked rows.  A masked row is
                    # identity on live blocks (it commits to the null
                    # block), and a budget-stalled slot (lengths == 0 with
                    # mapped blocks) always takes the masked prefill step
                    # so it is never fed a garbage token.  On the scan
                    # path the split is bitwise safe (decode_step_paged is
                    # the C=1 cell of prefill_step_paged); the chunk path
                    # agrees with it to float tolerance.
                    pure_decode = all(
                        lengths[b] == 1 for b, r in enumerate(slot_req)
                        if r is not None
                    )
                    with TraceAnnotation("serve.h2d"):
                        w = (1 if pure_decode
                             else _bucket_width(int(lengths.max()), C))
                        tok_d = self._dev_tok(tokens[:, :w])
                        pos_d = self._dev(positions)
                        bt_d = self._dev(block_tables)
                        # one copy of the lengths per step: a prefill step
                        # takes them here; a decode step does not, and
                        # copies them for the row gather after its dispatch,
                        # while the device runs the step
                        len_d = None if pure_decode else self._dev(lengths)
                    with TraceAnnotation("serve.dispatch"):
                        if pure_decode:
                            logits, cache = self._decode_paged(
                                self.params, tok_d, cache, pos_d, bt_d)
                        else:
                            logits, cache = self._prefill_paged(
                                self.params, tok_d, cache, pos_d, bt_d, len_d)
                        self.steps += 1
                    with TraceAnnotation("serve.select"):
                        # one transfer: select from each slot's LAST fed row
                        # (only slots that just consumed their final known
                        # token use it)
                        if len_d is None:
                            len_d = self._dev(lengths)
                        last = jnp.maximum(len_d - 1, 0)
                        rows = logits[jnp.arange(B), last][:, None]
                        nxt = self._sampler.select(rows, slot_req)[:, 0]
                    with TraceAnnotation("serve.commit") as span:
                        finished = []
                        for b, r in enumerate(slot_req):
                            if r is None or lengths[b] == 0:
                                continue
                            n_b = int(lengths[b])
                            t0 = int(positions[b])
                            positions[b] = t0 + n_b
                            if t0 + n_b < len(r.prompt) + len(r.generated):
                                continue  # still prefilling (or replaying)
                            tok = int(nxt[b])
                            self._note_first_token(r)
                            r.generated.append(tok)
                            if (len(r.generated) >= r.max_new_tokens
                                    or tok == r.eos_id):
                                self._finish(r)
                                self._free_slot(b)
                                finished.append(r)
                        if finished:
                            span.set_metadata(finished=_uids(finished))
        finally:
            self._absorb_pool(pool)
            self._live = None

    # -- public ----------------------------------------------------------------

    def run_until_drained(
        self, max_waves: int = 1000, *, max_steps: Optional[int] = None
    ) -> Dict[int, Request]:
        t0 = time.time()
        if self.scheduler == "wave":
            self._drain_waves(max_waves)
        elif self._spec is not None:
            self._spec.drain(self, max_steps)
        elif self.prefill_chunk > 1:
            self._drain_continuous_chunked(max_steps)
        else:
            self._drain_continuous(max_steps)
        self.wall_s += time.time() - t0
        return self.completed

    def stats(self) -> Dict[str, Any]:
        """Serving metrics in the perf-ledger schema (see
        :func:`repro.perf.ledger.metrics_from_serving`)."""
        lat = sorted(
            r.latency_s for r in self.completed.values()
            if r.latency_s is not None
        )
        ttft = sorted(
            r.ttft_s for r in self.completed.values()
            if r.ttft_s is not None
        )
        ttft_steps = sorted(
            r.ttft_steps for r in self.completed.values()
            if r.ttft_steps is not None
        )
        new_tokens = sum(len(r.generated) for r in self.completed.values())
        block_bytes = transformer.paged_block_bytes(
            self.cfg, self.block_size, self.kv_dtype
        )
        kv_bytes_served = self.logical_blocks * block_bytes
        kv_bytes_stored = self.physical_blocks * block_bytes
        return {
            "scheduler": self.scheduler,
            "prefill_chunk": self.prefill_chunk,
            "prefill_budget": self.prefill_budget,
            "prefill_path": self.prefill_path,
            "kv_dtype": self.kv_dtype,
            "share_prefixes": self.share_prefixes,
            # mesh placement: the DxM shape string keys the +mesh<DxM>
            # ledger fork; device_lane_utilization is Eq. 1 one level up
            # (worst device shard's busy-lane fraction — deterministic
            # slot accounting, gated at tol 0)
            "mesh": self.mesh_shape,
            "mesh_devices": (self.mesh.devices.size
                             if self.mesh is not None else 1),
            "device_lane_utilization": self.device_lane_utilization,
            "spec_adaptive": self.spec_adaptive,
            "requests": len(self.completed),
            "new_tokens": new_tokens,
            "fused_steps": self.steps,
            "busy_slot_steps": self.busy_slot_steps,
            "slot_steps": self.total_slot_steps,
            "slot_utilization": self.slot_utilization,
            "preemptions": self.preemptions,
            # block-pool dedup: bytes served / bytes stored is the
            # memory-side Eq. 1 analogue (see core.metrics.block_dedup_ratio)
            "logical_blocks": self.logical_blocks,
            "physical_blocks": self.physical_blocks,
            "shared_block_hits": self.shared_block_hits,
            "cow_copies": self.cow_copies,
            "kv_bytes_served": kv_bytes_served,
            "kv_bytes_stored": kv_bytes_stored,
            # speculative decoding: exact counters (zeros when off, so
            # the schema is stable across +spec ledger forks) plus the
            # Eq. 1 lane-utilization analogue — accepted drafts are the
            # active lanes of each k-wide verification issue
            "spec_k": self.spec_k,
            "drafted_tokens": self.drafted_tokens,
            "accepted_tokens": self.accepted_tokens,
            "rejected_tokens": self.rejected_tokens,
            "draft_steps": self.draft_steps,
            "target_steps": self.steps,
            "acceptance_rate": core_metrics.acceptance_rate(
                self.accepted_tokens, self.drafted_tokens
            ),
            # pure-SSM models page zero KV bytes; fall back to block-
            # granular units there so sharing still registers (the ratio
            # is unit-agnostic: served / stored)
            "block_dedup_ratio": core_metrics.block_dedup_ratio(
                kv_bytes_served, kv_bytes_stored
            ) if block_bytes > 0 else core_metrics.block_dedup_ratio(
                self.logical_blocks, self.physical_blocks
            ),
            "wall_s": self.wall_s,
            "tok_s": new_tokens / self.wall_s if self.wall_s > 0 else 0.0,
            "p50_latency_s": float(np.percentile(lat, 50)) if lat else 0.0,
            "p95_latency_s": float(np.percentile(lat, 95)) if lat else 0.0,
            "ttft_p50_s": float(np.percentile(ttft, 50)) if ttft else 0.0,
            "ttft_p95_s": float(np.percentile(ttft, 95)) if ttft else 0.0,
            "ttft_p50_steps": (float(np.percentile(ttft_steps, 50))
                               if ttft_steps else 0.0),
            "ttft_p95_steps": (float(np.percentile(ttft_steps, 95))
                               if ttft_steps else 0.0),
        }
