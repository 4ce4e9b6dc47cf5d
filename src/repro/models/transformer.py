"""Decoder-only LM core covering dense / MoE / SSM / hybrid architectures.

Depth is expressed as ``n_superblocks`` repetitions of a *superblock* (the
smallest repeating layer pattern, e.g. Jamba's [m m m m a m m m]); parameters
of all superblocks are stacked on a leading axis and the forward pass is a
``lax.scan`` over that axis, so the lowered HLO is O(1) in depth — essential
for 72–80-layer models compiled against 512-device meshes.

Paths:
* ``forward``      — teacher-forced logits for training (optionally remat'd)
* ``prefill``      — forward + KV/SSM cache construction, last-token logits
* ``decode_step``  — one-token serve step over fixed-size caches
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerKind, ModelConfig
from repro.models import attention, layers, mla, moe, ssm


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _slot_is_moe(cfg: ModelConfig, slot: int) -> bool:
    offset = 1 if (cfg.moe is not None and cfg.moe.first_dense) else 0
    return cfg._is_moe_layer(offset + slot)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def _init_slot(key, cfg: ModelConfig, kind: LayerKind, is_moe: bool, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    norm_init, _ = layers.make_norm(cfg)
    p: Dict[str, Any] = {"norm1": norm_init(dtype), "norm2": norm_init(dtype)}
    if kind == LayerKind.ATTN:
        if cfg.mla is not None:
            p["mla"] = mla.init_mla(k1, cfg, dtype)
        else:
            p["attn"] = attention.init_attention(k1, cfg, dtype)
    else:
        p["mamba"] = ssm.init_mamba(k1, cfg, dtype)
    if is_moe:
        p["moe"] = moe.init_moe(k2, cfg, dtype)
    elif cfg.d_ff > 0:
        p["ffn"] = layers.swiglu_init(k2, cfg.d_model, cfg.d_ff, dtype)
    else:
        del p["norm2"]  # pure-Mamba block: norm -> mixer -> residual only
    return p


def _init_superblock(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, len(cfg.superblock))
    return {
        f"slot{i}": _init_slot(ks[i], cfg, kind, _slot_is_moe(cfg, i), dtype)
        for i, kind in enumerate(cfg.superblock)
    }


def init_lm(key, cfg: ModelConfig) -> Dict[str, Any]:
    dtype = _dtype(cfg)
    k_emb, k_blocks, k_first, k_head = jax.random.split(key, 4)
    params: Dict[str, Any] = {
        "embed": layers.embed_init(k_emb, cfg.vocab_padded, cfg.d_model, dtype),
    }
    norm_init, _ = layers.make_norm(cfg)
    params["final_norm"] = norm_init(dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.dense_init(k_head, cfg.d_model, cfg.vocab_padded, dtype)
    if cfg.moe is not None and cfg.moe.first_dense:
        params["first_block"] = _init_slot(
            k_first, cfg, LayerKind.ATTN, is_moe=False, dtype=dtype
        )
    nsb = cfg.n_superblocks
    keys = jax.random.split(k_blocks, nsb)
    params["blocks"] = jax.vmap(lambda k: _init_superblock(k, cfg, dtype))(keys)
    return params


# --------------------------------------------------------------------------
# forward blocks
# --------------------------------------------------------------------------


def _apply_slot_full(p, cfg, kind, is_moe, x, positions, collect_cache: bool):
    from repro.distributed import context as mesh_ctx

    plan = mesh_ctx.current()
    _, norm_fn = layers.make_norm(cfg)
    h = norm_fn(p["norm1"], x)
    cache = None
    if kind == LayerKind.ATTN:
        if cfg.mla is not None:
            if collect_cache:
                att, cache = mla.mla_full_with_cache(p["mla"], cfg, h, positions)
            else:
                att = mla.mla_full(p["mla"], cfg, h, positions)
        else:
            if collect_cache:
                att, cache = attention.attention_full_with_cache(
                    p["attn"], cfg, h, positions
                )
            else:
                att = attention.attention_full(p["attn"], cfg, h, positions)
    else:
        if collect_cache:
            att, state = ssm.mamba_full(p["mamba"], cfg, h, return_state=True)
            # conv state = last d_conv-1 pre-conv xBC rows; recompute cheaply
            cache = {"ssm_state": state, "conv_state": _conv_tail(p["mamba"], cfg, h)}
        else:
            att = ssm.mamba_full(p["mamba"], cfg, h)
    # sequence-parallel residual: GSPMD turns the output-projection
    # all-reduce into reduce-scatter (+ all-gather on the next block entry)
    x = mesh_ctx.shard_seq(x + att, plan)
    if is_moe:
        f, aux = moe.moe_ffn(p["moe"], cfg, norm_fn(p["norm2"], x))
    elif "ffn" in p:
        f, aux = layers.swiglu(p["ffn"], norm_fn(p["norm2"], x)), jnp.zeros((), jnp.float32)
    else:
        return x, jnp.zeros((), jnp.float32), cache
    return mesh_ctx.shard_seq(x + f, plan), aux, cache


def _conv_tail(p_mamba, cfg, h):
    """Pre-activation conv window tail for decode handoff: (B, d_conv-1, ch)."""
    _, xBC, _ = ssm._project_in(p_mamba, cfg, h[:, -(cfg.ssm.d_conv - 1) :, :])
    return xBC


def _block_full(cfg, collect_cache):
    def fn(p_blk, x, positions):
        aux_total = jnp.zeros((), jnp.float32)
        caches = {}
        for i, kind in enumerate(cfg.superblock):
            x, aux, cache = _apply_slot_full(
                p_blk[f"slot{i}"], cfg, kind, _slot_is_moe(cfg, i), x, positions,
                collect_cache,
            )
            aux_total = aux_total + aux
            if collect_cache:
                caches[f"slot{i}"] = cache
        return x, aux_total, caches

    return fn


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "full":
        return jax.checkpoint(fn)
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    raise ValueError(f"unknown remat policy {policy!r}")


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------


def forward(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,
    *,
    img_embeds: Optional[jax.Array] = None,
    remat: str = "none",
) -> Tuple[jax.Array, jax.Array]:
    """Teacher-forced forward.  tokens: (B, S_text).  Returns (logits fp32
    (B,S,V), moe_aux).  With ``img_embeds`` (B, S_img, d) the sequence is
    [img, text] (InternVL-style stub frontend)."""
    x = layers.embed(params["embed"], tokens).astype(jnp.dtype(cfg.compute_dtype))
    if img_embeds is not None:
        x = jnp.concatenate([img_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)

    aux_total = jnp.zeros((), jnp.float32)
    if "first_block" in params:
        x, aux, _ = _apply_slot_full(
            params["first_block"], cfg, LayerKind.ATTN, False, x, positions, False
        )
        aux_total = aux_total + aux

    block = _block_full(cfg, collect_cache=False)

    def scan_body(x, p_blk):
        y, aux, _ = block(p_blk, x, positions)
        return y, aux

    scan_fn = _remat(scan_body, remat)
    x, auxs = jax.lax.scan(scan_fn, x, params["blocks"])
    aux_total = aux_total + auxs.sum()

    _, norm_fn = layers.make_norm(cfg)
    x = norm_fn(params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = layers.unembed(params["embed"], x)
    else:
        logits = layers.dense(params["lm_head"], x).astype(jnp.float32)
    return logits, aux_total


def lm_loss(
    logits: jax.Array,
    labels: jax.Array,
    *,
    mask: Optional[jax.Array] = None,
    real_vocab: Optional[int] = None,
) -> jax.Array:
    """Token-mean cross entropy.  labels: (B, S) int32; -1 = ignore.
    ``real_vocab`` masks the sharding-padded tail of the vocab dim."""
    V = logits.shape[-1]
    if real_vocab is not None and real_vocab < V:
        pad_mask = jnp.arange(V) < real_vocab
        logits = jnp.where(pad_mask, logits, -1e30)
    if mask is None:
        mask = labels >= 0
    labels_safe = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    # one-hot contraction, not take_along_axis: a vocab-dim gather would
    # force GSPMD to all-gather the vocab-sharded fp32 logits.  bf16 one-hot
    # (exact for 0/1) halves the temp; accumulate fp32.
    onehot = jax.nn.one_hot(labels_safe, V, dtype=jnp.bfloat16)
    gold = jnp.einsum(
        "bsv,bsv->bs", logits.astype(jnp.bfloat16), onehot,
        preferred_element_type=jnp.float32,
    )
    nll = (lse - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1)


# --------------------------------------------------------------------------
# serving: prefill + decode
# --------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Fixed-size cache pytree matching the superblock structure."""
    dtype = jnp.dtype(cfg.compute_dtype)
    nsb = cfg.n_superblocks
    cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32), "blocks": {}}
    for i, kind in enumerate(cfg.superblock):
        if kind == LayerKind.ATTN:
            if cfg.mla is not None:
                c = mla.init_mla_cache(cfg, batch, max_len, dtype, nsb)
            else:
                kv, hd = cfg.n_kv_heads, cfg.head_dim
                c = {
                    "k": jnp.zeros((nsb, batch, max_len, kv, hd), dtype),
                    "v": jnp.zeros((nsb, batch, max_len, kv, hd), dtype),
                }
        else:
            c = {
                "ssm_state": jnp.zeros(
                    (nsb, batch, cfg.ssm.n_heads(cfg.d_model), cfg.ssm.d_state,
                     cfg.ssm.head_dim), jnp.float32,
                ),
                "conv_state": jnp.zeros(
                    (nsb, batch, cfg.ssm.d_conv - 1,
                     cfg.ssm.d_inner(cfg.d_model)
                     + 2 * cfg.ssm.n_groups * cfg.ssm.d_state), dtype,
                ),
            }
        cache["blocks"][f"slot{i}"] = c
    if cfg.moe is not None and cfg.moe.first_dense:
        kv, hd = cfg.n_kv_heads, cfg.head_dim
        if cfg.mla is not None:
            cache["first_block"] = jax.tree.map(
                lambda a: a[0], mla.init_mla_cache(cfg, batch, max_len, dtype, 1)
            )
        else:
            cache["first_block"] = {
                "k": jnp.zeros((batch, max_len, kv, hd), dtype),
                "v": jnp.zeros((batch, max_len, kv, hd), dtype),
            }
    return cache


def _apply_slot_decode(p, cfg, kind, is_moe, x, cache, pos):
    """Returns (x, delta): delta holds NEW-TOKEN slices for attention caches
    (committed by the caller in one top-level update) and full replacement
    states for SSM slots."""
    _, norm_fn = layers.make_norm(cfg)
    h = norm_fn(p["norm1"], x)
    if kind == LayerKind.ATTN:
        if cfg.mla is not None:
            att, c_new, kr_new = mla.mla_decode(
                p["mla"], cfg, h, cache["c"], cache["k_rope"], pos
            )
            delta = {"c": c_new, "k_rope": kr_new}
        else:
            att, k_new, v_new = attention.attention_decode(
                p["attn"], cfg, h, cache["k"], cache["v"], pos
            )
            delta = {"k": k_new, "v": v_new}
    else:
        att, s_new, conv_new = ssm.mamba_decode(
            p["mamba"], cfg, h, cache["ssm_state"], cache["conv_state"]
        )
        delta = {"ssm_state": s_new, "conv_state": conv_new}
    x = x + att
    if is_moe:
        f, _ = moe.moe_ffn(p["moe"], cfg, norm_fn(p["norm2"], x))
    elif "ffn" in p:
        f = layers.swiglu(p["ffn"], norm_fn(p["norm2"], x))
    else:
        return x, delta
    return x + f, delta


_SEQ_CACHE_KEYS = ("k", "v", "c", "k_rope")  # (.., S, ...) caches, seq axis


def _commit(cache_leaf, delta_leaf, pos, key: str, stacked: bool):
    """Write a new-token slice (or replacement state) into the cache."""
    if key in _SEQ_CACHE_KEYS:
        start = (0, 0, pos) + (0,) * (cache_leaf.ndim - 3) if stacked else (
            (0, pos) + (0,) * (cache_leaf.ndim - 2)
        )
        return jax.lax.dynamic_update_slice(
            cache_leaf, delta_leaf.astype(cache_leaf.dtype), start
        )
    return delta_leaf.astype(cache_leaf.dtype)  # SSM states: full replace


def decode_step(
    params, cfg: ModelConfig, tokens: jax.Array, cache: Dict[str, Any]
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One serve step: tokens (B,1) + cache -> (logits (B,1,V) fp32, cache)."""
    pos = cache["pos"]
    x = layers.embed(params["embed"], tokens).astype(jnp.dtype(cfg.compute_dtype))

    new_cache: Dict[str, Any] = {"pos": pos + 1, "blocks": None}
    if "first_block" in params:
        x, fb_delta = _apply_slot_decode(
            params["first_block"], cfg, LayerKind.ATTN, False, x,
            cache["first_block"], pos,
        )
        new_cache["first_block"] = {
            k: _commit(cache["first_block"][k], d, pos, k, stacked=False)
            for k, d in fb_delta.items()
        }

    def scan_body(x, inp):
        p_blk, c_blk = inp
        deltas = {}
        for i, kind in enumerate(cfg.superblock):
            x, delta = _apply_slot_decode(
                p_blk[f"slot{i}"], cfg, kind, _slot_is_moe(cfg, i), x,
                c_blk[f"slot{i}"], pos,
            )
            deltas[f"slot{i}"] = delta
        return x, deltas

    x, deltas = jax.lax.scan(scan_body, x, (params["blocks"], cache["blocks"]))
    # single top-level commit: deltas are stacked (nsb, B, 1, ...) slices
    new_cache["blocks"] = {
        slot: {
            k: _commit(cache["blocks"][slot][k], d, pos, k, stacked=True)
            for k, d in slot_deltas.items()
        }
        for slot, slot_deltas in deltas.items()
    }

    _, norm_fn = layers.make_norm(cfg)
    x = norm_fn(params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = layers.unembed(params["embed"], x)
    else:
        logits = layers.dense(params["lm_head"], x).astype(jnp.float32)
    return logits, new_cache


# --------------------------------------------------------------------------
# serving: paged cache (slot-level continuous batching)
# --------------------------------------------------------------------------

#: Physical block 0 is reserved as the NULL block: block tables of idle
#: serving slots point at it, so their (masked-out) scatter writes land in
#: garbage space and can never corrupt a live request's cache.
NULL_BLOCK = 0

#: KV pool storage dtypes along the paper's ELEN axis: "f32" keeps the
#: pool in the model's compute dtype (the unquantized baseline), "bf16"
#: halves it, "int8" quarters it with one fp32 scale per (token row,
#: cache key) — more elements per vector lane at lower precision, the
#: same trade the paper's ELEN sweep measures.
KV_DTYPES = ("f32", "bf16", "int8")


def _pool_dtype(cfg: ModelConfig, kv_dtype: str):
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, "
                         f"got {kv_dtype!r}")
    if kv_dtype == "bf16":
        return jnp.bfloat16
    if kv_dtype == "int8":
        return jnp.int8
    return jnp.dtype(cfg.compute_dtype)


def init_paged_cache(
    cfg: ModelConfig, slots: int, max_len: int, block_size: int,
    kv_dtype: str = "f32", *, mesh=None,
) -> Dict[str, Any]:
    """Paged cache pytree: attention caches become pooled blocks.

    Sequence caches are laid out as a physical pool ``(nsb, n_blocks,
    block_size, ...)`` addressed through an engine-owned block table
    ``(slots, max_len // block_size)`` mapping each slot's logical block to
    a pool block.  The pool holds ``1 + slots * max_len/block_size``
    blocks — enough for every slot at full length plus the reserved
    :data:`NULL_BLOCK` — so admission never fails and freed blocks are
    recycled across requests.  SSM / conv states are O(1) per slot and stay
    densely indexed by slot (there is nothing to page).

    ``kv_dtype`` selects the pool's storage precision (:data:`KV_DTYPES`);
    ``"int8"`` adds an fp32 ``<key>_scale`` pool of shape ``(nsb,
    n_blocks, block_size)`` — one symmetric scale per committed token row,
    so dequantization is exact per row and stale rows can never poison a
    live one through a shared scale.
    """
    if max_len % block_size:
        raise ValueError(f"max_len {max_len} not a multiple of block_size "
                         f"{block_size}")
    dtype = jnp.dtype(cfg.compute_dtype)  # SSM/conv states: never quantized
    pool_dtype = _pool_dtype(cfg, kv_dtype)
    nsb = cfg.n_superblocks
    n_blocks = 1 + slots * (max_len // block_size)
    cache: Dict[str, Any] = {"blocks": {}}

    def _attn_pool(stacked: int):
        if cfg.mla is not None:
            ml = cfg.mla
            c = {
                "c": jnp.zeros(
                    (stacked, n_blocks, block_size, ml.kv_lora_rank),
                    pool_dtype),
                "k_rope": jnp.zeros(
                    (stacked, n_blocks, block_size, ml.qk_rope_dim),
                    pool_dtype),
            }
        else:
            kv, hd = cfg.n_kv_heads, cfg.head_dim
            c = {
                "k": jnp.zeros((stacked, n_blocks, block_size, kv, hd),
                               pool_dtype),
                "v": jnp.zeros((stacked, n_blocks, block_size, kv, hd),
                               pool_dtype),
            }
        if kv_dtype == "int8":
            for k in list(c):
                c[k + "_scale"] = jnp.zeros(
                    (stacked, n_blocks, block_size), jnp.float32
                )
        return c

    # mesh != None: place every pool by the serve sharding rules (k/v head
    # axis split over `model`, SSM heads/conv channels likewise, scale and
    # MLA latent pools replicated) — a pure-placement device_put, so the
    # sharded cache is byte-identical to the replicated one
    for i, kind in enumerate(cfg.superblock):
        if kind == LayerKind.ATTN:
            c = _attn_pool(nsb)
        else:
            c = {
                "ssm_state": jnp.zeros(
                    (nsb, slots, cfg.ssm.n_heads(cfg.d_model), cfg.ssm.d_state,
                     cfg.ssm.head_dim), jnp.float32,
                ),
                "conv_state": jnp.zeros(
                    (nsb, slots, cfg.ssm.d_conv - 1,
                     cfg.ssm.d_inner(cfg.d_model)
                     + 2 * cfg.ssm.n_groups * cfg.ssm.d_state), dtype,
                ),
            }
        cache["blocks"][f"slot{i}"] = c
    if cfg.moe is not None and cfg.moe.first_dense:
        cache["first_block"] = jax.tree.map(lambda a: a[0], _attn_pool(1))
    if mesh is not None:
        from repro.distributed import sharding as shard_rules
        cache = jax.device_put(
            cache, shard_rules.paged_cache_shardings(cache, mesh)
        )
    return cache


def _gather_paged(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Logical per-slot view of a block pool.

    pool: (n_blocks, block_size, ...); block_tables: (B, nb) ->
    (B, nb * block_size, ...).  Garbage rows (NULL_BLOCK, recycled blocks)
    are fine: the attention mask hides everything >= the slot's position.
    """
    B, nb = block_tables.shape
    g = pool[block_tables]  # (B, nb, block_size, ...)
    return g.reshape((B, nb * pool.shape[1]) + pool.shape[2:])


def _commit_paged(pool, delta, flat_idx, key: str, stacked: bool):
    """Per-slot scatter write of one new-token slice into the block pool.

    ``flat_idx`` (B,) indexes the flattened (n_blocks * block_size) token
    axis; idle slots all alias NULL_BLOCK offsets, where duplicate writes
    are harmless by construction.
    """
    if key not in _SEQ_CACHE_KEYS:
        return delta.astype(pool.dtype)  # SSM states: full replace
    if stacked:
        nsb, n_blocks, bs = pool.shape[:3]
        flat = pool.reshape((nsb, n_blocks * bs) + pool.shape[3:])
        vals = delta.astype(pool.dtype)[:, :, 0]  # (nsb, B, ...)
        return flat.at[:, flat_idx].set(vals).reshape(pool.shape)
    n_blocks, bs = pool.shape[:2]
    flat = pool.reshape((n_blocks * bs,) + pool.shape[2:])
    vals = delta.astype(pool.dtype)[:, 0]  # (B, ...)
    return flat.at[flat_idx].set(vals).reshape(pool.shape)


def reset_paged_slots(cache: Dict[str, Any], mask: jax.Array) -> Dict[str, Any]:
    """Zero the SSM/conv state of every slot where ``mask`` (B,) is True.

    Called when a finished slot is refilled with a new request: attention
    blocks need no scrub (the per-slot mask hides stale tokens) but
    recurrent state is accumulated, so a fresh request must start from
    zeros.
    """
    def _scrub(slot_cache):
        out = {}
        for k, leaf in slot_cache.items():
            if k in ("ssm_state", "conv_state"):
                m = mask.reshape((1, mask.shape[0]) + (1,) * (leaf.ndim - 2))
                out[k] = jnp.where(m, jnp.zeros((), leaf.dtype), leaf)
            else:
                out[k] = leaf
        return out

    new = dict(cache)
    new["blocks"] = {s: _scrub(c) for s, c in cache["blocks"].items()}
    return new


#: per-slot recurrent-state leaves of a paged cache (everything that is
#: NOT paged: attention rows rewind by masking, these rewind by restore)
_STATE_KEYS = ("ssm_state", "conv_state")


def slot_state(cache: Dict[str, Any]) -> Dict[str, Any]:
    """Reference snapshot of every per-slot recurrent-state leaf.

    jax arrays are immutable, so holding the leaves IS the snapshot —
    no copy, no device work.  Speculative verification snapshots before
    committing k+1 tokens: attention rows past a rejection point are
    hidden by the position mask, but SSM/conv state is *accumulated* by
    every scanned token, so a rejected suffix must be undone with
    :func:`restore_slot_state` + a replay of the accepted prefix.
    Empty per-slot dicts for attention-only architectures.
    """
    return {
        s: {k: leaf for k, leaf in c.items() if k in _STATE_KEYS}
        for s, c in cache["blocks"].items()
    }


def restore_slot_state(
    cache: Dict[str, Any], state: Dict[str, Any], mask: jax.Array
) -> Dict[str, Any]:
    """Restore recurrent state from a :func:`slot_state` snapshot for every
    slot where ``mask`` (B,) is True; other slots keep their current state
    bitwise (``where`` with a False lane is identity)."""
    def _blend(slot_cache, snap):
        out = dict(slot_cache)
        for k, leaf in snap.items():
            m = mask.reshape((1, mask.shape[0]) + (1,) * (leaf.ndim - 2))
            out[k] = jnp.where(m, leaf, slot_cache[k])
        return out

    new = dict(cache)
    new["blocks"] = {
        s: _blend(c, state.get(s, {})) for s, c in cache["blocks"].items()
    }
    return new


def copy_paged_block(
    cache: Dict[str, Any], src: jax.Array, dst: jax.Array
) -> Dict[str, Any]:
    """Copy physical pool block ``src`` into ``dst`` on every paged leaf.

    The device half of copy-on-write: when a slot is about to write a
    generated token into a block other slots still reference, the engine
    allocates ``dst``, copies ``src``'s bytes (scale pools included — a
    quantized row travels with its scale), and repoints its block table.
    ``src``/``dst`` may be traced scalars, so one jit trace serves every
    copy.  SSM/conv states are per-slot, not paged; they pass through.
    """
    def _copy(slot_cache, stacked: bool):
        out = {}
        for k, leaf in slot_cache.items():
            if k in _SEQ_CACHE_KEYS or k.endswith("_scale"):
                if stacked:
                    out[k] = leaf.at[:, dst].set(leaf[:, src])
                else:
                    out[k] = leaf.at[dst].set(leaf[src])
            else:
                out[k] = leaf
        return out

    new = dict(cache)
    new["blocks"] = {s: _copy(c, True) for s, c in cache["blocks"].items()}
    if "first_block" in cache:
        new["first_block"] = _copy(cache["first_block"], False)
    return new


def paged_block_bytes(
    cfg: ModelConfig, block_size: int, kv_dtype: str = "f32"
) -> int:
    """Bytes one physical block stores across every attention layer.

    Host-side arithmetic (no device pool needed) for the block-dedup
    ratio: logical blocks served x this = bytes served, physical blocks
    allocated x this = bytes stored.  int8 counts its fp32 per-row scales
    — the quantized pool's true footprint.
    """
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, "
                         f"got {kv_dtype!r}")
    itemsize = {
        "f32": jnp.dtype(cfg.compute_dtype).itemsize, "bf16": 2, "int8": 1,
    }[kv_dtype]
    if cfg.mla is not None:
        row_elems = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim
    else:
        row_elems = 2 * cfg.n_kv_heads * cfg.head_dim
    n_attn = cfg.n_superblocks * sum(
        1 for k in cfg.superblock if k == LayerKind.ATTN
    )
    if cfg.moe is not None and cfg.moe.first_dense:
        n_attn += 1  # the unstacked first dense block pages its cache too
    per_layer = block_size * row_elems * itemsize
    if kv_dtype == "int8":
        per_layer += 2 * block_size * 4  # one fp32 scale per row per key
    return n_attn * per_layer


def _commit_paged_masked(pool, delta, flat_idx, key: str, stacked: bool,
                         active: jax.Array):
    """Commit one token's delta, predicated per slot on ``active`` (B,).

    Sequence pools need no extra masking — the caller already routes
    inactive slots' ``flat_idx`` into NULL_BLOCK — but SSM/conv states are
    full replacements, so inactive slots keep their previous state
    bitwise.  ``where`` with an all-true mask is a bitwise identity, which
    is what keeps the decode path's numerics untouched by this refactor.
    """
    if key in _SEQ_CACHE_KEYS:
        return _commit_paged(pool, delta, flat_idx, key, stacked)
    new = delta.astype(pool.dtype)
    lead = (1,) if stacked else ()
    m = active.reshape(lead + (active.shape[0],) + (1,) * (pool.ndim - len(lead) - 1))
    return jnp.where(m, new, pool)


def _quantize_token(delta, stacked: bool):
    """Symmetric per-row int8 quantization of one token's cache slice.

    delta: ``(nsb, B, 1, ...)`` (stacked) or ``(B, 1, ...)`` float ->
    ``(q int8 same shape, scale fp32 (nsb, B, 1) / (B, 1))``.  One scale
    per committed row keeps dequantization exact per token: a recycled or
    null-block row's garbage scale can never touch a live row.
    """
    lead = 3 if stacked else 2
    axes = tuple(range(lead, delta.ndim))
    amax = jnp.max(jnp.abs(delta.astype(jnp.float32)), axis=axes)
    s = jnp.maximum(amax / 127.0, 1e-8)
    sb = s.reshape(s.shape + (1,) * (delta.ndim - s.ndim))
    q = jnp.clip(jnp.round(delta.astype(jnp.float32) / sb), -127, 127)
    return q.astype(jnp.int8), s


def _commit_slot(c_slot, slot_deltas, flat_idx, stacked: bool,
                 active: jax.Array, kv_dtype: str):
    """Commit one layer-slot's deltas, carrying non-delta leaves through.

    Scale pools have no delta of their own — they are derived from their
    data leaf's delta at commit time — so this iterates the CACHE's keys,
    not the delta's: a quantized pool's ``<key>_scale`` leaf is written
    alongside ``<key>`` and every other leaf passes through untouched.
    """
    out = {}
    for k, leaf in c_slot.items():
        if k.endswith("_scale"):
            continue  # written alongside its data leaf below
        d = slot_deltas.get(k)
        if d is None:
            out[k] = leaf
            if k + "_scale" in c_slot:
                out[k + "_scale"] = c_slot[k + "_scale"]
        elif k in _SEQ_CACHE_KEYS and kv_dtype == "int8":
            q, s = _quantize_token(d, stacked)
            out[k] = _commit_paged(leaf, q, flat_idx, k, stacked)
            out[k + "_scale"] = _commit_paged(
                c_slot[k + "_scale"], s, flat_idx, k, stacked
            )
        else:
            out[k] = _commit_paged_masked(leaf, d, flat_idx, k, stacked,
                                          active)
    return out


def _paged_view(c_slot, block_tables, kv_dtype: str, compute):
    """Gather logical per-slot views of one layer-slot's sequence pools,
    dequantizing quantized storage back to compute precision."""
    out = {}
    for k, leaf in c_slot.items():
        if k.endswith("_scale"):
            continue  # consumed by its data leaf's dequant below
        if k not in _SEQ_CACHE_KEYS:
            out[k] = leaf
            continue
        g = _gather_paged(leaf, block_tables)
        if kv_dtype == "int8":
            s = _gather_paged(c_slot[k + "_scale"], block_tables)
            g = g.astype(compute) * s.reshape(
                s.shape + (1,) * (g.ndim - s.ndim)
            ).astype(compute)
        elif kv_dtype == "bf16":
            g = g.astype(compute)
        out[k] = g
    return out


def _paged_token_step(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,
    cache: Dict[str, Any],
    positions: jax.Array,
    block_tables: jax.Array,
    active: jax.Array,
    *,
    block_size: int,
    kv_dtype: str = "f32",
) -> Tuple[jax.Array, Dict[str, Any]]:
    """The shared one-token cell of the paged serve path.

    Every per-slot op here (embed row, norms, per-slot attention over the
    gathered view, per-token MoE routing, SSM recurrence) is independent
    across batch rows, so a token's numerics depend only on its own slot's
    inputs — the invariant that makes chunked prefill bit-exact against
    token-by-token decode.  ``active`` (B,) predicates commits: inactive
    slots scatter their sequence writes into NULL_BLOCK and keep their
    recurrent state, exactly like idle slots always have.

    With ``kv_dtype != "f32"`` the sequence pools are stored quantized:
    gathers dequantize back to the compute dtype (int8 multiplies by the
    per-row fp32 scale) and commits quantize the new token's row — the
    attention math itself always runs at compute precision.
    """
    pos_b = positions.astype(jnp.int32)
    nb = block_tables.shape[1]
    blk = jnp.take_along_axis(
        block_tables, jnp.minimum(pos_b // block_size, nb - 1)[:, None], axis=1
    )[:, 0]
    flat_idx = jnp.where(
        active, blk * block_size + pos_b % block_size,
        NULL_BLOCK * block_size,
    )  # (B,) pool token index
    compute = jnp.dtype(cfg.compute_dtype)
    x = layers.embed(params["embed"], tokens).astype(compute)

    def _view(c_slot):
        return _paged_view(c_slot, block_tables, kv_dtype, compute)

    new_cache: Dict[str, Any] = {"blocks": None}
    if "first_block" in params:
        x, fb_delta = _apply_slot_decode(
            params["first_block"], cfg, LayerKind.ATTN, False, x,
            _view(cache["first_block"]), pos_b,
        )
        new_cache["first_block"] = _commit_slot(
            cache["first_block"], fb_delta, flat_idx, False, active, kv_dtype
        )

    def scan_body(x, inp):
        p_blk, c_blk = inp
        deltas = {}
        for i, kind in enumerate(cfg.superblock):
            x, delta = _apply_slot_decode(
                p_blk[f"slot{i}"], cfg, kind, _slot_is_moe(cfg, i), x,
                _view(c_blk[f"slot{i}"]), pos_b,
            )
            deltas[f"slot{i}"] = delta
        return x, deltas

    x, deltas = jax.lax.scan(scan_body, x, (params["blocks"], cache["blocks"]))
    new_cache["blocks"] = {
        slot: _commit_slot(cache["blocks"][slot], slot_deltas, flat_idx,
                           True, active, kv_dtype)
        for slot, slot_deltas in deltas.items()
    }

    _, norm_fn = layers.make_norm(cfg)
    x = norm_fn(params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = layers.unembed(params["embed"], x)
    else:
        logits = layers.dense(params["lm_head"], x).astype(jnp.float32)
    return logits, new_cache


def decode_step_paged(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,
    cache: Dict[str, Any],
    positions: jax.Array,
    block_tables: jax.Array,
    *,
    block_size: int,
    kv_dtype: str = "f32",
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One continuous-batching serve step over the paged cache.

    tokens: (B, 1); positions: (B,) per-slot cache lengths; block_tables:
    (B, nb) logical->physical block map.  Each slot attends over its own
    live prefix (mask ``< positions[slot]``) and the new token commits as a
    per-slot scatter at ``positions[slot]`` — predication-style slot
    accounting: finished/idle slots write into NULL_BLOCK and are masked
    out rather than synchronized on.  Scheduling state (positions, tables,
    allocator) lives with the caller; the cache holds only device pools.
    """
    active = jnp.ones((tokens.shape[0],), jnp.bool_)
    return _paged_token_step(
        params, cfg, tokens, cache, positions, block_tables, active,
        block_size=block_size, kv_dtype=kv_dtype,
    )


def prefill_step_paged(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,
    cache: Dict[str, Any],
    positions: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    block_size: int,
    kv_dtype: str = "f32",
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Commit a chunk of C prompt tokens per slot in ONE fused call.

    tokens: (B, C) — slot ``b``'s next ``lengths[b]`` known tokens (prompt
    or replayed), zero-padded past its length; positions: (B,) per-slot
    cache lengths before the chunk; lengths: (B,) int32 in [0, C].  The
    chunk is a ``lax.scan`` of the SAME per-token cell the decode path
    runs, with slot ``b`` active for the first ``lengths[b]`` iterations —
    so a P-token prompt costs ceil(P/C) fused calls instead of P while
    producing bit-identical logits, sequence pools, and SSM states (dense
    SSM states advance by in-chunk recurrence, never the parallel chunk
    scan, precisely because SSD's chunked accumulation order differs
    bitwise).  Returns (logits (B, C, vocab_padded) fp32 — iteration ``c``'s
    row for every slot; callers read row ``lengths[b]-1`` — and the updated
    cache).  Slots with ``lengths[b] == 0`` commit nothing and keep their
    state; their logit rows are garbage by contract.
    """
    B, C = tokens.shape
    pos0 = positions.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)

    def body(cache, xs):
        tok_c, c = xs
        logits, cache = _paged_token_step(
            params, cfg, tok_c[:, None], cache, pos0 + c, block_tables,
            c < lens, block_size=block_size, kv_dtype=kv_dtype,
        )
        return cache, logits[:, 0]

    cache, logits = jax.lax.scan(
        body, cache, (tokens.T, jnp.arange(C, dtype=jnp.int32))
    )
    return jnp.transpose(logits, (1, 0, 2)), cache


def chunk_parallel(cfg: ModelConfig) -> bool:
    """True where :func:`prefill_chunk_paged` can serve a chunk: every
    layer is dense GQA attention with a dense FFN.  SSM slots need
    in-chunk recurrence, MLA its own latent chunk maths, and MoE capacity
    drops tokens by how many rows are batched (so a batched pass would
    drop others); those keep :func:`prefill_step_paged`.  With ``moe``
    None there is no ``first_block`` either."""
    return (all(k == LayerKind.ATTN for k in cfg.superblock)
            and cfg.mla is None and cfg.moe is None)


def _as_stored(a, cfg: ModelConfig, kv_dtype: str):
    """A fresh key/value slice as a ``kv_dtype`` pool reads it back once
    committed (int8: the commit's per-row quantization)."""
    compute = jnp.dtype(cfg.compute_dtype)
    if kv_dtype == "int8":
        q, s = _quantize_token(a, stacked=False)
        return q.astype(compute) * s.reshape(
            s.shape + (1,) * (a.ndim - s.ndim)).astype(compute)
    return a.astype(_pool_dtype(cfg, kv_dtype)).astype(compute)


def prefill_chunk_paged(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,
    cache: Dict[str, Any],
    positions: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    block_size: int,
    kv_dtype: str = "f32",
) -> Tuple[jax.Array, Dict[str, Any]]:
    """:func:`prefill_step_paged`'s contract in ONE pass through the layers.

    Same arguments and results: slot ``b``'s row ``c`` sits at
    ``positions[b] + c`` and is active iff ``c < lengths[b]``; logits
    (B, C, vocab_padded) fp32 come back for every row.  Each layer runs
    its projections and FFN on all B x C rows at once, so every weight is
    read once per step instead of C times, and attends each row over its
    slot's cache prefix plus the chunk's own causal rows
    (:func:`~repro.models.attention.attention_chunk`).  The layers' key
    and value rows commit after the layer scan in one scatter; inactive
    rows land in :data:`NULL_BLOCK`.  Against the scan the sums are
    reassociated, so logits and committed rows agree to float tolerance,
    not bitwise.  Only for configs where :func:`chunk_parallel` holds.
    """
    if not chunk_parallel(cfg):
        raise ValueError(f"{cfg.name}: chunk-parallel prefill needs dense "
                         "GQA attention layers with dense FFNs")
    B, C = tokens.shape
    pos0 = positions.astype(jnp.int32)
    row = jnp.arange(C, dtype=jnp.int32)
    pos_bc = pos0[:, None] + row                                # (B, C)
    active = (row < lengths.astype(jnp.int32)[:, None]).reshape(B * C)
    nb = block_tables.shape[1]
    blk = jnp.take_along_axis(
        block_tables, jnp.minimum(pos_bc // block_size, nb - 1), axis=1)
    flat_idx = jnp.where(
        active, (blk * block_size + pos_bc % block_size).reshape(B * C),
        NULL_BLOCK * block_size,
    )  # (B*C,) pool token index
    compute = jnp.dtype(cfg.compute_dtype)
    _, norm_fn = layers.make_norm(cfg)
    x = layers.embed(params["embed"], tokens).astype(compute)

    def stored(a):
        return _as_stored(a, cfg, kv_dtype)

    def scan_body(x, inp):
        p_blk, c_blk = inp
        deltas = {}
        for i in range(len(cfg.superblock)):
            p = p_blk[f"slot{i}"]
            view = _paged_view(c_blk[f"slot{i}"], block_tables, kv_dtype,
                               compute)
            att, k_new, v_new = attention.attention_chunk(
                p["attn"], cfg, norm_fn(p["norm1"], x), view["k"],
                view["v"], pos0, stored)
            x = x + att
            if "ffn" in p:
                x = x + layers.swiglu(p["ffn"], norm_fn(p["norm2"], x))
            deltas[f"slot{i}"] = {"k": k_new, "v": v_new}
        return x, deltas

    x, deltas = jax.lax.scan(scan_body, x, (params["blocks"], cache["blocks"]))
    # (nsb, B, C, ...) -> (nsb, B*C, 1, ...): one token row per pool index
    new_cache: Dict[str, Any] = {"blocks": {
        slot: _commit_slot(
            cache["blocks"][slot],
            {k: d.reshape((d.shape[0], B * C, 1) + d.shape[3:])
             for k, d in slot_deltas.items()},
            flat_idx, True, active, kv_dtype)
        for slot, slot_deltas in deltas.items()
    }}

    x = norm_fn(params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = layers.unembed(params["embed"], x)
    else:
        logits = layers.dense(params["lm_head"], x).astype(jnp.float32)
    return logits, new_cache


def prefill(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,
    *,
    img_embeds: Optional[jax.Array] = None,
    remat: str = "none",
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Process the prompt, build caches, return last-token logits + cache."""
    x = layers.embed(params["embed"], tokens).astype(jnp.dtype(cfg.compute_dtype))
    if img_embeds is not None:
        x = jnp.concatenate([img_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)

    cache: Dict[str, Any] = {"pos": jnp.full((), S, jnp.int32)}
    if "first_block" in params:
        x, _, fb_cache = _apply_slot_full(
            params["first_block"], cfg, LayerKind.ATTN, False, x, positions, True
        )
        cache["first_block"] = fb_cache

    block = _block_full(cfg, collect_cache=True)

    def scan_body(x, p_blk):
        y, _, caches = block(p_blk, x, positions)
        return y, caches

    scan_fn = _remat(scan_body, remat)
    x, block_caches = jax.lax.scan(scan_fn, x, params["blocks"])
    cache["blocks"] = block_caches

    _, norm_fn = layers.make_norm(cfg)
    x_last = norm_fn(params["final_norm"], x[:, -1:, :])
    if cfg.tie_embeddings:
        logits = layers.unembed(params["embed"], x_last)
    else:
        logits = layers.dense(params["lm_head"], x_last).astype(jnp.float32)
    return logits, cache
