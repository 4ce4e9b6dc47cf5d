"""Flash-decode: one-token attention over a long KV cache, KV-blocked.

The serve-side hot loop of every decode_* cell: q (B, H, D) attends to a
(B, S, KV, D) cache of which only ``valid_len`` positions are live.  The
kernel streams KV blocks through VMEM keeping a running (max, sum, acc) —
online softmax — and PREDICATES each block on ``pos < valid_len``: ragged
context lengths occupy only ceil(valid/bs) block-issues per head instead of
S/bs, the SVE predication insight applied at the token level (a fixed-width
schedule must process the whole padded cache).

Grid: (B, KV-heads, S/bs) with the KV axis innermost (sequential).  GQA via
G query heads per KV head processed together — the q tile is (G, D), MXU
contractions are (G, D) x (D, bs).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _decode_kernel(vl_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, bs: int, ns: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = vl_ref[pl.program_id(0)]  # scalar-prefetched (SMEM)
    q = q_ref[0, 0]  # (G, D)
    k = k_ref[0, 0]  # (bs, D)
    v = v_ref[0, 0]
    D = q.shape[-1]
    scale = 1.0 / math.sqrt(D)

    pos = si * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)[0]
    pred = pos < valid  # predicate register analogue

    # skip fully-masked blocks entirely (ragged-length win; on TPU this is
    # the "don't issue the tile" branch)
    @pl.when(si * bs < valid)
    def _work():
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (G, bs)
        s = jnp.where(pred[None, :], s, NEG_INF)
        m_new = jnp.maximum(m_ref[...], s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_ref[...] - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_decode(
    q: jax.Array,       # (B, KV, G, D)
    k: jax.Array,       # (B, S, KV, D)
    v: jax.Array,       # (B, S, KV, D)
    valid_len: jax.Array,  # (B,) int32 — live cache length per sequence
    *,
    block_s: int = 512,
    interpret: bool = True,
) -> jax.Array:
    """Returns (B, KV, G, D) attention output over the predicated cache."""
    B, KV, G, D = q.shape
    S = k.shape[1]
    bs = min(block_s, S)
    assert S % bs == 0, (S, bs)
    ns = S // bs
    kernel = functools.partial(_decode_kernel, bs=bs, ns=ns)
    from jax.experimental.pallas import tpu as pltpu

    kt = k.transpose(0, 2, 1, 3)  # (B, KV, S, D): head-major streaming
    vt = v.transpose(0, 2, 1, 3)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # valid_len lives in SMEM
        grid=(B, KV, ns),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, s, vl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, D), lambda b, h, s, vl: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bs, D), lambda b, h, s, vl: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, s, vl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(valid_len, q, kt, vt)


def _decode_kernel_paged(bt_ref, vl_ref, *refs, bs: int, ns: int,
                         quantized: bool = False):
    """Same online-softmax body as :func:`_decode_kernel`; the KV tile for
    logical block ``si`` of sequence ``b`` is DMA'd from pool block
    ``bt_ref[b, si]`` (scalar-prefetched block table drives the index_map),
    so the kernel streams a non-contiguous paged cache without ever
    materializing a gathered copy.

    ``quantized`` threads two per-row fp32 scale tiles (the ELEN axis of
    the pool: int8 rows stream at 1/4 the HBM bytes and are widened back in
    VMEM right before the MXU contraction)."""
    if quantized:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        ks_ref = vs_ref = None
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = vl_ref[pl.program_id(0)]
    q = q_ref[0, 0]  # (G, D)
    k = k_ref[0, 0]  # (bs, D) — one pool block
    v = v_ref[0, 0]
    if ks_ref is not None:  # dequantize the tile in VMEM, post-DMA
        k = k.astype(jnp.float32) * ks_ref[0]  # (rows, 1) scale column
        v = v.astype(jnp.float32) * vs_ref[0]
    elif k.dtype != q.dtype:  # bf16 pool: widen to the compute dtype
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    D = q.shape[-1]
    scale = 1.0 / math.sqrt(D)

    pos = si * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)[0]
    pred = pos < valid  # per-slot length predication

    @pl.when(si * bs < valid)
    def _work():
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = jnp.where(pred[None, :], s, NEG_INF)
        m_new = jnp.maximum(m_ref[...], s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_ref[...] - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_decode_paged(
    q: jax.Array,            # (B, KV, G, D)
    k_pool: jax.Array,       # (n_blocks, block_size, KV, D)
    v_pool: jax.Array,       # (n_blocks, block_size, KV, D)
    block_tables: jax.Array,  # (B, nb) int32 — logical -> pool block map
    valid_len: jax.Array,    # (B,) int32 — live length per slot, >= 1
    *,
    k_scale: jax.Array = None,  # (n_blocks, block_size) f32 — int8 pools
    v_scale: jax.Array = None,
    head_shard: tuple = None,   # (shard_idx, n_shards) — local KV heads only
    interpret: bool = True,
) -> jax.Array:
    """Flash-decode over a PAGED cache: the continuous-batching serve path.

    Each slot's KV lives in ``valid_len[b] / block_size`` pool blocks named
    by its block-table row; the kernel walks logical blocks, prefetching
    the table so the BlockSpec index_map resolves the indirection at DMA
    time.  Fully-masked logical blocks (beyond the slot's live prefix) are
    never issued — the same predication economics as the contiguous
    kernel, now compounded with block reuse across requests.  Slots with
    ``valid_len == 0`` produce unspecified output (they have no live
    tokens to attend over); the serving engine masks such slots itself.

    Quantized paging (the ELEN axis of the pool): with int8 pools, pass
    ``k_scale``/``v_scale`` — one fp32 scale per pool ROW, shared across
    heads and the D axis — and each KV tile is dequantized in VMEM after
    the (4x smaller) DMA.  bf16 pools need no scales; the tile is widened
    to the query dtype before the contraction.

    Head sharding (tensor-parallel serving): ``head_shard=(i, n)`` runs
    only shard ``i``'s contiguous 1/n of the KV heads — q and the pools
    are sliced on their head axes and the output shrinks to ``(B, KV/n,
    G, D)``.  Attention is embarrassingly parallel over heads (softmax
    normalizes within a head), so shard outputs concatenate exactly to
    the unsharded result; per-row scales are head-agnostic and pass
    through whole.  :func:`flash_decode_paged_sharded` drives one such
    slice per device of a mesh's model axis via ``shard_map``.
    """
    if head_shard is not None:
        idx, n = head_shard
        kv_total = q.shape[1]
        if not 0 <= idx < n:
            raise ValueError(f"head_shard index {idx} outside [0, {n})")
        if kv_total % n:
            raise ValueError(
                f"{kv_total} KV heads not divisible into {n} shards")
        per = kv_total // n
        q = q[:, idx * per:(idx + 1) * per]
        k_pool = k_pool[:, :, idx * per:(idx + 1) * per]
        v_pool = v_pool[:, :, idx * per:(idx + 1) * per]
    B, KV, G, D = q.shape
    bs = k_pool.shape[1]
    nb = block_tables.shape[1]
    quantized = k_scale is not None
    if quantized and v_scale is None:
        raise ValueError("k_scale and v_scale must be passed together")
    kernel = functools.partial(_decode_kernel_paged, bs=bs, ns=nb,
                               quantized=quantized)
    from jax.experimental.pallas import tpu as pltpu

    kt = k_pool.transpose(0, 2, 1, 3)  # (n_blocks, KV, bs, D): head-major
    vt = v_pool.transpose(0, 2, 1, 3)
    in_specs = [
        pl.BlockSpec((1, 1, G, D), lambda b, h, s, bt, vl: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, bs, D), lambda b, h, s, bt, vl: (bt[b, s], h, 0, 0)),
        pl.BlockSpec((1, 1, bs, D), lambda b, h, s, bt, vl: (bt[b, s], h, 0, 0)),
    ]
    operands = [block_tables, valid_len, q, kt, vt]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, bs, 1), lambda b, h, s, bt, vl: (bt[b, s], 0, 0)),
            pl.BlockSpec((1, bs, 1), lambda b, h, s, bt, vl: (bt[b, s], 0, 0)),
        ]
        # (n_blocks, bs, 1) scale columns keep the blocks (8, 128)-tileable
        operands += [k_scale[..., None], v_scale[..., None]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block table and lengths live in SMEM
        grid=(B, KV, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, s, bt, vl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(*operands)


def flash_decode_paged_sharded(
    q: jax.Array,             # (B, KV, G, D)
    k_pool: jax.Array,        # (n_blocks, block_size, KV, D)
    v_pool: jax.Array,        # (n_blocks, block_size, KV, D)
    block_tables: jax.Array,  # (B, nb) int32
    valid_len: jax.Array,     # (B,) int32
    *,
    mesh,                     # jax Mesh with a "model" axis
    axis: str = "model",
    k_scale: jax.Array = None,
    v_scale: jax.Array = None,
    interpret: bool = True,
) -> jax.Array:
    """Tensor-parallel paged flash-decode: one kernel launch per device of
    the mesh's ``axis``, each over its local 1/n of the KV heads.

    The pools shard on their head axis (``P(None, None, axis, None)`` —
    the block axis stays replicated so block tables resolve without
    cross-device gathers, matching the serving engine's head-sharded
    block-pool layout), the block table / lengths / per-row scales
    replicate, and the per-shard outputs concatenate on the head axis.
    No collective is needed: softmax normalizes within a head.
    """
    from jax.sharding import PartitionSpec as P

    n = int(mesh.shape[axis])
    KV = q.shape[1]
    if KV % n:
        raise ValueError(f"{KV} KV heads not divisible over {n} "
                         f"{axis!r}-axis devices")
    head_q = P(None, axis, None, None)
    head_pool = P(None, None, axis, None)
    rep = P(*(None,) * 2)
    rep1 = P(None)
    quantized = k_scale is not None
    if quantized and v_scale is None:
        raise ValueError("k_scale and v_scale must be passed together")

    if quantized:
        def local(qi, kp, vp, bt, vl, ks, vs):
            return flash_decode_paged(qi, kp, vp, bt, vl, k_scale=ks,
                                      v_scale=vs, interpret=interpret)

        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(head_q, head_pool, head_pool, rep, rep1, rep, rep),
            out_specs=head_q, check_vma=False,
        )
        return fn(q, k_pool, v_pool, block_tables, valid_len,
                  k_scale, v_scale)

    def local(qi, kp, vp, bt, vl):
        return flash_decode_paged(qi, kp, vp, bt, vl, interpret=interpret)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(head_q, head_pool, head_pool, rep, rep1),
        out_specs=head_q, check_vma=False,
    )
    return fn(q, k_pool, v_pool, block_tables, valid_len)


# ---------------------------------------------------------------------------
# Chunked flash prefill over the paged cache
# ---------------------------------------------------------------------------


def _prefill_commit_kernel(bt_ref, qs_ref, ql_ref, kn_ref, vn_ref,
                           kp_ref, vp_ref, ko_ref, vo_ref, *, bs: int, C: int):
    """Scatter the chunk's K/V rows into one pool block of one slot.

    Grid (B, nb): every logical block of slot ``b`` streams through VMEM;
    rows whose global position lands in ``[q_start, q_start + q_len)`` are
    overlaid with the chunk's new K/V, the rest are copied through
    unchanged, and the block is written back to the (input-aliased) pool.
    Blocks no table row names are never visited and keep their bytes via
    the aliasing; the NULL block (0) may be written by several slots at
    once, so its content stays unspecified — exactly the idle-write
    contract the serving engine already relies on.
    """
    in_chunk, k_over, v_over = _chunk_rows(qs_ref, ql_ref, kn_ref, vn_ref,
                                           bs=bs, C=C)
    sel = in_chunk[None]  # (1, bs, 1) valid_len predication
    ko_ref[0] = jnp.where(sel, k_over.astype(ko_ref.dtype), kp_ref[0])
    vo_ref[0] = jnp.where(sel, v_over.astype(vo_ref.dtype), vp_ref[0])


def _chunk_rows(qs_ref, ql_ref, kn_ref, vn_ref, *, bs: int, C: int):
    """The chunk rows that land in pool block ``si`` of slot ``b``.

    Row ``r`` of the block holds global position ``si*bs + r``, i.e. chunk
    row ``c = si*bs + r - q_start`` when ``0 <= c < q_len``.  Mosaic has no
    1-D gather, so the rows are picked with a one-hot (bs, C) contraction
    per KV head; each output row sums a single product by 1, so the pick
    is exact.  Returns the (bs, 1) in-chunk predicate and the (KV, bs, D)
    fp32 rows (zero outside the chunk).
    """
    b, si = pl.program_id(0), pl.program_id(1)
    q_start = qs_ref[b]
    q_len = ql_ref[b]
    row = jax.lax.broadcasted_iota(jnp.int32, (bs, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bs, C), 1)
    pick = (si * bs + row - q_start == col) & (col < q_len)
    c_idx = si * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0) - q_start
    in_chunk = (c_idx >= 0) & (c_idx < q_len)

    def rows(n_ref):
        onehot = pick.astype(n_ref.dtype)
        # fp32 rows need the full-precision MXU passes to stay exact
        exact = (jax.lax.Precision.HIGHEST if n_ref.dtype == jnp.float32
                 else None)
        return jnp.stack([
            jnp.dot(onehot, n_ref[0, h], preferred_element_type=jnp.float32,
                    precision=exact)
            for h in range(n_ref.shape[1])
        ])

    return in_chunk, rows(kn_ref), rows(vn_ref)


def _quantize_rows_kernel(x):
    """Per-row symmetric int8: one fp32 scale per pool row, amax over the
    (heads, D) extent of that row.  ``x`` is (KV, bs, D); returns the int8
    rows and the (bs, 1) scales."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=0).max(
        axis=-1, keepdims=True)
    s = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / s[None]), -127, 127
    ).astype(jnp.int8)
    return q, s


def _prefill_commit_kernel_q(bt_ref, qs_ref, ql_ref, kn_ref, vn_ref,
                             kp_ref, vp_ref, ksp_ref, vsp_ref,
                             ko_ref, vo_ref, kso_ref, vso_ref,
                             *, bs: int, C: int):
    """Quantizing variant of :func:`_prefill_commit_kernel`: chunk rows are
    quantized to int8 with one fresh fp32 scale per pool row before the
    overlay, and the scale pools ride through the same block-table-indexed
    write-back (rows outside the chunk keep block AND scale bytes)."""
    in_chunk, k_over, v_over = _chunk_rows(qs_ref, ql_ref, kn_ref, vn_ref,
                                           bs=bs, C=C)
    kq, ks = _quantize_rows_kernel(k_over)
    vq, vs = _quantize_rows_kernel(v_over)
    sel = in_chunk[None]
    ko_ref[0] = jnp.where(sel, kq, kp_ref[0])
    vo_ref[0] = jnp.where(sel, vq, vp_ref[0])
    kso_ref[0] = jnp.where(in_chunk, ks, ksp_ref[0])
    vso_ref[0] = jnp.where(in_chunk, vs, vsp_ref[0])


def _prefill_attn_kernel(bt_ref, qs_ref, ql_ref, *refs, block_c: int,
                         block_s: int, ns: int, G: int,
                         quantized: bool = False):
    """Causal online-softmax over one (query-tile, KV-block) grid cell.

    Same running (max, sum, acc) recurrence as :func:`_decode_kernel_paged`
    lifted to a ``block_c``-row query tile: the G grouped query heads of
    every chunk row are flattened into the tile so one MXU contraction
    covers the whole (block_c*G, block_s) score panel.  KV blocks beyond
    the tile's causal frontier are never issued — prompt-length
    predication, one level up from the decode kernel's ``valid_len``.
    ``quantized`` dequantizes each int8 KV sub-tile with its per-row fp32
    scales, exactly as the decode kernel does.
    """
    if quantized:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        ks_ref = vs_ref = None
    qi = pl.program_id(2)
    si = pl.program_id(3)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qs_ref[pl.program_id(0)]
    q = q_ref[0, 0]  # (block_c * G, D): row r is chunk row r // G
    D = q.shape[-1]
    k = k_ref[0, 0]  # (block_s, D)
    v = v_ref[0, 0]
    if ks_ref is not None:
        k = k.astype(jnp.float32) * ks_ref[0]  # (rows, 1) scale column
        v = v.astype(jnp.float32) * vs_ref[0]
    elif k.dtype != q.dtype:
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    scale = 1.0 / math.sqrt(D)

    pos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    q_idx = (jax.lax.broadcasted_iota(jnp.int32, (block_c * G, 1), 0) // G
             + qi * block_c)
    limit = q_start + q_idx  # last key position each query row may see

    # skip KV blocks entirely beyond this query tile's causal frontier
    @pl.when(si * block_s <= q_start + (qi + 1) * block_c - 1)
    def _work():
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = jnp.where(pos <= limit, s, NEG_INF)
        m_new = jnp.maximum(m_ref[...], s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_ref[...] - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_prefill_paged(
    q: jax.Array,             # (B, C, KV, G, D) — chunk queries
    k_new: jax.Array,         # (B, C, KV, D) — chunk keys
    v_new: jax.Array,         # (B, C, KV, D) — chunk values
    k_pool: jax.Array,        # (n_blocks, block_size, KV, D)
    v_pool: jax.Array,        # (n_blocks, block_size, KV, D)
    block_tables: jax.Array,  # (B, nb) int32 — logical -> pool block map
    q_start: jax.Array,       # (B,) int32 — live context length before chunk
    q_len: jax.Array = None,  # (B,) int32 — valid chunk rows (default C)
    *,
    k_scale: jax.Array = None,  # (n_blocks, block_size) f32 — int8 pools
    v_scale: jax.Array = None,
    block_c: int = 8,
    block_s: int = 0,
    interpret: bool = True,
):
    """Chunked flash prefill over a PAGED cache: commit + attend, fused
    per chunk instead of per token.

    A chunk of ``C`` prompt tokens per slot is (1) scattered straight into
    the slot's pool blocks — the commit kernel walks the scalar-prefetched
    block table exactly like :func:`flash_decode_paged`, overlaying rows in
    ``[q_start, q_start + q_len)`` — and (2) attended causally against the
    updated pool with a ``block_c``-row online softmax, so a P-token prompt
    costs ``ceil(P / C)`` kernel launches instead of ``P``.  ``block_s``
    sub-tiles pool blocks (0 means one tile per pool block).

    Requirements and contract:
    * every chunk position must already be backed by a real (non-NULL)
      block-table entry — the engine allocates before it commits;
    * rows at or past ``q_len[b]`` are neither committed nor defined in the
      output (ragged final chunks);
    * the NULL block and pool blocks no table row references have
      unspecified content on return — compare through block tables.

    Quantized paging: with int8 pools pass ``k_scale``/``v_scale`` (one
    fp32 scale per pool row).  The commit kernel quantizes the chunk's
    rows and writes fresh scales alongside the blocks; the attend kernel
    dequantizes each sub-tile in VMEM.  The return grows to ``(out,
    k_pool', v_pool', k_scale', v_scale')``.  bf16 pools need no scales.

    Returns ``(out, k_pool', v_pool')`` with ``out`` shaped like ``q`` and
    the pools in their caller layout.
    """
    from jax.experimental.pallas import tpu as pltpu

    B, C, KV, G, D = q.shape
    bs = k_pool.shape[1]
    nb = block_tables.shape[1]
    quantized = k_scale is not None
    if quantized and v_scale is None:
        raise ValueError("k_scale and v_scale must be passed together")
    if q_len is None:
        q_len = jnp.full((B,), C, jnp.int32)
    bc = min(block_c, C)
    assert C % bc == 0, (C, bc)
    bks = bs if not block_s else min(block_s, bs)
    assert bs % bks == 0, (bs, bks)
    spp = bs // bks  # KV sub-tiles per pool block
    ns = nb * spp

    kp = k_pool.transpose(0, 2, 1, 3)  # (n_blocks, KV, bs, D): head-major
    vp = v_pool.transpose(0, 2, 1, 3)
    kn = k_new.transpose(0, 2, 1, 3)   # (B, KV, C, D)
    vn = v_new.transpose(0, 2, 1, 3)

    # block table, q_start and q_len are scalar-prefetched into SMEM
    pool_spec = pl.BlockSpec((1, KV, bs, D),
                             lambda b, s, bt, qs, ql: (bt[b, s], 0, 0, 0))
    scale_spec = pl.BlockSpec((1, bs, 1),
                              lambda b, s, bt, qs, ql: (bt[b, s], 0, 0))
    commit_in = [
        pl.BlockSpec((1, KV, C, D), lambda b, s, bt, qs, ql: (b, 0, 0, 0)),
        pl.BlockSpec((1, KV, C, D), lambda b, s, bt, qs, ql: (b, 0, 0, 0)),
        pool_spec, pool_spec,
    ]
    commit_out = [pool_spec, pool_spec]
    commit_operands = [block_tables, q_start, q_len, kn, vn, kp, vp]
    commit_shapes = [
        jax.ShapeDtypeStruct(kp.shape, kp.dtype),
        jax.ShapeDtypeStruct(vp.shape, vp.dtype),
    ]
    # pool (and scale) operands alias their outputs so unvisited blocks
    # keep their bytes (indices count the scalar-prefetch operands)
    aliases = {5: 0, 6: 1}
    if quantized:
        commit_in += [scale_spec, scale_spec]
        commit_out += [scale_spec, scale_spec]
        # scales travel as (n_blocks, bs, 1) columns: (8, 128)-tileable
        k_scale, v_scale = k_scale[..., None], v_scale[..., None]
        commit_operands += [k_scale, v_scale]
        commit_shapes += [
            jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
            jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
        ]
        aliases = {5: 0, 6: 1, 7: 2, 8: 3}
    commit_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nb),
        in_specs=commit_in,
        out_specs=commit_out,
    )
    commit_body = (
        functools.partial(_prefill_commit_kernel_q, bs=bs, C=C) if quantized
        else functools.partial(_prefill_commit_kernel, bs=bs, C=C)
    )
    committed = pl.pallas_call(
        commit_body,
        grid_spec=commit_spec,
        out_shape=commit_shapes,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*commit_operands)
    if quantized:
        kp, vp, k_scale, v_scale = committed
    else:
        kp, vp = committed

    # (B, KV, C * G, D): the G grouped heads of a chunk row are adjacent
    qh = q.transpose(0, 2, 1, 3, 4).reshape(B, KV, C * G, D)

    def kv_tile(b, h, qi, s, bt, qs, ql):
        return (bt[b, s // spp], h, s % spp, 0)

    def scale_tile(b, h, qi, s, bt, qs, ql):
        return (bt[b, s // spp], s % spp, 0)

    def q_tile(b, h, qi, s, bt, qs, ql):
        return (b, h, qi, 0)

    attn_in = [
        pl.BlockSpec((1, 1, bc * G, D), q_tile),
        pl.BlockSpec((1, 1, bks, D), kv_tile),
        pl.BlockSpec((1, 1, bks, D), kv_tile),
    ]
    attn_operands = [block_tables, q_start, q_len, qh, kp, vp]
    if quantized:
        attn_in += [pl.BlockSpec((1, bks, 1), scale_tile)] * 2
        attn_operands += [k_scale, v_scale]
    attn_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, C // bc, ns),
        in_specs=attn_in,
        out_specs=pl.BlockSpec((1, 1, bc * G, D), q_tile),
        scratch_shapes=[
            pltpu.VMEM((bc * G,), jnp.float32),
            pltpu.VMEM((bc * G,), jnp.float32),
            pltpu.VMEM((bc * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_attn_kernel, block_c=bc, block_s=bks,
                          ns=ns, G=G, quantized=quantized),
        grid_spec=attn_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, C * G, D), q.dtype),
        interpret=interpret,
    )(*attn_operands)

    out = out.reshape(B, KV, C, G, D).transpose(0, 2, 1, 3, 4)
    kp = kp.transpose(0, 2, 1, 3)
    vp = vp.transpose(0, 2, 1, 3)
    if quantized:
        return out, kp, vp, k_scale[..., 0], v_scale[..., 0]
    return out, kp, vp
