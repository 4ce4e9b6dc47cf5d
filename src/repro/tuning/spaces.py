"""Built-in tuning spaces for the registry kernels (paper Sec. 3.2 suite).

One :class:`~repro.tuning.space.TuningSpace` per Pallas kernel, declaring
its block/tile axes, the kernel's hard-coded defaults (so tuned-vs-default
is well defined), the VMEM working-set model, and — where tile shape
changes traffic — an HBM traffic model for roofline pruning.

This module also owns the GEMM tile model that used to live privately in
``kernels/gemm/ops.py`` (:func:`gemm_vmem_bytes`, :func:`pick_gemm_tiles`):
the old per-kernel heuristic is now one projection of the shared space, and
``gemm/ops.py`` delegates here unchanged (golden-pinned in
``tests/test_tuning.py``).

SpMV has no space on purpose: its tunable quantities (``row_block``,
``width_pad``) are data-layout parameters fixed at problem construction,
not kernel call arguments.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

from repro.tuning.space import TuningSpace

# ---------------------------------------------------------------------------
# GEMM — the only kernel with a 3-axis tile space (and the legacy heuristic)
# ---------------------------------------------------------------------------

#: The legacy `pick_tiles` candidate values, preserved verbatim (order is
#: the tie-break: first-seen max-volume config wins, exactly as the old
#: triple loop behaved).
GEMM_AXES: Dict[str, Tuple[int, ...]] = {
    "bm": (512, 256, 128),
    "bn": (512, 256, 128),
    "bk": (1024, 512, 256, 128),
}


def gemm_vmem_bytes(bm: int, bn: int, bk: int, in_bytes: int = 2) -> int:
    """Working set per grid step: x tile + y tile + fp32 acc + out tile.

    (The exact formula that lived in ``kernels/gemm/ops.py``.)
    """
    return bm * bk * in_bytes + bk * bn * in_bytes + bm * bn * 4 + bm * bn * in_bytes


def _gemm_dims(args: Tuple) -> Tuple[int, int, int]:
    x, y = args[0], args[1]
    M, K = x.shape
    N = y.shape[1]
    return M, N, K


def _gemm_clamp(cfg: Dict[str, Any], args: Tuple) -> Dict[str, Any]:
    M, N, K = _gemm_dims(args)
    return {"bm": min(cfg["bm"], M), "bn": min(cfg["bn"], N), "bk": min(cfg["bk"], K)}


def _gemm_ok(cfg: Dict[str, Any], args: Tuple) -> bool:
    M, N, K = _gemm_dims(args)
    bm, bn, bk = min(cfg["bm"], M), min(cfg["bn"], N), min(cfg["bk"], K)
    return M % bm == 0 and N % bn == 0 and K % bk == 0


def _gemm_vmem(cfg: Dict[str, Any], args: Tuple, dtype_bytes: int) -> float:
    return gemm_vmem_bytes(cfg["bm"], cfg["bn"], cfg["bk"], dtype_bytes)


def _gemm_traffic(cfg: Dict[str, Any], args: Tuple) -> float:
    """Tile-reuse model: x streams once per bn-tile of y, y once per
    bm-tile of x, the output is written once."""
    M, N, K = _gemm_dims(args)
    in_b = args[0].dtype.itemsize
    bm, bn = min(cfg["bm"], M), min(cfg["bn"], N)
    return float(M * K * (N // bn) * in_b + K * N * (M // bm) * in_b + M * N * in_b)


def _gemm_flops(args: Tuple) -> float:
    M, N, K = _gemm_dims(args)
    return 2.0 * M * N * K


def gemm_space() -> TuningSpace:
    return TuningSpace(
        kernel="gemm",
        axes=dict(GEMM_AXES),
        default={"bm": 128, "bn": 128, "bk": 128},
        dtypes=("fp32", "bf16"),
        clamp=_gemm_clamp,
        constraint=_gemm_ok,
        vmem_model=_gemm_vmem,
        traffic_model=_gemm_traffic,
        flops_model=_gemm_flops,
    )


def pick_gemm_tiles(
    M: int,
    N: int,
    K: int,
    *,
    vmem_budget: int = 96 * 2**20,
    in_bytes: int = 2,
) -> Tuple[int, int, int]:
    """Largest MXU-aligned tiles fitting the VMEM budget (legacy projection
    of the GEMM space: max bm*bn*bk volume, first-seen wins ties)."""
    space = gemm_space()
    best = (128, 128, 128)
    for cfg in space.configs():
        bm, bn, bk = cfg["bm"], cfg["bn"], cfg["bk"]
        if M % bm or N % bn or K % bk:
            continue
        if gemm_vmem_bytes(bm, bn, bk, in_bytes) <= vmem_budget:
            if bm * bn * bk > best[0] * best[1] * best[2]:
                best = (bm, bn, bk)
    return best


# ---------------------------------------------------------------------------
# STREAM — pure streaming; traffic is config-independent, timing decides
# ---------------------------------------------------------------------------


def _rows_of(args: Tuple) -> Tuple[int, int]:
    a = args[0]
    rows, width = a.shape
    return rows, width


def _stream_clamp(cfg: Dict[str, Any], args: Tuple) -> Dict[str, Any]:
    rows, _ = _rows_of(args)
    return {"block_rows": min(cfg["block_rows"], rows)}


def _stream_ok(cfg: Dict[str, Any], args: Tuple) -> bool:
    rows, _ = _rows_of(args)
    br = min(cfg["block_rows"], rows)
    return rows % br == 0


def stream_space(n_arrays: int, flops_per_elem: float) -> TuningSpace:
    def vmem(cfg: Dict[str, Any], args: Tuple, dtype_bytes: int) -> float:
        rows, width = _rows_of(args)
        br = min(cfg["block_rows"], rows)
        return float((n_arrays + 1) * br * width * dtype_bytes)

    def traffic(cfg: Dict[str, Any], args: Tuple) -> float:
        rows, width = _rows_of(args)
        return float((n_arrays + 1) * rows * width * args[0].dtype.itemsize)

    def flops(args: Tuple) -> float:
        rows, width = _rows_of(args)
        return flops_per_elem * rows * width

    return TuningSpace(
        kernel="stream",
        axes={"block_rows": (1024, 512, 256, 128, 64, 32, 8)},
        default={"block_rows": 256},
        dtypes=("fp32", "bf16", "fp16"),
        clamp=_stream_clamp,
        constraint=_stream_ok,
        vmem_model=vmem,
        traffic_model=traffic,
        flops_model=flops,
    )


# ---------------------------------------------------------------------------
# Jacobi2D — row tiles plus one aligned halo block above and below
# ---------------------------------------------------------------------------


def _jacobi_clamp(cfg: Dict[str, Any], args: Tuple) -> Dict[str, Any]:
    H, _ = args[0].shape
    return {"block_rows": min(cfg["block_rows"], H)}


def _jacobi_ok(cfg: Dict[str, Any], args: Tuple) -> bool:
    H, _ = args[0].shape
    br = min(cfg["block_rows"], H)
    return H % br == 0


def _jacobi_halo(br: int, dtype_bytes: int) -> int:
    return math.gcd(br, 32 // dtype_bytes)


def _jacobi_vmem(cfg: Dict[str, Any], args: Tuple, dtype_bytes: int) -> float:
    H, W = args[0].shape
    br = min(cfg["block_rows"], H)
    hb = _jacobi_halo(br, dtype_bytes)
    # double-buffered input tile + two halo blocks + output tile
    return float(2 * (2 * br + 2 * hb) * W * dtype_bytes)


def _jacobi_traffic(cfg: Dict[str, Any], args: Tuple) -> float:
    """Each row block re-reads two halo blocks, so larger row blocks mean
    less halo traffic on top of one read and one write of u."""
    H, W = args[0].shape
    b = args[0].dtype.itemsize
    br = min(cfg["block_rows"], H)
    hb = _jacobi_halo(br, b)
    return float(H * W * b * (1 + 2 * hb / br) + H * W * b)


def jacobi2d_space() -> TuningSpace:
    return TuningSpace(
        kernel="jacobi2d",
        axes={"block_rows": (256, 128, 64, 32, 16, 8)},
        default={"block_rows": 128},
        dtypes=("fp32",),
        clamp=_jacobi_clamp,
        constraint=_jacobi_ok,
        vmem_model=_jacobi_vmem,
        traffic_model=_jacobi_traffic,
        flops_model=lambda args: 4.0 * args[0].shape[0] * args[0].shape[1],
    )


# ---------------------------------------------------------------------------
# QC RX gate — outer-axis tiling over the (outer, 2, inner) state view
# ---------------------------------------------------------------------------


def _qc_outer(cfg: Dict[str, Any], args: Tuple) -> int:
    n_amp = args[0].shape[0]
    inner = 1 << int(cfg.get("qubit", 0))
    return n_amp // (2 * inner)


def _qc_clamp(cfg: Dict[str, Any], args: Tuple) -> Dict[str, Any]:
    # clamp against the qubit-0 view (the widest outer axis); the per-call
    # constraint re-checks with the caller's actual qubit
    outer = args[0].shape[0] // 2
    return {"block_outer": min(cfg["block_outer"], max(outer, 1))}


def _qc_ok(cfg: Dict[str, Any], args: Tuple) -> bool:
    outer = _qc_outer(cfg, args)
    if outer <= 0:
        return False
    bo = min(cfg["block_outer"], outer)
    return outer % bo == 0


def _qc_vmem(cfg: Dict[str, Any], args: Tuple, dtype_bytes: int) -> float:
    n_amp = args[0].shape[0]
    inner = 1 << int(cfg.get("qubit", 0))
    outer = n_amp // (2 * inner)
    bo = min(cfg["block_outer"], max(outer, 1))
    return float(4 * bo * 2 * inner * dtype_bytes)  # re/im in + out tiles


def qc_gate_space() -> TuningSpace:
    return TuningSpace(
        kernel="qc-gate",
        axes={"block_outer": (2048, 1024, 512, 256, 128, 64)},
        default={"block_outer": 256},
        dtypes=("fp32",),
        fixed={"qubit": 0, "theta": 0.25},
        clamp=_qc_clamp,
        constraint=_qc_ok,
        vmem_model=_qc_vmem,
        traffic_model=lambda cfg, args: float(
            4 * args[0].shape[0] * args[0].dtype.itemsize
        ),
        flops_model=lambda args: 6.0 * args[0].shape[0],
    )


# ---------------------------------------------------------------------------
# Flash-decode — KV-block length over the streamed cache
# ---------------------------------------------------------------------------


def _fd_s(args: Tuple) -> int:
    return args[1].shape[1]  # k: (B, S, KV, D)


def _fd_clamp(cfg: Dict[str, Any], args: Tuple) -> Dict[str, Any]:
    return {"block_s": min(cfg["block_s"], _fd_s(args))}


def _fd_ok(cfg: Dict[str, Any], args: Tuple) -> bool:
    S = _fd_s(args)
    bs = min(cfg["block_s"], S)
    return S % bs == 0


def _fd_vmem(cfg: Dict[str, Any], args: Tuple, dtype_bytes: int) -> float:
    q = args[0]
    D = q.shape[-1]
    G = q.shape[-2]
    bs = min(cfg["block_s"], _fd_s(args))
    return float((2 * bs * D + 2 * G * D) * dtype_bytes)  # k/v tiles + q + acc


def _fd_traffic(cfg: Dict[str, Any], args: Tuple) -> float:
    q, k = args[0], args[1]
    b = q.dtype.itemsize
    B, KV, G, D = q.shape
    S = k.shape[1]
    return float((2 * B * S * KV * D + 2 * B * KV * G * D) * b)


def flash_decode_space() -> TuningSpace:
    return TuningSpace(
        kernel="flash-decode",
        axes={"block_s": (1024, 512, 256, 128, 64, 32, 16)},
        default={"block_s": 512},
        dtypes=("fp32", "bf16"),
        clamp=_fd_clamp,
        constraint=_fd_ok,
        vmem_model=_fd_vmem,
        traffic_model=_fd_traffic,
        flops_model=lambda args: 4.0
        * args[0].shape[0] * args[0].shape[1] * args[0].shape[2]
        * args[0].shape[3] * args[1].shape[1],
    )


# ---------------------------------------------------------------------------
# Flash-prefill — chunk (query) tile x KV sub-tile over the paged pool
# ---------------------------------------------------------------------------
#
# args convention = the kernel call: (q (B,C,KV,G,D), k_new, v_new,
# k_pool (n_blocks,bs,KV,D), v_pool, block_tables (B,nb), q_start (B,)).


def _fp_dims(args: Tuple) -> Tuple[int, int, int, int, int, int, int]:
    q, k_pool, bt = args[0], args[3], args[5]
    B, C, KV, G, D = q.shape
    return B, C, KV, G, D, k_pool.shape[1], bt.shape[1]


def _fp_clamp(cfg: Dict[str, Any], args: Tuple) -> Dict[str, Any]:
    _, C, _, _, _, bs, _ = _fp_dims(args)
    bks = min(cfg["block_s"], bs) if cfg["block_s"] else bs  # 0 = pool block
    return {"block_c": min(cfg["block_c"], C), "block_s": bks}


def _fp_ok(cfg: Dict[str, Any], args: Tuple) -> bool:
    _, C, _, _, _, bs, _ = _fp_dims(args)
    bc = min(cfg["block_c"], C)
    bks = min(cfg["block_s"], bs) if cfg["block_s"] else bs
    return C % bc == 0 and bs % bks == 0


def _fp_vmem(cfg: Dict[str, Any], args: Tuple, dtype_bytes: int) -> float:
    _, C, _, G, D, bs, _ = _fp_dims(args)
    bc = min(cfg["block_c"], C)
    bks = min(cfg["block_s"], bs) if cfg["block_s"] else bs
    # q tile + k/v tiles + fp32 (m, l, acc) scratch + out tile
    return float(
        2 * bc * G * D * dtype_bytes
        + 2 * bks * D * dtype_bytes
        + bc * G * (D + 2) * 4
    )


def _fp_live(args: Tuple) -> float:
    """Mean causal frontier per chunk row: context plus half the chunk."""
    import numpy as np

    _, C, _, _, _, _, _ = _fp_dims(args)
    return float(np.mean(np.asarray(args[6]))) + (C + 1) / 2.0


def _fp_traffic(cfg: Dict[str, Any], args: Tuple) -> float:
    """Every query tile re-streams its causal KV prefix, so fewer/wider
    chunk tiles mean fewer passes over the context — monotone in
    ``block_c`` — while the chunk commit itself is written exactly once."""
    B, C, KV, G, D, bs, _ = _fp_dims(args)
    b = args[0].dtype.itemsize
    bc = min(cfg["block_c"], C)
    nq = C // bc
    live = _fp_live(args)
    return float(
        2 * B * KV * nq * live * D * b      # K+V streamed per query tile
        + 3 * B * C * KV * D * b            # chunk K/V read + committed
        + 2 * B * C * KV * G * D * b        # q read + out written
    )


def _fp_flops(args: Tuple) -> float:
    B, C, KV, G, D, _, _ = _fp_dims(args)
    return 4.0 * KV * G * D * B * C * _fp_live(args)


def flash_prefill_space() -> TuningSpace:
    return TuningSpace(
        kernel="flash-prefill",
        axes={
            "block_c": (64, 32, 16, 8, 4, 2, 1),
            "block_s": (512, 256, 128, 64, 32, 16, 8),
        },
        default={"block_c": 8, "block_s": 0},  # 0 = one tile per pool block
        dtypes=("fp32", "bf16"),
        clamp=_fp_clamp,
        constraint=_fp_ok,
        vmem_model=_fp_vmem,
        traffic_model=_fp_traffic,
        flops_model=_fp_flops,
    )


# ---------------------------------------------------------------------------
# Paged-KV storage dtype — the ELEN axis of the serve-path block pool
# ---------------------------------------------------------------------------
#
# args convention = the paged decode call: (q (B,KV,G,D),
# k_pool (n_blocks,bs,KV,D), v_pool, block_tables (B,nb), valid_len (B,)).
#
# Unlike the per-kernel ``dtypes`` tuple (which casts the COMPUTE operands,
# paper Eq. 1 applied to the arithmetic), ``kv_dtype`` narrows only the
# STORED cache: queries and the softmax stay at the compute dtype while
# each KV tile DMAs at 1/2 (bf16) or 1/4 (int8, plus one fp32 scale per
# row) of the f32 bytes and is widened in VMEM.  The tuner must therefore
# never cast the example operands for this axis — it is a distinct static
# argument of the serve path (``ServeEngine(kv_dtype=...)``), searched by
# the accuracy-vs-speed sweep, not by operand substitution.

#: Pool bytes per stored element for each kv_dtype candidate.
KV_DTYPE_ITEMSIZE: Dict[str, int] = {"f32": 4, "bf16": 2, "int8": 1}


def _kv_dims(args: Tuple) -> Tuple[int, int, int, int, int]:
    q, k_pool = args[0], args[1]
    B, KV, G, D = q.shape
    return B, KV, G, D, k_pool.shape[1]


def _kv_traffic(cfg: Dict[str, Any], args: Tuple) -> float:
    """Decode-step HBM traffic: live K+V rows stream at the pool itemsize
    (int8 adds the two fp32 scale rows per block); q/out traffic is at the
    compute dtype and independent of the axis."""
    import numpy as np

    B, KV, G, D, bs = _kv_dims(args)
    kv_dtype = cfg["kv_dtype"]
    item = KV_DTYPE_ITEMSIZE[kv_dtype]
    live = float(np.sum(np.asarray(args[4])))
    kv_bytes = 2.0 * live * KV * D * item
    if kv_dtype == "int8":
        kv_bytes += 2.0 * live * 4.0  # per-row fp32 scales
    q_bytes = 2.0 * B * KV * G * D * args[0].dtype.itemsize
    return kv_bytes + q_bytes


def _kv_vmem(cfg: Dict[str, Any], args: Tuple, dtype_bytes: int) -> float:
    """One pool block of K+V at the storage dtype, widened tile + q + acc
    at fp32 (dequant happens in VMEM, so both copies are resident)."""
    _, _, G, D, bs = _kv_dims(args)
    item = KV_DTYPE_ITEMSIZE[cfg["kv_dtype"]]
    return float(2 * bs * D * (item + 4) + 2 * G * D * 4)


def _kv_flops(args: Tuple) -> float:
    import numpy as np

    _, KV, G, D, _ = _kv_dims(args)
    live = float(np.sum(np.asarray(args[4])))
    return 4.0 * KV * G * D * live


def paged_kv_space() -> TuningSpace:
    """The ``kv_dtype`` axis of the paged serve path (quantized paging).

    Candidates are ordered widest-first so ``subset(1)`` (the CI tiny-space
    knob) keeps the exact f32 baseline.  ``dtypes`` is deliberately empty:
    the axis is a static serve-path argument, not an operand cast."""
    return TuningSpace(
        kernel="paged-kv",
        axes={"kv_dtype": ("f32", "bf16", "int8")},
        default={"kv_dtype": "f32"},
        dtypes=(),
        vmem_model=_kv_vmem,
        traffic_model=_kv_traffic,
        flops_model=_kv_flops,
    )
