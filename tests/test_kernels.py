"""Per-kernel shape/dtype sweeps vs pure-jnp/numpy oracles (interpret mode).

Every Pallas kernel in src/repro/kernels is asserted allclose against its
ref.py for a sweep of shapes, dtypes, and tilings — the assignment's
kernel-validation contract.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_decode import kernel as fdk, ref as fdr
from repro.kernels.gemm import kernel as gk, ops as gops, ref as gr
from repro.kernels.jacobi2d import kernel as jk, ops as jops, ref as jr
from repro.kernels.qc_gate import kernel as qk, ops as qops, ref as qr
from repro.kernels.stream import kernel as sk, ops as sops, ref as sr

# ---------------------------------------------------------------------------
# GEMM
# ---------------------------------------------------------------------------

GEMM_CASES = [
    # M, N, K, bm, bn, bk, dtype
    (32, 32, 32, 32, 32, 32, jnp.float32),
    (64, 32, 96, 32, 16, 24, jnp.float32),
    (128, 128, 64, 64, 64, 32, jnp.float32),
    (48, 80, 56, 16, 16, 8, jnp.float32),
    (64, 64, 64, 32, 32, 32, jnp.bfloat16),
    (64, 64, 128, 64, 64, 128, jnp.bfloat16),  # single k step
]


@pytest.mark.parametrize("M,N,K,bm,bn,bk,dtype", GEMM_CASES)
def test_gemm_sweep(M, N, K, bm, bn, bk, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K), dtype)
    y = jax.random.normal(jax.random.PRNGKey(1), (K, N), dtype)
    out = gk.gemm(x, y, bm=bm, bn=bn, bk=bk)
    ref = gr.gemm_ref(x, y)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )
    assert out.dtype == dtype


def test_gemm_tiling_is_invisible():
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 64), jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(3), (64, 64), jnp.float32)
    outs = [np.asarray(gk.gemm(x, y, bm=bm, bn=bn, bk=bk))
            for bm, bn, bk in [(64, 64, 64), (32, 32, 16), (16, 64, 32)]]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, rtol=1e-5, atol=1e-5)


def test_gemm_tile_picker_respects_vmem():
    bm, bn, bk = gops.pick_tiles(4096, 4096, 4096, vmem_budget=4 * 2**20)
    assert gops.vmem_bytes(bm, bn, bk) <= 4 * 2**20
    assert bm % 128 == 0 and bn % 128 == 0 and bk % 128 == 0


def test_gemm_ai_grows_with_size():
    small = gr.flops_bytes(128, 128, 128)
    big = gr.flops_bytes(4096, 4096, 4096)
    assert big["ai"] > 10 * small["ai"]


# ---------------------------------------------------------------------------
# STREAM
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
@pytest.mark.parametrize("rows,br", [(64, 16), (64, 64), (256, 32)])
def test_stream_sweep(dtype, rows, br):
    a = jax.random.normal(jax.random.PRNGKey(0), (rows, 128), dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (rows, 128), dtype)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    for name, got, want in [
        ("copy", sk.stream_copy(a, block_rows=br), sr.copy_ref(a)),
        ("scale", sk.stream_scale(a, 2.5, block_rows=br), sr.scale_ref(a, 2.5)),
        ("add", sk.stream_add(a, b, block_rows=br), sr.add_ref(a, b)),
        ("triad", sk.stream_triad(a, b, 3.0, block_rows=br), sr.triad_ref(a, b, 3.0)),
    ]:
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            err_msg=name, **tol,
        )


def test_stream_elen_issue_model():
    """Paper Sec. 4.2 (GCC column): R_ins 2x/4x/8x for fp64->fp16 at VLEN=128."""
    n = 1 << 20
    assert sops.issue_counts(n, 64)["r_ins"] == pytest.approx(2.0)
    assert sops.issue_counts(n, 32)["r_ins"] == pytest.approx(4.0)
    assert sops.issue_counts(n, 16)["r_ins"] == pytest.approx(8.0)


# ---------------------------------------------------------------------------
# Jacobi2D
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("H,W,br", [(32, 128, 8), (32, 128, 32), (64, 256, 16),
                                    (16, 128, 4)])
def test_jacobi_sweep(H, W, br):
    u = jax.random.normal(jax.random.PRNGKey(2), (H, W), jnp.float32)
    out = jk.jacobi_step(u, block_rows=br)
    ref = jr.jacobi_ref(u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5, atol=1e-5)


def test_jacobi_boundary_passthrough():
    u = jax.random.normal(jax.random.PRNGKey(3), (16, 128), jnp.float32)
    out = np.asarray(jk.jacobi_step(u, block_rows=8))
    np.testing.assert_array_equal(out[0], np.asarray(u[0]))
    np.testing.assert_array_equal(out[-1], np.asarray(u[-1]))
    np.testing.assert_array_equal(out[:, 0], np.asarray(u[:, 0]))
    np.testing.assert_array_equal(out[:, -1], np.asarray(u[:, -1]))


def test_jacobi_multi_sweep_converges():
    """Repeated sweeps smooth toward the boundary-harmonic solution."""
    u = jnp.zeros((16, 128), jnp.float32).at[8, 64].set(100.0)
    out = jops.jacobi(u, sweeps=50, block_rows=8)
    assert float(jnp.max(jnp.abs(out[1:-1, 1:-1]))) < 100.0
    assert np.all(np.isfinite(np.asarray(out)))


def test_jacobi_is_memory_bound_in_model():
    from repro.core import hw
    from repro.core.roofline import adapted_roofline

    fb = jr.flops_bytes(4096, 4096, dtype_bytes=8)
    rl = adapted_roofline(hw.GRACE_CORE, "fp64")
    assert fb["ai"] < rl.ai_irr  # left of the scalar knee: Class 2 territory


# ---------------------------------------------------------------------------
# flash-decode
# ---------------------------------------------------------------------------

FD_CASES = [
    # B, KV, G, D, S, bs
    (1, 1, 1, 16, 32, 8),
    (2, 2, 3, 16, 64, 16),
    (2, 4, 2, 32, 128, 32),
    (3, 2, 4, 16, 64, 64),  # single block
]


@pytest.mark.parametrize("B,KV,G,D,S,bs", FD_CASES)
def test_flash_decode_sweep(B, KV, G, D, S, bs):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, KV, G, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
    valid = jax.random.randint(ks[3], (B,), 1, S + 1)
    out = fdk.flash_decode(q, k, v, valid, block_s=bs)
    ref = fdr.decode_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5)


def test_flash_decode_masked_tail_is_inert():
    B, KV, G, D, S = 1, 2, 2, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, KV, G, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
    valid = jnp.asarray([40], jnp.int32)
    out1 = fdk.flash_decode(q, k, v, valid, block_s=16)
    out2 = fdk.flash_decode(
        q, k.at[:, 40:].set(99.0), v.at[:, 40:].set(-99.0), valid, block_s=16
    )
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6, atol=1e-6)


def test_flash_decode_issue_model():
    c = fdr.issue_counts([100, 512, 30], S=512, block_s=64)
    assert c["predicated"] == 2 + 8 + 1
    assert c["fixed"] == 3 * 8
    assert c["r_issue"] > 2.0


def _paged_setup(B, KV, D, bs, nb, seed=0):
    """Random pool + shuffled non-contiguous block tables (block 0 = null)."""
    n_blocks = 1 + B * nb
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    k_pool = jax.random.normal(ks[0], (n_blocks, bs, KV, D), jnp.float32)
    v_pool = jax.random.normal(ks[1], (n_blocks, bs, KV, D), jnp.float32)
    perm = np.random.default_rng(seed).permutation(np.arange(1, n_blocks))
    bt = jnp.asarray(perm[: B * nb].reshape(B, nb).astype(np.int32))
    return k_pool, v_pool, bt, ks[2]


@pytest.mark.parametrize("B,KV,G,D,bs,nb", [
    (1, 1, 1, 16, 8, 4),
    (3, 2, 2, 16, 4, 6),
    (2, 4, 2, 32, 16, 2),
])
def test_flash_decode_paged_matches_ref(B, KV, G, D, bs, nb):
    k_pool, v_pool, bt, kq = _paged_setup(B, KV, D, bs, nb)
    q = jax.random.normal(kq, (B, KV, G, D), jnp.float32)
    valid = jax.random.randint(jax.random.PRNGKey(7), (B,), 1, nb * bs + 1)
    out = fdk.flash_decode_paged(q, k_pool, v_pool, bt, valid)
    ref = fdr.decode_paged_ref(q, k_pool, v_pool, bt, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_flash_decode_paged_matches_contiguous():
    """A paged cache with an identity block table must reproduce the
    contiguous kernel bit-for-bit: paging changes placement, not math."""
    B, KV, G, D, bs, nb = 2, 2, 2, 16, 8, 4
    S = nb * bs
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (B, KV, G, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
    valid = jnp.asarray([13, 27], jnp.int32)
    # lay each sequence's blocks out contiguously after the null block
    k_pool = jnp.concatenate(
        [jnp.zeros((1, bs, KV, D), jnp.float32),
         k.reshape(B * nb, bs, KV, D)])
    v_pool = jnp.concatenate(
        [jnp.zeros((1, bs, KV, D), jnp.float32),
         v.reshape(B * nb, bs, KV, D)])
    bt = jnp.arange(1, 1 + B * nb, dtype=jnp.int32).reshape(B, nb)
    paged = fdk.flash_decode_paged(q, k_pool, v_pool, bt, valid)
    dense = fdk.flash_decode(q, k, v, valid, block_s=bs)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               rtol=1e-6, atol=1e-6)


def test_flash_decode_paged_stale_blocks_are_inert():
    """Garbage in recycled / never-allocated blocks past valid_len cannot
    leak into the output — per-slot length predication in the kernel."""
    B, KV, G, D, bs, nb = 2, 2, 2, 16, 4, 4
    k_pool, v_pool, bt, kq = _paged_setup(B, KV, D, bs, nb, seed=5)
    q = jax.random.normal(kq, (B, KV, G, D), jnp.float32)
    valid = jnp.asarray([6, 11], jnp.int32)
    out1 = fdk.flash_decode_paged(q, k_pool, v_pool, bt, valid)
    # poison every pool row belonging to a logical position >= valid
    kp, vp = np.asarray(k_pool).copy(), np.asarray(v_pool).copy()
    for b in range(B):
        for j in range(nb):
            for o in range(bs):
                if j * bs + o >= int(valid[b]):
                    kp[int(bt[b, j]), o] = 99.0
                    vp[int(bt[b, j]), o] = -99.0
    out2 = fdk.flash_decode_paged(
        q, jnp.asarray(kp), jnp.asarray(vp), bt, valid)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# quantized paging (the ELEN axis): int8 / bf16 pools vs the f32 oracle
# ---------------------------------------------------------------------------


def _quantized_paged_setup(B, KV, D, bs, nb, seed=0):
    """f32 pools + their per-row int8 quantization (kernel commit formula)."""
    k_pool, v_pool, bt, kq = _paged_setup(B, KV, D, bs, nb, seed=seed)
    kq8, ks = fdr.quantize_rows(k_pool)
    vq8, vs = fdr.quantize_rows(v_pool)
    return k_pool, v_pool, kq8, vq8, ks, vs, bt, kq


@pytest.mark.parametrize("B,KV,G,D,bs,nb", [
    (1, 1, 1, 16, 8, 4),
    (3, 2, 2, 16, 4, 6),
    (2, 4, 2, 32, 16, 2),
])
def test_flash_decode_paged_int8_matches_ref(B, KV, G, D, bs, nb):
    """Kernel-side per-tile dequant == whole-array ref dequant (tight),
    and both stay within quantization error of the f32 pools (loose)."""
    k_pool, v_pool, kq8, vq8, ks, vs, bt, kq = _quantized_paged_setup(
        B, KV, D, bs, nb)
    q = jax.random.normal(kq, (B, KV, G, D), jnp.float32)
    valid = jax.random.randint(jax.random.PRNGKey(7), (B,), 1, nb * bs + 1)
    out = fdk.flash_decode_paged(q, kq8, vq8, bt, valid,
                                 k_scale=ks, v_scale=vs)
    ref = fdr.decode_paged_ref(q, kq8, vq8, bt, valid,
                               k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
    f32 = fdr.decode_paged_ref(q, k_pool, v_pool, bt, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(f32), atol=0.08)


def test_flash_decode_paged_bf16_matches_ref():
    """bf16 pools (no scales) widen in VMEM; output within bf16 error of
    the f32 oracle."""
    B, KV, G, D, bs, nb = 2, 2, 2, 16, 8, 4
    k_pool, v_pool, bt, kq = _paged_setup(B, KV, D, bs, nb, seed=2)
    q = jax.random.normal(kq, (B, KV, G, D), jnp.float32)
    valid = jnp.asarray([9, 25], jnp.int32)
    out = fdk.flash_decode_paged(q, k_pool.astype(jnp.bfloat16),
                                 v_pool.astype(jnp.bfloat16), bt, valid)
    f32 = fdr.decode_paged_ref(q, k_pool, v_pool, bt, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(f32), atol=0.03)


def test_flash_decode_paged_int8_rejects_lone_scale():
    k_pool, v_pool, kq8, vq8, ks, vs, bt, kq = _quantized_paged_setup(
        1, 1, 16, 8, 4)
    q = jax.random.normal(kq, (1, 1, 1, 16), jnp.float32)
    valid = jnp.asarray([5], jnp.int32)
    with pytest.raises(ValueError):
        fdk.flash_decode_paged(q, kq8, vq8, bt, valid, k_scale=ks)


def test_flash_decode_paged_int8_stale_blocks_are_inert():
    """The f32 stale-block sweep, on quantized pools: poisoning int8 rows
    AND their scales past valid_len cannot change the output — length
    predication must mask before dequantization, not after."""
    B, KV, G, D, bs, nb = 2, 2, 2, 16, 4, 4
    _, _, kq8, vq8, ks, vs, bt, kq = _quantized_paged_setup(
        B, KV, D, bs, nb, seed=5)
    q = jax.random.normal(kq, (B, KV, G, D), jnp.float32)
    valid = jnp.asarray([6, 11], jnp.int32)
    out1 = fdk.flash_decode_paged(q, kq8, vq8, bt, valid,
                                  k_scale=ks, v_scale=vs)
    kp, vp = np.asarray(kq8).copy(), np.asarray(vq8).copy()
    ksp, vsp = np.asarray(ks).copy(), np.asarray(vs).copy()
    for b in range(B):
        for j in range(nb):
            for o in range(bs):
                if j * bs + o >= int(valid[b]):
                    kp[int(bt[b, j]), o] = 127
                    vp[int(bt[b, j]), o] = -127
                    ksp[int(bt[b, j]), o] = 99.0
                    vsp[int(bt[b, j]), o] = 99.0
    out2 = fdk.flash_decode_paged(
        q, jnp.asarray(kp), jnp.asarray(vp), bt, valid,
        k_scale=jnp.asarray(ksp), v_scale=jnp.asarray(vsp))
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("B,C,KV,G,D,bs,nb,bc,bks", [
    (1, 8, 1, 1, 16, 8, 4, 8, 0),
    (2, 8, 2, 2, 16, 8, 6, 4, 8),
    (3, 4, 1, 2, 16, 4, 8, 2, 4),
])
def test_flash_prefill_paged_int8_matches_ref(B, C, KV, G, D, bs, nb,
                                              bc, bks):
    """Quantized chunked prefill: the commit kernel's int8 rows and
    scales must be BIT-identical to the ref formula (same quantizer), and
    the attended output must match the dequantizing oracle."""
    n_blocks = 1 + B * nb
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    kq8, ks = fdr.quantize_rows(
        jax.random.normal(keys[0], (n_blocks, bs, KV, D), jnp.float32))
    vq8, vs = fdr.quantize_rows(
        jax.random.normal(keys[1], (n_blocks, bs, KV, D), jnp.float32))
    perm = np.random.default_rng(11).permutation(np.arange(1, n_blocks))
    bt = jnp.asarray(perm[: B * nb].reshape(B, nb).astype(np.int32))
    k_new = jax.random.normal(keys[2], (B, C, KV, D), jnp.float32)
    v_new = jax.random.normal(keys[3], (B, C, KV, D), jnp.float32)
    starts = np.random.default_rng(12).integers(0, nb * bs - C + 1, B)
    q_start = jnp.asarray(starts.astype(np.int32))
    q = jax.random.normal(keys[4], (B, C, KV, G, D), jnp.float32)
    q_len = jax.random.randint(jax.random.PRNGKey(9), (B,), 1, C + 1)

    out, kp2, vp2, ks2, vs2 = fdk.flash_prefill_paged(
        q, k_new, v_new, kq8, vq8, bt, q_start, q_len,
        k_scale=ks, v_scale=vs, block_c=bc, block_s=bks)
    rout, rkp, rvp, rks, rvs = fdr.prefill_paged_ref(
        q, k_new, v_new, kq8, vq8, bt, q_start, q_len,
        k_scale=ks, v_scale=vs)
    # compare through the block tables: unreferenced blocks are undefined
    for b in range(B):
        for j in range(nb):
            blk = int(bt[b, j])
            np.testing.assert_array_equal(
                np.asarray(kp2)[blk], np.asarray(rkp)[blk],
                err_msg=f"k block {blk}")
            np.testing.assert_array_equal(
                np.asarray(vp2)[blk], np.asarray(rvp)[blk],
                err_msg=f"v block {blk}")
            np.testing.assert_allclose(
                np.asarray(ks2)[blk], np.asarray(rks)[blk], rtol=1e-6,
                err_msg=f"k scale {blk}")
            np.testing.assert_allclose(
                np.asarray(vs2)[blk], np.asarray(rvs)[blk], rtol=1e-6,
                err_msg=f"v scale {blk}")
    for b in range(B):
        n = int(q_len[b])
        np.testing.assert_allclose(
            np.asarray(out)[b, :n], np.asarray(rout)[b, :n],
            rtol=3e-5, atol=3e-5, err_msg=f"slot {b}")


# ---------------------------------------------------------------------------
# QC RX gate
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_qubits,qubit", [(8, 0), (8, 4), (8, 7), (12, 6)])
def test_rx_gate_sweep(n_qubits, qubit):
    n = 1 << n_qubits
    re = jax.random.normal(jax.random.PRNGKey(4), (n,), jnp.float32)
    im = jax.random.normal(jax.random.PRNGKey(5), (n,), jnp.float32)
    o_re, o_im = qk.rx_gate(re, im, qubit, 1.1, block_outer=2)
    r_re, r_im = qr.rx_ref(re, im, qubit, 1.1)
    np.testing.assert_allclose(np.asarray(o_re), r_re, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(o_im), r_im, rtol=1e-5, atol=1e-5)


def test_rx_preserves_norm():
    """Unitarity: ||psi|| is invariant under RX."""
    re, im = qops.zero_state(10)
    re = jax.random.normal(jax.random.PRNGKey(6), re.shape, jnp.float32)
    im = jax.random.normal(jax.random.PRNGKey(7), im.shape, jnp.float32)
    norm0 = float(jnp.sum(re**2 + im**2))
    o_re, o_im = qops.rx_layer(re, im, n_qubits=10, theta=0.3)
    norm1 = float(jnp.sum(o_re**2 + o_im**2))
    np.testing.assert_allclose(norm0, norm1, rtol=1e-5)


def test_rx_two_pi_is_minus_identity():
    """RX(2pi) = -I (spin-1/2 phase)."""
    import math

    re, im = qops.zero_state(6)
    o_re, o_im = qk.rx_gate(re, im, 3, 2 * math.pi, block_outer=2)
    np.testing.assert_allclose(np.asarray(o_re), -np.asarray(re), atol=1e-5)
    np.testing.assert_allclose(np.asarray(o_im), -np.asarray(im), atol=1e-5)


def test_rx_default_tile_follows_the_inner_axis():
    """The default outer tile shrinks as the target qubit widens the inner
    axis, so the in/out tiles fit VMEM at any qubit; results are unchanged."""
    re = jax.random.normal(jax.random.PRNGKey(8), (1 << 12,), jnp.float32)
    im = jax.random.normal(jax.random.PRNGKey(9), (1 << 12,), jnp.float32)
    for qubit in (0, 9, 11):
        o_re, o_im = qk.rx_gate(re, im, qubit, 0.7)
        r_re, r_im = qr.rx_ref(re, im, qubit, 0.7)
        np.testing.assert_allclose(np.asarray(o_re), r_re, atol=1e-5)
        np.testing.assert_allclose(np.asarray(o_im), r_im, atol=1e-5)


# ---------------------------------------------------------------------------
# Registry call surface: default mode and tuned-config validation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["gemm", "stream-triad", "jacobi2d"])
def test_default_call_and_lower_interpret_on_cpu(name):
    """On the CPU backend the interpreter is the only option, so ``op(...)``
    and ``op.lower(...)`` default to it: the result is the interpret-mode
    one and the lowered program holds no TPU kernel."""
    from repro.kernels import registry
    from repro.tuning.tune import default_mode

    assert jax.default_backend() == "cpu"
    assert registry.default_interpret() and default_mode() == "interpret"
    ops = registry.get_kernel(name)
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    args = {"gemm": (x, x.T), "stream-triad": (x, x, 2.0),
            "jacobi2d": (x,)}[name]
    np.testing.assert_array_equal(np.asarray(ops(*args)),
                                  np.asarray(ops.interpret(*args)))
    assert "tpu_custom_call" not in ops.lower(*args).as_text()


class _RaisingSpace:
    """A tuning space whose validation itself is broken."""

    axes = {"bm": (64,), "bn": (64,), "bk": (64,)}

    def validate(self, config, args, *, extra=None):
        raise RuntimeError("validation bug")


def test_tuned_config_validation_error_propagates(monkeypatch):
    from repro.kernels import registry

    ops = registry.get_kernel("gemm")
    x = jnp.ones((128, 128), jnp.float32)
    try:
        ops.set_tuned({"bm": 64, "bn": 64, "bk": 64}, chip="c", dtype="fp32")
        monkeypatch.setattr(ops, "tuning_space", _RaisingSpace())
        with pytest.raises(RuntimeError, match="validation bug"):
            ops._tuned_kwargs((x, x), {"interpret": True})
        with pytest.raises(RuntimeError, match="validation bug"):
            ops(x, x)
    finally:
        ops.clear_tuned()


def test_tuned_config_that_does_not_fit_falls_back_to_defaults():
    from repro.kernels import registry

    ops = registry.get_kernel("gemm")
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 256), jnp.float32)
    try:
        # 256 is not a multiple of 192: the config does not fit this call
        ops.set_tuned({"bm": 192, "bn": 192, "bk": 192}, chip="c",
                      dtype="fp32")
        assert ops._tuned_kwargs((x, x), {"interpret": True}) == {
            "interpret": True}
        np.testing.assert_allclose(np.asarray(ops(x, x)),
                                   np.asarray(ops.ref(x, x)),
                                   rtol=2e-4, atol=2e-4)
    finally:
        ops.clear_tuned()
