"""Chunked-prefill contract, kernel to engine.

Three layers, one invariant — chunking changes SCHEDULING, not results:

* kernel: ``flash_prefill_paged`` (causal online-softmax over a chunk,
  committing K/V through the paged block tables) matches the dense
  ``prefill_paged_ref`` oracle, commits pools bit-exactly, and ignores
  stale bytes past the chunk frontier (predication, Eq. 1).
* model, two paths.  ``prefill_step_paged`` is a scan over the SAME
  per-token cell as ``decode_step_paged``, so a C-token chunk produces
  bit-identical logits AND bit-identical paged-cache bytes to C
  single-token steps — across every serve architecture (dense, GQA, MLA,
  MoE, SSM, hybrid).  ``prefill_chunk_paged``, the one-pass path the
  engine picks for dense-attention configs, sums in other orders: its
  logits and committed rows agree with the scan within ``CHUNK_TOL`` (f32
  reassociation, justified there), and it leaves every other block's
  bytes untouched.
* engine: chunked serving emits the same token streams as the
  token-by-token scheduler in strictly fewer fused steps (byte-identical
  by construction on the scan path), and the deterministic step-clock
  TTFT p95 drops on a bimodal prompt mix.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.kernels.flash_decode import kernel as fdk, ref as fdr
from repro.models import transformer
from repro.serve.engine import Request, ServeEngine
from repro.train import steps as steps_mod

SERVE_ARCHS = (
    "gpt2-124m", "qwen3-1.7b", "mamba2-370m", "deepseek-v2-lite-16b",
    "deepseek-moe-16b", "jamba-1.5-large-398b",
)

_MODELS = {}


def _model(arch):
    if arch not in _MODELS:
        cfg = configs.get_smoke_config(arch)
        _MODELS[arch] = (cfg, steps_mod.init_model(jax.random.PRNGKey(0), cfg))
    return _MODELS[arch]


# ---------------------------------------------------------------------------
# kernel: flash_prefill_paged vs the dense paged oracle
# ---------------------------------------------------------------------------


def _prefill_setup(B, C, KV, D, bs, nb, seed=0):
    """Random pools + shuffled block tables + a chunk at a ragged offset."""
    n_blocks = 1 + B * nb
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    k_pool = jax.random.normal(ks[0], (n_blocks, bs, KV, D), jnp.float32)
    v_pool = jax.random.normal(ks[1], (n_blocks, bs, KV, D), jnp.float32)
    perm = np.random.default_rng(seed).permutation(np.arange(1, n_blocks))
    bt = jnp.asarray(perm[: B * nb].reshape(B, nb).astype(np.int32))
    k_new = jax.random.normal(ks[2], (B, C, KV, D), jnp.float32)
    v_new = jax.random.normal(ks[3], (B, C, KV, D), jnp.float32)
    # ragged, unaligned starts; the chunk must fit inside the slot view
    starts = np.random.default_rng(seed + 1).integers(0, nb * bs - C + 1, B)
    q_start = jnp.asarray(starts.astype(np.int32))
    return k_pool, v_pool, bt, k_new, v_new, q_start, ks[4]


FP_CASES = [
    # B, C, KV, G, D, bs, nb, block_c, block_s
    (1, 8, 1, 1, 16, 8, 4, 8, 0),
    (2, 8, 2, 2, 16, 8, 6, 4, 8),
    (2, 16, 2, 3, 32, 16, 3, 8, 8),
    (3, 4, 1, 2, 16, 4, 8, 2, 4),
]


@pytest.mark.parametrize("B,C,KV,G,D,bs,nb,bc,bks", FP_CASES)
def test_flash_prefill_paged_matches_ref(B, C, KV, G, D, bs, nb, bc, bks):
    k_pool, v_pool, bt, k_new, v_new, q_start, kq = _prefill_setup(
        B, C, KV, D, bs, nb)
    q = jax.random.normal(kq, (B, C, KV, G, D), jnp.float32)
    q_len = jax.random.randint(jax.random.PRNGKey(9), (B,), 1, C + 1)
    out, kp2, vp2 = fdk.flash_prefill_paged(
        q, k_new, v_new, k_pool, v_pool, bt, q_start, q_len,
        block_c=bc, block_s=bks)
    ref, kr2, vr2 = fdr.prefill_paged_ref(
        q, k_new, v_new, k_pool, v_pool, bt, q_start, q_len)
    # output rows at or past q_len are undefined by contract
    for b in range(B):
        n = int(q_len[b])
        np.testing.assert_allclose(
            np.asarray(out)[b, :n], np.asarray(ref)[b, :n],
            rtol=3e-5, atol=3e-5, err_msg=f"slot {b}")
    # committed pools must match bit-for-bit through the block tables
    np.testing.assert_array_equal(np.asarray(kp2[bt]), np.asarray(kr2[bt]))
    np.testing.assert_array_equal(np.asarray(vp2[bt]), np.asarray(vr2[bt]))


def test_flash_prefill_paged_full_chunk_default():
    """q_len=None commits the whole chunk (the common non-ragged call)."""
    B, C, KV, G, D, bs, nb = 2, 8, 2, 2, 16, 8, 4
    k_pool, v_pool, bt, k_new, v_new, q_start, kq = _prefill_setup(
        B, C, KV, D, bs, nb, seed=3)
    q = jax.random.normal(kq, (B, C, KV, G, D), jnp.float32)
    out1, kp1, vp1 = fdk.flash_prefill_paged(
        q, k_new, v_new, k_pool, v_pool, bt, q_start, block_c=4)
    full = jnp.full((B,), C, jnp.int32)
    out2, kp2, vp2 = fdk.flash_prefill_paged(
        q, k_new, v_new, k_pool, v_pool, bt, q_start, full, block_c=4)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(kp1), np.asarray(kp2))
    np.testing.assert_array_equal(np.asarray(vp1), np.asarray(vp2))


def test_flash_prefill_paged_tile_invariance():
    """block_c / block_s choose tiling, not math: outputs agree across
    tile shapes (the tuning space's correctness precondition)."""
    B, C, KV, G, D, bs, nb = 2, 16, 2, 2, 16, 8, 4
    k_pool, v_pool, bt, k_new, v_new, q_start, kq = _prefill_setup(
        B, C, KV, D, bs, nb, seed=4)
    q = jax.random.normal(kq, (B, C, KV, G, D), jnp.float32)
    q_len = jnp.asarray([11, 16], jnp.int32)
    outs = []
    for bc, bks in ((16, 0), (8, 8), (4, 4), (2, 8)):
        out, kp, vp = fdk.flash_prefill_paged(
            q, k_new, v_new, k_pool, v_pool, bt, q_start, q_len,
            block_c=bc, block_s=bks)
        outs.append((out, kp, vp))
    base_out, base_kp, base_vp = outs[0]
    for out, kp, vp in outs[1:]:
        for b in range(B):
            n = int(q_len[b])
            np.testing.assert_allclose(
                np.asarray(out)[b, :n], np.asarray(base_out)[b, :n],
                rtol=3e-5, atol=3e-5)
        # the commit path is tile-independent bit-for-bit
        np.testing.assert_array_equal(np.asarray(kp), np.asarray(base_kp))
        np.testing.assert_array_equal(np.asarray(vp), np.asarray(base_vp))


def test_flash_prefill_paged_stale_blocks_are_inert():
    """Garbage at positions past the chunk frontier (recycled blocks, a
    previous tenant's tokens) cannot leak into any committed row's
    output — the causal frontier predication at chunk granularity."""
    B, C, KV, G, D, bs, nb = 2, 8, 2, 2, 16, 4, 6
    k_pool, v_pool, bt, k_new, v_new, q_start, kq = _prefill_setup(
        B, C, KV, D, bs, nb, seed=5)
    q = jax.random.normal(kq, (B, C, KV, G, D), jnp.float32)
    q_len = jnp.asarray([5, 8], jnp.int32)
    out1, _, _ = fdk.flash_prefill_paged(
        q, k_new, v_new, k_pool, v_pool, bt, q_start, q_len, block_c=4)
    # poison every pool row at a logical position >= q_start + q_len
    kp, vp = np.asarray(k_pool).copy(), np.asarray(v_pool).copy()
    for b in range(B):
        frontier = int(q_start[b]) + int(q_len[b])
        for j in range(nb):
            for o in range(bs):
                if j * bs + o >= frontier:
                    kp[int(bt[b, j]), o] = 99.0
                    vp[int(bt[b, j]), o] = -99.0
    out2, _, _ = fdk.flash_prefill_paged(
        q, k_new, v_new, jnp.asarray(kp), jnp.asarray(vp), bt, q_start,
        q_len, block_c=4)
    for b in range(B):
        n = int(q_len[b])
        np.testing.assert_allclose(
            np.asarray(out1)[b, :n], np.asarray(out2)[b, :n],
            rtol=1e-6, atol=1e-6)


def test_flash_prefill_paged_preserves_foreign_blocks():
    """Pool blocks belonging to OTHER slots (absent from this call's block
    tables) keep their bytes — the load-bearing invariant that lets the
    engine prefill one slot while its neighbors' caches stay live."""
    B, C, KV, G, D, bs, nb = 1, 8, 2, 2, 16, 8, 2
    n_blocks = 1 + 6  # more blocks than the single slot references
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    k_pool = jax.random.normal(ks[0], (n_blocks, bs, KV, D), jnp.float32)
    v_pool = jax.random.normal(ks[1], (n_blocks, bs, KV, D), jnp.float32)
    bt = jnp.asarray([[2, 5]], jnp.int32)  # blocks 1, 3, 4, 6 are foreign
    k_new = jax.random.normal(ks[2], (B, C, KV, D), jnp.float32)
    v_new = jax.random.normal(ks[3], (B, C, KV, D), jnp.float32)
    q = jax.random.normal(ks[4], (B, C, KV, G, D), jnp.float32)
    q_start = jnp.asarray([4], jnp.int32)
    _, kp2, vp2 = fdk.flash_prefill_paged(
        q, k_new, v_new, k_pool, v_pool, bt, q_start, block_c=4)
    for blk in (0, 1, 3, 4, 6):
        np.testing.assert_array_equal(
            np.asarray(kp2)[blk], np.asarray(k_pool)[blk], err_msg=f"k {blk}")
        np.testing.assert_array_equal(
            np.asarray(vp2)[blk], np.asarray(v_pool)[blk], err_msg=f"v {blk}")


def test_flash_prefill_registry_op_matches_ref():
    """The registry-managed op surface serves the same math as the oracle
    (tuned-kwarg resolution included)."""
    from repro.kernels.flash_decode import ops

    B, C, KV, G, D, bs, nb = 2, 8, 2, 2, 16, 8, 4
    k_pool, v_pool, bt, k_new, v_new, q_start, kq = _prefill_setup(
        B, C, KV, D, bs, nb, seed=7)
    q = jax.random.normal(kq, (B, C, KV, G, D), jnp.float32)
    out, kp, vp = ops.flash_prefill.interpret(
        q, k_new, v_new, k_pool, v_pool, bt, q_start)
    ref, kr, vr = ops.flash_prefill.ref(
        q, k_new, v_new, k_pool, v_pool, bt, q_start)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_array_equal(np.asarray(kp[bt]), np.asarray(kr[bt]))
    np.testing.assert_array_equal(np.asarray(vp[bt]), np.asarray(vr[bt]))


def test_prefill_flops_bytes_model():
    fb = fdr.prefill_flops_bytes(2, 8, 2, 2, 16, q_start=[16, 0])
    # live key-reads: q_start*C + C(C+1)/2 per slot
    live = (16 * 8 + 36) + (0 * 8 + 36)
    assert fb["flops"] == 4.0 * 2 * 2 * 16 * live
    assert fb["bytes"] == 2.0 * 2 * 16 * 2 * (live + 2 * 8)
    assert fb["ai"] > 0


# ---------------------------------------------------------------------------
# model: prefill_step_paged == a chain of single-token steps, bit for bit
# ---------------------------------------------------------------------------


def _assert_caches_bit_equal(c1, c2, msg=""):
    """Paged caches equal everywhere a request can read: every non-null
    pool block (block 0 is the garbage null block) and all dense state."""
    for slot, d1 in c1["blocks"].items():
        for k, leaf in d1.items():
            a, b = np.asarray(leaf), np.asarray(c2["blocks"][slot][k])
            if k in ("k", "v", "c", "k_rope"):
                np.testing.assert_array_equal(
                    a[:, 1:], b[:, 1:], err_msg=f"{msg}{slot}/{k}")
            else:
                np.testing.assert_array_equal(
                    a, b, err_msg=f"{msg}{slot}/{k}")
    if "first_block" in c1:
        for k, leaf in c1["first_block"].items():
            np.testing.assert_array_equal(
                np.asarray(leaf)[1:], np.asarray(c2["first_block"][k])[1:],
                err_msg=f"{msg}first_block/{k}")


def _fresh_paged(cfg, B, max_len, bs):
    cache = transformer.init_paged_cache(cfg, B, max_len, bs)
    nb = max_len // bs
    bt = np.arange(1, 1 + B * nb, dtype=np.int32).reshape(B, nb)
    return cache, jnp.asarray(bt)


def test_decode_step_is_the_chunk1_prefill_cell():
    """decode_step_paged must be bitwise the C=1 cell of prefill_step_paged
    (the refactor that makes chunked serving golden by construction)."""
    cfg, params = _model("gpt2-124m")
    B, max_len, bs = 2, 32, 8
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, 1)).astype(np.int32))
    pos = jnp.zeros((B,), jnp.int32)
    cache_d, bt = _fresh_paged(cfg, B, max_len, bs)
    cache_p, _ = _fresh_paged(cfg, B, max_len, bs)
    logits_d, cache_d = transformer.decode_step_paged(
        params, cfg, tokens, cache_d, pos, bt, block_size=bs)
    logits_p, cache_p = transformer.prefill_step_paged(
        params, cfg, tokens, cache_p, pos, bt, jnp.ones((B,), jnp.int32),
        block_size=bs)
    np.testing.assert_array_equal(np.asarray(logits_d),
                                  np.asarray(logits_p))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), cache_d, cache_p)


@pytest.mark.parametrize("arch", SERVE_ARCHS)
def test_prefill_chunk_bit_equals_token_chain(arch):
    """One C=7 chunked call == seven C=1 calls with the same per-slot
    active schedule: bit-identical last-prompt-token logits AND
    bit-identical cache bytes (pools, SSM state) on every architecture."""
    cfg, params = _model(arch)
    B, max_len, bs, C = 2, 32, 8, 7
    plen = np.array([7, 4], np.int32)  # ragged: slot 1 goes inactive early
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, cfg.vocab, (B, C)).astype(np.int32)
    pos0 = jnp.zeros((B,), jnp.int32)

    cache_c, bt = _fresh_paged(cfg, B, max_len, bs)
    logits_c, cache_c = transformer.prefill_step_paged(
        params, cfg, jnp.asarray(prompts), cache_c, pos0, bt,
        jnp.asarray(plen), block_size=bs)

    cache_t, _ = _fresh_paged(cfg, B, max_len, bs)
    last = {}
    for c in range(C):
        lens = (c < plen).astype(np.int32)  # (B,) active mask: 1 or 0
        logits_t, cache_t = transformer.prefill_step_paged(
            params, cfg, jnp.asarray(prompts[:, c:c + 1]), cache_t,
            pos0 + c, bt, jnp.asarray(lens), block_size=bs)
        for b in range(B):
            if c == plen[b] - 1:
                last[b] = np.asarray(logits_t)[b, 0]

    for b in range(B):
        np.testing.assert_array_equal(
            np.asarray(logits_c)[b, plen[b] - 1], last[b],
            err_msg=f"{arch} slot {b} logits")
    _assert_caches_bit_equal(cache_c, cache_t, msg=f"{arch} ")


# ---------------------------------------------------------------------------
# model: prefill_chunk_paged == prefill_step_paged, to float tolerance
# ---------------------------------------------------------------------------

#: Both paths run the same float32 operations on the same inputs but sum
#: them in other orders: batched matmuls block their reductions otherwise,
#: and the chunk merges each softmax over (cache, chunk) where the scan
#: merges it over (cache + earlier rows, the row itself).  Each sum's
#: rounding is about eps * sqrt(n) relative (eps 1.2e-7, n <= 128 terms at
#: the smoke widths): about 1e-6 on these O(1) logits (measured up to
#: 2.1e-6 over four seeds of every case).  5e-5 leaves a twenty-fold
#: margin.
CHUNK_TOL = dict(rtol=5e-5, atol=5e-5)

#: With a bf16 or int8 pool, a key or value element that the two paths
#: compute 1e-7 apart can sit on a rounding boundary and be stored one
#: step apart (2**-8 relative in bf16, 1/127 of its row's largest in
#: int8); later rows of the chunk read it back, which moves their logits
#: by about that step times the element's attention weight (measured up
#: to 1.0e-4 for bf16 and 2.8e-4 for int8 over four seeds of every case).
#: 2e-3 leaves a seven-fold margin; a rope position off by one or a
#: cache mask one row too wide moves them by more (3e-3 to 0.09 here).
STORED_TOL = dict(rtol=2e-3, atol=2e-3)

#: (positions before the chunk, lengths) for B = 3 slots, block size 8,
#: chunk width 8: ragged lengths beside an idle slot on a fresh cache; a
#: second chunk of each prompt, crossing a block boundary; and one
#: prefill slot beside two 1-token decode slots
CHUNK_CASES = {
    "ragged": ((0, 0, 0), (8, 3, 0)),
    "second-chunk": ((8, 13, 6), (8, 5, 2)),
    "mixed-decode": ((8, 13, 5), (1, 1, 8)),
}


def _touched_blocks(bt, pos, lens, bs):
    return {int(bt[b, (pos[b] + c) // bs])
            for b in range(len(pos)) for c in range(lens[b])}


def _assert_pool_close(a, b, key, kv_dtype, msg):
    """Committed rows agree: f32 pools to CHUNK_TOL; a bf16 pool may round
    a value near a rounding boundary one bf16 step (at most 2**-7 of it)
    the other way, an int8 pool one quantum; scales are f32."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if key.endswith("_scale") or kv_dtype == "f32":
        np.testing.assert_allclose(a, b, err_msg=msg, **CHUNK_TOL)
    elif kv_dtype == "bf16":
        np.testing.assert_allclose(a, b, rtol=2**-7, atol=1e-6, err_msg=msg)
    else:
        np.testing.assert_array_less(np.abs(a - b), 1.5, err_msg=msg)


@pytest.mark.parametrize("case", sorted(CHUNK_CASES))
@pytest.mark.parametrize("kv_dtype", transformer.KV_DTYPES)
@pytest.mark.parametrize("arch", ["gpt2-124m", "qwen3-1.7b"])
def test_prefill_chunk_paged_matches_scan(arch, kv_dtype, case):
    cfg, params = _model(arch)
    B, max_len, bs, C = 3, 64, 8, 8
    pos, lens = (np.asarray(v, np.int32) for v in CHUNK_CASES[case])
    rng = np.random.default_rng(21)
    cache = transformer.init_paged_cache(cfg, B, max_len, bs, kv_dtype)
    nb = max_len // bs
    bt = np.arange(1, 1 + B * nb, dtype=np.int32).reshape(B, nb)
    if pos.any():  # the prompts' history, committed by the scan
        hist = rng.integers(0, cfg.vocab, (B, 16)).astype(np.int32)
        _, cache = transformer.prefill_step_paged(
            params, cfg, jnp.asarray(hist), cache, jnp.zeros((B,), jnp.int32),
            jnp.asarray(bt), jnp.asarray(pos), block_size=bs,
            kv_dtype=kv_dtype)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, C)).astype(np.int32))
    args = (params, cfg, tokens, cache, jnp.asarray(pos), jnp.asarray(bt),
            jnp.asarray(lens))
    kw = dict(block_size=bs, kv_dtype=kv_dtype)
    logits_s, cache_s = transformer.prefill_step_paged(*args, **kw)
    logits_c, cache_c = transformer.prefill_chunk_paged(*args, **kw)

    assert logits_c.shape == logits_s.shape
    assert logits_c.dtype == jnp.float32
    tol = CHUNK_TOL if kv_dtype == "f32" else STORED_TOL
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(logits_c)[b, :lens[b]],
            np.asarray(logits_s)[b, :lens[b]],
            err_msg=f"slot {b} logits", **tol)
    touched = _touched_blocks(bt, pos, lens, bs)
    untouched = [k for k in range(1, 1 + B * nb) if k not in touched]
    assert jax.tree.structure(cache_c) == jax.tree.structure(cache_s)
    for slot, leaves in cache_c["blocks"].items():
        for key, leaf in leaves.items():
            msg = f"{slot}/{key}"
            _assert_pool_close(leaf[:, 1:], cache_s["blocks"][slot][key][:, 1:],
                               key, kv_dtype, msg)
            np.testing.assert_array_equal(
                np.asarray(leaf)[:, untouched],
                np.asarray(cache["blocks"][slot][key])[:, untouched],
                err_msg=f"{msg}: a block outside the chunk changed")


@pytest.mark.parametrize("arch", SERVE_ARCHS)
def test_engine_picks_chunk_path_for_dense_attention(monkeypatch, arch):
    """``_prefill_step_fn`` runs the one-pass chunk on dense-attention
    configs and keeps the scan for MoE, MLA, SSM and hybrid ones, and
    ``stats()['prefill_path']`` says which."""
    from repro.serve import engine as engine_mod

    expected = "chunk" if arch in ("gpt2-124m", "qwen3-1.7b") else "scan"
    cfg, params = _model(arch)
    called = []
    for name, path in (("prefill_chunk_paged", "chunk"),
                       ("prefill_step_paged", "scan")):
        real = getattr(transformer, name)

        def record(*a, _real=real, _path=path, **kw):
            called.append(_path)
            return _real(*a, **kw)

        monkeypatch.setattr(transformer, name, record)
    B, max_len, bs = 2, 32, 8
    cache, bt = _fresh_paged(cfg, B, max_len, bs)
    step = engine_mod._prefill_step_fn(cfg, bs, "f32")
    logits, _ = step(params, jnp.zeros((B, 4), jnp.int32), cache,
                     jnp.zeros((B,), jnp.int32), bt,
                     jnp.asarray([4, 2], jnp.int32))
    assert called == [expected]
    assert logits.shape == (B, 4, cfg.vocab_padded)
    eng = ServeEngine(cfg, params, max_batch=B, max_len=max_len,
                      block_size=bs, prefill_chunk=4)
    assert eng.stats()["prefill_path"] == expected


# ---------------------------------------------------------------------------
# engine: chunked serving is golden vs token-by-token
# ---------------------------------------------------------------------------


def _run_engine(arch, prompts, max_new, *, chunk=1, budget=None,
                max_batch=2, max_len=64, block_size=8, eos=()):
    cfg, params = _model(arch)
    eng = ServeEngine(cfg, params, max_batch=max_batch, max_len=max_len,
                      scheduler="continuous", block_size=block_size,
                      prefill_chunk=chunk, prefill_budget=budget)
    for uid, p in enumerate(prompts):
        eng.submit(Request(uid=uid, prompt=np.asarray(p, np.int32),
                           max_new_tokens=max_new,
                           eos_id=eos[uid] if eos else -1))
    eng.run_until_drained()
    return eng


@pytest.mark.parametrize("arch", SERVE_ARCHS)
def test_engine_chunked_matches_token_by_token(arch):
    """Across every serve architecture: identical streams, strictly fewer
    fused steps under chunked prefill on ragged prompts."""
    cfg, _ = _model(arch)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, size=int(n)).astype(np.int32)
               for n in (19, 4, 11, 26)]
    base = _run_engine(arch, prompts, 4)
    chunked = _run_engine(arch, prompts, 4, chunk=8, budget=8)
    for uid in range(len(prompts)):
        assert chunked.completed[uid].generated == \
            base.completed[uid].generated, f"{arch} req {uid}"
    assert chunked.steps < base.steps, (arch, chunked.steps, base.steps)


def test_engine_chunk_sweep_identical_streams():
    """Chunk widths 1 / ragged non-divisor / full-prompt: byte-identical
    streams, fused steps non-increasing in chunk width (strictly fewer
    than token-by-token for every C > 1)."""
    cfg, _ = _model("gpt2-124m")
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (13, 5, 29, 8)]
    runs = {c: _run_engine("gpt2-124m", prompts, 5, chunk=c)
            for c in (1, 3, 7, 32)}
    base = runs[1]
    steps = [runs[c].steps for c in (1, 3, 7, 32)]
    for c, eng in runs.items():
        for uid in range(len(prompts)):
            assert eng.completed[uid].generated == \
                base.completed[uid].generated, (c, uid)
        if c > 1:
            assert eng.steps < base.steps, (c, eng.steps, base.steps)
    assert steps == sorted(steps, reverse=True), steps


def test_engine_chunked_ttft_win_on_bimodal_mix():
    """The disaggregation headline on a bimodal prompt mix (short decode
    traffic + long prompts): deterministic step-clock TTFT p95 strictly
    drops, streams stay byte-identical, EOS still honored."""
    cfg, _ = _model("gpt2-124m")
    rng = np.random.default_rng(13)
    lens = (48, 4, 48, 4, 4, 48)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in lens]
    base = _run_engine("gpt2-124m", prompts, 4)
    chunked = _run_engine("gpt2-124m", prompts, 4, chunk=16, budget=16)
    for uid in range(len(prompts)):
        assert chunked.completed[uid].generated == \
            base.completed[uid].generated, uid
    bs_, cs_ = base.stats(), chunked.stats()
    assert cs_["ttft_p95_steps"] < bs_["ttft_p95_steps"], (cs_, bs_)
    assert cs_["ttft_p50_steps"] < bs_["ttft_p50_steps"], (cs_, bs_)
    assert chunked.steps < base.steps
    # the stats schema the ledger ingests carries the prefill config
    assert cs_["prefill_chunk"] == 16
    assert bs_["prefill_chunk"] == 1


def test_engine_chunked_respects_eos():
    """Early EOS fires on the same token under chunked prefill (the argmax
    only ever runs on a slot's frontier row)."""
    cfg, _ = _model("gpt2-124m")
    rng = np.random.default_rng(14)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (9, 17)]
    probe = _run_engine("gpt2-124m", [prompts[0]], 1, max_batch=1)
    eos0 = probe.completed[0].generated[0]
    base = _run_engine("gpt2-124m", prompts, 6, eos=(eos0, -1))
    chunked = _run_engine("gpt2-124m", prompts, 6, chunk=8, eos=(eos0, -1))
    assert chunked.completed[0].generated == [eos0]
    for uid in range(2):
        assert chunked.completed[uid].generated == \
            base.completed[uid].generated
