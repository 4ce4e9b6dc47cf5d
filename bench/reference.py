"""Plain float32 forward pass of the served architectures, for ``correct``.

Written from the published layer equations, in straightforward ``jax.numpy``
at ``highest`` matmul precision, with no cache, paging, batching of slots or
kernels; it imports nothing of the program.  Pre-norm decoder layers:
RMSNorm, grouped-query attention with per-head RMSNorm on q and k (Qwen3),
rotary embeddings on the two halves of each head, causal softmax, SwiGLU
feed-forward; in DeepSeekMoE layers the feed-forward is a softmax router
over all routed experts, the top-k experts' SwiGLU outputs weighted by
their gates (renormalised over the k when the configuration says so), plus
the shared experts.  No token is ever dropped: every routed token reaches
its experts.

The weights come from :mod:`bench.weights`, drawn again layer by layer from
the seed, so the pass runs one layer at a time and fits beside nothing else.
``quant="fp8"`` rounds every weight matrix to float8 e4m3 with one scale per
output column: the control, one precision step below the bf16 that the
configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

F32 = jnp.float32


def _fp8(w):
    """Round a weight matrix to e4m3 with one scale per output column."""
    w = w.astype(F32)
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _w(leaves, name, quant):
    w = leaves[name].astype(F32)
    if quant == "fp8" and w.ndim >= 2:
        return _fp8(w)
    return w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (L, H, D) at positions 0..L-1; rotate the two halves of D."""
    L, _, D = x.shape
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(L, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(cfg, lv, h, quant):
    """h: (L, d) normed input of one sequence -> (L, d)."""
    L = h.shape[0]
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ _w(lv, "attn/wq/w", quant)).reshape(L, H, D)
    k = (h @ _w(lv, "attn/wk/w", quant)).reshape(L, KV, D)
    v = (h @ _w(lv, "attn/wv/w", quant)).reshape(L, KV, D)
    if cfg.qk_norm:
        q = _rms(q, lv["attn/q_norm/scale"].astype(F32), cfg.norm_eps)
        k = _rms(k, lv["attn/k_norm/scale"].astype(F32), cfg.norm_eps)
    q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    q = q.reshape(L, KV, H // KV, D)
    s = jnp.einsum("qkgd,skd->kgqs", q, k) / np.sqrt(D)
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", p, v).reshape(L, H * D)
    return o @ _w(lv, "attn/wo/w", quant)


def _swiglu(x, wg, wu, wo):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wo


#: routed experts evaluated together in one pass of the expert loop
_EXPERT_CHUNK = 8


def _moe(cfg, lv, x, quant):
    """x: (T, d) -> (T, d): routed top-k experts plus shared experts."""
    m = cfg.moe
    probs = jax.nn.softmax(x @ lv["moe/router"].astype(F32), axis=-1)
    top, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], idx].set(top)
    wg, wu, wo = (_w(lv, f"moe/{n}", quant) for n in ("wi_gate", "wi_up", "wo"))
    n_chunks = m.n_routed // _EXPERT_CHUNK

    def chunk(y, c):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, c * _EXPERT_CHUNK, _EXPERT_CHUNK, 0)  # noqa: E731
        g = jnp.einsum("td,edf->tef", x, sl(wg))
        u = jnp.einsum("td,edf->tef", x, sl(wu))
        o = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, sl(wo))
        gc = jax.lax.dynamic_slice_in_dim(gates, c * _EXPERT_CHUNK, _EXPERT_CHUNK, 1)
        return y + jnp.einsum("ted,te->td", o, gc), None

    y, _ = jax.lax.scan(chunk, jnp.zeros_like(x), jnp.arange(n_chunks))
    if m.n_shared:
        y = y + _swiglu(x, *(_w(lv, f"moe/shared/{n}", quant)
                            for n in ("wi_gate", "wi_up", "wo")))
    return y


@functools.lru_cache(maxsize=None)
def _layer_jit(cfg, moe: bool, quant: str):
    def layer(lv, x):
        def one(xs):
            h = _rms(xs, lv["norm1/scale"].astype(F32), cfg.norm_eps)
            xs = xs + _attention(cfg, lv, h, quant)
            h = _rms(xs, lv["norm2/scale"].astype(F32), cfg.norm_eps)
            if moe:
                return xs + _moe(cfg, lv, h, quant)
            return xs + _swiglu(h, *(_w(lv, f"ffn/{n}", quant)
                                     for n in ("wi_gate", "wi_up", "wo")))
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(one, x)
    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _embed_jit(cfg, quant: str):
    def embed(top, tokens):
        e = top["embed/embedding"]
        e = _fp8(e.T).T if quant == "fp8" else e.astype(F32)
        return jnp.take(e, tokens, axis=0).astype(F32)
    return jax.jit(embed)


@functools.lru_cache(maxsize=None)
def _head_jit(cfg, quant: str):
    def head(top, x, rows, cols):
        h = _rms(x[rows, cols], top["final_norm/scale"].astype(F32), cfg.norm_eps)
        if cfg.tie_embeddings:
            w = _w(top, "embed/embedding", quant).T
        else:
            w = _w(top, "lm_head/w", quant)
        with jax.default_matmul_precision("highest"):
            return (h @ w)[:, :cfg.vocab]
    return jax.jit(head)


def logits_at(cfg, seed: int, tokens: np.ndarray, rows: np.ndarray,
              cols: np.ndarray, quant: str = "none") -> np.ndarray:
    """Reference logits (P, vocab) at positions ``(rows[i], cols[i])`` of
    ``tokens`` (N, L), each predicting the token that follows it."""
    words = jnp.asarray(W.seed_words(seed))
    top = W.top_fn(cfg)(words)
    x = _embed_jit(cfg, quant)(top, jnp.asarray(tokens, jnp.int32))
    for layer in range(cfg.n_layers):
        moe = W.is_moe_layer(cfg, layer)
        lv = W.layer_fn(cfg, moe)(words, jnp.uint32(layer))
        x = _layer_jit(cfg, moe, quant)(lv, x)
        del lv
    out = _head_jit(cfg, quant)(top, x, jnp.asarray(rows), jnp.asarray(cols))
    return np.asarray(out)
