"""Seeded random weights, one leaf at a time, for the program and the reference.

Every leaf is drawn from ``(seed, layer, leaf name)`` alone, so the reference
can draw layer ``l`` by itself and get the very values the program serves,
without taking anything the program made.  The program's parameter tree is
assembled on the device in one jitted call: stacked leaves are filled one
layer at a time inside a scan, so no more than one layer's draw is ever live
beside the result.

Matrices are normal with standard deviation ``1 / sqrt(fan_in)`` (fan-in is
the second-to-last axis; for the embedding table it is ``d_model``, its
fan-in as a tied head, so that tied logits spread as untied ones do); norm
scales are ``1 + 0.1 * normal`` so that a dropped scale shows in the logits.
"""

from __future__ import annotations

import functools
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16
F32 = jnp.float32

#: top-level leaves live on this pseudo-layer index
TOP = 1 << 20


def seed_words(seed: int) -> np.ndarray:
    """A whole-number seed as two uint32 words (seeds may exceed 32 bits)."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def layer_leaves(cfg, layer: int) -> Dict[str, Tuple[tuple, object]]:
    """Leaf name -> (shape, dtype) of global layer ``layer``, named by the
    program's parameter paths under one layer slot."""
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    out = {
        "norm1/scale": ((d,), BF16),
        "norm2/scale": ((d,), BF16),
        "attn/wq/w": ((d, h * hd), BF16),
        "attn/wk/w": ((d, kv * hd), BF16),
        "attn/wv/w": ((d, kv * hd), BF16),
        "attn/wo/w": ((h * hd, d), BF16),
    }
    if cfg.qk_norm:
        out["attn/q_norm/scale"] = ((hd,), BF16)
        out["attn/k_norm/scale"] = ((hd,), BF16)
    if is_moe_layer(cfg, layer):
        m = cfg.moe
        e, f = m.n_routed, m.d_ff_expert
        out.update({
            "moe/router": ((d, e), F32),
            "moe/wi_gate": ((e, d, f), BF16),
            "moe/wi_up": ((e, d, f), BF16),
            "moe/wo": ((e, f, d), BF16),
        })
        if m.n_shared:
            fs = m.n_shared * f
            out.update({
                "moe/shared/wi_gate": ((d, fs), BF16),
                "moe/shared/wi_up": ((d, fs), BF16),
                "moe/shared/wo": ((fs, d), BF16),
            })
    else:
        out.update({
            "ffn/wi_gate": ((d, cfg.d_ff), BF16),
            "ffn/wi_up": ((d, cfg.d_ff), BF16),
            "ffn/wo": ((cfg.d_ff, d), BF16),
        })
    return out


def top_leaves(cfg) -> Dict[str, Tuple[tuple, object]]:
    d, vp = cfg.d_model, cfg.vocab_padded
    out = {"embed/embedding": ((vp, d), BF16), "final_norm/scale": ((d,), BF16)}
    if not cfg.tie_embeddings:
        out["lm_head/w"] = ((d, vp), BF16)
    return out


def is_moe_layer(cfg, layer: int) -> bool:
    m = cfg.moe
    return m is not None and not (m.first_dense and layer == 0)


def _draw(words, layer, name: str, shape, dtype):
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), words[0]),
                             words[1])
    key = jax.random.fold_in(jax.random.fold_in(key, layer),
                             zlib.crc32(name.encode()))
    z = jax.random.normal(key, shape, F32)
    if name.endswith("scale"):
        return (1.0 + 0.1 * z).astype(dtype)
    fan_in = shape[-1] if name == "embed/embedding" else shape[-2]
    return (z * (1.0 / np.sqrt(fan_in))).astype(dtype)


@functools.lru_cache(maxsize=None)
def layer_fn(cfg, moe: bool):
    """jit: (seed words, layer) -> {leaf name: array} for one layer of the
    given kind (the layer index is traced, so one compile serves all)."""
    rep = next(l for l in range(cfg.n_layers) if is_moe_layer(cfg, l) == moe)
    leaves = layer_leaves(cfg, rep)

    def fn(words, layer):
        return {n: _draw(words, layer, n, s, dt) for n, (s, dt) in leaves.items()}

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def top_fn(cfg):
    leaves = top_leaves(cfg)
    return jax.jit(lambda words: {
        n: _draw(words, TOP, n, s, dt) for n, (s, dt) in leaves.items()})


def _nest(flat: Dict[str, jax.Array]) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return out


def _program_params(cfg, words):
    first = 1 if (cfg.moe is not None and cfg.moe.first_dense) else 0
    nsb = cfg.n_layers - first
    flat = {n: _draw(words, TOP, n, s, dt) for n, (s, dt) in top_leaves(cfg).items()}
    params = _nest(flat)
    if first:
        params["first_block"] = _nest({
            n: _draw(words, 0, n, s, dt)
            for n, (s, dt) in layer_leaves(cfg, 0).items()})
    stacked = {}
    for name, (shape, dt) in layer_leaves(cfg, first).items():
        def body(buf, i, name=name, shape=shape, dt=dt):
            return buf.at[i].set(_draw(words, i + first, name, shape, dt)), None
        buf, _ = jax.lax.scan(body, jnp.zeros((nsb,) + shape, dt),
                              jnp.arange(nsb, dtype=jnp.uint32))
        stacked[name] = buf
    params["blocks"] = {"slot0": _nest(stacked)}
    return params


def program_params(cfg, seed: int):
    """The program's whole parameter tree for ``cfg``, made on the device
    in one jitted call from ``seed``."""
    return _program_jit(cfg)(jnp.asarray(seed_words(seed)))


@functools.lru_cache(maxsize=None)
def _program_jit(cfg):
    return jax.jit(lambda words: _program_params(cfg, words))


def check_tree(arch, pcfg) -> None:
    """Fail unless the tree made here has the program's own structure,
    shapes and dtypes (as ``transformer.init_lm`` would build it)."""
    from repro.models import transformer

    want = jax.eval_shape(lambda: transformer.init_lm(jax.random.PRNGKey(0), pcfg))
    got = jax.eval_shape(_program_jit(arch), jnp.zeros(2, jnp.uint32))
    w = {jax.tree_util.keystr(p): (l.shape, l.dtype)
         for p, l in jax.tree_util.tree_leaves_with_path(want)}
    g = {jax.tree_util.keystr(p): (l.shape, l.dtype)
         for p, l in jax.tree_util.tree_leaves_with_path(got)}
    if w != g:
        diff = sorted(set(w.items()) ^ set(g.items()), key=str)[:6]
        raise ValueError(f"{arch.name}: weights differ from the program's "
                         f"parameter tree: {diff}")
