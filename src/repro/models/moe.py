"""Mixture-of-Experts FFN: shared + fine-grained routed experts (DeepSeekMoE).

Dispatch is GShard-style with a capacity factor: tokens are scattered into an
(E, C, d) expert buffer (position = rank of the token among the expert's
assignments, computed with an exclusive cumsum over the one-hot assignment
matrix), processed with batched expert GEMMs, and gathered back weighted by
the normalized router gates.  Overflow beyond capacity is dropped (standard
for capacity-based MoE).

Two distribution layouts (selected by the active MeshPlan):

* **global** (paper-faithful baseline): one (E, C, d) buffer over the GLOBAL
  token set.  Under pjit the scatter crosses the data sharding of tokens and
  the model sharding of experts, so GSPMD materializes and all-reduces the
  whole buffer — measured 237 TB/step of all-reduce on
  deepseek-moe-16b@train_4k (EXPERIMENTS.md §Perf).

* **hierarchical** (optimized): tokens are first split (Z, T/Z, d) with Z =
  the data-axis size, constrained so dim 0 lies on the data axes; dispatch
  runs per shard (vmapped) into a (Z, E, C_local, d) buffer.  Expert GEMMs
  batch over Z (data-sharded) x E (model-sharded) with a LOCAL contraction —
  the scatter never crosses a sharding boundary, and the only cross-shard
  movement left is the return-path combine (a TP-sized all-reduce).
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import context as mesh_ctx
from repro.models import layers


def init_moe(key, cfg, dtype) -> dict:
    m, d = cfg.moe, cfg.d_model
    k_router, k_routed, k_shared = jax.random.split(key, 3)
    ks = jax.random.split(k_routed, 3)
    e, f = m.n_routed, m.d_ff_expert
    p = {
        "router": layers.truncated_normal(k_router, (d, e), 1.0, jnp.float32),
        "wi_gate": layers.truncated_normal(ks[0], (e, d, f), 1.0, dtype),
        "wi_up": layers.truncated_normal(ks[1], (e, d, f), 1.0, dtype),
        "wo": layers.truncated_normal(ks[2], (e, f, d), 1.0, dtype),
    }
    if m.n_shared > 0:
        p["shared"] = layers.swiglu_init(k_shared, d, m.n_shared * f, dtype)
    return p


def _capacity(m, T: int) -> int:
    C = int(math.ceil(m.top_k * T / m.n_routed * m.capacity_factor))
    return max(8, -(-C // 8) * 8)  # round up to sublane multiple


def _route(params, m, xf):
    """(T, d) -> gates (T,K), idx (T,K), aux scalar."""
    E, K = m.n_routed, m.top_k
    T = xf.shape[0]
    logits = xf.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    gates, idx = jax.lax.top_k(probs, K)  # (T, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # load-balance aux (Switch): E * sum_e f_e * p_e
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)
    return gates, idx, aux


def _dispatch(m, xf, idx, C: int):
    """Scatter tokens into the (E, C, d) buffer; returns (buf, slot, keep)."""
    E, K = m.n_routed, m.top_k
    T, d = xf.shape
    e_flat = idx.reshape(T * K)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)  # (T*K, E)
    pos = jnp.cumsum(onehot, axis=0) - onehot  # exclusive cumsum
    pos = jnp.take_along_axis(pos, e_flat[:, None], axis=1)[:, 0]  # (T*K,)
    keep = pos < C
    slot = jnp.where(keep, e_flat * C + pos, E * C)  # OOB -> dropped
    x_rep = jnp.broadcast_to(xf[:, None, :], (T, K, d)).reshape(T * K, d)
    buf = jnp.zeros((E * C, d), xf.dtype).at[slot].add(
        jnp.where(keep[:, None], x_rep, 0), mode="drop"
    )
    return buf.reshape(E, C, d), slot, keep


def _combine(out_buf, slot, keep, gates, T: int, K: int, d: int, dtype):
    """Gather expert outputs back to token order, gate-weighted."""
    E_C = out_buf.shape[0] * out_buf.shape[1]
    y_rep = jnp.take(
        out_buf.reshape(E_C, d), jnp.minimum(slot, E_C - 1), axis=0
    )
    y_rep = jnp.where(keep[:, None], y_rep, 0)
    w = gates.reshape(T * K).astype(dtype)
    return (y_rep * w[:, None]).reshape(T, K, d).sum(axis=1)


def _expert_gemms(params, buf, dtype):
    """Batched expert SwiGLU; buf (..., E, C, d) -> (..., E, C, d)."""
    g = jnp.einsum("...ecd,edf->...ecf", buf, params["wi_gate"].astype(dtype))
    u = jnp.einsum("...ecd,edf->...ecf", buf, params["wi_up"].astype(dtype))
    h = jax.nn.silu(g) * u
    return jnp.einsum("...ecf,efd->...ecd", h, params["wo"].astype(dtype))


def moe_ffn(params, cfg, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss).  aux = Switch-style load-balance loss."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    plan = mesh_ctx.current()
    if (plan.moe_impl == "shard_map" and plan.mesh is not None
            and B % max(plan.n_data, 1) == 0
            and m.n_routed % max(plan.n_model, 1) == 0):
        return _moe_ffn_shard_map(params, cfg, x, plan)
    Z = plan.n_data if plan.moe_hierarchical else 1
    # B % Z: token shards must coincide with the batch sharding, otherwise
    # the (Z, T/Z) split would cut across sequences on other data shards
    if Z > 1 and B % Z == 0 and (T // Z) >= m.top_k:
        return _moe_ffn_hierarchical(params, cfg, x, plan)

    xf = x.reshape(T, d)
    gates, idx, aux = _route(params, m, xf)
    C = _capacity(m, T)
    buf, slot, keep = _dispatch(m, xf, idx, C)
    out_buf = _expert_gemms(params, buf, x.dtype)
    y = _combine(out_buf, slot, keep, gates, T, m.top_k, d, x.dtype)
    if m.n_shared > 0:
        y = y + layers.swiglu(params["shared"], xf)
    return y.reshape(B, S, d), aux


def _moe_ffn_hierarchical(params, cfg, x, plan) -> Tuple[jax.Array, jax.Array]:
    """Per-data-shard dispatch: (Z, T_local, d) buffers, local scatters,
    (Z x E)-batched expert GEMMs.  See module docstring."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    Z = plan.n_data
    Tl = T // Z
    xf = x.reshape(Z, Tl, d)
    xf = mesh_ctx.constrain(xf, P(plan.dp, None, None))

    gates, idx, aux = jax.vmap(lambda xs: _route(params, m, xs))(xf)
    C = _capacity(m, Tl)
    buf, slot, keep = jax.vmap(lambda xs, ix: _dispatch(m, xs, ix, C))(xf, idx)
    # buf (Z, E, C, d): Z on the data axes, E on the model axis; the GEMM
    # contraction (d) is fully local on every shard.
    buf = mesh_ctx.constrain(buf, P(plan.dp, plan.model_axis, None, None))
    out_buf = _expert_gemms(params, buf, x.dtype)
    out_buf = mesh_ctx.constrain(out_buf, P(plan.dp, plan.model_axis, None, None))
    y = jax.vmap(
        lambda ob, sl, kp, gt: _combine(ob, sl, kp, gt, Tl, m.top_k, d, x.dtype)
    )(out_buf, slot, keep, gates)
    y = mesh_ctx.constrain(y, P(plan.dp, None, None))
    if m.n_shared > 0:
        y = y + jax.vmap(lambda xs: layers.swiglu(params["shared"], xs))(xf)
    return y.reshape(B, S, d), aux.mean()


def _moe_ffn_shard_map(params, cfg, x, plan) -> Tuple[jax.Array, jax.Array]:
    """Expert parallelism under shard_map (iteration 3, EXPERIMENTS.md §Perf).

    Per device: tokens are data-sharded and model-replicated, so every model
    rank REDUNDANTLY computes routing + the full (E, C_local, d) scatter
    (cheap elementwise work), then slices only ITS E/n_model experts — zero
    communication for dispatch.  Each rank K-sums the combine for its local
    experts and ONE psum over the model axis crosses the EP boundary:
    (T_local, d) bf16 per layer, vs the (T_local*K, d) fp32 all-reduces
    GSPMD emits for the global layout (measured 98 TB -> ~8 TB per step on
    deepseek-moe-16b@train_4k).
    """

    m = cfg.moe
    B, S, d = x.shape
    E, K = m.n_routed, m.top_k
    n_model = plan.n_model
    E_loc = E // n_model
    dp = plan.dp
    model = plan.model_axis

    def per_device(wi_gate, wi_up, wo, router, xs):
        Bl = xs.shape[0]
        Tl = Bl * S
        xf = xs.reshape(Tl, d)
        p_local = {"router": router}
        gates, idx, aux = _route(p_local, m, xf)
        C = _capacity(m, Tl)
        buf, slot, keep = _dispatch(m, xf, idx, C)  # (E, C, d), local
        # my expert shard: dynamic slice at my model coordinate (free:
        # buf is computed model-replicated)
        e0 = jax.lax.axis_index(model) * E_loc if model else 0
        buf_loc = jax.lax.dynamic_slice_in_dim(buf, e0, E_loc, axis=0)
        p_exp = {"wi_gate": wi_gate, "wi_up": wi_up, "wo": wo}
        out_loc = _expert_gemms(p_exp, buf_loc, xs.dtype)  # (E_loc, C, d)
        # local combine: keep only assignments routed to MY experts
        mine = keep & (slot >= e0 * C) & (slot < (e0 + E_loc) * C)
        y_rep = jnp.take(
            out_loc.reshape(E_loc * C, d),
            jnp.clip(slot - e0 * C, 0, E_loc * C - 1), axis=0,
        )
        y_rep = jnp.where(mine[:, None], y_rep, 0)
        w = gates.reshape(Tl * K).astype(xs.dtype)
        y_part = (y_rep * w[:, None]).reshape(Tl, K, d).sum(axis=1)
        # the ONLY cross-device step: EP combine, bf16 (T_local, d)
        y = jax.lax.psum(y_part, model) if model else y_part
        if plan.data_axes:
            aux = jax.lax.pmean(aux, plan.data_axes)
        return y.reshape(Bl, S, d), aux

    specs_in = (
        P(model, None, None),  # wi_gate (E, d, f) -> E over model
        P(model, None, None),
        P(model, None, None),
        P(None, None),         # router replicated
        P(dp, None, None),     # x: batch over data axes
    )
    fn = jax.shard_map(
        per_device, mesh=plan.mesh,
        in_specs=specs_in,
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )
    y, aux = fn(params["wi_gate"], params["wi_up"], params["wo"],
                params["router"], x)
    if m.n_shared > 0:
        # shared expert OUTSIDE the shard_map: its wi/wo are TP-sharded by
        # the param rules, so GSPMD column/row-parallelizes it — inside the
        # shard_map it would run model-replicated (measured 16x redundant
        # compute, the dominant term of iteration 3a)
        y = y + layers.swiglu(params["shared"], x.reshape(B * S, d)).reshape(B, S, d)
    return y, aux
