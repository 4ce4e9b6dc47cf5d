"""The least work a serve step needs, counted from the configuration's shapes.

This is the algorithm's count, never the implementation's, so that no
implementation of the same step can read above 100% of its roofline:

- FLOPs (2 per multiply-add) count only the tokens that were fed, each
  attending over its own live prefix; the head counts only for rows that
  yield a token.
- Bytes count each weight that the step needs once, the live KV rows of
  each slot once (its ``position``, not ``max_len``), the new KV rows
  written, the embedding rows read (inside the head's read of a tied
  table) and the fp32 logits of yielding rows.
- Routed experts count at their least possible number: ``top_k`` experts
  per MoE layer, as if every token picked the same ones; shared experts and
  the router count in full.

Pool copies, the full-``max_len`` gather and logits of rows that yield no
token are implementation costs and are not counted.
"""

from __future__ import annotations

from typing import Sequence, Tuple

BF16_BYTES = 2
F32_BYTES = 4


def _moe_layers(arch) -> int:
    if arch.moe is None:
        return 0
    return arch.n_layers - (1 if arch.moe.first_dense else 0)


def attn_params(arch) -> int:
    d, h, kv, hd = arch.d_model, arch.n_heads, arch.n_kv_heads, arch.head_dim
    n = d * (h + 2 * kv) * hd + h * hd * d + 2 * d
    if arch.qk_norm:
        n += 2 * hd
    return n


def param_bytes(arch) -> int:
    """Every parameter the program holds (embedding and head at ``vocab``
    rows, the router in float32)."""
    d, v = arch.d_model, arch.vocab
    n_moe = _moe_layers(arch)
    n = arch.n_layers * attn_params(arch)
    n += (arch.n_layers - n_moe) * 3 * d * arch.d_ff
    b = 0
    if n_moe:
        m = arch.moe
        n += n_moe * 3 * d * m.d_ff_expert * (m.n_routed + m.n_shared)
        b += n_moe * d * m.n_routed * F32_BYTES
    n += v * d * (1 if arch.tie_embeddings else 2) + d
    return n * BF16_BYTES + b


def layer_weight_bytes(arch) -> int:
    """Weights one step must read across all layers: attention, dense FFN,
    router, shared experts, and ``top_k`` routed experts per MoE layer."""
    d = arch.d_model
    n_moe = _moe_layers(arch)
    n = arch.n_layers * attn_params(arch)
    n += (arch.n_layers - n_moe) * 3 * d * arch.d_ff
    b = 0
    if n_moe:
        m = arch.moe
        n += n_moe * 3 * d * m.d_ff_expert * (m.top_k + m.n_shared)
        b += n_moe * d * m.n_routed * F32_BYTES
    return n * BF16_BYTES + b


def kv_row_bytes(arch) -> int:
    """Bytes of one token's keys and values over every layer (bf16)."""
    return arch.n_layers * 2 * arch.n_kv_heads * arch.head_dim * BF16_BYTES


def token_flops(arch, position: int) -> int:
    """Matmul FLOPs to run one token at cache position ``position`` through
    every layer (it attends over ``position + 1`` keys), head excluded."""
    d, h, hd = arch.d_model, arch.n_heads, arch.head_dim
    per_layer = 2 * (attn_params(arch) - 2 * d - (2 * hd if arch.qk_norm else 0))
    per_layer += 4 * h * hd * (position + 1)
    n_moe = _moe_layers(arch)
    f = arch.n_layers * per_layer
    f += (arch.n_layers - n_moe) * 6 * d * arch.d_ff
    if n_moe:
        m = arch.moe
        f += n_moe * (2 * d * m.n_routed
                      + 6 * d * m.d_ff_expert * (m.top_k + m.n_shared))
    return f


def step_work(arch, positions: Sequence[int], lengths: Sequence[int],
              yields: Sequence[bool]) -> Tuple[int, int]:
    """(FLOPs, bytes) the step needs: slot ``b`` feeds ``lengths[b]`` tokens
    from cache position ``positions[b]``; ``yields[b]`` if its last fed
    token's logits give a token."""
    d, v = arch.d_model, arch.vocab
    flops = 0
    fed = 0
    live_rows = 0
    for p, n in zip(positions, lengths):
        if n <= 0:
            continue
        fed += n
        live_rows += p
        # token c of the chunk sits at p + c and attends over p + c + 1 keys
        flops += sum(token_flops(arch, p + c) for c in range(n))
    if fed == 0:
        return 0, 0
    n_yield = sum(1 for n, y in zip(lengths, yields) if n > 0 and y)
    flops += n_yield * 2 * d * v
    nbytes = layer_weight_bytes(arch) + d * BF16_BYTES  # + final norm
    if n_yield:
        nbytes += d * v * BF16_BYTES + n_yield * v * F32_BYTES
    if not (n_yield and arch.tie_embeddings):
        nbytes += fed * d * BF16_BYTES  # embedding rows
    nbytes += (live_rows + fed) * kv_row_bytes(arch)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peaks) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_s)
