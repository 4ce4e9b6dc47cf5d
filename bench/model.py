"""A configuration file of ``bench/configs`` -> the architecture it states,
the program's model config and its engine settings.

The file holds the configuration as it is run, under the keys of the
model's public ``config.json``; ``published`` keeps the source's values of
the keys that were changed.  :class:`Arch` is the benchmark's own reading
of it, which the weights and the reference use; :func:`program_config`
hands the same numbers to the system under test.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Optional

CONFIGS = Path(__file__).resolve().parent / "configs"


@dataclasses.dataclass(frozen=True)
class MoE:
    n_routed: int
    top_k: int
    d_ff_expert: int
    n_shared: int
    first_dense: bool
    norm_topk_prob: bool
    capacity_factor: float


@dataclasses.dataclass(frozen=True)
class Arch:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    vocab_padded: int
    qk_norm: bool
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool
    moe: Optional[MoE]


def load(name: str) -> dict:
    path = CONFIGS / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no configuration file {path}")
    return json.loads(path.read_text())


def _pad(vocab: int, multiple: int) -> int:
    return -(-vocab // multiple) * multiple


def arch(name: str, c: dict) -> Arch:
    if c["torch_dtype"] != "bfloat16" or c["hidden_act"] != "silu":
        raise ValueError(f"{name}: only bf16 SwiGLU models are served")
    moe = None
    if c.get("n_routed_experts"):
        if c["first_k_dense_replace"] not in (0, 1) or c["scoring_func"] != "softmax":
            raise ValueError(f"{name}: unsupported expert layout")
        moe = MoE(
            n_routed=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
            d_ff_expert=c["moe_intermediate_size"],
            n_shared=c["n_shared_experts"],
            first_dense=c["first_k_dense_replace"] == 1,
            norm_topk_prob=c["norm_topk_prob"],
            capacity_factor=c["moe_capacity_factor"],
        )
    return Arch(
        name=name, n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"],
        vocab_padded=_pad(c["vocab_size"], c["vocab_pad_multiple"]),
        qk_norm=c.get("qk_norm", False), rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=c["tie_word_embeddings"], moe=moe,
    )


def program_config(a: Arch):
    """The system under test's ModelConfig for the same numbers."""
    from repro.configs.base import MoEConfig, ModelConfig

    moe = None
    if a.moe is not None:
        if not a.moe.norm_topk_prob:
            raise ValueError(f"{a.name}: the program always renormalises "
                             "the top-k gates")
        moe = MoEConfig(n_routed=a.moe.n_routed, top_k=a.moe.top_k,
                        d_ff_expert=a.moe.d_ff_expert,
                        n_shared=a.moe.n_shared,
                        first_dense=a.moe.first_dense,
                        capacity_factor=a.moe.capacity_factor)
    cfg = ModelConfig(
        name=a.name, family="moe" if moe else "dense", n_layers=a.n_layers,
        d_model=a.d_model, n_heads=a.n_heads, n_kv_heads=a.n_kv_heads,
        head_dim=a.head_dim, d_ff=a.d_ff, vocab=a.vocab, qk_norm=a.qk_norm,
        rope_theta=a.rope_theta, norm_eps=a.norm_eps,
        tie_embeddings=a.tie_embeddings, moe=moe,
        param_dtype="bfloat16", compute_dtype="bfloat16",
    )
    if cfg.vocab_padded != a.vocab_padded:
        raise ValueError(f"{a.name}: vocab padding {cfg.vocab_padded} != "
                         f"{a.vocab_padded}")
    return cfg


def engine_settings(c: dict) -> dict:
    kv = {"bfloat16": "bf16"}[c["kv_cache_dtype"]]
    return dict(c["engine"], kv_dtype=kv)
