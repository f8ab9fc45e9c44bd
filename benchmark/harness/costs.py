"""Operations and bytes the algorithm needs, from a configuration's shapes.

These are floors, not what the program happens to move: weights count at the
Q40 file's 18 bytes per 32 weights (the information the model holds; the
program keeps int8 + f32 block scales, 36 bytes per 32, so its decode step
cannot pass 50% until it serves packed nibbles), keys and values at 2 bytes
over the positions really in context, and a sparse layer reads only the
experts some lane routed to. Attention arithmetic is left out of the FLOPs,
so a share computed from them is a lower bound too.
"""

from __future__ import annotations

import json
import os

Q40_BYTES_PER_WEIGHT = 18 / 32
KV_BYTES = 2  # bf16


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "..", "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["assumed"]["head_dim"]


def attention_weights(cfg: dict) -> int:
    """q, k, v and output projection weights of one layer."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    qd, kd = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * (qd + 2 * kd) + qd * d


def expert_weights(cfg: dict) -> int:
    """One SwiGLU's weights: the dense FFN, or one expert."""
    width = cfg.get("moe_intermediate_size") or cfg["intermediate_size"]
    return 3 * cfg["hidden_size"] * width


def distinct_experts(n_experts: int, top_k: int, tokens: float) -> float:
    """Expected number of experts that `tokens` tokens touch when each picks
    top_k of n_experts uniformly: the seeded router has no favourites."""
    return n_experts * (1.0 - (1.0 - top_k / n_experts) ** tokens)


def weights_per_token(cfg: dict) -> int:
    """Matmul weights one token's forward pass multiplies by."""
    ffn = expert_weights(cfg) * cfg.get("num_experts_per_tok", 1)
    router = cfg["hidden_size"] * cfg.get("num_experts", 0)
    per_layer = attention_weights(cfg) + ffn + router
    return cfg["num_hidden_layers"] * per_layer + cfg["hidden_size"] * cfg["vocab_size"]


def decode_step_bytes(cfg: dict, live_lanes: float, context: float) -> float:
    """Bytes one decode step has to read for `live_lanes` sequences with
    `context` positions each in cache."""
    e = cfg.get("num_experts", 0)
    if e:
        ffn = distinct_experts(e, cfg["num_experts_per_tok"], live_lanes) * expert_weights(cfg)
        router = 4 * cfg["hidden_size"] * e
    else:
        ffn, router = expert_weights(cfg), 0
    layer = (attention_weights(cfg) + ffn) * Q40_BYTES_PER_WEIGHT + router
    kv_row = 2 * cfg["num_key_value_heads"] * head_dim(cfg) * KV_BYTES
    layer += live_lanes * context * kv_row
    head = cfg["hidden_size"] * cfg["vocab_size"] * Q40_BYTES_PER_WEIGHT
    return cfg["num_hidden_layers"] * layer + head


def prefill_flops(cfg: dict, rows: int) -> float:
    """Multiply-adds x 2 of the weight matmuls over `rows` token rows (the
    head runs on one row per lane and is left out)."""
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return 2.0 * (weights_per_token(cfg) - head) * rows
