"""Floors of a dense decoder: every layer is grouped-query attention and one
SwiGLU, every decode step streams every weight and each lane's keys and
values (`harness/costs.py` says what a floor counts)."""

from benchmark.harness.costs import (
    KV_BYTES, Q40_BYTES_PER_WEIGHT, attention_weights, expert_weights, head_dim)


def head_weights(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def kv_row_bytes(cfg: dict) -> int:
    """One position's keys and values in one layer's cache."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * KV_BYTES


def weights_per_token(cfg: dict) -> int:
    per_layer = attention_weights(cfg) + expert_weights(cfg)
    return cfg["num_hidden_layers"] * per_layer + head_weights(cfg)


def decode_step_bytes(cfg: dict, live_lanes: float, context: float) -> float:
    layer = (attention_weights(cfg) + expert_weights(cfg)) * Q40_BYTES_PER_WEIGHT
    layer += live_lanes * context * kv_row_bytes(cfg)
    return cfg["num_hidden_layers"] * layer + head_weights(cfg) * Q40_BYTES_PER_WEIGHT


def prefill_flops(cfg: dict, rows: int) -> float:
    return 2.0 * (weights_per_token(cfg) - head_weights(cfg)) * rows
