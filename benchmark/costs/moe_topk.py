"""Floors of the `dense_gqa` layer with a sparse mixture of experts: a token
multiplies by its `num_experts_per_tok` experts and the router, and a decode
step reads only the experts some live lane routed to, as many as uniform
routing touches (the seeded router has no favourites), and the f32 router."""

from benchmark.costs.dense_gqa import head_weights, kv_row_bytes
from benchmark.harness.costs import (
    Q40_BYTES_PER_WEIGHT, attention_weights, distinct_experts, expert_weights)


def weights_per_token(cfg: dict) -> int:
    ffn = expert_weights(cfg) * cfg["num_experts_per_tok"]
    router = cfg["hidden_size"] * cfg["num_experts"]
    per_layer = attention_weights(cfg) + ffn + router
    return cfg["num_hidden_layers"] * per_layer + head_weights(cfg)


def decode_step_bytes(cfg: dict, live_lanes: float, context: float) -> float:
    e = cfg["num_experts"]
    ffn = distinct_experts(e, cfg["num_experts_per_tok"], live_lanes) * expert_weights(cfg)
    router = 4 * cfg["hidden_size"] * e
    layer = (attention_weights(cfg) + ffn) * Q40_BYTES_PER_WEIGHT + router
    layer += live_lanes * context * kv_row_bytes(cfg)
    return cfg["num_hidden_layers"] * layer + head_weights(cfg) * Q40_BYTES_PER_WEIGHT


def prefill_flops(cfg: dict, rows: int) -> float:
    return 2.0 * (weights_per_token(cfg) - head_weights(cfg)) * rows
