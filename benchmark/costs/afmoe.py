"""Floors of the `afmoe` decoder as one of the chips that share its layers
(`harness/costs.py` says what a floor counts): grouped-query attention with
a gate projection, window layers whose queries see `sliding_window` rows
and full layers that see the context, a leading dense SwiGLU, then sparse
layers of a shared expert, an f32 router over all `num_routed_experts` and
the `num_experts` of them held here. Uniform routing (the seeded router has
no favourites) sends a token's `num_experts_per_tok` choices to a held
expert with probability held / routed each."""

from benchmark.costs.dense_gqa import head_weights, kv_row_bytes
from benchmark.harness.costs import (
    Q40_BYTES_PER_WEIGHT, attention_weights, head_dim)


def gated_attention_weights(cfg: dict) -> int:
    """q, k, v, the output projection and the gate's projection."""
    gate = cfg["hidden_size"] * cfg["num_attention_heads"] * head_dim(cfg)
    return attention_weights(cfg) + gate


def swiglu_weights(cfg: dict, width: int) -> int:
    return 3 * cfg["hidden_size"] * width


def layer_counts(cfg: dict) -> tuple[int, int, int, int]:
    """(window layers, full layers, dense layers, sparse layers)."""
    window = sum(t == "sliding_attention" for t in cfg["layer_types"])
    dense = cfg["num_dense_layers"]
    return window, len(cfg["layer_types"]) - window, dense, cfg["num_hidden_layers"] - dense


def router_bytes(cfg: dict) -> int:
    """The f32 router matrix and the selection bias."""
    return 4 * (cfg["hidden_size"] + 1) * cfg["num_routed_experts"]


def held_experts_touched(cfg: dict, tokens: float) -> float:
    """Expected number of the held experts that `tokens` tokens touch."""
    miss = 1.0 - cfg["num_experts_per_tok"] / cfg["num_routed_experts"]
    return cfg["num_experts"] * (1.0 - miss ** tokens)


def shared_weights(cfg: dict) -> int:
    return swiglu_weights(cfg, cfg["num_shared_experts"] * cfg["moe_intermediate_size"])


def weights_per_token(cfg: dict) -> int:
    """Matmul weights one token's forward pass multiplies by on this chip:
    of its routed experts, the share that is held here."""
    _, _, dense, sparse = layer_counts(cfg)
    held = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["num_routed_experts"]
    per_sparse = (
        shared_weights(cfg) + cfg["hidden_size"] * cfg["num_routed_experts"]
        + held * swiglu_weights(cfg, cfg["moe_intermediate_size"]))
    return int(
        cfg["num_hidden_layers"] * gated_attention_weights(cfg)
        + dense * swiglu_weights(cfg, cfg["intermediate_size"])
        + sparse * per_sparse + head_weights(cfg))


def decode_step_bytes(cfg: dict, live_lanes: float, context: float) -> float:
    window, full, dense, sparse = layer_counts(cfg)
    rows = window * min(context, cfg["sliding_window"]) + full * context
    experts = held_experts_touched(cfg, live_lanes) * swiglu_weights(
        cfg, cfg["moe_intermediate_size"])
    weights = (
        cfg["num_hidden_layers"] * gated_attention_weights(cfg)
        + dense * swiglu_weights(cfg, cfg["intermediate_size"])
        + sparse * (shared_weights(cfg) + experts) + head_weights(cfg))
    return (weights * Q40_BYTES_PER_WEIGHT + sparse * router_bytes(cfg)
            + live_lanes * rows * kv_row_bytes(cfg))


def prefill_flops(cfg: dict, rows: int) -> float:
    return 2.0 * (weights_per_token(cfg) - head_weights(cfg)) * rows
