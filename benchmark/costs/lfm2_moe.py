"""Floors of the `lfm2_moe` decoder as one of the chips that share its layers
(`harness/costs.py` says what a floor counts): a gated short convolution in
the layers `layer_types` calls `conv` (`in_proj` hidden -> 3 x hidden,
`out_proj` hidden -> hidden, `conv_L_cache` f32 taps a channel, and a state
of `conv_L_cache - 1` bf16 rows a lane where attention would hold a cache
row a position), grouped-query attention in the others, leading dense
SwiGLUs, then sparse layers of an f32 router over all `num_routed_experts`
and the `num_experts` of them held here; no shared expert. Uniform routing
(the seeded router has no favourites) sends a token's `num_experts_per_tok`
choices to a held expert with probability held / routed each."""

from benchmark.costs.dense_gqa import head_weights, kv_row_bytes
from benchmark.harness.costs import KV_BYTES, Q40_BYTES_PER_WEIGHT, attention_weights


def layer_counts(cfg: dict) -> tuple[int, int, int, int]:
    """(convolution layers, attention layers, dense layers, sparse layers)."""
    conv = sum(t == "conv" for t in cfg["layer_types"])
    dense = cfg["num_dense_layers"]
    return conv, len(cfg["layer_types"]) - conv, dense, cfg["num_hidden_layers"] - dense


def conv_weights(cfg: dict) -> int:
    """Both projections of one convolution layer's operator."""
    return 4 * cfg["hidden_size"] * cfg["hidden_size"]


def conv_tap_bytes(cfg: dict) -> int:
    """One layer's depthwise taps, f32."""
    return 4 * cfg["hidden_size"] * cfg["conv_L_cache"]


def conv_state_bytes(cfg: dict) -> int:
    """One lane's state of one layer, read and written once a step."""
    return 2 * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * KV_BYTES


def swiglu_weights(cfg: dict, width: int) -> int:
    return 3 * cfg["hidden_size"] * width


def router_bytes(cfg: dict) -> int:
    """The f32 router matrix and the selection bias."""
    return 4 * (cfg["hidden_size"] + 1) * cfg["num_routed_experts"]


def shared_weights(cfg: dict) -> int:
    """No expert that every token passes through."""
    return 0


def held_experts_touched(cfg: dict, tokens: float) -> float:
    """Expected number of the held experts that `tokens` tokens touch."""
    miss = 1.0 - cfg["num_experts_per_tok"] / cfg["num_routed_experts"]
    return cfg["num_experts"] * (1.0 - miss ** tokens)


def weights_per_token(cfg: dict) -> int:
    """Matmul weights one token's forward pass multiplies by on this chip:
    of its routed experts, the share that is held here."""
    conv, attn, dense, sparse = layer_counts(cfg)
    held = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["num_routed_experts"]
    per_sparse = (
        cfg["hidden_size"] * cfg["num_routed_experts"]
        + held * swiglu_weights(cfg, cfg["moe_intermediate_size"]))
    return int(
        conv * conv_weights(cfg) + attn * attention_weights(cfg)
        + dense * swiglu_weights(cfg, cfg["intermediate_size"])
        + sparse * per_sparse + head_weights(cfg))


def conv_decode_bytes(cfg: dict, live_lanes: float) -> float:
    """What one convolution layer's operator has to move in one decode step:
    both projections at the file's bytes, the taps, each live lane's state in
    and out."""
    return (conv_weights(cfg) * Q40_BYTES_PER_WEIGHT + conv_tap_bytes(cfg)
            + live_lanes * conv_state_bytes(cfg))


def conv_prefill_flops(cfg: dict, rows: int) -> float:
    """Multiply-adds x 2 of one convolution layer's two projections over
    `rows` token rows (the taps and gates, 8 operations a channel and row
    against 8192, are left out)."""
    return 2.0 * conv_weights(cfg) * rows


def decode_step_bytes(cfg: dict, live_lanes: float, context: float) -> float:
    conv, attn, dense, sparse = layer_counts(cfg)
    experts = held_experts_touched(cfg, live_lanes) * swiglu_weights(
        cfg, cfg["moe_intermediate_size"])
    weights = (
        attn * attention_weights(cfg)
        + dense * swiglu_weights(cfg, cfg["intermediate_size"])
        + sparse * experts + head_weights(cfg))
    return (weights * Q40_BYTES_PER_WEIGHT + sparse * router_bytes(cfg)
            + conv * conv_decode_bytes(cfg, live_lanes)
            + attn * live_lanes * context * kv_row_bytes(cfg))


def prefill_flops(cfg: dict, rows: int) -> float:
    """The floor of a chunk program. The accepted reader hands over every
    lane's rows (`lanes x bucket`: what a chunk program of the dense models
    computes), and a chunk fills one lane: the rows the algorithm needs are
    one lane's bucket, and this family's program computes no more than those
    in 30 layers of 40 (a convolution layer and its FFN run over the admitted
    lane alone), so a floor over every lane's rows would pass the peak. One
    lane's share of `rows` it is."""
    lanes = cfg.get("serving", {}).get("lanes", 1)
    return 2.0 * (weights_per_token(cfg) - head_weights(cfg)) * rows / lanes
