"""Floors of the `pangu_ultra_moe` decoder as one of the chips that share
its layers (`harness/costs.py` says what a floor counts): latent attention
(queries through a latent of `q_lora_rank`, one cached row `[c | k_rope]` a
position and layer, `wkv_b` applied around the cache), leading dense SwiGLUs,
then sparse layers of a shared expert, an f32 router over all
`num_routed_experts` and the `n_routed_experts` of them held here. Weights
count at the Q40 file's 18 bytes per 32, but `wkv_b` at what is served: the
absorbed path contracts it over its output side, so the program holds it as
two per-head bf16 stacks, 2 bytes a weight. Uniform routing sends a token's
`num_experts_per_tok` choices to a held expert with probability held / routed.

`latent_row_bytes`, `latent_decode_cost` and `latent_prefill_flops` are what
the latent attention itself needs, for the readers of its two roofline
shares: a decode step reads each cached row once and, absorbed, spends
heads x (row width + kv_lora_rank) multiply-adds on it; a chunk is counted
in whichever form is cheaper for its (query rows, cached rows), so that the
work counted does not depend on the form the program took."""

from benchmark.costs.afmoe import swiglu_weights
from benchmark.costs.dense_gqa import head_weights
from benchmark.harness.costs import KV_BYTES, Q40_BYTES_PER_WEIGHT

SERVED_WKV_B_BYTES = 2  # bf16, dequantised once at load


def latent_row(cfg: dict) -> int:
    """Numbers in a cached row: `[c | k_rope]`."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_row_bytes(cfg: dict) -> int:
    """One position's cache row in one layer."""
    return latent_row(cfg) * KV_BYTES


def wkv_b_weights(cfg: dict) -> int:
    per_head = cfg["qk_nope_head_dim"] + cfg["v_head_dim"]
    return cfg["num_attention_heads"] * per_head * cfg["kv_lora_rank"]


def projection_weights(cfg: dict) -> int:
    """wq_a, wq_b, wkv_a and wo: the attention block's Q40 matrices."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    q_head = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * q_head
            + d * latent_row(cfg) + heads * cfg["v_head_dim"] * d)


def attention_weights(cfg: dict) -> int:
    return projection_weights(cfg) + wkv_b_weights(cfg)


def layer_counts(cfg: dict) -> tuple[int, int]:
    """(dense layers, sparse layers)."""
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def router_bytes(cfg: dict) -> int:
    """The f32 router matrix (no selection bias)."""
    return 4 * cfg["hidden_size"] * cfg["num_routed_experts"]


def held_experts_touched(cfg: dict, tokens: float) -> float:
    """Expected number of the held experts that `tokens` tokens touch."""
    miss = 1.0 - cfg["num_experts_per_tok"] / cfg["num_routed_experts"]
    return cfg["n_routed_experts"] * (1.0 - miss ** tokens)


def shared_weights(cfg: dict) -> int:
    return swiglu_weights(cfg, cfg["n_shared_experts"] * cfg["moe_intermediate_size"])


def weights_per_token(cfg: dict) -> int:
    """Matmul weights one token's forward pass multiplies by on this chip:
    of its routed experts, the share that is held here."""
    dense, sparse = layer_counts(cfg)
    held = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["num_routed_experts"]
    per_sparse = (
        shared_weights(cfg) + cfg["hidden_size"] * cfg["num_routed_experts"]
        + held * swiglu_weights(cfg, cfg["moe_intermediate_size"]))
    return int(
        cfg["num_hidden_layers"] * attention_weights(cfg)
        + dense * swiglu_weights(cfg, cfg["intermediate_size"])
        + sparse * per_sparse + head_weights(cfg))


def decode_step_bytes(cfg: dict, live_lanes: float, context: float) -> float:
    dense, sparse = layer_counts(cfg)
    layers = cfg["num_hidden_layers"]
    experts = held_experts_touched(cfg, live_lanes) * swiglu_weights(
        cfg, cfg["moe_intermediate_size"])
    q40 = (
        layers * projection_weights(cfg)
        + dense * swiglu_weights(cfg, cfg["intermediate_size"])
        + sparse * (shared_weights(cfg) + experts) + head_weights(cfg))
    return (q40 * Q40_BYTES_PER_WEIGHT
            + layers * wkv_b_weights(cfg) * SERVED_WKV_B_BYTES
            + sparse * router_bytes(cfg)
            + live_lanes * context * layers * latent_row_bytes(cfg))


def prefill_flops(cfg: dict, rows: int) -> float:
    return 2.0 * (weights_per_token(cfg) - head_weights(cfg)) * rows


def latent_decode_cost(cfg: dict, rows: float) -> tuple[float, float]:
    """(bytes, FLOPs) of one layer's absorbed decode attention over `rows`
    cached rows in all (live lanes' contexts, summed): every row read once,
    and per row and head a score over the row's width and a weighted sum
    over its first `kv_lora_rank` columns."""
    per_row = cfg["num_attention_heads"] * (latent_row(cfg) + cfg["kv_lora_rank"])
    return rows * latent_row_bytes(cfg), 2.0 * rows * per_row


def latent_prefill_flops(cfg: dict, q_rows: int, k_rows: int) -> float:
    """FLOPs of one layer's attention of a chunk of `q_rows` positions whose
    last one sees `k_rows` cached rows, in the cheaper of the two forms.
    Causal pairs: query i of the chunk sees k_rows - q_rows + i + 1 rows.
    Absorbed: heads x (row width + kv_lora_rank) multiply-adds a pair.
    Expanded: heads x (nope + rope + v) a pair, and `wkv_b` once a cached
    row to rebuild its keys and values."""
    heads = cfg["num_attention_heads"]
    pairs = q_rows * (k_rows - q_rows) + q_rows * (q_rows + 1) / 2.0
    absorbed = pairs * heads * (latent_row(cfg) + cfg["kv_lora_rank"])
    q_head = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    expanded = pairs * heads * (q_head + cfg["v_head_dim"]) + k_rows * wkv_b_weights(cfg)
    return 2.0 * min(absorbed, expanded)
