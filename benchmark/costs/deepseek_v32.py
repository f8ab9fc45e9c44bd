"""Floors of the `deepseek_v32` decoder as one of the chips that share its
layers (`harness/costs.py` says what a floor counts): latent attention as
`costs/pangu_ultra_moe.py` counts it, whose queries attend to the
`index_topk` rows a learned index picks; the index's two Q40 projections,
its f32 head weights and one cached key of `index_head_dim` columns a
position and layer; leading dense SwiGLUs, then sparse layers of a shared
expert, an f32 router with a selection bias over all `num_routed_experts`
and the `n_routed_experts` of them held here.

A decode step reads every index key in context (the index scores every
row) and the latent rows it selected, not the context's. The router keeps
`topk_group` of `n_group` groups a token, and this chip holds group 0 whole:
its group stays for `topk_group / n_group` of the tokens (a half), and such
a token sends `num_experts_per_tok x n_routed_experts / (routed x half)`
(two) of its choices here, where uniform routing without the limit would
land two thirds of the tokens with one and a half. A held expert is still
touched by a token with probability per_tok / routed, so the experts a
batch of tokens is expected to touch are what uniform routing touches.

`index_score_cost`, `sparse_decode_cost` and `sparse_prefill_flops` are
what the index and the restricted attention themselves need, for the
readers of their three roofline shares."""

from benchmark.costs.afmoe import swiglu_weights  # noqa: F401  (readers take it from the family)
from benchmark.costs.dense_gqa import head_weights
from benchmark.costs.pangu_ultra_moe import (  # noqa: F401
    SERVED_WKV_B_BYTES, latent_decode_cost, latent_prefill_flops, latent_row,
    latent_row_bytes, layer_counts, projection_weights, shared_weights, wkv_b_weights)
from benchmark.harness.costs import KV_BYTES, Q40_BYTES_PER_WEIGHT

F32_BYTES = 4


def index_q40_weights(cfg: dict) -> int:
    """The index's two Q40 projections: queries from the query latent, one
    key a position from the layer's input."""
    width = cfg["index_head_dim"]
    return cfg["q_lora_rank"] * cfg["index_n_heads"] * width + cfg["hidden_size"] * width


def index_f32_weights(cfg: dict) -> int:
    """The index's head weights, float32 as the router is."""
    return cfg["hidden_size"] * cfg["index_n_heads"]


def index_key_bytes(cfg: dict) -> int:
    """One position's cached index key in one layer."""
    return cfg["index_head_dim"] * KV_BYTES


def attention_weights(cfg: dict) -> int:
    return (projection_weights(cfg) + wkv_b_weights(cfg) + index_q40_weights(cfg)
            + index_f32_weights(cfg))


def router_bytes(cfg: dict) -> int:
    """The f32 router matrix and the selection bias."""
    return F32_BYTES * (cfg["hidden_size"] + 1) * cfg["num_routed_experts"]


def tokens_landed_share(cfg: dict) -> float:
    """Expected share of tokens with a choice on a held expert: the held
    group stays for topk_group / n_group of them, and such a token's
    choices, spread over the groups that stay, miss it with the chance
    that none of per_tok draws without replacement falls among the held."""
    stays = cfg["topk_group"] / cfg["n_group"]
    among = cfg["num_routed_experts"] * stays  # experts of the groups that stay
    miss = 1.0
    for i in range(cfg["num_experts_per_tok"]):
        miss *= (among - cfg["n_routed_experts"] - i) / (among - i)
    return stays * (1.0 - miss)


def held_experts_touched(cfg: dict, tokens: float) -> float:
    """Expected number of the held experts that `tokens` tokens touch: each
    is one of a token's choices with probability per_tok / routed, under
    the group limit as without it."""
    miss = 1.0 - cfg["num_experts_per_tok"] / cfg["num_routed_experts"]
    return cfg["n_routed_experts"] * (1.0 - miss ** tokens)


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
        layers * (projection_weights(cfg) + index_q40_weights(cfg))
        + dense * swiglu_weights(cfg, cfg["intermediate_size"])
        + sparse * (shared_weights(cfg) + experts) + head_weights(cfg))
    rows = (min(context, cfg["index_topk"]) * latent_row_bytes(cfg)
            + context * index_key_bytes(cfg))
    return (q40 * Q40_BYTES_PER_WEIGHT
            + layers * wkv_b_weights(cfg) * SERVED_WKV_B_BYTES
            + layers * index_f32_weights(cfg) * F32_BYTES
            + sparse * router_bytes(cfg)
            + live_lanes * layers * rows)


def prefill_flops(cfg: dict, rows: int) -> float:
    return 2.0 * (weights_per_token(cfg) - head_weights(cfg)) * rows


def index_score_cost(cfg: dict, pairs: float) -> tuple[float, float]:
    """(bytes, FLOPs) of one layer's index scores over `pairs` pairs of a
    query and a cached row it sees: a key is read once a pair in a decode
    step (a chunk reads it once for all its queries: its floor is the
    FLOPs), and every pair is heads x width multiply-adds."""
    return (pairs * index_key_bytes(cfg),
            2.0 * pairs * cfg["index_n_heads"] * cfg["index_head_dim"])


def sparse_decode_cost(cfg: dict, rows_selected: float) -> tuple[float, float]:
    """(bytes, FLOPs) of one layer's absorbed decode attention over the
    rows its queries selected."""
    return latent_decode_cost(cfg, rows_selected)


def sparse_prefill_flops(cfg: dict, pairs_selected: float, k_rows: int) -> float:
    """FLOPs of one layer's attention of a chunk whose queries attend to
    `pairs_selected` rows in all and whose last one sees `k_rows` cached
    rows, in the cheaper of the two forms (`pangu_ultra_moe.
    latent_prefill_flops`): absorbed, heads x (row width + kv_lora_rank)
    multiply-adds a pair; expanded, heads x (nope + rope + v) a pair and
    `wkv_b` once a cached row."""
    heads = cfg["num_attention_heads"]
    absorbed = pairs_selected * heads * (latent_row(cfg) + cfg["kv_lora_rank"])
    q_head = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    expanded = (pairs_selected * heads * (q_head + cfg["v_head_dim"])
                + k_rows * wkv_b_weights(cfg))
    return 2.0 * min(absorbed, expanded)
