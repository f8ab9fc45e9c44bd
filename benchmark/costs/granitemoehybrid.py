"""Floors of the `granitemoehybrid` decoder as one of the chips that share its
layers (`harness/costs.py` says what a floor counts): a Mamba-2 mixer in the
layers `layer_types` calls `mamba` (`in_proj` hidden -> inner + inner + 2 x
state + heads, `out_proj` inner -> hidden, f32 taps, biases, gains and a
head's three scalars, and a state a lane where attention would hold a cache
row a position: heads x head width x state width float32, read and written
whole every decode step, and `mamba_d_conv - 1` bf16 rows of the
convolution's input), grouped-query attention in the others, and in every
layer an f32 router over all `num_routed_experts`, the `num_experts` of them
held here and a shared expert of `shared_intermediate_size`. Uniform routing
(the seeded router has no favourites) sends a token's `num_experts_per_tok`
choices to a held expert with probability held / routed each."""

from benchmark.costs.dense_gqa import head_weights, kv_row_bytes
from benchmark.harness.costs import KV_BYTES, Q40_BYTES_PER_WEIGHT, attention_weights


def layer_counts(cfg: dict) -> tuple[int, int]:
    """(Mamba-2 layers, attention layers); every layer is sparse."""
    mamba = sum(t == "mamba" for t in cfg["layer_types"])
    return mamba, len(cfg["layer_types"]) - mamba


def ssm_sizes(cfg: dict) -> tuple[int, int, int, int]:
    """(heads, inner width, state width, channels of the convolution)."""
    heads, state = cfg["mamba_n_heads"], cfg["mamba_d_state"]
    inner = heads * cfg["mamba_d_head"]
    return heads, inner, state, inner + 2 * cfg["mamba_n_groups"] * state


def ssm_weights(cfg: dict) -> int:
    """Both projections of one Mamba-2 layer's mixer."""
    heads, inner, _, conv = ssm_sizes(cfg)
    return cfg["hidden_size"] * (inner + conv + heads) + inner * cfg["hidden_size"]


def ssm_small_bytes(cfg: dict) -> int:
    """One layer's f32 leaves: taps and bias a channel, the norm's gains, a
    head's step bias, log decay rate and skip."""
    heads, inner, _, conv = ssm_sizes(cfg)
    return 4 * (conv * (cfg["mamba_d_conv"] + 1) + inner + 3 * heads)


def ssm_state_bytes(cfg: dict) -> int:
    """One lane's states of one layer, read and written once a step: the
    float32 recurrent state and the bf16 rows of the convolution's input."""
    heads, inner, state, conv = ssm_sizes(cfg)
    return 2 * (4 * inner * state + (cfg["mamba_d_conv"] - 1) * conv * KV_BYTES)


def swiglu_weights(cfg: dict, width: int) -> int:
    return 3 * cfg["hidden_size"] * width


def router_bytes(cfg: dict) -> int:
    """The f32 router matrix; no selection bias."""
    return 4 * cfg["hidden_size"] * cfg["num_routed_experts"]


def shared_weights(cfg: dict) -> int:
    """The expert every token passes through."""
    return swiglu_weights(cfg, cfg["shared_intermediate_size"])


def held_experts_touched(cfg: dict, tokens: float) -> float:
    """Expected number of the held experts that `tokens` tokens touch."""
    miss = 1.0 - cfg["num_experts_per_tok"] / cfg["num_routed_experts"]
    return cfg["num_experts"] * (1.0 - miss ** tokens)


def weights_per_token(cfg: dict) -> int:
    """Matmul weights one token's forward pass multiplies by on this chip:
    of its routed experts, the share that is held here."""
    mamba, attn = layer_counts(cfg)
    held = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["num_routed_experts"]
    per_layer = (
        cfg["hidden_size"] * cfg["num_routed_experts"]
        + held * swiglu_weights(cfg, cfg["intermediate_size"]) + shared_weights(cfg))
    return int(
        mamba * ssm_weights(cfg) + attn * attention_weights(cfg)
        + (mamba + attn) * per_layer + head_weights(cfg))


def ssm_decode_bytes(cfg: dict, live_lanes: float) -> float:
    """What one Mamba-2 layer's mixer has to move in one decode step: both
    projections at the file's bytes, the f32 leaves, each live lane's states
    in and out."""
    return (ssm_weights(cfg) * Q40_BYTES_PER_WEIGHT + ssm_small_bytes(cfg)
            + live_lanes * ssm_state_bytes(cfg))


def ssm_prefill_flops(cfg: dict, rows: int) -> float:
    """Multiply-adds x 2 of one Mamba-2 layer's mixer over `rows` token rows
    of one lane: both projections, and the recurrence in its block form
    (blocks of `mamba_chunk_size` rows): a row's `C . B` and decayed sum over
    the rows before it in its block, the carried state's read-out, and the
    block's update of the state. The taps, gates and norm are left out."""
    heads, inner, state, _ = ssm_sizes(cfg)
    pairs = rows * (min(cfg["mamba_chunk_size"], rows) + 1) / 2.0  # s <= t in a block
    scan = 2.0 * pairs * (state + inner) + rows * 4.0 * inner * state
    return 2.0 * ssm_weights(cfg) * rows + scan


def decode_step_bytes(cfg: dict, live_lanes: float, context: float) -> float:
    mamba, attn = layer_counts(cfg)
    experts = held_experts_touched(cfg, live_lanes) * swiglu_weights(
        cfg, cfg["intermediate_size"])
    weights = (
        attn * attention_weights(cfg)
        + (mamba + attn) * (experts + shared_weights(cfg)) + head_weights(cfg))
    return (weights * Q40_BYTES_PER_WEIGHT + (mamba + attn) * router_bytes(cfg)
            + mamba * ssm_decode_bytes(cfg, live_lanes)
            + attn * live_lanes * context * kv_row_bytes(cfg))


def prefill_flops(cfg: dict, rows: int) -> float:
    """The floor of a chunk program. The accepted reader hands over every
    lane's rows (`lanes x bucket`: what a chunk program of the dense models
    computes), and a chunk fills one lane: the rows the algorithm needs are
    one lane's bucket, and this family's program computes no more than those
    in 18 layers of 20 (a Mamba-2 layer and its experts run over the admitted
    lane alone), so a floor over every lane's rows would pass the peak. One
    lane's share of `rows` it is; the recurrence's own operations, 3% of the
    projections', are left out here and counted by `ssm_prefill_flops`."""
    lanes = cfg.get("serving", {}).get("lanes", 1)
    return 2.0 * (weights_per_token(cfg) - head_weights(cfg)) * rows / lanes
