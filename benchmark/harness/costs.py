"""Operations and bytes the algorithm needs, from a configuration's shapes.

The formulas are a family's own: `benchmark/costs/<family>.py` gives
`decode_step_bytes`, `prefill_flops` and `weights_per_token`, found by the
configuration's `family` as its reference is, and the three functions of
those names here hand over to it. A family that brings no such file has no
floor: `family_costs` gives None, and a reader that divides by one returns
None and never another family's number. What any family may count with stays
here: the peaks, the bytes of a Q40 weight and of a cached key or value, the
weights of a grouped-query attention layer and of a SwiGLU, and how many
experts a batch of tokens touches.

These are floors, not what the program happens to move: weights count at the
Q40 file's 18 bytes per 32 weights (the information the model holds; the
program keeps int8 + f32 block scales, 36 bytes per 32, so its decode step
cannot pass 50% until it serves packed nibbles), keys and values at 2 bytes
over the positions really in context, and a sparse layer reads only the
experts some lane routed to. Attention arithmetic is left out of the FLOPs,
so a share computed from them is a lower bound too.
"""

from __future__ import annotations

import importlib
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


def family_costs(cfg: dict):
    """`benchmark/costs/<family>.py`, or None where the family brings none."""
    name = f"benchmark.costs.{cfg['family']}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        return None


def _family(cfg: dict):
    mod = family_costs(cfg)
    if mod is None:
        raise LookupError(f"family {cfg['family']!r} has no benchmark/costs/{cfg['family']}.py")
    return mod


def weights_per_token(cfg: dict) -> int:
    """Matmul weights one token's forward pass multiplies by."""
    return _family(cfg).weights_per_token(cfg)


def decode_step_bytes(cfg: dict, live_lanes: float, context: float) -> float:
    """Bytes one decode step has to read for `live_lanes` sequences with
    `context` positions each in cache."""
    return _family(cfg).decode_step_bytes(cfg, live_lanes, context)


def prefill_flops(cfg: dict, rows: int) -> float:
    """Multiply-adds x 2 of the weight matmuls over `rows` token rows (the
    head runs on one row per lane and is left out)."""
    return _family(cfg).prefill_flops(cfg, rows)
