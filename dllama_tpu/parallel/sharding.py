"""Tensor-parallel sharding rules for the params pytree and KV cache.

This module *is* the reference's TP design, restated declaratively:

| reference mechanism (src/llm.cpp:168-176)          | PartitionSpec here |
|----------------------------------------------------|--------------------|
| sliceRowMatmul on q/k/v (out-dim split)            | wq/wk/wv: (.., "tp") last (out) axis |
| sliceColMatmul on wo (in-dim split, partial sums)  | wo: ("tp", ..) in axis; XLA inserts the all-reduce the reference built from SYNC_NODE_SLICES + OP_MERGE_ADD |
| sliceRowMatmul on w1/w3, sliceColMatmul on w2      | same pattern on the FFN |
| sliceKvCache (kv-head split)                       | cache: kv-head axis over "tp" |
| sliceMultiHeadAtt (head split)                     | falls out of the q/k/v out-shards |
| sliceRowMatmul on wcls + logits gather-to-root     | wcls: vocab axis over "tp"; the gather is XLA's |
| replicated norms/gates/embedding broadcast         | PartitionSpec() |

The weight *splitters* (splitRowMatmulWeight etc., src/nn/nn-core.cpp:289-322)
and the TCP weight shipping (NnRootWeightLoader) collapse into
`jax.device_put(array, NamedSharding(mesh, spec))`.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..formats.model_file import LlmArch, LlmHeader


def param_spec_tree(h: LlmHeader) -> dict[str, Any]:
    """PartitionSpecs matching the params pytree from models/loader.py.

    The same specs cover every quantized device format's leaves: a
    QuantWeight/PackedQuantWeight is a (values, scales) pytree whose
    leaves all keep the [in-ish, out] axis order — row split puts
    "tp" on the last (out) axis of both leaves, col split on the
    second-to-last. For the packed q40i4 layout the value leaf's in axis
    is in//8 (words of eight nibbles, in groups of 256 weight rows) and
    the scale leaf's is in//32; the engine serves it at tp > 1 only where
    every in dim divides by 256*tp, so the col shard boundaries stay
    group- and block-aligned."""
    moe = h.arch in (
        LlmArch.QWEN3_MOE, LlmArch.AFMOE, LlmArch.PANGU_MOE, LlmArch.DEEPSEEK_V32,
        LlmArch.LFM2_MOE, LlmArch.GRANITE_MOE_HYBRID)
    # stacked layer weights carry a leading layer axis; MoE adds an expert axis
    row = P(None, None, None, "tp") if moe else P(None, None, "tp")  # out split
    col = P(None, None, "tp", None) if moe else P(None, "tp", None)  # in split
    layers: dict[str, Any] = {
        "att_norm": P(),
        "ffn_norm": P(),
        "wq": P(None, None, "tp"),
        "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"),
        # fused q|k|v / w1|w3 (loader fuse > 0): same row split — the
        # shard-major interleave makes the contiguous tp chunks each hold
        # one shard's slice of every constituent
        "wqkv": P(None, None, "tp"),
        "w13": P(None, None, "tp"),
        "wo": P(None, "tp", None),
        "w1": row,
        "w2": col,
        "w3": row,
    }
    if moe:
        layers["moe_gate"] = P()
        layers["expert_bias"] = P()
        # what lies beside the experts: a shared expert every token passes
        # through and leading dense layers, split as a dense FFN is
        for prefix in ("shared_", "dense_"):
            for n in ("w1", "w3", "w13"):
                layers[prefix + n] = P(None, None, "tp")
            layers[prefix + "w2"] = P(None, "tp", None)
    layers["wg"] = P(None, None, "tp")  # the attention gate: heads, as wq
    # a gated short convolution's projections and taps: one device holds them
    for n in ("conv_in", "conv_out", "conv_w", "ssm_in", "ssm_out", "ssm_conv_w",
              "ssm_conv_b", "ssm_dt_bias", "ssm_a_log", "ssm_d", "ssm_norm"):
        layers[n] = P()
    for n in ("q_norm", "k_norm", "post_att_norm", "post_ffn_norm"):
        layers[n] = P()
    return {
        # vocab-sharded (the reference computes the embedding on the root
        # node only and broadcasts X — SYNC_WITH_ROOT, src/llm.cpp:256 —
        # i.e. it holds the whole table on one node; here each shard
        # holds V/tp rows and the lookup masks+psums). Replicating the
        # table costs 2.1 GB/chip at 70B (docs/70b_plan.md) for no win:
        # the psum payload is a [B, T, D] activation, noise next to it.
        "embed": P("tp", None),
        "wcls": P(None, "tp"),
        "final_norm": P(),
        "rope_cos": P(),
        "rope_sin": P(),
        "layers": layers,
    }


def cache_specs(h: LlmHeader, sp: bool = False, pp: bool = False) -> dict[str, P]:
    """KV cache [L, B, KH, S, hd] (head-major): batch over dp, kv-heads
    over tp (reference: sliceKvCache, src/nn/nn-core.cpp:211-218). With
    `sp` the sequence axis additionally shards over the sp mesh axis — the
    long-context layout ring/merged attention consumes
    (models/transformer._attention_sp). With `pp` the LAYER axis shards
    over pipeline stages (each stage owns its layer range's cache,
    parallel/pipeline.py)."""
    lead = "pp" if pp else None
    spec = (
        P(lead, "dp", "tp", "sp", None)
        if sp
        else P(lead, "dp", "tp", None, None)
    )
    return {"k": spec, "v": spec}


def named_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def shard_params_put(mesh: Mesh, h: LlmHeader):
    """A `put` hook for models/loader.load_params that places each tensor
    with its TP sharding as it is read — per-shard streaming, so host
    memory and per-device HBM stay at one slice per tensor (the TPU
    equivalent of the reference's slice-by-slice socket streaming,
    src/llm.cpp:614-669). On a mesh with a `pp` axis the layer-stacked
    tensors additionally shard their leading (layer) axis over stages."""
    specs = param_spec_tree(h)
    if "pp" in mesh.axis_names:
        from .pipeline import pp_param_specs

        specs = pp_param_specs(specs)
    flat_layer_specs = specs["layers"]

    def _spec(name: str) -> P:
        spec = specs.get(name) if name in specs else flat_layer_specs.get(name)
        return spec if spec is not None else P()

    def put(name: str, arr: np.ndarray):
        return jax.device_put(arr, NamedSharding(mesh, _spec(name)))

    # Streaming hook: the loader asks for a tensor's sharding UP FRONT and
    # pulls each device shard's bytes lazily (make_array_from_callback)
    # instead of materializing whole layer stacks on host — see
    # models/loader._stream_quant_stack.
    put.sharding = lambda name: NamedSharding(mesh, _spec(name))
    return put
