"""Pipeline parallelism: decoder layers sharded into stages over a `pp`
mesh axis.

The reference cannot scale past `nNodes <= nKvHeads` — its only cross-node
strategy is tensor parallelism, bounded by the KV-head count (SURVEY.md §2
parallelism checklist; src/app.cpp:236-240). Pipeline stages lift that
ceiling: each stage holds a contiguous range of L/pp layers (weights AND
that range's KV cache), activations hop stage-to-stage over ICI
(`lax.ppermute` of one [B, T, D] tensor — the smallest inter-chip payload
in the whole model), and the per-stage HBM footprint shrinks by pp. A
70B+ checkpoint that cannot fit tp<=8 chips runs as pp stages of tp
groups.

Schedule (inference forward, single microbatch): P pipeline ticks; at
tick i stage i runs its local layer scan on the activation it received,
every other stage computes the same program on pass-through data and
discards it (SPMD requires identical programs; the discarded compute is
the classic pipeline bubble). Latency per forward is the same L layer
steps the single-device program pays — the bubble costs device
*utilization*, not request latency, so for fit-constrained serving the
trade is free. Stage-local math is `models.transformer.run_layers` —
bit-identical to the single-device path.

Caches: the [L, ...] KV cache shards its LAYER axis over pp (each stage
owns its range's cache); a stage's cache only commits on its active tick.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..formats.model_file import LlmHeader


def validate_pp(h: LlmHeader, pp: int) -> None:
    """Any pp >= 1 that divides the layer count works (the ring ppermute
    schedule has no power-of-two requirement — 80 layers over 5 stages is
    legal, unlike the reference's 2^n node rule)."""
    if pp < 1:
        raise ValueError(f"pp must be >= 1, got {pp}")
    if pp > 1 and h.n_layers % pp != 0:
        raise ValueError(
            f"nLayers={h.n_layers} not divisible by pp={pp} (stages hold "
            "equal layer ranges)"
        )


def pp_param_specs(base: dict) -> dict:
    """Layer-stacked params shard the stage axis on their leading (layer)
    dim; global tensors (embed, wcls, norms, rope) stay replicated on pp.
    `base` is parallel.sharding.param_spec_tree output (tp rules), whose
    layers-tree specs lead with the layer axis (None or empty = the
    replicated norms) — pp takes that axis over."""

    def with_pp(spec):
        tup = tuple(spec)
        if not tup:
            return P("pp")
        if tup[0] is not None:
            raise ValueError(
                f"layers leaf's leading (layer) axis is already sharded "
                f"({spec}); pp cannot take it over"
            )
        return P(*(("pp",) + tup[1:]))

    out = dict(base)
    out["layers"] = {k: with_pp(spec) for k, spec in base["layers"].items()}
    return out


def forward_pp(
    params,
    h: LlmHeader,
    tokens: jnp.ndarray,  # [B, T] int32
    pos: jnp.ndarray,  # scalar or [B]
    cache,  # {"k","v"}: [L, B, KH, S, hd], layer axis pp-sharded
    mesh,
    attn_window: int = 0,
    attn_park_threshold: int = 0,
    logits_mode: str = "all",
    n_micro: int = 1,
    sync_quant: bool = False,
    park_pos: int = 0,
    moe_decode_dedup: bool = False,
    live_lanes_alone: bool = False,
):
    """Pipeline-parallel forward: same contract as models.forward.

    Stage-local compute runs with mesh=None (plain kernels, no nested
    shard_map). When the mesh also carries a `tp` axis, each stage is a
    TENSOR-PARALLEL GROUP: weights arrive row/col-sliced per the same
    PartitionSpecs the flat mesh uses (pp_param_specs over
    param_spec_tree), kernels run on the local slices, and the col-split
    partial sums / MoE outputs psum over "tp" INSIDE the stage
    (run_layers tp_axis) — pp x tp is how a 70B+ checkpoint outgrows the
    tp <= nKvHeads ceiling: stages of tp groups. A `dp` mesh axis
    additionally shards the batch lanes inside every stage (tokens, pos,
    cache batch axis, logits all dp-split): pp x dp is the pipeline's
    throughput configuration — lockstep pp decode throughput is set by
    concurrent lanes (docs/pp_decode_model.md), and dp multiplies lanes
    without growing any single chip's batch. sp composition is handled
    via manual stats-merge attention (sp_axis). The manual partial-sum
    order differs from the flat
    mesh's single reduction, so low-precision (bf16) greedy streams can
    flip argmax near-ties on near-uniform logits — the same neutral
    divergence class any tensor-parallel partial summing has (f32 runs
    match the flat mesh exactly; tests pin that).

    `n_micro` > 1 splits the CHUNK (T) axis into sequence-wave
    microbatches, GPipe-style: at tick t stage s processes chunk t - s,
    so all stages work concurrently on successive chunks once the
    pipeline fills — utilization n_micro / (pp + n_micro - 1) instead of
    1/pp. Causality holds because chunk c reaches stage s only after
    chunks < c committed their KV rows at that stage (earlier ticks).
    Prefill is compute-bound, so this is where the pp bubble actually
    costs time; decode (T=1, weight-bandwidth-bound) keeps n_micro=1 —
    splitting lanes into groups would re-read the stage's weights per
    group and erase the batching win. Requires T % n_micro == 0.

    `park_pos` > 0 routes INVALID ticks' cache writes into the lane-
    padding rows at that index (the same scratch rows lane parking uses)
    instead of select-merging the whole stage cache every tick. The
    per-tick `jnp.where(valid, k_new, k_c)` reads+writes the stage's
    entire [L/pp, B, KH, S, hd] cache — on an 8B/pp=4 layout that is
    ~130 MB x2 moved per tick, comparable to the stage's weight read
    itself — while the park write touches only T rows. Causality is
    preserved because padding rows sit at indices > every real position,
    so the causal mask already excludes them from attention (identical
    to the engine's lane-parking argument). Requires the cache's S axis
    to carry >= chunk-width padding beyond `park_pos`.
    """
    from jax import shard_map

    from ..models.transformer import (
        attn_positions,
        lanes_on_one_device,
        logits_head,
        rope_slices,
        run_layers,
    )

    pp = mesh.shape["pp"]
    tp = mesh.shape.get("tp", 1)
    sp = mesh.shape.get("sp", 1)
    # dp: batch lanes shard over the dp axis INSIDE each stage — the
    # pipeline's throughput lever (docs/pp_decode_model.md: lockstep pp
    # decode throughput scales with concurrent lanes, and dp multiplies
    # lanes without growing any one chip's batch). sp: the cache's
    # sequence axis shards inside each stage; attention runs the manual
    # merged-stats math (run_layers sp_axis).
    b, t = tokens.shape
    if t % n_micro != 0:
        raise ValueError(f"T={t} not divisible by n_micro={n_micro}")
    tc = t // n_micro
    cache_s = cache["k"].shape[3]
    if park_pos and park_pos + tc > cache_s:
        # dynamic_update_slice clamps out-of-range starts silently, which
        # would divert the scratch writes onto the LAST REAL ROWS — make
        # the missing-padding case loud instead (the engine sizes the
        # cache with >= max-bucket padding whenever pp > 1)
        raise ValueError(
            f"park_pos={park_pos} needs {tc} scratch rows but the cache "
            f"sequence axis has only {cache_s} rows; allocate "
            f">= park_pos + chunk width"
        )
    attn_pos = attn_positions(pos, attn_park_threshold, cache_s)
    per_lane = jnp.ndim(pos) == 1

    layers = params["layers"]
    globals_ = {
        k: params[k]
        for k in ("embed", "wcls", "final_norm", "rope_cos", "rope_sin")
    }

    sp_ax = "sp" if sp > 1 else None
    if tp > 1:
        # per-leaf pp x tp specs: leading layer axis over stages, row/col
        # matmul splits over the stage's tp group (the flat mesh's rules,
        # parallel/sharding.param_spec_tree, pp-prefixed)
        from ..parallel.sharding import param_spec_tree

        all_specs = param_spec_tree(h)
        layer_specs = pp_param_specs(all_specs)["layers"]
        layers_spec = {k: layer_specs[k] for k in layers}
        cache_spec = P("pp", "dp", "tp", sp_ax, None)
        # wcls keeps its vocab-axis tp shard (pp-replicated): each stage's
        # tp group computes its vocab slice and all-gathers inside the
        # body (logits_head tp_axis) — passing it replicated would
        # re-all-gather the full vocab matrix onto every chip per step
        globals_spec = {k: all_specs[k] for k in globals_}
    else:
        layers_spec = P("pp")  # prefix: leading (layer) axis of every leaf
        cache_spec = P("pp", "dp", None, sp_ax, None)
        globals_spec = P()
    repl = P()
    # batch lanes shard over dp inside each stage (specs work for dp=1
    # too — the axis always exists on a pp mesh, parallel/mesh.make_mesh)
    tok_spec = P("dp", None)
    pos_spec = P("dp") if per_lane else P()
    logits_spec = P("dp", None, None)
    ring = [(i, (i + 1) % pp) for i in range(pp)]

    # logits_mode="last" (every prefill/decode step) only consumes the
    # final chunk's rows: keep a [B, tc, D] exit register instead of the
    # [B, T, D] buffer, shrinking both the HLO live range and the final
    # cross-stage psum payload by a factor of n_micro
    keep_all = logits_mode == "all"

    def body(layers, k_c, v_c, globals_, tokens, pos, attn_pos):
        stage = lax.axis_index("pp")
        d = globals_["embed"].shape[-1]
        bl = tokens.shape[0]  # dp-local batch lanes
        x0 = jnp.zeros((bl, tc, d), globals_["embed"].dtype)  # stage register
        done0 = jnp.zeros((bl, t if keep_all else tc, d), x0.dtype)

        def embed_lookup(ids):
            # vocab-sharded table under tp (param_spec_tree): each shard
            # gathers its local rows, out-of-range ids contribute zero,
            # psum assembles the [B, tc, D] rows — same manual move the
            # flat path gets from GSPMD's partitioned gather
            emb = globals_["embed"]
            if tp > 1:
                vloc = emb.shape[0]
                loc = ids - lax.axis_index("tp") * vloc
                ok = jnp.logical_and(loc >= 0, loc < vloc)
                rows = emb[jnp.clip(loc, 0, vloc - 1)]
                return lax.psum(
                    jnp.where(ok[..., None], rows, jnp.zeros_like(rows)),
                    "tp",
                )
            return emb[ids]

        def tick_body(tick, carry):
            # stage s processes chunk c = tick - s this tick (when valid);
            # stage 0 injects chunk `tick`'s embedding first. One traced
            # instance of the stage program serves every tick (the
            # schedule runs under fori_loop — unrolling would inline
            # pp + n_micro - 1 copies of the layer scan per compile).
            x, x_done, k_c, v_c = carry
            inj = lax.dynamic_slice_in_dim(
                tokens, jnp.clip(tick * tc, 0, t - tc), tc, axis=1
            )
            x = jnp.where(
                jnp.logical_and(stage == 0, tick < n_micro),
                embed_lookup(inj),
                x,
            )
            c = tick - stage
            valid = jnp.logical_and(c >= 0, c < n_micro)
            c_safe = jnp.clip(c, 0, n_micro - 1)
            pos_c = pos + c_safe * tc
            attn_pos_c = attn_pos + c_safe * tc
            if park_pos:
                # invalid ticks write their (garbage) chunk into the
                # padding scratch rows; real rows are untouched, so the
                # O(stage cache) select below collapses to a no-op
                pos_c = jnp.where(valid, pos_c, park_pos)
            cos, sin = rope_slices(globals_, pos_c, tc)
            x_out, k_new, v_new = run_layers(
                x, layers, k_c, v_c, h, pos_c, attn_pos_c, cos, sin,
                mesh=None, attn_window=attn_window,
                sync_quant=sync_quant,
                moe_decode_dedup=moe_decode_dedup,
                tp_axis="tp" if tp > 1 else None, tp_n=tp,
                sp_axis=sp_ax, sp_n=sp,
                # a micro-batch holds the admitted lane's rows as the chunk does
                live_lanes_alone=live_lanes_alone and lanes_on_one_device(mesh),
            )
            # commit this stage's cache range only for a valid chunk;
            # invalid ticks computed on pass-through/fill data (park mode:
            # their writes already landed in scratch rows)
            if park_pos:
                k_c, v_c = k_new, v_new
            else:
                # tree_map: an int8 cache is a QuantKV (values, scales) pair
                sel = lambda a, b: jnp.where(valid, a, b)  # noqa: E731
                k_c = jax.tree.map(sel, k_new, k_c)
                v_c = jax.tree.map(sel, v_new, v_c)
            x = jnp.where(valid, x_out, x)
            # a chunk finishing the LAST stage exits into the output
            # register (every stage computes the update; only the last
            # stage's is kept)
            exited = jnp.logical_and(valid, stage == pp - 1)
            if keep_all:
                x_done = jnp.where(
                    exited,
                    lax.dynamic_update_slice_in_dim(
                        x_done, x, c_safe * tc, axis=1
                    ),
                    x_done,
                )
            else:  # only the final chunk's rows feed logits_mode="last"
                x_done = jnp.where(
                    jnp.logical_and(exited, c == n_micro - 1), x, x_done
                )
            # hand the register to the next stage
            x = lax.ppermute(x, "pp", ring)
            return x, x_done, k_c, v_c

        _, x_done, k_c, v_c = lax.fori_loop(
            0, pp + n_micro - 1, tick_body, (x0, done0, k_c, v_c)
        )
        # collect the output from the last stage onto every stage
        x_done = lax.psum(
            jnp.where(stage == pp - 1, x_done, jnp.zeros_like(x_done)), "pp"
        )
        logits = logits_head(
            x_done, globals_, h, None, logits_mode,
            tp_axis="tp" if tp > 1 else None,
        )
        return logits, k_c, v_c

    logits, k_new, v_new = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            layers_spec, cache_spec, cache_spec, globals_spec, tok_spec,
            pos_spec, pos_spec,
        ),
        out_specs=(logits_spec, cache_spec, cache_spec),
        check_vma=False,
    )(layers, cache["k"], cache["v"], globals_, tokens, pos, attn_pos)
    return logits, {"k": k_new, "v": v_new}
