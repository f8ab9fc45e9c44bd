"""Pure-functional decoder forward pass for Llama / Qwen3 / Qwen3-MoE.

This is the TPU-native re-design of the reference's graph builder
(src/llm.cpp:151-605): where the reference emits an explicit per-node op
graph (segments, pipes, sync steps) interpreted by a pthread executor, here
the model is a single jit-traced function — XLA fuses what the reference
scheduled by hand, and the reference's cross-node sync points (its
SYNC_NODE_SLICES all-gather + OP_MERGE_ADD reduce = an all-reduce of the
row/col-split matmul partial sums) become sharding constraints that make XLA
insert `all-reduce` collectives over ICI (see parallel/sharding.py).

Layer walk per token (reference: src/llm.cpp:263-557):
    x += attn(rms_norm(x))     # q/k/v proj, [qk-norm,] rope, kv-cache, GQA attention, wo
    x += ffn(rms_norm(x))      # swiglu w1/w3 -> w2, or MoE gate/topk/experts
    logits = rms_norm(x) @ wcls

Shapes: tokens [B, T] -> logits [B, T, V]. The reference is B=1 with T the
prefill chunk (its `nBatches`); we keep a real batch axis as a data-parallel
surface. The KV cache is [L, B, nKvHeads, S, headDim] (HEAD-MAJOR) — the
kv-head axis is the tensor-parallel shard axis, mirroring the reference's
KV split (sliceKvCache, src/nn/nn-core.cpp:211-218), and per-head (S, hd)
planes are what the Pallas flash kernels tile (Mosaic's last-two-dims rule
rejects blocking a size-1 head dim; see ops/flash_attention.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..formats.model_file import HiddenAct, LlmHeader, RopeType, layer_table
from ..ops.jnp_ops import apply_rope, gelu, qk_rms_norm, rms_norm, silu
from ..ops.quant_matmul import (
    FusedQuantWeight,
    PackedQuantWeight,
    QuantWeight,
    dequant,
    dequant_packed,
    layer_of,
    qmatmul_tp,
)

# both Q40 device formats ride the same qmatmul dispatch (the packed
# variant unpacks nibbles in VMEM); of the expert kernels only
# `moe_held_experts_q40` reads packed leaves, so the mesh's expert kernels
# below test for plain QuantWeight alone
_QUANT_CLASSES = (QuantWeight, PackedQuantWeight)
from ..ops.flash_attention import (
    flash_attention,
    latent_flash_attention,
    pick_flash_blocks,
)
# QuantKV lives in ops/kv_cache so the flash kernels consume it natively
# (no models<->ops cycle); re-exported here for engine/cli/pipeline use.
from ..ops.kv_cache import (
    QuantKV,
    dequant_kv,
    layer_rows,
    quantize_kv_rows,
    write_rows,
)
from ..ops.short_conv import short_conv_chunk, short_conv_step
from ..ops.ssm_scan import (
    SsmShape, lane_state, put_lane_state, ssm_chunk, ssm_step, ssm_step_in_place)
from ..ops.sparse_index import index_scores, select_rows
from ..ops.moe_kernel import (
    held_forms,
    moe_active_experts,
    moe_active_experts_q40,
    moe_grouped_experts,
    moe_grouped_experts_q40,
    moe_held_experts_q40,
)

Params = Dict[str, Any]
KvCache = Dict[str, jnp.ndarray]

_NEG_INF = -1e30


def _mm(
    x: jnp.ndarray, w, role: str, mesh, sync_quant: bool = False, layer=None
) -> jnp.ndarray:
    """Matmul dispatch: dense [in, out] weights take the einsum path (GSPMD
    partitions them via the NamedSharding specs); Q40 QuantWeight leaves take
    the Pallas kernel (shard_map'd per TP role on a mesh), as a [L, in, out]
    stack and the `layer` to take. `sync_quant` Q80-compresses the col-split
    partial-sum all-reduce payload (reference: --buffer-float-type q80)."""
    if isinstance(w, _QUANT_CLASSES):
        return qmatmul_tp(
            x, w, role, mesh, sync_quant=sync_quant, layer=layer
        ).astype(x.dtype)
    return jnp.einsum("bti,io->bto", x, w)


def _mm_manual(
    x: jnp.ndarray, w, role: str, axis: str | None, sync_quant: bool = False,
    layer=None,
) -> jnp.ndarray:
    """Matmul for MANUAL-collective contexts (inside an enclosing
    shard_map, e.g. a pipeline stage's tp group): `w` is already this
    shard's local slice, the kernel runs locally, and the col-split
    partial sum all-reduces over `axis` exactly where qmatmul_tp's own
    shard_map would have psummed — in f32, downcasting AFTER the
    reduction like the flat path (rounding each partial before summing
    would compound per layer). `sync_quant` Q80-compresses the psum
    payload (the reference's --buffer-float-type q80), same as the flat
    path. `axis=None` = single-shard stage."""
    from ..ops.quant_matmul import qmatmul

    def reduce(out):
        if role == "col" and axis is not None:
            from ..parallel.collectives import psum_maybe_quantized

            return psum_maybe_quantized(out, axis, sync_quant)
        return out

    if isinstance(w, _QUANT_CLASSES):
        return reduce(qmatmul(x, w, layer)).astype(x.dtype)
    return reduce(jnp.einsum("bti,io->bto", x, w))


# what a state layer's operator reads (a gated short convolution's, a Mamba-2
# mixer's), and with it what attention's does: stacked over the layers of
# their own kind where a model has both
_STATE_LEAVES = (
    "conv_in", "conv_w", "conv_out",
    "ssm_in", "ssm_out", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias", "ssm_a_log",
    "ssm_d", "ssm_norm")
_OPERATOR_LEAVES = _STATE_LEAVES + (
    "wq", "wk", "wv", "wqkv", "wo", "wg", "q_norm", "k_norm")


def _pattern_period(flags: list) -> int:
    """The shortest period of a layer pattern: the least p with
    `flags[i] == flags[i % p]` throughout (the pattern's length at most)."""
    return next(
        p for p in range(1, len(flags) + 1)
        if all(f == flags[i % p] for i, f in enumerate(flags))
    )


def _is_quant_stack(leaf) -> bool:
    """A layer-stacked leaf that the Pallas kernels read in place: Q40
    values and scales, fused or not. Dense weights stay among the layer
    scan's `xs`."""
    if isinstance(leaf, FusedQuantWeight):
        leaf = leaf.weight
    return isinstance(leaf, _QUANT_CLASSES)


def _split_fused(out: jnp.ndarray, tp: int, dims: tuple[int, ...]):
    """Un-interleave a fused row-split matmul output [B, T, sum(dims)]
    whose columns are laid out shard-major (loader._interleave_concat):
    shard s's columns are [a_s | b_s | ...]. Returns one [B, T, dim]
    array per constituent with its global column order restored. All ops
    factor the tp-sharded axis into (tp, local) and slice the replicated
    local axis, so under GSPMD they stay shard-local."""
    b, t, total = out.shape
    locs = [d // tp for d in dims]
    assert sum(locs) * tp == total, (dims, tp, total)
    o = out.reshape(b, t, tp, sum(locs))
    parts, off = [], 0
    for dl, dg in zip(locs, dims):
        parts.append(o[..., off : off + dl].reshape(b, t, dg))
        off += dl
    return parts




def init_kv_cache(
    h: LlmHeader, batch_size: int, dtype=jnp.float32, seq_len: int | None = None,
    ring: int | None = None, ring_pad: int = 0,
) -> KvCache:
    """Allocate the KV cache (reference allocates per-layer f32 k/v buffers,
    src/llm.cpp:260-261). dtype jnp.int8 allocates the quantized layout
    (QuantKV leaves).

    A model with window layers (`layer_table`) gets two stacks side by
    side: `k`/`v` hold the full layers at `seq_len` rows, `kw`/`vw` the
    window layers as a ring of `ring` rows (default: as many, a ring that
    never wraps) between `ring_pad` spare rows before it and as many
    behind, which `run_layers` needs where chunks wrap, lanes park or a
    scan holds layers of both kinds. Each layer's `row` in the table is
    its place in its stack. A model with latent attention gets one stack
    `c` of `[L, B, 1, S, kv_lora_rank + qk_rope_head_dim]` and, where a
    learned index picks the rows a query attends to (`index_topk > 0`), a
    second, `i`, of the positions' index keys, `index_head_dim` wide. A model
    some of whose layers keep a state a lane (`h.stateful`) gets `k`/`v` over
    its attention layers alone and a state stack `s` of `[state layers, B,
    taps - 1, channels]` beside them: a gated short convolution's gated rows,
    `dim` wide, or a Mamba-2 mixer's convolution input, `ssm_conv_dim` wide,
    and then a second stack `r` of the recurrent states, float32 whatever
    `dtype` is, `[state layers, B, ssm_state_dim, ssm_n_heads * ssm_head_dim]`
    (`ops/ssm_scan.py` says why the columns lead; and every head's rows are
    one axis: with the heads an axis of their own the chip's compiler re-laid
    the whole stack out for a chunk program's products, head width major,
    2.4 GB copied in and out of every chunk)."""
    s = seq_len or h.seq_len
    if h.latent:
        # one stack of `[c | k_rope]` rows, one head for every query head:
        # no per-head keys or values are ever stored
        if dtype == jnp.int8:
            raise NotImplementedError("latent cache rows are not quantized: int8 KV")
        cache = {"c": jnp.zeros((h.n_layers, batch_size, 1, s, h.latent_row), dtype)}
        if h.indexed:
            cache["i"] = jnp.zeros((h.n_layers, batch_size, 1, s, h.index_head_dim), dtype)
        return cache
    n_window = sum(kind.window for kind in layer_table(h))
    n_conv = sum(kind.keeps_state for kind in layer_table(h))
    shape = (h.n_layers - n_window - n_conv, batch_size, h.n_kv_heads // h.kv_pack, s,
             h.head_dim * h.kv_pack)
    if dtype == jnp.int8:
        if n_conv:
            raise NotImplementedError("a state layer's state is not quantized: int8 KV")
        if n_window:
            raise NotImplementedError("window layers' ring cache is not quantized: int8 KV")

        def leaf():
            return QuantKV(
                jnp.zeros(shape, jnp.int8),
                jnp.ones(shape[:-1] + (1,), jnp.float32),
            )

        return {"k": leaf(), "v": leaf()}
    cache = {
        "k": jnp.zeros(shape, dtype=dtype),
        "v": jnp.zeros(shape, dtype=dtype),
    }
    if n_window:
        rows = (ring or s) + 2 * ring_pad
        shape = (n_window, batch_size, h.n_kv_heads, rows, h.head_dim)
        cache["kw"] = jnp.zeros(shape, dtype=dtype)
        cache["vw"] = jnp.zeros(shape, dtype=dtype)
    if n_conv:
        cache["s"] = jnp.zeros(
            (n_conv, batch_size, h.conv_state_rows, h.ssm_conv_dim or h.dim), dtype)
        if h.ssm_n_heads:
            cache["r"] = jnp.zeros(
                (n_conv, batch_size, h.ssm_state_dim, h.ssm_inner), jnp.float32)
    return cache


def _use_flash(t: int, rows: int) -> bool:
    """The Pallas flash kernels run on the chip alone, and where blocks
    divide the query and key row counts."""
    return (
        jax.default_backend() == "tpu"
        and pick_flash_blocks(t, rows) is not None
    )


def _attention_tp(
    q: jnp.ndarray,  # [B, T, H, hd]
    k_cache: jnp.ndarray,  # [L, B, KH, S, hd]: the whole stack
    v_cache: jnp.ndarray,
    layer: jnp.ndarray,  # int32 scalar: the layer to read
    pos: jnp.ndarray,
    head_dim: int,
    mesh,
    attn_window: int = 0,  # read the first `attn_window` rows only (0 = all)
) -> jnp.ndarray:
    """Attention dispatch on TPU: XLA dense attention for T=1 decode over
    the window's rows of the layer, the prefill flash kernel for T >= 8
    (blockwise online softmax, no [T, S] score materialization — the
    long-context replacement for multiheadAtt_F32), einsum elsewhere.

    Either way layer `layer` is read where it lies in the stack the layer
    scan carries: decode takes one `dynamic_slice` of the window's rows
    (`layer_rows`; the most XLA can materialise is what attention reads
    anyway), the flash kernel takes the stack whole with the layer number
    and the window as its row count. Nothing copies a layer out first.

    Decode deliberately does NOT use the Pallas flash-decode kernel: the
    round-3 silicon probe showed (a) Mosaic does
    not elide the HBM->VMEM copy when a clamped BlockSpec index repeats,
    so the kernel reads the WHOLE cache every step regardless of pos, and
    (b) XLA's own dense T=1 attention is faster on the same cache
    (0.25 vs 0.40 ms/iter on a 33 MB cache). O(pos) decode reads come
    from the engine's bucketed attn_window instead — the O(pos)
    property of the reference's decode attention
    (src/nn/nn-cpu-ops.cpp:753-788) lives in the window, not the kernel.

    Heads are the TP axis (reference: sliceMultiHeadAtt), so the kernels
    run per-shard under shard_map with no collectives.
    """
    b, t = q.shape[0], q.shape[1]
    per_lane = jnp.ndim(pos) == 1
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        # QuantKV rides into the sp shard_map quantized; the bodies
        # slice their local window first, then dequant — so int8 + sp
        # reads stay windowed AND int8-sized across the boundary
        return _attention_sp(
            q, k_cache, v_cache, layer, pos, head_dim, mesh,
            attn_window=attn_window,
        )
    s = k_cache.shape[3]
    rows = attn_window if 0 < attn_window < s else s
    if not (t >= 8 and _use_flash(t, rows)):
        # a QuantKV is dequantised after the slice: window-sized
        return _attention(
            q,
            dequant_kv(layer_rows(k_cache, layer, rows), q.dtype),
            dequant_kv(layer_rows(v_cache, layer, rows), q.dtype),
            pos, head_dim,
        )
    # QuantKV rides into the kernel natively (int8 planes + [bs, 1]
    # scale refs; dequant on the VMEM tile) — int8 prefill reads
    # ~half the HBM bytes of bf16 and never materializes a dense
    # cache copy. The scale-ref BlockSpec compiles on the v5e and
    # agrees with the plain path there (chip_smoke.py --kv-dtype int8).
    n_heads = q.shape[2]

    def kernel(qq, kk, vv, pp, ll):  # handles scalar and per-lane pos
        return flash_attention(qq, kk, vv, pp, layer=ll, rows=rows)

    if mesh is None or mesh.devices.size == 1:
        out = kernel(q, k_cache, v_cache, pos, layer)
    else:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        spec_q = P("dp", None, "tp", None)
        # the stack's spec, as the weight stacks': a leading None, and the
        # layer number replicated
        spec_kv = P(None, "dp", "tp", None, None)
        pos_spec = P("dp") if per_lane else P()
        out = shard_map(
            kernel,
            mesh=mesh,
            in_specs=(spec_q, spec_kv, spec_kv, pos_spec, P()),
            out_specs=spec_q,
            check_vma=False,
        )(q, k_cache, v_cache, pos, layer)
    return out.reshape(b, t, n_heads * head_dim)


def _attention_window(
    q: jnp.ndarray,  # [B, T, H, hd]
    k_cache: jnp.ndarray,  # [Lw, B, KH, rows, hd]: the window layers' stack
    v_cache: jnp.ndarray,
    layer: jnp.ndarray,  # int32 scalar: the layer's row in that stack
    pos: jnp.ndarray,
    head_dim: int,
    ring: int,  # position p lies at row p % ring
    window: int,  # a query sees the last `window` positions
    attn_window: int = 0,
    row0: int = 0,  # the ring's first row in the stack
) -> jnp.ndarray:
    """A window layer's attention over its ring, after the chunk's rows are
    written: the flash kernel for a chunk on the chip, XLA's dense
    attention for a decode step, each with the ring's own positions
    (`jnp_ops.ring_positions`) and the window's lower bound in its mask.
    While the engine's `attn_window` is below the ring nothing has wrapped
    and the first `attn_window` rows are all there is to read; past it the
    whole ring is read, never the context."""
    from ..ops.jnp_ops import attention_dense

    b, t, n_heads = q.shape[0], q.shape[1], q.shape[2]
    rows = min(attn_window, ring) if attn_window else ring
    if t >= 8 and _use_flash(t, rows):
        out = flash_attention(
            q, k_cache, v_cache, pos, layer=layer, rows=rows, ring=ring,
            window=window, row0=row0,
        )
    else:
        out = attention_dense(
            q, layer_rows(k_cache, layer, rows, row0),
            layer_rows(v_cache, layer, rows, row0), pos, ring=ring, window=window,
        )
    return out.reshape(b, t, n_heads * head_dim)


def latent_attention_dense(
    q: jnp.ndarray,  # [B, T, H, W]: absorbed queries `[q_nope U_h^T | q_rope]`
    rows: jnp.ndarray,  # [B, 1, S, W]: cached `[c | k_rope]`, positions in order
    pos,  # scalar or [B]: position of q[:, 0]; negative = a parked lane
    kv_rank: int,  # the rows' first `kv_rank` columns are the values
    scale: float,
    keep: jnp.ndarray | None = None,  # bool [B, T, S]: the rows an index picked
) -> jnp.ndarray:
    """Absorbed latent attention in plain XLA, [B, T, H, kv_rank]: every
    query head against the one cached head, the weighted sum over the rows'
    first `kv_rank` columns (a head's `V_h` is applied by the caller, once a
    query). f32 throughout, as `attention_stats` is: XLA fuses the cache's
    widening into the two products, which at the chip's default precision
    are one bf16 pass each, so a bf16 cache is read as bf16: per row
    2 x H x (W + kv_rank) FLOP over W x 2 bytes, the chip's ridge. A lane
    whose position is negative sees nothing and gives zeros. `keep`: a
    query attends to those of the rows it sees alone."""
    b, t = q.shape[0], q.shape[1]
    ck = rows[:, 0].astype(jnp.float32)  # [B, S, W]
    scores = jnp.einsum("bthw,bsw->bhts", q.astype(jnp.float32), ck) * scale
    q_pos = jnp.atleast_1d(jnp.asarray(pos, jnp.int32))[:, None] + jnp.arange(
        t, dtype=jnp.int32)[None, :]  # [1 or B, T]
    seen = jnp.arange(ck.shape[1], dtype=jnp.int32)[None, None, :] <= q_pos[:, :, None]
    if keep is not None:
        seen = jnp.logical_and(seen, keep)
    scores = jnp.where(seen[:, None], scores, _NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(m <= _NEG_INF / 2, 0.0, jnp.exp(scores - m))
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    return jnp.einsum("bhts,bsc->bthc", p, ck[..., :kv_rank]).astype(q.dtype)


def _attention_latent(
    q: jnp.ndarray,  # [B, T, H, W] absorbed queries
    c_cache: jnp.ndarray,  # [L, B, 1, S, W]: the latent layers' stack
    layer: jnp.ndarray,
    pos: jnp.ndarray,
    kv_rank: int,
    scale: float,
    attn_window: int = 0,
    keep: jnp.ndarray | None = None,  # bool [B or 1, T, rows]: `index_keep`'s
) -> jnp.ndarray:
    """Absorbed attention over the window's rows of layer `layer` of the
    latent stack, after the chunk's rows are written: the Pallas kernel
    for a chunk on the chip (`latent_flash_attention`: the H heads of a
    position are H query rows against the one cached head, a parked
    lane's blocks skipped), XLA's dense products for a decode step and on
    the CPU (`_attention_tp` says why decode takes no kernel). Both read
    the stack where it lies; [B, T, H, kv_rank]. `keep`: the rows an index
    picked for each query (one lane's where the program admits one: the
    others are parked and see nothing); both paths mask the rest."""
    t, n_heads = q.shape[1], q.shape[2]
    s = c_cache.shape[3]
    rows = attn_window if 0 < attn_window < s else s
    if t >= 8 and _use_flash(t * n_heads, rows):
        return latent_flash_attention(
            q, c_cache, pos, layer=layer, rows=rows, kv_rank=kv_rank, scale=scale,
            keep=keep,
        )
    # a lane at a time, as `write_rows` writes: with the lanes as a batch
    # axis of one product the chip's compiler keeps the stack in a layout of
    # its own (the lanes between the rows and the columns) and copies it
    # whole twice a step (described v5e, bf16[5,4,1,16896,576]); and each
    # lane's rows by one `dynamic_slice` of the stack itself: a static slice
    # of the lane first made it four stacks of one lane, a copy of the whole
    b, w = q.shape[0], q.shape[3]
    lane_pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    return jnp.concatenate([
        latent_attention_dense(
            q[lane : lane + 1],
            lax.dynamic_slice(c_cache, (layer, lane, 0, 0, 0), (1, 1, 1, rows, w))[0],
            lane_pos[lane], kv_rank, scale,
            keep=None if keep is None else keep[min(lane, keep.shape[0] - 1)][None],
        )
        for lane in range(b)
    ])


def index_keep(
    qi: jnp.ndarray,  # [B, T, J, dI] index queries
    w: jnp.ndarray,  # [B, T, J] f32 index head weights
    i_cache: jnp.ndarray,  # [L, B, 1, S, dI]: the index keys' stack
    layer: jnp.ndarray,
    pos: jnp.ndarray,  # scalar or [B]: position of qi[:, 0]; negative = parked
    topk: int,
    rows: int,  # score the first `rows` cached rows
    lane=None,  # traced lane number: the one lane the program admits
) -> jnp.ndarray:
    """The rows each query attends to, bool [B, T, rows] ([1, T, rows] for
    the one admitted `lane`): the `topk` of largest index score among those
    it sees (`ops/sparse_index`), after the chunk's keys are written. The
    keys of a lane are one `dynamic_slice` of the stack where it lies, a
    lane at a time as `_attention_latent` reads the rows; the selection runs
    over every lane's queries at once."""
    b, t = qi.shape[0], qi.shape[1]
    di = i_cache.shape[4]
    lane_pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    lanes = range(b) if lane is None else [lane]
    with jax.named_scope("index_score"):
        scores = jnp.stack([
            index_scores(
                lax.dynamic_index_in_dim(qi, ln, 0, keepdims=False),
                lax.dynamic_index_in_dim(w, ln, 0, keepdims=False),
                lax.dynamic_slice(i_cache, (layer, ln, 0, 0, 0), (1, 1, 1, rows, di))[0, 0, 0],
            )
            for ln in lanes
        ])  # [B or 1, T, rows]
    with jax.named_scope("index_select"):
        if lane is not None:
            lane_pos = lax.dynamic_index_in_dim(lane_pos, lane, 0, keepdims=True)
        # a parked lane's positions stay negative across the chunk
        q_pos = jnp.where(
            lane_pos[:, None] < 0, -1,
            lane_pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :])
        keep = select_rows(scores.reshape(-1, rows), q_pos.reshape(-1), topk)
    return keep.reshape(-1, t, rows)


def _attention_sp_merge(
    qq: jnp.ndarray,  # [B, T, H, hd] — full queries, replicated over sp
    kk: jnp.ndarray,  # [B, KH, S/sp, hd] — LOCAL sequence shard (cyclic)
    vv: jnp.ndarray,
    pos,  # scalar or [B] query positions (global coordinates)
    sp_axis: str,
    sp_n: int,
) -> jnp.ndarray:
    """Merged-stats sequence-parallel attention for callers ALREADY inside
    a shard_map: each sp shard computes online-softmax partial state over
    its local KV rows, merged with a log-sum-exp pmax/psum over `sp_axis`.
    Collective payload is [B, KH, G, T](+hd) — tiny next to the cache
    reads it splits. Used by the flat-mesh decode path (_attention_sp)
    and by run_layers' manual sp mode inside pipeline stages (sp_axis).

    The sequence layout is CYCLIC: shard i's local row j holds global
    position j*sp + i (strided key positions in the stats math). This is
    what makes attention windows tile the sp axis — the live prefix
    [0, pos] spreads evenly over shards, so a global window w (an sp*512
    multiple) is exactly the local prefix [0, w/sp) on every shard; with
    the contiguous block layout early shards are fully live and no
    uniform static local slice can shrink reads (engine._attn_window).
    Returns [B, T, H, hd]."""
    from ..ops.jnp_ops import attention_stats

    idx = lax.axis_index(sp_axis)
    acc, m, l = attention_stats(qq, kk, vv, pos, idx, s_stride=sp_n)
    m_g = lax.pmax(m, sp_axis)
    scale = jnp.where(m <= _NEG_INF / 2, 0.0, jnp.exp(m - m_g))
    l_g = lax.psum(l * scale, sp_axis)
    acc_g = lax.psum(acc * scale[..., None], sp_axis)
    l_safe = jnp.where(l_g == 0.0, 1.0, l_g)
    out = acc_g / l_safe[..., None]  # [b, kh, g, t, hd]
    bb, kh, g, tq, hd = out.shape
    return (
        out.transpose(0, 3, 1, 2, 4)
        .reshape(bb, tq, kh * g, hd)
        .astype(qq.dtype)
    )


def _attention_sp(
    q: jnp.ndarray,  # [B, T, H, hd]
    k_cache: jnp.ndarray,  # [L, B, KH, S, hd] — S sharded over "sp", CYCLIC
    v_cache: jnp.ndarray,
    layer: jnp.ndarray,  # int32 scalar, replicated
    pos: jnp.ndarray,
    head_dim: int,
    mesh,
    attn_window: int = 0,
) -> jnp.ndarray:
    """Sequence-parallel attention: the KV cache's sequence axis lives on
    the `sp` mesh axis (the long-context scaling axis the reference lacks —
    SURVEY.md §5 marks SP/ring absent there), in the CYCLIC row order
    (global position g at shard g % sp, local row g // sp — see
    _attention_sp_merge for why this is the windowable layout).

    Decode (T=1): every sp shard computes online-softmax partial state over
    its local KV rows, merged with a log-sum-exp pmax/psum — the collective
    payload is [B, KH, G, 1(, hd)], tiny next to the cache reads it saves.
    `pos` may be a [B] per-lane vector (continuous batching composes with
    sp): the stats math broadcasts per-lane query positions, and a parked
    lane's strongly negative sentinel masks it on every shard.

    Prefill (T % sp == 0): queries shard over sp too and the KV shards
    rotate around the ring (parallel/ring_attention.ring_attention_local,
    cyclic mode), overlapping each hop's ppermute with the local compute.

    `attn_window` (a multiple of sp) slices every shard's LOCAL prefix to
    window/sp rows before attending — O(pos) decode reads on the
    long-context axis, the same engine-window mechanism the sp=1 path
    uses. Each shard takes those rows of layer `layer` out of its part of
    the stack (`layer_rows`); the ring needs them as a buffer of its own
    to rotate.

    Heads stay tp-sharded inside the same shard_map — attention needs no
    tp collectives (reference: sliceMultiHeadAtt head independence)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel.ring_attention import ring_attention_local

    b, t, n_heads = q.shape[0], q.shape[1], q.shape[2]
    s = k_cache.shape[3]
    sp = mesh.shape["sp"]
    shard = s // sp
    w_loc = 0
    if attn_window and attn_window < s:
        if attn_window % sp:
            raise ValueError(
                f"attn_window {attn_window} must be a multiple of sp={sp}"
            )
        w_loc = attn_window // sp
    kv_spec = P(None, "dp", "tp", "sp", None)
    per_lane = jnp.ndim(pos) == 1
    pos_spec = P("dp") if per_lane else P()

    if t == 1:
        q_spec = P("dp", None, "tp", None)
        # dense jnp stats as the local step: the round-3 silicon probe
        # showed XLA's dense T=1 attention beats
        # the Pallas decode kernel and that the kernel's pos-clamped DMA
        # schedule does not actually elide copies on Mosaic — so the
        # Pallas local step (flash_decode_stats) buys nothing here

        def body(qq, kk, vv, pp, ll):
            kk = dequant_kv(layer_rows(kk, ll, w_loc), qq.dtype)
            vv = dequant_kv(layer_rows(vv, ll, w_loc), qq.dtype)
            return _attention_sp_merge(qq, kk, vv, pp, "sp", sp)

    else:
        q_spec = P("dp", "sp", "tp", None)
        # cyclic key layout: the flash-stats local step handles strided
        # key positions (ops/flash_attention s_stride), auto-selected on
        # TPU when the per-shard shapes tile. An int8 QuantKV shard rides
        # the ring QUANTIZED: the kernel dequants per-tile in VMEM, the
        # jnp fallback dequants locally, and each ppermute hop moves int8
        # payloads — halving both HBM reads and ICI traffic vs the r4
        # dense materialization. Ring hops rotate only
        # the windowed local prefix, shrinking payloads with the window.
        tq_local = t // sp
        rows_local = w_loc or shard
        use_flash = _use_flash(tq_local, rows_local)

        def body(qq, kk, vv, pp, ll):
            idx = lax.axis_index("sp")
            tq = qq.shape[1]
            kk = layer_rows(kk, ll, w_loc)
            vv = layer_rows(vv, ll, w_loc)
            return ring_attention_local(
                qq, kk, vv,
                q_pos0=pp + idx * tq,
                shard_size=jnp.int32(shard),
                axis_name="sp",
                use_flash=use_flash,
                cyclic=True,
            )

    out = shard_map(
        body,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, pos_spec, P()),
        out_specs=q_spec,
        check_vma=False,
    )(q, k_cache, v_cache, pos, layer)
    return out.reshape(b, t, n_heads * head_dim)


def _attention(
    q: jnp.ndarray,  # [B, T, H, hd]
    k_cache: jnp.ndarray,  # [B, KH, S, hd]
    v_cache: jnp.ndarray,  # [B, KH, S, hd]
    pos: jnp.ndarray,  # scalar int32: absolute position of tokens[:, 0]
    head_dim: int,
) -> jnp.ndarray:
    """Causal GQA attention over the full cache, flattened to
    [B, T, H * hd]; math lives in ops/jnp_ops.attention_dense (reference:
    multiheadAtt_F32, src/nn/nn-cpu-ops.cpp:753-788)."""
    from ..ops.jnp_ops import attention_dense

    b, t, n_heads, _ = q.shape
    out = attention_dense(q, k_cache, v_cache, pos)
    return out.reshape(b, t, n_heads * head_dim)


@dataclasses.dataclass(frozen=True)
class Routing:
    """How a layer's router turns its scores into weighted experts, and
    which of the experts it scores are held here: `n_held` from `first` of
    `n_routed`. The reference's router is the default: softmax over all,
    the chosen weights renormalised, every expert held. `n_group` and
    `topk_group`: the routed experts lie in `n_group` groups of equal size,
    and a token's experts come from the `topk_group` groups whose two best
    selection scores add up to most (1 of 1: no limit)."""

    n_active: int
    sigmoid: bool = False
    norm: bool = True
    scale: float = 1.0
    first: int = 0
    n_held: int = 0
    n_routed: int = 0
    n_group: int = 1
    topk_group: int = 1

    @property
    def shared_out(self) -> bool:
        """Some experts the router scores lie on other chips."""
        return self.n_held < self.n_routed

    def held(self, top_i: jnp.ndarray) -> jnp.ndarray:
        """Chosen ids as rows of the held experts' stack; `n_held` for a
        pair that landed on an expert held elsewhere."""
        local = top_i - self.first
        return jnp.where(
            jnp.logical_and(local >= 0, local < self.n_held), local, self.n_held
        )


def routing_of(h: LlmHeader) -> Routing:
    return Routing(
        h.n_active_experts, h.score_sigmoid, h.route_norm, h.route_scale,
        h.first_expert, h.n_experts, h.n_routed_experts, h.n_group, h.topk_group,
    )


def _moe_route(x_flat: jnp.ndarray, gate_w: jnp.ndarray, route: Routing, bias=None):
    """Shared gate routing (softmax over all experts -> top-k -> normTopk=1
    weights; reference: src/nn/nn-cpu-ops.cpp:1462-1492). `x_flat` is
    [..., D]; returns (top_i [..., k], weights [..., k]) in f32, the ids
    among all the experts the router scores. Sigmoid scores take `bias`
    [E] into the selection and never into the weights; a group limit
    (`Routing.topk_group` of `n_group`) zeroes the selection scores of the
    groups left out before the top-k."""
    logits = jnp.einsum(
        "...d,de->...e", x_flat.astype(jnp.float32), gate_w.astype(jnp.float32)
    )
    scores = jax.nn.sigmoid(logits) if route.sigmoid else jax.nn.softmax(logits, axis=-1)
    chosen_by = scores
    if route.sigmoid and bias is not None:
        chosen_by = scores + bias.astype(jnp.float32)
    if route.n_group > 1:
        grouped = chosen_by.reshape(*chosen_by.shape[:-1], route.n_group, -1)
        group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)
        _, kept = lax.top_k(group_score, route.topk_group)
        stays = jnp.any(
            kept[..., None] == jnp.arange(route.n_group, dtype=kept.dtype), axis=-2)
        chosen_by = jnp.where(stays[..., None], grouped, 0.0).reshape(chosen_by.shape)
    top_p, top_i = lax.top_k(chosen_by, route.n_active)
    if route.sigmoid:  # the weights are the scores themselves, not what chose them
        top_p = jnp.take_along_axis(scores, top_i, axis=-1)
    weights = top_p
    if route.norm:
        # a sigmoid's chosen scores may all be near 0
        total = jnp.sum(top_p, axis=-1, keepdims=True)
        weights = top_p / (total + 1e-20 if route.sigmoid else total)
    if route.scale != 1.0:
        weights = weights * route.scale
    return top_i, weights


def _moe_ffn(
    x: jnp.ndarray,  # [B, T, D]
    gate_w: jnp.ndarray,  # [D, E]
    w1: jnp.ndarray,  # [E, D, F]
    w2: jnp.ndarray,  # [E, F, D]
    w3: jnp.ndarray,  # [E, D, F]
    route: Routing,
    act,
    routed=None,
) -> jnp.ndarray:
    """MoE FFN: softmax over all experts -> top-k -> normalized weights ->
    weighted sum of expert SwiGLU outputs. `routed`: (rows of the held
    experts' stack, weights) already chosen; a row of E, an expert held
    elsewhere, adds nothing.

    (reference: the OP_SOFTMAX / OP_MOE_GATE / 3x OP_MATMUL / OP_SCALE /
    OP_MERGE_SUM chain, src/llm.cpp:425-499; gate math
    src/nn/nn-cpu-ops.cpp:1462-1492 with normTopk=1.)

    Routing is dense over experts (every expert computes, outputs are
    masked by routing weight). That is compile-friendly and exact; the
    gather/ragged fast path for decode is `_moe_ffn_pallas`.

    Quantized expert weights (QuantWeight, or PackedQuantWeight through
    `dequant_packed`) are dequantized on the fly — one layer's experts at
    a time under the scan, so the transient is one [E, D, F] bf16 tensor,
    never the whole stack.
    """
    if isinstance(w1, PackedQuantWeight):
        w1, w2, w3 = (dequant_packed(w, x.dtype) for w in (w1, w2, w3))
    elif isinstance(w1, QuantWeight):
        w1, w2, w3 = (dequant(w, x.dtype) for w in (w1, w2, w3))
    e = w1.shape[0]
    top_i, weights = routed or _moe_route(x, gate_w, route)  # [B, T, k]

    # routing matrix [B, T, E]: normalized weight where selected, else 0
    routing = jnp.sum(
        jax.nn.one_hot(top_i, e, dtype=jnp.float32) * weights[..., None], axis=2
    )

    h1 = jnp.einsum("btd,edf->btef", x, w1)
    h3 = jnp.einsum("btd,edf->btef", x, w3)
    hidden = act(h1) * h3.astype(h1.dtype)
    expert_out = jnp.einsum("btef,efd->bted", hidden, w2)
    out = jnp.einsum(
        "bted,bte->btd", expert_out.astype(jnp.float32), routing
    )
    return out.astype(x.dtype)


# Largest B*T routed through the ragged Pallas kernel: decode-lane sized.
# Beyond this, dense all-expert compute wins back (at m*k approaching E the
# per-(token, choice) DMA schedule re-reads experts the dense path reads
# once).
MOE_PALLAS_MAX_TOKENS = 16


def _expert_stacks(*ws) -> tuple[jnp.ndarray, ...]:
    """Quantized experts' values (int8, or packed words) and scales as
    [L, E, ...] stacks, in argument order; one layer's [E, ...] experts are
    a stack of one."""
    return tuple(a.reshape(-1, *a.shape[-3:]) for w in ws for a in w)


def _moe_ffn_pallas(
    x: jnp.ndarray,  # [B, T, D] with B*T <= MOE_PALLAS_MAX_TOKENS
    gate_w: jnp.ndarray,
    w1,  # [E, D, F] dense, or QuantWeight (q int8 [L, E, D, F] + d [L, E, D/32, F])
    w2,  # [E, F, D] (same)
    w3,  # [E, D, F] (same)
    route: Routing,
    mesh,
    interpret: bool = False,
    sync_quant: bool = False,
    dedup: bool = False,
    layer=0,  # which layer of quantized experts' [L, E, ...] stacks
    bias=None,
    routed=None,  # (top_i, weights) [n, k] where the caller has routed
) -> jnp.ndarray:
    """Decode-step MoE via the ragged Pallas kernel (ops/moe_kernel.py):
    each token's top-k expert ids drive the HBM->VMEM DMA schedule, so only
    active experts' weights are read — quantized blocks when the experts
    are stored Q40 (the reference's storage format, src/llm.cpp:425-499).
    TP: experts are hidden-dim sliced like the reference (w1/w3 row-split,
    w2 col-split, llm.cpp:450-487), so each shard computes its slice and
    the partial outputs psum over ICI; tokens (the engine's dp lanes) stay
    dp-sharded."""
    b, t, d = x.shape
    n = b * t
    xf = x.reshape(n, d)
    top_i, weights = routed or _moe_route(xf, gate_w, route, bias)  # [n, k]
    quantized = isinstance(w1, QuantWeight)
    # two-tier dedup (opt-in): when concurrent lanes share experts, a
    # small-grid grouped kernel reads each UNIQUE expert's tiles once.
    # The small grid must be sized statically BELOW the all-distinct
    # worst case to beat the ragged kernel's A DMA steps (static grids
    # pay empty steps' DMAs — docs/moe_decode_dedup.md), so a lax.cond
    # on the runtime unique count picks between the compiled variants.
    # The cap derives from the PER-SHARD token count (ii's local shape
    # inside a dp shard_map), else dp runs would always "fit" a grid
    # larger than their local ragged step count. Off by default pending
    # routing-correlation data from real MoE checkpoints (uniform
    # routing rarely satisfies u <= A/2).

    def _maybe_two_tier(ii, ragged_fn, grouped_fn):
        n_loc, k_loc = ii.shape
        cap = (n_loc * k_loc) // 2 if dedup and n_loc > 1 else 0
        if not cap:
            return ragged_fn()
        flat = jnp.sort(ii.reshape(-1))
        u = 1 + jnp.sum(flat[1:] != flat[:-1])
        return lax.cond(u <= cap, lambda: grouped_fn(cap), ragged_fn)

    if quantized:
        operands = (
            xf, *_expert_stacks(w1, w2, w3), top_i, weights,
            jnp.asarray(layer, jnp.int32),
        )

        def run(xx, w1q, w1d, w2q, w2d, w3q, w3d, ii, wts, ll):
            return _maybe_two_tier(
                ii,
                lambda: moe_active_experts_q40(
                    xx, w1q, w1d, w2q, w2d, w3q, w3d, ii, wts, ll,
                    interpret=interpret,
                ),
                lambda cap: moe_grouped_experts_q40(
                    xx, w1q, w1d, w2q, w2d, w3q, w3d, ii, wts, ll,
                    interpret=interpret, max_segments=cap,
                ).astype(jnp.float32),
            )

    else:
        operands = (xf, w1, w2, w3, top_i, weights)

        def run(xx, ww1, ww2, ww3, ii, wts):
            return _maybe_two_tier(
                ii,
                lambda: moe_active_experts(
                    xx, ww1, ww2, ww3, ii, wts, interpret=interpret
                ),
                lambda cap: moe_grouped_experts(
                    xx, ww1, ww2, ww3, ii, wts,
                    interpret=interpret, max_segments=cap,
                ).astype(jnp.float32),
            )

    if mesh is None or mesh.devices.size == 1:
        out = run(*operands)
    else:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        # tokens ride the dp axis (xf's flat axis folds in the dp-sharded
        # batch); expert weights ride tp exactly like the dense FFN
        tok = P("dp", None) if (n % mesh.shape.get("dp", 1) == 0 and n > 1) else P()
        row_q = P(None, None, "tp")  # w1/w3 values AND scales: F on lanes
        col_q = P(None, "tp", None)  # w2 values AND scales: F on sublanes
        if quantized:
            # a stack's layer axis is on no mesh axis; the layer is replicated
            row_q, col_q = P(None, *row_q), P(None, *col_q)
            in_specs = (tok, row_q, row_q, col_q, col_q, row_q, row_q, tok, tok, P())
        else:
            in_specs = (tok, row_q, col_q, row_q, tok, tok)

        from ..parallel.collectives import psum_maybe_quantized

        def body(*args):
            return psum_maybe_quantized(run(*args), "tp", sync_quant)

        out = shard_map(
            body,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=tok,
            check_vma=False,
        )(*operands)
    return out.reshape(b, t, d).astype(x.dtype)


def _moe_ffn_grouped(
    x: jnp.ndarray,  # [B, T, D] prefill-scale B*T
    gate_w: jnp.ndarray,
    w1,  # [E, D, F] dense or QuantWeight [L, E, D, F]
    w2,
    w3,
    route: Routing,
    mesh,
    interpret: bool = False,
    sync_quant: bool = False,
    layer=0,  # which layer of quantized experts' [L, E, ...] stacks
    bias=None,
    routed=None,  # (top_i, weights) [n, k] where the caller has routed
) -> jnp.ndarray:
    """Prefill MoE via the grouped active-expert kernel
    (ops/moe_kernel.moe_grouped_experts*): assignments sorted by expert,
    expert weights streamed once per overlapping row tile — FLOPs and
    HBM reads proportional to the ACTIVE experts, where the dense prefill
    path paid the full E/k factor. TP layout
    matches _moe_ffn_pallas: experts F-sliced over tp, partial outputs
    psum'd; routing and the schedule are computed per shard from the
    shard's tokens."""
    from ..ops.moe_kernel import (
        moe_grouped_experts,
        moe_grouped_experts_q40,
    )

    b, t, d = x.shape
    n = b * t
    xf = x.reshape(n, d)
    quantized = isinstance(w1, QuantWeight)
    # route ONCE, outside any shard_map (same as _moe_ffn_pallas): the
    # gate einsum + top_k would otherwise rerun per tp shard
    top_i, wts = routed or _moe_route(xf, gate_w, route, bias)

    def run(xx, ii, ww, *wargs):
        if quantized:
            # six weight planes, then the layer number
            return moe_grouped_experts_q40(
                xx, *wargs[:6], ii, ww, wargs[6], interpret=interpret
            )
        ww1, ww2, ww3 = wargs
        return moe_grouped_experts(
            xx, ww1, ww2, ww3, ii, ww, interpret=interpret
        )

    operands = (
        (xf, top_i, wts, *_expert_stacks(w1, w2, w3), jnp.asarray(layer, jnp.int32))
        if quantized
        else (xf, top_i, wts, w1, w2, w3)
    )
    if mesh is None or mesh.devices.size == 1:
        out = run(*operands)
    else:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ..parallel.collectives import psum_maybe_quantized

        tok = P("dp", None) if (n % mesh.shape.get("dp", 1) == 0 and n > 1) else P()
        row_q = P(None, None, "tp")
        col_q = P(None, "tp", None)
        if quantized:
            row_q, col_q = P(None, *row_q), P(None, *col_q)
            in_specs = (tok, tok, tok, row_q, row_q, col_q, col_q, row_q, row_q, P())
        else:
            in_specs = (tok, tok, tok, row_q, col_q, row_q)

        def body(*args):
            return psum_maybe_quantized(run(*args), "tp", sync_quant)

        out = shard_map(
            body,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=tok,
            check_vma=False,
        )(*operands)
    return out.reshape(b, t, d).astype(x.dtype)


def forward(
    params: Params,
    h: LlmHeader,
    tokens: jnp.ndarray,  # [B, T] int32
    pos: jnp.ndarray,  # scalar int32, or [B] per-lane positions
    cache: KvCache,
    mesh=None,
    attn_window: int = 0,
    attn_park_threshold: int = 0,
    logits_mode: str = "all",
    sync_quant: bool = False,
    moe_decode_dedup: bool = False,
    kv_ring: int = 0,
    route_stats: list | None = None,
    expert_forms: list | None = None,
    live_lanes_alone: bool = False,
    state_rows: jnp.ndarray | None = None,
    state_fresh: jnp.ndarray | None = None,
    write_floor: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, KvCache]:
    """Run the decoder on T tokens starting at absolute position `pos`.

    Returns (logits [B, T, V] f32, updated cache). Jit-safe: T is static,
    `pos` is a traced scalar. Layers run under one `lax.scan` over the
    layer number (`run_layers`), so compile time is O(1) in depth; the
    quantized weight stacks reach the kernels whole.

    `mesh` is only consulted by the quantized (Pallas) matmul path, which
    needs explicit shard_map partitioning; the dense path is GSPMD-managed
    and ignores it.

    `attn_window` (static) restricts attention reads to the first
    `attn_window` cache rows — the caller guarantees pos + T <= window.
    On a 128k-seq-len model decoding at position 1k this cuts per-step
    cache reads by 128x; cache writes still land in the full-length cache.

    `attn_park_threshold` (static, per-lane mode): lanes whose position is
    >= the threshold are PARKED — their cache writes land at that position
    (the engine's padding rows) but their attention queries are masked out
    entirely (position pushed strongly negative), so an idle or prefilling
    -elsewhere lane costs one skipped-compute block instead of a full
    cache scan, and its discarded output is exactly zero.

    `logits_mode` (static): "all" -> logits [B, T, V]; "last" -> [B, 1, V],
    computing the final norm + vocab matmul on the last chunk row only —
    prefill chunks only sample from their last row, and for small models
    the vocab matmul is a large fraction of chunk FLOPs (~25% on a
    1B/128k-vocab shape), which lands directly on TTFT.

    `live_lanes_alone` (static): the caller's program is a chunk program,
    whose lanes are admitting ones at a position and parked ones:
    `run_layers`' expert block then computes the live lanes' rows alone.

    `state_rows`, `state_fresh`, `write_floor`: a model with lane state
    alone (`run_layers` says what each means); left out, every live lane's
    state moves by the T rows, from zero at position 0.

    `route_stats` or `expert_forms` (a list, one of the two): what the
    expert layers count of this pass is appended, summed over them: the
    routed pairs (`moe_block`), or the forms their held kernel took
    (`ops/moe_kernel.held_forms`).
    """
    b, t = tokens.shape
    # `pos` may be a [B] vector: each batch lane decodes at its own
    # position (independent request lanes — the continuous-batching
    # surface the reference's single-stream loop lacks)
    names = [n for n in ("k", "v", "kw", "vw", "c", "i", "s", "r") if n in cache]
    attn_pos = attn_positions(pos, attn_park_threshold, cache[names[0]].shape[3])

    x = params["embed"][tokens]  # [B, T, D] (reference: OP_EMBEDDING)
    if h.embed_scale or h.embed_multiplier:
        x = (x.astype(jnp.float32) * (h.embed_multiplier or float(h.dim) ** 0.5)).astype(x.dtype)

    cos, sin = rope_slices(params, pos, t)
    x, *caches = run_layers(
        x, params["layers"], cache.get("k"), cache.get("v"), h, pos, attn_pos,
        cos, sin, mesh=mesh, attn_window=attn_window,
        sync_quant=sync_quant, moe_decode_dedup=moe_decode_dedup,
        kw_cache=cache.get("kw"), vw_cache=cache.get("vw"), kv_ring=kv_ring,
        route_stats=route_stats, expert_forms=expert_forms,
        c_cache=cache.get("c"), i_cache=cache.get("i"),
        live_lanes_alone=live_lanes_alone,
        **({"s_cache": cache["s"], "r_cache": cache.get("r"), "state_rows": state_rows,
            "state_fresh": state_fresh, "write_floor": write_floor} if "s" in cache else {}),
    )
    logits = logits_head(x, params, h, mesh, logits_mode)
    return logits, dict(zip(names, caches))


def lanes_on_one_device(mesh) -> bool:
    """No mesh axis splits the lanes, so one lane's rows are a local slice."""
    return mesh is None or mesh.shape.get("dp", 1) == 1


def attn_positions(pos, attn_park_threshold: int, cache_len: int):
    """Attention-query positions from cache-write positions: per-lane
    vectors with a park threshold mask parked lanes out of attention
    entirely (sentinel strongly negative for every query row of a T-wide
    chunk, hence -cache_len). Shared by `forward` and the pipeline driver
    so the park semantics cannot drift between them."""
    if jnp.ndim(pos) == 1 and attn_park_threshold:
        return jnp.where(pos >= attn_park_threshold, -cache_len, pos)
    return pos


def rope_slices(params: Params, pos: jnp.ndarray, t: int):
    """cos/sin rows for a T-wide chunk at `pos` (scalar, or [B] per-lane
    positions -> per-lane gathered [B, T, hd/2] tables)."""
    if jnp.ndim(pos) == 1:
        positions = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        return params["rope_cos"][positions], params["rope_sin"][positions]
    cos = lax.dynamic_slice_in_dim(params["rope_cos"], pos, t, axis=0)
    sin = lax.dynamic_slice_in_dim(params["rope_sin"], pos, t, axis=0)
    return cos, sin


@jax.named_scope("logits_head")
def logits_head(
    x, params: Params, h: LlmHeader, mesh, logits_mode: str,
    tp_axis: str | None = None,
):
    """Final norm + vocab matmul (reference: src/llm.cpp:560-599).

    `tp_axis`: manual-collective mode (pipeline stages): `wcls` is this
    shard's vocab slice; the local logits all-gather over the axis — the
    reference's logits gather-to-root (llm.cpp:599), moved on-chip."""
    if logits_mode not in ("all", "last"):
        raise ValueError(f"unknown logits_mode: {logits_mode!r}")
    if logits_mode == "last":
        x = x[:, -1:, :]
    y = rms_norm(x, params["final_norm"], h.norm_epsilon)
    wcls = params["wcls"]
    if tp_axis is not None:
        from ..ops.quant_matmul import qmatmul

        if isinstance(wcls, _QUANT_CLASSES):
            local = qmatmul(y, wcls)
        else:
            local = jnp.einsum(
                "btd,dv->btv", y.astype(jnp.float32),
                wcls.astype(jnp.float32),
            )
        return lax.all_gather(local, tp_axis, axis=-1, tiled=True)
    if isinstance(wcls, _QUANT_CLASSES):
        logits = qmatmul_tp(y, wcls, "row", mesh)
    else:
        logits = jnp.einsum(
            "btd,dv->btv", y.astype(jnp.float32), wcls.astype(jnp.float32)
        )
    return logits if h.logits_scaling == 1.0 else logits / h.logits_scaling


def run_layers(
    x: jnp.ndarray,  # [B, T, D]
    layers: Params,  # stacked per-layer params, [L, ...] leading axis
    k_cache: jnp.ndarray,  # [L, B, KH, S, hd]
    v_cache: jnp.ndarray,
    h: LlmHeader,
    pos: jnp.ndarray,  # scalar or [B]: cache-write positions
    attn_pos: jnp.ndarray,  # same, possibly park-masked (see forward)
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    mesh=None,
    attn_window: int = 0,
    sync_quant: bool = False,
    moe_decode_dedup: bool = False,
    tp_axis: str | None = None,
    tp_n: int = 1,
    sp_axis: str | None = None,
    sp_n: int = 1,
    kw_cache: jnp.ndarray | None = None,  # [Lw, B, KH, rows, hd]: window layers
    vw_cache: jnp.ndarray | None = None,
    kv_ring: int = 0,
    route_stats: list | None = None,
    expert_forms: list | None = None,
    c_cache: jnp.ndarray | None = None,  # [L, B, 1, S, W]: latent layers, alone
    live_lanes_alone: bool = False,
    i_cache: jnp.ndarray | None = None,  # [L, B, 1, S, dI]: their index keys
    s_cache: jnp.ndarray | None = None,  # [Ls, B, K - 1, C]: state layers' convolution rows
    r_cache: jnp.ndarray | None = None,  # [Ls, B, N, H * P] f32: Mamba-2 layers' recurrent states
    state_rows: jnp.ndarray | None = None,  # [B] int32
    state_fresh: jnp.ndarray | None = None,  # [B] bool
    write_floor: jnp.ndarray | None = None,  # int32 scalar
):
    """`lax.scan` the decoder layers over x; returns (x, k_new, v_new), and
    the window layers' (kw_new, vw_new) behind them where the model has such;
    for a model with latent attention (x, c_new), and (x, c_new, i_new) where
    an index picks the rows its queries attend to: the carry holds the stacks
    the layer table asks for and no other.

    What a layer is comes from the header's layer table (`layer_table`):
    attention in full or over a window, rope or none, a dense FFN or
    experts, and the layer's row in its cache stack. Layers of one FFN
    kind are one scan (their weights are one stack); inside it the
    attention kind is scanned data where it varies, and `lax.cond` takes
    the window branch over the ring stack or the full branch over the
    other, so each kernel compiles once a scan. A model all of whose
    layers are alike is one scan without a branch, as ever.

    The scan runs over the layer number and the small or dense per-layer
    leaves. Quantized weight stacks (`_is_quant_stack`) are not among its
    `xs`: a Pallas call is opaque to XLA, which would copy every layer's
    slice out of the stack before the kernel reads it again. The step
    closes over them and hands the kernels `(stack, l)`. The caches are
    the scan's carry, whole: a step writes its chunk's rows into layer `l`
    of the stack (`write_rows`, in place) and attention reads that layer
    where it lies (`_attention_tp`), so no layer's cache is sliced out of
    the stack or written back.

    Factored out of `forward` so the pipeline-parallel driver
    (parallel/pipeline.py) can run a STAGE'S LOCAL layer slice with
    identical math — there `layers`/caches carry L/pp layers and
    mesh=None (each stage computes locally; activations ride ppermute).

    `tp_axis`/`tp_n`: MANUAL tensor parallelism for callers already
    inside a shard_map (a pipeline stage's tp group): weights arrive as
    this shard's local slices (out dims / tp_n for row splits, kv-heads /
    tp_n on the cache), kernels run locally, and col-split partial sums
    psum over `tp_axis` — the same collective placement qmatmul_tp's own
    shard_map produces on a flat mesh. Requires mesh=None.

    `sp_axis`/`sp_n`: MANUAL sequence parallelism (pp x sp): the caches
    arrive as this shard's LOCAL rows of the CYCLIC sequence layout
    (local row j holds global position j*sp_n + shard index — the
    layout that makes attention windows tile sp, _attention_sp_merge),
    queries stay full-width and replicated over the axis. Attention is
    the merged-stats math and cache writes land on owning shards via a
    fixed-width window update + validity gather (a chunk's rows spread
    over every shard). Requires mesh=None.

    `live_lanes_alone`: what the program is, not what its rows look like: a
    chunk program holds admitting lanes, whose entry of `attn_pos` is a
    position, and parked ones. A layer's expert block (the norm before it,
    the router, the routed and the shared experts, the norm behind) then
    visits the live lanes one after another, a loop of as many trips as
    lanes are live: each lane's `[T, D]` rows are one contiguous slice of
    `x` at a traced lane number, computed as a program that admitted that
    lane alone computes them and written back; a parked lane's rows, which
    no query reads, pass the block as they came. The routing counters and
    the form counter sum over the lanes visited. A layer that keeps a state
    a lane, a latent index's mask and `write_floor` take ONE lane, the
    first live one: such a model's program admits one (`engine.
    chunk_takes_one_lane`). Where the lanes are split over devices (`dp`)
    every lane's rows are computed as before: the slice would gather
    across them.

    `s_cache`: a model some of whose layers keep a state a lane
    (`LayerKind.keeps_state`: gated short convolutions, Mamba-2 mixers)
    carries their states behind `k` and `v`, which then hold the attention
    layers alone; returns (x, k_new, v_new, s_new), and r_new, the Mamba-2
    layers' recurrent states (`r_cache`), behind them where the model has
    such. A
    state belongs to a lane and not to a position, so parking and padding
    must not move it: lane b's state advances by its first `state_rows[b]`
    rows of the T (left out: all T of a lane whose `attn_pos` is a position,
    none of a parked one), from zero where `state_fresh[b]` or the lane
    writes at position 0, which nothing precedes. `write_floor` (a chunk
    program's, which admits one lane): that lane's cache rows at positions
    below it keep what they hold (it adopted them and replays the rows
    before to rebuild its states: `runtime/engine.py`).
    The layer pattern is static: each run of one FFN kind is scanned by
    whole periods of its pattern, a period's layers unrolled in the scan's
    body, so no branch is taken on the device and an attention layer alone
    writes cache rows. In a chunk program (`live_lanes_alone`) a convolution
    layer, its FFN with it, runs over the admitted lane's rows alone.
    """
    b, t = x.shape[0], x.shape[1]
    interleaved = h.rope_type in (RopeType.LLAMA, RopeType.LLAMA3_1, RopeType.YARN)
    act = silu if h.hidden_act == HiddenAct.SILU else gelu
    per_lane = jnp.ndim(pos) == 1
    if (tp_axis is not None or sp_axis is not None) and mesh is not None:
        raise ValueError("manual tp/sp (tp_axis/sp_axis) requires mesh=None")
    latent = c_cache is not None
    if latent:
        if k_cache is not None or kw_cache is not None:
            raise ValueError("a latent cache stands alone: no k, v, kw or vw")
        if not ((mesh is None or mesh.devices.size == 1)
                and tp_axis is None and sp_axis is None):
            raise NotImplementedError(
                "latent attention layers run on one device: tp, sp, dp, pp > 1"
            )
        k_cache = c_cache  # the stack whose shape the lines below read
    if latent != layer_table(h)[0].latent or (i_cache is not None) != h.indexed:
        raise ValueError("the layer table and the cache stacks disagree on latent rows")
    stateful = s_cache is not None
    if stateful != h.stateful or (r_cache is not None) != (h.ssm_n_heads > 0):
        raise ValueError("the layer table and the cache stacks disagree on lane state")
    if stateful and not (
        (mesh is None or mesh.devices.size == 1) and tp_axis is None and sp_axis is None
    ):
        raise NotImplementedError(
            "layers with lane state run on one device: tp, sp, dp, pp > 1"
        )
    shard_s = k_cache.shape[3]  # local (per-sp-shard) sequence length
    # manual sp: the per-shard write window is t//sp_n (+1 for unaligned
    # chunk starts) local rows, capped at the whole local shard — a
    # capped window starts at 0 and still covers any chunk's overlap
    sp_win = min(t // sp_n + 1, shard_s) if sp_axis is not None else 0
    sp_idx = lax.axis_index(sp_axis) if sp_axis is not None else None
    # flat GSPMD path over an sp mesh: same cyclic layout, permuted
    # whole-axis indices (shard g%sp holds global row g at local g//sp,
    # i.e. axis index (g%sp)*shard_rows + g//sp)
    _sp_mesh = mesh.shape.get("sp", 1) if mesh is not None else 1
    _shard_rows = k_cache.shape[3] // _sp_mesh
    # per-shard head/out dims (tp_n=1 on the flat/GSPMD path)
    hq, hkv = h.n_heads // tp_n, h.n_kv_heads // tp_n
    # mesh tp size: per-shard shape checks (MoE kernel gate)
    _tp_n = mesh.shape.get("tp", 1) if mesh is not None else 1
    # the layer's weights lie whole on the device that runs this
    one_device = (mesh is None or mesh.devices.size == 1) and tp_axis is None

    table = layer_table(h)
    n_layers = jax.tree.leaves(k_cache)[0].shape[0] + (
        0 if kw_cache is None else kw_cache.shape[0]
    ) + (s_cache.shape[0] if stateful else 0)
    alike = all(
        (kind.cache, kind.rope, kind.experts) == (
            table[0].cache, table[0].rope, table[0].experts)
        for kind in table
    )
    if alike:
        # one kind of layer (a pipeline stage holds its own share of them)
        segments = [(0, n_layers)]
    else:
        if n_layers != len(table) or tp_axis is not None or sp_axis is not None:
            raise NotImplementedError(
                "layers of several kinds run whole on one mesh: no pipeline "
                "stages, manual tp or manual sp"
            )
        # maximal runs of one FFN kind: their FFN weights are one stack
        cuts = [0] + [
            l for l in range(1, n_layers)
            if table[l].experts != table[l - 1].experts
        ] + [n_layers]
        segments = list(zip(cuts[:-1], cuts[1:]))
    window = h.sliding_window
    route = routing_of(h)
    if kw_cache is not None:
        if mesh is not None and mesh.devices.size > 1:
            raise NotImplementedError(
                "window attention layers run on one device: tp, sp, dp > 1"
            )
        ring = kv_ring or kw_cache.shape[3]
        ring_pad = (kw_cache.shape[3] - ring) // 2  # spare rows before and behind
        spare_needed = jnp.ndim(attn_pos) == 1 or any(
            len({table[l].window for l in range(a, e)}) > 1 for a, e in segments
        )
        if (spare_needed or ring < h.seq_len) and (
            ring_pad < t or (spare_needed and h.seq_len + t > k_cache.shape[3])
        ):
            raise ValueError(
                f"a chunk of {t} rows needs as many spare rows on either "
                f"side of the window layers' ring of {ring} (it has "
                f"{ring_pad}: a chunk that wraps is written twice, a parked "
                f"lane's and the other cache kind's rows go there) and "
                f"behind the context's {h.seq_len} rows of the full layers' "
                f"stack (it has {k_cache.shape[3]} rows)"
            )
        if ring < min(window + t, k_cache.shape[3]):
            raise ValueError(
                f"a ring of {ring} rows cannot hold a window of {window} and "
                f"a chunk of {t}: the chunk would overwrite rows its own "
                f"first query sees"
            )
    # token rows of live lanes: what the expert block computes pairs for and
    # the routing counters count; `lone`: one live lane's rows at a time
    lone = live_lanes_alone and jnp.ndim(attn_pos) == 1 and lanes_on_one_device(mesh)
    if write_floor is not None and not (lone or b == 1):
        raise ValueError("write_floor: of a program that admits one lane (live_lanes_alone)")
    if lone:
        # the live lanes' numbers first, in the order the expert block visits
        # them; `lane`: the first, which is the one where one is admitted
        lanes_live = jnp.argsort(attn_pos < 0, stable=True).astype(jnp.int32)
        n_live = jnp.sum(attn_pos >= 0).astype(jnp.int32)
        lane = lanes_live[0]
        live_rows = jnp.broadcast_to(n_live > 0, (t,))
    elif jnp.ndim(attn_pos) == 1:
        live_rows = jnp.broadcast_to((attn_pos >= 0)[:, None], (b, t)).reshape(-1)
    else:
        live_rows = jnp.ones((b * t,), bool)
    if stateful:
        # each lane's rows that move its states, and the lanes that start from zero
        if state_rows is None:
            state_rows = jnp.where(
                jnp.broadcast_to(attn_pos, (b,)) >= 0, t, 0).astype(jnp.int32)
        state_zero = jnp.broadcast_to(pos, (b,)) == 0
        if state_fresh is not None:
            state_zero = jnp.logical_or(state_zero, state_fresh)

    def _cache_append(cache, l, val, there=None):
        """Write the chunk into layer `l` of the carried stack at each
        lane's position (reference: OP_SHIFT,
        src/nn/nn-cpu-ops.cpp:1419-1441): a `dynamic_update_slice` of the
        chunk's rows alone (`write_rows`; one a lane when positions
        differ), which XLA applies to the carry in place. `val` arrives
        [B, T, KH, hd] from the projection. An int8 cache (QuantKV)
        quantizes the rows once here and routes values and scales through
        the SAME positional writer (the scale leaf's trailing singleton
        keeps ranks equal). `there` (traced bool): where false the layer
        is of the other kind, and the rows go past the context's
        (`write_kv`)."""
        val = val.transpose(0, 2, 1, 3)  # [B, KH, T, hd]
        if isinstance(cache, QuantKV):
            qv, sv = quantize_kv_rows(val)
            return QuantKV(
                _positional_write(cache.q, l, qv),
                _positional_write(cache.s, l, sv),
            )
        if there is not None:
            return write_rows(cache, l, jnp.where(there, pos, h.seq_len), val)
        if write_floor is not None:
            # the admitted lane's rows below the floor keep what they hold:
            # read where they lie and written back with the chunk. One lane's
            # rows in one slice: for a slice a lane the chip's compiler wrote
            # every lane's rows of the whole stack anew (2.1 ms a layer)
            at = lane if lone else 0
            p = jnp.broadcast_to(pos, (b,))[at]
            old = lax.dynamic_slice(
                cache, (l, at, 0, p, 0), (1, 1, cache.shape[2], t, cache.shape[4]))[0]
            mine = lax.dynamic_slice_in_dim(val, at, 1, axis=0).astype(cache.dtype)
            keep = (p + jnp.arange(t, dtype=jnp.int32) < write_floor)[None, None, :, None]
            val = lax.dynamic_update_slice_in_dim(
                val.astype(cache.dtype), jnp.where(keep, old, mine), at, axis=0)
        return _positional_write(cache, l, val)

    def _ring_append(cache, l, val, there=None):
        """Write the chunk into row `l` of the window layers' stack as a
        ring: position p at ring row p % ring, which is row `ring_pad` +
        that of the stack: `ring_pad` spare rows lie before the ring and
        as many behind it. A chunk may run over the ring's end, and a
        `dynamic_update_slice` cannot, so the chunk is written twice,
        whole, as `write_rows` writes it: where it starts, running on into
        the spare rows behind; and one ring's length before, so that what
        ran over lands on the ring's first rows and the rest on the spare
        rows before them. A chunk that does not wrap, a parked lane (its
        query position negative) and a layer of the other kind (`there`
        false) send that second copy, or both, to the spare rows behind,
        where no query reads. (A scatter of the rows, and a read-modify-
        write with the chunk rolled into place, each made the chip's
        compiler keep the stack in another layout and copy it whole twice
        a layer: described v5e, bf16[7,8,8,5120,128].)"""
        val = val.transpose(0, 2, 1, 3)  # [B, KH, T, hd]
        live = attn_pos >= 0
        if there is not None:
            live = jnp.logical_and(live, there)
        r0, spare = pos % ring, ring_pad + ring
        cache = write_rows(cache, l, jnp.where(live, ring_pad + r0, spare), val)
        if t == 1 or not ring_pad:
            return cache  # one row cannot wrap, and a ring of the whole context never does
        wraps = jnp.logical_and(live, r0 + t > ring)
        return write_rows(
            cache, l, jnp.where(wraps, ring_pad + r0 - ring, spare), val
        )

    def _positional_write(cache, l, val):
        if sp_axis is not None:
            return _cache_append_sp(cache, l, val)
        if _sp_mesh > 1:
            return _cache_append_cyclic(cache, l, val)
        return write_rows(cache, l, pos, val)

    def _cache_append_cyclic(cache, l, val):
        """Flat-mesh sp write in the cyclic layout: global row g lives at
        axis index (g % sp) * shard_rows + g // sp. T == 1 stays a row
        update at the permuted index; T > 1 scatters the chunk's rows to
        their permuted indices of layer `l` (GSPMD routes each row to
        its owning shard)."""

        def perm(g):
            return (g % _sp_mesh) * _shard_rows + g // _sp_mesh

        if t == 1:
            return write_rows(cache, l, perm(pos), val)
        rows = jnp.arange(t, dtype=jnp.int32)
        # the index arrays lead the result's axes: [B, T] then [KH, hd]
        val = val.transpose(0, 2, 1, 3).astype(cache.dtype)
        lanes = jnp.arange(b, dtype=jnp.int32)[:, None]
        starts = pos[:, None] if per_lane else pos
        return cache.at[l, lanes, :, perm(starts + rows)].set(val)

    def _cache_append_sp(cache, l, val):
        """Owning-shard window write for the manual (pp x sp) path with
        the CYCLIC layout: this shard's local row j holds global position
        j*sp_n + sp_idx, so a chunk [p, p+T) touches a contiguous local
        range of <= T//sp_n + 1 rows; a fixed sp_win-row window at the
        clamped local start covers the whole overlap, per-row validity +
        a gather route each chunk row to its slot. The window is read out
        of layer `l` of the carried stack and written back to it:
        O(T/sp rows) per shard — no whole-slab select, no cross-shard
        collective. `pos` a scalar or [B]: the trailing axes broadcast."""
        jstart = jnp.clip(
            (pos - sp_idx + sp_n - 1) // sp_n, 0, shard_s - sp_win
        )
        cur = layer_rows(cache, l, sp_win, jstart)  # [B, KH, sp_win, hd]
        gpos = (
            jstart[..., None] + jnp.arange(sp_win, dtype=jnp.int32)
        ) * sp_n + sp_idx
        # chunk row belonging at each window row, [sp_win] or [B, sp_win]
        r = jnp.broadcast_to(gpos - pos[..., None], (b, sp_win))
        ok = jnp.logical_and(r >= 0, r < t)[:, None, :, None]
        gathered = jnp.take_along_axis(
            val, jnp.clip(r, 0, t - 1)[:, None, :, None], axis=2
        )
        return write_rows(
            cache, l, jstart, jnp.where(ok, gathered.astype(cur.dtype), cur)
        )

    phase = "decode" if t == 1 else "prefill"

    def scaled(o):
        """A block's output as its residual add takes it."""
        if h.residual_multiplier == 1.0:
            return o
        return (o.astype(jnp.float32) * h.residual_multiplier).astype(o.dtype)

    def write_kv(caches, k, v, row, is_window):
        """The chunk's keys and values into the layer's row of its stack.
        Where a scan holds layers of both kinds (`is_window` traced) each
        stack is written every layer, the other kind's at its rows past
        the live ones, where parked lanes write and no query reads: a
        write of the chunk's rows costs less than a conditional that
        hands a stack through, which XLA copies whole (1.1 GB a layer at
        8 lanes of 16k; the described v5e's compiler)."""
        if latent:
            # one row of `[c | k_rope]` (`k`; there are no values) into the
            # one stack, a parked lane's past the context as any row's; the
            # position's index key (`v`) into the stack beside it
            with jax.named_scope("kv_write"):
                return tuple(
                    _cache_append(cache, row[0], val) for cache, val in zip(caches, (k, v))
                )
        k_cache, v_cache, *ring_caches = caches
        mixed = not isinstance(is_window, bool)
        with jax.named_scope("kv_write"):
            if mixed or not is_window:
                there = jnp.logical_not(is_window) if mixed else None
                k_cache = _cache_append(k_cache, row[0], k, there)
                v_cache = _cache_append(v_cache, row[0], v, there)
            if mixed or is_window:
                kw, vw = ring_caches
                there = is_window if mixed else None
                ring_caches = [
                    _ring_append(kw, row[1], k, there),
                    _ring_append(vw, row[1], v, there),
                ]
        return (k_cache, v_cache, *ring_caches)

    def attend_full(q, caches, row):
        """Read the updated stack: the chunk's rows were written first,
        and nothing else holds the carry, so they landed in place."""
        k_cache, v_cache = caches[:2]
        with jax.named_scope("attn"), jax.named_scope(f"full_{phase}"):
            if sp_axis is not None:
                # manual sp (cyclic layout): a global window (sp multiple) is
                # the local prefix window/sp on every shard; dequant AFTER
                # slicing so int8 caches keep windowed, int8-sized reads
                if attn_window and attn_window % sp_n:
                    raise ValueError(
                        f"attn_window {attn_window} must be a multiple of "
                        f"sp={sp_n}"
                    )
                w_rows = (
                    attn_window // sp_n
                    if attn_window and attn_window < shard_s * sp_n
                    else 0
                )
                return _attention_sp_merge(
                    q,
                    dequant_kv(layer_rows(k_cache, row[0], w_rows), x.dtype),
                    dequant_kv(layer_rows(v_cache, row[0], w_rows), x.dtype),
                    attn_pos, sp_axis, sp_n,
                ).reshape(b, t, hq * h.head_dim)
            # the window's rows of layer `row`, read where they lie; the
            # sp mesh path windows inside _attention_sp per shard
            return _attention_tp(
                q, k_cache, v_cache, row[0], attn_pos, q.shape[-1], mesh,
                attn_window=attn_window,
            )

    def attend_window(q, caches, row):
        """The same over the window layers' ring stack."""
        with jax.named_scope("attn"), jax.named_scope(f"window_{phase}"):
            return _attention_window(
                q, caches[2], caches[3], row[1], attn_pos, h.head_dim, ring,
                window, attn_window=attn_window, row0=ring_pad,
            )

    def attend_latent(q, caches, row, index=None):
        """Absorbed attention over the latent stack: [B, T, H, kv_lora].
        `index`: a layer's index queries and head weights; its queries then
        attend to the `index_topk` rows of largest index score alone. While
        the window's rows are no more than that, every row is taken and the
        index is left unread."""
        keep = None
        s_rows = caches[0].shape[3]
        rows = attn_window if 0 < attn_window < s_rows else s_rows
        if index is not None and rows > h.index_topk:
            with jax.named_scope("attn"):
                keep = index_keep(
                    *index, caches[1], row[0], attn_pos, h.index_topk, rows,
                    lane=lane if lone else None,
                )
        with jax.named_scope("attn"), jax.named_scope(f"latent_{phase}"):
            return _attention_latent(
                q, caches[0], row[0], attn_pos, h.kv_lora_rank,
                h.softmax_scale, attn_window=attn_window, keep=keep,
            )

    def latent_queries_and_row(y, lp, mm):
        """The five projections' first three and both norms of a latent
        layer: (absorbed queries [B, T, H, W], the cache row [B, T, 1, W],
        the normalised query latent, which an index's queries start from).
        A query is `[q_nope U_h^T | rope(q_rope)]`, so that against the
        cached `[c | rope(k_rope)]` it scores what `q_nope . k_nope +
        q_rope . k_rope` scores, with no key rebuilt."""
        nope, kvl = h.qk_nope_head_dim, h.kv_lora_rank
        cq = rms_norm(mm(y, lp["wq_a"], "row"), lp["q_a_norm"], h.norm_epsilon)
        q = mm(cq, lp["wq_b"], "row").reshape(b, t, hq, h.head_dim)
        ckv = mm(y, lp["wkv_a"], "row")
        c = rms_norm(ckv[..., :kvl], lp["kv_a_norm"], h.norm_epsilon)
        kr = apply_rope(ckv[..., None, kvl:], cos, sin, interleaved)
        q_rope = apply_rope(q[..., nope:], cos, sin, interleaved)
        q_abs = jnp.einsum("bthn,hnc->bthc", q[..., :nope], lp["wkv_b_k"])
        return (
            jnp.concatenate([q_abs, q_rope], axis=-1),
            jnp.concatenate([c[..., None, :], kr], axis=-1),
            cq,
        )

    def index_queries_and_key(y, cq, lp, mm):
        """A layer's index: ((queries [B, T, J, dI], head weights [B, T, J]
        f32), the position's index key [B, T, 1, dI]). Queries come from the
        normalised query latent, the key from the layer's input through a
        LayerNorm (weight and bias), each with rope on its first rope
        columns in the rotate-half pairing, whatever the heads' own; the
        weights from the input in f32, times heads^-1/2 dI^-1/2."""
        n_idx, di, rd = h.index_n_heads, h.index_head_dim, h.rope_dim

        def turned(z):  # [B, T, heads, dI]
            return jnp.concatenate(
                [apply_rope(z[..., :rd], cos, sin, False), z[..., rd:]], axis=-1)

        qi = turned(mm(cq, lp["idx_wq_b"], "row").reshape(b, t, n_idx, di))
        kf = mm(y, lp["idx_wk"], "row").astype(jnp.float32)
        kf = kf - jnp.mean(kf, axis=-1, keepdims=True)
        kf = kf * lax.rsqrt(jnp.mean(kf * kf, axis=-1, keepdims=True) + h.norm_epsilon)
        ki = (kf * lp["idx_k_norm"] + lp["idx_k_bias"]).astype(y.dtype)
        w = jnp.einsum(
            "btd,dj->btj", y.astype(jnp.float32), lp["idx_w"].astype(jnp.float32)
        ) * (float(n_idx) ** -0.5 * float(di) ** -0.5)
        return (qi, w), turned(ki[..., None, :])

    def moe_block(y, lp, lf):
        """The experts' FFN of a layer whose experts' row is `lf` over the
        rows of `y` (every lane's, or the one admitted lane's), and what the
        routing counters count of it, or the form counter, where one of them
        is asked for."""
        from ..ops.moe_kernel import moe_pallas_supported

        b, t = y.shape[0], y.shape[1]

        _w1 = lp["w1"]
        # packed words are the held kernel's alone; anything else that
        # meets them takes the dense path below
        _packed = isinstance(_w1, PackedQuantWeight)
        _quantized = isinstance(_w1, _QUANT_CLASSES)
        _itemsize = 1 if _quantized else _w1.dtype.itemsize
        _f = _w1.out_dim if _quantized else _w1.shape[-1]
        bias = lp.get("expert_bias")
        # the kernels run PER-SHARD under shard_map, so the VMEM/
        # tiling gate must see the per-shard F (= F / tp), not the
        # global one — a shape legal globally can have no Mosaic-legal
        # F block per shard
        pallas_ok = (
            h.hidden_act == HiddenAct.SILU
            and jax.default_backend() == "tpu"
            and _f % _tp_n == 0
            and moe_pallas_supported(
                h.dim, _f // _tp_n, _quantized, _itemsize
            )
        )
        # route once over all the experts the router scores, and compute
        # the pairs that landed on an expert held here (all of them, where
        # no other chip shares the layer)
        routed = _moe_route(y.reshape(b * t, -1), lp["moe_gate"], route, bias)
        top_i, wts = routed
        # a parked lane's rows are one token's, 512 times over: routed,
        # they would all land on the same few experts, and a held one of
        # those would cost the kernel 28 more row tiles a layer for rows
        # nobody reads. They count as landed elsewhere.
        held_i = jnp.where(live_rows[:, None], route.held(top_i), route.n_held)
        counts = None
        if route_stats is not None:
            on = held_i < route.n_held
            touched = jnp.zeros((route.n_held + 1,), bool).at[held_i].set(
                True)[: route.n_held]
            # pairs chosen, pairs landed here, held experts touched, and token
            # rows with a pair that landed here (summed over the layers)
            counts = jnp.stack([
                jnp.sum(live_rows) * route.n_active, jnp.sum(on),
                jnp.sum(touched), jnp.sum(jnp.any(on, axis=-1)),
            ]).astype(jnp.int32)
        elif expert_forms is not None:
            counts = held_forms(held_i, route.n_held, route.n_routed, _packed)
        # one device holds the layer: each distinct quantized expert the
        # live rows touched is read once, in a chunk and a decode block
        # alike (the kernel's grid stops behind the last of them)
        if pallas_ok and _quantized and one_device:
            return moe_held_experts_q40(
                y.reshape(b * t, -1), *_expert_stacks(lp["w1"], lp["w2"], lp["w3"]),
                held_i, wts, jnp.asarray(lf, jnp.int32), n_routed=route.n_routed,
            ).reshape(b, t, -1).astype(y.dtype), counts
        if pallas_ok and not _packed:
            # more than one device, or unquantized experts: the older two
            # kernels until the held kernel is partitioned
            # (docs/moe_decode_dedup.md): one expert read a (token,
            # choice) pair at decode sizes, every row's pairs sorted by
            # expert over a static grid beyond them
            if route.shared_out:
                raise NotImplementedError(
                    "a share of the experts is computed on one device, "
                    "from quantized experts"
                )
            if b * t <= MOE_PALLAS_MAX_TOKENS:
                return _moe_ffn_pallas(
                    y, lp["moe_gate"], lp["w1"], lp["w2"], lp["w3"],
                    route, mesh, sync_quant=sync_quant,
                    dedup=moe_decode_dedup, layer=lf, bias=bias, routed=routed,
                ), counts
            return _moe_ffn_grouped(
                y, lp["moe_gate"], lp["w1"], lp["w2"], lp["w3"],
                route, mesh, sync_quant=sync_quant, layer=lf, bias=bias,
                routed=routed,
            ), counts
        # the CPU, and shapes the gate refuses: dense over the held experts
        # (XLA compiles this and fuses the slice into the dequant)
        return _moe_ffn(
            y, lp["moe_gate"],
            *(layer_of(lp[n], lf) if _quantized else lp[n]
              for n in ("w1", "w2", "w3")),
            route, act,
            routed=(held_i.reshape(b, t, -1), wts.reshape(b, t, -1)),
        ), counts

    def head_slot():
        """[H, pack] one-hot: the place of a query head's key-value head in
        its cache row (`LlmHeader.kv_pack` heads side by side)."""
        slot = (jnp.arange(hq) // (hq // hkv)) % h.kv_pack
        return jax.nn.one_hot(slot, h.kv_pack, dtype=x.dtype)

    def pack_heads(q, k, v):
        """Keys and values of neighbouring heads side by side, `kv_pack` to a
        row; a query padded with zeros to the row's width, its own columns
        where its head's lie, so that it scores its own head's keys alone;
        and times sqrt(pack), since attention scales by the row's width."""
        rows = (b, t, hkv // h.kv_pack, h.kv_pack * h.head_dim)
        scale = jnp.asarray(float(h.kv_pack) ** 0.5, jnp.float32)
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
        q = (q[..., None, :] * head_slot()[:, :, None]).reshape(b, t, hq, rows[-1])
        return q, k.reshape(rows), v.reshape(rows)

    def unpack_heads(z):
        """A head's own columns of its packed row's weighted values."""
        z = z.reshape(b, t, hq, h.kv_pack, h.head_dim)
        return jnp.sum(z * head_slot()[:, :, None], axis=3).reshape(b, t, hq * h.head_dim)

    def carried_rows(y, s_cache, srow):
        """Layer `srow`'s rows of the state stack `s` for the lanes `y` holds
        (every lane, or the admitted one of a chunk program), zero where a
        lane starts from nothing: (whether that is the admitted lane alone,
        the rows [B or 1, K - 1, C], each lane's real rows of the T, the lanes
        that start from zero, where the rows lie in the stack)."""
        rows, zero = state_rows, state_zero
        alone = y.shape[0] == 1 and b > 1
        if alone:
            state = lax.dynamic_slice(
                s_cache, (srow, lane, 0, 0), (1, 1, *s_cache.shape[2:]))[0]
            rows = lax.dynamic_slice_in_dim(rows, lane, 1)
            zero = lax.dynamic_slice_in_dim(zero, lane, 1)
        else:
            state = lax.dynamic_index_in_dim(s_cache, srow, 0, keepdims=False)
        state = jnp.where(zero[:, None, None], jnp.zeros((), state.dtype), state)
        return alone, state, rows, zero, (srow, lane if alone else 0, 0, 0)

    def conv_operator(y, lp, s_cache, srow, mm):
        """A convolution layer's operator over `y`'s rows (every lane's, or
        the admitted lane's [1, T, D]): (its output, the state stack with
        the layer's row `srow` moved on)."""
        _, state, rows, _, at = carried_rows(y, s_cache, srow)
        bcx = mm(y, lp["conv_in"], "row")
        with jax.named_scope("mix"):
            if t == 1:
                o, state = short_conv_step(bcx, lp["conv_w"], state, rows > 0)
            else:
                o, state = short_conv_chunk(bcx, lp["conv_w"], state, rows)
        o = mm(o, lp["conv_out"], "col", sync=True)
        return o, lax.dynamic_update_slice(s_cache, state[None], at)

    ssm_shape = SsmShape(
        h.ssm_n_heads, h.ssm_head_dim, h.ssm_state_dim, eps=h.norm_epsilon)

    # a decode step's live lanes, first in the order `ssm_step_in_place` visits
    live_order = None
    if h.ssm_n_heads and t == 1:
        live_order = (
            jnp.argsort(state_rows <= 0, stable=True).astype(jnp.int32),
            jnp.sum(state_rows > 0).astype(jnp.int32))

    def ssm_operator(y, lp, s_cache, r_cache, srow, mm):
        """A Mamba-2 layer's mixer over `y`'s rows (every lane's, or the
        admitted lane's [1, T, D]): (its output, both state stacks with the
        layer's row `srow` moved on). A chunk program reads and writes the
        admitted lane's slice of the stacks alone; a decode step on the chip
        the live lanes' (`ssm_step_in_place`), elsewhere every lane's, a
        parked lane's as it was."""
        alone, conv, rows, zero, at = carried_rows(y, s_cache, srow)
        zxd = mm(y, lp["ssm_in"], "row")
        # on the chip the recurrent stack is read and written by kernels alone,
        # where and as it lies (`ops/ssm_scan.lane_state` says why)
        on_chip = jax.default_backend() == "tpu"
        if t == 1 and not alone and on_chip:
            with jax.named_scope("mix"):
                o, r_cache, conv = ssm_step_in_place(
                    zxd, lp, r_cache, srow, conv, rows > 0, zero, ssm_shape, live_order)
        else:
            by_kernel = alone and on_chip
            rec = lane_state(r_cache, srow, lane) if by_kernel else lax.dynamic_slice(
                r_cache, at, (1, conv.shape[0], *r_cache.shape[2:]))[0]
            # a head's [N, P] block out of the stack's columns, and back
            heads = (*rec.shape[:2], h.ssm_n_heads, h.ssm_head_dim)
            rec = jnp.where(zero[:, None, None], 0.0, rec).reshape(heads)
            with jax.named_scope("mix"):
                if t == 1:
                    o, rec, conv = ssm_step(zxd, lp, rec, conv, rows > 0, ssm_shape)
                else:
                    o, rec, conv = ssm_chunk(zxd, lp, rec, conv, rows, ssm_shape)
            rec = rec.reshape(-1, *r_cache.shape[2:])
            r_cache = put_lane_state(r_cache, srow, lane, rec) if by_kernel else (
                lax.dynamic_update_slice(r_cache, rec[None], at))
        o = mm(o, lp["ssm_out"], "col", sync=True)
        return o, lax.dynamic_update_slice(s_cache, conv[None], at), r_cache

    def make_step(a: int, kinds, stacks, ffn_row0=None, whole=None):
        """The scan body of layers [a, a + len(kinds)), all of one FFN
        kind. `stacks`: the quantized weight stacks it closes over; `whole`:
        the dense stacks of an operator whose layers are a subset (a model
        with lane state), from which the step takes its layer's."""
        experts = kinds[0].experts
        ffn_row0 = kinds[0].ffn_row if ffn_row0 is None else ffn_row0
        # a state layer of a chunk program: the admitted lane's rows
        lane_alone = lone and kinds[0].keeps_state

        def layer_step(carry, layer):
            x, caches = carry
            lp, l, extra = layer
            lp = {**lp, **stacks}
            # the layer's row in the full and in the window layers' cache
            # stack (0 in the stack it is not of), and among its FFN kind's
            row = extra.get("row", (l, l))
            lf = l - a + ffn_row0
            is_window = extra.get("window", kinds[0].window)
            has_rope = extra.get("rope")
            counts = None
            # the layer's place among its operator's kind, whose weights are
            # stacked apart where the kinds keep different ones
            op_row = extra.get("op_row", l)
            if whole:
                lp.update({
                    name: jax.tree.map(
                        lambda v: lax.dynamic_index_in_dim(v, op_row, 0, keepdims=False),
                        leaf)
                    for name, leaf in whole.items()
                })

            def mm(yy, w, role, sync=False, ffn=False):
                # the layer counts only where `w` is one of `stacks`
                li = lf if ffn else op_row
                if tp_axis is not None:
                    return _mm_manual(yy, w, role, tp_axis, sync and sync_quant, li)
                return _mm(yy, w, role, mesh, sync and sync_quant, li)

            def swiglu(yy, prefix=""):
                """A dense SwiGLU of this layer: its own FFN, or a shared expert."""
                if prefix + "w13" in lp:
                    # fused w1|w3: the SwiGLU pair shares its input and activation
                    fw13 = lp[prefix + "w13"]
                    dl13 = mm(yy, fw13.weight, "row", ffn=True)
                    d1, l3 = _split_fused(
                        dl13, fw13.fuse // tp_n, tuple(d // tp_n for d in fw13.dims)
                    )
                    d = act(d1)
                else:
                    d = act(mm(yy, lp[prefix + "w1"], "row", ffn=True))
                    l3 = mm(yy, lp[prefix + "w3"], "row", ffn=True)
                return mm(
                    d * l3.astype(d.dtype), lp[prefix + "w2"], "col", sync=True,
                    ffn=True,
                )

            # -- attention block (reference: src/llm.cpp:263-403) --
            x_all = x
            if lane_alone:
                x = lax.dynamic_slice_in_dim(x_all, lane, 1, axis=0)  # [1, T, D]
            with jax.named_scope("norm"):
                y = rms_norm(x, lp["att_norm"], h.norm_epsilon)
            if kinds[0].conv:
                # a gated short convolution stands where attention would (and
                # in its scope: a profile's reduction that knows the layers'
                # scopes by name would read any other as the scan's own
                # copying): no cache row is written, the lane's state moves on
                with (jax.named_scope("attn"), jax.named_scope("conv"),
                      jax.named_scope(phase)):
                    o, s_new = conv_operator(y, lp, caches[2], op_row, mm)
                    x = x + o.astype(x.dtype)
                caches = (*caches[:2], s_new)
            elif kinds[0].ssm:
                # a Mamba-2 mixer, in attention's scope as the convolution is
                with (jax.named_scope("attn"), jax.named_scope("ssm"),
                      jax.named_scope(phase)):
                    o, s_new, r_new = ssm_operator(y, lp, caches[2], caches[3], op_row, mm)
                    x = x + scaled(o.astype(x.dtype))
                caches = (*caches[:2], s_new, r_new)
            else:
                with jax.named_scope("attn"):
                    gate = None
                    if kinds[0].latent:
                        # the cache row stands where the keys do; there are no values
                        with jax.named_scope("latent_proj"):
                            q, k, cq = latent_queries_and_row(y, lp, mm)
                        v = index = None
                        if "idx_wk" in lp:  # the index key is cached where values would be
                            with jax.named_scope("index_proj"):
                                index, v = index_queries_and_key(y, cq, lp, mm)
                    elif "wqkv" in lp:
                        # fused q|k|v: one kernel launch reads y once (7 -> 4 launches
                        # per decode layer at ~41 us fixed cost each on the round-3
                        # chip run). The un-interleave factor is the
                        # weight's own static metadata, not the mesh's tp — a fused-
                        # load/mesh mismatch stays correct (just non-optimally laid
                        # out) instead of silently permuting columns. Under manual tp
                        # the shard's local slice is one interleave chunk (the shard-
                        # major layout puts shard i's [q_i|k_i|v_i] in chunk i), so
                        # the local split factor is fuse / tp_n. A gate on the
                        # attention output is a fourth constituent.
                        fw = lp["wqkv"]
                        if fw.fuse % tp_n != 0:
                            raise ValueError(
                                f"fused weight interleave {fw.fuse} incompatible with "
                                f"manual tp_n={tp_n}"
                            )
                        qkv = mm(y, fw.weight, "row")
                        q, k, v, *gate = _split_fused(
                            qkv, fw.fuse // tp_n, tuple(d // tp_n for d in fw.dims)
                        )
                        gate = gate[0] if gate else None
                        q = q.reshape(b, t, hq, h.head_dim)
                        k = k.reshape(b, t, hkv, h.head_dim)
                        v = v.reshape(b, t, hkv, h.head_dim)
                    else:
                        q = mm(y, lp["wq"], "row").reshape(b, t, hq, h.head_dim)
                        k = mm(y, lp["wk"], "row").reshape(b, t, hkv, h.head_dim)
                        v = mm(y, lp["wv"], "row").reshape(b, t, hkv, h.head_dim)
                        if "wg" in lp:
                            gate = mm(y, lp["wg"], "row")
                    if "q_norm" in lp:
                        q = qk_rms_norm(q, lp["q_norm"], h.norm_epsilon)
                        k = qk_rms_norm(k, lp["k_norm"], h.norm_epsilon)
                    if has_rope is not None:
                        q = jnp.where(has_rope, apply_rope(q, cos, sin, interleaved), q)
                        k = jnp.where(has_rope, apply_rope(k, cos, sin, interleaved), k)
                    elif kinds[0].rope:
                        q = apply_rope(q, cos, sin, interleaved)
                        k = apply_rope(k, cos, sin, interleaved)
                    if h.attention_multiplier:
                        # the kernels scale scores by head_dim^-1/2: the
                        # queries carry what the stated scale differs by
                        q = (q.astype(jnp.float32) * (
                            h.attention_multiplier * float(h.head_dim) ** 0.5)).astype(q.dtype)

                if h.kv_pack > 1:
                    with jax.named_scope("attn"):
                        q, k, v = pack_heads(q, k, v)
                caches = write_kv(caches, k, v, row, is_window)
                if kinds[0].latent:
                    z = attend_latent(q, caches, row, index)
                    with jax.named_scope("attn"), jax.named_scope("latent_proj"):
                        # a head's values from the weighted latents, once a query
                        z = jnp.einsum("bthc,hcv->bthv", z, lp["wkv_b_v"]).reshape(
                            b, t, hq * h.v_head_dim)
                elif not isinstance(is_window, bool):
                    # the stacks go in and only the attention's output comes out
                    z = lax.cond(is_window, attend_window, attend_full, q, caches, row)
                elif is_window:
                    z = attend_window(q, caches, row)
                else:
                    z = attend_full(q, caches, row)

                if h.kv_pack > 1:
                    with jax.named_scope("attn"):
                        z = unpack_heads(z)
                with jax.named_scope("attn"):
                    if gate is not None:
                        with jax.named_scope("gate"):
                            z = (
                                z.astype(jnp.float32)
                                * jax.nn.sigmoid(gate.astype(jnp.float32))
                            ).astype(z.dtype)
                    o = mm(z, lp["wo"], "col", sync=True).astype(x.dtype)
                    if "post_att_norm" in lp:
                        o = rms_norm(o, lp["post_att_norm"], h.norm_epsilon)
                    x = x + scaled(o)

            # -- FFN block (reference: src/llm.cpp:405-557) --
            def ffn_block(x):
                """The layer's FFN over `x`'s rows and the residual add: (x,
                what `moe_block` counted)."""
                counts = None
                with jax.named_scope("norm"):
                    y = rms_norm(x, lp["ffn_norm"], h.norm_epsilon)
                with jax.named_scope("moe" if experts else "ffn"):
                    if experts:
                        with jax.named_scope(phase):
                            with jax.named_scope("routed"):
                                f, counts = moe_block(y, lp, lf)
                            if tp_axis is not None:
                                # manual tp: experts arrived F-sliced (same layout the
                                # mesh path shards); the local partial outputs all-reduce
                                # here instead of inside the helpers' shard_map
                                f = lax.psum(f, tp_axis)
                            if "shared_w2" in lp:
                                with jax.named_scope("shared"):
                                    f = f + swiglu(y, "shared_").astype(f.dtype)
                    else:
                        f = swiglu(y)
                    f = f.astype(x.dtype)
                    if "post_ffn_norm" in lp:
                        f = rms_norm(f, lp["post_ffn_norm"], h.norm_epsilon)
                    return x + scaled(f), counts

            if lane_alone:
                x, counts = ffn_block(x)
                x = lax.dynamic_update_slice_in_dim(x_all, x, lane, axis=0)
            elif experts and lone:
                # experts of a chunk program: over the live lanes' rows alone,
                # a lane a trip, each as a program of its own computes them
                def visit(i, carry):
                    x_all, counts = carry
                    at = lanes_live[i]
                    x, c = ffn_block(lax.dynamic_slice_in_dim(x_all, at, 1, axis=0))
                    with jax.named_scope("moe"):  # the write-back fuses the residual add
                        x_all = lax.dynamic_update_slice_in_dim(x_all, x, at, axis=0)
                    return x_all, None if c is None else counts + c

                none_yet = jax.tree.map(
                    lambda c: jnp.zeros(c.shape, c.dtype),
                    jax.eval_shape(lambda rows: ffn_block(rows)[1], x[:1]))
                x, counts = lax.fori_loop(0, n_live, visit, (x, none_yet))
            else:
                x, counts = ffn_block(x)
            return (x, caches), counts

        return layer_step

    # scopes name the device's operations in a profile (`op_name`) and
    # change nothing that is compiled: what runs under `layers` but under
    # no scope of `layer_step` is the scan's own slicing of its `xs`,
    # which are the norms, a dense model's weights and the layer number.
    # The caches are the scan's carry: as `xs` and `ys` every layer's whole
    # lane cache was copied out of the stack and back to write a row a lane
    caches = (
        (c_cache,) if i_cache is None else (c_cache, i_cache)
    ) if latent else (
        (k_cache, v_cache, s_cache) if r_cache is None
        else (k_cache, v_cache, s_cache, r_cache)) if stateful else (
        k_cache, v_cache) if kw_cache is None else (
        k_cache, v_cache, kw_cache, vw_cache)
    counted = []
    for a, e in segments:
        kinds = [table[0] if alike else table[l] for l in range(a, e)]
        whole = (a, e) == (0, n_layers)
        r0 = kinds[0].ffn_row
        if not alike and [kind.ffn_row for kind in kinds] != list(range(r0, r0 + e - a)):
            raise ValueError(f"layers [{a}, {e}) are not one run of their FFN stack")
        # beside experts, the leading dense layers' FFN is stacked apart
        # under `dense_`; a scan sees its own kind's under the plain names
        mine = {}
        for name, leaf in layers.items():
            of_ffn = len(segments) > 1 and (
                name.startswith(("dense_", "shared_"))
                or name in ("w1", "w2", "w3", "w13", "moe_gate", "expert_bias")
            )
            if of_ffn and name.startswith("dense_") == kinds[0].experts:
                continue  # the other kind's
            lo, n = (r0, jax.tree.leaves(leaf)[0].shape[0]) if of_ffn else (a, n_layers)
            if stateful and name in _OPERATOR_LEAVES:
                pass  # stacked over the layers of its kind: taken by `op_row`
            elif not _is_quant_stack(leaf) and (lo, e - a) != (0, n):
                leaf = jax.tree.map(lambda v: v[lo : lo + e - a], leaf)
            mine[name.removeprefix("dense_") if of_ffn else name] = leaf
        stacks = {k: v for k, v in mine.items() if _is_quant_stack(v)}
        sliced = {k: v for k, v in mine.items() if k not in stacks}
        if stateful:
            # the pattern is static: scan by whole periods of it, a period's
            # layers unrolled in the body, each with its own kind's step
            op_whole = {k: sliced.pop(k) for k in list(sliced) if k in _OPERATOR_LEAVES}
            op_rows = jnp.asarray([kind.row for kind in kinds], jnp.int32)
            xs = (sliced, jnp.arange(a, e, dtype=jnp.int32),
                  {"op_row": op_rows, "row": (op_rows, op_rows)})
            period = _pattern_period([kind.keeps_state for kind in kinds])
            done = 0
            for n_groups, width in (((e - a) // period, period), (1, (e - a) % period)):
                if not n_groups * width:
                    continue
                steps = [
                    make_step(
                        a, [kinds[done + j]],
                        {k: v for k, v in stacks.items()
                         if k not in _OPERATOR_LEAVES
                         or (k in _STATE_LEAVES) == kinds[done + j].keeps_state},
                        ffn_row0=r0,
                        whole={k: v for k, v in op_whole.items()
                               if (k in _STATE_LEAVES) == kinds[done + j].keeps_state},
                    )
                    for j in range(width)
                ]

                def group_step(carry, group, steps=steps):
                    counts = None
                    for j, step in enumerate(steps):
                        carry, c = step(carry, jax.tree.map(lambda v: v[j], group))
                        if c is not None:
                            counts = c if counts is None else counts + c
                    return carry, counts

                lo, hi = done, done + n_groups * width
                with jax.named_scope("layers"):
                    (x, caches), counts = lax.scan(
                        group_step, (x, caches),
                        jax.tree.map(
                            lambda v: v[lo:hi].reshape(n_groups, width, *v.shape[1:]), xs),
                    )
                if counts is not None:
                    counted.append(counts.sum(axis=0))
                done = hi
            continue
        extra = {}
        if not alike:
            extra["row"] = tuple(
                jnp.asarray([kind.row * (kind.window == w) for kind in kinds], jnp.int32)
                for w in (False, True)
            )
        for flag in ("window", "rope"):
            if len({getattr(kind, flag) for kind in kinds}) > 1:
                extra[flag] = jnp.asarray([getattr(kind, flag) for kind in kinds])
        with jax.named_scope("layers"):
            (x, caches), counts = lax.scan(
                make_step(a, kinds, stacks),
                (x, caches),
                (sliced, jnp.arange(a, e, dtype=jnp.int32), extra),
            )
        if counts is not None:
            counted.append(counts.sum(axis=0))
    if counted:
        (expert_forms if route_stats is None else route_stats).append(sum(counted))
    return (x, *caches)
