"""Causal GQA flash attention over the positional KV cache (Pallas TPU).

Replaces the reference's multiheadAtt_F32 (src/nn/nn-cpu-ops.cpp:753-788)
for prefill: the reference materializes a per-head [seqLen] score row per
query (O(T*S) memory); blockwise online-softmax keeps everything in VMEM
tiles, which is what makes 100k+ context feasible (SURVEY.md §5 calls this
out as the biggest idiomatic upgrade over the reference).

Semantics match models/transformer._attention exactly:
  * queries at absolute positions pos..pos+T-1 attend to cache rows
    0..q_pos (causal, inclusive);
  * GQA: q head h reads kv head h // (H // KH);
  * f32 softmax/accumulation, bf16/f32 inputs.

Kernel layout: grid (B * H, T blocks, S blocks), S innermost so the online
softmax state (m, l, acc) lives in VMEM scratch across S steps. S blocks
entirely above the causal frontier are compute-skipped via pl.when, and
their kv index map is clamped to the causal frontier. NOTE (round-3
silicon finding): Mosaic does NOT elide the
HBM->VMEM copy when a block index repeats, so the clamp bounds COMPUTE
but not DMA traffic — per-call cache reads are O(S), which is why the
engine bounds decode reads with bucketed attn_window slicing instead and
uses these kernels only where blockwise softmax itself is the win
(prefill's [T, S] score materialization). The cache is HEAD-MAJOR
[B, KH, S, hd]: each grid step's kv tile is a (block_s, hd) plane of one
head, which satisfies Mosaic's last-two-dims tiling rule for any head_dim
(a [B, S, KH, hd] layout would need an illegal size-1 head block inside
the last two dims — rejected on real silicon) and avoids
(KH, hd) -> (8, 128) tile padding in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kv_cache import QuantKV, layer_rows

_NEG_INF = -1e30


def pick_flash_blocks(t: int, s: int, max_t: int = 256) -> tuple[int, int] | None:
    """(block_t, block_s) that divide the shapes, or None when the flash
    kernel can't run them (callers then fall back to dense attention).
    block_t: largest multiple of 8 <= `max_t` dividing t; block_s: largest
    multiple of 128 <= 512 dividing s."""
    bt = next((b for b in range(min(max_t, t), 0, -8) if t % b == 0), None)
    bs = next((b for b in range(min(512, s - s % 128), 0, -128) if s % b == 0), None)
    if not bt or not bs:
        return None
    return bt, bs


def attention_ref(
    q: jnp.ndarray,  # [B, T, H, hd]
    k_cache: jnp.ndarray,  # [B, KH, S, hd]
    v_cache: jnp.ndarray,  # [B, KH, S, hd]
    pos: jnp.ndarray,  # scalar int32
) -> jnp.ndarray:
    """jnp reference: the canonical masked-softmax math from ops/jnp_ops
    (same source the model's dense path and ring attention use)."""
    from .jnp_ops import attention_dense

    return attention_dense(q, k_cache, v_cache, pos)


def _flash_stats_kernel(
    pos_ref,  # SMEM scalar prefetch: [B] int32 per-lane q start positions
    spos_ref,  # SMEM scalar prefetch: [1] int32 (s_pos0)
    l_ref,  # SMEM scalar prefetch: [1] int32 layer, read by the index maps
    q_ref,  # [1, bt, hd]
    k_ref,  # [1, 1, bs, hd] — one head's (seq, hd) plane of that layer
    v_ref,  # [1, 1, bs, hd]
    *rest,  # quant_kv: (ks_ref [1,1,bs,128], vs_ref [1,1,bs,128]); then
    #         outputs (acc_out [1,bt,hd], m_out [1,bt,128], l_out
    #         [1,bt,128]) and scratch (m_ref, l_ref, acc_ref)
    block_t: int,
    block_s: int,
    n_s: int,
    n_heads: int,
    scale: float,
    s_stride: int = 1,
    quant_kv: bool = False,
    ring: int = 0,
    window: int = 0,
    t_total: int = 0,
):
    """Like _flash_kernel but emits UNNORMALIZED online-softmax partial
    state (acc, m, l) — the drop-in local step for ring attention's
    log-sum-exp merge (parallel/ring_attention.py). Query positions are
    per LANE (pos_ref[b]); a lane position <= -T keeps EVERY query row of
    the chunk negative (the engine's parked lanes use -(cache length)),
    producing fully-masked stats at one block of DMA. A bare -1 would
    only mask the first row of a multi-row chunk. `s_stride` > 1: the
    key rows are a CYCLIC sequence shard (row j at global position
    s_pos0 + j*stride — the windowable sp layout, see
    models/transformer._attention_sp_merge); positions and the causal
    frontier scale by the stride. `quant_kv`: k/v tiles arrive int8 with
    per-row f32 scales as two extra [bs, 128]-blocked refs (every lane
    holds the row's scale; column 0 is read) that follow the kv index
    map — dequant happens HERE on the VMEM tile, so HBM traffic is the
    int8 bytes, amortized over the tile's bt queries. `ring` > 0: the key
    rows are a ring (position p at row p % ring, `jnp_ops.ring_positions`)
    that holds the lane's chunk, `t_total` rows, as its latest positions;
    `window` > 0: a query sees the last `window` positions only. A block
    none of whose rows a query of the tile can see is skipped: above the
    causal frontier or below the window while the ring has not wrapped,
    and all of a parked lane's."""
    if quant_kv:
        ks_ref, vs_ref, acc_out, m_out, l_out, m_ref, l_ref, acc_ref = rest
    else:
        acc_out, m_out, l_out, m_ref, l_ref, acc_ref = rest
    ti = pl.program_id(1)
    si = pl.program_id(2)
    q_pos0 = pos_ref[pl.program_id(0) // n_heads] + ti * block_t
    s_pos0 = spos_ref[0]

    @pl.when(si == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    s_start = s_pos0 + si * block_s * s_stride
    visible = s_start <= q_pos0 + block_t - 1
    if ring:
        lane_last = pos_ref[pl.program_id(0) // n_heads] + t_total - 1
        below = s_start + block_s - 1 <= q_pos0 - window if window else False
        visible = jnp.logical_and(
            lane_last >= 0,
            jnp.logical_or(
                lane_last >= ring,
                jnp.logical_and(visible, jnp.logical_not(below)),
            ),
        )

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        if quant_kv:
            k = k * ks_ref[0, 0, :, :1]  # (bs, 1) per-row scales, lane-broadcast
        scores = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )
        q_pos = q_pos0 + jax.lax.broadcasted_iota(jnp.int32, (block_t, block_s), 0)
        s_pos = s_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_t, block_s), 1
        ) * s_stride
        seen = s_pos <= q_pos
        if ring:  # a live lane: lane_last - s_pos + ring > 0
            s_pos = lane_last - jax.lax.rem(lane_last - s_pos + ring, ring)
            seen = jnp.logical_and(s_pos <= q_pos, s_pos >= 0)
        if window:
            seen = jnp.logical_and(seen, q_pos - s_pos < window)
        scores = jnp.where(seen, scores, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        # fully-masked tiles keep exp(-inf - -inf) out of the stats
        p = jnp.where(m_new <= _NEG_INF / 2, 0.0, p)
        alpha = jnp.where(m_prev <= _NEG_INF / 2, 0.0, alpha)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        if quant_kv:
            v = v * vs_ref[0, 0, :, :1]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(si == n_s - 1)
    def _emit():
        acc_out[0] = acc_ref[:]
        m_out[0] = m_ref[:]
        l_out[0] = l_ref[:]


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_t", "block_s", "interpret", "s_stride", "rows", "ring", "window",
        "row0",
    ),
)
def flash_attention_stats(
    q: jnp.ndarray,  # [B, T, H, hd]
    k: jnp.ndarray,  # [B, KH, S, hd], or the [L, B, KH, S, hd] cache stack
    v: jnp.ndarray,
    q_pos0: jnp.ndarray,  # scalar or [B] int32: position of q[:, 0] per lane
    s_pos0: jnp.ndarray,  # scalar int32: absolute position of k[:, 0]
    block_t: int = 0,
    block_s: int = 0,
    interpret: bool = False,
    s_stride: int = 1,
    layer=None,  # int32 scalar: which layer of a stack
    rows: int = 0,  # attend to the first `rows` key rows only (0 = all S)
    ring: int = 0,  # the rows are a ring of this many positions (0: in order)
    window: int = 0,  # a query sees the last `window` positions (0: all)
    row0: int = 0,  # the first key row read (whole blocks)
):
    """Blockwise causal GQA attention partial state: returns f32
    (acc [B, KH, G, T, hd], m [B, KH, G, T], l [B, KH, G, T]) — the same
    contract as ops/jnp_ops.attention_stats, MXU-tiled. A vector q_pos0
    gives each lane its own query start (per-lane prefill); a strongly
    negative lane position masks that lane entirely at one block of DMA.
    `s_stride` > 1 treats the key rows as a cyclic sequence shard (row j
    at global position s_pos0 + j*stride) — the sp layout whose windows
    tile shards; masks and the causal-frontier DMA clamp scale by it.

    `k`/`v` may be QuantKV (int8 values + f32 [.., S, 1] per-row scales):
    the kernel then DMAs the int8 planes plus a [bs, 128]-blocked scale ref
    and dequants on the VMEM tile — int8 prefill reads ~half the HBM
    bytes of bf16 and never materializes a dense cache copy (the pre-r5
    behavior).

    The model hands in its whole cache `[L, B, KH, S, hd]` with the `layer`
    to read and the attention window as `rows`: the stack stays where it
    lies in HBM, the layer number rides in as scalar prefetch and the kv
    index maps pick the layer, and the grid covers `rows` key rows — so a
    layer scan that carries the cache copies neither a layer nor a window
    out of it ahead of this call (which XLA cannot fuse into). A
    `[B, KH, S, hd]` argument (ring attention, tests) is a stack of one.

    `ring` and `window` are a window layer's cache (`_flash_stats_kernel`):
    the lane's chunk has been written, so the ring's latest position is
    `q_pos0 + T - 1`, and no block index is clamped, since a ring that has
    wrapped holds rows a query sees in every block."""
    quant_kv = isinstance(k, QuantKV)
    if ring and (s_stride != 1 or quant_kv):
        raise NotImplementedError("a ring cache is dense and unstrided")
    if isinstance(v, QuantKV) != quant_kv:
        raise TypeError(
            f"k and v must both be QuantKV or both dense, got "
            f"k={type(k).__name__}, v={type(v).__name__}"
        )
    if len(k.shape) == 4:
        assert layer is None, "a layer number needs a [L, B, KH, S, hd] stack"
        k, v = jax.tree.map(lambda a: a[None], (k, v))
        layer = 0
    b, t, h, hd = q.shape
    kh, s = k.shape[2], rows or k.shape[3] - row0
    assert row0 + s <= k.shape[3], (row0, rows, k.shape)
    g = h // kh
    if not block_t or not block_s:
        picked = pick_flash_blocks(t, s)
        if picked is None:
            if not interpret:
                # same contract as flash_attention: Mosaic needs aligned
                # tiles; callers fall back to the dense path
                raise ValueError(
                    f"no valid flash blocks for t={t}, s={s}; use dense attention"
                )
            picked = (t, s)  # interpret-mode tests: single tile is fine
        auto_t, auto_s = picked
        block_t = block_t or auto_t
        block_s = block_s or auto_s
    assert t % block_t == 0 and s % block_s == 0, (t, s, block_t, block_s)
    assert row0 % block_s == 0, (row0, block_s)
    n_t = t // block_t
    n_s = s // block_s
    scale = 1.0 / (hd**0.5)

    # queries transpose is chunk-sized (cheap); the cache is consumed in
    # its storage layout [B, KH, S, hd] — no copy of the S rows is ever
    # materialized
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, t, hd)
    pos_arr = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(q_pos0, jnp.int32)), (b,)
    )
    spos_arr = jnp.asarray(s_pos0, jnp.int32).reshape(1)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_map(bh, ti, si, pos_ref, spos_ref, l_ref):
        return (bh, ti, 0)

    def kv_map(bh, ti, si, pos_ref, spos_ref, l_ref):
        # clamp past the causal frontier of this query tile (fully-masked
        # tiles re-fetch the frontier block: compute is skipped but Mosaic
        # does not elide the repeated-index DMA — see module docstring);
        # strided shards divide the frontier by the stride first
        limit = jnp.maximum(
            (pos_ref[bh // h] + (ti + 1) * block_t - 1 - spos_ref[0])
            // s_stride
            // block_s,
            0,
        )
        if ring:
            limit = n_s - 1
        return (
            l_ref[0], bh // h, (bh % h) // g,
            row0 // block_s + jnp.minimum(si, limit), 0,
        )

    in_specs = [
        pl.BlockSpec((1, block_t, hd), q_map),
        pl.BlockSpec((None, 1, 1, block_s, hd), kv_map),
        pl.BlockSpec((None, 1, 1, block_s, hd), kv_map),
    ]
    operands = [qt, k, v]
    if quant_kv:
        # the scales of this layer's `s` rows, sliced out of the stack and
        # spread over the 128 lanes: the chip stores a [.., S, 1] leaf with
        # S minor, and a kernel operand of that shape takes (8, 128) tiles,
        # 128 times the bytes — demanded of a whole stack that the layer
        # scan carries, that is gigabytes of temporaries (described-v5e
        # compile, 32 x [5, 8, 4608, 1]: 6.6 GB). Spread, the operand is a
        # shape of its own and as large as those tiles were for one layer.
        def scale_rows(c):  # [L, B, KH, S, 1] -> [1, B, KH, s, 128]
            r = layer_rows(c.s, layer, s)[None]
            return jnp.broadcast_to(r, r.shape[:-1] + (128,))

        def scale_map(*idx):  # the value planes' map, in a stack of one
            return (0, *kv_map(*idx)[1:])

        in_specs += [
            pl.BlockSpec((None, 1, 1, block_s, 128), scale_map),
            pl.BlockSpec((None, 1, 1, block_s, 128), scale_map),
        ]
        operands = [qt, k.q, v.q, scale_rows(k), scale_rows(v)]
    acc, m, l = pl.pallas_call(
        functools.partial(
            _flash_stats_kernel,
            block_t=block_t,
            block_s=block_s,
            n_s=n_s,
            n_heads=h,
            scale=scale,
            s_stride=s_stride,
            quant_kv=quant_kv,
            ring=ring,
            window=window,
            t_total=t,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b * h, n_t, n_s),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_t, hd), q_map),
                pl.BlockSpec((1, block_t, 128), q_map),
                pl.BlockSpec((1, block_t, 128), q_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_t, 128), jnp.float32),
                pltpu.VMEM((block_t, 128), jnp.float32),
                pltpu.VMEM((block_t, hd), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, hd), jnp.float32),
            jax.ShapeDtypeStruct((b * h, t, 128), jnp.float32),
            jax.ShapeDtypeStruct((b * h, t, 128), jnp.float32),
        ],
        interpret=interpret,
    )(pos_arr, spos_arr, layer_arr, *operands)

    # [B*H, T, ...] -> [B, KH, G, T, ...]
    acc = acc.reshape(b, kh, g, t, hd)
    m = m[:, :, 0].reshape(b, kh, g, t)
    l = l[:, :, 0].reshape(b, kh, g, t)
    return acc, m, l


def _flash_decode_kernel(
    pos_ref,  # SMEM scalar prefetch: [B] int32 (per-lane query positions)
    spos_ref,  # SMEM scalar prefetch: [1] int32 (this KV shard's first pos)
    q_ref,  # [1, G, hd] (the G query heads sharing this KV head)
    k_ref,  # [1, 1, bs, hd] — one head's (seq, hd) plane
    v_ref,  # [1, 1, bs, hd]
    *rest,  # emit_stats: (acc_out [1,G,hd], m_out [1,G,128], l_out [1,G,128])
    #         else: (o_ref [1,G,hd]); then scratch (m_ref, l_ref, acc_ref)
    block_s: int,
    n_s: int,
    n_kv_heads: int,
    scale: float,
    emit_stats: bool,
):
    """T=1 decode step: one query token per lane group, online softmax
    over S blocks. Blocks entirely beyond `pos` are compute-skipped and
    their kv index clamps to pos's block — but on real Mosaic the
    repeated-index DMA is NOT elided (round-3 chip finding), so cache
    reads stay O(S) per call and the ENGINE does not use this kernel for
    decode anymore (windowed XLA dense attention measured faster there);
    it is kept as the op-level T=1 flash surface and for stats emission.
    Positions are per LANE (pos_ref[b]). With `emit_stats` the kernel
    emits the UNNORMALIZED (acc, m, l) partial state relative to a KV
    shard starting at absolute position spos_ref[0] (the contract
    models/transformer._attention_sp's merge consumes)."""
    if emit_stats:
        acc_out, m_out, l_out, m_ref, l_ref, acc_ref = rest
    else:
        (o_ref, m_ref, l_ref, acc_ref) = rest
    si = pl.program_id(1)
    pos = pos_ref[pl.program_id(0) // n_kv_heads]
    # highest LOCAL row index this query may see (negative: whole shard
    # is in the future -> nothing computes, stats emit as fully-masked)
    local_limit = pos - spos_ref[0]

    @pl.when(si == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    s_start = si * block_s

    @pl.when(s_start <= local_limit)
    def _compute():
        g = q_ref.shape[1]
        q = q_ref[0].astype(jnp.float32)  # [G, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [bs, hd]
        scores = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [G, bs]
        s_row = s_start + jax.lax.broadcasted_iota(
            jnp.int32, (g, block_s), 1
        )
        scores = jnp.where(s_row <= local_limit, scores, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        p = jnp.where(m_new <= _NEG_INF / 2, 0.0, p)
        alpha = jnp.where(m_prev <= _NEG_INF / 2, 0.0, alpha)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(si == n_s - 1)
    def _emit():
        if emit_stats:
            acc_out[0] = acc_ref[:]
            m_out[0] = m_ref[:]
            l_out[0] = l_ref[:]
        else:
            # pos indexes a row written this step (the engine appends k/v
            # at pos before attention), so l >= 1 always; the guard is
            # belt and braces for direct op-level callers
            l_safe = jnp.where(l_ref[:, :1] == 0.0, 1.0, l_ref[:, :1])
            o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def pick_decode_block(s: int) -> int | None:
    """KV block length for the decode kernel: largest multiple of 128
    <= 1024 dividing s, or None (caller falls back to dense)."""
    return next(
        (b for b in range(min(1024, s - s % 128), 0, -128) if s % b == 0),
        None,
    )


@functools.partial(
    jax.jit, static_argnames=("block_s", "interpret", "emit_stats")
)
def _flash_decode_impl(
    q: jnp.ndarray,  # [B, 1, H, hd]
    k_cache: jnp.ndarray,  # [B, KH, S, hd]
    v_cache: jnp.ndarray,  # [B, KH, S, hd]
    pos: jnp.ndarray,  # scalar int32, or [B] per-lane positions
    s_pos0: jnp.ndarray,  # scalar int32: absolute position of cache row 0
    block_s: int = 0,
    interpret: bool = False,
    emit_stats: bool = False,
):
    """Single-token causal GQA attention over a (possibly shard-local) KV
    range. Normalized output [B, 1, H, hd] (emit_stats=False) or the
    unnormalized (acc, m, l) partial state in attention_stats layout
    (emit_stats=True, the sp decode local step).

    The G = H/KH query heads of each KV group ride the sublane dim (one
    [G, hd] x [hd, block_s] matmul per KV block), and the kv BlockSpec
    index map clamps at pos's block (compute skip only — the repeated
    -index DMA is not elided on Mosaic, so reads are O(S) per call; see
    module docstring). The cache is consumed in its storage layout
    [B, KH, S, hd] via 4-D BlockSpecs — no per-step copy/transpose of the
    cache is ever materialized, and each tile is a Mosaic-legal
    (block_s, hd) plane.
    """
    b, t, h, hd = q.shape
    assert t == 1, "flash_decode is the T=1 path"
    kh, s = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    if not block_s:
        picked = pick_decode_block(s)
        if picked is None:
            if not interpret:
                raise ValueError(
                    f"no valid decode block for s={s}; use dense attention"
                )
            picked = s
        block_s = picked
    assert s % block_s == 0, (s, block_s)
    n_s = s // block_s
    scale = 1.0 / (hd**0.5)

    # [B, 1, H, hd] -> [B * KH, G, hd] (pure reshape: T=1, no data movement)
    qt = q.reshape(b, kh, g, hd).reshape(b * kh, g, hd)
    pos_arr = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(pos, jnp.int32)), (b,)
    )
    spos_arr = jnp.asarray(s_pos0, jnp.int32).reshape(1)

    def q_map(bk, si, pos_ref, spos_ref):
        return (bk, 0, 0)

    def kv_map(bk, si, pos_ref, spos_ref):
        # clamp to pos's block (fully-masked steps re-fetch that block;
        # compute is skipped but the DMA is not elided — see module note)
        limit = jnp.maximum(pos_ref[bk // kh] - spos_ref[0], 0)
        return (bk // kh, bk % kh, jnp.minimum(si, limit // block_s), 0)

    kernel = functools.partial(
        _flash_decode_kernel,
        block_s=block_s,
        n_s=n_s,
        n_kv_heads=kh,
        scale=scale,
        emit_stats=emit_stats,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * kh, n_s),
        in_specs=[
            pl.BlockSpec((1, g, hd), q_map),
            pl.BlockSpec((1, 1, block_s, hd), kv_map),
            pl.BlockSpec((1, 1, block_s, hd), kv_map),
        ],
        out_specs=(
            [
                pl.BlockSpec((1, g, hd), q_map),
                pl.BlockSpec((1, g, 128), q_map),
                pl.BlockSpec((1, g, 128), q_map),
            ]
            if emit_stats
            else pl.BlockSpec((1, g, hd), q_map)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    out_shape = (
        [
            jax.ShapeDtypeStruct((b * kh, g, hd), jnp.float32),
            jax.ShapeDtypeStruct((b * kh, g, 128), jnp.float32),
            jax.ShapeDtypeStruct((b * kh, g, 128), jnp.float32),
        ]
        if emit_stats
        else jax.ShapeDtypeStruct((b * kh, g, hd), jnp.float32)
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(pos_arr, spos_arr, qt, k_cache, v_cache)

    if emit_stats:
        acc, m, l = out
        # match ops/jnp_ops.attention_stats: acc [B, KH, G, T=1, hd],
        # m/l [B, KH, G, 1]
        acc = acc.reshape(b, kh, g, 1, hd)
        m = m[:, :, 0].reshape(b, kh, g, 1)
        l = l[:, :, 0].reshape(b, kh, g, 1)
        return acc, m, l
    return out.reshape(b, kh, g, hd).reshape(b, 1, h, hd).astype(q.dtype)


def flash_decode(
    q: jnp.ndarray,  # [B, 1, H, hd]
    k_cache: jnp.ndarray,  # [B, KH, S, hd]
    v_cache: jnp.ndarray,
    pos: jnp.ndarray,  # scalar int32, or [B] per-lane positions
    block_s: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Normalized single-token decode attention (see _flash_decode_impl)."""
    return _flash_decode_impl(
        q, k_cache, v_cache, pos, jnp.int32(0),
        block_s=block_s, interpret=interpret, emit_stats=False,
    )


def flash_decode_stats(
    q: jnp.ndarray,  # [B, 1, H, hd]
    k_cache: jnp.ndarray,  # [B, KH, Ss, hd] — one sequence SHARD
    v_cache: jnp.ndarray,
    pos: jnp.ndarray,  # scalar or [B]
    s_pos0: jnp.ndarray,  # absolute position of this shard's row 0
    block_s: int = 0,
    interpret: bool = False,
):
    """Unnormalized (acc, m, l) decode partial state over a KV shard in
    the attention_stats contract (log-sum-exp mergeable). Shards entirely
    in the query's future emit fully-masked stats (m = -inf, l = 0) with
    all compute skipped. No longer the engine's sp local step (the dense
    jnp stats won on silicon; see _attention_sp) — kept as the op-level
    stats surface and covered by tests/test_flash_and_ring.py."""
    return _flash_decode_impl(
        q, k_cache, v_cache, pos, jnp.asarray(s_pos0, jnp.int32),
        block_s=block_s, interpret=interpret, emit_stats=True,
    )


def _paged_decode_kernel(
    pos_ref,  # SMEM scalar prefetch: [B] int32 per-lane query positions
    pt_ref,  # SMEM scalar prefetch: [B, n_blocks] int32 page table
    q_ref,  # [1, G, hd]
    k_ref,  # [1, 1, ps, hd] — one PAGE of one head, via page-table lookup
    v_ref,  # [1, 1, ps, hd]
    *rest,  # quant_kv: (ks_ref [1,1,ps,1], vs_ref [1,1,ps,1]); then
    #         o_ref [1, G, hd] and scratch (m_ref, l_ref, acc_ref)
    page_size: int,
    n_blocks: int,
    n_kv_heads: int,
    scale: float,
    quant_kv: bool = False,
):
    """T=1 decode over a PAGED pool: identical online-softmax body to
    _flash_decode_kernel, but the kv tiles arrive through the page table
    (the index map below) instead of a contiguous per-lane slab, so logical
    block ``si`` of lane ``b`` reads physical page ``pt_ref[b, si]``. A lane
    whose prefix is shared never holds its own copy of those rows."""
    if quant_kv:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    si = pl.program_id(1)
    pos = pos_ref[pl.program_id(0) // n_kv_heads]

    @pl.when(si == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    s_start = si * page_size

    @pl.when(s_start <= pos)
    def _compute():
        g = q_ref.shape[1]
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        if quant_kv:
            k = k * ks_ref[0, 0]
        scores = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        s_row = s_start + jax.lax.broadcasted_iota(jnp.int32, (g, page_size), 1)
        scores = jnp.where(s_row <= pos, scores, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        p = jnp.where(m_new <= _NEG_INF / 2, 0.0, p)
        alpha = jnp.where(m_prev <= _NEG_INF / 2, 0.0, alpha)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        if quant_kv:
            v = v * vs_ref[0, 0]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(si == n_blocks - 1)
    def _emit():
        l_safe = jnp.where(l_ref[:, :1] == 0.0, 1.0, l_ref[:, :1])
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_flash_decode(
    q: jnp.ndarray,  # [B, 1, H, hd]
    k_pages,  # [P, KH, ps, hd] pool leaf (or QuantKV pair)
    v_pages,
    page_table: jnp.ndarray,  # [B, n_blocks] int32 physical page per logical block
    pos: jnp.ndarray,  # scalar int32, or [B] per-lane positions
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-token causal GQA attention reading KV through a page table.

    This is the page-indirection seam over the flash decode kernel: the kv
    BlockSpec index map resolves logical block ``si`` of lane ``b`` to
    physical pool page ``page_table[b, si]`` (clamped at the lane's causal
    frontier, padding entries point at the reserved scratch page), so lanes
    sharing a prefix read the SAME physical pages — storage is per unique
    prefix, not per lane. Accepts a QuantKV pool (int8 values + per-row
    scales ride the same index map; dequant on the VMEM tile).

    Block length equals the pool's page size. On real Mosaic the same
    caveats as _flash_decode_kernel apply (repeated-index DMAs are not
    elided, and tiny pages under-utilize the (8, 128) tile), so the engine
    keeps windowed dense attention on the decode hot path; this kernel is
    the op-level paged surface, exercised interpret-mode in tests and ready
    for silicon page-size tuning (page_size a multiple of 8 f32 / 16 bf16,
    head_dim a multiple of 128)."""
    quant_kv = isinstance(k_pages, QuantKV)
    if isinstance(v_pages, QuantKV) != quant_kv:
        raise TypeError(
            f"k_pages and v_pages must both be QuantKV or both dense, got "
            f"k={type(k_pages).__name__}, v={type(v_pages).__name__}"
        )
    b, t, h, hd = q.shape
    assert t == 1, "paged_flash_decode is the T=1 path"
    kh, ps = k_pages.shape[1], k_pages.shape[2]
    g = h // kh
    n_blocks = page_table.shape[1]
    scale = 1.0 / (hd**0.5)

    qt = q.reshape(b, kh, g, hd).reshape(b * kh, g, hd)
    pos_arr = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(pos, jnp.int32)), (b,))
    pt = page_table.astype(jnp.int32)

    def q_map(bk, si, pos_ref, pt_ref):
        return (bk, 0, 0)

    def kv_map(bk, si, pos_ref, pt_ref):
        # page-table indirection with the usual causal-frontier clamp:
        # blocks past the lane's position re-fetch the frontier page
        # (compute skipped); clamping also keeps padding page-table slots
        # (scratch page 0) from ever being DMA'd beyond the frontier
        lane = bk // kh
        limit = jnp.maximum(pos_ref[lane], 0) // ps
        return (pt_ref[lane, jnp.minimum(si, limit)], bk % kh, 0, 0)

    in_specs = [
        pl.BlockSpec((1, g, hd), q_map),
        pl.BlockSpec((1, 1, ps, hd), kv_map),
        pl.BlockSpec((1, 1, ps, hd), kv_map),
    ]
    operands = [qt, k_pages, v_pages]
    if quant_kv:
        in_specs += [
            pl.BlockSpec((1, 1, ps, 1), kv_map),
            pl.BlockSpec((1, 1, ps, 1), kv_map),
        ]
        operands = [qt, k_pages.q, v_pages.q, k_pages.s, v_pages.s]
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel,
            page_size=ps,
            n_blocks=n_blocks,
            n_kv_heads=kh,
            scale=scale,
            quant_kv=quant_kv,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b * kh, n_blocks),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, g, hd), q_map),
            scratch_shapes=[
                pltpu.VMEM((g, 128), jnp.float32),
                pltpu.VMEM((g, 128), jnp.float32),
                pltpu.VMEM((g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * kh, g, hd), jnp.float32),
        interpret=interpret,
    )(pos_arr, pt, *operands)
    return out.reshape(b, kh, g, hd).reshape(b, 1, h, hd).astype(q.dtype)


def _latent_flash_kernel(
    pos_ref,  # SMEM scalar prefetch: [B] int32 per-lane q start positions
    l_ref,  # SMEM scalar prefetch: [1] int32 layer, read by the index maps
    q_ref,  # [1, bq, W]: bq query rows, row r is head r % H of position r // H
    c_ref,  # [1, 1, W, bs]: cached `[c | k_rope]` rows of that layer and lane, turned
    out_ref,  # [1, bq, kv_rank]
    m_ref, l_acc, acc_ref,  # scratch: running max, denominator, weighted sum
    *,
    keep_ref=None,  # [1, 1, P, bs]: `_latent_flash_kernel_kept`'s fifth operand
    block_q: int,
    block_s: int,
    n_s: int,
    n_heads: int,
    kv_rank: int,
    scale: float,
):
    """Absorbed latent attention, one block of query rows against one block
    of cached rows, online softmax across the row blocks (innermost grid
    axis). The H heads of a position are H query rows of one matrix product
    against the ONE cached head, and the values are the same block's first
    `kv_rank` columns: a cached row is moved once a query block, for keys
    and values both. A block above the causal frontier of the query block's
    last position is skipped, and so is every block of a parked lane
    (position <= -T). `keep_ref` [1, 1, P, bs] (where an index picks the rows
    a query attends to): above 0 where the block's position p attends to the
    row; its heads' query rows, bq / P to a position, take the same mask."""
    qi = pl.program_id(1)
    si = pl.program_id(2)
    pos0 = pos_ref[pl.program_id(0)]
    row0 = qi * block_q  # first query row of the block, of T * H

    @pl.when(si == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_acc[:] = jnp.zeros_like(l_acc)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    s_start = si * block_s
    last_pos = pos0 + (row0 + block_q - 1) // n_heads

    @pl.when(s_start <= last_pos)
    def _compute():
        q = q_ref[0]
        c = c_ref[0, 0]  # [W, bs]: a cached row is a column
        scores = jax.lax.dot_general(
            q, c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_s), 0)
        if n_heads & (n_heads - 1) == 0:  # a shift where the vector unit can
            q_pos = pos0 + jax.lax.shift_right_logical(
                rows, jnp.int32(n_heads.bit_length() - 1))
        else:
            q_pos = pos0 + rows // n_heads
        s_pos = s_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_s), 1)
        seen = s_pos <= q_pos
        if keep_ref is not None:
            kept = keep_ref[0, 0]  # [P padded to whole tiles, bs]
            seen = jnp.logical_and(seen, jnp.concatenate([
                jnp.broadcast_to(kept[i : i + 1], (n_heads, block_s))
                for i in range(block_q // n_heads)
            ]) > 0)
        scores = jnp.where(seen, scores, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.where(m_prev <= _NEG_INF / 2, 0.0, jnp.exp(m_prev - m_new))
        p = jnp.where(m_new <= _NEG_INF / 2, 0.0, jnp.exp(scores - m_new))
        l_new = alpha * l_acc[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(c.dtype), c[:kv_rank], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_acc[:] = jnp.broadcast_to(l_new, l_acc.shape)

    @pl.when(si == n_s - 1)
    def _emit():
        l = l_acc[:, :1]
        out_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(out_ref.dtype)


def _latent_flash_kernel_kept(pos_ref, l_ref, q_ref, c_ref, keep_ref, *refs, **static):
    """`_latent_flash_kernel` under an index's mask: the mask rides as the
    operand after the cached rows."""
    _latent_flash_kernel(pos_ref, l_ref, q_ref, c_ref, *refs, keep_ref=keep_ref, **static)


@functools.partial(
    jax.jit,
    static_argnames=("rows", "kv_rank", "scale", "block_q", "block_s", "interpret"),
)
def latent_flash_attention(
    q: jnp.ndarray,  # [B, T, H, W]: absorbed queries `[q_nope U_h^T | q_rope]`
    c_stack: jnp.ndarray,  # [L, B, 1, S, W] latent cache stack, or [B, 1, S, W]
    pos: jnp.ndarray,  # scalar or [B] int32: position of q[:, 0] per lane
    layer=None,  # int32 scalar: which layer of a stack
    rows: int = 0,  # attend to the first `rows` cached rows only (0 = all S)
    kv_rank: int = 0,  # the rows' first `kv_rank` columns are the values
    scale: float = 1.0,
    block_q: int = 0,
    block_s: int = 0,
    interpret: bool = False,
    keep: jnp.ndarray | None = None,  # bool [B or 1, T, rows]: rows a query attends to
) -> jnp.ndarray:
    """Blockwise causal latent attention in the absorbed form, [B, T, H,
    kv_rank] in q's type: `softmax_j(q . row_j * scale) row_j[:kv_rank]`
    over the cached rows a query's position sees, or with `keep` over those
    of them that it names (one lane's mask serves every lane: a program that
    admits one lane parks the others, whose blocks are skipped). The stack stays where it
    lies (the layer rides as scalar prefetch, the window as `rows`); the
    products take the operands' own type with f32 accumulation, the softmax
    is f32. A strongly negative lane position masks the lane whole.

    The kernel takes the stack with its last two axes swapped, `[.., W, S]`.
    On the chip that moves nothing: W = 576 is no multiple of the 128 lanes
    and S is, so the chip's own layout of a `[.., S, 576]` array already has
    the positions minor (described v5e: `{3,4,2,1,0}`), and the swapped view
    in its default layout is the same bytes. Handed over unswapped, the
    stack was copied whole into the other layout before the layer scan and
    back after it, 0.39 GB each way a chunk."""
    if c_stack.ndim == 4:
        assert layer is None, "a layer number needs a [L, B, 1, S, W] stack"
        c_stack, layer = c_stack[None], 0
    b, t, h, w = q.shape
    s = rows or c_stack.shape[3]
    assert c_stack.shape[2] == 1 and c_stack.shape[4] == w and s <= c_stack.shape[3]
    n_rows = t * h
    if not block_q or not block_s:
        # up to 512 query rows a block (T x H of them): a cached block is
        # moved once a query block
        picked = pick_flash_blocks(n_rows, s, max_t=512)
        if picked is None:
            if not interpret:
                raise ValueError(
                    f"no valid latent blocks for {n_rows} query rows, s={s}; "
                    "use dense attention"
                )
            picked = (n_rows, s)
        block_q, block_s = block_q or picked[0], block_s or picked[1]
    assert n_rows % block_q == 0 and s % block_s == 0, (n_rows, s, block_q, block_s)
    n_q, n_s = n_rows // block_q, s // block_s
    qr = q.reshape(b, n_rows, w).astype(c_stack.dtype)
    pos_arr = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(pos, jnp.int32)), (b,))
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_map(bi, qi, si, pos_ref, l_ref):
        return (bi, qi, 0)

    def s_block(bi, qi, si, pos_ref):
        # clamp past the causal frontier of the query block's last position
        limit = jnp.maximum(
            (pos_ref[bi] + ((qi + 1) * block_q - 1) // h) // block_s, 0)
        return jnp.minimum(si, limit)

    def c_map(bi, qi, si, pos_ref, l_ref):
        return (l_ref[0], bi, 0, 0, s_block(bi, qi, si, pos_ref))

    in_specs = [
        pl.BlockSpec((1, block_q, w), q_map),
        pl.BlockSpec((None, 1, 1, w, block_s), c_map),
    ]
    operands = [qr, jnp.swapaxes(c_stack, 3, 4)]
    if keep is not None:
        # a query block is whole positions, P of them; their masks lie as
        # one tile-aligned block [P padded to 8s, bs] a query block
        if block_q % h:
            raise ValueError(
                f"a mask a position needs query blocks of whole positions: "
                f"{block_q} rows a block, {h} heads")
        per, one = block_q // h, keep.shape[0] == 1
        assert keep.shape[1:] == (t, s) and keep.shape[0] in (1, b), keep.shape
        pad = -per % 8
        kept = jnp.pad(
            keep.reshape(keep.shape[0], n_q, per, s).astype(jnp.float32),
            ((0, 0), (0, 0), (0, pad), (0, 0)))

        def keep_map(bi, qi, si, pos_ref, l_ref):
            return (0 if one else bi, qi, 0, s_block(bi, qi, si, pos_ref))

        in_specs.append(pl.BlockSpec((1, 1, per + pad, block_s), keep_map))
        operands.append(kept)

    out = pl.pallas_call(
        functools.partial(
            _latent_flash_kernel if keep is None else _latent_flash_kernel_kept,
            block_q=block_q, block_s=block_s, n_s=n_s,
            n_heads=h, kv_rank=kv_rank, scale=scale,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_q, n_s),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block_q, kv_rank), q_map),
            scratch_shapes=[
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, kv_rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_rows, kv_rank), q.dtype),
        interpret=interpret,
    )(pos_arr, layer_arr, *operands)
    return out.reshape(b, t, h, kv_rank)


def flash_attention(
    q: jnp.ndarray,  # [B, T, H, hd]
    k_cache: jnp.ndarray,  # [B, KH, S, hd], or [L, B, KH, S, hd] + `layer`
    v_cache: jnp.ndarray,
    pos: jnp.ndarray,  # scalar int32, or [B] per-lane positions
    block_t: int = 0,
    block_s: int = 0,
    interpret: bool = False,
    layer=None,
    rows: int = 0,
    ring: int = 0,
    window: int = 0,
    row0: int = 0,
) -> jnp.ndarray:
    """Blockwise causal GQA attention; returns [B, T, H, hd] in q.dtype.
    `layer` and `rows`: the cache stack read in place, `ring` and `window`:
    a window layer's cache, as `flash_attention_stats` says.

    Implemented as normalize(flash_attention_stats(...)) so one kernel body
    serves both the dense path and ring attention's partial-state merge; the
    extra m/l emission is noise next to the score/value traffic.
    """
    b, t, h, hd = q.shape
    acc, m, l = flash_attention_stats(
        q, k_cache, v_cache, pos, 0,
        block_t=block_t, block_s=block_s, interpret=interpret,
        layer=layer, rows=rows, ring=ring, window=window, row0=row0,
    )
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[..., None]  # [B, KH, G, T, hd]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, hd).astype(q.dtype)
