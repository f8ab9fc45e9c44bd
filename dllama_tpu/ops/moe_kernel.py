"""Ragged MoE dispatch: Pallas kernels computing ONLY the active experts.

The decode-path answer to SURVEY.md §7's "MoE top-k on TPU with tiny active
expert counts (A3B: 8 of 128) without wasting a dense 128-expert matmul".
The reference walks an indexes buffer and runs just the selected experts'
matmuls (src/nn/nn-cpu-ops.cpp:1104-1136); the straightforward XLA
restatement (`jnp.take` of the expert weights) measures ~3x slower than
even the dense all-expert einsum on v5e, because the gather materializes
the selected weights through HBM.

These kernels instead make the expert id part of the DMA schedule: the
top-k indices arrive via scalar prefetch and the BlockSpec index_map picks
which expert's weight tile to copy HBM->VMEM per grid step — the selected
expert weights are read exactly once per (token, choice), nothing else
moves.

Grid: (m, k, F blocks) — token-major, active experts next, the expert's
hidden (F) dim innermost. F-blocking is exact (SwiGLU is elementwise in F
and w2 contracts over it) and is what keeps full-scale experts (e.g. A3B:
D=2048, F=768 -> 9 MB of bf16 tiles per step unblocked) inside the 16 MB
scoped-VMEM budget with double buffering — the unblocked version was
rejected by the real compiler at exactly that shape. Routing is PER TOKEN
(each decode lane picks its own top-k, matching the reference's per-row
indexes buffer). Decode-sized m (the engine's dp lanes); prefill keeps the
dense path where every expert is busy anyway.

Two variants:
- `moe_active_experts`: dense bf16/f32 expert weights.
- `moe_active_experts_q40`: block-quantized experts (int8 values +
  per-32-block f32 scales, the `QuantWeight` device layout) dequantized
  in-VMEM after the DMA, exactly like ops/quant_matmul._qmm_kernel — the
  reference stores experts Q40 too (src/llm.cpp:425-499) and ships Q40
  slices per expert (src/nn/nn-network.cpp:856-888).

One device that holds a sparse layer whole runs `moe_held_experts_q40`
(below), in decode blocks and chunks alike, over int8 values or over the
packed words `--weight-format q40i4` holds there (`PackedQuantWeight`: 0.625
B a weight, unpacked in VMEM by `quant_matmul.unpack_tile`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quant_matmul import NIBBLES, PACKED_GROUP, unpack_tile

Q_BLOCK = 32

# Per-step VMEM budget for the three expert tiles (double-buffered by the
# pipeline; the 16 MB scoped-vmem ceiling also holds dequant temporaries).
_TILE_BUDGET_BYTES = 8_000_000


def _pick_f_block(f: int, d: int, quantized: bool, itemsize: int = 2) -> int:
    """Largest F block that divides f, satisfies Mosaic tiling for every
    operand, and fits the VMEM budget.

    The q40 variant's w2 scale tensor [E, F // 32, D] blocks its sublane
    dim at bf // 32, which Mosaic requires to be a multiple of 8 (or the
    full extent) — so quantized blocks must be multiples of 256; dense
    blocks multiples of 128. Falls back to whole-F (no blocking) when no
    multiple divides f — small test shapes take that path. `itemsize` is
    the dense weights' actual bytes/elem (the loader materializes f32/f16
    wire weights as float32, i.e. 4, not bf16's 2)."""
    # effective bytes/elem across the three tiles incl. in-kernel dequant
    # temporaries (q40: int8 + f32/32 scales + a bf16 dequant copy)
    bpe = 3.2 if quantized else float(itemsize)
    step = 256 if quantized else 128
    budget_bf = int(_TILE_BUDGET_BYTES / (2 * 3 * d * bpe))
    best = 0
    b = step
    while b <= min(f, max(budget_bf, step)):
        if f % b == 0:
            best = b
        b += step
    if best:
        return best
    if f <= max(budget_bf, step):
        return f  # small shapes: whole F fits, no blocking needed
    # no legal divisor AND whole-F busts the VMEM budget: refuse loudly
    # (callers gate on moe_pallas_supported and fall back to the dense
    # path) instead of shipping a kernel the real compiler will reject
    raise ValueError(
        f"no Mosaic-legal F block for F={f}, D={d} (need a multiple-of-"
        f"{step} divisor within the {_TILE_BUDGET_BYTES // 10**6} MB tile "
        "budget); use the dense MoE path"
    )


def moe_pallas_supported(
    d: int, f: int, quantized: bool, itemsize: int = 2
) -> bool:
    """Whether the ragged kernels can tile this expert shape inside the
    scoped-VMEM budget (transformer.forward gates the Pallas MoE path on
    this and keeps the dense path otherwise)."""
    try:
        _pick_f_block(f, d, quantized, itemsize)
        return True
    except ValueError:
        return False


def _swiglu_accum(x, w1_f, w3_f, w2_f, routing_w, ti, ki, fi, n_k, n_f,
                  acc_ref, o_ref):
    """Shared kernel tail: one F-block of SwiGLU through one expert's
    weights, weighted accumulation in VMEM scratch, row emit on the last
    (expert, F-block) step. Exact under F-blocking: silu(x@w1)*(x@w3) is
    elementwise in F and the w2 product sums over F."""

    @pl.when((ki == 0) & (fi == 0))
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    h1 = jax.lax.dot_general(
        x, w1_f, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    h3 = jax.lax.dot_general(
        x, w3_f, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    hidden = (h1 / (1.0 + jnp.exp(-h1))) * h3  # silu(w1 x) * (w3 x), f32
    out = jax.lax.dot_general(
        hidden.astype(x.dtype), w2_f,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[:] += out * routing_w

    @pl.when((ki == n_k - 1) & (fi == n_f - 1))
    def _emit():
        o_ref[pl.ds(ti, 1), :] = acc_ref[:].astype(o_ref.dtype)


def _moe_kernel(
    idx_ref,  # scalar prefetch: [m, k] int32 expert ids
    w_ref,  # scalar prefetch: [m, k] f32 routing weights (SMEM)
    x_ref,  # [m, D] f32 (ALL token rows; whole-array block)
    w1_ref,  # [1, D, bf] (selected expert, F block)
    w3_ref,  # [1, D, bf]
    w2_ref,  # [1, bf, D]
    o_ref,  # [m, D] (whole-array block, one row written per token)
    acc_ref,  # VMEM [1, D] f32
    *,
    n_k: int,
    n_f: int,
):
    ti, ki, fi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    # dynamic sublane row: this token. x rides in f32 — an (8, 128)-tiled
    # dtype, so any row index is aligned; a bf16 x packs two rows per
    # sublane word and Mosaic demands the index be provably even. Compute
    # happens in the weights' dtype.
    x = x_ref[pl.ds(ti, 1), :].astype(w1_ref.dtype)
    _swiglu_accum(
        x, w1_ref[0], w3_ref[0], w2_ref[0],
        w_ref[ti, ki], ti, ki, fi, n_k, n_f, acc_ref, o_ref,
    )


def _dequant_block(q, d):
    """In-VMEM Q40 dequant: q int8 [I, O], d f32 [I // 32, O] -> bf16 [I, O]
    (sublane-broadcast multiply; same move as quant_matmul._qmm_kernel)."""
    i, o = q.shape
    return (
        (q.astype(jnp.float32).reshape(i // Q_BLOCK, Q_BLOCK, o) * d[:, None, :])
        .reshape(i, o)
        .astype(jnp.bfloat16)
    )


def _moe_kernel_q40(
    idx_ref,  # scalar prefetch: [m, k] int32 expert ids
    w_ref,  # scalar prefetch: [m, k] f32 routing weights
    x_ref,  # [m, D] f32 (whole-array block)
    w1q_ref,  # [1, D, bf] int8
    w1d_ref,  # [1, D // 32, bf] f32
    w3q_ref,  # [1, D, bf] int8
    w3d_ref,  # [1, D // 32, bf] f32
    w2q_ref,  # [1, bf, D] int8
    w2d_ref,  # [1, bf // 32, D] f32
    o_ref,  # [m, D] (whole-array block)
    acc_ref,  # VMEM [1, D] f32
    *,
    n_k: int,
    n_f: int,
):
    ti, ki, fi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    w1 = _dequant_block(w1q_ref[0], w1d_ref[0])
    w3 = _dequant_block(w3q_ref[0], w3d_ref[0])
    w2 = _dequant_block(w2q_ref[0], w2d_ref[0])
    x = x_ref[pl.ds(ti, 1), :].astype(jnp.bfloat16)  # f32 in: row-aligned
    _swiglu_accum(
        x, w1, w3, w2, w_ref[ti, ki], ti, ki, fi, n_k, n_f, acc_ref, o_ref
    )


def _layer_experts(layer, *stacks):
    """A layer's experts inside `[L, E, ...]` stacks, for kernels that pick
    an expert by id: the stacks viewed as `[L * E, ...]` (a merge of the
    leading axes, no copy) and `layer * E`, the offset that turns the
    layer's expert ids into rows of that view. So a layer scan hands the
    kernels the whole stacks and nothing copies a layer's E experts out
    for the few a token reads. One layer's `[E, ...]` experts are a stack
    of one, at layer 0."""
    n_experts = stacks[0].shape[-3]
    return (
        jnp.asarray(layer, jnp.int32) * n_experts,
        tuple(w.reshape(-1, *w.shape[-2:]) for w in stacks),
    )


def _full_map(ti, ki, fi, idx_ref, w_ref):
    # x and out ride as ONE whole-array block: a per-token (1, D) block
    # would put a size-1 dim in the last-two block dims, which Mosaic
    # rejects for m > 1 (the same tiling rule that forced the head-major
    # KV layout); rows are selected inside the kernel by dynamic sublane
    # slice instead. m is decode-lane sized, so the resident tile is tiny.
    return (0, 0)


def _row_sel_map(ti, ki, fi, idx_ref, w_ref):
    # w1/w3-shaped operands [E, D|D//32, F]: expert by routing, F by block
    return (idx_ref[ti, ki], 0, fi)


def _col_sel_map(ti, ki, fi, idx_ref, w_ref):
    # w2-shaped operands [E, F|F//32, D]: the F axis is the sublane dim
    return (idx_ref[ti, ki], fi, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_active_experts(
    x: jnp.ndarray,  # [m, D] tokens (decode-sized m)
    w1: jnp.ndarray,  # [E, D, F]
    w2: jnp.ndarray,  # [E, F, D]
    w3: jnp.ndarray,  # [E, D, F]
    top_i: jnp.ndarray,  # [m, k] int32 per-token selected expert ids
    weights: jnp.ndarray,  # [m, k] f32 normalized routing weights
    interpret: bool = False,
) -> jnp.ndarray:
    """SwiGLU-MoE over exactly each token's selected experts; [m, D] f32."""
    m, d = x.shape
    e, _, f = w1.shape
    k = top_i.shape[-1]
    assert top_i.shape == (m, k), (top_i.shape, m, k)
    bf = _pick_f_block(f, d, quantized=False, itemsize=w1.dtype.itemsize)
    n_f = f // bf

    return pl.pallas_call(
        functools.partial(_moe_kernel, n_k=k, n_f=n_f),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m, k, n_f),
            in_specs=[
                pl.BlockSpec((m, d), _full_map),
                pl.BlockSpec((1, d, bf), _row_sel_map),
                pl.BlockSpec((1, d, bf), _row_sel_map),
                pl.BlockSpec((1, bf, d), _col_sel_map),
            ],
            out_specs=pl.BlockSpec((m, d), _full_map),
            scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        interpret=interpret,
    )(top_i, weights.astype(jnp.float32), x.astype(jnp.float32), w1, w3, w2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_active_experts_q40(
    x: jnp.ndarray,  # [m, D]
    w1q: jnp.ndarray,  # [E, D, F] int8
    w1d: jnp.ndarray,  # [E, D // 32, F] f32
    w2q: jnp.ndarray,  # [E, F, D] int8
    w2d: jnp.ndarray,  # [E, F // 32, D] f32
    w3q: jnp.ndarray,  # [E, D, F] int8
    w3d: jnp.ndarray,  # [E, D // 32, F] f32
    top_i: jnp.ndarray,  # [m, k] int32
    weights: jnp.ndarray,  # [m, k] f32
    layer=0,  # int32 scalar: which layer of [L, E, ...] stacks
    interpret: bool = False,
) -> jnp.ndarray:
    """Quantized ragged MoE: selected experts' Q40 blocks are DMA'd and
    dequantized in VMEM (0.56x the bytes of bf16 per weight — the same
    HBM-traffic win as the dense-layer Pallas matmul); [m, D] f32."""
    m, d = x.shape
    e, _, f = w1q.shape[-3:]
    k = top_i.shape[-1]
    assert top_i.shape == (m, k), (top_i.shape, m, k)
    first, (w1q, w1d, w2q, w2d, w3q, w3d) = _layer_experts(
        layer, w1q, w1d, w2q, w2d, w3q, w3d
    )
    bf = _pick_f_block(f, d, quantized=True)
    n_f = f // bf

    return pl.pallas_call(
        functools.partial(_moe_kernel_q40, n_k=k, n_f=n_f),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m, k, n_f),
            in_specs=[
                pl.BlockSpec((m, d), _full_map),
                pl.BlockSpec((1, d, bf), _row_sel_map),
                pl.BlockSpec((1, d // Q_BLOCK, bf), _row_sel_map),
                pl.BlockSpec((1, d, bf), _row_sel_map),
                pl.BlockSpec((1, d // Q_BLOCK, bf), _row_sel_map),
                pl.BlockSpec((1, bf, d), _col_sel_map),
                pl.BlockSpec((1, bf // Q_BLOCK, d), _col_sel_map),
            ],
            out_specs=pl.BlockSpec((m, d), _full_map),
            scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        interpret=interpret,
    )(
        top_i + first, weights.astype(jnp.float32),
        x.astype(jnp.float32), w1q, w1d, w3q, w3d, w2q, w2d,
    )


# ---------------------------------------------------------------------------
# Grouped (prefill-scale) ragged MoE: active experts only, tokens sorted by
# expert. The decode kernels above dedicate one grid step per (token,
# choice) — fine for lane-sized m, but prefill would re-read every selected
# expert's weights per token. Here the B*T*k routing assignments are sorted
# by expert id, row-tiled at R rows, and a STATIC-size schedule (computed
# in jnp, delivered via scalar prefetch) gives each grid step one
# (row-tile, expert-segment) pair: expert weights stream once per
# overlapping tile (~once per occupied expert when tokens group well), and
# FLOPs are proportional to assignments, not to E. This is the
# megablocks-style grouped GEMM restated for Pallas-on-TPU (SURVEY.md §7's
# "MoE top-k without a dense 128-expert matmul" hard part, at prefill
# scale; reference active-only semantics: src/nn/nn-cpu-ops.cpp:1104-1136).
# ---------------------------------------------------------------------------

_GROUP_ROWS = 32  # row tile; worst-case wasted compute = E extra tiles


def _grouped_schedule(top_i, weights, n_tokens, n_experts,
                      max_segments: int | None = None,
                      rows: int = _GROUP_ROWS,
                      keep: int | None = None):
    """jnp (traced) schedule for the grouped kernel.

    Returns (t_sorted [A_pad], w_col [A_pad, 1], step_lo/hi/tile/expert
    [G]) where A_pad pads the A = N*k sorted assignments to the row tile
    and G = A_pad/R + min(E, A) + 1 statically bounds the (tile, segment)
    pairs — every extra distinct expert inside a tile adds one step, and
    there are at most min(E, A)+1 distinct ids (incl. the padding
    sentinel). The min(E, A) term matters at DECODE scale: lane batches
    have A = m*k << E assignments, and the old E+1 bound would append ~E
    empty grid steps that each still DMA an expert tile (Mosaic does not
    elide repeated-index block loads, round-3 chip finding).

    `max_segments` caps the expert-segment budget BELOW the worst case —
    the two-tier decode dedup (docs/moe_decode_dedup.md) compiles a
    small-grid variant and only dispatches it (lax.cond) when the
    runtime unique-expert count fits; with more segments than the cap
    the trailing scatter indices fall out of range and XLA drops them
    (never executed: the caller's predicate guarantees the fit).

    `keep` (a multiple of the row tile, below A): the schedule of the sorted
    assignments' first `keep` alone, every array sized by them. It is the
    whole schedule's leading part, step for step, as far as the kept rows
    reach (`moe_held_experts_q40`: the held pairs lead)."""
    n, k = top_i.shape
    a = n * k
    r = rows
    a_pad = -(-a // r) * r
    if keep is not None:
        assert keep % r == 0 and 0 < keep < a, (keep, r, a)
        a = a_pad = keep
    n_tiles = a_pad // r
    seg_budget = (
        min(n_experts, a)
        if max_segments is None
        else min(n_experts, a, max_segments)
    )
    g_steps = n_tiles + seg_budget + 1

    flat_e = top_i.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    flat_w = weights.reshape(-1).astype(jnp.float32)
    order = jnp.argsort(flat_e, stable=True)
    if keep is not None:
        order = order[:keep]
    e_s = jnp.concatenate(
        [flat_e[order], jnp.full((a_pad - a,), n_experts, flat_e.dtype)]
    )
    t_s = jnp.concatenate(
        [flat_t[order], jnp.zeros((a_pad - a,), jnp.int32)]
    )
    w_s = jnp.concatenate(
        [flat_w[order], jnp.zeros((a_pad - a,), jnp.float32)]
    )

    pos = jnp.arange(a_pad, dtype=jnp.int32)
    prev_e = jnp.concatenate([jnp.full((1,), -1, e_s.dtype), e_s[:-1]])
    step_start = jnp.logical_or(pos % r == 0, e_s != prev_e)
    step_id = jnp.cumsum(step_start.astype(jnp.int32)) - 1  # [a_pad]

    step_lo = jnp.full((g_steps,), a_pad, jnp.int32).at[step_id].min(pos)
    step_hi = jnp.zeros((g_steps,), jnp.int32).at[step_id].max(pos) + 1
    # empty trailing steps: lo=a_pad, hi=1 -> hi<=lo masks every row
    step_tile = jnp.clip(step_lo // r, 0, n_tiles - 1)
    step_expert = e_s[jnp.clip(step_lo, 0, a_pad - 1)]
    step_expert = jnp.clip(step_expert, 0, n_experts - 1)  # sentinel -> any
    return t_s, w_s[:, None], step_lo, step_hi, step_tile, step_expert


def _grouped_kernel(
    lo_ref, hi_ref, tile_ref, expert_ref,  # scalar prefetch [G] int32
    x_ref,  # [R, D] bf16: this tile's sorted token rows
    w_ref,  # [R, 1] f32: per-row routing weights (masked by segment here)
    w1_ref,  # [1, D, bf]
    w3_ref,  # [1, D, bf]
    w2_ref,  # [1, bf, D]
    o_ref,  # [R, D] f32
    acc_ref,  # VMEM [R, D] f32
    *,
    n_f: int,
    n_steps: int,
    rows: int,
):
    g, fi = pl.program_id(0), pl.program_id(1)
    tile = tile_ref[g]
    prev_tile = tile_ref[jnp.maximum(g - 1, 0)]
    next_tile = tile_ref[jnp.minimum(g + 1, n_steps - 1)]
    new_tile = jnp.logical_or(g == 0, tile != prev_tile)
    last_of_tile = jnp.logical_or(g == n_steps - 1, tile != next_tile)

    @pl.when(jnp.logical_and(new_tile, fi == 0))
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:].astype(w1_ref.dtype)
    h1 = jax.lax.dot_general(
        x, w1_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    h3 = jax.lax.dot_general(
        x, w3_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    hidden = (h1 / (1.0 + jnp.exp(-h1))) * h3
    out = jax.lax.dot_general(
        hidden.astype(x.dtype), w2_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # rows outside this step's [lo, hi) segment belong to another expert
    # (or to padding): their routing weight is forced to 0, so the wasted
    # compute contributes exactly nothing
    row_pos = tile * rows + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0
    )
    in_seg = jnp.logical_and(row_pos >= lo_ref[g], row_pos < hi_ref[g])
    w_rows = jnp.where(in_seg, w_ref[:], 0.0)
    acc_ref[:] += out * w_rows

    @pl.when(jnp.logical_and(last_of_tile, fi == n_f - 1))
    def _emit():
        o_ref[:] = acc_ref[:]


def _grouped_x_map(g, fi, lo, hi, tile, expert):
    return (tile[g], 0)


def _grouped_row_map(g, fi, lo, hi, tile, expert):
    return (tile[g], 0)


def _grouped_w13_map(g, fi, lo, hi, tile, expert):
    return (expert[g], 0, fi)


def _grouped_w2_map(g, fi, lo, hi, tile, expert):
    return (expert[g], fi, 0)


@functools.partial(
    jax.jit, static_argnames=("interpret", "max_segments")
)
def moe_grouped_experts(
    x: jnp.ndarray,  # [N, D] tokens (prefill-scale N)
    w1: jnp.ndarray,  # [E, D, F]
    w2: jnp.ndarray,  # [E, F, D]
    w3: jnp.ndarray,  # [E, D, F]
    top_i: jnp.ndarray,  # [N, k] int32
    weights: jnp.ndarray,  # [N, k] f32
    interpret: bool = False,
    max_segments: int | None = None,
) -> jnp.ndarray:
    """Grouped active-expert SwiGLU MoE; [N, D] f32. See module section
    comment: assignments sorted by expert, one grid step per (row tile,
    expert segment), expert weights streamed once per overlapping tile."""
    n, d = x.shape
    e, _, f = w1.shape
    k = top_i.shape[-1]
    bf = _pick_f_block(f, d, quantized=False, itemsize=w1.dtype.itemsize)
    n_f = f // bf
    r = _GROUP_ROWS

    t_s, w_col, lo, hi, tile, expert = _grouped_schedule(
        top_i, weights, n, e, max_segments=max_segments
    )
    a_pad = t_s.shape[0]
    g_steps = lo.shape[0]
    x_sorted = jnp.take(x, t_s, axis=0).astype(jnp.bfloat16)  # [A_pad, D]

    o_sorted = pl.pallas_call(
        functools.partial(
            _grouped_kernel, n_f=n_f, n_steps=g_steps, rows=r
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(g_steps, n_f),
            in_specs=[
                pl.BlockSpec((r, d), _grouped_x_map),
                pl.BlockSpec((r, 1), _grouped_row_map),
                pl.BlockSpec((1, d, bf), _grouped_w13_map),
                pl.BlockSpec((1, d, bf), _grouped_w13_map),
                pl.BlockSpec((1, bf, d), _grouped_w2_map),
            ],
            out_specs=pl.BlockSpec((r, d), _grouped_x_map),
            scratch_shapes=[pltpu.VMEM((r, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((a_pad, d), jnp.float32),
        interpret=interpret,
    )(lo, hi, tile, expert, x_sorted, w_col, w1, w3, w2)
    # weights ride in their NATIVE dtype — a pre-cast would materialize
    # full all-expert copies, the exact all-E HBM cost this kernel avoids;
    # the kernel casts x per tile to match instead

    # scatter-add each weighted assignment back to its token (the
    # reference's OP_SCALE + OP_MERGE_SUM combine, src/llm.cpp:489-499)
    return jnp.zeros((n, d), jnp.float32).at[t_s].add(o_sorted)


def _grouped_kernel_q40(
    lo_ref, hi_ref, tile_ref, expert_ref,  # scalar prefetch [G] int32
    x_ref,  # [R, D] bf16
    w_ref,  # [R, 1] f32
    w1q_ref,  # [1, D, bf] int8
    w1d_ref,  # [1, D // 32, bf] f32
    w3q_ref,  # [1, D, bf] int8
    w3d_ref,  # [1, D // 32, bf] f32
    w2q_ref,  # [1, bf, D] int8
    w2d_ref,  # [1, bf // 32, D] f32
    o_ref,  # [R, D] f32
    acc_ref,  # VMEM [R, D] f32
    *,
    n_f: int,
    n_steps,  # the grid's steps: static, or a traced scalar (`_held_kernel_q40`)
    rows: int,
    dequant=_dequant_block,  # `unpack_tile` where the values are packed words
):
    g, fi = pl.program_id(0), pl.program_id(1)
    tile = tile_ref[g]
    prev_tile = tile_ref[jnp.maximum(g - 1, 0)]
    next_tile = tile_ref[jnp.minimum(g + 1, n_steps - 1)]
    new_tile = jnp.logical_or(g == 0, tile != prev_tile)
    last_of_tile = jnp.logical_or(g == n_steps - 1, tile != next_tile)

    @pl.when(jnp.logical_and(new_tile, fi == 0))
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    w1 = dequant(w1q_ref[0], w1d_ref[0])
    w3 = dequant(w3q_ref[0], w3d_ref[0])
    w2 = dequant(w2q_ref[0], w2d_ref[0])
    x = x_ref[:]
    h1 = jax.lax.dot_general(
        x, w1, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    h3 = jax.lax.dot_general(
        x, w3, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    hidden = (h1 / (1.0 + jnp.exp(-h1))) * h3
    out = jax.lax.dot_general(
        hidden.astype(x.dtype), w2,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    row_pos = tile * rows + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0
    )
    in_seg = jnp.logical_and(row_pos >= lo_ref[g], row_pos < hi_ref[g])
    acc_ref[:] += out * jnp.where(in_seg, w_ref[:], 0.0)

    @pl.when(jnp.logical_and(last_of_tile, fi == n_f - 1))
    def _emit():
        o_ref[:] = acc_ref[:]


@functools.partial(
    jax.jit, static_argnames=("interpret", "max_segments")
)
def moe_grouped_experts_q40(
    x: jnp.ndarray,  # [N, D]
    w1q: jnp.ndarray,  # [E, D, F] int8
    w1d: jnp.ndarray,  # [E, D // 32, F] f32
    w2q: jnp.ndarray,  # [E, F, D] int8
    w2d: jnp.ndarray,  # [E, F // 32, D] f32
    w3q: jnp.ndarray,  # [E, D, F] int8
    w3d: jnp.ndarray,  # [E, D // 32, F] f32
    top_i: jnp.ndarray,  # [N, k] int32
    weights: jnp.ndarray,  # [N, k] f32
    layer=0,  # int32 scalar: which layer of [L, E, ...] stacks
    interpret: bool = False,
    max_segments: int | None = None,
) -> jnp.ndarray:
    """Quantized grouped active-expert MoE (see moe_grouped_experts):
    selected experts' Q40 blocks stream once per overlapping row tile."""
    n, d = x.shape
    e, _, f = w1q.shape[-3:]
    first, (w1q, w1d, w2q, w2d, w3q, w3d) = _layer_experts(
        layer, w1q, w1d, w2q, w2d, w3q, w3d
    )
    bf = _pick_f_block(f, d, quantized=True)
    n_f = f // bf
    r = _GROUP_ROWS

    t_s, w_col, lo, hi, tile, expert = _grouped_schedule(
        top_i, weights, n, e, max_segments=max_segments
    )
    a_pad = t_s.shape[0]
    g_steps = lo.shape[0]
    x_sorted = jnp.take(x, t_s, axis=0).astype(jnp.bfloat16)

    # the schedule sorted on the layer's own ids; only the rows read differ
    o_sorted = pl.pallas_call(
        functools.partial(
            _grouped_kernel_q40, n_f=n_f, n_steps=g_steps, rows=r
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(g_steps, n_f),
            in_specs=[
                pl.BlockSpec((r, d), _grouped_x_map),
                pl.BlockSpec((r, 1), _grouped_row_map),
                pl.BlockSpec((1, d, bf), _grouped_w13_map),
                pl.BlockSpec((1, d // Q_BLOCK, bf), _grouped_w13_map),
                pl.BlockSpec((1, d, bf), _grouped_w13_map),
                pl.BlockSpec((1, d // Q_BLOCK, bf), _grouped_w13_map),
                pl.BlockSpec((1, bf, d), _grouped_w2_map),
                pl.BlockSpec((1, bf // Q_BLOCK, d), _grouped_w2_map),
            ],
            out_specs=pl.BlockSpec((r, d), _grouped_x_map),
            scratch_shapes=[pltpu.VMEM((r, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((a_pad, d), jnp.float32),
        interpret=interpret,
    )(lo, hi, tile, expert + first, x_sorted, w_col,
      w1q, w1d, w3q, w3d, w2q, w2d)

    return jnp.zeros((n, d), jnp.float32).at[t_s].add(o_sorted)


# ---------------------------------------------------------------------------
# A share of the experts: the router scores all of a layer's experts, and
# this chip holds `n_held` of them. Pairs that landed on an expert held
# elsewhere carry the id `n_held`: they sort behind every held pair, as the
# padding does, and the grid stops at the last step that holds a real pair
# (a dynamic grid bound), so neither a grid step nor a DMA is spent on them.
# One kernel for decode and prefill alike: a decode batch is one row tile,
# and its steps are the distinct held experts its tokens touched.
# ---------------------------------------------------------------------------

_HELD_ROWS = 128  # 2048 held pairs of a 512-row chunk over 32 experts: 16 tiles


# Packed words: a grid step of three `[2048, 256]` tiles under a 128-row tile
# took 3.0 us where the int8 step took 2.8 (PR 44's probe,
# `scripts/moe_packed_probe.py`): once the copy is 0.625 B a weight the step
# is bound by what it does with the tile's ROWS, all of them against every
# weight block for the few an expert owns, and by a step's fixed cost. So
# the packed form takes the rows in tiles a quarter of the call's pairs
# high, 16 to 64 rows (a decode block's 128 pairs: 32-row tiles, 6.0 us a
# whole-F step of 4.7 M weights, the MXUs' weight intake; a chunk's 4096:
# 64), and F in blocks of up to 2 M weights a tile (F = 768 and 1536 at
# D = 2048 whole or halved, 512 at D = 3072, 256 at D = 7168 and 7680,
# where 512 measured no better). The int8 form keeps 128 rows and
# `_pick_f_block`: it is bound by its copy under every tile.
_PACKED_TILE_WEIGHTS = 1 << 21


def _held_f_block(f: int, d: int, packed: bool) -> int:
    """The held kernel's F block: `_pick_f_block`'s for int8 values, the
    widest whole-groups divisor of F within `_PACKED_TILE_WEIGHTS` for
    packed words (F is whole groups of 256: the caller's assertion)."""
    if not packed:
        return _pick_f_block(f, d, quantized=True)
    fits = [
        b for b in range(PACKED_GROUP, f + 1, PACKED_GROUP)
        if f % b == 0 and d * b <= _PACKED_TILE_WEIGHTS
    ]
    return max(fits, default=PACKED_GROUP)


def _held_rows(pairs: int, packed: bool) -> int:
    """The held kernel's row tile (see above): 128 rows over int8 values; a
    quarter of the call's pairs over packed words, as a power of two from
    16 (a bf16 tile's sublanes) to 64."""
    if not packed:
        return _HELD_ROWS
    return min(64, max(16, pl.next_power_of_2(max(pairs, 1)) // 4))


def _held_compiler_params(d: int, bf: int):
    """A larger scoped-VMEM limit where the smallest legal F block still
    passes the default 16 MiB: at D = 7680 the three 7680 x 256 tiles, their
    dequantised copies and the 128-row x, out and accumulator blocks read
    16.41 MB on the described v5e (of 128 MiB physical). Expert shapes that
    fit (3072 x 256 and below) keep the default, and their programs."""
    if d * bf <= 1 << 20:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=48 << 20)


def _held_kernel_q40(lo_ref, hi_ref, tile_ref, expert_ref, n_ref, *refs, **kw):
    _grouped_kernel_q40(
        lo_ref, hi_ref, tile_ref, expert_ref, *refs, n_steps=n_ref[0], **kw
    )


def _with_count(index_map):
    """A grouped index map under one more scalar-prefetch operand."""
    return lambda g, fi, lo, hi, tile, expert, n: index_map(g, fi, lo, hi, tile, expert)


# What surrounds the kernel is sized by the call's pairs in the whole form:
# the schedule's scatters, the gather of x, the mask and the scatter-add. Where
# this chip holds a share of the experts the router scores, the held pairs are
# the sorted pairs' leading part, about `n_held / n_routed` of them, so the
# landed form builds all of that over the first `cap` sorted pairs, one and a
# half times the pairs a uniform router would land here, and the whole form is
# kept for the call whose pairs pass `cap`.
_LANDED_ROOM = 1.5
# Fewer rows saved than this and the call keeps one form. Microseconds a call,
# whole form and landed (PR 48's probe on a v5e, `scripts/moe_packed_probe.py`
# SHARE_CASES, rows saved in brackets): 7680 x 2048, 1024 pairs [832] 2445 and
# 1972, 4096 pairs [3328] 5680 and 3325; 4096 x 768, 320 pairs [192] 326 and
# 298, 1280 [768] 522 and 406, 5120 [3200] 1445 and 922; 2048 x 1536, 64 pairs
# [32] 191 and 197, 512 [320] 301 and 294, 2048 [1280] 490 and 425. A decode
# step's pairs (32 to 320 in the cells) keep the whole form's straight line.
_LANDED_MIN_SAVED = 512


def _landed_cap(pairs: int, rows: int, n_held: int, n_routed: int | None) -> int:
    """The sorted pairs the landed form is built over, a multiple of the row
    tile: the call's pairs padded to the tile (one form: the whole one)
    where every expert is held or too few rows would be saved."""
    a_pad = -(-pairs // rows) * rows
    if not n_routed or n_held >= n_routed:
        return a_pad
    landed = -(-int(_LANDED_ROOM * pairs * n_held) // n_routed)
    cap = max(-(-landed // rows), 1) * rows
    return cap if a_pad - cap >= _LANDED_MIN_SAVED else a_pad


def _sum_landed(o_sorted: jnp.ndarray, held_i: jnp.ndarray, n_pairs) -> jnp.ndarray:
    """The whole form's scatter-add out of the landed form's rows, [N, D]
    f32, by gathers: the scatter-add's time does not go with its rows (768
    rows of 7680 columns into 512 took 1.41 ms where 4096 took 1.96, PR 48's
    traces), a gather's does. A token's rows are added from zero in ascending
    sorted position, the order in which the scatter-add meets them (it sorts
    its updates by row, ties in place; the CPU applies them in turn): the
    same bits, which the probe checks on the chip at every shape."""
    n, k = held_i.shape
    order = jnp.argsort(held_i.reshape(-1), stable=True)
    # [k, N]: where the sorted order put each of a token's pairs
    at = jnp.sort(jnp.argsort(order).astype(jnp.int32).reshape(n, k), axis=1).T
    rows = jnp.take(o_sorted, at.reshape(-1), axis=0, mode="clip")
    rows = jnp.where((at < n_pairs).reshape(-1, 1), rows, 0.0).reshape(k, n, -1)
    out = jnp.zeros(rows.shape[1:], jnp.float32)
    for j in range(k):
        out = out + rows[j]
    return out


def held_forms(held_i: jnp.ndarray, n_held: int, n_routed: int, packed: bool):
    """int32 [3] of one `moe_held_experts_q40` call over `held_i`, for a
    program's counter: (1, 0, pairs landed) where it takes the landed form,
    (0, 1, pairs landed) where the whole one, by the kernel's own test."""
    cap = _landed_cap(held_i.size, _held_rows(held_i.size, packed), n_held, n_routed)
    n_pairs = jnp.sum(held_i < n_held).astype(jnp.int32)
    short = jnp.logical_and(cap < held_i.size, n_pairs <= cap).astype(jnp.int32)
    return jnp.stack([short, 1 - short, n_pairs])


@functools.partial(
    jax.jit, static_argnames=("n_routed", "interpret", "block_f", "row_tile", "cap"))
def moe_held_experts_q40(
    x: jnp.ndarray,  # [N, D]
    w1q: jnp.ndarray,  # [E, D, F] int8, E the experts held here; or the
    w1d: jnp.ndarray,  # packed words int32 [E, D // 8, F] of all three
    w2q: jnp.ndarray,
    w2d: jnp.ndarray,
    w3q: jnp.ndarray,
    w3d: jnp.ndarray,
    held_i: jnp.ndarray,  # [N, k] int32: the held expert's row, or E
    weights: jnp.ndarray,  # [N, k] f32
    layer=0,
    n_routed: int | None = None,  # the experts the router scores (None: E)
    interpret: bool = False,
    block_f: int | None = None,  # the probe's; served: `_held_f_block`
    row_tile: int | None = None,  # the probe's; served: `_held_rows`
    cap: int | None = None,  # the probe's and the tests'; served: `_landed_cap`
) -> jnp.ndarray:
    """The held experts' part of a layer's routed sum, [N, D] f32: zero for
    a token none of whose experts is held here. By the values' type, as
    `qmatmul` chooses its kernel: int8 values are dequantised by
    `_dequant_block`; int32 words of eight nibbles (`PackedQuantWeight`: the
    copy moves 0.625 B a weight where int8 moves 1.125) by `unpack_tile`,
    into the same bf16 tile bit for bit. Schedule, masks, accumulator and
    emit are one body's.

    A held share of `n_routed` experts: `lax.cond` on the pairs that landed
    takes the landed form (`_landed_cap`, `_sum_landed`) or the whole one.
    Row tile and F block are the call's in both, a pair's row meets the same
    dots in the same order and a token's pairs are added in the same order:
    one output, bit for bit."""
    n, d = x.shape
    e, _, f = w1d.shape[-3:]
    packed = w1q.dtype == jnp.int32
    pack = NIBBLES if packed else 1
    assert not packed or (d % PACKED_GROUP == 0 and f % PACKED_GROUP == 0), (d, f)
    assert w1q.shape[-2:] == (d // pack, f) and w2q.shape[-2:] == (f // pack, d)
    first, (w1q, w1d, w2q, w2d, w3q, w3d) = _layer_experts(
        layer, w1q, w1d, w2q, w2d, w3q, w3d
    )
    bf = block_f or _held_f_block(f, d, packed)
    n_f = f // bf
    r = row_tile or _held_rows(held_i.size, packed)
    w13_map, w2_map = _with_count(_grouped_w13_map), _with_count(_grouped_w2_map)

    def over(keep: int | None):
        """The routed sum from the sorted pairs' first `keep` (None: all)."""
        t_s, w_col, lo, hi, tile, expert = _grouped_schedule(
            held_i, weights, n, e, rows=r, keep=keep
        )
        a_pad = t_s.shape[0]
        # sorted, the held pairs lead: the steps up to the last of them
        n_pairs = jnp.sum(held_i < e).astype(jnp.int32)
        n_steps = jnp.sum(lo < n_pairs).astype(jnp.int32)
        x_sorted = jnp.take(x, t_s, axis=0).astype(jnp.bfloat16)
        o_sorted = pl.pallas_call(
            functools.partial(
                _held_kernel_q40, n_f=n_f, rows=r,
                dequant=unpack_tile if packed else _dequant_block,
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(n_steps, n_f),
                in_specs=[
                    pl.BlockSpec((r, d), _with_count(_grouped_x_map)),
                    pl.BlockSpec((r, 1), _with_count(_grouped_row_map)),
                    pl.BlockSpec((1, d // pack, bf), w13_map),
                    pl.BlockSpec((1, d // Q_BLOCK, bf), w13_map),
                    pl.BlockSpec((1, d // pack, bf), w13_map),
                    pl.BlockSpec((1, d // Q_BLOCK, bf), w13_map),
                    pl.BlockSpec((1, bf // pack, d), w2_map),
                    pl.BlockSpec((1, bf // Q_BLOCK, d), w2_map),
                ],
                out_specs=pl.BlockSpec((r, d), _with_count(_grouped_x_map)),
                scratch_shapes=[pltpu.VMEM((r, d), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((a_pad, d), jnp.float32),
            interpret=interpret,
            compiler_params=_held_compiler_params(d, bf),
        )(lo, hi, tile, expert + first, n_steps.reshape(1), x_sorted, w_col,
          w1q, w1d, w3q, w3d, w2q, w2d)
        if keep is not None:
            return _sum_landed(o_sorted, held_i, n_pairs)
        # tiles the grid never reached hold whatever the buffer held
        reached = jnp.arange(a_pad, dtype=jnp.int32)[:, None] < n_pairs
        return jnp.zeros((n, d), jnp.float32).at[t_s].add(
            jnp.where(reached, o_sorted, 0.0)
        )

    cap = cap or _landed_cap(held_i.size, r, e, n_routed)
    if cap >= held_i.size:
        return over(None)
    # jitted under names of their own: a profile names a kernel by the
    # function that holds it, and a `cond`'s branch has none
    def moe_held_experts_q40_landed():
        return over(cap)

    def moe_held_experts_q40_whole():
        return over(None)

    return jax.lax.cond(
        jnp.sum(held_i < e) <= cap,
        jax.jit(moe_held_experts_q40_landed), jax.jit(moe_held_experts_q40_whole))
