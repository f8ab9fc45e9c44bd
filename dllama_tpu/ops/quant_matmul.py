"""Q40 weight-quantized matmul: Pallas TPU kernel + jnp reference.

The reference's hottest kernel is the Q80-activation x Q40-weight int dot
(src/nn/nn-cpu-ops.cpp:231-449). On TPU the right design is different
(SURVEY.md §7 translation table): weights stay block-quantized in HBM
(int8 values + per-32-block scales — 0.56 B/elem vs 2 for bf16) and are
dequantized INSIDE the kernel after the HBM->VMEM copy, feeding the MXU in
bf16. Decode-step matmuls are HBM-bandwidth-bound, so the ~3.6x traffic
reduction is the win; the reference's int8 activation quantization was a
CPU SIMD trick, not a quality choice, and is deliberately not reproduced
(activations ride in bf16; accumulation is f32 like the reference).

Device layout — chosen for the TPU (sublane, lane) tiling: weights are
stored TRANSPOSED relative to the `.m` file, ``q`` int8 [in, out] with the
contraction (in) axis on sublanes. The 32-element quant blocks then run
along sublanes, so the in-kernel dequant is a sublane-broadcast multiply
(a lane-dim reshape would be an unsupported Mosaic shape cast):

    w[i, o] = q[i, o] * d[i // 32, o]        # d: [in // 32, out]

and the MXU consumes ``x [m, in] @ w [in, out]`` directly, no transpose.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Q_BLOCK = 32


class QuantWeight(NamedTuple):
    """Planar Q40 tensor in device layout (a pytree of two leaves). A
    model's layers hold one such tensor per weight with a leading layer
    axis, [L, in, out]: the kernels take that stack whole with a layer
    number (`qmatmul(x, w, layer)`) and their block specs pick the layer.

    ``q`` int8 [..., in, out] with values in [-8, 7];
    ``d`` f32 [..., in // 32, out] per-block scales (f32 holds the wire's
    f16 values exactly; bf16 would round them — scale bytes are ~2% of the
    tensor so the traffic cost is noise).
    """

    q: jnp.ndarray
    d: jnp.ndarray

    @property
    def in_dim(self) -> int:
        return self.q.shape[-2]

    @property
    def out_dim(self) -> int:
        return self.q.shape[-1]


# nibbles of a packed word; the weight rows of a packed tensor's group: a
# quant block a nibble position, which the kernel asks of every in axis and
# a tp shard's in slice has to be whole multiples of
NIBBLES = 8
PACKED_GROUP = NIBBLES * Q_BLOCK


def packed_kernels_take(in_dim: int, shards: int = 1) -> bool:
    """Whether the packed kernels take an in axis split over `shards`: each
    shard's slice is whole groups of 256 rows. The one statement of it;
    models/loader decides from it which tensors of a file are held packed."""
    return in_dim % (PACKED_GROUP * shards) == 0


def packed_segment(in_dim: int) -> int:
    """Weight rows a nibble position covers inside one group of a packed
    tensor: a quant block (32) where the in axis is whole groups of 8 x 32
    = 256 rows, which every served width is and the kernel asks for; else
    the axis is one group of eight equal segments (tiny test models)."""
    return Q_BLOCK if packed_kernels_take(in_dim) else in_dim // NIBBLES


class PackedQuantWeight(NamedTuple):
    """Packed-nibble Q40 tensor in device layout (weight_format="q40i4",
    and what "auto" serves on a TPU): 0.5 B a weight in HBM, unpacked by
    the kernel AFTER the HBM->VMEM copy.

    ``qp`` int32 [..., in // 8, out]: eight weights a word, each a
    two's-complement nibble (the wire's ``nib - 8``, so a shift pair
    sign-extends it: no mask, no subtract). Word row ``g * seg + t`` holds
    in nibble ``j`` (bits 4j..4j+3) weight row ``g * 8 * seg + j * seg + t``
    with ``seg = packed_segment(in)`` = 32: a nibble position of 32 word
    rows is one whole quant block, already in the (8, 128) tiles of an
    int32 array, so the unpacked pieces need no sublane shuffle and meet
    their block's scale row as they are.
    ``d``  f32 [..., in // 32, out] per-block scales, as `QuantWeight`'s
    (the wire's f16 values, exactly).

    0.5 + 4/32 = 0.625 B/weight including scales, vs 1.125 for the
    unpacked QuantWeight layout. The dequantised bf16 tile is the int8
    kernel's bit for bit: ``(nib - 8) * d`` in f32, narrowed.
    """

    qp: jnp.ndarray
    d: jnp.ndarray

    @property
    def in_dim(self) -> int:
        return self.qp.shape[-2] * NIBBLES

    @property
    def out_dim(self) -> int:
        return self.qp.shape[-1]


@jax.tree_util.register_pytree_node_class
class FusedQuantWeight:
    """Several row-split matmul weights fused along the out axis in
    shard-major interleaved order (models/loader._interleave_concat).

    ``fuse`` (the interleave shard count) and ``dims`` (the constituents'
    global out dims) ride as STATIC pytree aux data, so the un-interleave
    factor travels with the weights themselves — consuming fused params on
    a mesh with a different tp cannot silently mis-permute columns."""

    def __init__(self, weight: QuantWeight, fuse: int, dims: tuple[int, ...]):
        self.weight = weight
        self.fuse = int(fuse)
        self.dims = tuple(int(d) for d in dims)

    def tree_flatten(self):
        return (self.weight,), (self.fuse, self.dims)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


def planar_to_device_layout(
    q_out_in: np.ndarray, d_out_blocks: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side layout transform from `q40_to_planar` output ([out, in]
    values, [out, in//32] f16 scales) to the kernel layout: transpose so the
    contraction axis leads, scales widened to f32."""
    q = np.ascontiguousarray(np.swapaxes(q_out_in, -1, -2))
    d = np.ascontiguousarray(np.swapaxes(d_out_blocks, -1, -2)).astype(np.float32)
    return q, d


def from_planar(q_out_in: np.ndarray, d_out_blocks: np.ndarray) -> QuantWeight:
    """Device QuantWeight from `q40_to_planar` output."""
    q, d = planar_to_device_layout(q_out_in, d_out_blocks)
    return QuantWeight(jnp.asarray(q), jnp.asarray(d))


def dequant(w: QuantWeight, dtype=jnp.bfloat16) -> jnp.ndarray:
    """[..., in, out] dense tensor (jnp reference semantics of
    nn-quants.cpp:229-246)."""
    *lead, inner, out = w.q.shape
    q = w.q.astype(jnp.float32).reshape(*lead, inner // Q_BLOCK, Q_BLOCK, out)
    dense = q * w.d.astype(jnp.float32)[..., :, None, :]
    return dense.reshape(*lead, inner, out).astype(dtype)


def pack_nibbles(w: QuantWeight) -> PackedQuantWeight:
    """Device-layout int8 QuantWeight -> PackedQuantWeight (jnp;
    formats.quants.pack_q40_device packs the wire's bytes for the load
    path). Values must already be in [-8, 7]."""
    *lead, inner, out = w.q.shape
    seg = packed_segment(inner)
    nib = jax.lax.bitcast_convert_type(w.q.astype(jnp.int32) & 0xF, jnp.uint32)
    nib = nib.reshape(*lead, inner // (NIBBLES * seg), NIBBLES, seg, out)
    shifts = (4 * jnp.arange(NIBBLES, dtype=jnp.uint32)).reshape(NIBBLES, 1, 1)
    words = jnp.sum(nib << shifts, axis=-3, dtype=jnp.uint32)  # disjoint bits
    return PackedQuantWeight(
        jax.lax.bitcast_convert_type(words, jnp.int32).reshape(
            *lead, inner // NIBBLES, out
        ),
        w.d.astype(jnp.float32),
    )


def _nibble(words: jnp.ndarray, j: int) -> jnp.ndarray:
    """Nibble j of every word as int32 in [-8, 7]: to the top of the word,
    then an arithmetic shift down sign-extends it."""
    return (words << (28 - 4 * j) if j < NIBBLES - 1 else words) >> 28


def unpack_nibbles(qp: jnp.ndarray) -> jnp.ndarray:
    """Packed words [..., in // 8, out] -> int values [..., in, out] int32
    in [-8, 7], a group's eight nibble positions side by side (the jnp twin
    of what `_qmm_i4_kernel` does to a VMEM tile)."""
    *lead, rows, out = qp.shape
    seg = packed_segment(rows * NIBBLES)
    words = qp.reshape(*lead, rows // seg, seg, out)
    pieces = [_nibble(words, j) for j in range(NIBBLES)]
    return jnp.concatenate(pieces, axis=-2).reshape(*lead, rows * NIBBLES, out)


def dequant_packed(w: PackedQuantWeight, dtype=jnp.bfloat16) -> jnp.ndarray:
    """[..., in, out] dense tensor from the packed-nibble layout; computes
    exactly what `dequant` computes on the unpacked equivalent (same int
    values, same scales)."""
    return dequant(QuantWeight(unpack_nibbles(w.qp), w.d), dtype)


def layer_of(w, layer):
    """Layer `layer` (a traced scalar) of a [L, ...] stacked weight, for
    consumers that XLA compiles and so fuses the slice into."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False), w
    )


def qmatmul_ref(x: jnp.ndarray, w, layer=None) -> jnp.ndarray:
    """Reference path: dequant + dense matmul. x [..., in] -> [..., out] f32.
    Used for equivalence tests and as the off-TPU fallback. Accepts both
    QuantWeight and PackedQuantWeight, and like the kernels a [L, in, out]
    stack with a layer number, sliced before it is dequantised."""
    if layer is not None:
        w = layer_of(w, layer)
    if isinstance(w, PackedQuantWeight):
        dense = dequant_packed(w, jnp.float32)
    else:
        dense = dequant(w, jnp.float32)
    return jnp.einsum("...i,io->...o", x.astype(jnp.float32), dense)


def _mxu_accumulate(x_ref, w, o_ref, acc_ref, n_k: int):
    """The tail of both Q40 kernels: the rows against the dequantised bf16
    tile `w` on the MXU, accumulated over the k steps in VMEM scratch."""
    pk = pl.program_id(2)
    partial_out = jax.lax.dot_general(
        x_ref[:],
        w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pk == 0)
    def _init():
        acc_ref[:] = partial_out

    @pl.when(pk > 0)
    def _accum():
        acc_ref[:] += partial_out

    @pl.when(pk == n_k - 1)
    def _emit():
        o_ref[:] = acc_ref[:]


def _qmm_kernel(l_ref, x_ref, q_ref, d_ref, o_ref, acc_ref, *, n_k: int):
    """One (m, block_n) output tile, accumulated over k blocks in VMEM
    scratch: sublane-broadcast dequant then MXU. `l_ref` (the layer
    number) is read by the block specs alone."""
    q = q_ref[:]  # [bk, bn] int8
    d = d_ref[:]  # [bk // 32, bn] f32
    bk, bn = q.shape
    w = (
        (
            q.astype(jnp.float32).reshape(bk // Q_BLOCK, Q_BLOCK, bn)
            * d[:, None, :]
        )
        .reshape(bk, bn)
        .astype(jnp.bfloat16)
    )
    _mxu_accumulate(x_ref, w, o_ref, acc_ref, n_k)


def unpack_tile(qp: jnp.ndarray, d: jnp.ndarray) -> jnp.ndarray:
    """A VMEM tile of packed words int32 [bk // 8, bn] under its scale rows
    f32 [bk // 32, bn] -> bf16 [bk, bn]: each nibble position of a group's
    32 word rows is shifted out as one quant block of 32 weight rows,
    multiplied by its scale row broadcast along sublanes and narrowed, all
    in whole (8, 128) tiles; the eight blocks side by side are the group's
    256 rows of the tile the int8 kernels build (`_qmm_kernel`,
    `moe_kernel._dequant_block`), bit for bit."""
    rows, bn = qp.shape
    groups = rows // Q_BLOCK
    words = qp.reshape(groups, Q_BLOCK, bn)
    d = d.reshape(groups, NIBBLES, bn)  # a group's eight scale rows
    pieces = [
        (_nibble(words, j).astype(jnp.float32) * d[:, j : j + 1, :]).astype(
            jnp.bfloat16
        )
        for j in range(NIBBLES)
    ]
    return jnp.concatenate(pieces, axis=1).reshape(rows * NIBBLES, bn)


def _qmm_i4_kernel(l_ref, x_ref, qp_ref, d_ref, o_ref, acc_ref, *, n_k: int):
    """One (m, block_n) output tile from packed words: the HBM->VMEM copy
    moves 0.625 B a weight, then `unpack_tile` builds the bf16 tile
    `_qmm_kernel` builds, bit for bit."""
    _mxu_accumulate(x_ref, unpack_tile(qp_ref[:], d_ref[:]), o_ref, acc_ref, n_k)


def _pick_block(n: int, preferred: int, ragged: bool = False, step: int = 128) -> int:
    """Block length for an axis of length n: the largest 128-multiple
    <= preferred that divides n (vocab dims like 151936 aren't multiples
    of 256), or n itself when the whole axis fits in one block. `step`:
    what the block is to be a multiple of, where 128 is not enough.

    Otherwise no 128-multiple tiles the axis exactly (a tp shard of a
    Llama-3 vocab: 128256 / 4 = 32064 = 250.5 x 128). An output (n) axis
    may then run `ragged`: `preferred`-wide blocks on a `pl.cdiv` grid,
    where Pallas pads the tail block's reads and drops its out-of-range
    writes — columns are independent, so the pad never reaches a kept
    value. A contraction axis can't (pad rows would be summed in), and a
    whole-axis block would only turn into a minutes-long compiler OOM,
    so that case raises here with the shape in it."""
    if n <= preferred:
        return n
    for b in range(preferred // step * step, 0, -step):
        if n % b == 0:
            return b
    if ragged and preferred % 128 == 0:
        return preferred
    raise ValueError(
        f"no legal kernel block for an axis of length {n}: no multiple of "
        f"{step} <= {preferred} divides it"
        + ("" if ragged else " and a contraction axis cannot be padded")
    )


# Row (m) block: a prefill chunk over several lanes is thousands of rows,
# and an (m, block_k) activation tile that size alone overflows the 16 MB
# of scoped VMEM (m=2048: 2 x 16 MB double-buffered). 512 rows compile at
# every 8B width; beyond that the rows are tiled, re-reading the weights
# once per row block — prefill is compute-bound, so the re-read is cheap.
BLOCK_M = 512
# What a k step that accumulates may hold of the activations, in bytes of
# its bf16 (rows, block_k) tile: 512 rows against 3584 deep, the largest
# that has compiled (k = 14336). At 4096 deep, accumulated over three steps
# (k = 12288), the described v5e's compiler passes the 16 MB of scoped VMEM
# by a third of a megabyte; a single step that deep (k = 4096) keeps no
# partial product beside the accumulator and fits.
ACC_X_TILE_BYTES = BLOCK_M * 3584 * 2
# the most rows at which the packed kernel takes 512-wide tiles
DECODE_ROWS = 64


def _pick_row_block(m: int) -> int:
    """Rows a block: the call's rows whole up to BLOCK_M; beyond it the
    fewest blocks that hold them, evened out. A block costs its rows on the
    MXUs whatever is real in it, so 640 rows (five lanes' 128-row chunk) are
    2 x 320 and not 512 and a tail of 128 padded to 512, and 1280 are 3 x
    432 (the last one ragged by 16: rows are independent, the pad's are
    dropped) where 3 x 512 compute a fifth more. A multiple of 16, the
    sublanes of a bf16 tile; any multiple of BLOCK_M keeps its blocks."""
    if m <= BLOCK_M:
        return m
    n_blocks = pl.cdiv(m, BLOCK_M)
    return pl.cdiv(pl.cdiv(m, n_blocks), 16) * 16


def _pick_k_block(k: int, preferred: int, rows: int) -> int:
    """`_pick_block` for the contraction axis under `rows` activation rows:
    the deepest legal block whose activation tile, where k takes several
    steps, stays within `ACC_X_TILE_BYTES`. A block that is not the whole
    axis is a multiple of 256: its 32nd part, the scales' block, has to be a
    multiple of 8 rows (k = 11776 = 23 x 512 has the 128-multiple 2944, whose
    92 scale rows the chip's compiler refuses; 512 it takes)."""
    bk = _pick_block(k, preferred, step=8 * Q_BLOCK)
    if bk < k and rows * bk * 2 > ACC_X_TILE_BYTES:
        bk = _pick_block(k, ACC_X_TILE_BYTES // (rows * 2) // 128 * 128, step=8 * Q_BLOCK)
    return bk


def _qmm_call(
    kernel, x, values, scales, layer, pack: int, block_n: int, block_k: int,
    interpret: bool,
) -> jnp.ndarray:
    """The one `pallas_call` of both Q40 kernels: `values` [L, k // pack, n]
    and `scales` [L, k // 32, n] stay whole in HBM, the layer number rides
    in as scalar prefetch, and the weight blocks' index maps pick the layer
    — so a layer scan that closes over the stacks copies nothing out of
    them. A 2-D weight (`wcls`) is a stack of one."""
    if values.ndim == 2:
        assert layer is None, "a layer number needs a [L, k, n] stack"
        values, scales, layer = values[None], scales[None], 0
    m, k = x.shape
    n = values.shape[-1]
    assert values.shape[1:] == (k // pack, n), (values.shape, x.shape)
    assert scales.shape == (values.shape[0], k // Q_BLOCK, n), scales.shape
    bn = _pick_block(n, block_n, ragged=True)
    bm = _pick_row_block(m)
    bk = _pick_k_block(k, block_k, bm)
    assert bk % Q_BLOCK == 0
    n_k = k // bk
    return pl.pallas_call(
        functools.partial(kernel, n_k=n_k),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # k innermost: the accumulator tile stays live
            grid=(pl.cdiv(m, bm), pl.cdiv(n, bn), n_k),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda r, i, j, l: (r, j)),
                pl.BlockSpec(
                    (None, bk // pack, bn), lambda r, i, j, l: (l[0], j, i)
                ),
                pl.BlockSpec(
                    (None, bk // Q_BLOCK, bn), lambda r, i, j, l: (l[0], j, i)
                ),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda r, i, j, l: (r, i)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        x.astype(jnp.bfloat16), values, scales,
    )


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_k", "interpret")
)
def qmatmul_2d(
    x: jnp.ndarray,  # [m, k]
    q: jnp.ndarray,  # [L, k, n] int8, or [k, n]
    d: jnp.ndarray,  # [L, k // 32, n] f32, or [k // 32, n]
    layer=None,  # int32 scalar: which layer of a stack
    block_n: int = 256,
    block_k: int = 4096,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas quantized matmul of 2D activations with one layer of a weight
    stack; returns [m, n] f32.

    Default blocks are the round-3 silicon sweep winner (v5e, m=1 k=4096
    n=14336): (bn=256, bk=4096) ran
    0.465 ms vs 0.893 ms for the previous (512, 2048) default and 0.936 ms
    for XLA's dense bf16 matvec on the same shape — narrow n tiles with
    the whole k per step keep the accumulator live and the weight DMAs
    tall; wider tiles hit the 16 MB scoped-VMEM ceiling."""
    if d.dtype != jnp.float32:
        d = d.astype(jnp.float32)
    return _qmm_call(
        _qmm_kernel, x, q, d, layer, 1, block_n, block_k, interpret
    )


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_k", "interpret")
)
def qmatmul_i4_2d(
    x: jnp.ndarray,  # [m, k]
    qp: jnp.ndarray,  # [L, k // 8, n] int32 packed words, or [k // 8, n]
    d: jnp.ndarray,  # [L, k // 32, n] f32, or [k // 32, n]
    layer=None,
    block_n: int | None = None,
    block_k: int = 4096,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas packed-nibble quantized matmul; returns [m, n] f32.

    Same call as `qmatmul_2d` (`_qmm_call`); the weight BlockSpec moves an
    eighth of the rows because each word carries eight values. k has to be
    whole groups of 256 rows (`packed_segment`).

    `block_n` by the rows, where the caller names none: at decode rows a
    call is bound by how fast the MXUs take weight tiles (4 x 128 weights a
    cycle: 1.37 us a [4096, 256] tile on a v5e, whatever the bytes), and
    512-wide tiles reach that (w13 at 5 rows: 155 us against 173 at 256
    wide and the int8 kernel's 205; PR 43's chip runs); under a chunk's
    512-row blocks a tile that wide passes the 16 MB of scoped VMEM."""
    assert qp.dtype == jnp.int32 and d.dtype == jnp.float32, (qp.dtype, d.dtype)
    assert packed_kernels_take(x.shape[-1]), x.shape
    if block_n is None:
        block_n = 512 if x.shape[0] <= DECODE_ROWS else 256
    return _qmm_call(
        _qmm_i4_kernel, x, qp, d, layer, NIBBLES, block_n, block_k, interpret
    )


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def qmatmul(x: jnp.ndarray, w, layer=None) -> jnp.ndarray:
    """x [..., in] @ W -> [..., out] f32, auto-flattening leading dims.

    Accepts QuantWeight (int8 values) or PackedQuantWeight (nibble-packed),
    [in, out], or a [L, in, out] stack with the `layer` to take.
    Dispatches to the matching Pallas kernel on TPU; off-TPU (CPU test
    meshes) uses the dequant reference path — pallas interpret mode is
    orders of magnitude slower and numerically identical anyway.
    """
    *lead, k = x.shape
    packed = isinstance(w, PackedQuantWeight)
    # a packed axis that is not whole groups of 256 is no served width
    if not _use_pallas() or (packed and not packed_kernels_take(k)):
        return qmatmul_ref(x, w, layer)
    m = 1
    for s in lead:
        m *= s
    kernel = qmatmul_i4_2d if packed else qmatmul_2d
    out = kernel(x.reshape(m, k), *w, layer)
    return out.reshape(*lead, w.out_dim)


def qmatmul_tp(
    x: jnp.ndarray,  # [B, T, in]
    w,  # QuantWeight | PackedQuantWeight [in, out] (+ scales), tp-shardable,
    #   or the [L, in, out] stack when `layer` is given
    role: str,  # "row" (out split) | "col" (in split, partial-sum psum)
    mesh=None,
    sync_quant: bool = False,  # Q80-compress the col-split partial-sum
    #   all-reduce payload (the reference's --buffer-float-type q80; see
    #   parallel/collectives.psum_q80) — for DCN multi-host, not ICI
    layer=None,  # int32 scalar: which layer of the stack
) -> jnp.ndarray:
    """Tensor-parallel quantized matmul.

    GSPMD cannot partition a `pallas_call`, so on a multi-device mesh the
    kernel runs per-shard under `shard_map` with the TP layout made
    explicit — the manual-collective restatement of the reference's design:
    row-split needs no collective (the all-gather the reference does per
    block is deferred to the residual psum), col-split partial sums psum
    over ICI exactly where the reference ran SYNC_NODE_SLICES + OP_MERGE_ADD
    (src/llm.cpp:403,554). A stack's layer axis is on no mesh axis and the
    layer number is replicated.

    Off TPU this degrades to the dequant einsum and lets GSPMD shard it.
    """
    if not _use_pallas():
        return qmatmul_ref(x, w, layer)
    if mesh is None or mesh.devices.size == 1:
        return qmatmul(x, w, layer)

    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    # both weight classes are (values, scales) NamedTuples whose leaves
    # shard identically: the packed in/8 axis and the in/32 scale axis
    # both divide by tp under the engine's divisibility check (32*tp; a
    # packed in axis whole groups of 256 a shard)
    cls = type(w)
    stack = (None,) * (w[0].ndim - 2)
    at = () if layer is None else (jnp.asarray(layer, jnp.int32),)

    if role == "row":
        x_spec, w_spec = P("dp", None, None), P(*stack, None, "tp")
        out_spec = P("dp", None, "tp")

        def f(xx, qq, dd, *ll):
            return qmatmul(xx, cls(qq, dd), *ll)

    elif role == "col":
        from ..parallel.collectives import psum_maybe_quantized

        x_spec, w_spec = P("dp", None, "tp"), P(*stack, "tp", None)
        out_spec = P("dp", None, None)

        def f(xx, qq, dd, *ll):
            return psum_maybe_quantized(
                qmatmul(xx, cls(qq, dd), *ll), "tp", sync_quant
            )

    else:
        raise ValueError(f"unknown role: {role}")

    return shard_map(
        f,
        mesh=mesh,
        in_specs=(x_spec, w_spec, w_spec) + (P(),) * len(at),
        out_specs=out_spec,
        check_vma=False,
    )(x, *w, *at)
