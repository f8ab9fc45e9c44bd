"""Grouped-int8 matmul: native MXU integer dots for Q40 checkpoints.

Round-3 silicon showed the shipping Q40 kernel is DEQUANT-compute-bound,
not DMA-bound: per-element int8->float conversion + sublane-broadcast
multiply on the VPU costs more than the HBM reads it saves (the kernel
realizes ~46% of HBM peak vs 67% for XLA dense bf16; the r3 sweep's
"int8-raw" probe, which measured the convert alone, ran 1.01 ms vs
0.47 ms for the full kernel). The fix is the
reference's own arithmetic (src/nn/nn-cpu-ops.cpp:231-449: Q80
activations x Q40 weights in INTEGER dot products, scales applied to the
block sums) restated for the MXU:

  * weights are REQUANTIZED once at load from Q40 (int4 values, per-32
    f16 scales — a CPU SIMD layout) to int8 values with per-(G, column)
    scales, G rows per group (default 512). int8 is the MXU's native
    low-precision input; the 16x coarser scale granularity is repaid by
    int8's 16x finer step (per-32 int4 step = d; per-512 int8 step =
    max_group|w|/127 <= 8*max_d/127 ~= d_max/16), so requantization adds
    less error than Q40 itself carries whenever a column's scales vary
    by < ~16x within a group.
  * activations are quantized per-(row, G-group) to int8 on the fly
    (XLA ops, fused into the preceding norm) — the Q80 analogue with
    group-sized blocks so the scale factors out of each MXU dot.
  * the kernel computes int8 x int8 -> int32 `lax.dot_general`s per
    G-slice — NO per-element dequant work at all — and applies
    sx[m,g] * sw[g,n] to the [m, bn] group sums in f32.

HBM traffic per weight: 1 byte + 4/G scale (~1.008 at G=512) vs 1.125
for the Q40 layout and 2.0 for bf16.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quant_matmul import BLOCK_M, QuantWeight, _pick_block, dequant


class Int8Weight(NamedTuple):
    """Grouped-int8 tensor in device layout (a pytree).

    ``q`` int8 [..., k, n] values in [-127, 127];
    ``s`` f32 [..., k // G, n] per-(group, column) scales. The group size
    G rides implicitly as ``k // s.shape[-2]`` so the pytree stays
    two-leaf and scan/device_put compose.
    """

    q: jnp.ndarray
    s: jnp.ndarray

    @property
    def in_dim(self) -> int:
        return self.q.shape[-2]

    @property
    def out_dim(self) -> int:
        return self.q.shape[-1]

    @property
    def group(self) -> int:
        return self.q.shape[-2] // self.s.shape[-2]


def requantize_q40(w: QuantWeight, group: int = 512) -> Int8Weight:
    """One-time load transform Q40 -> grouped int8 (see module docstring).

    Works on stacked [..., k, n] tensors. jit-safe; runs on-device at
    load so an 8B checkpoint requantizes in seconds.
    """
    k = w.in_dim
    if k % group != 0:
        raise ValueError(f"k={k} not divisible by group={group}")
    dense = dequant(w, jnp.float32)  # [..., k, n]
    *lead, _, n = dense.shape
    g = dense.reshape(*lead, k // group, group, n)
    s = jnp.max(jnp.abs(g), axis=-2) / 127.0  # [..., k//G, n]
    s = jnp.where(s == 0, 1.0, s)
    qi = jnp.clip(jnp.round(g / s[..., :, None, :]), -127, 127).astype(jnp.int8)
    return Int8Weight(qi.reshape(*lead, k, n), s)


def quantize_acts(x: jnp.ndarray, group: int):
    """Per-(row, G-group) int8 activation quantization: the Q80 step
    (reference: quantizeQ80Row) with group-sized blocks. Returns
    (xq int8 [..., k], sx f32 [..., k//G])."""
    *lead, k = x.shape
    if k % group != 0:
        raise ValueError(f"k={k} not divisible by group={group}")
    g = x.astype(jnp.float32).reshape(*lead, k // group, group)
    sx = jnp.max(jnp.abs(g), axis=-1) / 127.0
    sx = jnp.where(sx == 0, 1.0, sx)
    xq = jnp.clip(jnp.round(g / sx[..., None]), -127, 127).astype(jnp.int8)
    return xq.reshape(*lead, k), sx


def i8matmul_ref(x: jnp.ndarray, w: Int8Weight) -> jnp.ndarray:
    """Reference path (exact integer semantics of the kernel): quantize
    activations, integer dots per group, scale the group sums. Off-TPU
    fallback and the tests' oracle."""
    group = w.group
    *lead, k = x.shape
    m = int(np.prod(lead, dtype=np.int64)) if lead else 1
    xq, sx = quantize_acts(x.reshape(m, k), group)
    n = w.out_dim
    ng = k // group
    xg = xq.astype(jnp.int32).reshape(m, ng, group)
    qg = w.q.astype(jnp.int32).reshape(ng, group, n)
    idot = jnp.einsum("mgk,gkn->mgn", xg, qg)  # int32 group sums
    out = jnp.einsum(
        "mgn,mg,gn->mn", idot.astype(jnp.float32), sx, w.s.astype(jnp.float32)
    )
    return out.reshape(*lead, n)


def _i8mm_kernel(xq_ref, sx_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k: int,
                 group: int):
    """One (m, bn) output tile accumulated over k blocks: per G-slice
    native int8 MXU dots, scales applied to the [m, bn] group sums."""
    pk = pl.program_id(2)
    bk = xq_ref.shape[1]
    m = xq_ref.shape[0]
    partial_out = jnp.zeros((m, o_ref.shape[1]), jnp.float32)
    for g in range(bk // group):
        idot = lax.dot_general(
            xq_ref[:, g * group : (g + 1) * group],
            q_ref[g * group : (g + 1) * group, :],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        scale = sx_ref[:, g][:, None] * s_ref[g, :][None, :]
        partial_out = partial_out + idot.astype(jnp.float32) * scale

    @pl.when(pk == 0)
    def _init():
        acc_ref[:] = partial_out

    @pl.when(pk > 0)
    def _accum():
        acc_ref[:] += partial_out

    @pl.when(pk == n_k - 1)
    def _emit():
        o_ref[:] = acc_ref[:]


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_k", "interpret")
)
def i8matmul_2d(
    xq: jnp.ndarray,  # [m, k] int8
    sx: jnp.ndarray,  # [m, k // G] f32
    q: jnp.ndarray,  # [k, n] int8
    s: jnp.ndarray,  # [k // G, n] f32
    block_n: int = 256,
    block_k: int = 4096,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas grouped-int8 matmul; returns [m, n] f32.

    Default blocks inherit the Q40 sweep winner (bn=256, bk=4096) as the
    starting point; not yet swept on silicon."""
    m, k = xq.shape
    n = q.shape[1]
    ng = s.shape[0]
    assert k % ng == 0, (k, ng)
    group = k // ng
    assert q.shape == (k, n) and sx.shape == (m, ng), (q.shape, sx.shape)
    bn = _pick_block(n, block_n, ragged=True)
    # The k block must divide k AND hold whole groups; search downward over
    # group multiples for a divisor of k (group itself always qualifies:
    # pick_group guarantees group | k).
    bk = next(
        b
        for b in range(max(group, min(block_k, k) // group * group), 0, -group)
        if k % b == 0
    )
    assert k % bk == 0 and bk % group == 0, (k, bk, group)
    if s.dtype != jnp.float32:
        s = s.astype(jnp.float32)

    n_k = k // bk
    gpb = bk // group  # scale rows per k block
    # Scale planes ride with the k-block index as a leading, squeezed axis:
    # a (gpb, bn) / (m, gpb) block cut straight out of [ng, n] / [m, ng] is
    # only legal on the chip when gpb is a multiple of 8 / 128 or the whole
    # axis (k=14336, G=512: gpb=7 of ng=28 is neither). As the trailing
    # dims of [n_k, gpb, n] and [n_k, m, gpb] they always equal the array's.
    s3 = s.reshape(n_k, gpb, n)
    sx3 = sx.reshape(m, n_k, gpb).transpose(1, 0, 2)
    bm = min(m, BLOCK_M)  # row tiling: see quant_matmul.BLOCK_M
    # k innermost: the accumulator tile stays live
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), n_k)
    return pl.pallas_call(
        functools.partial(_i8mm_kernel, n_k=n_k, group=group),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda r, i, j: (r, j)),
            pl.BlockSpec((None, bm, gpb), lambda r, i, j: (j, r, 0)),
            pl.BlockSpec((bk, bn), lambda r, i, j: (j, i)),
            pl.BlockSpec((None, gpb, bn), lambda r, i, j: (j, 0, i)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda r, i, j: (r, i)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xq, sx3, q, s3)


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def i8matmul(x: jnp.ndarray, w: Int8Weight, block_n: int = 256) -> jnp.ndarray:
    """x [..., in] @ W -> [..., out] f32, auto-flattening leading dims.
    Quantizes activations per group on the fly (XLA, fuses into the
    preceding ops), then dispatches to the Pallas kernel on TPU; off-TPU
    uses the exact-integer reference path."""
    if not _use_pallas():
        return i8matmul_ref(x, w)
    *lead, k = x.shape
    m = 1
    for d in lead:
        m *= d
    xq, sx = quantize_acts(x.reshape(m, k), w.group)
    out = i8matmul_2d(xq, sx, w.q, w.s, block_n=block_n)
    return out.reshape(*lead, w.out_dim)


def i8matmul_tp(
    x: jnp.ndarray,  # [B, T, in]
    w: Int8Weight,  # [in, out] (+ grouped scales), possibly tp-sharded
    role: str,  # "row" (out split) | "col" (in split, partial-sum psum)
    mesh=None,
    sync_quant: bool = False,
) -> jnp.ndarray:
    """Tensor-parallel grouped-int8 matmul — same collective layout as
    quant_matmul.qmatmul_tp (row split: no collective; col split: psum
    where the reference ran SYNC_NODE_SLICES + OP_MERGE_ADD). Activation
    quantization happens INSIDE the shard body on the local x slice, so
    col-split groups align with the shard's own scale rows."""
    if not _use_pallas():
        return i8matmul_ref(x, w)
    if mesh is None or mesh.devices.size == 1:
        return i8matmul(x, w)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if role == "row":
        in_specs = (
            P("dp", None, None),
            P(None, "tp"),
            P(None, "tp"),
        )
        out_spec = P("dp", None, "tp")

        def f(xx, qq, ss):
            return i8matmul(xx, Int8Weight(qq, ss))

    elif role == "col":
        from ..parallel.collectives import psum_maybe_quantized

        in_specs = (
            P("dp", None, "tp"),
            P("tp", None),
            P("tp", None),
        )
        out_spec = P("dp", None, None)

        def f(xx, qq, ss):
            return psum_maybe_quantized(
                i8matmul(xx, Int8Weight(qq, ss)), "tp", sync_quant
            )

    else:
        raise ValueError(f"unknown role: {role}")

    return shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_spec, check_vma=False
    )(x, w.q, w.s)


def requantize_q40_stacked(w: QuantWeight, group: int = 512) -> Int8Weight:
    """Layer-stacked [L, k, n] requantization with bounded transient
    memory: `lax.map` processes one layer at a time, so the f32 dequant
    scratch peaks at one layer's [k, n] instead of the whole stack (an
    8B w13 stack would need ~15 GB at once)."""
    if w.q.ndim == 2:
        return jax.jit(requantize_q40, static_argnames=("group",))(
            w, group=group
        )
    return lax.map(
        lambda wl: requantize_q40(wl, group), w
    )


def pick_group(h, tp: int, preferred: int = 512) -> int:
    """Largest group <= preferred dividing every PER-SHARD contraction
    dim (row matmuls contract over the full `dim`; col splits contract
    over q_dim/tp and ff_dim/tp locally), so scale rows tile both the
    weight shards and the kernel's k blocks."""
    import math

    dims = [h.dim, h.q_dim // tp, h.ff_dim // tp]
    g = math.gcd(*dims)
    group = min(preferred, g)
    while group > 1 and any(d % group for d in dims):
        group //= 2
    if group < 32:
        raise ValueError(
            f"no viable int8 group for dims {dims} (gcd {g}); "
            "use weight_format='q40'"
        )
    return group


def requantize_params(params: dict, h, group: int) -> dict:
    """Load-time transform of a q40 params tree to grouped int8: every
    attention/FFN/vocab QuantWeight becomes an Int8Weight (fused wrappers
    keep their interleave metadata). MoE EXPERT tensors stay Q40 — the
    ragged/grouped MoE kernels consume Q40 blocks natively and their
    active-expert DMA schedule is the win there."""
    from .quant_matmul import FusedQuantWeight

    moe = bool(getattr(h, "n_experts", 0))

    def conv(v, name: str):
        if isinstance(v, FusedQuantWeight):
            return FusedQuantWeight(
                requantize_q40_stacked(v.weight, group), v.fuse, v.dims
            )
        if isinstance(v, QuantWeight):
            if moe and name in ("w1", "w2", "w3"):
                return v  # expert tensors stay q40 for the MoE kernels
            return requantize_q40_stacked(v, group)
        return v

    out = dict(params)
    out["layers"] = {
        k: conv(v, k) for k, v in params["layers"].items()
    }
    if isinstance(params.get("wcls"), QuantWeight):
        out["wcls"] = requantize_q40_stacked(params["wcls"], group)
    return out
