"""Quantized KV-cache container and helpers (shared by models and ops).

Lives in ops/ (not models/) so the Pallas attention kernels can consume a
QuantKV natively without a models<->ops import cycle: the int8-KV flash
prefill passes the int8 values and per-row scales straight
into the kernel instead of materializing a dense bf16 view of the cache.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
from jax import lax


class QuantKV(NamedTuple):
    """int8 KV cache tensor: per-row (position) symmetric quantization.

    ``q`` int8 [..., S, hd]; ``s`` f32 [..., S, 1] per-row scales. The
    trailing singleton keeps the scale tensor the same RANK as the
    values, so every positional write strategy (plain / cyclic-sp /
    owning-shard window) and every PartitionSpec applies to both leaves
    unchanged. The flash prefill kernels consume the pair natively (the
    layer's scale rows ride as a second ref that follows the kv index
    map; dequant happens on the VMEM tile after the DMA — so prefill
    reads int8 bytes, not a materialized dense copy); the windowed
    decode read dequants in XLA, fused into the attention dot. Halves
    KV HBM vs bf16 (+1/(2*hd) scale overhead): the long-context fit
    lever on top of the windowed reads."""

    q: jnp.ndarray
    s: jnp.ndarray

    @property
    def shape(self):  # value-tensor shape: callers index S via shape[i]
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype


def quantize_kv_rows(val: jnp.ndarray):
    """[..., T, hd] -> (int8 values, f32 [..., T, 1] scales): symmetric
    quantization with one scale per cache row, max|row| / 127 (the
    reference's Q80 step, quantizeQ80Row, with the row as its block). An
    all-zero row takes scale 1, so nothing divides by zero."""
    x = val.astype(jnp.float32)
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    q = jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8)
    return q, s


def dequant_kv(cache_l, dtype):
    """Dense view of a cache leaf: QuantKV -> values * scales (XLA
    fuses this into the consuming attention dot on the decode path);
    plain arrays pass through."""
    if isinstance(cache_l, QuantKV):
        return (cache_l.q.astype(jnp.float32) * cache_l.s).astype(dtype)
    return cache_l


def layer_rows(stack, layer, rows: int = 0, start=0):
    """`rows` rows (0 = all) of layer `layer` of a [L, B, KH, S, hd] cache
    stack, from row `start` (a scalar, or [B] per lane), as [B, KH, rows,
    hd]; QuantKV-aware. One `dynamic_slice` into the stack where it lies:
    what a consumer can at most materialise is the rows it asked for,
    never the layer."""
    if isinstance(stack, QuantKV):
        return QuantKV(
            layer_rows(stack.q, layer, rows, start),
            layer_rows(stack.s, layer, rows, start),
        )
    _, b, kh, s, hd = stack.shape
    if jnp.ndim(start) == 1:
        return jnp.concatenate([
            layer_rows(stack[:, lane : lane + 1], layer, rows, start[lane])
            for lane in range(b)
        ])
    return lax.dynamic_slice(
        stack, (layer, 0, 0, start, 0), (1, b, kh, rows or s, hd)
    )[0]


def write_rows(stack, layer, start, val):
    """Write `val` [B, KH, T, hd] at rows [start, start + T) of layer
    `layer` of a [L, B, KH, S, hd] cache leaf (`start` a scalar, or [B]
    per lane: then one update a lane). Each is a `dynamic_update_slice`
    of the rows alone, so a stack that is a loop's carry or a donated
    argument is updated in place: T rows move, not the layer. A static
    loop over the lanes and not one scatter: on the v5e a leaf's write
    took 7.9 against 37.5 us at 5 lanes and T = 1, 20.7 against 60.3 at
    16, 83 against 320 at T = 512 (PERF.md section 6, PR 29)."""
    val = val[None].astype(stack.dtype)
    if jnp.ndim(start) == 0:
        return lax.dynamic_update_slice(stack, val, (layer, 0, 0, start, 0))
    for lane in range(val.shape[1]):
        stack = lax.dynamic_update_slice(
            stack, val[:, lane : lane + 1], (layer, lane, 0, start[lane], 0)
        )
    return stack


def gather_pages(pool_l, page_ids):
    """Contiguous read view of a paged pool leaf.

    ``pool_l`` [P, KH, ps, hd] (one layer of the engine's page pool, or a
    QuantKV pair of [P, KH, ps, hd] values + [P, KH, ps, 1] scales) and
    ``page_ids`` [n] int32 -> [KH, n*ps, hd] rows in page order, the
    head-major layout every attention path consumes."""
    if isinstance(pool_l, QuantKV):
        return QuantKV(
            gather_pages(pool_l.q, page_ids), gather_pages(pool_l.s, page_ids)
        )
    pages = pool_l[page_ids]  # [n, KH, ps, last]
    n, kh, ps, last = pages.shape
    return pages.transpose(1, 0, 2, 3).reshape(kh, n * ps, last)


def scatter_pages(pool_l, page_ids, rows):
    """Write contiguous rows back into pool pages (inverse of
    :func:`gather_pages`): ``rows`` [KH, n*ps, hd] lands in ``pool_l``
    [P, KH, ps, hd] at ``page_ids`` [n]. QuantKV-aware on both sides."""
    if isinstance(pool_l, QuantKV):
        return QuantKV(
            scatter_pages(pool_l.q, page_ids, rows.q),
            scatter_pages(pool_l.s, page_ids, rows.s),
        )
    kh, _, last = rows.shape
    ps = pool_l.shape[2]
    n = page_ids.shape[0]
    pages = rows.reshape(kh, n, ps, last).transpose(1, 0, 2, 3)
    return pool_l.at[page_ids].set(pages.astype(pool_l.dtype))


def paged_view(pool_l, page_ids, dtype):
    """Dense [KH, n*ps, hd] view of the given pages, dequantized when the
    pool stores QuantKV — the read path for code that wants contiguous
    rows without caring how the pool stores them."""
    return dequant_kv(gather_pages(pool_l, page_ids), dtype)
