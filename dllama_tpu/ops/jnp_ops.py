"""Reference jnp implementations of the model ops.

These are the semantic twins of the reference's CPU kernels
(src/nn/nn-cpu-ops.cpp); the Pallas kernels in ops/pallas/* are validated
against them (the same cross-implementation equivalence strategy the
reference uses for SIMD vs scalar and Vulkan vs CPU — SURVEY.md §4).

Everything here is shape-polymorphic jnp, jit-safe, and f32-accumulating:
norms, RoPE and softmax stay in f32 regardless of the activation dtype,
matching the reference numerics (all its kernels accumulate in f32).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..formats.model_file import LlmHeader, RopeType, yarn_mscale


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMS norm over the last axis (reference: OP_INV_RMS + OP_RMS_NORM,
    src/nn/nn-cpu-ops.cpp:114-189 — the reference splits the inverse-rms
    reduce from the scale so one reduce can feed several columns; under XLA
    that split is fusion, not an op boundary)."""
    xf = x.astype(jnp.float32)
    inv = jnp.reciprocal(jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps))
    return (xf * inv * weight.astype(jnp.float32)).astype(x.dtype)


def qk_rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    """Per-head RMS norm for Qwen3 QK-norm: ``x`` is [..., nHeads, headDim],
    ``weight`` is [headDim] (reference: the nQNormColumns-column variant of
    OP_INV_RMS/OP_RMS_NORM, src/llm.cpp:322-346)."""
    return rms_norm(x, weight, eps)


def silu(x: jnp.ndarray) -> jnp.ndarray:
    """(reference: src/nn/nn-cpu-ops.cpp:454-478)"""
    xf = x.astype(jnp.float32)
    return (xf / (1.0 + jnp.exp(-xf))).astype(x.dtype)


def gelu(x: jnp.ndarray) -> jnp.ndarray:
    """tanh-approx GELU (reference: gelu_F32, src/nn/nn-cpu-ops.cpp:480-500)."""
    xf = x.astype(jnp.float32)
    return (
        0.5
        * xf
        * (1.0 + jnp.tanh(0.797884560802865 * (xf + 0.044715 * xf * xf * xf)))
    ).astype(x.dtype)


def _scale_frequency_llama3(freq: "np.ndarray", h: LlmHeader) -> "np.ndarray":
    """Llama-3.1 NTK-by-parts frequency scaling
    (reference: src/nn/nn-core.cpp:326-340)."""
    wave_len = 2.0 * np.pi / freq
    high_freq_wavelen = h.rope_scaling_orig_max_seq_len / h.rope_scaling_high_freq_factor
    low_freq_wavelen = h.rope_scaling_orig_max_seq_len / h.rope_scaling_low_freq_factor
    smooth = (h.rope_scaling_orig_max_seq_len / wave_len - h.rope_scaling_low_freq_factor) / (
        h.rope_scaling_high_freq_factor - h.rope_scaling_low_freq_factor
    )
    return np.where(
        wave_len < high_freq_wavelen,
        freq,
        np.where(
            wave_len > low_freq_wavelen,
            freq / h.rope_scaling_factor,
            (1.0 - smooth) * freq / h.rope_scaling_factor + smooth * freq,
        ),
    )


def _scale_frequency_yarn(freq: "np.ndarray", h: LlmHeader) -> "np.ndarray":
    """Frequencies scaled by band (`rope_scaling.type: yarn`): pair d keeps
    its frequency below band `low`, has it divided by the factor above
    band `high`, and a linear blend between; the limits are the pairs that
    turn `beta_fast` and `beta_slow` times over the original length."""
    dim, orig = h.rope_dim, h.rope_scaling_orig_max_seq_len

    def band(rotations: float) -> float:
        return dim * np.log(orig / (rotations * 2.0 * np.pi)) / (2.0 * np.log(h.rope_theta))

    low = max(int(np.floor(band(h.rope_beta_fast))), 0)
    high = min(int(np.ceil(band(h.rope_beta_slow))), dim // 2 - 1)
    ramp = np.clip(
        (np.arange(dim // 2, dtype=np.float32) - low) / max(high - low, 0.001), 0.0, 1.0
    )
    return (1.0 - ramp) * freq + ramp * freq / h.rope_scaling_factor


def rope_frequencies(h: LlmHeader) -> "np.ndarray":
    """Per-pair inverse frequencies, shape [headDim // 2], f32, on host.

    The reference computes ``theta^{-(i % headDim)/headDim}`` for even i
    (llama layout, src/nn/nn-core.cpp:342-359) and ``theta^{-2j/headDim}``
    for the falcon layout (src/nn/nn-core.cpp:361-374) — identical values,
    different pairing; the pairing lives in `apply_rope`.
    """
    half = h.rope_dim // 2
    exponents = 2.0 * np.arange(half, dtype=np.float32) / np.float32(h.rope_dim)
    freqs = (1.0 / (h.rope_theta**exponents)).astype(np.float32)
    if h.rope_type == RopeType.LLAMA3_1 and h.rope_scaling_factor != 1.0:
        freqs = _scale_frequency_llama3(freqs, h).astype(np.float32)
    if h.rope_type == RopeType.YARN and h.rope_scaling_factor != 1.0:
        freqs = _scale_frequency_yarn(freqs, h).astype(np.float32)
    return freqs


def rope_cache(h: LlmHeader, seq_len: int | None = None):
    """(cos, sin) host numpy tables of shape [seqLen, headDim // 2]
    (reference: fullfillRopeCache, src/nn/nn-core.cpp:376-383).

    Computed on host deliberately: the tables are load-time constants placed
    by the loader's `put` hook, so building them on-device would just buy a
    device->host->device round trip."""
    if seq_len is None:
        seq_len = h.seq_len
    freqs = rope_frequencies(h)
    angles = np.arange(seq_len, dtype=np.float32)[:, None] * freqs[None, :]
    cos, sin = np.cos(angles), np.sin(angles)
    if h.rope_type == RopeType.YARN:
        # the table's own magnitude factor (1 where the two mscales agree)
        m = yarn_mscale(h.rope_scaling_factor, h.rope_mscale) / yarn_mscale(
            h.rope_scaling_factor, h.rope_mscale_all_dim)
        if m != 1.0:
            cos, sin = cos * np.float32(m), sin * np.float32(m)
    return cos, sin


def apply_rope(
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    interleaved: bool,
) -> jnp.ndarray:
    """Rotate ``x`` of shape [..., T, nHeads, headDim] by position.

    ``cos``/``sin`` are [T, headDim//2] rows for the absolute positions of
    the T axis — or [B, T, headDim//2] when lanes sit at different
    positions (per-lane decode). ``interleaved=True`` pairs (2j, 2j+1) —
    the llama layout the converter permutes q/k for (reference:
    ropeLlama_F32, src/nn/nn-cpu-ops.cpp:843-863); ``False`` pairs
    (j, j+headDim/2) — the falcon/neox layout used by Qwen3
    (src/nn/nn-cpu-ops.cpp:865-885).
    """
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    c = cos[..., :, None, :]  # [(B,) T, 1, half]
    s = sin[..., :, None, :]
    if interleaved:
        x0 = xf[..., 0::2]
        x1 = xf[..., 1::2]
        r0 = x0 * c - x1 * s
        r1 = x0 * s + x1 * c
        out = jnp.stack([r0, r1], axis=-1).reshape(xf.shape)
    else:
        half = xf.shape[-1] // 2
        x0 = xf[..., :half]
        x1 = xf[..., half:]
        r0 = x0 * c - x1 * s
        r1 = x0 * s + x1 * c
        out = jnp.concatenate([r0, r1], axis=-1)
    return out.astype(dtype)


_NEG_INF = -1e30


def attention_stats(
    q: jnp.ndarray,  # [B, Tq, H, hd]
    k: jnp.ndarray,  # [B, KH, Ts, hd] — head-major cache layout
    v: jnp.ndarray,  # [B, KH, Ts, hd]
    q_pos0,  # scalar or [B]: absolute position of q[:, 0] (per lane)
    s_pos0,  # scalar: absolute position of k[:, :, 0]
    s_stride: int = 1,  # position step between consecutive key rows
    ring: int = 0,  # key rows are a ring of this many positions (0: in order)
    window: int = 0,  # a query sees the last `window` positions only (0: all)
):
    """Causal GQA attention partial state (unnormalized acc, running max m,
    denominator l) in f32 — the single source of the reference's
    multiheadAtt_F32 math (src/nn/nn-cpu-ops.cpp:753-788). Dense attention
    normalizes it directly; ring attention merges several of these across
    sequence shards. A vector ``q_pos0`` gives each batch lane its own
    position (independent decode lanes).

    The cache is HEAD-MAJOR ([B, KH, S, hd]): per-KV-head tiles are then
    (seq, head_dim) planes whose Pallas BlockSpecs satisfy Mosaic's
    last-two-dims tiling rule for any head_dim — blocking a size-1 head
    inside the last two dims of a [B, S, KH, hd] array is rejected by the
    real TPU compiler (and pads (KH, hd) tiles up to (8, 128))."""
    b, tq, h, hd = q.shape
    kh, ts = k.shape[1], k.shape[2]
    g = h // kh
    qf = q.astype(jnp.float32).reshape(b, tq, kh, g, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("btkgh,bksh->bkgts", qf, kf) / jnp.sqrt(jnp.float32(hd))
    q_pos0_arr = jnp.atleast_1d(jnp.asarray(q_pos0, jnp.int32))  # [1] or [B]
    q_pos = q_pos0_arr[:, None] + jnp.arange(tq, dtype=jnp.int32)[None, :]
    # s_stride > 1: CYCLIC sequence layout — local key row j holds the
    # global position s_pos0 + j*stride (sp shard of a strided cache;
    # see parallel/sharding.cache_specs / docs on sp windows)
    s_pos = s_pos0 + jnp.arange(ts, dtype=jnp.int32) * s_stride
    if ring:
        s_pos = ring_positions(q_pos0_arr + (tq - 1), s_pos, ring)[:, None, :]
    else:
        s_pos = s_pos[None, None, :]
    mask = s_pos <= q_pos[:, :, None]  # [1 or B, tq, ts]
    if ring:
        mask = jnp.logical_and(mask, s_pos >= 0)
    if window:
        mask = jnp.logical_and(mask, q_pos[:, :, None] - s_pos < window)
    scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)
    m = jnp.max(scores, axis=-1)  # [b, kh, g, tq]
    p = jnp.exp(scores - m[..., None])
    # fully-masked rows (query before every key in this shard) -> zero
    p = jnp.where(m[..., None] <= _NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgts,bksh->bkgth", p, vf)
    return acc, m, l


def ring_positions(last, rows, ring: int):
    """The position that each of a ring's `rows` holds once position `last`
    ([B] or [1]) is written: position p lies at row p % ring, so row i holds
    the latest position <= last that is i modulo ring. A row that holds
    nothing yet, and every row of a parked lane (`last` < 0), comes out
    negative. [B or 1, len(rows)]."""
    last = last[:, None]
    return last - jnp.mod(last - rows[None, :], ring)


def attention_dense(
    q: jnp.ndarray,  # [B, T, H, hd]
    k_cache: jnp.ndarray,  # [B, KH, S, hd]
    v_cache: jnp.ndarray,
    pos,  # scalar: absolute position of q[:, 0]
    ring: int = 0,
    window: int = 0,
) -> jnp.ndarray:
    """Normalized causal GQA attention over the cache; [B, T, H, hd].
    `ring` and `window`: a window layer's cache, as `attention_stats` says."""
    b, t, h, hd = q.shape
    acc, m, l = attention_stats(
        q, k_cache, v_cache, pos, 0, ring=ring, window=window
    )
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[..., None]  # [b, kh, g, tq, hd]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, hd).astype(q.dtype)
