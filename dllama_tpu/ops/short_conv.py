"""A gated short convolution: the operator that stands where attention would
in a layer that keeps a state a lane and no cache row a position.

From the layer's input `u`: `[B | C | x] = in_proj(u)`, `g = B * x`,
`c_t = sum_j w[j] * g_{t - (K - 1) + j}` (depthwise over the channels, causal:
the last of the K taps meets the newest row, and what lies before position 0
is zero), `out_proj(C * c)`. The two projections are the caller's matmuls;
here is what lies between them (the profile's scope `mix`: the gates, the
taps, the state update), in two forms over one formula:

* `short_conv_chunk`: a chunk of T rows with the lane's carried rows
  `(g_{-(K-1)}, ..., g_{-1})` to the left of row 0. A chunk is padded to its
  bucket, so the new state is taken behind the lane's `n_rows` real rows,
  not at the chunk's end; a lane with `n_rows` 0 keeps the state it had.
* `short_conv_step`: one decode step on `[lanes, D]`: the state shifts by one
  row where the lane is live, and stays where it is not.

The sum runs in float32 whatever the activations are; the state holds the
gated rows as the activations had them, so a prompt in two chunks carries
exactly the rows that one chunk would have read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _gated(bcx: jnp.ndarray, state: jnp.ndarray):
    """(`C`, the gated rows behind the carried ones [B, K - 1 + T, D])."""
    d = bcx.shape[-1] // 3
    g = bcx[..., :d] * bcx[..., 2 * d :]
    return bcx[..., d : 2 * d], jnp.concatenate([state.astype(g.dtype), g], axis=1)


def _mixed(c, ext, taps, t: int):
    acc = sum(
        taps[j].astype(jnp.float32) * ext[:, j : j + t].astype(jnp.float32)
        for j in range(taps.shape[0])
    )
    return (c.astype(jnp.float32) * acc).astype(c.dtype)


def short_conv_chunk(
    bcx: jnp.ndarray,  # [B, T, 3 * D]: `in_proj`'s output, `[B | C | x]`
    taps: jnp.ndarray,  # [K, D] f32: taps[j] meets row t - (K - 1) + j
    state: jnp.ndarray,  # [B, K - 1, D]: the gated rows before row 0, oldest first
    n_rows: jnp.ndarray,  # [B] int32: a lane's real rows of the T (0: the lane stands)
):
    """(`C * c` [B, T, D], the state behind each lane's `n_rows` rows)."""
    t, k = bcx.shape[1], taps.shape[0]
    c, ext = _gated(bcx, state)
    # row n of `ext` is g_{n - (K - 1)}: the K - 1 rows from n are those
    # before position n
    new = jax.vmap(lambda e, n: lax.dynamic_slice_in_dim(e, n, k - 1, axis=0))(
        ext, n_rows.astype(jnp.int32))
    return _mixed(c, ext, taps, t), new.astype(state.dtype)


def short_conv_step(
    bcx: jnp.ndarray,  # [B, 1, 3 * D]
    taps: jnp.ndarray,  # [K, D]
    state: jnp.ndarray,  # [B, K - 1, D]
    live: jnp.ndarray,  # [B] bool: the lanes whose state moves
):
    """One decode step: (`C * c` [B, 1, D], the state shifted by one row
    where `live`, as it was elsewhere)."""
    c, ext = _gated(bcx, state)
    new = jnp.where(live[:, None, None], ext[:, 1:].astype(state.dtype), state)
    return _mixed(c, ext, taps, 1), new
