"""A Mamba-2 mixer's recurrence: what lies between `in_proj` and `out_proj`
in a layer that keeps a recurrent state a lane and no cache row a position.

From `in_proj`'s output `[z | xBC | dt]`: a depthwise causal convolution with
bias and a SiLU over `xBC` (the last of the K taps meets the newest row, zeros
before position 0), `[x | B | C] = xBC` (x as H heads of P columns; B and C,
N wide, are shared by every head: one group), and per head k, in float32:

    d_t = softplus(dt_t[k] + dt_bias[k])        a_t = exp(-exp(A_log[k]) * d_t)
    H_t[k] = a_t * H_{t-1}[k] + d_t * x_t[k] (outer) B_t        [P, N]
    y_t[k] = H_t[k] C_t + D[k] * x_t[k]

then `RMSNorm(y * silu(z))` over all H * P columns under the mixer's own
gains. The two projections are the caller's matmuls; here is the rest (the
profile's scope `mix`), in two forms over one formula:

* `ssm_chunk`: T rows from the lane's carried state, by blocks of `block`
  rows in the matmul form: with `L_t` the running sum of `log a` inside a
  block, `y_t = sum_{s<=t} exp(L_t - L_s) (C_t . B_s) d_s x_s + exp(L_t) H_in
  C_t` and `H_out = exp(L_Q) H_in + sum_s exp(L_Q - L_s) d_s x_s (outer) B_s`.
  A chunk is padded to its bucket: rows behind the lane's `n_rows` real rows
  take d = 0, which neither decays the state nor adds to it, so the state
  behind the chunk is the state behind its real rows, and a lane with 0 rows
  keeps the state it had. The convolution's carried rows are taken behind the
  real rows as `short_conv_chunk` takes them.
* `ssm_step`: one decode step on `[lanes, ...]`: a live lane's state moves by
  the one row, every other's stays bit for bit. `ssm_step_in_place` is the
  same step on the chip, over the layers' whole stack where it lies: a Pallas
  kernel whose grid runs over the live lanes alone (their numbers ride in as
  scalar prefetch, the grid's bound is their count), the stack aliased to
  its output, so a lane that is not live costs neither a copy nor a step.

The state is float32 in and out, and is kept `[N, H * P]` a lane: the state's
columns down the sublanes, every head's rows along the lanes, so that what
differs by row (`a`, `d x`) is a lane-dense row and what differs by column
(`B`, `C`) is shared by all heads. Every product that reads or writes it is
float32 at the highest precision (on the chip a default-precision product
would round the state to bfloat16 on its way in). The convolution's carried
rows are kept as the activations had them, so a prompt in two chunks carries
exactly the rows that one chunk would have read.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = lax.Precision.HIGHEST
# lanes of the state a grid step of the decode kernel takes: [N, 2048] f32 is
# 1 MB, in and out and double-buffered 4 MB of VMEM
_STEP_COLUMNS = 2048


@dataclasses.dataclass(frozen=True)
class SsmShape:
    """The mixer's sizes: H heads of P columns, a state N wide, K taps, and
    the rows of a block of the chunk form (`mamba_chunk_size`)."""

    n_heads: int
    head_dim: int
    state_dim: int
    block: int = 256
    eps: float = 1e-5

    @property
    def inner(self) -> int:
        return self.n_heads * self.head_dim


def _conv(xbc, taps, bias, rows):
    """`silu(bias + sum_j taps[j] * ext[t + j])` over the T rows behind the
    carried ones: `ext` [B, K - 1 + T, C], `taps` [K, C]; f32."""
    t = xbc.shape[1]
    ext = jnp.concatenate([rows.astype(xbc.dtype), xbc], axis=1)
    acc = bias.astype(jnp.float32) + sum(
        taps[j].astype(jnp.float32) * ext[:, j : j + t].astype(jnp.float32)
        for j in range(taps.shape[0])
    )
    return jax.nn.silu(acc), ext


def _split(zxd, shape: SsmShape):
    """`[z | xBC | dt]` of `in_proj`'s output."""
    inner, conv_dim = shape.inner, shape.inner + 2 * shape.state_dim
    return zxd[..., :inner], zxd[..., inner : inner + conv_dim], zxd[..., inner + conv_dim :]


def _steps(dt, lp):
    """(d [.., H], log a [.., H]) in f32 from the raw `dt` columns."""
    d = jax.nn.softplus(dt.astype(jnp.float32) + lp["ssm_dt_bias"])
    return d, -jnp.exp(lp["ssm_a_log"]) * d


def _gated_norm(y, z, gains, eps: float, dtype):
    """`RMSNorm(y * silu(z))` over the mixer's whole inner width."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g * gains).astype(dtype)


def ssm_chunk(
    zxd: jnp.ndarray,  # [B, T, inner + conv_dim + H]: `in_proj`'s output
    lp: dict,  # the layer's ssm_conv_w [K, C], ssm_conv_b, ssm_dt_bias, ssm_a_log, ssm_d, ssm_norm
    state: jnp.ndarray,  # [B, N, H, P] f32: the lanes' recurrent states before row 0
    rows: jnp.ndarray,  # [B, K - 1, C]: the convolution's input rows before row 0
    n_rows: jnp.ndarray,  # [B] int32: a lane's real rows of the T (0: the lane stands)
    shape: SsmShape,
):
    """(the gated, normed `y` [B, T, inner], the state and the convolution
    rows behind each lane's `n_rows` rows)."""
    b, t = zxd.shape[0], zxd.shape[1]
    h, p, n = shape.n_heads, shape.head_dim, shape.state_dim
    z, xbc, dt = _split(zxd, shape)
    k = lp["ssm_conv_w"].shape[0]
    act, ext = _conv(xbc, lp["ssm_conv_w"], lp["ssm_conv_b"], rows)
    new_rows = jax.vmap(lambda e, m: lax.dynamic_slice_in_dim(e, m, k - 1, axis=0))(
        ext, n_rows.astype(jnp.int32))
    x = act[..., : h * p].reshape(b, t, h, p)
    bm, cm = act[..., h * p : h * p + n], act[..., h * p + n :]
    d, log_a = _steps(dt, lp)
    real = (jnp.arange(t, dtype=jnp.int32)[None, :] < n_rows[:, None])[..., None]
    d, log_a = jnp.where(real, d, 0.0), jnp.where(real, log_a, 0.0)

    q = min(shape.block, t)
    if t % q:
        raise ValueError(f"a chunk of {t} rows is not whole blocks of {q}")
    nb = t // q

    def blocks(a):  # [B, T, ...] -> [T / Q, B, Q, ...]
        return jnp.moveaxis(a.reshape(b, nb, q, *a.shape[2:]), 1, 0)

    seen = jnp.tril(jnp.ones((q, q), bool))

    def block(hin, args):
        x, bm, cm, d, log_a = args  # [B, Q, H, P], [B, Q, N] x 2, [B, Q, H] x 2
        run = jnp.cumsum(log_a, axis=1)  # L_t
        dx = d[..., None] * x  # d_s x_s
        # inside the block: exp(L_t - L_s) (C_t . B_s) over s <= t
        g = jnp.einsum("btn,bsn->bts", cm, bm, precision=_HI)
        decay = jnp.exp(jnp.where(
            seen[None, :, :, None], run[:, :, None, :] - run[:, None, :, :], -jnp.inf))
        y = jnp.einsum("btsh,bshp->bthp", g[..., None] * decay, dx, precision=_HI)
        # what the state before the block adds: exp(L_t) H_in C_t
        y = y + jnp.exp(run)[..., None] * jnp.einsum(
            "bnhp,btn->bthp", hin, cm, precision=_HI)
        # the state behind the block
        left = jnp.exp(run[:, -1:, :] - run)  # exp(L_Q - L_s)
        hout = jnp.exp(run[:, -1])[:, None, :, None] * hin + jnp.einsum(
            "bshp,bsn->bnhp", left[..., None] * dx, bm, precision=_HI)
        return hout, y

    state, y = lax.scan(block, state, tuple(map(blocks, (x, bm, cm, d, log_a))))
    y = jnp.moveaxis(y, 0, 1).reshape(b, t, h, p) + lp["ssm_d"][:, None] * x
    out = _gated_norm(y.reshape(b, t, h * p), z, lp["ssm_norm"], shape.eps, zxd.dtype)
    return out, state, new_rows.astype(rows.dtype)


def _step_inputs(zxd, lp, rows, shape: SsmShape):
    """What a decode step's state update reads beside the state: (z, the
    convolution's extended rows, x [B, H, P], B and C [B, N], d and log a
    [B, H])."""
    b = zxd.shape[0]
    h, p, n = shape.n_heads, shape.head_dim, shape.state_dim
    z, xbc, dt = _split(zxd, shape)
    act, ext = _conv(xbc, lp["ssm_conv_w"], lp["ssm_conv_b"], rows)
    act = act[:, 0]
    d, log_a = _steps(dt[:, 0], lp)
    return (z, ext, act[:, : h * p].reshape(b, h, p), act[:, h * p : h * p + n],
            act[:, h * p + n :], d, log_a)


def ssm_step(
    zxd: jnp.ndarray,  # [B, 1, inner + conv_dim + H]
    lp: dict,
    state: jnp.ndarray,  # [B, N, H, P] f32
    rows: jnp.ndarray,  # [B, K - 1, C]
    live: jnp.ndarray,  # [B] bool: the lanes whose state moves
    shape: SsmShape,
):
    """One decode step: (the gated, normed `y` [B, 1, inner], the state moved
    by the one row and the convolution rows shifted by it where `live`, both
    as they were elsewhere)."""
    b = zxd.shape[0]
    z, ext, x, bm, cm, d, log_a = _step_inputs(zxd, lp, rows, shape)
    # elementwise on the state, a sum over its columns: no product rounds it
    moved = jnp.exp(log_a)[:, None, :, None] * state + (
        bm[:, :, None, None] * (d[..., None] * x)[:, None])
    y = jnp.sum(moved * cm[:, :, None, None], axis=1) + lp["ssm_d"][:, None] * x
    out = _gated_norm(y.reshape(b, 1, shape.inner), z, lp["ssm_norm"], shape.eps, zxd.dtype)
    keep = live[:, None, None]
    return (
        out,
        jnp.where(keep[..., None], moved, state),
        jnp.where(keep, ext[:, 1:].astype(rows.dtype), rows),
    )


def _step_kernel(lanes_ref, layer_ref, a_ref, dx_ref, b_ref, c_ref, s_ref, o_ref, y_ref):
    """One live lane's [N, columns] of one layer's state: `a * H + B (outer)
    d x` written where it lay, and `C . H` of the new state a column."""
    del lanes_ref, layer_ref  # the index maps read them
    bc, cc = b_ref[...], c_ref[...]  # [N, 128]: a column value along every lane
    for j in range(s_ref.shape[1] // 128):
        at = pl.ds(j * 128, 128)
        new = a_ref[:, at] * s_ref[:, at] + bc * dx_ref[:, at]
        o_ref[:, at] = new
        y_ref[:, at] = jnp.sum(new * cc, axis=0, keepdims=True)


def ssm_step_in_place(
    zxd: jnp.ndarray,  # [B, 1, inner + conv_dim + H]
    lp: dict,
    stack: jnp.ndarray,  # [Ls, B, N, H * P] f32: every layer's states, donated
    layer,  # int32 scalar: the layer's row in the stack
    rows: jnp.ndarray,  # [B, K - 1, C]
    live: jnp.ndarray,  # [B] bool
    zero: jnp.ndarray,  # [B] bool: the lane's state counts as zero before the step
    shape: SsmShape,
    order: tuple | None = None,  # (the lanes' numbers, the live ones first; their count)
    interpret: bool = False,
):
    """`ssm_step` over the stack where it lies: (y [B, 1, inner], the stack
    with layer `layer` of the live lanes moved by the one row, the convolution
    rows). The kernel's grid is (live lanes, column blocks): each step copies
    one lane's [N, columns] in, updates it and copies it back to where it
    came from (the stack is aliased to the output); a lane that is not live
    is never visited. `zero`: the old state is multiplied by 0, not read as
    it is. `order`: what the grid runs over, where the caller has it already
    (the same for every layer of a step)."""
    b, n = zxd.shape[0], shape.state_dim
    width = shape.inner
    cols = min(_STEP_COLUMNS, width)
    assert stack.shape[1:] == (b, n, width) and width % cols == 0 and cols % 128 == 0
    z, ext, x, bm, cm, d, log_a = _step_inputs(zxd, lp, rows, shape)
    decay = jnp.where(zero[:, None], 0.0, jnp.exp(log_a))
    a_row = jnp.repeat(decay, shape.head_dim, axis=1)[:, None, :]  # [B, 1, H * P]
    dx_row = (d[..., None] * x).reshape(b, 1, width)
    b_col = jnp.broadcast_to(bm[:, :, None], (b, n, 128))
    c_col = jnp.broadcast_to(cm[:, :, None], (b, n, 128))
    # the live lanes' numbers first; the grid stops behind the last of them
    lanes, n_live = order or (
        jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32),
        jnp.sum(live).astype(jnp.int32))

    def lane_row(i, j, lanes, layer):
        return lanes[i], 0, j

    def lane_col(i, j, lanes, layer):
        return lanes[i], 0, 0

    def lane_state(i, j, lanes, layer):
        return layer[0], lanes[i], 0, j

    stack, y = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_live, width // cols),
            in_specs=[
                pl.BlockSpec((None, 1, cols), lane_row),
                pl.BlockSpec((None, 1, cols), lane_row),
                pl.BlockSpec((None, n, 128), lane_col),
                pl.BlockSpec((None, n, 128), lane_col),
                pl.BlockSpec((None, None, n, cols), lane_state),
            ],
            out_specs=[
                pl.BlockSpec((None, None, n, cols), lane_state),
                pl.BlockSpec((None, 1, cols), lane_row),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(stack.shape, jnp.float32),
            jax.ShapeDtypeStruct((b, 1, width), jnp.float32),
        ],
        input_output_aliases={6: 0},
        interpret=interpret,
    )(lanes, jnp.asarray(layer, jnp.int32).reshape(1), a_row, dx_row, b_col, c_col, stack)
    # rows of `y` the grid never reached hold whatever the buffer held
    y = jnp.where(live[:, None, None], y, 0.0).reshape(b, shape.n_heads, shape.head_dim)
    y = y + lp["ssm_d"][:, None] * x
    out = _gated_norm(y.reshape(b, 1, width), z, lp["ssm_norm"], shape.eps, zxd.dtype)
    new_rows = jnp.where(live[:, None, None], ext[:, 1:].astype(rows.dtype), rows)
    return out, stack, new_rows


def _copy_kernel(at_ref, src_ref, dst_ref):
    del at_ref
    dst_ref[...] = src_ref[...]


def _put_kernel(at_ref, src_ref, stack_ref, dst_ref):
    del at_ref, stack_ref  # the stack is the output, aliased
    dst_ref[...] = src_ref[...]


def lane_state(stack: jnp.ndarray, layer, lane, interpret: bool = False) -> jnp.ndarray:
    """One lane's state of one layer out of the stack, [1, N, H * P]: a copy
    by a kernel, which reads the stack where and as it lies. A
    `dynamic_slice` leaves the stack's layout to the compiler, and a chunk
    program's products want the state's axes the other way round: it re-laid
    the whole stack out before the layer scan and back behind it (described
    v5e: 2.4 GB copied twice a chunk, for 4 MB a layer that the chunk reads)."""
    _, _, n, width = stack.shape
    cols = min(_STEP_COLUMNS, width)
    at = jnp.stack([jnp.asarray(layer, jnp.int32), jnp.asarray(lane, jnp.int32)])
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(width // cols,),
            in_specs=[pl.BlockSpec((None, None, n, cols), lambda j, at: (at[0], at[1], 0, j))],
            out_specs=pl.BlockSpec((None, n, cols), lambda j, at: (0, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((1, n, width), stack.dtype),
        interpret=interpret,
    )(at, stack)


def put_lane_state(stack: jnp.ndarray, layer, lane, state: jnp.ndarray,
                   interpret: bool = False) -> jnp.ndarray:
    """The stack with `state` [1, N, H * P] where that lane's state of that
    layer lay: written in place (the stack is aliased to the output), by a
    kernel for `lane_state`'s reason."""
    _, _, n, width = stack.shape
    cols = min(_STEP_COLUMNS, width)
    at = jnp.stack([jnp.asarray(layer, jnp.int32), jnp.asarray(lane, jnp.int32)])
    return pl.pallas_call(
        _put_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(width // cols,),
            in_specs=[
                pl.BlockSpec((None, n, cols), lambda j, at: (0, 0, j)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, None, n, cols), lambda j, at: (at[0], at[1], 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct(stack.shape, stack.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(at, state.astype(stack.dtype), stack)
