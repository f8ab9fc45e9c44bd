"""Ring attention: causal attention with the KV sequence sharded over chips.

Long-context/sequence parallelism the reference does not have (SURVEY.md §2
lists SP/CP/ring as absent; §5 marks it the biggest upgrade surface): when a
context no longer fits one chip's HBM, the KV cache shards along the
SEQUENCE axis over the `sp` mesh axis and attention runs as a ring:

  * every chip holds one Q shard (its slice of query positions) and one KV
    shard (its slice of the sequence);
  * sp steps: each chip computes blockwise attention of its Q shard against
    the KV shard currently resident, accumulating online-softmax partial
    state (m, l, acc); after each step the KV shard rotates one hop around
    the ring via `lax.ppermute` over ICI;
  * causality falls out of absolute positions: a KV block from a later part
    of the sequence than a query contributes nothing (fully masked), so the
    combine is exact, not approximate.

The partial-state combine is the standard log-sum-exp merge:
    m' = max(m1, m2); l' = e^{m1-m'} l1 + e^{m2-m'} l2
    acc' = e^{m1-m'} acc1 + e^{m2-m'} acc2

The local step has two backends: the shared jnp einsum math (correct on any
backend; XLA overlaps the ppermute with compute) and the Pallas flash-stats
kernel (ops/flash_attention.flash_attention_stats), auto-selected on TPU
when the shard shapes tile cleanly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


from ..ops.jnp_ops import attention_stats as _stats_jnp


def _local_attention_stats(
    q, k, v, q_pos0, s_pos0, use_flash: bool = False, interpret: bool = False,
    s_stride: int = 1,
):
    """Per-shard causal-GQA partial state: the Pallas flash-stats kernel when
    requested (TPU hot path — blockwise, no [Tq, Ss] score buffer), else the
    shared jnp math (ops/jnp_ops.attention_stats). Both backends support
    `s_stride` > 1 (cyclic sequence layouts: key row j at position
    s_pos0 + j*stride) and an int8 `QuantKV` shard — the kernel consumes
    it natively (per-row scales dequant on the VMEM tile; int8-sized HBM
    reads AND int8-sized ring ppermute payloads), the jnp path dequants."""
    if use_flash:
        from ..ops.flash_attention import flash_attention_stats

        return flash_attention_stats(
            q, k, v, q_pos0, s_pos0, interpret=interpret,
            s_stride=s_stride,
        )
    from ..ops.kv_cache import dequant_kv

    return _stats_jnp(
        q, dequant_kv(k, q.dtype), dequant_kv(v, q.dtype), q_pos0, s_pos0,
        s_stride=s_stride,
    )


def _merge_stats(acc1, m1, l1, acc2, m2, l2):
    """Log-sum-exp merge of two online-softmax partial states."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    # fully-masked states (m == -inf) contribute nothing
    a1 = jnp.where(m1 <= _NEG_INF / 2, 0.0, a1)
    a2 = jnp.where(m2 <= _NEG_INF / 2, 0.0, a2)
    return (
        acc1 * a1[..., None] + acc2 * a2[..., None],
        m,
        l1 * a1 + l2 * a2,
    )


def ring_attention_local(
    q: jnp.ndarray,  # [B, Tq, H, hd] this chip's query shard
    k: jnp.ndarray,  # [B, KH, Ss, hd] this chip's KV shard (head-major)
    v: jnp.ndarray,
    q_pos0: jnp.ndarray,  # absolute position of this chip's first query
    shard_size: jnp.ndarray,  # sequence length held per chip (Ss)
    axis_name: str = "sp",
    use_flash: bool = False,
    interpret: bool = False,
    cyclic: bool = False,
) -> jnp.ndarray:
    """Per-shard ring attention body; call under shard_map with the sequence
    axis of q/k/v sharded over `axis_name`. Returns [B, Tq, H, hd].

    `cyclic`: the KV shards use the cyclic sequence layout (shard i's row
    j holds global position j*sp + i — the layout that lets attention
    windows tile sp shards, see engine._attn_window): key positions of
    the shard owned by `owner` are then owner + arange*sp instead of the
    contiguous owner*shard_size + arange. Both the jnp and flash-stats
    local steps handle the stride."""
    sp = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    stride = sp if cyclic else 1

    def step(carry, _):
        k_cur, v_cur, owner, acc, m, l = carry
        s_pos0 = owner if cyclic else owner * shard_size
        acc2, m2, l2 = _local_attention_stats(
            q, k_cur, v_cur, q_pos0, s_pos0, use_flash, interpret,
            s_stride=stride,
        )
        acc, m, l = _merge_stats(acc, m, l, acc2, m2, l2)
        # rotate KV one hop: chip i sends to chip (i+1) % sp, so the shard
        # owned by (idx - step - 1) arrives next
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        owner = (owner - 1) % sp
        return (k_nxt, v_nxt, owner, acc, m, l), None

    b, tq, h, hd = q.shape
    kh = k.shape[1]
    g = h // kh
    acc0 = jnp.zeros((b, kh, g, tq, hd), jnp.float32)
    m0 = jnp.full((b, kh, g, tq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kh, g, tq), jnp.float32)

    # sp-1 compute+rotate steps, then one final compute — the last shard's
    # rotation would be discarded, so don't pay that ICI hop
    carry = (k, v, idx, acc0, m0, l0)
    if sp > 1:
        carry, _ = lax.scan(step, carry, None, length=sp - 1)
    k_last, v_last, owner, acc, m, l = carry
    acc2, m2, l2 = _local_attention_stats(
        q, k_last, v_last, q_pos0,
        owner if cyclic else owner * shard_size,
        use_flash, interpret, s_stride=stride,
    )
    acc, m, l = _merge_stats(acc, m, l, acc2, m2, l2)

    # normalize; rows with no visible keys (can't happen for causal pos>=0
    # queries, but keep the guard) -> 0
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[..., None]  # [b, kh, g, tq, hd]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, tq, h, hd)
    return out.astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,  # [B, T, H, hd] global queries
    k: jnp.ndarray,  # [B, KH, S, hd] global keys (S = T for self-attention)
    v: jnp.ndarray,
    mesh,
    q_pos0: int | jnp.ndarray = 0,
    axis_name: str = "sp",
    use_flash: bool | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Driver: shards the sequence axis of q/k/v over `axis_name`, runs the
    ring, returns globally-assembled [B, T, H, hd].

    Requires T % sp == 0 and S % sp == 0. Head axes stay whole here; combine
    with the tp axis by nesting specs when both are in play.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    sp = mesh.shape[axis_name]
    b, t, h, hd = q.shape
    s = k.shape[2]
    assert t % sp == 0 and s % sp == 0, (t, s, sp)
    shard_size = s // sp
    tq = t // sp
    if use_flash is None:
        from ..ops.flash_attention import pick_flash_blocks

        use_flash = (
            jax.default_backend() == "tpu"
            and pick_flash_blocks(tq, shard_size) is not None
        )

    def body(qq, kk, vv):
        idx = lax.axis_index(axis_name)
        return ring_attention_local(
            qq,
            kk,
            vv,
            q_pos0=q_pos0 + idx * tq,
            shard_size=shard_size,
            axis_name=axis_name,
            use_flash=use_flash,
            interpret=interpret,
        )

    q_spec = P(None, axis_name, None, None)
    kv_spec = P(None, None, axis_name, None)
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
        check_vma=False,
    )(q, k, v)
