"""Flash attention + ring attention equivalence tests (kernel vs jnp
reference; sequence-parallel ring vs single-device — SURVEY.md §4's
cross-implementation pattern applied to the new parallelism axis)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.models.transformer import Routing
from dllama_tpu.ops.flash_attention import attention_ref, flash_attention
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.parallel.ring_attention import ring_attention


def make_qkv(b, t, h, kh, hd, s, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, t, h, hd)).astype(np.float32))
    # head-major cache layout [B, KH, S, hd] (see ops/flash_attention.py)
    k = jnp.asarray(rng.standard_normal((b, kh, s, hd)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, kh, s, hd)).astype(np.float32))
    return q, k, v


@pytest.mark.parametrize("pos", [0, 5, 24])
def test_flash_matches_reference(pos):
    q, k, v = make_qkv(1, 8, 4, 2, 16, 32)
    ref = attention_ref(q, k, v, jnp.int32(pos))
    out = flash_attention(
        q, k, v, jnp.int32(pos), block_t=8, block_s=8, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("pos", [0, 1, 7, 8, 30, 31])
def test_flash_decode_matches_reference(pos):
    """T=1 decode kernel vs the dense reference across positions, incl.
    block boundaries (block_s=8) and the last cache row."""
    from dllama_tpu.ops.flash_attention import flash_decode

    q, k, v = make_qkv(1, 1, 4, 2, 16, 32, seed=11)
    ref = attention_ref(q, k, v, jnp.int32(pos))
    out = flash_decode(q, k, v, jnp.int32(pos), block_s=8, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("h,kh", [(8, 8), (8, 2), (4, 1)])
def test_flash_decode_gqa_groupings(h, kh):
    """MHA (G=1), GQA (G=4), MQA-ish (G=4 single kv head) and batch > 1."""
    from dllama_tpu.ops.flash_attention import flash_decode

    q, k, v = make_qkv(2, 1, h, kh, 16, 64, seed=12)
    for pos in (3, 40, 63):
        ref = attention_ref(q, k, v, jnp.int32(pos))
        out = flash_decode(q, k, v, jnp.int32(pos), block_s=16, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"h={h} kh={kh} pos={pos}",
        )


def test_flash_decode_stats_matches_jnp_stats():
    """The decode-stats variant (sp decode local step) vs the shared jnp
    partial-state math, across shard offsets — including a shard entirely
    in the query's future (fully-masked stats) and per-lane positions."""
    from dllama_tpu.ops.flash_attention import flash_decode_stats
    from dllama_tpu.ops.jnp_ops import attention_stats

    q, k, v = make_qkv(2, 1, 4, 2, 16, 32, seed=14)
    for pos, s0 in [(20, 0), (20, 16), (10, 16), (3, 0), (31, 16)]:
        acc, m, l = flash_decode_stats(
            q, k, v, jnp.int32(pos), jnp.int32(s0), block_s=8, interpret=True
        )
        acc_r, m_r, l_r = attention_stats(q, k, v, jnp.int32(pos), jnp.int32(s0))
        mask = np.asarray(l_r) > 0
        assert (np.asarray(l) > 0).tolist() == mask.tolist(), (pos, s0)
        if mask.any():
            o = np.asarray(acc) / np.maximum(np.asarray(l)[..., None], 1e-30)
            o_r = np.asarray(acc_r) / np.maximum(
                np.asarray(l_r)[..., None], 1e-30
            )
            np.testing.assert_allclose(
                o[mask], o_r[mask], rtol=1e-5, atol=1e-5, err_msg=f"{pos},{s0}"
            )
            lse = np.asarray(m) + np.log(np.maximum(np.asarray(l), 1e-30))
            lse_r = np.asarray(m_r) + np.log(
                np.maximum(np.asarray(l_r), 1e-30)
            )
            np.testing.assert_allclose(
                lse[mask], lse_r[mask], rtol=1e-5, atol=1e-5
            )
    # per-lane positions: lane 0 deep, lane 1 shallow
    posv = jnp.asarray([24, 5], jnp.int32)
    acc, m, l = flash_decode_stats(
        q, k, v, posv, jnp.int32(0), block_s=8, interpret=True
    )
    for lane, p in enumerate([24, 5]):
        acc_r, m_r, l_r = attention_stats(
            q[lane : lane + 1], k[lane : lane + 1], v[lane : lane + 1],
            jnp.int32(p), jnp.int32(0),
        )
        o = np.asarray(acc[lane]) / np.asarray(l[lane])[..., None]
        o_r = np.asarray(acc_r[0]) / np.asarray(l_r[0])[..., None]
        np.testing.assert_allclose(o, o_r, rtol=1e-5, atol=1e-5)


def test_flash_decode_bf16():
    from dllama_tpu.ops.flash_attention import flash_decode

    q, k, v = make_qkv(1, 1, 4, 2, 32, 64, seed=13)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ref = attention_ref(q, k, v, jnp.int32(50))
    out = flash_decode(q, k, v, jnp.int32(50), block_s=16, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_flash_multi_batch_gqa():
    q, k, v = make_qkv(2, 16, 8, 2, 16, 64, seed=3)
    ref = attention_ref(q, k, v, jnp.int32(48))
    out = flash_attention(
        q, k, v, jnp.int32(48), block_t=8, block_s=16, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("sp", [2, 4, 8])
def test_ring_attention_matches_single_device(sp):
    """Causal self-attention with the sequence ring-sharded over sp chips
    must equal the single-device result exactly."""
    b, t, h, kh, hd = 1, 32, 4, 2, 16
    q, k, v = make_qkv(b, t, h, kh, hd, t, seed=7)
    mesh = make_mesh(sp=sp)
    expected = attention_ref(q, k, v, jnp.int32(t - 1) * 0 + jnp.int32(0))
    # attention_ref treats pos as the position of q[:, 0]; for full
    # self-attention q covers positions 0..t-1 over keys 0..t-1
    out = ring_attention(q, k, v, mesh, q_pos0=0)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=2e-4, atol=2e-4
    )


def test_ring_attention_gqa_batch():
    b, t, h, kh, hd = 2, 64, 8, 4, 16
    q, k, v = make_qkv(b, t, h, kh, hd, t, seed=11)
    mesh = make_mesh(sp=4)
    expected = attention_ref(q, k, v, jnp.int32(0))
    out = ring_attention(q, k, v, mesh, q_pos0=0)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=2e-4, atol=2e-4
    )


def test_ring_with_tp_mesh_axes():
    """sp combined with a tp axis in the same mesh (heads whole on the sp
    ring, tp present for the rest of the model)."""
    b, t, h, kh, hd = 1, 32, 4, 2, 16
    q, k, v = make_qkv(b, t, h, kh, hd, t, seed=13)
    mesh = make_mesh(tp=2, sp=4)
    expected = attention_ref(q, k, v, jnp.int32(0))
    out = ring_attention(q, k, v, mesh, q_pos0=0)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=2e-4, atol=2e-4
    )


def test_moe_pallas_tp_branch_matches_dense():
    """The shard_map TP branch of the ragged MoE path (psum over F-sliced
    experts) vs the dense MoE, on a tp=2 CPU mesh in interpret mode."""
    from dllama_tpu.models.transformer import _moe_ffn, _moe_ffn_pallas
    from dllama_tpu.ops.jnp_ops import silu

    rng = np.random.default_rng(21)
    E, D, F, K = 8, 64, 128, 3
    w1 = jnp.asarray(rng.standard_normal((E, D, F)).astype(np.float32) * 0.1)
    w2 = jnp.asarray(rng.standard_normal((E, F, D)).astype(np.float32) * 0.1)
    w3 = jnp.asarray(rng.standard_normal((E, D, F)).astype(np.float32) * 0.1)
    gate = jnp.asarray(rng.standard_normal((D, E)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((1, 1, D)).astype(np.float32))

    mesh = make_mesh(tp=2)
    out = _moe_ffn_pallas(x, gate, w1, w2, w3, Routing(K), mesh, interpret=True)
    dense = _moe_ffn(x, gate, w1, w2, w3, Routing(K), silu)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense), rtol=1e-4, atol=1e-4
    )


def test_moe_pallas_tp_quantized_and_multitoken():
    """The quantized 9-operand shard_map branch and the dp-sharded
    multi-token branch of _moe_ffn_pallas: tp=2 x dp=2 CPU mesh, interpret
    mode, 4 tokens with per-token routing, Q40 expert weights — vs the
    dense MoE over dequantized experts."""
    from dllama_tpu.formats.quants import q40_to_planar, quantize_q40
    from dllama_tpu.models.transformer import _moe_ffn, _moe_ffn_pallas
    from dllama_tpu.ops.jnp_ops import silu
    from dllama_tpu.ops.quant_matmul import QuantWeight, dequant, from_planar

    rng = np.random.default_rng(22)
    E, D, F, K = 8, 64, 128, 3

    def make_experts(out_dim, in_dim, seed):
        qs, ds = [], []
        for e in range(E):
            w = rng.standard_normal((out_dim, in_dim)).astype(np.float32) * 0.1
            qv, dv = q40_to_planar(quantize_q40(w), out_dim * in_dim)
            qw = from_planar(qv.reshape(out_dim, in_dim),
                             dv.reshape(out_dim, in_dim // 32))
            qs.append(np.asarray(qw.q))
            ds.append(np.asarray(qw.d))
        return QuantWeight(jnp.asarray(np.stack(qs)), jnp.asarray(np.stack(ds)))

    w1, w3 = make_experts(F, D, 1), make_experts(F, D, 2)
    w2 = make_experts(D, F, 3)
    gate = jnp.asarray(rng.standard_normal((D, E)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((4, 1, D)).astype(np.float32))  # 4 dp lanes

    mesh = make_mesh(tp=2, dp=2)
    out = _moe_ffn_pallas(x, gate, w1, w2, w3, Routing(K), mesh, interpret=True)
    dense = _moe_ffn(
        x, gate, dequant(w1, jnp.float32), dequant(w2, jnp.float32),
        dequant(w3, jnp.float32), Routing(K), silu,
    )
    # bf16 tolerance: the kernel computes in bf16, the reference in f32
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense), rtol=2e-2, atol=2e-2
    )


def test_flash_stats_matches_jnp_stats():
    """Pallas flash-stats kernel vs the shared jnp partial-state math,
    across query/key offsets (normalized output + log-sum-exp invariants)."""
    from dllama_tpu.ops.flash_attention import flash_attention_stats
    from dllama_tpu.ops.jnp_ops import attention_stats

    q, k, v = make_qkv(1, 16, 4, 2, 16, 32, seed=5)
    for qp, sp in [(0, 0), (16, 0), (0, 16), (40, 16)]:
        acc, m, l = flash_attention_stats(
            q, k, v, jnp.int32(qp), jnp.int32(sp),
            block_t=8, block_s=8, interpret=True,
        )
        acc_r, m_r, l_r = attention_stats(q, k, v, jnp.int32(qp), jnp.int32(sp))
        mask = np.asarray(l_r) > 0
        o = np.asarray(acc) / np.maximum(np.asarray(l)[..., None], 1e-30)
        o_r = np.asarray(acc_r) / np.maximum(np.asarray(l_r)[..., None], 1e-30)
        np.testing.assert_allclose(o[mask], o_r[mask], rtol=1e-5, atol=1e-5)
        lse = np.asarray(m) + np.log(np.maximum(np.asarray(l), 1e-30))
        lse_r = np.asarray(m_r) + np.log(np.maximum(np.asarray(l_r), 1e-30))
        np.testing.assert_allclose(lse[mask], lse_r[mask], rtol=1e-5, atol=1e-5)


def test_flash_stats_per_lane_positions():
    """Vector q_pos0 in the prefill stats kernel: each lane's chunk starts
    at its own position; a strongly negative lane (the engine's parked
    sentinel) yields fully-masked stats."""
    from dllama_tpu.ops.flash_attention import flash_attention_stats
    from dllama_tpu.ops.jnp_ops import attention_stats

    q, k, v = make_qkv(3, 8, 4, 2, 16, 32, seed=15)
    posv = jnp.asarray([0, 16, -64], jnp.int32)  # lane 2 parked
    acc, m, l = flash_attention_stats(
        q, k, v, posv, jnp.int32(0), block_t=8, block_s=8, interpret=True
    )
    for lane, p in enumerate([0, 16]):
        acc_r, m_r, l_r = attention_stats(
            q[lane : lane + 1], k[lane : lane + 1], v[lane : lane + 1],
            jnp.int32(p), jnp.int32(0),
        )
        mask = np.asarray(l_r[0]) > 0
        o = np.asarray(acc[lane]) / np.maximum(
            np.asarray(l[lane])[..., None], 1e-30
        )
        o_r = np.asarray(acc_r[0]) / np.maximum(
            np.asarray(l_r[0])[..., None], 1e-30
        )
        np.testing.assert_allclose(
            o[mask], o_r[mask], rtol=1e-5, atol=1e-5, err_msg=f"lane {lane}"
        )
    # parked lane: zero weight everywhere
    assert float(np.abs(np.asarray(l[2])).max()) == 0.0


def test_ring_with_flash_local_step():
    """Ring attention using the Pallas flash-stats local step (interpret)
    must equal the single-device reference."""
    b, t, h, kh, hd = 1, 32, 4, 2, 16
    q, k, v = make_qkv(b, t, h, kh, hd, t, seed=19)
    mesh = make_mesh(sp=4)
    expected = attention_ref(q, k, v, jnp.int32(0))
    out = ring_attention(q, k, v, mesh, q_pos0=0, use_flash=True, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=2e-4, atol=2e-4
    )


def test_moe_pallas_tp_q80_sync_close():
    """The MoE TP branch with Q80-compressed partial-sum psum
    (sync_quant=True; parallel/collectives.psum_q80) must stay within
    quantization tolerance of the exact-psum result on a tp=2 mesh."""
    from dllama_tpu.models.transformer import _moe_ffn_pallas

    rng = np.random.default_rng(23)
    E, D, F, K = 8, 64, 128, 3
    w1 = jnp.asarray(rng.standard_normal((E, D, F)).astype(np.float32) * 0.1)
    w2 = jnp.asarray(rng.standard_normal((E, F, D)).astype(np.float32) * 0.1)
    w3 = jnp.asarray(rng.standard_normal((E, D, F)).astype(np.float32) * 0.1)
    gate = jnp.asarray(rng.standard_normal((D, E)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((1, 1, D)).astype(np.float32))

    mesh = make_mesh(tp=2)
    exact = _moe_ffn_pallas(x, gate, w1, w2, w3, Routing(K), mesh, interpret=True)
    q80 = _moe_ffn_pallas(
        x, gate, w1, w2, w3, Routing(K), mesh, interpret=True, sync_quant=True
    )
    scale = float(np.abs(np.asarray(exact)).max())
    err = float(np.abs(np.asarray(q80) - np.asarray(exact)).max())
    assert err / scale < 2e-2, (err, scale)
    assert err > 0.0  # the compressed path actually took effect


def _rand_moe(rng, E, D, F):
    w1 = jnp.asarray(rng.standard_normal((E, D, F)).astype(np.float32) * 0.1)
    w2 = jnp.asarray(rng.standard_normal((E, F, D)).astype(np.float32) * 0.1)
    w3 = jnp.asarray(rng.standard_normal((E, D, F)).astype(np.float32) * 0.1)
    gate = jnp.asarray(rng.standard_normal((D, E)).astype(np.float32))
    return w1, w2, w3, gate


def test_moe_grouped_matches_dense_routing():
    """Prefill-scale grouped active-expert MoE (assignments sorted by
    expert, static (tile, segment) schedule) vs the dense-over-all-experts
    path — same routing, bf16 kernel tolerance. Covers partial tiles and
    tiles spanning several expert segments."""
    from dllama_tpu.models.transformer import _moe_ffn, _moe_ffn_grouped
    from dllama_tpu.ops.jnp_ops import silu

    rng = np.random.default_rng(41)
    E, D, F = 8, 64, 128
    w1, w2, w3, gate = _rand_moe(rng, E, D, F)
    x = jnp.asarray(rng.standard_normal((2, 20, D)).astype(np.float32))

    out = _moe_ffn_grouped(x, gate, w1, w2, w3, Routing(3), mesh=None, interpret=True)
    dense = _moe_ffn(x, gate, w1, w2, w3, Routing(3), silu)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense), rtol=2e-2, atol=2e-2
    )


def test_moe_grouped_tp_and_q40():
    """Grouped MoE through the tp=2 shard_map branch with Q40 experts vs
    dense routing over dequantized experts."""
    from dllama_tpu.formats.quants import q40_to_planar, quantize_q40
    from dllama_tpu.models.transformer import _moe_ffn, _moe_ffn_grouped
    from dllama_tpu.ops.jnp_ops import silu
    from dllama_tpu.ops.quant_matmul import QuantWeight, dequant, from_planar

    rng = np.random.default_rng(42)
    E, D, F, K = 8, 64, 128, 3

    def make_experts(out_dim, in_dim):
        qs, ds = [], []
        for _ in range(E):
            w = rng.standard_normal((out_dim, in_dim)).astype(np.float32) * 0.1
            qv, dv = q40_to_planar(quantize_q40(w), out_dim * in_dim)
            qw = from_planar(qv.reshape(out_dim, in_dim),
                             dv.reshape(out_dim, in_dim // 32))
            qs.append(np.asarray(qw.q))
            ds.append(np.asarray(qw.d))
        return QuantWeight(jnp.asarray(np.stack(qs)), jnp.asarray(np.stack(ds)))

    w1, w3 = make_experts(F, D), make_experts(F, D)
    w2 = make_experts(D, F)
    gate = jnp.asarray(rng.standard_normal((D, E)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((2, 24, D)).astype(np.float32))

    mesh = make_mesh(tp=2, dp=2)
    out = _moe_ffn_grouped(x, gate, w1, w2, w3, Routing(K), mesh, interpret=True)
    dense = _moe_ffn(
        x, gate, dequant(w1, jnp.float32), dequant(w2, jnp.float32),
        dequant(w3, jnp.float32), Routing(K), silu,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense), rtol=3e-2, atol=3e-2
    )


def test_moe_grouped_schedule_dedups_shared_experts():
    """The grouped schedule collapses shared experts to one SEGMENT per
    (tile, unique expert) — the compute-side dedup — and its grid bound
    is tiles + min(E, A) + 1, not tiles + E + 1 (decode-sized batches
    would otherwise pay ~E pure-waste steps). NB the static grid still
    caps the HBM-read saving (empty steps DMA regardless): the full
    analysis and the lax.cond two-tier design that would realize read
    dedup live in docs/moe_decode_dedup.md."""
    from dllama_tpu.ops.moe_kernel import _GROUP_ROWS, _grouped_schedule

    E, m, k = 128, 8, 4
    # all 8 lanes pick the SAME 4 experts
    top_i = jnp.tile(jnp.asarray([[3, 7, 11, 90]], jnp.int32), (m, 1))
    wts = jnp.full((m, k), 0.25, jnp.float32)
    t_s, w_col, lo, hi, tile, expert = _grouped_schedule(top_i, wts, m, E)
    a = m * k  # 32 assignments -> exactly one 32-row tile
    assert lo.shape[0] == (-(-a // _GROUP_ROWS)) + min(E, a) + 1
    nonempty = np.asarray(hi > lo)
    # one step per unique expert (4), not per assignment (32)
    assert int(nonempty.sum()) == 4, np.asarray(lo)
    loaded = np.asarray(expert)[nonempty]
    assert sorted(set(loaded.tolist())) == [3, 7, 11, 90]


def test_moe_grouped_multilane_decode_parity():
    """The grouped kernel is correct at DECODE shapes (lane-sized m, one
    partial row tile): parity with the ragged per-(token, choice) kernel
    — the correctness harness the two-tier dedup design
    (docs/moe_decode_dedup.md) will reuse."""
    from dllama_tpu.models.transformer import (
        _moe_ffn_grouped,
        _moe_ffn_pallas,
    )

    rng = np.random.default_rng(17)
    E, D, F = 8, 64, 128
    w1, w2, w3, gate = _rand_moe(rng, E, D, F)
    m = 6  # decode-lane scale
    x = jnp.asarray(rng.standard_normal((m, 1, D)).astype(np.float32))

    ragged = _moe_ffn_pallas(x, gate, w1, w2, w3, Routing(3), mesh=None, interpret=True)
    grouped = _moe_ffn_grouped(x, gate, w1, w2, w3, Routing(3), mesh=None, interpret=True)
    np.testing.assert_allclose(
        np.asarray(grouped), np.asarray(ragged), rtol=2e-2, atol=2e-2
    )


def test_flash_stats_strided_matches_jnp():
    """s_stride > 1 (cyclic sp shards: key row j at position
    s_pos0 + j*stride): the flash-stats kernel's strided masks and
    causal-frontier clamp must reproduce the jnp stats math for every
    shard offset, including queries mid-shard and fully-masked shards."""
    from dllama_tpu.ops.flash_attention import flash_attention_stats
    from dllama_tpu.ops.jnp_ops import attention_stats

    q, k, v = make_qkv(1, 16, 4, 2, 16, 32, seed=19)
    for stride, s0, qpos in [(2, 0, 8), (2, 1, 8), (4, 3, 0), (2, 0, 50)]:
        acc, m, l = flash_attention_stats(
            q, k, v, jnp.int32(qpos), jnp.int32(s0),
            block_t=8, block_s=8, interpret=True, s_stride=stride,
        )
        acc_r, m_r, l_r = attention_stats(
            q, k, v, jnp.int32(qpos), jnp.int32(s0), s_stride=stride
        )
        mask = np.asarray(l_r) > 0
        assert (np.asarray(l) > 0).tolist() == mask.tolist(), (stride, s0)
        if mask.any():
            o = np.asarray(acc) / np.maximum(np.asarray(l)[..., None], 1e-30)
            o_r = np.asarray(acc_r) / np.maximum(
                np.asarray(l_r)[..., None], 1e-30
            )
            np.testing.assert_allclose(
                o[mask], o_r[mask], rtol=1e-5, atol=1e-5,
                err_msg=f"stride={stride} s0={s0} qpos={qpos}",
            )


def test_ring_cyclic_flash_local_step():
    """ring_attention_local in cyclic mode with the flash local step ==
    jnp local step (interpret mode, 4 shards)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from dllama_tpu.parallel.ring_attention import ring_attention_local

    b, t, h, kh, hd, sp = 1, 32, 4, 2, 16, 4
    q, k, v = make_qkv(b, t, h, kh, hd, t, seed=23)
    mesh = make_mesh(sp=sp)
    shard = t // sp

    def run(use_flash):
        def body(qq, kk, vv):
            idx = jax.lax.axis_index("sp")
            return ring_attention_local(
                qq, kk, vv, q_pos0=idx * (t // sp),
                shard_size=jnp.int32(shard), axis_name="sp",
                use_flash=use_flash, interpret=True, cyclic=True,
            )

        return shard_map(
            body, mesh=mesh,
            in_specs=(P(None, "sp", None, None), P(None, None, "sp", None),
                      P(None, None, "sp", None)),
            out_specs=P(None, "sp", None, None),
            check_vma=False,
        )(q, k, v)

    np.testing.assert_allclose(
        np.asarray(run(True)), np.asarray(run(False)),
        rtol=1e-5, atol=1e-5,
    )


def test_moe_two_tier_dedup_matches_ragged():
    """Opt-in two-tier decode dedup: with lanes sharing most experts the
    lax.cond dispatches the small-grid grouped kernel; with distinct
    experts it falls back to the ragged kernel — both must match the
    always-ragged output. The test VERIFIES each regime really lands on
    its branch (u vs the A/2 cap) so a predicate regression cannot pass
    silently."""
    from dllama_tpu.models.transformer import _moe_ffn_pallas, _moe_route

    rng = np.random.default_rng(31)
    E, D, F, K = 64, 64, 128, 3
    w1, w2, w3, gate = _rand_moe(rng, E, D, F)
    m = 8
    cap = (m * K) // 2
    # shared-expert regime: near-identical rows route identically
    x_shared = jnp.asarray(
        np.repeat(rng.standard_normal((1, 1, D)), m, axis=0).astype(
            np.float32
        )
        + rng.standard_normal((m, 1, D)).astype(np.float32) * 1e-3
    )
    # diverse regime: independent rows over E=64 experts spread wide
    x_div = jnp.asarray(rng.standard_normal((m, 1, D)).astype(np.float32))

    def uniques(x):
        ii, _ = _moe_route(x.reshape(m, D), gate, Routing(K))
        return len(np.unique(np.asarray(ii)))

    assert uniques(x_shared) <= cap, (uniques(x_shared), cap)
    assert uniques(x_div) > cap, (uniques(x_div), cap)

    for x in (x_shared, x_div):
        base = _moe_ffn_pallas(
            x, gate, w1, w2, w3, Routing(K), mesh=None, interpret=True
        )
        two = _moe_ffn_pallas(
            x, gate, w1, w2, w3, Routing(K), mesh=None, interpret=True, dedup=True
        )
        np.testing.assert_allclose(
            np.asarray(two), np.asarray(base), rtol=2e-2, atol=2e-2
        )


def _quant_kv_pair(k, v):
    from dllama_tpu.ops.kv_cache import QuantKV, quantize_kv_rows

    kq, ks = quantize_kv_rows(k)
    vq, vs = quantize_kv_rows(v)
    return QuantKV(kq, ks), QuantKV(vq, vs)


def test_flash_stats_quantkv_matches_dequant():
    """QuantKV-native flash stats (int8 planes + [bs, 1] scale refs,
    per-tile dequant in the kernel) == jnp stats over the
    dense dequantized view, across offsets, per-lane positions and a
    parked lane."""
    from dllama_tpu.ops.flash_attention import flash_attention_stats
    from dllama_tpu.ops.jnp_ops import attention_stats
    from dllama_tpu.ops.kv_cache import dequant_kv

    q, k, v = make_qkv(1, 16, 4, 2, 16, 32, seed=31)
    qk, qv = _quant_kv_pair(k, v)
    kd, vd = dequant_kv(qk, q.dtype), dequant_kv(qv, q.dtype)
    for qp, sp in [(0, 0), (16, 0), (40, 16)]:
        acc, m, l = flash_attention_stats(
            q, qk, qv, jnp.int32(qp), jnp.int32(sp),
            block_t=8, block_s=8, interpret=True,
        )
        acc_r, m_r, l_r = attention_stats(q, kd, vd, jnp.int32(qp), jnp.int32(sp))
        mask = np.asarray(l_r) > 0
        o = np.asarray(acc) / np.maximum(np.asarray(l)[..., None], 1e-30)
        o_r = np.asarray(acc_r) / np.maximum(np.asarray(l_r)[..., None], 1e-30)
        np.testing.assert_allclose(o[mask], o_r[mask], rtol=1e-5, atol=1e-5)

    # per-lane positions + parked lane over QuantKV
    q3, k3, v3 = make_qkv(3, 8, 4, 2, 16, 32, seed=32)
    qk3, qv3 = _quant_kv_pair(k3, v3)
    posv = jnp.asarray([0, 16, -64], jnp.int32)
    acc, m, l = flash_attention_stats(
        q3, qk3, qv3, posv, jnp.int32(0), block_t=8, block_s=8, interpret=True
    )
    kd3, vd3 = dequant_kv(qk3, q3.dtype), dequant_kv(qv3, q3.dtype)
    for lane, p in enumerate([0, 16]):
        acc_r, m_r, l_r = attention_stats(
            q3[lane : lane + 1], kd3[lane : lane + 1], vd3[lane : lane + 1],
            jnp.int32(p), jnp.int32(0),
        )
        mask = np.asarray(l_r[0]) > 0
        o = np.asarray(acc[lane]) / np.maximum(np.asarray(l[lane])[..., None], 1e-30)
        o_r = np.asarray(acc_r[0]) / np.maximum(np.asarray(l_r[0])[..., None], 1e-30)
        np.testing.assert_allclose(o[mask], o_r[mask], rtol=1e-5, atol=1e-5)
    assert float(np.abs(np.asarray(l[2])).max()) == 0.0


def test_flash_stats_quantkv_strided():
    """QuantKV + s_stride > 1 (cyclic sp shards): the int8-native kernel
    must keep the strided masks/clamp semantics."""
    from dllama_tpu.ops.flash_attention import flash_attention_stats
    from dllama_tpu.ops.jnp_ops import attention_stats
    from dllama_tpu.ops.kv_cache import dequant_kv

    q, k, v = make_qkv(1, 16, 4, 2, 16, 32, seed=33)
    qk, qv = _quant_kv_pair(k, v)
    kd, vd = dequant_kv(qk, q.dtype), dequant_kv(qv, q.dtype)
    for stride, s0, qpos in [(2, 0, 8), (2, 1, 8), (4, 3, 0), (2, 0, 50)]:
        acc, m, l = flash_attention_stats(
            q, qk, qv, jnp.int32(qpos), jnp.int32(s0),
            block_t=8, block_s=8, interpret=True, s_stride=stride,
        )
        acc_r, m_r, l_r = attention_stats(
            q, kd, vd, jnp.int32(qpos), jnp.int32(s0), s_stride=stride
        )
        mask = np.asarray(l_r) > 0
        assert (np.asarray(l) > 0).tolist() == mask.tolist(), (stride, s0)
        if mask.any():
            o = np.asarray(acc) / np.maximum(np.asarray(l)[..., None], 1e-30)
            o_r = np.asarray(acc_r) / np.maximum(np.asarray(l_r)[..., None], 1e-30)
            np.testing.assert_allclose(
                o[mask], o_r[mask], rtol=1e-5, atol=1e-5,
                err_msg=f"stride={stride} s0={s0} qpos={qpos}",
            )


def test_flash_quantkv_no_dense_materialization():
    """The int8 prefill read claim:
    (a) the traced program feeds the kernel the int8 planes directly —
    no dense cache-shaped f32/bf16 intermediate exists anywhere in the
    jaxpr; (b) the cache-sized kernel inputs are ~53% the bytes of the
    bf16 dense view (int8 values + f32 per-row scale vs 2B/elem)."""
    from dllama_tpu.ops.flash_attention import flash_attention_stats
    from dllama_tpu.ops.kv_cache import QuantKV

    b, kh, s, hd = 1, 2, 256, 64
    q, k, v = make_qkv(b, 8, 4, kh, hd, s, seed=34)
    qk, qv = _quant_kv_pair(k.astype(jnp.bfloat16), v.astype(jnp.bfloat16))

    def run(qq, kq, ks, vq, vs):
        return flash_attention_stats(
            qq.astype(jnp.bfloat16), QuantKV(kq, ks), QuantKV(vq, vs),
            jnp.int32(0), jnp.int32(0), block_t=8, block_s=128,
        )

    txt = str(jax.make_jaxpr(run)(q, qk.q, qk.s, qv.q, qv.s))
    dense_shape = f"[{b},{kh},{s},{hd}]"
    assert f"i8{dense_shape}" in txt  # int8 planes reach the kernel
    for dt in ("f32", "bf16"):
        assert dt + dense_shape not in txt, (
            f"dense {dt} cache materialized:\n"
            + "\n".join(ln for ln in txt.splitlines() if dense_shape in ln)
        )
    int8_bytes = qk.q.nbytes + qk.s.nbytes
    bf16_bytes = 2 * b * kh * s * hd
    assert int8_bytes / bf16_bytes < 0.55, int8_bytes / bf16_bytes


def test_ring_cyclic_flash_quantkv():
    """ring_attention_local in cyclic mode over a QuantKV shard: flash
    local step (int8-native) == jnp local step (local dequant); the ring
    rotates int8 payloads either way."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from dllama_tpu.ops.kv_cache import QuantKV
    from dllama_tpu.parallel.ring_attention import ring_attention_local

    b, t, h, kh, hd, sp = 1, 32, 4, 2, 16, 4
    q, k, v = make_qkv(b, t, h, kh, hd, t, seed=35)
    qk, qv = _quant_kv_pair(k, v)
    mesh = make_mesh(sp=sp)
    shard = t // sp

    def run(use_flash):
        def body(qq, kk, ks, vv, vs):
            idx = jax.lax.axis_index("sp")
            return ring_attention_local(
                qq, QuantKV(kk, ks), QuantKV(vv, vs),
                q_pos0=idx * (t // sp),
                shard_size=jnp.int32(shard), axis_name="sp",
                use_flash=use_flash, interpret=True, cyclic=True,
            )

        kv_spec = P(None, None, "sp", None)
        return shard_map(
            body, mesh=mesh,
            in_specs=(P(None, "sp", None, None), kv_spec, kv_spec,
                      kv_spec, kv_spec),
            out_specs=P(None, "sp", None, None),
            check_vma=False,
        )(q, qk.q, qk.s, qv.q, qv.s)

    np.testing.assert_allclose(
        np.asarray(run(True)), np.asarray(run(False)), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("per_lane", [False, True], ids=["scalar", "per_lane"])
@pytest.mark.parametrize("quant", [False, True], ids=["dense", "quantkv"])
@pytest.mark.parametrize("layer", [0, 1, 3])
def test_flash_reads_a_layer_of_the_stack_in_place(layer, quant, per_lane):
    """`flash_attention(q, stack, stack, pos, layer=l, rows=w)` over the
    whole `[L, B, KH, S, hd]` cache is the call on `stack[l][:, :, :w]`,
    bit for bit: the layer number only moves the K/V block index, the row
    count only the grid. (What the model used to hand the kernel was that
    slice, copied out of the stack first.)"""
    n_layers, b, t, h, kh, hd, s, w = 4, 2, 8, 4, 2, 16, 64, 32
    rng = np.random.default_rng(40 + layer)
    q = jnp.asarray(rng.standard_normal((b, t, h, hd)), jnp.float32)
    k, v = (
        jnp.asarray(rng.standard_normal((n_layers, b, kh, s, hd)), jnp.float32)
        for _ in range(2)
    )
    if quant:
        k, v = _quant_kv_pair(k, v)
    pos = jnp.asarray([9, 20], jnp.int32) if per_lane else jnp.int32(13)
    one = jax.tree.map(lambda a: a[layer][:, :, :w], (k, v))
    kw = dict(block_t=8, block_s=8, interpret=True)
    want = flash_attention(q, *one, pos, **kw)
    got = flash_attention(q, k, v, pos, layer=jnp.int32(layer), rows=w, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # a window that is the whole axis, and the layer as a Python int
    full = jax.tree.map(lambda a: a[layer], (k, v))
    np.testing.assert_array_equal(
        np.asarray(flash_attention(q, k, v, pos, layer=layer, **kw)),
        np.asarray(flash_attention(q, *full, pos, **kw)),
    )
