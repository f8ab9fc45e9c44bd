"""Pallas Q40 matmul vs jnp dequant reference (cross-implementation
equivalence, the reference's nn-cpu-ops-test.cpp:257-277 pattern)."""

import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.formats.quants import quantize_q40, q40_to_planar
from dllama_tpu.ops.quant_matmul import (

    QuantWeight,
    dequant,
    from_planar,
    packed_kernels_take,
    qmatmul,
    qmatmul_2d,
    qmatmul_ref,
)

# sub-minute CPU-only surface (codecs, tokenizer, native loader,
# interpret-mode kernel parity): the first CI lane runs `pytest -m fast`
pytestmark = pytest.mark.fast


def make_qw(n, k, seed=0):
    """QuantWeight for a logical [out=n, in=k] matmul weight."""
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((n, k)).astype(np.float32) * 0.1
    raw = quantize_q40(w)
    q, d = q40_to_planar(raw, n * k)
    return from_planar(q.reshape(n, k), d.reshape(n, k // 32)), w


def test_dequant_matches_codec():
    qw, w = make_qw(64, 128)
    dense = np.asarray(dequant(qw, jnp.float32)).T  # device layout is [in, out]
    # within one Q40 block scale of the original
    scales = np.abs(w.reshape(-1, 32)).max(axis=1) / 8.0
    err = np.abs(dense.reshape(-1, 32) - w.reshape(-1, 32))
    assert (err <= scales[:, None] * 1.01 + 1e-6).all()


@pytest.mark.parametrize("m,n,k", [(1, 256, 512), (8, 512, 256), (16, 256, 1024)])
def test_pallas_kernel_matches_reference(m, n, k):
    """Interpret-mode kernel vs dequant einsum (bf16 input rounding is the
    only difference source)."""
    qw, _ = make_qw(n, k, seed=1)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    expected = np.asarray(qmatmul_ref(x.astype(jnp.bfloat16).astype(jnp.float32), qw))
    got = np.asarray(qmatmul_2d(x, qw.q, qw.d, block_n=128, interpret=True))
    np.testing.assert_allclose(got, expected, rtol=2e-2, atol=2e-2)


def test_qmatmul_auto_flatten():
    qw, _ = make_qw(128, 256, seed=3)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((2, 3, 256)).astype(np.float32))
    out = qmatmul(x, qw)
    assert out.shape == (2, 3, 128)
    expected = qmatmul_ref(x, qw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=2e-2, atol=2e-2)


def test_quantweight_is_pytree():
    import jax

    qw, _ = make_qw(64, 64)
    stacked = jax.tree.map(lambda a: jnp.stack([a, a]), qw)
    assert isinstance(stacked, QuantWeight)
    assert stacked.q.shape == (2, 64, 64)
    leaves = jax.tree.leaves(qw)
    assert len(leaves) == 2


@pytest.mark.parametrize("m", [1, 4])
def test_moe_active_experts_kernel(m):
    """Ragged MoE kernel (per-token top-k) vs the dense jnp path
    (interpret mode)."""
    import jax
    from jax import lax

    from dllama_tpu.ops.moe_kernel import moe_active_experts

    rng = np.random.default_rng(2)
    E, D, F, K = 8, 64, 96, 3
    w1 = jnp.asarray(rng.standard_normal((E, D, F)).astype(np.float32) * 0.1)
    w2 = jnp.asarray(rng.standard_normal((E, F, D)).astype(np.float32) * 0.1)
    w3 = jnp.asarray(rng.standard_normal((E, D, F)).astype(np.float32) * 0.1)
    gate = jnp.asarray(rng.standard_normal((D, E)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((m, D)).astype(np.float32))

    probs = jax.nn.softmax(x @ gate, axis=-1)
    top_p, top_i = lax.top_k(probs, K)  # [m, K]
    weights = top_p / top_p.sum(axis=-1, keepdims=True)
    out = moe_active_experts(x, w1, w2, w3, top_i, weights, interpret=True)

    from dllama_tpu.models.transformer import Routing, _moe_ffn
    from dllama_tpu.ops.jnp_ops import silu

    dense = _moe_ffn(x[:, None], gate, w1, w2, w3, Routing(K), silu)  # [m, 1, D]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense)[:, 0], rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("m", [1, 4])
def test_moe_active_experts_q40_kernel(m):
    """Quantized ragged MoE kernel vs dequant-then-dense-kernel: the only
    difference source is where the dequant happens (in-VMEM vs host), so
    tolerances are bf16-rounding tight."""
    import jax
    from jax import lax

    from dllama_tpu.ops.moe_kernel import (
        moe_active_experts,
        moe_active_experts_q40,
    )

    rng = np.random.default_rng(7)
    E, D, F, K = 8, 64, 96, 3

    def make_experts(out_dim, in_dim, seed):
        qs, ds = [], []
        for e in range(E):
            qw, _ = make_qw(out_dim, in_dim, seed=seed * 100 + e)
            qs.append(np.asarray(qw.q))
            ds.append(np.asarray(qw.d))
        return QuantWeight(jnp.asarray(np.stack(qs)), jnp.asarray(np.stack(ds)))

    w1 = make_experts(F, D, 1)  # device layout: q [E, D, F]
    w3 = make_experts(F, D, 2)
    w2 = make_experts(D, F, 3)  # q [E, F, D]
    gate = jnp.asarray(rng.standard_normal((D, E)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((m, D)).astype(np.float32))

    probs = jax.nn.softmax(x @ gate, axis=-1)
    top_p, top_i = lax.top_k(probs, K)
    weights = top_p / top_p.sum(axis=-1, keepdims=True)

    out = moe_active_experts_q40(
        x, w1.q, w1.d, w2.q, w2.d, w3.q, w3.d, top_i, weights, interpret=True
    )
    expected = moe_active_experts(
        x.astype(jnp.bfloat16),
        dequant(w1, jnp.bfloat16),
        dequant(w2, jnp.bfloat16),
        dequant(w3, jnp.bfloat16),
        top_i,
        weights,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=2e-2, atol=2e-2
    )


@pytest.mark.parametrize("tp", [1, 2, 4])
def test_fused_interleave_roundtrip(tp):
    """loader._interleave_concat + transformer._split_fused restore the
    separate matmul outputs (up to XLA reduction-order f32 noise: the
    fused width changes the einsum's tiling, not its math)."""
    from dllama_tpu.models.loader import _interleave_concat
    from dllama_tpu.models.transformer import _split_fused

    rng = np.random.default_rng(7)
    k = 64
    dims = (32 * tp, 16 * tp, 16 * tp)
    ws = [rng.standard_normal((k, d)).astype(np.float32) for d in dims]
    fused = _interleave_concat(ws, tp)
    x = jnp.asarray(rng.standard_normal((2, 3, k)).astype(np.float32))
    out = jnp.einsum("btk,ko->bto", x, jnp.asarray(fused))
    parts = _split_fused(out, tp, dims)
    for part, w in zip(parts, ws):
        expect = jnp.einsum("btk,ko->bto", x, jnp.asarray(w))
        np.testing.assert_allclose(
            np.asarray(part), np.asarray(expect), rtol=1e-5, atol=1e-5
        )


def test_fused_quant_loader_matches_split(tmp_path):
    """Engine-default fusion (weight_format=q40) at the loader level: the
    fused wqkv QuantWeight dequantizes to the column-permuted concat of
    wq/wk/wv, and un-interleaving the fused matmul output reproduces the
    split results (same dequant blocks, f32-noise-level tolerance)."""
    from dllama_tpu.models.loader import _interleave_concat
    from dllama_tpu.models.transformer import _split_fused

    tp = 2
    k = 128
    dims = (64, 64, 64)
    qws = [make_qw(d, k, seed=10 + i)[0] for i, d in enumerate(dims)]
    fused = QuantWeight(
        jnp.asarray(
            _interleave_concat([np.asarray(w.q) for w in qws], tp)
        ),
        jnp.asarray(
            _interleave_concat([np.asarray(w.d) for w in qws], tp)
        ),
    )
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((1, 1, k)).astype(np.float32))
    out = qmatmul_ref(x, fused)
    parts = _split_fused(out, tp, dims)
    for part, w in zip(parts, qws):
        expect = qmatmul_ref(x, w)
        np.testing.assert_allclose(
            np.asarray(part), np.asarray(expect), rtol=0, atol=1e-5
        )


# ---------------------------------------------------------------- packed int4


def make_packed(n, k, seed=0):
    """(PackedQuantWeight, QuantWeight, dense) triple for [out=n, in=k]."""
    from dllama_tpu.ops.quant_matmul import pack_nibbles

    qw, w = make_qw(n, k, seed=seed)
    return pack_nibbles(qw), qw, w


def test_pack_nibbles_roundtrip():
    from dllama_tpu.ops.quant_matmul import unpack_nibbles

    pw, qw, _ = make_packed(64, 128)
    assert pw.qp.shape == (16, 64) == (qw.q.shape[0] // 8, qw.q.shape[1])
    assert pw.qp.dtype == jnp.int32 and pw.d.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(unpack_nibbles(pw.qp)), np.asarray(qw.q, dtype=np.int32)
    )


@pytest.mark.parametrize("n,k", [(128, 256), (40, 64), (24, 288), (16, 1024)])
def test_host_pack_matches_device_pack(n, k):
    """formats.pack_q40_device (numpy, the load path: straight from the
    wire's bytes) produces the exact words of ops.pack_nibbles (jnp, from
    the int8 plane), whole groups of 256 rows or one group of eight
    segments."""
    from dllama_tpu.formats.quants import pack_q40_device

    pw, _, w = make_packed(n, k, seed=5)
    qp_np, d_np = pack_q40_device(quantize_q40(w), n, k)
    assert qp_np.dtype == np.int32 and d_np.dtype == np.float32
    np.testing.assert_array_equal(qp_np, np.asarray(pw.qp))
    np.testing.assert_array_equal(d_np, np.asarray(pw.d))


def test_dequant_packed_matches_dequant():
    """The packed form holds the same nibbles and the same f32 scales (the
    wire's f16 values, exactly), so the packed dequant is bit-identical to
    the int8 dequant."""
    from dllama_tpu.ops.quant_matmul import dequant_packed

    pw, qw, _ = make_packed(64, 128, seed=2)
    np.testing.assert_array_equal(
        np.asarray(dequant_packed(pw, jnp.float32)),
        np.asarray(dequant(qw, jnp.float32)),
    )


@pytest.mark.parametrize("m,n,k", [(1, 256, 512), (8, 512, 256), (16, 256, 1024)])
def test_packed_kernel_matches_reference(m, n, k):
    """Interpret-mode int4 kernel vs the dequant einsum AND vs the int8
    kernel on the unpacked twin. The in-kernel nibble unpack and scale
    widening are exact, so both kernels feed the dot the same bf16
    weights; the two compiled dots may still order their f32 sums
    differently, which is worth a few ulps of the largest output."""
    from dllama_tpu.ops.quant_matmul import qmatmul_i4_2d

    pw, qw, _ = make_packed(n, k, seed=1)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    expected = np.asarray(qmatmul_ref(x.astype(jnp.bfloat16).astype(jnp.float32), qw))
    got = np.asarray(
        qmatmul_i4_2d(x, pw.qp, pw.d, block_n=128, interpret=True)
    )
    np.testing.assert_allclose(got, expected, rtol=2e-2, atol=2e-2)
    int8 = np.asarray(qmatmul_2d(x, qw.q, qw.d, block_n=128, interpret=True))
    ulp = np.spacing(np.abs(int8).max())
    np.testing.assert_allclose(got, int8, rtol=0, atol=4 * ulp)


def _edge_weights(k, n, seed):
    """(QuantWeight, PackedQuantWeight) [k, n] of every nibble, -8 and 7
    in every block, under scales of every kind the wire can hold: f16
    subnormals, negative ones, both zeros, the largest and the smallest."""
    from dllama_tpu.ops.quant_matmul import pack_nibbles

    rng = np.random.default_rng(seed)
    q = rng.integers(-8, 8, (k, n)).astype(np.int8)
    q[0::32], q[1::32] = -8, 7
    bits = rng.integers(0, 1 << 16, (k // 32, n)).astype(np.uint16)
    bits[0, :8] = [0x0001, 0x8001, 0x03FF, 0x83FF, 0x0000, 0x8000, 0x7BFF, 0xFBFF]
    d = bits.view(np.float16)
    d = np.where(np.isfinite(d), d, np.float16(-0.00123)).astype(np.float32)
    qw = QuantWeight(jnp.asarray(q), jnp.asarray(d))
    return qw, pack_nibbles(qw)


@pytest.mark.parametrize(
    "k,n,block_n,block_k",
    [(256, 256, 128, 4096), (1024, 384, 128, 512), (768, 128, None, 256)],
)
def test_packed_tile_is_the_int8_tile_bit_for_bit(k, n, block_n, block_k):
    """The identity's rows through both kernels read their dequantised
    bf16 tiles out exactly (a one-hot row sums one product): the packed
    kernel's is `_qmm_kernel`'s, which is `(nib - 8) * d` in f32 narrowed
    to bf16, for every nibble and every kind of scale. k = 1024 and 768 are
    more rows than one row block (BLOCK_M = 512), in several k steps."""
    from dllama_tpu.ops.quant_matmul import BLOCK_M, qmatmul_i4_2d

    qw, pw = _edge_weights(k, n, seed=k + n)
    eye = jnp.eye(k, dtype=jnp.bfloat16)
    assert k <= BLOCK_M or -(-k // BLOCK_M) > 1
    packed = qmatmul_i4_2d(
        eye, pw.qp, pw.d, block_n=block_n, block_k=block_k, interpret=True)
    int8 = qmatmul_2d(
        eye, qw.q, qw.d, block_n=block_n or 256, block_k=block_k, interpret=True)
    want = np.asarray(dequant(qw, jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(int8), want)
    np.testing.assert_array_equal(np.asarray(packed), want)


@pytest.mark.parametrize("m", [5, 16])
def test_packed_decode_rows_equal_the_int8_kernel(m):
    """At decode rows, with the packed kernel's own tile width (512 there):
    the same bf16 tile through the same dot, column for column."""
    from dllama_tpu.ops.quant_matmul import qmatmul_i4_2d

    qw, pw = _edge_weights(512, 1024, seed=m)
    x = jnp.asarray(
        np.random.default_rng(m).standard_normal((m, 512)).astype(np.float32))
    got = qmatmul_i4_2d(x, pw.qp, pw.d, interpret=True)
    want = qmatmul_2d(x, qw.q, qw.d, block_n=512, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,block", [
    (1, 1), (5, 5), (512, 512), (520, 272), (640, 320), (1280, 432), (1536, 512),
    (2048, 512), (2560, 512), (4096, 512), (8192, 512)])
def test_row_blocks_are_the_fewest_that_hold_the_rows_evened_out(m, block):
    """Five lanes' 128-row chunk is 640 rows: two blocks of 320, not 512 and
    a tail of 128 padded to 512; a multiple of 512 keeps its blocks."""
    from dllama_tpu.ops.quant_matmul import BLOCK_M, _pick_row_block

    assert _pick_row_block(m) == block
    assert block <= BLOCK_M and (m <= BLOCK_M or block % 16 == 0)
    assert -(-m // block) == -(-m // BLOCK_M)  # no block more than before


@pytest.mark.parametrize("m", [640, 1280])
def test_packed_rows_of_a_middle_rung_equal_the_int8_kernel_and_the_reference(m):
    """Five lanes' chunk at the ladder's rungs of 128 and 256 rows, through
    evened row blocks (the last of 1280 ragged by 16 rows): every row is the
    int8 kernel's bit for bit and the reference's, none lost to a pad."""
    from dllama_tpu.ops.quant_matmul import qmatmul_i4_2d

    qw, pw = _edge_weights(256, 256, seed=m)
    x = jnp.asarray(
        np.random.default_rng(m).standard_normal((m, 256)).astype(np.float32))
    got = np.asarray(qmatmul_i4_2d(x, pw.qp, pw.d, interpret=True))
    want = np.asarray(qmatmul_2d(x, qw.q, qw.d, interpret=True))
    np.testing.assert_array_equal(got, want)
    ref = np.asarray(qmatmul_ref(x, qw))
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-2 * np.abs(ref).max())
    assert np.abs(got[-1]).max() > 0


def test_packed_bytes_per_weight():
    """The device residency win the format exists for: 0.625 B/weight
    including scales (0.5 packed nibbles + 4/32 f32 scale)."""
    pw, qw, _ = make_packed(256, 512)
    n_weights = 256 * 512
    packed_bytes = pw.qp.nbytes + pw.d.nbytes
    assert packed_bytes / n_weights == 0.625
    assert pw.qp.nbytes * 2 == qw.q.nbytes  # exactly half the value bytes


def test_packed_qmatmul_dispatch():
    """qmatmul auto-dispatches on the weight class (ref path off-TPU)."""
    from dllama_tpu.ops.quant_matmul import PackedQuantWeight

    pw, qw, _ = make_packed(128, 256, seed=3)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((2, 3, 256)).astype(np.float32))
    out = qmatmul(x, pw)
    assert out.shape == (2, 3, 128)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(qmatmul_ref(x, qw)), rtol=2e-2, atol=2e-2
    )


def test_packedquantweight_is_pytree():
    import jax

    from dllama_tpu.ops.quant_matmul import PackedQuantWeight

    pw, _, _ = make_packed(64, 64)
    stacked = jax.tree.map(lambda a: jnp.stack([a, a]), pw)
    assert isinstance(stacked, PackedQuantWeight)
    assert stacked.qp.shape == (2, 8, 64)
    assert len(jax.tree.leaves(pw)) == 2


def test_fused_packed_matches_split():
    """Interleave (out-axis permutation) commutes with packing (in-axis
    halving): packing the fused int8 weight equals fusing then packing,
    and the fused packed ref output un-interleaves to the split results."""
    from dllama_tpu.models.loader import _interleave_concat
    from dllama_tpu.models.transformer import _split_fused
    from dllama_tpu.ops.quant_matmul import pack_nibbles

    tp = 2
    k = 128
    dims = (64, 64, 64)
    qws = [make_qw(d, k, seed=20 + i)[0] for i, d in enumerate(dims)]
    fused = QuantWeight(
        jnp.asarray(_interleave_concat([np.asarray(w.q) for w in qws], tp)),
        jnp.asarray(_interleave_concat([np.asarray(w.d) for w in qws], tp)),
    )
    pfused = pack_nibbles(fused)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((1, 1, k)).astype(np.float32))
    out = qmatmul_ref(x, pfused)
    parts = _split_fused(out, tp, dims)
    for part, w in zip(parts, qws):
        expect = qmatmul_ref(x, pack_nibbles(w))
        np.testing.assert_allclose(
            np.asarray(part), np.asarray(expect), rtol=0, atol=1e-5
        )


# ------------------------------------------------- stacks and a layer number

STACK_L = 3


def _stacked(make, n, k):
    """`STACK_L` weights of one shape as one [L, ...] stack, leaf by leaf."""
    ws = [make(n, k, seed=40 + l) for l in range(STACK_L)]
    return type(ws[0])(*(jnp.stack(leaves) for leaves in zip(*ws)))


def _stacked_experts(out_dim, in_dim, n_experts, seed):
    """QuantWeight [L, E, in, out]."""
    per_layer = [
        [make_qw(out_dim, in_dim, seed=seed + 10 * l + e)[0] for e in range(n_experts)]
        for l in range(STACK_L)
    ]
    return QuantWeight(
        *(
            jnp.stack([jnp.stack([getattr(w, f) for w in ws]) for ws in per_layer])
            for f in ("q", "d")
        )
    )


@pytest.mark.parametrize("layer", [0, 1, STACK_L - 1])
@pytest.mark.parametrize(
    "kind", ["q40", "q40i4", "fused", "ref", "moe_active", "moe_grouped"]
)
def test_stack_and_layer_equals_the_layers_slice(kind, layer):
    """The kernels take a whole [L, ...] stack and a layer number (the layer
    scan closes over the stacks, models/transformer.run_layers): the result
    is that of the same kernel on the layer's own slice, bit for bit, since
    only the block specs' index maps differ. n = 320 has no 128-multiple
    divisor, so the q40 cases run the ragged tail block too."""
    from dllama_tpu.ops.moe_kernel import (
        moe_active_experts_q40,
        moe_grouped_experts_q40,
    )
    from dllama_tpu.ops.quant_matmul import (
        FusedQuantWeight,
        layer_of,
        qmatmul_i4_2d,
    )

    rng = np.random.default_rng(50 + layer)
    at = jnp.int32(layer)
    if kind in ("q40", "q40i4", "fused", "ref"):
        k, n = 256, 320
        x = jnp.asarray(rng.standard_normal((5, k)).astype(np.float32))
        if kind == "q40i4":
            stack = _stacked(lambda *a, **kw: make_packed(*a, **kw)[0], n, k)
            kernel = qmatmul_i4_2d
        else:
            stack = _stacked(lambda *a, **kw: make_qw(*a, **kw)[0], n, k)
            kernel = qmatmul_2d
        if kind == "fused":  # the fused wrapper carries the stack as it is
            stack = FusedQuantWeight(stack, 2, (160, 160)).weight
        if kind == "ref":  # the off-chip path takes the same (stack, layer)
            got = qmatmul_ref(x, stack, at)
            want = qmatmul_ref(x, layer_of(stack, layer))
        else:
            got = kernel(x, *stack, at, interpret=True)
            want = kernel(x, *layer_of(stack, layer), interpret=True)
        assert got.shape == (5, n)
    else:
        E, D, F, K = 4, 64, 256, 2
        m = 3 if kind == "moe_active" else 40
        kernel = (
            moe_active_experts_q40
            if kind == "moe_active"
            else moe_grouped_experts_q40
        )
        w1 = _stacked_experts(F, D, E, 100)  # q [L, E, D, F]
        w3 = _stacked_experts(F, D, E, 200)
        w2 = _stacked_experts(D, F, E, 300)  # q [L, E, F, D]
        x = jnp.asarray(rng.standard_normal((m, D)).astype(np.float32))
        top_i = jnp.asarray(
            np.stack([rng.permutation(E)[:K] for _ in range(m)]).astype(np.int32)
        )
        wts = jnp.asarray(rng.random((m, K)).astype(np.float32))
        got = kernel(x, *w1, *w2, *w3, top_i, wts, at, interpret=True)
        want = kernel(
            x, *layer_of(w1, layer), *layer_of(w2, layer), *layer_of(w3, layer),
            top_i, wts, interpret=True,
        )
        assert got.shape == (m, D)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.abs(np.asarray(got)).max() > 0


# -- the packing rule (`packed_kernels_take`; models/loader owns what follows) --
#
# Which Q40 tensors of a file are held as packed words: the dense matmuls
# all or none, by their in axes a tp shard; the routed experts where one
# device holds them and the kernel takes their in axes. The six served
# configurations at tp 1 and 4, on one and four devices, and tiny headers
# that have to stay int8: (a configuration's name or a tiny file, tp, devices,
# dense, experts).

_PACKABLE_MOE = dict(dim=512, hidden_dim=512, moe_hidden_dim=512, n_layers=1,
                     n_heads=32, n_kv_heads=4, head_dim=16, vocab_size=256,
                     seq_len=64, n_experts=4, n_active_experts=2)
PACKING_CASES = {
    "mistral-7b-v0.3/tp1": ("mistral-7b-v0.3", 1, 1, True, False),
    "mistral-7b-v0.3/tp4": ("mistral-7b-v0.3", 4, 4, True, False),
    "qwen3-30b-a3b-l12/tp1": ("qwen3-30b-a3b-l12", 1, 1, True, True),
    # routed experts on a mesh stay int8, whatever their widths
    "qwen3-30b-a3b-l12/tp4": ("qwen3-30b-a3b-l12", 4, 4, True, False),
    "trinity-large-l9-e32/tp1": ("trinity-large-l9-e32", 1, 1, True, True),
    "trinity-large-l9-e32/tp4": ("trinity-large-l9-e32", 4, 4, True, False),
    # q_lora_rank 1536 and kv_lora_rank + rope 576 are no 1024s
    "openpangu-ultra-l5-e32/tp1": ("openpangu-ultra-l5-e32", 1, 1, True, True),
    "openpangu-ultra-l5-e32/tp4": ("openpangu-ultra-l5-e32", 4, 4, False, False),
    "deepseek-v3.2-l5-e32/tp1": ("deepseek-v3.2-l5-e32", 1, 1, True, True),
    "deepseek-v3.2-l5-e32/tp4": ("deepseek-v3.2-l5-e32", 4, 4, False, False),
    "lfm2-24b-a2b-e16/tp1": ("lfm2-24b-a2b-e16", 1, 1, True, True),
    "lfm2-24b-a2b-e16/tp4": ("lfm2-24b-a2b-e16", 4, 4, False, False),
    # four replicas' lanes on a mesh, no in axis sliced: still a mesh
    "qwen3-30b-a3b-l12/dp4": ("qwen3-30b-a3b-l12", 1, 4, True, False),
    # tiny files, held whole below: (architecture, make_tiny_model's cfg)
    "tiny/in-axis-64": (("LLAMA", None), 1, 1, False, False),
    "tiny-moe/in-axes-64-96": (("QWEN3_MOE", None), 1, 1, False, False),
    "tiny-moe/in-axes-512": (("QWEN3_MOE", _PACKABLE_MOE), 1, 1, True, True),
    "tiny-moe/in-axes-512-on-4-devices": (("QWEN3_MOE", _PACKABLE_MOE), 1, 4, True, False),
}


def _quant_classes(params) -> dict:
    leaves = {**params["layers"], "wcls": params["wcls"]}
    return {n: type(w).__name__ for n, w in leaves.items() if isinstance(w, tuple)}


@pytest.mark.parametrize("case", list(PACKING_CASES))
def test_packing_rule(tmp_path, case):
    """`packs_dense` / `packs_experts` over a header's tensor plan give the
    forms the engine's two methods gave at 98f7288, and for a header small
    enough to hold, `load_params` and `random_params` hold the same classes
    of leaves under `q40i4`: the rule has one home."""
    import json
    import os
    import sys

    from helpers import REPO_ROOT, make_tiny_model

    from dllama_tpu.formats import FloatType, ModelReader
    from dllama_tpu.formats.model_file import LlmArch, read_llm_header, tensor_plan
    from dllama_tpu.formats.writer import write_header
    from dllama_tpu.models.loader import (
        load_params,
        packs_dense,
        packs_experts,
        weight_forms,
    )

    source, tp, devices, dense, experts = PACKING_CASES[case]
    path = str(tmp_path / "m.m")
    if isinstance(source, str):  # a served configuration: its header alone
        if REPO_ROOT not in sys.path:
            sys.path.insert(0, REPO_ROOT)
        from benchmark.harness import weights

        with open(os.path.join(REPO_ROOT, "benchmark", "configs", source + ".json")) as f:
            wire = weights.header_for(json.load(f))
        with open(path, "wb") as f:
            write_header(f, wire)
        specs = tensor_plan(read_llm_header(path))
    else:
        arch, cfg = source
        make_tiny_model(path, arch=LlmArch[arch], weight_type=FloatType.Q40, cfg=cfg)
        specs = ModelReader(path).specs
    assert packed_kernels_take(256) and packed_kernels_take(1024, 4)
    assert not packed_kernels_take(64) and not packed_kernels_take(768, 4)
    assert packs_dense(specs, tp) is dense
    assert packs_experts(specs, devices) is experts
    assert weight_forms(specs, "q40", devices) == ("int8", "int8")
    assert weight_forms(specs, "dense", devices) == ("float", "float")
    # under an explicit `q40i4` the dense matmuls are packed as asked (the
    # engine refuses it at tp > 1 where `packs_dense` is false)
    assert weight_forms(specs, "q40i4", devices) == (
        "packed", "packed" if experts else "int8")
    if isinstance(source, str):
        return
    from dllama_tpu.models.synthetic import random_params
    from dllama_tpu.parallel import make_mesh, shard_params_put

    reader = ModelReader(path)
    mesh = make_mesh(dp=devices) if devices > 1 else None
    loaded = load_params(
        reader, weight_format="q40i4",
        **({"put": shard_params_put(mesh, reader.header)} if mesh else {}))
    made = random_params(reader.header, dtype=jnp.float32, mesh=mesh,
                         weight_format="q40i4")
    classes = _quant_classes(loaded)
    assert classes == _quant_classes(made)
    assert classes["wo"] == "PackedQuantWeight"
    if reader.header.n_experts:
        assert classes["w1"] == ("PackedQuantWeight" if experts else "QuantWeight")
