"""Grouped-int8 matmul (ops/int8_matmul.py): the MXU-native restatement
of the reference's Q80-activation x Q40-weight integer dot
(src/nn/nn-cpu-ops.cpp:231-449). Pins (a) the requantization error stays
in the Q40 noise floor, (b) the Pallas kernel (interpret mode) matches
the exact-integer reference path, (c) shape/validation edges."""

import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.formats.quants import q40_to_planar, quantize_q40
from dllama_tpu.ops import quant_matmul as qm
from dllama_tpu.ops.int8_matmul import (

    Int8Weight,
    i8matmul,
    i8matmul_2d,
    i8matmul_ref,
    quantize_acts,
    requantize_q40,
)

# sub-minute CPU-only surface (codecs, tokenizer, native loader,
# interpret-mode kernel parity): the first CI lane runs `pytest -m fast`
pytestmark = pytest.mark.fast


def _q40(rng, k, n, scale=0.1):
    w = (rng.standard_normal((n, k)) * scale).astype(np.float32)
    qv, dv = q40_to_planar(quantize_q40(w), n * k)
    return qm.from_planar(qv.reshape(n, k), dv.reshape(n, k // 32)), w


def test_requantize_error_within_q40_noise():
    """int8-per-512 requantization of a Q40 tensor must add error small
    relative to what Q40 quantization itself already carries."""
    rng = np.random.default_rng(7)
    k, n = 1024, 256
    w, dense_true = _q40(rng, k, n)
    dense_q40 = np.asarray(qm.dequant(w, jnp.float32))  # [k, n]
    w8 = requantize_q40(w, group=512)
    assert w8.group == 512
    dense_i8 = np.asarray(w8.q, np.float32) * np.repeat(
        np.asarray(w8.s), 512, axis=0
    )
    q40_err = np.abs(dense_q40 - dense_true.T).max()
    i8_err = np.abs(dense_i8 - dense_q40).max()
    assert i8_err < q40_err, (i8_err, q40_err)


def test_i8matmul_ref_close_to_f32():
    rng = np.random.default_rng(11)
    k, n = 2048, 512
    w, dense_true = _q40(rng, k, n)
    x = jnp.asarray(rng.standard_normal((3, k)).astype(np.float32))
    w8 = requantize_q40(w, group=256)
    got = np.asarray(i8matmul_ref(x, w8))
    want = np.asarray(qm.qmatmul_ref(x, w))
    scale = np.abs(want).max()
    err = np.abs(got - want).max()
    assert err / scale < 2e-2, (err, scale)


@pytest.mark.parametrize("group,block_k", [(256, 1024), (512, 512), (1024, 2048)])
def test_kernel_matches_ref(group, block_k):
    """Pallas kernel in interpret mode == exact-integer reference path
    (same int math; only fp summation order differs)."""
    rng = np.random.default_rng(3)
    m, k, n = 4, 2048, 512
    w, _ = _q40(rng, k, n)
    w8 = requantize_q40(w, group=group)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    xq, sx = quantize_acts(x, group)
    got = np.asarray(
        i8matmul_2d(xq, sx, w8.q, w8.s, block_n=256, block_k=block_k,
                    interpret=True)
    )
    want = np.asarray(i8matmul_ref(x, w8))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_kernel_awkward_k_block_alignment():
    """k=11264 (22*512), group=512, block_k=4096: naive group-rounding of
    the preferred block gives 2560, which does NOT divide k — the block
    search must fall back to a group multiple that does."""
    rng = np.random.default_rng(11)
    m, k, n, group = 2, 11264, 256, 512
    w, _ = _q40(rng, k, n)
    w8 = requantize_q40(w, group=group)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    xq, sx = quantize_acts(x, group)
    got = np.asarray(
        i8matmul_2d(xq, sx, w8.q, w8.s, block_n=256, block_k=4096,
                    interpret=True)
    )
    want = np.asarray(i8matmul_ref(x, w8))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_i8matmul_leading_dims():
    rng = np.random.default_rng(5)
    k, n = 512, 256
    w, _ = _q40(rng, k, n)
    w8 = requantize_q40(w, group=256)
    x = jnp.asarray(rng.standard_normal((2, 3, k)).astype(np.float32))
    out = i8matmul(x, w8)  # off-TPU: ref path
    assert out.shape == (2, 3, n)
    flat = i8matmul_ref(x.reshape(6, k), w8).reshape(2, 3, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(flat), rtol=1e-6)


def test_requantize_stacked_layers():
    """Stacked [L, k, n] tensors (the lax.scan layout) requantize
    layerwise-identically to per-layer calls."""
    rng = np.random.default_rng(9)
    k, n = 256, 128
    w0, _ = _q40(rng, k, n)
    w1, _ = _q40(rng, k, n)
    stacked = qm.QuantWeight(
        jnp.stack([w0.q, w1.q]), jnp.stack([w0.d, w1.d])
    )
    w8s = requantize_q40(stacked, group=128)
    w80 = requantize_q40(w0, group=128)
    np.testing.assert_array_equal(np.asarray(w8s.q[0]), np.asarray(w80.q))
    np.testing.assert_allclose(np.asarray(w8s.s[0]), np.asarray(w80.s))


def test_group_divisibility_validation():
    rng = np.random.default_rng(1)
    w, _ = _q40(rng, 256, 128)
    with pytest.raises(ValueError):
        requantize_q40(w, group=192)
    with pytest.raises(ValueError):
        quantize_acts(jnp.ones((2, 256)), 192)


def test_zero_columns_safe():
    """All-zero groups must not divide by zero (scale floors to 1)."""
    q = jnp.zeros((256, 128), jnp.int8)
    d = jnp.zeros((8, 128), jnp.float32)
    w8 = requantize_q40(qm.QuantWeight(q, d), group=128)
    out = i8matmul_ref(jnp.ones((1, 256)), w8)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


# -- engine integration (weight_format="q40i8") ---------------------------

CFG_I8 = dict(dim=64, hidden_dim=256, n_layers=2, n_heads=8, n_kv_heads=4,
              head_dim=16, vocab_size=288, seq_len=64)


def _engine(tmp_path, **kw):
    from dllama_tpu.formats import FloatType
    from dllama_tpu.runtime.engine import InferenceEngine

    import sys

    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))
    from helpers import make_tiny_model

    mp = str(tmp_path / "m8.m")
    make_tiny_model(mp, weight_type=FloatType.Q40, seed=13, cfg=CFG_I8)
    return InferenceEngine(mp, dtype=jnp.float32, temperature=0.0, **kw)


def test_engine_q40i8_params_converted(tmp_path):
    """q40i8 load produces Int8Weight leaves (fused wrappers included)
    and a picked group recorded on the engine."""
    from dllama_tpu.ops.quant_matmul import FusedQuantWeight

    e = _engine(tmp_path, tp=1, weight_format="q40i8")
    assert e.i8_group >= 32
    lp = e.params["layers"]
    assert isinstance(lp["wqkv"], FusedQuantWeight)
    assert isinstance(lp["wqkv"].weight, Int8Weight)
    assert isinstance(lp["w2"], Int8Weight)
    assert isinstance(e.params["wcls"], Int8Weight)


def test_engine_q40i8_tp_token_parity(tmp_path):
    """q40i8 greedy decode: tp=2 must reproduce the tp=1 token stream
    (same int8 params, collectives change only the summation layout)."""
    e1 = _engine(tmp_path, tp=1, weight_format="q40i8")
    out1, _, _ = e1.generate([5, 6, 7], max_steps=12)
    del e1
    e2 = _engine(tmp_path, tp=2, weight_format="q40i8")
    out2, _, _ = e2.generate([5, 6, 7], max_steps=12)
    assert out1 == out2


def test_engine_q40i8_perplexity_close_to_q40(tmp_path):
    """Requantization must stay in the Q40 noise floor end-to-end: the
    teacher-forced NLL of the int8 engine tracks the q40 engine's."""
    toks = [(i * 11) % 250 + 1 for i in range(40)]
    eq = _engine(tmp_path, tp=1, weight_format="q40")
    nll_q, _, _ = eq.perplexity(toks)
    del eq
    e8 = _engine(tmp_path, tp=1, weight_format="q40i8")
    nll_8, _, _ = e8.perplexity(toks)
    assert abs(nll_8 - nll_q) / abs(nll_q) < 0.02, (nll_8, nll_q)


def test_engine_q40i8_moe_keeps_expert_q40(tmp_path):
    """MoE checkpoints: experts stay Q40 (the ragged kernels' format);
    attention/wcls convert; the engine still generates."""
    from dllama_tpu.formats import FloatType
    from dllama_tpu.formats.model_file import LlmArch
    from dllama_tpu.ops.quant_matmul import QuantWeight
    from dllama_tpu.runtime.engine import InferenceEngine

    import sys

    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))
    from helpers import make_tiny_model

    mp = str(tmp_path / "moe8.m")
    make_tiny_model(mp, arch=LlmArch.QWEN3_MOE, weight_type=FloatType.Q40,
                    seed=3)
    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                        weight_format="q40i8")
    lp = e.params["layers"]
    assert isinstance(lp["w1"], QuantWeight)  # experts untouched
    assert isinstance(lp["wqkv"].weight, Int8Weight)
    out, _, _ = e.generate([1, 2, 3], max_steps=8)
    assert len(out) == 6  # max_steps - (prompt_len - 1)


def test_engine_q40i8_pp_and_sp_parity(tmp_path):
    """q40i8 composes with pipeline stages (Int8Weight leaves ride the
    per-name pp x tp specs — q and s are both rank-3, so the same
    PartitionSpec applies) and with sequence parallelism; token streams
    match the q40i8 single-device run."""
    e1 = _engine(tmp_path, tp=1, weight_format="q40i8")
    expected, _, _ = e1.generate([5, 6, 7], max_steps=12)
    del e1
    for kw in (dict(pp=2), dict(sp=2), dict(pp=2, tp=2)):
        e = _engine(tmp_path, weight_format="q40i8", **kw)
        got, _, _ = e.generate([5, 6, 7], max_steps=12)
        del e
        assert got == expected, (kw, got, expected)
