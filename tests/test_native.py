"""Native C++ loader kernels vs numpy codecs (exact parity required)."""

import numpy as np
import pytest

from dllama_tpu.formats.quants import dequantize_q40, q40_to_planar, quantize_q40
from dllama_tpu.utils import native

# sub-minute CPU-only surface (codecs, tokenizer, native loader,
# interpret-mode kernel parity): the first CI lane runs `pytest -m fast`
pytestmark = pytest.mark.fast



@pytest.fixture(scope="module")
def lib():
    lib = native.load_library()
    if lib is None:
        pytest.skip("native library unavailable (no toolchain)")
    return lib


def test_unpack_transposed_parity(lib):
    rows, cols = 96, 160
    rng = np.random.default_rng(0)
    w = rng.standard_normal((rows, cols)).astype(np.float32)
    raw = quantize_q40(w)
    q, d = native.q40_unpack_transposed(raw, rows, cols)
    q_np, d_np = q40_to_planar(raw, rows * cols)
    np.testing.assert_array_equal(q, q_np.reshape(rows, cols).T)
    np.testing.assert_allclose(
        d, d_np.reshape(rows, cols // 32).T.astype(np.float32), rtol=0, atol=0
    )


@pytest.mark.parametrize(
    "rows,cols", [(96, 160), (130, 512), (7, 288), (64, 256), (200, 1024)]
)
def test_pack_transposed_parity(lib, rows, cols):
    """The native packer writes the numpy packer's words and scales
    (formats.quants.pack_q40_device), and `unpack_nibbles` reads the wire's
    values back out of them: whole groups of 256 rows (512, 256, 1024) or
    one group of eight segments (160, 288), more rows than a tile of 64."""
    import jax.numpy as jnp

    from dllama_tpu.formats.quants import pack_q40_device
    from dllama_tpu.ops.quant_matmul import unpack_nibbles

    rng = np.random.default_rng(rows + cols)
    raw = quantize_q40(rng.standard_normal((rows, cols)).astype(np.float32))
    words, d = native.q40_pack_transposed(raw, rows, cols)
    words_np, d_np = pack_q40_device(raw, rows, cols)
    assert words.dtype == np.int32 and words.shape == (cols // 8, rows)
    np.testing.assert_array_equal(words, words_np)
    np.testing.assert_array_equal(d, d_np)
    q_np, dq = q40_to_planar(raw, rows * cols)
    np.testing.assert_array_equal(
        np.asarray(unpack_nibbles(jnp.asarray(words))), q_np.reshape(rows, cols).T
    )
    np.testing.assert_array_equal(
        d, dq.reshape(rows, cols // 32).T.astype(np.float32)
    )


def test_dequant_parity(lib):
    rows, cols = 64, 128
    rng = np.random.default_rng(1)
    w = rng.standard_normal((rows, cols)).astype(np.float32)
    raw = quantize_q40(w)
    expected = dequantize_q40(raw, rows * cols).reshape(rows, cols)
    np.testing.assert_allclose(
        native.q40_dequant(raw, rows, cols), expected, rtol=0, atol=0
    )
    np.testing.assert_allclose(
        native.q40_dequant_transposed(raw, rows, cols), expected.T, rtol=0, atol=0
    )


@pytest.mark.parametrize("weight_format", ["q40", "q40i4"])
def test_loader_uses_native_path(tmp_path, lib, weight_format):
    """End-to-end: params loaded with the native path match the numpy path,
    int8 planes (`wq.q`) and packed words (`wq.qp`) alike."""
    import sys

    sys.path.insert(0, "tests")
    from helpers import make_tiny_model

    from dllama_tpu.formats import FloatType, ModelReader
    from dllama_tpu.models import load_params

    mp = str(tmp_path / "m.m")
    make_tiny_model(mp, weight_type=FloatType.Q40)
    reader = ModelReader(mp)
    p_native = load_params(reader, weight_format=weight_format)
    # force numpy fallback
    saved = native._lib
    native._lib = None
    native._lib_tried = True
    try:
        p_numpy = load_params(reader, weight_format=weight_format)
    finally:
        native._lib = saved
    np.testing.assert_array_equal(
        np.asarray(p_native["layers"]["wq"][0]), np.asarray(p_numpy["layers"]["wq"][0])
    )
    np.testing.assert_allclose(
        np.asarray(p_native["layers"]["wq"].d), np.asarray(p_numpy["layers"]["wq"].d)
    )
    np.testing.assert_array_equal(
        np.asarray(p_native["wcls"][0]), np.asarray(p_numpy["wcls"][0])
    )


def test_f32_transpose_parity(lib):
    rng = np.random.default_rng(5)
    a = rng.standard_normal((130, 257)).astype(np.float32)  # odd sizes
    out = native.f32_transpose(a)
    np.testing.assert_array_equal(out, a.T)


def test_bpe_encode_parity(lib, tmp_path):
    """Native heap-based BPE vs the Python rescan loop: identical token
    streams over random texts, specials on and off, empty input, and the
    un-tokenizable case (native punts back to Python's detailed error)."""
    from helpers import make_tiny_tokenizer
    from dllama_tpu.tokenizer import Tokenizer

    make_tiny_tokenizer(str(tmp_path / "t.t"))
    tok = Tokenizer(str(tmp_path / "t.t"))

    def python_encode(text, **kw):
        saved = tok._encode_native
        tok._encode_native = lambda raw, sp, bos: None
        try:
            return tok.encode(text, **kw)
        finally:
            tok._encode_native = saved

    rng = np.random.default_rng(9)
    cases = [
        "hello world",
        "",
        "the quick brown fox jumps over the lazy dog " * 10,
        "<s>special</s> mixed <|eot|> text",
        "émojis 🦙 and ünïcode",
    ]
    for _ in range(20):
        n = int(rng.integers(1, 200))
        cases.append(bytes(rng.integers(32, 127, n).astype(np.uint8)).decode())
    for text in cases:
        for sp in (True, False):
            got = tok.encode(text, add_special_tokens=sp)
            want = python_encode(text, add_special_tokens=sp)
            assert got == want, (text[:40], sp, got[:10], want[:10])

    # multi-byte UTF-8 straddling merges
    s = "ααββγγ" * 30
    assert tok.encode(s) == python_encode(s)


def test_bpe_encode_tie_break_leftmost(lib):
    """Equal merge scores: the heap must pick the LEFTMOST pair, exactly
    like the Python rescan loop's strictly-greater comparison does.
    Vocab: a,b,c + ab,bc with EQUAL scores — "abc" must merge (a,b)
    first -> [ab, c], not [a, bc]."""
    from dllama_tpu.formats.tokenizer_file import TokenizerData
    from dllama_tpu.tokenizer import Tokenizer

    vocab = [b"a", b"b", b"c", b"ab", b"bc", b"<s>"]
    scores = [0.0, 0.0, 0.0, 5.0, 5.0, 0.0]
    data = TokenizerData(
        vocab=vocab, scores=scores, bos_id=5, add_bos=False,
        eos_token_ids=[], chat_template=None, max_token_length=3,
    )
    tok = Tokenizer(data)

    def python_encode(text):
        saved = tok._encode_native
        tok._encode_native = lambda raw, sp, bos: None
        try:
            return tok.encode(text)
        finally:
            tok._encode_native = saved

    for text in ("abc", "abcabc", "abcbcab", "aabbcc", "cabcab"):
        got = tok.encode(text)
        want = python_encode(text)
        assert got == want, (text, got, want)
    # the canonical tie: leftmost pair wins
    assert tok.encode("abc") == [3, 2]  # [ab, c]
