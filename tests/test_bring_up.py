"""Repairs made to run on the chip: where the compile cache lives, the HBM
peak table, synthetic checkpoints that can fail a comparison, kernel blocks
for shapes no 128-multiple tiles, and chip_smoke.py's own token check."""

from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import REPO_ROOT

sys.path.insert(0, REPO_ROOT)


# -- compile cache -----------------------------------------------------------


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_dir_honours_env(monkeypatch, tmp_path, cache_config):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself: nothing is set in code
    and nothing is created."""
    from dllama_tpu.parallel import mesh

    want = str(tmp_path / "from-env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    before = jax.config.jax_compilation_cache_dir
    assert mesh.enable_compilation_cache() == want
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.path.exists(want)


def test_cache_dir_default_is_fixed_in_checkout(monkeypatch, cache_config):
    from dllama_tpu.parallel import mesh

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(REPO_ROOT, ".jax_cache")
    assert mesh.DEFAULT_COMPILATION_CACHE_DIR == fixed
    assert mesh.enable_compilation_cache() == fixed
    assert jax.config.jax_compilation_cache_dir == fixed
    assert os.path.isdir(fixed)


def test_cache_dir_that_cannot_be_made_raises(monkeypatch, tmp_path, cache_config):
    from dllama_tpu.parallel import mesh

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    monkeypatch.setattr(
        mesh, "DEFAULT_COMPILATION_CACHE_DIR", str(blocker / "cache")
    )
    with pytest.raises(OSError):
        mesh.enable_compilation_cache()


# -- HBM peak table ------------------------------------------------------------


def _as_tpu(monkeypatch, kind: str) -> None:
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        jax, "devices", lambda *a: [SimpleNamespace(device_kind=kind)]
    )


def test_hbm_peak_finds_v5e_by_its_device_kind(monkeypatch):
    from dllama_tpu.obs.cost import hbm_peak_bytes_per_s

    _as_tpu(monkeypatch, "TPU v5 lite")  # what the v5e reports
    assert hbm_peak_bytes_per_s() == 819e9


def test_hbm_peak_unknown_tpu_kind_raises(monkeypatch):
    from dllama_tpu.obs.cost import hbm_peak_bytes_per_s

    _as_tpu(monkeypatch, "TPU v99 imaginary")
    with pytest.raises(ValueError, match="TPU v99 imaginary"):
        hbm_peak_bytes_per_s()


# -- synthetic checkpoints -------------------------------------------------------


def test_synth_model_rows_differ_and_greedy_wanders(tmp_path):
    """Every row of a synthetic tensor is its own, so logits differ and a
    comparison against a reference on such a file can fail: greedy decode
    does not sit on one token."""
    from dllama_tpu.formats.model_file import ModelReader
    from dllama_tpu.models.synthetic import (
        write_synth_model,
        write_synth_tokenizer,
    )
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.tokenizer import Tokenizer

    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=4, n_kv_heads=2,
               head_dim=16, vocab_size=512, seq_len=128)
    model, tok_path = str(tmp_path / "m.m"), str(tmp_path / "t.t")
    write_synth_model(model, cfg, seed=3, max_seq_len=128)
    reader = ModelReader(model)
    for name in ("wcls", "embed", "layers.0.w1"):
        rows = reader.dense_f32(name)
        rows = rows.reshape(-1, rows.shape[-1])
        assert len(np.unique(rows[:64], axis=0)) == 64, name
    # the same seed writes the same bytes; another seed does not
    write_synth_model(str(tmp_path / "again.m"), cfg, seed=3, max_seq_len=128)
    write_synth_model(str(tmp_path / "other.m"), cfg, seed=4, max_seq_len=128)
    data = open(model, "rb").read()
    assert data == open(tmp_path / "again.m", "rb").read()
    assert data != open(tmp_path / "other.m", "rb").read()

    write_synth_tokenizer(tok_path, cfg["vocab_size"])
    tok = Tokenizer(tok_path)
    engine = InferenceEngine(model, tokenizer=tok, temperature=0.0)
    prompt = tok.encode("hello world", is_start=True, add_special_tokens=True)
    out, _, _ = engine.generate(prompt, len(prompt) + 24)
    assert len(set(out)) > 4, out


# -- kernel blocks -----------------------------------------------------------------


def test_pick_block_per_shard_vocab():
    """Llama-3 vocab over tp=4 / tp=8 (32064, 16032): no 128-multiple
    divides it. An output axis runs ragged at the preferred width, a
    contraction axis raises our error; nothing returns the whole axis."""
    from dllama_tpu.ops.quant_matmul import _pick_block

    for n in (32064, 16032):
        assert _pick_block(n, 256, ragged=True) == 256
        with pytest.raises(ValueError, match=str(n)):
            _pick_block(n, 256)
    assert _pick_block(151936, 256) == 128  # divisible: exact tiling stays
    assert _pick_block(14336, 4096) == 3584
    assert _pick_block(96, 256) == 96  # short axis: one whole block


@pytest.mark.parametrize("m,n", [(3, 320), (520, 128)], ids=["ragged-n", "tiled-m"])
def test_qmatmul_ragged_tail_matches_reference(m, n):
    """A ragged last column block (n=320 under 128-wide blocks) and row
    tiling past BLOCK_M give the reference's numbers: pad never leaks."""
    from dllama_tpu.ops.quant_matmul import QuantWeight, qmatmul_2d, qmatmul_ref

    k = 64
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.integers(-8, 8, (k, n)), jnp.int8)
    d = jnp.asarray(rng.uniform(0.25, 0.5, (k // 32, n)) / 8, jnp.float32)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    got = np.asarray(qmatmul_2d(x, q, d, block_n=128, interpret=True))
    want = np.asarray(qmatmul_ref(x, QuantWeight(q, d)))
    assert got.shape == (m, n) and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


# -- chip_smoke.py -------------------------------------------------------------------


def test_chip_smoke_token_check():
    """A served token that is neither the reference's top-1 nor tied with it
    inside the stated tolerance fails the run; a tie is counted, not hidden."""
    import chip_smoke

    ref = np.zeros((3, 8), np.float32)
    ref[0, 2], ref[1, 5], ref[2, 1] = 1.0, 1.0, 1.0
    ref[1, 6] = 0.99  # a near-tie with the top-1 at position 1
    assert chip_smoke.check_tokens([2, 5, 1], ref, tol=0.05) == (3, 0)
    assert chip_smoke.check_tokens([2, 6, 1], ref, tol=0.05) == (2, 1)
    with pytest.raises(AssertionError, match="served 7"):
        chip_smoke.check_tokens([2, 5, 7], ref, tol=0.05)
    with pytest.raises(AssertionError, match="served 6"):
        chip_smoke.check_tokens([2, 6, 1], ref, tol=0.001)


def test_chip_smoke_refuses_to_run_without_a_tpu(monkeypatch, capsys):
    """No accelerator: non-zero, and no result line."""
    import chip_smoke

    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert chip_smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out
