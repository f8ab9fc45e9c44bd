"""Tensor-parallel equivalence: sharded forward over a 2/4/8-device mesh must
reproduce the single-device logits (the TPU analogue of the reference's
multi-worker-vs-single-node validation; SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dllama_tpu.formats import FloatType, ModelReader
from dllama_tpu.formats.model_file import LlmArch
from dllama_tpu.models import forward, init_kv_cache, load_params
from dllama_tpu.parallel import (
    cache_specs,
    make_mesh,
    param_spec_tree,
    shard_params_put,
    validate_tp,
)

from helpers import make_tiny_model

TOKENS = [3, 17, 92, 5, 44, 120, 7, 3]


def single_device_logits(reader, tokens):
    params = load_params(reader)
    h = reader.header
    cache = init_kv_cache(h, batch_size=tokens.shape[0])
    logits, _ = forward(params, h, tokens, jnp.int32(0), cache)
    return np.asarray(logits)


def sharded_logits(reader, tokens, tp, dp=1):
    h = reader.header
    mesh = make_mesh(tp=tp, dp=dp)
    params = load_params(reader, put=shard_params_put(mesh, h))
    cache = init_kv_cache(h, batch_size=tokens.shape[0])
    cspecs = cache_specs(h)
    cache = {
        k: jax.device_put(v, NamedSharding(mesh, cspecs[k])) for k, v in cache.items()
    }
    tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))

    @jax.jit
    def run(params, tokens, pos, cache):
        return forward(params, h, tokens, pos, cache)

    logits, new_cache = run(params, tokens, jnp.int32(0), cache)
    return np.asarray(logits), new_cache, mesh


@pytest.mark.parametrize("tp", [2, 4, 8])
def test_tp_matches_single_device(tmp_path, tp):
    path = str(tmp_path / "m.m")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=16, n_kv_heads=8,
               head_dim=16, vocab_size=256, seq_len=32)
    make_tiny_model(path, weight_type=FloatType.F32, cfg=cfg)
    reader = ModelReader(path)
    validate_tp(reader.header, tp)
    tokens = jnp.asarray([TOKENS], dtype=jnp.int32)
    expected = single_device_logits(reader, tokens)
    got, _, _ = sharded_logits(reader, tokens, tp=tp)
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-4)


def test_tp_cache_is_sharded(tmp_path):
    """The updated KV cache must stay sharded on the kv-head axis (no silent
    full replication of the cache)."""
    path = str(tmp_path / "m.m")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=256, seq_len=32)
    make_tiny_model(path, weight_type=FloatType.F32, cfg=cfg)
    reader = ModelReader(path)
    tokens = jnp.asarray([TOKENS], dtype=jnp.int32)
    _, new_cache, mesh = sharded_logits(reader, tokens, tp=4)
    shard = new_cache["k"].sharding
    assert isinstance(shard, NamedSharding)
    # kv-head axis (index 2 of [L, B, KH, S, hd]) sharded over tp
    spec = tuple(shard.spec) + (None,) * (5 - len(tuple(shard.spec)))
    assert spec[2] == "tp", shard.spec


def test_tp_with_dp(tmp_path):
    """dp=2 x tp=4 over 8 devices: batch of two identical sequences."""
    path = str(tmp_path / "m.m")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=256, seq_len=32)
    make_tiny_model(path, weight_type=FloatType.F32, cfg=cfg)
    reader = ModelReader(path)
    tokens = jnp.asarray([TOKENS, TOKENS], dtype=jnp.int32)
    expected = single_device_logits(reader, tokens)
    got, _, _ = sharded_logits(reader, tokens, tp=4, dp=2)
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch", [LlmArch.QWEN3, LlmArch.QWEN3_MOE])
def test_tp_qwen3_variants(tmp_path, arch):
    path = str(tmp_path / "m.m")
    make_tiny_model(path, arch=arch, weight_type=FloatType.F32)
    reader = ModelReader(path)
    tokens = jnp.asarray([TOKENS], dtype=jnp.int32)
    expected = single_device_logits(reader, tokens)
    got, _, _ = sharded_logits(reader, tokens, tp=2)
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("tp,sp", [(1, 2), (2, 2), (1, 4), (2, 4)])
def test_engine_sp_matches_single_device(tmp_path, tp, sp):
    """Engine-level sequence parallelism: greedy tokens with the KV cache
    sequence-sharded over sp (x kv-heads over tp) must equal the tp=1/sp=1
    run — prefill goes through the ring path, decode through the
    merged-stats path."""
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "m.m")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=256, seq_len=64)
    make_tiny_model(path, weight_type=FloatType.F32, cfg=cfg)
    e1 = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0)
    expected, _, _ = e1.generate([1, 2, 3, 4, 5, 6, 7, 8, 9], max_steps=24)
    esp = InferenceEngine(path, tp=tp, sp=sp, dtype=jnp.float32,
                          temperature=0.0)
    # the cache really is sequence-sharded
    from jax.sharding import PartitionSpec as P

    assert esp.cache["k"].sharding.spec == P(None, "dp", "tp", "sp", None)
    got, _, _ = esp.generate([1, 2, 3, 4, 5, 6, 7, 8, 9], max_steps=24)
    assert got == expected, f"tp={tp} sp={sp}: {got} != {expected}"


def test_engine_sp_with_quantized_weights(tmp_path):
    """sp=2 over Q40-format weights: the sequence-sharded cache and the
    quantized matmul fallback (GSPMD off-TPU) must compose."""
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "m.m")
    # dims divisible by 32*tp (the quantized col-split shards the scale
    # tensors' block axis)
    cfg = dict(dim=128, hidden_dim=256, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=256, seq_len=64)
    make_tiny_model(path, weight_type=FloatType.Q40, cfg=cfg)
    e1 = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0,
                         weight_format="q40")
    expected, _, _ = e1.generate([1, 2, 3, 4, 5], max_steps=16)
    esp = InferenceEngine(path, tp=2, sp=2, dtype=jnp.float32,
                          temperature=0.0, weight_format="q40")
    got, _, _ = esp.generate([1, 2, 3, 4, 5], max_steps=16)
    assert got == expected


def test_engine_sp_rejects_bad_seq_len(tmp_path):
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "m.m")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=256, seq_len=60)
    make_tiny_model(path, weight_type=FloatType.F32, cfg=cfg)
    with pytest.raises(ValueError, match="divisible by sp"):
        InferenceEngine(path, sp=8, dtype=jnp.float32)


def test_validate_tp_rejects_bad_configs(tmp_path):
    path = str(tmp_path / "m.m")
    make_tiny_model(path)  # n_kv_heads=2
    h = ModelReader(path).header
    with pytest.raises(ValueError, match="power of two"):
        validate_tp(h, 3)
    with pytest.raises(ValueError, match="nKvHeads"):
        validate_tp(h, 4)
    validate_tp(h, 2)  # ok


def test_weight_shards_actually_split(tmp_path):
    """Row-split weights must be distributed, not replicated: each device
    holds 1/tp of wq (the TPU twin of splitRowMatmulWeight)."""
    path = str(tmp_path / "m.m")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=256, seq_len=32)
    make_tiny_model(path, weight_type=FloatType.F32, cfg=cfg)
    reader = ModelReader(path)
    mesh = make_mesh(tp=4)
    params = load_params(reader, put=shard_params_put(mesh, reader.header))
    wq = params["layers"]["wq"]
    shard_shapes = {s.data.shape for s in wq.addressable_shards}
    assert shard_shapes == {(2, 64, 128 // 4)}


def test_psum_q80_error_bound():
    """Q80-compressed all-reduce (the reference's --buffer-float-type q80,
    src/llm.cpp:195) vs the exact f32 psum on a tp=4 mesh: per-32-block
    int8 quantization bounds the relative error."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from dllama_tpu.parallel.collectives import (
        dequantize_q80_blocks,
        psum_q80,
        quantize_q80_blocks,
    )

    rng = np.random.default_rng(31)
    x = jnp.asarray(rng.standard_normal((4, 1, 256)).astype(np.float32))

    # roundtrip: block-local error <= scale/2 = amax/254
    q, s = quantize_q80_blocks(x)
    rt = dequantize_q80_blocks(q, s)
    blocks = np.asarray(x).reshape(4, 1, 8, 32)
    amax = np.abs(blocks).max(axis=-1)
    assert (
        np.abs(np.asarray(rt).reshape(4, 1, 8, 32) - blocks)
        <= amax[..., None] / 254 + 1e-7
    ).all()
    # all-zero blocks stay exactly zero
    z_q, z_s = quantize_q80_blocks(jnp.zeros((1, 64)))
    assert np.asarray(dequantize_q80_blocks(z_q, z_s)).max() == 0.0

    mesh = make_mesh(tp=4)
    exact = shard_map(
        lambda a: jax.lax.psum(a, "tp"), mesh=mesh,
        in_specs=P("tp"), out_specs=P("tp"), check_vma=False,
    )(x)
    compressed = shard_map(
        lambda a: psum_q80(a, "tp"), mesh=mesh,
        in_specs=P("tp"), out_specs=P("tp"), check_vma=False,
    )(x)
    err = np.abs(np.asarray(compressed) - np.asarray(exact)).max()
    scale = np.abs(np.asarray(exact)).max()
    assert err / scale < 2e-2, (err, scale)


def test_qmatmul_tp_col_q80_sync(monkeypatch):
    """The qmatmul_tp 'col' shard_map branch with sync_quant=True must run
    psum_q80 over the per-shard partial sums and land within quantization
    tolerance of the exact psum. Off-TPU the dispatcher would bypass the
    shard_map path entirely, so force it and stub the Pallas kernel entry
    with the reference matmul — the wiring under test is the collective,
    not the kernel."""
    from dllama_tpu.ops import quant_matmul as qm
    from dllama_tpu.formats.quants import q40_to_planar, quantize_q40

    monkeypatch.setattr(qm, "_use_pallas", lambda: True)
    monkeypatch.setattr(
        qm, "qmatmul", lambda x, w, block_n=256: qm.qmatmul_ref(x, w)
    )

    rng = np.random.default_rng(33)
    k_dim, n_dim = 128, 64
    w = rng.standard_normal((n_dim, k_dim)).astype(np.float32) * 0.1
    qv, dv = q40_to_planar(quantize_q40(w), n_dim * k_dim)
    qw = qm.from_planar(qv.reshape(n_dim, k_dim), dv.reshape(n_dim, k_dim // 32))
    x = jnp.asarray(rng.standard_normal((1, 1, k_dim)).astype(np.float32))

    mesh = make_mesh(tp=2)
    exact = qm.qmatmul_tp(x, qw, "col", mesh, sync_quant=False)
    q80 = qm.qmatmul_tp(x, qw, "col", mesh, sync_quant=True)
    scale = float(np.abs(np.asarray(exact)).max())
    err = float(np.abs(np.asarray(q80) - np.asarray(exact)).max())
    assert err / scale < 2e-2, (err, scale)
    assert err > 0.0  # the compressed collective actually ran


def test_lanes_with_sp_mesh(tmp_path):
    """Continuous batching composed with sequence parallelism: per-lane prefill + per-lane decode on a tp=2 x sp=2 mesh
    must reproduce each prompt's single-stream tokens."""
    path = str(tmp_path / "m.m")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=256, seq_len=64)
    make_tiny_model(path, weight_type=FloatType.F32, cfg=cfg)
    from dllama_tpu.runtime.engine import InferenceEngine

    prompts = [[1, 2, 3, 4], [9, 8, 7, 6, 5, 4]]  # different lengths
    singles = []
    e1 = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0)
    for p in prompts:
        e1.reset()
        out, _, _ = e1.generate(p, max_steps=16)
        singles.append(out)
    del e1

    esp = InferenceEngine(
        path, tp=2, sp=2, dtype=jnp.float32, temperature=0.0, batch_size=2
    )
    outs = esp.generate_batch(prompts, max_steps=16)
    assert outs == singles, (outs, singles)


def test_qmatmul_tp_row_fused_shard_map(monkeypatch):
    """The 'row' shard_map branch over a FUSED shard-major-interleaved
    weight: each tp shard must receive its own q|k|v slice and the
    un-interleave must restore the split results. Off-TPU the dispatcher
    bypasses shard_map, so force it (Pallas entry stubbed with the
    reference matmul — the wiring under test is the partitioning)."""
    from dllama_tpu.ops import quant_matmul as qm
    from dllama_tpu.formats.quants import q40_to_planar, quantize_q40
    from dllama_tpu.models.loader import _interleave_concat
    from dllama_tpu.models.transformer import _split_fused

    monkeypatch.setattr(qm, "_use_pallas", lambda: True)
    monkeypatch.setattr(
        qm, "qmatmul", lambda x, w, block_n=256: qm.qmatmul_ref(x, w)
    )

    rng = np.random.default_rng(44)
    tp, k_dim = 2, 128
    dims = (64, 32, 32)

    def qw_for(n_dim, seed):
        r = np.random.default_rng(seed)
        w = r.standard_normal((n_dim, k_dim)).astype(np.float32) * 0.1
        qv, dv = q40_to_planar(quantize_q40(w), n_dim * k_dim)
        return qm.from_planar(
            qv.reshape(n_dim, k_dim), dv.reshape(n_dim, k_dim // 32)
        )

    qws = [qw_for(d, 50 + i) for i, d in enumerate(dims)]
    fused = qm.QuantWeight(
        jnp.asarray(_interleave_concat([np.asarray(w.q) for w in qws], tp)),
        jnp.asarray(_interleave_concat([np.asarray(w.d) for w in qws], tp)),
    )
    x = jnp.asarray(rng.standard_normal((1, 1, k_dim)).astype(np.float32))
    mesh = make_mesh(tp=tp)

    out = qm.qmatmul_tp(x, fused, "row", mesh)
    parts = _split_fused(out, tp, dims)
    for part, w in zip(parts, qws):
        expect = qm.qmatmul_tp(x, w, "row", mesh)
        np.testing.assert_allclose(
            np.asarray(part), np.asarray(expect), rtol=1e-5, atol=1e-5
        )


@pytest.mark.parametrize("arch", [LlmArch.LLAMA, LlmArch.QWEN3_MOE])
def test_tp2_ids_equal_tp1_through_stacked_shard_map(tmp_path, monkeypatch, arch):
    """The layer scan closes over the quantized [L, in, out] stacks and
    hands `qmatmul_tp` the stack and a layer number: under `shard_map` the
    stacks' specs gain a leading None and the layer is replicated. Off-TPU
    the dispatcher bypasses shard_map, so force it (Pallas entry stubbed
    with the reference matmul, which takes the same `(stack, layer)`);
    greedy ids at tp=2 must equal tp=1's."""
    from dllama_tpu.ops import quant_matmul as qm
    from dllama_tpu.runtime.engine import InferenceEngine

    seen = []

    def stub(x, w, layer=None, block_n=256):
        seen.append((w[0].ndim, layer is not None))
        return qm.qmatmul_ref(x, w, layer)

    monkeypatch.setattr(qm, "_use_pallas", lambda: True)
    monkeypatch.setattr(qm, "qmatmul", stub)

    path = str(tmp_path / "m.m")
    cfg = dict(dim=128, hidden_dim=256, n_layers=3, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=256, seq_len=64)
    if arch == LlmArch.QWEN3_MOE:
        cfg.update(n_experts=4, n_active_experts=2, moe_hidden_dim=64)
    make_tiny_model(path, arch=arch, weight_type=FloatType.Q40, cfg=cfg)
    prompt = [1, 2, 3, 4, 5, 6, 7]
    e1 = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0,
                         weight_format="q40")
    expected, _, _ = e1.generate(prompt, max_steps=16)
    del e1
    seen.clear()
    e2 = InferenceEngine(path, tp=2, dtype=jnp.float32, temperature=0.0,
                         weight_format="q40")
    got, _, _ = e2.generate(prompt, max_steps=16)
    assert got == expected
    # inside the shard_map: layer weights as 3-D stacks with their layer
    # number, the vocabulary head as the 2-D weight it is
    assert (3, True) in seen and (2, False) in seen
    assert all(stacked == (ndim == 3) for ndim, stacked in seen)


@pytest.mark.parametrize("arch", [LlmArch.LLAMA, LlmArch.QWEN3_MOE])
def test_tp2_ids_equal_tp1_through_stacked_cache_shard_map(
    tmp_path, monkeypatch, arch
):
    """Prefill attention takes the whole `[L, B, KH, S, hd]` cache, which
    the layer scan carries, and the layer number: under `shard_map` the
    stack's spec is `P(None, "dp", "tp", None, None)` and the layer is
    replicated. Off-TPU the dispatcher takes the dense path, so force the
    kernel's branch (the kernel stubbed with the reference attention over
    the same `(stack, layer, rows)`); greedy ids at tp=2 must equal
    tp=1's."""
    from dllama_tpu.models import transformer as tf
    from dllama_tpu.ops.flash_attention import attention_ref
    from dllama_tpu.ops.kv_cache import layer_rows
    from dllama_tpu.runtime.engine import InferenceEngine

    seen = []

    def stub(q, k, v, pos, layer=None, rows=0):
        seen.append((k.shape, layer is not None, rows))
        return attention_ref(
            q, layer_rows(k, layer, rows), layer_rows(v, layer, rows), pos
        )

    path = str(tmp_path / "m.m")
    cfg = dict(dim=128, hidden_dim=256, n_layers=3, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=256, seq_len=64)
    if arch == LlmArch.QWEN3_MOE:
        cfg.update(n_experts=4, n_active_experts=2, moe_hidden_dim=64)
    make_tiny_model(path, arch=arch, weight_type=FloatType.Q40, cfg=cfg)
    prompt = list(range(1, 20))
    e1 = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0,
                         weight_format="q40")
    expected, _, _ = e1.generate(prompt, max_steps=28)
    del e1
    monkeypatch.setattr(tf, "_use_flash", lambda t, rows: True)
    monkeypatch.setattr(tf, "flash_attention", stub)
    e2 = InferenceEngine(path, tp=2, dtype=jnp.float32, temperature=0.0,
                         weight_format="q40")
    got, _, _ = e2.generate(prompt, max_steps=28)
    assert got == expected
    # inside the shard_map: every layer of the cache, this shard's half of
    # the 4 kv heads, and a layer number with it
    assert seen and all(
        shape[0] == 3 and shape[2] == 2 and layered
        for shape, layered, _ in seen
    ), seen


def test_engine_sp_windowed_decode_parity(tmp_path):
    """sp=2 with a seq_len large enough that decode windows engage
    (window = 512*sp < seq_len): the cyclic cache layout must keep exact
    token parity with the single-device engine across the window."""
    import sys

    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))
    from helpers import make_tiny_model
    from dllama_tpu.formats import FloatType
    from dllama_tpu.runtime.engine import InferenceEngine

    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=4, n_kv_heads=2,
               head_dim=16, vocab_size=256, seq_len=2048)
    mp = str(tmp_path / "mw.m")
    make_tiny_model(mp, weight_type=FloatType.Q40, seed=21, cfg=cfg)
    prompt = [(i * 7) % 250 + 1 for i in range(9)]
    e1 = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    assert e1._attn_window(10) == 512
    expected, _, _ = e1.generate(prompt, max_steps=24)
    del e1
    esp = InferenceEngine(mp, tp=1, sp=2, dtype=jnp.float32, temperature=0.0)
    # the sp window is a 512-row local prefix per shard, not the full cache
    assert esp._attn_window(10) == 1024 < cfg["seq_len"]
    got, _, _ = esp.generate(prompt, max_steps=24)
    del esp
    assert got == expected, (got, expected)


def test_sp_window_cuts_decode_bytes(tmp_path):
    """Per-step sp decode reads must be proportional
    to the window, not seq_len — compiled bytes-accessed of a windowed
    sp decode step is well below the unwindowed one."""
    import sys

    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))
    from helpers import make_tiny_model
    from dllama_tpu.formats import FloatType
    from dllama_tpu.models import forward, init_kv_cache, load_params
    from dllama_tpu.formats import ModelReader

    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=4, n_kv_heads=2,
               head_dim=16, vocab_size=256, seq_len=4096)
    mp = str(tmp_path / "mb.m")
    make_tiny_model(mp, weight_type=FloatType.Q40, seed=21, cfg=cfg)
    r = ModelReader(mp)
    h = r.header
    params = load_params(r, weight_format="dense")
    mesh = make_mesh(sp=2)
    tok = jnp.asarray([[7]], jnp.int32)

    def compiled_bytes(window):
        cache = init_kv_cache(h, 1)

        def step(p, t, c):
            return forward(
                p, h, t, jnp.int32(600), c, mesh=mesh, attn_window=window
            )

        cost = (
            jax.jit(step, donate_argnums=(2,))  # engine donates the cache
            .lower(params, tok, cache)
            .compile()
            .cost_analysis()
        )
        if isinstance(cost, list):
            cost = cost[0]
        return cost.get("bytes accessed", 0.0)

    b_1k = compiled_bytes(1024)
    b_2k = compiled_bytes(2048)
    b_full = compiled_bytes(0)
    # the cache-read term must scale with the window: each 1024 rows of
    # window are L x KH x 1024 x hd x 4B x {k,v} = 0.52 MB of reads
    row_bytes = 2 * 2 * 16 * 4 * 2  # L * KH * hd * itemsize * (k+v)
    step = 1024 * row_bytes
    assert b_2k - b_1k > 0.8 * step, (b_1k, b_2k)
    assert b_full - b_2k > 0.8 * 2 * step, (b_2k, b_full)  # full = 4096


def test_vocab_sharded_embed_no_table_gather(tmp_path):
    """The embed table is vocab-sharded (sharding.py: P(\"tp\", None)) so a
    tp>1 flat-path forward must NOT lower an all-gather that reassembles
    the [vocab, dim] table on every chip — the lookup masks locally and
    psums the [B, T, D] activation (the reference holds the table on the
    root node only, SYNC_WITH_ROOT, src/llm.cpp:256). The logits
    all-gather over [B, T, vocab] is expected and allowed.

    Thin wrapper over the xlalint collective-census parser
    (analysis/rules_hlo.py) — the regather check that used to live here
    as a one-off regex now guards EVERY compiled program the engine
    builds; this test keeps the targeted flat-forward coverage."""
    from dllama_tpu.analysis.rules_hlo import forbidden_gather_findings

    path = str(tmp_path / "m.m")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=256, seq_len=32)
    make_tiny_model(path, weight_type=FloatType.F32, cfg=cfg)
    reader = ModelReader(path)
    h = reader.header
    mesh = make_mesh(tp=2)
    params = load_params(reader, put=shard_params_put(mesh, h))
    cache = init_kv_cache(h, batch_size=1)
    cspecs = cache_specs(h)
    cache = {
        k: jax.device_put(v, NamedSharding(mesh, cspecs[k]))
        for k, v in cache.items()
    }
    tokens = jnp.asarray([TOKENS], dtype=jnp.int32)

    def step(p, t, c):
        return forward(p, h, t, jnp.int32(0), c)

    txt = jax.jit(step).lower(params, tokens, cache).compile().as_text()
    table_dims = {(cfg["vocab_size"], cfg["dim"]),
                  (cfg["dim"], cfg["vocab_size"])}
    # trailing-two check also rejects batched [.., vocab, dim] variants
    hits = forbidden_gather_findings(txt, table_dims)
    assert not hits, (
        f"all-gather reassembles the full embed/wcls table: {hits}"
    )
    # the per-partition HLO carries the V/tp-row shard; the full table
    # shape must not materialize in ANY op (gather, copy, or otherwise) —
    # replicating `embed` instead makes f32[256,64] appear immediately
    v, dim = cfg["vocab_size"], cfg["dim"]
    assert f"f32[{v // 2},{dim}]" in txt
    assert f"f32[{v},{dim}]" not in txt


def _scatter_operand_dims(hlo_text):
    """Dims of every scatter op's result in an HLO dump (thin wrapper
    over the shared xlalint parser, keeping this module's historical
    helper name)."""
    from dllama_tpu.analysis.rules_hlo import scatter_result_dims

    return [list(d) for d in scatter_result_dims(hlo_text)]


def test_cyclic_write_lowering_isolated():
    """_cache_append_cyclic's T>1 scatter (transformer.py, the flat-GSPMD
    sp write) must partition into a SHARD-LOCAL scatter:
    zero collectives, operand rows = S/sp not S. Mirrors the closure's
    exact index math (perm(g) = (g%sp)*shard_rows + g//sp)."""
    SP, B, KH, S, HD, T = 4, 1, 2, 4096, 64, 16
    shard_rows = S // SP
    mesh = make_mesh(sp=SP)
    shard = NamedSharding(mesh, P(None, None, "sp", None))

    def perm(g):
        return (g % SP) * shard_rows + g // SP

    rows = jnp.arange(T, dtype=jnp.int32)

    def write(cache, val, pos):
        return cache.at[:, :, perm(pos + rows)].set(val)

    def write_per_lane(cache, val, pos):
        return jax.vmap(lambda c, u, p: c.at[:, perm(p + rows)].set(u))(
            cache, val, pos
        )

    cache = jax.device_put(jnp.zeros((B, KH, S, HD), jnp.float32), shard)
    val = jnp.ones((B, KH, T, HD), jnp.float32)
    for fn, pos in (
        (write, jnp.int32(600)),
        (write_per_lane, jnp.full((B,), 600, jnp.int32)),
    ):
        txt = (
            jax.jit(fn, donate_argnums=(0,), out_shardings=shard)
            .lower(cache, val, pos)
            .compile()
            .as_text()
        )
        # shard-local means ZERO collectives of any kind (census parser
        # shared with xlalint, analysis/rules_hlo.py)
        from dllama_tpu.analysis.rules_hlo import collective_census

        assert collective_census(txt) == {}, (
            fn.__name__, collective_census(txt)
        )
        dims = _scatter_operand_dims(txt)
        assert dims, f"{fn.__name__}: expected a scatter lowering"
        for d in dims:
            assert S not in d, (fn.__name__, d)  # not a full-S scatter
            assert shard_rows in d, (fn.__name__, d)


def test_cyclic_write_lowering_in_forward(tmp_path):
    """Same pin on the REAL forward: a T>1 prefill chunk on an sp mesh
    compiles with no all-to-all and only shard-local scatters (every
    scatter operand carries the S/sp local row count, never full S)."""
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=4, n_kv_heads=2,
               head_dim=16, vocab_size=256, seq_len=4096)
    mp = str(tmp_path / "cyc.m")
    make_tiny_model(mp, weight_type=FloatType.Q40, seed=23, cfg=cfg)
    r = ModelReader(mp)
    h = r.header
    params = load_params(r, weight_format="dense")
    mesh = make_mesh(sp=2)
    cache = init_kv_cache(h, 1)
    tok = jnp.ones((1, 16), jnp.int32)

    def step(p, t, c):
        return forward(p, h, t, jnp.int32(600), c, mesh=mesh)

    txt = (
        jax.jit(step, donate_argnums=(2,))
        .lower(params, tok, cache)
        .compile()
        .as_text()
    )
    from dllama_tpu.analysis.rules_hlo import collective_census

    assert "all-to-all" not in collective_census(txt)
    dims = _scatter_operand_dims(txt)
    assert dims, "expected the cyclic cache write to lower to a scatter"
    for d in dims:
        assert cfg["seq_len"] not in d, d
        assert cfg["seq_len"] // 2 in d, d


def test_measure_sync_ms_collectives():
    """measure_sync_ms (the reference's per-step sync clock restated for
    XLA, nn-executor.cpp:158-163): a psum-heavy program on the 8-device
    mesh reports nonzero collective time; a collective-free program
    reports ~0."""
    from jax import shard_map
    from dllama_tpu.utils.telemetry import measure_sync_ms

    mesh = make_mesh(tp=8)
    x = jnp.ones((8, 1024), jnp.float32)

    def with_psum():
        f = shard_map(
            lambda v: jax.lax.psum(v @ v.T, "tp"),
            mesh=mesh,
            in_specs=P("tp", None),
            out_specs=P(None, None),
            check_vma=False,
        )
        out = jax.jit(f)(x)
        np.asarray(out)

    def without():
        out = jax.jit(lambda v: v * 2.0)(x)
        np.asarray(out)

    ms_with = measure_sync_ms(with_psum, steps=2)
    ms_without = measure_sync_ms(without, steps=2)
    if ms_with is None:
        import pytest as _pytest

        _pytest.skip("profiler trace unavailable on this backend")
    assert ms_with > 0.0
    assert (ms_without or 0.0) <= ms_with


@pytest.mark.parametrize("aot", [True, False], ids=["aot", "lazy_jit"])
def test_lane_tokens_reach_a_two_device_program_with_their_sharding(
        tmp_path, monkeypatch, aot):
    """On a mesh of two devices a lane dispatch's token array reaches its
    program placed by `_token_sharding`, as the program's arg spec states
    it, whether the program was compiled ahead of time against that spec
    or is lazily jitted against the array; the rest are host arrays (and,
    last of a block's, the block before's last tokens, which stay on the
    devices under the same sharding), and the stream is the one-device
    stream."""
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "m.m")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=256, seq_len=64)
    make_tiny_model(path, weight_type=FloatType.F32, cfg=cfg)
    if not aot:
        monkeypatch.setenv("DLLAMA_WINDOW_PRECOMPILE", "0")
    prompts = [[1, 2, 3, 4], [9, 8, 7, 6, 5, 4]]

    def stream(e):
        for lane, p in enumerate(prompts):
            assert e.prefill_lane_chunk(lane, p[:-1], 0) == len(p) - 1
        return e.decode_lanes(
            [p[-1] for p in prompts], [len(p) - 1 for p in prompts], 6)

    kw = dict(tp=1, dtype=jnp.float32, temperature=0.0, batch_size=2,
              prefill_buckets=(8,))
    want = stream(InferenceEngine(path, **kw))
    e = InferenceEngine(path, dp=2, **kw)
    assert e._aot_blocks is aot
    assert len(e._token_sharding.device_set) == 2
    stream(e)  # builds the programs
    e.reset()
    seen = []
    for key, fn in list(e._compiled.items()):
        monkeypatch.setitem(e._compiled, key, lambda *a, _k=key, _f=fn: (
            seen.append((_k[0], a[1], a[3:])), _f(*a))[1])
    assert stream(e) == want
    assert [kind for kind, _, _ in seen] == ["lane_prefill"] * 2 + ["lane_block"]
    for kind, tokens, rest in seen:
        assert isinstance(tokens, jax.Array) and tokens.committed
        assert tokens.sharding == e._token_sharding
        assert {d.id for d in tokens.devices()} == {d.id for d in e.mesh.devices.flat}
        if kind == "lane_block":
            *rest, last = rest
            # (as the block before placed it: the program constrains it)
            assert isinstance(last, jax.Array) and last.committed
            assert last.sharding.is_equivalent_to(e._token_sharding, last.ndim)
        assert all(isinstance(a, np.ndarray) for a in rest)
