"""Model forward-pass tests: JAX model vs independent numpy oracle, plus
prefill/decode consistency invariants."""

from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.formats import FloatType, ModelReader
from dllama_tpu.formats.model_file import LlmArch
from dllama_tpu.models import forward, init_kv_cache, load_params

from helpers import make_tiny_model
from numpy_model import numpy_forward

TOKENS = [3, 17, 92, 5, 44, 120, 7, 3]


def build(tmp_path, arch=LlmArch.LLAMA, weight_type=FloatType.F32, **kw):
    path = str(tmp_path / "m.m")
    tensors = make_tiny_model(path, arch=arch, weight_type=weight_type, **kw)
    reader = ModelReader(path)
    params = load_params(reader)
    return reader.header, params, tensors


@pytest.mark.parametrize(
    "arch", [LlmArch.LLAMA, LlmArch.QWEN3, LlmArch.QWEN3_MOE]
)
def test_forward_matches_numpy_oracle(tmp_path, arch):
    h, params, tensors = build(tmp_path, arch=arch)
    tokens = jnp.asarray([TOKENS], dtype=jnp.int32)
    cache = init_kv_cache(h, batch_size=1)
    logits, _ = forward(params, h, tokens, jnp.int32(0), cache)
    expected = numpy_forward(tensors, h, TOKENS)
    np.testing.assert_allclose(
        np.asarray(logits)[0], expected, rtol=2e-4, atol=2e-4
    )


def test_moe_lanes_with_one_parked_match_numpy_oracle(tmp_path):
    """Qwen3-MoE, four lanes at unequal positions decode a step together,
    lane 2 parked: its rows are routed nowhere (`run_layers`' `live_rows`),
    and every live lane's logits are still the oracle's for its sequence,
    which is what the forward pass gave when parked rows were routed too."""
    import jax

    h, params, tensors = build(tmp_path, arch=LlmArch.QWEN3_MOE)
    lanes, park, chunk = 4, h.seq_len, 8
    rng = np.random.default_rng(7)
    lengths = [5, 17, 3, 30]
    seqs = [[int(t) for t in rng.integers(0, h.vocab_size, n + 1)] for n in lengths]
    cache = init_kv_cache(h, lanes, seq_len=h.seq_len + chunk)
    step = jax.jit(lambda toks, pos, cache: forward(
        params, h, toks, pos, cache, attn_park_threshold=park))
    # fill lane by lane, as the engine's lane prefill does: the others parked
    for lane, ids in enumerate(seqs):
        for p in range(0, lengths[lane], chunk):
            width = min(chunk, lengths[lane] - p)
            toks = np.zeros((lanes, width), np.int32)
            toks[lane] = ids[p:p + width]
            pos = np.full(lanes, park, np.int32)
            pos[lane] = p
            _, cache = step(jnp.asarray(toks), jnp.asarray(pos), cache)
    pos = np.asarray(lengths, np.int32)
    pos[2] = park
    toks = np.asarray([[ids[-1]] for ids in seqs], np.int32)
    logits, _ = step(jnp.asarray(toks), jnp.asarray(pos), cache)
    for lane, ids in enumerate(seqs):
        if lane == 2:
            continue
        want = numpy_forward(tensors, h, ids)[-1]
        assert np.abs(np.asarray(logits[lane, 0]) - want).max() < 2e-4 * want.std(), lane


def _sparse_family(tmp_path, family: str):
    """(header, params, forward's cache keywords, cache keywords, a
    sequence's logits by the family's oracle) of a tiny sparse model: the
    top-k softmax router with every expert held, `afmoe`'s held share with a
    shared expert over two cache stacks, the latent family's sparse layers."""
    seq, chunk = 256, 16
    if family == "qwen3_moe":
        h, params, tensors = build(tmp_path, arch=LlmArch.QWEN3_MOE)
        return h, params, {}, {"seq_len": h.seq_len + chunk}, (
            lambda ids: numpy_forward(tensors, h, ids))
    import sys

    import helpers

    if helpers.REPO_ROOT not in sys.path:
        sys.path.insert(0, helpers.REPO_ROOT)
    if family == "afmoe":
        from benchmark.references import afmoe as ref

        cfg = helpers.tiny_afmoe_config()
        ring = helpers.AFMOE_WINDOW + chunk
        fwd_kw = {"kv_ring": ring}
        cache_kw = {"seq_len": seq + chunk, "ring": ring, "ring_pad": chunk}
    else:
        from benchmark.references import pangu_ultra_moe as ref

        cfg = helpers.tiny_pangu_config()
        fwd_kw, cache_kw = {}, {"seq_len": seq + chunk}
    path = str(tmp_path / "m.m")
    helpers._write_tiny(path, cfg, 3)
    reader = ModelReader(path, max_seq_len=seq)
    params = load_params(reader, dtype=jnp.float32)
    return reader.header, params, fwd_kw, cache_kw, (
        lambda ids: np.asarray(ref.last_logits(path, cfg, [ids], [len(ids)])[0]))


@pytest.mark.parametrize("live", [0, 2, 3], ids=["first", "middle", "last"])
@pytest.mark.parametrize("family", ["qwen3_moe", "afmoe", "pangu_ultra_moe"])
def test_chunk_of_one_live_lane_computes_its_experts_alone(tmp_path, family, live):
    """A chunk program's rows hold one admitted lane of four, the others
    parked (`live_lanes_alone`): the expert block runs over that lane's rows
    alone. Two chunks at the lane, first, in the middle or last: every row's
    logits and the cache rows written are what the program over every lane's
    rows gives (the uncompacted formula), the other lanes' rows stay as they
    were, and the logits are the oracle's for the sequence."""
    import jax

    h, params, fwd_kw, cache_kw, oracle = _sparse_family(tmp_path, family)
    lanes, park, chunk = 4, h.seq_len, 16
    ids = [int(t) for t in np.random.default_rng(11 + live).integers(
        0, min(500, h.vocab_size), 2 * chunk)]

    def run(live_lanes_alone: bool):
        cache = init_kv_cache(h, lanes, jnp.float32, **cache_kw)
        rng = np.random.default_rng(5)
        cache = {k: jnp.asarray(rng.standard_normal(v.shape), v.dtype)
                 for k, v in cache.items()}  # rows a stray write would change
        before = {k: np.asarray(v) for k, v in cache.items()}
        step = jax.jit(lambda toks, pos, cache: forward(
            params, h, toks, pos, cache, attn_park_threshold=park,
            live_lanes_alone=live_lanes_alone, **fwd_kw))
        out = []
        for p in (0, chunk):
            toks = np.zeros((lanes, chunk), np.int32)
            toks[live] = ids[p:p + chunk]
            pos = np.full(lanes, park, np.int32)
            pos[live] = p
            logits, cache = step(jnp.asarray(toks), jnp.asarray(pos), cache)
            out.append(np.asarray(logits[live]))
        return np.concatenate(out), before, {k: np.asarray(v) for k, v in cache.items()}

    got, before, cache = run(True)
    plain, _, plain_cache = run(False)
    want = oracle(ids)
    assert np.abs(got - plain).max() < 1e-5 * want.std()
    assert np.abs(got - want).max() < 2e-4 * want.std()
    for name, rows in cache.items():
        # the live lane's rows as the uncompacted program wrote them; nobody
        # else's rows inside the context moved
        assert np.abs(rows[:, live] - plain_cache[name][:, live]).max() < 1e-5, name
        assert not np.array_equal(rows[:, live], before[name][:, live]), name
        # context rows only: a parked lane writes past them (a ring: its spare rows)
        kept = slice(chunk, rows.shape[3] - chunk) if name in ("kw", "vw") else slice(0, park)
        for lane in set(range(lanes)) - {live}:
            assert np.array_equal(rows[:, lane, :, kept], before[name][:, lane, :, kept]), (
                name, lane)


@pytest.mark.parametrize("live", [(0, 2), (1, 2, 3)], ids=["two", "three"])
@pytest.mark.parametrize("family", ["qwen3_moe", "afmoe", "pangu_ultra_moe"])
def test_chunk_of_several_live_lanes_visits_each_as_its_own_program_does(
        tmp_path, family, live):
    """A chunk program's rows hold two or three admitting lanes of four, each
    at a position of its own (for `afmoe` one of them across its ring's end):
    the expert block visits them one after another. Every live lane's logits
    and cache rows are, bit for bit, those of the same rows run one live lane
    a program; the parked lanes' rows stay as they were; and the routing
    counters and the form counter are the sums over those programs."""
    import jax

    h, params, fwd_kw, cache_kw, _ = _sparse_family(tmp_path, family)
    lanes, park, chunk = 4, h.seq_len, 16
    ring = fwd_kw.get("kv_ring", 0)
    at = dict(zip(live, (ring - 6 if ring else 40, 0, 3 * chunk)))
    ids = np.random.default_rng(23).integers(
        0, min(500, h.vocab_size), (lanes, chunk)).astype(np.int32)
    cache0 = init_kv_cache(h, lanes, jnp.float32, **cache_kw)
    rng = np.random.default_rng(5)
    cache0 = {k: np.asarray(rng.standard_normal(v.shape), v.dtype) for k, v in cache0.items()}

    def program(counter):
        def fn(toks, pos, cache):
            counted = []
            logits, cache = forward(
                params, h, toks, pos, cache, attn_park_threshold=park,
                live_lanes_alone=True, **{counter: counted}, **fwd_kw)
            return logits, cache, counted[0]
        return jax.jit(fn)

    def run(step, groups):
        cache = {k: jnp.asarray(v) for k, v in cache0.items()}
        logits, counts = {}, 0
        for group in groups:
            toks = np.zeros((lanes, chunk), np.int32)
            pos = np.full(lanes, park, np.int32)
            for lane in group:
                toks[lane], pos[lane] = ids[lane], at[lane]
            out, cache, c = step(jnp.asarray(toks), jnp.asarray(pos), cache)
            counts = counts + np.asarray(c)
            logits.update({lane: np.asarray(out[lane]) for lane in group})
        return logits, {k: np.asarray(v) for k, v in cache.items()}, counts

    for counter in ("route_stats", "expert_forms"):
        step = program(counter)
        got, cache, counts = run(step, [live])
        want, apart, summed = run(step, [(lane,) for lane in live])
        assert np.array_equal(counts, summed) and counts.any(), (counter, counts, summed)
        for lane in live:
            assert np.array_equal(got[lane], want[lane]), (counter, lane)
        for name, rows in cache.items():
            kept = slice(chunk, rows.shape[3] - chunk) if name in ("kw", "vw") else slice(0, park)
            for lane in range(lanes):
                assert np.array_equal(rows[:, lane, :, kept], apart[name][:, lane, :, kept]), (
                    name, lane)
                assert (lane in live) != np.array_equal(
                    rows[:, lane, :, kept], cache0[name][:, lane, :, kept]), (name, lane)


@pytest.mark.parametrize("program", ["chunk", "decode", "verify", "scalar"])
def test_only_a_chunk_program_routes_one_lane(tmp_path, program):
    """What the router's `top_k` is shaped by: a chunk program (one admitted
    lane by construction) routes `t` rows; a decode block and a verify
    program, whose lanes are all live, and a single stream at a scalar
    position route every row they hold, told or not."""
    import jax

    h, params, _ = build(tmp_path, arch=LlmArch.QWEN3_MOE)
    lanes, t = (1, 8) if program == "scalar" else (4, 1 if program == "decode" else 8)
    cache = init_kv_cache(h, lanes, seq_len=h.seq_len + t)
    pos = jnp.int32(0) if program == "scalar" else jnp.zeros((lanes,), jnp.int32)
    # a decode block or a verify program never says so; a scalar position has no lanes
    told = program in ("chunk", "scalar")
    jaxpr = jax.make_jaxpr(lambda toks, pos, cache: forward(
        params, h, toks, pos, cache, attn_park_threshold=h.seq_len,
        live_lanes_alone=told))(jnp.zeros((lanes, t), jnp.int32), pos, cache)
    routed = {e.invars[0].aval.shape[0] for e, _ in _equations(jaxpr.jaxpr)
              if e.primitive.name == "top_k"}
    assert routed == {t if program == "chunk" else lanes * t}


def test_forward_llama31_rope_scaling(tmp_path):
    h, params, tensors = build(tmp_path, rope_scaling=True)
    assert h.rope_scaling_factor == 8.0
    tokens = jnp.asarray([TOKENS], dtype=jnp.int32)
    cache = init_kv_cache(h, batch_size=1)
    logits, _ = forward(params, h, tokens, jnp.int32(0), cache)
    expected = numpy_forward(tensors, h, TOKENS)
    np.testing.assert_allclose(
        np.asarray(logits)[0], expected, rtol=2e-4, atol=2e-4
    )


def test_decode_matches_prefill(tmp_path):
    """Feeding tokens one-at-a-time through the cache must reproduce the
    full-prefill logits (the reference's decode loop is exactly this)."""
    h, params, _ = build(tmp_path)
    tokens = jnp.asarray([TOKENS], dtype=jnp.int32)
    cache = init_kv_cache(h, batch_size=1)
    full_logits, _ = forward(params, h, tokens, jnp.int32(0), cache)

    cache = init_kv_cache(h, batch_size=1)
    step_logits = []
    for i, t in enumerate(TOKENS):
        lg, cache = forward(
            params, h, jnp.asarray([[t]], dtype=jnp.int32), jnp.int32(i), cache
        )
        step_logits.append(np.asarray(lg)[0, 0])
    np.testing.assert_allclose(
        np.asarray(full_logits)[0], np.stack(step_logits), rtol=1e-4, atol=1e-4
    )


def test_chunked_prefill_matches_full(tmp_path):
    """Prefill in chunks (the reference's nBatches chunking) == one shot."""
    h, params, _ = build(tmp_path)
    tokens = jnp.asarray([TOKENS], dtype=jnp.int32)
    cache = init_kv_cache(h, batch_size=1)
    full_logits, _ = forward(params, h, tokens, jnp.int32(0), cache)

    cache = init_kv_cache(h, batch_size=1)
    lg1, cache = forward(params, h, tokens[:, :5], jnp.int32(0), cache)
    lg2, cache = forward(params, h, tokens[:, 5:], jnp.int32(5), cache)
    chunked = np.concatenate([np.asarray(lg1), np.asarray(lg2)], axis=1)
    np.testing.assert_allclose(
        np.asarray(full_logits), chunked, rtol=1e-4, atol=1e-4
    )


def test_q40_load_path_matches_oracle(tmp_path):
    """The Q40 model must match the numpy oracle fed the *dequantized*
    tensors exactly — isolates the load path from quantization noise
    (quality itself is validated end-to-end by perplexity mode)."""
    path40 = str(tmp_path / "q40.m")
    make_tiny_model(path40, weight_type=FloatType.Q40, seed=9)
    r40 = ModelReader(path40)
    dequant = {s.name: r40.dense_f32(s.name) for s in r40.specs}
    p40 = load_params(r40)
    tokens = jnp.asarray([TOKENS], dtype=jnp.int32)
    lg40, _ = forward(p40, r40.header, tokens, jnp.int32(0), init_kv_cache(r40.header, 1))
    expected = numpy_forward(dequant, r40.header, TOKENS)
    np.testing.assert_allclose(
        np.asarray(lg40)[0], expected, rtol=2e-4, atol=2e-4
    )


def test_batch_axis(tmp_path):
    """Two identical sequences in the batch produce identical logits."""
    h, params, _ = build(tmp_path)
    tokens = jnp.asarray([TOKENS, TOKENS], dtype=jnp.int32)
    cache = init_kv_cache(h, batch_size=2)
    logits, _ = forward(params, h, tokens, jnp.int32(0), cache)
    np.testing.assert_allclose(
        np.asarray(logits)[0], np.asarray(logits)[1], rtol=1e-6, atol=1e-6
    )


def test_logits_mode_last_matches_all(tmp_path):
    """logits_mode='last' must equal the full computation's final row and
    produce the identical updated cache (prefill chunks only sample from
    their last row; the vocab matmul on the other rows is skipped)."""
    h, params, _ = build(tmp_path)
    tokens = jnp.asarray([TOKENS], dtype=jnp.int32)
    cache_a = init_kv_cache(h, batch_size=1)
    logits_all, cache_all = forward(params, h, tokens, jnp.int32(0), cache_a)
    cache_b = init_kv_cache(h, batch_size=1)
    logits_last, cache_last = forward(
        params, h, tokens, jnp.int32(0), cache_b, logits_mode="last"
    )
    assert logits_last.shape == (1, 1, h.vocab_size)
    np.testing.assert_allclose(
        np.asarray(logits_last)[:, 0], np.asarray(logits_all)[:, -1],
        rtol=1e-6, atol=1e-6,
    )
    np.testing.assert_array_equal(
        np.asarray(cache_last["k"]), np.asarray(cache_all["k"])
    )
    np.testing.assert_array_equal(
        np.asarray(cache_last["v"]), np.asarray(cache_all["v"])
    )


def test_forward_parked_lane_isolation(tmp_path):
    """Per-lane forward with a parked lane (attn_park_threshold): the
    active lane's logits must equal a solo run, the parked lane's writes
    must land only in the padding rows, and its masked attention output
    must be finite."""
    h, params, _ = build(tmp_path)
    s = h.seq_len
    pad = 8
    park = s  # first padding row
    # solo reference: one lane at pos 3
    cache1 = init_kv_cache(h, batch_size=1, seq_len=s + pad)
    tok = jnp.asarray([[7, 9]], dtype=jnp.int32)
    # seed the cache with a short prefix so attention has context
    logits1, cache1 = forward(params, h, tok, jnp.int32(3), cache1)

    cache2 = init_kv_cache(h, batch_size=2, seq_len=s + pad)
    tok2 = jnp.asarray([[7, 9], [1, 2]], dtype=jnp.int32)
    posv = jnp.asarray([3, park], jnp.int32)
    logits2, cache2 = forward(
        params, h, tok2, posv, cache2, attn_park_threshold=park
    )
    np.testing.assert_allclose(
        np.asarray(logits2)[0], np.asarray(logits1)[0], rtol=1e-5, atol=1e-5
    )
    assert np.isfinite(np.asarray(logits2)[1]).all()
    # parked lane wrote ONLY padding rows: its real cache region is zeros
    k2 = np.asarray(cache2["k"])  # [L, B, KH, S+pad, hd]
    assert np.abs(k2[:, 1, :, :s]).max() == 0.0
    assert np.abs(k2[:, 1, :, s : s + 2]).max() > 0.0  # parked writes landed


def test_fused_load_no_mesh_matches_unfused(tmp_path):
    """Params loaded with fuse=2 (tp-interleaved wqkv/w13) run through
    forward with NO mesh must still match the unfused load bit-for-policy:
    the un-interleave factor is the FusedQuantWeight's own static
    metadata, not the mesh's tp, so a fused-load/mesh mismatch cannot
    mis-permute columns."""
    path = str(tmp_path / "m.m")
    make_tiny_model(path, weight_type=FloatType.Q40, seed=5)
    r = ModelReader(path)
    p_split = load_params(r, weight_format="q40")
    p_fused = load_params(r, weight_format="q40", fuse=2)
    assert "wqkv" in p_fused["layers"] and "w13" in p_fused["layers"]
    tokens = jnp.asarray([TOKENS], dtype=jnp.int32)
    lg_s, _ = forward(
        p_split, r.header, tokens, jnp.int32(0), init_kv_cache(r.header, 1)
    )
    lg_f, _ = forward(
        p_fused, r.header, tokens, jnp.int32(0), init_kv_cache(r.header, 1)
    )
    np.testing.assert_allclose(
        np.asarray(lg_f), np.asarray(lg_s), rtol=1e-5, atol=1e-5
    )


def test_fused_load_indivisible_tp_fails_loudly(tmp_path):
    """fuse that does not divide a constituent's out dim must raise at
    load time, not drop trailing columns."""
    path = str(tmp_path / "m.m")
    make_tiny_model(path, weight_type=FloatType.Q40, seed=5)
    r = ModelReader(path)
    with pytest.raises(ValueError, match="not divisible"):
        load_params(r, weight_format="q40", fuse=3)  # kv_dim=32 % 3 != 0


def test_streamed_load_matches_stack(tmp_path):
    """The streaming loader (shard-by-shard make_array_from_callback over
    ranged memmap reads) must produce leaf-identical params to the
    host-stack path, for plain, FUSED and MoE-expert Q40 stacks."""
    import os

    import jax
    from jax.sharding import PartitionSpec as P

    from dllama_tpu.formats.model_file import LlmArch
    from dllama_tpu.parallel import make_mesh, shard_params_put

    def load(path, arch, mesh, fuse):
        r = ModelReader(path)
        return load_params(
            r, weight_format="q40", put=shard_params_put(mesh, r.header),
            fuse=fuse,
        )

    # q40-over-tp=2 needs every contraction dim divisible by 32*tp
    dense_cfg = dict(dim=64, hidden_dim=128, n_layers=3, n_heads=4,
                     n_kv_heads=2, head_dim=16, vocab_size=256, seq_len=64)
    moe_cfg = dict(dim=64, hidden_dim=128, moe_hidden_dim=64, n_layers=2,
                   n_heads=4, n_kv_heads=2, head_dim=16, vocab_size=256,
                   seq_len=64, n_experts=4, n_active_experts=2)
    cases = [
        ("plain.m", LlmArch.LLAMA, dense_cfg, 0, make_mesh(tp=2, dp=2)),
        ("fused.m", LlmArch.LLAMA, dense_cfg, 2, make_mesh(tp=2, dp=2)),
        ("moe.m", LlmArch.QWEN3_MOE, moe_cfg, 0, make_mesh(tp=2, dp=2)),
        # pp: the one mesh where the lead (layer) axis slicing is
        # non-trivial — a mis-ordered stage range would pass tp/dp-only
        ("pp.m", LlmArch.LLAMA, dict(dense_cfg, n_layers=4), 2,
         make_mesh(tp=2, pp=2)),
    ]
    for fname, arch, cfg, fuse, mesh in cases:
        path = str(tmp_path / fname)
        make_tiny_model(path, arch=arch, weight_type=FloatType.Q40, cfg=cfg)
        os.environ["DLLAMA_STREAM_LOAD"] = "0"
        try:
            stacked = load(path, arch, mesh, fuse)
        finally:
            del os.environ["DLLAMA_STREAM_LOAD"]
        streamed = load(path, arch, mesh, fuse)
        ls, lt = jax.tree.leaves(streamed), jax.tree.leaves(stacked)
        assert len(ls) == len(lt)
        for a, b in zip(ls, lt):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=fname
            )


@pytest.mark.slow
def test_streamed_loader_memory_bound(tmp_path):
    """The 70B fit story's loader half: streaming load of
    a model with REAL Llama-70B layer dims (8192 dim / 28672 ffn; vocab
    shrunk so embed doesn't dominate a CI run) must keep the host
    high-water mark near the device bytes — NOT device + whole host
    layer stacks, which is what the pre-r5 np.stack loader cost (at 80
    layers the w13 stack alone is ~37 GB). Measured as subprocess VmHWM,
    streamed vs forced-stack."""
    import json
    import os
    import subprocess
    import sys as _sys

    from dllama_tpu.models.synthetic import write_synth_model

    cfg = dict(dim=8192, hidden_dim=28672, n_layers=4, n_heads=64,
               n_kv_heads=8, head_dim=128, vocab_size=8192, seq_len=2048)
    path = str(tmp_path / "big.m")
    write_synth_model(path, cfg, max_seq_len=2048)

    def probe(stream: str) -> dict:
        out = subprocess.run(
            [_sys.executable,
             str(Path(__file__).parent / "loader_hwm_probe.py"),
             path, "8", "8", stream],
            capture_output=True, timeout=900, text=True,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    streamed = probe("1")
    stacked = probe("0")
    # the stack path holds every [L, in, out] host stack on top of the
    # device buffers; the streamed path must stay within device bytes +
    # the memmapped FILE (clean file-backed pages count in VmHWM once
    # every byte has been read, though they are evictable under
    # pressure) + interpreter/runtime slack
    file_gb = os.path.getsize(path) / 1e9
    # measured runtime overhead (hwm - device - file) is ~0.3 GB on this
    # fixture; 1.6 keeps the bound far from flaking while still well
    # under the ~1.9 GB biggest host stack the streamed path must avoid
    slack_gb = 1.6
    assert streamed["hwm_gb"] < streamed["device_gb"] + file_gb + slack_gb, (
        streamed, file_gb,
    )
    # and it must beat the stack path by at least the biggest stack
    # (w13: 4 layers x 8192 x 57344 int8 ~ 1.9 GB)
    assert stacked["hwm_gb"] - streamed["hwm_gb"] > 1.0, (stacked, streamed)


def _equations(jaxpr, path=()):
    """(equation, names of the equations around it, outermost first) of
    every equation of a jaxpr, sub-jaxprs included."""
    import jax

    for eqn in jaxpr.eqns:
        yield eqn, path
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, path + (eqn.primitive.name,))


def _layer_scans(jaxpr):
    """Every `scan` equation of a jaxpr, sub-jaxprs included."""
    return (e for e, _ in _equations(jaxpr) if e.primitive.name == "scan")


@pytest.mark.parametrize(
    "arch,weight_format,fuse",
    [
        (LlmArch.LLAMA, "q40", 0),
        (LlmArch.LLAMA, "q40", 2),
        (LlmArch.LLAMA, "q40i4", 0),
        (LlmArch.QWEN3_MOE, "q40", 0),
    ],
)
def test_layer_scan_does_not_slice_quantized_stacks(
    tmp_path, arch, weight_format, fuse
):
    """The layer scan's `xs` hold no quantized weight or scale stack: they
    are constants of the scan, handed whole to the kernels with the layer
    number. As `xs`, XLA would copy every layer's slice out of the stack
    before the Pallas call reads it (2 x 218 MB a Mistral-7B layer; PERF.md,
    PR 25). This is the guard a CPU run can give."""
    import jax

    from dllama_tpu.models.transformer import _is_quant_stack

    path = str(tmp_path / "m.m")
    make_tiny_model(path, arch=arch, weight_type=FloatType.Q40, seed=3)
    r = ModelReader(path)
    params = load_params(r, weight_format=weight_format, fuse=fuse)
    h = r.header
    stacks = {
        k: v for k, v in params["layers"].items() if _is_quant_stack(v)
    }
    assert {"w2", "wo"} <= set(stacks)
    assert ("wqkv" in stacks and "w13" in stacks) if fuse else "w1" in stacks
    quantized = {
        (a.dtype, a.shape[1:]) for a in jax.tree.leaves(stacks)
    }
    tokens = jnp.asarray([TOKENS], dtype=jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, c: forward(p, h, tokens, jnp.int32(0), c)
    )(params, init_kv_cache(h, 1)).jaxpr
    (scan,) = [
        e for e in _layer_scans(jaxpr) if e.params["length"] == h.n_layers
    ]
    first_x = scan.params["num_consts"] + scan.params["num_carry"]
    consts = [v.aval for v in scan.invars[: scan.params["num_consts"]]]
    xs = [v.aval for v in scan.invars[first_x:]]
    assert xs and all(a.shape[0] == h.n_layers for a in xs)
    sliced = [a for a in xs if (a.dtype, a.shape[1:]) in quantized]
    assert not sliced, f"quantized stacks among the layer scan's xs: {sliced}"
    whole = {(a.dtype, a.shape[1:]) for a in consts if a.ndim >= 3}
    assert quantized <= whole, quantized - whole


def test_layer_scans_of_two_layer_kinds_do_not_slice_quantized_stacks(tmp_path):
    """The same over a model whose layers differ (window and full layers
    over two cache stacks, a leading dense layer and then experts): one
    scan a run of layers of one FFN kind, the quantized stacks constants of
    each, the two cache stacks carried whole by both and among neither's
    `xs` or `ys`."""
    import jax

    from helpers import make_tiny_afmoe

    from dllama_tpu.models.transformer import _is_quant_stack

    path = str(tmp_path / "m.m")
    make_tiny_afmoe(path)
    r = ModelReader(path, max_seq_len=64)
    params = load_params(r, weight_format="q40", fuse=1)
    h = r.header
    stacks = {k: v for k, v in params["layers"].items() if _is_quant_stack(v)}
    assert {"wqkv", "wo", "dense_w13", "dense_w2", "shared_w13", "shared_w2",
            "w1", "w2", "w3"} <= set(stacks)
    quantized = {(a.dtype, a.shape[1:]) for a in jax.tree.leaves(stacks)}
    cache = init_kv_cache(h, 1, seq_len=64 + 8, ring=40, ring_pad=8)
    tokens = jnp.asarray([TOKENS[:8]], dtype=jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, c: forward(p, h, tokens, jnp.int32(0), c, kv_ring=40)
    )(params, cache).jaxpr
    scans = [e for e in _layer_scans(jaxpr) if e.params["num_carry"] >= 3]
    assert sorted(e.params["length"] for e in scans) == [1, 4]  # dense, experts
    per_layer = {(a.dtype, a.shape[1:]) for a in jax.tree.leaves(cache)}
    for scan in scans:
        n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
        consts = [v.aval for v in scan.invars[:n_consts]]
        carry = [v.aval for v in scan.invars[n_consts:n_consts + n_carry]]
        moved = [v.aval for v in scan.invars[n_consts + n_carry:]] + [
            v.aval for v in scan.outvars[n_carry:]]
        assert not [a for a in moved if (a.dtype, a.shape[1:]) in quantized]
        assert not [a for a in moved if (a.dtype, a.shape[1:]) in per_layer]
        # the dense layer is a window layer: its scan carries that stack alone
        names = ("kw", "vw") if scan.params["length"] == 1 else tuple(cache)
        for name in names:
            assert (cache[name].dtype, cache[name].shape) in [
                (a.dtype, a.shape) for a in carry]
        whole = {(a.dtype, a.shape[1:]) for a in consts if a.ndim >= 3}
        assert whole & quantized


def _filled_cache(h, batch, kv, seq_len, seed=0):
    """A cache whose every row holds something, so a row that moved shows."""
    import jax

    rng = np.random.default_rng(seed)
    cache = init_kv_cache(h, batch, dtype=kv, seq_len=seq_len)

    def fill(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        return jnp.asarray(rng.uniform(0.5, 1.5, a.shape), a.dtype)

    return jax.tree.map(fill, cache)


@pytest.mark.parametrize("per_lane", [False, True], ids=["scalar", "per_lane"])
@pytest.mark.parametrize("kv", [jnp.bfloat16, jnp.int8], ids=["bf16", "int8"])
@pytest.mark.parametrize(
    "arch", [LlmArch.LLAMA, LlmArch.QWEN3_MOE], ids=["dense", "sparse"]
)
def test_layer_scan_carries_the_cache_whole(tmp_path, arch, kv, per_lane):
    """The caches are the layer scan's carry, every leaf whole (a
    `QuantKV`'s values and scales alike), and are neither among its `xs`
    nor its `ys`. As `xs` and `ys`, XLA sliced every layer's whole lane
    cache out of the stack and wrote it back whole each step, to write one
    row a lane (47 MB twice a Mistral-7B layer; PERF.md, PR 29)."""
    import jax

    h, params, _ = build(tmp_path, arch=arch)
    b = 2
    cache = init_kv_cache(h, b, dtype=kv, seq_len=h.seq_len + 8)
    tokens = jnp.asarray([TOKENS[:2]] * b, dtype=jnp.int32)
    pos = jnp.asarray([3, 5], jnp.int32) if per_lane else jnp.int32(3)
    jaxpr = jax.make_jaxpr(lambda p, c: forward(p, h, tokens, pos, c))(
        params, cache
    ).jaxpr
    (scan,) = [
        e for e in _layer_scans(jaxpr) if e.params["length"] == h.n_layers
    ]
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    carry = [v.aval for v in scan.invars[n_consts : n_consts + n_carry]]
    xs = [v.aval for v in scan.invars[n_consts + n_carry :]]
    ys = [v.aval for v in scan.outvars[n_carry:]]
    leaves = jax.tree.leaves(cache)
    assert len(leaves) == (4 if kv == jnp.int8 else 2)
    per_layer = {(a.dtype, a.shape[1:]) for a in leaves}
    for name, avals in (("xs", xs), ("ys", ys)):
        sliced = [
            a for a in avals
            if a.shape[:1] == (h.n_layers,)
            and (a.dtype, a.shape[1:]) in per_layer
        ]
        assert not sliced, f"cache leaves among the layer scan's {name}: {sliced}"
    carried = [(a.dtype, a.shape) for a in carry]
    for a in leaves:
        assert (a.dtype, a.shape) in carried, (a.dtype, a.shape, carried)
        carried.remove((a.dtype, a.shape))


# what only the sampled side of the sampler may hold: the top-p sort and
# running sum over [lanes, vocabulary], and the draw's key and bits
SAMPLER_ONLY = {
    "sort", "cumsum", "random_bits", "random_seed", "random_fold_in",
    "random_wrap", "random_unwrap", "threefry2x32",
}


@pytest.mark.parametrize("program", ["lane_block", "lane_block_paged"])
def test_sampler_work_sits_inside_the_conditional(tmp_path, monkeypatch, program):
    """In the lane decode programs every sort, running sum and random-bits
    equation lies inside a branch of the sampler's `cond`, itself inside
    the block's loop over steps, and the loop's body holds none outside
    it: a block whose live lanes are all greedy runs the argmax alone.
    Unconditional, a `sort` of `f32[16,151936]` was 3.5 ms of a 16.7 ms
    decode step at temperature 0 (PERF.md, PR 29 and PR 31)."""
    import jax

    from dllama_tpu.runtime.engine import InferenceEngine

    # the lazily jitted function, which can still be traced
    monkeypatch.setenv("DLLAMA_WINDOW_PRECOMPILE", "0")
    model = str(tmp_path / "m.m")
    make_tiny_model(model, cfg=dict(
        dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
        head_dim=16, vocab_size=288, seq_len=64))
    e = InferenceEngine(model, tp=1, dtype=jnp.float32, temperature=0.0,
                        batch_size=2)
    if program == "lane_block":
        fn, specs = e._lane_decode_fn(4, 64), e._lane_arg_specs(4)
    else:
        e.init_kv_pool(4, native=True)
        fn = e._lane_decode_paged_fn(4, 64)
        specs = e._lane_decode_paged_arg_specs(4)
    found = [
        (eqn.primitive.name, path)
        for eqn, path in _equations(jax.make_jaxpr(fn)(*specs).jaxpr)
        if eqn.primitive.name in SAMPLER_ONLY
    ]
    assert {"sort", "cumsum", "random_bits"} <= {name for name, _ in found}
    for name, path in found:
        loops = [i for i, p in enumerate(path) if p in ("scan", "while")]
        assert "cond" in path and loops, (name, path)
        assert path.index("cond") > loops[0], (name, path)
    # and one conditional holds them all: the sampler's
    conds = {path[: path.index("cond") + 1] for _, path in found}
    assert len(conds) == 1, conds


@pytest.mark.parametrize("per_lane", [False, True], ids=["scalar", "per_lane"])
@pytest.mark.parametrize("kv", [jnp.bfloat16, jnp.int8], ids=["bf16", "int8"])
def test_forward_moves_only_the_rows_it_writes(tmp_path, kv, per_lane):
    """After `forward` at `pos`, every cache row outside [pos, pos + T) of
    each lane and layer is bit-equal to what it was, and the rows inside
    were written. A parked lane (per-lane positions) moves its padding
    rows only."""
    import jax

    h, params, _ = build(tmp_path)
    s, pad, t = h.seq_len, 8, 2
    cache = _filled_cache(h, 3, kv, s + pad)
    tokens = jnp.asarray([[7, 9], [1, 2], [4, 4]], dtype=jnp.int32)
    if per_lane:
        starts = [3, s, 9]  # lane 1 is parked: it writes at the padding
        pos = jnp.asarray(starts, jnp.int32)
    else:
        starts = [5, 5, 5]
        pos = jnp.int32(5)
    _, new = forward(params, h, tokens, pos, cache, attn_park_threshold=s)
    for old_leaf, new_leaf in zip(jax.tree.leaves(cache), jax.tree.leaves(new)):
        old_a, new_a = np.asarray(old_leaf), np.asarray(new_leaf)
        assert old_a.dtype == new_a.dtype and old_a.shape == new_a.shape
        for lane, p in enumerate(starts):
            rows = np.zeros(s + pad, bool)
            rows[p : p + t] = True
            np.testing.assert_array_equal(
                new_a[:, lane][:, :, ~rows], old_a[:, lane][:, :, ~rows]
            )
            # uniform(0.5, 1.5) and the scales never equal a projection
            moved = new_a[:, lane][:, :, rows] != old_a[:, lane][:, :, rows]
            assert moved.any(axis=-1).all(), (lane, p)


# -- the int8 cache's row quantizer (ops/kv_cache.quantize_kv_rows) ---------


def test_quantize_kv_rows_round_trip_error():
    """One scale per cache row, max|row| / 127: a value comes back within
    half a step of that row's scale, and the row's largest is exact."""
    from dllama_tpu.ops.kv_cache import QuantKV, dequant_kv, quantize_kv_rows

    rng = np.random.default_rng(0)
    val = jnp.asarray(rng.standard_normal((2, 3, 5, 16)).astype(np.float32))
    q, s = quantize_kv_rows(val)
    assert q.dtype == jnp.int8 and q.shape == val.shape
    assert s.dtype == jnp.float32 and s.shape == (2, 3, 5, 1)
    np.testing.assert_allclose(
        np.asarray(s)[..., 0], np.abs(np.asarray(val)).max(-1) / 127.0,
        rtol=1e-6)
    back = np.asarray(dequant_kv(QuantKV(q, s), jnp.float32))
    err = np.abs(back - np.asarray(val))
    assert (err <= np.asarray(s) * 0.5 * (1 + 1e-5)).all()
    assert np.abs(np.asarray(q)).max(-1).min() == 127


def test_quantize_kv_rows_zero_row():
    """An all-zero row (a cache row never written) takes scale 1: nothing
    divides by zero and the row comes back as zeros."""
    from dllama_tpu.ops.kv_cache import quantize_kv_rows

    val = jnp.ones((4, 16), jnp.bfloat16).at[2].set(0)
    q, s = quantize_kv_rows(val)
    assert np.isfinite(np.asarray(s)).all()
    assert float(s[2, 0]) == 1.0
    np.testing.assert_array_equal(np.asarray(q[2]), 0)
    np.testing.assert_array_equal(np.asarray(q[0]), 127)


def test_quantize_kv_rows_rows_are_independent():
    """A row's values and scale depend on that row alone, whatever the
    leading axes (a `[L, B, KH, T, hd]` write or one flat `[T, hd]`), and
    the row width need not be a multiple of anything."""
    from dllama_tpu.ops.kv_cache import quantize_kv_rows

    rng = np.random.default_rng(1)
    val = jnp.asarray(rng.standard_normal((2, 2, 3, 4, 20)).astype(np.float32))
    q, s = quantize_kv_rows(val)
    qf, sf = quantize_kv_rows(val.reshape(-1, 20))
    np.testing.assert_array_equal(np.asarray(q).reshape(-1, 20), np.asarray(qf))
    np.testing.assert_array_equal(np.asarray(s).reshape(-1, 1), np.asarray(sf))
    louder = val.at[0, 0, 0, 0].multiply(100.0)
    q2, s2 = quantize_kv_rows(louder)
    np.testing.assert_array_equal(np.asarray(q2)[1:], np.asarray(q)[1:])
    np.testing.assert_array_equal(np.asarray(s2)[0, 0, 0, 1:], np.asarray(s)[0, 0, 0, 1:])
