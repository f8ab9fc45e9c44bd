"""Pipeline parallelism: forward_pp vs the single-device forward.

The reference has no pipeline strategy (SURVEY.md §2 checklist: TP only,
bounded by nNodes <= nKvHeads); these tests pin the pp stage schedule —
identical logits AND identical per-layer cache commits — on the virtual
CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.formats import FloatType, ModelReader
from dllama_tpu.models import forward, init_kv_cache, load_params
from dllama_tpu.parallel.mesh import make_mesh
from dllama_tpu.parallel.pipeline import forward_pp, validate_pp

from helpers import make_tiny_model

CFG4 = dict(dim=64, hidden_dim=160, n_layers=4, n_heads=4, n_kv_heads=2,
            head_dim=16, vocab_size=256, seq_len=64)
TOKENS = [3, 17, 92, 5, 44, 120, 7, 3]


def _params(tmp_path, weight_format="dense", fuse=0, cfg=None):
    path = str(tmp_path / "m.m")
    make_tiny_model(path, weight_type=FloatType.Q40, seed=11, cfg=cfg or CFG4)
    r = ModelReader(path)
    p = load_params(r, weight_format=weight_format, fuse=fuse)
    return r.header, p


@pytest.mark.parametrize("pp", [2, 4])
def test_forward_pp_matches_single(tmp_path, pp):
    h, params = _params(tmp_path)
    mesh = make_mesh(pp=pp)
    tokens = jnp.asarray([TOKENS], jnp.int32)

    lg_ref, cache_ref = forward(
        params, h, tokens, jnp.int32(0), init_kv_cache(h, 1)
    )
    lg_pp, cache_pp = forward_pp(
        params, h, tokens, jnp.int32(0), init_kv_cache(h, 1), mesh
    )
    np.testing.assert_allclose(
        np.asarray(lg_pp), np.asarray(lg_ref), rtol=1e-5, atol=1e-5
    )
    for k in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(cache_pp[k]), np.asarray(cache_ref[k]),
            rtol=1e-5, atol=1e-5,
        )


def test_forward_pp_decode_chain(tmp_path):
    """Greedy prefill + 6 decode steps through forward_pp must reproduce
    the single-device token stream (cache committed per stage range)."""
    h, params = _params(tmp_path)
    mesh = make_mesh(pp=2)
    prompt = TOKENS[:4]

    def run(fwd, **kw):
        cache = init_kv_cache(h, 1)
        toks = jnp.asarray([prompt], jnp.int32)
        logits, cache = fwd(params, h, toks, jnp.int32(0), cache, **kw)
        out = [int(jnp.argmax(logits[0, -1]))]
        pos = len(prompt)
        for _ in range(6):
            logits, cache = fwd(
                params, h, jnp.asarray([[out[-1]]], jnp.int32),
                jnp.int32(pos), cache, **kw,
            )
            out.append(int(jnp.argmax(logits[0, -1])))
            pos += 1
        return out

    expected = run(forward)
    got = run(forward_pp, mesh=mesh)
    assert got == expected, (got, expected)


def test_forward_pp_q40_fused(tmp_path):
    """Quantized weights with fused wqkv/w13 run stage-local inside the pp
    shard_map (mesh=None per stage -> local qmatmul) and match dense."""
    h, pq = _params(tmp_path, weight_format="q40", fuse=1)
    mesh = make_mesh(pp=2)
    tokens = jnp.asarray([TOKENS], jnp.int32)
    lg_ref, _ = forward(pq, h, tokens, jnp.int32(0), init_kv_cache(h, 1))
    lg_pp, _ = forward_pp(pq, h, tokens, jnp.int32(0), init_kv_cache(h, 1), mesh)
    np.testing.assert_allclose(
        np.asarray(lg_pp), np.asarray(lg_ref), rtol=1e-5, atol=1e-5
    )


def test_validate_pp(tmp_path):
    h, _ = _params(tmp_path)
    validate_pp(h, 2)
    validate_pp(h, 4)  # any divisor of nLayers is legal, not just 2^n
    with pytest.raises(ValueError, match=">= 1"):
        validate_pp(h, 0)
    with pytest.raises(ValueError, match="not divisible"):
        validate_pp(h, 3)  # 4 layers / 3 stages
    with pytest.raises(ValueError, match="not divisible"):
        validate_pp(h, 8)  # 4 layers / 8 stages


def test_engine_pp_matches_single_device(tmp_path):
    """The full engine path (bucketed prefill + on-device block decode)
    over pp=2 stages must reproduce the single-device token stream, for
    dense AND fused-q40 weights."""
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "m.m")
    make_tiny_model(path, weight_type=FloatType.Q40, seed=11, cfg=CFG4)
    prompt = [1, 2, 3, 4, 5]
    for fmt in ("dense", "q40"):
        e1 = InferenceEngine(
            path, tp=1, dtype=jnp.float32, temperature=0.0, weight_format=fmt
        )
        expected, _, _ = e1.generate(prompt, max_steps=16)
        del e1
        epp = InferenceEngine(
            path, pp=2, dtype=jnp.float32, temperature=0.0, weight_format=fmt
        )
        assert epp.mesh.shape["pp"] == 2
        got, _, _ = epp.generate(prompt, max_steps=16)
        del epp
        assert got == expected, (fmt, got, expected)


def test_engine_pp_with_lanes(tmp_path):
    """Continuous batching over pipeline stages: per-lane prefill+decode
    with pp=2 must reproduce each prompt's single-stream tokens (parked
    writes and per-lane positions flow through the stage schedule)."""
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "m.m")
    make_tiny_model(path, weight_type=FloatType.Q40, seed=11, cfg=CFG4)
    prompts = [[1, 2, 3, 4], [9, 8, 7, 6, 5]]
    singles = []
    e1 = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0)
    for p in prompts:
        e1.reset()
        o, _, _ = e1.generate(p, max_steps=16)
        singles.append(o)
    del e1
    epp = InferenceEngine(
        path, pp=2, dtype=jnp.float32, temperature=0.0, batch_size=2
    )
    outs = epp.generate_batch(prompts, max_steps=16)
    assert outs == singles, (outs, singles)


@pytest.mark.parametrize("n_micro", [2, 4])
def test_forward_pp_sequence_microbatch(tmp_path, n_micro):
    """Sequence-wave microbatching (GPipe over the T axis): chunk c hits
    stage s only after chunks < c committed their KV there, so logits and
    caches must match the flat forward exactly for a 32-token chunk."""
    h, params = _params(tmp_path)
    mesh = make_mesh(pp=2)
    toks = (list(range(3, 35)))
    tokens = jnp.asarray([toks], jnp.int32)

    lg_ref, cache_ref = forward(
        params, h, tokens, jnp.int32(0), init_kv_cache(h, 1)
    )
    lg_pp, cache_pp = forward_pp(
        params, h, tokens, jnp.int32(0), init_kv_cache(h, 1), mesh,
        n_micro=n_micro,
    )
    np.testing.assert_allclose(
        np.asarray(lg_pp), np.asarray(lg_ref), rtol=1e-4, atol=1e-4
    )
    for k in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(cache_pp[k]), np.asarray(cache_ref[k]),
            rtol=1e-5, atol=1e-5,
        )


def test_engine_pp_micro_prefill(tmp_path):
    """A prompt long enough to trigger the microbatched prefill bucket
    (t=32 with pp=2 -> n_micro via _pp_micro when rows allow) still
    reproduces single-device tokens through the engine."""
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "m.m")
    make_tiny_model(path, weight_type=FloatType.Q40, seed=11, cfg=CFG4)
    prompt = list(range(2, 36))  # 34 tokens -> 32-wide bucket in play
    e1 = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0)
    expected, _, _ = e1.generate(prompt, max_steps=44)
    del e1
    epp = InferenceEngine(path, pp=2, dtype=jnp.float32, temperature=0.0)
    assert epp._pp_micro(32) == 4  # 32 rows / 4 waves of 8
    got, _, _ = epp.generate(prompt, max_steps=44)
    del epp
    assert got == expected, (got, expected)


def test_engine_pp_perplexity_matches(tmp_path):
    """Chunked teacher-forced scoring through pp stages (the score path
    runs logits_mode='all' over microbatched waves) must match the
    single-device perplexity."""
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "m.m")
    make_tiny_model(path, weight_type=FloatType.Q40, seed=11, cfg=CFG4)
    toks = [(i * 7) % 250 + 1 for i in range(40)]
    e1 = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0)
    nll1, ppl1, n1 = e1.perplexity(toks)
    del e1
    epp = InferenceEngine(path, pp=2, dtype=jnp.float32, temperature=0.0)
    nll2, ppl2, n2 = epp.perplexity(toks)
    del epp
    assert n1 == n2
    np.testing.assert_allclose(nll2, nll1, rtol=1e-4)


CFG4_TP = dict(CFG4, hidden_dim=256)  # q40 col splits need dims % (32*tp)


def _params_tp(tmp_path, weight_format="dense", fuse=0):
    path = str(tmp_path / "mtp.m")
    make_tiny_model(path, weight_type=FloatType.Q40, seed=11, cfg=CFG4_TP)
    r = ModelReader(path)
    return r.header, load_params(r, weight_format=weight_format, fuse=fuse)


def test_forward_pp_with_tp(tmp_path):
    """pp x tp: stages of tensor-parallel groups (manual psum inside the
    stage shard_map). Logits and caches must match the flat forward, for
    dense and fused-q40 weights."""
    for fmt, fuse in (("dense", 0), ("q40", 2)):
        h, params = _params_tp(tmp_path, weight_format=fmt, fuse=fuse)
        mesh = make_mesh(pp=2, tp=2)
        tokens = jnp.asarray([TOKENS], jnp.int32)
        lg_ref, cache_ref = forward(
            params, h, tokens, jnp.int32(0), init_kv_cache(h, 1)
        )
        lg_pp, cache_pp = forward_pp(
            params, h, tokens, jnp.int32(0), init_kv_cache(h, 1), mesh
        )
        np.testing.assert_allclose(
            np.asarray(lg_pp), np.asarray(lg_ref), rtol=2e-4, atol=2e-4,
            err_msg=fmt,
        )
        for k in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(cache_pp[k]), np.asarray(cache_ref[k]),
                rtol=1e-4, atol=1e-4, err_msg=fmt,
            )


def test_forward_pp_tp_wcls_stays_sharded(tmp_path):
    """Under pp x tp the vocab head must keep wcls tp-sharded and compute
    per-shard logits slices (logits_head tp_axis): the ONLY all-gather in
    the compiled program is the [B, T, V] logits gather over the tp
    groups. A replicated wcls in_spec would add a weight-sized [D, V]
    all-gather per step — GB-scale on a real 70B layout."""
    from dllama_tpu.parallel.sharding import shard_params_put

    path = str(tmp_path / "mtp.m")
    make_tiny_model(path, weight_type=FloatType.Q40, seed=11, cfg=CFG4_TP)
    r = ModelReader(path)
    h = r.header
    mesh = make_mesh(pp=2, tp=2)
    params = load_params(
        r, weight_format="dense", put=shard_params_put(mesh, h)
    )
    tokens = jnp.asarray([TOKENS], jnp.int32)
    cache = init_kv_cache(h, 1)
    f = jax.jit(
        lambda p, t, c: forward_pp(p, h, t, jnp.int32(0), c, mesh)
    )
    txt = f.lower(params, tokens, cache).compile().as_text()
    gathers = [ln for ln in txt.splitlines() if "all-gather(" in ln]
    assert len(gathers) == 1, gathers
    b, t = tokens.shape
    assert f"f32[{b},{t},{h.vocab_size}]" in gathers[0], gathers[0]


def test_forward_pp_tp_sync_quant(tmp_path):
    """buffer_float_type=q80 must reach the pp x tp stage-local partial
    sums (not be silently dropped): logits stay within quantization
    tolerance of the exact run AND differ from it (the compressed
    collective actually ran)."""
    h, params = _params_tp(tmp_path)
    mesh = make_mesh(pp=2, tp=2)
    tokens = jnp.asarray([TOKENS], jnp.int32)
    lg_exact, _ = forward_pp(
        params, h, tokens, jnp.int32(0), init_kv_cache(h, 1), mesh,
        sync_quant=False,
    )
    lg_q80, _ = forward_pp(
        params, h, tokens, jnp.int32(0), init_kv_cache(h, 1), mesh,
        sync_quant=True,
    )
    exact = np.asarray(lg_exact)
    q80 = np.asarray(lg_q80)
    scale = np.abs(exact).max()
    err = np.abs(q80 - exact).max()
    assert err / scale < 2e-2, (err, scale)
    assert err > 0.0  # compression actually happened


def test_engine_pp_x_tp_matches_single_device(tmp_path):
    """Engine-level pp=2 x tp=2 (4 virtual chips): generated tokens match
    the single-device stream for fused q40."""
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "m.m")
    make_tiny_model(path, weight_type=FloatType.Q40, seed=11, cfg=CFG4_TP)
    prompt = list(range(2, 36))
    e1 = InferenceEngine(
        path, tp=1, dtype=jnp.float32, temperature=0.0, weight_format="q40"
    )
    expected, _, _ = e1.generate(prompt, max_steps=44)
    del e1
    epp = InferenceEngine(
        path, pp=2, tp=2, dtype=jnp.float32, temperature=0.0,
        weight_format="q40",
    )
    got, _, _ = epp.generate(prompt, max_steps=44)
    del epp
    assert got == expected, (got, expected)


def test_forward_pp_park_writes_match_select(tmp_path):
    """park_pos mode (invalid-tick writes into padding scratch rows) must
    reproduce the select-merge logits and every REAL cache row, prefill
    and decode, including the n_micro sequence-wave schedule."""
    h, params = _params(tmp_path)
    mesh = make_mesh(pp=2)
    s = h.seq_len
    pad = 8

    def run(park):
        cache = init_kv_cache(h, 1, seq_len=s + pad)
        toks = jnp.asarray([TOKENS], jnp.int32)
        logits, cache = forward_pp(
            params, h, toks, jnp.int32(0), cache, mesh,
            park_pos=park, n_micro=2,
        )
        out = [logits]
        pos = len(TOKENS)
        for _ in range(3):
            nxt = jnp.argmax(logits[0, -1])[None, None].astype(jnp.int32)
            logits, cache = forward_pp(
                params, h, nxt, jnp.int32(pos), cache, mesh, park_pos=park
            )
            out.append(logits)
            pos += 1
        return out, cache

    lg_sel, cache_sel = run(0)
    lg_park, cache_park = run(s)
    for a, b in zip(lg_sel, lg_park):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
        )
    for k in ("k", "v"):  # real rows identical; rows >= s are scratch
        np.testing.assert_allclose(
            np.asarray(cache_park[k][:, :, :, :s]),
            np.asarray(cache_sel[k][:, :, :, :s]),
            rtol=1e-5, atol=1e-5,
        )


def test_forward_pp_park_cuts_decode_bytes(tmp_path):
    """The park path must actually remove the per-tick O(stage cache)
    select: compiled bytes-accessed of a decode step drops vs the
    select-merge path (the select reads+writes the whole stage cache
    every one of the pp ticks). Long seq_len so the cache term dominates
    the tiny model's weights, as it does at real scale."""
    h, params = _params(tmp_path, cfg=dict(CFG4, seq_len=512))
    mesh = make_mesh(pp=4)
    s = h.seq_len

    def compiled_bytes(park):
        cache = init_kv_cache(h, 1, seq_len=s + 8)
        tok = jnp.asarray([[7]], jnp.int32)

        def step(p, t, c):
            return forward_pp(p, h, t, jnp.int32(10), c, mesh, park_pos=park)

        lowered = jax.jit(step).lower(params, tok, cache)
        cost = lowered.compile().cost_analysis()
        if isinstance(cost, list):  # per-device list on some backends
            cost = cost[0]
        return cost.get("bytes accessed", 0.0)

    b_sel = compiled_bytes(0)
    b_park = compiled_bytes(s)
    # the select reads the stage's old and new cache and writes one: three
    # passes over a stage's share (the cost analysis counts a loop's body
    # once). A ratio of the totals would also move with everything else a
    # step reads, as it did when the layer scan stopped copying the cache.
    stage_cache = sum(
        a.nbytes for a in jax.tree.leaves(init_kv_cache(h, 1, seq_len=s + 8))
    ) // 4
    assert b_sel - b_park >= 0.9 * 3 * stage_cache, (b_park, b_sel, stage_cache)


def test_engine_pp_x_dp_matches_single_device(tmp_path):
    """pp=2 x dp=2: batch lanes shard over dp inside every stage; each
    prompt's token stream must match its single-device run (the pipeline
    throughput configuration — docs/pp_decode_model.md)."""
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "m.m")
    make_tiny_model(path, weight_type=FloatType.Q40, seed=11, cfg=CFG4)
    prompts = [[1, 2, 3, 4], [9, 8, 7, 6, 5]]
    singles = []
    e1 = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0)
    for p in prompts:
        e1.reset()
        o, _, _ = e1.generate(p, max_steps=14)
        singles.append(o)
    del e1
    epp = InferenceEngine(
        path, pp=2, dp=2, dtype=jnp.float32, temperature=0.0, batch_size=2
    )
    assert epp.mesh.shape == {"pp": 2, "dp": 2, "tp": 1}
    outs = epp.generate_batch(prompts, max_steps=14)
    del epp
    assert outs == singles, (outs, singles)


def test_engine_pp_x_dp_x_tp_matches_single_device(tmp_path):
    """The full pp=2 x dp=2 x tp=2 composition on 8 virtual devices:
    stages of tp groups with dp-sharded lanes, token parity per prompt."""
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "m.m")
    make_tiny_model(path, weight_type=FloatType.Q40, seed=11, cfg=CFG4)
    prompts = [[1, 2, 3], [7, 6, 5, 4]]
    singles = []
    e1 = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0)
    for p in prompts:
        e1.reset()
        o, _, _ = e1.generate(p, max_steps=12)
        singles.append(o)
    del e1
    epp = InferenceEngine(
        path, pp=2, dp=2, tp=2, dtype=jnp.float32, temperature=0.0,
        batch_size=2,
    )
    outs = epp.generate_batch(prompts, max_steps=12)
    del epp
    assert outs == singles, (outs, singles)


def test_engine_pp_dp_batch_divisibility(tmp_path):
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "m.m")
    make_tiny_model(path, weight_type=FloatType.Q40, seed=11, cfg=CFG4)
    with pytest.raises(ValueError, match="batch_size"):
        InferenceEngine(path, pp=2, dp=2, batch_size=3, dtype=jnp.float32)


def test_forward_pp_x_sp_matches_single(tmp_path):
    """pp=2 x sp=2: stage-local sequence shards with merged-stats
    attention and owning-shard window writes must reproduce the flat
    forward's logits and cache — prefill chunk AND decode steps,
    including a chunk that straddles the sp shard boundary."""
    h, params = _params(tmp_path)
    mesh = make_mesh(pp=2, sp=2)
    s = h.seq_len  # 64 -> 32-row local shards

    def run(fwd, **kw):
        cache = init_kv_cache(h, 1)
        toks = jnp.asarray([list(range(2, 30))], jnp.int32)  # 28 rows
        logits, cache = fwd(params, h, toks, jnp.int32(0), cache, **kw)
        outs = [logits]
        pos = 28
        # decode across the 32-row shard boundary (positions 28..35)
        for i in range(8):
            nxt = jnp.argmax(logits[0, -1])[None, None].astype(jnp.int32)
            logits, cache = fwd(
                params, h, nxt, jnp.int32(pos), cache, **kw
            )
            outs.append(logits)
            pos += 1
        return outs, cache

    ref, cache_ref = run(forward)
    got, cache_pp = run(forward_pp, mesh=mesh)
    for a, b in zip(ref, got):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-4
        )
    # sp caches use the cyclic layout: global row g sits at axis index
    # (g % sp) * shard + g // sp — undo the permutation before comparing
    sp, shard = 2, s // 2
    g = np.arange(s)
    perm = (g % sp) * shard + g // sp
    for k in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(cache_pp[k])[:, :, :, perm],
            np.asarray(cache_ref[k]),
            rtol=1e-5, atol=1e-5,
        )


def test_engine_pp_x_sp_matches_single_device(tmp_path):
    """Engine pp=2 x sp=2 (and pp x sp x tp on 8 devices): the bucketed
    prefill + block decode path with stage-local sequence shards must
    reproduce single-device tokens."""
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "m.m")
    make_tiny_model(path, weight_type=FloatType.Q40, seed=11, cfg=CFG4)
    prompt = [1, 2, 3, 4, 5, 6, 7]
    e1 = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0)
    expected, _, _ = e1.generate(prompt, max_steps=18)
    del e1
    for kw in (dict(pp=2, sp=2), dict(pp=2, sp=2, tp=2)):
        epp = InferenceEngine(
            path, dtype=jnp.float32, temperature=0.0, **kw
        )
        got, _, _ = epp.generate(prompt, max_steps=18)
        del epp
        assert got == expected, (kw, got, expected)


def test_forward_pp_x_sp_windowed_decode(tmp_path):
    """pp x sp with an ACTIVE attention window (sp-multiple, smaller than
    the cache): the manual-path local prefix slice must reproduce the
    unwindowed logits while the window covers the live prefix."""
    h, params = _params(tmp_path, cfg=dict(CFG4, seq_len=2048))
    mesh = make_mesh(pp=2, sp=2)
    cache0 = init_kv_cache(h, 1)

    toks = jnp.asarray([TOKENS], jnp.int32)
    _, cache = forward_pp(
        params, h, toks, jnp.int32(0), cache0, mesh
    )
    step = jnp.asarray([[9]], jnp.int32)
    lg_full, _ = forward_pp(
        params, h, step, jnp.int32(len(TOKENS)), cache, mesh
    )
    lg_win, _ = forward_pp(
        params, h, step, jnp.int32(len(TOKENS)), cache, mesh,
        attn_window=1024,  # sp multiple, < 2048: local 512-row prefix
    )
    np.testing.assert_allclose(
        np.asarray(lg_win), np.asarray(lg_full), rtol=1e-5, atol=1e-5
    )
    # misaligned windows fail loudly on the manual path too
    with pytest.raises(ValueError, match="multiple of sp"):
        forward_pp(
            params, h, step, jnp.int32(len(TOKENS)), cache, mesh,
            attn_window=1025,
        )


def test_forward_pp_int8_cache_no_park(tmp_path):
    """forward_pp with a QuantKV (int8) cache and NO park rows: the
    invalid-tick cache select must tree-map over the (values, scales)
    pair (r5 regression — found by the 70B rehearsal script)."""
    h, params = _params(tmp_path)
    mesh = make_mesh(pp=2)
    tokens = jnp.asarray([TOKENS], jnp.int32)
    lg_ref, _ = forward(
        params, h, tokens, jnp.int32(0), init_kv_cache(h, 1, dtype=jnp.int8)
    )
    lg_pp, cache_pp = forward_pp(
        params, h, tokens, jnp.int32(0),
        init_kv_cache(h, 1, dtype=jnp.int8), mesh,
    )
    np.testing.assert_allclose(
        np.asarray(lg_pp), np.asarray(lg_ref), rtol=1e-5, atol=1e-5
    )
