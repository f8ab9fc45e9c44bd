"""The `pangu_ultra_moe` decoder (openPangu-Ultra-MoE: latent attention over
one cache stack of `[c | k_rope]` rows, absorbed) through the program's
normal path, at a small size on the CPU, against the benchmark's plain
reference in the expanded form: logits, not ids. Prefill in chunks then
decode through the cache, across a chunk and a window boundary; the
absorbed against the expanded attention; lanes at unequal positions with one
parked; the prefix pool over latent rows; the file format's keys 33-37."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "benchmark")) if p not in sys.path]

from benchmark.harness import weights  # noqa: E402
from benchmark.references import pangu_ultra_moe as pangu  # noqa: E402
from helpers import tiny_pangu_config as tiny  # noqa: E402
from dllama_tpu.formats.model_file import (  # noqa: E402
    HeaderKey, LlmArch, ModelReader, layer_table, read_llm_header, tensor_plan)
from dllama_tpu.models import transformer as tf  # noqa: E402
from dllama_tpu.models.loader import load_params  # noqa: E402
from dllama_tpu.models.transformer import forward, init_kv_cache  # noqa: E402

CHUNK, SEQ = 16, 256
# f32 on both sides, but not the same sums: the program scores a query
# against the latent (`q_nope U_h^T . c`) and weighs latents before `V_h`,
# the reference rebuilds keys and values. The largest logit error read over
# these cases is 3e-5 of a logit std near 1.
TOL = 2e-4


def build(tmp_path, cfg: dict, seed: int = 3, weight_format: str = "dense"):
    path = str(tmp_path / f"{cfg['name']}-{seed}.m")
    weights.write_model(path, cfg, seed)
    reader = ModelReader(path, max_seq_len=SEQ)
    return path, reader.header, load_params(
        reader, dtype=jnp.float32, weight_format=weight_format)


def token_ids(n: int, seed: int = 0) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(0, 500, n)]


def served_logits(h, params, ids, n_prefill: int, chunk: int = CHUNK, window=None):
    """Logits of every position: chunks of `chunk` rows up to `n_prefill`,
    then a decode step a token, through the one latent stack. `window(p)`:
    the rows attention reads for a dispatch that ends at position p."""
    cache = init_kv_cache(h, 1, jnp.float32, seq_len=SEQ + chunk)
    assert set(cache) == {"c"}  # no keys or values are stored
    step = jax.jit(
        lambda toks, pos, cache, w: forward(params, h, toks, pos, cache, attn_window=w),
        static_argnums=3)
    out, p = [], 0
    while p < len(ids):
        width = chunk if p + chunk <= n_prefill else 1
        w = window(p + width) if window else 0
        logits, cache = step(jnp.asarray([ids[p:p + width]]), jnp.int32(p), cache, w)
        out.append(np.asarray(logits[0]))
        p += width
    return np.concatenate(out)


def reference_logits(path, cfg, ids):
    return np.asarray(pangu.last_logits(path, cfg, [ids], [len(ids)])[0])


def pow2_window(limit: int, floor: int = 32) -> int:
    w = floor
    while w < limit:
        w *= 2
    return w


@pytest.mark.parametrize("n,n_prefill,weight_format", [
    (24, 16, "dense"),  # one chunk, then steps
    (60, 32, "q40"),  # across a chunk boundary, from the Q40 leaves the server holds
    (150, 96, "dense"),  # chunks and steps across the windows 32, 64, 128, 256
    (70, 64, "q40"),  # the last chunk ends on a window's edge
], ids=["one-chunk", "across-chunk-q40", "across-windows", "chunk-ends-on-window-q40"])
def test_prefill_then_decode_through_the_latent_cache_equals_the_reference(
        tmp_path, n, n_prefill, weight_format):
    cfg = tiny()
    path, h, params = build(tmp_path, cfg, weight_format=weight_format)
    assert [k.cache for k in layer_table(h)] == ["latent"] * 5
    assert [k.experts for k in layer_table(h)] == [False, True, True, True, True]
    ids = token_ids(n)
    want = reference_logits(path, cfg, ids)
    got = served_logits(h, params, ids, n_prefill, window=pow2_window)
    assert np.abs(got - want).max() < TOL * want.std()


@pytest.mark.parametrize("over", [
    {"first_k_dense_replace": 0},
    {"first_k_dense_replace": 5},
    {"n_shared_experts": 0},
    {"n_routed_experts": 8},
], ids=["experts-only", "dense-only", "no-shared-expert", "every-expert-held"])
def test_each_kind_of_ffn_under_latent_attention_equals_the_reference(tmp_path, over):
    cfg = tiny(**over)
    path, h, params = build(tmp_path, cfg)
    ids = token_ids(100, seed=1)
    got = served_logits(h, params, ids, 64)
    want = reference_logits(path, cfg, ids)
    assert np.abs(got - want).max() < TOL * want.std()


@pytest.mark.parametrize("name", [n for n in pangu.FAULTS if "float8" not in n])
def test_a_fault_changes_the_references_logits(tmp_path, name):
    """Each of the family's faults moves the reference's logits by a good
    share of their std at the test widths (what the ladder then reads on
    the chip is PERF.md's), and laying none leaves them as they were."""
    cfg = tiny()
    path, _, _ = build(tmp_path, cfg)
    ids = token_ids(90, seed=2)
    sound = reference_logits(path, cfg, ids)
    wrong = reference_logits(path, {**cfg, **pangu.FAULTS[name]}, ids)
    assert np.abs(wrong - sound).max() > 0.3 * sound.std()
    assert np.array_equal(reference_logits(path, cfg, ids), sound)


def expanded_attention(q, rows, wk, wv, pos, scale):
    """The reference's form on the program's operands: keys and values of
    every head rebuilt from the cached rows, q [B, T, H, nope + rope] not
    absorbed. float64 numpy."""
    b, t, n_heads, _ = q.shape
    kvl = wk.shape[-1]
    c, kr = rows[:, 0, :, :kvl], rows[:, 0, :, kvl:]
    k_nope = np.einsum("bsc,hnc->bshn", c, wk)
    v = np.einsum("bsc,hcv->bshv", c, wv)
    k = np.concatenate([k_nope, np.broadcast_to(kr[:, :, None], (*k_nope.shape[:3], kr.shape[-1]))], -1)
    out = np.zeros((b, t, n_heads, wv.shape[-1]))
    for lane in range(b):
        for i in range(t):
            last = pos[lane] + i
            if last < 0:
                continue
            s = np.einsum("hd,shd->hs", q[lane, i], k[lane, :last + 1]) * scale
            p = np.exp(s - s.max(axis=1, keepdims=True))
            p /= p.sum(axis=1, keepdims=True)
            out[lane, i] = np.einsum("hs,shv->hv", p, v[lane, :last + 1])
    return out


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas-interpret"])
def test_the_absorbed_attention_equals_the_expanded_on_random_inputs(kernel):
    """`[q_nope U_h^T | q_rope]` against the cached rows, values = the rows'
    first columns, `V_h` once a query: the same numbers as keys and values
    rebuilt per head, to float32 rounding (1e-5 of the largest output: sums
    of 32 to 200 products in another order). Through XLA's dense path and
    through the Pallas kernel (interpret mode), lanes at their own
    positions, one parked."""
    from dllama_tpu.ops.flash_attention import latent_flash_attention

    rng = np.random.default_rng(0)
    b, t, n_heads, nope, rope, vd, kvl, s = 3, 16, 4, 16, 8, 24, 32, 128
    scale = (nope + rope) ** -0.5
    q = rng.standard_normal((b, t, n_heads, nope + rope))
    rows = rng.standard_normal((1, b, 1, s, kvl + rope))
    wk = rng.standard_normal((n_heads, nope, kvl)) / np.sqrt(kvl)
    wv = rng.standard_normal((n_heads, kvl, vd)) / np.sqrt(kvl)
    pos = np.asarray([40, 100, -1000])
    q_abs = np.concatenate(
        [np.einsum("bthn,hnc->bthc", q[..., :nope], wk), q[..., nope:]], -1)
    qa, stack = jnp.asarray(q_abs, jnp.float32), jnp.asarray(rows, jnp.float32)
    if kernel:
        weighed = latent_flash_attention(
            qa, stack, jnp.asarray(pos), layer=0, rows=s, kv_rank=kvl, scale=scale,
            block_q=32, block_s=32, interpret=True)
    else:
        weighed = tf.latent_attention_dense(qa, stack[0], jnp.asarray(pos), kvl, scale)
    got = np.einsum("bthc,hcv->bthv", np.asarray(weighed, np.float64), wv)
    want = expanded_attention(q, rows[0], wk, wv, pos, scale)
    assert not got[2].any() and not want[2].any()  # the parked lane sees nothing
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


def test_four_lanes_at_unequal_positions_one_parked(tmp_path):
    """Four lanes, each its own sequence at its own length, decode one step
    together; lane 2 is parked (its position is the park row). Every live
    lane's logits are the reference's for its sequence, and the parked
    lane's rows of the context are as they were."""
    cfg = tiny()
    path, h, params = build(tmp_path, cfg)
    lanes, park = 4, SEQ
    lengths = [5, 47, 20, 130]
    seqs = [token_ids(n + 1, seed=10 + i) for i, n in enumerate(lengths)]
    cache = init_kv_cache(h, lanes, jnp.float32, seq_len=SEQ + CHUNK)
    step = jax.jit(lambda toks, pos, cache: forward(
        params, h, toks, pos, cache, attn_park_threshold=park))
    for lane, ids in enumerate(seqs):  # lane by lane, the others parked
        p = 0
        while p < lengths[lane]:
            width = CHUNK if p + CHUNK <= lengths[lane] else 1
            toks = np.zeros((lanes, width), np.int32)
            toks[lane] = ids[p:p + width]
            pos = np.full(lanes, park, np.int32)
            pos[lane] = p
            _, cache = step(jnp.asarray(toks), jnp.asarray(pos), cache)
            p += width
    before = np.asarray(cache["c"])
    pos = np.asarray(lengths, np.int32)
    pos[2] = park
    toks = np.asarray([[ids[-1]] for ids in seqs], np.int32)
    logits, cache = step(jnp.asarray(toks), jnp.asarray(pos), cache)
    for lane, ids in enumerate(seqs):
        if lane == 2:
            continue
        want = reference_logits(path, cfg, ids)[-1]
        assert np.abs(np.asarray(logits[lane, 0]) - want).max() < TOL * want.std(), lane
    after = np.asarray(cache["c"])
    assert np.array_equal(after[:, 2, :, :SEQ], before[:, 2, :, :SEQ])
    assert not np.array_equal(after[:, 3, :, :SEQ], before[:, 3, :, :SEQ])


# -- the file format ----------------------------------------------------------


def test_header_keys_33_to_37_and_the_tensor_plan_round_trip(tmp_path):
    cfg = tiny()
    path = str(tmp_path / "m.m")
    weights.write_model(path, cfg, 3)
    h = read_llm_header(path)
    assert [int(k) for k in (HeaderKey.Q_LORA_RANK, HeaderKey.KV_LORA_RANK,
                             HeaderKey.QK_NOPE_HEAD_DIM, HeaderKey.QK_ROPE_HEAD_DIM,
                             HeaderKey.V_HEAD_DIM)] == [33, 34, 35, 36, 37]
    assert h.arch == LlmArch.PANGU_MOE and h.latent
    assert (h.q_lora_rank, h.kv_lora_rank, h.qk_nope_head_dim, h.qk_rope_head_dim,
            h.v_head_dim) == (96, 32, 16, 8, 24)
    assert (h.head_dim, h.latent_row, h.rope_dim, h.q_dim) == (24, 40, 8, 8 * 24)
    plan = {s.name: s for s in tensor_plan(h)}
    shapes = {n: plan[f"layers.1.{n}"].shape for n in
              ("wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b", "wo")}
    assert shapes == {
        "wq_a": (96, 64), "q_a_norm": (96,), "wq_b": (8 * 24, 96), "wkv_a": (40, 64),
        "kv_a_norm": (32,), "wkv_b": (8 * (16 + 24), 32), "wo": (64, 8 * 24)}
    assert "layers.1.q" not in plan and "layers.1.expert_bias" not in plan
    assert "layers.0.w1" in plan and "layers.1.shared.w1" in plan
    assert list(plan)[-1] == "wcls"
    reader = ModelReader(path)  # the file ends where the plan ends
    # the loader's two per-head stacks are `wkv_b`, cut and turned
    params = load_params(reader, dtype=jnp.float32)
    whole = reader.dense_f32("layers.1.wkv_b").reshape(8, 40, 32)
    assert np.array_equal(np.asarray(params["layers"]["wkv_b_k"][1]), whole[:, :16])
    assert np.array_equal(
        np.asarray(params["layers"]["wkv_b_v"][1]), whole[:, 16:].transpose(0, 2, 1))
    # a model without the keys reads 0 for each: what every other model means
    from helpers import make_tiny_model

    plain = str(tmp_path / "plain.m")
    make_tiny_model(plain)
    h0 = read_llm_header(plain)
    assert not h0.latent and h0.rope_dim == h0.head_dim
    assert not any(k.latent for k in layer_table(h0))


# -- through the engine: lanes, the pool over latent rows ---------------------


@pytest.fixture(scope="module")
def lanes(tmp_path_factory):
    from helpers import make_tiny_pangu

    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path_factory.mktemp("pangu") / "m.m")
    cfg = make_tiny_pangu(path)
    e = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0, batch_size=4,
                        prefill_buckets=(1, CHUNK), max_seq_len=SEQ)
    return e, cfg, path


def lane_logits(e, lane: int, token: int, pos: int):
    toks = np.zeros((e.batch_size, 1), np.int32)
    toks[lane] = token
    posv = np.full(e.batch_size, e._park, np.int32)
    posv[lane] = pos
    logits, _ = e._fwd(e.params, jnp.asarray(toks), jnp.asarray(posv), e.cache,
                       attn_window=e._attn_window(pos + 1),
                       attn_park_threshold=e._park, logits_mode="last")
    return np.asarray(logits[lane, 0])


def test_the_cache_is_one_stack_of_latent_rows_and_the_gauge_says_its_bytes(lanes):
    e, _, _ = lanes
    h = e.header
    assert set(e.cache) == {"c"}
    assert e.cache["c"].shape == (5, 4, 1, SEQ + CHUNK, 40)
    per_row = h.latent_row * 4  # f32 here; 1152 B at the published widths in bf16
    assert e.kv_cache_bytes == {
        "full": 0, "window": 0, "latent": per_row * (SEQ + CHUNK) * 5 * 4}
    (event,) = e.recorder.events("kv_cache")[-1:]
    assert event["latent_bytes"] == e.kv_cache_bytes["latent"]
    assert e.obs.render().count('dllama_kv_cache_bytes{kind="latent"}') == 1
    # the smallest window is the latent floor, never more than the context
    assert e._attn_window(1) == min(4096, SEQ) and e._attn_window(SEQ) == SEQ


def test_the_latent_window_floor_is_4096(lanes, monkeypatch):
    e, _, _ = lanes
    monkeypatch.setattr(e.header, "seq_len", 16384)
    assert [e._attn_window(n) for n in (1, 4096, 4097, 8193)] == [4096, 4096, 8192, 16384]


def test_an_adopted_prefix_gives_the_logits_of_the_request_served_without_it(lanes):
    from dllama_tpu.kv.manager import PagedKVManager

    e, cfg, path = lanes
    kv = PagedKVManager(e, page_size=4, n_pages=80)
    assert set(e.kv_pool) == {"c"} and e.kv_pool["c"].shape == (5, 80, 1, 4, 40)
    first = token_ids(40, seed=21)
    e.prefill_lane(0, first)
    assert kv.publish(0, first[:39]) == 9
    second = first[:30] + token_ids(12, seed=22)
    m, pages = kv.match(1, second)
    assert m == 30 and len(pages) == 8
    kv.adopt(1, pages)
    e.prefill_lane(1, second[m:], pos0=m)
    e.prefill_lane(2, second)  # the same request with nothing adopted
    adopted = lane_logits(e, 1, second[-1], len(second) - 1)
    plain = lane_logits(e, 2, second[-1], len(second) - 1)
    assert np.abs(adopted - plain).max() < 1e-5  # the same rows, copied
    want = reference_logits(path, cfg, second)[-1]
    assert np.abs(adopted - want).max() < TOL * want.std()
    kv.release_lane(1)
    # no ring: a prompt longer than any smaller window is published whole
    long = token_ids(200, seed=23)
    e.prefill_lane(3, long)
    assert e.kv_publishable(199) == 199
    assert kv.publish(3, long[:199]) == 49
    assert kv.match(0, long[:150] + [1, 2, 3])[0] == 150


def test_dispatches_carry_the_latent_rows_and_the_routed_pairs(lanes):
    e, _, _ = lanes
    e.prefill_lane(0, token_ids(60, seed=24))
    e.prefill_lane(1, token_ids(20, seed=25))
    chunk = [d for d in e.recorder.events("step_dispatch")
             if d["step"] == "prefill_lane_chunk"][-1]
    assert {"rows_latent", "n_tokens", "pos", "bucket", "window"} <= set(chunk)
    n0 = len(e.recorder.events("moe_route"))
    out = e.decode_lanes([5, 6, 0, 0], [59, 19, 0, 0], 4, active=[True, True, False, False])
    assert np.asarray(out).shape == (4, 4)
    (event,) = e.recorder.events("moe_route")[n0:]
    assert event["pairs_routed"] == 4 * 2 * 2 * 4  # steps x lanes x k x expert layers
    assert 0 < event["pairs_held"] < event["pairs_routed"]
    dispatch = [d for d in e.recorder.events("step_dispatch") if d["step"] == "decode_lanes"][-1]
    assert dispatch["rows_latent"] == sum(p + i + 1 for p in (59, 19) for i in range(4))
    assert "rows_full" not in dispatch


@pytest.mark.parametrize("kwargs,named", [
    ({"tp": 2}, "--tp 2"), ({"sp": 2}, "--sp 2"), ({"pp": 2}, "--pp 2"),
    ({"dp": 2}, "--dp 2"), ({"kv_dtype": "int8"}, "--kv-dtype int8"),
])
def test_what_a_latent_cache_does_not_run_under_fails_at_start_up(lanes, kwargs, named):
    from dllama_tpu.runtime.engine import InferenceEngine

    _, _, path = lanes
    with pytest.raises(ValueError, match=named + ".*latent"):
        InferenceEngine(path, **{"tp": 1, "dtype": jnp.float32, "batch_size": 2,
                                 "max_seq_len": SEQ, **kwargs})


def test_pool_native_pages_and_speculation_are_refused_by_name(lanes):
    e, _, _ = lanes
    with pytest.raises(ValueError, match="--kv-native.*latent"):
        e.init_kv_pool(4, 40, native=True)
    with pytest.raises(ValueError, match="--speculation.*latent"):
        e.rehearse_admission(4, spec_k=4)


@pytest.mark.parametrize("n_prompt", [100, 200])
def test_a_prompt_through_a_middle_rung_leaves_what_the_largest_rung_leaves(lanes, n_prompt):
    """The served ladder's rungs at 128 and 256 rows (PR 49) against the 512
    this family's chunk program always ran at: cache rows, lane states and
    the next token's logits of one prompt through either."""
    from helpers import assert_a_middle_rung_equals_the_largest

    assert_a_middle_rung_equals_the_largest(lanes[2], n_prompt)
