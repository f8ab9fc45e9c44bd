"""The `deepseek_v32` decoder (DeepSeek-V3.2: latent attention over the rows
a learned index picks, a second cache stack of index keys, a router limited
to some of its groups, a rotary table scaled by band) through the program's
normal path, at a small size on the CPU, against the benchmark's plain
reference: logits, not ids. Prefill in chunks then decode through both
stacks, across a chunk boundary, across `index_topk` and across a window;
the selection against `lax.top_k`; the masked kernel against XLA; lanes at
unequal positions with one parked; the prefix pool over both stacks; the
router's group limit; the rotary table; the file format's keys 38-46."""

from __future__ import annotations

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "benchmark")) if p not in sys.path]

from benchmark.harness import weights  # noqa: E402
from benchmark.references import deepseek_v32 as dsv32  # noqa: E402
from helpers import DSV32_TOPK as TOPK  # noqa: E402
from helpers import tiny_dsv32_config as tiny  # noqa: E402
from dllama_tpu.formats.model_file import (  # noqa: E402
    HeaderKey, LlmArch, LlmHeader, ModelReader, RopeType, layer_table, read_llm_header,
    tensor_plan)
from dllama_tpu.models import transformer as tf  # noqa: E402
from dllama_tpu.models.loader import load_params  # noqa: E402
from dllama_tpu.models.transformer import forward, init_kv_cache  # noqa: E402
from dllama_tpu.ops import jnp_ops  # noqa: E402
from dllama_tpu.ops.sparse_index import index_scores, select_rows  # noqa: E402

CHUNK, SEQ = 16, 256
# f32 on both sides, but not the same sums (absorbed against expanded, as
# tests/test_pangu_mla.py says); the largest logit error read over these
# cases is 2e-5 of a logit std. A selection that differed in one row would
# read a hundred times that.
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
    then a decode step a token, through the two stacks. `window(p)`: the
    rows attention reads for a dispatch that ends at position p."""
    cache = init_kv_cache(h, 1, jnp.float32, seq_len=SEQ + chunk)
    assert set(cache) == {"c", "i"}
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
    return np.asarray(dsv32.last_logits(path, cfg, [ids], [len(ids)])[0])


def pow2_window(limit: int, floor: int = 32) -> int:
    w = floor
    while w < limit:
        w *= 2
    return w


@pytest.mark.parametrize("n,n_prefill,weight_format", [
    (14, 0, "dense"),  # steps alone, every row taken: fewer than index_topk
    (24, 16, "dense"),  # one chunk that ends on index_topk, then steps past it
    (60, 32, "q40"),  # across a chunk boundary, from the Q40 leaves the server holds
    (150, 96, "dense"),  # chunks and steps across the windows 32, 64, 128, 256
    (70, 64, "q40"),  # the last chunk ends on a window's edge
], ids=["below-topk", "one-chunk", "across-chunk-q40", "across-windows",
        "chunk-ends-on-window-q40"])
def test_prefill_then_decode_through_both_stacks_equals_the_reference(
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
    {"n_shared_experts": 0},
    {"n_routed_experts": 8},
    {"n_group": 1, "topk_group": 1},
    {"index_topk": 40},
    {"rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                      "mscale_all_dim": 1, "original_max_position_embeddings": 64,
                      "type": "yarn"}},  # the table's own magnitude is not 1
], ids=["experts-only", "no-shared-expert", "every-expert-held", "one-group", "topk-40",
        "unequal-mscales"])
def test_each_variant_of_the_configuration_equals_the_reference(tmp_path, over):
    cfg = tiny(**over)
    path, h, params = build(tmp_path, cfg)
    ids = token_ids(100, seed=1)
    got = served_logits(h, params, ids, 64)
    want = reference_logits(path, cfg, ids)
    assert np.abs(got - want).max() < TOL * want.std()


@pytest.mark.parametrize("name", [n for n in dsv32.FAULTS if "float8" not in n])
def test_a_fault_changes_the_references_logits(tmp_path, name):
    """Each of the family's faults moves the reference's logits by a good
    share of their std at the test widths (what the ladder then reads on
    the chip is PERF.md's), and laying none leaves them as they were. The
    faults' `min_prompt` is the published index_topk's; here 16 rows are
    kept of 90."""
    cfg = tiny()
    path, _, _ = build(tmp_path, cfg)
    ids = token_ids(90, seed=2)
    sound = reference_logits(path, cfg, ids)
    fault = dict(dsv32.FAULTS[name])
    if "index_topk" in fault:
        fault["index_topk"] = TOPK // 2
    wrong = reference_logits(path, {**cfg, **fault}, ids)
    assert np.abs(wrong - sound).max() > 0.2 * sound.std()
    assert np.array_equal(reference_logits(path, cfg, ids), sound)


def test_the_float8_control_is_a_fault_of_precision_alone(tmp_path):
    cfg = tiny()
    path, _, _ = build(tmp_path, cfg)
    ids = token_ids(60, seed=2)
    sound = reference_logits(path, cfg, ids)
    lossy = reference_logits(path, {**cfg, **dsv32.FAULTS["activations in float8"]}, ids)
    assert 0.01 * sound.std() < np.abs(lossy - sound).max()


# -- the index and the selection ----------------------------------------------


def brute_scores(qi, w, keys):
    dots = np.einsum("tjd,sd->tjs", qi.astype(np.float64), keys.astype(np.float64))
    return np.einsum("tjs,tj->ts", np.maximum(dots, 0.0), w.astype(np.float64))


@pytest.mark.parametrize("t,s", [(1, 64), (16, 128), (48, 96)], ids=["step", "chunk", "blocks"])
def test_index_scores_are_the_weighted_relu_sum(t, s, monkeypatch):
    from dllama_tpu.ops import sparse_index

    rng = np.random.default_rng(t)
    qi = rng.standard_normal((t, 4, 16)).astype(np.float32)
    w = rng.standard_normal((t, 4)).astype(np.float32)
    keys = rng.standard_normal((s, 16)).astype(np.float32)
    if t == 48:  # three blocks of 16 queries
        monkeypatch.setattr(sparse_index, "_SCORE_BLOCK_BYTES", 16 * 4 * 4 * s)
    got = np.asarray(index_scores(jnp.asarray(qi), jnp.asarray(w), jnp.asarray(keys)))
    want = brute_scores(qi, w, keys)
    assert got.shape == (t, s) and np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    assert (want < 0).any()  # a head's weight may be negative: scores of either sign


@pytest.mark.parametrize("n", [12, 36], ids=["few-queries", "many-queries"])
@pytest.mark.parametrize("case", ["random", "ties", "all-equal", "negative"])
def test_the_selection_is_top_k_of_the_rows_seen_ties_to_the_lower_row(case, n):
    rng = np.random.default_rng(5)
    s, k = 96, 16
    scores = rng.standard_normal((n, s)).astype(np.float32)
    if case == "ties":
        scores = np.round(scores * 2) / 2
    elif case == "all-equal":
        scores[:] = 0.25
    elif case == "negative":
        scores = -np.abs(scores) - 1.0
    pos = np.tile(np.asarray([95, 95, 40, 15, 14, 3, 0, -1, -200, 60, 16, 17], np.int32), n // 12)
    keep = np.asarray(select_rows(jnp.asarray(scores), jnp.asarray(pos), k))
    for row in range(n):
        seen = max(0, int(pos[row]) + 1)
        want = np.zeros(s, bool)
        if seen:
            _, idx = jax.lax.top_k(jnp.asarray(scores[row, :seen]), min(k, seen))
            want[np.asarray(idx)] = True
        assert np.array_equal(keep[row], want), (case, row)
        assert keep[row].sum() == min(k, seen)


def test_the_programs_selected_set_is_the_references_at_f32(tmp_path):
    """A layer's index on the model's own weights: `index_keep` over the
    keys a prefill wrote picks, for every query, the rows the reference's
    `selection` picks from the same inputs."""
    cfg = tiny()
    path, h, params = build(tmp_path, cfg)
    n = 80
    rng = np.random.default_rng(9)
    y = jnp.asarray(rng.standard_normal((n, 64)), jnp.float32)
    cq = jnp.asarray(rng.standard_normal((n, 96)), jnp.float32)
    f = dsv32.Q40File(path)
    w = dsv32.layer_weights(f, 1, cfg)
    pad = -n % dsv32.QB
    want = np.asarray(dsv32.selection(
        jnp.pad(y, ((0, pad), (0, 0))), None, jnp.pad(cq, ((0, pad), (0, 0))), w,
        dict(dsv32.statics(cfg))))[:n, :n]
    lp = {k: v[1] for k, v in params["layers"].items() if k.startswith("idx_")}
    cos, sin = (jnp.asarray(a[:n]) for a in jnp_ops.rope_cache(h, n))
    rd = h.rope_dim

    def turned(z):
        return jnp.concatenate(
            [jnp_ops.apply_rope(z[..., :rd], cos, sin, False), z[..., rd:]], axis=-1)

    qi = turned((cq @ lp["idx_wq_b"]).reshape(1, n, 4, 16))
    kf = y @ lp["idx_wk"]
    kf = kf - kf.mean(-1, keepdims=True)
    kf = kf * jax.lax.rsqrt((kf * kf).mean(-1, keepdims=True) + h.norm_epsilon)
    ki = turned((kf * lp["idx_k_norm"] + lp["idx_k_bias"])[None, :, None, :])
    wj = (y @ lp["idx_w"])[None] * (4 ** -0.5 * 16 ** -0.5)
    stack = jnp.zeros((2, 1, 1, 128, 16), jnp.float32).at[1, 0, 0, :n].set(ki[0, :, 0])
    keep = np.asarray(tf.index_keep(qi, wj, stack, 1, jnp.int32(0), TOPK, 128))
    assert keep.shape == (1, n, 128) and not keep[0, :, n:].any()
    assert np.array_equal(keep[0, :, :n], want)
    assert [int(r.sum()) for r in keep[0]] == [min(TOPK, t + 1) for t in range(n)]


def test_with_index_topk_at_least_the_rows_the_path_is_the_unindexed_one(tmp_path):
    """`index_topk >= rows`: every row a query sees is taken, and the
    program is the latent path without an index, the index left unread:
    its logits equal, bit for bit, those of the same weights served with
    the selection switched off in `_attention_latent`'s arguments, and the
    reference's with the selection ignored. The mask of all rows seen
    changes no number either."""
    cfg = tiny(index_topk=SEQ + CHUNK)
    path, h, params = build(tmp_path, cfg)
    ids = token_ids(70, seed=4)
    got = served_logits(h, params, ids, 48)
    want = reference_logits(path, {**cfg, "fault_dense": True}, ids)
    assert np.abs(got - want).max() < TOL * want.std()
    calls = []
    real = tf.index_keep
    try:
        tf.index_keep = lambda *a, **k: calls.append(1) or real(*a, **k)
        again = served_logits(h, params, ids, 48)
    finally:
        tf.index_keep = real
    assert not calls and np.array_equal(again, got)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 8, 4, 40)), jnp.float32)
    rows = jnp.asarray(rng.standard_normal((2, 1, 64, 40)), jnp.float32)
    pos = jnp.asarray([30, 5], jnp.int32)
    scores = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    q_pos = (pos[:, None] + jnp.arange(8)[None, :]).reshape(-1)
    keep = select_rows(scores, q_pos, 64).reshape(2, 8, 64)
    assert np.array_equal(np.asarray(keep), np.asarray(jnp.arange(64)[None, None] <= q_pos.reshape(2, 8, 1)))
    assert np.array_equal(
        np.asarray(tf.latent_attention_dense(q, rows, pos, 32, 0.2, keep=keep)),
        np.asarray(tf.latent_attention_dense(q, rows, pos, 32, 0.2)))


@pytest.mark.parametrize("one_mask", [False, True], ids=["a-mask-a-lane", "one-lanes-mask"])
def test_the_masked_kernel_interpreted_equals_xla(one_mask):
    """`latent_flash_attention` with the selection's mask against the dense
    XLA path: lanes at their own positions, one parked; blocks of whole
    positions, some block wholly masked for some query."""
    from dllama_tpu.ops.flash_attention import latent_flash_attention

    rng = np.random.default_rng(1)
    b, t, n_heads, w, s, kvl = 3, 16, 8, 40, 256, 32
    q = jnp.asarray(rng.standard_normal((b, t, n_heads, w)), jnp.float32)
    stack = jnp.asarray(rng.standard_normal((2, b, 1, s, w)), jnp.float32)
    pos = jnp.asarray([-s, 100, -s] if one_mask else [40, 100, -s], jnp.int32)
    keep = jnp.asarray(rng.random((1 if one_mask else b, t, s)) < 0.2)
    keep = keep.at[:, :, 128:].set(False) if one_mask else keep
    got = latent_flash_attention(
        q, stack, pos, layer=1, rows=s, kv_rank=kvl, scale=0.2, block_q=64, block_s=128,
        interpret=True, keep=keep)
    want = jnp.concatenate([
        tf.latent_attention_dense(
            q[ln:ln + 1], stack[1, ln:ln + 1], pos[ln], kvl, 0.2,
            keep=keep[0 if one_mask else ln][None])
        for ln in range(b)])
    assert not np.asarray(got[2]).any()
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    with pytest.raises(ValueError, match="whole positions"):
        latent_flash_attention(
            q, stack, pos, layer=1, rows=s, kv_rank=kvl, scale=0.2, block_q=4,
            block_s=128, interpret=True, keep=keep)


def test_four_lanes_at_unequal_positions_one_parked(tmp_path):
    """Four lanes, each its own sequence at its own length (below and past
    index_topk), decode one step together; lane 2 is parked. Every live
    lane's logits are the reference's for its sequence, and the parked
    lane's rows of both stacks are as they were."""
    cfg = tiny()
    path, h, params = build(tmp_path, cfg)
    lanes, park = 4, SEQ
    lengths = [5, 47, 20, 130]
    seqs = [token_ids(n + 1, seed=10 + i) for i, n in enumerate(lengths)]
    cache = init_kv_cache(h, lanes, jnp.float32, seq_len=SEQ + CHUNK)
    step = jax.jit(lambda toks, pos, cache, lone: forward(
        params, h, toks, pos, cache, attn_park_threshold=park, live_lanes_alone=lone),
        static_argnums=3)
    for lane, ids in enumerate(seqs):  # lane by lane, the others parked
        p = 0
        while p < lengths[lane]:
            width = CHUNK if p + CHUNK <= lengths[lane] else 1
            toks = np.zeros((lanes, width), np.int32)
            toks[lane] = ids[p:p + width]
            pos = np.full(lanes, park, np.int32)
            pos[lane] = p
            _, cache = step(jnp.asarray(toks), jnp.asarray(pos), cache, width > 1)
            p += width
    before = {k: np.asarray(v) for k, v in cache.items()}
    pos = np.asarray(lengths, np.int32)
    pos[2] = park
    toks = np.asarray([[ids[-1]] for ids in seqs], np.int32)
    logits, cache = step(jnp.asarray(toks), jnp.asarray(pos), cache, False)
    for lane, ids in enumerate(seqs):
        if lane == 2:
            continue
        want = reference_logits(path, cfg, ids)[-1]
        assert np.abs(np.asarray(logits[lane, 0]) - want).max() < TOL * want.std(), lane
    for name in ("c", "i"):
        after = np.asarray(cache[name])
        assert np.array_equal(after[:, 2, :, :SEQ], before[name][:, 2, :, :SEQ])
        assert not np.array_equal(after[:, 3, :, :SEQ], before[name][:, 3, :, :SEQ])


# -- the router's group limit ---------------------------------------------------


def old_route(x_flat, gate_w, route, bias=None):
    """`_moe_route` as it stood before the group limit, line for line."""
    from jax import lax

    logits = jnp.einsum(
        "...d,de->...e", x_flat.astype(jnp.float32), gate_w.astype(jnp.float32))
    if route.sigmoid:
        scores = jax.nn.sigmoid(logits)
        _, top_i = lax.top_k(
            scores if bias is None else scores + bias.astype(jnp.float32), route.n_active)
        top_p = jnp.take_along_axis(scores, top_i, axis=-1)
        weights = (top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)
                   if route.norm else top_p)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = lax.top_k(probs, route.n_active)
        weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True) if route.norm else top_p
    if route.scale != 1.0:
        weights = weights * route.scale
    return top_i, weights


@pytest.mark.parametrize("sigmoid,norm,scale,biased", [
    (False, True, 1.0, False), (False, False, 1.0, False), (True, True, 2.448, True),
    (True, True, 2.5, False), (True, False, 1.0, True),
], ids=["softmax", "softmax-raw", "sigmoid-bias", "sigmoid", "sigmoid-bias-raw"])
def test_one_group_is_todays_router_bit_for_bit(sigmoid, norm, scale, biased):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((40, 64)), jnp.float32)
    gate = jnp.asarray(rng.standard_normal((64, 16)) / 8, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(16) * 0.05, jnp.float32) if biased else None
    route = tf.Routing(4, sigmoid, norm, scale, 0, 16, 16)
    assert (route.n_group, route.topk_group) == (1, 1)
    for got, want in zip(tf._moe_route(x, gate, route, bias), old_route(x, gate, route, bias)):
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_the_group_limit_keeps_the_groups_of_the_two_best_biased_scores():
    rng = np.random.default_rng(4)
    n, d, e, groups, kept, k = 60, 64, 16, 4, 2, 4
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    gate = jnp.asarray(rng.standard_normal((d, e)) / 8, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(e) * 0.3, jnp.float32)
    route = tf.Routing(k, True, True, 2.5, 0, e, e, groups, kept)
    top_i, wts = (np.asarray(a) for a in tf._moe_route(x, gate, route, bias))
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64) @ np.asarray(gate, np.float64))))
    sb = s + np.asarray(bias, np.float64)
    differs = 0
    for row in range(n):
        g = np.sort(sb[row].reshape(groups, -1), axis=1)[:, -2:].sum(axis=1)
        stay = np.argsort(-g, kind="stable")[:kept]
        masked = np.where(np.isin(np.arange(e) // (e // groups), stay), sb[row], 0.0)
        want = np.argsort(-masked, kind="stable")[:k]
        assert sorted(top_i[row]) == sorted(want)
        assert {i // (e // groups) for i in top_i[row]} <= set(stay)
        w = s[row][top_i[row]]
        assert np.allclose(wts[row], 2.5 * w / w.sum(), rtol=1e-5)
        differs += sorted(np.argsort(-sb[row], kind="stable")[:k]) != sorted(want)
    assert differs > 5  # the limit changes the choice for a good share of tokens
    h = LlmHeader(n_active_experts=k, score_sigmoid=True, route_scale=2.5, n_experts=4,
                  n_routed_experts=e, n_group=groups, topk_group=kept)
    assert tf.routing_of(h) == tf.Routing(k, True, True, 2.5, 0, 4, e, groups, kept)


# -- the rotary table ----------------------------------------------------------


def test_the_scaled_rotary_table_is_the_formula_and_factor_1_the_plain_table():
    h = LlmHeader(
        dim=64, n_heads=8, head_dim=192, kv_lora_rank=512, qk_rope_head_dim=64,
        qk_nope_head_dim=128, rope_theta=10000.0, rope_type=RopeType.YARN,
        rope_scaling_factor=40.0, rope_scaling_orig_max_seq_len=4096,
        rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=1.0, rope_mscale_all_dim=1.0)
    freqs = jnp_ops.rope_frequencies(h)
    d = np.arange(32)
    plain = 10000.0 ** (-2.0 * d / 64)
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    ramp = np.clip((d - low) / (high - low), 0, 1)
    want = (1 - ramp) * plain + ramp * plain / 40
    assert np.allclose(freqs, want, rtol=1e-6)
    assert np.allclose(freqs[:11], plain[:11]) and np.allclose(freqs[23:], plain[23:] / 40)
    cos, sin = jnp_ops.rope_cache(h, 8)
    assert np.allclose(cos, np.cos(np.arange(8)[:, None] * want[None]), atol=1e-6)
    # the softmax scale carries m^2, m = 0.1 ln 40 + 1; the table's own factor is 1
    m = 0.1 * math.log(40) + 1
    assert abs(m - 1.3689) < 1e-4 and abs(h.softmax_scale - 192 ** -0.5 * m * m) < 1e-9
    assert abs(h.softmax_scale - 0.1352) < 1e-4
    h.rope_mscale = 0.707
    assert np.allclose(jnp_ops.rope_cache(h, 8)[0], cos * (0.1 * 0.707 * math.log(40) + 1) / m,
                       atol=1e-6)
    h.rope_mscale, h.rope_scaling_factor = 1.0, 1.0
    assert np.allclose(jnp_ops.rope_frequencies(h), plain, rtol=1e-6)
    h.rope_type = RopeType.LLAMA
    assert np.array_equal(jnp_ops.rope_cache(h, 8)[0], jnp_ops.rope_cache(
        LlmHeader(dim=64, n_heads=8, head_dim=192, kv_lora_rank=512, qk_rope_head_dim=64,
                  rope_theta=10000.0, rope_type=RopeType.YARN), 8)[0])
    assert h.softmax_scale == 192 ** -0.5
    # the reference's table is the same numbers
    kw = dict(dsv32.statics({**tiny(), "qk_rope_head_dim": 64, "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn"}}))
    assert np.allclose(np.asarray(dsv32.rope_frequencies(kw)), want, rtol=1e-6)


# -- the file format ------------------------------------------------------------


def test_header_keys_38_to_46_and_the_tensor_plan_round_trip(tmp_path):
    cfg = tiny()
    path = str(tmp_path / "m.m")
    weights.write_model(path, cfg, 3)
    h = read_llm_header(path)
    assert [int(k) for k in (
        HeaderKey.INDEX_N_HEADS, HeaderKey.INDEX_HEAD_DIM, HeaderKey.INDEX_TOPK,
        HeaderKey.N_GROUP, HeaderKey.TOPK_GROUP, HeaderKey.ROPE_BETA_FAST,
        HeaderKey.ROPE_BETA_SLOW, HeaderKey.ROPE_MSCALE_MILLI,
        HeaderKey.ROPE_MSCALE_ALL_DIM_MILLI)] == list(range(38, 47))
    assert h.arch == LlmArch.DEEPSEEK_V32 and h.latent and h.indexed
    assert (h.index_n_heads, h.index_head_dim, h.index_topk) == (4, 16, TOPK)
    assert (h.n_group, h.topk_group) == (4, 2)
    assert h.rope_type == RopeType.YARN and h.rope_scaling_factor == 40.0
    assert (h.rope_scaling_orig_max_seq_len, h.rope_beta_fast, h.rope_beta_slow,
            h.rope_mscale, h.rope_mscale_all_dim) == (64, 32.0, 1.0, 1.0, 1.0)
    assert h.norm_epsilon == 1e-6
    plan = {s.name: s for s in tensor_plan(h)}
    names = [n.split(".", 2)[2] for n in plan if n.startswith("layers.1.") and "experts" not in n]
    assert names == [
        "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b", "wo", "idx_wq_b",
        "idx_wk", "idx_k_norm", "idx_k_bias", "idx_w", "moe_gate", "expert_bias",
        "shared.w1", "shared.w2", "shared.w3", "att_norm", "ffn_norm"]
    shapes = {n: plan[f"layers.1.{n}"].shape for n in
              ("idx_wq_b", "idx_wk", "idx_k_norm", "idx_k_bias", "idx_w", "expert_bias")}
    assert shapes == {"idx_wq_b": (4 * 16, 96), "idx_wk": (16, 64), "idx_k_norm": (16,),
                      "idx_k_bias": (16,), "idx_w": (4, 64), "expert_bias": (8,)}
    assert plan["layers.0.w1"].shape == (160, 64)  # the dense layer, intermediate_size wide
    assert "layers.1.post_att_norm" not in plan and list(plan)[-1] == "wcls"
    reader = ModelReader(path)  # the file ends where the plan ends
    params = load_params(reader, dtype=jnp.float32)
    assert np.array_equal(
        np.asarray(params["layers"]["idx_w"][1]), reader.dense_f32("layers.1.idx_w").T)
    assert params["layers"]["idx_k_bias"].shape == (5, 16)
    # a model without the keys reads what every other model means by them
    from helpers import make_tiny_pangu

    plain = str(tmp_path / "plain.m")
    make_tiny_pangu(plain)
    h0 = read_llm_header(plain)
    assert not h0.indexed and (h0.n_group, h0.topk_group) == (1, 1)
    assert h0.rope_type == RopeType.FALCON and h0.softmax_scale == 24 ** -0.5
    assert set(init_kv_cache(h0, 1, jnp.float32, seq_len=8)) == {"c"}
    assert not any(s.name.startswith("layers.1.idx_") for s in tensor_plan(h0))


@pytest.mark.parametrize("header,named", [
    ({"index_head_dim": 4}, "index_head_dim"),
    ({"kv_lora_rank": 0}, "latent"),
    ({"topk_group": 5}, "groups"),
    ({"n_group": 3}, "groups"),
], ids=["index-narrower-than-rope", "index-without-latent", "more-groups-kept-than-are",
        "groups-that-do-not-divide"])
def test_a_header_that_cannot_be_served_is_refused_at_the_read(tmp_path, header, named):
    cfg = tiny()
    cfg["file"]["header"].update(header)
    if "kv_lora_rank" in header:
        for k in ("q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"):
            cfg["file"]["header"][k] = 0
    with pytest.raises(ValueError, match=named):
        weights.write_model(str(tmp_path / "bad.m"), cfg, 3)


# -- through the engine: lanes, the pool over both stacks ------------------------


@pytest.fixture(scope="module")
def lanes(tmp_path_factory):
    from helpers import make_tiny_dsv32

    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path_factory.mktemp("dsv32") / "m.m")
    cfg = make_tiny_dsv32(path)
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


def test_the_cache_is_two_stacks_and_the_gauge_says_each_ones_bytes(lanes):
    e, _, _ = lanes
    assert set(e.cache) == {"c", "i"}
    assert e.cache["c"].shape == (5, 4, 1, SEQ + CHUNK, 40)
    assert e.cache["i"].shape == (5, 4, 1, SEQ + CHUNK, 16)
    rows = (SEQ + CHUNK) * 5 * 4
    assert e.kv_cache_bytes == {
        "full": 0, "window": 0, "latent": 40 * 4 * rows, "index": 16 * 4 * rows}
    (event,) = e.recorder.events("kv_cache")[-1:]
    assert event["index_bytes"] == e.kv_cache_bytes["index"]
    text = e.obs.render()
    assert text.count('dllama_kv_cache_bytes{kind="index"}') == 1
    assert text.count('dllama_kv_cache_bytes{kind="latent"}') == 1
    assert e._attn_window(1) == min(4096, SEQ)


def test_an_adopted_prefix_gives_the_logits_of_the_request_served_without_it(lanes):
    """The index keys travel with the pages: a lane that adopts a published
    prefix selects, past index_topk, the rows an unshared lane selects."""
    from dllama_tpu.kv.manager import PagedKVManager

    e, cfg, path = lanes
    kv = PagedKVManager(e, page_size=4, n_pages=80)
    assert set(e.kv_pool) == {"c", "i"}
    assert e.kv_pool["c"].shape == (5, 80, 1, 4, 40)
    assert e.kv_pool["i"].shape == (5, 80, 1, 4, 16)
    first = token_ids(40, seed=21)
    e.prefill_lane(0, first)
    assert kv.publish(0, first[:39]) == 9
    second = first[:30] + token_ids(12, seed=22)
    m, pages = kv.match(1, second)
    assert m == 30 and len(pages) == 8
    kv.adopt(1, pages)
    assert np.array_equal(np.asarray(e.cache["i"][:, 1, :, :28]),
                          np.asarray(e.cache["i"][:, 0, :, :28]))
    assert np.asarray(e.cache["i"][:, 1, :, :28]).any()
    e.prefill_lane(1, second[m:], pos0=m)
    e.prefill_lane(2, second)  # the same request with nothing adopted
    adopted = lane_logits(e, 1, second[-1], len(second) - 1)
    plain = lane_logits(e, 2, second[-1], len(second) - 1)
    assert np.abs(adopted - plain).max() < 1e-5  # the same rows, copied
    want = reference_logits(path, cfg, second)[-1]
    assert np.abs(adopted - want).max() < TOL * want.std()
    kv.release_lane(1)
    long = token_ids(200, seed=23)
    e.prefill_lane(3, long)
    assert e.kv_publishable(199) == 199
    assert kv.publish(3, long[:199]) == 49


def test_dispatches_carry_the_rows_selected_and_the_tokens_landed(lanes):
    e, _, _ = lanes
    e.prefill_lane(0, token_ids(60, seed=24))
    e.prefill_lane(1, token_ids(10, seed=25))
    chunks = [d for d in e.recorder.events("step_dispatch")
              if d["step"] == "prefill_lane_chunk" and d["lane"] == 0][-4:]
    first = [c for c in chunks if c["pos"] == 0 and c["n_tokens"] == CHUNK][-1]
    assert first["rows_latent"] == sum(range(1, CHUNK + 1)) == first["rows_selected"]
    second = [c for c in chunks if c["pos"] == CHUNK and c["n_tokens"] == CHUNK][-1]
    assert second["rows_latent"] == sum(range(CHUNK + 1, 2 * CHUNK + 1))
    assert second["rows_selected"] == CHUNK * TOPK
    n0 = len(e.recorder.events("moe_route"))
    out = e.decode_lanes([5, 6, 0, 0], [59, 9, 0, 0], 4, active=[True, True, False, False])
    assert np.asarray(out).shape == (4, 4)
    (event,) = e.recorder.events("moe_route")[n0:]
    assert event["pairs_routed"] == 4 * 2 * 2 * 4  # steps x lanes x k x expert layers
    assert 0 < event["pairs_held"] < event["pairs_routed"]
    # a token lands with one or two of its two pairs: between half the pairs and all
    assert event["pairs_held"] / 2 <= event["tokens_landed"] <= event["pairs_held"]
    assert event["tokens_landed"] <= 4 * 2 * 4
    dispatch = [d for d in e.recorder.events("step_dispatch") if d["step"] == "decode_lanes"][-1]
    seen = [p + i + 1 for p in (59, 9) for i in range(4)]
    assert dispatch["rows_latent"] == sum(seen)
    assert dispatch["rows_selected"] == sum(min(r, TOPK) for r in seen) == 4 * TOPK + 10 + 11 + 12 + 13
    text = e.obs.render()
    assert 'dllama_attn_index_rows_total{kind="scored"}' in text
    assert 'dllama_attn_index_rows_total{kind="selected"}' in text


@pytest.mark.parametrize("kwargs,named", [
    ({"tp": 2}, "--tp 2"), ({"sp": 2}, "--sp 2"), ({"pp": 2}, "--pp 2"),
    ({"dp": 2}, "--dp 2"), ({"kv_dtype": "int8"}, "--kv-dtype int8"),
])
def test_what_the_two_stacks_do_not_run_under_fails_at_start_up(lanes, kwargs, named):
    from dllama_tpu.runtime.engine import InferenceEngine

    _, _, path = lanes
    with pytest.raises(ValueError, match=named + ".*latent.*DEEPSEEK_V32"):
        InferenceEngine(path, **{"tp": 1, "dtype": jnp.float32, "batch_size": 2,
                                 "max_seq_len": SEQ, **kwargs})


def test_pool_native_pages_and_speculation_are_refused_by_name(lanes):
    e, _, _ = lanes
    with pytest.raises(ValueError, match="--kv-native.*DEEPSEEK_V32.*latent"):
        e.init_kv_pool(4, 40, native=True)
    with pytest.raises(ValueError, match="--speculation.*latent.*DEEPSEEK_V32"):
        e.rehearse_admission(4, spec_k=4)


@pytest.mark.parametrize("n_prompt", [100, 200])
def test_a_prompt_through_a_middle_rung_leaves_what_the_largest_rung_leaves(lanes, n_prompt):
    """The served ladder's rungs at 128 and 256 rows (PR 49) against the 512
    this family's chunk program always ran at: cache rows, lane states and
    the next token's logits of one prompt through either."""
    from helpers import assert_a_middle_rung_equals_the_largest

    assert_a_middle_rung_equals_the_largest(lanes[2], n_prompt)
