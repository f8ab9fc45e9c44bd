"""The `lfm2_moe` decoder (LFM2-24B-A2B: gated short convolutions that keep
two rows of state a lane where every fourth layer keeps a cache, leading dense
layers, then a held share of the experts behind a biased sigmoid router)
through the program's normal path, at a small size on the CPU, against the
benchmark's plain reference: logits, not ids. Prefill alone; in two and three
chunks against one; then decode through cache and state across every chunk
and block boundary; sixteen lanes admitted at different times, each against
its own sequence; the state of a lane that stands, bit for bit; an adopted
prefix against the same request cold; a parked and resumed stream through the
scheduler; the four shares of a layer; the file format's keys 47-49."""

from __future__ import annotations

import json
import os
import sys
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "benchmark")) if p not in sys.path]

from benchmark.harness import weights  # noqa: E402
from benchmark.references import lfm2_moe as lfm2  # noqa: E402
from helpers import LFM2_TYPES, attn_layer_words  # noqa: E402
from helpers import tiny_lfm2_config as tiny  # noqa: E402
from dllama_tpu.formats.model_file import (  # noqa: E402
    HeaderKey, LlmArch, LlmHeader, ModelReader, RopeType, layer_table, read_llm_header,
    tensor_plan)
from dllama_tpu.models import transformer as tf  # noqa: E402
from dllama_tpu.models.loader import load_params  # noqa: E402
from dllama_tpu.models.transformer import forward, init_kv_cache  # noqa: E402
from dllama_tpu.ops.short_conv import short_conv_chunk, short_conv_step  # noqa: E402

CHUNK, SEQ = 16, 256
# f32 on both sides, but not the same sums (the reference's whole-sequence
# convolution against the program's carried rows, its attention over query
# blocks); the largest logit error read over these cases is 2e-5 of a logit
# std, and a lost state row reads ten thousand times that
TOL = 2e-4


def build(tmp_path, cfg: dict, seed: int = 3):
    path = str(tmp_path / f"{cfg['name']}-{seed}.m")
    weights.write_model(path, cfg, seed)
    reader = ModelReader(path, max_seq_len=SEQ)
    return path, reader.header, load_params(reader, dtype=jnp.float32)


def token_ids(n: int, seed: int = 0) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(0, 500, n)]


def reference_logits(path, cfg, ids):
    return np.asarray(lfm2.last_logits(path, cfg, [ids], [len(ids)])[0])


def jit_forward(h, params):
    """`forward` compiled once a chunk width (called bare, every call builds
    its layer scans anew)."""
    return jax.jit(lambda toks, pos, cache, **state: forward(
        params, h, toks, pos, cache, **state))


def served_logits(h, params, ids, n_prefill: int, chunk: int = CHUNK):
    """Logits of every position: chunks of `chunk` rows up to `n_prefill`,
    then a decode step a token, through cache and state."""
    cache = init_kv_cache(h, 1, jnp.float32, seq_len=SEQ)
    step, out, p = jit_forward(h, params), [], 0
    while p < len(ids):
        width = min(chunk, n_prefill - p) if p < n_prefill else 1
        logits, cache = step(jnp.asarray([ids[p:p + width]]), jnp.int32(p), cache)
        out.append(np.asarray(logits[0]))
        p += width
    return np.concatenate(out), cache


# -- the model against the reference --------------------------------------------


@pytest.mark.parametrize("n", [1, 2, 3, 17, 70])
def test_prefill_alone_equals_the_reference(tmp_path, n):
    cfg = tiny()
    path, h, params = build(tmp_path, cfg)
    ids = token_ids(n, seed=n)
    got, _ = served_logits(h, params, ids, n, chunk=n)
    want = reference_logits(path, cfg, ids)
    assert np.abs(got - want).max() < TOL * want.std()


@pytest.mark.parametrize("chunks", [(24, 24), (16, 16, 16), (47, 1), (1, 47), (2, 45, 1)])
def test_prefill_in_chunks_equals_prefill_in_one(tmp_path, chunks):
    """The state is carried from chunk to chunk: the logits, the cache rows
    and the final state of a prompt in two or three chunks are one chunk's."""
    cfg = tiny()
    _, h, params = build(tmp_path, cfg)
    ids = token_ids(sum(chunks), seed=7)
    whole, cache_whole = served_logits(h, params, ids, len(ids), chunk=len(ids))
    cache, p, out = init_kv_cache(h, 1, jnp.float32, seq_len=SEQ), 0, []
    step = jit_forward(h, params)
    for width in chunks:
        logits, cache = step(jnp.asarray([ids[p:p + width]]), jnp.int32(p), cache)
        out.append(np.asarray(logits[0]))
        p += width
    assert np.abs(np.concatenate(out) - whole).max() < 1e-5 * whole.std()
    # the carried rows are the gated rows themselves
    assert np.abs(np.asarray(cache["s"]) - np.asarray(cache_whole["s"])).max() < 1e-5
    assert np.asarray(cache["s"]).any()


@pytest.mark.parametrize("n,n_prefill", [(40, 15), (40, 16), (40, 17), (50, 32), (70, 33), (36, 1)])
def test_prefill_then_decode_through_cache_and_state_equals_the_reference(
        tmp_path, n, n_prefill):
    """Lengths on either side of every chunk boundary, decode from there on."""
    cfg = tiny()
    path, h, params = build(tmp_path, cfg)
    ids = token_ids(n, seed=n + n_prefill)
    got, _ = served_logits(h, params, ids, n_prefill)
    want = reference_logits(path, cfg, ids)
    assert np.abs(got - want).max() < TOL * want.std()


@pytest.mark.parametrize("types", [
    ["conv", "full_attention"] * 3, ["full_attention", "conv", "conv"] * 2,
    ["conv", "conv", "full_attention", "conv", "full_attention", "full_attention", "conv"],
])
def test_any_pattern_of_layers_equals_the_reference(tmp_path, types):
    """A period of two, attention in a dense layer, no period at all."""
    cfg = tiny(layer_types=types)
    path, h, params = build(tmp_path, cfg)
    ids = token_ids(41, seed=len(types))
    got, _ = served_logits(h, params, ids, 32)
    want = reference_logits(path, cfg, ids)
    assert np.abs(got - want).max() < TOL * want.std()


@pytest.mark.parametrize("name", [n for n in lfm2.FAULTS if "float8" not in n])
def test_a_fault_changes_the_references_logits(tmp_path, name):
    """Every fault of `FAULTS` moves the reference's logits by far more than
    the program differs from the honest reference."""
    cfg = tiny()
    path, h, params = build(tmp_path, cfg)
    fault = lfm2.FAULTS[name]
    over = dict(fault)
    if "fault_zero_state_every" in over:
        over["fault_zero_state_every"] = CHUNK  # the test's chunk
    ids = token_ids(48, seed=11)
    want = reference_logits(path, cfg, ids)
    wrong = reference_logits(path, {**cfg, **over}, ids)
    assert np.abs(wrong - want).max() > 100 * TOL * want.std()


def test_the_zero_state_fault_is_a_program_that_carries_no_state(tmp_path):
    """The reference with `zero state at a chunk boundary` is what the
    program computes when every chunk starts from a zero state."""
    cfg = tiny()
    path, h, params = build(tmp_path, cfg)
    ids = token_ids(48, seed=12)
    cache, out = init_kv_cache(h, 1, jnp.float32, seq_len=SEQ), []
    step = jit_forward(h, params)
    for p in range(0, 48, CHUNK):
        logits, cache = step(
            jnp.asarray([ids[p:p + CHUNK]]), jnp.int32(p), cache,
            state_fresh=jnp.asarray([True]))
        out.append(np.asarray(logits[0]))
    wrong = reference_logits(path, {**cfg, "fault_zero_state_every": CHUNK}, ids)
    assert np.abs(np.concatenate(out) - wrong).max() < TOL * wrong.std()


# -- the operator's two forms -------------------------------------------------------


def test_the_chunk_form_takes_its_state_behind_the_real_rows():
    rng = np.random.default_rng(0)
    b, t, d = 3, 8, 16
    bcx = jnp.asarray(rng.standard_normal((b, t, 3 * d)), jnp.float32)
    taps = jnp.asarray(rng.standard_normal((3, d)), jnp.float32)
    state = jnp.asarray(rng.standard_normal((b, 2, d)), jnp.float32)
    g = np.asarray(bcx[..., :d] * bcx[..., 2 * d:])
    ext = np.concatenate([np.asarray(state), g], axis=1)
    want = np.asarray(bcx[..., d:2 * d]) * sum(
        np.asarray(taps)[j] * ext[:, j:j + t] for j in range(3))
    y, new = short_conv_chunk(bcx, taps, state, jnp.asarray([t, 3, 0], jnp.int32))
    assert np.abs(np.asarray(y) - want).max() < 1e-5
    assert np.array_equal(np.asarray(new[0]), g[0, -2:])  # the chunk's last two
    assert np.array_equal(np.asarray(new[1]), g[1, 1:3])  # rows 1 and 2 of three real ones
    assert np.array_equal(np.asarray(new[2]), np.asarray(state[2]))  # the lane stood
    # one real row: the newer carried row moves up, the row joins it
    _, one = short_conv_chunk(bcx, taps, state, jnp.asarray([1, 1, 1], jnp.int32))
    assert np.array_equal(np.asarray(one[:, 0]), np.asarray(state[:, 1]))
    assert np.array_equal(np.asarray(one[:, 1]), g[:, 0])


def test_the_step_form_is_the_chunk_form_of_one_row():
    rng = np.random.default_rng(1)
    b, d = 4, 16
    bcx = jnp.asarray(rng.standard_normal((b, 1, 3 * d)), jnp.float32)
    taps = jnp.asarray(rng.standard_normal((3, d)), jnp.float32)
    state = jnp.asarray(rng.standard_normal((b, 2, d)), jnp.float32)
    live = jnp.asarray([True, False, True, False])
    y1, s1 = short_conv_step(bcx, taps, state, live)
    y2, s2 = short_conv_chunk(bcx, taps, state, live.astype(jnp.int32))
    assert np.array_equal(np.asarray(y1), np.asarray(y2))
    assert np.array_equal(np.asarray(s1), np.asarray(s2))
    assert np.array_equal(np.asarray(s1[1]), np.asarray(state[1]))


# -- the share tied to the model --------------------------------------------------


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer(tmp_path):
    """One expert layer of a model that holds all 8 experts the router
    scores, in the reference; and the same layer as 4 chips would compute it,
    each holding two experts, in the program's own routing and expert code.
    The routed parts of the four shares are the uncut layer's output (no
    shared expert); some token has no expert on some chip and gets nothing
    from it."""
    cfg = tiny(num_experts=8)
    path, h, params = build(tmp_path, cfg)
    layer = 3  # an expert layer; its row among the expert layers' stacks is 1
    lp = {k: v[1] for k, v in params["layers"].items()
          if k in ("moe_gate", "expert_bias", "w1", "w2", "w3")}
    y = jnp.asarray(np.random.default_rng(2).standard_normal((1, 40, 64)), jnp.float32)
    f = lfm2.Q40File(path)
    want = np.asarray(lfm2.routed_experts(y[0], lfm2.layer_weights(f, layer, cfg), cfg))
    parts, empty = [], 0
    for first in range(0, 8, 2):
        route = tf.Routing(2, True, True, 1.0, first, 2, 8)
        top_i, wts = tf._moe_route(y, lp["moe_gate"], route, lp["expert_bias"])
        held = route.held(top_i)
        part = tf._moe_ffn(
            y, lp["moe_gate"], *(lp[n][first:first + 2] for n in ("w1", "w2", "w3")),
            route, tf.silu, routed=(held, wts))
        rows_without = np.asarray((held == 2).all(axis=-1))[0]
        assert not np.asarray(part)[0][rows_without].any()
        empty += int(rows_without.sum())
        parts.append(np.asarray(part[0]))
    assert empty > 0
    assert np.abs(sum(parts) - want).max() < 1e-4 * np.abs(want).max()


# -- the file format -------------------------------------------------------------


def test_header_keys_47_to_49_and_the_tensor_plan_round_trip(tmp_path):
    cfg = tiny()
    path, h, params = build(tmp_path, cfg)
    assert (HeaderKey.CONV_L_CACHE, HeaderKey.ATTN_LAYERS_LO, HeaderKey.ATTN_LAYERS_HI) == (
        47, 48, 49)
    assert h.arch == LlmArch.LFM2_MOE and h.rope_type == RopeType.FALCON
    assert h.conv_l_cache == 3 and h.stateful and h.conv_state_rows == 2
    assert [l for l in range(h.n_layers) if h.attn_layers >> l & 1] == [
        l for l, t in enumerate(LFM2_TYPES) if t == "full_attention"] == [2, 6, 10]
    table = layer_table(h)
    assert [k.conv for k in table] == [t == "conv" for t in LFM2_TYPES]
    assert [k.row for k in table if k.conv] == list(range(9))  # the state stack's rows
    assert [k.row for k in table if not k.conv] == [0, 1, 2]  # the cache stack's
    assert [k.cache for k in table][:3] == ["state", "state", "full"]
    assert not any(k.rope for k in table if k.conv)
    names = [s.name for s in tensor_plan(h)]
    assert names[-1] == "wcls"
    assert names[1:4] == ["layers.0.conv_in", "layers.0.conv_w", "layers.0.conv_out"]
    assert "layers.0.q_norm" not in names and "layers.2.q_norm" in names
    assert "layers.2.conv_in" not in names and "layers.2.expert_bias" in names
    by = {s.name: s for s in tensor_plan(h)}
    assert by["layers.0.conv_in"].shape == (192, 64)
    assert by["layers.0.conv_w"].shape == (64, 3)
    assert by["layers.1.w1"].shape == (160, 64)  # a leading dense layer, `intermediate_size`
    assert by["layers.2.experts.0.w1"].shape == (128, 64)
    # each operator's leaves are stacked over the layers of its kind
    assert params["layers"]["conv_in"].shape == (9, 64, 192)
    assert params["layers"]["conv_w"].shape == (9, 3, 64)
    assert params["layers"]["wq"].shape[0] == params["layers"]["q_norm"].shape[0] == 3
    cache = init_kv_cache(h, 5, jnp.float32, seq_len=SEQ)
    # the four key-value heads of 8 columns lie side by side in one cache row
    assert h.kv_pack == 4
    assert cache["k"].shape == (3, 5, 1, SEQ, 32) and cache["s"].shape == (9, 5, 2, 64)


def test_the_mask_names_layers_past_the_first_word():
    types = ["conv"] * 31 + ["full_attention", "conv", "full_attention"]
    words = attn_layer_words(types)
    assert words == {"attn_layers_lo": 0, "attn_layers_hi": 0b1010}
    assert attn_layer_words(["conv"] * 29 + ["full_attention"]) == {
        "attn_layers_lo": 1 << 29, "attn_layers_hi": 0}


def test_the_published_sizes_give_the_files_bytes():
    """`tensor_plan` at the benchmark configuration's sizes: the 3.67 GB of
    Q40 weights and 0.13 GB of f32 embedding that ISSUE 42 reckons."""
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b-e16.json")) as f:
        cfg = json.load(f)
    wire = weights.header_for(cfg)
    h = LlmHeader(
        arch=LlmArch.LFM2_MOE, dim=wire["dim"], hidden_dim=wire["hidden_dim"],
        n_layers=wire["n_layers"], n_heads=wire["n_heads"], n_kv_heads=wire["n_kv_heads"],
        n_experts=wire["n_experts"], n_active_experts=wire["n_active_experts"],
        vocab_size=wire["vocab_size"], head_dim=wire["head_dim"],
        moe_hidden_dim=wire["moe_hidden_dim"], n_dense_layers=wire["n_dense_layers"],
        n_routed_experts=wire["n_routed_experts"], conv_l_cache=wire["conv_l_cache"],
        attn_layers=wire["attn_layers_lo"] | wire["attn_layers_hi"] << 30)
    assert h.kv_pack == 2  # heads of 64: two to a cache row of 128 columns
    assert [l for l in range(40) if h.attn_layers >> l & 1] == list(range(2, 40, 4))
    assert [k.conv for k in layer_table(h)] == [t == "conv" for t in cfg["layer_types"]]
    plan = tensor_plan(h)
    q40 = sum(s.n_elements for s in plan if s.float_type.name == "Q40")
    experts = 38 * 16 * 3 * 2048 * 1536
    assert q40 == experts + 30 * 4 * 2048 * 2048 + 10 * 2048 * (2048 + 512 + 512 + 2048) + (
        2 * 3 * 2048 * 11776 + 16384 * 2048)
    assert round(q40 * 18 / 32 / 1e9, 2) == 3.67
    assert by_name(plan, "embed").nbytes == 16384 * 2048 * 4
    assert by_name(plan, "layers.5.moe_gate").shape == (64, 2048)
    assert by_name(plan, "layers.5.expert_bias").shape == (64,)


def by_name(plan, name):
    return next(s for s in plan if s.name == name)


@pytest.mark.parametrize("header,named", [
    ({"conv_l_cache": 1}, "conv_l_cache >= 2"),
    ({"sliding_window": 32}, "full attention alone"),
    ({"attn_layers_lo": 1 << 20}, "attention layers"),
    ({"attn_layers_lo": 0}, "layers of both kinds"),
    ({"attn_layers_lo": (1 << 12) - 1}, "layers of both kinds"),
])
def test_a_header_that_cannot_be_served_is_refused_at_the_read(tmp_path, header, named):
    cfg = tiny()
    cfg["file"]["header"].update(header)
    with pytest.raises(ValueError, match=named):
        weights.write_model(str(tmp_path / "m.m"), cfg, 3)


# -- through the engine: lanes, the pool, the scheduler ----------------------------


@pytest.fixture(scope="module")
def lanes(tmp_path_factory):
    from helpers import make_tiny_lfm2

    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path_factory.mktemp("lfm2") / "m.m")
    cfg = make_tiny_lfm2(path)
    e = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0, batch_size=16,
                        prefill_buckets=(1, 8, CHUNK), max_seq_len=SEQ)
    return e, cfg, path


def greedy_gap(path, cfg, prompt, generated):
    """How far below the reference's largest logit each generated token lies,
    in logit std, the sequence teacher-forced."""
    seq = prompt + generated
    logits = np.asarray(lfm2.last_logits(path, cfg, [seq[:-1]], [len(generated)])[0])
    return (logits.max(-1) - logits[np.arange(len(generated)), generated]) / logits.std(-1)


def test_the_state_lives_in_the_cache_and_the_gauge_says_its_bytes(lanes):
    e, _, _ = lanes
    assert set(e.cache) == {"k", "v", "s"}
    assert e.cache["k"].shape == (3, 16, 1, SEQ + CHUNK, 32)
    assert e.cache["s"].shape == (9, 16, 2, 64)
    assert e.kv_cache_bytes["conv"] == 9 * 16 * 2 * 64 * 4
    assert e.kv_cache_bytes["full"] == 2 * 3 * 16 * 4 * (SEQ + CHUNK) * 8 * 4
    event = e.recorder.events("kv_cache")[-1]
    assert event["conv_bytes"] == e.kv_cache_bytes["conv"]
    assert e.obs.render().count('dllama_kv_cache_bytes{kind="conv"}') == 1
    assert e.state_replay_rows == 24  # 9 layers x 2 rows, up to a multiple of 8


def test_sixteen_lanes_admitted_at_different_times_each_equal_their_own(lanes):
    """Lanes are admitted in four waves between decode blocks, prompts of 1 to
    75 tokens (one chunk, several, none at all). Every lane's greedy stream
    is the reference's for its own sequence; and at every admission and at
    every block the state of each lane that stands is bit for bit what it
    was."""
    e, cfg, path = lanes
    e.reset()
    lengths = [45, 9, 70, 1, 16, 17, 33, 2, 75, 31, 8, 64, 5, 48, 3, 24]
    prompts = [token_ids(n, seed=100 + i) for i, n in enumerate(lengths)]
    hist = [list(p) for p in prompts]
    live: list[int] = []

    def state():
        return np.asarray(e.cache["s"])

    def block(n_steps=4):
        before = state()
        active = [l in live for l in range(16)]
        out = e.decode_lanes(
            [h_[-1] for h_ in hist], [len(h_) - 1 for h_ in hist], n_steps, active)
        for row in out:
            for l in live:
                hist[l].append(row[l])
        after = state()
        for l in range(16):
            if l not in live:
                assert np.array_equal(after[:, l], before[:, l]), l
        assert live and not np.array_equal(after[:, live[0]], before[:, live[0]])

    for wave in ([0, 2, 5], [1, 3, 8, 11], [4, 6, 7, 9, 10], [12, 13, 14, 15]):
        for lane in wave:
            before = state()
            e.prefill_lane(lane, prompts[lane])
            after = state()
            for other in range(16):
                if other != lane:
                    assert np.array_equal(after[:, other], before[:, other]), (lane, other)
            live.append(lane)
        block()
        block(3)
    for lane in range(16):
        generated = hist[lane][lengths[lane]:]
        assert len(generated) >= 7
        assert greedy_gap(path, cfg, prompts[lane], generated).max() < TOL, lane
    # what the dispatches say of it
    chunk = [d for d in e.recorder.events("step_dispatch")
             if d["step"] == "prefill_lane_chunk" and d["lane"] == 8][-1]
    assert chunk["state_lanes"] == 1 and chunk["replay_tokens"] == 0
    assert chunk["rows_full"] == sum(p + 1 for p in range(chunk["pos"], chunk["pos"] + chunk["n_tokens"]))
    dispatch = [d for d in e.recorder.events("step_dispatch") if d["step"] == "decode_lanes"][-1]
    assert dispatch["state_lanes"] == dispatch["n_live"] == 16


def test_an_adopted_prefix_gives_the_state_and_logits_of_the_request_cold(lanes):
    """Pages hold the attention layers' rows and nothing of the 9 states: the
    lane runs the `state_replay_rows` positions before the prefix's end again,
    from a zero state, its cache writes masked below the end. Behind it every
    layer's state and every cache row are what the same request gives cold."""
    from dllama_tpu.kv.manager import PagedKVManager

    e, cfg, path = lanes
    e.reset()
    kv = PagedKVManager(e, page_size=4, n_pages=160)
    assert set(e.kv_pool) == {"k", "v"} and e.kv_pool["k"].shape == (3, 160, 1, 4, 32)
    first = token_ids(80, seed=21)
    e.prefill_lane(0, first)
    assert kv.publish(0, first[:79]) == 19
    second = first[:61] + token_ids(30, seed=22)
    m, pages = kv.match(1, second)
    assert m == 61  # not a page's end: the floor is a position, not a page
    kv.adopt(1, pages)
    replay0 = e._m_replay_tokens.value
    start = m - e.state_replay_rows
    e.prefill_lane(1, second[start:], pos0=start, write_floor=m)
    e.prefill_lane(2, second)  # the same request with nothing adopted
    n = len(second) - 1
    s, k = np.asarray(e.cache["s"]), np.asarray(e.cache["k"])
    assert np.abs(s[:, 1] - s[:, 2]).max() < 1e-5 and s[:, 1].any()
    assert np.abs(k[:, 1, :, :n] - k[:, 2, :, :n]).max() < 1e-5
    assert np.array_equal(k[:, 1, :, :m], k[:, 0, :, :m])  # the adopted rows, untouched
    assert e._m_replay_tokens.value - replay0 == e.state_replay_rows
    # the recorder is the process's: another engine's chunks carry no such field
    replayed = [d for d in e.recorder.events("step_dispatch")
                if d["step"] == "prefill_lane_chunk" and d["lane"] == 1
                and d.get("replay_tokens")]
    assert replayed[0]["pos"] == start
    assert sum(d["replay_tokens"] for d in replayed) == 24
    out = e.decode_lanes([second[-1]] * 16, [n] * 16, 6, [l in (1, 2) for l in range(16)])
    adopted, cold = [r[1] for r in out], [r[2] for r in out]
    assert adopted == cold
    assert greedy_gap(path, cfg, second, adopted).max() < TOL
    # a replay that starts elsewhere, or a chunk that continues nothing, is refused
    with pytest.raises(ValueError, match="neither continues"):
        e.prefill_lane_chunk(3, second[40:60], 40)
    with pytest.raises(ValueError, match="positions before the floor"):
        e.prefill_lane_chunk(3, second[10:30], 10, write_floor=61)
    with pytest.raises(ValueError, match="states stand at"):
        e.decode_lanes([5] * 16, [30] * 16, 2, [l == 4 for l in range(16)])
    kv.release_lane(1)


@pytest.mark.parametrize("kwargs,named", [
    ({"tp": 2}, "--tp 2"), ({"sp": 2}, "--sp 2"), ({"pp": 2}, "--pp 2"),
    ({"dp": 2}, "--dp 2"), ({"kv_dtype": "int8"}, "--kv-dtype int8"),
    ({"batch_size": 1}, "--batch-size 1"),
])
def test_what_lane_state_does_not_run_under_fails_at_start_up(lanes, kwargs, named):
    from dllama_tpu.runtime.engine import InferenceEngine

    _, _, path = lanes
    with pytest.raises(ValueError, match=named + ".*(state|lane).*LFM2_MOE"):
        InferenceEngine(path, **{"tp": 1, "dtype": jnp.float32, "batch_size": 2,
                                 "max_seq_len": SEQ, **kwargs})


def test_pool_native_pages_speculation_and_the_single_stream_are_refused_by_name(lanes):
    e, _, _ = lanes
    with pytest.raises(ValueError, match="--kv-native.*LFM2_MOE"):
        e.init_kv_pool(4, 40, native=True)
    with pytest.raises(ValueError, match="--speculation.*convolution.*LFM2_MOE"):
        e.rehearse_admission(4, spec_k=4)
    for call in (lambda: e.prefill([1, 2, 3]), lambda: e.decode_block(1, 0, 4),
                 lambda: e.perplexity([1, 2, 3, 4])):
        with pytest.raises(ValueError, match="lane programs.*LFM2_MOE"):
            call()


# -- through the HTTP front: adoption, park and resume ------------------------------


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    """Two lanes behind the scheduler and the pool, up to four streams."""
    from helpers import make_tiny_lfm2

    from dllama_tpu.models.synthetic import write_synth_tokenizer
    from dllama_tpu.runtime.api_server import serve
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.tokenizer import Tokenizer

    d = tmp_path_factory.mktemp("lfm2srv")
    mp, tp_ = str(d / "m.m"), str(d / "t.t")
    make_tiny_lfm2(mp)
    write_synth_tokenizer(tp_, 512)
    tok = Tokenizer(tp_)

    def start(**kw):
        engine = InferenceEngine(mp, tokenizer=tok, tp=1, dtype=jnp.float32,
                                 temperature=0.0, seed=3, batch_size=2, max_seq_len=384)
        srv = serve(engine, tok, host="127.0.0.1", port=0, lane_block_size=4, **kw)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv, f"http://127.0.0.1:{srv.server_address[1]}"

    started = []

    def factory(**kw):
        started.append(start(**kw))
        return started[-1]

    yield factory
    for srv, _ in started:
        srv.shutdown()


def chat(url, content, max_tokens=24):
    payload = {"model": "m", "stream": False, "max_tokens": max_tokens, "temperature": 0,
               "messages": [{"role": "user", "content": content}]}
    req = urllib.request.Request(
        url + "/v1/chat/completions", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=600) as r:
        return json.loads(r.read())["choices"][0]["message"]["content"]


PROMPTS = [f"story number {i} " + "once upon a time " * (i + 1) for i in range(4)]


def test_served_with_the_pool_parked_and_resumed_equals_served_without(server):
    """The same four requests: through a server without a pool, one at a
    time (every admission cold); and through a server with the pool, first
    one at a time (the second adopts the chat template's rows and replays),
    then all four at once on two lanes, so that streams are parked and
    resumed. Every answer is the cold one, byte for byte."""
    plain, plain_url = server(kv_page_size=-1)  # no pool
    # the metrics registry is the process's: count from here
    replays = plain.state.engine._m_state_installs.labels(how="replay")
    replays0 = replays.value
    cold = [chat(plain_url, p) for p in PROMPTS]
    assert replays.value == replays0
    srv, url = server(kv_page_size=4, max_streams=4)
    e = srv.state.engine
    assert [chat(url, p) for p in PROMPTS] == cold
    assert e._m_state_installs.labels(how="replay").value > replays0
    resumes0 = srv.state.m_stream_resumes.value
    results = [None] * 4

    def run(i):
        results[i] = chat(url, PROMPTS[i])

    threads = [threading.Thread(target=run, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert results == cold, "park -> resume changed a stream"
    assert srv.state.m_stream_resumes.value > resumes0, "no stream was parked"
    assert srv.state.scheduler._n_parked == 0 and not srv.state.scheduler.pending
    srv.state.kv_manager.check()
    chunks = [d for d in e.recorder.events("step_dispatch")
              if d["step"] == "prefill_lane_chunk" and d.get("replay_tokens")]
    assert chunks and all(c["replay_tokens"] <= e.state_replay_rows for c in chunks)


def test_a_prefix_no_longer_than_the_replay_is_declined(server):
    """An adoption of no more positions than the lane would run again is not
    worth taking: the scheduler starts the request cold and counts a miss."""
    srv, url = server(kv_page_size=4, max_streams=2)
    sched, e = srv.state.scheduler, srv.state.engine
    assert sched._prefill_start(0) == 0
    assert sched._prefill_start(100) == 100 - e.state_replay_rows
    chat(url, "a", max_tokens=4)  # publishes the template's rows and its own
    prompt = srv.state.tokenizer.encode("zzzz", is_start=True, add_special_tokens=True)
    shared, _ = sched.kv.match(0, prompt)
    sched.kv.release_lane(0)
    assert 0 < shared <= e.state_replay_rows  # the start token: something is stored
    assert sched._match_prefix(0, prompt) == (0, [])
    sched.kv.check()


@pytest.mark.parametrize("n_prompt", [100, 200])
def test_a_prompt_through_a_middle_rung_leaves_what_the_largest_rung_leaves(lanes, n_prompt):
    """The served ladder's rungs at 128 and 256 rows (PR 49) against the 512
    this family's chunk program always ran at: cache rows, lane states and
    the next token's logits of one prompt through either."""
    from helpers import assert_a_middle_rung_equals_the_largest

    assert_a_middle_rung_equals_the_largest(lanes[2], n_prompt)
