"""The `granitemoehybrid` decoder (granite-4.0-h-small: Mamba-2 layers that keep
a float32 recurrent state a lane and three rows of their convolution's input,
an attention layer without rope now and then, a held share of the experts
behind a softmax router beside a shared expert, multipliers on the embedding,
the residual adds, the scores and the logits) through the program's normal
path, at a small size on the CPU, against the benchmark's plain reference:
logits, not ids. Prefill alone; in two and three chunks against one; then
decode through cache and both states; the recurrence's two forms against each
other row by row; lanes admitted at different times, each against its own
sequence, the states of a lane that stands bit for bit; adoption declined and
counted, nothing published; the four shares of a layer and the shared expert
once; the file format's keys 50-58."""

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
from benchmark.references import granitemoehybrid as granite  # noqa: E402
from helpers import GRANITE_TYPES, attn_layer_words  # noqa: E402
from helpers import tiny_granite_config as tiny  # noqa: E402
from dllama_tpu.formats.model_file import (  # noqa: E402
    HeaderKey, LlmArch, LlmHeader, ModelReader, layer_table, tensor_plan)
from dllama_tpu.models import transformer as tf  # noqa: E402
from dllama_tpu.models.loader import load_params  # noqa: E402
from dllama_tpu.models.transformer import forward, init_kv_cache  # noqa: E402
from dllama_tpu.ops.ssm_scan import (  # noqa: E402
    SsmShape, lane_state, put_lane_state, ssm_chunk, ssm_step, ssm_step_in_place)

CHUNK, SEQ = 16, 256
# f32 on both sides, but not the same sums (the reference's recurrence a
# position at a time against the program's blocks, its attention over query
# blocks); the largest logit error read over these cases is 8e-6 of a logit
# std, and a state rounded to bfloat16 reads two thousand times that
TOL = 5e-5


def build(tmp_path, cfg: dict, seed: int = 3):
    path = str(tmp_path / f"{cfg['name']}-{seed}.m")
    weights.write_model(path, cfg, seed)
    reader = ModelReader(path, max_seq_len=SEQ)
    return path, reader.header, load_params(reader, dtype=jnp.float32)


def token_ids(n: int, seed: int = 0) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(0, 500, n)]


def reference_logits(path, cfg, ids):
    return np.asarray(granite.last_logits(path, cfg, [ids], [len(ids)])[0])


def jit_forward(h, params):
    """`forward` compiled once a chunk width (called bare, every call builds
    its layer scans anew)."""
    return jax.jit(lambda toks, pos, cache, **state: forward(
        params, h, toks, pos, cache, **state))


def served_logits(h, params, ids, n_prefill: int, chunk: int = CHUNK, narrow=None):
    """Logits of every position: chunks of `chunk` rows up to `n_prefill`,
    then a decode step a token, through cache and states. `narrow`: a type the
    recurrent state is rounded to between programs."""
    cache = init_kv_cache(h, 1, jnp.float32, seq_len=SEQ)
    step, out, p = jit_forward(h, params), [], 0
    while p < len(ids):
        width = min(chunk, n_prefill - p) if p < n_prefill else 1
        logits, cache = step(jnp.asarray([ids[p:p + width]]), jnp.int32(p), cache)
        if narrow is not None:
            cache["r"] = cache["r"].astype(narrow).astype(jnp.float32)
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
    """Both states are carried from chunk to chunk: the logits, the cache rows
    and the final states of a prompt in two or three chunks are one chunk's."""
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
    for name in ("s", "r"):
        a, b = np.asarray(cache[name]), np.asarray(cache_whole[name])
        assert np.abs(a - b).max() < 1e-5 * np.abs(b).max() and b.any(), name


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
    ["mamba", "attention"] * 3, ["attention", "mamba", "mamba"] * 2,
    ["mamba", "mamba", "attention", "mamba", "attention", "attention", "mamba"],
])
def test_any_pattern_of_layers_equals_the_reference(tmp_path, types):
    """A period of two, attention first, no period at all."""
    cfg = tiny(layer_types=types)
    path, h, params = build(tmp_path, cfg)
    ids = token_ids(41, seed=len(types))
    got, _ = served_logits(h, params, ids, 32)
    want = reference_logits(path, cfg, ids)
    assert np.abs(got - want).max() < TOL * want.std()


def test_a_state_rounded_to_bfloat16_fails_the_tolerance(tmp_path):
    """The tolerance is not slack: the same run with the recurrent state
    rounded to bfloat16 between programs is a hundred times outside it."""
    cfg = tiny()
    path, h, params = build(tmp_path, cfg)
    ids = token_ids(60, seed=5)
    want = reference_logits(path, cfg, ids)
    got, _ = served_logits(h, params, ids, 32, narrow=jnp.bfloat16)
    assert np.abs(got - want).max() > 100 * TOL * want.std()


@pytest.mark.parametrize("name", [n for n in granite.FAULTS if "float8" not in n])
def test_a_fault_changes_the_references_logits(tmp_path, name):
    """Every fault of `FAULTS` (a dropped term, a wrong scale) moves the
    reference's logits by far more than the program differs from the honest
    reference: the comparison above would fail on each."""
    cfg = tiny()
    path, h, params = build(tmp_path, cfg)
    over = dict(granite.FAULTS[name])
    if "fault_zero_state_every" in over:
        over["fault_zero_state_every"] = CHUNK  # the test's chunk
    ids = token_ids(48, seed=11)
    want = reference_logits(path, cfg, ids)
    wrong = reference_logits(path, {**cfg, **over}, ids)
    assert np.abs(wrong - want).max() > 100 * TOL * want.std()


def test_the_zero_state_fault_is_a_program_that_carries_no_state(tmp_path):
    """The reference with `zero state at a chunk boundary` is what the
    program computes when every chunk starts from zero states."""
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


# -- the recurrence's two forms ----------------------------------------------------


def mixer_inputs(b: int, t: int, seed: int = 0, shape=SsmShape(4, 8, 16, block=4)):
    """Random inputs of a mixer of 4 heads (of 8 columns) and a state 16 wide."""
    rng = np.random.default_rng(seed)
    c = shape.inner + 2 * shape.state_dim
    lp = {
        "ssm_conv_w": jnp.asarray(rng.standard_normal((4, c)) * 0.5, jnp.float32),
        "ssm_conv_b": jnp.asarray(rng.standard_normal(c) * 0.1, jnp.float32),
        "ssm_dt_bias": jnp.asarray(rng.uniform(-3, -1, 4), jnp.float32),
        "ssm_a_log": jnp.asarray(np.log(rng.uniform(1, 16, 4)), jnp.float32),
        "ssm_d": jnp.asarray(rng.uniform(0.9, 1.1, 4), jnp.float32),
        "ssm_norm": jnp.asarray(rng.uniform(0.8, 1.2, shape.inner), jnp.float32),
    }
    zxd = jnp.asarray(rng.standard_normal((b, t, shape.inner + c + 4)), jnp.float32)
    state = jnp.asarray(
        rng.standard_normal((b, 16, shape.n_heads, shape.head_dim)), jnp.float32)
    rows = jnp.asarray(rng.standard_normal((b, 3, c)), jnp.float32)
    return shape, lp, zxd, state, rows


@pytest.mark.parametrize("block", [4, 8, 16])
def test_the_chunk_form_is_the_step_form_row_by_row(block):
    """Sixteen rows in blocks of 4, 8 and 16 against sixteen steps: outputs
    and both states."""
    shape, lp, zxd, state, rows = mixer_inputs(3, 16)
    shape = SsmShape(4, 8, 16, block=block)
    y, s_chunk, r_chunk = ssm_chunk(zxd, lp, state, rows, jnp.full((3,), 16, jnp.int32), shape)
    s, r, ys = state, rows, []
    for i in range(16):
        yi, s, r = ssm_step(zxd[:, i:i + 1], lp, s, r, jnp.ones((3,), bool), shape)
        ys.append(np.asarray(yi[:, 0]))
    assert np.abs(np.asarray(y) - np.stack(ys, axis=1)).max() < 1e-5
    assert np.abs(np.asarray(s_chunk) - np.asarray(s)).max() < 1e-5 * np.abs(np.asarray(s)).max()
    assert np.array_equal(np.asarray(r_chunk), np.asarray(r))


def test_padding_moves_no_state_and_a_lane_without_rows_keeps_its_own():
    """A chunk of 16 rows of which a lane has 16, 5 and 0 real ones: behind it
    each lane's states are what its real rows alone leave, and the lane with
    none keeps its states bit for bit."""
    shape, lp, zxd, state, rows = mixer_inputs(3, 16, seed=1)
    n_rows = jnp.asarray([16, 5, 0], jnp.int32)
    y, s_new, r_new = ssm_chunk(zxd, lp, state, rows, n_rows, shape)
    _, s5, r5 = ssm_chunk(
        zxd[1:2, :8], lp, state[1:2], rows[1:2], jnp.asarray([5], jnp.int32), shape)
    _, s5_exact, r5_exact = ssm_chunk(
        jnp.pad(zxd[1:2, :5], ((0, 0), (0, 3), (0, 0))), lp, state[1:2], rows[1:2],
        jnp.asarray([5], jnp.int32), shape)
    assert np.abs(np.asarray(s_new[1]) - np.asarray(s5[0])).max() < 1e-6
    assert np.abs(np.asarray(s5[0]) - np.asarray(s5_exact[0])).max() < 1e-6
    assert np.array_equal(np.asarray(r_new[1]), np.asarray(r5[0]))
    assert np.array_equal(np.asarray(r_new[1]), np.asarray(zxd[1, 2:5, 32:96]))
    assert np.array_equal(np.asarray(s_new[2]), np.asarray(state[2]))
    assert np.array_equal(np.asarray(r_new[2]), np.asarray(rows[2]))
    assert not np.array_equal(np.asarray(s_new[0]), np.asarray(state[0]))
    # and the real rows' outputs do not see the padding
    y5, _, _ = ssm_chunk(
        zxd[1:2, :8], lp, state[1:2], rows[1:2], jnp.asarray([8], jnp.int32), shape)
    assert np.abs(np.asarray(y[1, :5]) - np.asarray(y5[0, :5])).max() < 1e-6


def test_a_step_moves_the_live_lanes_alone():
    shape, lp, zxd, state, rows = mixer_inputs(4, 1, seed=2)
    live = jnp.asarray([True, False, True, False])
    y1, s1, r1 = ssm_step(zxd, lp, state, rows, live, shape)
    y2, s2, r2 = ssm_chunk(zxd, lp, state, rows, live.astype(jnp.int32), shape)
    assert np.abs(np.asarray(y1[0]) - np.asarray(y2[0])).max() < 1e-5
    assert np.abs(np.asarray(s1) - np.asarray(s2)).max() < 1e-5
    for lane in (1, 3):
        assert np.array_equal(np.asarray(s1[lane]), np.asarray(state[lane]))
        assert np.array_equal(np.asarray(r1[lane]), np.asarray(rows[lane]))
    assert not np.array_equal(np.asarray(s1[0]), np.asarray(state[0]))


def test_the_step_over_the_stack_in_place_is_the_step_form_and_visits_the_live_alone():
    """`ssm_step_in_place`, the chip's decode step (a Pallas kernel over the
    layers' stack, here interpreted): the live lanes' states of the one layer
    move as `ssm_step` moves them, a lane marked zero starts from nothing,
    every other lane and every other layer stays bit for bit; with no lane
    live the grid is empty and nothing moves."""
    b = 5
    shape, lp, zxd, _, rows = mixer_inputs(b, 1, seed=4, shape=SsmShape(4, 64, 16))
    stack = jnp.asarray(
        np.random.default_rng(4).standard_normal((3, b, 16, shape.inner)), jnp.float32)
    zero = jnp.asarray([False, False, True, False, True])
    step = jax.jit(lambda *a: ssm_step_in_place(*a, shape, interpret=True))
    for flags in ([True, False, True, True, False], [False] * 5, [True] * 5):
        live = jnp.asarray(flags)
        y, new, r = step(zxd, lp, stack, jnp.int32(1), rows, live, zero)
        old = jnp.where(zero[:, None, None], 0.0, stack[1]).reshape(b, 16, 4, 64)
        y2, s2, r2 = ssm_step(zxd, lp, old, rows, live, shape)
        for lane, on in enumerate(flags):
            if on:
                assert np.abs(np.asarray(y[lane]) - np.asarray(y2[lane])).max() < 1e-5
                assert np.abs(np.asarray(new[1, lane]) - np.asarray(s2[lane]).reshape(16, -1)
                              ).max() < 1e-5
            else:
                assert np.array_equal(np.asarray(new[1, lane]), np.asarray(stack[1, lane]))
        assert np.array_equal(np.asarray(new[0]), np.asarray(stack[0]))
        assert np.array_equal(np.asarray(new[2]), np.asarray(stack[2]))
        assert np.array_equal(np.asarray(r), np.asarray(r2))


def test_a_lanes_state_is_read_out_of_the_stack_and_written_back_where_it_lay():
    """`lane_state` and `put_lane_state`, a chunk program's way to the
    recurrent stack on the chip (kernels, here interpreted): one lane's state
    of one layer, and nothing else moves."""
    rng = np.random.default_rng(5)
    stack = jnp.asarray(rng.standard_normal((3, 4, 16, 256)), jnp.float32)
    got = lane_state(stack, jnp.int32(2), jnp.int32(3), interpret=True)
    assert np.array_equal(np.asarray(got[0]), np.asarray(stack[2, 3]))
    new = jnp.asarray(rng.standard_normal((1, 16, 256)), jnp.float32)
    after = put_lane_state(stack, jnp.int32(2), jnp.int32(3), new, interpret=True)
    want = np.asarray(stack).copy()
    want[2, 3] = np.asarray(new[0])
    assert np.array_equal(np.asarray(after), want)


# -- the share tied to the model --------------------------------------------------


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(tmp_path):
    """One layer of a model that holds all 8 experts the router scores, in the
    reference; and the same layer as 4 chips would compute it, each holding
    two experts, in the program's own routing and expert code. The routed
    parts of the four shares are the uncut layer's routed sum: the shared
    expert, which every chip computes whole, is added once and not four
    times. Some token has no expert on some chip and gets nothing from it."""
    cfg = tiny(num_experts=8, num_local_experts=8)
    path, h, params = build(tmp_path, cfg)
    layer = 3
    lp = {k: v[layer] for k, v in params["layers"].items() if k in ("moe_gate", "w1", "w2", "w3")}
    y = jnp.asarray(np.random.default_rng(2).standard_normal((1, 40, 64)), jnp.float32)
    f = granite.Q40File(path)
    w = granite.layer_weights(f, layer, cfg)
    want = np.asarray(granite.routed_experts(y[0], w, cfg))
    parts, empty = [], 0
    for first in range(0, 8, 2):
        route = tf.Routing(3, False, True, 1.0, first, 2, 8)
        top_i, wts = tf._moe_route(y, lp["moe_gate"], route)
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
    # the router's weights are a softmax over the chosen logits
    logits = np.asarray(y[0] @ lp["moe_gate"])
    top = np.sort(logits, axis=1)[:, -3:]
    _, wts = tf._moe_route(y, lp["moe_gate"], tf.Routing(3, False, True, 1.0, 0, 8, 8))
    soft = np.exp(top - top.max(1, keepdims=True))
    assert np.abs(np.sort(np.asarray(wts[0]), axis=1) - soft / soft.sum(1, keepdims=True)).max() < 1e-6


# -- the file format -------------------------------------------------------------


def test_header_keys_50_to_58_and_the_tensor_plan_round_trip(tmp_path):
    cfg = tiny()
    path, h, params = build(tmp_path, cfg)
    assert [int(k) for k in (
        HeaderKey.SSM_N_HEADS, HeaderKey.SSM_HEAD_DIM, HeaderKey.SSM_STATE_DIM,
        HeaderKey.SSM_N_GROUPS, HeaderKey.SSM_CONV_TAPS, HeaderKey.EMBED_MULTIPLIER_MILLI,
        HeaderKey.RESIDUAL_MULTIPLIER_NANO, HeaderKey.ATTENTION_MULTIPLIER_NANO,
        HeaderKey.LOGITS_SCALING_MILLI)] == list(range(50, 59))
    assert h.arch == LlmArch.GRANITE_MOE_HYBRID
    assert (h.ssm_n_heads, h.ssm_head_dim, h.ssm_state_dim, h.ssm_n_groups, h.ssm_conv_taps) == (
        4, 8, 16, 1, 4)
    assert (h.embed_multiplier, h.residual_multiplier, h.attention_multiplier,
            h.logits_scaling) == (12.0, 0.22, 0.25, 16.0)
    assert h.softmax_scale == 0.25 and h.stateful and h.state_unbounded
    assert h.conv_l_cache == 0 and h.conv_state_rows == 3
    assert (h.ssm_inner, h.ssm_conv_dim) == (32, 64)
    assert [l for l in range(h.n_layers) if h.attn_layers >> l & 1] == [2, 6]
    table = layer_table(h)
    assert [k.ssm for k in table] == [t == "mamba" for t in GRANITE_TYPES]
    assert not any(k.conv for k in table) and all(k.experts for k in table)
    assert [k.row for k in table if k.ssm] == list(range(6))  # the state stacks' rows
    assert [k.row for k in table if not k.ssm] == [0, 1]  # the cache stack's
    assert not any(k.rope for k in table)  # no layer takes the rotary embedding
    names = [s.name for s in tensor_plan(h)]
    assert names[-1] == "wcls"
    assert names[1:11] == [f"layers.0.{n}" for n in granite.MAMBA]
    assert "layers.2.ssm_out" not in names and "layers.2.q" in names
    assert "layers.2.q_norm" not in names and "layers.0.expert_bias" not in names
    by = {s.name: s for s in tensor_plan(h)}
    # `in_proj`'s three parts lie one behind the other: one tensor's bytes
    z, xbc, dt = (by[f"layers.0.ssm_in_{n}"] for n in ("z", "xbc", "dt"))
    assert (z.shape, xbc.shape, dt.shape) == ((32, 64), (64, 64), (4, 64))
    assert z.offset + z.nbytes == xbc.offset and xbc.offset + xbc.nbytes == dt.offset
    assert by["layers.0.ssm_conv_w"].shape == (64, 4)
    assert by["layers.0.shared.w1"].shape == (64, 64)  # `shared_intermediate_size`
    assert by["layers.0.experts.3.w1"].shape == (32, 64)
    assert by["layers.0.moe_gate"].shape == (8, 64)  # the router keeps its width
    # each operator's leaves are stacked over the layers of its kind
    assert params["layers"]["ssm_in"].shape == (6, 64, 100)
    assert params["layers"]["ssm_conv_w"].shape == (6, 4, 64)
    assert params["layers"]["wq"].shape[0] == 2
    cache = init_kv_cache(h, 5, jnp.bfloat16, seq_len=SEQ)
    # the four key-value heads of 8 columns lie side by side in one cache row
    assert h.kv_pack == 4 and cache["k"].shape == (2, 5, 1, SEQ, 32)
    assert cache["s"].shape == (6, 5, 3, 64) and cache["s"].dtype == jnp.bfloat16
    # the recurrent state is float32 whatever the activations are
    assert cache["r"].shape == (6, 5, 16, 32) and cache["r"].dtype == jnp.float32


def test_the_published_sizes_give_the_files_bytes():
    """`tensor_plan` at the benchmark configuration's sizes: the 5,801.8 M Q40
    weights (3.26 GB in the file) and 0.41 GB of f32 embedding that ISSUE 46
    reckons, and the states a lane keeps."""
    with open(os.path.join(
            ROOT, "benchmark", "configs", "granite-4.0-h-small-l20-e18.json")) as f:
        cfg = json.load(f)
    wire = weights.header_for(cfg)
    h = LlmHeader(
        arch=LlmArch.GRANITE_MOE_HYBRID, dim=wire["dim"], hidden_dim=wire["hidden_dim"],
        n_layers=wire["n_layers"], n_heads=wire["n_heads"], n_kv_heads=wire["n_kv_heads"],
        n_experts=wire["n_experts"], n_active_experts=wire["n_active_experts"],
        vocab_size=wire["vocab_size"], head_dim=wire["head_dim"],
        n_shared_experts=wire["n_shared_experts"],
        n_routed_experts=wire["n_routed_experts"], ssm_n_heads=wire["ssm_n_heads"],
        ssm_head_dim=wire["ssm_head_dim"], ssm_state_dim=wire["ssm_state_dim"],
        ssm_conv_taps=wire["ssm_conv_taps"],
        attn_layers=wire["attn_layers_lo"] | wire["attn_layers_hi"] << 30)
    assert [l for l in range(20) if h.attn_layers >> l & 1] == [5, 15]
    assert [k.ssm for k in layer_table(h)] == [t == "mamba" for t in cfg["layer_types"]]
    assert wire["residual_multiplier_nano"] == 220_000_000
    assert wire["attention_multiplier_nano"] == 7_812_500
    assert (wire["embed_multiplier_milli"], wire["logits_scaling_milli"]) == (12_000, 16_000)
    plan = tensor_plan(h)
    q40 = sum(s.n_elements for s in plan if s.float_type.name == "Q40")
    experts = 20 * 18 * 3 * 4096 * 768
    mixers = 18 * (4096 * 16768 + 8192 * 4096)
    attention = 2 * 4096 * (4096 + 1024 + 1024 + 4096)
    shared = 20 * 3 * 4096 * 1536
    assert q40 == experts + mixers + attention + shared + 25088 * 4096
    assert round(q40 / 1e6, 1) == 5801.8 and round(q40 * 18 / 32 / 1e9, 2) == 3.26
    by = {s.name: s for s in plan}
    assert by["embed"].nbytes == 25088 * 4096 * 4
    assert by["layers.0.ssm_in_xbc"].shape == (8448, 4096)
    assert by["layers.5.moe_gate"].shape == (72, 4096)
    # a lane's states: 4 MB a layer, 75 MB over the 18
    assert h.ssm_n_heads * h.ssm_head_dim * h.ssm_state_dim * 4 == 1 << 22
    lanes = cfg["serving"]["lanes"]
    cache = jax.eval_shape(lambda: init_kv_cache(h, lanes, jnp.bfloat16, seq_len=4096))
    assert cache["r"].shape == (18, lanes, 128, 8192)
    assert cache["s"].shape == (18, lanes, 3, 8448)
    assert cache["k"].shape == (2, lanes, 8, 4096, 128)


@pytest.mark.parametrize("header,named", [
    ({"ssm_conv_taps": 1}, "ssm_conv_taps >= 2"),
    ({"ssm_n_groups": 2}, "one group"),
    ({"ssm_state_dim": 0}, "ssm_head_dim and"),
    ({"conv_l_cache": 3}, "one kind of"),
    ({"sliding_window": 32}, "full attention alone"),
    ({"attn_layers_lo": 0}, "layers of both kinds"),
    ({"attn_layers_lo": 1 << 20}, "attention layers"),
])
def test_a_header_that_cannot_be_served_is_refused_at_the_read(tmp_path, header, named):
    cfg = tiny()
    cfg["file"]["header"].update(header)
    with pytest.raises(ValueError, match=named):
        weights.write_model(str(tmp_path / "m.m"), cfg, 3)


# -- through the engine: lanes, the pool, the scheduler ----------------------------

LANES = 8


@pytest.fixture(scope="module")
def lanes(tmp_path_factory):
    from helpers import make_tiny_granite

    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path_factory.mktemp("granite") / "m.m")
    cfg = make_tiny_granite(path)
    e = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0, batch_size=LANES,
                        prefill_buckets=(1, 8, CHUNK), max_seq_len=SEQ)
    return e, cfg, path


def greedy_gap(path, cfg, prompt, generated):
    """How far below the reference's largest logit each generated token lies,
    in logit std, the sequence teacher-forced."""
    seq = prompt + generated
    logits = np.asarray(granite.last_logits(path, cfg, [seq[:-1]], [len(generated)])[0])
    return (logits.max(-1) - logits[np.arange(len(generated)), generated]) / logits.std(-1)


def test_both_states_live_in_the_cache_and_the_gauge_says_their_bytes(lanes):
    e, _, _ = lanes
    assert set(e.cache) == {"k", "v", "s", "r"}
    assert e.cache["k"].shape == (2, LANES, 1, SEQ + CHUNK, 32)
    assert e.cache["s"].shape == (6, LANES, 3, 64)
    assert e.cache["r"].shape == (6, LANES, 16, 32) and e.cache["r"].dtype == jnp.float32
    assert e.kv_cache_bytes["conv"] == 6 * LANES * 3 * 64 * 4
    assert e.kv_cache_bytes["recurrent"] == 6 * LANES * 4 * 8 * 16 * 4
    event = e.recorder.events("kv_cache")[-1]
    assert event["recurrent_bytes"] == e.kv_cache_bytes["recurrent"]
    assert e.obs.render().count('dllama_kv_cache_bytes{kind="recurrent"}') == 1
    # no replay rebuilds a state that reaches back to position 0
    assert e.state_unbounded and e.state_replay_rows == 0
    assert e.kv_publishable(100) == 0


def test_lanes_admitted_at_different_times_each_equal_their_own(lanes):
    """Lanes are admitted in three waves between decode blocks, prompts of 1 to
    75 tokens (one chunk, several, none at all). Every lane's greedy stream is
    the reference's for its own sequence; and at every admission (a chunk
    program, padded to its bucket) and at every block the states of each lane
    that stands are bit for bit what they were."""
    e, cfg, path = lanes
    e.reset()
    lengths = [45, 9, 70, 1, 16, 17, 33, 75]
    prompts = [token_ids(n, seed=100 + i) for i, n in enumerate(lengths)]
    hist = [list(p) for p in prompts]
    live: list[int] = []

    def states():
        return np.asarray(e.cache["s"]), np.asarray(e.cache["r"])

    def stood(before, after, lane):
        return all(np.array_equal(a[:, lane], b[:, lane]) for a, b in zip(after, before))

    def block(n_steps=4):
        before = states()
        active = [l in live for l in range(LANES)]
        out = e.decode_lanes(
            [h_[-1] for h_ in hist], [len(h_) - 1 for h_ in hist], n_steps, active)
        for row in out:
            for l in live:
                hist[l].append(row[l])
        after = states()
        for l in range(LANES):
            assert stood(before, after, l) == (l not in live), l

    installs0 = e._m_state_installs.labels(how="zero").value
    for wave in ([0, 2, 5], [1, 3, 7], [4, 6]):
        for lane in wave:
            before = states()
            e.prefill_lane(lane, prompts[lane])
            after = states()
            for other in range(LANES):
                if other != lane:
                    assert stood(before, after, other), (lane, other)
            live.append(lane)
        block()
        block(3)
    for lane in range(LANES):
        generated = hist[lane][lengths[lane]:]
        assert len(generated) >= 7
        assert greedy_gap(path, cfg, prompts[lane], generated).max() < TOL, lane
    # what the dispatches and the counters say of it
    assert e._m_state_installs.labels(how="zero").value - installs0 == 7  # lane 3 had no chunk
    chunk = [d for d in e.recorder.events("step_dispatch")
             if d["step"] == "prefill_lane_chunk" and d["lane"] == 7][-1]
    assert chunk["state_lanes"] == 1 and chunk["replay_tokens"] == 0
    dispatch = [d for d in e.recorder.events("step_dispatch") if d["step"] == "decode_lanes"][-1]
    assert dispatch["state_lanes"] == dispatch["n_live"] == LANES


def test_a_chunk_that_continues_nothing_and_a_lane_astray_are_refused(lanes):
    e, _, _ = lanes
    e.reset()
    with pytest.raises(ValueError, match="neither continues"):
        e.prefill_lane_chunk(3, token_ids(20), 40)
    with pytest.raises(ValueError, match="states stand at"):
        e.decode_lanes([5] * LANES, [30] * LANES, 2, [l == 4 for l in range(LANES)])


def test_a_refused_chunk_counts_no_row_and_a_run_one_its_rung(lanes):
    """`dllama_prefill_rows_total` and `dllama_prefill_chunks_total` (PR 49)
    count a chunk where it is dispatched: one refused for its lane's states
    counts nothing, and a prompt's chunks count their real rows and the
    rows of the rungs they ran."""
    e, _, _ = lanes
    e.reset()

    def counted():
        return [e._m_prefill_rows.labels(kind=k).value for k in ("real", "bucket")] + [
            e._m_prefill_chunks.labels(bucket=str(b)).value for b in (1, 8, CHUNK)]

    before = counted()
    with pytest.raises(ValueError, match="neither continues"):
        e.prefill_lane_chunk(3, token_ids(20), 40)
    assert counted() == before
    e.prefill_lane(3, token_ids(46))  # 45 fills: two whole chunks, 13 rows in a third
    assert [a - b for a, b in zip(counted(), before)] == [45, 3 * CHUNK, 0, 0, 3]
    e.prefill_lane(5, token_ids(6))  # 5 fills: the 8-row rung
    assert [a - b for a, b in zip(counted(), before)] == [50, 3 * CHUNK + 8, 0, 1, 3]


@pytest.mark.parametrize("kwargs,named", [
    ({"tp": 2}, "--tp 2"), ({"sp": 2}, "--sp 2"), ({"pp": 2}, "--pp 2"),
    ({"dp": 2}, "--dp 2"), ({"kv_dtype": "int8"}, "--kv-dtype int8"),
    ({"batch_size": 1}, "--batch-size 1"),
])
def test_what_lane_state_does_not_run_under_fails_at_start_up(lanes, kwargs, named):
    from dllama_tpu.runtime.engine import InferenceEngine

    _, _, path = lanes
    with pytest.raises(ValueError, match=named + ".*(state|lane).*GRANITE_MOE_HYBRID"):
        InferenceEngine(path, **{"tp": 1, "dtype": jnp.float32, "batch_size": 2,
                                 "max_seq_len": SEQ, **kwargs})


def test_pool_native_pages_speculation_and_the_single_stream_are_refused_by_name(lanes):
    e, _, _ = lanes
    with pytest.raises(ValueError, match="--kv-native.*GRANITE_MOE_HYBRID"):
        e.init_kv_pool(4, 40, native=True)
    with pytest.raises(ValueError, match="--speculation.*states.*GRANITE_MOE_HYBRID"):
        e.rehearse_admission(4, spec_k=4)
    for call in (lambda: e.prefill([1, 2, 3]), lambda: e.decode_block(1, 0, 4),
                 lambda: e.perplexity([1, 2, 3, 4])):
        with pytest.raises(ValueError, match="lane programs.*GRANITE_MOE_HYBRID"):
            call()


# -- through the HTTP front: adoption declined, park and resume ----------------------


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    """Two lanes behind the scheduler and the pool, up to four streams."""
    from helpers import make_tiny_granite

    from dllama_tpu.models.synthetic import write_synth_tokenizer
    from dllama_tpu.runtime.api_server import serve
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.tokenizer import Tokenizer

    d = tmp_path_factory.mktemp("granitesrv")
    mp, tp_ = str(d / "m.m"), str(d / "t.t")
    make_tiny_granite(mp)
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


def test_adoption_is_declined_and_counted_and_nothing_is_published(server):
    """The same four requests through a server without a pool and through one
    with it, one at a time and then all four at once on two lanes, so that
    streams are parked and resumed: every answer is the cold one, byte for
    byte, because no lane ever adopts a prefix (its recurrent states would not
    come with the rows) and none is stored; a parked stream runs its history
    again from position 0."""
    plain, plain_url = server(kv_page_size=-1)  # no pool
    cold = [chat(plain_url, p) for p in PROMPTS]
    srv, url = server(kv_page_size=4, max_streams=4)
    e, sched = srv.state.engine, srv.state.scheduler
    declined = e._m_adoptions_declined.labels(why="unbounded")
    declined0, hits0 = declined.value, srv.state.m_prefix_hits.value
    assert [chat(url, p) for p in PROMPTS] == cold
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
    assert sched._n_parked == 0 and not sched.pending
    srv.state.kv_manager.check()
    # nothing was stored, so nothing could be matched, adopted or replayed
    assert srv.state.m_prefix_hits.value == hits0
    assert e._m_state_installs.labels(how="replay").value == 0
    assert not [d for d in e.recorder.events("step_dispatch") if d["step"] == "kv_adopt"]
    prompt = srv.state.tokenizer.encode(PROMPTS[0], is_start=True, add_special_tokens=True)
    assert sched.kv.match(0, prompt)[0] == 0
    sched.kv.release_lane(0)
    # and were rows stored all the same (another process's pages), the lane declines them
    e.state_unbounded = False
    try:
        chat(url, PROMPTS[0], max_tokens=4)  # publishes
    finally:
        e.state_unbounded = True
    stored, _ = sched.kv.match(0, prompt)
    sched.kv.release_lane(0)
    assert stored > 0
    assert sched._match_prefix(0, prompt) == (0, [])
    assert declined.value == declined0 + 1
    assert e.recorder.events("prefix_adoption_declined")[-1]["why"] == "unbounded"
    sched.kv.check()


@pytest.mark.parametrize("n_prompt", [100, 200])
def test_a_prompt_through_a_middle_rung_leaves_what_the_largest_rung_leaves(lanes, n_prompt):
    """The served ladder's rungs at 128 and 256 rows (PR 49) against the 512
    this family's chunk program always ran at: cache rows, lane states and
    the next token's logits of one prompt through either."""
    from helpers import assert_a_middle_rung_equals_the_largest

    assert_a_middle_rung_equals_the_largest(lanes[2], n_prompt)
