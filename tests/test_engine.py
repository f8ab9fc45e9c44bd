"""Engine + CLI tests: generation invariants and the dllama-compatible
command surface."""

import contextlib
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.formats import FloatType
from dllama_tpu.formats.model_file import LlmArch
from dllama_tpu.runtime.engine import InferenceEngine, prefill_ladder
from dllama_tpu.runtime.faults import InjectedFault
from dllama_tpu.tokenizer import Tokenizer

from helpers import (
    MIDDLE_RUNG_PROMPTS, REPO_ROOT, TINY, assert_a_middle_rung_equals_the_largest, make_tiny_model,
    make_tiny_tokenizer)


@pytest.fixture()
def tiny_model(tmp_path):
    mp = str(tmp_path / "m.m")
    tp_ = str(tmp_path / "t.t")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=288, seq_len=64)
    make_tiny_model(mp, weight_type=FloatType.Q40, cfg=cfg)
    make_tiny_tokenizer(tp_, chat_template="<|start_header_id|>")
    return mp, tp_


def test_generate_deterministic_greedy(tiny_model):
    mp, tp_ = tiny_model
    eng = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    out1, ev1, pr1 = eng.generate([1, 2, 3, 4], max_steps=12)
    eng.reset()
    out2, _, _ = eng.generate([1, 2, 3, 4], max_steps=12)
    assert out1 == out2
    assert len(out1) == 12 - 3  # maxPos - prefill positions
    assert ev1.n_tokens == 3
    assert pr1.n_tokens == len(out1)


def test_generate_tp_matches_single_chip(tiny_model):
    """The engine's sharded decode must produce the same greedy tokens as
    single-chip — end-to-end TP equivalence including sampling."""
    mp, _ = tiny_model
    e1 = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    out1, _, _ = e1.generate([5, 6, 7], max_steps=10)
    e4 = InferenceEngine(mp, tp=4, dtype=jnp.float32, temperature=0.0)
    out4, _, _ = e4.generate([5, 6, 7], max_steps=10)
    assert out1 == out4


def test_generate_with_sampling_seeded(tiny_model):
    mp, _ = tiny_model
    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.9, topp=0.9, seed=7)
    out1, _, _ = e.generate([1, 2, 3], max_steps=10)
    e2 = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.9, topp=0.9, seed=7)
    out2, _, _ = e2.generate([1, 2, 3], max_steps=10)
    assert out1 == out2


def test_prefill_bucketing_consistent(tiny_model):
    """Bucketed/padded prefill must give the same next tokens as unbucketed."""
    mp, _ = tiny_model
    prompt = list(range(1, 12))  # 11 tokens -> buckets pad to 32 etc.
    ea = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                         prefill_buckets=(4,))
    outa, _, _ = ea.generate(prompt, max_steps=16)
    eb = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                         prefill_buckets=(32,))
    outb, _, _ = eb.generate(prompt, max_steps=16)
    assert outa == outb


def test_max_seq_len_clamps(tiny_model):
    mp, _ = tiny_model
    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, max_seq_len=16, temperature=0.0)
    assert e.header.seq_len == 16
    out, _, _ = e.generate([1, 2, 3], max_steps=100)
    assert len(out) == 16 - 2  # clamped by seq_len, not steps


def _run_cli(args, env_extra=None):
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-m", "dllama_tpu"] + args,
        capture_output=True,
        text=True,
        env=env,
        cwd=REPO_ROOT,
        timeout=600,
    )


def test_cli_inference(tiny_model):
    mp, tp_ = tiny_model
    r = _run_cli(
        ["inference", "--model", mp, "--tokenizer", tp_,
         "--prompt", "hello world", "--steps", "16",
         "--temperature", "0.0", "--dtype", "f32", "--tp", "2"]
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "🔶 Pred" in r.stdout
    assert "tokens/s:" in r.stdout
    assert "Evaluation" in r.stdout and "Prediction" in r.stdout


def test_cli_help_renders():
    """--help must not crash: argparse %-expands help strings, so a bare
    `%` in any of them raises at render time (regression: the --dp help
    carried an unescaped `% dp`)."""
    r = _run_cli(["--help"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "--weight-format {auto,q40,dense}" in r.stdout
    assert "q40i4" not in r.stdout


def test_cli_perplexity(tiny_model):
    mp, tp_ = tiny_model
    r = _run_cli(
        ["perplexity", "--model", mp, "--tokenizer", tp_,
         "--prompt", "hello world hello world", "--dtype", "f32"]
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "perplexity:" in r.stdout


def test_cli_worker_mode_explains(tiny_model):
    r = _run_cli(["worker"])
    assert r.returncode != 0
    assert "SPMD" in r.stderr or "SPMD" in r.stdout


def test_cli_rejects_gpu_flags(tiny_model):
    mp, tp_ = tiny_model
    r = _run_cli(
        ["inference", "--model", mp, "--tokenizer", tp_, "--prompt", "x",
         "--steps", "4", "--gpu-index", "0"]
    )
    assert r.returncode != 0
    assert "TPU" in (r.stderr + r.stdout)


def test_prefill_bucket_never_pads_past_seq_len(tiny_model):
    """Padded chunk extent must respect seqLen (dynamic_update_slice clamps
    silently otherwise, corrupting earlier cache rows)."""
    mp, _ = tiny_model
    # seq_len=64; prompt of 44 with buckets (8, 32): last chunks must not
    # write a padded 32-wide window past position 64
    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                        max_seq_len=48, prefill_buckets=(8, 32))
    prompt = list(range(1, 45))  # 44 tokens
    out_bucketed, _, _ = e.generate(prompt, max_steps=47)
    e2 = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                         max_seq_len=48, prefill_buckets=(8,))
    out_exact, _, _ = e2.generate(prompt, max_steps=47)
    assert out_bucketed == out_exact


def test_quant_weight_format_matches_dense(tiny_model):
    """weight_format='q40' must reproduce the dense-load greedy tokens
    exactly (off-TPU the quant path dequantizes at run time — numerically
    identical to dequant-at-load)."""
    mp, _ = tiny_model
    e_dense = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                              weight_format="dense")
    out_dense, _, _ = e_dense.generate([1, 2, 3, 4], max_steps=12)
    e_quant = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                              weight_format="q40")
    out_quant, _, _ = e_quant.generate([1, 2, 3, 4], max_steps=12)
    assert out_dense == out_quant


def test_quant_weight_format_tp(tmp_path):
    """Quantized weights sharded over a tp=4 mesh reproduce single-chip.
    Dims must divide by 32*tp (the scale tensors shard their block axis)."""
    mp = str(tmp_path / "mq.m")
    cfg = dict(dim=128, hidden_dim=256, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=256, seq_len=64)
    make_tiny_model(mp, weight_type=FloatType.Q40, cfg=cfg)
    e1 = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                         weight_format="q40")
    out1, _, _ = e1.generate([5, 6, 7], max_steps=10)
    e4 = InferenceEngine(mp, tp=4, dtype=jnp.float32, temperature=0.0,
                         weight_format="q40")
    out4, _, _ = e4.generate([5, 6, 7], max_steps=10)
    assert out1 == out4


def test_quant_weight_format_moe_matches_dense(tmp_path):
    """Qwen3-MoE with weight_format='q40' keeps the expert weights
    block-quantized on device (the reference stores experts Q40 too,
    src/llm.cpp:425-499) and must reproduce the dense-load greedy tokens."""
    from dllama_tpu.ops.quant_matmul import QuantWeight

    mp = str(tmp_path / "moe.m")
    make_tiny_model(mp, arch=LlmArch.QWEN3_MOE, weight_type=FloatType.Q40)
    e_dense = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                              weight_format="dense")
    out_dense, _, _ = e_dense.generate([1, 2, 3, 4], max_steps=12)
    e_quant = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                              weight_format="q40")
    # the experts really are stored quantized: int8 values + f32 scales
    w1 = e_quant.params["layers"]["w1"]
    assert isinstance(w1, QuantWeight) and w1.q.dtype == jnp.int8
    assert w1.q.ndim == 4  # [L, E, D, F]
    out_quant, _, _ = e_quant.generate([1, 2, 3, 4], max_steps=12)
    assert out_dense == out_quant


def test_quant_rejects_non_q40(tmp_path):
    mp = str(tmp_path / "f32.m")
    make_tiny_model(mp, weight_type=FloatType.F32)
    with pytest.raises(ValueError, match="q40"):
        InferenceEngine(mp, tp=1, dtype=jnp.float32, weight_format="q40")


def test_prefetch_builder_failure_is_recorded(tiny_model, caplog):
    """A builder exception in the _prefetch daemon thread must not vanish
    silently: it is logged, the key is marked 'prefetch-failed' in
    _compile_origin, the inflight slot is released (so the boundary
    crossing doesn't deadlock on a never-set event), and the engine keeps
    serving (the dispatch path falls back to a synchronous compile)."""
    import logging
    import time

    mp, _ = tiny_model
    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    key = ("block", 99, True, e._attn_window(1))

    def boom():
        raise RuntimeError("synthetic prefetch failure")

    with caplog.at_level(logging.ERROR, logger="dllama_tpu.runtime.engine"):
        e._prefetch(key, boom)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with e._compile_lock:
                if key not in e._inflight:
                    break
            time.sleep(0.01)
    with e._compile_lock:
        assert key not in e._inflight
        assert e._compile_origin.get(key) == "prefetch-failed"
        assert key not in e._compiled
    assert any("prefetch failed" in r.message for r in caplog.records)
    out, _, _ = e.generate([1, 2, 3], max_steps=4)
    assert len(out) > 0


def test_packed_weight_format_matches_q40(tiny_model):
    """weight_format='q40i4' (packed nibbles, the same f32 scales)
    reproduces the q40 greedy tokens exactly: the nibble unpack is
    lossless, so off-TPU the two dequant paths are bit-identical. Also pins
    the loaded leaf layout (the point of the format: 0.625 B/w on device
    instead of 1.125)."""
    from dllama_tpu.models.loader import FusedQuantWeight
    from dllama_tpu.ops.quant_matmul import PackedQuantWeight

    mp, _ = tiny_model
    e_q40 = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                            weight_format="q40")
    out_q40, _, _ = e_q40.generate([1, 2, 3, 4], max_steps=12)
    e_i4 = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                           weight_format="q40i4")
    out_i4, _, _ = e_i4.generate([1, 2, 3, 4], max_steps=12)
    assert out_q40 == out_i4

    wqkv = e_i4.params["layers"]["wqkv"]
    assert isinstance(wqkv, FusedQuantWeight)
    pw = wqkv.weight
    assert isinstance(pw, PackedQuantWeight)
    assert pw.qp.dtype == jnp.int32 and pw.d.dtype == jnp.float32
    n_weights = pw.in_dim * pw.out_dim * pw.qp.shape[0]  # [L, in//8, out]
    assert (pw.qp.nbytes + pw.d.nbytes) / n_weights == 0.625


def test_packed_weight_format_tp(tmp_path):
    """Packed weights sharded over a tp=2 mesh reproduce single-chip: every
    in dim is whole groups of 256 rows a shard (the engine's check), so the
    in//8 (word) and in//32 (scale) axes split at group boundaries and each
    col shard is a packed tensor of its own. A width that is not is refused
    by name."""
    mp = str(tmp_path / "mq4.m")
    cfg = dict(dim=512, hidden_dim=512, n_layers=2, n_heads=32, n_kv_heads=4,
               head_dim=16, vocab_size=256, seq_len=64)
    make_tiny_model(mp, weight_type=FloatType.Q40, cfg=cfg)
    e1 = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                         weight_format="q40i4")
    out1, _, _ = e1.generate([5, 6, 7], max_steps=10)
    e2 = InferenceEngine(mp, tp=2, dtype=jnp.float32, temperature=0.0,
                         weight_format="q40i4")
    out2, _, _ = e2.generate([5, 6, 7], max_steps=10)
    assert out1 == out2
    with pytest.raises(ValueError, match="divisible by 1024"):
        InferenceEngine(mp, tp=4, dtype=jnp.float32, temperature=0.0,
                        weight_format="q40i4")


def test_packed_weight_format_moe_keeps_int8_experts(tmp_path):
    """q40i4 on a Qwen3-MoE whose expert in axes are no whole groups of 256
    rows (64 and 96 here) packs the attention/dense weights but leaves the
    expert stacks in the int8 QuantWeight layout, and still reproduces the
    q40 greedy tokens."""
    from dllama_tpu.ops.quant_matmul import PackedQuantWeight, QuantWeight

    mp = str(tmp_path / "moe4.m")
    make_tiny_model(mp, arch=LlmArch.QWEN3_MOE, weight_type=FloatType.Q40)
    e_q40 = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                            weight_format="q40")
    out_q40, _, _ = e_q40.generate([1, 2, 3, 4], max_steps=12)
    e_i4 = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                           weight_format="q40i4")
    w1 = e_i4.params["layers"]["w1"]
    assert isinstance(w1, QuantWeight) and not isinstance(w1, PackedQuantWeight)
    assert w1.q.dtype == jnp.int8 and w1.q.ndim == 4  # [L, E, D, F]
    wo = e_i4.params["layers"]["wo"]
    assert isinstance(wo, PackedQuantWeight)
    out_i4, _, _ = e_i4.generate([1, 2, 3, 4], max_steps=12)
    assert out_q40 == out_i4


# a sparse model every in axis of which is whole groups of 256 rows, a tp
# shard of two included
_PACKABLE_MOE = dict(dim=512, hidden_dim=512, moe_hidden_dim=512, n_layers=2,
                     n_heads=32, n_kv_heads=4, head_dim=16, vocab_size=256,
                     seq_len=64, n_experts=4, n_active_experts=2)


def test_packed_experts_on_one_device_int8_on_a_mesh(tmp_path):
    """Where one device holds a sparse layer whole, q40i4 holds the routed
    experts packed too: the `weights` event's `decode_packed_share` reads
    0.95 or more (0.0 under q40). On a mesh (tp = 2) the experts stay int8,
    as under q40, because the mesh's expert kernels read that layout. All
    three serve the same tokens."""
    from dllama_tpu.ops.quant_matmul import PackedQuantWeight, QuantWeight

    mp = str(tmp_path / "moe_packable.m")
    make_tiny_model(mp, arch=LlmArch.QWEN3_MOE, weight_type=FloatType.Q40,
                    cfg=_PACKABLE_MOE)
    share, out = {}, {}
    for name, fmt, tp in (("q40", "q40", 1), ("packed", "q40i4", 1), ("mesh", "q40i4", 2)):
        e = InferenceEngine(mp, tp=tp, dtype=jnp.float32, temperature=0.0,
                            weight_format=fmt)
        (event,) = e.recorder.events("weights")[-1:]
        assert event["decode_packed_share"] == e.weight_bytes["decode_packed_share"]
        share[name] = event["decode_packed_share"]
        w1 = e.params["layers"]["w1"]
        if name == "packed":
            assert type(w1) is PackedQuantWeight and w1.qp.dtype == jnp.int32
            assert w1.qp.shape == (2, 4, 512 // 8, 512)  # [L, E, D // 8, F]
            assert e.weight_bytes["int8"] == 0
        else:
            assert type(w1) is QuantWeight and w1.q.dtype == jnp.int8
        out[name], _, _ = e.generate([1, 2, 3, 4], max_steps=12)
    assert share["q40"] == 0.0 and share["packed"] >= 0.95
    assert 0.0 < share["mesh"] < 0.95
    assert out["q40"] == out["packed"] == out["mesh"]


@pytest.mark.parametrize("path", ["streamed", "host-stack"])
def test_experts_packed_from_the_wire_equal_pack_nibbles_of_int8(
        tmp_path, monkeypatch, path):
    """The expert stacks the loader packs straight from the wire's bytes
    (an expert at a time: `q40_pack_transposed` or its numpy twin) are
    `pack_nibbles` of the int8 stacks, words and scales, on the streamed
    shard path and on the host-stack path."""
    from dllama_tpu.ops.quant_matmul import PackedQuantWeight, pack_nibbles

    mp = str(tmp_path / "moe_wire.m")
    make_tiny_model(mp, arch=LlmArch.QWEN3_MOE, weight_type=FloatType.Q40,
                    cfg=_PACKABLE_MOE)
    if path == "host-stack":
        monkeypatch.setenv("DLLAMA_STREAM_LOAD", "0")
    held = {
        fmt: InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                             weight_format=fmt).params["layers"]
        for fmt in ("q40", "q40i4")
    }
    for n in ("w1", "w2", "w3"):
        got, want = held["q40i4"][n], pack_nibbles(held["q40"][n])
        assert type(got) is PackedQuantWeight
        np.testing.assert_array_equal(np.asarray(got.qp), np.asarray(want.qp))
        np.testing.assert_array_equal(np.asarray(got.d), np.asarray(want.d))


def test_packed_streamed_load_matches_host_stack(tmp_path, monkeypatch):
    """The streamed shard loader (per-shard host pack) and the host-stack
    path produce byte-identical packed param trees."""
    from jax.tree_util import tree_leaves_with_path

    mp = str(tmp_path / "mq4s.m")
    cfg = dict(dim=512, hidden_dim=512, n_layers=2, n_heads=32, n_kv_heads=4,
               head_dim=16, vocab_size=256, seq_len=64)
    make_tiny_model(mp, weight_type=FloatType.Q40, cfg=cfg)
    e_stream = InferenceEngine(mp, tp=2, dtype=jnp.float32, temperature=0.0,
                               weight_format="q40i4")
    monkeypatch.setenv("DLLAMA_STREAM_LOAD", "0")
    e_host = InferenceEngine(mp, tp=2, dtype=jnp.float32, temperature=0.0,
                             weight_format="q40i4")
    a = tree_leaves_with_path(e_stream.params)
    b = tree_leaves_with_path(e_host.params)
    assert len(a) == len(b)
    for (pa, la), (pb, lb) in zip(a, b):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_generate_batch_unequal_prompts_match_single(tiny_model):
    """Per-lane serving: three lanes with different prompt lengths decode
    together (parked prefill + per-lane positions) and must reproduce each
    prompt's single-stream greedy output exactly."""
    mp, _ = tiny_model
    prompts = [[1, 2, 3, 4], [9, 8, 7, 6, 5, 4, 3], [40, 41]]
    singles = []
    e1 = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    for p in prompts:
        e1.reset()
        out, _, _ = e1.generate(p, max_steps=20)
        singles.append(out)
    eb = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                         batch_size=3)
    outs = eb.generate_batch(prompts, max_steps=20)
    assert outs == singles, (outs, singles)


def test_engine_moe_lanes_unequal_prompts(tmp_path):
    """Qwen3-MoE through the per-lane serving surface: unequal prompts in
    lanes reproduce single-stream outputs (per-token routing must respect
    lane boundaries)."""
    path = str(tmp_path / "moe.m")
    make_tiny_model(path, arch=LlmArch.QWEN3_MOE, weight_type=FloatType.F32)
    prompts = [[1, 2, 3, 4, 5, 6], [9, 8, 7]]
    e1 = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0)
    singles = []
    for p in prompts:
        e1.reset()
        out, _, _ = e1.generate(p, max_steps=16)
        singles.append(out)
    eb = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0,
                         batch_size=2)
    outs = eb.generate_batch(prompts, max_steps=16)
    assert outs == singles, (outs, singles)


def test_moe_lane_block_counts_routed_pairs_on_one_device(tmp_path):
    """Qwen3-MoE on one device takes the path that computes the pairs that
    landed here, with every expert held: a decode block records `moe_route`
    (all routed pairs land, the touched experts are the distinct ones of a
    layer and step) and moves both counters, in the block's one output."""
    path = str(tmp_path / "moe.m")
    make_tiny_model(path, arch=LlmArch.QWEN3_MOE, weight_type=FloatType.Q40)
    e = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0, batch_size=4)
    e.prefill_lane(0, [1, 2, 3, 4, 5, 6])
    e.prefill_lane(2, [9, 8, 7])

    def counters():
        return [e._m_moe_pairs.labels(landed="routed").value,
                e._m_moe_pairs.labels(landed="held").value, e._m_moe_touched.value]

    before = counters()
    n_steps, n_live, n_expert_layers = 4, 2, e.header.n_layers
    n0 = len(e.recorder.events("moe_route"))
    out = e.decode_lanes([6, 0, 7, 0], [5, 0, 2, 0], n_steps,
                         active=[True, False, True, False])
    assert np.asarray(out).shape == (n_steps, 4)
    (event,) = e.recorder.events("moe_route")[n0:]
    assert event["n_steps"] == n_steps
    assert event["pairs_routed"] == (
        n_steps * n_live * e.header.n_active_experts * n_expert_layers)
    assert event["pairs_held"] == event["pairs_routed"]
    assert 0 < event["held_touched"] <= min(
        event["pairs_held"], e.header.n_experts * n_expert_layers * n_steps)
    assert [a - b for a, b in zip(counters(), before)] == [
        event["pairs_routed"], event["pairs_held"], event["held_touched"]]


@pytest.mark.parametrize("case", ["one_device", "dp2", "dense"])
def test_chunk_dispatch_says_which_rows_its_experts_computed(tmp_path, tiny_model, case):
    """A chunk program admits one lane of four and its expert block computes
    that lane's rows alone: `step_dispatch` and the `prefill_lane_chunk`
    span carry `expert_rows` = the bucket, and
    `dllama_moe_chunk_rows_total` counts them and the parked lanes' rows
    left out. Lanes split over devices (`dp`) compute every lane's rows and
    say so; a dense model says nothing. What the routing counters count of a
    served prompt is what they counted before: the live lanes' pairs of the
    decode block, every one landed."""
    if case == "dense":
        path = tiny_model[0]
    else:
        path = str(tmp_path / "moe.m")
        make_tiny_model(path, arch=LlmArch.QWEN3_MOE, weight_type=FloatType.Q40)
    lanes = 4
    e = InferenceEngine(path, tp=1, dp=2 if case == "dp2" else 1, dtype=jnp.float32,
                        temperature=0.0, batch_size=lanes)
    def rows_counted():
        return [e._m_moe_chunk_rows.labels(rows=r).value for r in ("computed", "parked_skipped")]

    # the recorder, the span ring and the registry outlive an engine
    n_spans, n_events = e._spans.total_recorded, len(e.recorder.events("step_dispatch"))
    n_routes, rows0 = len(e.recorder.events("moe_route")), rows_counted()
    routed0 = e._m_moe_pairs.labels(landed="routed").value
    prompt = list(range(1, 12))
    e.prefill_lane(2, prompt)
    spans = [sp for sp in e._spans.completed()[-(e._spans.total_recorded - n_spans):]
             if sp["name"] == "prefill_lane_chunk"]
    chunks = [ev for ev in e.recorder.events("step_dispatch")[n_events:]
              if ev["step"] == "prefill_lane_chunk"]
    assert chunks and sum(ev["n_tokens"] for ev in chunks) == len(prompt) - 1
    computed, skipped = (a - b for a, b in zip(rows_counted(), rows0))
    every = sum(lanes * ev["bucket"] for ev in chunks)
    if case == "dense":
        assert all("expert_rows" not in ev for ev in chunks)
        assert (computed, skipped) == (0, 0)
        return
    want = [ev["bucket"] * (lanes if case == "dp2" else 1) for ev in chunks]
    assert [ev["expert_rows"] for ev in chunks] == want
    assert [sp["attrs"]["expert_rows"] for sp in spans] == want
    assert (computed, skipped) == (sum(want), every - sum(want))
    n_steps = 4
    e.decode_lanes([0, 0, prompt[-1], 0], [0, 0, len(prompt) - 1, 0], n_steps,
                   active=[False, False, True, False])
    if case == "dp2":
        return  # more than one device: the older kernels, which count nothing
    (event,) = e.recorder.events("moe_route")[n_routes:]
    routed = n_steps * e.header.n_active_experts * e.header.n_layers
    assert (event["pairs_routed"], event["pairs_held"]) == (routed, routed)
    assert e._m_moe_pairs.labels(landed="routed").value - routed0 == routed


def test_prefill_lane_preserves_other_lanes(tiny_model):
    """Prefilling a new request into a free lane must not disturb a lane
    mid-conversation: decode lane 0, prefill lane 1, keep decoding lane 0
    — the token stream must equal an undisturbed run."""
    mp, _ = tiny_model
    prompt = [5, 6, 7, 8, 9]
    e1 = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    expected, _, _ = e1.generate(prompt, max_steps=20)

    eb = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                         batch_size=2)
    eb.prefill_lane(0, prompt)
    pos = [len(prompt) - 1, 0]
    toks = [prompt[-1], 0]
    got = []
    rows = eb.decode_lanes(toks, pos, 6, active=[True, False])
    got += [r[0] for r in rows]
    pos[0] += len(rows)
    toks[0] = got[-1]
    # admit a second request mid-stream, then continue lane 0
    eb.prefill_lane(1, [30, 31, 32, 33, 34, 35, 36, 37, 38])
    pos[1], toks[1] = 8, 38
    while pos[0] < 20:
        rows = eb.decode_lanes(toks, pos, 4, active=[True, True])
        if not rows:
            break
        got += [r[0] for r in rows][: 20 - pos[0]]
        pos = [pos[0] + len(rows), pos[1] + len(rows)]
        toks = [rows[-1][0], rows[-1][1]]
    assert got == expected, (got, expected)


def test_perplexity_chunk_size_invariant(tiny_model):
    """Chunked on-device scoring must be invariant to the prefill bucket
    shape (the chunks see earlier chunks only through the KV cache), and
    match a direct full-prompt numpy computation of the NLL."""
    mp, _ = tiny_model
    prompt = [(i * 7 + 3) % 256 for i in range(50)]

    ppls = []
    for buckets in [(4,), (8, 32), (50,)]:
        e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                            prefill_buckets=buckets)
        nll, ppl, n = e.perplexity(prompt)
        assert n == len(prompt) - 1
        ppls.append(ppl)
    assert abs(ppls[0] - ppls[1]) < 1e-3 and abs(ppls[0] - ppls[2]) < 1e-3, ppls

    # oracle: single un-chunked forward, host softmax
    from dllama_tpu.models import forward

    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    cache = e._fresh_cache()
    arr = jnp.asarray([prompt], dtype=jnp.int32)
    logits, _ = forward(e.params, e.header, arr, jnp.int32(0), cache,
                        mesh=e.mesh)
    lg = np.asarray(logits, np.float32)[0]
    mx = lg.max(-1, keepdims=True)
    logprobs = lg - mx - np.log(np.exp(lg - mx).sum(-1, keepdims=True))
    nll_ref = -np.mean(
        [logprobs[i, prompt[i + 1]] for i in range(len(prompt) - 1)]
    )
    assert abs(ppls[0] - float(np.exp(nll_ref))) < 1e-3


def test_topp_mask_matches_host_sampler_support():
    """The on-device top-p mask must keep exactly the token set the host
    (reference-parity) sampler can return — same nucleus, different RNG. Covers generic rows and the topp 0/1 edge cases
    where both paths degrade to the full distribution."""
    from dllama_tpu.runtime.engine import _topp_mask
    from dllama_tpu.runtime.sampler import softmax, topp_support

    rng = np.random.default_rng(3)
    v = 64
    for topp in (0.1, 0.5, 0.9, 0.99):
        for trial in range(5):
            logits = rng.standard_normal(v).astype(np.float32) * 3.0
            probs = softmax(logits / 0.8)
            order, _ = topp_support(probs, topp)  # the host sampler's set
            host_support = set(int(i) for i in order)

            masked = np.asarray(
                _topp_mask(jnp.asarray(probs)[None, :], jnp.float32(topp))
            )[0]
            device_support = set(int(i) for i in np.nonzero(masked > 0)[0])
            assert device_support == host_support, (
                topp, trial, device_support ^ host_support
            )
    # topp <= 0 / >= 1: both paths keep the whole distribution
    logits = rng.standard_normal(v).astype(np.float32)
    probs = softmax(logits)
    for topp in (0.0, 1.0):
        masked = np.asarray(
            _topp_mask(jnp.asarray(probs)[None, :], jnp.float32(topp))
        )[0]
        assert (masked > 0).all()
    # f32-cumsum saturation: topp above the summed mass must keep the
    # whole set (the host's empty-`over` branch), not collapse to top-1
    probs = np.full(v, 1.0 / v, np.float32)
    masked = np.asarray(
        _topp_mask(jnp.asarray(probs)[None, :], jnp.float32(0.999999))
    )[0]
    assert int((masked > 0).sum()) == v, int((masked > 0).sum())


def test_telemetry_report_and_ici():
    from dllama_tpu.models.synthetic import make_header, random_params
    from dllama_tpu.models import init_kv_cache
    from dllama_tpu.utils.telemetry import ici_traffic_per_token, memory_report

    h = make_header("tiny")
    params = random_params(h, dtype=jnp.float32)
    cache = init_kv_cache(h, 1)
    rep2 = memory_report(params, cache, n_devices=2)
    rep8 = memory_report(params, cache, n_devices=8)
    assert rep2.params_bytes > 0 and rep2.cache_bytes > 0
    assert 0 < rep2.replicated_bytes < rep2.total_bytes
    # the replicated portion must not shrink with chip count: per-chip at
    # 8 devices stays above a pure total/8 split by ~the replicated bytes
    assert rep8.per_device_bytes >= rep8.total_bytes // 8
    assert rep8.per_device_bytes - rep8.total_bytes // 8 >= int(
        rep8.replicated_bytes * 0.8
    )
    rep = rep2
    assert ici_traffic_per_token(h, 1) == 0
    t2 = ici_traffic_per_token(h, 2)
    t4 = ici_traffic_per_token(h, 4)
    assert t4 > t2 > 0
    assert ici_traffic_per_token(h, 2, include_logits=False) < t2


def test_generate_batch_lanes_independent(tiny_model):
    """dp lanes decode independent sequences; each lane must match a
    single-lane run of the same prompt."""
    mp, _ = tiny_model
    e2 = InferenceEngine(mp, tp=1, dp=2, batch_size=2, dtype=jnp.float32,
                         temperature=0.0)
    p1, p2 = [1, 2, 3, 4], [9, 8, 7, 6]
    outs = e2.generate_batch([p1, p2], max_steps=14)
    e1 = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    ref1, _, _ = e1.generate(p1, max_steps=14)
    e1.reset()
    ref2, _, _ = e1.generate(p2, max_steps=14)
    assert outs[0] == ref1
    assert outs[1] == ref2


def test_attn_window_equivalence(tmp_path):
    """Windowed attention (power-of-2 cache prefix) must reproduce the
    full-cache tokens on a long-seq-len model decoded at short positions."""
    mp = str(tmp_path / "w.m")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=288, seq_len=2048)
    make_tiny_model(mp, weight_type=FloatType.F32, cfg=cfg)
    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    assert e._attn_window(5) == 512          # min window
    assert e._attn_window(600) == 1024       # next pow2
    assert e._attn_window(1500) == 2048      # clamped to seq_len
    out_windowed, _, _ = e.generate([1, 2, 3, 4], max_steps=16)

    # force full-cache windows and compare
    e2 = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    e2._attn_window = lambda limit: cfg["seq_len"]
    out_full, _, _ = e2.generate([1, 2, 3, 4], max_steps=16)
    assert out_windowed == out_full

    # cross the 512 -> 1024 window boundary mid-generation (the risky edge:
    # window growth + recompile must not drop live cache rows)
    prompt = list(range(1, 509))
    e.reset()
    out_cross, _, _ = e.generate(prompt, max_steps=530)
    e2.reset()
    out_cross_full, _, _ = e2.generate(prompt, max_steps=530)
    assert out_cross == out_cross_full
    assert len(out_cross) == 530 - (len(prompt) - 1)


def test_ici_traffic_accounts_pp():
    from dllama_tpu.models.synthetic import make_header
    from dllama_tpu.utils.telemetry import ici_traffic_per_token

    h = make_header("tiny")
    assert ici_traffic_per_token(h, 1, pp=1) == 0
    t_pp = ici_traffic_per_token(h, 1, pp=2)
    assert t_pp > 0  # tick hand-offs + exit psum
    # pp traffic is per-token tiny next to tp's per-layer all-reduces
    assert t_pp < ici_traffic_per_token(h, 2, include_logits=False)


def test_cache_guard_recovers_from_failed_dispatch(tiny_model):
    """Crash consistency (reference analogue: dllama-api's whole-app
    retry, src/dllama-api.cpp:616-628): a dispatch that raises AFTER
    donating the KV cache must leave the engine usable — the guard swaps
    in a fresh cache (epoch moves) and the next generate produces the
    clean-engine token stream instead of a donated-buffer error."""
    mp, _ = tiny_model
    eng = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    clean, _, _ = eng.generate([1, 2, 3, 4], max_steps=12)
    eng.reset()
    epoch0 = eng.cache_epoch

    real = eng._decode_block_fn

    def poisoned(n_steps, greedy, window=0):
        block = real(n_steps, greedy, window)

        def bad(params, token, cache, pos, rng, temp, topp):
            block(params, token, cache, pos, rng, temp, topp)  # donates
            raise RuntimeError("injected dispatch failure")

        return bad

    eng._decode_block_fn = poisoned
    with pytest.raises(RuntimeError, match="injected"):
        eng.generate([1, 2, 3, 4], max_steps=12)
    eng._decode_block_fn = real

    assert eng.cache_epoch > epoch0  # the donated cache was replaced
    again, _, _ = eng.generate([1, 2, 3, 4], max_steps=12)
    assert again == clean


def test_kv_int8_bounded_quality_and_capacity(tiny_model):
    """kv_dtype=int8 (QuantKV per-row quantization)
    keeps teacher-forced NLL within a tight bound of the f32 cache and
    halves-ish the cache footprint (int8 values + 1/hd scale rows)."""
    mp, _ = tiny_model
    toks = [(i * 11) % 250 + 1 for i in range(40)]
    ef = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    nll_f, _, _ = ef.perplexity(toks)
    bytes_f = sum(
        v.nbytes for v in jax.tree_util.tree_leaves(ef.cache)
    )
    del ef
    e8 = InferenceEngine(
        mp, tp=1, dtype=jnp.float32, temperature=0.0, kv_dtype="int8"
    )
    nll_8, _, _ = e8.perplexity(toks)
    bytes_8 = sum(
        v.nbytes for v in jax.tree_util.tree_leaves(e8.cache)
    )
    assert abs(nll_8 - nll_f) / abs(nll_f) < 0.01, (nll_8, nll_f)
    # f32 reference cache = 4 B/elem; int8 = 1 B + 4/hd scale
    assert bytes_8 < 0.32 * bytes_f, (bytes_8, bytes_f)


def test_kv_int8_composes_with_sp_tp_pp(tiny_model):
    """The quantized cache threads through every parallel axis: sp
    (cyclic layout, both leaves permuted), tp (kv-head sharding), and pp
    (stage-local caches) reproduce the int8 single-device stream —
    quantization is per-row deterministic, so parity is exact."""
    mp, _ = tiny_model
    prompt = [1, 2, 3, 4, 5]
    base = InferenceEngine(
        mp, tp=1, dtype=jnp.float32, temperature=0.0, kv_dtype="int8"
    )
    expected, _, _ = base.generate(prompt, max_steps=14)
    del base
    for kw in (dict(sp=2), dict(tp=2), dict(pp=2), dict(tp=2, sp=2)):
        e = InferenceEngine(
            mp, dtype=jnp.float32, temperature=0.0, kv_dtype="int8", **kw
        )
        got, _, _ = e.generate(prompt, max_steps=14)
        del e
        assert got == expected, (kw, got, expected)


def test_kv_dtype_name_validation(tiny_model):
    mp, _ = tiny_model
    with pytest.raises(ValueError, match="kv_dtype"):
        InferenceEngine(mp, kv_dtype="int4", dtype=jnp.float32)


def test_engine_moe_decode_dedup_parity(tmp_path):
    """moe_decode_dedup=True through the full engine (q40 experts,
    4 concurrent lanes): per-lane streams match the default engine."""
    from dllama_tpu.formats.model_file import LlmArch

    mp = str(tmp_path / "moe.m")
    make_tiny_model(mp, arch=LlmArch.QWEN3_MOE, weight_type=FloatType.Q40,
                    seed=7)
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 4]]
    base = InferenceEngine(
        mp, tp=1, dtype=jnp.float32, temperature=0.0, batch_size=4
    )
    expected = base.generate_batch(prompts, max_steps=10)
    del base
    eded = InferenceEngine(
        mp, tp=1, dtype=jnp.float32, temperature=0.0, batch_size=4,
        moe_decode_dedup=True,
    )
    got = eded.generate_batch(prompts, max_steps=10)
    del eded
    assert got == expected, (got, expected)


def test_kv_int8_with_lanes_and_dp(tiny_model):
    """int8 KV under continuous-batching lanes (and lanes sharded over
    dp): per-lane streams match the single-lane int8 runs."""
    mp, _ = tiny_model
    prompts = [[1, 2, 3, 4], [9, 8, 7, 6, 5]]
    singles = []
    e1 = InferenceEngine(
        mp, tp=1, dtype=jnp.float32, temperature=0.0, kv_dtype="int8"
    )
    for p in prompts:
        e1.reset()
        o, _, _ = e1.generate(p, max_steps=14)
        singles.append(o)
    del e1
    for kw in (dict(), dict(dp=2)):
        eb = InferenceEngine(
            mp, dtype=jnp.float32, temperature=0.0, kv_dtype="int8",
            batch_size=2, **kw,
        )
        outs = eb.generate_batch(prompts, max_steps=14)
        del eb
        assert outs == singles, (kw, outs, singles)


def test_window_precompile_no_boundary_stall(tmp_path, monkeypatch):
    """Window-crossing pre-compile: decode blocks past
    75% of the current attention window must trigger a BACKGROUND build
    of the next window's program, so the boundary crossing finds it in
    the cache (origin == 'prefetch', no synchronous compile) — and the
    AOT executables must produce the same tokens as the plain jit path."""
    import time as _time

    mp = str(tmp_path / "w.m")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=288, seq_len=2048)
    make_tiny_model(mp, weight_type=FloatType.F32, cfg=cfg)
    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    assert e._aot_blocks

    toks = []
    tok, pos = 7, 0
    while pos + 32 <= 512:
        out = e.decode_block(tok, pos, 32)
        toks.extend(out)
        tok, pos = out[-1], pos + 32
    # 75% trigger fired during the tail blocks; wait for the thread
    key = ("block", 32, True, 1024)
    deadline = _time.time() + 120
    while _time.time() < deadline and key not in e._compiled:
        _time.sleep(0.2)
    assert key in e._compiled, "next-window program was not prefetched"
    assert e._compile_origin[key] == "prefetch"
    # the crossing dispatch reuses it (origin unchanged -> no sync compile)
    out = e.decode_block(tok, pos, 32)
    toks.extend(out)
    assert e._compile_origin[key] == "prefetch"

    # token parity vs the plain jit path
    monkeypatch.setenv("DLLAMA_WINDOW_PRECOMPILE", "0")
    e2 = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    assert not e2._aot_blocks
    toks2 = []
    tok, pos = 7, 0
    while pos + 32 <= 544:
        out = e2.decode_block(tok, pos, 32)
        toks2.extend(out)
        tok, pos = out[-1], pos + 32
    assert toks == toks2


def test_moe_decode_dedup_auto_resolution(tmp_path, tiny_model):
    """'auto' (default) resolves per the routing-correlation study
    (docs/moe_decode_dedup.md): on iff MoE and >= 8 decode lanes."""
    from dllama_tpu.formats.model_file import LlmArch

    mp_moe = str(tmp_path / "amoe.m")
    make_tiny_model(mp_moe, arch=LlmArch.QWEN3_MOE,
                    weight_type=FloatType.Q40, seed=3)
    e8 = InferenceEngine(mp_moe, tp=1, dtype=jnp.float32, batch_size=8)
    assert e8.moe_decode_dedup is True
    del e8
    e4 = InferenceEngine(mp_moe, tp=1, dtype=jnp.float32, batch_size=4)
    assert e4.moe_decode_dedup is False
    del e4
    mp_dense, _ = tiny_model  # non-MoE: never on
    ed = InferenceEngine(mp_dense, tp=1, dtype=jnp.float32, batch_size=8)
    assert ed.moe_decode_dedup is False


def test_lane_window_precompile_no_boundary_stall(tmp_path):
    """Same boundary-stall pin for decode_lanes — the API server's actual
    serving path: the next window's lane program must arrive via the
    background prefetch, not a synchronous compile at the crossing."""
    import time as _time

    mp = str(tmp_path / "wl.m")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=288, seq_len=2048)
    make_tiny_model(mp, weight_type=FloatType.F32, cfg=cfg)
    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                        batch_size=2)
    toks, pos = [5, 7], [0, 0]
    while pos[0] + 32 <= 512:
        out = e.decode_lanes(toks, pos, 32)
        toks = out[-1]
        pos = [p + 32 for p in pos]
    key = ("lane_block", 32, 1024)
    deadline = _time.time() + 120
    while _time.time() < deadline and key not in e._compiled:
        _time.sleep(0.2)
    assert key in e._compiled, "next-window lane program was not prefetched"
    assert e._compile_origin[key] == "prefetch"
    out = e.decode_lanes(toks, pos, 32)
    assert len(out) == 32
    assert e._compile_origin[key] == "prefetch"


def test_lane_seed_reproducible_across_lane_mix(tiny_model):
    """Per-lane seeds (r5, closes r4's 'seed ignored in lane mode'): a
    seeded lane's sampled stream depends only on (seed, positions) — it
    reproduces with DIFFERENT traffic on the other lane and across
    different block splits."""
    mp, _ = tiny_model
    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.8,
                        batch_size=2)
    out1 = e.decode_lanes([5, 9], [0, 0], 12, temperature=[0.8, 0.7],
                          seeds=[42, None])
    lane0_a = [r[0] for r in out1]
    # different other-lane token/temperature/seed: lane 0 must not move
    e.reset()
    out2 = e.decode_lanes([5, 3], [0, 0], 12, temperature=[0.8, 0.9],
                          seeds=[42, 7])
    assert [r[0] for r in out2] == lane0_a
    # same stream when the 12 steps split into 6+6 blocks
    e.reset()
    o1 = e.decode_lanes([5, 9], [0, 0], 6, temperature=[0.8, 0.7],
                        seeds=[42, None])
    o2 = e.decode_lanes([r for r in o1[-1]], [6, 6], 6,
                        temperature=[0.8, 0.7], seeds=[42, None])
    assert [r[0] for r in o1 + o2] == lane0_a
    # and a different seed produces a different stream (sanity)
    e.reset()
    out3 = e.decode_lanes([5, 9], [0, 0], 12, temperature=[0.8, 0.7],
                          seeds=[43, None])
    assert [r[0] for r in out3] != lane0_a


def _parent_sample(logits, temperature, topp, counts, draw):
    """The sampler as it stood before `_sample` took a branch: softmax,
    top-p mask and draw on every call, greedy lanes selected at the end.
    `counts` is taken and ignored, as the parent had no such mask."""
    from dllama_tpu.runtime.engine import _topp_mask

    temp_col = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(temperature, jnp.float32)),
        logits.shape[:1],
    )[:, None]
    probs = _topp_mask(
        jax.nn.softmax(logits / jnp.maximum(temp_col, 1e-6), axis=-1), topp
    )
    sampled = draw(jnp.log(probs + 1e-30)).astype(jnp.int32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.where(temp_col[:, 0] <= 0.0, greedy, sampled)


# (temperature, top-p, lanes that count, whether the ids are the argmax's)
SAMPLER_CASES = {
    "all_greedy": ([0.0] * 4, [0.9] * 4, [True] * 4, True),
    "mixed": ([0.0, 0.7, 0.0, 1.2], [0.9, 0.9, 0.5, 1.0], [True] * 4, False),
    "one_of_four": ([0.0, 0.0, 0.8, 0.0], [0.9] * 4, [True] * 4, False),
    "all_sampling_topp_inside": ([0.8] * 4, [0.9, 0.5, 0.3, 0.99], [True] * 4, False),
    "all_sampling_topp_outside": ([0.8] * 4, [0.0, 1.0, 0.0, 1.0], [True] * 4, False),
    "masked_lane_samples": ([0.0, 0.0, 0.8, 0.0], [0.9] * 4,
                            [True, True, False, True], True),
    "masked_lane_beside_sampling": ([0.0, 0.7, 0.8, 0.0], [0.9] * 4,
                                    [True, True, False, True], False),
}


def _ids_with_and_without_the_branch(monkeypatch, sampler, *args):
    """`sampler`'s ids as the engine has it, and with the parent's
    unconditional formula in `_sample`'s place. A fresh lambda a call:
    `jax.jit` keeps its traces by function, and the oracle's trace must
    not be the first call's."""
    from dllama_tpu.runtime import engine as eng

    got = jax.jit(lambda *a: getattr(eng, sampler)(*a))(*args)
    with monkeypatch.context() as m:
        m.setattr(eng, "_sample", _parent_sample)
        want = jax.jit(lambda *a: getattr(eng, sampler)(*a))(*args)
    assert got.dtype == jnp.int32
    return np.asarray(got), np.asarray(want)


def _flat_logits():
    return jax.random.normal(jax.random.PRNGKey(3), (4, 96)) * 0.5


@pytest.mark.parametrize("case", list(SAMPLER_CASES))
def test_sampler_branch_matches_the_unconditional_formula(case, monkeypatch):
    """`_sample` takes its sampled side only when a lane that counts has
    a temperature above 0, and that side is the parent's formula whole:
    the ids are the oracle's bit for bit, with the same seeds. Where no
    such lane exists the ids are the argmax's on every lane, a masked-out
    lane at temperature 0.8 included (the oracle draws for it)."""
    logits = _flat_logits()
    argmax = np.argmax(np.asarray(logits), axis=-1)
    temp, topp, counts, greedy = SAMPLER_CASES[case]
    got, want = _ids_with_and_without_the_branch(
        monkeypatch, "_sample_per_lane",
        logits, jnp.asarray(temp, jnp.float32), jnp.asarray(topp, jnp.float32),
        jnp.asarray([42, 7, 1234, 99], jnp.int32),
        jnp.asarray([3, 17, 5, 250], jnp.int32), jnp.asarray(counts),
    )
    if greedy:
        np.testing.assert_array_equal(got, argmax)
        # the oracle drew for the masked lane: this batch took the other side
        assert case == "all_greedy" or want[2] != argmax[2]
    else:
        np.testing.assert_array_equal(got, want)
        assert (got != argmax).any()
    live = np.asarray(counts)
    np.testing.assert_array_equal(got[live], want[live])


@pytest.mark.parametrize("temperature", [0.7, 0.0])
def test_sample_on_device_scalar_temperature(temperature, monkeypatch):
    """The one-stream sampler through the same helper, every lane
    counting: the oracle's ids at 0.7, the argmax's at 0."""
    logits = _flat_logits()
    got, want = _ids_with_and_without_the_branch(
        monkeypatch, "_sample_on_device",
        logits, temperature, 0.9, jax.random.PRNGKey(11),
    )
    np.testing.assert_array_equal(got, want)
    argmax = np.argmax(np.asarray(logits), axis=-1)
    assert (got == argmax).all() == (temperature == 0.0)


NEIGHBOURS = {
    "greedy": dict(temperature=[0.7, 0.0, 0.0], seeds=[42, None, None]),
    "sampling": dict(temperature=[0.7, 0.9, 1.1], seeds=[42, 7, None]),
    "parked": dict(temperature=[0.7, 0.8, 0.8], seeds=[42, 5, 6],
                   active=[True, False, False]),
}


def test_decode_lanes_serves_the_parents_ids(tiny_model, monkeypatch):
    """Engine level: at temperature 0 `decode_lanes` gives the ids of the
    one-stream `block` program built with `greedy=True` (an argmax and no
    sampler, as before), and a seeded lane at 0.7 gives the same ids
    whether its neighbours are greedy, sampling or parked: the ids an
    engine gives whose sampler is the parent's unconditional formula."""
    from dllama_tpu.runtime import engine as eng

    mp, _ = tiny_model

    def engine():
        return InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                               batch_size=3)

    def run(e, **kw):
        e.reset()
        first = e.decode_lanes([5, 9, 3], [0, 0, 0], 5, **kw)
        rest = e.decode_lanes(first[-1], [5, 5, 5], 5, **kw)
        return [r[0] for r in first + rest], first + rest

    e = engine()
    e.reset()
    block = e.decode_block([5, 9, 3], 0, 10)
    _, rows = run(e, temperature=[0.0] * 3)
    assert rows == block
    with monkeypatch.context() as m:
        m.setattr(eng, "_sample", _parent_sample)
        parent = engine()
        want = {name: run(parent, **kw)[0] for name, kw in NEIGHBOURS.items()}
        assert run(parent, temperature=[0.0] * 3)[1] == block
    got = {name: run(e, **kw)[0] for name, kw in NEIGHBOURS.items()}
    assert got == want
    assert got["greedy"] == got["sampling"] == got["parked"]
    assert got["greedy"] != [r[0] for r in block]
    assert run(e, **NEIGHBOURS["sampling"])[0] == got["sampling"]


def test_decode_lanes_counts_the_lanes_that_sample(tiny_model):
    """`n_sampling` (live lanes with temperature > 0) rides on the
    recorder's `step_dispatch` / `step_complete` events and the engine's
    span beside `n_live`, and each block raises one of the sampler
    counter's two labels. A parked lane's temperature counts for nothing."""
    mp, _ = tiny_model
    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.8,
                        batch_size=2)
    count = {k: e._m_sampler.labels(sampler=k) for k in ("greedy", "full")}

    def block(**kw):
        e.reset()
        seq = e.recorder.total_recorded
        before = {k: c.value for k, c in count.items()}
        e.decode_lanes([5, 9], [0, 0], 4, **kw)
        events = [
            ev for kind in ("step_dispatch", "step_complete")
            for ev in e.recorder.events(kind)
            if ev["seq"] > seq and ev["step"] == "decode_lanes"
        ]
        assert len(events) == 2
        assert {ev["n_live"] for ev in events} == {sum(kw.get("active", [1, 1]))}
        (n,) = {ev["n_sampling"] for ev in events}
        span = [s for s in e._spans.completed() if s["name"] == "decode_lanes"][-1]
        assert span["attrs"]["n_sampling"] == n
        return n, {k: c.value - before[k] for k, c in count.items()}

    assert block(temperature=[0.0, 0.0]) == (0, {"greedy": 1, "full": 0})
    assert block(temperature=[0.0, 0.7]) == (1, {"greedy": 0, "full": 1})
    assert block(temperature=[0.0, 0.7], active=[True, False]) == (
        0, {"greedy": 1, "full": 0})
    # a direct caller that names no temperature gets the engine's own, 0.8
    assert block() == (2, {"greedy": 0, "full": 1})
    assert block(active=[False, True]) == (1, {"greedy": 0, "full": 1})


def test_aot_specs_use_init_snapshot(tiny_model):
    """The AOT lowering specs are built from the init-time
    ShapeDtypeStruct snapshot, never from the live trees: a prefetch
    thread reads these specs while the serving thread's dispatch is
    donating (deleting) the live cache buffers. Nulling the live trees
    proves no such read happens."""
    mp, _ = tiny_model
    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    expect_cache = jax.tree.map(lambda x: (x.shape, str(x.dtype)), e.cache)
    live_cache, live_params = e.cache, e.params
    e.cache = None
    e.params = None
    try:
        specs = e._block_arg_specs(8)
    finally:
        e.cache, e.params = live_cache, live_params
    param_specs, tok, cache_specs = specs[0], specs[1], specs[2]
    assert tok.shape == (e.batch_size, 1)
    got_cache = jax.tree.map(lambda s: (s.shape, str(s.dtype)), cache_specs)
    assert got_cache == expect_cache
    assert jax.tree.structure(param_specs) == jax.tree.structure(live_params)
    # and the specs really drive a compile: the engine still generates
    out, _, _ = e.generate([1, 2, 3], max_steps=5)
    assert len(out) > 0


def test_lane_aot_specs_use_init_snapshot(tiny_model):
    """decode_lanes' lowering specs come from the same snapshot (the lane
    scheduler's prefetches race donated dispatches the same way)."""
    mp, _ = tiny_model
    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                        batch_size=2)
    live_cache, live_params = e.cache, e.params
    e.cache = None
    e.params = None
    try:
        specs = e._lane_arg_specs(4)
    finally:
        e.cache, e.params = live_cache, live_params
    assert specs[1].shape == (2, 1)  # token vector is per-lane
    assert specs[3].shape == (2,)  # positions
    rows = e.decode_lanes([1, 2], [0, 0], 4, active=[True, True])
    assert len(rows) == 4 and all(len(r) == 2 for r in rows)


def test_engine_obs_counters(tiny_model):
    """Engine instrumentation: dispatch compiles and step latencies are
    counted, and the window-crossing counter fires exactly on growth."""
    mp, _ = tiny_model
    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    disp = e._m_compiles.labels(origin="dispatch")
    b_disp = disp.value
    b_step = e._m_step.labels(kind="decode_block").count
    b_tpot = e._m_tpot.count
    out, _, _ = e.generate([1, 2, 3], max_steps=8)
    assert len(out) > 0
    assert disp.value > b_disp  # prefill and/or block programs compiled
    assert e._m_step.labels(kind="decode_block").count > b_step
    assert e._m_tpot.count > b_tpot

    crossings = e._m_window_crossings
    e._obs_last_window = None
    b_w = crossings.value
    e._note_window(32)
    e._note_window(32)  # same window: no crossing
    assert crossings.value == b_w
    e._note_window(64)  # growth: one crossing
    assert crossings.value == b_w + 1
    e._note_window(32)  # shrink (fresh request): no crossing
    assert crossings.value == b_w + 1


def test_compile_cache_report_and_cost(tiny_model):
    """Engine introspection behind /v1/debug/compile: every cached
    program is classified by kind with its compile origin, AOT block
    programs carry real XLA cost analysis (even on CPU), and cost_report
    folds them into per-kind figures with the roofline fraction honestly
    absent when the backend's HBM peak is unknown."""
    mp, _ = tiny_model
    eng = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    assert InferenceEngine._key_kind(("block", 8, True, 64)) == "decode_block"
    assert InferenceEngine._key_kind(("lane_block", 8, 64)) == "decode_lanes"
    assert InferenceEngine._key_kind(("lane_prefill", 8, 64)) == "prefill_lane"
    assert InferenceEngine._key_kind(("score", 8, 64)) == "score"
    assert InferenceEngine._key_kind((8, True, 64)) == "prefill"

    eng.generate([1, 2, 3], max_steps=10)
    report = eng.compile_cache_report()
    assert report
    kinds = {e["kind"] for e in report}
    assert "decode_block" in kinds
    for e in report:
        assert e["origin"] in ("dispatch", "prefetch", "prefetch-failed")
        assert e["cost"] == "unavailable" or e["cost"]["bytes_accessed"] > 0
    blocks = [e for e in report if e["kind"] == "decode_block"]
    if eng._aot_blocks:
        assert any(isinstance(e["cost"], dict) for e in blocks)
        assert all(e["compile_seconds"] is not None for e in blocks)

    cost = eng.cost_report()
    if eng._aot_blocks:
        info = cost["kinds"]["decode_block"]
        assert info["bytes_accessed"] > 0 and info["mean_step_s"] > 0
        if cost["hbm_peak_bytes_per_s"] is None:  # CPU test backend
            assert info["roofline_fraction"] is None
        # the per-kind gauges took the same values
        g = eng.obs.gauge(
            "dllama_compiled_step_bytes_accessed", labelnames=("kind",))
        assert g.child_values()[("decode_block",)] == info["bytes_accessed"]


def test_recorder_captures_engine_events(tiny_model):
    """One generate() leaves a coherent event trail in the flight
    recorder: dispatches paired with completes, and the KV-cache epoch
    event from engine init."""
    from dllama_tpu.obs.recorder import get_recorder

    rec = get_recorder()
    base_seq = rec.total_recorded
    mp, _ = tiny_model
    eng = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    eng.generate([1, 2, 3], max_steps=8)
    new = [e for e in rec.events() if e["seq"] > base_seq]
    kinds = [e["kind"] for e in new]
    assert "cache_epoch" in kinds
    assert "step_dispatch" in kinds and "step_complete" in kinds
    completes = [e for e in new if e["kind"] == "step_complete"]
    assert completes and all(e["ms"] >= 0 for e in completes)
    steps = {e.get("step") for e in completes}
    assert "prefill" in steps and "decode_block" in steps


# -- the one build path (`InferenceEngine._build`) ---------------------------
#
# Every compiled program enters `_compiled` through `_build`; the benchmark
# reads what it records (`compiles_in_window`, `correct`, `_compile_origin`).
# One engine serves all of these tests: each case builds under a key of its
# own, so no case sees another's program.


@pytest.fixture(scope="module")
def build_engine(tmp_path_factory):
    """A tiny two-lane engine that can build every kind of program: the
    slab's, the pool-native ones (a native pool), the draft's."""
    mp = str(tmp_path_factory.mktemp("build") / "b.m")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=288, seq_len=64)
    make_tiny_model(mp, weight_type=FloatType.F32, cfg=cfg)
    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                        batch_size=2, prefill_buckets=(8, 16))
    e.init_kv_pool(4, 40, native=True)
    e.init_draft_model(mp)
    return e


def _w(e):
    return e._attn_window(1)


# kind -> (key, builder call) for a size `n` and an origin
PROGRAMS = {
    "block": lambda e, n, o: (
        ("block", n, True, _w(e)),
        lambda: e._decode_block_fn(n, True, _w(e), origin=o)),
    "lane_prefill": lambda e, n, o: (
        ("lane_prefill", n, _w(e)),
        lambda: e._lane_prefill_fn(n, window=_w(e), origin=o)),
    "kv_adopt": lambda e, n, o: (
        ("kv_adopt", n), lambda: e._kv_copy_fn("adopt", n, origin=o)),
    "kv_publish": lambda e, n, o: (
        ("kv_publish", n), lambda: e._kv_copy_fn("publish", n, origin=o)),
    "kv_page_copy": lambda e, n, o: (
        ("kv_page_copy", n), lambda: e._kv_page_copy_fn(n, origin=o)),
    "lane_block_paged": lambda e, n, o: (
        ("lane_block_paged", n, _w(e)),
        lambda: e._lane_decode_paged_fn(n, _w(e), origin=o)),
    "lane_verify_paged": lambda e, n, o: (
        ("lane_verify_paged", n, _w(e)),
        lambda: e._lane_verify_paged_fn(n, _w(e), origin=o)),
    "lane_prefill_paged": lambda e, n, o: (
        ("lane_prefill_paged", n, _w(e)),
        lambda: e._lane_prefill_paged_fn(n, _w(e), origin=o)),
    "lane_block": lambda e, n, o: (
        ("lane_block", n, _w(e)),
        lambda: e._lane_decode_fn(n, _w(e), origin=o)),
    "lane_verify": lambda e, n, o: (
        ("lane_verify", n, _w(e)),
        lambda: e._lane_verify_fn(n, _w(e), origin=o)),
    "draft_prefill": lambda e, n, o: (
        ("draft_prefill", n), lambda: e._draft_prefill_fn(n, origin=o)),
    "draft_step": lambda e, n, o: (
        ("draft_step", n), lambda: e._draft_step_fn(n, origin=o)),
    # lazily jitted: no arg specs, one deferred `compile` record
    "step": lambda e, n, o: (
        (n, True, _w(e)), lambda: e._step_fn(n, True, _w(e))),
}
SIZE_OF = {"dispatch": 2, "prefetch": 3}


def _compile_events(e, key, since):
    return [
        (ev["kind"], ev["origin"]) for ev in e.recorder.events()
        if ev["seq"] > since and ev["kind"].startswith("compile")
        and ev.get("key") == str(key)
    ]


@pytest.mark.parametrize(
    "kind,origin",
    [(k, o) for k in PROGRAMS if k != "step" for o in SIZE_OF]
    + [("step", "dispatch")],
)
def test_program_build_path(build_engine, kind, origin):
    """The first call of a builder records exactly one `compile_start` /
    `compile_end` pair (a lazily jitted program: one deferred `compile`)
    under its key and origin, and fills `_compile_origin` and, when
    compiled ahead of time, `_compile_seconds`; the second call returns
    the same program and records nothing."""
    e = build_engine
    key, build = PROGRAMS[kind](e, SIZE_OF[origin], origin)
    assert key not in e._compiled
    counted = e._m_compiles.labels(origin=origin)
    n0, seq0 = counted.value, e.recorder.total_recorded
    fn = build()
    if kind == "step":
        want = [("compile", "dispatch")]
        assert key not in e._compile_seconds
    else:
        want = [("compile_start", origin), ("compile_end", origin)]
        assert e._aot_blocks and e._compile_seconds[key] >= 0
        assert isinstance(fn, jax.stages.Compiled)
        end = [ev for ev in e.recorder.events("compile_end")
               if ev["key"] == str(key)]
        assert end[-1]["s"] == round(e._compile_seconds[key], 4)
    assert _compile_events(e, key, seq0) == want
    assert e._compiled[key] is fn
    assert e._compile_origin[key] == origin
    assert counted.value == n0 + 1
    seq1 = e.recorder.total_recorded
    assert build() is fn
    assert _compile_events(e, key, seq1) == []
    assert counted.value == n0 + 1


LANE_PROGRAMS = ["lane_prefill", "lane_block", "lane_verify"]


@pytest.mark.parametrize("kind", LANE_PROGRAMS)
def test_dispatch_waits_for_an_inflight_prefetch(build_engine, kind):
    """A dispatch that finds a prefetch thread building its program waits
    for it and takes that program: one build, origin `prefetch`."""
    import threading

    e = build_engine
    key, prefetch = PROGRAMS[kind](e, 5, "prefetch")
    _, dispatch = PROGRAMS[kind](e, 5, "dispatch")
    gate = threading.Event()
    seq0 = e.recorder.total_recorded

    def held_prefetch():
        assert gate.wait(60)
        prefetch()

    e._prefetch(key, held_prefetch)
    got = []
    t = threading.Thread(target=lambda: got.append(dispatch()), daemon=True)
    t.start()
    t.join(0.3)
    assert t.is_alive() and not got, "the dispatch did not wait"
    assert key not in e._compiled
    gate.set()
    t.join(120)
    assert not t.is_alive()
    assert got == [e._compiled[key]]
    assert e._compile_origin[key] == "prefetch"
    assert _compile_events(e, key, seq0) == [
        ("compile_start", "prefetch"), ("compile_end", "prefetch")]
    with e._compile_lock:
        assert key not in e._inflight


@pytest.mark.parametrize("kind", LANE_PROGRAMS)
def test_failed_prefetch_is_marked(build_engine, kind, monkeypatch, caplog):
    """A build that fails on a prefetch thread is logged and marked
    `prefetch-failed` (what the benchmark's `compile_admission_path`
    reads), releases its in-flight slot, and leaves the dispatch path
    able to build the program itself."""
    import logging
    import time

    e = build_engine
    key, prefetch = PROGRAMS[kind](e, 6, "prefetch")
    _, dispatch = PROGRAMS[kind](e, 6, "dispatch")
    failed = e._m_compiles.labels(origin="prefetch-failed")
    n0 = failed.value

    def refuse(fn):
        raise RuntimeError("synthetic lowering failure")

    with monkeypatch.context() as m, caplog.at_level(
        logging.ERROR, logger="dllama_tpu.runtime.engine"
    ):
        m.setattr(jax.stages.Lowered, "compile", refuse)
        e._prefetch(key, prefetch)
        deadline = time.monotonic() + 60
        while key in e._inflight and time.monotonic() < deadline:
            time.sleep(0.01)
    assert e._compile_origin[key] == "prefetch-failed"
    assert key not in e._compiled
    assert failed.value == n0 + 1
    assert any("prefetch failed" in r.message for r in caplog.records)
    with e._compile_lock:
        assert key not in e._inflight
    assert dispatch() is e._compiled[key]
    assert e._compile_origin[key] == "dispatch"


# what `benchmark/harness/server.py` takes from the engine by name, with
# the harness's own argument shapes (`compile_admission_path`,
# `build_programs`): this PR may not edit the harness, so these are the
# calls that must keep working, on the slab path the cells run


@pytest.fixture(scope="module", params=["llama", "afmoe"])
def slab_engine(tmp_path_factory, request):
    """An engine of one kind of layer, and one of two (window and full
    layers over two cache stacks, a share of the experts): the harness
    calls both by the same names."""
    mp = str(tmp_path_factory.mktemp("slab") / "s.m")
    if request.param == "afmoe":
        from helpers import make_tiny_afmoe

        make_tiny_afmoe(mp)
    else:
        cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
                   head_dim=16, vocab_size=288, seq_len=1024)
        make_tiny_model(mp, weight_type=FloatType.F32, cfg=cfg)
    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                        batch_size=2, prefill_buckets=(8, 16), max_seq_len=1024)
    e.init_kv_pool(4, 40)
    return e


BLOCK = 4


def _harness_rehearse_admission(e):
    e.rehearse_admission(BLOCK, wait=True)
    assert [k for k, o in e._compile_origin.items()
            if o == "prefetch-failed"] == []
    with e._compile_lock:
        assert not e._inflight
    built = {k for k, o in e._compile_origin.items() if o == "prefetch"}
    assert {("lane_block", BLOCK, 512), ("kv_adopt", 1), ("kv_publish", 1)} | {
        ("lane_prefill", b, 512) for b in e.prefill_buckets} <= built


def _harness_attn_window(e):
    ctx = e.header.seq_len
    assert e._attn_window(1) == e._attn_window(min(ctx, 100 + 16)) == 512
    assert e._attn_window(512 + 1) == e._attn_window(min(ctx, 900 + BLOCK)) == ctx


def _harness_prefill_buckets(e):
    assert e.prefill_buckets == (8, 16) and max(e.prefill_buckets) == 16


def _harness_lane_prefill_fn(e):
    for bucket in e.prefill_buckets:
        fn = e._lane_prefill_fn(bucket, window=1024, origin="prefetch")
        assert fn is e._compiled[("lane_prefill", bucket, 1024)]
        assert e._compile_origin[("lane_prefill", bucket, 1024)] == "prefetch"


def _harness_lane_decode_fn(e):
    fn = e._lane_decode_fn(BLOCK, 1024, origin="prefetch")
    assert fn is e._compiled[("lane_block", BLOCK, 1024)]
    assert e._compile_origin[("lane_block", BLOCK, 1024)] == "prefetch"
    assert e._lane_decode_fn(BLOCK, 1024) is fn  # the scheduler's dispatch


HARNESS_CALLS = {
    "rehearse_admission": _harness_rehearse_admission,
    "_attn_window": _harness_attn_window,
    "prefill_buckets": _harness_prefill_buckets,
    "_lane_prefill_fn": _harness_lane_prefill_fn,
    "_lane_decode_fn": _harness_lane_decode_fn,
}


@pytest.mark.parametrize("name", list(HARNESS_CALLS))
def test_what_the_benchmark_harness_calls(slab_engine, name):
    HARNESS_CALLS[name](slab_engine)


# -- the prefill ladder (PR 49): one rule, `engine.prefill_ladder` --------------

LADDERS = {
    1: (1, 128, 256, 512),
    8: (1, 8, 128, 256, 512),
    32: (1, 32, 128, 256, 512),
    128: (1, 128, 256, 512),
    256: (1, 256, 512),  # what the CLI serves by default: no program more than (1, 32, 512)
    512: (1, 512),  # the long-context configurations': no program more than they built
    1024: (1, 512, 1024),  # above the largest: what `sorted({1, n, 512})` always gave
}


@pytest.mark.parametrize("smallest", list(LADDERS))
def test_prefill_ladder_rule(smallest):
    """1, the smallest rung asked for, the largest, and the rungs at 128
    and 256 where they lie strictly between the two."""
    ladder = prefill_ladder(smallest)
    assert ladder == LADDERS[smallest]
    assert list(ladder) == sorted(set(ladder))
    assert {1, smallest, 512} <= set(ladder)
    assert set(ladder) - {1, smallest, 512} == {
        b for b in (128, 256) if smallest < b < 512}


def test_prefill_ladder_under_another_largest_rung():
    assert prefill_ladder(32, largest=256) == (1, 32, 128, 256)
    assert prefill_ladder(64, largest=128) == (1, 64, 128)


@pytest.fixture(scope="module")
def long_tiny_model(tmp_path_factory):
    """The tiny preset with a context every rung of the ladder fits."""
    d = tmp_path_factory.mktemp("ladder")
    mp, tp_ = str(d / "m.m"), str(d / "t.t")
    make_tiny_model(mp, cfg=dict(TINY, seq_len=1024))
    make_tiny_tokenizer(tp_)
    return mp, tp_


@pytest.mark.parametrize("nbatches", [None, 32, 128, 512])
def test_cli_load_engine_serves_the_ladder_of_its_nbatches(long_tiny_model, nbatches):
    """`--nbatches` is the smallest rung above 1 and defaults to 256; the
    engine `cli.load_engine` builds holds `prefill_ladder` of it."""
    from dllama_tpu.cli import _build_parser, load_engine

    mp, tp_ = long_tiny_model
    argv = ["inference", "--model", mp, "--tokenizer", tp_, "--dtype", "f32", "--tp", "1"]
    args = _build_parser().parse_args(
        argv + (["--nbatches", str(nbatches)] if nbatches else []))
    assert args.nbatches == (nbatches or 256)
    engine, _ = load_engine(args)
    assert engine.prefill_buckets == prefill_ladder(args.nbatches)
    if nbatches is None:
        assert engine.prefill_buckets == (1, 256, 512)


@pytest.fixture(scope="module")
def ladder_engine(long_tiny_model):
    return InferenceEngine(long_tiny_model[0], tp=1, dtype=jnp.float32, temperature=0.0,
                           batch_size=2, prefill_buckets=prefill_ladder(32))


@pytest.mark.parametrize("n,pos,bucket", [
    (1, 0, 1), (2, 0, 32), (32, 0, 32), (33, 0, 128), (128, 0, 128), (129, 0, 256),
    (256, 0, 256), (257, 0, 512), (512, 0, 512), (900, 0, 512),
    # near the end of the context a rung's PADDED extent has to fit: the
    # largest rung below the space, which then cuts the chunk
    (200, 1024 - 300, 256), (290, 1024 - 300, 256), (200, 1024 - 256, 256),
    (200, 1024 - 255, 128), (90, 1024 - 100, 32), (20, 1024 - 20, 1),
])
def test_bucket_for_takes_the_smallest_rung_that_covers_the_chunk(ladder_engine, n, pos, bucket):
    assert ladder_engine.prefill_buckets == (1, 32, 128, 256, 512)
    assert ladder_engine._bucket_for(n, pos) == bucket


def test_rehearse_admission_builds_every_rung(ladder_engine):
    e = ladder_engine
    e.rehearse_admission(BLOCK, wait=True)
    built = {k for k, o in e._compile_origin.items() if o == "prefetch"}
    assert {("lane_prefill", b, e._attn_window(b)) for b in (1, 32, 128, 256, 512)} <= built
    assert not [k for k, o in e._compile_origin.items() if o == "prefetch-failed"]


def test_prefill_counters_say_chunks_by_rung_and_rows_real_and_computed(ladder_engine):
    """`dllama_prefill_chunks_total{bucket}`, `dllama_prefill_lanes_total`
    and `dllama_prefill_rows_total{kind}`: what a chunk program puts into
    its `step_dispatch` event as `bucket`, `lanes` and `n_tokens`, summed;
    `computed` is every lane's rows, the parked one's too."""
    e = ladder_engine

    def counted():  # the registry outlives an engine: differences
        return (
            {b: e._m_prefill_chunks.labels(bucket=str(b)).value for b in e.prefill_buckets},
            [e._m_prefill_rows.labels(kind=k).value for k in ("real", "bucket", "computed")]
            + [e._m_prefill_lanes.value],
        )

    chunks0, rows0 = counted()
    widths = [5, 32, 33, 1, 200, 300, 128]  # rungs 32, 32, 128, 1, 256, 512, 128
    pos = 0
    for w in widths:
        assert e.prefill_lane_chunk(1, list(range(1, w + 1)), pos) == w
        pos += w
    # a budget cuts the chunk before its rung is taken (--admission-chunk)
    assert e.prefill_lane_chunk(0, list(range(1, 300)), 0, budget=100) == 100
    chunks, rows = counted()
    assert {b: chunks[b] - chunks0[b] for b in chunks} == {1: 1, 32: 2, 128: 3, 256: 1, 512: 1}
    rungs = 1 + 2 * 32 + 3 * 128 + 256 + 512
    assert [a - b for a, b in zip(rows, rows0)] == [sum(widths) + 100, rungs, 2 * rungs, 8]
    # one program that fills both lanes: a rung a lane carried, and no row more computed
    assert e.prefill_lanes_chunk([(0, list(range(1, 41)), 100), (1, [7] * 90, 0)]) == [40, 90]
    assert [a - b for a, b in zip(counted()[1], rows)] == [130, 256, 256, 2]
    assert counted()[0][128] - chunks[128] == 1
    text = e.obs.render()
    assert 'dllama_prefill_chunks_total{bucket="256"}' in text
    assert 'dllama_prefill_rows_total{kind="real"}' in text
    assert 'dllama_prefill_rows_total{kind="computed"}' in text
    assert "dllama_prefill_lanes_total" in text


@pytest.mark.parametrize("family,rides", [
    ("llama", True), ("qwen3_moe", True), ("afmoe", True), ("pangu_ultra_moe", True),
    ("lfm2_moe", False), ("granitemoehybrid", False), ("deepseek_v32", False),
    ("qwen3_moe --kv-native 1", False),
])
def test_chunk_lanes_by_what_the_header_says(tmp_path, tiny_model, family, rides):
    """A chunk program fills every lane (`chunk_lanes` = `batch_size`) of a
    dense model and of one with experts, whose block visits the live lanes
    one after another, so that a rider adds rows there and none to a dense
    program; it fills ONE lane where a layer keeps a state a lane, where an
    index builds a lane's mask, and where it reads the pool's pages. Read
    from the header: no family is named in the engine."""
    import helpers

    path = str(tmp_path / "f.m")
    name, _, native = family.partition(" ")
    if name == "llama":
        path = tiny_model[0]
    elif name == "qwen3_moe":
        make_tiny_model(path, arch=LlmArch.QWEN3_MOE, weight_type=FloatType.Q40)
    else:
        helpers.TINY_FAMILY_WRITERS[name](path)
    e = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0, batch_size=3,
                        prefill_buckets=(1, 8))
    if native:
        e.init_kv_pool(4, native=True)
    h = e.header
    assert e.chunk_lanes == (3 if rides else 1)
    assert rides == (not (h.stateful or h.indexed or e.kv_native))
    assert e.chunk_rider_adds_rows == bool(h.n_experts) == (name != "llama")
    if e.chunk_lanes == 1:
        with pytest.raises(ValueError, match="fills 1 to 1 lanes"):
            e.prefill_lanes_chunk([(0, [1, 2], 0), (1, [3], 0)])


def test_expert_rows_count_the_bucket_a_carried_lane(tmp_path):
    """A chunk program of a model with experts that fills two lanes of four,
    and then one: `expert_rows` and `dllama_moe_chunk_rows_total{rows=
    "computed"}` count the bucket a carried lane, `parked_skipped` the rows
    of the lanes left parked."""
    path = str(tmp_path / "moe.m")
    make_tiny_model(path, arch=LlmArch.QWEN3_MOE, weight_type=FloatType.Q40)
    e = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0, batch_size=4,
                        prefill_buckets=(1, 8))

    def rows_counted():
        return [e._m_moe_chunk_rows.labels(rows=r).value for r in ("computed", "parked_skipped")]

    rows0, base = rows_counted(), e.recorder.total_recorded
    assert e.prefill_lanes_chunk([(3, [5, 6, 7], 0), (1, list(range(1, 12)), 4)]) == [3, 8]
    assert e.prefill_lanes_chunk([(1, [9, 10, 11], 12)]) == [3]
    events = [ev for ev in e.recorder.events() if ev["seq"] > base and ev["kind"] == "step_dispatch"]
    assert [(ev["lanes"], ev["bucket"], ev["expert_rows"]) for ev in events] == [
        ([3, 1], 8, 16), ([1], 8, 8)]
    assert [a - b for a, b in zip(rows_counted(), rows0)] == [24, 2 * 4 * 8 - 24]


@pytest.mark.parametrize("chunks,budget,widths,bucket,window", [
    # the common rung is the widest that a carried lane asks, the window the deepest
    ([(0, 300, 0), (1, 40, 500)], None, [300, 40], 512, 1024),
    ([(0, 300, 0), (1, 40, 500)], 100, [100, 40], 128, 1024),
    ([(1, 40, 0), (0, 300, 100)], 100, [40, 100], 128, 512),
    # the lead rides in a rider's wider rung
    ([(0, 20, 0), (1, 200, 32)], None, [20, 200], 256, 512),
    # a rider whose rows would pass the context's end in the common rung is left out
    ([(0, 300, 0), (1, 40, 600)], None, [300, 0], 512, 512),
    # and so is a rider whose own rung the lead's rows would not fit
    ([(1, 40, 600), (0, 300, 0)], None, [40, 0], 128, 1024),
])
def test_one_chunk_program_for_the_lanes_that_fit_its_common_rung(
        ladder_engine, chunks, budget, widths, bucket, window):
    e = ladder_engine
    assert e.chunk_lanes == 2 and e.header.seq_len == 1024
    built = set(e._compiled)
    base = e.recorder.total_recorded
    got = e.prefill_lanes_chunk(
        [(lane, list(range(1, n + 1)), pos) for lane, n, pos in chunks], budget=budget)
    assert got == widths
    ev, = [ev for ev in e.recorder.events() if ev["seq"] > base and ev["kind"] == "step_dispatch"]
    carried = [c for c, w in zip(chunks, widths) if w]
    assert (ev["step"], ev["lane"], ev["pos"]) == ("prefill_lane_chunk", chunks[0][0], chunks[0][2])
    assert (ev["lanes"], ev["n_tokens"]) == ([c[0] for c in carried], sum(widths))
    assert (ev["bucket"], ev["window"]) == (bucket, window)
    # no program that one lane a tick would not build
    assert set(e._compiled) - built <= {("lane_prefill", bucket, window)}


def test_a_chunk_program_fills_no_more_lanes_than_it_computes_and_each_once(ladder_engine):
    e = ladder_engine
    with pytest.raises(ValueError, match="fills 1 to 2 lanes, not 3"):
        e.prefill_lanes_chunk([(0, [1], 0), (1, [1], 0), (0, [1], 8)])
    with pytest.raises(ValueError, match="a lane twice"):
        e.prefill_lanes_chunk([(1, [1], 0), (1, [1], 8)])
    with pytest.raises(ValueError, match="fills 1 to 2 lanes, not 0"):
        e.prefill_lanes_chunk([])


@pytest.mark.parametrize("n_prompt", list(MIDDLE_RUNG_PROMPTS))
def test_a_prompt_through_a_middle_rung_leaves_what_the_largest_rung_leaves(
        long_tiny_model, n_prompt):
    """The dense family's case of the test every family's file has."""
    assert_a_middle_rung_equals_the_largest(long_tiny_model[0], n_prompt)


def test_weight_format_q40i8_is_refused_by_the_engine(tiny_model):
    mp, _ = tiny_model
    with pytest.raises(ValueError, match="weight_format must be"):
        InferenceEngine(mp, tp=1, dtype=jnp.float32, weight_format="q40i8")


@pytest.mark.parametrize("value", ["auto", "q40", "dense", "q40i4", "q40i8"])
def test_cli_weight_format_choices(tiny_model, capsys, value):
    """`--weight-format` offers `auto | q40 | dense`. `q40i4` is what `auto`
    resolves to where it runs, so the parser refuses it as it refuses
    `q40i8`; the engine's keyword still takes it (the CPU tests compare the
    packed form with `q40` through it)."""
    from dllama_tpu.cli import _build_parser

    argv = ["inference", "--model", "m.m", "--weight-format", value]
    if value in ("auto", "q40", "dense"):
        assert _build_parser().parse_args(argv).weight_format == value
        return
    with pytest.raises(SystemExit):
        _build_parser().parse_args(argv)
    assert f"invalid choice: '{value}'" in capsys.readouterr().err
    if value == "q40i4":
        mp, _ = tiny_model
        e = InferenceEngine(mp, tp=1, dtype=jnp.float32, weight_format="q40i4")
        assert e.weight_format == "q40i4"


# -- completion stamps: when each program left the device ----------------------
#
# Each case is a run of dispatches on the tiny preset under a clock that
# advances one second a reading. A program's handle is held until the case
# says `done` (the device has finished everything enqueued, and the watcher
# has stamped it); a read-back stamps by itself. What is listed: the steps
# before which the device stood drained, and each dispatch's `dry`.

DRAINED_CASES = {
    # a block behind a chunk that still runs finds the device busy; between
    # two blocks there is exactly one interval
    "chunk_block_block": (["chunk", "block", "block"], ["decode_lanes"], [1, 0, 1]),
    # a pool copy onto a drained device is host work inside the interval
    "block_publish_chunk_block": (
        ["block", "publish", "chunk", "block"], ["prefill_lane_chunk"], [1, 1, 0]),
    "block_adopt_block": (["block", "adopt", "block"], ["decode_lanes"], [1, 1]),
    # and behind a chunk that still runs it changes nothing
    "block_chunk_publish_block": (
        ["block", "chunk", "publish", "block"], ["prefill_lane_chunk"], [1, 1, 0]),
    "block_block_block": (["block"] * 3, ["decode_lanes"] * 2, [1, 1, 1]),
    # a block dispatched ahead is queued behind the awaited one: that wait
    # says nothing of it; the wait for the newest program does
    "block_dispatch_ahead_collect_collect_block": (
        ["block", "dispatch", "ahead", "collect", "collect", "block"],
        ["decode_lanes", "decode_lanes"], [1, 1, 0, 1]),
    # nor does a wait behind which a chunk was enqueued
    "dispatch_ahead_collect_chunk_collect_block": (
        ["dispatch", "ahead", "collect", "chunk", "collect", "block"], [], [1, 0, 0, 0]),
    # a dispatch ahead that raises enqueued nothing: the block in flight is
    # still the newest program, and its wait says the device is dry
    "dispatch_poison_collect_block": (
        ["dispatch", "poison", "collect", "block"], ["decode_lanes"], [1, 0, 1]),
    # a block abandoned un-read still runs, and the next block is not ahead
    # of a collect that never comes
    "dispatch_discard_block": (["dispatch", "discard", "block"], [], [1, 0]),
    # a block dispatched ahead of a collect that leaves the device before
    # the next dispatch leaves an interval, though nobody has read it back
    "ahead_done_before_the_next_dispatch": (
        ["dispatch", "ahead", "collect", "done", "ahead", "collect", "collect"],
        ["decode_lanes"], [1, 0, 1]),
    # one that leaves it after the next dispatch leaves none
    "ahead_done_after_the_next_dispatch": (
        ["dispatch", "ahead", "collect", "ahead", "collect", "collect"], [], [1, 0, 0]),
    # chunks are never read back: the watcher alone says when one has left
    "chunk_done_chunk": (["chunk", "done", "chunk"], ["prefill_lane_chunk"], [1, 1]),
    "chunk_chunk": (["chunk", "chunk"], [], [1, 0]),
}


class _TickingTime:
    """`time`, with a `monotonic` that advances a second a reading."""

    def __init__(self):
        self.t = 1000.0

    def monotonic(self):
        self.t += 1.0
        return self.t

    def __getattr__(self, name):
        import time

        return getattr(time, name)


class _Held:
    """A program's handle whose completion the test releases: to the
    watcher the program runs until then."""

    def __init__(self, real, raises=None):
        self.real, self.raises = real, raises
        self.waited_for, self.released = threading.Event(), threading.Event()

    def block_until_ready(self):
        self.waited_for.set()
        assert self.released.wait(60)
        if self.raises is not None:
            raise self.raises
        self.real.block_until_ready()

    def is_ready(self):
        return self.released.is_set()


@contextlib.contextmanager
def _held_programs(e, monkeypatch, raises=()):
    """Every program `e` enqueues inside hands the watcher a `_Held`; yields
    the programs, and `done()`: release them all and wait for their stamps.
    `raises`: {n: the exception the n-th program's handle raises}."""
    import time

    programs = []
    real = e._launched

    def launched(begun, handle):
        programs.append(real(begun, _Held(handle, dict(raises).get(len(programs)))))
        return programs[-1]

    def done():
        for p in programs:
            held = p.handle
            if held is not None:
                held.released.set()
        deadline = time.monotonic() + 60
        while not all(p.watched is not None for p in programs):
            assert time.monotonic() < deadline, "the watcher stamped nothing"
            time.sleep(0.001)

    e.close()  # what earlier tests enqueued is no program before these
    with monkeypatch.context() as m:
        m.setattr(e, "_launched", launched)
        try:
            yield programs, done
        finally:
            done()
            e.close()


def _stamp_calls(e, monkeypatch):
    """The calls of a case, by name, on lanes 0 (blocks) and 1 (chunks)."""
    flying, at = [], [0]

    def dispatch(ahead):
        tokens = [None if ahead else 5, 0]
        flying.append(e.dispatch_lanes(tokens, [at[0], 0], 4, active=[True, False]))
        at[0] += 4

    def poisoned():
        fault = InjectedFault("dispatch", "decode_lanes", "poison", 1)
        with monkeypatch.context() as m:
            m.setattr(e, "_fault", lambda op: fault)
            with pytest.raises(InjectedFault):
                dispatch(ahead=True)
        at[0] -= 4

    def block():
        dispatch(ahead=False)
        return e.collect_lanes(flying.pop())

    return {
        "chunk": lambda: e.prefill_lane_chunk(1, list(range(1, 9)), 0),
        "block": block,
        "publish": lambda: e.kv_publish(0, [1], start_page=0),
        "adopt": lambda: e.kv_adopt(1, [1]),
        "dispatch": lambda: dispatch(ahead=False),
        # lane 0 goes on from the token the block in flight samples last
        "ahead": lambda: dispatch(ahead=True),
        "collect": lambda: e.collect_lanes(flying.pop(0)),
        "poison": poisoned,
        "discard": lambda: e.discard_lanes(flying.pop(0)),
    }, flying, at


def _new_spans(e, n_before, name="device_drained"):
    return [s for s in e._spans.completed()[-(e._spans.total_recorded - n_before):]
            if s["name"] == name] if e._spans.total_recorded > n_before else []


@pytest.mark.parametrize("case", list(DRAINED_CASES))
def test_device_drained_from_a_programs_end_to_the_next_dispatch(
        slab_engine, monkeypatch, case):
    import dllama_tpu.runtime.engine as engine_mod

    e = slab_engine
    calls, want, want_dry = DRAINED_CASES[case]
    do, flying, at = _stamp_calls(e, monkeypatch)
    for call in dict.fromkeys(c for c in calls if c != "done"):
        do[call]()  # built outside the clock's reach
    while flying:
        do["collect"]()
    e.reset()
    at[0] = 0
    programs_run = [c for c in calls if c in ("chunk", "block", "dispatch", "ahead")]
    steps = {"chunk": "prefill_lane_chunk"}
    with _held_programs(e, monkeypatch) as (programs, done):
        monkeypatch.setattr(engine_mod, "time", _TickingTime())
        n_spans, seq = e._spans.total_recorded, e.recorder.total_recorded
        counted0 = {b: e._m_drained.labels(before=b).value for b in set(want)}
        busy0 = {s: e._m_busy.labels(step=s).value
                 for s in ("decode_lanes", "prefill_lane_chunk")}
        do["done"] = done
        for call in calls:
            do[call]()
    spans = _new_spans(e, n_spans)
    assert [s["attrs"] for s in spans] == [{"before": b} for b in want]
    assert all(s["component"] == "engine" and "parent" not in s for s in spans)
    assert all(s["thread"] == threading.get_ident() for s in spans)
    assert all(s["dur_s"] >= 1.0 and s["dur_s"] == int(s["dur_s"]) for s in spans)
    # one `device_done` a program, in dispatch order, none in error
    done_events = [ev for ev in e.recorder.events("device_done") if ev["seq"] > seq]
    assert [ev["step"] for ev in done_events] == [
        steps.get(c, "decode_lanes") for c in programs_run]
    assert [ev["program"] for ev in done_events] == [p.seq for p in programs]
    assert not [ev for ev in done_events if "error" in ev]
    assert [ev["at"] for ev in done_events] == sorted(ev["at"] for ev in done_events)
    assert all(ev["device_ms"] >= 0 and ev["queued_ms"] >= 0 for ev in done_events)
    # the counter, the spans and `dry_ms` are one pair of clock readings
    for b in set(want):
        assert e._m_drained.labels(before=b).value - counted0[b] == sum(
            s["dur_s"] for s in spans if s["attrs"]["before"] == b)
    assert [(ev["step"], ev["dry_ms"]) for ev in done_events if ev["dry_ms"]] == [
        (s["attrs"]["before"], s["dur_s"] * 1000) for s in spans]
    for step, was in busy0.items():
        assert e._m_busy.labels(step=step).value - was == pytest.approx(sum(
            ev["device_ms"] for ev in done_events if ev["step"] == step) / 1000)
    events = [ev for ev in e.recorder.events("step_dispatch") if ev["seq"] > seq]
    assert len(events) == sum(c not in ("collect", "discard", "done") for c in calls)
    # a dispatch that is no pool copy says whether it found the device dry
    assert [ev["dry"] for ev in events if "dry" in ev] == want_dry
    assert all(("dry" in ev) == (ev["step"] not in e._POOL_COPIES) for ev in events)
    # a block says whether one was in flight at its dispatch
    assert [ev["ahead"] for ev in events if ev["step"] == "decode_lanes"] == [
        int(c in ("ahead", "poison")) for c in calls
        if c in ("block", "dispatch", "ahead", "poison")]
    assert not any("drained" in key for ev in events for key in ev)


def test_a_late_watcher_does_not_move_a_stamp_a_read_back_took(slab_engine, monkeypatch):
    """The watcher's reading can be late (here: by the test's hold; on a
    server: by the interpreter's lock); the read-back's is the stamp, the
    event says how late the watcher was, and the interval before the next
    dispatch begins at the read-back's end."""
    import dllama_tpu.runtime.engine as engine_mod

    e = slab_engine
    do, _, _ = _stamp_calls(e, monkeypatch)
    do["block"]()
    e.reset()
    with _held_programs(e, monkeypatch) as (programs, done):
        monkeypatch.setattr(engine_mod, "time", _TickingTime())
        n_spans, seq = e._spans.total_recorded, e.recorder.total_recorded
        do["block"]()
        done()
        do["block"]()
    first, second = [ev for ev in e.recorder.events("device_done") if ev["seq"] > seq]
    assert programs[0].watched > programs[0].read == first["at"]
    assert first["late_ms"] == (programs[0].watched - programs[0].read) * 1000 >= 1000
    (span,) = _new_spans(e, n_spans)
    assert span["t0"] + e._spans.epoch_monotonic == pytest.approx(programs[0].read)
    assert second["dry_ms"] == span["dur_s"] * 1000 == (programs[1].t0 - programs[0].read) * 1000


def test_a_handle_that_raises_is_stamped_error_and_the_next_is_still_stamped(
        slab_engine, monkeypatch):
    e = slab_engine
    do, _, _ = _stamp_calls(e, monkeypatch)
    do["chunk"]()
    e.reset()
    with _held_programs(e, monkeypatch, raises={0: RuntimeError("poisoned")}) as (
            programs, done):
        seq = e.recorder.total_recorded
        do["chunk"]()
        do["chunk"]()
        done()
    assert [p.error for p in programs] == ["RuntimeError", None]
    assert all(p.handle is None for p in programs)  # the watcher keeps nothing
    events = [ev for ev in e.recorder.events("device_done") if ev["seq"] > seq]
    assert [ev.get("error") for ev in events] == ["RuntimeError", None]
    assert [ev["program"] for ev in events] == [p.seq for p in programs]


def test_device_drained_carries_the_thread_that_dispatched(slab_engine, monkeypatch):
    """The span is committed by whoever next comes by; its `thread` is the
    dispatching thread's (`benchmark/harness/drained.py` takes the spans
    of the thread that holds the ticks)."""
    e = slab_engine
    do, _, _ = _stamp_calls(e, monkeypatch)
    do["chunk"]()
    e.reset()
    with _held_programs(e, monkeypatch) as (programs, done):
        n_spans = e._spans.total_recorded

        def dispatches():
            do["chunk"]()
            done()
            do["chunk"]()

        other = threading.Thread(target=dispatches, name="test-dispatcher")
        other.start()
        other.join(timeout=120)
        assert not other.is_alive()
    (span,) = _new_spans(e, n_spans)  # settled here, on the test's thread
    assert span["thread"] == other.ident != threading.get_ident()
    assert span["attrs"] == {"before": "prefill_lane_chunk"}


def test_a_stopped_engines_watcher_ends_and_drops_what_is_left(slab_engine, monkeypatch):
    e = slab_engine
    do, _, _ = _stamp_calls(e, monkeypatch)
    do["chunk"]()
    e.reset()
    with _held_programs(e, monkeypatch) as (programs, done):
        seq = e.recorder.total_recorded
        do["chunk"]()
        do["chunk"]()
        thread = e._watcher._thread
        assert thread.is_alive() and thread.daemon and thread.name == "dllama-device-done"
        assert programs[0].handle.waited_for.wait(60)
        stopper = threading.Thread(target=e.close)
        stopper.start()  # waits for the thread, which waits for the first handle
        while not e._watcher._stopped.is_set():
            pass
        programs[0].handle.released.set()
        stopper.join(timeout=60)
        assert not stopper.is_alive() and not thread.is_alive() and not e._watcher.alive
        # the first was stamped, the second dropped un-stamped with its handle
        assert programs[0].watched is not None and programs[1].watched is None
        assert programs[1].handle is None and not e._unsettled
        programs.clear()
    assert [ev for ev in e.recorder.events("device_done") if ev["seq"] > seq] == []
    # and the engine serves on: the next program starts a watcher of its own
    do["chunk"]()
    e.close()
    assert len([ev for ev in e.recorder.events("device_done") if ev["seq"] > seq]) <= 1


# -- dispatch and collect, one block ahead ------------------------------------
#
# `dispatch_lanes` + `collect_lanes` with every block dispatched before the
# one in flight is collected give what `decode_lanes`, block after block from
# host tokens, gives: the rows token for token and the cache bit for bit.


def _two_streams(e, step, chunk):
    """Lane 0 greedy throughout; lane 1 a seeded sampled stream whose length
    ends two tokens into the second block, then a chunk for a new prompt and
    another seeded stream that joins with its host token. `step(tokens, pos,
    active, seeds)` is called five times; tokens are (lane 0, lane 1), None
    for a lane that goes on where the last block left it."""
    temps = [0.0, 0.8]
    chunk(0, [5, 6, 7, 8, 9, 10, 11, 12], 0)
    chunk(1, [30, 31, 32, 33, 34, 35, 36, 37], 0)
    step([13, 38], [8, 8], [True, True], [None, 11], temps)
    step([None, None], [8 + BLOCK, 8 + BLOCK], [True, True], [None, 11], temps)
    # lane 1 ended by length inside that block: not live in the next one,
    # and its lane takes another prompt behind it
    step([None, 0], [8 + 2 * BLOCK, 0], [True, False], [None, None], temps)
    chunk(1, [40, 41, 42, 43, 44, 45, 46, 47], 0)
    step([None, 48], [8 + 3 * BLOCK, 8], [True, True], [None, 12], temps)
    step([None, None], [8 + 4 * BLOCK, 8 + BLOCK], [True, True], [None, 12], temps)


def test_dispatch_and_collect_one_block_ahead_give_decode_lanes_rows(slab_engine):
    e = slab_engine
    chunk = lambda lane, tokens, pos: e.prefill_lane_chunk(lane, tokens, pos)
    e.reset()
    want = []

    def drained(tokens, pos, active, seeds, temps):
        # the host's own tokens: the last row it read back
        tokens = [want[-1][-1][i] if t is None else t for i, t in enumerate(tokens)]
        want.append(e.decode_lanes(tokens, pos, BLOCK, active, temps, seeds=seeds))

    _two_streams(e, drained, chunk)
    cache_want = [np.asarray(x) for x in jax.tree.leaves(e.cache)]

    e.reset()
    got, flying = [], []
    seq = e.recorder.total_recorded

    def ahead(tokens, pos, active, seeds, temps):
        flying.append(e.dispatch_lanes(tokens, pos, BLOCK, active, temps, seeds=seeds))
        if len(flying) == 2:
            got.append(e.collect_lanes(flying.pop(0)))

    _two_streams(e, ahead, chunk)
    got.append(e.collect_lanes(flying.pop(0)))
    assert len(got) == len(want) == 5
    for n, (rows, ref) in enumerate(zip(got, want)):
        assert len(rows) == BLOCK
        live = (0,) if n == 2 else (0, 1)  # a parked lane reports zeros either way
        assert [[r[i] for i in live] for r in rows] == [[r[i] for i in live] for r in ref], n
    # the sampled lane moved (no argmax), and both caches are one
    assert [r[1] for r in got[0]] != [r[0] for r in got[0]]
    for a, b in zip(jax.tree.leaves(e.cache), cache_want):
        np.testing.assert_array_equal(np.asarray(a), b)
    dispatches = [ev for ev in e.recorder.events("step_dispatch")
                  if ev["seq"] > seq and ev["step"] == "decode_lanes"]
    assert [ev["ahead"] for ev in dispatches] == [0, 1, 1, 1, 1]
    assert [ev["n_live"] for ev in dispatches] == [2, 2, 1, 2, 2]
    completes = [ev for ev in e.recorder.events("step_complete")
                 if ev["seq"] > seq and ev["step"] == "decode_lanes"]
    assert len(completes) == 5 and all(ev["ms"] >= 0 for ev in completes)


def test_a_lane_goes_on_from_the_device_only_where_the_last_block_ran_it(slab_engine):
    """The device's last tokens are the newest uncollected block's: a lane
    it did not run live, or a block that was collected, has none to give."""
    e = slab_engine
    e.reset()
    block = e.dispatch_lanes([5, 0], [0, 0], BLOCK, active=[True, False])
    with pytest.raises(ValueError, match="continue from the device"):
        e.dispatch_lanes([None, None], [BLOCK, 0], BLOCK, active=[True, True])
    rows = e.collect_lanes(block)
    with pytest.raises(ValueError, match="continue from the device"):
        e.dispatch_lanes([None, 0], [BLOCK, 0], BLOCK, active=[True, False])
    assert e.decode_lanes([rows[-1][0], 0], [BLOCK, 0], BLOCK, active=[True, False])
    e.reset()


# -- a dispatch hands its host arguments over in the call ----------------------
#
# Between a method's `dispatch_prep` (a pool copy: its head) and its program
# call nothing is launched on the device: no `jnp.asarray`, no `jnp.int32(..)`
# (each a little program of its own) and, on one device, no `device_put`.


@contextlib.contextmanager
def _watched(e, monkeypatch):
    """For the block's duration: the programs `e` calls, by key with their
    arguments; the eager constructors of `jax.numpy` that were called; the
    shardings `device_put` was asked for."""
    seen = {"programs": [], "eager": [], "puts": []}

    def noting(what, note, real):
        def call(*a, **k):
            seen[what].append(note(*a, **k))
            return real(*a, **k)

        return call

    with monkeypatch.context() as m:
        for key, fn in list(e._compiled.items()):
            m.setitem(e._compiled, key, noting(
                "programs", lambda *a, _k=key: (_k, a), fn))
        for name in ("asarray", "array", "zeros", "ones", "full", "arange"):
            m.setattr(jnp, name, noting(
                "eager", lambda *a, _n=name, **k: _n, getattr(jnp, name)))
        scalar = type(jnp.int32)  # jnp.int32(3) is an eager launch too
        m.setattr(scalar, "__call__", noting(
            "eager", lambda self, *a, **k: str(self), scalar.__call__))
        m.setattr(jax, "device_put", noting(
            "puts", lambda x, device=None, **k: device, jax.device_put))
        yield seen


N_PAGES = 7  # three power-of-two buckets: 4, 2, 1
HANDED_OVER = {
    # method -> (call, program keys, host arrays a program)
    "decode_lanes": (
        lambda e: e.decode_lanes([5, 0], [0, 0], BLOCK, active=[True, False]),
        [("lane_block", BLOCK, 512)], 6),
    "prefill_lane_chunk": (
        lambda e: e.prefill_lane_chunk(1, list(range(1, 8)), 0),
        [("lane_prefill", 8, 512)], 2),
    "verify_lanes": (
        lambda e: e.verify_lanes([[5, 6, 7], [0, 0, 0]], [0, 0], [True, False]),
        [("lane_verify", 3, 512)], 3),
    "kv_adopt": (
        lambda e: e.kv_adopt(1, list(range(1, 1 + N_PAGES))),
        [("kv_adopt", b) for b in (4, 2, 1)], 3),
    "kv_publish": (
        lambda e: e.kv_publish(0, list(range(1, 1 + N_PAGES)), start_page=2),
        [("kv_publish", b) for b in (4, 2, 1)], 3),
    "kv_page_copy": (
        lambda e: e.kv_page_copy(list(range(1, 1 + N_PAGES)),
                                 list(range(11, 11 + N_PAGES))),
        [("kv_page_copy", b) for b in (4, 2, 1)], 2),
}


@pytest.mark.parametrize("method", list(HANDED_OVER))
def test_a_step_method_launches_its_programs_and_nothing_else(
        slab_engine, monkeypatch, method):
    """After warm-up, one call of a step method runs its step program(s)
    and no other device program: every host value reaches the program as
    a numpy array of the spec's dtype, as the call's own argument."""
    e = slab_engine
    call, keys, n_host = HANDED_OVER[method]
    call(e)  # builds what it needs
    e.reset()
    seq = e.recorder.total_recorded
    with _watched(e, monkeypatch) as seen:
        call(e)
    assert seen["eager"] == []
    assert [k for k, _ in seen["programs"]] == keys
    assert seen["puts"] == []  # one device: the call moves the tokens too
    for _, args in seen["programs"]:
        # a block's last argument stays on the device: the last tokens of
        # the block before it, which no host array carries
        on_device = [a for a in args if isinstance(a, jax.Array)]
        assert len(on_device) == (method == "decode_lanes")
        host = [a for a in args if not isinstance(a, (dict, jax.Array))]
        assert len(host) == n_host
        assert all(isinstance(a, (np.ndarray, np.generic)) for a in host)
        assert {str(a.dtype) for a in host} <= {"int32", "bool", "float32"}
    dispatch, = [ev for ev in e.recorder.events("step_dispatch") if ev["seq"] > seq]
    assert dispatch["step"] == method
    assert dispatch["host_args"] == n_host * len(keys)


def test_a_chunks_token_rows_are_built_without_a_python_list(
        slab_engine, monkeypatch):
    """A chunk's `lanes x bucket` token array is zeros with one row
    assigned, never a nested Python list, and equals the parent's."""
    e = slab_engine
    lane, tokens, bucket = 1, list(range(3, 10)), 8
    rows = [[0] * bucket for _ in range(e.batch_size)]  # the parent's lines
    rows[lane] = tokens + [0] * (bucket - len(tokens))
    posv = [e._park] * e.batch_size
    posv[lane] = 5
    handed = []
    real = e._host_args
    monkeypatch.setattr(e, "_host_args", lambda *a, tokens=None: (
        handed.append((tokens, a)), real(*a, tokens=tokens))[1])
    assert e.prefill_lane_chunk(lane, tokens, 5) == len(tokens)
    (got_rows, (got_pos,)), = handed
    assert isinstance(got_rows, np.ndarray) and isinstance(got_pos, np.ndarray)
    assert got_rows.dtype == got_pos.dtype == np.int32
    np.testing.assert_array_equal(got_rows, np.asarray(rows, np.int32))
    np.testing.assert_array_equal(got_pos, np.asarray(posv, np.int32))
    e.reset()


STEP_KINDS = {
    # step -> (call, host arrays)
    "decode_lanes": (HANDED_OVER["decode_lanes"][0], 6),
    "prefill_lane_chunk": (HANDED_OVER["prefill_lane_chunk"][0], 2),
    "verify_lanes": (HANDED_OVER["verify_lanes"][0], 3),
    "kv_adopt": (lambda e: e.kv_adopt(1, [1, 2, 3]), 6),
    "kv_publish": (lambda e: e.kv_publish(0, [1], start_page=0), 3),
    "kv_page_copy": (lambda e: e.kv_page_copy([1, 2], [3, 4]), 2),
    "decode_block": (lambda e: e.decode_block([5, 6], 0, 2), 4),
    "decode_step": (lambda e: e.decode_step(5, 0), 2),
    "prefill": (lambda e: e.prefill([1, 2, 3, 4]), 2),
}
# the pool-native programs take the page table too; the draft's are the slab's
NATIVE_STEP_KINDS = {
    "decode_lanes": (HANDED_OVER["decode_lanes"][0], 7),
    "prefill_lane_chunk": (HANDED_OVER["prefill_lane_chunk"][0], 3),
    "verify_lanes": (HANDED_OVER["verify_lanes"][0], 4),
    "draft_step": (
        lambda e: e.draft_propose([5, 0], [0, 0], [True, False], 2), 3),
}


def _dispatch_says_prep_and_host_arrays(e, step, call, n_host):
    seq = e.recorder.total_recorded
    call(e)
    e.reset()
    dispatch = [ev for ev in e.recorder.events("step_dispatch") if ev["seq"] > seq]
    assert [ev["step"] for ev in dispatch] == [step]
    assert dispatch[0]["host_args"] == n_host
    assert dispatch[0]["prep_ms"] >= 0.0
    completes = [ev for ev in e.recorder.events("step_complete") if ev["seq"] > seq]
    assert [ev["step"] for ev in completes] == [step]
    assert "prep_ms" not in completes[0] and "host_args" not in completes[0]


@pytest.mark.parametrize("step", list(STEP_KINDS))
def test_step_dispatch_says_its_prep_and_its_host_arrays(slab_engine, step):
    """Every step kind's `step_dispatch` carries `prep_ms` (from the
    method's `dispatch_prep`, or its head, to the dispatch's begin) and
    `host_args`; `step_complete` carries neither."""
    _dispatch_says_prep_and_host_arrays(slab_engine, step, *STEP_KINDS[step])


@pytest.mark.parametrize("step", list(NATIVE_STEP_KINDS))
def test_paged_step_dispatch_says_its_prep_and_its_host_arrays(build_engine, step):
    e = build_engine
    for lane in range(e.batch_size):  # rows 0-15 of every lane on its own pages
        e.adopt_pages(lane, [1 + 4 * lane + i for i in range(4)])
    _dispatch_says_prep_and_host_arrays(e, step, *NATIVE_STEP_KINDS[step])


def test_prep_ms_is_the_prep_spans_begin_to_the_dispatchs(slab_engine, monkeypatch):
    """`prep_ms` is made of readings the spans take: `dispatch_prep`'s
    begin to the step span's begin; a pool copy's, its head to its
    span's begin. A pool copy is no program of the completion stamps: its
    `step_dispatch` says no `dry`, it leaves no `device_done`, and the
    block before it stays the newest program."""
    import dllama_tpu.runtime.engine as engine_mod

    e = slab_engine
    HANDED_OVER["decode_lanes"][0](e)
    e.kv_publish(0, [1], start_page=0)
    e.reset()
    e.close()
    monkeypatch.setattr(engine_mod, "time", _TickingTime())
    n_spans, seq = e._spans.total_recorded, e.recorder.total_recorded
    HANDED_OVER["decode_lanes"][0](e)
    newest = e._newest
    e.kv_publish(0, [1], start_page=0)
    assert e._newest is newest and newest.step == "decode_lanes"
    spans = {s["name"]: s for s in
             e._spans.completed()[-(e._spans.total_recorded - n_spans):]}
    block, copy = [ev for ev in e.recorder.events("step_dispatch") if ev["seq"] > seq]
    # `e._spans` keeps its own clock: only the engine's readings tick
    assert block["prep_ms"] == round(
        (spans["decode_lanes"]["t0"] - spans["dispatch_prep"]["t0"]) * 1000, 3)
    assert copy["step"] == "kv_publish" and copy["prep_ms"] == 1000.0
    assert block["dry"] == 1 and "dry" not in copy
    e.close()
    done = [ev for ev in e.recorder.events("device_done") if ev["seq"] > seq]
    assert [ev["step"] for ev in done] == ["decode_lanes"]
    e.reset()


# ------------------------------------------- what "auto" serves, and the gauges

_PACKABLE = dict(dim=256, hidden_dim=256, n_layers=1, n_heads=16, n_kv_heads=4,
                 head_dim=16, vocab_size=256, seq_len=64)


@pytest.mark.parametrize(
    "backend,cfg,want",
    [
        ("tpu", _PACKABLE, "q40i4"),  # every in dim whole groups of 256 rows
        ("tpu", dict(_PACKABLE, hidden_dim=160), "q40"),  # w2's is not
        ("cpu", _PACKABLE, "dense"),
    ],
)
def test_auto_weight_format_by_backend(tmp_path, monkeypatch, backend, cfg, want):
    """`--weight-format auto`: on a TPU a Q40 file is served packed where
    the packed kernel takes every dense matmul's in dim, as int8 values
    where it does not; off a TPU dense. The backend is what
    `jax.default_backend()` reports (monkeypatched: nothing runs here)."""
    import dllama_tpu.runtime.engine as engine_mod
    from dllama_tpu.ops.quant_matmul import PackedQuantWeight, QuantWeight

    mp = str(tmp_path / "auto.m")
    make_tiny_model(mp, weight_type=FloatType.Q40, cfg=cfg)
    monkeypatch.setattr(engine_mod.jax, "default_backend", lambda: backend)
    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                        weight_format="auto")
    assert e.weight_format == want
    held = {"q40i4": PackedQuantWeight, "q40": QuantWeight}.get(want)
    wo = e.params["layers"]["wo"]
    assert type(wo) is held if held else not isinstance(wo, tuple)


@pytest.mark.parametrize(
    "arch,weight_format",
    [
        (LlmArch.LLAMA, "q40i4"), (LlmArch.LLAMA, "q40"), (LlmArch.LLAMA, "dense"),
        (LlmArch.QWEN3_MOE, "q40i4"), (LlmArch.QWEN3_MOE, "q40"),
    ],
)
def test_weight_bytes_by_form_add_up(tmp_path, arch, weight_format):
    """The resident-bytes gauges add up to the leaves' bytes, form by form,
    and the packed share of a decode step's quantized bytes is 1.0 for a
    dense model served packed, 0.0 served as int8 values, and between them
    where routed experts that stay int8 (their in axes, 64 and 96, are no
    whole groups of 256; read at the active share of those held) stand
    beside packed dense matmuls."""
    import jax

    from dllama_tpu.obs.metrics import get_registry
    from dllama_tpu.ops.quant_matmul import PackedQuantWeight, QuantWeight

    mp = str(tmp_path / "wb.m")
    make_tiny_model(mp, arch=arch, weight_type=FloatType.Q40)
    e = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0,
                        weight_format=weight_format)
    wb = e.weight_bytes
    leaves = jax.tree.leaves(e.params)
    assert wb["packed"] + wb["int8"] + wb["float"] == sum(a.nbytes for a in leaves)
    quant = lambda cls: sum(
        a.nbytes
        for w in jax.tree.leaves(e.params, is_leaf=lambda x: isinstance(x, tuple))
        if type(w) is cls for a in w
    )
    assert wb["packed"] == quant(PackedQuantWeight)
    assert wb["int8"] == quant(QuantWeight)
    text = get_registry().render()
    for form in ("packed", "int8", "float"):
        assert f'dllama_weight_bytes{{form="{form}"}} {wb[form]}\n' in text
    share = wb["decode_packed_share"]
    if arch == LlmArch.LLAMA:
        assert share == {"q40i4": 1.0, "q40": 0.0, "dense": 0.0}[weight_format]
        assert (wb["packed"] > 0) == (weight_format == "q40i4")
        assert (wb["int8"] > 0) == (weight_format == "q40")
    elif weight_format == "q40":
        assert share == 0.0 and wb["packed"] == 0
    else:
        h = e.header
        experts = sum(
            a.nbytes for n in ("w1", "w2", "w3") for a in e.params["layers"][n])
        assert experts == wb["int8"]  # the routed stacks, and nothing else
        step_int8 = experts * h.n_active_experts / h.n_experts
        assert share == pytest.approx(wb["packed"] / (wb["packed"] + step_int8))
        assert 0.0 < share < 1.0
