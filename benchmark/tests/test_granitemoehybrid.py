"""The `granitemoehybrid` family as the harness takes it from its files: header
keys and tensors of `configs/granite-4.0-h-small-l20-e18.json` through the
program's format code, costs against one decode step and one chunk counted by
hand, the two mixer readers against a hand-made run directory, and every fault
of the reference's `FAULTS` against the sound reference."""

import json
import os

import numpy as np
import pytest

import run as bench
from benchmark.costs import granitemoehybrid as family_costs
from benchmark.harness import compare, costs, scopes, weights
from benchmark.references import granitemoehybrid
from benchmark.references.q40file import Q40File
from dllama_tpu.formats.model_file import LlmArch, layer_table, read_llm_header, tensor_plan

NAME = "granite-4.0-h-small-l20-e18"


def test_the_header_keys_reach_the_file_and_the_layer_table(tmp_path):
    cfg = bench.load_config(NAME, rehearse=True)
    path = str(tmp_path / "model.m")
    weights.write_model(path, cfg, seed=2)
    h = read_llm_header(path)
    assert h.arch == LlmArch.GRANITE_MOE_HYBRID and h.stateful and h.state_unbounded
    assert (h.ssm_n_heads, h.ssm_head_dim, h.ssm_state_dim, h.ssm_conv_taps) == (4, 16, 16, 4)
    assert (h.n_experts, h.n_routed_experts, h.first_expert, h.n_shared_experts) == (4, 8, 0, 2)
    assert (h.score_sigmoid, h.route_norm, h.full_attn_no_rope) == (False, True, True)
    assert (h.embed_multiplier, h.residual_multiplier, h.attention_multiplier,
            h.logits_scaling) == (12.0, 0.22, 0.0078125, 16.0)
    table = layer_table(h)
    assert [k.ssm for k in table] == [t == "mamba" for t in cfg["layer_types"]]
    assert all(k.experts for k in table) and not any(k.rope for k in table)
    assert [k.row for k in table] == [0, 1, 0, 2, 3]
    leaves = {s.name.split(".", 2)[-1] for s in tensor_plan(h) if s.name.startswith("layers.3.")}
    assert {"ssm_in_z", "ssm_in_xbc", "ssm_in_dt", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias",
            "ssm_a_log", "ssm_d", "ssm_norm", "ssm_out", "moe_gate", "shared.w2",
            "experts.3.w2"} <= leaves
    assert not {"q", "q_norm", "expert_bias", "experts.4.w1", "conv_in"} & leaves
    assert tensor_plan(h)[-1].name == "wcls"


def test_the_published_file_states_its_cut_and_its_header():
    cfg = bench.load_config(NAME, rehearse=False)
    wire = weights.header_for(cfg)
    assert (wire["n_layers"], wire["n_experts"], wire["vocab_size"]) == (20, 18, 25088)
    assert (wire["dim"], wire["hidden_dim"], wire["head_dim"], wire["n_active_experts"]) == (
        4096, 768, 128, 10)
    assert (wire["n_routed_experts"], wire["n_shared_experts"], wire["full_attn_no_rope"]) == (
        72, 2, 1)
    assert (wire["ssm_n_heads"], wire["ssm_head_dim"], wire["ssm_state_dim"],
            wire["ssm_n_groups"], wire["ssm_conv_taps"]) == (128, 64, 128, 1, 4)
    mask = wire["attn_layers_lo"] | wire["attn_layers_hi"] << 30
    assert [l for l in range(20) if mask >> l & 1] == [5, 15] == [
        l for l, t in enumerate(cfg["layer_types"]) if t == "attention"]
    assert cfg["published"]["num_hidden_layers"] == 40 == 2 * len(cfg["layer_types"])
    assert cfg["published"]["num_local_experts"] == 72 == cfg["num_routed_experts"]
    assert cfg["published"]["vocab_size"] == 100352 == 4 * cfg["vocab_size"]
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types", "num_local_experts",
                              "vocab_size"]
    assert cfg["num_local_experts"] == cfg["num_experts"] == 18
    assert cfg["layer_types"].count("mamba") == 18
    assert "two pipeline stages" in cfg["deployment"] and "0-19 of 40" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {
        "head_dim", "in_proj_order", "in_proj_in_parts", "taps", "recurrence", "gate",
        "state_dtype", "attention", "router", "experts", "multipliers", "norms", "head"}
    # the published widths, every one
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["shared_intermediate_size"],
            cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_expand"], cfg["num_experts_per_tok"]) == (
        4096, 768, 1536, 128, 64, 128, 2, 10)


def test_the_stated_tensors_give_a_memory_of_many_lengths_and_a_fair_router(tmp_path):
    """The recurrence's scalars as the file states them: decay rates `A` over
    1-16, step biases whose softplus lies over 0.001-0.1, so that a head's
    state forgets over anything from a few positions to a thousand; `D` 1; and
    the router's matrix by the rule: every expert is chosen, none by more
    than a few times its share."""
    cfg = bench.load_config(NAME, rehearse=True)
    assert cfg["file"]["tensors"] == bench.load_config(NAME, rehearse=False)["file"]["tensors"]
    path = str(tmp_path / "model.m")
    weights.write_model(path, cfg, seed=3)
    f = Q40File(path)
    mamba = [l for l, t in enumerate(cfg["layer_types"]) if t == "mamba"]
    a = np.exp(np.concatenate([np.asarray(f.f32(f"layers.{l}.ssm_a_log")) for l in mamba]))
    bias = np.concatenate([np.asarray(f.f32(f"layers.{l}.ssm_dt_bias")) for l in mamba])
    step = np.log1p(np.exp(bias))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.max() > 4 * a.min()
    assert 0.0009 < step.min() and step.max() < 0.11 and step.max() > 10 * step.min()
    life = 1.0 / (a[:, None] * step[None, :])  # positions until a state falls by e
    assert life.min() < 8 and life.max() > 50  # sixteen heads here; 2304 at the published size
    assert np.all(np.asarray(f.f32(f"layers.{mamba[0]}.ssm_d")) == 1.0)
    dt_rows = np.asarray(f.f32(f"layers.{mamba[0]}.ssm_in_dt"))
    z_rows = np.asarray(f.f32(f"layers.{mamba[0]}.ssm_in_z"))
    assert 0.2 < dt_rows.std() / z_rows.std() < 0.3  # the dt rows at a quarter of the gain
    embed = np.asarray(f.f32("embed"))
    assert abs(embed.std() * cfg["embedding_multiplier"] - 1.0) < 0.05
    out = np.asarray(f.f32(f"layers.{mamba[0]}.ssm_out"))
    assert abs(out.std() * np.sqrt(out.shape[1]) * cfg["residual_multiplier"] - 1.0) < 0.1
    gate = np.asarray(f.f32("layers.2.moe_gate"))
    y = np.random.default_rng(0).standard_normal((4000, gate.shape[1])).astype(np.float32)
    chosen = np.argsort(-(y @ gate.T), axis=1)[:, :3]
    share = np.bincount(chosen.ravel(), minlength=8) / chosen.size
    assert share.min() > 0.3 / 8 and share.max() < 3.0 / 8


def test_costs_of_one_decode_step_and_one_chunk_counted_by_hand():
    cfg = bench.load_config(NAME, rehearse=False)
    d, hd, q40 = 4096, 128, 18 / 32
    attention = d * (32 * hd + 2 * 8 * hd) + 32 * hd * d  # q, k, v; o
    mixer = d * (8192 + 8448 + 128) + 8192 * d  # in_proj, out_proj
    assert family_costs.ssm_weights(cfg) == mixer == 102_236_160 and attention == 41_943_040
    assert family_costs.layer_counts(cfg) == (18, 2)
    expert, shared, head = 3 * d * 768, 3 * d * 1536, d * 25088
    router = 4 * d * 72
    assert family_costs.router_bytes(cfg) == router
    assert family_costs.shared_weights(cfg) == shared == 18_874_368
    assert family_costs.swiglu_weights(cfg, 768) == expert == 9_437_184
    # 32 live lanes at 500 positions: every held expert is touched
    touched = 18 * (1 - (1 - 10 / 72) ** 32)
    assert family_costs.held_experts_touched(cfg, 32) == pytest.approx(touched) and touched > 17.8
    small = 4 * (8448 * 5 + 8192 + 3 * 128)
    state = 2 * (4 * 8192 * 128 + 3 * 8448 * 2)  # f32 state, bf16 rows, in and out
    assert state == 2 * (4_194_304 + 50_688)
    one_mixer = mixer * q40 + small + 32 * state
    assert family_costs.ssm_decode_bytes(cfg, 32) == pytest.approx(one_mixer)
    kv = 2 * 32 * 500 * (2 * 8 * hd * 2)
    weights_read = 2 * attention + 20 * (touched * expert + shared) + head
    want = weights_read * q40 + 20 * router + 18 * one_mixer + kv
    assert costs.decode_step_bytes(cfg, 32, 500.0) == pytest.approx(want)
    assert 8.1e9 < want < 8.4e9 and 18 * 32 * state == pytest.approx(4.89e9, rel=0.01)
    # a token multiplies by its share of the routed experts: 10 x 18 / 72 of one
    per_token = 18 * mixer + 2 * attention + 20 * (d * 72 + 2.5 * expert + shared) + head
    assert costs.weights_per_token(cfg) == int(per_token)
    # the accepted reader hands over 32 lanes x 512 rows; the floor is one lane's
    assert costs.prefill_flops(cfg, 32 * 512) == pytest.approx(2.0 * (int(per_token) - head) * 512)
    # the recurrence's block form over 300 rows: two blocks' triangles and the carried state
    pairs = 300 * 257 / 2
    scan = 2.0 * pairs * (128 + 8192) + 300 * 4.0 * 8192 * 128
    assert family_costs.ssm_prefill_flops(cfg, 300) == pytest.approx(2.0 * mixer * 300 + scan)
    assert scan < 0.04 * 2.0 * mixer * 300


def run_dir_of(tmp_path, cfg, events, table, modules):
    """A run directory as `run.py` leaves it, by hand."""
    os.makedirs(tmp_path, exist_ok=True)
    window = {"t0": 0.0, "t1": 50.0, "trace_t0": 10.0, "trace_t1": 15.0, "lanes": 32,
              "chips": 1, "device_kind": "TPU v5 lite", "config": cfg, "mean_context": 500.0}
    for name, obj in (("window.json", window), ("recorder.json", {"events": events}),
                      ("trace_digest.json", {"modules": modules}), (scopes.TABLE, table)):
        with open(os.path.join(tmp_path, name), "w") as f:
            json.dump(obj, f)
    return str(tmp_path)


def test_the_two_mixer_readers_give_hand_computed_shares(tmp_path):
    cfg = bench.load_config(NAME, rehearse=False)
    peaks = costs.peaks("TPU v5 lite")
    block = {"kind": "step_dispatch", "step": "decode_lanes", "t": 11.0, "n_steps": 8,
             "n_live": 12, "state_lanes": 12}
    chunk = {"kind": "step_dispatch", "step": "prefill_lane_chunk", "t": 12.0, "n_tokens": 300,
             "bucket": 512, "state_lanes": 1, "replay_tokens": 0}
    outside = dict(block, t=30.0)  # not in the traced slice
    table = {"attn/ssm/decode": 0.20, "attn/ssm/decode/mix": 0.10, "attn/ssm/prefill": 0.02,
             "attn/ssm/prefill/mix": 0.01, "attn/full_decode": 0.5}
    modules = {"jit_block": {"seconds": 1.0, "calls": 4}, "jit_step": {"seconds": 1.0, "calls": 2}}
    run = run_dir_of(tmp_path / "run", cfg, [block, block, chunk, outside], table, modules)
    decode = bench.layer_reader("ssm_decode_hbm_roofline").read(run)
    need = 2 * 8 * 18 * family_costs.ssm_decode_bytes(cfg, 12)
    assert decode == pytest.approx(100 * need / peaks["hbm_bytes_per_s"] / (0.30 / 4 * 2))
    prefill = bench.layer_reader("ssm_prefill_mxu_roofline").read(run)
    flops = 18 * family_costs.ssm_prefill_flops(cfg, 300)
    assert prefill == pytest.approx(100 * flops / peaks["bf16_flops_per_s"] / (0.03 / 2 * 1))
    assert 0 < decode < 100 and 0 < prefill < 100
    # a program that lacks the fields or the scopes (the parent's): nothing, and no error
    bare = [{k: v for k, v in e.items() if k not in ("state_lanes", "replay_tokens")}
            for e in (block, chunk)]
    old = run_dir_of(tmp_path / "old", cfg, bare, table, modules)
    no_scope = run_dir_of(tmp_path / "noscope", cfg, [block, chunk],
                          {"attn/full_decode": 0.5, "attn/conv/decode": 0.1}, modules)
    other = run_dir_of(tmp_path / "other", bench.load_config("lfm2-24b-a2b-e16", False),
                       [block, chunk], table, modules)
    for name in ("ssm_decode_hbm_roofline", "ssm_prefill_mxu_roofline"):
        for run_dir in (old, no_scope, other):
            assert bench.layer_reader(name).read(run_dir) is None


@pytest.fixture(scope="module")
def long_sequence(tmp_path_factory):
    """(cfg, model path, ids past a 512-row chunk) of a tiny seeded file."""
    cfg = bench.load_config(NAME, rehearse=True)
    path = str(tmp_path_factory.mktemp("faults") / "model.m")
    weights.write_model(path, cfg, seed=6)
    ids = [int(t) for t in np.random.default_rng(6).integers(0, 500, 700)]
    return cfg, path, ids


def logits_of(cfg, path, ids):
    return np.asarray(compare.reference_for(cfg).last_logits(path, cfg, [ids], [256])[0])


@pytest.fixture(scope="module")
def sound(long_sequence):
    return logits_of(*long_sequence)


@pytest.mark.parametrize("name", granitemoehybrid.FAULTS)
def test_a_fault_changes_the_references_logits(name, long_sequence, sound):
    """Every fault is caught at the tiny widths: it moves the last 256
    positions' logits (which lie past the 512-row chunk boundary, 188
    positions and more: what the longest-lived heads still carry) by a tenth
    of a logit std or more; the float8 control by more than nothing."""
    cfg, path, ids = long_sequence
    fault = granitemoehybrid.FAULTS[name]
    assert isinstance(fault, dict) and fault.min_prompt in (0, 512 + 64)
    assert len(ids) > fault.min_prompt
    wrong = logits_of({**cfg, **fault}, path, ids)
    change = np.abs(wrong - sound).max() / sound.std()
    print(f"{name}: logits move by up to {change:.3f} std")
    assert change > (0.05 if "float8" in name else 0.1), name


def test_the_sound_reference_repeats_and_the_faults_are_fourteen(long_sequence, sound):
    assert np.array_equal(logits_of(*long_sequence), sound)
    # the issue's thirteen, and the precision control that bounds `gap_tol` from above
    assert len(granitemoehybrid.FAULTS) == 14 and "activations in float8" in granitemoehybrid.FAULTS
    assert {"zero state at a chunk boundary", "decay left out", "D left out",
            "gate after the norm", "rope applied"} <= set(granitemoehybrid.FAULTS)
