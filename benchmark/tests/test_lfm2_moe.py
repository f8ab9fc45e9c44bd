"""The `lfm2_moe` family as the harness takes it from its files: header keys
and tensors of `configs/lfm2-24b-a2b-e16.json` through the program's format
code, costs against one decode step and one chunk counted by hand, the two
convolution readers against a hand-made run directory, and every fault of the
reference's `FAULTS` against the sound reference."""

import json
import os

import numpy as np
import pytest

import run as bench
from benchmark.costs import lfm2_moe as family_costs
from benchmark.harness import compare, costs, scopes, weights
from benchmark.references import lfm2_moe
from benchmark.references.q40file import Q40File
from dllama_tpu.formats.model_file import LlmArch, layer_table, read_llm_header, tensor_plan

NAME = "lfm2-24b-a2b-e16"


def test_the_header_keys_reach_the_file_and_the_layer_table(tmp_path):
    cfg = bench.load_config(NAME, rehearse=True)
    path = str(tmp_path / "model.m")
    weights.write_model(path, cfg, seed=2)
    h = read_llm_header(path)
    assert h.arch == LlmArch.LFM2_MOE and h.conv_l_cache == 3 and h.stateful
    assert (h.n_experts, h.n_routed_experts, h.first_expert, h.n_dense_layers) == (4, 8, 0, 2)
    assert (h.score_sigmoid, h.route_norm, h.route_scale) == (True, True, 1.0)
    table = layer_table(h)
    assert [k.conv for k in table] == [t == "conv" for t in cfg["layer_types"]]
    assert [k.experts for k in table] == [False, False] + [True] * 6
    assert [k.row for k in table] == [0, 1, 0, 2, 3, 4, 1, 5]
    leaves = {s.name.split(".", 2)[-1] for s in tensor_plan(h) if s.name.startswith("layers.3.")}
    assert {"conv_in", "conv_w", "conv_out", "expert_bias", "moe_gate", "experts.3.w2"} <= leaves
    assert not {"q", "q_norm", "experts.4.w1", "shared.w1"} & leaves
    assert tensor_plan(h)[-1].name == "wcls"


def test_the_published_file_states_its_cut_and_its_header():
    cfg = bench.load_config(NAME, rehearse=False)
    wire = weights.header_for(cfg)
    assert (wire["n_layers"], wire["n_experts"], wire["vocab_size"]) == (40, 16, 16384)
    assert (wire["dim"], wire["hidden_dim"], wire["moe_hidden_dim"], wire["head_dim"]) == (
        2048, 11776, 1536, 64)
    assert (wire["n_routed_experts"], wire["n_dense_layers"], wire["conv_l_cache"]) == (64, 2, 3)
    mask = wire["attn_layers_lo"] | wire["attn_layers_hi"] << 30
    assert [l for l in range(40) if mask >> l & 1] == [
        l for l, t in enumerate(cfg["layer_types"]) if t == "full_attention"]
    assert cfg["published"] == {"num_experts": 64, "vocab_size": 65536}
    assert cfg["reduced"] == ["num_experts", "vocab_size"]
    assert cfg["num_routed_experts"] == 64 and cfg["vocab_size"] * 4 == 65536
    assert cfg["layer_types"].count("conv") == 30 and len(cfg["layer_types"]) == 40
    assert "four" in cfg["deployment"] and "40 of 40" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {"head_dim", "in_proj_order", "taps", "qk_norm", "router",
                                   "final_norm", "head"}


def test_the_stated_tensors_carry_the_state_and_route_neither_uniformly_nor_to_one(tmp_path):
    """The taps as the file states them: three a channel of std 3^-1/2, so the
    two carried rows give two thirds of the convolution's variance. The
    router's matrix by the rule and the bias as stated: every expert is
    chosen, none by more than a few times its share."""
    cfg = bench.load_config(NAME, rehearse=True)
    assert cfg["file"]["tensors"] == bench.load_config(NAME, rehearse=False)["file"]["tensors"]
    path = str(tmp_path / "model.m")
    weights.write_model(path, cfg, seed=3)
    f = Q40File(path)
    taps = np.concatenate([np.asarray(f.f32(f"layers.{l}.conv_w")) for l in (0, 1, 3, 4, 5, 7)])
    assert taps.shape[1] == 3 and abs(taps.std() - 3 ** -0.5) < 0.03
    share = (taps[:, :2] ** 2).sum() / (taps ** 2).sum()
    assert 0.6 < share < 0.73
    gate, bias = np.asarray(f.f32("layers.2.moe_gate")), np.asarray(f.f32("layers.2.expert_bias"))
    assert 0.003 < bias.std() < 0.02
    y = np.random.default_rng(0).standard_normal((4000, gate.shape[1])).astype(np.float32)
    scores = 1 / (1 + np.exp(-(y @ gate.T)))
    chosen = np.argsort(-(scores + bias), axis=1)[:, :2]
    share = np.bincount(chosen.ravel(), minlength=8) / chosen.size
    assert share.min() > 0.3 / 8 and share.max() < 3.0 / 8


def test_costs_of_one_decode_step_and_one_chunk_counted_by_hand():
    cfg = bench.load_config(NAME, rehearse=False)
    d, hd, q40 = 2048, 64, 18 / 32
    attention = d * (32 * hd + 2 * 8 * hd) + 32 * hd * d  # q, k, v; o
    conv = d * 3 * d + d * d  # in_proj, out_proj
    assert family_costs.conv_weights(cfg) == conv == 16_777_216 and attention == 10_485_760
    assert family_costs.layer_counts(cfg) == (30, 10, 2, 38)
    expert, dense, head = 3 * d * 1536, 3 * d * 11776, d * 16384
    router = 4 * (d + 1) * 64
    assert family_costs.router_bytes(cfg) == router and family_costs.shared_weights(cfg) == 0
    assert family_costs.swiglu_weights(cfg, 1536) == expert == 9_437_184
    # 16 live lanes at 500 positions
    touched = 16 * (1 - (1 - 4 / 64) ** 16)
    assert family_costs.held_experts_touched(cfg, 16) == pytest.approx(touched) and 10 < touched < 11
    taps, state = 4 * d * 3, 2 * 2 * d * 2  # f32 taps; two bf16 rows in and out
    one_conv = conv * q40 + taps + 16 * state
    assert family_costs.conv_decode_bytes(cfg, 16) == pytest.approx(one_conv)
    kv = 10 * 16 * 500 * (2 * 8 * hd * 2)
    weights_read = 10 * attention + 2 * dense + 38 * touched * expert + head
    want = weights_read * q40 + 38 * router + 30 * one_conv + kv
    assert costs.decode_step_bytes(cfg, 16, 500.0) == pytest.approx(want)
    assert 2.6e9 < want < 2.8e9  # 2.2 GB of it the touched experts
    # a token multiplies by its share of the routed experts: 4 x 16 / 64 of one
    per_token = 30 * conv + 10 * attention + 2 * dense + 38 * (d * 64 + expert) + head
    assert costs.weights_per_token(cfg) == int(per_token)
    # the accepted reader hands over 16 lanes x 512 rows; the floor is one lane's
    assert costs.prefill_flops(cfg, 16 * 512) == pytest.approx(2.0 * (int(per_token) - head) * 512)
    assert family_costs.conv_prefill_flops(cfg, 300) == 2.0 * conv * 300


def run_dir_of(tmp_path, cfg, events, table, modules):
    """A run directory as `run.py` leaves it, by hand."""
    os.makedirs(tmp_path, exist_ok=True)
    window = {"t0": 0.0, "t1": 50.0, "trace_t0": 10.0, "trace_t1": 15.0, "lanes": 16,
              "chips": 1, "device_kind": "TPU v5 lite", "config": cfg, "mean_context": 500.0}
    for name, obj in (("window.json", window), ("recorder.json", {"events": events}),
                      ("trace_digest.json", {"modules": modules}), (scopes.TABLE, table)):
        with open(os.path.join(tmp_path, name), "w") as f:
            json.dump(obj, f)
    return str(tmp_path)


def test_the_two_convolution_readers_give_hand_computed_shares(tmp_path):
    cfg = bench.load_config(NAME, rehearse=False)
    peaks = costs.peaks("TPU v5 lite")
    block = {"kind": "step_dispatch", "step": "decode_lanes", "t": 11.0, "n_steps": 8,
             "n_live": 12, "state_lanes": 12}
    chunk = {"kind": "step_dispatch", "step": "prefill_lane_chunk", "t": 12.0, "n_tokens": 300,
             "bucket": 512, "state_lanes": 1, "replay_tokens": 0}
    outside = dict(block, t=30.0)  # not in the traced slice
    table = {"conv/decode": 0.010, "conv/decode/mix": 0.002, "conv/prefill": 0.004,
             "conv/prefill/mix": 0.001, "attn/full_decode": 0.5}
    modules = {"jit_block": {"seconds": 1.0, "calls": 4}, "jit_step": {"seconds": 1.0, "calls": 2}}
    run = run_dir_of(tmp_path / "run", cfg, [block, block, chunk, outside], table, modules)
    decode = bench.layer_reader("conv_decode_hbm_roofline").read(run)
    need = 2 * 8 * 30 * family_costs.conv_decode_bytes(cfg, 12)
    assert decode == pytest.approx(100 * need / peaks["hbm_bytes_per_s"] / (0.012 / 4 * 2))
    prefill = bench.layer_reader("conv_prefill_mxu_roofline").read(run)
    flops = 30 * 2.0 * 16_777_216 * 300
    assert prefill == pytest.approx(100 * flops / peaks["bf16_flops_per_s"] / (0.005 / 2 * 1))
    assert 0 < decode < 100 and 0 < prefill < 100
    # a program that lacks the fields or the scopes (the parent's): nothing, and no error
    bare = [{k: v for k, v in e.items() if k not in ("state_lanes", "replay_tokens")}
            for e in (block, chunk)]
    old = run_dir_of(tmp_path / "old", cfg, bare, table, modules)
    no_scope = run_dir_of(tmp_path / "noscope", cfg, [block, chunk],
                          {"attn/full_decode": 0.5}, modules)
    for name in ("conv_decode_hbm_roofline", "conv_prefill_mxu_roofline"):
        assert bench.layer_reader(name).read(old) is None
        assert bench.layer_reader(name).read(no_scope) is None


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


@pytest.mark.parametrize("name", lfm2_moe.FAULTS)
def test_a_fault_changes_the_references_logits(name, long_sequence, sound):
    """Every fault is caught at the tiny widths: it moves the last 256
    positions' logits (which lie past the 512-row chunk boundary) by a
    quarter of a logit std or more; the float8 control by less than the
    model faults, and by more than nothing."""
    cfg, path, ids = long_sequence
    fault = lfm2_moe.FAULTS[name]
    assert isinstance(fault, dict) and fault.min_prompt in (0, 512 + 64)
    assert len(ids) > fault.min_prompt
    wrong = logits_of({**cfg, **fault}, path, ids)
    change = np.abs(wrong - sound).max() / sound.std()
    print(f"{name}: logits move by up to {change:.3f} std")
    assert change > (0.05 if "float8" in name else 0.25), name


def test_the_sound_reference_repeats_and_the_faults_are_ten(long_sequence, sound):
    assert np.array_equal(logits_of(*long_sequence), sound)
    # the issue's nine, and the precision control that bounds `gap_tol` from above
    assert len(lfm2_moe.FAULTS) == 10 and "activations in float8" in lfm2_moe.FAULTS
    assert {"zero state at a chunk boundary", "B left out", "C left out"} <= set(lfm2_moe.FAULTS)
