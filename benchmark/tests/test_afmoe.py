"""The `afmoe` family as the harness takes it from its files: header keys and
tensors of `configs/trinity-large-l9-e32.json` through the program's format
code, costs against one decode step and one chunk counted by hand, and every
fault of the reference's `FAULTS` against the sound reference."""

import numpy as np
import pytest

import run as bench
from benchmark.costs import afmoe as family_costs
from benchmark.harness import compare, costs, weights
from benchmark.references import afmoe
from benchmark.references.q40file import Q40File
from dllama_tpu.formats.model_file import LlmArch, layer_table, read_llm_header, tensor_plan

NAME = "trinity-large-l9-e32"


def test_the_header_keys_reach_the_file_and_the_layer_table(tmp_path):
    cfg = bench.load_config(NAME, rehearse=True)
    path = str(tmp_path / "model.m")
    weights.write_model(path, cfg, seed=2)
    h = read_llm_header(path)
    assert h.arch == LlmArch.AFMOE
    assert (h.sliding_window, h.full_attn_period, h.full_attn_no_rope) == (1024, 4, True)
    assert (h.n_experts, h.n_routed_experts, h.first_expert, h.n_shared_experts) == (4, 8, 0, 1)
    assert (h.score_sigmoid, h.route_norm, h.route_scale, h.embed_scale) == (True, True, 2.448, True)
    table = layer_table(h)
    assert [k.window for k in table] == [t == "sliding_attention" for t in cfg["layer_types"]]
    assert [k.rope for k in table] == [k.window for k in table]
    assert [k.experts for k in table] == [False, True, True, True, True]
    assert [k.row for k in table] == [0, 1, 2, 0, 3]
    leaves = {s.name.split(".", 2)[-1] for s in tensor_plan(h) if s.name.startswith("layers.1.")}
    assert {"att_gate", "expert_bias", "moe_gate", "shared.w1", "experts.3.w2", "q_norm",
            "post_att_norm", "post_ffn_norm"} <= leaves and "experts.4.w1" not in leaves
    assert tensor_plan(h)[-1].name == "wcls"


def test_the_published_file_states_its_cut_and_its_header():
    cfg = bench.load_config(NAME, rehearse=False)
    wire = weights.header_for(cfg)
    assert (wire["n_layers"], wire["n_experts"], wire["vocab_size"]) == (9, 32, 25024)
    assert (wire["dim"], wire["hidden_dim"], wire["moe_hidden_dim"], wire["head_dim"]) == (
        3072, 12288, 3072, 128)
    assert (wire["n_routed_experts"], wire["sliding_window"], wire["route_scale_milli"]) == (
        256, 4096, 2448)
    assert cfg["published"]["num_experts"] == cfg["num_routed_experts"] == 256
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["layer_types"].count("full_attention") == 2 and len(cfg["layer_types"]) == 9
    assert "eight" in cfg["deployment"] and set(cfg["assumed"]) >= {
        "attention_gate", "rope_on_sliding_layers_only", "qk_norm"}


def test_the_stated_tensors_route_neither_uniformly_nor_all_to_one(tmp_path):
    """The router's matrix as the rule draws it and the bias as the file
    states it: every expert is chosen, none by more than a few times its
    share, and the bias changes the selection of a share of the tokens."""
    cfg = bench.load_config(NAME, rehearse=True)
    path = str(tmp_path / "model.m")
    weights.write_model(path, cfg, seed=3)
    f = Q40File(path)
    gate, bias = np.asarray(f.f32("layers.1.moe_gate")), np.asarray(f.f32("layers.1.expert_bias"))
    assert 0.005 < bias.std() < 0.02
    y = np.random.default_rng(0).standard_normal((4000, gate.shape[1])).astype(np.float32)
    scores = 1 / (1 + np.exp(-(y @ gate.T)))
    with_bias = np.argsort(-(scores + bias), axis=1)[:, :2]
    without = np.argsort(-scores, axis=1)[:, :2]
    share = np.bincount(with_bias.ravel(), minlength=8) / with_bias.size
    assert share.min() > 0.3 / 8 and share.max() < 3.0 / 8
    moved = (np.sort(with_bias) != np.sort(without)).any(axis=1).mean()
    assert 0.005 < moved < 0.5


def test_the_expert_blocks_post_norm_is_drawn_smaller_than_the_attention_blocks(tmp_path):
    """`post_ffn_norm` as the file states it (gains near 0.4: PERF.md section 6
    says what the rule's gains near 1 did to the comparison), every other norm
    by the rule; the published file states the same draw as the rehearsal."""
    cfg = bench.load_config(NAME, rehearse=True)
    assert cfg["file"]["tensors"]["post_ffn_norm"] == bench.load_config(
        NAME, rehearse=False)["file"]["tensors"]["post_ffn_norm"]
    path = str(tmp_path / "model.m")
    weights.write_model(path, cfg, seed=4)
    f = Q40File(path)
    for layer in (0, 1, 4):  # the dense layer and two sparse ones
        ffn = np.asarray(f.f32(f"layers.{layer}.post_ffn_norm"))
        att = np.asarray(f.f32(f"layers.{layer}.post_att_norm"))
        assert 0.32 <= ffn.min() and ffn.max() <= 0.48 and 0.38 < ffn.mean() < 0.42
        assert 0.8 <= att.min() and att.max() <= 1.2


def test_costs_of_one_decode_step_and_one_chunk_counted_by_hand():
    cfg = bench.load_config(NAME, rehearse=False)
    d, hd, q40 = 3072, 128, 18 / 32
    attention = d * (48 * hd + 2 * 8 * hd) + 48 * hd * d + d * 48 * hd  # q, k, v; o; gate
    assert family_costs.gated_attention_weights(cfg) == attention == 62_914_560
    expert = 3 * d * 3072
    dense, head, router = 3 * d * 12288, d * 25024, 4 * (d + 1) * 256
    # 8 live lanes at 10000 positions: a window layer reads 4096 rows, a full layer all
    touched = 32 * (1 - (1 - 4 / 256) ** 8)
    assert family_costs.held_experts_touched(cfg, 8) == pytest.approx(touched) and 3.7 < touched < 3.8
    kv = 8 * (7 * 4096 + 2 * 10000) * (2 * 8 * hd * 2)
    weights_read = 9 * attention + dense + 8 * (expert + touched * expert) + head
    want = weights_read * q40 + 8 * router + kv
    assert costs.decode_step_bytes(cfg, 8, 10000.0) == pytest.approx(want)
    assert 2.6e9 < want < 2.8e9  # 1.6 GB of it keys and values
    # below the window both kinds read the context
    short = costs.decode_step_bytes(cfg, 8, 1000.0)
    assert short == pytest.approx(weights_read * q40 + 8 * router + 8 * 9 * 1000 * 4096)
    # a token multiplies by its share of the routed experts: 4 x 32 / 256 of one
    per_token = 9 * attention + dense + 8 * (expert + d * 256 + 0.5 * expert) + head
    assert costs.weights_per_token(cfg) == int(per_token)
    assert costs.prefill_flops(cfg, 8 * 512) == pytest.approx(2.0 * (int(per_token) - head) * 4096)


@pytest.fixture(scope="module")
def long_sequence(tmp_path_factory):
    """(cfg, model path, ids past the rehearsal's window of 1024) of a tiny seeded file."""
    cfg = bench.load_config(NAME, rehearse=True)
    path = str(tmp_path_factory.mktemp("faults") / "model.m")
    weights.write_model(path, cfg, seed=6)
    ids = [int(t) for t in np.random.default_rng(6).integers(0, 500, 1500)]
    return cfg, path, ids


def logits_of(cfg, path, ids):
    return np.asarray(compare.reference_for(cfg).last_logits(path, cfg, [ids], [64])[0])


@pytest.fixture(scope="module")
def sound(long_sequence):
    return logits_of(*long_sequence)


@pytest.mark.parametrize("name", afmoe.FAULTS)
def test_a_fault_changes_the_references_logits(name, long_sequence, sound):
    cfg, path, ids = long_sequence
    fault = afmoe.FAULTS[name]
    assert isinstance(fault, dict) and fault.min_prompt in (0, 4096 + 256)
    over = dict(fault)
    if name == "window ignored":  # the rehearsal's window, as its file has it
        assert len(ids) > cfg["sliding_window"] + 256
    wrong = logits_of({**cfg, **over}, path, ids)
    # a bias of std 0.01 beside scores near 0.9 moves a weight by a hundredth
    least = 0.02 if name == "bias in the weights" else 0.25
    change = np.abs(wrong - sound).max() / sound.std()
    print(f"{name}: logits move by up to {change:.3f} std")
    assert change > least, name


def test_the_sound_reference_repeats_and_the_faults_are_ten(long_sequence, sound):
    assert np.array_equal(logits_of(*long_sequence), sound)
    # the issue's ten, and the precision control that bounds `gap_tol` from above
    assert len(afmoe.FAULTS) == 11 and "activations in float8" in afmoe.FAULTS
