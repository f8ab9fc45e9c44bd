"""An architecture reaches the harness as a configuration's own files: header
keys and tensor distributions from `configs/<name>.json`, costs from
`costs/<family>.py`, faults from `references/<family>.py`, rehearsal widths
from the configuration's `rehearse`. And for the two configurations the
benchmark has, nothing that a run reads has moved."""

import hashlib
import json
import os
import sys
import time
import types

import numpy as np
import pytest

import run as bench
from benchmark.harness import compare, costs, weights
from benchmark.references import dense_gqa, moe_topk
from benchmark.references.q40file import Q40File
from dllama_tpu.formats.model_file import ModelReader, RopeType, read_llm_header, tensor_plan

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# sha256 of `write_model`'s file at the rehearsal widths, taken on the parent
# commit (c77eeb7, PR 25) by its own `harness/weights.py` before any edit
PARENT_SHA256 = {
    ("mistral-7b-v0.3", 1): "fcf0e2a47bae28925b3f0aec2d94fa01230f68613049fc85d17f35108f6f82c0",
    ("mistral-7b-v0.3", 2): "9714a5eba4ab795573fc03fd8c5acde7c1b74368d98c94d0d98cbc886f89b926",
    ("qwen3-30b-a3b-l12", 1): "efbd293ec6bb552d175390acae6763f5322b7a7492f153fc77feaa65d69904bb",
    ("qwen3-30b-a3b-l12", 2): "fd2c00ede2a618bb2ab0f9428b76dc664a78bfcb7e2f558429f5a4c3dab8e488",
}
# what the parent's `harness/costs.py` returned at the published widths:
# decode_step_bytes(5 lanes, 1234.5 positions), (16, 300), prefill_flops(2560 rows), weights_per_token
PARENT_COSTS = {
    "mistral-7b-v0.3": (4810407936.0, 4630511616.0, 35734127902720.0, 7113539584),
    "qwen3-30b-a3b-l12": (1591124016.0, 3058177834.5397367, 3495029637120.0, 993787904),
}


def small(**file_keys) -> dict:
    """The test's own configuration (named by no cell), with `file` keys laid over."""
    with open(os.path.join(DATA, "llama31-rope-small.json")) as f:
        cfg = json.load(f)
    cfg["file"] = {**cfg["file"], **file_keys}
    return cfg


def sha256(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


# (a) the two present configurations: the same bytes, the same costs

@pytest.mark.parametrize("config,seed", PARENT_SHA256)
def test_a_present_configuration_gets_the_parents_bytes(config, seed, tmp_path):
    path = str(tmp_path / "model.m")
    weights.write_model(path, bench.load_config(config, rehearse=True), seed)
    assert sha256(path) == PARENT_SHA256[config, seed]


@pytest.mark.parametrize("config", PARENT_COSTS)
def test_a_present_configuration_gets_the_parents_costs(config):
    cfg = bench.load_config(config, rehearse=False)
    assert (costs.decode_step_bytes(cfg, 5, 1234.5), costs.decode_step_bytes(cfg, 16, 300),
            costs.prefill_flops(cfg, 2560), costs.weights_per_token(cfg)) == PARENT_COSTS[config]


# (b) header keys pass through

def test_file_header_keys_reach_the_file_and_move_the_tensor_section(tmp_path):
    path, bare = str(tmp_path / "model.m"), str(tmp_path / "bare.m")
    weights.write_model(path, small(), seed=3)
    weights.write_model(bare, small(header={}), seed=3)
    h, h0 = read_llm_header(path), read_llm_header(bare)
    assert (h.rope_type, h.rope_scaling_factor, h.rope_scaling_orig_max_seq_len) == (
        RopeType.LLAMA3_1, 8.0, 8192)
    assert (h0.rope_type, h0.rope_scaling_orig_max_seq_len) == (RopeType.LLAMA, 0)
    assert h.header_bytes == h0.header_bytes + 3 * 8
    assert tensor_plan(h)[0].offset == h.header_bytes
    ModelReader(path)  # the file ends where the longer header's plan ends
    with open(path, "rb") as f, open(bare, "rb") as f0:
        f.seek(h.header_bytes), f0.seek(h0.header_bytes)
        assert f.read() == f0.read()  # the keys moved the tensors and nothing else


@pytest.mark.parametrize("file_keys,named", [
    ({"header": {"mamba_d_state": 128}}, "mamba_d_state"),
    ({"arch": "GRANITE_HYBRID"}, "GRANITE_HYBRID"),
    ({"header": {"rope_theta": 10000}}, "rope_theta"),  # written from the published size
    ({"header": {"rope_scaling_factor": 0.22}}, "rope_scaling_factor"),  # int32 pairs only
], ids=["unknown-key", "unknown-arch", "restated-key", "not-an-integer"])
def test_a_key_or_an_arch_the_format_lacks_raises_before_the_file_exists(
        file_keys, named, tmp_path):
    path = str(tmp_path / "model.m")
    with pytest.raises(ValueError, match=named):
        weights.write_model(path, small(**file_keys), seed=3)
    assert not os.path.exists(path)


def test_a_run_of_such_a_configuration_ends_at_once_and_writes_nothing(
        monkeypatch, capsys, tmp_path):
    cell = bench.load_json("workloads", "mistral7b-chat.json")
    monkeypatch.setattr(bench, "load_cell", lambda name, rehearse: (
        cell, small(arch="GRANITE_HYBRID"), {}))
    monkeypatch.setattr(bench, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", cell["name"], "--seed", "1",
                                      "--seconds", "1", "--rehearse"])
    t = time.monotonic()
    assert bench.main() == 2
    assert time.monotonic() - t < 10
    out = capsys.readouterr()
    assert out.out == "" and "GRANITE_HYBRID" in out.err
    assert not os.path.exists(bench.WORK)


# (c) tensor distributions

def tensor_bytes(path: str) -> dict:
    r = ModelReader(path)
    return {s.name: bytes(r.raw(s.name)) for s in r.specs}


def test_stated_tensors_are_drawn_as_stated_and_no_other_moves(tmp_path):
    stated = {
        "att_norm": {"dist": "uniform", "lo": 0.5, "hi": 0.7},
        "q": {"gain": 3.0},
        "embed": {"dist": "normal", "std": 0.25},
        "ffn_norm": {"dist": "uniform", "lo": 1.0, "hi": 16.0, "map": "log"},
        "final_norm": {"dist": "uniform", "lo": 0.001, "hi": 0.1, "map": "inv_softplus"},
    }
    path, plain = str(tmp_path / "model.m"), str(tmp_path / "plain.m")
    weights.write_model(path, small(tensors=stated), seed=4)
    weights.write_model(plain, small(), seed=4)
    got, was = tensor_bytes(path), tensor_bytes(plain)
    f = Q40File(path)
    values = lambda name: np.asarray(f.f32(name), dtype=np.float64).ravel()
    for name in got:
        if name.rsplit(".", 1)[-1] not in stated:
            assert got[name] == was[name], name
    for layer in range(2):
        norm = values(f"layers.{layer}.att_norm")
        assert 0.5 <= norm.min() and norm.max() <= 0.7 and norm.std() > 0.04
        decay = np.exp(values(f"layers.{layer}.ffn_norm"))  # as A = exp(A_log) would be
        assert 1.0 <= decay.min() and decay.max() <= 16.0 and decay.std() > 3.0
    q = np.concatenate([values(f"layers.{layer}.q") for layer in range(2)])
    assert q.std() == pytest.approx(3.0 / np.sqrt(256), rel=0.02) and abs(q.mean()) < 0.01
    k = values("layers.0.k")  # not stated: the rule's SCORE_GAIN
    assert k.std() == pytest.approx(weights.SCORE_GAIN / np.sqrt(256), rel=0.03)
    assert values("embed").std() == pytest.approx(0.25, rel=0.02)
    dt = np.log1p(np.exp(values("final_norm")))  # as dt = softplus(dt_bias) would be
    assert 0.001 * (1 - 1e-4) <= dt.min() and dt.max() <= 0.1 * (1 + 1e-4) and dt.std() > 0.02
    # the end-of-sequence rows of the head are still zero
    assert not np.asarray(f.f32("wcls"))[-weights.N_EOS:].any()


@pytest.mark.parametrize("tensors,named", [
    ({"A_log": {"dist": "uniform", "lo": 1, "hi": 16, "map": "log"}}, "A_log"),
    ({"q": {"dist": "normal", "std": 1.0}}, "'q'"),  # a Q40 tensor takes a gain
    ({"att_norm": {"gain": 1.0}}, "att_norm"),  # an f32 tensor takes a dist
    ({"att_norm": {"dist": "uniform", "lo": 1, "hi": 2, "map": "sqrt"}}, "sqrt"),
], ids=["leaf-not-in-plan", "dist-on-q40", "gain-on-f32", "unknown-map"])
def test_a_leaf_the_plan_lacks_or_a_wrong_statement_raises(tensors, named, tmp_path):
    path = str(tmp_path / "model.m")
    with pytest.raises(ValueError, match=named):
        weights.write_model(path, small(tensors=tensors), seed=4)
    assert not os.path.exists(path)


def test_a_plan_that_does_not_end_in_the_head_is_refused(monkeypatch, tmp_path):
    monkeypatch.setattr(weights, "tensor_plan", lambda h: tensor_plan(h)[:-1])
    with pytest.raises(ValueError, match="final_norm"):
        weights.write_model(str(tmp_path / "model.m"), small(), seed=4)


# (d) costs by family

@pytest.fixture
def roofline_run(tmp_path):
    """A traced run's directory with all that the two roofline readers read."""
    def make(cfg: dict) -> str:
        window = {"t0": 100.0, "t1": 151.0, "trace_t0": 110.0, "trace_t1": 115.0,
                  "lanes": 5, "chips": 1, "device_kind": "TPU v5 lite",
                  "mean_context": 400.0, "config": cfg}
        digest = {"busy_s": 4.0, "window_s": 5.0, "modules": {
            "jit_block": {"seconds": 2.0, "calls": 10}, "jit_step": {"seconds": 1.0, "calls": 4}}}
        events = [{"kind": "step_dispatch", "step": "decode_lanes", "t": 111.0,
                   "n_steps": 8, "n_live": 5},
                  {"kind": "step_dispatch", "step": "prefill_lane_chunk", "t": 112.0,
                   "bucket": 512}]
        for name, obj in (("window.json", window), ("trace_digest.json", digest),
                          ("recorder.json", {"events": events})):
            with open(tmp_path / name, "w") as f:
                json.dump(obj, f)
        return str(tmp_path)
    return make


ROOFLINES = ("decode_hbm_roofline", "prefill_mxu_roofline")


def test_the_roofline_readers_read_the_fixture(roofline_run):
    cfg = bench.load_config("mistral-7b-v0.3", rehearse=False)
    run = roofline_run(cfg)
    decode, prefill = (bench.layer_reader(m).read(run) for m in ROOFLINES)
    assert decode == pytest.approx(
        100 * 8 * costs.decode_step_bytes(cfg, 5, 400.0) / 819e9 / 0.2)
    assert prefill == pytest.approx(100 * costs.prefill_flops(cfg, 5 * 512) / 197e12 / 0.25)


def test_a_family_without_a_cost_file_has_no_roofline_share(roofline_run):
    cfg = dict(bench.load_config("mistral-7b-v0.3", rehearse=False), family="ssm_hybrid")
    assert costs.family_costs(cfg) is None
    run = roofline_run(cfg)
    assert [bench.layer_reader(m).read(run) for m in ROOFLINES] == [None, None]
    with pytest.raises(LookupError, match="ssm_hybrid"):
        costs.decode_step_bytes(cfg, 5, 400.0)


def test_a_familys_own_cost_module_is_the_one_called(roofline_run, monkeypatch):
    stub = types.ModuleType("benchmark.costs.ssm_hybrid")
    stub.decode_step_bytes = lambda cfg, live_lanes, context: 819e9 * 0.2 / 8 / 4
    stub.prefill_flops = lambda cfg, rows: 197e12 * 0.25 / 2
    stub.weights_per_token = lambda cfg: 7
    monkeypatch.setitem(sys.modules, "benchmark.costs.ssm_hybrid", stub)
    cfg = dict(bench.load_config("mistral-7b-v0.3", rehearse=False), family="ssm_hybrid")
    run = roofline_run(cfg)
    assert [bench.layer_reader(m).read(run) for m in ROOFLINES] == [
        pytest.approx(25.0), pytest.approx(50.0)]
    assert costs.weights_per_token(cfg) == 7


# (e) faults by family

@pytest.fixture(scope="module")
def long_sequence(tmp_path_factory):
    """(cfg, model path, 1100 token ids) of a tiny seeded file; no server."""
    cfg = bench.load_config("mistral-7b-v0.3", rehearse=True)
    path = str(tmp_path_factory.mktemp("faults") / "model.m")
    weights.write_model(path, cfg, seed=6)
    ids = [int(t) for t in np.random.default_rng(6).integers(0, 500, 1100)]
    return cfg, path, ids


def logits_of(cfg, path, ids):
    return np.asarray(compare.reference_for(cfg).last_logits(path, cfg, [ids], [64])[0])


@pytest.mark.parametrize("name", dense_gqa.FAULTS)
def test_a_fault_changes_the_references_logits_and_leaves_them_as_they_were(
        name, long_sequence):
    cfg, path, ids = long_sequence
    fault = dense_gqa.FAULTS[name]
    assert len(ids) > getattr(fault, "min_prompt", 0)
    sound = logits_of(cfg, path, ids)
    if isinstance(fault, dict):
        wrong = logits_of({**cfg, **fault}, path, ids)
    else:
        with fault:
            wrong = logits_of(cfg, path, ids)
    assert np.abs(wrong - sound).max() > 0.5 * sound.std()
    assert np.array_equal(logits_of(cfg, path, ids), sound)


def test_the_sparse_family_brings_the_dense_attentions_faults():
    assert moe_topk.FAULTS is dense_gqa.FAULTS and len(dense_gqa.FAULTS) == 2


# rehearsal widths by configuration

def test_a_rehearsal_takes_the_configurations_own_widths():
    cfg = small()
    tiny = bench.rehearsal_of(cfg)
    assert tiny["hidden_size"] == bench.TINY["hidden_size"] and tiny["gap_tol"] is None
    assert tiny["max_position_embeddings"] == 2048  # `rehearse` over TINY's 4096
    assert tiny["file"]["header"] == {"rope_type": 2}  # a key of `file` is replaced whole
    assert tiny["file"]["arch"] == "LLAMA"  # and the others stay
    assert cfg["file"]["header"]["rope_scaling_factor"] == 8  # the argument is not changed
    for name in ("mistral-7b-v0.3", "qwen3-30b-a3b-l12"):
        assert "rehearse" not in bench.load_json("configs", f"{name}.json")
