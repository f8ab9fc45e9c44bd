"""The `deepseek_v32` family as the harness takes it from its files: header
keys 25-46 and tensors of `configs/deepseek-v3.2-l5-e32.json` through the
program's format code, the file's bytes by the tensor plan at the published
widths, costs against one decode step and one chunk counted by hand, every
fault of the reference's `FAULTS` against the sound reference, and the three
readers this family brings on a hand-made run directory.
(`test_architecture.py` is not PR 39's to edit: its cases for this
configuration live here, as `test_pangu_ultra_moe.py` holds openPangu's.)"""

import hashlib
import json
import os

import numpy as np
import pytest

import run as bench
from benchmark.harness import compare, costs, weights
from benchmark.references.q40file import Q40File
from dllama_tpu.formats.model_file import ModelReader, read_llm_header, tensor_plan

DSV32 = "deepseek-v3.2-l5-e32"


def sha256(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def logits_of(cfg, path, ids):
    return np.asarray(compare.reference_for(cfg).last_logits(path, cfg, [ids], [64])[0])


def test_dsv32_header_integers_and_the_cut_as_the_file_states_them():
    cfg = bench.load_config(DSV32, rehearse=False)
    wire = weights.header_for(cfg)
    assert (wire["n_layers"], wire["n_experts"], wire["vocab_size"], wire["n_heads"]) == (
        5, 32, 16160, 128)
    assert (wire["dim"], wire["hidden_dim"], wire["moe_hidden_dim"], wire["head_dim"]) == (
        7168, 18432, 2048, 192)
    assert [wire[k] for k in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                              "qk_rope_head_dim", "v_head_dim")] == [1536, 512, 128, 64, 128]
    assert [wire[k] for k in ("index_n_heads", "index_head_dim", "index_topk", "n_group",
                              "topk_group")] == [64, 128, 2048, 8, 4]
    assert [wire[k] for k in ("rope_type", "rope_scaling_factor",
                              "rope_scaling_orig_max_seq_len", "rope_beta_fast",
                              "rope_beta_slow", "rope_mscale_milli",
                              "rope_mscale_all_dim_milli")] == [3, 40, 4096, 32, 1, 1000, 1000]
    assert (wire["n_routed_experts"], wire["n_dense_layers"], wire["n_shared_experts"],
            wire["score_func"], wire["route_norm"], wire["route_scale_milli"],
            wire["norm_epsilon"], wire["rope_theta"]) == (256, 1, 1, 1, 1, 2500, 6, 10000)
    assert cfg["published"] == {"num_hidden_layers": 61, "first_k_dense_replace": 3,
                                "n_routed_experts": 256, "vocab_size": 129280}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] == cfg["n_routed_experts"] == 32 and cfg["num_routed_experts"] == 256
    # the share is one whole group of the router's eight
    assert cfg["num_routed_experts"] // cfg["n_group"] == cfg["n_routed_experts"]
    assert "eight" in cfg["deployment"] and "group 0" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {
        "index_precision", "nextn", "index", "group_limit", "latent_norms", "rope",
        "softmax_scale", "weights"}
    assert sum(v.startswith("DEPARTURE") for v in cfg["assumed"].values() if isinstance(v, str)) == 3
    assert cfg["serving"]["lanes"] == 4 and cfg["serving"]["max_seq_len"] == 8192
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
                              "vocab_size"]
    assert cfg["gap_tol"] and "ladder" in cfg["gap_tol_why"]


def test_dsv32_file_bytes_and_tensor_plan_at_the_published_widths(tmp_path):
    """The published widths' plan without writing 4 GB: the header alone is
    written, the program's reader parses it and plans the tensors."""
    from dllama_tpu.formats.writer import write_header

    cfg = bench.load_config(DSV32, rehearse=False)
    path = str(tmp_path / "header.m")
    with open(path, "wb") as f:
        write_header(f, weights.header_for(cfg))
    h = read_llm_header(path)
    plan = {s.name: s for s in tensor_plan(h)}
    assert (h.latent, h.indexed, h.head_dim, h.latent_row, h.rope_dim) == (True, True, 192, 576, 64)
    assert abs(h.softmax_scale - 0.1352) < 1e-4
    assert {n: plan[f"layers.4.{n}"].shape for n in
            ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "idx_wq_b", "idx_wk", "idx_w")} == {
        "wq_a": (1536, 7168), "wq_b": (24576, 1536), "wkv_a": (576, 7168),
        "wkv_b": (32768, 512), "wo": (7168, 16384), "idx_wq_b": (8192, 1536),
        "idx_wk": (128, 7168), "idx_w": (64, 7168)}
    # the issue's reckoning: attention 187.11 M, the index's two Q40 matrices 13.50 M
    layer = sum(plan[f"layers.4.{n}"].n_elements for n in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo"))
    assert layer == 187_105_280
    assert plan["layers.4.idx_wq_b"].n_elements + plan["layers.4.idx_wk"].n_elements == 13_500_416
    q40 = sum(s.n_elements for s in plan.values() if s.float_type.name == "Q40")
    assert 7.3e9 < q40 < 7.5e9  # the issue's 7.45 B less what the file holds as f32
    last = list(plan.values())[-1]
    # 4.19 GB of Q40 and 0.43 GB of f32 (the embedding's 16160 rows, routers, index weights)
    assert last.name == "wcls" and last.offset + last.nbytes == 4_624_538_960
    # the rehearsal's file is the same bytes for the same seed, and loads
    tiny = bench.load_config(DSV32, rehearse=True)
    a, b = str(tmp_path / "a.m"), str(tmp_path / "b.m")
    weights.write_model(a, tiny, 7)
    weights.write_model(b, tiny, 7)
    assert sha256(a) == sha256(b)
    r = ModelReader(a)
    assert (r.header.q_lora_rank, r.header.kv_lora_rank, r.header.latent_row) == (96, 32, 40)
    assert (r.header.index_n_heads, r.header.index_head_dim, r.header.index_topk) == (4, 16, 256)
    assert (r.header.n_group, r.header.topk_group, r.header.rope_scaling_orig_max_seq_len) == (
        4, 2, 512)
    gain = np.asarray(Q40File(a).f32("layers.1.q_a_norm"))
    assert 1.0 <= gain.min() and gain.max() <= 1.5  # `file.tensors`: scores of std 1.25
    # every FFN's output matrix at gain 0.25 (the dense layer's, the shared expert's, an
    # expert's), the input matrices at the rule's gain 1: std 0.25 / sqrt(in) against 1 / sqrt(in)
    f = Q40File(a)
    for name, width in (("layers.0.w2", 160), ("layers.1.shared.w2", 128),
                        ("layers.1.experts.3.w2", 128)):
        assert np.asarray(f.f32(name)).std() == pytest.approx(0.25 / np.sqrt(width), rel=0.1)
    assert np.asarray(f.f32("layers.1.experts.3.w1")).std() == pytest.approx(1 / 8, rel=0.1)
    bias = np.asarray(Q40File(a).f32("layers.1.expert_bias"))
    assert 0.002 < bias.std() < 0.03


def test_dsv32_costs_of_one_decode_step_and_one_chunk_counted_by_hand():
    from benchmark.costs import deepseek_v32 as family

    cfg = bench.load_config(DSV32, rehearse=False)
    d, heads, q40 = 7168, 128, 18 / 32
    projections = d * 1536 + 1536 * heads * 192 + d * 576 + heads * 128 * d
    wkv_b = heads * 256 * 512
    assert family.projection_weights(cfg) == projections == 170_328_064
    assert family.wkv_b_weights(cfg) == wkv_b == 16_777_216
    index_q40, index_f32 = 1536 * 64 * 128 + d * 128, d * 64
    assert family.index_q40_weights(cfg) == index_q40 == 13_500_416
    assert family.index_f32_weights(cfg) == index_f32 == 458_752
    expert, dense, head = 3 * d * 2048, 3 * d * 18432, d * 16160
    router = 4 * (d + 1) * 256
    assert family.router_bytes(cfg) == router
    touched = 32 * (1 - (1 - 8 / 256) ** 4)
    assert family.held_experts_touched(cfg, 4) == pytest.approx(touched)
    # half the tokens keep group 0, and of those 90% have a choice in it
    landed = family.tokens_landed_share(cfg)
    miss = np.prod([(96 - i) / (128 - i) for i in range(8)])
    assert landed == pytest.approx(0.5 * (1 - miss)) and 0.44 < landed < 0.46
    # 4 live lanes at 6000 positions: 2048 selected rows of 1152 B and 6000 keys of 256 B
    rows = 4 * 5 * (2048 * 1152 + 6000 * 256)
    q40_read = 5 * (projections + index_q40) + dense + 4 * (expert + touched * expert) + head
    want = q40_read * q40 + 5 * wkv_b * 2 + 5 * index_f32 * 4 + 4 * router + rows
    assert costs.decode_step_bytes(cfg, 4, 6000.0) == pytest.approx(want)
    assert rows == 77_905_920 and 1.5e9 < want < 1.7e9
    # below index_topk every row in context is read
    assert costs.decode_step_bytes(cfg, 1, 1000.0) - costs.decode_step_bytes(
        cfg, 1, 999.0) == pytest.approx(5 * (1152 + 256))
    per_token = (5 * (projections + wkv_b + index_q40 + index_f32) + dense
                 + 4 * (expert + d * 256 + expert) + head)
    assert costs.weights_per_token(cfg) == int(per_token)
    assert costs.prefill_flops(cfg, 4 * 512) == pytest.approx(2.0 * (per_token - head) * 2048)
    # the index: 64 heads x 128 multiply-adds a pair, a 256-byte key a pair in decode
    assert family.index_score_cost(cfg, 1000) == (256_000, 2.0 * 1000 * 64 * 128)
    assert family.index_score_cost(cfg, 1)[1] == 16384  # 8192 MAC a pair: 6% of a latent pair
    assert family.sparse_decode_cost(cfg, 1000) == (1_152_000, 2.0 * 1000 * 128 * 1088)
    # a deep chunk: 512 queries of 2048 selected rows each, 8192 cached rows to rebuild
    pairs = 512 * 2048
    assert family.sparse_prefill_flops(cfg, pairs, 8192) == pytest.approx(
        2.0 * min(pairs * 128 * 1088, pairs * 128 * 320 + 8192 * wkv_b))


@pytest.fixture(scope="module")
def dsv32_sequence(tmp_path_factory):
    """(cfg, model path, ids, the sound logits) at the configuration's rehearsal widths."""
    cfg = bench.load_config(DSV32, rehearse=True)
    path = str(tmp_path_factory.mktemp("dsv32-faults") / "model.m")
    weights.write_model(path, cfg, seed=6)
    ids = [int(t) for t in np.random.default_rng(6).integers(0, 500, 700)]
    return cfg, path, ids, logits_of(cfg, path, ids)


def dsv32_faults():
    from benchmark.references import deepseek_v32

    return deepseek_v32.FAULTS


@pytest.mark.parametrize("name", [
    "selection ignored (dense attention)", "index_topk 1024", "no rope on the index",
    "no LayerNorm on the index key", "no ReLU in the index",
    "index queries from the un-normalised latent", "group limit ignored",
    "bias left out of the selection", "m^2 left out of the softmax scale",
    "rotary table unscaled", "no shared expert", "absent experts computed",
    "routed_scaling_factor=1", "activations in float8"])
def test_a_dsv32_fault_changes_the_references_logits(name, dsv32_sequence):
    cfg, path, ids, sound = dsv32_sequence
    fault = dict(dsv32_faults()[name])
    if "index_topk" in fault:  # half of what the rehearsal keeps
        fault["index_topk"] = cfg["index_topk"] // 2
    wrong = logits_of({**cfg, **fault}, path, ids)
    change = np.abs(wrong - sound).max() / sound.std()
    print(f"{name}: logits move by up to {change:.3f} std")
    assert change > 0.1, name


def test_the_sound_dsv32_reference_repeats_and_the_faults_are_thirteen(dsv32_sequence):
    cfg, path, ids, sound = dsv32_sequence
    assert np.array_equal(logits_of(cfg, path, ids), sound)
    # the issue's thirteen, and the precision control that bounds `gap_tol` from above
    assert len(dsv32_faults()) == 14 and "activations in float8" in dsv32_faults()
    # faults of the selection cannot show on a prompt that index_topk covers
    assert dsv32_faults()["selection ignored (dense attention)"].min_prompt > 2048
    assert dsv32_faults()["group limit ignored"].min_prompt == 0


# -- the three readers on a hand-made run directory ------------------------------


def run_dir_with(tmp_path, scope_seconds: dict, events: list, modules: dict) -> str:
    cfg = bench.load_config(DSV32, rehearse=False)
    d = str(tmp_path)
    window = {"t0": 0.0, "t1": 51.0, "trace_t0": 10.0, "trace_t1": 15.0, "lanes": 4,
              "chips": 1, "block_size": 8, "device_kind": "TPU v5 lite", "config": cfg,
              "mean_context": 6000.0, "seconds": 51.0}
    for name, obj in (("window.json", window), ("recorder.json", {"events": events}),
                      ("trace_digest.json", {"modules": modules}),
                      ("device_by_scope_path.json", scope_seconds)):
        with open(os.path.join(d, name), "w") as f:
            json.dump(obj, f)
    return d


def dispatch(t, step, **fields):
    return {"kind": "step_dispatch", "t": t, "step": step, **fields}


def test_the_three_readers_give_hand_computed_shares(tmp_path):
    peaks = costs.peaks("TPU v5 lite")
    flops_s, hbm_s = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    chunk = dispatch(11.0, "prefill_lane_chunk", pos=4096, n_tokens=512, bucket=512,
                     rows_latent=512 * 4096 + 512 * 513 // 2, rows_selected=512 * 2048)
    block = dispatch(12.0, "decode_lanes", n_steps=8, n_live=4,
                     rows_latent=8 * 4 * 6000, rows_selected=8 * 4 * 2048)
    outside = dispatch(30.0, "decode_lanes", n_steps=8, n_live=4, rows_latent=1, rows_selected=1)
    d = run_dir_with(
        tmp_path,
        {"attn/index_score": 0.010, "attn/index_select": 0.006, "attn/latent_decode": 0.004,
         "attn/latent_prefill": 0.200, "attn/latent_proj": 1.0},
        [chunk, block, outside],
        {"jit_block": {"seconds": 0.08, "calls": 2}, "jit_step": {"seconds": 0.4, "calls": 4}})
    score = bench.layer_reader("index_score_roofline").read(d)
    chunk_s = 5 * chunk["rows_latent"] * 64 * 128 * 2 / flops_s
    block_s = max(5 * block["rows_latent"] * 256 / hbm_s,
                  5 * block["rows_latent"] * 64 * 128 * 2 / flops_s)
    assert score == pytest.approx(100 * (chunk_s + block_s) / 0.016)
    decode = bench.layer_reader("sparse_attn_decode_roofline").read(d)
    rows = 5 * block["rows_selected"]
    floor = max(rows * 1152 / hbm_s, rows * 2 * 128 * 1088 / flops_s)
    assert decode == pytest.approx(100 * floor / (0.004 / 2 * 1))  # one of the slice's two calls
    prefill = bench.layer_reader("sparse_attn_prefill_roofline").read(d)
    pairs = 512 * 2048
    need = 5 * 2.0 * min(pairs * 128 * 1088, pairs * 128 * 320 + 4608 * 128 * 256 * 512)
    assert prefill == pytest.approx(100 * need / flops_s / (0.200 / 4 * 1))
    assert 0 < score < 100 and 0 < decode < 100 and 0 < prefill < 100


def test_the_three_readers_find_nothing_in_a_run_without_an_index(tmp_path):
    """A program without the index (the parent, or another family's cell)
    carries no `rows_selected` and no index scopes: each reader returns None."""
    chunk = dispatch(11.0, "prefill_lane_chunk", pos=0, n_tokens=512, bucket=512,
                     rows_latent=512 * 513 // 2)
    block = dispatch(12.0, "decode_lanes", n_steps=8, n_live=4, rows_latent=8 * 4 * 6000)
    d = run_dir_with(
        tmp_path, {"attn/latent_decode": 0.004, "attn/latent_prefill": 0.020},
        [chunk, block],
        {"jit_block": {"seconds": 0.08, "calls": 2}, "jit_step": {"seconds": 0.4, "calls": 4}})
    for name in ("index_score_roofline", "sparse_attn_decode_roofline",
                 "sparse_attn_prefill_roofline"):
        assert bench.layer_reader(name).read(d) is None
        assert bench.layer_reader(name).read(os.path.join(d, "no-such-run")) is None
