"""The `pangu_ultra_moe` family as the harness takes it from its files: header
keys 25-37 and tensors of `configs/openpangu-ultra-l5-e32.json` through the
program's format code, the file's bytes by the tensor plan at the published
widths, costs against one decode step and one chunk counted by hand, and
every fault of the reference's `FAULTS` against the sound reference.
(`test_architecture.py` is not PR 34's to edit: its cases for this
configuration live here, as `test_afmoe.py` holds Trinity's.)"""

import hashlib

import numpy as np
import pytest

import run as bench
from benchmark.harness import compare, costs, weights
from benchmark.references.q40file import Q40File
from dllama_tpu.formats.model_file import ModelReader, read_llm_header, tensor_plan

PANGU = "openpangu-ultra-l5-e32"


def sha256(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def logits_of(cfg, path, ids):
    return np.asarray(compare.reference_for(cfg).last_logits(path, cfg, [ids], [64])[0])


def test_pangu_header_integers_and_the_cut_as_the_file_states_them():
    cfg = bench.load_config(PANGU, rehearse=False)
    wire = weights.header_for(cfg)
    assert (wire["n_layers"], wire["n_experts"], wire["vocab_size"], wire["n_heads"]) == (
        5, 32, 19200, 128)
    assert (wire["dim"], wire["hidden_dim"], wire["moe_hidden_dim"], wire["head_dim"]) == (
        7680, 18432, 2048, 192)
    assert [wire[k] for k in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                              "qk_rope_head_dim", "v_head_dim")] == [1536, 512, 128, 64, 128]
    assert (wire["n_routed_experts"], wire["n_dense_layers"], wire["n_shared_experts"],
            wire["score_func"], wire["route_norm"], wire["route_scale_milli"]) == (
        256, 1, 1, 1, 1, 2500)
    assert cfg["published"] == {"num_hidden_layers": 61, "first_k_dense_replace": 3,
                                "n_routed_experts": 256, "vocab_size": 153600}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] == cfg["n_routed_experts"] == 32 and cfg["num_routed_experts"] == 256
    assert "eight" in cfg["deployment"] and set(cfg["assumed"]) >= {
        "score_function", "selection_bias", "group_limit", "latent_norms", "sandwich_norm",
        "rope_pairing", "nextn", "weights"}
    assert cfg["serving"]["lanes"] == 4 and cfg["serving"]["max_seq_len"] == 8192


def test_pangu_file_bytes_and_tensor_plan_at_the_published_widths(tmp_path):
    """The published widths' plan without writing 5 GB: the header alone is
    written, the program's reader parses it and plans the tensors."""
    from dllama_tpu.formats.writer import write_header

    cfg = bench.load_config(PANGU, rehearse=False)
    path = str(tmp_path / "header.m")
    with open(path, "wb") as f:
        write_header(f, weights.header_for(cfg))
    h = read_llm_header(path)
    plan = {s.name: s for s in tensor_plan(h)}
    assert (h.latent, h.head_dim, h.latent_row, h.rope_dim) == (True, 192, 576, 64)
    assert {n: plan[f"layers.4.{n}"].shape for n in
            ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")} == {
        "wq_a": (1536, 7680), "wq_b": (24576, 1536), "wkv_a": (576, 7680),
        "wkv_b": (32768, 512), "wo": (7680, 16384)}
    q40 = sum(s.n_elements for s in plan.values() if s.float_type.name == "Q40")
    assert q40 == 7_783_546_880  # 7.78 B weights at 18 bytes per 32
    last = list(plan.values())[-1]
    assert last.name == "wcls" and last.offset + last.nbytes == 5_000_212_720  # 5.00 GB
    # the rehearsal's file is the same bytes for the same seed, and loads
    tiny = bench.load_config(PANGU, rehearse=True)
    a, b = str(tmp_path / "a.m"), str(tmp_path / "b.m")
    weights.write_model(a, tiny, 7)
    weights.write_model(b, tiny, 7)
    assert sha256(a) == sha256(b)
    r = ModelReader(a)
    assert (r.header.q_lora_rank, r.header.kv_lora_rank, r.header.latent_row) == (96, 32, 40)
    gain = np.asarray(Q40File(a).f32("layers.1.q_a_norm"))
    assert 2.0 <= gain.min() and gain.max() <= 3.0  # `file.tensors`: scores of std 2.5
    post = np.asarray(Q40File(a).f32("layers.1.post_ffn_norm"))
    assert 0.32 <= post.min() and post.max() <= 0.48


def test_pangu_costs_of_one_decode_step_and_one_chunk_counted_by_hand():
    from benchmark.costs import pangu_ultra_moe as family

    cfg = bench.load_config(PANGU, rehearse=False)
    d, heads, q40 = 7680, 128, 18 / 32
    projections = d * 1536 + 1536 * heads * 192 + d * 576 + heads * 128 * d
    wkv_b = heads * 256 * 512
    assert family.projection_weights(cfg) == projections == 179_798_016
    assert family.wkv_b_weights(cfg) == wkv_b == 16_777_216
    expert, dense, head, router = 3 * d * 2048, 3 * d * 18432, d * 19200, 4 * d * 256
    touched = 32 * (1 - (1 - 8 / 256) ** 4)
    assert family.held_experts_touched(cfg, 4) == pytest.approx(touched) and 3.8 < touched < 3.9
    # 4 live lanes at 9000 positions: one 1152-byte row a position and layer
    rows = 4 * 9000 * 5 * 1152
    q40_read = 5 * projections + dense + 4 * (expert + touched * expert) + head
    want = q40_read * q40 + 5 * wkv_b * 2 + 4 * router + rows
    assert costs.decode_step_bytes(cfg, 4, 9000.0) == pytest.approx(want)
    assert 1.7e9 < want < 1.8e9 and rows == 207_360_000
    # a token multiplies by its share of the routed experts: 8 x 32 / 256 = one expert
    per_token = 5 * (projections + wkv_b) + dense + 4 * (expert + d * 256 + expert) + head
    assert costs.weights_per_token(cfg) == int(per_token)
    assert costs.prefill_flops(cfg, 4 * 512) == pytest.approx(2.0 * (per_token - head) * 2048)
    # the latent attention itself: a decode step reads a row once and spends
    # 128 x (576 + 512) multiply-adds on it: 241.8 FLOP a byte, the v5e's ridge
    nbytes, flops = family.latent_decode_cost(cfg, 1000)
    assert (nbytes, flops) == (1_152_000, 2.0 * 1000 * 128 * 1088)
    assert flops / nbytes == pytest.approx(241.8, abs=0.1)
    # a chunk in the cheaper form: a prompt's first chunk absorbed, a deep one expanded
    first = 512 * 513 / 2
    assert family.latent_prefill_flops(cfg, 512, 512) == pytest.approx(
        2.0 * min(first * 128 * 1088, first * 128 * 320 + 512 * wkv_b))
    deep = 512 * (8192 - 512) + first
    assert family.latent_prefill_flops(cfg, 512, 8192) == pytest.approx(
        2.0 * (deep * 128 * 320 + 8192 * wkv_b))
    assert deep * 128 * 1088 > deep * 128 * 320 + 8192 * wkv_b


@pytest.fixture(scope="module")
def pangu_sequence(tmp_path_factory):
    """(cfg, model path, ids, the sound logits) at the configuration's rehearsal widths."""
    cfg = bench.load_config(PANGU, rehearse=True)
    path = str(tmp_path_factory.mktemp("pangu-faults") / "model.m")
    weights.write_model(path, cfg, seed=6)
    ids = [int(t) for t in np.random.default_rng(6).integers(0, 500, 700)]
    return cfg, path, ids, logits_of(cfg, path, ids)


def pangu_faults():
    from benchmark.references import pangu_ultra_moe

    return pangu_ultra_moe.FAULTS


@pytest.mark.parametrize("name", [
    "scale 1/sqrt(nope)", "no rope on k_rope", "rope on the nope columns", "no kv_a_norm",
    "no q_a_norm", "values from the key half of wkv_b", "routed_scaling_factor=1",
    "no shared expert", "absent experts computed", "no post-norms", "activations in float8"])
def test_a_pangu_fault_changes_the_references_logits(name, pangu_sequence):
    cfg, path, ids, sound = pangu_sequence
    fault = pangu_faults()[name]
    assert isinstance(fault, dict) and fault.min_prompt == 0
    wrong = logits_of({**cfg, **fault}, path, ids)
    change = np.abs(wrong - sound).max() / sound.std()
    print(f"{name}: logits move by up to {change:.3f} std")
    assert change > 0.25, name


def test_the_sound_pangu_reference_repeats_and_the_faults_are_ten(pangu_sequence):
    cfg, path, ids, sound = pangu_sequence
    assert np.array_equal(logits_of(cfg, path, ids), sound)
    # the issue's ten, and the precision control that bounds `gap_tol` from above
    assert len(pangu_faults()) == 11 and "activations in float8" in pangu_faults()
