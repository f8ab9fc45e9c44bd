"""`xplane.digest` and `costs` reduce a small hand-recorded trace and
hand-computed shapes to known numbers."""

import pytest

import run as bench
from benchmark.harness import costs, xplane

MS = 1e6  # nanoseconds


def test_digest_of_a_small_trace():
    ops = [("%fusion.1 = bf16[8,128]{1,0} fusion(%p0)", 0, 2 * MS),
           ("fusion.2", 1 * MS, 2 * MS),  # overlaps the first: 3 ms busy
           ("%while.7 = (s32[]) while(%t)", 5 * MS, 1 * MS),  # a container and
           ("copy.3", 5 * MS, 1 * MS),  # what ran inside it
           ("fusion.1", 8 * MS, 2 * MS)]
    mods = [("jit_block(123)", 0, 3 * MS), ("jit_step(9)", 5 * MS, 1 * MS),
            ("jit_block(123)", 8 * MS, 2 * MS)]
    lines = [("/device:TPU:0", xplane.OPS, ops), ("/device:TPU:0", xplane.MODULES, mods)]
    d = xplane.digest(lines, window_s=0.010)
    assert d["devices"] == 1
    assert d["busy_s"] == pytest.approx(0.006)
    assert d["modules"]["jit_block"] == {"seconds": pytest.approx(0.005), "calls": 2}
    assert d["modules"]["jit_step"] == {"seconds": pytest.approx(0.001), "calls": 1}
    assert d["device_ops"][0] == ["fusion.1", pytest.approx(0.004)]
    assert "while.7" not in dict(map(tuple, d["device_ops"]))
    assert dict(map(tuple, d["idle_gaps"])) == {
        "before jit_step": pytest.approx(0.002), "before jit_block": pytest.approx(0.002)}


def test_digest_averages_over_chips():
    one = [("fusion", 0, 4 * MS)]
    lines = [(f"/device:TPU:{i}", xplane.OPS, one if i else one + [("x", 6 * MS, 2 * MS)])
             for i in range(4)]
    d = xplane.digest(lines, window_s=0.010)
    assert d["devices"] == 4 and d["busy_s"] == pytest.approx((0.006 + 3 * 0.004) / 4)


def test_a_trace_without_device_planes_is_an_error():
    with pytest.raises(ValueError):
        xplane.digest([], 1.0)


def test_mistral_costs_by_hand():
    cfg = bench.load_json("configs", "mistral-7b-v0.3.json")
    att = 4096 * (4096 + 2 * 1024) + 4096 * 4096
    ffn = 3 * 4096 * 14336
    assert costs.attention_weights(cfg) == att
    assert costs.expert_weights(cfg) == ffn
    assert costs.weights_per_token(cfg) == 32 * (att + ffn) + 4096 * 32768
    kv = 8 * 1000 * 2 * 1024 * 2  # lanes x positions x (k, v) x kv_dim x 2 bytes
    want = 32 * ((att + ffn) * 18 / 32 + kv) + 4096 * 32768 * 18 / 32
    assert costs.decode_step_bytes(cfg, 8, 1000) == pytest.approx(want)
    assert costs.prefill_flops(cfg, 4096) == 2.0 * 32 * (att + ffn) * 4096


def test_sparse_costs_count_only_routed_experts():
    cfg = bench.load_json("configs", "qwen3-30b-a3b-l12.json")
    assert costs.distinct_experts(128, 8, 1) == pytest.approx(8)
    assert costs.distinct_experts(128, 8, 16) == pytest.approx(128 * (1 - (15 / 16) ** 16))
    one = costs.decode_step_bytes(cfg, 1, 0)
    att, expert = costs.attention_weights(cfg), 3 * 2048 * 768
    want = 12 * ((att + 8 * expert) * 18 / 32 + 4 * 2048 * 128) + 2048 * 151936 * 18 / 32
    assert one == pytest.approx(want)
    assert costs.weights_per_token(cfg) == 12 * (att + 8 * expert + 2048 * 128) + 2048 * 151936


def test_unknown_device_is_an_error():
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("cpu")
