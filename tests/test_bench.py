"""bench.py emission-path guards.

A run off the TPU must never produce a record that pattern-matches a real
perf datapoint: the record names the device it ran on, and only on `tpu`
is it `comparable`, scored against the north star, or named per chip.
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import NORTH_STAR_TOK_S_PER_CHIP, headline_record

TPU = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1}
CPU = {"platform": "cpu", "device_kind": "cpu", "device_count": 8}


@pytest.mark.parametrize("device", [TPU, CPU], ids=["tpu", "cpu"])
def test_comparable_only_on_tpu(device):
    rec = headline_record(
        "tiny", "q40", "bf16", per_chip=2374.3, weight_gbs=0.3, device=device
    )
    on_tpu = device["platform"] == "tpu"
    assert rec["comparable"] is on_tpu
    assert (rec["vs_baseline"] is not None) is on_tpu
    assert ("per_chip" in rec["metric"]) is on_tpu
    assert ("/chip" in rec["unit"]) is on_tpu
    assert rec["value"] == 2374.3  # the raw number stays, honestly labeled
    for k, v in device.items():  # the record names where it ran
        assert rec[k] == v


def test_real_record_carries_ratio():
    rec = headline_record(
        "llama-8b", "q40i8", "int8", per_chip=55.0, weight_gbs=600.0,
        device=TPU,
    )
    assert rec["metric"] == "decode_tok_s_per_chip_llama_8b_q40i8_kv8"
    assert rec["comparable"] is True
    assert rec["vs_baseline"] == round(55.0 / NORTH_STAR_TOK_S_PER_CHIP, 3)


def test_bench_summaries_section_split():
    from bench import bench_summaries

    result = {
        "metric": "decode_tok_s_per_chip_tiny_q40",
        "value": 12.3, "unit": "tokens/s/chip", "vs_baseline": 0.25,
        "comparable": True, "weight_gbs_per_chip": 100.0,
        "step_ms": {"block_tokens": 64, "n_blocks": 5, "p50": 10.0,
                    "p90": 12.0, "max": 13.0, "per_token_p50": 0.156},
        "ttft_ms_p50": 42.5,
        "lanes4_tok_s_per_chip": 30.0,
        "format_sweep_tok_s_per_chip": {"q40": 12.3, "q40i8": 14.0},
        "serving": {"n_clients": 3, "ttft_ms_p50": 50.0,
                    "obs_overhead_pct": 0.4},
    }
    out = bench_summaries(result)
    assert set(out) == {"DECODE", "TTFT", "LANES", "SWEEP", "SERVING"}
    assert out["DECODE"]["value"] == 12.3
    assert out["DECODE"]["step_ms"]["p90"] == 12.0
    assert out["TTFT"]["ttft_ms_p50"] == 42.5
    assert out["LANES"]["lanes4_tok_s_per_chip"] == 30.0
    assert out["SWEEP"]["tok_s_per_chip"]["q40i8"] == 14.0
    assert out["SERVING"]["obs_overhead_pct"] == 0.4


def test_bench_summaries_only_sections_that_ran():
    from bench import bench_summaries

    out = bench_summaries({
        "metric": "decode_tok_s_cpu_tiny_q40",
        "value": 1.0, "unit": "tokens/s", "vs_baseline": None,
        "comparable": False,
    })
    assert set(out) == {"DECODE"}  # skipped sections leave no stale files
    assert bench_summaries({}) == {}


def test_write_bench_summaries_files(tmp_path):
    import json

    from bench import write_bench_summaries

    result = {"metric": "m", "value": 1.0, "unit": "tokens/s/chip",
              "vs_baseline": None, "comparable": False,
              "ttft_ms_p50": 9.0}
    paths = write_bench_summaries(result, out_dir=str(tmp_path))
    assert sorted(p.split("/")[-1] for p in paths) == [
        "BENCH_DECODE.json", "BENCH_TTFT.json",
    ]
    decode = json.loads((tmp_path / "BENCH_DECODE.json").read_text())
    assert decode["metric"] == "m" and decode["comparable"] is False
    # unwritable destination degrades to a logged skip, never a crash
    assert write_bench_summaries(result, out_dir=str(tmp_path / "no" / "x")) == []
