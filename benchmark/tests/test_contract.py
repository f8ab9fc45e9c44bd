"""`BENCHMARK.json` and the files it names agree, so that a cell, a
configuration or a metric added as files is found by name."""

import json
import os

import pytest

import run as bench

ROOT = os.path.dirname(bench.HERE)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = {w["name"]: w for w in SPEC["workloads"]}


def cells_of(metric: dict) -> set:
    return set(metric.get("workloads", CELLS))


@pytest.mark.parametrize("name", CELLS)
def test_cell_file_matches_its_entry(name):
    cell = bench.load_json("workloads", f"{name}.json")
    entry = CELLS[name]
    assert {k: cell[k] for k in ("name", "config", "traffic", "chips", "why")} == entry
    traffic = bench.load_json("traffic", f"{cell['traffic']}.json")
    assert os.path.exists(os.path.join(bench.HERE, "generators", traffic["generator"] + ".py"))
    if traffic["generator"] == "open_poisson":
        assert cell["rate"] > 0
    e2e = {m["name"]: m for m in SPEC["end_to_end"] if name in cells_of(m)}
    assert {m: e2e[m]["unit"] for m in e2e} == cell["end_to_end"]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m["name"] for m in SPEC["per_layer"] if name in cells_of(m)]
    assert sorted(layer) == sorted(cell["per_layer"]) and layer
    for m in SPEC["per_layer"]:
        if name in cells_of(m):
            assert m["moves"] in e2e, f"{m['name']} moves a metric {name} lacks"


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_reader_matches_its_entry(metric):
    reader = bench.layer_reader(metric["name"])
    assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES) == tuple(
        metric[k] for k in ("unit", "better", "source", "layer", "moves"))
    assert reader.read(os.path.join(bench.HERE, "no-such-run")) is None


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_configuration_file_matches_its_entry(entry):
    cfg = bench.load_json("configs", f"{entry['name']}.json")
    assert entry["file"] == f"benchmark/configs/{entry['name']}.json"
    assert (cfg["name"], cfg["source"], cfg["reduced"]) == (
        entry["name"], entry["source"], entry["reduced"])
    assert os.path.exists(os.path.join(bench.HERE, "references", cfg["family"] + ".py"))
    assert any(w["config"] == entry["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_catalog_numbers_are_kept(entry):
    """A configuration whose `source` is a catalog row's `source_url` holds
    every value of the row's `config` under its key, except what `reduced`
    lists; one from elsewhere says so."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        rows = [r for r in map(json.loads, f) if r["source_url"] == entry["source"]]
    cfg = bench.load_json("configs", f"{entry['name']}.json")
    if not rows:
        assert cfg["assumed"]["not_in_catalog"]
    for row in rows:
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key


def test_four_chip_cells_are_a_quarter_at_most():
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 4)
