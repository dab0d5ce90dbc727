"""Configurations, mixes and metrics are found by the names in
BENCHMARK.json, and each file agrees with its entry."""
import re

import pytest

from chipbench.harness import cell as cells
from chipbench.harness.cell import BENCH, ROOT, load_json

SPEC = load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_is_found_by_name(w):
    cell = cells.find(w["name"])
    assert cell.config["name"] == w["config"]
    assert cell.mix == cells.mix(w["traffic"])
    assert cell.chips == w["chips"]
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_matches_its_entry(c):
    assert c["file"] == f"chipbench/configs/{c['name']}.json"
    cfg = load_json(ROOT / c["file"])
    assert cfg["name"] == c["name"]
    assert cfg["source"] == c["source"]
    assert cfg["reduced"] == c["reduced"]
    assert (BENCH / "references" / f"{cfg['reference']}.py").is_file()
    assert cells.adapter(cfg).model_config(cfg).num_layers == \
        cfg["model"]["n_layer"]


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_reader_is_found_by_name(m):
    reader = cells.metric_reader(m["name"])
    assert reader.UNIT == m["unit"]
    assert callable(reader.read)
    if m in SPEC["per_layer"]:
        assert reader.LAYER == m["layer"]
        assert reader.MOVES == m["moves"]


def test_names_and_layers_keep_the_format():
    named = SPEC["configs"] + SPEC["workloads"] + METRICS
    assert all(NAME.match(x["name"]) for x in named)
    assert len({x["name"] for x in named}) == len(named)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["moves"] in e2e for m in SPEC["per_layer"])
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in SPEC["per_layer"]:
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_unknown_workload_is_refused():
    with pytest.raises(cells.CellError):
        cells.find("no-such-cell")
