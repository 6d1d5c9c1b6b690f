"""Every data file and reader loads by name, ``BENCHMARK.json`` agrees with
them, and a cell that lives outside the repository loads and runs."""
import json
import re

import pytest

from bench import common
from bench.reference import lm as ref

ROOT = common.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads(cell):
    c = common.load_cell(cell)
    spec = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert c.chips == spec["chips"] and c.why == spec["why"]
    assert json.loads((common.BENCH / "workloads" / f"{cell}.json").read_text())["config"] \
        == spec["config"]
    assert (common.BENCH / "drivers" / f"{c.traffic['driver']}.py").is_file()
    assert ref.Arch(c.config).num_params() > 0


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_every_config_is_its_file(config):
    data = json.loads((ROOT / config["file"]).read_text())
    assert data["name"] == config["name"] and data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    for key in config["reduced"]:
        assert key in data["published"] and NAME.match(key)


def test_names_and_metrics():
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    readers = common.per_layer_readers()
    assert {m["name"] for m in SPEC["per_layer"]} <= set(readers)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
    assert "setup_s" in e2e
    assert common.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        common.peaks_for("TPU v4")


def test_a_cell_outside_the_repository_loads(tiny_bench):
    cell = common.load_cell("tiny.train", tiny_bench)
    assert cell.config["name"] == "tiny" and cell.traffic["driver"] == "train"
    with pytest.raises(FileNotFoundError):
        common.load_cell("no-such-cell", tiny_bench)
