"""Every cell of the real ``BENCHMARK.json`` loads into the program's
inputs, and its files agree with themselves: no run, no chip."""

import json
import pathlib

import pytest
import run
import spec

ROOT = pathlib.Path(__file__).resolve().parents[3]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_and_agrees_with_its_files(name):
    cell = spec.load_cell(name, ROOT)
    geom, _, phases = run.program_inputs(cell)
    assert geom.lba_pages == cell.lba_pages()
    for ph in phases:
        assert ph.n_writes % cell.trace_every == 0, (name, ph.n_writes)
    cfg = cell.config
    for key, published in cfg["published"].items():
        if key in cfg["geometry"]:
            assert cfg["geometry"][key] == published or key in cfg["reduced"], (
                name, key)
    entry = next(c for w in BENCH["workloads"] if w["name"] == name
                 for c in BENCH["configs"] if c["name"] == w["config"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]), name
