"""The uncut Table 2 configuration and its traffic, cut to the CPU's size
as ``conftest.small_root`` cuts the others: a whole run is correct against
the plain reference, and the control fails the comparison."""

import copy
import json
import pathlib

import control
import jax
import pytest
import reference
import spec

import check

CHIP = pathlib.Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
CELL = "small.wolf.t2"


@pytest.fixture(scope="module")
def t2_root(tmp_path_factory):
    """BENCHMARK.json with one cell: ``wolf_t2`` at 8 LUNs x 8 blocks x 16
    pages under ``tpcc.t2`` with a twentieth of its events."""
    root = tmp_path_factory.mktemp("checkout_t2")
    rel = CHIP.relative_to(ROOT)
    (root / rel / "configs").mkdir(parents=True)
    (root / rel / "traffic").mkdir(parents=True)
    cfg = json.loads((CHIP / "configs" / "wolf_t2.json").read_text())
    cfg["geometry"].update(blocks_per_lun=8, pages_per_block=16)
    cfg_file = rel / "configs" / "small_wolf_t2.json"
    (root / cfg_file).write_text(json.dumps(cfg))
    tr = copy.deepcopy(json.loads(
        (CHIP / "traffic" / "tpcc.t2.json").read_text()))
    tr["trace_every"] = 500
    for ph in tr["phases"]:
        ph["events"] //= 20
    (root / rel / "traffic" / f"small_{CELL}.json").write_text(json.dumps(tr))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "wolf_t2", "file": str(cfg_file)}]
    bench["workloads"] = [{"name": CELL, "config": "wolf_t2",
                           "traffic": f"small_{CELL}", "chips": 1}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_sound_run_is_correct(t2_root, monkeypatch, tmp_path, capsys):
    import kernels
    import run

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(run, "ROOT", t2_root)
    monkeypatch.setattr(run, "require_chip", lambda jax, cell: jax.devices())
    real = kernels.peaks
    monkeypatch.setattr(kernels, "peaks", lambda kind: real("TPU v5 lite"))
    assert run.main(["--workload", CELL, "--seed", str(2**31 + 11),
                     "--seconds", "0.1", "--trace", "0"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    assert out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_control_fails_and_program_passes(t2_root):
    cell = spec.load_cell(CELL, t2_root)
    fma = reference.fused_multiply_add(jax.devices()[0].device_kind)
    rows = [control.readings(cell, s, fma) for s in (2**31 + 1, 2**31 + 2)]
    for r in rows:
        assert check.verdict(r["program"]), r
        c = dict(r["program"], **r["control"])
        assert not check.verdict(c), r
