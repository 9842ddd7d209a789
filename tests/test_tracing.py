"""Phase scopes in the compiled step and host spans in ``simulate_fleet``.

The step's phases are traced inside ``jax.named_scope("ssd.*")`` names
(``core/simulator.py``, "Phase scopes") and each sub-batch's host work
inside ``fleet.*`` profiler spans (``core/fleet.py``). A profiler trace
of a fleet job reads both; these tests check that the scopes reach the
runner's HLO, that they change nothing else in it, and that the spans
land in a recorded trace in the order ``simulate_fleet`` runs them.
"""

import contextlib
import pathlib
import re

import jax
import pytest

from repro.core import fleet, fleet_exec
from repro.core import managers as M
from repro.core import workloads as W
from repro.core.fleet import DriveSpec, simulate_fleet
from repro.core.ssd import Geometry

GEOM = Geometry(n_luns=4, blocks_per_lun=16, pages_per_block=8, lba_pba=0.7)
LBA = GEOM.lba_pages
N = 1000
EVERY = 100

# every phase a wolf sub-batch's step can reach: static groups, movement
# ops, the §5.1 interval, the on-device sampler and the chunked scan
WOLF = {"ssd.append", "ssd.gc", "ssd.victim", "ssd.drain", "ssd.valve",
        "ssd.movement", "ssd.interval", "ssd.chunk", "ssd.sampler"}
CASES = {
    "wolf": (DriveSpec(M.wolf(), (W.tpcc_like(LBA, N),), seed=1), WOLF),
    "wolf_dynamic": (
        DriveSpec(M.wolf_dynamic(), tuple(W.swap_phases(LBA, N // 2)),
                  seed=2),
        WOLF | {"ssd.detector"}),
    "wolf_trim": (
        DriveSpec(M.wolf(), (W.trimmed(W.tpcc_like(LBA, N), 0.1),), seed=3),
        WOLF | {"ssd.trim"}),
}
HOST_SPANS = ("fleet.build", "fleet.stack", "fleet.dispatch",
              "fleet.resolve")


class _Captured(Exception):
    pass


def runner_inputs(spec, monkeypatch):
    """The memo key and argument shapes of the one runner a fleet of
    ``spec`` would call, without running it."""
    got = {}

    def spy(*key):
        def run(*args):
            got["key"] = key
            got["shapes"] = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
            raise _Captured

        return run

    with monkeypatch.context() as m:
        m.setattr(fleet, "subbatch_runner", spy)
        with pytest.raises(_Captured):
            simulate_fleet(GEOM, [spec], sampler="jax", trace_every=EVERY)
    return got["key"], got["shapes"]


def fresh_runner(key, monkeypatch):
    """A runner traced anew (not the memoized one)."""
    monkeypatch.setattr(fleet_exec, "_RUNNERS", {})
    monkeypatch.setattr(fleet_exec, "_STATS", fleet_exec.CacheStats())
    return fleet_exec.subbatch_runner(*key)


def normalized(hlo: str) -> str:
    """Compiled HLO text without op metadata, the stack-frame tables that
    metadata points into, computations' parameter lists and instruction
    names (each ``%name`` becomes its order of first use): a cond branch's
    parameter is named after the innermost scope around it."""
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    hlo = re.sub(r"\nFileNames\n.*?\n(?=%|ENTRY)", "\n", hlo, flags=re.S)
    # a computation's parameter list (its parameter instructions repeat it)
    hlo = re.sub(r"^((?:ENTRY )?%[\w.\-]+) \(.*?\) -> ", r"\1 -> ", hlo,
                 flags=re.M)
    names = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(), f"%{len(names)}"),
                  hlo)


@pytest.mark.parametrize("case", sorted(CASES))
def test_scopes_reach_the_runner_hlo(case, monkeypatch):
    spec, want = CASES[case]
    key, shapes = runner_inputs(spec, monkeypatch)
    text = fresh_runner(key, monkeypatch).lower(*shapes).as_text(
        debug_info=True)
    assert set(re.findall(r"ssd\.[a-z]+", text)) == want


@pytest.mark.parametrize("case", ["wolf", "wolf_dynamic"])
def test_scopes_change_nothing_else_in_the_compiled_runner(case,
                                                           monkeypatch):
    spec, _ = CASES[case]
    key, shapes = runner_inputs(spec, monkeypatch)
    scoped = fresh_runner(key, monkeypatch).lower(*shapes).compile()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = fresh_runner(key, monkeypatch).lower(*shapes).compile()
    assert "ssd." in scoped.as_text()
    assert "ssd." not in plain.as_text()
    assert normalized(scoped.as_text()) == normalized(plain.as_text())


def test_host_spans_in_a_recorded_call(tmp_path):
    """Two sub-batches: each is built, stacked and dispatched before the
    next, and both resolve after every dispatch, inside the call."""
    from jax.profiler import ProfileData

    specs = [CASES["wolf"][0], CASES["wolf_dynamic"][0]]
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.call"):
            res = simulate_fleet(GEOM, specs, sampler="jax",
                                 trace_every=EVERY)
    finally:
        jax.profiler.stop_trace()
    assert len(res.exec_meta) == 2
    (path,) = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    spans = sorted(
        (e.start_ns, e.end_ns, e.name)
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name in HOST_SPANS + ("test.call",))
    call = [sp for sp in spans if sp[2] == "test.call"]
    assert len(call) == 1
    inner = [sp for sp in spans if sp[2] != "test.call"]
    assert [n for _, _, n in inner] == [
        "fleet.build", "fleet.stack", "fleet.dispatch",
        "fleet.build", "fleet.stack", "fleet.dispatch",
        "fleet.resolve", "fleet.resolve"]
    lo, hi = call[0][:2]
    assert all(lo <= s <= e <= hi for s, e, _ in inner)
    assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))
