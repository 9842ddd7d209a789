"""The compiled one-lane fleet runner, and the pool copies on its per-write
path.

Shared by ``test_write_engine.py`` (the runner compiled by XLA:CPU) and
``test_tpu_compile.py`` (the same runner compiled for a described v5e).
"""

import re

import jax
import pytest

from repro.core import fleet, fleet_exec
from repro.core.fleet import simulate_fleet


def captured_runner(geom, spec, monkeypatch, trace_every):
    """(runner, argument shapes) of the one sub-batch that a one-drive
    fleet of ``spec`` would run, captured before anything runs."""
    got = {}

    class Captured(Exception):
        pass

    def spy(*key):
        def run(*args):
            got["fn"] = fleet_exec.subbatch_runner(*key)
            got["shapes"] = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
            raise Captured

        return run

    monkeypatch.setattr(fleet, "subbatch_runner", spy)
    with pytest.raises(Captured):
        simulate_fleet(geom, [spec], sampler="jax", trace_every=trace_every)
    return got["fn"], got["shapes"]


def computations(hlo: str) -> dict:
    """{computation name: its instruction lines}, and "ENTRY" for the entry."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if m:
            name = "ENTRY" if m.group(1) else m.group(2)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            comps[name].append(line)
    return comps


def _callees(lines, pattern):
    return [c for line in lines
            for m in re.finditer(pattern, line)
            for c in m.group(1).replace("%", "").split(", ")]


def per_write_pool_converts(hlo: str, geom) -> list:
    """Computations that a loop body of a compiled runner reaches (the body,
    its fusions, conds and nested loops) and that convert a whole
    valid-shaped pool: a ``convert`` to a [K, B] array (bool to int32) or a
    ``compare`` that yields a [K, B] bool (int32 back to bool). Empty when
    ``valid`` stays in one dtype across the per-write path."""
    k, b = geom.n_blocks, geom.pages_per_block
    whole = re.compile(rf"= (s32\[(1,)?{k},{b}\]\S* convert"
                       rf"|pred\[(1,)?{k},{b}\]\S* compare)\(")
    comps = computations(hlo)
    seen = set()
    todo = _callees(comps["ENTRY"], r" while\(.*?body=%?([\w.\-]+)")
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        todo += _callees(comps[name], r"(?:calls|to_apply|body|condition)"
                                      r"=%?([\w.\-]+)")
        todo += _callees(comps[name], r"branch_computations=\{([^}]*)\}")
    assert len(seen) > 3, seen
    return sorted(name for name in seen
                  if any(whole.search(line) for line in comps[name]))


def per_write_pool_copies(hlo: str, geom) -> list:
    """Computations on the per-write path of a compiled one-lane runner
    that copy an array of the page_map, slot_lba or valid shape: the entry
    and every loop body nested in it, and of each cond in one its thinnest
    branch (the pass-through). The lean write updates the pools in place
    when this is empty; copies belong in the heavy tail only."""
    k, b = geom.n_blocks, geom.pages_per_block
    pool = re.compile(
        rf"= (s32|pred)\[({geom.lba_pages}|{k},{b}|{k * b})\]"
        r"\S* copy\(")
    comps = computations(hlo)
    loops, todo = [], ["ENTRY"]
    while todo:  # the entry and the loop bodies nested in it
        name = todo.pop()
        loops.append(name)
        todo += _callees(comps[name], r" while\(.*?body=%?([\w.\-]+)")
    conds = [_callees(comps[name], r"branch_computations=\{([^}]*)\}")
             for name in loops]
    assert len(loops) >= 3 and any(conds), loops
    thin = [min(branches, key=lambda c: len(comps[c]))
            for branches in filter(None, conds)]
    return [name for name in loops + thin
            if any(pool.search(line) for line in comps[name])]
