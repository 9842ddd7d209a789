"""Per-phase device time of the simulator's step, from the profiler trace
a traced run records.

The step's phases run inside ``jax.named_scope`` names that start with
``ssd.`` (``core/simulator.py``, "Phase scopes"), which XLA keeps in each
instruction's op metadata (``op_name``). A v5e trace names each device
op by its instruction only, so the name path is read from the compiled
program. ``simulate_fleet`` wraps each sub-batch's host work in
``fleet.*`` spans (``core/fleet.py``). This module extends ``trace.py``'s
reduction with both, and changes none of its numbers:

* :func:`load` reads the trace as ``trace.load`` does, and also keeps the
  ``fleet.*`` host spans, each device op's name path and its exact
  times. Its result is one ``trace.reduce`` reads unchanged: ``busy_s``,
  ``window_s``, ``ops`` and ``cut_s`` are the same, and idle gaps are
  named by the innermost span of either kind.
* :func:`reduce` is ``trace.reduce`` plus ``scopes`` and ``spans``.
  ``scopes`` is computed over complete chunks only: the ``ssd.chunk``
  loop events (one per ``trace_every`` events of the scan) that lie
  wholly inside the kept window, so a cut trace is read over the chunks
  it holds. Each instant of a chunk goes to the innermost op event that
  covers it (self time), and that op to the outermost ``ssd.*`` phase on
  its path, to ``unscoped`` where its path has none, or, for the chunk's
  own loop event, to loop overhead. ``spans`` is the seconds of each host
  span name in the window.
* :func:`metrics` turns them into numbers per simulated host write
  (``step.*_us``), the loop's share of chunk time and the share of the
  job spent building drives; :func:`table` prints every phase.

    python benchmarks/chip/phases.py --workload <cell> --seed <n> \\
        --seconds <s> [--out <file.json>]

runs ``run.py --trace 1`` with this reduction in place of ``trace.py``'s,
then prints the table and the metrics on standard error (and writes them
and the runners' compiled HLO beside ``--out``). Like ``run.py``, it
exits with no result without a TPU.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import trace  # noqa: E402

HOST_PREFIXES = (trace.HOST_PREFIX, "fleet.")
# the line of a TPU device plane that holds one event per program run
MODULES_LINE = "XLA Modules"
SCOPE = "ssd."
CHUNK = "ssd.chunk"
# the exact start and length of a v5e op event, in ps: its start_ns is a
# float of the ns since the epoch, 256 ns apart at today's dates
OFFSET_PS, DURATION_PS = "device_offset_ps", "device_duration_ps"
# the opcode of a loop in an op's name (``%while.414 = (...) while(...)``)
WHILE = " while("
# an HLO instruction line with its op name path
INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%(\S+) = .*?, metadata=\{op_name="([^"]*)"', re.M)
# per-layer metric -> the phases it sums, in device us per host write
PHASE_METRICS = {
    "step.append_us": ("ssd.append",),
    "step.detector_us": ("ssd.detector",),
    "step.gc_us": ("ssd.gc", "ssd.valve"),
    "step.movement_us": ("ssd.movement",),
    "step.interval_us": ("ssd.interval",),
}


def program_paths(hlo_text: str) -> tuple:
    """``(module name, {instruction: op name path})`` of a compiled
    program's HLO text (``Compiled.as_text()``): the trace names each
    device op by its instruction and each program run by its module, but
    keeps no op metadata."""
    module = hlo_text.split("\n", 1)[0].split()[1].rstrip(",")
    return module, dict(INSTRUCTION.findall(hlo_text))


def scope_of(path: str) -> tuple:
    """``(scopes, loop)`` of an op's name path: the ``ssd.*`` parts below
    its innermost ``ssd.chunk``, outermost first (all of them where the
    path has no chunk), and whether the op is the chunk's loop itself
    (``.../ssd.chunk/while``) rather than an op in its body."""
    parts = [p.split(":")[0] for p in path.split("/")] if path else []
    rest = parts
    if CHUNK in parts:
        rest = parts[len(parts) - parts[::-1].index(CHUNK):]
    loop = CHUNK in parts and len(rest) == 1 and rest[0].startswith("while")
    return tuple(p for p in rest if p.startswith(SCOPE)), loop


def load(log_dir, device_ids, programs=()) -> dict:
    """``trace.load``'s result with ``fleet.*`` host spans among the host
    spans, and for each device the index into ``paths`` of each op's name
    path (``op_paths``) and, where the events carry them, each op's exact
    start and end in ps (``op_ps``). ``programs`` holds the compiled HLO
    texts of the programs whose ops get a path: an op's program is the
    ``XLA Modules`` event it lies in, named by its module, and its
    instruction is the head of its name (``%while.414 = ...``)."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(str(files[-1]))
    known = {}
    for text in programs:
        module, ops = program_paths(text)
        known.setdefault(module, {}).update(ops)
    devices, op_paths, op_ps, host, names = {}, {}, {}, [], {}
    paths = {"": 0}
    for plane in data.planes:
        idx = plane.name[len(trace.DEVICE_PREFIX):]
        if (plane.name.startswith(trace.DEVICE_PREFIX) and idx.isdigit()
                and int(idx) in device_ids):
            cols = {trace.OPS_LINE: ([], [], []), MODULES_LINE: ([], [], [])}
            modules, exact = {}, ([], [])
            for line in plane.lines:
                if line.name not in cols:
                    continue
                ops_line = line.name == trace.OPS_LINE
                ids = names if ops_line else modules
                start, end, name = cols[line.name]
                for e in line.events:
                    start.append(e.start_ns)
                    end.append(e.end_ns)
                    name.append(ids.setdefault(e.name, len(ids)))
                    if ops_line:
                        st = dict(e.stats)
                        exact[0].append(st.get(OFFSET_PS, -1))
                        exact[1].append(st.get(DURATION_PS, -1))
            start, end, name = (np.asarray(c, np.int64)
                                for c in cols[trace.OPS_LINE])
            devices[plane.name] = (start, end, name)
            t0, dt = (np.asarray(c, np.int64) for c in exact)
            if len(t0) and t0.min() >= 0 and dt.min() >= 0:
                op_ps[plane.name] = (t0, t0 + dt)
            # the program run each op lies in, -1 outside any
            m_start, m_end, m_name = (np.asarray(c, np.int64)
                                      for c in cols[MODULES_LINE])
            order = np.argsort(m_start, kind="stable")
            m_start, m_end, m_name = (m_start[order], m_end[order],
                                      m_name[order])
            k = np.searchsorted(m_start, start, side="right") - 1
            inside = (k >= 0) & (start < np.append(m_end, 0)[k])
            module = np.where(inside, np.append(m_name, -1)[k], -1)
            key = (module + 1) * (len(names) + 1) + name
            uniq, inverse = np.unique(key, return_inverse=True)
            op_name = sorted(names, key=names.get)
            mod_name = [m.split("(")[0] for m in
                        sorted(modules, key=modules.get)]
            key_path = np.zeros(len(uniq), np.int64)
            for j, u in enumerate(uniq.tolist()):
                m, n = divmod(u, len(names) + 1)
                ops = known.get(mod_name[m - 1], {}) if m else {}
                path = ops.get(op_name[n].split(" = ", 1)[0].lstrip("%"), "")
                key_path[j] = paths.setdefault(path, len(paths))
            op_paths[plane.name] = key_path[inverse.reshape(-1)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.end_ns, e.name)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIXES))
    return {"devices": devices, "host": host,
            "names": sorted(names, key=names.get),
            "op_paths": op_paths, "op_ps": op_ps,
            "paths": sorted(paths, key=paths.get)}


def self_times(start, end):
    """Self time of each of a nested family of intervals of positive
    length: its length less its children's, in the input's order; and
    the count of children that end after their parent (0 where the
    family nests properly). The self times of an interval's subtree sum
    to its length either way."""
    n = len(start)
    order = np.lexsort((-end, start))  # by start, the enclosing first
    s, e = start[order], end[order]
    # intervals open at each start, itself included: 1 + its ancestors
    depth = np.arange(1, n + 1) - np.searchsorted(np.sort(e), s, "right")
    parent = np.full(n, -1, np.int64)
    for d in range(2, int(depth.max(initial=0)) + 1):
        kids = np.nonzero(depth == d)[0]
        above = np.nonzero(depth == d - 1)[0]
        k = np.searchsorted(above, kids) - 1
        parent[kids] = np.where(k >= 0, above[np.maximum(k, 0)], -1)
    has = parent >= 0
    covered = np.zeros(n, np.int64)
    np.add.at(covered, parent[has], (e - s)[has])
    own = np.empty(n, np.int64)
    own[order] = (e - s) - covered
    return own, int(np.sum(e[has] > e[parent[has]]))


def scopes(tr: dict, lo: int, hi: int, top: int = 15) -> dict:
    """Per-phase self seconds inside the complete chunks of the window,
    averaged over the devices, with the chunk count, the chunks' seconds,
    the loop's own (``loop_s``), the unscoped ops' (``unscoped_s``), each
    scope path's (``paths``), the in-chunk op events (``ops``), the
    ``top`` in-chunk instructions by self time (``[instruction, phase,
    seconds]``), the self seconds of phases outside any chunk
    (``outside``) and the children that end after their parent
    (``crossing``). Only ops wholly inside the window count; their times
    are the exact ones of ``op_ps`` where the trace has them."""
    info = [scope_of(p) for p in tr.get("paths", [""])]
    is_loop = np.array([loop for _, loop in info])
    # XLA copies a loop's metadata to ops around it: the loop event is
    # the one of them whose instruction is a while
    is_while = np.array([WHILE in n for n in tr["names"]], bool)
    chunked = [CHUNK in p for p in tr.get("paths", [""])]
    total = {"chunks": 0, "chunk_ps": 0, "loop_ps": 0, "unscoped_ps": 0,
             "ops": 0, "crossing": 0}
    phases, paths, outside, by_op = {}, {}, {}, {}
    width = len(info)
    for dev, (start, end, name) in tr["devices"].items():
        pid = tr.get("op_paths", {}).get(dev)
        if pid is None:
            continue
        s, e = tr.get("op_ps", {}).get(dev, (start * 1000, end * 1000))
        keep = (start >= lo) & (end <= hi) & (e > s)
        s, e, pid, name = s[keep], e[keep], pid[keep], name[keep]
        self_ps, crossing = self_times(s, e)
        total["crossing"] += crossing
        complete = is_loop[pid] & is_while[name]
        cs, ce = s[complete], e[complete]
        order = np.argsort(cs, kind="stable")
        cs, ce = cs[order], np.append(ce[order], 0)
        # the complete chunk each op lies in, if any (k = -1 reads the
        # appended 0, which no op ends before)
        k = np.searchsorted(cs, s, side="right") - 1
        in_chunk = (k >= 0) & (s < ce[k]) & (e <= ce[k])
        total["chunks"] += len(cs)
        total["chunk_ps"] += int(np.sum(ce[:-1] - cs))
        total["loop_ps"] += int(np.sum(self_ps[complete]))
        body = in_chunk & ~complete
        total["ops"] += int(np.sum(body))
        by_path = np.bincount(pid[body], self_ps[body], width)
        out_path = np.bincount(pid[~in_chunk], self_ps[~in_chunk], width)
        for p in np.nonzero(by_path)[0]:
            ps, sc = int(by_path[p]), info[p][0]
            if not sc:
                total["unscoped_ps"] += ps
                continue
            phases[sc[0]] = phases.get(sc[0], 0) + ps
            key = "/".join(sc)
            paths[key] = paths.get(key, 0) + ps
        for p in np.nonzero(out_path)[0]:
            sc = info[p][0]
            if sc and not chunked[p]:
                outside[sc[0]] = outside.get(sc[0], 0) + int(out_path[p])
        pairs, inverse = np.unique(name[body] * width + pid[body],
                                   return_inverse=True)
        for pair, ps in zip(pairs.tolist(), np.bincount(
                inverse.reshape(-1), self_ps[body]).tolist()):
            by_op[pair] = by_op.get(pair, 0) + ps
    n = max(len(tr["devices"]), 1)
    ops = []
    for pair, ps in sorted(by_op.items(), key=lambda kv: -kv[1])[:top]:
        op, p = divmod(pair, width)
        ops.append([tr["names"][op].split(" = ", 1)[0],
                    "/".join(info[p][0]) or "unscoped", ps * 1e-12 / n])
    return {
        "chunks": total["chunks"] / n,
        "chunk_s": total["chunk_ps"] * 1e-12 / n,
        "loop_s": total["loop_ps"] * 1e-12 / n,
        "unscoped_s": total["unscoped_ps"] * 1e-12 / n,
        "phases": {k: v * 1e-12 / n for k, v in phases.items()},
        "paths": {k: v * 1e-12 / n for k, v in paths.items()},
        "outside": {k: v * 1e-12 / n for k, v in outside.items()},
        "top": ops,
        "ops": total["ops"],
        "crossing": total["crossing"],
    }


def spans(tr: dict, lo: int, hi: int) -> dict:
    """Seconds of each host span name over the spans that start in the
    window, whole (a span cut by the window's end counts in full)."""
    out = {}
    for s, e, name in tr["host"]:
        if lo <= s < hi:
            out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return out


def reduce(tr: dict, top: int = 10) -> dict:
    """``trace.reduce`` of the trace, plus ``scopes`` and ``spans`` over
    its window: from the first ``bench.job`` span's start, ``window_s``
    long (ended where the trace was cut)."""
    summary = trace.reduce(tr, top)
    lo = min(s for s, _, n in tr["host"] if n == "bench.job")
    hi = lo + round(summary["window_s"] * 1e9)
    return dict(summary, scopes=scopes(tr, lo, hi), spans=spans(tr, lo, hi))


def metrics(summary: dict, chunk_writes: int) -> dict:
    """Per-layer numbers of a reduced trace. ``chunk_writes`` is the
    simulated host writes of one chunk on one device: ``trace_every``
    times the drive lanes a device scans. A phase no op carried, or a
    trace with no complete chunk or no ``fleet.build`` span, gives no
    number."""
    out = {}
    sc, sp = summary["scopes"], summary["spans"]
    if sc["chunks"] and chunk_writes:
        writes = sc["chunks"] * chunk_writes
        for name, parts in PHASE_METRICS.items():
            if any(p in sc["phases"] for p in parts):
                out[name] = 1e6 * sum(sc["phases"].get(p, 0.0)
                                      for p in parts) / writes
        out["scan.overhead_share"] = sc["loop_s"] / sc["chunk_s"]
    if sp.get("fleet.build") and sp.get("bench.job"):
        out["fleet.build_share"] = sp["fleet.build"] / sp["bench.job"]
    return out


def fault(summary: dict):
    """Why the phase readings would be plausible and wrong, or None: ops
    ran inside chunks but none carried a phase (the scopes were lost)."""
    sc = summary["scopes"]
    if sc["ops"] and not sc["phases"]:
        return ("phases.py: ops ran inside the chunks but none carries "
                "an ssd.* phase")
    return None


def table(summary: dict, chunk_writes: int) -> list:
    """Every phase and scope path of the complete chunks: seconds, us per
    host write and share of chunk time, then the unscoped ops, the loop,
    the chunks, the phases outside them and the largest ops."""
    sc = summary["scopes"]
    writes = sc["chunks"] * chunk_writes
    us = (lambda t: f"{1e6 * t / writes:10.3f}") if writes else (
        lambda t: f"{'-':>10}")
    share = (lambda t: f"{t / sc['chunk_s']:8.4f}") if sc["chunk_s"] else (
        lambda t: f"{'-':>8}")
    rows = [f"{'scope':<34}{'seconds':>12}{'us/write':>10}{'share':>8}"]

    def row(label, t, in_chunk=True):
        rows.append(f"{label:<34}{t:12.6f}"
                    + (us(t) + share(t) if in_chunk
                       else f"{'-':>10}{'-':>8}"))

    for phase, t in sorted(sc["phases"].items(), key=lambda kv: -kv[1]):
        row(phase, t)
        for path, tp in sorted(sc["paths"].items(), key=lambda kv: -kv[1]):
            if path.split("/")[0] == phase and path != phase:
                row("  " + path, tp)
    row("unscoped", sc["unscoped_s"])
    row("loop", sc["loop_s"])
    row(f"chunks ({sc['chunks']:g})", sc["chunk_s"])
    for phase, t in sorted(sc["outside"].items()):
        row(phase + " (outside chunks)", t, in_chunk=False)
    rows.append("largest in-chunk ops by self time:")
    for op, phase, t in sc["top"]:
        row(f"  {op} {phase}"[:33], t)
    return rows


class Programs:
    """While active, keeps each sub-batch runner ``simulate_fleet`` calls
    and the shapes of its arguments, so that the runners' compiled HLO
    (:meth:`texts`) can name the ops of a trace."""

    def __init__(self, fleet):
        self.fleet, self.runners = fleet, {}

    def __enter__(self):
        import jax

        real = self._real = self.fleet.subbatch_runner

        def runner(*key):
            fn = real(*key)

            def run(*args):
                self.runners[key] = (fn, jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=a.sharding),
                    args))
                return fn(*args)

            return run

        self.fleet.subbatch_runner = runner
        return self

    def __exit__(self, *exc):
        self.fleet.subbatch_runner = self._real

    def texts(self) -> list:
        """Each runner's compiled HLO, from JAX's compilation cache where
        the run put it there."""
        return [fn.lower(*shapes).compile().as_text()
                for fn, shapes in self.runners.values()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=pathlib.Path,
                    help="write the readings here as JSON, and each "
                         "runner's compiled HLO beside it")
    args = ap.parse_args(argv)

    import run
    import spec
    from repro.core import fleet

    cell = spec.load_cell(args.workload, run.ROOT)
    got = {}
    programs = Programs(fleet)

    class Reader:
        """``run.py``'s trace module, with this module's reduction."""

        breakdown = staticmethod(trace.breakdown)

        @staticmethod
        def load(log_dir, device_ids):
            got["hlo"] = programs.texts()
            got["trace"] = tr = load(log_dir, device_ids, got["hlo"])
            return tr

        @staticmethod
        def reduce(tr):
            got["summary"] = summary = reduce(tr)
            why = fault(summary)
            if why:
                raise SystemExit(why)
            return summary

    run.trace_mod = Reader
    with programs:
        rc = run.main(["--workload", args.workload, "--seed",
                       str(args.seed), "--seconds", str(args.seconds),
                       "--trace", "1"])
    summary, tr = got["summary"], got["trace"]
    chunk_writes = cell.trace_every * int(cell.traffic["drives_per_chip"])
    for line in table(summary, chunk_writes):
        run.log(line)
    result = {
        "metrics": metrics(summary, chunk_writes),
        "scopes": summary["scopes"], "spans": summary["spans"],
        "events": {d: len(c[0]) for d, c in tr["devices"].items()},
        "cut_s": summary["cut_s"],
        "breakdown": trace.breakdown(summary),
    }
    run.log(f"phases {json.dumps(result)}")
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(result, indent=1))
        for i, text in enumerate(got["hlo"]):
            args.out.with_suffix(f".hlo{i}.txt").write_text(text)
    return rc


if __name__ == "__main__":
    sys.exit(main())
