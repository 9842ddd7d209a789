"""Chip smoke test: the SSD fleet simulator on a TPU at the paper's drive.

Drives ``repro.core.fleet.simulate_fleet`` — the main path — once at the
paper's Table 2 geometry (``configs/wolf_ssd.py``: 8 LUNs x 1,024 blocks x
128 pages, LBA/PBA 0.7, i.e. 1,048,576 physical and 734,003 logical pages)
with the Pallas kernels compiled for the chip, and checks what comes out by
the simulator's own means. Phases, all in this one process:

  kernels    the three pool kernels (apply_write, apply_trim, compact_slots)
             at Table 2 width, plain and vmapped, against their jnp
             references
  fleet      4 drives (wolf, wolf-dynamic, fdp, and wolf under TPC-C churn
             with TRIMs), 500,000 events each, streams drawn on device:
             SimState invariants, GC in every drive, no dropped writes,
             finite WA >= 1, and ``tpu_custom_call`` in every compiled
             runner
  exactness  the fleet's pure-write wolf drive through the fleet with host
             streams and through the single-drive engine
             ``managers.simulate``: app/mig traces and every integer state
             field equal; the float fields' largest ulp gap is printed

With ``--four-chips`` only the mesh comparison runs: the fleet padded to
8 drives, with ``devices=4`` and with ``devices=1``, results equal.

Usage, from the repository root on a machine with a TPU:

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips

Timings are printed on earlier lines, each labelled with the device it ran
on; they are one-off readings, not benchmark results. The last line of
stdout is ``{"ok": true, "device": {...}}``, printed only when every phase
passed. Without a TPU the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.wolf_ssd import CONFIG  # noqa: E402
from repro.core import fleet as FL  # noqa: E402
from repro.core import managers as M  # noqa: E402
from repro.core import workloads as W  # noqa: E402
from repro.core.fleet import DriveSpec, simulate_fleet  # noqa: E402
from repro.core.ssd import assert_invariants  # noqa: E402
from repro.kernels.gc_compact import kernel as gc_kernel  # noqa: E402
from repro.kernels.gc_compact.ref import compact_slots_ref  # noqa: E402
from repro.kernels.write_path import kernel as wp_kernel  # noqa: E402
from repro.kernels.write_path.ref import (  # noqa: E402
    apply_trim_ref,
    apply_write_ref,
)
from repro.utils import compile_cache  # noqa: E402

EVENTS = 500_000  # per drive: past the first GC (314,573 free pages)


def check(ok, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def require_tpu(n_chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} TPU chips, found {len(devs)}")
    return devs


def fleet_specs(lba: int, n: int, four_chips: bool = False):
    specs = [
        DriveSpec(M.wolf(), (W.two_modal(lba, n),), seed=1,
                  name="wolf/two_modal"),
        DriveSpec(M.wolf_dynamic(), (W.tpcc_like(lba, n),), seed=2,
                  name="wolf-dynamic/tpcc"),
        DriveSpec(M.fdp(), (W.two_modal(lba, n),), seed=3,
                  name="fdp/two_modal"),
        DriveSpec(M.wolf(), (W.tpcc_churn(lba, n),), seed=4,
                  name="wolf/tpcc_churn"),
    ]
    if four_chips:
        # pad to 8 drives with four single-group drives: one sub-batch of
        # four, one drive per chip (the lightest step structure, because
        # the devices=1 side runs it as four vmapped lanes)
        specs += [
            DriveSpec(M.single_group(), phases, seed=s, name=f"single/{wl}")
            for s, (wl, phases) in enumerate((
                ("uniform", (W.uniform(lba, n),)),
                ("two_modal", (W.two_modal(lba, n),)),
                ("tpcc", (W.tpcc_like(lba, n),)),
                ("swap", tuple(W.swap_phases(lba, n // 2))),
            ), start=5)
        ]
    return specs


class RunnerLog:
    """Wrap ``fleet.subbatch_runner`` for the length of a phase: compile
    each sub-batch runner ahead of time (keeping its text), block on its
    result, and time both. ``simulate_fleet`` itself is called unchanged."""

    def __init__(self, label: str):
        self.label = label
        self.rows: list[dict] = []

    def __enter__(self):
        self._real = FL.subbatch_runner
        FL.subbatch_runner = self._runner
        return self

    def __exit__(self, *exc):
        FL.subbatch_runner = self._real

    def _runner(self, ctx, n_total, on_device, d):
        jitted = self._real(ctx, n_total, on_device, d)

        def run(*args):
            t0 = time.perf_counter()
            compiled = jitted.lower(*args).compile()  # jax caches repeats
            t1 = time.perf_counter()
            out = jax.block_until_ready(compiled(*args))
            t2 = time.perf_counter()
            drives = jax.tree_util.tree_leaves(args[0])[0].shape[0]
            self.rows.append({
                "structure": (ctx.mcfg.td_mode, ctx.use_movement,
                              ctx.use_dynamic, ctx.with_trim),
                "drives": drives, "devices": d, "events": n_total,
                "compile_s": t1 - t0, "run_s": t2 - t1,
                "kernels": compiled.as_text().count("tpu_custom_call"),
            })
            return out

        return run

    def report(self, dev: str) -> None:
        for r in self.rows:
            per_event = r["run_s"] / (r["events"] * r["drives"])
            print(
                f"[{dev}] {self.label} sub-batch {r['structure']} "
                f"drives={r['drives']} devices={r['devices']} "
                f"compile_s={r['compile_s']:.3f} run_s={r['run_s']:.3f} "
                f"s_per_event={per_event:.3e} "
                f"tpu_custom_calls={r['kernels']}",
                flush=True,
            )


def kernel_phase(geom, dev: str) -> None:
    """Each pool kernel at full width against its jnp reference, plain and
    under vmap over two drives (the fleet's batching rule)."""
    k, b, lba = geom.n_blocks, geom.pages_per_block, geom.lba_pages
    rng = np.random.default_rng(0)
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    page, blk, slot = i32(123_457), i32(k - 5), i32(7)
    # a GC drain's move list: the victim's slots (some dead) into the open
    # halves of two destination blocks
    half = np.arange(b // 2)
    moves = (
        i32(np.where(rng.random(b) < 0.8, 17, -1)), i32(np.arange(b)),
        i32(np.repeat([k - 3, k - 2], b // 2)), i32(np.tile(half, 2)),
    )

    def cases():
        pm = i32(rng.integers(-1, k * b, lba))
        sl = i32(rng.integers(-1, lba, (k, b)))
        va = jnp.asarray(rng.random((k, b)) < 0.5)
        old = pm[page]
        return {
            "apply_write": (wp_kernel.apply_write, apply_write_ref,
                            (pm, sl, va, page, old, blk, slot)),
            "apply_trim": (wp_kernel.apply_trim, apply_trim_ref,
                           (pm, va, page, old)),
            "compact_slots": (gc_kernel.compact_slots, compact_slots_ref,
                              (sl, va, *moves)),
        }

    drives = [cases(), cases()]
    for name, (kern, ref, args) in drives[0].items():
        t0 = time.perf_counter()
        got = jax.block_until_ready(jax.jit(kern)(*args))
        dt = time.perf_counter() - t0
        for g, w in zip(got, ref(*args)):
            check(np.array_equal(np.asarray(g), np.asarray(w)),
                  f"{name} != reference at Table 2 width")
        per_drive = [d[name][2] for d in drives]
        stacked = [jnp.stack(xs) for xs in zip(*per_drive)]
        gotb = jax.jit(jax.vmap(kern))(*stacked)
        for i, drive_args in enumerate(per_drive):
            for g, w in zip(gotb, ref(*drive_args)):
                check(np.array_equal(np.asarray(g[i]), np.asarray(w)),
                      f"vmapped {name} != reference (drive {i})")
        print(f"[{dev}] kernels {name}: equal to reference, plain and "
              f"vmapped; first call incl. compile {dt:.3f}s", flush=True)


def fleet_phase(geom, n: int, dev: str):
    specs = fleet_specs(geom.lba_pages, n)
    with RunnerLog("fleet") as log:
        t0 = time.perf_counter()
        res = simulate_fleet(geom, specs, sampler="jax", devices=1)
        wall = time.perf_counter() - t0
    log.report(dev)
    for r in log.rows:
        check(r["kernels"] > 0,
              f"no tpu_custom_call in the {r['structure']} runner")
    wa = res.wa_total
    for i, s in enumerate(specs):
        st = res.state(i)
        assert_invariants(st, s.label)
        c = {f: int(st[f]) for f in
             ("n_app", "n_mig", "n_erase", "n_dropped", "n_trim")}
        print(f"[{dev}] fleet drive {s.label}: {c} WA={wa[i]!r}", flush=True)
        check(c["n_erase"] > 0, f"{s.label}: never garbage-collected")
        check(c["n_dropped"] == 0, f"{s.label}: dropped writes")
        check(np.isfinite(wa[i]) and wa[i] >= 1.0, f"{s.label}: WA {wa[i]}")
        if FL._spec_has_trim(s):
            check(c["n_trim"] > 0, f"{s.label}: no TRIM ran")
        else:
            check(c["n_mig"] > 0, f"{s.label}: no migration")
    run_s = sum(r["run_s"] for r in log.rows)
    events = n * len(specs)
    print(f"[{dev}] fleet phase: {len(specs)} drives x {n} events, "
          f"wall_s={wall:.3f} (incl. host set-up and compile) "
          f"compile_s={sum(r['compile_s'] for r in log.rows):.3f} "
          f"run_s={run_s:.3f} s_per_event={run_s / events:.3e}", flush=True)
    return specs


def _ulp_gap(a: np.ndarray, b: np.ndarray) -> int:
    ia = a.astype(np.float32).view(np.int32).astype(np.int64)
    ib = b.astype(np.float32).view(np.int32).astype(np.int64)
    # map the sign-magnitude bit patterns onto one monotone integer line
    ia = np.where(ia < 0, np.int64(-(2**31)) - ia, ia)
    ib = np.where(ib < 0, np.int64(-(2**31)) - ib, ib)
    return int(np.max(np.abs(ia - ib), initial=0))


def exactness_phase(geom, spec: DriveSpec, dev: str) -> None:
    n = sum(ph.n_writes for ph in spec.phases)
    with RunnerLog("exactness") as log:
        t0 = time.perf_counter()
        fleet = simulate_fleet(geom, [spec], sampler="numpy", devices=1)
        t_fleet = time.perf_counter() - t0
    log.report(dev)
    t0 = time.perf_counter()
    single = M.simulate(geom, spec.mcfg, list(spec.phases), seed=spec.seed)
    jax.block_until_ready(single.state)
    t_single = time.perf_counter() - t0
    check(np.array_equal(fleet.app[0], single.app), "app traces differ")
    check(np.array_equal(fleet.mig[0], single.mig), "mig traces differ")
    gap = 0
    st_f = fleet.state(0)
    for key, a in single.state.items():
        a, b = np.asarray(a), np.asarray(st_f[key])
        if np.issubdtype(a.dtype, np.floating):
            gap = max(gap, _ulp_gap(a, b))
        else:
            check(np.array_equal(a, b), f"state[{key}] differs")
    print(f"[{dev}] exactness {spec.label}: {n} writes, fleet (host "
          f"streams) wall_s={t_fleet:.3f}, managers.simulate wall_s="
          f"{t_single:.3f} (both incl. compile); traces and integer state "
          f"equal; largest float ulp gap={gap}", flush=True)


def four_chip_phase(geom, n: int, dev: str) -> None:
    specs = fleet_specs(geom.lba_pages, n, four_chips=True)
    results = {}
    for d in (4, 1):
        with RunnerLog(f"devices={d}") as log:
            results[d] = simulate_fleet(geom, specs, sampler="jax", devices=d)
        log.report(dev)
    four, one = results[4], results[1]
    print(f"[{dev}] exec_meta devices=4: {four.exec_meta}", flush=True)
    check(four.devices_used == 4, f"devices_used={four.devices_used}")
    check(one.devices_used == 1, f"devices_used={one.devices_used}")
    check(np.array_equal(four.app, one.app), "app differs across meshes")
    check(np.array_equal(four.mig, one.mig), "mig differs across meshes")
    for i, s in enumerate(specs):
        for key, a in one.state(i).items():
            b = np.asarray(four.state(i)[key])
            check(np.array_equal(np.asarray(a), b),
                  f"{s.label}: state[{key}] differs across meshes")
    print(f"[{dev}] four chips: {len(specs)} drives x {n} events, "
          f"devices=4 and devices=1 results equal", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device vs 1-device fleet comparison")
    args = ap.parse_args()
    devs = require_tpu(4 if args.four_chips else 1)
    compile_cache.enable()
    kind = devs[0].device_kind
    dev = f"{devs[0].platform} {kind} x{len(devs)}"
    print(f"[{dev}] jax {jax.__version__}", flush=True)
    geom = CONFIG.geometry()
    print(f"[{dev}] geometry {geom} lba_pages={geom.lba_pages} "
          f"pba_pages={geom.pba_pages}", flush=True)
    if args.four_chips:
        four_chip_phase(geom, EVENTS, dev)
    else:
        kernel_phase(geom, dev)
        specs = fleet_phase(geom, EVENTS, dev)
        exactness_phase(geom, specs[0], dev)
    stats = devs[0].memory_stats() or {}
    print(f"[{dev}] peak_bytes_in_use={stats.get('peak_bytes_in_use')}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs),
    }}))


if __name__ == "__main__":
    main()
