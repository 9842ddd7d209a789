"""Fleet throughput: the shard_map drive-axis fleet over a policy × workload
grid vs a Python loop of per-drive ``managers.simulate`` on the same grid.

Reports drives/sec for both paths (post-warmup, i.e. compile excluded for
both), the speedup, the per-drive equilibrium WA curves of the grid — the
batched analogue of the paper's §6 policy comparisons — and a 1→N
device-count scaling curve for the mesh executor (the batched path at 1, 2
and every visible device; on CPU the devices are virtual cores, on an
accelerator they are chips — same code, same numbers expected to be
bit-identical, only wall-clock moves).

The speedup is hardware-dependent: XLA:CPU executes batched gather/scatter
serially per lane, so on CPU the vmap win comes from shard_map sharding
across cores (virtual host devices, set up below) and dispatch
amortization; on an accelerator backend the same code batches the lanes in
silicon. (The executor is ``jit(shard_map(vmap))`` over
``launch.mesh.drive_mesh`` — the old pmap path is gone; see
core/fleet_exec.py.)

Every run emits ``BENCH_fleet.json`` at the repo root (schema
``bench_fleet/v3``): steps/sec for the batched fleet, per policy × workload
cell (loop path), the ``scaling`` curve per device count, plus host/JAX
metadata (platform, python, jax version, backend, device count) so
PR-over-PR comparisons are pinned to a host AND a backend — the trajectory
is multi-backend from v3 on. ``--smoke`` runs a reduced grid for the CI
lane (``scripts/run_tests.sh --bench-smoke``); ``--out PATH`` redirects
the JSON (used by ``--bench-compare`` to diff a fresh run against the
committed baseline without clobbering it); ``--only SUBSTR`` restricts the
grid to matching cells (the ``--trim-smoke`` lane benches just the
``tpcc_churn`` op-stream cells that way); ``--devices D`` pins the fleet
to D devices (the ``--mesh-smoke`` lane benches one 2-device cell that
way). The scaling sweep runs only on full-grid, unpinned runs.
"""

from __future__ import annotations

import os

from repro.utils.hostdev import force_host_device_count

# must run before jax initializes: expose the cores as host devices so the
# fleet can shard_map its sub-batches (min 2 so the scaling curve always
# has a multi-device point, even on a 1-core container — virtual devices
# oversubscribe threads; structure stays, speedup needs real cores)
force_host_device_count(max(os.cpu_count() or 1, 2))

import json
import pathlib
import platform
import sys

from repro.core import managers as M
from repro.core import workloads as W
from repro.core.fleet import DriveSpec, simulate_fleet
from repro.core.ssd import Geometry
from repro.utils import compile_cache

from benchmarks.common import report, table, timer

POLICIES = (
    ("wolf", M.wolf),
    ("wolf-dynamic", M.wolf_dynamic),
    # wear-leveled weight point of the unified victim score (β=0.25):
    # benchmarked so the scoring layer's cost shows up in the trajectory
    # and the wear columns have a leveled row to compare against
    ("wolf-wear", M.wolf_wear),
    ("fdp", M.fdp),
    ("single", M.single_group),
    # finite-endurance row: wolf on an AGING drive — blocks retire once
    # their P-E count crosses the limit, shrinking the OP the allocator
    # divides. Shares a sub-batch with wolf/wolf-wear (faults are traced
    # data, not a partition dimension), so this row also keeps the mixed
    # faulty/fault-free compiled path on the benchmarked trajectory.
    ("wolf-endurance", M.wolf_endurance),
)


def grid_specs(geom: Geometry, writes: int, seeds=(0,),
               only: str | None = None) -> list[DriveSpec]:
    lba = geom.lba_pages
    # scale the P-E limit to the run length so every mode ages its drives
    # into visible retirement (mean P-E at this geometry ≈ writes·WA/(K·B))
    pe_limit = max(writes // 4000, 1)

    def preset_cfg(pname, preset):
        if pname == "wolf-endurance":
            return preset(endurance_pe_limit=pe_limit)
        return preset()
    workloads = (
        ("uniform", lambda: (W.uniform(lba, writes),)),
        ("two_modal", lambda: (W.two_modal(lba, writes),)),
        ("swap", lambda: tuple(W.swap_phases(lba, writes // 2))),
        ("tpcc", lambda: (W.tpcc_like(lba, writes),)),
        # op-stream cells: the TPC-C insert/update/delete churn (TRIMs
        # interleaved) — these exercise the WRITE/TRIM dispatch engine;
        # the pure-write cells above keep their historical streams (the
        # fleet partitions op-stream drives into their own sub-batch)
        ("tpcc_churn", lambda: (W.tpcc_churn(lba, writes),)),
    )
    specs = [
        DriveSpec(
            preset_cfg(pname, preset), wl(), seed=seed,
            name=f"{pname}/{wname}#{seed}"
        )
        for seed in seeds
        for pname, preset in POLICIES
        for wname, wl in workloads
    ]
    if only:
        specs = [s for s in specs if only in s.name]
        assert specs, f"--only {only!r} matched no grid cell"
    return specs


def run(full: bool = False, smoke: bool = False,
        out_path: str | None = None, only: str | None = None,
        devices: int | None = None) -> dict:
    compile_cache.enable()
    geom = Geometry(n_luns=4, blocks_per_lun=32, pages_per_block=8)
    writes = 60_000 if full else (4_000 if smoke else 20_000)
    seeds = (0,) if smoke else (0, 1)  # 5 policies × 5 workloads × seeds
    specs = grid_specs(geom, writes, seeds, only=only)

    # -- fleet path: warm the jit cache, then time steady-state ------------
    # trace stride: the grid's WA analysis samples windows of writes//10,
    # so a stride of writes//40 loses nothing while cutting the per-step
    # trace stores from the hot scan (engine default stays dense)
    trace_every = max(writes // 40, 1)
    fleet_kw = dict(
        sampler="jax", devices=devices if devices else "auto",
        trace_every=trace_every,
    )
    simulate_fleet(geom, specs, **fleet_kw)
    # best of 3: the whole-grid call is sub-10s post-refactor, so a single
    # sample is at the mercy of host scheduling noise
    fleet_sec = None
    for _ in range(3):
        with timer() as t_rep:
            fleet = simulate_fleet(geom, specs, **fleet_kw)
        fleet_sec = t_rep.dt if fleet_sec is None else min(fleet_sec, t_rep.dt)

    # -- loop path: same grid, per-drive managers.simulate, timed per drive
    # (per policy×workload cell steps/sec). Warm each DISTINCT jit
    # signature first — the compiled shape includes the scan length, the
    # drive's group count (from the first phase's group structure), AND
    # whether the op-stream engine is traced (trim-bearing phases), so the
    # warm key carries all three; warming at a reduced write count would
    # leave every timed cell paying XLA compilation (and cells would not
    # be comparable across modes).
    for s in {
        (s.mcfg.name,
         tuple((ph.n_writes, len(ph.sizes), ph.has_trim)
               for ph in s.phases)): s
        for s in specs
    }.values():
        M.simulate(geom, s.mcfg, list(s.phases), seed=0)
    loop_results, drive_secs = [], []
    with timer() as t_loop:
        for s in specs:
            with timer() as t_drive:
                loop_results.append(
                    M.simulate(geom, s.mcfg, list(s.phases), seed=s.seed)
                )
            drive_secs.append(t_drive.dt)

    b = len(specs)
    fleet_dps = b / fleet_sec
    loop_dps = b / t_loop.dt
    speedup = fleet_dps / loop_dps

    # -- device-count scaling curve (1 → 2 → N): the mesh executor's
    # multi-backend trajectory. Results are bit-identical per drive at
    # every point (tests/test_fleet_mesh.py), so only wall-clock moves; on
    # a CPU with virtual devices the curve is flat-to-worse (threads
    # oversubscribe cores) but the per-backend shape is exactly what the
    # trajectory tracks. Skipped on pinned-device or filtered runs (quick
    # CI cells).
    import jax

    scaling = {}
    if devices is None and only is None:
        n_host = len(jax.devices())
        # only device counts that exist: a count above n_host is an error
        for d in sorted({1, min(2, n_host), n_host}):
            kw = dict(fleet_kw, devices=d)
            simulate_fleet(geom, specs, **kw)  # warm (compile excluded)
            d_sec = None
            for _ in range(2):
                with timer() as t_d:
                    simulate_fleet(geom, specs, **kw)
                d_sec = t_d.dt if d_sec is None else min(d_sec, t_d.dt)
            scaling[str(d)] = {
                "devices": d,
                "sec": round(d_sec, 3),
                "fleet_steps_per_sec": round(b * writes / d_sec, 1),
            }
        base_sps = scaling["1"]["fleet_steps_per_sec"]
        for cell in scaling.values():
            cell["speedup_vs_1dev"] = round(
                cell["fleet_steps_per_sec"] / base_sps, 3
            )

    window = max(writes // 10, 500)
    # endurance columns ride on the carried O(1) aggregates — no extra
    # simulation work, just a read-off per drive
    wear_var = fleet.wear_variance()
    wear_imb = fleet.wear_imbalance()
    # survival columns (retired capacity + degraded lanes): zeros for every
    # fault-free row, the aging story for the wolf-endurance row
    retired_frac = fleet.retired_fraction()
    status = fleet.drive_status()
    rows = []
    cells: dict[str, dict] = {}
    for i, s in enumerate(specs):
        cell = s.name.rsplit("#", 1)[0]  # "policy/workload"
        c = cells.setdefault(
            cell, {"sec": 0.0, "n": 0, "wa": [], "wvar": [], "wimb": [],
                   "rfrac": [], "degraded": 0}
        )
        c["sec"] += drive_secs[i]
        c["n"] += 1
        c["wa"].append(float(fleet.wa_total[i]))
        c["wvar"].append(float(wear_var[i]))
        c["wimb"].append(float(wear_imb[i]))
        c["rfrac"].append(float(retired_frac[i]))
        c["degraded"] += int(status[i] != 0)
        if s.seed != seeds[0]:
            continue
        curve = fleet.result(i).wa_curve(window)
        rows.append({
            "drive": s.name,
            "wa_total": round(float(fleet.wa_total[i]), 3),
            "wa_equilibrium": round(float(curve[-3:].mean()), 3),
            "loop_wa_total": round(loop_results[i].wa_total, 3),
            "wear_var": round(float(wear_var[i]), 2),
            "wear_imbalance": round(float(wear_imb[i]), 3),
            "retired_frac": round(float(retired_frac[i]), 4),
            "degraded": int(status[i] != 0),
        })
    print(table(rows, list(rows[0].keys())))
    summary = {
        "drives": b,
        "writes_per_drive": writes,
        "host_devices": len(jax.devices()),
        "fleet_devices": fleet.devices_used,
        "fleet_sec": round(fleet_sec, 3),
        "loop_sec": round(t_loop.dt, 3),
        "fleet_drives_per_sec": round(fleet_dps, 3),
        "loop_drives_per_sec": round(loop_dps, 3),
        "fleet_steps_per_sec": round(b * writes / fleet_sec, 1),
        "loop_steps_per_sec": round(b * writes / t_loop.dt, 1),
        "speedup": round(speedup, 2),
    }
    out = {
        "summary": summary,
        "rows": rows,
        "wa_curves": {
            s.name: [round(float(x), 3) for x in fleet.result(i).wa_curve(window)]
            for i, s in enumerate(specs) if s.seed == seeds[0]
        },
    }
    report("fleet", out)

    # machine-readable perf trajectory, tracked PR-over-PR; host/JAX
    # metadata pins WHERE the numbers were taken (host AND backend — the
    # scaling curve makes the trajectory multi-backend) so bench-compare
    # across hosts is recognizable as apples-to-oranges
    bench = {
        "schema": "bench_fleet/v3",
        "mode": "smoke" if smoke else ("full" if full else "default"),
        "host": {
            "platform": platform.platform(),
            "machine": platform.machine(),
            "python": sys.version.split()[0],
            "cpu_count": os.cpu_count(),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "devices": len(jax.devices()),
        },
        "config": {
            "drives": b, "writes_per_drive": writes,
            "trace_every": trace_every,
            "geometry": {
                "n_luns": geom.n_luns, "blocks_per_lun": geom.blocks_per_lun,
                "pages_per_block": geom.pages_per_block,
                "lba_pba": geom.lba_pba,
            },
            "host_devices": len(jax.devices()),
            "fleet_devices": fleet.devices_used,
        },
        "fleet_steps_per_sec": summary["fleet_steps_per_sec"],
        "loop_steps_per_sec": summary["loop_steps_per_sec"],
        "speedup": summary["speedup"],
        # per-device-count batched-fleet throughput (empty on pinned or
        # --only runs); bench_compare diffs cells with matching counts
        "scaling": scaling,
        "cells": {
            name: {
                "steps_per_sec_loop": round(c["n"] * writes / c["sec"], 1),
                # measurement duration: bench_compare refuses to gate on
                # cells too fast to time reliably
                "sec": round(c["sec"], 4),
                "wa_total_mean": round(sum(c["wa"]) / c["n"], 4),
                # endurance context (never gated, like the WA column):
                # erase-count variance and max/mean P-E imbalance
                "wear_var_mean": round(sum(c["wvar"]) / c["n"], 4),
                "wear_imbalance_mean": round(sum(c["wimb"]) / c["n"], 4),
                # survival context (report-only, like the wear columns):
                # mean retired-capacity fraction + degraded-drive count
                "retired_frac_mean": round(sum(c["rfrac"]) / c["n"], 4),
                "degraded_count": c["degraded"],
            }
            for name, c in sorted(cells.items())
        },
    }
    bench_path = (
        pathlib.Path(out_path) if out_path
        else pathlib.Path(__file__).resolve().parent.parent / "BENCH_fleet.json"
    )
    bench_path.write_text(json.dumps(bench, indent=2))
    print(f"\nwrote {bench_path}")
    print(
        f"fleet: {b} drives × {writes} writes in {fleet_sec:.2f}s "
        f"({fleet_dps:.2f} drives/s, {summary['fleet_steps_per_sec']:.0f} steps/s) | "
        f"loop: {t_loop.dt:.2f}s ({loop_dps:.2f} drives/s) | "
        f"speedup ×{speedup:.1f}"
    )
    return out


if __name__ == "__main__":
    out = None
    if "--out" in sys.argv:
        out = sys.argv[sys.argv.index("--out") + 1]
    only = None
    if "--only" in sys.argv:  # cell filter, e.g. --only tpcc_churn
        only = sys.argv[sys.argv.index("--only") + 1]
    devices = None
    if "--devices" in sys.argv:  # pin the fleet's device count (mesh lane)
        devices = int(sys.argv[sys.argv.index("--devices") + 1])
    run(full="--full" in sys.argv, smoke="--smoke" in sys.argv,
        out_path=out, only=only, devices=devices)
