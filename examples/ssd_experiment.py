"""End-to-end driver for the paper's own experiment kind: a configurable
SSD simulation campaign (the storage-paper analogue of a training run).

    PYTHONPATH=src python examples/ssd_experiment.py --workload swap \
        --managers wolf,fdp,single --writes 100000
"""

import argparse

import numpy as np

from repro.core import managers as M
from repro.core import workloads as W
from repro.core.ssd import Geometry
from repro.utils import compile_cache


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=("uniform", "swap", "tpcc", "exp5"),
                    default="swap")
    ap.add_argument("--managers", default="wolf,fdp")
    ap.add_argument("--writes", type=int, default=100_000)
    ap.add_argument("--lba-pba", type=float, default=0.7)
    ap.add_argument("--blocks-per-lun", type=int, default=64)
    ap.add_argument("--pages-per-block", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    geom = Geometry(
        blocks_per_lun=args.blocks_per_lun,
        pages_per_block=args.pages_per_block,
        lba_pba=args.lba_pba,
    )
    lba = geom.lba_pages
    if args.workload == "uniform":
        phases = [W.uniform(lba, args.writes)]
    elif args.workload == "swap":
        phases = list(W.swap_phases(lba, args.writes))
    elif args.workload == "exp5":
        base = W.exponential_groups(lba, args.writes)
        phases = [base, W.pairwise_swap(base, 0, 4, args.writes)]
    else:
        phases = [W.tpcc_like(lba, args.writes)]

    presets = {
        "wolf": M.wolf, "fdp": M.fdp, "single": M.single_group,
        "wolf_lru": M.wolf_lru, "wolf_dynamic": M.wolf_dynamic,
        "wolf_endurance": M.wolf_endurance,
    }
    print(f"SSD: {geom.n_blocks} blocks × {geom.pages_per_block} pages, "
          f"LBA/PBA={geom.lba_pba}  workload={args.workload}")
    for name in args.managers.split(","):
        res = M.simulate(geom, presets[name](), phases, seed=args.seed)
        curve = res.wa_curve(max(2000, args.writes // 20))
        spark = " ".join(f"{x:.2f}" for x in curve[:: max(1, len(curve) // 12)])
        print(f"  {name:12s} WA={res.wa_total:.3f}   over time: {spark}")


if __name__ == "__main__":
    main()
