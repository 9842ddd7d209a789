"""Quickstart: the paper's model + Wolf in 60 seconds.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np

from repro.core import (
    allocate_closed_form,
    delta_from_op_ratio,
    optimal_allocation,
    total_wa,
    wa_from_op_ratio,
)
from repro.core import managers as M
from repro.core import workloads as W
from repro.core.ssd import Geometry
from repro.utils import compile_cache

compile_cache.enable()

print("=== 1. The closed-form WA model (paper §4) ===")
for r in (0.6, 0.7, 0.8, 0.9):
    print(
        f"  LBA/PBA={r:.2f}  δ={float(delta_from_op_ratio(jnp.asarray(r))):.3f}"
        f"  WA={float(wa_from_op_ratio(jnp.asarray(r))):.2f}"
    )

print("\n=== 2. Near-optimal OP allocation (paper §5.5, eq. 8) ===")
s = jnp.asarray([50_000.0, 30_000.0, 20_000.0])  # group sizes (pages)
p = jnp.asarray([0.1, 0.3, 0.6])                  # update frequencies
op = 40_000.0                                      # spare pages
cf = allocate_closed_form(s, p, op)
opt = optimal_allocation(s, p, jnp.asarray(op))
print(f"  closed form: {np.asarray(cf).round(0)}  WA={float(total_wa(s,p,cf)):.4f}")
print(f"  optimum:     {np.asarray(opt).round(0)}  WA={float(total_wa(s,p,opt)):.4f}")

print("\n=== 3. Wolf vs FDP across a workload swap (paper §6.1) ===")
geom = Geometry(n_luns=4, blocks_per_lun=48, pages_per_block=16)
ph1, ph2 = W.swap_phases(geom.lba_pages, 40_000, p=(0.1, 0.9))
for name, mcfg in (("wolf", M.wolf()), ("fdp", M.fdp())):
    swap = M.simulate(geom, mcfg, [ph1, ph2], seed=0)
    noswap = M.simulate(geom, mcfg, [ph1, ph1], seed=0)
    extra = float(swap.mig[-1] - noswap.mig[-1]) / geom.pba_pages
    print(f"  {name:5s}: WA={swap.wa_total:.3f}  extra migrations/PBA={extra:+.3f}")

print("\nSee examples/ssd_experiment.py, train_lm.py, serve_wolf_kv.py for more.")
