"""Shared plumbing for the simulator's HBM-resident pool kernels.

The simulator's mapping pools (``slot_lba``/``valid``: [K, B] per drive)
are far larger than VMEM at real geometry (the paper's Table 2 drive is
8,192 × 128), while every update a write or a GC drain makes touches one
or a few ROWS of them. So the kernels keep the pools in HBM
(``memory_space=pl.ANY``, aliased in place) and read-modify-write single
rows through a small SMEM buffer: one DMA in, scalar stores, one DMA out.
A row is the whole minor dimension, so every DMA is tile-aligned.

The kernels are written for a LEADING DRIVE AXIS ([N, K, B] pools, grid
over N, scalar operands flattened into one scalar-prefetch row). A plain
call runs them at N = 1; :func:`drive_batched` registers a ``vmap`` rule
that feeds the batch straight to the kernel, because the generic Pallas
batching rule would turn the HBM refs into whole-pool VMEM windows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ANY = pl.BlockSpec(memory_space=pl.ANY)


def copy_row(src, dst, sem):
    """One row DMA between HBM and SMEM, complete before return."""
    copy = pltpu.make_async_copy(src, dst, sem)
    copy.start()
    copy.wait()


def rmw_row(pool, n, row, buf, sem, update):
    """Read row ``pool[n, row]`` into the SMEM ``buf``, apply ``update(buf)``
    (scalar stores), and write it back."""
    copy_row(pool.at[n, row], buf, sem)
    update(buf)
    copy_row(buf, pool.at[n, row], sem)


def drive_batched(call, n_out: int):
    """Wrap ``call(*pools_and_ops)`` — a kernel invocation on arrays with a
    leading drive axis — as a per-drive function with its own vmap rule.

    Unbatched, every argument gains a drive axis of 1 and the outputs drop
    it; under ``vmap`` the batched arguments pass straight through (the
    unbatched ones are broadcast) and the kernel grids over the drives.
    """

    @jax.custom_batching.custom_vmap
    def per_drive(*args):
        outs = call(*(a[None] for a in args))
        return tuple(o[0] for o in outs)

    @per_drive.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = [
            a if batched else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for a, batched in zip(args, in_batched)
        ]
        return tuple(call(*args)), (True,) * n_out

    return per_drive
