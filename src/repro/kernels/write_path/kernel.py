"""The fused fast-path write as a Pallas TPU kernel.

The simulator's steady-state write (invalidate the page's old slot, append
it to the target group's open block, repoint the packed map) is three
single-element updates on three pools. The kernel lands the two [K, B]
slot-pool updates in one call: the update row ``(lba, old_pm, new_pm, ok)``
is a scalar-prefetch operand, the pools stay in HBM aliased in place, and
each touched slot is a read-modify-write of its block's row through SMEM
(see ``kernels/hbm_rows``) — the invalidate first, then the append, so a
shared row sees both. The packed ``page_map`` entry is one dynamic-update-
slice outside the kernel: the map is a 1-D [LBA] pool whose length need
not be a multiple of the 128-lane tile, so no aligned row DMA covers its
tail.

``old_pm < 0`` masks the invalidate and ``ok`` the append with its map
entry: the simulator's split step invalidates on every write here, before
its heavy-path cond, and appends only on the lean ones.

``apply_trim`` is the discard peer: the same shape with the append dropped
— clear the old slot's valid bit, store -1 into the packed map. It backs
the op-stream engine's TRIM fast path on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.hbm_rows import ANY, drive_batched, rmw_row

_W = 4  # scalar-prefetch words per drive: (lba, old_pm, new_pm, ok)


def _clear_old(val_out, n, old, when, buf, sem):
    b = val_out.shape[2]

    @pl.when(when)
    def _clear():
        def update(row):
            row[old % b] = jnp.int32(0)

        rmw_row(val_out, n, old // b, buf, sem, update)


def _apply_write_kernel(ops_ref, lba_in, val_in, lba_out, val_out, buf, sem):
    del lba_in, val_in  # aliased to the outputs
    n = pl.program_id(0)
    b = val_out.shape[2]
    lba = ops_ref[_W * n]
    old = ops_ref[_W * n + 1]
    new = ops_ref[_W * n + 2]
    ok = ops_ref[_W * n + 3] != 0
    _clear_old(val_out, n, old, old >= 0, buf, sem)

    @pl.when(ok)
    def _set():
        def set_valid(row):
            row[new % b] = jnp.int32(1)

        def set_lba(row):
            row[new % b] = lba

        rmw_row(val_out, n, new // b, buf, sem, set_valid)
        rmw_row(lba_out, n, new // b, buf, sem, set_lba)


def _apply_trim_kernel(ops_ref, val_in, val_out, buf, sem):
    del val_in
    n = pl.program_id(0)
    old = ops_ref[_W * n + 1]
    ok = ops_ref[_W * n + 3] != 0
    _clear_old(val_out, n, old, ok & (old >= 0), buf, sem)


def _pool_call(kernel, pools, ops, interpret):
    """Run ``kernel`` over [N, K, B] int32 pools with [N, _W] scalar ops."""
    nb, _, b = pools[0].shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[ANY] * len(pools),
        out_specs=[ANY] * len(pools),
        scratch_shapes=[
            pltpu.SMEM((b,), jnp.int32), pltpu.SemaphoreType.DMA(()),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(p.shape, jnp.int32) for p in pools],
        input_output_aliases={i + 1: i for i in range(len(pools))},
        interpret=interpret,
    )(ops.reshape(-1), *pools)


@functools.lru_cache(maxsize=None)
def _write_op(interpret: bool):
    return drive_batched(
        lambda sl, va, ops: _pool_call(
            _apply_write_kernel, (sl, va), ops, interpret
        ),
        n_out=2,
    )


@functools.lru_cache(maxsize=None)
def _trim_op(interpret: bool):
    return drive_batched(
        lambda va, ops: _pool_call(_apply_trim_kernel, (va,), ops, interpret),
        n_out=1,
    )


def _ops_row(lba, old_pm, new_pm, enabled):
    return jnp.stack(
        [lba, old_pm, new_pm, jnp.asarray(enabled, jnp.int32)]
    ).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def apply_write(
    page_map: jax.Array,  # [LBA] int32
    slot_lba: jax.Array,  # [K, B] int32
    valid: jax.Array,     # [K, B] int32 0/1 (the scan carry's), or bool
    lba: jax.Array,       # [] int32
    old_pm: jax.Array,    # [] int32, -1 = page had no mapping
    dst_blk: jax.Array,   # [] int32
    dst_slot: jax.Array,  # [] int32
    *,
    enabled: jax.Array | bool = True,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    b = slot_lba.shape[1]
    new_pm = (dst_blk * b + dst_slot).astype(jnp.int32)
    ops = _ops_row(lba, old_pm, new_pm, enabled)
    lba_new, val_new = _write_op(interpret)(
        slot_lba, valid.astype(jnp.int32), ops
    )
    page_map = page_map.at[lba].set(
        jnp.where(ops[3] != 0, new_pm, page_map[lba])
    )
    return page_map, lba_new, val_new.astype(valid.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def apply_trim(
    page_map: jax.Array,  # [LBA] int32
    valid: jax.Array,     # [K, B] int32 0/1 (the scan carry's), or bool
    lba: jax.Array,       # [] int32
    old_pm: jax.Array,    # [] int32, -1 = page had no mapping (no-op trim)
    *,
    enabled: jax.Array | bool = True,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    ops = _ops_row(lba, old_pm, jnp.int32(-1), enabled)
    (val_new,) = _trim_op(interpret)(valid.astype(jnp.int32), ops)
    page_map = page_map.at[lba].set(
        jnp.where(ops[3] != 0, -1, page_map[lba])
    )
    return page_map, val_new.astype(valid.dtype)
