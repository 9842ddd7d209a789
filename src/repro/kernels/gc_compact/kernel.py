"""GC compaction (the paper's migration operation) as a Pallas TPU kernel.

Wolf's movement operations pack the live token-slots of victim KV blocks
into fresh blocks. On GPU this is a gather/scatter loop; the TPU-native
form makes the move list a scalar-prefetch operand so each (src→dst) copy
is a pair of DMA'd BlockSpec tiles — Pallas pipelines the copies.

Grid = (M,) moves. Input tile = pool[src_block[i], src_slot[i]] (one token
slot, [Hkv, D]); output tile = pool[dst_block[i], dst_slot[i]]. The output
aliases the input pool (donate) so untouched slots are preserved.

No-op rows (src_block < 0) redirect to slot (0, 0) of block dst_block[i]=src
— handled by clamping and a copy-through of the existing contents.

``compact_slots`` is the simulator's variant: its pools are scalar [K, B]
slot maps, too large for VMEM at real geometry and far too narrow for a
tile per slot, so it keeps them in HBM and moves slots through SMEM rows
(see ``kernels/hbm_rows``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.hbm_rows import ANY, copy_row, drive_batched


def _compact_kernel(moves_ref, src_ref, dst_cur_ref, out_ref):
    i = pl.program_id(0)
    ok = moves_ref[i, 0] >= 0

    @pl.when(ok)
    def _move():
        out_ref[...] = src_ref[...]

    @pl.when(jnp.logical_not(ok))
    def _keep():
        out_ref[...] = dst_cur_ref[...]


def _run(pool, moves, *, interpret):
    m = moves.shape[0]
    n, p, hkv, d = pool.shape

    def src_map(i, moves_ref):
        ok = moves_ref[i, 0] >= 0
        return (
            jnp.where(ok, moves_ref[i, 0], 0),
            jnp.where(ok, moves_ref[i, 1], 0),
            0,
            0,
        )

    def dst_map(i, moves_ref):
        ok = moves_ref[i, 0] >= 0
        blk = jnp.where(ok, moves_ref[i, 2], 0)
        slot = jnp.where(ok, moves_ref[i, 3], 0)
        return (blk, slot, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m,),
        in_specs=[
            pl.BlockSpec((None, None, hkv, d), src_map),
            pl.BlockSpec((None, None, hkv, d), dst_map),
        ],
        out_specs=pl.BlockSpec((None, None, hkv, d), dst_map),
    )
    out = pl.pallas_call(
        _compact_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={1: 0},  # pool aliases the output
        interpret=interpret,
    )(moves, pool, pool)
    return out


def _compact_slots_kernel(moves_ref, lba_in, val_in, lba_out, val_out,
                          stage_l, stage_v, row_l, row_v, sem):
    """Gather every move's source slot, then scatter to the destinations.

    Pools stay in HBM; a row is fetched into SMEM only when the move list
    changes rows (a GC drain's sources share one victim row and its
    destinations form a few runs), so a drain costs a handful of row DMAs
    instead of a copy of the pool.
    """
    del lba_in, val_in  # aliased to the outputs
    n = pl.program_id(0)
    m = stage_l.shape[0]
    base = 4 * m * n

    def move(i):
        return tuple(moves_ref[base + 4 * i + j] for j in range(4))

    def fetch(row):
        copy_row(lba_out.at[n, row], row_l, sem)
        copy_row(val_out.at[n, row], row_v, sem)

    def flush(row):
        copy_row(row_l, lba_out.at[n, row], sem)
        copy_row(row_v, val_out.at[n, row], sem)

    def gather(i, cur):
        sb, ss, _, _ = move(i)
        ok = sb >= 0

        @pl.when(ok & (sb != cur))
        def _():
            fetch(sb)

        @pl.when(ok)
        def _():
            stage_l[i] = row_l[ss]
            stage_v[i] = row_v[ss]

        return jnp.where(ok, sb, cur)

    jax.lax.fori_loop(0, m, gather, jnp.int32(-1))

    def scatter(i, cur):
        sb, _, db, ds = move(i)
        ok = sb >= 0
        switch = ok & (db != cur)

        @pl.when(switch & (cur >= 0))
        def _():
            flush(cur)

        @pl.when(switch)
        def _():
            fetch(db)

        @pl.when(ok)
        def _():
            row_l[ds] = stage_l[i]
            row_v[ds] = stage_v[i]

        return jnp.where(ok, db, cur)

    last = jax.lax.fori_loop(0, m, scatter, jnp.int32(-1))

    @pl.when(last >= 0)
    def _():
        flush(last)


def _compact_slots_call(slot_lba, valid, moves, interpret):
    nb, _, b = slot_lba.shape
    m = moves.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[ANY, ANY],
        out_specs=[ANY, ANY],
        scratch_shapes=[
            pltpu.SMEM((m,), jnp.int32), pltpu.SMEM((m,), jnp.int32),
            pltpu.SMEM((b,), jnp.int32), pltpu.SMEM((b,), jnp.int32),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    return pl.pallas_call(
        _compact_slots_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(slot_lba.shape, jnp.int32),
            jax.ShapeDtypeStruct(valid.shape, jnp.int32),
        ],
        input_output_aliases={1: 0, 2: 1},
        interpret=interpret,
    )(moves.reshape(-1), slot_lba, valid)


@functools.lru_cache(maxsize=None)
def _compact_slots_op(interpret: bool):
    return drive_batched(
        lambda sl, va, mv: _compact_slots_call(sl, va, mv, interpret),
        n_out=2,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def compact_slots(
    slot_lba: jax.Array,
    valid: jax.Array,
    src_block: jax.Array,
    src_slot: jax.Array,
    dst_block: jax.Array,
    dst_slot: jax.Array,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Metadata-pool variant of :func:`gc_compact` for the simulator's
    bulk-GC drain: the pools are the [K, B] per-slot lba map and its valid
    bitmap, kept in HBM; the move list (a victim's live slots) is a
    scalar-prefetch operand and only the rows it names are touched. All
    reads happen before any write, as in ``compact_slots_ref``. ``valid``
    is int32 0/1 in the scan carry, so the casts around the call are
    no-ops there; a bool pool is converted each way."""
    moves = jnp.stack(
        [src_block, src_slot, dst_block, dst_slot], axis=1
    ).astype(jnp.int32)
    lba_new, val_new = _compact_slots_op(interpret)(
        slot_lba, valid.astype(jnp.int32), moves
    )
    return lba_new, val_new.astype(valid.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gc_compact(
    k_pool: jax.Array,
    v_pool: jax.Array,
    src_block: jax.Array,
    src_slot: jax.Array,
    dst_block: jax.Array,
    dst_slot: jax.Array,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    moves = jnp.stack(
        [src_block, src_slot, dst_block, dst_slot], axis=1
    ).astype(jnp.int32)
    k_new = _run(k_pool, moves, interpret=interpret)
    v_new = _run(v_pool, moves, interpret=interpret)
    return k_new, v_new
