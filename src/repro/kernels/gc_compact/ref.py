"""Pure-jnp oracle for KV-block compaction (GC migration)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def gc_compact_ref(
    k_pool: jax.Array,  # [N, P, Hkv, D]
    v_pool: jax.Array,  # [N, P, Hkv, D]
    src_block: jax.Array,  # [M] int32 source block per live slot (-1 = skip)
    src_slot: jax.Array,  # [M] int32
    dst_block: jax.Array,  # [M] int32 destination block
    dst_slot: jax.Array,  # [M] int32
) -> tuple[jax.Array, jax.Array]:
    """Scatter live slots (src_block, src_slot) -> (dst_block, dst_slot).

    A no-op row (src_block < 0) leaves the pool untouched.
    """
    ok = src_block >= 0
    sb = jnp.maximum(src_block, 0)
    ss = jnp.maximum(src_slot, 0)
    db = jnp.where(ok, dst_block, 0)
    ds = jnp.where(ok, dst_slot, 0)
    k_rows = k_pool[sb, ss]  # [M, Hkv, D]
    v_rows = v_pool[sb, ss]
    k_new = k_pool.at[db, ds].set(
        jnp.where(ok[:, None, None], k_rows, k_pool[db, ds])
    )
    v_new = v_pool.at[db, ds].set(
        jnp.where(ok[:, None, None], v_rows, v_pool[db, ds])
    )
    return k_new, v_new


def compact_slots_ref(
    slot_lba: jax.Array,  # [K, B] int32 per-slot content (lba or -1)
    valid: jax.Array,     # [K, B] per-slot liveness, int32 0/1 or bool
    src_block: jax.Array,  # [M] int32 source block per move (-1 = skip)
    src_slot: jax.Array,   # [M] int32
    dst_block: jax.Array,  # [M] int32 destination block
    dst_slot: jax.Array,   # [M] int32
) -> tuple[jax.Array, jax.Array]:
    """Metadata-pool compaction: scatter victim slot contents (the lba plus
    its valid bit) to their destination slots in one gather + one scatter.

    The simulator's bulk-GC drain is this op with pools of scalars instead
    of KV tiles. All reads happen before any write (gather-then-scatter), so
    src and dst slot sets may freely interleave across moves. A no-op row
    (src_block < 0) leaves both pools untouched.
    """
    ok = src_block >= 0
    sb = jnp.maximum(src_block, 0)
    ss = jnp.maximum(src_slot, 0)
    # redirect no-op rows out of bounds: dropped by the scatter
    db = jnp.where(ok, dst_block, slot_lba.shape[0])
    ds = jnp.where(ok, dst_slot, 0)
    lba_rows = slot_lba[sb, ss]
    valid_rows = valid[sb, ss]
    slot_lba = slot_lba.at[db, ds].set(lba_rows, mode="drop")
    valid = valid.at[db, ds].set(valid_rows, mode="drop")
    return slot_lba, valid


def compact_slots_dense(
    slot_lba: jax.Array,
    valid: jax.Array,
    src_block: jax.Array,
    src_slot: jax.Array,
    dst_block: jax.Array,
    dst_slot: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Flattened-index lowering of :func:`compact_slots_ref` for XLA:CPU.

    The 2-D ``.at[db, ds]`` scatter of the reference is expanded by
    XLA:CPU into a while loop over the update rows — inside the simulator's
    per-write scan that costs more than the entire rest of the GC drain.
    Scattering into the FLATTENED [K·B] pools with 1-D indices lowers to a
    native O(M) scatter instead (no expansion, no capacity-sized masks).
    All reads happen before any write, as in the reference.
    """
    kk, bb = slot_lba.shape
    ok = src_block >= 0
    src_flat = jnp.maximum(src_block, 0) * bb + jnp.maximum(src_slot, 0)
    dst_flat = jnp.where(ok, dst_block * bb + dst_slot, kk * bb)  # OOB drop
    lba_rows = slot_lba.reshape(-1)[src_flat]
    valid_rows = valid.reshape(-1)[src_flat]
    lba_new = slot_lba.reshape(-1).at[dst_flat].set(lba_rows, mode="drop")
    valid_new = valid.reshape(-1).at[dst_flat].set(valid_rows, mode="drop")
    return lba_new.reshape(kk, bb), valid_new.reshape(kk, bb)
