"""Pure-jnp oracle + off-TPU lowering for the fused fast-path write.

One application write on the simulator's fast path touches exactly three
mapping structures: the old physical slot's valid bit (the invalidate), the
destination slot's (lba, valid) pair (the append), and the packed
logical→physical ``page_map`` entry. The split step makes the call on every
write, before its heavy-path cond: ``enabled`` masks the append (a write
that takes the heavy path appends there), never the invalidate.
``apply_write_ref`` is the obvious 2-D-indexed formulation;
``apply_write_xla`` is the lowering the simulator uses off-TPU, shaped so
that XLA updates the pools in place inside the step's scan.

The TRIM peer (``apply_trim_ref`` / ``apply_trim_flat``) is the same op
minus the append: kill the old slot's valid bit and unmap the page — the
fast path of the op-stream engine's discard handling.

Every function here takes ``valid`` as bool or as int32 0/1 (the
simulator's scan carry) and returns it in the dtype it came in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def apply_write_ref(
    page_map: jax.Array,  # [LBA] int32 packed physical address, -1 unmapped
    slot_lba: jax.Array,  # [K, B] int32 per-slot content (lba or -1)
    valid: jax.Array,     # [K, B] per-slot liveness, int32 0/1 or bool
    lba: jax.Array,       # [] int32 page being written
    old_pm: jax.Array,    # [] int32 page's old packed address (-1 = none)
    dst_blk: jax.Array,   # [] int32 destination block (an OPEN active block)
    dst_slot: jax.Array,  # [] int32 destination slot (the block's fill ptr)
    enabled: jax.Array | bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Invalidate ``old_pm``; where ``enabled``, land ``lba`` at
    ``(dst_blk, dst_slot)`` and repoint its map entry.

    The destination is always a fresh slot strictly above the block's
    current fill pointer, so it can never equal the old slot — the clear
    and the set commute. Returns (page_map, slot_lba, valid).
    """
    b = slot_lba.shape[1]
    has_old = old_pm >= 0
    old_c = jnp.maximum(old_pm, 0)
    ob, os = old_c // b, old_c % b
    valid = valid.at[ob, os].set(jnp.where(has_old, False, valid[ob, os]))
    new_pm = dst_blk * b + dst_slot
    slot_lba = slot_lba.at[dst_blk, dst_slot].set(
        jnp.where(enabled, lba, slot_lba[dst_blk, dst_slot]))
    valid = valid.at[dst_blk, dst_slot].set(
        jnp.where(enabled, True, valid[dst_blk, dst_slot]))
    page_map = page_map.at[lba].set(
        jnp.where(enabled, new_pm, page_map[lba]))
    return page_map, slot_lba, valid


def apply_trim_ref(
    page_map: jax.Array,  # [LBA] int32 packed physical address, -1 unmapped
    valid: jax.Array,     # [K, B] per-slot liveness, int32 0/1 or bool
    lba: jax.Array,       # [] int32 page being trimmed
    old_pm: jax.Array,    # [] int32 page's old packed address (-1 = none)
) -> tuple[jax.Array, jax.Array]:
    """The TRIM peer of :func:`apply_write_ref`: unmap ``lba`` and kill its
    physical slot. A trim of an already-unmapped page (``old_pm < 0`` —
    re-trims are legal in real discard streams) is a pure no-op.
    ``slot_lba`` keeps its stale content, exactly as an overwrite's
    invalidate does — dead slots are identified by ``valid`` alone.
    Returns (page_map, valid)."""
    b = valid.shape[1]
    has_old = old_pm >= 0
    old_c = jnp.maximum(old_pm, 0)
    ob, os = old_c // b, old_c % b
    valid = valid.at[ob, os].set(jnp.where(has_old, False, valid[ob, os]))
    # unconditional: an unmapped page's entry is already -1
    page_map = page_map.at[lba].set(-1)
    return page_map, valid


def apply_trim_flat(
    page_map: jax.Array,
    valid: jax.Array,
    lba: jax.Array,
    old_pm: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Flattened-index lowering of :func:`apply_trim_ref` (CPU/GPU path):
    one dropped-out-of-bounds single-element store per pool."""
    kk, b = valid.shape
    old_c = jnp.where(old_pm >= 0, old_pm, kk * b)
    vflat = valid.reshape(-1).at[old_c].set(False, mode="drop")
    page_map = page_map.at[lba].set(-1)
    return page_map, vflat.reshape(kk, b)


def apply_write_xla(
    page_map: jax.Array,
    slot_lba: jax.Array,
    valid: jax.Array,
    lba: jax.Array,
    old_pm: jax.Array,
    dst_blk: jax.Array,
    dst_slot: jax.Array,
    enabled: jax.Array | bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Off-TPU lowering of :func:`apply_write_ref`, in place under XLA.

    Both ``valid`` updates are one scatter whose masked-off rows point out
    of bounds and are dropped: no update reads a ``valid`` element another
    one writes, which would keep the pre-write pool alive and make XLA copy
    it. ``slot_lba`` and ``page_map`` each read and write one element at the
    same index.
    """
    kk, b = slot_lba.shape
    old_c = jnp.maximum(old_pm, 0)
    rows = jnp.stack([jnp.where(old_pm >= 0, old_c // b, kk),
                      jnp.where(enabled, dst_blk, kk)])
    cols = jnp.stack([old_c % b, dst_slot])
    valid = valid.at[rows, cols].set(jnp.array([False, True]), mode="drop")
    slot_lba = slot_lba.at[dst_blk, dst_slot].set(
        jnp.where(enabled, lba, slot_lba[dst_blk, dst_slot]))
    page_map = page_map.at[lba].set(
        jnp.where(enabled, dst_blk * b + dst_slot, page_map[lba]))
    return page_map, slot_lba, valid
