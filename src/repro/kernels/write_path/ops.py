"""Public ops: apply_write / apply_trim (Pallas on TPU, XLA lowering off-TPU)."""

from __future__ import annotations

import jax

from .kernel import apply_trim as _apply_trim_kernel
from .kernel import apply_write as _apply_write_kernel
from .ref import (
    apply_trim_flat,
    apply_trim_ref,
    apply_write_ref,
    apply_write_xla,
)


def apply_write(page_map, slot_lba, valid, lba, old_pm, dst_blk, dst_slot,
                enabled=True):
    """Fused fast-path write for core/simulator's split step: invalidate
    the old physical slot of ``lba`` and, where ``enabled``, append it at
    (dst_blk, dst_slot), in one op over the three mapping pools.

    On TPU the (lba, old_pm, new_pm, enabled) row feeds the Pallas
    scalar-prefetch kernel with the pools aliased in place. Off-TPU the
    in-place XLA lowering runs instead (identical math — asserted equal to
    the 2-D reference and the interpret-mode kernel in
    tests/test_kernels.py): this op sits inside the per-write ``lax.scan``,
    where interpret-mode grid emulation would serialize the very hot path
    the fast-path split exists to speed up.

    ``valid`` is int32 0/1 in the simulator's scan carry, the dtype the
    kernel updates in place; a bool pool is accepted too (the kernel then
    converts the whole pool each way) and comes back bool.
    """
    if jax.default_backend() == "tpu":
        return _apply_write_kernel(
            page_map, slot_lba, valid, lba, old_pm, dst_blk, dst_slot,
            enabled=enabled, interpret=False,
        )
    return apply_write_xla(
        page_map, slot_lba, valid, lba, old_pm, dst_blk, dst_slot, enabled
    )


def apply_trim(page_map, valid, lba, old_pm):
    """Fused fast-path TRIM: kill ``lba``'s old physical slot and unmap it
    — the discard peer of :func:`apply_write` (same dispatch rule, same
    equivalence suite). ``old_pm < 0`` (a re-trim of an already-unmapped
    page) leaves the valid pool untouched; the map entry is stored -1
    unconditionally (it already held -1). ``valid`` as in
    :func:`apply_write`: int32 in the scan carry, bool accepted.
    """
    if jax.default_backend() == "tpu":
        return _apply_trim_kernel(page_map, valid, lba, old_pm,
                                  interpret=False)
    return apply_trim_flat(page_map, valid, lba, old_pm)


__all__ = [
    "apply_write", "apply_write_ref", "apply_write_xla",
    "apply_trim", "apply_trim_ref", "apply_trim_flat",
]
