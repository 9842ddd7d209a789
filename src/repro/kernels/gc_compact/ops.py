"""Public ops: gc_compact + compact_slots (Pallas on TPU, fallback off-TPU)."""

from __future__ import annotations

import jax

from .kernel import compact_slots as _compact_slots_kernel
from .kernel import gc_compact as _kernel
from .ref import compact_slots_dense, compact_slots_ref, gc_compact_ref


def gc_compact(k_pool, v_pool, src_block, src_slot, dst_block, dst_slot):
    return _kernel(
        k_pool, v_pool, src_block, src_slot, dst_block, dst_slot,
        interpret=jax.default_backend() != "tpu",
    )


def compact_slots(slot_lba, valid, src_block, src_slot, dst_block, dst_slot):
    """Bulk-GC slot-content copy used by core/simulator's vectorized drain.

    On TPU the move list feeds the Pallas scalar-prefetch kernel. Off-TPU
    the dense one-hot lowering runs instead (identical math — asserted
    equal to both the scatter reference and the interpret-mode kernel in
    tests/test_kernels.py): this op sits inside the per-write ``lax.scan``
    of a possibly-vmapped fleet, where interpret-mode grid emulation or an
    XLA:CPU-expanded scatter loop would serialize the very hot path the
    bulk drain exists to speed up.

    ``valid`` is int32 0/1 in the simulator's scan carry, the dtype the
    kernel updates in place; a bool pool is accepted and comes back bool.
    """
    if jax.default_backend() == "tpu":
        return _compact_slots_kernel(
            slot_lba, valid, src_block, src_slot, dst_block, dst_slot,
            interpret=False,
        )
    return compact_slots_dense(
        slot_lba, valid, src_block, src_slot, dst_block, dst_slot
    )


__all__ = [
    "gc_compact", "gc_compact_ref",
    "compact_slots", "compact_slots_ref", "compact_slots_dense",
]
