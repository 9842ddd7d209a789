"""Compile the simulator's Pallas kernels, and the fleet runner that calls
them, for a TPU v5e that is described, not attached, at the paper's Table 2
width (8 LUNs × 1,024 blocks × 128 pages, LBA/PBA 0.7).

Off-TPU the simulator dispatches to the flat XLA lowerings, so no CPU test
of the whole step ever reaches these kernels; this file asks the TPU
compiler directly, unbatched and under ``vmap`` over a fleet of 8 drives,
and checks the kernel is in the compiled program (``tpu_custom_call``).
Nothing runs: a pass here says the chip's compiler accepts the kernels
(VMEM limits, tiling), not that they are correct or fast. The one-lane
fleet runner of one Wolf drive, the program a chip holding one drive runs,
is compiled once with its step routed to the kernels as on the chip, and
its per-write path is checked for pool copies.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library at a time.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.wolf_ssd import CONFIG
from repro.core import fleet_exec
from repro.core import managers as M
from repro.core import workloads as W
from repro.core.fleet import DriveSpec
from repro.kernels.gc_compact.kernel import compact_slots
from repro.kernels.write_path.kernel import apply_trim, apply_write

from _runner_hlo import (
    captured_runner, per_write_pool_converts, per_write_pool_copies,
)

GEOM = CONFIG.geometry()
K, B, LBA = GEOM.n_blocks, GEOM.pages_per_block, GEOM.lba_pages
FLEET = 8
# a Table 2 job: two fills of the logical span, traced every 2,500 writes
RUN_EVENTS, RUN_TRACE_EVERY = 1_500_000, 2_500


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the on-disk cache but
    cannot be read back without one: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


# (fn, per-drive operand shapes/dtypes)
_I32, _BOOL = jnp.int32, jnp.bool_
KERNELS = {
    "apply_write": (apply_write,
                    [((LBA,), _I32), ((K, B), _I32), ((K, B), _BOOL)]
                    + [((), _I32)] * 4),
    "apply_trim": (apply_trim, [((LBA,), _I32), ((K, B), _BOOL)]
                   + [((), _I32)] * 2),
    # one GC drain's move list: every slot of the victim block
    "compact_slots": (compact_slots, [((K, B), _I32), ((K, B), _BOOL)]
                      + [((B,), _I32)] * 4),
}


@pytest.mark.parametrize("batch", [None, FLEET], ids=["unbatched", "vmap8"])
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, batch, one_chip, no_persistent_cache):
    fn, operands = KERNELS[name]
    lead = () if batch is None else (batch,)
    args = [
        jax.ShapeDtypeStruct(lead + shape, dtype, sharding=one_chip)
        for shape, dtype in operands
    ]
    f = fn if batch is None else jax.vmap(fn)
    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the pools stay in HBM and are updated in place: no pool-sized temp
    pool_bytes = K * B * 4 * (batch or 1)
    assert mem.temp_size_in_bytes < pool_bytes, mem


@pytest.fixture(scope="module")
def runner_hlo(one_chip, no_persistent_cache):
    """The v5e-compiled runner of a one-drive Wolf fleet at Table 2. Off
    the chip the ops pick their XLA lowerings by the default backend; the
    step is traced here with that answering "tpu", so the runner holds the
    Pallas kernels the chip runs."""
    with pytest.MonkeyPatch.context() as mp:
        # a fresh runner: one traced earlier in the process holds the
        # CPU lowerings
        mp.setattr(fleet_exec, "_RUNNERS", {})
        spec = DriveSpec(M.wolf(), (W.tpcc_like(LBA, RUN_EVENTS),), seed=1)
        fn, shapes = captured_runner(GEOM, spec, mp, RUN_TRACE_EVERY)
        placed = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), shapes)
        mp.setattr(jax, "default_backend", lambda: "tpu")
        try:
            return fn.lower(*placed).compile().as_text()
        finally:
            # jitted helpers keep the traces made while the backend read
            # "tpu"; drop them so later CPU traces take the CPU lowerings
            jax.clear_caches()


def test_fleet_runner_runs_the_kernels(runner_hlo):
    """The write on every step and the GC drain are the Pallas kernels."""
    calls = [line for line in runner_hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for kernel in ("apply_write", "compact_slots"):
        assert any(f"jit({kernel})/pallas_call" in c for c in calls), kernel


def test_fleet_runner_updates_pools_in_place(runner_hlo):
    """No loop body that runs on every write, nor the pass-through branch
    of a cond in one, copies a page_map-, slot_lba- or valid-shaped array
    at Table 2 (a slot_lba copy alone would move 4.2 MB per write)."""
    assert per_write_pool_copies(runner_hlo, GEOM) == []


def test_fleet_runner_keeps_valid_in_one_dtype(runner_hlo):
    """No loop body, nor anything it calls, converts the whole valid pool
    between bool and int32: the scan carries it as the int32 the kernels
    update in place, so at Table 2 no write or erase pays a 1-MB round
    trip each way."""
    assert per_write_pool_converts(runner_hlo, GEOM) == []
