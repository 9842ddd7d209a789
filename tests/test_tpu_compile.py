"""Compile the simulator's Pallas kernels for a TPU v5e that is described,
not attached, at the paper's Table 2 width (8 LUNs × 1,024 blocks × 128
pages, LBA/PBA 0.7).

Off-TPU the simulator dispatches to the flat XLA lowerings, so no CPU test
of the whole step ever reaches these kernels; this file asks the TPU
compiler directly, unbatched and under ``vmap`` over a fleet of 8 drives,
and checks the kernel is in the compiled program (``tpu_custom_call``).
Nothing runs: a pass here says the chip's compiler accepts the kernels
(VMEM limits, tiling), not that they are correct or fast.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library at a time.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.wolf_ssd import CONFIG
from repro.kernels.gc_compact.kernel import compact_slots
from repro.kernels.write_path.kernel import apply_trim, apply_write

GEOM = CONFIG.geometry()
K, B, LBA = GEOM.n_blocks, GEOM.pages_per_block, GEOM.lba_pages
FLEET = 8


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
