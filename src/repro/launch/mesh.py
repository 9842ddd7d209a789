"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — jax locks the device count on first backend
init, and only launch/dryrun.py is allowed to force 512 host devices.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16×16 = 256 chips per pod; 2 pods = 512 chips when multi_pod.

    The dry-run process exposes 512 placeholder devices; the single-pod mesh
    uses the first 256 of them."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    return jax.make_mesh(shape, axes, devices=jax.devices()[:n])


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """Arbitrary mesh (tests use small ones, elasticity re-meshes here)."""
    return jax.make_mesh(shape, axes)


def drive_mesh(n_dev: int) -> jax.sharding.Mesh:
    """1-D fleet mesh: ``n_dev`` devices along a single ``"drives"`` axis.

    The fleet executor (core/fleet_exec.py) shard_maps each sub-batch over
    this axis — drives are embarrassingly parallel, so the 1-D mesh is the
    whole topology story: on CPU the devices are virtual cores (see
    repro.utils.hostdev), on an accelerator they are chips, and a multi-pod
    fleet is just a longer axis. Kept here, beside the production meshes,
    so every mesh the repo builds goes through one module.
    """
    devs = jax.devices()
    assert 1 <= n_dev <= len(devs), (n_dev, len(devs))
    # Auto axis: the executor's shard_map body is plain per-device code
    # (jax.make_mesh defaults to Explicit axes, whose sharding-in-types
    # rules reject the step's gathers)
    return jax.make_mesh(
        (n_dev,), ("drives",), devices=devs[:n_dev],
        axis_types=(jax.sharding.AxisType.Auto,),
    )


def mesh_devices(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n
