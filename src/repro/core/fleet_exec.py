"""Fleet execution backend: shard_map over a 1-D drive-axis mesh.

This module owns everything between ``simulate_fleet``'s per-sub-batch
arrays and the devices:

* **Sharding** — each sub-batch runs as ``jit(shard_map(vmap(run_one)))``
  over :func:`repro.launch.mesh.drive_mesh`'s ``"drives"`` axis. Drives are
  independent lanes, so each device executes the plain vmapped scan over
  its slice of the batch; numerics are bit-identical to the single-device
  ``jit(vmap(...))`` path (which remains the ``n_dev == 1`` special case —
  no mesh, no collective, same trace). The legacy ``pmap(vmap(...))``
  executor is gone: shard_map composes with jit (one dispatch, donation,
  the compilation cache) where pmap was its own retired code path.

* **Lanes per device pick the scan and the step** (:func:`split_step`).
  A device holding one drive scans it without the vmap, so the step's
  conditionals are real branches, and it builds the split step
  (``SimContext.fast_path=True``): a write runs the GC / valve / movement
  / interval tail only when an O(1) predicate says it may need it. Under
  vmap a cond is a select that runs both branches, so a vmapped
  sub-batch keeps the single-path step. Both engines give the same
  values, element for element.

* **Padding** — a sub-batch whose size is not a multiple of the device
  count is padded with inert filler drives (copies of the sub-batch's
  drive 0) up to the next multiple, and the filler rows are dropped before
  results surface. Wall-clock per device is ``ceil(B / n_dev)`` drive
  scans, so the pad fills lanes that would otherwise sit idle — padding is
  never slower than shrinking the shard count, which is why the old
  "largest divisor of B" clamp (which silently collapsed ragged sub-batches
  to 1 device) is gone.

* **Donation** — the stacked drive state (the dominant buffer,
  O(fleet size) many block/page arrays) is donated into the jitted scan,
  so the executor holds one copy per sub-batch in flight instead of
  input + output: peak state memory stays O(B/n_dev) per device. On
  backends without input-output aliasing (older XLA:CPU) donation is a
  silent no-op — correctness is unaffected either way because callers
  never reuse the stacked input.

* **Compiled-step cache** — runners are memoized on
  ``(SimContext, scan length, sampler kind, n_dev)``. The SimContext of a
  sub-batch is a pure function of its ``_part_key``, the step engine its
  lanes per device pick (plus geometry and the fleet-shared constants),
  so a one-lane and a many-lane sub-batch never share a runner, and
  sweep grids that revisit a step structure —
  e.g. the same policy grid at a new seed set, or bench scaling curves —
  reuse the jitted runner and pay XLA compilation once per structure.
  :func:`step_cache_stats` exposes hit/miss counters (asserted by
  tests/test_fleet_mesh.py). Entry points that want the compiles to
  survive the process call :func:`repro.utils.compile_cache.enable`.
"""

from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.core.simulator import SimContext, make_step, scan_writes
from repro.core.workloads import sample_phases_device
from repro.launch.mesh import drive_mesh
from repro.utils.hostdev import host_device_flag


@dataclasses.dataclass
class SubbatchFailure:
    """One failed sub-batch resolution (see SubbatchResolutionError)."""

    subbatch: int            # dispatch-order index of the sub-batch
    part_key: tuple          # its fleet._part_key (step structure)
    drive_ids: tuple         # original spec indices of its drives
    labels: tuple            # DriveSpec.label per drive
    error: Exception         # the underlying exception, unchanged

    def __str__(self) -> str:
        return (
            f"sub-batch {self.subbatch} (part_key={self.part_key}, "
            f"drives={list(self.drive_ids)}, labels={list(self.labels)}): "
            f"{type(self.error).__name__}: {self.error}"
        )


class SubbatchResolutionError(RuntimeError):
    """Raised by ``simulate_fleet`` when one or more sub-batches failed to
    resolve. Dispatch is asynchronous, so a device-side error (OOM, a
    poisoned buffer, a runtime failure) only surfaces when the host blocks
    on the outputs — this wrapper pins each failure to its sub-batch
    index, ``_part_key``, and drive ids, and is raised only AFTER every
    healthy sub-batch has resolved (their work is never orphaned; the
    partial results are simply not returned). ``failures`` holds one
    :class:`SubbatchFailure` per failed sub-batch."""

    def __init__(self, failures: list[SubbatchFailure], *,
                 n_subbatches: int):
        self.failures = list(failures)
        self.n_subbatches = n_subbatches
        detail = "\n  ".join(str(f) for f in self.failures)
        super().__init__(
            f"{len(self.failures)}/{n_subbatches} fleet sub-batches failed "
            f"to resolve:\n  {detail}"
        )


def resolve_devices(devices: int | str | None) -> int:
    """Resolve ``simulate_fleet``'s ``devices=`` argument to a device count.

    None/1 = single device; ``"auto"`` = every visible jax device; an int
    (or numeric string) asks for exactly that many, and more than are
    visible is an error, never a quiet smaller mesh. On CPU the
    visible count is an IMPORT-ORDER property — jax locks it at first
    backend init, so ``"auto"`` from an entry point that imported jax
    before setting ``--xla_force_host_platform_device_count`` sees 1
    device. Call :func:`repro.utils.hostdev.force_host_device_count`
    before the first jax import; this resolver warns about the trap rather
    than silently degrading.
    """
    if devices in (None, 1):
        return 1
    n_avail = len(jax.devices())
    if devices == "auto":
        if n_avail == 1 and jax.default_backend() == "cpu" \
                and host_device_flag() is None:
            warnings.warn(
                'devices="auto" sees a single CPU device: jax was '
                "initialized before --xla_force_host_platform_device_count "
                "was set. Call repro.utils.hostdev.force_host_device_count "
                "before the first jax import to shard across cores.",
                RuntimeWarning,
                stacklevel=3,
            )
        return n_avail
    n = int(devices)
    if not 1 <= n <= n_avail:
        raise ValueError(
            f"devices={devices!r}: {n_avail} jax device(s) visible "
            f"({jax.default_backend()})"
        )
    return n


def pad_batch(tree, pad: int):
    """Append ``pad`` filler rows to every leaf's leading (drive) axis by
    replicating row 0. Lanes are independent, so fillers change nothing for
    real drives; callers drop the filler rows from every output."""
    if pad == 0:
        return tree
    return jax.tree_util.tree_map(
        lambda a: jnp.concatenate(
            [a, jnp.broadcast_to(a[:1], (pad,) + a.shape[1:])], axis=0
        ),
        tree,
    )


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0


_RUNNERS: dict[tuple, object] = {}
_STATS = CacheStats()


def step_cache_stats() -> CacheStats:
    """In-process compiled-runner memo counters (a copy)."""
    return dataclasses.replace(_STATS)


def step_cache_clear() -> None:
    """Drop the runner memo and zero the counters (tests only — the
    underlying jax jit caches are left alone)."""
    _RUNNERS.clear()
    _STATS.hits = 0
    _STATS.misses = 0


def split_step(fast_path: bool | None, lanes: int) -> bool:
    """The step engine of a sub-batch whose devices scan ``lanes`` drives
    each: the split step (True) for one lane, which the runner scans
    without vmap, the single-path step (False) for vmapped lanes.
    ``fast_path`` True or False overrides the choice."""
    return lanes == 1 if fast_path is None else fast_path


def subbatch_runner(ctx: SimContext, n_total: int, on_device_sampler: bool,
                    n_dev: int):
    """Compiled runner for one sub-batch, memoized on its step structure.

    The returned callable maps stacked per-drive args (leading axis B,
    a multiple of ``n_dev``) to ``(final_state, (app, mig), lbas)``. The
    state argument is donated. Dispatch is asynchronous: the call returns
    as soon as the computation is enqueued, so the host can build the next
    sub-batch while this one executes (simulate_fleet's pipeline).
    """
    key = (ctx, n_total, on_device_sampler, n_dev)
    fn = _RUNNERS.get(key)
    if fn is not None:
        _STATS.hits += 1
        return fn
    _STATS.misses += 1

    def run_one(st, stream, params, page_rate, page_group0, policy):
        ops = None
        if on_device_sampler:
            with jax.named_scope("ssd.sampler"):
                if ctx.with_trim:
                    ops, lbas = sample_phases_device(
                        stream, params, n_total, with_ops=True
                    )
                else:
                    lbas = sample_phases_device(stream, params, n_total)
        elif ctx.with_trim:
            ops, lbas = stream
        else:
            lbas = stream
        cum = jnp.cumsum(params["counts"])

        def rate_fn(s, lba, t):
            # t is the shared EVENT clock (== write clock for pure-write
            # sub-batches); phase boundaries are event counts either way
            ph = jnp.minimum(
                jnp.searchsorted(cum, t, side="right"), cum.shape[0] - 1
            )
            return page_rate[ph, lba]

        step = make_step(ctx, policy, rate_fn, page_group0)
        ts = jnp.arange(n_total, dtype=jnp.int32)
        st, trace = scan_writes(ctx, step, st, lbas, ts, ops)
        return st, trace, lbas

    def batched(*args):
        # one lane per device: scan it unbatched, so every lax.cond of the
        # step is a real branch taken only when needed; the same lane
        # count gave ctx the split step (split_step). Under vmap a cond is
        # a select that runs both branches on every write, and ctx holds
        # the single-path step. Same values either way.
        if jax.tree_util.tree_leaves(args)[0].shape[0] == 1:
            out = run_one(*jax.tree_util.tree_map(lambda a: a[0], args))
            return jax.tree_util.tree_map(lambda a: a[None], out)
        return jax.vmap(run_one)(*args)

    if n_dev > 1:
        spec = PartitionSpec("drives")
        # check_vma=False: the body is collective-free with fully
        # partitioned in/outs, so the varying-axes check has nothing to
        # prove
        batched = jax.shard_map(
            batched, mesh=drive_mesh(n_dev), in_specs=spec, out_specs=spec,
            check_vma=False,
        )
    fn = jax.jit(batched, donate_argnums=(0,))
    _RUNNERS[key] = fn
    return fn
