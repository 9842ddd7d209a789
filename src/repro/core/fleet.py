"""Fleet-scale batched SSD simulation: B drives in one jitted vmap(lax.scan),
shard_mapped over a 1-D drive-axis device mesh.

Where ``managers.simulate`` runs ONE drive per Python call, a fleet stacks
the per-drive state pytrees and runs every drive lock-step through the same
compiled write-step — per-drive differences (workload, seed, FDP assumption
arrays, allocation / GC / detector / movement policy, group-count caps, and
the §5.1 constants ``ewma_a`` / interval length) are traced data, so wolf,
wolf-dynamic, fdp and single-group drives — and EWMA/interval sweeps — batch
into one ``vmap``. This is the substrate for exploring policy × workload grids
("as many scenarios as you can imagine"): per-drive write streams are drawn
on device by ``workloads.sample_phases_device`` inside the jitted region, so
host work is O(B) setup, not O(B·T) sampling.

Execution architecture (core/fleet_exec.py owns the device side):

* **Partitioning** — drives are split into sub-batches by step STRUCTURE,
  the :func:`_part_key` of (detector, movement ops, dynamic groups,
  closed-form allocation, op stream): a vmapped ``lax.cond`` lowers to a
  select over both branches, so any machinery one drive of a sub-batch
  carries is machinery every drive of that sub-batch executes per step.
  Partitioning keeps the (G × bits) bloom filter pair, the §5.6
  GC-demotion scan, and the movement-op second drain out of the compiled
  step of drives that can never use them.
* **Sharding** — ``devices=`` runs each sub-batch as
  ``jit(shard_map(vmap(scan)))`` over the ``"drives"`` axis of
  :func:`repro.launch.mesh.drive_mesh`; each device scans its slice of the
  batch, bit-identical to the single-device vmap (no cross-drive ops, no
  collectives). A ragged sub-batch (size not a multiple of the device
  count) is padded with inert filler drives and the filler rows are
  dropped from every result: per-device wall-clock is ceil(B/n_dev) drive
  scans either way, so the pad only fills otherwise-idle lanes — padding
  is free, which is why it replaced the old divisor clamp that silently
  collapsed ragged sub-batches to 1 device. (The ``pmap(vmap(...))``
  executor this supersedes is fully removed: shard_map composes with jit —
  one dispatch, donated state buffers, one compilation cache.)
* **Pipelining** — sub-batches are DISPATCHED in one pass and RESOLVED in a
  second: jax dispatch is asynchronous, so while sub-batch k executes on
  the devices the host is already building (``build_drive``, stacking,
  padding) sub-batch k+1. Host-side construction overlaps device
  execution instead of serializing with it, which is where the old
  executor spent its host time on large grids.
* **Compile amortization** — per-sub-batch runners are memoized on
  (step structure × geometry × scan length × device count), so sweeps
  that revisit a structure compile once (entry points add the on-disk
  cache: ``repro.utils.compile_cache.enable``). On CPU, spawn virtual
  devices via :func:`repro.utils.hostdev.force_host_device_count` *before*
  the first jax import — the device count is locked at backend init (that
  is also why ``devices="auto"`` from a jax-already-imported entry point
  warns instead of silently running on 1 device).
* **Host spans** — each sub-batch's host work is wrapped in
  ``jax.profiler.TraceAnnotation`` spans, which land in a profiler trace
  on the same clock as the device ops: ``fleet.build`` (the drive loop:
  ``build_drive``, contexts, policies, params, streams), ``fleet.stack``
  (stacking, host-to-device copies, filler padding), ``fleet.dispatch``
  (runner lookup and call; a memo miss compiles here) and
  ``fleet.resolve`` (the blocking host copy of the traces and the trim of
  filler rows). Without an active profiler they cost nothing.

Degraded drives are inert lanes, like filler drives: the fault-injection
layer (see simulator.py's fault section) freezes a drive whose spare pool
is exhausted via the traced ``drive_status`` + halt guard — every later op
is a counted no-op on frozen-valid state. That is exactly the mechanism
the mesh padding above uses for ragged sub-batches (a filler drive is a
replicated row whose results are dropped), so a drive dying mid-scan never
poisons its vmapped/shard_mapped sub-batch: survivors' lanes are
elementwise untouched (tests/test_faults.py pins survivors bit-identical
to running them alone), and the dead lane keeps producing valid (frozen)
buffers until the scan ends. ``FleetResult.drive_status()`` /
``retired_fraction()`` / ``time_to_degraded()`` / ``wa_vs_lifetime()``
report the survival story per drive.

Geometry is shared at the SHAPE level (array sizes: blocks, pages/block,
logical span, group slots); within that shape, drives vary utilization and
locality through their phase mix (e.g. a zero-probability cold tail emulates
a shorter logical span at identical state shapes) — and, as of the op-stream
engine, through TRIMs: drives whose phases carry ``trim_probs`` run the
WRITE/TRIM dispatch step in their own sub-batch (``_part_key``), so one
fleet sweeps utilization × trim-rate × policy while pure-write drives keep
their exact historical streams and step. ``FleetResult.trim_fraction()`` /
``predicted_wa()`` read the carried effective-utilization counters for the
Frankie-style effective-OP analytics.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fleet_exec import (
    SubbatchFailure,
    SubbatchResolutionError,
    pad_batch,
    resolve_devices,
    split_step,
    subbatch_runner,
)
from repro.core.managers import RunResult, build_drive
from repro.core.simulator import SimContext, policy_from_config
from repro.core.ssd import Geometry, ManagerConfig, SimState
from repro.core.workloads import Phase, phase_param_arrays

# ManagerConfig fields that must agree fleet-wide: they are baked into the
# shared static SimContext (paper constants), not per-drive policy data.
# interval_frac and ewma_a are NOT here: the §5.1 constants ride in the
# traced per-drive policy pytree, so fleets can sweep them in one batch.
_SHARED_FIELDS = (
    "q_create", "w_intervals",
    "cold_hit_rate_frac", "cold_op_frac", "gc_reserve_blocks",
    "bloom_bits_per_page", "valve_max_tries", "bloom_rotate_min_writes",
    # the retry ladder depth is a static exponent in the compiled fault
    # hook (rate^(1+retries)), not traced policy data — see
    # simulator._erase_fault_retire; the RATES themselves are per-drive
    "erase_max_retries",
)


@dataclasses.dataclass(frozen=True)
class DriveSpec:
    """One drive of a fleet: a manager preset over a phase sequence."""

    mcfg: ManagerConfig
    phases: tuple[Phase, ...]
    seed: int = 0
    name: str | None = None

    @property
    def label(self) -> str:
        return self.name or f"{self.mcfg.name}#{self.seed}"


@dataclasses.dataclass
class FleetResult:
    app: np.ndarray  # [B, T // trace_every] cumulative application writes
    mig: np.ndarray  # [B, T // trace_every] cumulative migrations
    specs: list[DriveSpec]
    # (original drive indices, stacked SimState pytree) per sub-batch
    shards: list[tuple[list[int], SimState]]
    lbas: np.ndarray | None = None  # [B, T] when return_lbas=True
    geom: Geometry | None = None  # shared fleet geometry (analytics)
    trace_every: int = 1  # trace stride (RunResult.stride of every drive)
    # per-sub-batch executor report, aligned with .shards: drive count,
    # devices actually used, and filler-drive padding. With mesh padding a
    # ragged sub-batch uses min(requested devices, its drive count)
    # devices — the visible record of the effective shard count.
    exec_meta: list[dict] = dataclasses.field(default_factory=list)

    @property
    def devices_used(self) -> int:
        """Device count the fleet actually sharded over (max across
        sub-batches; 1 = pure single-device vmap)."""
        return max((m["devices"] for m in self.exec_meta), default=1)

    def state(self, i: int) -> SimState:
        """Final state pytree of drive i."""
        for idx, states in self.shards:
            if i in idx:
                pos = idx.index(i)
                return jax.tree_util.tree_map(lambda a: a[pos], states)
        raise IndexError(i)

    @property
    def states(self) -> SimState:
        """Stacked state pytree — only for single-shard (unpartitioned)
        fleets; mixed bloom/non-bloom fleets must use .state(i)."""
        assert len(self.shards) == 1, "mixed fleet: use .state(i)"
        return self.shards[0][1]

    def result(self, i: int) -> RunResult:
        """Per-drive view with the single-drive RunResult API."""
        return RunResult(
            self.app[i], self.mig[i], self.state(i), stride=self.trace_every
        )

    @property
    def wa_total(self) -> np.ndarray:
        """[B] end-to-end write amplification per drive."""
        return (self.app[:, -1] + self.mig[:, -1]) / np.maximum(
            self.app[:, -1], 1
        )

    def wa_curves(self, window: int = 2000) -> np.ndarray:
        """[B, K] windowed WA over time per drive."""
        return np.stack(
            [self.result(i).wa_curve(window) for i in range(len(self.specs))]
        )

    # -- closed-form analytics (paper eq. 3/5 + Frankie effective OP) -------

    def trim_fraction(self) -> np.ndarray:
        """[B] fraction of the logical span each drive holds TRIMMED at its
        final state (0.0 for pure-write drives) — read off the carried
        ``mapped_pages`` counter, no page_map reduction."""
        assert self.geom is not None, "fleet built without geometry"
        lba = self.geom.lba_pages
        return np.array([
            1.0 - float(self.state(i)["mapped_pages"]) / lba
            for i in range(len(self.specs))
        ])

    def predicted_wa(self) -> np.ndarray:
        """[B] closed-form model WA per drive at its final operating point.

        Each active group is treated as a uniform sub-SSD of EFFECTIVE
        logical size ``grp_live`` (mapped pages — trimmed pages act as
        dynamic over-provisioning, Frankie et al.) with over-provisioning
        ``grp_alloc·B − grp_live``, so its δ solves eq. 4 (≡ eq. 3 per
        group); the drive prediction is the frequency-weighted sum of the
        per-group WAs (eq. 5), weighted by the measured EWMA frequencies.
        A single-group pure-write drive degenerates to the plain eq. 3
        equilibrium model; a trimmed one to eq. 3 at the post-trim
        utilization (``effective_op_ratio``).
        """
        from repro.core.allocation import total_wa

        assert self.geom is not None, "fleet built without geometry"
        b = self.geom.pages_per_block
        out = np.zeros(len(self.specs))
        for i in range(len(self.specs)):
            st = self.state(i)
            active = np.asarray(st["grp_active"])
            s = np.asarray(st["grp_live"], np.float64)  # effective sizes
            op_x = np.asarray(st["grp_alloc"], np.float64) * b - s
            p = np.where(active, np.asarray(st["grp_p"], np.float64), 0.0)
            if p.sum() <= 0.0:  # no interval completed yet: weight by size
                p = np.where(active, s, 0.0)
            p = p / max(p.sum(), 1e-12)
            s_safe = np.where(active & (s > 0), s, 1.0)
            out[i] = float(
                total_wa(
                    jnp.asarray(s_safe, jnp.float32),
                    jnp.asarray(p, jnp.float32),
                    jnp.asarray(np.maximum(op_x, 0.0), jnp.float32),
                )
            )
        return out

    # -- wear / endurance analytics (per-block P-E counts) ------------------

    def wear_variance(self) -> np.ndarray:
        """[B] population variance of per-block erase counts, from the O(1)
        carried aggregates (no block-array reduction)."""
        from repro.core.analytics import wear_variance

        assert self.geom is not None, "fleet built without geometry"
        k = self.geom.n_blocks
        return np.array([
            float(wear_variance(
                self.state(i)["erase_total"],
                self.state(i)["erase_sq_total"], k,
            ))
            for i in range(len(self.specs))
        ])

    def wear_imbalance(self) -> np.ndarray:
        """[B] max/mean P-E ratio per drive (1.0 = perfectly level)."""
        from repro.core.analytics import wear_imbalance

        return np.array([
            float(wear_imbalance(self.state(i)["erase_count"]))
            for i in range(len(self.specs))
        ])

    def lifetime_dwpd(self, *, pe_cycles: float = 3000.0,
                      years: float = 5.0) -> np.ndarray:
        """[B] sustainable drive-writes-per-day over a warranty window,
        projecting each drive's measured WA and wear imbalance onto a NAND
        P-E budget (default 3k cycles, TLC-class)."""
        from repro.core.analytics import (
            dwpd_from_lifetime,
            lifetime_host_writes,
        )

        assert self.geom is not None, "fleet built without geometry"
        host = lifetime_host_writes(
            n_blocks=self.geom.n_blocks,
            pages_per_block=self.geom.pages_per_block,
            pe_cycles=pe_cycles,
            wa=jnp.asarray(self.wa_total, jnp.float32),
            imbalance=jnp.asarray(self.wear_imbalance(), jnp.float32),
        )
        return np.asarray(dwpd_from_lifetime(
            host, lba_pages=self.geom.lba_pages, years=years
        ))

    # -- survival / endurance analytics (fault-injection layer) -------------

    def drive_status(self) -> np.ndarray:
        """[B] traced drive status at the final state: 0 = STATUS_OK,
        1 = STATUS_DEGRADED (spares exhausted or pool death — the drive
        froze into an inert lane; see simulator._erase_fault_retire)."""
        return np.array([
            int(self.state(i)["drive_status"])
            for i in range(len(self.specs))
        ])

    def retired_fraction(self) -> np.ndarray:
        """[B] fraction of each drive's physical blocks in the terminal
        RETIRED state — the capacity the §5.5 allocator has lost (0.0 for
        fault-free drives)."""
        assert self.geom is not None, "fleet built without geometry"
        k = self.geom.n_blocks
        return np.array([
            float(self.state(i)["retired_blocks"]) / k
            for i in range(len(self.specs))
        ])

    def time_to_degraded(self) -> np.ndarray:
        """[B] application-write index at which each drive degraded, or -1
        for drives still in service at the end of the run — the fleet's
        time-to-failure curve (plot survival with
        ``analytics.survival_fraction``)."""
        return np.array([
            int(self.state(i)["degraded_at"])
            for i in range(len(self.specs))
        ])

    def wa_vs_lifetime(self, window: int = 2000) -> np.ndarray:
        """[B, K] windowed WA over each drive's lifetime, NaN once the
        drive is degraded (frozen windows complete no application writes)
        — the WA-vs-lifetime curve of the aging study
        (``analytics.wa_vs_lifetime`` computes one drive's curve)."""
        from repro.core.analytics import wa_vs_lifetime

        return np.stack([
            wa_vs_lifetime(
                self.app[i], self.mig[i], window=window,
                stride=self.trace_every,
            )
            for i in range(len(self.specs))
        ])

    def model_error(self, window: int = 2000, tail: int = 3,
                    pred: np.ndarray | None = None) -> np.ndarray:
        """[B] relative error of the eq. 3/5 prediction vs the simulated
        equilibrium WA (mean of the last ``tail`` windows per drive).
        The prediction consumes each drive's effective (post-trim)
        utilization — ``grp_live``/``grp_alloc`` at the final state — so
        trimmed and pure-write drives are judged by the same model.

        pred: pass a precomputed :meth:`predicted_wa` to avoid running the
        per-drive closed-form pass twice.
        """
        if pred is None:
            pred = self.predicted_wa()
        measured = np.array([
            float(np.mean(self.result(i).wa_curve(window)[-tail:]))
            for i in range(len(self.specs))
        ])
        return (pred - measured) / np.maximum(measured, 1e-12)


def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _spec_has_trim(s: DriveSpec) -> bool:
    return any(ph.has_trim for ph in s.phases)


def _part_key(s: DriveSpec) -> tuple[str, bool, bool, bool, bool]:
    """Sub-batch partition key: step STRUCTURE a drive's compiled scan must
    carry. A vmapped lax.cond lowers to a select over both branches, so
    machinery any drive of a sub-batch carries is machinery every drive of
    that sub-batch executes per step. Keying on (detector, movement ops,
    dynamic groups, closed-form allocation, op stream) keeps the [G, bits]
    filter pair and §5.6 demotion machinery out of static-detector drives,
    the movement-op compaction (a second full GC drain per step) out of
    fdp/single-style drives, the §5.2/eq.-8 interval machinery (two
    argsorts + an 80-iteration bisection per interval) out of drives that
    never run it, and the WRITE/TRIM dispatch (plus its per-drive §5.1
    interval predicate) out of pure-write drives — which also keeps their
    device-sampled streams bit-identical to the pre-op-stream engine. The
    detector is part of the key, so every sub-batch is td-homogeneous and
    the simulator dispatches it at trace time."""
    return (
        s.mcfg.td_mode,
        s.mcfg.movement_ops,
        s.mcfg.dynamic_groups,
        s.mcfg.alloc_mode in ("wolf", "optimal", "fdp_assumed"),
        _spec_has_trim(s),
    )


def simulate_fleet(
    geom: Geometry,
    specs: list[DriveSpec],
    *,
    sampler: str = "jax",
    init_p_from_phase: bool = True,
    return_lbas: bool = False,
    devices: int | str | None = None,
    gc_impl: str = "bulk",
    fast_path: bool | None = None,
    trace_every: int = 1,
    unroll: int = 1,
    ops_stream: bool | None = None,
) -> FleetResult:
    """Run B independent drives in a single jitted vmap(lax.scan).

    sampler: "jax" draws every write stream on device inside the jitted
    region (fast path); "numpy" replays the exact host streams
    ``managers.simulate`` would draw for the same (phases, seed) — the two
    paths then agree elementwise, which tests/test_fleet.py asserts.

    ops_stream: None (default) routes each drive through the op-stream
    engine iff its phases carry TRIMs (the partition key separates them,
    so pure-write drives keep their exact historical streams and step);
    True forces EVERY drive through the op engine — with the numpy
    sampler the events are then draw-for-draw identical on pure-write
    phases, the bit-compatibility anchor of tests/test_write_engine.py.

    devices: None/1 = pure single-device vmap; "auto" = shard over all
    jax.devices(); int = shard over exactly that many (more than are
    visible raises). Every sub-batch — ragged or not — uses the resolved
    device count, or its drive count if smaller: ragged sub-batches are
    padded with inert filler drives (free: the pad fills otherwise-idle
    lanes) and the filler rows never surface in results.
    ``FleetResult.exec_meta`` records drives/devices/padding and the step
    engine (``"split"`` or ``"single"``) per sub-batch. Results are
    bit-identical across device counts. NOTE: on CPU the visible device
    count is locked at jax backend init — see
    ``repro.utils.hostdev.force_host_device_count``.

    gc_impl: GC drain implementation ("bulk" | "reference"), threaded to
    SimContext — the bulk-vs-reference equivalence suite runs whole fleets
    under both.

    fast_path: step engine. None (default) picks it per sub-batch by its
    lanes per device (``fleet_exec.split_step``): a device holding one
    drive scans it without vmap and takes the split step, whose heavy
    tail (GC, valve, movement, §5.1 interval) is a real branch entered
    only when an O(1) predicate trips; vmapped lanes take the single-path
    step, since under vmap a lax.cond runs BOTH branches and selects.
    True / False force the split / single-path step everywhere. Both
    engines are elementwise-identical (tests/test_write_engine.py), so
    this is a pure scheduling knob; ``exec_meta`` records the engine of
    each sub-batch. trace_every / unroll: trace stride and scan unroll
    (simulator.scan_writes); trace_every must divide n_total, and app/mig
    come back [B, n_total // trace_every].

    Every spec must issue the same total number of writes (one shared scan).
    """
    assert specs, "empty fleet"
    if sampler not in ("jax", "numpy"):
        raise ValueError(f"unknown sampler {sampler!r}")
    if ops_stream is False:  # mirror managers.simulate: fail loudly
        assert not any(_spec_has_trim(s) for s in specs), (
            "specs carry TRIMs: ops_stream=False is not available"
        )
    totals = {sum(ph.n_writes for ph in s.phases) for s in specs}
    assert len(totals) == 1, f"drives must issue equal write totals: {totals}"
    n_total = totals.pop()
    assert n_total % trace_every == 0, (n_total, trace_every)
    base = specs[0].mcfg
    for s in specs:
        for f in _SHARED_FIELDS:
            assert getattr(s.mcfg, f) == getattr(base, f), (
                f"fleet drives must share ManagerConfig.{f} "
                "(a static paper constant)"
            )
    n_dev = resolve_devices(devices)
    p_max = max(len(s.phases) for s in specs)
    g_wl = max(len(ph.sizes) for s in specs for ph in s.phases)

    def part_key(s: DriveSpec):
        key = _part_key(s)
        if ops_stream:  # force every drive onto the op engine
            key = key[:-1] + (True,)
        return key

    partitions: list[tuple[tuple, list[int]]] = []
    for key in sorted({part_key(s) for s in specs}):
        partitions.append(
            (key, [i for i, s in enumerate(specs) if part_key(s) == key])
        )

    n_trace = n_total // trace_every
    app = np.zeros((len(specs), n_trace), np.int32)
    mig = np.zeros((len(specs), n_trace), np.int32)
    lbas_out = np.zeros((len(specs), n_total), np.int32) if return_lbas else None
    shards, pending, exec_meta = [], [], []
    for key, idx in partitions:
        td_mode, use_movement, use_dynamic, use_closed, with_trim = key
        use_bloom = td_mode == "bloom"
        can_demote = td_mode != "static"
        sub = [specs[i] for i in idx]
        # faults are traced per-drive DATA (rates/limits/seeds ride in
        # policy), deliberately NOT a _part_key dimension: a faulty drive
        # and a fault-free one share a compiled sub-batch, and the fault
        # machinery is traced in only when some drive of the sub-batch can
        # actually fail an erase — all-zero-rate sub-batches keep the
        # exact fault-free step structure (bit-identity, tests/test_faults)
        with_faults = any(s.mcfg.has_faults for s in sub)
        # group-cap padding is PER PARTITION: bloom filter width scales with
        # 1/max_groups, so padding a bloom drive beyond its sub-batch's own
        # cap would change its hashes vs the standalone managers.simulate
        g_max = max(s.mcfg.max_groups for s in sub)
        # per-drive interval lengths force the traced-h predicate (per-step
        # selects of the §5.1 machinery under vmap); homogeneous sub-batches
        # keep the scalar fast path
        per_drive_interval = (
            len({s.mcfg.interval_frac for s in sub}) > 1
        )
        # mesh dispatch: every sub-batch uses the full resolved device
        # count; raggedness is absorbed by inert filler drives (per-device
        # wall-clock is ceil(B/d) scans with or without the pad), and the
        # lanes each device scans pick the step engine. Dispatch is async —
        # the runner call returns once enqueued, so the next iteration's
        # host-side build_drive/stacking overlaps this sub-batch's device
        # execution (the pipeline).
        d = min(n_dev, len(sub))
        pad = (-len(sub)) % d
        split = split_step(fast_path, (len(sub) + pad) // d)
        with jax.profiler.TraceAnnotation("fleet.build"):
            sts, policies, page_rates, params, streams = [], [], [], [], []
            page_groups = []
            n_groups_max = 1
            for s in sub:
                st, n_groups, assumed_p, fdp_rate, rates, pg0 = build_drive(
                    geom, s.mcfg, list(s.phases),
                    init_p_from_phase=init_p_from_phase,
                    g_max=g_max, use_bloom=use_bloom,
                )
                page_groups.append(pg0)
                n_groups_max = max(n_groups_max, n_groups)
                ctx_d = SimContext(
                    geom, dataclasses.replace(s.mcfg, max_groups=g_max),
                    n_groups, use_bloom=use_bloom,
                    use_movement=use_movement, can_demote=can_demote,
                    use_dynamic=use_dynamic, use_closed_alloc=use_closed,
                    with_faults=with_faults,
                )
                policy = policy_from_config(ctx_d, assumed_p, fdp_rate)
                # the drive keeps its OWN dynamic-group cap in the padded
                # arrays
                policy["max_groups"] = jnp.asarray(
                    s.mcfg.max_groups, jnp.int32)
                sts.append(st)
                policies.append(policy)
                page_rates.append(
                    np.concatenate(
                        [rates,
                         np.zeros((p_max - len(rates),) + rates.shape[1:],
                                  rates.dtype)]
                    )
                )
                params.append(
                    phase_param_arrays(list(s.phases), g_max=g_wl, p_max=p_max)
                )
                if sampler == "numpy" and with_trim:
                    # exact host op streams (Phase.sample_ops: pure-write
                    # phases consume the draws Phase.sample would)
                    rng = np.random.default_rng(s.seed)
                    pairs = [ph.sample_ops(rng) for ph in s.phases]
                    streams.append((
                        jnp.asarray(np.concatenate([o for o, _ in pairs]),
                                    jnp.int32),
                        jnp.asarray(np.concatenate([l for _, l in pairs]),
                                    jnp.int32),
                    ))
                elif sampler == "numpy":
                    rng = np.random.default_rng(s.seed)
                    streams.append(
                        jnp.asarray(
                            np.concatenate(
                                [ph.sample(rng) for ph in s.phases]),
                            jnp.int32,
                        )
                    )
                else:
                    # key on the seed ALONE, mirroring the numpy sampler: a
                    # drive's stream is a function of (phases, seed), never of
                    # its position in the specs list (same seed + same phases
                    # → common random numbers for paired policy comparisons)
                    streams.append(jax.random.PRNGKey(s.seed))

            ctx = SimContext(
                geom,
                # the shared ctx keeps the SUB-BATCH's interval_frac so ctx.h
                # (the scalar predicate) is exact on the homogeneous fast path;
                # td_mode/movement/dynamic/alloc mirror the partition key (the
                # simulator dispatches the detector and the interval machinery
                # from these statics at trace time)
                dataclasses.replace(
                    base, name="fleet", max_groups=g_max,
                    interval_frac=sub[0].mcfg.interval_frac,
                    movement_ops=use_movement, td_mode=td_mode,
                    dynamic_groups=use_dynamic,
                    alloc_mode=sub[0].mcfg.alloc_mode,
                    # normalize per-drive fault knobs out of the shared ctx:
                    # rates/limits/seeds are traced policy data, so the
                    # memoized runner key must depend only on with_faults
                    # (structure) and erase_max_retries (shared static),
                    # never on which rates this particular fleet happens to
                    # sweep
                    fault_rate=0.0, fault_rate_worn=1.0,
                    endurance_pe_limit=0, spare_blocks=None, fault_seed=0,
                ),
                n_groups_max,
                use_bloom=use_bloom,
                gc_impl=gc_impl,
                per_drive_interval=per_drive_interval,
                fast_path=split,
                use_movement=use_movement,
                can_demote=can_demote,
                use_dynamic=use_dynamic,
                use_closed_alloc=use_closed,
                trace_every=trace_every,
                unroll=unroll,
                with_trim=with_trim,
                with_faults=with_faults,
            )
        with jax.profiler.TraceAnnotation("fleet.stack"):
            args = (
                _stack(sts),
                _stack(streams),
                {k: jnp.asarray(np.stack([p[k] for p in params]))
                 for k in params[0]},
                jnp.asarray(np.stack(page_rates)),
                jnp.asarray(np.stack(page_groups)),
                _stack(policies),
            )
            if pad:
                args = pad_batch(args, pad)
        # a runner memo miss compiles inside this span
        with jax.profiler.TraceAnnotation("fleet.dispatch"):
            runner = subbatch_runner(ctx, n_total, sampler == "jax", d)
            pending.append((key, idx, runner(*args), pad))
        exec_meta.append({"drives": len(sub), "devices": d, "padding": pad,
                          "engine": "split" if split else "single"})

    # resolve pass: block on each sub-batch's outputs (host↔device transfer
    # happens here, after every sub-batch has been enqueued) and strip the
    # filler rows so padding never surfaces. Resolution is fenced PER
    # sub-batch: a failure (device OOM, a poisoned buffer, a runtime error
    # deferred by async dispatch) is recorded with its sub-batch index,
    # partition key, and drive ids, and the REMAINING sub-batches still
    # resolve — one bad sub-batch no longer orphans the others' already-
    # dispatched work or surfaces as a context-free traceback.
    failures: list[SubbatchFailure] = []
    for k_i, (key, idx, out, pad) in enumerate(pending):
        try:
            with jax.profiler.TraceAnnotation("fleet.resolve"):
                st_f, trace, lbas = out
                b = len(idx)
                app[idx], mig[idx] = (
                    np.asarray(trace[0][:b]), np.asarray(trace[1][:b])
                )
                if return_lbas:
                    lbas_out[idx] = np.asarray(lbas[:b])
                if pad:
                    st_f = jax.tree_util.tree_map(lambda a: a[:b], st_f)
                shards.append((idx, st_f))
        except Exception as e:  # noqa: BLE001 — rewrapped with context below
            failures.append(SubbatchFailure(
                subbatch=k_i, part_key=key, drive_ids=tuple(idx),
                labels=tuple(specs[i].label for i in idx), error=e,
            ))
    if failures:
        raise SubbatchResolutionError(failures, n_subbatches=len(pending))

    return FleetResult(
        app=app, mig=mig, specs=list(specs), shards=shards, lbas=lbas_out,
        geom=geom, trace_every=trace_every, exec_meta=exec_meta,
    )
