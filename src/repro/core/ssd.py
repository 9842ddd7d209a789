"""SSD geometry + simulator state (paper §3 system model).

The simulator is WRITE-AMPLIFICATION-faithful, not timing-faithful: every
figure in the paper reports WA (migrations per application write), which is
what we reproduce. Consequences (documented in DESIGN.md):

  * LUNs are kept as a static label (they set Wolf's F = LUNs·B minimum group
    size) but placement/victim search are pool-global — per-LUN victim search
    changes victim-search COST, not WA (§5.4).
  * channel timing / virtual time is out of scope.

State is a :class:`SimState` — a frozen dataclass registered as a JAX
pytree, so the whole simulator jits, vmaps, checkpoints, and scans. The
logical→physical mapping is ONE packed int32 array (``page_map``,
``blk * pages_per_block + slot``, ``-1`` = unmapped), so every lookup,
invalidate, and write touches a single gather/scatter instead of two.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

FREE, OPEN, CLOSED = 0, 1, 2
# terminal block state: an erase failed past its retry budget and the block
# was pulled from circulation. A RETIRED block KEEPS its group_of label (so
# the per-group retired accounting survives §5.2 merges) but is excluded
# from every FREE/CLOSED mask — it can never be claimed, written, or
# selected as a GC victim again.
RETIRED = 3
# traced drive_status values: a drive whose spare pool is exhausted flips
# to DEGRADED (read-only/halted — every subsequent op freezes as a no-op)
# instead of violating the pool invariants.
STATUS_OK, STATUS_DEGRADED = 0, 1
INT32_MAX = 2**31 - 1


def surplus_of(grp_active, grp_phys, grp_alloc):
    """Masked per-group block surplus (the carried ``SimState.grp_surplus``).

    Inactive groups sit at -INT32_MAX so the movement-op argmax never picks
    them. Recomputed (an O(G) elementwise op) at every site that touches
    ``grp_phys``/``grp_alloc``/``grp_active`` rather than patched per index —
    G is tiny and one formula can't drift from the invariant.
    """
    return jnp.where(
        grp_active, grp_phys - grp_alloc, -INT32_MAX
    ).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Physical geometry. Defaults: a scaled-down Table-2 SSD (ratios kept)."""

    n_luns: int = 8
    blocks_per_lun: int = 64
    pages_per_block: int = 16
    lba_pba: float = 0.70

    @property
    def n_blocks(self) -> int:
        return self.n_luns * self.blocks_per_lun

    @property
    def pba_pages(self) -> int:
        return self.n_blocks * self.pages_per_block

    @property
    def lba_pages(self) -> int:
        return int(self.pba_pages * self.lba_pba)

    @property
    def op_pages(self) -> int:
        return self.pba_pages - self.lba_pages


# (α, β, γ, τ) victim-score weight points per gc_policy preset; see
# ManagerConfig.gc_weights. wear's β trades reclaim benefit (pages freed)
# against per-block P-E imbalance: 0.25 — a page of benefit per 4 cycles
# of wear skew — levels ~3× harder than greedy at single-digit-% WA cost;
# larger β overshoots (GC starts cleaning full cold blocks, churning
# erases faster than it levels them). Swept per-drive via gc_beta.
GC_WEIGHT_PRESETS = {
    "greedy": (1.0, 0.0, 0.0, 0.0),
    "lru": (0.0, 0.0, 1.0, 0.0),
    "wear": (1.0, 0.25, 0.0, 0.0),
    "trim_aware": (1.0, 0.0, 0.0, 1.0),
}


@dataclasses.dataclass(frozen=True)
class ManagerConfig:
    """Block-manager policy knobs. Presets in core/managers.py."""

    name: str = "wolf"
    max_groups: int = 8
    # over-provisioning allocation: wolf | fdp_assumed | size | freq |
    # optimal | single
    alloc_mode: str = "wolf"
    # victim-selection preset: greedy | lru | wear | trim_aware. Resolved by
    # :meth:`gc_weights` into the traced (α, β, γ, τ) score weights; the
    # explicit gc_* float fields below override individual components.
    gc_policy: str = "greedy"
    # multi-objective victim-score weights (None → take from the gc_policy
    # preset). The score, maximised over CLOSED blocks of the GC group:
    #   S(blk) = α·(B − live)  − γ·stamp  − β·erase_count  − τ·trim_dead
    # α: reclaim benefit, γ: migration-cost/recency (LRU), β: wear-leveling,
    # τ: trim-awareness (deprioritise blocks rich in trimmed-but-unerased
    # slots). These are per-drive TRACED data in the fleet runner — a batch
    # sweeps the weight space in one compiled grid.
    gc_alpha: float | None = None
    gc_beta: float | None = None
    gc_gamma: float | None = None
    gc_trim_penalty: float | None = None
    movement_ops: bool = True
    # temperature detection / page targeting:
    #   static  — page stays in its (workload-defined) group  [Wolf+oracle]
    #   fdp     — promote on update / demote on GC vs fixed assumed bands
    #   bloom   — two bloom filters per group (paper §5.6)
    td_mode: str = "static"
    dynamic_groups: bool = False  # create/merge groups (paper §5.2)
    # paper constants; interval_frac and ewma_a are lowered into the traced
    # per-drive policy pytree (fleet drives may sweep them — §5.1 knobs)
    interval_frac: float = 0.001  # h = LBA · 0.001
    ewma_a: float = 0.3
    q_create: float = 2.0
    w_intervals: int = 50
    cold_hit_rate_frac: float = 0.05
    cold_op_frac: float = 0.05
    gc_reserve_blocks: int = 2
    bloom_bits_per_page: int = 4
    # emergency-valve bound: max global greedy reclaims per write when the
    # pool is (nearly) empty (simulator.make_step's while_loop)
    valve_max_tries: int = 4
    # §5.6 bloom rotation floor: a group's filter pair rotates every
    # max(grp_size, this) writes, so tiny/fresh groups don't thrash
    bloom_rotate_min_writes: int = 64
    # -- fault injection / bad-block retirement (simulator erase sites) ----
    # Per-erase Bernoulli failure probability. A failed erase retries up to
    # erase_max_retries times; a block whose retries all fail is RETIRED
    # and replaced from the spare pool. fault_rate and the endurance knobs
    # are TRACED per-drive policy data — fleets sweep failure rates ×
    # endurance limits in one compiled grid (no step-structure change).
    fault_rate: float = 0.0
    # failure probability once a block's erase_count crosses the endurance
    # limit (the worn regime). Default 1.0: a block dies deterministically
    # at its P-E limit, the classic endurance-budget model — a worn rate
    # < 1 models the softer exponential tail instead.
    fault_rate_worn: float = 1.0
    # per-block P-E endurance limit; 0 disables the worn regime entirely
    endurance_pe_limit: int = 0
    # retry budget before a failing erase retires its block (shared static:
    # it shapes the retire probability rate^(1+retries), not the trace)
    erase_max_retries: int = 3
    # spare-block pool size; None = every physical block beyond the logical
    # content + GC reserve + group slots (the init_state auto bound). When
    # the pool exhausts, the next retirement flips drive_status to DEGRADED.
    spare_blocks: int | None = None
    # per-drive fault stream seed (traced policy data, like fault_rate)
    fault_seed: int = 0

    @property
    def has_faults(self) -> bool:
        """True iff this config can ever fail an erase — the fleet layer
        derives ``SimContext.with_faults`` (per sub-batch) from this."""
        return self.fault_rate > 0.0 or (
            self.endurance_pe_limit > 0 and self.fault_rate_worn > 0.0
        )

    def gc_weights(self) -> tuple:
        """Resolve the victim-score weights (α, β, γ, τ) for this drive.

        Starts from the :data:`GC_WEIGHT_PRESETS` entry for ``gc_policy``;
        any explicitly-set ``gc_alpha``/``gc_beta``/``gc_gamma``/
        ``gc_trim_penalty`` overrides its component. The legacy policies are
        exact weight points: greedy = (1,0,0,0) maximises ``B − live`` ≡
        minimises ``live``; lru = (0,0,1,0) minimises ``stamp`` — both with
        the same first-index tie-break as the old argmin branch.
        """
        base = GC_WEIGHT_PRESETS[self.gc_policy]
        over = (self.gc_alpha, self.gc_beta, self.gc_gamma,
                self.gc_trim_penalty)
        return tuple(
            float(b if o is None else o) for b, o in zip(base, over)
        )


def bloom_bits(geom: Geometry, mcfg: ManagerConfig) -> int:
    """Bits per group-filter for the §5.6 bloom detector pair."""
    return max(
        64, geom.lba_pages * mcfg.bloom_bits_per_page // mcfg.max_groups
    )


_SIM_STATE_FIELDS = (
    # page mapping (packed: blk * pages_per_block + slot, -1 = unmapped)
    "page_map",
    # block state
    "slot_lba", "valid", "live", "fill", "stamp", "state", "group_of",
    # wear / endurance (per-block P-E counts + O(1) carried aggregates)
    "erase_count", "trim_dead", "erase_total", "erase_sq_total",
    # per-group
    "active_blk", "grp_size", "grp_phys", "grp_p", "grp_writes",
    "grp_alloc", "grp_active", "grp_created", "grp_surplus", "grp_live",
    # O(1) accounting (incrementally maintained; see check_invariants)
    "free_blocks", "mapped_pages",
    # fault / retirement layer (bad-block management; see simulator.py)
    "retired_blocks", "spares_left", "grp_retired", "drive_status",
    "degraded_at", "n_erase_fail", "n_halted", "fault_draws",
    # detector (bloom filter pair)
    "bloom_active", "bloom_passive", "bloom_writes",
    # counters
    "n_app", "n_mig", "n_erase", "n_dropped", "n_trim", "clock",
    "interval", "cooldown",
)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=list(_SIM_STATE_FIELDS),
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class SimState:
    """Full drive state: a frozen, pytree-registered bundle of jnp arrays.

    Immutable by construction — state-mutating helpers build the successor
    state with :meth:`replace` (no ``dict(st)`` copies). Mapping-style read
    access (``st["live"]``, ``.items()``) is kept for analysis/tests code
    that iterates fields generically.
    """

    page_map: jax.Array  # [LBA] int32 packed physical address, -1 unmapped
    slot_lba: jax.Array  # [K, B] int32 lba living in each slot, -1 empty
    valid: jax.Array     # [K, B] bool (int32 0/1 in scan_writes' carry)
    live: jax.Array      # [K] int32 live pages per block
    fill: jax.Array      # [K] int32 written slots per block
    stamp: jax.Array     # [K] int32 LRU age (claim-time clock)
    state: jax.Array     # [K] int8 FREE/OPEN/CLOSED
    group_of: jax.Array  # [K] int32 owning group, -1 = none
    # wear/endurance layer: every erase site bumps erase_count[victim] and
    # the two carried aggregates (cross-checked in check_invariants), so
    # variance/imbalance analytics are O(1) reads, never reductions
    erase_count: jax.Array  # [K] int32 per-block P-E (erase) cycles
    # trimmed-but-unerased slots per block: +1 when a TRIM invalidates a
    # mapping in the block, reset to 0 when the block is erased. Feeds the
    # τ term of the victim score; always ≤ fill − live (dead slots)
    trim_dead: jax.Array  # [K] int32
    erase_total: jax.Array     # [] int32 == Σ erase_count == n_erase
    erase_sq_total: jax.Array  # [] int32 == Σ erase_count² (for variance)
    active_blk: jax.Array   # [G] int32 open block per group, -1 = none
    grp_size: jax.Array     # [G] int32 logical pages per group
    grp_phys: jax.Array     # [G] int32 physical blocks per group
    grp_p: jax.Array        # [G] float32 EWMA update frequency
    grp_writes: jax.Array   # [G] int32 writes this interval
    grp_alloc: jax.Array    # [G] int32 block budget (§5.5)
    grp_active: jax.Array   # [G] bool
    grp_created: jax.Array  # [G] int32 creation interval
    # carried block-surplus per group: grp_phys - grp_alloc where active,
    # -INT_MAX elsewhere — the movement-op argmax reads this directly
    grp_surplus: jax.Array  # [G] int32
    # carried per-group mapped-page count: == Σ live over the group's
    # blocks always, and — because group membership IS residence, so a
    # trimmed page belongs to no group — equal to ``grp_size`` by
    # construction (every mutation site applies the same delta to both).
    # Carried separately so the effective-size consumers (§5.5 allocator,
    # detector hit rates, fleet analytics) name the utilization counter
    # the TRIM model is stated in (Frankie et al., arXiv:1208.1794:
    # trimmed space is dynamic over-provisioning), and so
    # ``check_invariants`` cross-checks both update chains against the
    # ground truth independently.
    grp_live: jax.Array  # [G] int32
    # incrementally-maintained pool size: == (state == FREE).sum() always.
    # Every per-write predicate (GC low-pool, emergency valve, movement-op
    # headroom) is an O(1) read of this scalar; the only surviving full
    # reductions over block state are per-GC (victim search) or diagnostic
    # (check_invariants).
    free_blocks: jax.Array  # [] int32
    # incrementally-maintained drive utilization: == (page_map >= 0).sum()
    # always. TRIM decrements it, a write of an unmapped page increments
    # it; the effective-OP analytics (core/analytics.effective_op_ratio,
    # FleetResult.predicted_wa) read this scalar instead of reducing over
    # the logical span.
    mapped_pages: jax.Array  # [] int32
    # -- fault / retirement layer (bad-block management) --------------------
    # O(1) carried retirement accounting, cross-checked by check_invariants:
    # retired_blocks == (state == RETIRED).sum(); grp_retired[g] == retired
    # blocks still labeled group g (RETIRED blocks keep group_of, so the
    # counts relabel consistently through §5.2 merges); spares_left is the
    # remaining spare-block budget (each retirement draws one; at 0 the
    # NEXT retirement degrades the drive instead).
    retired_blocks: jax.Array  # [] int32 == (state == RETIRED).sum()
    spares_left: jax.Array     # [] int32 ≥ 0 always
    grp_retired: jax.Array     # [G] int32 retired blocks per group label
    # STATUS_OK until a retirement finds the spare pool empty, then
    # STATUS_DEGRADED forever: every subsequent op freezes as a no-op
    # (an inert lane under vmap — the fleet masks it like a filler drive)
    drive_status: jax.Array  # [] int32 STATUS_OK / STATUS_DEGRADED
    degraded_at: jax.Array   # [] int32 n_app at degradation, -1 = alive
    n_erase_fail: jax.Array  # [] int32 failed erase attempts (incl. retired)
    n_halted: jax.Array      # [] int32 ops frozen after degradation
    fault_draws: jax.Array   # [] uint32 fault-stream counter (hash input)
    bloom_active: jax.Array   # [G, bits] bool (§5.6); [G, 1] when unused
    bloom_passive: jax.Array  # [G, bits] bool
    bloom_writes: jax.Array   # [G] int32
    n_app: jax.Array      # [] int32 application writes
    n_mig: jax.Array      # [] int32 GC migrations
    n_erase: jax.Array    # [] int32 block erases
    n_dropped: jax.Array  # [] int32 dropped writes (pool exhausted; tested 0)
    n_trim: jax.Array     # [] int32 TRIM ops processed (incl. no-op re-trims)
    clock: jax.Array      # [] int32 block-claim clock (LRU)
    interval: jax.Array   # [] int32 completed §5.1 intervals
    cooldown: jax.Array   # [] int32 intervals until create/merge allowed

    def replace(self, **updates) -> "SimState":
        return dataclasses.replace(self, **updates)

    # -- read-only mapping conveniences (analysis / generic test code) ------
    def __getitem__(self, key: str) -> jax.Array:
        return getattr(self, key)

    def keys(self):
        return iter(_SIM_STATE_FIELDS)

    def items(self):
        return ((k, getattr(self, k)) for k in _SIM_STATE_FIELDS)

    # -- diagnostics --------------------------------------------------------
    def check_invariants(self) -> dict:
        """Full-reduction cross-checks of the O(1)/O(G) carried accounting.

        Returns a dict of named boolean jnp scalars (jit/vmap-friendly);
        :func:`assert_invariants` is the host-side raising wrapper. This is
        the ONLY place outside victim selection that still reduces over the
        whole block array — by design: the write path reads the carried
        scalars, and this checker proves they never drift.
        """
        k, b = self.slot_lba.shape
        arange_g = jnp.arange(self.grp_active.shape[0])
        # per-group physical block counts from scratch. A RETIRED block
        # keeps its group label for grp_retired accounting but is out of
        # circulation — grp_phys counts only OPEN/CLOSED blocks.
        owned = self.group_of[None, :] == arange_g[:, None]  # [G, K]
        in_service = (self.state[None, :] == OPEN) | (
            self.state[None, :] == CLOSED
        )
        phys = jnp.sum(owned & in_service, axis=1)
        # packed-map injectivity: every mapped lba names a distinct, valid
        # slot whose slot_lba points back at it
        pm = self.page_map
        mapped = pm >= 0
        pm_c = jnp.where(mapped, pm, k * b)
        hits = jnp.zeros(k * b + 1, jnp.int32).at[pm_c].add(1)
        back = jnp.where(
            mapped,
            self.slot_lba.reshape(-1)[jnp.minimum(pm_c, k * b - 1)]
            == jnp.arange(pm.shape[0]),
            True,
        )
        slot_valid = jnp.where(
            mapped,
            self.valid.reshape(-1)[jnp.minimum(pm_c, k * b - 1)],
            True,
        )
        return {
            "free_blocks": self.free_blocks == jnp.sum(self.state == FREE),
            "grp_phys": jnp.all(phys == self.grp_phys),
            "grp_surplus": jnp.all(
                self.grp_surplus
                == surplus_of(self.grp_active, self.grp_phys, self.grp_alloc)
            ),
            "grp_size": jnp.all(
                jnp.sum(
                    owned * self.live[None, :], axis=1
                ) == self.grp_size
            ),
            "grp_live": jnp.all(
                jnp.sum(
                    owned * self.live[None, :], axis=1
                ) == self.grp_live
            ),
            "mapped_pages": self.mapped_pages == jnp.sum(mapped),
            "page_map_injective": jnp.all(hits[: k * b] <= 1),
            "page_map_valid": jnp.all(slot_valid),
            "page_map_backptr": jnp.all(back),
            "live_counts": jnp.all(
                jnp.sum(self.valid, axis=1) == self.live
            ),
            "fill_bounds": jnp.all(
                (self.fill >= self.live) & (self.fill <= b)
            ),
            # wear accounting: the carried aggregates equal the reductions,
            # the per-block counters never go negative, and every erase
            # bumped exactly one block (Σ erase_count == n_erase)
            "erase_conservation": (
                (self.erase_total == jnp.sum(self.erase_count))
                & (self.erase_total == self.n_erase)
            ),
            "erase_sq_total": self.erase_sq_total
            == jnp.sum(self.erase_count * self.erase_count),
            "erase_nonneg": jnp.all(self.erase_count >= 0),
            # trim_dead counts a subset of each block's dead slots and is
            # cleared by erase — FREE blocks (fill == 0) sit at 0
            "trim_dead_bounds": jnp.all(
                (self.trim_dead >= 0)
                & (self.trim_dead <= self.fill - self.live)
            ),
            "trim_dead_pure_write": (self.n_trim > 0)
            | jnp.all(self.trim_dead == 0),
            # fault / retirement accounting: the carried counters equal the
            # reductions, the spare pool never goes negative, and a
            # degraded drive has a recorded degradation time
            "retired_blocks": self.retired_blocks
            == jnp.sum(self.state == RETIRED),
            "grp_retired": jnp.all(
                jnp.sum(owned & (self.state[None, :] == RETIRED), axis=1)
                == self.grp_retired
            ),
            "spares_nonneg": self.spares_left >= 0,
            "degraded_consistent": (self.drive_status == STATUS_OK)
            | (self.degraded_at >= 0),
        }


def assert_invariants(st: SimState, label: str = "") -> None:
    """Host-side :meth:`SimState.check_invariants` with named failures."""
    failed = [k for k, ok in st.check_invariants().items() if not bool(ok)]
    assert not failed, f"invariants violated{f' ({label})' if label else ''}: {failed}"


def init_state(
    geom: Geometry,
    mcfg: ManagerConfig,
    page_group,
    n_groups: int,
    use_bloom: bool = True,
) -> SimState:
    """Build a pre-conditioned (fully mapped) drive.

    page_group: int array [LBA] — initial group of every logical page.
    Pages are laid out group-contiguously; leftover blocks are FREE.
    """
    import numpy as np

    k, b, lba = geom.n_blocks, geom.pages_per_block, geom.lba_pages
    g_max = mcfg.max_groups
    page_group = np.asarray(page_group, np.int32)
    assert page_group.shape == (lba,)
    assert page_group.max() < n_groups <= g_max

    order = np.argsort(page_group, kind="stable")  # group-contiguous layout
    page_map = np.full(lba, -1, np.int32)
    slot_lba = np.full((k, b), -1, np.int32)
    valid = np.zeros((k, b), bool)
    live = np.zeros(k, np.int32)
    fill = np.zeros(k, np.int32)
    group_of = np.full(k, -1, np.int32)
    state_arr = np.zeros(k, np.int8)

    blk = 0
    slot = 0
    prev_g = int(page_group[order[0]])
    for idx in order:
        g = int(page_group[idx])
        if g != prev_g and slot > 0:  # group boundary → new block
            blk += 1
            slot = 0
            prev_g = g
        if slot == 0:
            group_of[blk] = g
            state_arr[blk] = CLOSED
        page_map[idx] = blk * b + slot
        slot_lba[blk, slot] = idx
        valid[blk, slot] = True
        slot += 1
        if slot == b:
            blk += 1
            slot = 0
    if slot > 0:
        blk += 1
    # fill levels / live counts
    for j in range(blk):
        live[j] = valid[j].sum()
        fill[j] = b if state_arr[j] == CLOSED else valid[j].sum()
    fill[:blk] = b  # partially-filled tail blocks are sealed CLOSED
    state_arr[:blk] = CLOSED

    grp_size = np.bincount(page_group, minlength=g_max).astype(np.int32)
    grp_phys = np.bincount(group_of[group_of >= 0], minlength=g_max).astype(np.int32)
    grp_active = np.zeros(g_max, bool)
    grp_active[:n_groups] = True

    # spare-block pool: at most the physical blocks beyond the logical
    # content, the GC reserve, one active block per group slot, and two
    # blocks of migration headroom — retiring more than this would leave
    # the allocator with no usable over-provisioning. mcfg.spare_blocks
    # clamps WITHIN that bound (None = take it all).
    content_blocks = -(-lba // b)  # ceil
    auto_spares = max(
        0, k - content_blocks - mcfg.gc_reserve_blocks - g_max - 2
    )
    spares = (
        auto_spares
        if mcfg.spare_blocks is None
        else max(0, min(mcfg.spare_blocks, auto_spares))
    )

    return SimState(
        page_map=jnp.asarray(page_map),
        slot_lba=jnp.asarray(slot_lba),
        valid=jnp.asarray(valid),
        live=jnp.asarray(live),
        fill=jnp.asarray(fill),
        # LRU ages: initially-filled blocks aged by layout order (see
        # simulator._pop_free_block for the claim-time clock)
        stamp=jnp.asarray(
            np.where(np.arange(k) < blk, np.arange(k), 0).astype(np.int32)
        ),
        state=jnp.asarray(state_arr),
        group_of=jnp.asarray(group_of),
        erase_count=jnp.zeros(k, jnp.int32),
        trim_dead=jnp.zeros(k, jnp.int32),
        erase_total=jnp.zeros((), jnp.int32),
        erase_sq_total=jnp.zeros((), jnp.int32),
        active_blk=jnp.full(g_max, -1, jnp.int32),
        grp_size=jnp.asarray(grp_size),
        grp_phys=jnp.asarray(grp_phys),
        grp_p=jnp.zeros(g_max, jnp.float32),
        grp_writes=jnp.zeros(g_max, jnp.int32),
        grp_alloc=jnp.asarray(np.maximum(grp_phys, 1)),
        grp_active=jnp.asarray(grp_active),
        grp_created=jnp.zeros(g_max, jnp.int32),
        grp_surplus=jnp.asarray(
            np.where(
                grp_active, grp_phys - np.maximum(grp_phys, 1), -INT32_MAX
            ).astype(np.int32)
        ),
        grp_live=jnp.asarray(grp_size),  # fully mapped: live == size
        free_blocks=jnp.asarray(int((state_arr == FREE).sum()), jnp.int32),
        mapped_pages=jnp.asarray(lba, jnp.int32),
        retired_blocks=jnp.zeros((), jnp.int32),
        spares_left=jnp.asarray(spares, jnp.int32),
        grp_retired=jnp.zeros(g_max, jnp.int32),
        drive_status=jnp.asarray(STATUS_OK, jnp.int32),
        degraded_at=jnp.asarray(-1, jnp.int32),
        n_erase_fail=jnp.zeros((), jnp.int32),
        n_halted=jnp.zeros((), jnp.int32),
        fault_draws=jnp.zeros((), jnp.uint32),
        # (G, 1) placeholder when the context excludes the bloom branch
        # (SimContext.use_bloom=False)
        bloom_active=jnp.zeros(
            (g_max, bloom_bits(geom, mcfg) if use_bloom else 1), bool
        ),
        bloom_passive=jnp.zeros(
            (g_max, bloom_bits(geom, mcfg) if use_bloom else 1), bool
        ),
        bloom_writes=jnp.zeros(g_max, jnp.int32),
        n_app=jnp.zeros((), jnp.int32),
        n_mig=jnp.zeros((), jnp.int32),
        n_erase=jnp.zeros((), jnp.int32),
        n_dropped=jnp.zeros((), jnp.int32),
        n_trim=jnp.zeros((), jnp.int32),
        clock=jnp.asarray(blk, jnp.int32),
        interval=jnp.zeros((), jnp.int32),
        cooldown=jnp.zeros((), jnp.int32),
    )
