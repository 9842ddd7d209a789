"""Event-granularity SSD simulator (jittable, lax.scan over an op stream).

One scan step = one application event. The engine is an OP-STREAM engine:
an event is ``(op, lba)`` with ``op ∈ {OP_WRITE, OP_TRIM}`` (pure-write
contexts scan bare ``lba`` streams — see the bit-compatibility note
below). A WRITE:
  1. invalidate the page's old physical slot (one gather in the packed
     ``page_map``),
  2. pick the target group (temperature detection, §5.6 / oracle; a page
     re-mapped after a TRIM inherits its workload layout group via
     ``page_group0``),
  3. garbage-collect inside the group if it's out of budgeted space (§5.4),
  4. append the page to the group's active block,
  5. every h writes: interval bookkeeping (§5.1) — EWMA update frequencies,
     re-allocate over-provisioning (§5.5), create/merge groups (§5.2),
  6. movement operations (§5.3): ≤1 proactive compaction GC per step on the
     most block-surplus group, donating redeemed blocks to the pool.
A TRIM unmaps the page and kills its physical slot (:func:`_trim_page`,
one fused ``kernels/write_path.apply_trim`` op + O(1) carried-counter
updates). It frees space, so it can never trip the GC / valve / movement
predicates, and it completes no application write, so it never closes a
§5.1 interval — TRIM has only a fast path.

Architecture (op-stream layer):

* **TRIM is dynamic over-provisioning** (Frankie et al., arXiv:1208.1794).
  ``SimState`` carries ``mapped_pages`` (scalar == mapped LBAs) and
  ``grp_live`` ([G] == mapped pages per group), maintained at every
  map/unmap/GC site and cross-checked by ``SimState.check_invariants``.
  The §5.5 allocator and the detector hit rates consume these EFFECTIVE
  group sizes, so trimmed space automatically re-enters the OP budgets at
  the next §5.1 interval and equilibrium WA tracks
  ``analytics.wa_from_op_ratio(effective_op_ratio(r, t))``
  (tests/test_trim.py).

* **Bit-compatibility with pure-write runs.** ``SimContext.with_trim``
  is static: ``False`` (default) traces the historical (lba, t) step —
  pure-write fleets keep their exact streams, step structure, and scalar
  §5.1 interval predicate, so results are bit-identical to the
  pre-op-stream engine at zero cost. ``True`` scans (op, lba, t) triples;
  an all-WRITE op stream still reproduces the pure-write run
  bit-identically (state, counters, WA curves — asserted under jit and
  vmap in tests/test_write_engine.py) because a WRITE event executes the
  identical write body; only the interval predicate reads the carried
  ``n_app`` instead of the scan clock (equal values on all-WRITE
  streams, where n_app == t+1 at the read). Under vmap the op dispatch
  is a select and the §5.1 predicate is per-drive, which is why
  ``core/fleet.py`` partitions trim-bearing drives into their own
  sub-batches.

Architecture (fault-injection / bad-block retirement layer):

* **Faults are data, not step structure.** ``SimContext.with_faults``
  (static, default ``False``) gates the traced fault machinery — the
  per-erase Bernoulli draw, the halt guard, the retired-capacity term in
  the §5.5 allocator — but the RATES live in ``policy``
  (``fault_rate``, ``fault_rate_worn``, ``endurance_limit``,
  ``fault_seed``), so a fleet sweeps failure rates × endurance limits in
  ONE compiled grid and faulty + fault-free drives share a sub-batch
  (``with_faults`` is deliberately NOT a ``fleet._part_key`` dimension).
  ``with_faults=False`` traces the exact pre-fault step: zero-fault
  drives stay bit-identical to the fault-free engine under jit and vmap
  (tests/test_faults.py).

* **Retry-then-retire at every erase site.** Each of the three GC drains
  is wrapped by :func:`_erase_fault_retire` inside :func:`_gc_one`'s
  dieted cond: one counter-based uniform (:func:`_fault_uniform`, a pure
  function of ``(fault_seed, fault_draws)`` — replayable) decides the
  whole retry ladder. The erase fails iff ``u < rate``; all
  ``1 + erase_max_retries`` attempts fail iff ``u < rate^(1+retries)``,
  and then the block RETIRES: the erase is undone from the wear
  aggregates (a failed erase completes no P-E cycle), the block enters
  the terminal ``RETIRED`` state keeping its group label
  (``grp_retired`` follows §5.2 merges), and a spare is drawn. The rate
  jumps from ``fault_rate`` to ``fault_rate_worn`` (default 1.0) once
  the block's P-E count crosses ``endurance_limit`` — deterministic
  block death at the limit, the simplest endurance model that makes WA
  vs LIFETIME a measurable curve.

* **Graceful degradation, not invariant violation.** Retired capacity is
  subtracted from the §5.5 OP budget at the next interval, so the
  allocator divides the SHRUNKEN physical space and
  ``predicted_wa()``/``model_error()`` track the degraded geometry. When
  the spare pool is dry the drive flips ``drive_status`` to
  STATUS_DEGRADED (recording ``degraded_at``) and :func:`_halt_wrap`
  freezes every later op into a counted no-op — at fleet scale a dead
  drive is an inert lane in its vmapped sub-batch, masked exactly like
  PR 6's filler drives, and never poisons its neighbors.

Architecture (post fast-path refactor — see also the bulk-GC notes below):

* **O(1) incremental accounting.** The paper treats pool occupancy and
  per-group budgets as counters, and so does the simulator: ``SimState``
  carries ``free_blocks`` (a scalar, == ``(state == FREE).sum()`` always)
  and ``grp_surplus`` (``grp_phys - grp_alloc`` masked to active groups),
  maintained at the handful of sites that change block state
  (:func:`_pop_free_block`, the two GC drains, :func:`_recompute_alloc`,
  group create/merge). Every per-write predicate — the GC low-pool check,
  the emergency valve, movement-op headroom — is an O(1) scalar read; full
  reductions over the block array survive only inside per-GC victim
  selection and ``SimState.check_invariants`` (the debug checker that
  proves the counters never drift).

* **Fast path / heavy path.** ``make_step`` splits the write: after the
  invalidate and the target group, a heavy path (GC, emergency valve,
  movement ops, §5.1 interval bookkeeping) is entered only when the
  scalar predicates demand it — the active block is full, the pool is at
  reserve, a group holds redeemable surplus, or the interval elapses —
  and every other write takes the lean append to the group's open active
  block (``kernels/write_path.apply_write``). GC is a rare event
  amortized over many steady-state writes (cf. Nagel et al.,
  arXiv:1807.09313); under plain jit the heavy machinery is a real
  untaken branch on most writes. The seed-shaped single-path step
  survives as ``SimContext.fast_path=False``: the step-equivalence oracle
  (tests/test_write_engine.py), and the step of vmapped fleet lanes,
  where a cond runs both branches (``fleet_exec.split_step``).

* **Chunked scan + strided tracing.** :func:`scan_writes` scans chunks of
  ``trace_every`` writes (inner scan ``unroll``-ed) and emits the
  cumulative (n_app, n_mig) counters once per chunk instead of per write —
  the trace at stride E samples exactly the dense trace at steps E, 2E, …
  Write-order semantics are untouched: chunking only regroups scan
  iterations, every write still sees the state its predecessors left.
  Dense tracing (``trace_every=1``) is the default everywhere.

Architecture (post bulk-GC refactor):

* **State** is a :class:`repro.core.ssd.SimState` — a frozen dataclass
  registered as a JAX pytree. Mutating helpers return successors via
  ``st.replace(...)``; there are no ad-hoc ``dict(st)`` copies. The
  logical→physical map is ONE packed int32 array (``page_map = blk · B +
  slot``, ``-1`` unmapped): lookups, invalidates, and writes each cost a
  single gather/scatter instead of the former ``map_blk``/``map_slot`` pair.

* **GC drains in bulk.** :func:`_gc_drain_bulk` migrates a victim's live
  pages in one shot: the ``[B]`` ``slot_lba``/``valid`` lanes are read at
  once, per-slot target groups come from the demotion policy, pages are
  segment-counted per target group, fresh blocks are claimed up front (one
  per overflowing target group, in the exact order the sequential pop would
  produce), and the landings are chunked writes — dense one-hot masked ops
  for the group/block-sized updates (XLA:CPU expands vector-index ``.at[]``
  scatters into a while loop each, measured at ~4× the whole drain's cost)
  and flat 1-D scatters for the two capacity-sized ones. The slot-content
  copy itself routes through ``kernels/gc_compact`` (Pallas-backed on TPU,
  the flattened-index lowering elsewhere). Only the *demotion
  decision* keeps a sequential flavor: §5.6 demotion reads hit rates, which
  drift as the drain moves pages, so when any page is demotion-flagged a
  ``lax.scan`` carrying just the [G] group sizes replays the per-page
  decisions bit-exactly (sort-free; the common static-detector case
  short-circuits to constant targets). No ``fori_loop`` over victim slots
  remains; the former per-page path survives as
  :func:`_gc_drain_reference` (``SimContext.gc_impl="reference"``) and is
  asserted elementwise-identical in tests/test_bulk_gc.py.

* **GC victim selection is ONE traced score, not a policy branch.**
  :func:`_select_victim` maximises

      S(blk) = α·(B − live) − γ·stamp − β·erase_count − τ·trim_dead

  over the CLOSED blocks of the GC group (others masked to -inf).
  α scores reclaim benefit (pages freed by erasing the block), γ scores
  migration cost by recency (a recently-claimed block's pages are about
  to die on their own — migrating them is wasted work, the classic LRU
  rationale), β steers selection away from high-P-E blocks (wear
  leveling against the carried ``erase_count``), and τ deprioritises
  blocks rich in trimmed-but-unerased slots (``trim_dead``). The legacy
  policies are EXACT weight points — greedy = (1,0,0,0) ≡ argmin(live),
  lru = (0,0,1,0) ≡ argmin(stamp), bit-identical victims including the
  first-index tie-break, because every term is an int32 counter cast to
  float32 (exact below 2^24) — and wear/trim-aware policies are just
  other points of the same traced (α, β, γ, τ) vector, so a vmapped
  fleet sweeps the whole policy space in one compiled grid with no
  step-structure change. Victim selection stays the only full
  block-array reduction on the write path: the score reads four carried
  [K] counters elementwise, and every erase site maintains
  ``erase_count``/``erase_total``/``erase_sq_total``/``trim_dead`` in
  O(1) (cross-checked by ``SimState.check_invariants``).

* **Policy switches: traced data where drives differ, trace-time structure
  where they can't.** The GC weight vector, movement firing, FDP
  assumption arrays, and the §5.1 constants ``ewma_a``/``h`` live in a
  per-drive ``policy`` pytree of scalars selected with ``lax.cond`` —
  under jit they are runtime branches, under ``jax.vmap`` selects, which
  is what lets ``core/fleet.py`` batch drives with different manager
  configs (including EWMA/interval/GC-weight sweeps) into one jitted
  ``vmap(lax.scan)``. But switches that define step STRUCTURE — the
  temperature detector, movement ops, dynamic groups, closed-form
  allocation — dispatch at TRACE time from ``SimContext``
  (``can_demote``/``use_movement``/``use_dynamic``/``use_closed_alloc``):
  a vmapped ``lax.switch`` executes every branch and selects, so
  ``core/fleet.py`` partitions fleets into structure-homogeneous
  sub-batches (``fleet._part_key``) and each compiled step carries only
  the machinery its drives can ever run. Conditionals that remain are
  SELECT-DIETED (``_cond_fields``): their branches return only the fields
  they can modify, never the whole ~29-array state pytree. When every
  drive of a fleet shares ``h``, the interval predicate stays a scalar
  (``SimContext.per_drive_interval=False``) so the §5.1 bookkeeping
  remains a real every-h-steps branch, not a per-step select.

Phase scopes. The step's phases are traced inside ``jax.named_scope``
names, which XLA keeps as op metadata (``op_name``) and a profiler trace
of the compiled step reports per device op; they change no instruction.
Every name starts with ``ssd.``:

* ``ssd.append`` — invalidate, the target group's liveness check,
  append and the per-write counters (the fast branch of the split write
  too);
* ``ssd.detector`` — the §5.6 bloom (or FDP) temperature detector;
* ``ssd.gc`` — the §5.4 GC predicate and :func:`_gc_one`, refined by
  ``ssd.victim`` (:func:`_select_victim`) and ``ssd.drain`` (the drains);
* ``ssd.valve`` — the emergency-valve loop; ``ssd.movement`` — the §5.3
  movement GC; ``ssd.interval`` — the §5.1/§5.5/§5.2 interval update;
* ``ssd.trim`` — :func:`_trim_page`;
* ``ssd.chunk`` — :func:`scan_writes`'s inner loop over ``trace_every``
  events; ``ssd.sampler`` — the on-device stream sampler of the fleet.

An op belongs to the outermost phase on its path, so a valve's or a
movement's GC counts as valve or movement.

GC migrations re-enter the same write semantics (so migrated pages can be
demoted by the detector, as in Listing 1/3 of the paper).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.allocation import (
    allocate_by_frequency,
    allocate_by_size,
    allocate_closed_form,
)
from repro.core.ssd import (
    CLOSED,
    FREE,
    OPEN,
    RETIRED,
    STATUS_DEGRADED,
    STATUS_OK,
    Geometry,
    ManagerConfig,
    SimState,
    bloom_bits,
    surplus_of,
)
from repro.core.workloads import OP_TRIM
from repro.kernels.gc_compact.ops import compact_slots
from repro.kernels.write_path.ops import apply_trim, apply_write

INT_MAX = jnp.iinfo(jnp.int32).max

# policy codes (traced per-drive scalars; see policy_from_config)
ALLOC_CLOSED, ALLOC_FDP, ALLOC_SIZE, ALLOC_FREQ = 0, 1, 2, 3
_ALLOC_CODES = {
    "wolf": ALLOC_CLOSED,
    "optimal": ALLOC_CLOSED,
    "fdp_assumed": ALLOC_FDP,
    "size": ALLOC_SIZE,
    "freq": ALLOC_FREQ,
    "single": ALLOC_SIZE,
}
TD_STATIC, TD_FDP, TD_BLOOM = 0, 1, 2
_TD_CODES = {"static": TD_STATIC, "fdp": TD_FDP, "bloom": TD_BLOOM}


@dataclasses.dataclass(frozen=True)
class SimContext:
    """Static context threaded through the jitted step.

    Holds the SHAPE-defining geometry and the scalar paper constants shared
    by every drive of a fleet; everything that may differ per drive lives in
    the traced ``policy`` pytree.
    """

    geom: Geometry
    mcfg: ManagerConfig
    n_groups: int  # initial groups (may grow in dynamic mode)
    # static because it gates array SHAPES and traced branches: when False
    # the bloom detector branch is structurally absent (vmapped fleets then
    # never pay per-step selects over the [G, bits] filter pair) and the
    # state carries (G, 1) placeholders
    use_bloom: bool = True
    # GC drain implementation: "bulk" (vectorized, default) or "reference"
    # (the per-page fori_loop it replaced — kept as the equivalence oracle)
    gc_impl: str = "bulk"
    # static because it gates the interval predicate's batching: False keeps
    # ((t+1) % h == 0) a SCALAR under vmap (every drive shares h, the §5.1
    # work stays a real branch); True reads the per-drive policy["h"], which
    # under vmap turns the interval machinery into per-step selects — only
    # fleets actually sweeping the interval length pay that
    per_drive_interval: bool = False
    # step engine: True = fast-path/heavy-path split (default); False = the
    # seed-shaped single-path step, kept as the step-equivalence oracle
    fast_path: bool = True
    # op-stream mode: when True the scan consumes (op, lba) events and the
    # step dispatches WRITE/TRIM (both engines). Static because it gates
    # traced structure AND the interval clock: pure-write contexts keep the
    # scalar ((t+1) % h) predicate (t unbatched under vmap), op contexts
    # read the carried n_app (write counts diverge across drives once
    # trims interleave, so the §5.1 predicate is per-drive there). False
    # traces the EXACT pre-op-stream step — pure-write fleets pay nothing.
    with_trim: bool = False
    # static because they gate traced STRUCTURE (like use_bloom): when False
    # the movement-op / §5.6-demotion / §5.2-dynamic-group / closed-form-
    # allocation machinery is structurally absent from the compiled step,
    # so vmapped fleets whose sub-batch can't need it never pay its
    # per-step (or per-interval) cost. core/fleet.py partitions on these.
    use_movement: bool = True
    can_demote: bool = True
    use_dynamic: bool = True
    # the eq.-8 closed-form OP allocation embeds an 80-iteration bisection
    # (analytics eq. 3 inversion) per §5.1 interval; size/freq-allocated
    # drives never read its result
    use_closed_alloc: bool = True
    # fault-injection / bad-block retirement layer. Static because it gates
    # traced STRUCTURE (the per-erase fault draw, the degraded-drive halt
    # guard, the retired-capacity term of the §5.5 allocator) — but it is
    # deliberately NOT a fleet partition dimension: fault rates, endurance
    # limits, and seeds are per-drive POLICY data, so faulty and fault-free
    # drives share one compiled sub-batch (the fleet layer sets this per
    # sub-batch iff any drive's mcfg.has_faults). False traces the EXACT
    # fault-free step; True with zero-rate policy data produces
    # elementwise-identical values on every pre-existing field.
    with_faults: bool = False
    # trace stride: emit the cumulative (n_app, n_mig) counters after every
    # E-th write instead of every write (must divide the segment length);
    # the scan is then chunked [T//E, E] and the inner chunk emits nothing
    trace_every: int = 1
    # lax.scan unroll factor for the (inner) write loop — amortizes
    # XLA:CPU per-iteration dispatch; semantics-free
    unroll: int = 1

    @property
    def h(self) -> int:
        return max(16, int(self.geom.lba_pages * self.mcfg.interval_frac))

    @property
    def f_min_pages(self) -> int:
        return self.geom.n_luns * self.geom.pages_per_block


def policy_from_config(ctx: SimContext, assumed_p=None, fdp_rate=None) -> dict:
    """Lower a ManagerConfig's policy switches to a traced pytree.

    assumed_p/fdp_rate: [G] FDP fixed-assumption arrays (zeros if unused).
    """
    g_max = ctx.mcfg.max_groups
    if assumed_p is None:
        assumed_p = jnp.zeros(g_max, jnp.float32)
    if fdp_rate is None:
        fdp_rate = jnp.zeros(g_max, jnp.float32)
    assert ctx.use_bloom or ctx.mcfg.td_mode != "bloom", (
        "bloom detector requested but ctx.use_bloom is False"
    )
    assert ctx.use_movement or not ctx.mcfg.movement_ops, (
        "movement ops requested but ctx.use_movement is False"
    )
    assert ctx.can_demote or ctx.mcfg.td_mode == "static", (
        f"detector {ctx.mcfg.td_mode!r} can demote but ctx.can_demote is False"
    )
    assert ctx.use_dynamic or not ctx.mcfg.dynamic_groups, (
        "dynamic groups requested but ctx.use_dynamic is False"
    )
    assert ctx.use_closed_alloc or ctx.mcfg.alloc_mode not in (
        "wolf", "optimal", "fdp_assumed"
    ), f"alloc {ctx.mcfg.alloc_mode!r} needs the closed form"
    assert ctx.with_faults or not ctx.mcfg.has_faults, (
        "mcfg can fail erases but ctx.with_faults is False"
    )
    return {
        "alloc_mode": jnp.asarray(_ALLOC_CODES[ctx.mcfg.alloc_mode], jnp.int32),
        # (α, β, γ, τ) victim-score weights (ManagerConfig.gc_weights):
        # per-drive TRACED data, so one vmapped fleet sweeps the weight
        # space — greedy/LRU/wear/trim-aware are all points of this vector
        "gc_w": jnp.asarray(ctx.mcfg.gc_weights(), jnp.float32),
        "movement_ops": jnp.asarray(ctx.mcfg.movement_ops),
        "td_mode": jnp.asarray(_TD_CODES[ctx.mcfg.td_mode], jnp.int32),
        "dynamic_groups": jnp.asarray(ctx.mcfg.dynamic_groups),
        "max_groups": jnp.asarray(ctx.mcfg.max_groups, jnp.int32),
        "f_min_pages": jnp.asarray(ctx.f_min_pages, jnp.int32),
        # §5.1 constants as per-drive sweep axes (ROADMAP: online frequency
        # re-estimation); h doubles as the interval predicate when
        # ctx.per_drive_interval is True
        "h": jnp.asarray(ctx.h, jnp.int32),
        "ewma_a": jnp.asarray(ctx.mcfg.ewma_a, jnp.float32),
        "assumed_p": jnp.asarray(assumed_p, jnp.float32),
        "fdp_rate": jnp.asarray(fdp_rate, jnp.float32),
        # fault injection (per-drive TRACED data — a fleet sweeps failure
        # rates × endurance limits in one compiled grid; consumed by
        # _erase_fault_retire only when ctx.with_faults). endurance_limit
        # INT_MAX = the worn regime is unreachable for this drive.
        "fault_rate": jnp.asarray(ctx.mcfg.fault_rate, jnp.float32),
        "fault_rate_worn": jnp.asarray(ctx.mcfg.fault_rate_worn, jnp.float32),
        "endurance_limit": jnp.asarray(
            ctx.mcfg.endurance_pe_limit
            if ctx.mcfg.endurance_pe_limit > 0 else INT_MAX,
            jnp.int32,
        ),
        "fault_seed": jnp.asarray(
            ctx.mcfg.fault_seed & 0xFFFFFFFF, jnp.uint32
        ),
    }


# ---------------------------------------------------------------------------
# select-dieted conditionals
# ---------------------------------------------------------------------------
#
# Under vmap a lax.cond lowers to a select over its OUTPUTS; a branch that
# returns the whole SimState therefore copies all ~29 state arrays per
# step per lane — including the [G, bits] bloom filter pair that GC never
# writes. Every per-step conditional below routes through _cond_fields /
# _while_fields, which carry ONLY the fields the true branch can modify;
# the rest ride through the enclosing closure untouched. Under plain jit
# this is the same real branch either way.

# every field any GC drain (bulk or reference) can touch
_GC_FIELDS = (
    "page_map", "slot_lba", "valid", "live", "fill", "stamp", "state",
    "group_of", "active_blk", "grp_size", "grp_live", "grp_phys",
    "grp_surplus", "free_blocks", "mapped_pages", "clock", "n_mig",
    "n_dropped", "n_erase",
    # wear layer: every drain bumps the victim's P-E count + the carried
    # aggregates and clears its trimmed-slot tally
    "erase_count", "trim_dead", "erase_total", "erase_sq_total",
)
# extra fields the post-erase fault hook (_erase_fault_retire) can touch —
# appended to _GC_FIELDS at every drain cond/while ONLY in with_faults
# contexts, so fault-free steps keep their exact select set
_FAULT_FIELDS = (
    "retired_blocks", "spares_left", "grp_retired", "drive_status",
    "degraded_at", "n_erase_fail", "fault_draws",
)


def _gc_fields(ctx: SimContext):
    """The drain-cond field set: _GC_FIELDS, plus the fault hook's fields
    when the context injects faults (every erase site shares this)."""
    return _GC_FIELDS + (_FAULT_FIELDS if ctx.with_faults else ())
# fields the in-write block allocation (_pop_free_block + seal) can touch
_ALLOC_FIELDS = (
    "state", "group_of", "fill", "grp_phys", "grp_surplus", "free_blocks",
    "stamp", "clock", "active_blk",
)
# fields the §5.1 interval update (EWMA + create/merge + re-allocation)
# can touch — group stats plus the block relabel/seal of a merge
_INTERVAL_FIELDS = (
    "grp_p", "grp_writes", "interval", "cooldown", "grp_active",
    "grp_size", "grp_live", "grp_phys", "grp_alloc", "grp_surplus",
    "grp_created", "group_of", "state", "active_blk",
)
# everything the post-target-selection write step (fast append OR the whole
# heavy tail) can touch: all state except the bloom filter triple, which
# only target selection writes
_STEP_FIELDS = tuple(
    f for f in SimState.__dataclass_fields__
    if f not in ("bloom_active", "bloom_passive", "bloom_writes")
)
# the op-stream WRITE/TRIM dispatch selects over everything: the write
# branch contains target selection, which owns the bloom triple
_OP_FIELDS = tuple(SimState.__dataclass_fields__)


def _scoped(name):
    """Trace the decorated step piece inside ``jax.named_scope(name)``: its
    device ops carry the step phase in their op metadata, where a profiler
    trace of the compiled step finds it (the names are listed in the
    module docstring's "Phase scopes")."""

    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return scoped

    return wrap


def _fields_of(st: SimState, fields):
    return tuple(getattr(st, f) for f in fields)


def _cond_fields(pred, fn, st: SimState, fields):
    """``st if not pred else fn(st)``, selecting only over ``fields``.

    ``fn`` must not modify any field outside ``fields`` (the others are
    silently dropped from its result — keep the lists exhaustive).
    """
    out = jax.lax.cond(
        pred,
        lambda s: _fields_of(fn(s), fields),
        lambda s: _fields_of(s, fields),
        st,
    )
    return st.replace(**dict(zip(fields, out)))


def _while_fields(cond_fn, body_fn, st: SimState, extra, fields):
    """A bounded while_loop whose carry is (fields-of-st, extra) instead of
    the whole state — fields outside ``fields`` must be loop-invariant.
    cond_fn/body_fn take and return (full-state, extra)."""

    def rebuild(carry):
        vals, extra = carry
        return st.replace(**dict(zip(fields, vals))), extra

    def cond(carry):
        return cond_fn(*rebuild(carry))

    def body(carry):
        s2, e2 = body_fn(*rebuild(carry))
        return _fields_of(s2, fields), e2

    vals, extra = jax.lax.while_loop(
        cond, body, (_fields_of(st, fields), extra)
    )
    return st.replace(**dict(zip(fields, vals))), extra


# ---------------------------------------------------------------------------
# primitive state updates
# ---------------------------------------------------------------------------

def _pop_free_block(st: SimState, g):
    """Claim a FREE block for group g (becomes its OPEN active block)."""
    free_mask = st.state == FREE
    blk = jnp.argmax(free_mask)  # reserve logic upstream guarantees ≥1
    ok = free_mask[blk]
    d = jnp.where(ok, 1, 0)
    grp_phys = st.grp_phys.at[g].add(d)
    st = st.replace(
        state=st.state.at[blk].set(jnp.where(ok, OPEN, st.state[blk])),
        group_of=st.group_of.at[blk].set(jnp.where(ok, g, st.group_of[blk])),
        fill=st.fill.at[blk].set(jnp.where(ok, 0, st.fill[blk])),
        grp_phys=grp_phys,
        grp_surplus=surplus_of(st.grp_active, grp_phys, st.grp_alloc),
        free_blocks=st.free_blocks - d,
        # LRU clock: a block's age is its claim time — "least recently
        # erased" degenerates into cleaning freshly-filled (never-erased)
        # blocks if ages only advance on erase.
        stamp=st.stamp.at[blk].set(jnp.where(ok, st.clock, st.stamp[blk])),
        clock=st.clock + d,
    )
    return st, blk, ok


def _write_page(ctx: SimContext, st: SimState, lba, g, *, is_migration: bool,
                enabled=True):
    """Append page `lba` to group g's active block (allocating if needed).

    enabled: traced mask — when False every update is an elementwise no-op.
    The reference GC drain uses this instead of wrapping the call in
    lax.cond, which under vmap would select over the whole state pytree per
    page.
    """
    b = ctx.geom.pages_per_block
    blk = st.active_blk[g]
    blk_full = jnp.where(blk >= 0, st.fill[jnp.maximum(blk, 0)] >= b, True)

    def alloc(st):
        old = st.active_blk[g]
        # seal the previous active block
        st = st.replace(
            state=st.state.at[jnp.maximum(old, 0)].set(
                jnp.where(old >= 0, CLOSED, st.state[jnp.maximum(old, 0)])
            )
        )
        st, new_blk, ok = _pop_free_block(st, g)
        return st.replace(
            active_blk=st.active_blk.at[g].set(jnp.where(ok, new_blk, old))
        )

    st = _cond_fields(blk_full & enabled, alloc, st, _ALLOC_FIELDS)
    blk = st.active_blk[g]
    slot = st.fill[blk]
    # overflow guard: if the pool was empty the active block may still be
    # full — drop the write and count it (tests assert this never fires).
    ok = enabled & (blk >= 0) & (slot < b)
    blk_c = jnp.maximum(blk, 0)
    slot_c = jnp.minimum(slot, b - 1)
    updates = dict(
        fill=st.fill.at[blk_c].add(jnp.where(ok, 1, 0)),
        slot_lba=st.slot_lba.at[blk_c, slot_c].set(
            jnp.where(ok, lba, st.slot_lba[blk_c, slot_c])
        ),
        valid=st.valid.at[blk_c, slot_c].set(
            jnp.where(ok, True, st.valid[blk_c, slot_c])
        ),
        live=st.live.at[blk_c].add(jnp.where(ok, 1, 0)),
        # a FAILED (enabled but not ok) write unmaps the page; a disabled
        # call must leave the mapping untouched
        page_map=st.page_map.at[lba].set(
            jnp.where(ok, blk * b + slot,
                      jnp.where(enabled, -1, st.page_map[lba]))
        ),
        grp_size=st.grp_size.at[g].add(jnp.where(ok, 1, 0)),
        grp_live=st.grp_live.at[g].add(jnp.where(ok, 1, 0)),
        mapped_pages=st.mapped_pages + jnp.where(ok, 1, 0),
        n_dropped=st.n_dropped + jnp.where(ok | jnp.logical_not(enabled), 0, 1),
    )
    if is_migration:
        updates["n_mig"] = st.n_mig + jnp.where(ok, 1, 0)
    return st.replace(**updates)


@_scoped("ssd.append")
def _invalidate(ctx: SimContext, st: SimState, lba):
    b = ctx.geom.pages_per_block
    pm = st.page_map[lba]
    has = pm >= 0
    pm_c = jnp.maximum(pm, 0)
    blk_c = pm_c // b
    slot = pm_c % b
    old_g = st.group_of[blk_c]
    d_g = jnp.where(has & (old_g >= 0), -1, 0)
    st = st.replace(
        valid=st.valid.at[blk_c, slot].set(
            jnp.where(has, False, st.valid[blk_c, slot])
        ),
        live=st.live.at[blk_c].add(jnp.where(has, -1, 0)),
        grp_size=st.grp_size.at[jnp.maximum(old_g, 0)].add(d_g),
        grp_live=st.grp_live.at[jnp.maximum(old_g, 0)].add(d_g),
        mapped_pages=st.mapped_pages + jnp.where(has, -1, 0),
    )
    return st, jnp.where(has, old_g, 0)


@_scoped("ssd.append")
def _invalidate_counts(ctx: SimContext, st: SimState, lba):
    """The counter half of :func:`_invalidate`: live/grp_size decrements and
    the old-group lookup, WITHOUT the valid-bit clear.

    The split step and the TRIM step leave the clear to their fused
    ``write_path`` op. Nothing between here and there reads ``valid`` —
    target selection only touches group stats and the bloom pair — so the
    split is exact. Returns (st, old_g, old_pm).
    """
    b = ctx.geom.pages_per_block
    pm = st.page_map[lba]
    has = pm >= 0
    pm_c = jnp.maximum(pm, 0)
    old_g = st.group_of[pm_c // b]
    d_g = jnp.where(has & (old_g >= 0), -1, 0)
    st = st.replace(
        live=st.live.at[pm_c // b].add(jnp.where(has, -1, 0)),
        grp_size=st.grp_size.at[jnp.maximum(old_g, 0)].add(d_g),
        grp_live=st.grp_live.at[jnp.maximum(old_g, 0)].add(d_g),
        mapped_pages=st.mapped_pages + jnp.where(has, -1, 0),
    )
    return st, jnp.where(has, old_g, 0), pm


# ---------------------------------------------------------------------------
# fault injection / bad-block retirement
# ---------------------------------------------------------------------------

def _fault_uniform(seed, n):
    """Counter-based uniform in [0, 1): murmur3's fmix32 finalizer over
    (seed, draw index). The top 24 hash bits map to an exactly-representable
    float32 in [0, 1 - 2^-24], so ``u < rate`` is never perturbed by
    rounding at either endpoint: rate 0 fails nothing, rate 1 fails
    everything. Counter-based (the draw index is carried state) so the
    fault stream is a pure function of (seed, #erases so far) — replayable,
    order-independent of everything else the step does."""
    h = seed + n * jnp.uint32(2654435761)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return (h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def _erase_fault_retire(ctx: SimContext, st: SimState, victim, g, policy):
    """Retry-then-retire fault hook, applied to a drain's OUTPUT state
    (the victim is already erased: FREE, wear counters bumped).

    One uniform decides the whole retry ladder: the erase attempt fails
    iff ``u < rate`` and all ``1 + erase_max_retries`` attempts fail iff
    ``u < rate^(1+retries)`` — retire ⊂ fail by construction, so a single
    draw covers both and a zero-rate drive consumes the identical (empty)
    event set. ``rate`` is the per-drive base ``fault_rate`` until the
    victim's P-E count crosses the per-drive ``endurance_limit``, then
    ``fault_rate_worn`` (default 1.0: deterministic death at the limit).

    On retire the erase is UNDONE from the wear accounting (a failed erase
    completes no P-E cycle) and the block leaves circulation: state
    RETIRED, group label restored (``grp_retired`` tracks labels through
    §5.2 merges), the pool gives back the block it just reclaimed, one
    spare is drawn. If the spare pool was already dry, the drive degrades
    instead of violating the pool invariants: ``drive_status`` flips to
    STATUS_DEGRADED and every later op freezes (make_step's halt guard).
    """
    if not ctx.with_faults:
        return st
    ec_new = st.erase_count[victim]  # post-bump P-E count of this erase
    worn = (ec_new - 1) >= policy["endurance_limit"]
    rate = jnp.where(
        worn,
        jnp.maximum(policy["fault_rate_worn"], policy["fault_rate"]),
        policy["fault_rate"],
    )
    u = _fault_uniform(policy["fault_seed"], st.fault_draws)
    failed = u < rate
    retired = u < rate ** (1 + ctx.mcfg.erase_max_retries)
    d = jnp.where(retired, 1, 0)
    spares0 = st.spares_left
    # Death has two doors. (1) Spares exhausted: the accounting margin
    # that keeps effective OP positive is gone. (2) Pool death: a
    # retiring GC nets ZERO free blocks (drain +1, retire -1), so heavy
    # retirement can drain the pool to empty — and at free_blocks == 0
    # no GC can ever run again (_gc_one needs ≥ 1 for migration
    # headroom): the drive is operationally dead even with spares left.
    # Either way we freeze instead of silently dropping writes.
    free_after = st.free_blocks - d
    degrade = (
        retired
        & (st.drive_status == STATUS_OK)
        & ((spares0 <= 0) | (free_after <= 0))
    )
    return st.replace(
        state=st.state.at[victim].set(
            jnp.where(retired, RETIRED, st.state[victim]).astype(
                st.state.dtype
            )
        ),
        group_of=st.group_of.at[victim].set(
            jnp.where(retired, jnp.asarray(g, jnp.int32),
                      st.group_of[victim])
        ),
        free_blocks=free_after,
        # a failed erase completes no P-E cycle: undo the drain's bump
        # (e_old = ec_new - 1; Σe² loses (e_old+1)² − e_old²)
        erase_count=st.erase_count.at[victim].add(-d),
        erase_total=st.erase_total - d,
        erase_sq_total=st.erase_sq_total - d * (2 * (ec_new - 1) + 1),
        n_erase=st.n_erase - d,
        retired_blocks=st.retired_blocks + d,
        grp_retired=st.grp_retired.at[g].add(d),
        spares_left=jnp.maximum(spares0 - d, 0),
        n_erase_fail=st.n_erase_fail + jnp.where(failed, 1, 0),
        drive_status=jnp.where(
            degrade, STATUS_DEGRADED, st.drive_status
        ).astype(jnp.int32),
        degraded_at=jnp.where(
            degrade & (st.degraded_at < 0), st.n_app, st.degraded_at
        ).astype(jnp.int32),
        fault_draws=st.fault_draws + jnp.uint32(1),
    )


# ---------------------------------------------------------------------------
# garbage collection (one victim) — §5.4
# ---------------------------------------------------------------------------

# the emergency valve's fixed weight point: pure greedy reclaim
GC_W_GREEDY = (1.0, 0.0, 0.0, 0.0)


@_scoped("ssd.victim")
def _select_victim(ctx: SimContext, st: SimState, g, gc_w):
    """Multi-objective victim selection: one traced score, maximised.

        S(blk) = α·(B − live) − γ·stamp − β·erase_count − τ·trim_dead

    over CLOSED blocks of group g (others masked to -inf). Every term is an
    int32 counter cast to float32 — exact below 2^24, far beyond any test
    horizon — so the legacy policies are EXACT weight points with the same
    first-index tie-break as the argmin they replace: greedy = (1,0,0,0)
    (argmax of B − live ≡ argmin of live), lru = (0,0,1,0) (argmin of
    stamp). β > 0 steers GC away from high-P-E blocks (wear leveling);
    τ > 0 deprioritises blocks rich in trimmed-but-unerased slots. This
    stays the only full block-array reduction on the write path.
    """
    closed = (st.state == CLOSED) & (st.group_of == g)
    b = ctx.geom.pages_per_block
    alpha, beta, gamma, tau = gc_w[0], gc_w[1], gc_w[2], gc_w[3]
    score = (
        alpha * (b - st.live).astype(jnp.float32)
        - gamma * st.stamp.astype(jnp.float32)
        - beta * st.erase_count.astype(jnp.float32)
        - tau * st.trim_dead.astype(jnp.float32)
    )
    victim = jnp.argmax(jnp.where(closed, score, -jnp.inf))
    # a fully-live victim frees nothing: skip unless the policy is
    # age-driven (γ > 0 — LRU must clean stale blocks even when full;
    # the old gc_lru boolean guard, generalised)
    age_driven = gamma > 0.0
    ok = closed[victim] & (age_driven | (st.live[victim] < b))
    return victim, ok


@_scoped("ssd.drain")
def _gc_drain_bulk(ctx: SimContext, st: SimState, victim, g, policy, rate_fn):
    """Vectorized victim drain: migrate every live page in one shot.

    Elementwise-identical to :func:`_gc_drain_reference` whenever no write
    is dropped mid-drain (the pool-reserve invariant callers maintain;
    tests assert ``n_dropped == 0``). The only sequential remnant is the
    demotion decision below — everything that lands state is a chunked
    gather/scatter.
    """
    b = ctx.geom.pages_per_block
    k = ctx.geom.n_blocks
    g_max = st.grp_active.shape[0]
    lba_pages = st.page_map.shape[0]
    g32 = jnp.asarray(g, jnp.int32)

    lbas = st.slot_lba[victim]            # [B]; dead slots hold -1
    is_live = st.valid[victim] != 0       # [B]
    lbas_c = jnp.maximum(lbas, 0)
    n_live = jnp.sum(is_live)

    # -- per-slot DEMOTION FLAGS (§5.6), vectorized over the victim's lanes.
    # A GC demotion only ever moves a page one group colder, and whether a
    # page is demotion-eligible depends solely on drain-invariant state
    # (oracle rates, fdp bands, the bloom filter pair) — so it precomputes
    # as one [B] mask, for the trace-time-dispatched detector only (every
    # compiled step has exactly one). Keeping the big state arrays out of
    # the per-slot machinery below matters: anything a lax.scan touches is
    # hauled through the loop boundary every iteration on XLA:CPU.
    td = ctx.mcfg.td_mode
    if td == "fdp" and ctx.can_demote:
        r = jax.vmap(lambda l: rate_fn(st, l))(lbas_c)
        demote_flag = r < 0.5 * policy["fdp_rate"][g]
    elif td == "bloom" and ctx.can_demote:
        in_a = jax.vmap(
            lambda l: _bloom_query(ctx, st.bloom_active, l, g)
        )(lbas_c)
        in_p = jax.vmap(
            lambda l: _bloom_query(ctx, st.bloom_passive, l, g)
        )(lbas_c)
        demote_flag = ~in_a & ~in_p
    else:
        # static detector: pages never change temperature during GC, so
        # the whole flags/targets machinery below is structurally absent
        demote_flag = jnp.zeros(b, bool)

    # -- per-slot target groups, exact sequential semantics. A demoted page
    # lands one group colder BY CURRENT HIT-RATE ORDER, and hit rates
    # (grp_p / grp_size) drift as the drain itself moves pages — so when any
    # page is flagged, a lax.scan carrying ONLY the [G] group sizes replays
    # the per-page neighbor decisions bit-exactly. The common case (static
    # detector / nothing flagged) short-circuits to constant targets.
    grp_p, grp_active = st.grp_p, st.grp_active

    def const_targets(_):
        return jnp.full(b, g32)

    arange_g = jnp.arange(g_max, dtype=jnp.int32)

    def scan_targets(_):
        def body(gs, xs):
            flag, live = xs
            # _hit_rates over the drifted sizes, [G]-sized; the
            # next-colder neighbor comes from the shared reduction helper
            # (== _sgv_neighbors' stable argsort; no sort — a batched
            # XLA:CPU sort 16×/drain dominates the drain)
            hr = jnp.where(
                grp_active,
                grp_p / jnp.maximum(gs.astype(jnp.float32), 1.0),
                -1.0,
            )
            nb = _neighbor_colder(hr, grp_active, g32, g_known_active=True)
            t = jnp.where(flag & live, nb, g32).astype(jnp.int32)
            gs = gs.at[g].add(jnp.where(live, -1, 0)).at[t].add(
                jnp.where(live, 1, 0)
            )
            return gs, t

        # full unroll: B is small and static; the scan-loop overhead on
        # XLA:CPU would otherwise dominate the tiny [G]-sized body.
        # The carry is the EFFECTIVE sizes (grp_live, what _hit_rates
        # reads); identical drift to grp_size within a drain.
        _, ts = jax.lax.scan(
            body, st.grp_live, (demote_flag, is_live), unroll=b
        )
        return ts

    if ctx.can_demote:
        targets = jax.lax.cond(
            jnp.any(demote_flag & is_live), scan_targets, const_targets, 0
        )
    else:
        targets = const_targets(0)
    t_live = jnp.where(is_live, targets, g_max)  # dead rows → masked out

    # NOTE on lowering: XLA:CPU's scatter expander rewrites every multi-row
    # .at[] scatter into a while loop (measured: ~14 scatters/drain → ~40
    # extra loops, ~70µs, 4× the whole drain). Group/block-sized updates
    # below therefore use DENSE one-hot masked ops ([b,G]/[G,K]/[b,K] —
    # tiny, they fuse); only the two capacity-sized updates (page_map and
    # the compact_slots pool copy) stay 1-D scatters, where ONE expanded
    # loop per drain beats a capacity-wide mask. Scalar-index updates (the
    # victim erase) lower to dynamic-update-slice and are free either way.
    arange_k = jnp.arange(k, dtype=jnp.int32)
    idx = jnp.arange(b, dtype=jnp.int32)

    # -- segment-count pages per target group; claim fresh blocks up front.
    # A victim holds ≤ B live pages, so each target group claims at most ONE
    # fresh block per drain; the i-th claim (ordered by the slot position of
    # the first non-fitting page) takes the i-th lowest-index FREE block —
    # exactly what the sequential argmax-pop produces.
    onehot_t = t_live[:, None] == arange_g[None, :]  # [b, G], live rows only
    m = jnp.sum(onehot_t, axis=0, dtype=jnp.int32)   # pages per target group
    ab = st.active_blk
    has_ab = ab >= 0
    ab_c = jnp.maximum(ab, 0)
    fill_ab = jnp.where(has_ab, st.fill[ab_c], b)
    space = b - jnp.minimum(fill_ab, b)   # free slots in the active block
    claim = m > space                     # group needs a fresh block
    seal = claim & has_ab                 # …sealing its current active

    # within-group rank of each live page, in slot order
    same = (
        (targets[:, None] == targets[None, :])
        & is_live[None, :] & is_live[:, None]
    )
    rank = jnp.sum(same & (idx[None, :] < idx[:, None]), axis=1)

    is_claim_pg = is_live & (rank == space[targets])
    claim_pos = jnp.min(
        jnp.where(onehot_t & is_claim_pg[:, None], idx[:, None], INT_MAX),
        axis=0,
    )  # [G] slot position of each group's claim
    claim_rank = jnp.sum(
        claim[None, :] & (claim_pos[None, :] < claim_pos[:, None]), axis=1
    )
    free_mask = st.state == FREE
    n_free = st.free_blocks  # carried scalar == sum(free_mask), invariant
    # free_by_rank[r] = r-th lowest FREE block index (what the sequential
    # argmax-pop hands out); an XLA:CPU sort here would cost ~100µs/drain
    frank = jnp.cumsum(free_mask) - 1  # free-rank of each free block
    free_by_rank = jnp.min(
        jnp.where(
            free_mask[None, :] & (frank[None, :] == arange_g[:, None]),
            arange_k[None, :], k,
        ),
        axis=1,
    )  # [G]
    claim_ok = claim & (claim_rank < n_free)  # pool-exhausted claims fail
    new_blk = jnp.where(
        claim_ok, free_by_rank[jnp.minimum(claim_rank, g_max - 1)], -1
    )

    # -- per-page destinations ---------------------------------------------
    space_p = space[targets]
    in_old = rank < space_p
    dst_blk = jnp.where(in_old, ab_c[targets], new_blk[targets])
    dst_slot = jnp.where(in_old, fill_ab[targets] + rank, rank - space_p)
    ok = is_live & (in_old | claim_ok[targets])
    db = jnp.where(ok, dst_blk, k)        # masked rows land nowhere

    # -- seal / claim bookkeeping ------------------------------------------
    seal_mask = jnp.any(
        (ab_c[None, :] == arange_k[:, None]) & seal[None, :], axis=1
    )  # [K]
    claim_onehot = (
        (new_blk[None, :] == arange_k[:, None]) & claim_ok[None, :]
    )  # [K, G]
    claim_mask = jnp.any(claim_onehot, axis=1)
    state_a = jnp.where(seal_mask, CLOSED, st.state)
    state_a = jnp.where(claim_mask, OPEN, state_a)
    group_of = jnp.where(
        claim_mask, jnp.sum(claim_onehot * arange_g[None, :], axis=1),
        st.group_of,
    )
    stamp = jnp.where(
        claim_mask,
        jnp.sum(claim_onehot * (st.clock + claim_rank)[None, :], axis=1),
        st.stamp,
    )
    n_claimed = jnp.sum(claim_ok)
    clock = st.clock + n_claimed
    grp_phys = st.grp_phys + claim_ok.astype(jnp.int32)
    active_blk = jnp.where(claim_ok, new_blk, ab)

    # -- land the pages (dense chunked writes) ------------------------------
    dst_onehot = db[:, None] == arange_k[None, :]    # [b, K], ok rows only
    dst_count = jnp.sum(dst_onehot, axis=0, dtype=jnp.int32)
    fill_a = jnp.where(claim_mask, 0, st.fill) + dst_count
    live_a = st.live + dst_count
    # the slot-content copy (victim slots → destination slots) is the GC
    # kernel's move list: Pallas-backed on TPU, dense one-hot writes off-TPU
    slot_lba, valid = compact_slots(
        st.slot_lba, st.valid,
        jnp.where(ok, victim, -1), idx, db, dst_slot,
    )
    # 1-D scatter, not a [b, LBA] one-hot: a dense mask here would scale
    # with drive capacity, and a single expanded scatter loop per site is
    # measurably cheaper than the capacity-wide mask even at test geometry
    page_map = st.page_map.at[jnp.where(is_live, lbas_c, lba_pages)].set(
        jnp.where(ok, dst_blk * b + dst_slot, -1), mode="drop"
    )  # dead slots land out of bounds → untouched
    landed = jnp.sum(onehot_t & ok[:, None], axis=0, dtype=jnp.int32)
    grp_size = st.grp_size.at[g].add(-n_live) + landed
    grp_live_a = st.grp_live.at[g].add(-n_live) + landed
    n_lost = jnp.sum(is_live & jnp.logical_not(ok))  # dropped migrations

    # -- erase the victim ---------------------------------------------------
    grp_phys_f = grp_phys.at[g].add(-1)
    e_old = st.erase_count[victim]
    return st.replace(
        state=state_a.at[victim].set(FREE),
        group_of=group_of.at[victim].set(-1),
        fill=fill_a.at[victim].set(0),
        live=live_a.at[victim].set(0),
        slot_lba=slot_lba.at[victim].set(-1),
        valid=valid.at[victim].set(False),
        stamp=stamp.at[victim].set(clock),
        clock=clock + 1,
        grp_phys=grp_phys_f,
        grp_surplus=surplus_of(st.grp_active, grp_phys_f, st.grp_alloc),
        free_blocks=st.free_blocks - n_claimed + 1,
        mapped_pages=st.mapped_pages - n_lost,
        active_blk=active_blk,
        page_map=page_map,
        grp_size=grp_size,
        grp_live=grp_live_a,
        n_mig=st.n_mig + jnp.sum(ok),
        n_dropped=st.n_dropped + n_lost,
        n_erase=st.n_erase + 1,
        # wear: one more P-E cycle on the victim; Σe² gains (e+1)² − e²
        erase_count=st.erase_count.at[victim].add(1),
        trim_dead=st.trim_dead.at[victim].set(0),
        erase_total=st.erase_total + 1,
        erase_sq_total=st.erase_sq_total + 2 * e_old + 1,
    )


@_scoped("ssd.drain")
def _gc_drain_bulk_static(ctx: SimContext, st: SimState, victim, g):
    """Single-target specialization of :func:`_gc_drain_bulk` for
    static-detector contexts (``ctx.can_demote=False``).

    Every live page lands back in group g, so the per-target-group claim
    machinery ([b,G] one-hots, free-rank assignment, [K]-wide seal/claim
    masks) collapses to scalars: at most ONE fresh block is claimed (the
    lowest-index FREE block — what the sequential pop hands out) and every
    block-sized update is a masked single-index store. Elementwise-
    identical to the general drain with constant targets, which the
    bulk-vs-reference equivalence suite asserts for every static manager.
    """
    b = ctx.geom.pages_per_block
    k = ctx.geom.n_blocks
    lba_pages = st.page_map.shape[0]

    lbas = st.slot_lba[victim]            # [B]; dead slots hold -1
    is_live = st.valid[victim] != 0       # [B]
    lbas_c = jnp.maximum(lbas, 0)
    n_live = jnp.sum(is_live)
    live_i = is_live.astype(jnp.int32)
    rank = jnp.cumsum(live_i) - live_i    # live-rank of each slot

    ab = st.active_blk[g]
    has_ab = ab >= 0
    ab_c = jnp.maximum(ab, 0)
    fill_ab = jnp.where(has_ab, st.fill[ab_c], b)
    space = b - jnp.minimum(fill_ab, b)   # free slots in the active block
    claim = n_live > space
    seal = claim & has_ab

    new_blk = jnp.argmax(st.state == FREE)  # lowest-index FREE block
    claim_ok = claim & (st.free_blocks >= 1)
    new_c = jnp.where(claim_ok, new_blk, 0)

    # -- per-page destinations ---------------------------------------------
    in_old = rank < space
    dst_blk = jnp.where(in_old, ab_c, new_c)
    dst_slot = jnp.where(in_old, fill_ab + rank, rank - space)
    ok = is_live & (in_old | claim_ok)
    db = jnp.where(ok, dst_blk, k)        # masked rows land nowhere
    n_old = jnp.minimum(n_live, space)
    n_new = jnp.where(claim_ok, n_live - n_old, 0)
    n_ok = n_old + n_new

    # -- seal / claim bookkeeping (all scalar-index stores) -----------------
    state_a = st.state.at[ab_c].set(
        jnp.where(seal, CLOSED, st.state[ab_c])
    )
    state_a = state_a.at[new_c].set(
        jnp.where(claim_ok, OPEN, state_a[new_c])
    )
    group_of = st.group_of.at[new_c].set(
        jnp.where(claim_ok, g, st.group_of[new_c])
    )
    stamp = st.stamp.at[new_c].set(
        jnp.where(claim_ok, st.clock, st.stamp[new_c])
    )
    clock = st.clock + jnp.where(claim_ok, 1, 0)
    fill_a = st.fill.at[ab_c].add(jnp.where(has_ab, n_old, 0))
    fill_a = fill_a.at[new_c].set(
        jnp.where(claim_ok, n_new, fill_a[new_c])
    )
    live_a = st.live.at[ab_c].add(jnp.where(has_ab, n_old, 0))
    live_a = live_a.at[new_c].add(jnp.where(claim_ok, n_new, 0))
    active_blk = st.active_blk.at[g].set(jnp.where(claim_ok, new_blk, ab))

    # -- land the pages -----------------------------------------------------
    idx = jnp.arange(b, dtype=jnp.int32)
    slot_lba, valid = compact_slots(
        st.slot_lba, st.valid,
        jnp.where(ok, victim, -1), idx, db, dst_slot,
    )
    page_map = st.page_map.at[jnp.where(is_live, lbas_c, lba_pages)].set(
        jnp.where(ok, dst_blk * b + dst_slot, -1), mode="drop"
    )  # dead slots land out of bounds → untouched

    # -- erase the victim ---------------------------------------------------
    # +1 physical block if one was claimed, -1 for the erased victim
    grp_phys = st.grp_phys.at[g].add(jnp.where(claim_ok, 0, -1))
    e_old = st.erase_count[victim]
    return st.replace(
        state=state_a.at[victim].set(FREE),
        group_of=group_of.at[victim].set(-1),
        fill=fill_a.at[victim].set(0),
        live=live_a.at[victim].set(0),
        slot_lba=slot_lba.at[victim].set(-1),
        valid=valid.at[victim].set(False),
        stamp=stamp.at[victim].set(clock),
        clock=clock + 1,
        grp_phys=grp_phys,
        grp_surplus=surplus_of(st.grp_active, grp_phys, st.grp_alloc),
        free_blocks=st.free_blocks - jnp.where(claim_ok, 1, 0) + 1,
        mapped_pages=st.mapped_pages - (n_live - n_ok),
        active_blk=active_blk,
        page_map=page_map,
        grp_size=st.grp_size.at[g].add(n_ok - n_live),
        grp_live=st.grp_live.at[g].add(n_ok - n_live),
        n_mig=st.n_mig + n_ok,
        n_dropped=st.n_dropped + (n_live - n_ok),
        n_erase=st.n_erase + 1,
        erase_count=st.erase_count.at[victim].add(1),
        trim_dead=st.trim_dead.at[victim].set(0),
        erase_total=st.erase_total + 1,
        erase_sq_total=st.erase_sq_total + 2 * e_old + 1,
    )


@_scoped("ssd.drain")
def _gc_drain_reference(ctx: SimContext, st: SimState, victim, g, demote_fn):
    """The pre-refactor per-page drain (16-step fori of single-page writes).

    Kept as the equivalence oracle for :func:`_gc_drain_bulk`
    (tests/test_bulk_gc.py); never on the default path.
    """
    b = ctx.geom.pages_per_block

    def body(j, st):
        # masked migration (no lax.cond: under vmap a per-slot cond would
        # select over the whole state pytree B×/GC)
        lba = st.slot_lba[victim, j]
        is_live = st.valid[victim, j] != 0
        lba_c = jnp.maximum(lba, 0)  # dead slots hold -1
        st = st.replace(
            valid=st.valid.at[victim, j].set(
                jnp.where(is_live, False, st.valid[victim, j])
            ),
            live=st.live.at[victim].add(jnp.where(is_live, -1, 0)),
        )
        g_tgt = demote_fn(st, lba_c, g)  # pure read of st
        d = jnp.where(is_live, -1, 0)
        st = st.replace(
            grp_size=st.grp_size.at[g].add(d),
            grp_live=st.grp_live.at[g].add(d),
            mapped_pages=st.mapped_pages + d,
        )
        return _write_page(
            ctx, st, lba_c, g_tgt, is_migration=True, enabled=is_live
        )

    st = jax.lax.fori_loop(0, b, body, st)
    # erase
    grp_phys = st.grp_phys.at[g].add(-1)
    e_old = st.erase_count[victim]
    return st.replace(
        state=st.state.at[victim].set(FREE),
        group_of=st.group_of.at[victim].set(-1),
        fill=st.fill.at[victim].set(0),
        live=st.live.at[victim].set(0),
        slot_lba=st.slot_lba.at[victim].set(-1),
        valid=st.valid.at[victim].set(False),
        stamp=st.stamp.at[victim].set(st.clock),
        clock=st.clock + 1,
        grp_phys=grp_phys,
        grp_surplus=surplus_of(st.grp_active, grp_phys, st.grp_alloc),
        free_blocks=st.free_blocks + 1,
        n_erase=st.n_erase + 1,
        erase_count=st.erase_count.at[victim].add(1),
        trim_dead=st.trim_dead.at[victim].set(0),
        erase_total=st.erase_total + 1,
        erase_sq_total=st.erase_sq_total + 2 * e_old + 1,
    )


@_scoped("ssd.gc")
def _gc_one(ctx: SimContext, st: SimState, g, policy, rate_fn, gc_w,
            enabled=True):
    """GC one victim in group g; migrate live pages via the bulk drain.

    rate_fn(st, lba) -> the page's true update rate (oracle detector input);
    must be a pure function of drain-invariant data (it is: oracle arrays
    are indexed by lba/phase only). The §5.6 demotion rule itself is
    derived from ``policy`` — see _gc_drain_bulk / _target_group_gc.

    gc_w: the traced (α, β, γ, τ) victim-score weights (see
    :func:`_select_victim`); callers pass ``policy["gc_w"]`` or a fixed
    point like :data:`GC_W_GREEDY`.

    enabled: the caller's firing predicate, folded into the ONE dieted
    drain cond here instead of a second full-state cond at the call site
    (victim selection is a pair of [K] reductions, cheap to run masked).
    """
    assert ctx.gc_impl in ("bulk", "reference"), ctx.gc_impl
    victim, ok = _select_victim(ctx, st, g, gc_w)
    # migrations may need one fresh block beyond the active's free slots:
    # never start a GC with an empty pool (callers keep it ≥ 2).
    ok = ok & (st.free_blocks >= 1) & enabled
    if ctx.gc_impl == "bulk" and not ctx.can_demote:
        # static detector: every page lands back in g — the scalar-claim
        # specialization (no [b,G]/[K]-wide claim machinery per step)
        def drain(s):
            return _gc_drain_bulk_static(ctx, s, victim, g)
    elif ctx.gc_impl == "bulk":
        def drain(s):
            return _gc_drain_bulk(ctx, s, victim, g, policy, rate_fn)
    else:
        def demote_fn(s, l, gg):
            return _target_group_gc(ctx, s, l, gg, policy, rate_fn)

        def drain(s):
            return _gc_drain_reference(ctx, s, victim, g, demote_fn)

    if ctx.with_faults:
        # the fault hook runs on the drain OUTPUT (victim just erased),
        # inside this same dieted cond — no second full-state select
        base_drain = drain

        def drain(s):
            return _erase_fault_retire(ctx, base_drain(s), victim, g, policy)

    return _cond_fields(ok, drain, st, _gc_fields(ctx))


# ---------------------------------------------------------------------------
# over-provisioning allocation (interval) — §5.5
# ---------------------------------------------------------------------------

def _recompute_alloc(ctx: SimContext, st: SimState, policy):
    geom, mcfg = ctx.geom, ctx.mcfg
    b = geom.pages_per_block
    active = st.grp_active
    # EFFECTIVE group sizes (carried grp_live == mapped pages per group;
    # == grp_size by construction — a trimmed page belongs to no group):
    # trimmed pages drop out of s, so op_total below grows by exactly the
    # trimmed span — TRIM is dynamic over-provisioning the §5.5 budgets
    # redistribute at the next interval (Frankie et al., arXiv:1208.1794).
    s = jnp.where(active, st.grp_live.astype(jnp.float32), 0.0)
    s = jnp.maximum(s, jnp.where(active, 1.0, 0.0))
    use_assumed = policy["alloc_mode"] == ALLOC_FDP
    p = jnp.where(
        active, jnp.where(use_assumed, policy["assumed_p"], st.grp_p), 0.0
    )
    p = p / jnp.maximum(p.sum(), 1e-9)
    # usable OP = spare pages beyond logical content, minus the GC reserve
    # and one block per active group (absorbs the per-group ceil slack so
    # the budgets can never collectively over-claim the pool)
    n_active = active.sum()
    op_total = (
        jnp.asarray(geom.pba_pages, jnp.float32)
        - (mcfg.gc_reserve_blocks + 1 + n_active) * b
        - s.sum()
    )
    if ctx.with_faults:
        # retired capacity leaves the OP budget: the allocator divides the
        # SHRUNKEN physical space, so predicted_wa()/model_error() track
        # the degraded geometry. Zero-retirement drives subtract exactly
        # 0.0. Budgets refresh at the next §5.1 interval (deliberate — an
        # eager realloc on retire would make the 80-iter closed-form
        # bisection a per-step select).
        op_total = op_total - st.retired_blocks.astype(jnp.float32) * b

    if ctx.use_closed_alloc:
        op_closed = allocate_closed_form(
            s, p, op_total,
            cold_rule=True,
            cold_hit_rate_frac=mcfg.cold_hit_rate_frac,
            cold_op_frac=mcfg.cold_op_frac,
        )
    else:
        # no drive in this context reads the closed form (is_closed is
        # identically False): skip its 80-iteration eq.-3 bisection
        op_closed = jnp.zeros_like(s)
    op_size = allocate_by_size(s, op_total)
    op_freq = allocate_by_frequency(p, op_total)
    is_closed = (policy["alloc_mode"] == ALLOC_CLOSED) | use_assumed
    is_freq = policy["alloc_mode"] == ALLOC_FREQ
    op = jnp.where(is_closed, op_closed, jnp.where(is_freq, op_freq, op_size))
    alloc_blocks = jnp.ceil((s + op) / b).astype(jnp.int32)
    alloc_blocks = jnp.where(active, jnp.maximum(alloc_blocks, 1), 0)
    return st.replace(
        grp_alloc=alloc_blocks,
        grp_surplus=surplus_of(active, st.grp_phys, alloc_blocks),
    )


def _interval_update(ctx: SimContext, st: SimState, policy):
    a = policy["ewma_a"]
    u = st.grp_writes.astype(jnp.float32) / policy["h"].astype(jnp.float32)
    active = st.grp_active
    st = st.replace(
        # p·(1−a) + a·u written with ONE product: LLVM contracts a
        # mul+add into an FMA, and with two products it picked a different
        # one batched and unbatched (a one-ulp split between vmap and jit)
        grp_p=jnp.where(active, st.grp_p + a * (u - st.grp_p), 0.0),
        grp_writes=jnp.zeros_like(st.grp_writes),
        interval=st.interval + 1,
        cooldown=jnp.maximum(st.cooldown - 1, 0),
    )
    if ctx.use_dynamic:  # §5.2 create/merge: two argsorts per interval
        st = _maybe_create_or_merge(ctx, st, policy)
    st = _recompute_alloc(ctx, st, policy)
    return st


# ---------------------------------------------------------------------------
# group creation / merging (dynamic mode) — §5.2
# ---------------------------------------------------------------------------

def _hit_rates(st: SimState):
    # per EFFECTIVE (mapped) page — under TRIM a group's temperature is
    # measured over the pages it actually holds (grp_live, the carried
    # utilization counter; == grp_size, see its declaration)
    s = jnp.maximum(st.grp_live.astype(jnp.float32), 1.0)
    hr = st.grp_p / s
    return jnp.where(st.grp_active, hr, -1.0)


def _maybe_create_or_merge(ctx: SimContext, st: SimState, policy):
    mcfg = ctx.mcfg
    dynamic = policy["dynamic_groups"]
    f_min = policy["f_min_pages"]
    hr = _hit_rates(st)
    order = jnp.argsort(-hr)  # hottest first
    hottest, second = order[0], order[1]
    n_active = st.grp_active.sum()
    can_slot = n_active < policy["max_groups"]
    hot_ratio = hr[hottest] / jnp.maximum(hr[second], 1e-12)
    create = (
        dynamic
        & can_slot
        & (st.cooldown == 0)
        & (n_active >= 2)
        & (hot_ratio >= mcfg.q_create)
        & (st.grp_size[hottest] >= f_min)
    )

    def do_create(st):
        slot = jnp.argmin(st.grp_active)  # first inactive slot
        grp_active = st.grp_active.at[slot].set(True)
        grp_phys = st.grp_phys.at[slot].set(0)
        return st.replace(
            grp_active=grp_active,
            # seed stats: half the hottest group's measured frequency
            grp_p=st.grp_p.at[slot].set(st.grp_p[hottest] * 0.5),
            grp_size=st.grp_size.at[slot].set(0),
            grp_live=st.grp_live.at[slot].set(0),
            grp_phys=grp_phys,
            grp_surplus=surplus_of(grp_active, grp_phys, st.grp_alloc),
            grp_created=st.grp_created.at[slot].set(st.interval),
            cooldown=jnp.asarray(mcfg.w_intervals, jnp.int32),
        )

    st = _cond_fields(
        create, do_create, st,
        ("grp_active", "grp_p", "grp_size", "grp_live", "grp_phys",
         "grp_surplus", "grp_created", "cooldown"),
    )

    # merge: coldest adjacent pair that converged, or an undersized group
    hr = _hit_rates(st)
    order = jnp.argsort(-hr)
    n_active = st.grp_active.sum()
    # adjacent pair ratios in hit-rate order
    hr_sorted = hr[order]
    idx = jnp.arange(hr.shape[0])
    valid_pair = (idx + 1 < n_active)
    ratio = hr_sorted / jnp.maximum(jnp.roll(hr_sorted, -1), 1e-12)
    converged = valid_pair & (ratio < 1.3) & (hr_sorted > 0)
    tiny = valid_pair & (
        st.grp_size[order] < f_min
    ) & (jnp.roll(hr_sorted, -1) > 0)
    mergeable = converged | tiny
    pair_i = jnp.argmax(mergeable)
    do_merge = (
        dynamic & mergeable[pair_i] & (st.cooldown == 0) & (n_active > 2)
    )

    def merge(st):
        g_from = order[pair_i]          # hotter of the pair
        g_to = order[pair_i + 1]        # absorbed into the colder
        # relabel blocks (the paper: a merge is logical)
        group_of = jnp.where(st.group_of == g_from, g_to, st.group_of)
        # seal g_from's active block (no longer reachable)
        ab = st.active_blk[g_from]
        state_a = st.state.at[jnp.maximum(ab, 0)].set(
            jnp.where(ab >= 0, CLOSED, st.state[jnp.maximum(ab, 0)])
        )
        merged = {}
        # RETIRED blocks keep their group label, so a merge must move the
        # per-group retired counts along with the live/phys aggregates
        merge_keys = ("grp_size", "grp_live", "grp_phys", "grp_p",
                      "grp_writes")
        if ctx.with_faults:
            merge_keys = merge_keys + ("grp_retired",)
        for key in merge_keys:
            arr = getattr(st, key)
            merged[key] = arr.at[g_to].add(arr[g_from]).at[g_from].set(0)
        grp_active = st.grp_active.at[g_from].set(False)
        return st.replace(
            group_of=group_of,
            state=state_a,
            active_blk=st.active_blk.at[g_from].set(-1),
            grp_active=grp_active,
            grp_surplus=surplus_of(
                grp_active, merged["grp_phys"], st.grp_alloc
            ),
            cooldown=jnp.asarray(mcfg.w_intervals, jnp.int32),
            **merged,
        )

    merge_cond_fields = (
        "group_of", "state", "active_blk", "grp_active", "grp_surplus",
        "cooldown", "grp_size", "grp_live", "grp_phys", "grp_p",
        "grp_writes",
    )
    if ctx.with_faults:
        merge_cond_fields = merge_cond_fields + ("grp_retired",)
    return _cond_fields(do_merge, merge, st, merge_cond_fields)


# ---------------------------------------------------------------------------
# temperature detection — §5.6 (+ oracle modes for §6 experiments)
# ---------------------------------------------------------------------------

def _sgv_neighbors(st: SimState):
    """hotter_of[g], colder_of[g] by current hit-rate order (argsort form).

    Kept as the semantic oracle for the reduction-based
    :func:`_neighbor_hotter` / :func:`_neighbor_colder` the hot paths use
    (tests/test_write_engine.py cross-checks them on random stats) — an
    XLA:CPU argsort hauled through every vmapped write step is measurable.
    """
    hr = _hit_rates(st)
    g_max = hr.shape[0]
    # rank[g] = position in descending order
    order = jnp.argsort(-hr)
    rank = jnp.zeros(g_max, jnp.int32).at[order].set(jnp.arange(g_max))
    n_active = st.grp_active.sum()

    def neighbor(g, delta):
        r = rank[g] + delta
        r = jnp.clip(r, 0, n_active - 1)
        return order[r]

    return neighbor


def _neighbor_hotter(hr, active, g):
    """``order[clip(rank[g]-1, 0, n_active-1)]`` of the stable (-hr, idx)
    sort, as two reductions: the adjacent hotter group is the candidate
    (hotter than g, or same hr with lower index) with the LOWEST hit rate,
    ties to the highest index; with no candidate g is already hottest and
    stays put."""
    g_max = hr.shape[0]
    idx = jnp.arange(g_max, dtype=jnp.int32)
    g = jnp.asarray(g, jnp.int32)
    hr_g = hr[g]
    cand = active & ((hr > hr_g) | ((hr == hr_g) & (idx < g)))
    min_hr = jnp.min(jnp.where(cand, hr, jnp.inf))
    nb = jnp.max(jnp.where(cand & (hr == min_hr), idx, -1))
    return jnp.where(jnp.any(cand), nb, g).astype(jnp.int32)


def _neighbor_colder(hr, active, g, *, g_known_active: bool = False):
    """``order[clip(rank[g]+1, 0, n_active-1)]``: the candidate set is
    every active group strictly after g in (-hr, index) lexicographic
    order, and the neighbor is its (max hr, then min index) element. An
    empty candidate set means an active g is already the coldest and stays
    put; an inactive g (post-merge corner) falls to the coldest active —
    exactly argsort's clip(rank+1, n_active-1).

    g_known_active: trace-time promise that g is active (GC always drains
    an active group), which drops the coldest-active fallback reductions —
    this runs once per unrolled iteration of the drain's demotion scan.
    """
    g_max = hr.shape[0]
    idx = jnp.arange(g_max, dtype=jnp.int32)
    g = jnp.asarray(g, jnp.int32)
    hr_g = hr[g]
    cand = active & ((hr < hr_g) | ((hr == hr_g) & (idx > g)))
    best_hr = jnp.max(jnp.where(cand, hr, -2.0))
    nb = jnp.min(jnp.where(cand & (hr == best_hr), idx, g_max))
    if g_known_active:
        fallback = g
    else:
        cold_hr = jnp.min(jnp.where(active, hr, jnp.inf))
        coldest = jnp.max(jnp.where(active & (hr == cold_hr), idx, -1))
        fallback = jnp.where(active[g], g, coldest)
    return jnp.where(jnp.any(cand), nb, fallback).astype(jnp.int32)


def _target_group_app(ctx: SimContext, st: SimState, lba, cur_g, policy, rate_fn):
    """Target group for an application update of `lba` living in cur_g.

    The detector is dispatched at TRACE time from ``ctx.mcfg.td_mode``:
    every compiled step (one drive under jit, or one structure-homogeneous
    fleet sub-batch under vmap — see fleet._part_key) has exactly one
    detector, so the former per-step ``lax.switch`` over all branches —
    which under vmap selected the full [G, bits] bloom triple three ways
    every write — is structurally a single branch.
    """
    cur_g = jnp.asarray(cur_g, jnp.int32)
    td = ctx.mcfg.td_mode
    if td == "static" or not ctx.can_demote:
        # pages never change temperature: no detector machinery at all
        return st, cur_g
    if td == "fdp":
        # fixed assumed per-page rate bands: promote if ≥2× the group's
        # assumed rate (paper §5/§6: FDP's fixed-order assumption)
        with jax.named_scope("ssd.detector"):
            r = rate_fn(st, lba)
            promote = r > 2.0 * policy["fdp_rate"][cur_g]
            nb = _neighbor_hotter(_hit_rates(st), st.grp_active, cur_g)
            return st, jnp.where(promote, nb, cur_g).astype(jnp.int32)
    assert td == "bloom", td
    # bloom (§5.6): in both filters → promote
    with jax.named_scope("ssd.detector"):
        st, in_both = _bloom_update(ctx, st, lba, cur_g)
        nb = _neighbor_hotter(_hit_rates(st), st.grp_active, cur_g)
        return st, jnp.where(in_both, nb, cur_g).astype(jnp.int32)


def _target_group_gc(ctx: SimContext, st: SimState, lba, cur_g, policy, rate_fn):
    """Per-page GC demotion target (the reference drain's demote_fn);
    trace-time detector dispatch, like :func:`_target_group_app`."""
    cur_g = jnp.asarray(cur_g, jnp.int32)
    td = ctx.mcfg.td_mode
    if td == "static" or not ctx.can_demote:
        return cur_g
    if td == "fdp":
        r = rate_fn(st, lba)
        demote = r < 0.5 * policy["fdp_rate"][cur_g]
        nb = _neighbor_colder(_hit_rates(st), st.grp_active, cur_g)
        return jnp.where(demote, nb, cur_g).astype(jnp.int32)
    assert td == "bloom", td
    # bloom: in neither filter during a migration → demote
    in_active = _bloom_query(ctx, st.bloom_active, lba, cur_g)
    in_passive = _bloom_query(ctx, st.bloom_passive, lba, cur_g)
    nb = _neighbor_colder(_hit_rates(st), st.grp_active, cur_g)
    return jnp.where(
        ~in_active & ~in_passive, nb, cur_g
    ).astype(jnp.int32)


# -- bloom filter pair (per group) ------------------------------------------

def _bloom_hashes(ctx: SimContext, lba):
    bits = bloom_bits(ctx.geom, ctx.mcfg)
    u = lba.astype(jnp.uint32)
    h1 = (u * jnp.uint32(2654435761)) % jnp.uint32(bits)
    h2 = (u * jnp.uint32(40503) + jnp.uint32(99991)) % jnp.uint32(bits)
    return h1.astype(jnp.int32), h2.astype(jnp.int32), bits


def _bloom_query(ctx, filt, lba, g):
    h1, h2, _ = _bloom_hashes(ctx, lba)
    return filt[g, h1] & filt[g, h2]


def _bloom_update(ctx: SimContext, st: SimState, lba, g):
    """Insert lba into group g's active filter; rotate when the group's
    write interval (= group size) elapses. Returns (st, was_in_both)."""
    h1, h2, _ = _bloom_hashes(ctx, lba)
    in_active = st.bloom_active[g, h1] & st.bloom_active[g, h2]
    in_passive = st.bloom_passive[g, h1] & st.bloom_passive[g, h2]
    bloom_active = st.bloom_active.at[g, h1].set(True).at[g, h2].set(True)
    bloom_writes = st.bloom_writes.at[g].add(1)
    rotate = bloom_writes[g] >= jnp.maximum(
        st.grp_size[g], ctx.mcfg.bloom_rotate_min_writes
    )
    # row-masked rotation (no lax.cond: under vmap a cond would select over
    # the full [G, bits] filter pair every step; this touches one row)
    row_active = bloom_active[g]
    st = st.replace(
        bloom_passive=st.bloom_passive.at[g].set(
            jnp.where(rotate, row_active, st.bloom_passive[g])
        ),
        bloom_active=bloom_active.at[g].set(
            jnp.where(rotate, False, row_active)
        ),
        bloom_writes=bloom_writes.at[g].set(
            jnp.where(rotate, 0, bloom_writes[g])
        ),
    )
    return st, in_active & in_passive


# ---------------------------------------------------------------------------
# the step + runner
# ---------------------------------------------------------------------------

def _step_tail(ctx: SimContext, st: SimState, lba, t, g, policy, lookup):
    """GC → emergency valve → write → movement ops → §5.1 interval update.

    The seed step order downstream of invalidate + target selection. Runs
    on every write of the reference engine (``ctx.fast_path=False``) and as
    the heavy branch of the split engine. All pool/budget predicates are
    O(1) reads of the carried ``free_blocks``/``grp_surplus`` accounting.
    """
    geom, mcfg = ctx.geom, ctx.mcfg
    b = geom.pages_per_block

    # GC when the group needs a new block it is not entitled to, or the
    # pool is at reserve.
    with jax.named_scope("ssd.gc"):
        blk = st.active_blk[g]
        needs_block = jnp.where(
            blk >= 0, st.fill[jnp.maximum(blk, 0)] >= b, True
        )
        over_budget = st.grp_phys[g] >= st.grp_alloc[g]
        low_pool = st.free_blocks <= mcfg.gc_reserve_blocks
        do_gc = needs_block & (over_budget | low_pool)
    st = _gc_one(ctx, st, g, policy, lookup, policy["gc_w"], enabled=do_gc)

    # emergency valve: if the pool is (nearly) empty, greedily reclaim
    # from the fullest group until headroom returns (bounded loop; only
    # fires when a policy briefly overdraws its budget). The carry is the
    # GC-mutable field subset, not the whole state.
    def needs_air(s, tries):
        return (s.free_blocks < 2) & (tries < mcfg.valve_max_tries)

    def reclaim(s, tries):
        # global greedy: the best victim anywhere (its group pays)
        closed = s.state == CLOSED
        score = jnp.where(closed, s.live, INT_MAX)
        victim = jnp.argmin(score)
        g_v = jnp.maximum(s.group_of[victim], 0)
        return (
            _gc_one(ctx, s, g_v, policy, lookup,
                    jnp.asarray(GC_W_GREEDY, jnp.float32)),
            tries + 1,
        )

    with jax.named_scope("ssd.valve"):
        st, _ = _while_fields(needs_air, reclaim, st, 0, _gc_fields(ctx))

    with jax.named_scope("ssd.append"):
        st = _write_page(ctx, st, lba, g, is_migration=False)
        st = st.replace(
            n_app=st.n_app + 1,
            grp_writes=st.grp_writes.at[g].add(1),
        )

    # movement operations (§5.3): one compaction GC per step on the most
    # surplus group, donating the redeemed block to the pool. Structurally
    # absent when the context rules movement out (ctx.use_movement=False).
    if ctx.use_movement:
        with jax.named_scope("ssd.movement"):
            g_s = jnp.argmax(st.grp_surplus)
            pool_ok = st.free_blocks >= 2  # migration headroom
            st = _gc_one(
                ctx, st, g_s, policy, lookup, policy["gc_w"],
                enabled=policy["movement_ops"] & (st.grp_surplus[g_s] >= 1)
                & pool_ok,
            )

    # interval completion (§5.1); t+1 == n_app after this write, so the
    # predicate is exactly (n_app % h == 0). With a fleet-shared h it is
    # a SCALAR shared by every vmapped drive; per-drive interval sweeps
    # (ctx.per_drive_interval) read the traced policy["h"] instead. In
    # op-stream mode t is the EVENT index (trims interleave, so write
    # counts diverge across drives) and the predicate reads the carried
    # write clock — same value under a pure-write stream, where
    # st.n_app == t + 1 at this point.
    h = policy["h"] if ctx.per_drive_interval else ctx.h
    interval_fields = _INTERVAL_FIELDS
    if ctx.with_faults:
        # §5.2 merges relabel retired counts (see _maybe_create_or_merge)
        interval_fields = interval_fields + ("grp_retired",)
    with jax.named_scope("ssd.interval"):
        if ctx.with_trim:
            is_interval = (st.n_app % h) == 0
        else:
            is_interval = ((t + 1) % h) == 0
        return _cond_fields(
            is_interval,
            lambda s: _interval_update(ctx, s, policy),
            st,
            interval_fields,
        )


@_scoped("ssd.trim")
def _trim_page(ctx: SimContext, st: SimState, lba):
    """The op-stream TRIM step: unmap ``lba`` and kill its physical slot.

    The fast-path peer of the ``kernels/write_path`` append — the counter
    half rides :func:`_invalidate_counts` (O(1) carried updates: ``live``,
    ``grp_size``/``grp_live``, ``mapped_pages``) and the mapping half is
    one fused ``apply_trim`` op. A TRIM frees space, so it can never need
    the GC / valve / movement machinery, and it completes no application
    write, so it never closes a §5.1 interval: there is no heavy path.
    A re-trim of an already-unmapped page is a counted no-op.
    """
    st, _old_g, old_pm = _invalidate_counts(ctx, st, lba)
    page_map, valid = apply_trim(st.page_map, st.valid, lba, old_pm)
    # the killed slot is a trimmed-but-unerased hole: tally it on its
    # block for the victim score's τ term (cleared when the block erases)
    has = old_pm >= 0
    blk_c = jnp.maximum(old_pm, 0) // ctx.geom.pages_per_block
    return st.replace(
        page_map=page_map, valid=valid, n_trim=st.n_trim + 1,
        trim_dead=st.trim_dead.at[blk_c].add(jnp.where(has, 1, 0)),
    )


def _halt_wrap(ctx: SimContext, body):
    """Freeze a degraded drive: once ``drive_status`` leaves STATUS_OK
    (spares exhausted, see :func:`_erase_fault_retire`) every subsequent
    op is a counted no-op — the drive is an inert lane that only bumps
    ``n_halted``, never a crashed trace or an invariant violation. The
    guard is one dieted cond over the op-mutable field set; fault-free
    contexts return ``body`` unchanged (zero structural footprint).
    Under vmap a degraded lane still executes both select branches on its
    (frozen, valid) state — all inner loops stay bounded."""
    if not ctx.with_faults:
        return body

    def guarded(st, *args):
        out = jax.lax.cond(
            st.drive_status == STATUS_OK,
            lambda s: _fields_of(body(s, *args), _OP_FIELDS),
            lambda s: _fields_of(
                s.replace(n_halted=s.n_halted + 1), _OP_FIELDS
            ),
            st,
        )
        return st.replace(**dict(zip(_OP_FIELDS, out)))

    return guarded


def make_step(ctx: SimContext, policy, rate_fn, page_group0=None):
    """Build the per-event scan step.

    policy: traced pytree from :func:`policy_from_config` (per-drive under
    vmap). rate_fn(st, lba, t) -> true per-page update rate of `lba` at
    scan index t (oracle detector input; phase-aware in fleets).

    Pure-write mode (``ctx.with_trim=False``, the default): scan input =
    (lba, t); t is the global application-write index, which is
    deliberately NOT taken from batched state so the interval predicate
    stays a scalar under vmap whenever every drive shares h
    (ctx.per_drive_interval=False) — the expensive §5.1 bookkeeping then
    lowers to a real branch taken every h steps, not a per-step select.

    Op-stream mode (``ctx.with_trim=True``): scan input = (op, lba, t)
    with ``op ∈ {OP_WRITE, OP_TRIM}`` and t the EVENT index (it feeds only
    the oracle's phase lookup). A WRITE event runs the same write body as
    pure-write mode — only the §5.1 predicate reads the carried ``n_app``
    instead of t, the identical value whenever every event is a write —
    and a TRIM event runs :func:`_trim_page`. ``page_group0`` ([LBA]
    int32, the workload's layout groups) resolves the residence group of
    a write that RE-MAPS a trimmed page, which has no physical home to
    inherit a group from.

    With ``ctx.fast_path=True`` (default) the write is split: one whose
    target group has an open active block with room, with the pool above
    reserve, no redeemable movement surplus anywhere, and no interval
    boundary, is LEAN — invalidate counters, pick the group, and one fused
    invalidate-and-append (``kernels/write_path``). Everything else
    (:func:`_step_tail`) runs only when one of those O(1) scalar
    predicates trips. The predicates are exact, not conservative: a lean
    write is bit-identical to what the heavy path would have produced,
    which tests/test_write_engine.py asserts against ``fast_path=False``.
    The split pays where the heavy tail is a real branch: under plain
    jit, and in a fleet whose device scans one lane
    (``fleet_exec.split_step`` picks it there); under vmap the tail's
    cond is a select that runs on every write, so vmapped lanes take the
    single path. The lean append runs before the tail's cond, masked, and
    the cond's other branch passes the pools through: no branch rewrites
    a pool the other passes through, so XLA updates ``page_map``,
    ``slot_lba`` and ``valid`` in place on every lean write
    (tests/test_write_engine.py reads the compiled step).
    """
    geom, mcfg = ctx.geom, ctx.mcfg
    b = geom.pages_per_block
    if ctx.with_trim:
        assert page_group0 is not None, "op-stream step needs page_group0"
        page_group0 = jnp.asarray(page_group0, jnp.int32)

    @_scoped("ssd.append")
    def resolve_group(st, old_g, had_mapping, lba):
        # a write that re-maps a trimmed page inherits the workload's
        # layout group (first active group if dynamic-mode merging has
        # retired that slot); mapped pages keep their residence group
        pg0 = page_group0[lba]
        pg0 = jnp.where(
            st.grp_active[pg0], pg0, jnp.argmax(st.grp_active)
        ).astype(jnp.int32)
        return jnp.where(had_mapping, old_g, pg0).astype(jnp.int32)

    def reference_write(st, lba, t, lookup):
        # the seed-shaped single-path write, shared by both stream modes
        if ctx.with_trim:
            had = st.page_map[lba] >= 0
            st, old_g = _invalidate(ctx, st, lba)
            old_g = resolve_group(st, old_g, had, lba)
        else:
            st, old_g = _invalidate(ctx, st, lba)
        st, g = _target_group_app(ctx, st, lba, old_g, policy, lookup)
        with jax.named_scope("ssd.append"):
            g = jnp.where(st.grp_active[g], g, old_g)
        return _step_tail(ctx, st, lba, t, g, policy, lookup)

    def split_write(st, lba, t, lookup):
        st, old_g, old_pm = _invalidate_counts(ctx, st, lba)
        if ctx.with_trim:
            old_g = resolve_group(st, old_g, old_pm >= 0, lba)
        st, g = _target_group_app(ctx, st, lba, old_g, policy, lookup)
        with jax.named_scope("ssd.append"):
            g = jnp.where(st.grp_active[g], g, old_g)

        # O(1) heavy-path predicates. Exactness argument per term:
        #  * room in the active block → _step_tail's do_gc and the
        #    _write_page alloc are both predicated on the block being full
        #    (low_pool alone never GCs without needs_block);
        #  * free_blocks ≥ 2 → the emergency valve cannot fire, and the
        #    lean append claims no block so the pool is untouched;
        #  * movement: a lean append changes no grp_phys/grp_alloc, so the
        #    post-write surplus the tail would read equals the carried
        #    pre-write surplus — if its max is < 1, movement cannot fire;
        #  * the interval predicate is the tail's own (op-stream mode
        #    reads the carried write clock, not the event index).
        blk = st.active_blk[g]
        blk_c = jnp.maximum(blk, 0)
        has_room = (blk >= 0) & (st.fill[blk_c] < b)
        valve_may = st.free_blocks < 2
        if ctx.use_movement:
            movement_may = policy["movement_ops"] & (
                jnp.max(st.grp_surplus) >= 1
            )
        else:
            movement_may = False
        h = policy["h"] if ctx.per_drive_interval else ctx.h
        if ctx.with_trim:
            is_interval = ((st.n_app + 1) % h) == 0
        else:
            is_interval = ((t + 1) % h) == 0
        heavy = (~has_room) | valve_may | movement_may | is_interval

        # every write completes its invalidate here; a lean one also
        # appends (a heavy one appends in the tail). The lean write runs
        # before the cond rather than as its other branch, so the cond
        # passes the pools through on a lean write and XLA updates them
        # in place
        @_scoped("ssd.append")
        def lean_write(st):
            lean = ~heavy
            d = lean.astype(jnp.int32)
            page_map, slot_lba, valid = apply_write(
                st.page_map, st.slot_lba, st.valid, lba, old_pm, blk_c,
                st.fill[blk_c], enabled=lean,
            )
            return st.replace(
                page_map=page_map,
                slot_lba=slot_lba,
                valid=valid,
                fill=st.fill.at[blk_c].add(d),
                live=st.live.at[blk_c].add(d),
                grp_size=st.grp_size.at[g].add(d),
                grp_live=st.grp_live.at[g].add(d),
                mapped_pages=st.mapped_pages + d,
                n_app=st.n_app + d,
                grp_writes=st.grp_writes.at[g].add(d),
            )

        return _cond_fields(
            heavy,
            lambda s: _step_tail(ctx, s, lba, t, g, policy, lookup),
            lean_write(st),
            _STEP_FIELDS,
        )

    # degraded drives (faults only) freeze before any per-op work runs
    reference_write_g = _halt_wrap(ctx, reference_write)
    split_write_g = _halt_wrap(ctx, split_write)

    def reference_step(st, xs):
        lba, t = xs

        def lookup(s, l):
            return rate_fn(s, l, t)

        st = reference_write_g(st, lba, t, lookup)
        return st, (st.n_app, st.n_mig)

    def split_step(st, xs):
        lba, t = xs

        def lookup(s, l):
            return rate_fn(s, l, t)

        st = split_write_g(st, lba, t, lookup)
        return st, (st.n_app, st.n_mig)

    def op_step(st, xs):
        op, lba, t = xs

        def lookup(s, l):
            return rate_fn(s, l, t)

        write_fn = split_write if ctx.fast_path else reference_write

        def op_body(st):
            out = jax.lax.cond(
                op == OP_TRIM,
                lambda s: _fields_of(_trim_page(ctx, s, lba), _OP_FIELDS),
                lambda s: _fields_of(
                    write_fn(s, lba, t, lookup), _OP_FIELDS
                ),
                st,
            )
            return st.replace(**dict(zip(_OP_FIELDS, out)))

        st = _halt_wrap(ctx, op_body)(st)
        return st, (st.n_app, st.n_mig)

    if ctx.with_trim:
        return op_step
    return split_step if ctx.fast_path else reference_step


def scan_writes(ctx: SimContext, step, st: SimState, lbas, ts, ops=None):
    """Scan ``step`` over an event segment, honoring the chunking knobs.

    ``ops`` (required iff ``ctx.with_trim``): the per-event op codes; the
    scan then folds (op, lba, t) triples instead of (lba, t) pairs.

    ``ctx.trace_every == 1``: one scan over T steps, dense cumulative
    (n_app, n_mig) trace [T]. ``trace_every = E > 1``: the events are
    regrouped [T//E, E] (E must divide T) and the counters are emitted once
    per chunk — element j equals the dense trace at step (j+1)·E - 1. The
    inner chunk emits nothing, so XLA sees E fused write-steps between
    trace stores. Chunking preserves event-order semantics trivially: the
    same step function is folded over the same event sequence, only the
    loop nest and the trace sampling change. ``ctx.unroll`` unrolls the
    (inner) scan body to amortize XLA:CPU per-iteration overhead.
    """
    assert (ops is not None) == ctx.with_trim, (
        "ops stream and ctx.with_trim must agree"
    )
    t_total = int(lbas.shape[0])
    e = ctx.trace_every
    cols = (lbas, ts) if ops is None else (ops, lbas, ts)
    assert e <= 1 or t_total % e == 0, (
        f"trace_every={e} must divide the segment length {t_total}"
    )

    def inner(s, xs):
        s, _ = step(s, xs)
        return s, None

    def chunk(s, xs):
        # the chunk's loop: a profiler trace reads one ssd.chunk loop event
        # per trace_every events, and the step's phases inside it
        with jax.named_scope("ssd.chunk"):
            s, _ = jax.lax.scan(inner, s, xs, unroll=min(ctx.unroll, e))
        return s, (s.n_app, s.n_mig)

    # the carry holds ``valid`` as int32 0/1, the dtype the pool kernels
    # read and write in place (a bool row cannot be DMA'd): one conversion
    # each way per scan, none per write
    st = st.replace(valid=st.valid.astype(jnp.int32))
    if e <= 1:
        st, trace = jax.lax.scan(
            step, st, cols, unroll=min(ctx.unroll, max(t_total, 1))
        )
    else:
        xs = tuple(c.reshape(t_total // e, e) for c in cols)
        st, trace = jax.lax.scan(chunk, st, xs)
    return st.replace(valid=st.valid != 0), trace


# st is DONATED: the scan's carry rewrites every state array, so aliasing
# the input buffers halves peak state memory (the fleet executor makes the
# same promise per shard — core/fleet_exec.py). Callers must treat the st
# they pass in as consumed (managers.simulate threads the returned state
# forward and never re-reads its input, the donation-safe signature every
# entry point follows). Backends without input-output aliasing silently
# skip donation; numerics are unaffected either way.
@functools.partial(jax.jit, static_argnames=("ctx",), donate_argnums=(1,))
def _run_jit(ctx: SimContext, st: SimState, lbas, page_rate, policy):
    def rate_fn(s, lba, t):
        return page_rate[lba]

    step = make_step(ctx, policy, rate_fn)
    ts = st.n_app + jnp.arange(lbas.shape[0], dtype=jnp.int32)
    return scan_writes(ctx, step, st, lbas, ts)


@functools.partial(jax.jit, static_argnames=("ctx",), donate_argnums=(1,))
def _run_ops_jit(ctx: SimContext, st: SimState, ops, lbas, page_rate,
                 page_group0, policy):
    def rate_fn(s, lba, t):
        return page_rate[lba]

    step = make_step(ctx, policy, rate_fn, page_group0)
    ts = jnp.arange(lbas.shape[0], dtype=jnp.int32)  # event index
    return scan_writes(ctx, step, st, lbas, ts, ops)


def run(ctx: SimContext, st: SimState, lbas, *, ops=None, page_group0=None,
        page_rate=None, assumed_p=None, fdp_rate=None):
    """Run the simulator over a segment of writes (or, with ``ops``, of
    WRITE/TRIM events).

    lbas: int32 [T]; page_rate: float32 [LBA] true per-page update rates
    (oracle detector modes). ops: int32 [T] op codes (requires
    ``ctx.with_trim=True`` and ``page_group0`` — the [LBA] layout groups
    re-mapped pages land in). Returns (final_state, trace dict of
    CUMULATIVE counters — [T] dense, or [T // ctx.trace_every] sampled at
    every trace_every-th event) — segment the workload (e.g. at a
    frequency swap) by calling run() repeatedly with updated oracle
    arrays. ``st`` is donated into the jitted scan: treat the passed-in
    state as consumed and read only the returned one (thread it forward
    across segments, as managers.simulate does).
    """
    lbas = jnp.asarray(lbas, jnp.int32)
    if page_rate is None:
        page_rate = jnp.zeros(ctx.geom.lba_pages, jnp.float32)
    policy = policy_from_config(ctx, assumed_p, fdp_rate)
    assert (ops is not None) == ctx.with_trim, (
        "pass ops= iff the context is op-stream (ctx.with_trim)"
    )
    if ops is None:
        st, (app, mig) = _run_jit(
            ctx, st, lbas, jnp.asarray(page_rate, jnp.float32), policy
        )
    else:
        assert page_group0 is not None
        st, (app, mig) = _run_ops_jit(
            ctx, st, jnp.asarray(ops, jnp.int32), lbas,
            jnp.asarray(page_rate, jnp.float32),
            jnp.asarray(page_group0, jnp.int32), policy,
        )
    return st, {"app": app, "mig": mig}
