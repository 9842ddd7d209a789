"""Fast-path write-engine equivalence suite (the tentpole's acceptance bar).

The split step (``SimContext.fast_path=True``: O(1) scalar predicates
routing steady-state writes around the GC/valve/movement/interval
machinery, with the fused ``kernels/write_path`` append) must be
elementwise-identical to the seed-shaped single-path step retained as
``fast_path=False`` — final state, counters, and WA curves — across
manager presets, under both jit (``managers.simulate``) and vmap
(``simulate_fleet``), and against the ``gc_impl="reference"`` oracle so the
whole new engine is anchored to the seed semantics end-to-end.

Also here: the fleet's choice of engine by lanes per device (one lane →
split, vmapped lanes → single path) and the in-place lean write of the
compiled one-lane runner, the strided-trace contract (``trace_every=k``
samples the dense cumulative counters exactly) and the O(1)-accounting
invariant property test (``SimState.check_invariants`` after random write
segments under both GC drains).
"""

import dataclasses
import inspect

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import fleet, fleet_exec
from repro.core import managers as M
from repro.core import simulator as S
from repro.core import workloads as W
from repro.core.fleet import DriveSpec, simulate_fleet
from repro.core.ssd import Geometry, ManagerConfig, assert_invariants

from _runner_hlo import captured_runner, per_write_pool_copies

GEOM = Geometry(n_luns=4, blocks_per_lun=32, pages_per_block=8, lba_pba=0.7)
N_WRITES = 6_000

_MANAGERS = {
    "wolf": M.wolf,            # closed-form alloc, greedy GC, static TD
    "wolf_lru": M.wolf_lru,    # LRU GC under movement ops
    "fdp": M.fdp,              # assumed alloc, LRU GC, fdp demotion
    "wolf_dynamic": M.wolf_dynamic,  # bloom detector + dynamic groups
    "single": M.single_group,  # one group, size alloc
}


def _phases(workload: str, rng: np.random.Generator):
    lba = GEOM.lba_pages
    if workload == "two_modal":
        return [W.two_modal(
            lba, N_WRITES,
            p_hot=float(rng.uniform(0.6, 0.95)),
            frac_hot=float(rng.uniform(0.2, 0.8)),
        )]
    if workload == "tpcc":
        return [W.tpcc_like(lba, N_WRITES)]
    return list(W.swap_phases(lba, N_WRITES // 2))


def _assert_identical(a, b, label: str):
    np.testing.assert_array_equal(a.app, b.app, err_msg=f"{label}: app")
    np.testing.assert_array_equal(a.mig, b.mig, err_msg=f"{label}: mig")
    assert int(a.state["n_dropped"]) == 0, f"{label}: writes dropped"
    # the scan carries valid as int32 0/1; both engines hand back bool
    assert a.state["valid"].dtype == b.state["valid"].dtype == np.bool_
    for key, arr in a.state.items():
        np.testing.assert_array_equal(
            np.asarray(arr), np.asarray(b.state[key]),
            err_msg=f"{label}: state[{key}]",
        )
    np.testing.assert_array_equal(
        a.wa_curve(1000), b.wa_curve(1000), err_msg=f"{label}: wa_curve"
    )


class TestStepEquivalence:
    """Split engine vs the seed-shaped oracle step."""

    @settings(max_examples=6, deadline=None)
    @given(
        st.sampled_from(sorted(_MANAGERS)),
        st.sampled_from(["two_modal", "tpcc", "swap"]),
        st.integers(min_value=0, max_value=10_000),
    )
    def test_split_matches_oracle_under_jit(self, manager, workload, seed):
        mcfg = _MANAGERS[manager]()
        phases = _phases(workload, np.random.default_rng(seed))
        split = M.simulate(GEOM, mcfg, phases, seed=seed)  # fast_path=True
        oracle = M.simulate(
            GEOM, mcfg, phases, seed=seed,
            fast_path=False, gc_impl="reference",
        )
        _assert_identical(split, oracle, f"{manager}/{workload}#{seed}")

    def test_split_matches_oracle_under_vmap(self):
        """Whole mixed fleet (all four step-structure partitions, a §5.1
        sweep drive, multi-phase swap) under both engines."""
        lba, n = GEOM.lba_pages, N_WRITES
        specs = [
            DriveSpec(M.wolf(), (W.two_modal(lba, n),), seed=1),
            DriveSpec(M.fdp(), (W.two_modal(lba, n),), seed=2),
            DriveSpec(M.single_group(), (W.tpcc_like(lba, n),), seed=3),
            DriveSpec(M.wolf(ewma_a=0.6, interval_frac=0.05),
                      (W.two_modal(lba, n),), seed=4),
            DriveSpec(M.wolf(), tuple(W.swap_phases(lba, n // 2)), seed=5),
            DriveSpec(M.wolf_dynamic(), (W.tpcc_like(lba, n),), seed=6),
        ]
        split = simulate_fleet(GEOM, specs, sampler="numpy", fast_path=True)
        oracle = simulate_fleet(
            GEOM, specs, sampler="numpy",
            fast_path=False, gc_impl="reference",
        )
        np.testing.assert_array_equal(split.app, oracle.app)
        np.testing.assert_array_equal(split.mig, oracle.mig)
        for i, s in enumerate(specs):
            for key, arr in split.state(i).items():
                np.testing.assert_array_equal(
                    np.asarray(arr), np.asarray(oracle.state(i)[key]),
                    err_msg=f"{s.label}: state[{key}]",
                )
        np.testing.assert_array_equal(
            split.wa_curves(1000), oracle.wa_curves(1000)
        )


# (specs, the engine simulate_fleet's default picks for their one
# sub-batch): a lone drive is one lane on its device, two drives on one
# device are vmapped lanes
_LBA = GEOM.lba_pages
_ENGINE_CASES = {
    "wolf_tpcc_one": (
        [DriveSpec(M.wolf(), (W.tpcc_like(_LBA, N_WRITES),), seed=21)],
        "split"),
    "wolfdyn_swap_one": (
        [DriveSpec(M.wolf_dynamic(), tuple(W.swap_phases(_LBA, N_WRITES // 2)),
                   seed=22)],
        "split"),
    "two_lanes": (
        [DriveSpec(M.wolf(), (W.tpcc_like(_LBA, N_WRITES),), seed=23),
         DriveSpec(M.wolf(), (W.two_modal(_LBA, N_WRITES),), seed=24)],
        "single"),
}


class TestFleetEngine:
    """simulate_fleet's default engine follows the lanes per device: a
    device scanning one drive takes the split step, vmapped lanes the
    single path, and both equal the seed-shaped oracle."""

    @pytest.mark.parametrize("case", sorted(_ENGINE_CASES))
    def test_default_engine_matches_oracle(self, case):
        specs, engine = _ENGINE_CASES[case]
        got = simulate_fleet(GEOM, specs, sampler="jax")
        oracle = simulate_fleet(
            GEOM, specs, sampler="jax", fast_path=False, gc_impl="reference",
        )
        assert [m["engine"] for m in got.exec_meta] == [engine]
        assert [m["engine"] for m in oracle.exec_meta] == ["single"]
        np.testing.assert_array_equal(got.app, oracle.app)
        np.testing.assert_array_equal(got.mig, oracle.mig)
        for i, s in enumerate(specs):
            assert int(got.state(i)["n_dropped"]) == 0
            # the scan carries valid as int32 0/1; the fleet hands back bool
            assert got.state(i)["valid"].dtype == np.bool_
            assert oracle.state(i)["valid"].dtype == np.bool_
            for key, arr in got.state(i).items():
                np.testing.assert_array_equal(
                    np.asarray(arr), np.asarray(oracle.state(i)[key]),
                    err_msg=f"{s.label}: state[{key}]",
                )

    def test_runner_memo_keys_on_engine(self, monkeypatch):
        """A one-lane sub-batch never gets the runner a many-lane
        sub-batch of the same step structure compiled, nor the reverse."""
        monkeypatch.setattr(fleet_exec, "_RUNNERS", {})
        monkeypatch.setattr(fleet_exec, "_STATS", fleet_exec.CacheStats())
        real, built = fleet.subbatch_runner, []

        def spy(ctx, *rest):
            fn = real(ctx, *rest)
            built.append((ctx.fast_path, fn))
            return fn

        monkeypatch.setattr(fleet, "subbatch_runner", spy)
        spec = DriveSpec(M.wolf(), (W.tpcc_like(_LBA, 600),), seed=1)
        for specs in ([spec], [spec, spec], [spec]):
            simulate_fleet(GEOM, specs)
        (one, f_one), (many, f_many), (again, f_again) = built
        assert (one, many, again) == (True, False, True)
        assert f_one is not f_many and f_again is f_one
        stats = fleet_exec.step_cache_stats()
        assert (stats.misses, stats.hits) == (2, 1)

    def test_lean_write_updates_pools_in_place(self, monkeypatch):
        """In the compiled one-lane runner, no loop body that runs on every
        write, and no pass-through branch of a cond in one, copies an array
        of the page_map, slot_lba or valid shape: the lean write updates
        the pools in place (copies stay in the heavy tail)."""
        spec = DriveSpec(M.wolf_dynamic(), (W.tpcc_like(_LBA, 1000),), seed=3)
        fn, shapes = captured_runner(GEOM, spec, monkeypatch, trace_every=500)
        hlo = fn.lower(*shapes).compile().as_text()
        assert per_write_pool_copies(hlo, GEOM) == []


@pytest.mark.trim
class TestOpStreamEquivalence:
    """An all-WRITE op stream must reproduce the pure-write engine
    bit-identically — state, counters, WA curves — under jit and vmap
    (the op-stream tentpole's baseline-compatibility bar). With the host
    sampler, Phase.sample_ops consumes exactly the draws Phase.sample
    would on a pure-write phase, so the event sequences are identical and
    any divergence is the op engine's fault."""

    @settings(max_examples=6, deadline=None)
    @given(
        st.sampled_from(sorted(_MANAGERS)),
        st.sampled_from(["two_modal", "tpcc", "swap"]),
        st.integers(min_value=0, max_value=10_000),
    )
    def test_all_write_ops_match_write_engine_under_jit(
        self, manager, workload, seed
    ):
        mcfg = _MANAGERS[manager]()
        phases = _phases(workload, np.random.default_rng(seed))
        base = M.simulate(GEOM, mcfg, phases, seed=seed)
        ops = M.simulate(GEOM, mcfg, phases, seed=seed, ops_stream=True)
        _assert_identical(ops, base, f"ops:{manager}/{workload}#{seed}")
        assert int(ops.state["n_trim"]) == 0

    def test_all_write_ops_match_write_engine_under_vmap(self):
        """Whole mixed fleet (every step-structure partition forced onto
        the op engine) vs the pure-write fleet."""
        lba, n = GEOM.lba_pages, N_WRITES
        specs = [
            DriveSpec(M.wolf(), (W.two_modal(lba, n),), seed=1),
            DriveSpec(M.fdp(), (W.two_modal(lba, n),), seed=2),
            DriveSpec(M.single_group(), (W.tpcc_like(lba, n),), seed=3),
            DriveSpec(M.wolf(ewma_a=0.6, interval_frac=0.05),
                      (W.two_modal(lba, n),), seed=4),
            DriveSpec(M.wolf(), tuple(W.swap_phases(lba, n // 2)), seed=5),
            DriveSpec(M.wolf_dynamic(), (W.tpcc_like(lba, n),), seed=6),
        ]
        base = simulate_fleet(GEOM, specs, sampler="numpy")
        ops = simulate_fleet(GEOM, specs, sampler="numpy", ops_stream=True)
        np.testing.assert_array_equal(ops.app, base.app)
        np.testing.assert_array_equal(ops.mig, base.mig)
        for i, s in enumerate(specs):
            for key, arr in ops.state(i).items():
                np.testing.assert_array_equal(
                    np.asarray(arr), np.asarray(base.state(i)[key]),
                    err_msg=f"{s.label}: state[{key}]",
                )
        np.testing.assert_array_equal(
            ops.wa_curves(1000), base.wa_curves(1000)
        )

    def test_ops_engine_split_matches_oracle(self):
        """Both step engines agree on an op stream WITH trims (jit)."""
        phases = [W.trimmed(W.two_modal(GEOM.lba_pages, N_WRITES), 0.25)]
        for manager in ("wolf", "fdp", "wolf_dynamic", "single"):
            mcfg = _MANAGERS[manager]()
            split = M.simulate(GEOM, mcfg, phases, seed=13)
            oracle = M.simulate(GEOM, mcfg, phases, seed=13,
                                fast_path=False, gc_impl="reference")
            _assert_identical(split, oracle, f"trim:{manager}")


class TestStridedTrace:
    """trace_every=k cumulative counters == dense trace at steps k·j."""

    @pytest.mark.parametrize("k", [10, 250, 1500])
    def test_jit_stride_samples_dense(self, k):
        phases = [W.two_modal(GEOM.lba_pages, N_WRITES, p_hot=0.9,
                              frac_hot=0.3)]
        dense = M.simulate(GEOM, M.wolf(), phases, seed=7)
        strided = M.simulate(GEOM, M.wolf(), phases, seed=7, trace_every=k)
        assert len(strided.app) == N_WRITES // k
        np.testing.assert_array_equal(
            np.asarray(dense.app)[k - 1 :: k], strided.app
        )
        np.testing.assert_array_equal(
            np.asarray(dense.mig)[k - 1 :: k], strided.mig
        )
        # stride-aware windowed WA agrees elementwise with the dense curve
        if 3000 % k == 0:
            np.testing.assert_array_equal(
                dense.wa_curve(3000), strided.wa_curve(3000)
            )
        assert strided.wa_total == dense.wa_total

    def test_vmap_stride_samples_dense(self):
        lba, n = GEOM.lba_pages, N_WRITES
        specs = [
            DriveSpec(M.wolf(), (W.two_modal(lba, n),), seed=1),
            DriveSpec(M.single_group(), (W.uniform(lba, n),), seed=2),
        ]
        dense = simulate_fleet(GEOM, specs, sampler="numpy")
        strided = simulate_fleet(
            GEOM, specs, sampler="numpy", trace_every=500
        )
        np.testing.assert_array_equal(dense.app[:, 499::500], strided.app)
        np.testing.assert_array_equal(dense.mig[:, 499::500], strided.mig)
        np.testing.assert_array_equal(
            dense.wa_curves(1000), strided.wa_curves(1000)
        )
        for i in range(len(specs)):
            for key, arr in dense.state(i).items():
                np.testing.assert_array_equal(
                    np.asarray(arr), np.asarray(strided.state(i)[key]),
                    err_msg=f"state[{key}]",
                )

    def test_unroll_is_semantics_free(self):
        phases = [W.tpcc_like(GEOM.lba_pages, 3_000)]
        base = M.simulate(GEOM, M.wolf(), phases, seed=9)
        unrolled = M.simulate(
            GEOM, M.wolf(), phases, seed=9, trace_every=100, unroll=4
        )
        np.testing.assert_array_equal(
            np.asarray(base.app)[99::100], unrolled.app
        )
        for key, arr in base.state.items():
            np.testing.assert_array_equal(
                np.asarray(arr), np.asarray(unrolled.state[key]),
                err_msg=f"state[{key}]",
            )

    def test_stride_must_divide_segment(self):
        phases = [W.uniform(GEOM.lba_pages, 1_000)]
        with pytest.raises(AssertionError):
            M.simulate(GEOM, M.wolf(), phases, seed=0, trace_every=300)


class TestInvariantChecker:
    """SimState.check_invariants: the debug cross-check of the carried
    O(1) accounting (satellite task)."""

    @settings(max_examples=6, deadline=None)
    @given(
        st.sampled_from(["wolf", "fdp", "wolf_dynamic", "single"]),
        st.sampled_from(["two_modal", "tpcc"]),
        st.integers(min_value=0, max_value=10_000),
        st.sampled_from(["bulk", "reference"]),
        st.sampled_from([0.0, 0.2, 0.5]),
    )
    def test_invariants_after_random_segments(
        self, manager, workload, seed, gc_impl, trim_frac
    ):
        mcfg = _MANAGERS[manager]()
        rng = np.random.default_rng(seed)
        phases = _phases(workload, rng)
        if trim_frac:  # random interleaved TRIMs through the op engine
            phases = [W.trimmed(ph, trim_frac) for ph in phases]
        # split the stream into irregular segments: the checker must hold
        # at every re-entry point, not only at the end of a clean run
        res = M.simulate(GEOM, mcfg, phases, seed=seed, gc_impl=gc_impl)
        assert_invariants(
            res.state, f"{manager}/{workload}/{gc_impl}/t={trim_frac}"
        )

    def test_checker_catches_drift(self):
        import jax.numpy as jnp

        phases = [W.two_modal(GEOM.lba_pages, 2_000)]
        res = M.simulate(GEOM, M.wolf(), phases, seed=0)
        good = res.state
        assert all(bool(v) for v in good.check_invariants().values())
        bad = good.replace(free_blocks=good.free_blocks + 1)
        assert not bool(bad.check_invariants()["free_blocks"])
        bad = good.replace(grp_surplus=good.grp_surplus.at[0].add(1))
        assert not bool(bad.check_invariants()["grp_surplus"])
        bad = good.replace(mapped_pages=good.mapped_pages - 1)
        assert not bool(bad.check_invariants()["mapped_pages"])
        bad = good.replace(grp_live=good.grp_live.at[0].add(1))
        assert not bool(bad.check_invariants()["grp_live"])
        bad = good.replace(
            page_map=good.page_map.at[1].set(good.page_map[0])
        )
        assert not bool(bad.check_invariants()["page_map_injective"])
        with pytest.raises(AssertionError, match="free_blocks"):
            assert_invariants(
                good.replace(free_blocks=jnp.asarray(-1)), "drift"
            )


class TestNeighborReductions:
    """The reduction-based hotter/colder neighbor finds must equal the
    argsort oracle (_sgv_neighbors) on arbitrary group stats."""

    @settings(max_examples=30, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_matches_argsort_oracle(self, seed):
        import jax.numpy as jnp

        rng = np.random.default_rng(seed)
        g_max = int(rng.integers(2, 13))
        active = rng.random(g_max) < 0.8
        if not active.any():
            active[0] = True
        grp_p = np.where(active, rng.random(g_max).astype(np.float32), 0.0)
        # force ties sometimes
        if g_max > 2 and rng.random() < 0.5:
            grp_p[1] = grp_p[0]
        grp_size = np.where(
            active, rng.integers(1, 50, g_max), 0
        ).astype(np.int32)
        hr = jnp.where(
            jnp.asarray(active),
            jnp.asarray(grp_p) / jnp.maximum(
                jnp.asarray(grp_size, jnp.float32), 1.0
            ),
            -1.0,
        )

        class FakeState:
            grp_active = jnp.asarray(active)

        fake = FakeState()
        g_mx = hr.shape[0]
        order = np.argsort(-np.asarray(hr), kind="stable")
        rank = np.zeros(g_mx, np.int32)
        rank[order] = np.arange(g_mx)
        n_active = int(active.sum())
        for g in range(g_max):
            if not active[g]:
                continue
            up = order[np.clip(rank[g] - 1, 0, n_active - 1)]
            dn = order[np.clip(rank[g] + 1, 0, n_active - 1)]
            got_up = int(S._neighbor_hotter(hr, fake.grp_active, g))
            got_dn = int(S._neighbor_colder(hr, fake.grp_active, g))
            assert got_up == up, (seed, g, np.asarray(hr), active)
            assert got_dn == dn, (seed, g, np.asarray(hr), active)


class TestEngineStructure:
    def test_default_context_uses_split_engine(self):
        ctx = S.SimContext(GEOM, M.wolf(), 2)
        assert ctx.fast_path and ctx.trace_every == 1

    def test_no_full_reduction_in_step_predicates(self):
        """Acceptance bar: per-write predicates are O(1) reads of the
        carried accounting — no `state == FREE` reduction survives in the
        step builder or the tail (only victim selection and the drains'
        free-rank computation may reduce over blocks)."""
        for fn in (S.make_step, S._step_tail):
            src = inspect.getsource(fn)
            assert "state == FREE" not in src, fn.__name__
            assert "free_blocks" in src, fn.__name__

    def test_valve_and_bloom_bounds_are_config(self):
        mcfg = ManagerConfig()
        assert mcfg.valve_max_tries == 4  # seed default
        assert mcfg.bloom_rotate_min_writes == 64  # seed default
        # and they are honored as overrides
        m2 = dataclasses.replace(mcfg, valve_max_tries=2,
                                 bloom_rotate_min_writes=128)
        assert m2.valve_max_tries == 2
        assert m2.bloom_rotate_min_writes == 128
        src = inspect.getsource(S._step_tail)
        assert "valve_max_tries" in src and "tries < 4" not in src
        src = inspect.getsource(S._bloom_update)
        assert "bloom_rotate_min_writes" in src

    def test_fast_path_has_no_gc_machinery(self):
        """The lean append carries no GC/valve/interval calls."""
        src = inspect.getsource(S.make_step)
        after_def = src.split("def lean_write(st):")[1]
        delim = "return _cond_fields("
        assert delim in after_def, "split_write structure changed"
        fast = after_def.split(delim)[0]
        for marker in ("_gc_one", "while_loop", "_interval_update",
                       "_step_tail"):
            assert marker not in fast, marker
