"""Executor edge paths: device resolution, ragged padding, the compiled-
runner memo, fenced sub-batch resolution, and the compile-cache helper.

These pin the failure-handling seams of ``fleet_exec`` that the happy-path
fleet suites never reach: the import-order device trap only warns when the
host-device flag was never set; ``pad_batch`` must be a no-op at pad=0 and
a pure row-0 replication otherwise; ``step_cache_clear`` must actually
force a recompile; a poisoned sub-batch must surface as a
``SubbatchResolutionError`` carrying its partition key and drive ids after
the healthy sub-batches resolved; and the on-disk compilation cache lands
where ``JAX_COMPILATION_CACHE_DIR`` says, else at a fixed checkout path.
"""

import dataclasses
import pathlib
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fleet as FL
from repro.core import fleet_exec as fe
from repro.core import managers as M
from repro.core import workloads as W
from repro.core.fleet import DriveSpec, simulate_fleet
from repro.core.ssd import Geometry
from repro.utils import compile_cache

GEOM = Geometry(n_luns=4, blocks_per_lun=32, pages_per_block=8, lba_pba=0.7)


@pytest.mark.mesh
class TestResolveDevices:
    def test_single_device_fast_paths(self):
        assert fe.resolve_devices(None) == 1
        assert fe.resolve_devices(1) == 1

    def test_auto_and_clamp(self):
        # conftest pins 2 virtual CPU devices before jax init
        n = len(jax.devices())
        assert n >= 2
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # the flag IS set: no warning
            assert fe.resolve_devices("auto") == n
        assert fe.resolve_devices(2) == 2
        assert fe.resolve_devices("2") == 2
        # more devices than exist is an error, never a quiet smaller mesh
        with pytest.raises(ValueError, match="device"):
            fe.resolve_devices(99)

    def test_auto_warns_on_unset_flag(self, monkeypatch):
        """jax initialized before --xla_force_host_platform_device_count:
        "auto" silently seeing 1 device is the trap — it must warn."""
        monkeypatch.setattr(fe, "host_device_flag", lambda: None)
        monkeypatch.setattr(fe.jax, "devices", lambda: ["cpu:0"])
        if jax.default_backend() != "cpu":  # pragma: no cover
            pytest.skip("import-order trap is CPU-specific")
        with pytest.warns(RuntimeWarning, match="single CPU device"):
            assert fe.resolve_devices("auto") == 1


@pytest.mark.mesh
class TestPadBatch:
    def test_pad_zero_is_identity(self):
        tree = {"a": jnp.arange(6).reshape(3, 2)}
        assert fe.pad_batch(tree, 0) is tree

    def test_pad_replicates_row_zero(self):
        tree = {
            "a": jnp.arange(6, dtype=jnp.int32).reshape(3, 2),
            "b": jnp.asarray([1.0, 2.0, 3.0]),
        }
        out = fe.pad_batch(tree, 2)
        assert out["a"].shape == (5, 2) and out["b"].shape == (5,)
        np.testing.assert_array_equal(out["a"][:3], tree["a"])
        np.testing.assert_array_equal(
            out["a"][3:], np.broadcast_to(np.asarray(tree["a"][0]), (2, 2))
        )
        np.testing.assert_array_equal(out["b"][3:], [1.0, 1.0])

    def test_single_drive_pads_to_full_width(self):
        """The 1-drive sub-batch on a d-device mesh: every filler lane is
        a copy of the one real drive."""
        tree = (jnp.ones((1, 4)), {"x": jnp.zeros((1,))})
        out = fe.pad_batch(tree, 3)
        assert out[0].shape == (4, 4)
        assert out[1]["x"].shape == (4,)
        np.testing.assert_array_equal(out[0], np.ones((4, 4)))


@pytest.mark.mesh
class TestStepCacheClear:
    def test_clear_between_geometry_changes(self):
        spec = [DriveSpec(M.wolf(), (W.two_modal(GEOM.lba_pages, 1_200),),
                          seed=0)]
        fe.step_cache_clear()
        assert fe.step_cache_stats().misses == 0
        simulate_fleet(GEOM, spec, sampler="numpy")
        s1 = fe.step_cache_stats()
        assert s1.misses >= 1
        # identical step structure: pure memo hit, no new compile
        simulate_fleet(GEOM, spec, sampler="numpy")
        s2 = fe.step_cache_stats()
        assert s2.misses == s1.misses
        assert s2.hits > s1.hits
        # a cleared memo must recompile even for the structure just run
        fe.step_cache_clear()
        s3 = fe.step_cache_stats()
        assert (s3.hits, s3.misses) == (0, 0)
        simulate_fleet(GEOM, spec, sampler="numpy")
        assert fe.step_cache_stats().misses >= 1
        # a geometry change is a new step structure: miss, not hit
        geom2 = dataclasses.replace(GEOM, blocks_per_lun=16)
        spec2 = [DriveSpec(M.wolf(),
                           (W.two_modal(geom2.lba_pages, 1_200),), seed=0)]
        before = fe.step_cache_stats()
        simulate_fleet(geom2, spec2, sampler="numpy")
        after = fe.step_cache_stats()
        assert after.misses > before.misses

    def test_stats_is_a_copy(self):
        snap = fe.step_cache_stats()
        snap.hits += 1000
        assert fe.step_cache_stats().hits != snap.hits or snap.hits == 1000


class _PoisonedOutput:
    """Stands in for a sub-batch's device outputs whose resolution blows
    up (OOM, poisoned buffer): any attempt to unpack it raises."""

    def __iter__(self):
        raise RuntimeError("poisoned device buffer")


@pytest.mark.fault
class TestSubbatchResolution:
    def test_poisoned_subbatch_reports_context(self, monkeypatch):
        """One bad sub-batch must not orphan the others: the error names
        the failed sub-batch's partition key, drive ids, and labels, and
        is raised only after the healthy sub-batch resolved."""
        lba, n = GEOM.lba_pages, 1_200
        specs = [
            DriveSpec(M.wolf(), (W.two_modal(lba, n),), seed=1, name="ok0"),
            DriveSpec(M.wolf(), (W.two_modal(lba, n),), seed=2, name="ok1"),
            # the bloom drive lands in its own partition — that one dies
            DriveSpec(M.wolf_dynamic(), (W.tpcc_like(lba, n),), seed=3,
                      name="doomed"),
        ]
        real_runner = FL.subbatch_runner
        resolved_ctxs = []

        def fake_runner(ctx, n_total, on_device, d):
            runner = real_runner(ctx, n_total, on_device, d)

            def wrapped(*args):
                out = runner(*args)
                resolved_ctxs.append(ctx)
                if ctx.use_bloom:
                    return _PoisonedOutput()
                return out

            return wrapped

        monkeypatch.setattr(FL, "subbatch_runner", fake_runner)
        with pytest.raises(fe.SubbatchResolutionError) as ei:
            simulate_fleet(GEOM, specs, sampler="numpy")
        err = ei.value
        assert err.n_subbatches == 2
        assert len(resolved_ctxs) == 2, "healthy dispatch was orphaned"
        (failure,) = err.failures
        assert failure.drive_ids == (2,)
        assert failure.labels == ("doomed",)
        assert isinstance(failure.error, RuntimeError)
        assert isinstance(failure.part_key, tuple)
        msg = str(err)
        assert "1/2" in msg and "doomed" in msg
        assert "poisoned device buffer" in msg


class TestCompileCache:
    """repro.utils.compile_cache.enable: the env var wins untouched, else a
    fixed checkout-root directory. jax.config.update is recorded, never
    applied, so the test run writes nothing into the checkout."""

    @pytest.fixture
    def calls(self, monkeypatch):
        recorded = []
        monkeypatch.setattr(
            jax.config, "update", lambda *a: recorded.append(a)
        )
        return recorded

    def test_env_dir_is_used_as_is(self, monkeypatch, calls, tmp_path):
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert calls == []  # jax reads the variable itself; no other dir

    def test_unset_env_uses_checkout_root(self, monkeypatch, calls):
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        path = compile_cache.enable()
        root = pathlib.Path(__file__).resolve().parent.parent
        assert path == str(root / ".jax_cache")
        assert calls == [("jax_compilation_cache_dir", path)]

    def test_idempotent(self, monkeypatch, calls):
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        first = compile_cache.enable()
        assert compile_cache.enable() == first
        assert {c for c in calls} == {("jax_compilation_cache_dir", first)}

    def test_checkout_cache_is_ignored_by_git(self):
        root = pathlib.Path(__file__).resolve().parent.parent
        ignored = (root / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored
        assert compile_cache.CHECKOUT_CACHE.parent == root
