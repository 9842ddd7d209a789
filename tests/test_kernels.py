"""Per-kernel shape/dtype sweeps vs pure-jnp oracles (interpret mode on CPU).

Tolerances: fp32 tight; bf16 loose (scores are rounded to bf16 before
softmax — the same trade every production bf16 attention kernel makes).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.flash_attention.kernel import flash_attention

# interpret-mode shape/dtype sweeps take minutes in aggregate: keep them in
# tier-1 but out of the fast lane (scripts/run_tests.sh --fast)
pytestmark = pytest.mark.slow
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.gc_compact.kernel import gc_compact
from repro.kernels.gc_compact.ref import gc_compact_ref
from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref


def _tol(dtype):
    return dict(atol=2e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(
        atol=2e-5, rtol=2e-5
    )


def _mk(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.5).astype(dtype)


# the simulator's scan carries ``valid`` as int32 0/1; bool is accepted too
VALID_DTYPES = pytest.mark.parametrize(
    "valid_dtype", [np.bool_, np.int32], ids=["bool", "int32"]
)


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "b,sq,skv,hq,hkv,d,causal,window",
        [
            (1, 128, 128, 4, 4, 64, True, 0),    # MHA causal
            (2, 256, 256, 8, 2, 64, True, 0),    # GQA
            (2, 128, 128, 4, 1, 128, True, 0),   # MQA, d=128
            (1, 256, 256, 4, 2, 64, True, 64),   # sliding window
            (2, 64, 192, 2, 2, 64, False, 0),    # cross (Sq≠Skv, non-causal)
            (1, 100, 100, 4, 4, 64, True, 0),    # ragged tail (non-multiple)
        ],
    )
    def test_matches_ref(self, dtype, b, sq, skv, hq, hkv, d, causal, window):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = _mk(ks[0], (b, sq, hq, d), dtype)
        k = _mk(ks[1], (b, skv, hkv, d), dtype)
        v = _mk(ks[2], (b, skv, hkv, d), dtype)
        out = flash_attention(
            q, k, v, causal=causal, window=window,
            block_q=64, block_kv=64, interpret=True,
        )
        ref = flash_attention_ref(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
        )

    @settings(max_examples=10, deadline=None)
    @given(
        st.sampled_from([64, 96, 128, 200]),
        st.sampled_from([(4, 4), (4, 2), (8, 1)]),
        st.sampled_from([64, 128]),
        st.integers(min_value=0, max_value=3),
    )
    def test_property_random_shapes(self, s, heads, d, seed):
        hq, hkv = heads
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = _mk(ks[0], (1, s, hq, d), jnp.float32)
        k = _mk(ks[1], (1, s, hkv, d), jnp.float32)
        v = _mk(ks[2], (1, s, hkv, d), jnp.float32)
        out = flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True)
        ref = flash_attention_ref(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5
        )


class TestPagedAttention:
    def _case(self, b, hq, hkv, d, n, p, m, dtype, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        q = _mk(ks[0], (b, hq, d), dtype)
        kp = _mk(ks[1], (n, p, hkv, d), dtype)
        vp = _mk(ks[2], (n, p, hkv, d), dtype)
        rng = np.random.default_rng(seed)
        lengths = rng.integers(1, m * p + 1, b).astype(np.int32)
        tables = np.full((b, m), -1, np.int32)
        for i in range(b):
            npages = -(-int(lengths[i]) // p)
            tables[i, :npages] = rng.choice(n, npages, replace=False)
        return q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "b,hq,hkv,d,n,p,m",
        [
            (2, 4, 4, 64, 16, 16, 4),   # MHA
            (4, 8, 2, 64, 32, 16, 6),   # GQA
            (2, 8, 1, 128, 16, 32, 3),  # MQA, d=128
            (1, 4, 2, 64, 8, 8, 8),     # long table
        ],
    )
    def test_matches_ref(self, dtype, b, hq, hkv, d, n, p, m):
        q, kp, vp, tables, lengths = self._case(b, hq, hkv, d, n, p, m, dtype)
        out = paged_attention(q, kp, vp, tables, lengths, interpret=True)
        ref = paged_attention_ref(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
        )

    def test_single_token_sequence(self):
        q, kp, vp, tables, lengths = self._case(2, 4, 2, 64, 8, 16, 2, jnp.float32)
        lengths = jnp.asarray([1, 1], jnp.int32)
        out = paged_attention(q, kp, vp, tables, lengths, interpret=True)
        ref = paged_attention_ref(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    @settings(max_examples=8, deadline=None)
    @given(st.integers(min_value=0, max_value=1000))
    def test_property_random_tables(self, seed):
        q, kp, vp, tables, lengths = self._case(3, 4, 2, 64, 24, 8, 5, jnp.float32, seed)
        out = paged_attention(q, kp, vp, tables, lengths, interpret=True)
        ref = paged_attention_ref(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)


class TestGcCompact:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n,p,h,d,m", [(16, 8, 2, 64, 12), (8, 16, 1, 128, 5)])
    def test_matches_ref(self, dtype, n, p, h, d, m):
        rng = np.random.default_rng(1)
        kp = _mk(jax.random.PRNGKey(0), (n, p, h, d), dtype)
        vp = _mk(jax.random.PRNGKey(1), (n, p, h, d), dtype)
        # distinct destinations; a couple of no-op rows
        dst_flat = rng.choice(n * p, m, replace=False)
        src_flat = rng.choice(n * p, m, replace=False)
        sb, ss = (src_flat // p).astype(np.int32), (src_flat % p).astype(np.int32)
        db, ds = (dst_flat // p).astype(np.int32), (dst_flat % p).astype(np.int32)
        sb[1] = -1
        sb[m - 1] = -1
        args = tuple(map(jnp.asarray, (sb, ss, db, ds)))
        got_k, got_v = gc_compact(kp, vp, *args, interpret=True)
        ref_k, ref_v = gc_compact_ref(kp, vp, *args)
        np.testing.assert_array_equal(np.asarray(got_k), np.asarray(ref_k))
        np.testing.assert_array_equal(np.asarray(got_v), np.asarray(ref_v))

    @settings(max_examples=8, deadline=None)
    @given(st.integers(min_value=0, max_value=1000))
    def test_property_random_moves(self, seed):
        rng = np.random.default_rng(seed)
        n, p, h, d = 12, 8, 2, 64
        m = int(rng.integers(1, 20))
        kp = _mk(jax.random.PRNGKey(seed), (n, p, h, d), jnp.float32)
        vp = _mk(jax.random.PRNGKey(seed + 1), (n, p, h, d), jnp.float32)
        dst_flat = rng.choice(n * p, m, replace=False)
        src_flat = rng.choice(n * p, m, replace=False)
        sb = (src_flat // p).astype(np.int32)
        ss = (src_flat % p).astype(np.int32)
        db = (dst_flat // p).astype(np.int32)
        ds = (dst_flat % p).astype(np.int32)
        noop = rng.random(m) < 0.2
        sb = np.where(noop, -1, sb).astype(np.int32)
        args = tuple(map(jnp.asarray, (sb, ss, db, ds)))
        got_k, got_v = gc_compact(kp, vp, *args, interpret=True)
        ref_k, ref_v = gc_compact_ref(kp, vp, *args)
        np.testing.assert_array_equal(np.asarray(got_k), np.asarray(ref_k))
        np.testing.assert_array_equal(np.asarray(got_v), np.asarray(ref_v))


class TestCompactSlots:
    """Metadata-pool variant backing the simulator's bulk-GC drain: the
    pure-jnp fallback the simulator runs off-TPU must match the
    interpret-mode Pallas kernel move-for-move."""

    @VALID_DTYPES
    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=1000))
    def test_kernel_matches_ref(self, valid_dtype, seed):
        from repro.kernels.gc_compact.kernel import compact_slots
        from repro.kernels.gc_compact.ref import (
            compact_slots_dense,
            compact_slots_ref,
        )

        rng = np.random.default_rng(seed)
        k, b = 24, 8
        m = int(rng.integers(1, b + 1))
        slot_lba = rng.integers(-1, 200, (k, b)).astype(np.int32)
        valid = (rng.random((k, b)) < 0.5).astype(valid_dtype)
        # a GC-shaped move list: one victim block's slots → distinct dsts
        victim = int(rng.integers(0, k))
        dst_flat = rng.choice((k - 1) * b, m, replace=False)
        db = (dst_flat // b).astype(np.int32)
        db = np.where(db >= victim, db + 1, db).astype(np.int32)  # dst ≠ src
        ds = (dst_flat % b).astype(np.int32)
        sb = np.full(m, victim, np.int32)
        ss = rng.choice(b, m, replace=False).astype(np.int32)
        sb[rng.random(m) < 0.3] = -1  # no-op rows
        args = tuple(map(jnp.asarray, (sb, ss, db, ds)))
        got_l, got_v = compact_slots(
            jnp.asarray(slot_lba), jnp.asarray(valid), *args, interpret=True
        )
        # the reference on the bool pool: an int32 one moves the same bits
        ref_l, ref_v = compact_slots_ref(
            jnp.asarray(slot_lba), jnp.asarray(valid.astype(bool)), *args
        )
        ref_v = np.asarray(ref_v).astype(valid_dtype)
        np.testing.assert_array_equal(np.asarray(got_l), np.asarray(ref_l))
        np.testing.assert_array_equal(np.asarray(got_v), ref_v)
        assert got_v.dtype == valid.dtype
        # the scatter-free CPU lowering the simulator actually runs
        den_l, den_v = compact_slots_dense(
            jnp.asarray(slot_lba), jnp.asarray(valid), *args
        )
        np.testing.assert_array_equal(np.asarray(den_l), np.asarray(ref_l))
        np.testing.assert_array_equal(np.asarray(den_v), ref_v)
        assert den_v.dtype == valid.dtype


class TestWritePath:
    """Fused fast-path write (invalidate + masked append + map repoint)
    backing the simulator's split step: the off-TPU XLA lowering must match
    both the 2-D reference and the interpret-mode Pallas kernel
    update-for-update."""

    @VALID_DTYPES
    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=1000))
    def test_kernel_matches_ref(self, valid_dtype, seed):
        from repro.kernels.write_path.kernel import apply_write
        from repro.kernels.write_path.ref import (
            apply_write_ref,
            apply_write_xla,
        )

        rng = np.random.default_rng(seed)
        k, b, lba_pages = 24, 8, 128
        slot_lba = rng.integers(-1, lba_pages, (k, b)).astype(np.int32)
        valid = (rng.random((k, b)) < 0.5).astype(valid_dtype)
        page_map = rng.integers(-1, k * b, lba_pages).astype(np.int32)
        lba = int(rng.integers(0, lba_pages))
        old_pm = int(page_map[lba])
        dst_blk = int(rng.integers(0, k))
        # a write-shaped destination: never the page's own old slot
        dst_slot = int(rng.integers(0, b))
        while dst_blk * b + dst_slot == old_pm:
            dst_slot = (dst_slot + 1) % b
        enabled = jnp.asarray(bool(rng.random() < 0.75))
        args = (
            jnp.asarray(page_map), jnp.asarray(slot_lba), jnp.asarray(valid),
            jnp.asarray(lba), jnp.asarray(old_pm),
            jnp.asarray(dst_blk), jnp.asarray(dst_slot),
        )
        # the reference on the bool pool: an int32 one ends with the same
        # bits, as int32 0/1
        ref_pm, ref_l, ref_v = apply_write_ref(
            *args[:2], jnp.asarray(valid.astype(bool)), *args[3:], enabled)
        ref_v = np.asarray(ref_v).astype(valid_dtype)
        xla_pm, xla_l, xla_v = apply_write_xla(*args, enabled)
        ker_pm, ker_l, ker_v = apply_write(
            *args, enabled=enabled, interpret=True)
        for got, ref, name in (
            (xla_pm, ref_pm, "xla page_map"), (xla_l, ref_l, "xla slot_lba"),
            (xla_v, ref_v, "xla valid"),
            (ker_pm, ref_pm, "kernel page_map"), (ker_l, ref_l, "kernel slot_lba"),
            (ker_v, ref_v, "kernel valid"),
        ):
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(ref), err_msg=name
            )
        assert xla_v.dtype == valid.dtype and ker_v.dtype == valid.dtype
        # the old slot is dead either way; the new mapping is installed
        # only where enabled
        if old_pm >= 0:
            assert not bool(ref_v[old_pm // b, old_pm % b])
        if bool(enabled):
            assert int(ref_pm[lba]) == dst_blk * b + dst_slot
            assert bool(ref_v[dst_blk, dst_slot])
        else:
            assert int(ref_pm[lba]) == old_pm

    def test_unmapped_page_touches_nothing_old(self):
        from repro.kernels.write_path.ref import (
            apply_write_ref,
            apply_write_xla,
        )

        k, b, lba_pages = 8, 4, 24
        page_map = jnp.full(lba_pages, -1, jnp.int32)
        slot_lba = jnp.full((k, b), -1, jnp.int32)
        valid = jnp.zeros((k, b), bool)
        for fn in (apply_write_ref, apply_write_xla):
            pm, sl, va = fn(
                page_map, slot_lba, valid,
                jnp.asarray(5), jnp.asarray(-1),
                jnp.asarray(2), jnp.asarray(0),
            )
            assert int(pm[5]) == 2 * b + 0
            assert int(va.sum()) == 1 and bool(va[2, 0])
            assert int(sl[2, 0]) == 5

    def test_disabled_kernel_write_is_noop(self):
        """A disabled write appends nothing: with no old mapping it leaves
        every pool untouched, and with one it only kills the old slot."""
        from repro.kernels.write_path.kernel import apply_write
        from repro.kernels.write_path.ref import apply_write_xla

        rng = np.random.default_rng(0)
        k, b, lba_pages = 8, 4, 24
        page_map = jnp.asarray(rng.integers(-1, k * b, lba_pages), jnp.int32)
        slot_lba = jnp.asarray(rng.integers(-1, lba_pages, (k, b)), jnp.int32)
        valid = jnp.asarray(rng.random((k, b)) < 0.5)
        for old in (-1, 5):
            args = (page_map, slot_lba, valid, jnp.asarray(3),
                    jnp.asarray(old), jnp.asarray(1), jnp.asarray(2))
            want_v = valid if old < 0 else valid.at[old // b, old % b].set(
                False)
            for pm, sl, va in (
                apply_write(*args, enabled=jnp.asarray(False), interpret=True),
                apply_write_xla(*args, jnp.asarray(False)),
            ):
                np.testing.assert_array_equal(np.asarray(pm),
                                              np.asarray(page_map))
                np.testing.assert_array_equal(np.asarray(sl),
                                              np.asarray(slot_lba))
                np.testing.assert_array_equal(np.asarray(va),
                                              np.asarray(want_v))


class TestTrimPath:
    """Fused fast-path TRIM (invalidate + unmap): flat lowering and
    interpret-mode kernel vs the 2-D ref."""

    @VALID_DTYPES
    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=1000))
    def test_kernel_matches_ref(self, valid_dtype, seed):
        from repro.kernels.write_path.kernel import apply_trim
        from repro.kernels.write_path.ref import (
            apply_trim_flat,
            apply_trim_ref,
        )

        rng = np.random.default_rng(seed)
        k, b, lba_pages = 24, 8, 128
        valid = (rng.random((k, b)) < 0.5).astype(valid_dtype)
        page_map = rng.integers(-1, k * b, lba_pages).astype(np.int32)
        lba = int(rng.integers(0, lba_pages))
        if rng.random() < 0.3:
            page_map[lba] = -1  # re-trim of an unmapped page
        old_pm = int(page_map[lba])
        args = (
            jnp.asarray(page_map), jnp.asarray(valid),
            jnp.asarray(lba), jnp.asarray(old_pm),
        )
        ref_pm, ref_v = apply_trim_ref(
            args[0], jnp.asarray(valid.astype(bool)), *args[2:])
        ref_v = np.asarray(ref_v).astype(valid_dtype)
        flat_pm, flat_v = apply_trim_flat(*args)
        ker_pm, ker_v = apply_trim(*args, interpret=True)
        for got, ref, name in (
            (flat_pm, ref_pm, "flat page_map"), (flat_v, ref_v, "flat valid"),
            (ker_pm, ref_pm, "kernel page_map"), (ker_v, ref_v, "kernel valid"),
        ):
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(ref), err_msg=name
            )
        assert flat_v.dtype == valid.dtype and ker_v.dtype == valid.dtype
        # the page is unmapped and its old slot is dead
        assert int(flat_pm[lba]) == -1
        if old_pm >= 0:
            assert not bool(flat_v[old_pm // b, old_pm % b])

    def test_retrim_is_noop_on_valid(self):
        from repro.kernels.write_path.ref import (
            apply_trim_flat,
            apply_trim_ref,
        )

        k, b, lba_pages = 8, 4, 24
        page_map = jnp.full(lba_pages, -1, jnp.int32)
        valid = jnp.ones((k, b), bool)
        for fn in (apply_trim_ref, apply_trim_flat):
            pm, va = fn(page_map, valid, jnp.asarray(5), jnp.asarray(-1))
            assert int(pm[5]) == -1
            np.testing.assert_array_equal(np.asarray(va), np.ones((k, b), bool))

    def test_disabled_kernel_trim_is_noop(self):
        from repro.kernels.write_path.kernel import apply_trim

        rng = np.random.default_rng(0)
        k, b, lba_pages = 8, 4, 24
        page_map = jnp.asarray(rng.integers(0, k * b, lba_pages), jnp.int32)
        valid = jnp.asarray(rng.random((k, b)) < 0.5)
        pm, va = apply_trim(
            page_map, valid, jnp.asarray(3), jnp.asarray(5),
            enabled=jnp.asarray(False), interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(pm), np.asarray(page_map))
        np.testing.assert_array_equal(np.asarray(va), np.asarray(valid))
