"""ALS op tests: segmented bucket construction, numpy cross-check of the
normal equation solves, hot-row splitting (Gramian accumulation), chunked
scans, convergence on synthetic low-rank data, implicit-ALS ranking sanity,
and mesh-sharded == single-device equivalence on both a pure-data mesh and
a (4,2) data x model mesh (exercising real GSPMD partitioning on the
virtual 8-device CPU platform from conftest)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.controller.context import mesh_context
from predictionio_tpu.ops.als import (
    ALSConfig,
    build_buckets,
    predict_scores,
    rated_row_mask,
    top_k_items,
    train_als,
)
from predictionio_tpu.ops.als import _device_buckets, _half_sweep  # internal


def synthetic_ratings(num_users=60, num_items=40, rank=4, density=0.3, seed=0):
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(num_users, rank)) / np.sqrt(rank)
    V = rng.normal(size=(num_items, rank)) / np.sqrt(rank)
    full = U @ V.T + 3.0
    mask = rng.random((num_users, num_items)) < density
    rows, cols = np.nonzero(mask)
    vals = full[rows, cols].astype(np.float32)
    return rows, cols, vals, full


def _entries(b):
    """All (row, col, val) triples stored in a BucketedRatings (hot slots
    resolved back to row ids), for coverage checks."""
    seen = []
    for ch in b.normal:
        rid = np.asarray(ch.row_id).reshape(-1)
        idx = np.asarray(ch.idx).reshape(rid.size, -1)
        val = np.asarray(ch.val).reshape(rid.size, -1)
        m = np.asarray(ch.mask).reshape(rid.size, -1).astype(bool)
        for i in range(rid.size):
            if rid[i] == b.num_rows:
                assert not m[i].any()
                continue
            for j in np.nonzero(m[i])[0]:
                seen.append((int(rid[i]), int(idx[i, j]), float(val[i, j])))
    for ch, hot_rows_g in zip(b.hot, b.hot_rows):
        hot_rows = np.asarray(hot_rows_g)
        slot = np.asarray(ch.row_id).reshape(-1)
        idx = np.asarray(ch.idx).reshape(slot.size, -1)
        val = np.asarray(ch.val).reshape(slot.size, -1)
        m = np.asarray(ch.mask).reshape(slot.size, -1).astype(bool)
        n_hot = hot_rows.size - 1
        for i in range(slot.size):
            if slot[i] == n_hot:
                assert not m[i].any()
                continue
            for j in np.nonzero(m[i])[0]:
                seen.append((int(hot_rows[slot[i]]), int(idx[i, j]), float(val[i, j])))
    return seen


class TestBuildBuckets:
    def test_covers_all_entries(self):
        rows, cols, vals, _ = synthetic_ratings()
        b = build_buckets(rows, cols, vals, 60, 40)
        seen = _entries(b)
        assert len(seen) == len(rows)
        assert set(seen) == {
            (int(r), int(c), float(v)) for r, c, v in zip(rows, cols, vals)
        }

    def test_hot_rows_split_into_segments(self):
        # widths max out at 8 -> rows with >8 ratings go to the hot path
        rng = np.random.default_rng(0)
        rows = np.concatenate([np.zeros(30, np.int64), rng.integers(1, 10, 40)])
        cols = np.arange(70, dtype=np.int64) % 50
        vals = rng.uniform(1, 5, 70).astype(np.float32)
        b = build_buckets(rows, cols, vals, 10, 50, widths=(4, 8))
        assert b.hot, "row 0 (30 ratings) must be hot"
        hot_rows = np.concatenate([np.asarray(hr)[:-1] for hr in b.hot_rows])
        assert 0 in hot_rows
        # all entries still covered exactly once
        seen = _entries(b)
        assert len(seen) == 70
        assert set(seen) == {
            (int(r), int(c), float(v)) for r, c, v in zip(rows, cols, vals)
        }

    def test_chunking_bounds_entries_per_step(self):
        rows, cols, vals, _ = synthetic_ratings(num_users=200, num_items=50, density=0.5)
        b = build_buckets(rows, cols, vals, 200, 50, chunk_entries=128, row_multiple=8)
        for ch in list(b.normal) + list(b.hot):
            n, c, l = ch.idx.shape
            assert c % 8 == 0
            assert c * l <= max(128, 8 * l)  # min one row_multiple of rows

    def test_row_counts_padded_to_multiple(self):
        rows, cols, vals, _ = synthetic_ratings()
        b = build_buckets(rows, cols, vals, 60, 40, row_multiple=8)
        for ch in list(b.normal) + list(b.hot):
            assert ch.row_id.shape[1] % 8 == 0

    def test_zero_rating_rows_absent(self):
        rows = np.array([0, 0, 2])
        cols = np.array([0, 1, 1])
        vals = np.array([1.0, 2.0, 3.0])
        b = build_buckets(rows, cols, vals, 4, 2)
        ids = {r for r, _, _ in _entries(b)}
        assert ids == {0, 2}
        np.testing.assert_array_equal(rated_row_mask(b), [True, False, True, False])

    def test_index_validation(self):
        with pytest.raises(ValueError, match="out of range"):
            build_buckets(np.array([5]), np.array([0]), np.array([1.0]), 4, 2)

    def test_row_multiple_lcm_with_odd_axis_sizes(self):
        # regression: a 6-device data axis needs lcm(8,6)=24, not max(8,6)=8
        rows, cols, vals, _ = synthetic_ratings()
        for mult in (24, 40):  # lcm(8,6), lcm(8,5)
            b = build_buckets(rows, cols, vals, 60, 40, row_multiple=mult)
            for ch in list(b.normal) + list(b.hot):
                assert ch.row_id.shape[1] % mult == 0

    def test_padding_accounting(self):
        rows, cols, vals, _ = synthetic_ratings()
        b = build_buckets(rows, cols, vals, 60, 40)
        assert b.nnz == len(rows)
        assert b.padded_nnz >= b.nnz


class TestExplicitSolveVsNumpy:
    def _direct_expected(self, rows, cols, vals, item_f, num_users, K, reg):
        expect = np.zeros((num_users, K), np.float64)
        for u in range(num_users):
            sel = rows == u
            if not sel.any():
                continue
            Q = item_f[cols[sel]]
            n = sel.sum()
            A = Q.T @ Q + reg * max(n, 1) * np.eye(K)
            expect[u] = np.linalg.solve(A, Q.T @ vals[sel])
        return expect

    def test_half_sweep_matches_direct_solve(self):
        rows, cols, vals, _ = synthetic_ratings(num_users=20, num_items=15)
        K = 4
        reg = 0.05
        rng = np.random.default_rng(1)
        item_f = rng.normal(size=(16, K)).astype(np.float32)  # 15 + sentinel
        item_f[15] = 0.0
        user_b = build_buckets(rows, cols, vals, 20, 15)
        uf0 = jnp.zeros((21, K), jnp.float32)
        got = np.asarray(
            _half_sweep(
                uf0, jnp.asarray(item_f), _device_buckets(user_b, None),
                reg, False, 1.0, jax.lax.Precision.HIGHEST, "cholesky",
                None, None, None,
            )
        )
        expect = self._direct_expected(rows, cols, vals, item_f, 20, K, reg)
        np.testing.assert_allclose(got[:20], expect, rtol=2e-4, atol=2e-5)
        assert np.allclose(got[20], 0.0)  # sentinel re-zeroed

    def test_hot_path_matches_direct_solve(self):
        """Rows forced through segment splitting + Gramian accumulation
        must produce the same solution as a direct one-shot solve."""
        rng = np.random.default_rng(2)
        num_users, num_items, K, reg = 6, 30, 4, 0.1
        rows = np.repeat(np.arange(num_users), 25)  # every row has 25 ratings
        cols = rng.integers(0, num_items, rows.size)
        vals = rng.uniform(1, 5, rows.size).astype(np.float32)
        item_f = rng.normal(size=(num_items + 1, K)).astype(np.float32)
        item_f[num_items] = 0.0
        # widths cap at 8 -> every row is hot (25 ratings -> 4 segments)
        user_b = build_buckets(
            rows, cols, vals, num_users, num_items, widths=(8,), chunk_entries=64
        )
        assert user_b.hot and not user_b.normal
        got = np.asarray(
            _half_sweep(
                jnp.zeros((num_users + 1, K), jnp.float32),
                jnp.asarray(item_f),
                _device_buckets(user_b, None),
                reg, False, 1.0, jax.lax.Precision.HIGHEST, "cholesky",
                None, None, None,
            )
        )
        expect = self._direct_expected(rows, cols, vals, item_f, num_users, K, reg)
        np.testing.assert_allclose(got[:num_users], expect, rtol=2e-4, atol=2e-5)


class TestTrainConvergence:
    def test_explicit_reconstructs_observed(self):
        rows, cols, vals, _ = synthetic_ratings(density=0.5)
        factors = train_als(
            rows, cols, vals, 60, 40,
            ALSConfig(rank=6, iterations=12, reg=0.01),
        )
        pred = np.asarray(factors.user) @ np.asarray(factors.item).T
        rmse = np.sqrt(np.mean((pred[rows, cols] - vals) ** 2))
        assert rmse < 0.15, f"RMSE {rmse} too high"

    def test_explicit_with_hot_splitting_reconstructs(self):
        rows, cols, vals, _ = synthetic_ratings(density=0.5)
        factors = train_als(
            rows, cols, vals, 60, 40,
            ALSConfig(rank=6, iterations=12, reg=0.01,
                      bucket_widths=(4, 8), chunk_entries=256),
        )
        pred = np.asarray(factors.user) @ np.asarray(factors.item).T
        rmse = np.sqrt(np.mean((pred[rows, cols] - vals) ** 2))
        assert rmse < 0.15, f"RMSE {rmse} too high"

    def test_implicit_ranks_interacted_items_higher(self):
        rng = np.random.default_rng(3)
        # two user groups, two item groups; users interact within group
        rows, cols, vals = [], [], []
        for u in range(30):
            group = u % 2
            for i in range(20):
                if i % 2 == group and rng.random() < 0.6:
                    rows.append(u)
                    cols.append(i)
                    vals.append(rng.integers(1, 5))
        rows, cols = np.array(rows), np.array(cols)
        vals = np.array(vals, dtype=np.float32)
        factors = train_als(
            rows, cols, vals, 30, 20,
            ALSConfig(rank=8, iterations=10, reg=0.01, implicit=True, alpha=10.0),
        )
        scores = np.asarray(factors.user) @ np.asarray(factors.item).T
        in_group = [scores[u, i] for u in range(30) for i in range(20) if i % 2 == u % 2]
        out_group = [scores[u, i] for u in range(30) for i in range(20) if i % 2 != u % 2]
        assert np.mean(in_group) > np.mean(out_group) + 0.2

    def test_deterministic_given_seed(self):
        rows, cols, vals, _ = synthetic_ratings()
        cfg = ALSConfig(rank=4, iterations=3, seed=7)
        f1 = train_als(rows, cols, vals, 60, 40, cfg)
        f2 = train_als(rows, cols, vals, 60, 40, cfg)
        np.testing.assert_array_equal(np.asarray(f1.user), np.asarray(f2.user))

    def test_unrated_rows_get_zero_factors(self):
        # advisor fix: entities with no ratings must not carry random factors
        rows = np.array([0, 0, 2])
        cols = np.array([0, 1, 1])
        vals = np.array([4.0, 3.0, 5.0], np.float32)
        f = train_als(rows, cols, vals, 4, 3, ALSConfig(rank=4, iterations=2))
        assert np.allclose(np.asarray(f.user)[[1, 3]], 0.0)
        assert np.allclose(np.asarray(f.item)[2], 0.0)
        assert not np.allclose(np.asarray(f.user)[0], 0.0)


class TestMeshSharding:
    def test_mesh_matches_single_device(self):
        assert len(jax.devices()) == 8, "conftest must provide 8 CPU devices"
        rows, cols, vals, _ = synthetic_ratings()
        cfg = ALSConfig(rank=4, iterations=4, seed=5)
        single = train_als(rows, cols, vals, 60, 40, cfg)
        ctx = mesh_context()  # all 8 devices on the data axis
        sharded = train_als(rows, cols, vals, 60, 40, cfg, mesh=ctx.mesh)
        np.testing.assert_allclose(
            np.asarray(single.user), np.asarray(sharded.user), rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(single.item), np.asarray(sharded.item), rtol=1e-4, atol=1e-5
        )

    def test_data_model_mesh_matches_single_device(self):
        """(4,2) data x model mesh: factor tables sharded over model, bucket
        rows over data — the ALX layout with a model axis > 1."""
        rows, cols, vals, _ = synthetic_ratings()
        cfg = ALSConfig(rank=4, iterations=4, seed=5)
        single = train_als(rows, cols, vals, 60, 40, cfg)
        ctx = mesh_context(axis_sizes=(4, 2))
        assert ctx.mesh.shape["model"] == 2
        sharded = train_als(rows, cols, vals, 60, 40, cfg, mesh=ctx.mesh)
        np.testing.assert_allclose(
            np.asarray(single.user), np.asarray(sharded.user), rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(single.item), np.asarray(sharded.item), rtol=1e-4, atol=1e-5
        )

    def test_data_only_mesh_falls_back_to_replicated_tables(self):
        # regression: `pio train --mesh data=8` builds a mesh with no
        # 'model' axis; train_als must not require one
        rows, cols, vals, _ = synthetic_ratings()
        cfg = ALSConfig(rank=4, iterations=2, seed=5)
        single = train_als(rows, cols, vals, 60, 40, cfg)
        mesh = jax.make_mesh((8,), ("data",))
        sharded = train_als(rows, cols, vals, 60, 40, cfg, mesh=mesh)
        np.testing.assert_allclose(
            np.asarray(single.user), np.asarray(sharded.user), rtol=1e-4, atol=1e-5
        )

    def test_invalid_precision_rejected(self):
        rows, cols, vals, _ = synthetic_ratings()
        with pytest.raises(ValueError, match="precision"):
            train_als(rows, cols, vals, 60, 40, ALSConfig(precision="bf16"))

    def test_chunked_gather_never_replicates_table(self):
        """VERDICT r2 item 1 'done' check: with a model axis, the opposite
        factor table must NEVER materialize replicated in the sweep — the
        partitioned HLO may only contain per-shard [N/S, K] table tensors.
        Shape math for the memory claim: the full item table here is
        n_i*K*4 bytes; each device holds n_i/S*K*4 — a catalog S× larger
        than any single device could hold replicated still trains."""
        from jax.sharding import NamedSharding, PartitionSpec

        from predictionio_tpu.ops.als import _device_buckets, als_sweep, build_buckets

        num_users, num_items, K = 96, 4096, 8
        rng = np.random.default_rng(0)
        rows = np.repeat(np.arange(num_users), 20).astype(np.int64)
        cols = rng.integers(0, num_items, rows.size).astype(np.int64)
        vals = rng.uniform(1, 5, rows.size).astype(np.float32)

        ctx = mesh_context(axis_sizes=(2, 4))
        mesh = ctx.mesh
        S = mesh.shape["model"]
        n_u = -(-(num_users + 1) // S) * S
        n_i = -(-(num_items + 1) // S) * S
        table_bytes = n_i * K * 4
        shard_bytes = (n_i // S) * K * 4
        budget = 100_000  # per-device: full table breaks it, a shard fits
        assert table_bytes > budget > shard_bytes

        user_b = _device_buckets(
            build_buckets(rows, cols, vals, num_users, num_items, row_multiple=8),
            mesh,
        )
        item_b = _device_buckets(
            build_buckets(cols, rows, vals, num_items, num_users, row_multiple=8),
            mesh,
        )
        ms = NamedSharding(mesh, PartitionSpec("model", None))
        uf = jax.device_put(jnp.zeros((n_u, K), jnp.float32), ms)
        vf = jax.device_put(jnp.zeros((n_i, K), jnp.float32), ms)
        lowered = als_sweep.lower(
            uf, vf, user_b, item_b,
            reg=0.1, implicit=False, alpha=1.0, precision="highest",
            solver="cholesky", mesh=mesh, data_axis="data", model_axis="model",
        )
        txt = lowered.compile().as_text()
        assert f"f32[{n_i},{K}]" not in txt, (
            "full item table materialized on a device — chunked gather broken"
        )
        assert f"f32[{n_i // S},{K}]" in txt, "expected per-shard table tensors"

    def test_data_model_mesh_with_hot_rows(self):
        rows, cols, vals, _ = synthetic_ratings(density=0.6)
        cfg = ALSConfig(rank=4, iterations=3, seed=5, bucket_widths=(4, 8),
                        chunk_entries=512, implicit=True, alpha=5.0)
        single = train_als(rows, cols, vals, 60, 40, cfg)
        ctx = mesh_context(axis_sizes=(4, 2))
        sharded = train_als(rows, cols, vals, 60, 40, cfg, mesh=ctx.mesh)
        np.testing.assert_allclose(
            np.asarray(single.user), np.asarray(sharded.user), rtol=1e-4, atol=1e-5
        )


class TestDeviceBucketing:
    def test_matches_host_bucketing_coverage(self):
        from predictionio_tpu.ops.als import build_buckets_device

        rows, cols, vals, _ = synthetic_ratings(density=0.5)
        host_b = build_buckets(rows, cols, vals, 60, 40, widths=(4, 8))
        dev_b, rated = build_buckets_device(rows, cols, vals, 60, 40, widths=(4, 8))
        assert set(_entries(dev_b)) == set(_entries(host_b))
        assert dev_b.nnz == host_b.nnz
        assert dev_b.padded_nnz == host_b.padded_nnz
        np.testing.assert_array_equal(rated, rated_row_mask(host_b))

    def test_train_with_device_bucketing_matches_host(self):
        rows, cols, vals, _ = synthetic_ratings(density=0.5)
        host = train_als(rows, cols, vals, 60, 40,
                         ALSConfig(rank=4, iterations=4, seed=5, bucketing="host"))
        dev = train_als(rows, cols, vals, 60, 40,
                        ALSConfig(rank=4, iterations=4, seed=5, bucketing="device"))
        np.testing.assert_allclose(
            np.asarray(host.user), np.asarray(dev.user), rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(host.item), np.asarray(dev.item), rtol=1e-4, atol=1e-5
        )

    def test_device_bucketing_with_hot_groups(self):
        from predictionio_tpu.ops.als import build_buckets_device

        rng = np.random.default_rng(0)
        rows = np.repeat(np.arange(7), 12).astype(np.int64)
        cols = rng.integers(0, 30, rows.size).astype(np.int64)
        vals = rng.uniform(1, 5, rows.size).astype(np.float32)
        host_b = build_buckets(rows, cols, vals, 7, 30, widths=(8,), hot_group_slots=3)
        dev_b, _ = build_buckets_device(
            rows, cols, vals, 7, 30, widths=(8,), hot_group_slots=3
        )
        assert len(dev_b.hot) == len(host_b.hot) == 3
        assert set(_entries(dev_b)) == set(_entries(host_b))

    def test_device_arrays_validated_on_device(self):
        # negative indices WRAP in jax scatters — the device-side
        # validation must catch them explicitly
        from predictionio_tpu.ops.als import build_buckets_device

        rows = jnp.asarray(np.array([0, -1], np.int32))
        cols = jnp.asarray(np.array([0, 1], np.int32))
        vals = jnp.asarray(np.array([1.0, 2.0], np.float32))
        with pytest.raises(ValueError, match="row index out of range"):
            build_buckets_device(rows, cols, vals, 4, 3)
        rows2 = jnp.asarray(np.array([0, 1], np.int32))
        cols2 = jnp.asarray(np.array([0, 7], np.int32))
        with pytest.raises(ValueError, match="column index out of range"):
            build_buckets_device(rows2, cols2, vals, 4, 3)

    def test_empty_ratings_fall_back(self):
        from predictionio_tpu.ops.als import build_buckets_device

        b, rated = build_buckets_device(
            np.zeros(0, np.int64), np.zeros(0, np.int64), np.zeros(0, np.float32),
            4, 3,
        )
        assert b.nnz == 0 and not rated.any()

    def test_invalid_bucketing_rejected(self):
        rows, cols, vals, _ = synthetic_ratings()
        with pytest.raises(ValueError, match="bucketing"):
            train_als(rows, cols, vals, 60, 40, ALSConfig(bucketing="gpu"))


class TestHotGroups:
    def test_hot_groups_bound_accumulator_shape(self):
        # 7 hot rows with group size 3 -> 3 groups of (3, 3, 1) slots; the
        # sweep's [H_g+1, K, K] accumulator is bounded by the knob
        rng = np.random.default_rng(0)
        rows = np.repeat(np.arange(7), 12).astype(np.int64)  # all hot at w<=8
        cols = rng.integers(0, 30, rows.size).astype(np.int64)
        vals = rng.uniform(1, 5, rows.size).astype(np.float32)
        b = build_buckets(rows, cols, vals, 7, 30, widths=(8,), hot_group_slots=3)
        assert len(b.hot) == 3 and len(b.hot_rows) == 3
        assert [hr.shape[0] - 1 for hr in b.hot_rows] == [3, 3, 1]
        # coverage is preserved across the group split
        seen = _entries(b)
        assert len(seen) == rows.size

    def test_hot_groups_train_equivalence(self):
        rows, cols, vals, _ = synthetic_ratings(density=0.6)
        base = ALSConfig(rank=4, iterations=3, seed=5, bucket_widths=(4, 8),
                         chunk_entries=512)
        grouped = dataclasses.replace(base, hot_group_slots=4)
        f1 = train_als(rows, cols, vals, 60, 40, base)
        f2 = train_als(rows, cols, vals, 60, 40, grouped)
        np.testing.assert_allclose(
            np.asarray(f1.user), np.asarray(f2.user), rtol=1e-4, atol=1e-5
        )

    def test_hot_groups_on_mesh(self):
        rows, cols, vals, _ = synthetic_ratings(density=0.6)
        cfg = ALSConfig(rank=4, iterations=3, seed=5, bucket_widths=(4, 8),
                        chunk_entries=512, hot_group_slots=4)
        single = train_als(rows, cols, vals, 60, 40, cfg)
        ctx = mesh_context(axis_sizes=(4, 2))
        sharded = train_als(rows, cols, vals, 60, 40, cfg, mesh=ctx.mesh)
        np.testing.assert_allclose(
            np.asarray(single.user), np.asarray(sharded.user), rtol=1e-4, atol=1e-5
        )


class TestInference:
    def test_top_k_with_exclusion(self):
        item_f = jnp.eye(5, dtype=jnp.float32)
        user = jnp.array([0.1, 0.9, 0.5, 0.3, 0.0])
        idx, vals = top_k_items(user, item_f, 2)
        assert list(np.asarray(idx)) == [1, 2]
        exclude = jnp.array([False, True, False, False, False])
        idx2, _ = top_k_items(user, item_f, 2, exclude)
        assert list(np.asarray(idx2)) == [2, 3]

    def test_predict_scores_shape(self):
        s = predict_scores(jnp.ones(4), jnp.ones((7, 4)))
        assert s.shape == (7,)
        np.testing.assert_allclose(np.asarray(s), 4.0)


def _spd_batch(seed: int, B: int, K: int, ridge: float = 0.1):
    """Well-conditioned SPD systems in float32 and their float64 solution."""
    rng = np.random.default_rng(seed)
    M = rng.normal(size=(B, K, K)).astype(np.float32)
    A = M @ M.transpose(0, 2, 1) / K + ridge * np.eye(K, dtype=np.float32)
    b = rng.normal(size=(B, K)).astype(np.float32)
    want = np.linalg.solve(A.astype(np.float64), b.astype(np.float64)[..., None])[..., 0]
    return A, b, want


def _row_err(got, want) -> float:
    """Largest relative L2 error of a row."""
    got = np.asarray(got, np.float64)
    return float(
        (np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)).max()
    )


class TestPallasSolver:
    """The lane-batched Cholesky kernel (ops/solve.py) in interpret mode:
    the same kernel body Mosaic compiles on the chip."""

    @pytest.mark.parametrize("K", [8, 16, 24, 64, 96, 128])
    def test_kernel_matches_cholesky_and_float64(self, K):
        # every sublane-tile count the column loop meets: one vreg a
        # column (8), a ragged last block of columns (24), the bench rank
        # (64), the ranks whose blocks ask for more scoped VMEM (96, 128)
        from predictionio_tpu.ops.solve import chol_solve_pallas, cholesky_solve

        A, b, want = _spd_batch(K, 5, K)
        x = chol_solve_pallas(jnp.asarray(A), jnp.asarray(b), interpret=True)
        assert x.shape == b.shape
        assert _row_err(x, want) < 1e-5
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(cholesky_solve(jnp.asarray(A), jnp.asarray(b))),
            rtol=5e-4, atol=5e-5,
        )

    @pytest.mark.parametrize("B", [1, 33, 129, 2049])
    def test_batch_is_no_multiple_of_the_lane_block(self, B):
        # 2049 is the hot group's H_g + 1: 16 groups of 128 lanes and one
        # system more, in grid steps of 4 groups at this rank; the last
        # block is ragged, nothing is padded
        from predictionio_tpu.ops.solve import chol_solve_pallas

        A, b, want = _spd_batch(B, B, 8)
        x = chol_solve_pallas(jnp.asarray(A), jnp.asarray(b), interpret=True)
        assert x.shape == (B, 8)
        assert _row_err(x, want) < 1e-5

    @pytest.mark.parametrize("n", [20, 67_359])
    def test_als_wr_extremes(self, n):
        # the least and the most rated row of the ML-20M shape, lambda
        # 0.05: A = X'X + lambda n I grows with n, its conditioning too
        from predictionio_tpu.ops.solve import chol_solve_pallas

        rng = np.random.default_rng(n)
        K = 64
        X = np.abs(rng.normal(size=(3, n, K))) / 8
        r = rng.integers(1, 11, size=(3, n)) / 2.0
        A = np.einsum("bnk,bnj->bkj", X, X) + 0.05 * n * np.eye(K)
        b = np.einsum("bnk,bn->bk", X, r)
        A32, b32 = A.astype(np.float32), b.astype(np.float32)
        want = np.linalg.solve(
            A32.astype(np.float64), b32.astype(np.float64)[..., None]
        )[..., 0]
        x = chol_solve_pallas(jnp.asarray(A32), jnp.asarray(b32), interpret=True)
        assert _row_err(x, want) < 1e-4

    def test_padding_systems_solve_to_zero(self):
        # a chunk's padding rows reach the solver as A = lambda I, b = 0
        # (no ratings: the ridge alone): they solve to exactly 0, beside
        # real systems and in a ragged last block (130 = 128 + 2 lanes;
        # the lanes past the batch hold whatever the buffer held)
        from predictionio_tpu.ops.solve import chol_solve_pallas

        A, b, want = _spd_batch(3, 130, 8)
        pad = np.arange(130) % 3 == 0
        A[pad], b[pad] = 0.05 * np.eye(8, dtype=np.float32), 0.0
        x = np.asarray(chol_solve_pallas(jnp.asarray(A), jnp.asarray(b), interpret=True))
        assert x.shape == (130, 8) and np.isfinite(x).all()
        np.testing.assert_array_equal(x[pad], 0.0)
        assert _row_err(x[~pad], want[~pad]) < 1e-5

    def test_the_kernel_body_is_traced_once_for_every_batch(self, monkeypatch):
        # a sweep solves once a bucket and hot group (21 times at the
        # ML-20M shape), each a pallas_call of its own batch: the body is
        # jitted on its refs, so they share one trace (traced once a
        # call, a warm job's first sweep took 125 s on the chip's host).
        # A trace calls rsqrt once a column.
        from predictionio_tpu.ops import solve

        K = 40  # a rank no other test of this file solves at
        calls = []
        real = jax.lax.rsqrt
        monkeypatch.setattr(jax.lax, "rsqrt", lambda x: calls.append(1) or real(x))
        for B in (5, 130, 300):
            A, b, want = _spd_batch(B, B, K)
            x = solve.chol_solve_pallas(jnp.asarray(A), jnp.asarray(b), interpret=True)
            assert _row_err(x, want) < 1e-5
        assert len(calls) == K

    def test_groups_per_step_follow_the_rank(self):
        # the block follows K: several 128-system groups a grid step at
        # small ranks (a row of K floats lies padded to 128 lanes), one
        # from K=24 up; the ranks whose blocks pass the default scoped
        # VMEM ask for more, up to the ceiling
        from predictionio_tpu.ops.solve import (
            _MAX_PALLAS_K, _block_bytes, _groups_per_step,
        )

        assert [_groups_per_step(k) for k in (8, 16, 24, 64, 128)] == [4, 2, 1, 1, 1]
        assert all(_groups_per_step(k) >= 1 for k in range(8, _MAX_PALLAS_K + 1, 8))
        assert _block_bytes(64) == 4 << 20 and _block_bytes(_MAX_PALLAS_K) == 8 << 20

    def test_interpret_kernel_matches_cholesky(self):
        from predictionio_tpu.ops.solve import cholesky_solve, spd_solve

        rng = np.random.default_rng(0)
        B, K = 40, 16
        M = rng.normal(size=(B, K, K)).astype(np.float32)
        A = jnp.asarray(M @ M.transpose(0, 2, 1) + 5 * np.eye(K, dtype=np.float32))
        b = jnp.asarray(rng.normal(size=(B, K)).astype(np.float32))
        x_ref = np.asarray(cholesky_solve(A, b))
        x = np.asarray(spd_solve(A, b, method="pallas_interpret"))
        np.testing.assert_allclose(x, x_ref, rtol=5e-4, atol=5e-5)

    def test_train_with_pallas_interpret_matches_cholesky(self):
        rows, cols, vals, _ = synthetic_ratings()
        ref = train_als(rows, cols, vals, 60, 40,
                        ALSConfig(rank=8, iterations=3, solver="cholesky"))
        got = train_als(rows, cols, vals, 60, 40,
                        ALSConfig(rank=8, iterations=3, solver="pallas_interpret"))
        np.testing.assert_allclose(
            np.asarray(got.user), np.asarray(ref.user), rtol=5e-3, atol=5e-4
        )

    @pytest.mark.parametrize(
        "solver,kernel",
        [("pallas_interpret", "chol_solve_pallas"), ("cholesky", "cholesky_xla")],
    )
    def test_train_records_the_solve_kernel_and_its_systems(self, solver, kernel):
        # what `pio train` writes under kernels.als: the kernel as a
        # device trace names it, and the systems a sweep hands the solver
        # (every chunk row and hot slot of both sides, padding included)
        from predictionio_tpu.ops.als import build_buckets

        rows, cols, vals, _ = synthetic_ratings()
        info = {}
        config = ALSConfig(rank=8, iterations=1, solver=solver, bucketing="host")
        train_als(rows, cols, vals, 60, 40, config, info=info)
        assert info["solver"] == solver
        assert info["solveKernel"] == kernel
        want = 0
        for r, c, nr, nc in ((rows, cols, 60, 40), (cols, rows, 40, 60)):
            bk = build_buckets(
                r, c, vals, nr, nc, config.bucket_widths, 8,
                config.chunk_entries, config.hot_group_slots,
            )
            want += sum(ch.row_id.size for ch in bk.normal)
            want += sum(len(hr) for hr in bk.hot_rows)
        assert info["solveSystemsPerSweep"] == want
        assert want >= 100  # 60 users and 40 items, padded to whole chunks

    def test_invalid_solver_rejected(self):
        rows, cols, vals, _ = synthetic_ratings()
        with pytest.raises(ValueError, match="solver"):
            train_als(rows, cols, vals, 60, 40, ALSConfig(solver="qr"))

    def test_rank_above_vmem_ceiling_falls_back_loudly(self, caplog):
        from predictionio_tpu.ops.solve import (
            cholesky_solve, solve_kernel_name, spd_solve,
        )

        rng = np.random.default_rng(8)
        B, K = 2, 136  # multiple of 8 but above _MAX_PALLAS_K
        M = rng.normal(size=(B, K, K)).astype(np.float32)
        A = jnp.asarray(M @ M.transpose(0, 2, 1) + 50 * np.eye(K, dtype=np.float32))
        b = jnp.asarray(rng.normal(size=(B, K)).astype(np.float32))
        with caplog.at_level("WARNING", logger="predictionio_tpu.ops.solve"):
            x = np.asarray(spd_solve(A, b, method="pallas_interpret"))
        # bit-identical to Cholesky because it IS Cholesky — and it says so
        np.testing.assert_array_equal(x, np.asarray(cholesky_solve(A, b)))
        assert any(
            "K=136" in r.getMessage() and "Cholesky" in r.getMessage()
            for r in caplog.records
        ), "the downgrade to Cholesky must be logged"
        assert solve_kernel_name("pallas", K) == "cholesky_xla"
        assert solve_kernel_name("pallas", 128) == "chol_solve_pallas"

    def test_non_multiple_rank_runs_the_kernel_padded(self, caplog, monkeypatch):
        # rank 10 (the templates' default) is not a multiple of the
        # sublane tile: spd_solve embeds it in a 16x16 system with an
        # identity block, so the kernel runs — no downgrade, nothing to log
        from predictionio_tpu.ops import solve

        rng = np.random.default_rng(1)
        B, K = 8, 10
        M = rng.normal(size=(B, K, K)).astype(np.float32)
        A = M @ M.transpose(0, 2, 1) + 5 * np.eye(K, dtype=np.float32)
        b = rng.normal(size=(B, K)).astype(np.float32)
        seen = []
        real = solve.chol_solve_pallas
        monkeypatch.setattr(
            solve, "chol_solve_pallas",
            lambda A2, b2, **kw: seen.append(A2.shape) or real(A2, b2, **kw),
        )
        with caplog.at_level("WARNING", logger="predictionio_tpu.ops.solve"):
            x = np.asarray(
                solve.spd_solve(
                    jnp.asarray(A), jnp.asarray(b), method="pallas_interpret"
                )
            )
        assert seen == [(B, 16, 16)], seen
        assert not caplog.records
        want = np.linalg.solve(
            A.astype(np.float64), b.astype(np.float64)[..., None]
        )[..., 0]
        np.testing.assert_allclose(x, want, rtol=1e-4, atol=1e-5)
