"""The implicit objective against a plain float64 reference of the
Hu-Koren-Volinsky normal equations (ICDM 2008, with MLlib's scaling of
lambda by a row's count of positives), on seeded play counts: a heavy tail, a
count in the thousands, a hot row, a row with no positives.

After ``train_als(implicit=True)`` every item row must be the reference's
solution given the stored user rows (items are the half-sweep that runs
last), and a whole plain dense ALS from the same seeded tables must agree
after a few sweeps. Over alpha 1 and 40, the normal and the hot-row path, a
single device and the 8-CPU-device mesh (a data x model mesh: the shared
Gramian psums over the model axis). The same reference in one bf16 pass, put
in the program's place, fails the same limit. The shared Gramian alone at a
million rows, on both: summed pairwise it keeps float32's last bits, where
one accumulator does not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from benchmark.references.als import matmul_passes
from predictionio_tpu.controller.context import mesh_context
from predictionio_tpu.ops.als import (
    _YTY_BLOCK_ROWS,
    ALSConfig,
    _device_buckets,
    _gram_all_rows,
    als_sweep,
    build_buckets,
    train_als,
)

USERS, ITEMS, RANK, REG = 90, 40, 8, 0.01
#: relative L2 error of a row against float64 (an absolute floor for the
#: row whose solution is zero). Float32 reads under 2e-5 here at alpha 40
#: and one bf16 pass over 1e-3.
ROW_LIMIT, ROW_FLOOR = 1e-4, 1e-6
PATHS = {
    # every row fits one segment
    "normal": {},
    # rows wider than 8 are hot: Gramians summed across segments, in
    # several groups of 16 slots, several chunks a group
    "hot": {"bucket_widths": (4, 8), "chunk_entries": 64, "hot_group_slots": 16},
}


def play_counts(seed=0):
    """Seeded (user, item, count) triplets, pairs distinct. Item 0 is heard
    by every user (a hot row under any widths); one count is 3,000; item 1's
    entries are all 0 (a row with entries and no positives); user 5 has no
    entry at all."""
    rng = np.random.default_rng(seed)
    heard = rng.random((USERS, ITEMS)) < 0.25
    heard[:, 0] = True
    heard[5, :] = False
    rows, cols = np.nonzero(heard)
    vals = np.minimum(rng.zipf(2.2, rows.size), 500).astype(np.float32)
    vals[np.flatnonzero(cols == 0)[3]] = 3000.0
    vals[cols == 1] = 0.0
    order = rng.permutation(rows.size)
    return rows[order], cols[order], vals[order]


def seeded_tables(seed=1):
    """MLlib's seeding: |normal| rows of unit length."""
    rng = np.random.default_rng(seed)
    out = []
    for n in (USERS, ITEMS):
        t = np.abs(rng.standard_normal((n, RANK))).astype(np.float32)
        out.append(t / np.linalg.norm(t, axis=1, keepdims=True))
    return out


def hkv_half_sweep(own, other, row_of, col_of, vals, alpha, passes=0):
    """Every row of ``own`` that has entries, solved given ``other``: float64
    (``passes`` 0), or every product in bf16 passes and a float32 solve."""
    k = other.shape[1]
    other = other.astype(np.float32 if passes else np.float64)
    gram = matmul_passes(other.T, other, passes) if passes else other.T @ other
    out = np.array(own, other.dtype)
    for i in np.unique(row_of):
        sel = row_of == i
        x, r = other[col_of[sel]], vals[sel].astype(other.dtype)
        pos = (r > 0).astype(other.dtype)
        w = other.dtype.type(alpha) * np.abs(r)
        if passes:
            a = matmul_passes((x * w[:, None]).T, x, passes)
            b = matmul_passes(x.T, ((1 + w) * pos)[:, None], passes)[:, 0]
        else:
            a, b = (x.T * w) @ x, x.T @ ((1 + w) * pos)
        a = a + gram + other.dtype.type(REG * max(pos.sum(), 1.0)) * np.eye(k, dtype=other.dtype)
        out[i] = np.linalg.solve(a, b)
    return out


def row_errors(got, ref):
    return np.linalg.norm(got - ref, axis=1) / np.maximum(
        np.linalg.norm(ref, axis=1), ROW_FLOOR / ROW_LIMIT)


def mesh_of(devices):
    return None if devices == "single" else mesh_context(axis_sizes=(4, 2)).mesh


CASES = [(a, p, d) for a in (1.0, 40.0) for p in PATHS for d in ("single", "mesh8")]
IDS = [f"alpha{a:g}-{p}-{d}" for a, p, d in CASES]


def _train(alpha, path, devices, iterations, info=None):
    rows, cols, vals = play_counts()
    init_user, init_item = seeded_tables()
    factors = train_als(
        rows, cols, vals, USERS, ITEMS,
        ALSConfig(rank=RANK, iterations=iterations, reg=REG, implicit=True,
                  alpha=alpha, **PATHS[path]),
        mesh=mesh_of(devices), init_user=init_user, init_item=init_item, info=info)
    return np.asarray(factors.user), np.asarray(factors.item)


@pytest.mark.parametrize("alpha,path,devices", CASES, ids=IDS)
def test_every_item_row_solves_the_hkv_equations_of_the_stored_user_rows(
        alpha, path, devices):
    rows, cols, vals = play_counts()
    info = {}
    user, item = _train(alpha, path, devices, 3, info)
    if path == "hot":
        assert info["hotRows"]["item"] > 16 and info["hotGroups"]["item"] > 1
    else:
        assert info["hotRows"] == {"user": 0, "item": 0}
    assert np.all(user[5] == 0)  # no entry, never solved
    assert np.all(np.abs(item[1]) < 1e-6)  # entries and no positives: b = 0
    ref = hkv_half_sweep(np.zeros((ITEMS, RANK)), user, cols, rows, vals, alpha)
    err = row_errors(item.astype(np.float64), ref)
    assert err.max() < ROW_LIMIT, (err.max(), int(err.argmax()))


@pytest.mark.parametrize("alpha,path,devices", CASES, ids=IDS)
def test_a_whole_plain_dense_hkv_als_agrees_after_three_sweeps(alpha, path, devices):
    rows, cols, vals = play_counts()
    user, item = (t.astype(np.float64) for t in seeded_tables())
    user[5] = 0.0  # a row without entries is zero from the start
    for _ in range(3):
        user = hkv_half_sweep(user, item, rows, cols, vals, alpha)
        item = hkv_half_sweep(item, user, cols, rows, vals, alpha)
    got_user, got_item = _train(alpha, path, devices, 3)
    # three sweeps of float32 rounding fed through the next solve
    assert row_errors(got_user.astype(np.float64), user).max() < 10 * ROW_LIMIT
    assert row_errors(got_item.astype(np.float64), item).max() < 10 * ROW_LIMIT


@pytest.mark.parametrize("alpha", [1.0, 40.0])
def test_the_reference_in_one_bf16_pass_fails_the_same_limit(alpha):
    rows, cols, vals = play_counts()
    user, _ = _train(alpha, "normal", "single", 3)
    ref = hkv_half_sweep(np.zeros((ITEMS, RANK)), user, cols, rows, vals, alpha)
    control = hkv_half_sweep(np.zeros((ITEMS, RANK)), user, cols, rows, vals, alpha,
                             passes=1)
    err = row_errors(control.astype(np.float64), ref)
    assert err.max() > ROW_LIMIT and np.median(err) > ROW_LIMIT


@pytest.mark.parametrize("implicit", [True, False], ids=["implicit", "explicit"])
def test_the_objective_is_recorded(implicit):
    rows, cols, vals = play_counts()
    info = {}
    train_als(rows, cols, vals, USERS, ITEMS,
              ALSConfig(rank=RANK, iterations=1, reg=REG, implicit=implicit, alpha=40.0),
              info=info)
    assert info["objective"] == ("implicit" if implicit else "explicit")
    assert info["hotGroups"] == {"user": 0, "item": 0}
    if implicit:
        assert info["alpha"] == 40.0
        assert info["positiveEntries"] == int((vals > 0).sum()) < vals.size
    else:  # the explicit job carries no field of the other objective
        assert "alpha" not in info and "positiveEntries" not in info


@pytest.mark.parametrize("implicit", [True, False], ids=["implicit", "explicit"])
def test_the_shared_gramian_has_a_scope_of_its_own(implicit):
    rows, cols, vals = play_counts()
    ub = _device_buckets(build_buckets(rows, cols, vals, USERS, ITEMS), None)
    ib = _device_buckets(build_buckets(cols, rows, vals, ITEMS, USERS), None)
    lowered = als_sweep.lower(
        jnp.zeros((USERS + 1, RANK)), jnp.zeros((ITEMS + 1, RANK)), ub, ib,
        reg=REG, implicit=implicit, alpha=40.0)
    text = lowered.as_text(debug_info=True)
    assert "pio_als_gram" in text
    assert ("pio_als_yty" in text) is implicit


@pytest.mark.parametrize("devices", ["single", "mesh8"])
def test_the_shared_gramian_of_a_million_rows_keeps_float32s_last_bits(devices):
    """Unit-norm rows as the tables are seeded, 2^20 of them: the relative
    error of ``_gram_all_rows`` against float64 stays at the rounding of its
    result (4.3e-8 here), on one device and with the rows sharded over the
    mesh's model axis alike. The same blocks' float32 Gramians added into ONE
    float32 accumulator in turn, as a matmul's accumulator adds them on the
    chip (2.1e-5 there, PERF.md), read 5e-7 here: over the limit."""
    n, limit = 1 << 20, 1e-7
    rng = np.random.default_rng(3)
    table = np.abs(rng.standard_normal((n, RANK))).astype(np.float32)
    table /= np.linalg.norm(table, axis=1, keepdims=True)
    want = table.astype(np.float64).T @ table.astype(np.float64)

    def rel(got):
        return np.linalg.norm(np.asarray(got, np.float64) - want) / np.linalg.norm(want)

    mesh = mesh_of(devices)
    hi = jax.lax.Precision.HIGHEST
    if mesh is None:
        got = jax.jit(lambda t: _gram_all_rows(t, hi, None, None))(table)
    else:
        sharded = jax.device_put(table, NamedSharding(mesh, PartitionSpec("model", None)))
        with jax.set_mesh(mesh):
            got = jax.jit(lambda t: _gram_all_rows(t, hi, mesh, "model"))(sharded)
        assert got.sharding.is_fully_replicated
    assert rel(got) < limit
    one_accumulator = np.zeros((RANK, RANK), np.float32)
    for s in range(0, n, _YTY_BLOCK_ROWS):
        block = table[s:s + _YTY_BLOCK_ROWS]
        one_accumulator += block.T @ block
    assert rel(one_accumulator) > 3 * limit
