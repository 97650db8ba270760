"""The two-tower step against the benchmark's plain reference
(``benchmark/references/twotower.py``: float64, nothing of ``ops/``) on
seeded pairs with heavy duplication: the losses, and ``p``, ``m``, ``v`` of
every row; rows no batch drew keep their first bits; the row update against
``optax.adam`` where every row is in every batch; and the reference's two
controls, each failing."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.references import twotower as reference  # noqa: E402
from predictionio_tpu.ops import twotower as tt  # noqa: E402
from predictionio_tpu.ops.twotower import TwoTowerConfig, train_two_tower  # noqa: E402

SEED, USERS, ITEMS, DIM, BATCH, STEPS = 7, 3000, 32, 16, 256, 20
LR, TEMP = 0.05, 0.1


def _pairs(seed=SEED, users=USERS, items=ITEMS, n=STEPS * BATCH):
    """Distinct pairs sorted as the data source sorts them: 32 items, so
    every batch of 256 holds each item some eight times. A sixth of the
    users and two items are in no pair."""
    rng = np.random.default_rng(seed)
    key = rng.choice((users - users // 6) * (items - 2), n, replace=False)
    key.sort()
    return (key // (items - 2)).astype(np.int64), (key % (items - 2)).astype(np.int64)


def _program(ce_path, gemm, rows, cols, steps=STEPS, batch=BATCH, users=USERS,
             items=ITEMS, optimizer="rows"):
    """One epoch of the program itself from the seed's draw: losses, rows
    touched, and the carry."""
    k_u, k_i, k_perm = jax.random.split(jax.random.PRNGKey(SEED), 3)
    scale = 1.0 / np.sqrt(DIM)
    params = {"user": jax.random.normal(k_u, (users, DIM), jnp.float32) * scale,
              "item": jax.random.normal(k_i, (items, DIM), jnp.float32) * scale}
    start = {k: np.asarray(v) for k, v in params.items()}
    epoch, init_state, tables = tt._epoch_program(
        None, "data", "model", batch, DIM, steps * batch, steps, LR, 1.0 / TEMP,
        gemm, ce_path, optimizer)
    p, o = init_state(params)
    p, o, losses, touched, grad_sq = epoch(
        p, o, jnp.int32(0), jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32),
        k_perm)
    return (np.asarray(losses), np.asarray(touched), np.sqrt(np.asarray(grad_sq)),
            p, o, start, tables)


def _reference(rows, cols, steps=STEPS, batch=BATCH, users=USERS, items=ITEMS, **kw):
    user0, item0 = reference.initial_tables(SEED, users, items, DIM)
    replay = reference.Replay(user0, item0, LR, TEMP, **kw)
    r, c, _ = reference.padded_pairs(rows, cols, batch)
    perm = reference.epoch_permutation(SEED, 0, r.size)
    return reference.replay_steps(replay, r, c, perm, batch, steps), replay, (r, c, perm)


# (largest loss gap; median and 99th-centile error of p, m, v over their mean
# size). Float32 GEMMs: rounding only. bf16 operands (and, in the kernel,
# bf16 exp in the denominators): a gradient is off by parts in a hundred, and
# where an element of it lies near 0 Adam's first steps move p the other way
# by the whole learning rate, so the largest error says nothing
@pytest.mark.parametrize("ce_path, gemm, loss_tol, median_tol, q99_tol", [
    ("xla", "float32", 5e-5, 1e-4, 5e-4),
    ("xla", "bfloat16", 2e-2, 2e-2, 0.25),
    ("interpret", "bfloat16", 2e-2, 2e-2, 0.25),
])
def test_twenty_steps_agree_with_the_reference(ce_path, gemm, loss_tol, median_tol,
                                               q99_tol):
    rows, cols = _pairs()
    losses, touched, norms, p, _, start, _ = _program(ce_path, gemm, rows, cols)
    ref, replay, (r, c, perm) = _reference(rows, cols)
    assert np.abs(losses - np.asarray(ref)).max() <= loss_tol, (losses, ref)
    assert reference.norm_gap(norms.tolist(), replay.grad_norms) <= max(
        20 * loss_tol, 2e-3), (norms, replay.grad_norms)
    for name, table, ids in (("user", replay.user, r), ("item", replay.item, c)):
        got = [np.asarray(a, np.float64) for a in tt.unpack_rows(p[name], DIM)]
        want = table.dense()
        never = np.setdiff1d(np.arange(start[name].shape[0]), ids)
        assert never.size, "the case must leave rows undrawn"
        # a row no batch drew: p at its first bits, m and v zero
        assert np.array_equal(np.asarray(tt.unpack_rows(p[name], DIM)[0])[never],
                              start[name][never])
        assert not got[1][never].any() and not got[2][never].any()
        assert not np.asarray(p[name])[:, 3 * DIM:].any()
        drawn = np.unique(ids)
        assert (np.abs(got[0][drawn] - start[name][drawn]).max(axis=1) > 0).all()
        for part, g, w in zip("pmv", got, want):
            err, size = np.abs(g - w)[drawn], np.abs(w[drawn]).mean()
            assert np.median(err) <= median_tol * size, (name, part, np.median(err), size)
            assert np.quantile(err, 0.99) <= q99_tol * size, (
                name, part, np.quantile(err, 0.99), size)
    # the device's count of distinct rows, step by step
    want_touched = [np.unique(r[perm[k * BATCH:(k + 1) * BATCH]]).size
                    + np.unique(c[perm[k * BATCH:(k + 1) * BATCH]]).size
                    for k in range(STEPS)]
    assert touched.tolist() == want_touched


def test_rows_equal_dense_adam_where_every_row_is_in_every_batch():
    # 16 users x 16 items, every pair: a batch of 256 is all of them
    users = items = 16
    rows, cols = (np.tile(a, 12) for a in np.divmod(np.arange(256), 16))
    out = {}
    for optimizer in ("rows", "dense"):
        losses, touched, _, p, o, _, tables = _program(
            "xla", "float32", rows, cols, steps=12, users=users, items=items,
            optimizer=optimizer)
        out[optimizer] = (losses, {k: np.asarray(v) for k, v in tables(p).items()}, p, o)
    assert out["rows"][0] == pytest.approx(out["dense"][0], abs=2e-6)
    for name in ("user", "item"):
        np.testing.assert_allclose(
            out["rows"][1][name], out["dense"][1][name], rtol=2e-4, atol=2e-6)
        _, m, v = (np.asarray(a) for a in tt.unpack_rows(out["rows"][2][name], DIM))
        adam = out["dense"][3][0]
        np.testing.assert_allclose(m, np.asarray(adam.mu[name]), rtol=2e-4, atol=1e-7)
        np.testing.assert_allclose(v, np.asarray(adam.nu[name]), rtol=2e-4, atol=1e-9)
    # and the reference agrees with both
    ref, _, _ = _reference(rows, cols, steps=12, users=users, items=items)
    assert out["rows"][0] == pytest.approx(np.asarray(ref), abs=2e-5)


@pytest.mark.parametrize("control", [
    {"both_halves": False}, {"sum_duplicates": False}])
def test_each_control_of_the_reference_fails(control):
    """The comparison's teeth: the replay without the item-to-user half, and
    with a duplicate's gradient applied once, leaves the program's losses or
    gradient norms by far more than the program leaves the reference's."""
    rows, cols = _pairs()
    losses, _, norms, *_ = _program("interpret", "bfloat16", rows, cols)
    ref, replay, _ = _reference(rows, cols)
    ctl, controlled, _ = _reference(rows, cols, **control)
    sound = max(reference.loss_gap(ref, losses),
                reference.norm_gap(replay.grad_norms, norms.tolist()))
    assert max(reference.loss_gap(ctl, losses),
               reference.norm_gap(controlled.grad_norms, norms.tolist())) > 10 * sound


@pytest.mark.parametrize("fused_ce", ["off", "interpret"])
def test_a_train_records_what_the_reference_replays(fused_ce):
    """`train_two_tower` itself: its decisions, its checksum of the uploaded
    ids and its first losses against the reference drawn from the seed."""
    rows, cols = _pairs()
    info = {}
    model = train_two_tower(
        rows, cols, USERS, ITEMS,
        TwoTowerConfig(dim=DIM, batch_size=BATCH, epochs=2, learning_rate=LR,
                       temperature=TEMP, seed=SEED, fused_ce=fused_ce), info=info)
    assert info["optimizer"] == "rows" and "single device" in info["optimizerWhy"]
    assert info["fusedCe"] == ("xla" if fused_ce == "off" else "interpret")
    assert info["stepsPerEpoch"] == STEPS and len(info["epochSeconds"]) == 2
    ref, _, (r, c, perm) = _reference(rows, cols, steps=tt.FIRST_LOSSES)
    assert info["pairsChecksum"] == reference.padded_pairs(rows, cols, BATCH)[2]
    assert len(info["firstLosses"]) == tt.FIRST_LOSSES
    assert np.asarray(info["firstGradNorms"]).shape == (tt.FIRST_LOSSES, 2)
    assert info["firstLosses"] == pytest.approx(ref, abs=2e-2)  # bf16 operands
    assert len(info["lastLosses"]) == tt.FIRST_LOSSES
    assert 0 < info["rowsTouchedPerStep"] <= 2 * BATCH
    assert info["rowsTouched"] == round(info["rowsTouchedPerStep"] * 2 * STEPS)
    # the compile ledger's entry: empty where an earlier test compiled it
    assert info["stepMs"] > 0 and isinstance(info["epochProgram"], dict)
    norms = np.linalg.norm(model.user_vecs, axis=1)
    assert np.abs(norms - 1).max() < 1e-5


def test_seeding_and_packing_are_one_named_phase_around_the_upload():
    """Span ``train.init`` twice (seeding the tables and padding the pairs,
    then packing the tables), ``train.ingest`` between them: every leaf of
    the job from the first key to the first epoch has a name, and
    ``initSeconds`` is the two together."""
    from predictionio_tpu.utils import spans

    rows, cols = _pairs()
    info = {}
    collector = spans.Collector(cpu=True)
    unbound = spans.bind(collector)
    try:
        train_two_tower(
            rows, cols, USERS, ITEMS,
            TwoTowerConfig(dim=DIM, batch_size=BATCH, epochs=1, learning_rate=LR,
                           temperature=TEMP, seed=SEED, fused_ce="off"), info=info)
    finally:
        spans.bind(unbound)
    records = collector.take()
    assert [r.name for r in records] == [
        "train.init", "train.ingest", "train.init", "train.epoch", "train.finalize"]
    assert all(r.parent is None for r in records)  # leaves: each a pio.* event
    assert info["initSeconds"] == pytest.approx(
        spans.durations_ms(records)["train.init"] / 1e3, abs=0.002)
    assert info["initSeconds"] > 0
    # one phase ends where the next begins: nothing of the host's between
    # seeding and the upload, little between the upload and the packing
    seeding, ingest = records[0], records[1]
    assert 0 <= ingest.start_ns - seeding.end_ns < 500_000_000
    assert all(0 <= r.cpu_ns <= r.end_ns - r.start_ns for r in records)
