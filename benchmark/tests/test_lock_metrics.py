"""Who waits for the interpreter lock (ISSUE 37), read through the harness
on the CPU: every new per-layer metric is a number in the traced run of a
toy cell of its kind. The toy cells are the ones the other test files of
this directory add to a temporary copy; their fixtures are taken as they
are. No time here is a speed: only that each reader finds its number, and
the orderings that hold on any machine."""

import pytest

from benchmark.tests import toy
from benchmark.tests.test_ecom_cell import root as ecom_root  # noqa: F401
from benchmark.tests.test_simprod_cell import root as sim_root  # noqa: F401
from benchmark.tests.test_twotower_cell import CELL as TT_CELL
from benchmark.tests.test_twotower_cell import root as tt_root  # noqa: F401

KINDS = ("host_cpu_ms", "host_wait_ms", "rider_cpu_ms", "rider_wait_ms",
         "lock_acquire_ms", "lock_busy_pct")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy.make_toy_root(str(tmp_path_factory.mktemp("toy_lock")))


def _traced(root, cell, seconds, **kw):
    rc, line, out = toy.drive(root, cell, seconds=seconds, trace=1, **kw)
    assert rc == 0, out[-3000:]
    return {k: v["value"] for k, v in line["metrics"].items()}, out


def _account(m, prefix, out):
    """The seven kinds of one serve cell: numbers, and what holds of them
    anywhere: CPU times above 0, waits and shares at least 0 (a rider's
    wait is a remainder: a hair under 0 at most)."""
    want = {f"{prefix}.{k}" for k in KINDS}
    assert want <= set(m), (want - set(m), out[-3000:])
    assert m[f"{prefix}.host_cpu_ms"] > 0 and m[f"{prefix}.rider_cpu_ms"] > 0
    assert m[f"{prefix}.host_wait_ms"] >= 0
    assert m[f"{prefix}.rider_wait_ms"] > -0.5
    assert m[f"{prefix}.lock_acquire_ms"] >= 0
    # a median over every beat since boot: an idle boot can outweigh a
    # window of a few seconds, so only that it is a share
    assert 0 <= m[f"{prefix}.lock_busy_pct"] < 800


def test_the_steady_toy_cell_reads_its_three(root):
    m, out = _traced(root, "toy_als.toy_steady", 4.0)
    want = {"serve.host_wait_ms", "serve.rider_wait_ms", "serve.lock_acquire_ms"}
    assert want <= set(m), (want - set(m), out[-3000:])
    assert m["serve.lock_acquire_ms"] >= 0 and m["serve.host_wait_ms"] >= 0


def test_the_saturated_toy_cell_reads_the_lock_account_and_its_rests(root):
    m, out = _traced(root, "toy_als.toy_saturated", 4.0)
    _account(m, "sat", out)
    assert m["sat.claim_rests"] >= 0


def test_the_toy_retrain_reads_its_cpu_seconds(root):
    m, out = _traced(root, "toy_als.toy_retrain", 2.0)
    want = {"train.startup_cpu_s", "train.read_cpu_s", "train.first_sweep_cpu_s"}
    assert want <= set(m), (want - set(m), out[-3000:])
    # a thread's CPU time in a span lies inside the span's wall (both
    # rounded to the millisecond)
    assert 0 <= m["train.first_sweep_cpu_s"] <= m["train.first_sweep_s"] + 0.002
    assert 0 <= m["train.read_cpu_s"] <= m["train.read_s"] + 0.002
    assert m["train.startup_cpu_s"] > 0


def test_the_filtered_toy_cell_reads_the_lock_account(ecom_root):
    m, out = _traced(ecom_root, "toy_ecom.toy_filtered", 3.0)
    _account(m, "ecom", out)
    assert m["ecom.give_way_ms"] >= 0 and m["ecom.second_batch_rests"] >= 0


def test_the_similar_toy_cell_reads_the_lock_account(sim_root):
    m, out = _traced(sim_root, "toy_simprod.toy_similar", 3.0)
    _account(m, "sim", out)
    assert m["sim.give_way_ms"] >= 0 and m["sim.second_batch_rests"] >= 0


def test_the_pairs_toy_retrain_reads_its_init_span_and_read_cpu(tt_root):
    m, out = _traced(tt_root, TT_CELL, 2.0, seed=11)
    assert {"tt.read_cpu_s", "tt.init_s"} <= set(m), out[-3000:]
    assert 0 <= m["tt.read_cpu_s"] <= m["tt.read_s"] + 0.002
    assert m["tt.init_s"] > 0
