"""Test harness configuration.

Forces JAX onto a virtual 8-device CPU platform *before* jax is imported
anywhere, so mesh/sharding tests exercise real multi-device semantics
without TPU hardware — the analog of the reference's Spark ``local[*]``
test fixture (SURVEY.md section 5.1).
"""

import os

# Force the virtual 8-device CPU platform: the environment variables for
# a jax not yet imported, and the config update for one an interpreter
# start-up hook already imported (backends are created lazily at first
# jax.devices()/dispatch, so both land in time).
#
# PIO_TEST_TPU=1 keeps the real accelerator backend instead — for the
# hardware-marked suite (tests/test_pallas_tpu.py), which tier-1 skips and
# a chip run executes: PIO_TEST_TPU=1 python -m pytest tests/test_pallas_tpu.py
if os.environ.get("PIO_TEST_TPU") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

from predictionio_tpu.data.storage import Storage  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--lock-witness",
        action="store_true",
        default=False,
        help="run the suite under the composed runtime lock/fsync "
        "witness (predictionio_tpu.analysis.lock_witness): records the "
        "lock acquisition-order digraph plus fsync/rename orderings, "
        "fails loudly on witnessed lock-order inversions AND on a "
        "failed static/dynamic crosscheck (a witnessed edge missing "
        "from the static lock graph, or an unmanifested static cycle "
        "without a lock-witness-waivers.json entry). Report lands at "
        "$PIO_LOCK_WITNESS_REPORT (JSON) or the terminal summary.",
    )


    parser.addoption(
        "--jit-witness",
        action="store_true",
        default=False,
        help="run the suite under the runtime jit-witness sanitizer "
        "(predictionio_tpu.analysis.jit_witness): counts XLA compiles "
        "per call site, device->host transfer bytes and per-call "
        "jax.jit constructions; classifies every static PIO306-308 "
        "finding CONFIRMED/PLAUSIBLE at session end. Report lands at "
        "$PIO_JIT_WITNESS_REPORT (JSON) or the terminal summary.",
    )


def pytest_configure(config):
    if config.getoption("--lock-witness"):
        from predictionio_tpu.analysis import lock_witness, witness

        # install BEFORE any test allocates a lock, so every
        # object constructed during the run is witnessed; the composed
        # witness adds the fsync/rename record on top of the lock half
        w = lock_witness.LockFsyncWitness()
        w.install()
        witness._ACTIVE = w.locks  # witness.active()/report() still work
        config._lock_witness = w
    if config.getoption("--jit-witness"):
        from predictionio_tpu.analysis import jit_witness

        # install before collection so imports-under-test and fixtures
        # compile under the witness too
        config._jit_witness = jit_witness.install()


def pytest_sessionfinish(session, exitstatus):
    # "fails loudly": a witnessed lock-order inversion OR a failed
    # static/dynamic crosscheck turns a green run red even though no
    # individual test asserted on it — the sanitizer is only worth
    # running if its findings gate CI. The full payload (crosscheck
    # included) is computed once here and stashed for unconfigure.
    w = getattr(session.config, "_lock_witness", None)
    if w is None:
        return
    from predictionio_tpu.analysis import lock_witness

    payload = lock_witness.lockwitness_report(w.report())
    session.config._lock_witness_payload = payload
    if exitstatus == 0 and not payload["ok"]:
        session.exitstatus = 3


def pytest_unconfigure(config):
    jw = getattr(config, "_jit_witness", None)
    if jw is not None:
        from predictionio_tpu.analysis import jit_witness

        jit_witness.uninstall()
        rep = jw.report()
        payload = jit_witness.jitwitness_report(rep)
        path = os.environ.get("PIO_JIT_WITNESS_REPORT")
        if path:
            jit_witness.write_report(path, payload)
        confirmed = [
            c
            for c in payload["staticCompileFindings"]
            if c["status"] == "CONFIRMED"
        ]
        # informational, not a gate: a test suite legitimately compiles
        # everywhere — the compile-budget gate lives in the bench smoke
        # guard's WARMED serving window and the compile-count regression
        # tests, where zero/bounded compiles is a meaningful invariant
        print(
            f"\njit-witness: {len(rep.get('compiles', {}))} compile "
            f"site(s) ({rep.get('totalCompiles', 0)} compiles, "
            f"{rep.get('totalCompileMs', 0.0):.0f} ms), "
            f"{len(rep.get('transfers', {}))} transfer site(s) "
            f"({rep.get('totalTransferBytes', 0)} bytes), "
            f"{len(payload['staticCompileFindings'])} static PIO306-308 "
            f"finding(s) ({len(confirmed)} CONFIRMED), "
            f"{len(payload['budget']['violations'])} budget violation(s)"
        )
    w = getattr(config, "_lock_witness", None)
    if w is None:
        return
    import json as _json

    from predictionio_tpu.analysis import lock_witness, witness

    w.uninstall()
    witness._ACTIVE = None
    payload = getattr(config, "_lock_witness_payload", None)
    if payload is None:  # sessionfinish never ran (collection crash)
        payload = lock_witness.lockwitness_report(w.report())
    rep = payload["witness"]
    path = os.environ.get("PIO_LOCK_WITNESS_REPORT")
    if path:
        witness.write_report(path, payload)
    inv = rep.get("inversions", [])
    confirmed = [
        c for c in payload["staticLockCycles"] if c["status"] == "CONFIRMED"
    ]
    cc = payload["crosscheck"]
    fs = rep.get("fsync", {})
    print(
        f"\nlock-witness: {len(rep.get('locks', {}))} lock site(s), "
        f"{len(rep.get('edges', []))} order edge(s), "
        f"{len(inv)} inversion(s), "
        f"{len(payload['staticLockCycles'])} static cycle(s) "
        f"({len(confirmed)} CONFIRMED); "
        f"fsync: {fs.get('fsyncCalls', 0)} call(s), "
        f"{len(fs.get('renames', []))} rename(s); "
        f"crosscheck: {len(cc['gaps'])} gap(s), "
        f"{len(cc['unwaivedStaticCycles'])} unwaived cycle(s), "
        f"{len(cc['staleWaivers'])} stale waiver(s)"
    )
    if inv:
        print(_json.dumps(inv, indent=2))
    if cc["gaps"] or cc["unwaivedStaticCycles"]:
        print(_json.dumps(
            {"gaps": cc["gaps"],
             "unwaivedStaticCycles": cc["unwaivedStaticCycles"]},
            indent=2,
        ))


@pytest.fixture()
def storage_env(tmp_path):
    """Point the global Storage registry at throwaway in-memory metadata and
    a tmp sqlite db + localfs model dir; restore afterwards."""
    Storage.configure(
        {
            "PIO_FS_BASEDIR": str(tmp_path),
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "TEST_SQLITE",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "TEST_SQLITE",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "TEST_FS",
            "PIO_STORAGE_SOURCES_TEST_SQLITE_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_TEST_SQLITE_PATH": str(tmp_path / "pio.db"),
            "PIO_STORAGE_SOURCES_TEST_FS_TYPE": "localfs",
            "PIO_STORAGE_SOURCES_TEST_FS_PATH": str(tmp_path / "models"),
        }
    )
    yield Storage
    Storage.configure(None)


@pytest.fixture()
def memory_storage_env():
    """All three roles on the in-memory driver."""
    Storage.configure(
        {
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
            "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        }
    )
    yield Storage
    Storage.configure(None)
