"""Runtime jit-witness (predictionio_tpu.analysis.jit_witness) +
compile-budget ledger — ISSUE 14.

Three layers:

* witness primitives — compile counting via jax.monitoring with
  call-site attribution, transfer recording through the patched numpy
  boundary, per-call jit-construction recording, clean (nested)
  uninstall;
* ledger mechanics — ``check_budget`` violation/unbudgeted split,
  ``prune_ledger`` stale-entry cleanup, CONFIRMED/PLAUSIBLE
  classification of static PIO306–308 findings;
* compile-count regression tests for the three known pow2-bucket
  serving paths (ISSUE 14 satellite): a WARMED path serving N distinct
  request shapes must witness ≤ bucket-count compiles (and zero after
  warm-up) — deleting a bucketing step turns these red, which is the
  compile-budget CI gate for flows the static taint analysis cannot
  see (the fold-in width bucket).
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from predictionio_tpu.analysis import jit_witness as jw
from predictionio_tpu.analysis.engine import Finding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Witness primitives
# ---------------------------------------------------------------------------


class TestWitnessPrimitives:
    def test_compile_counted_and_attributed(self):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return x * 3 + 1

        def drive():
            # unique shape so no earlier test's in-process cache hides
            # the compile
            f(jnp.ones((3, 41))).block_until_ready()
            f(jnp.ones((3, 43))).block_until_ready()

        _, rep = jw.run_with_jit_witness(drive)
        assert rep["totalCompiles"] >= 2
        key = "tests/test_jit_witness.py:drive"
        assert key in rep["compiles"]
        st = rep["compiles"][key]
        assert st["count"] >= 2
        assert st["firstCompileMs"] > 0
        assert st["totalCompileMs"] >= st["firstCompileMs"]

    def test_transfer_recorded_with_bytes(self):
        import jax.numpy as jnp

        x = jnp.ones((8, 16), jnp.float32)

        def drive():
            np.asarray(x)
            np.array(x)
            host = np.ones(4)
            np.asarray(host)  # host->host: NOT a transfer

        _, rep = jw.run_with_jit_witness(drive)
        key = "tests/test_jit_witness.py:drive"
        assert key in rep["transfers"]
        st = rep["transfers"][key]
        assert st["count"] == 2
        assert st["bytes"] == 2 * x.nbytes
        assert rep["totalTransferBytes"] == 2 * x.nbytes

    def test_device_get_recorded(self):
        import jax
        import jax.numpy as jnp

        x = jnp.ones((4, 4))

        def drive():
            jax.device_get({"a": x})

        _, rep = jw.run_with_jit_witness(drive)
        st = rep["transfers"]["tests/test_jit_witness.py:drive"]
        assert st["count"] == 1
        assert st["bytes"] == x.nbytes
        assert "device_get" in st["kinds"]

    def test_jit_construction_recorded(self):
        import jax

        def drive():
            f = jax.jit(lambda x: x)
            return f(1.0)

        _, rep = jw.run_with_jit_witness(drive)
        key = "tests/test_jit_witness.py:drive"
        assert key in rep["jitConstructions"]
        assert rep["jitConstructions"][key]["count"] == 1

    def test_uninstall_restores_and_nests(self):
        # explicit instances, NOT the module singleton — the suite may
        # itself be running under a session-wide `pytest --jit-witness`
        import jax
        import numpy

        before_asarray = numpy.asarray
        before_jit = jax.jit
        outer = jw.JitWitness()
        outer.install()
        try:
            assert numpy.asarray is not before_asarray
            mid_asarray = numpy.asarray
            # nested witness displaces the OUTER wrappers and must hand
            # them back on uninstall, not the import-time originals
            inner = jw.JitWitness()
            inner.install()
            assert numpy.asarray is not mid_asarray
            inner.uninstall()
            assert numpy.asarray is mid_asarray
        finally:
            outer.uninstall()
        assert numpy.asarray is before_asarray
        assert jax.jit is before_jit


# ---------------------------------------------------------------------------
# Ledger mechanics
# ---------------------------------------------------------------------------


class TestLedger:
    def test_check_budget_split(self):
        rep = {
            "compiles": {
                "predictionio_tpu/ops/ivf.py:query_topk": {"count": 3},
                "predictionio_tpu/ops/ivf.py:other_fn": {"count": 2},
                "predictionio_tpu/online/foldin.py:foldin_rows": {
                    "count": 99
                },
                "predictionio_tpu/workflow/mystery.py:serve": {"count": 1},
                "tests/test_x.py:drive": {"count": 50},  # not a package site
            }
        }
        ledger = {
            "entries": [
                {
                    "entrypoint": "predictionio_tpu/ops/ivf.py:query_topk",
                    "maxCompiles": 8,
                },
                # path-level entry budgets every function in the file
                {
                    "entrypoint": "predictionio_tpu/ops/ivf.py",
                    "maxCompiles": 4,
                },
                {
                    "entrypoint": "predictionio_tpu/online/foldin.py:"
                    "foldin_rows",
                    "maxCompiles": 16,
                },
            ]
        }
        out = jw.check_budget(rep, ledger)
        assert out["checked"] == 4  # the tests/ site is excluded
        assert [v["entrypoint"] for v in out["violations"]] == [
            "predictionio_tpu/online/foldin.py:foldin_rows"
        ]
        assert out["violations"][0]["maxCompiles"] == 16
        assert [u["entrypoint"] for u in out["unbudgeted"]] == [
            "predictionio_tpu/workflow/mystery.py:serve"
        ]

    def test_path_level_budget_is_shared_across_functions(self):
        """A bare-path entry budgets the whole file: exact-entry-less
        functions SUM against maxCompiles — five functions compiling a
        few programs each cannot hide under a per-site reading."""
        rep = {
            "compiles": {
                f"predictionio_tpu/workflow/device_state.py:f{i}": {
                    "count": 3
                }
                for i in range(5)
            }
        }
        ledger = {
            "entries": [
                {
                    "entrypoint": "predictionio_tpu/workflow/"
                    "device_state.py",
                    "maxCompiles": 8,
                }
            ]
        }
        out = jw.check_budget(rep, ledger)
        assert len(out["violations"]) == 1
        v = out["violations"][0]
        assert v["entrypoint"] == "predictionio_tpu/workflow/device_state.py"
        assert v["compiles"] == 15 and v["maxCompiles"] == 8
        assert len(v["sites"]) == 5
        # under the shared pool an exact entry still takes its function
        # OUT of the pool
        ledger["entries"].append(
            {
                "entrypoint": "predictionio_tpu/workflow/"
                "device_state.py:f0",
                "maxCompiles": 4,
            }
        )
        out = jw.check_budget(rep, ledger)
        assert out["violations"][0]["compiles"] == 12  # f0 pooled out

    def test_deleting_a_bucket_step_fails_the_budget_gate(self):
        """The CI shape of a retrace regression: a serving entrypoint
        whose bucket step was deleted compiles per-request-cardinality
        and blows its ledger entry."""
        ledger = jw.load_ledger(jw.default_ledger_path(REPO))
        regressed = {
            "compiles": {
                # what ops/ivf.py:query_topk looks like WITHOUT its kb
                # bucket: one compile per distinct requested k
                "predictionio_tpu/ops/ivf.py:query_topk": {"count": 40},
            }
        }
        out = jw.check_budget(regressed, ledger)
        assert out["violations"], (
            "compile-budget.json no longer budgets ops/ivf.py:query_topk "
            "— the retrace-regression gate is gone"
        )

    def test_prune_ledger(self, tmp_path):
        path = str(tmp_path / "compile-budget.json")
        jw.write_ledger(
            path,
            {
                "entries": [
                    {  # live: real file + real function
                        "entrypoint": "predictionio_tpu/ops/ivf.py:"
                        "query_topk",
                        "maxCompiles": 8,
                        "justification": "keep",
                    },
                    {  # live: path-level entry on a real file
                        "entrypoint": "predictionio_tpu/ops/topk.py",
                        "maxCompiles": 8,
                    },
                    {  # stale: file is gone
                        "entrypoint": "predictionio_tpu/ops/gone.py:f",
                        "maxCompiles": 4,
                    },
                    {  # stale: file exists, function does not
                        "entrypoint": "predictionio_tpu/ops/ivf.py:"
                        "no_such_function",
                        "maxCompiles": 4,
                    },
                ]
            },
        )
        pruned = jw.prune_ledger(path, REPO)
        assert pruned == 2
        kept = jw.load_ledger(path)["entries"]
        assert {e["entrypoint"] for e in kept} == {
            "predictionio_tpu/ops/ivf.py:query_topk",
            "predictionio_tpu/ops/topk.py",
        }
        # justifications survive the prune
        assert kept[0]["justification"] == "keep"
        # pruning a clean ledger is a no-op
        assert jw.prune_ledger(path, REPO) == 0

    def test_prune_via_pio_lint_cli(self, tmp_path):
        import subprocess
        import sys

        pkg = tmp_path / "predictionio_tpu"
        pkg.mkdir()
        (pkg / "mod.py").write_text("def live():\n    return 1\n")
        jw.write_ledger(
            str(tmp_path / "compile-budget.json"),
            {
                "entries": [
                    {
                        "entrypoint": "predictionio_tpu/mod.py:live",
                        "maxCompiles": 2,
                    },
                    {
                        "entrypoint": "predictionio_tpu/gone.py:dead",
                        "maxCompiles": 2,
                    },
                ]
            },
        )
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [
                sys.executable, "-m", "predictionio_tpu.tools.console",
                "lint", "--root", str(tmp_path), "--prune-baseline",
            ],
            capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "1 stale compile-budget entry pruned" in proc.stdout
        kept = jw.load_ledger(str(tmp_path / "compile-budget.json"))
        assert [e["entrypoint"] for e in kept["entries"]] == [
            "predictionio_tpu/mod.py:live"
        ]


# ---------------------------------------------------------------------------
# CONFIRMED / PLAUSIBLE classification
# ---------------------------------------------------------------------------


class TestClassification:
    def _root_with(self, tmp_path, source: str) -> str:
        pkg = tmp_path / "predictionio_tpu"
        pkg.mkdir()
        (pkg / "svc.py").write_text(source)
        return str(tmp_path)

    def test_confirmed_vs_plausible(self, tmp_path):
        root = self._root_with(
            tmp_path,
            "def serve(body):\n"
            "    x = body\n"
            "    return x\n"
            "\n"
            "def fold(batch):\n"
            "    return batch\n",
        )
        findings = [
            Finding("PIO306", "predictionio_tpu/svc.py", 2, "retrace"),
            Finding("PIO307", "predictionio_tpu/svc.py", 3, "transfer"),
            Finding("PIO308", "predictionio_tpu/svc.py", 6, "perjit"),
        ]
        rep = {
            "compiles": {
                "predictionio_tpu/svc.py:serve": {"count": 5}
            },
            "transfers": {
                "predictionio_tpu/svc.py:serve": {"count": 2, "bytes": 64}
            },
            "jitConstructions": {},  # fold never constructed
        }
        out = jw.classify_findings(findings, rep, root)
        by_code = {o["code"]: o for o in out}
        assert by_code["PIO306"]["status"] == "CONFIRMED"
        assert by_code["PIO306"]["witnessedEvents"] == 5
        assert by_code["PIO306"]["function"] == "serve"
        assert by_code["PIO307"]["status"] == "CONFIRMED"
        assert by_code["PIO308"]["status"] == "PLAUSIBLE"
        assert by_code["PIO308"]["witnessedEvents"] == 0

    def test_single_compile_is_not_a_confirmed_retrace(self, tmp_path):
        """One compile at a PIO306 site is warm-up, not a retrace: the
        CONFIRMED bar is >= 2 (the site really compiled again)."""
        root = self._root_with(tmp_path, "def serve(body):\n    return 1\n")
        findings = [
            Finding("PIO306", "predictionio_tpu/svc.py", 2, "retrace")
        ]
        rep = {"compiles": {"predictionio_tpu/svc.py:serve": {"count": 1}}}
        out = jw.classify_findings(findings, rep, root)
        assert out[0]["status"] == "PLAUSIBLE"

    def test_jitwitness_report_shape(self):
        """The `pio jitwitness` / pytest --jit-witness payload: raw
        witness + classified static findings + budget. The tree ships
        PIO306-308-clean, so the finding list is empty on trunk (the
        fixtures above prove the classifier both ways — same contract
        as the lock-witness's static-cycle join)."""
        payload = jw.jitwitness_report(
            {"compiles": {}, "transfers": {}, "jitConstructions": {}},
            root=REPO,
        )
        assert payload["ok"] is True
        assert payload["staticCompileFindings"] == []
        assert payload["ledgerEntries"] >= 10
        assert payload["budget"] == {
            "checked": 0, "violations": [], "unbudgeted": []
        }
        json.dumps(payload)  # JSON-serializable end to end


# ---------------------------------------------------------------------------
# Compile-count regression: the three pow2-bucket serving paths
# ---------------------------------------------------------------------------


class TestBucketCompileCounts:
    def test_ivf_query_topk_buckets(self):
        """Warmed `ops/ivf.query_topk` serves 40 distinct k values with
        <= 3 compiles (buckets 16/32/64) and ZERO compiles after warm-up
        — deleting the kb bucket makes the serve phase compile per
        distinct k and turns this red (the runtime half of PIO306)."""
        from predictionio_tpu.ops import ivf

        rng = np.random.default_rng(7)
        # unique dims so no other test's in-process jit cache hides or
        # pre-pays our compiles
        items = rng.standard_normal((310, 21)).astype(np.float32)
        items /= np.linalg.norm(items, axis=1, keepdims=True)
        index, _info = ivf.build_ivf(items, nlist=8, seed=0, iters=2)
        rt = ivf.AnnRuntime(index, nprobe=4, build_info={})

        def warm():
            for k in (5, 20, 40):  # one per bucket: 16, 32, 64
                ivf.query_topk(rt, items[0], k)

        _, warm_rep = jw.run_with_jit_witness(warm)
        site = "predictionio_tpu/ops/ivf.py:query_topk"
        assert site in warm_rep["compiles"], warm_rep["compiles"]
        warm_compiles = warm_rep["compiles"][site]["count"]
        assert 1 <= warm_compiles <= 3

        def serve():
            for k in range(1, 41):
                ids, scores = ivf.query_topk(rt, items[k % 100], k)
                assert len(ids) == min(k, 310)

        _, serve_rep = jw.run_with_jit_witness(serve)
        assert serve_rep["compiles"].get(site, {"count": 0})["count"] == 0, (
            "a warmed query_topk recompiled while serving known-bucket "
            f"k values: {serve_rep['compiles']}"
        )
        # the checked-in ledger budgets this entrypoint
        ledger = jw.load_ledger(jw.default_ledger_path(REPO))
        assert jw.check_budget(warm_rep, ledger)["violations"] == []
        assert (
            jw.check_budget(warm_rep, ledger)["unbudgeted"] == []
        ), "warm-up compiled at a site compile-budget.json does not cover"

    def test_foldin_width_buckets(self):
        """Warmed `online/foldin.foldin_rows` folds histories of 20
        distinct widths with <= 3 compiles (width buckets 8/16/32) and
        zero after warm-up. This is the bucket whose taint flows through
        state-dict mutation the static PIO306 cannot see — the witness
        IS its regression gate."""
        from predictionio_tpu.online.foldin import foldin_rows

        rng = np.random.default_rng(3)
        opposite = rng.standard_normal((50, 11)).astype(np.float32)

        def entries_of(width: int):
            ix = rng.integers(0, 50, width).tolist()
            vs = rng.uniform(1, 5, width).tolist()
            return [(ix, vs)]

        def warm():
            for width in (3, 12, 20):  # buckets 8, 16, 32
                foldin_rows(opposite, entries_of(width), reg=0.1)

        _, warm_rep = jw.run_with_jit_witness(warm)
        site = "predictionio_tpu/online/foldin.py:foldin_rows"
        assert site in warm_rep["compiles"], warm_rep["compiles"]
        # 3 width buckets + up to 2 tiny operand-conversion programs
        # (whether those appear depends on what earlier tests already
        # compiled in-process); the hard gate is the ZERO below
        assert 1 <= warm_rep["compiles"][site]["count"] <= 5

        def serve():
            for width in range(1, 21):
                rows = foldin_rows(opposite, entries_of(width), reg=0.1)
                assert rows.shape == (1, 11)

        _, serve_rep = jw.run_with_jit_witness(serve)
        assert serve_rep["compiles"].get(site, {"count": 0})["count"] == 0, (
            "a warmed fold-in recompiled at known width buckets: "
            f"{serve_rep['compiles']}"
        )
        ledger = jw.load_ledger(jw.default_ledger_path(REPO))
        budget = jw.check_budget(warm_rep, ledger)
        assert budget["violations"] == []
        assert budget["unbudgeted"] == []

    def test_microbatcher_bucket_shapes(self):
        """A pinned, batching deployment serves every batch size 1..8
        through its pow2 buckets with ZERO post-warm-up compiles: the
        micro-batcher pads each dispatch up to a bucket and the chunked
        device path pads queries to one chunk shape, so after the
        constructor's warm-up no live batch size can retrace."""
        from predictionio_tpu.controller import local_context
        from predictionio_tpu.data.event import DataMap, Event
        from predictionio_tpu.data.storage import Storage
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.serving import BatcherConfig, CacheConfig
        from predictionio_tpu.serving.batcher import _Pending
        from predictionio_tpu.utils import spans
        from predictionio_tpu.workflow import load_engine_variant, run_train
        from predictionio_tpu.workflow.serving import QueryService

        Storage.configure(
            {
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
                "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
            }
        )
        try:
            app_id = Storage.get_meta_data_apps().insert(
                App(id=0, name="jw-app")
            )
            rng = np.random.default_rng(9)
            Storage.get_p_events().write(
                (
                    Event(
                        event="rate",
                        entity_type="user",
                        entity_id=str(u),
                        target_entity_type="item",
                        target_entity_id=str(i),
                        properties=DataMap({"rating": float((u + i) % 5 + 1)}),
                    )
                    for u, i in zip(
                        rng.integers(0, 25, 600), rng.integers(0, 57, 600)
                    )
                ),
                app_id,
            )
            variant = load_engine_variant(
                {
                    "id": "jw-eng",
                    "version": "1",
                    "engineFactory": "predictionio_tpu.templates."
                    "recommendation:engine_factory",
                    "datasource": {"params": {"appName": "jw-app"}},
                    "algorithms": [
                        {
                            "name": "als",
                            "params": {
                                "rank": 9,
                                "numIterations": 2,
                                "lambda": 0.05,
                                "seed": 9,
                            },
                        }
                    ],
                }
            )
            run_train(variant, local_context())
            body = {"user": "1", "num": 7}

            def build():
                return QueryService(
                    variant,
                    batching=BatcherConfig(
                        max_batch_size=8,
                        max_batch_delay_ms=0.0,
                        warmup_body=body,
                    ),
                    cache=CacheConfig(pin_model=True),
                )

            qs, warm_rep = jw.run_with_jit_witness(build)
            try:

                def serve():
                    collector = spans.Collector()
                    for n in range(1, 9):
                        qs.batcher._dispatch(
                            [
                                _Pending({"user": str(u % 25), "num": 7})
                                for u in range(n)
                            ],
                            collector,
                            *qs.batcher._number(collector, n),
                        )

                _, serve_rep = jw.run_with_jit_witness(serve)
                pkg_compiles = {
                    k: v
                    for k, v in serve_rep["compiles"].items()
                    if k.startswith("predictionio_tpu/")
                }
                assert pkg_compiles == {}, (
                    "warmed batched serving recompiled on live batch "
                    f"sizes: {pkg_compiles}"
                )
                # warm-up itself stays inside the checked-in budgets
                ledger = jw.load_ledger(jw.default_ledger_path(REPO))
                budget = jw.check_budget(warm_rep, ledger)
                assert budget["violations"] == []
                assert budget["unbudgeted"] == []
            finally:
                qs.close()
        finally:
            Storage.configure(None)
