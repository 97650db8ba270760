"""Program spans and the compile ledger (predictionio_tpu/utils/spans.py)."""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.utils import spans
from predictionio_tpu.utils.spans import CompileLedger, Collector, span


@pytest.fixture()
def collector():
    c = Collector()
    previous = spans.bind(c)
    yield c
    spans.bind(previous)


class TestSpan:
    def test_without_a_collector_it_times_and_records_nothing(self):
        assert spans.current() is None
        with span("bind") as s:
            time.sleep(0.002)
        assert s.ns >= 2_000_000
        assert s.ms == s.ns / 1e6 and s.seconds == s.ns / 1e9

    def test_a_bound_collector_gets_the_closed_span(self, collector):
        collector.seq = 7
        with span("bind") as s:
            pass
        (record,) = collector.take()
        assert record == ("bind", None, 7, s.start_ns, s.end_ns, 0)
        assert collector.take() == []  # take empties it

    def test_nested_spans_know_their_parent(self, collector):
        with span("handle", enclosing=True):
            with span("bind"):
                pass
            with span("format"):
                pass
        by_name = {r.name: r for r in collector.take()}
        assert by_name["bind"].parent == "handle"
        assert by_name["format"].parent == "handle"
        assert by_name["handle"].parent is None
        assert by_name["handle"].start_ns <= by_name["bind"].start_ns
        assert by_name["format"].end_ns <= by_name["handle"].end_ns

    def test_start_and_stop_are_the_two_ends(self, collector):
        s = span("httpRead").start()
        s.stop()
        assert [r.name for r in collector.take()] == ["httpRead"]

    def test_on_close_hears_each_span_as_it_closes_the_record_already_kept(
            self, collector):
        heard = []
        collector.on_close = lambda name: heard.append((name, len(collector._closed)))
        with span("handle", enclosing=True):
            with span("dispatch"):
                pass
            assert heard == [("dispatch", 1)]  # while handle is still open
        assert heard == [("dispatch", 1), ("handle", 2)]
        collector.on_close = None
        with span("format"):
            pass
        assert len(heard) == 2

    def test_a_collector_belongs_to_its_thread(self, collector):
        seen = []

        def other():
            seen.append(spans.current())
            with span("elsewhere"):
                pass

        t = threading.Thread(target=other, daemon=True)
        t.start()
        t.join(timeout=5)
        assert seen == [None] and collector.take() == []

    def test_a_collector_nobody_takes_from_stays_bounded(self, collector):
        for _ in range(Collector.MAX_SPANS + 10):
            with span("x"):
                pass
        assert len(collector.take()) == Collector.MAX_SPANS

    def test_durations_sum_the_spans_of_one_name(self):
        records = [
            spans.SpanRecord("format", None, 1, 0, 2_000_000),
            spans.SpanRecord("format", None, 1, 5_000_000, 6_000_000),
            spans.SpanRecord("bind", None, 1, 2_000_000, 2_500_000),
        ]
        assert spans.durations_ms(records) == {"format": 3.0, "bind": 0.5}

    def test_cpu_sums_the_spans_of_one_name(self):
        records = [
            spans.SpanRecord("format", None, 1, 0, 2_000_000, 1_500_000),
            spans.SpanRecord("format", None, 1, 5_000_000, 6_000_000, 500_000),
            spans.SpanRecord("bind", None, 1, 2_000_000, 2_500_000),  # none taken
        ]
        assert spans.cpu_ms(records) == {"format": 2.0, "bind": 0.0}

    def test_process_age_is_the_kernels_record(self):
        age = spans.process_age_s()
        assert age is not None and 0 < age < 24 * 3600
        time.sleep(0.05)
        assert spans.process_age_s() > age


class TestSpanCpu:
    """A span on a collector made with ``cpu`` reads its thread's CPU
    clock beside the wall (ISSUE 37). Orderings, not times: the suite
    shares its machine."""

    @pytest.fixture()
    def cpu_collector(self):
        c = Collector(cpu=True)
        previous = spans.bind(c)
        yield c
        spans.bind(previous)

    def test_a_span_that_spins_reads_its_cpu_and_no_more_than_its_wall(
            self, cpu_collector):
        with span("spin") as s:
            t0 = time.thread_time_ns()
            while time.thread_time_ns() - t0 < 20_000_000:
                pass
        (record,) = cpu_collector.take()
        assert record.cpu_ns == s.cpu_ns >= 20_000_000
        # the CPU clock is read inside the wall clock's two reads
        assert s.cpu_ns <= s.ns
        assert s.cpu_ms == s.cpu_ns / 1e6 and s.cpu_seconds == s.cpu_ns / 1e9
        assert spans.cpu_ms([record]) == {"spin": s.cpu_ms}

    def test_a_span_that_sleeps_reads_next_to_none(self, cpu_collector):
        with span("sleep") as s:
            time.sleep(0.05)
        (record,) = cpu_collector.take()
        assert s.ns >= 50_000_000
        assert 0 <= record.cpu_ns < 0.1 * s.ns

    def test_a_thread_that_waits_for_the_interpreter_lock_accrues_none(self):
        """Two threads spinning in Python share one lock: each is on the
        CPU for part of its wall only, and wall less CPU is the wait."""
        out = {}

        def spin(name):
            spans.bind(Collector(cpu=True))
            with span(name) as s:
                end = time.perf_counter_ns() + 200_000_000
                while time.perf_counter_ns() < end:
                    pass
            out[name] = s

        threads = [threading.Thread(target=spin, args=(n,)) for n in "ab"]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        a, b = out["a"], out["b"]
        assert a.cpu_ns <= a.ns and b.cpu_ns <= b.ns
        # while both ran, one of them at a time held the lock: together
        # they cannot have been on the CPU for more than the wall of the
        # two, end to end, plus what either ran alone
        overlap = min(a.end_ns, b.end_ns) - max(a.start_ns, b.start_ns)
        assert overlap > 100_000_000
        alone = (a.ns - overlap) + (b.ns - overlap)
        assert a.cpu_ns + b.cpu_ns <= overlap + alone + 20_000_000

    def test_enclosing_spans_hold_their_inner_spans_cpu(self, cpu_collector):
        with span("handle", enclosing=True) as outer:
            with span("bind") as inner:
                t0 = time.thread_time_ns()
                while time.thread_time_ns() - t0 < 5_000_000:
                    pass
        assert 5_000_000 <= inner.cpu_ns <= outer.cpu_ns <= outer.ns

    def test_without_cpu_a_span_reads_zero_and_never_calls_the_thread_clock(
            self, collector, monkeypatch):
        def no_call():
            raise AssertionError("a span read the thread's CPU clock")

        monkeypatch.setattr(time, "thread_time_ns", no_call)
        with span("bind") as s:
            pass
        t = span("two-ends").start()
        t.stop()
        assert s.cpu_ns == 0 and s.cpu_ms == 0.0
        assert [r.cpu_ns for r in collector.take()] == [0, 0]
        spans.bind(None)
        with span("nobody") as s:  # no collector at all
            pass
        assert s.cpu_ns == 0


class TestCompileLedger:
    def test_one_ledger_a_process(self):
        assert CompileLedger.install() is CompileLedger.install()

    def test_a_fresh_function_is_traced_lowered_and_compiled_once(self):
        ledger = CompileLedger.install()
        before = ledger.snapshot()

        @jax.jit
        def ledger_probe_one(x):
            return x * 3 + 1

        ledger_probe_one(np.ones(3, np.float32)).block_until_ready()
        ledger_probe_one(np.ones(3, np.float32)).block_until_ready()  # in-process hit
        entry = ledger.table(since=before)["ledger_probe_one"]
        assert entry["traces"] == entry["lowers"] == entry["compiles"] == 1
        assert entry["traceSeconds"] > 0 and entry["lowerSeconds"] > 0
        assert entry["loadSeconds"] > 0
        # nothing of it is left once it is the baseline
        assert "ledger_probe_one" not in ledger.table(since=ledger.snapshot())

    def test_since_boot_counts_from_the_mark(self):
        ledger = CompileLedger.install()

        @jax.jit
        def ledger_probe_two(x):
            return x - 2

        # numpy operands: a jnp.ones of a fresh shape compiles a program
        # of its own
        ledger_probe_two(np.ones(4, np.float32)).block_until_ready()
        ledger.mark_boot_complete()
        assert ledger.since_boot() == 0
        assert ledger.to_json()["sinceBoot"] == 0
        ledger_probe_two(np.ones(4, np.float32)).block_until_ready()  # warmed
        assert ledger.since_boot() == 0
        ledger_probe_two(np.ones(5, np.float32)).block_until_ready()  # fresh
        assert ledger.since_boot() == 1
        block = ledger.to_json()
        assert block["sinceBoot"] == 1 and block["missesSinceBoot"] == 0
        assert block["functions"]["ledger_probe_two"]["compiles"] == 2

    def test_a_persistent_cache_hit_is_a_hit_and_not_a_miss(self, tmp_path):
        from jax.experimental.compilation_cache import compilation_cache

        ledger = CompileLedger.install()
        saved = {
            name: getattr(jax.config, name)
            for name in (
                "jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes",
            )
        }
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        try:
            @jax.jit
            def ledger_probe_three(x):
                return jnp.tanh(x) * 5

            before = ledger.snapshot()
            ledger_probe_three(np.ones(6, np.float32)).block_until_ready()
            cold = ledger.table(since=before)["ledger_probe_three"]
            assert (cold["cacheRequests"], cold["cacheHits"],
                    cold["cacheMisses"]) == (1, 0, 1)
            jax.clear_caches()  # the in-process executable goes; the file stays
            ledger.mark_boot_complete()
            before = ledger.snapshot()
            ledger_probe_three(np.ones(6, np.float32)).block_until_ready()
            warm = ledger.table(since=before)["ledger_probe_three"]
            assert (warm["cacheRequests"], warm["cacheHits"],
                    warm["cacheMisses"]) == (1, 1, 0)
            assert warm["cacheRetrievalSeconds"] > 0
            assert warm["loadSeconds"] >= warm["cacheRetrievalSeconds"]
            # a hit is still a backend compile since boot, and no miss
            block = ledger.to_json()
            assert block["sinceBoot"] == 1 and block["missesSinceBoot"] == 0
        finally:
            for name, value in saved.items():
                jax.config.update(name, value)
            compilation_cache.reset_cache()

    def test_the_table_is_bounded(self):
        ledger = CompileLedger()
        for i in range(CompileLedger.MAX_FUNCTIONS + 5):
            ledger._on_duration(
                "/jax/core/compile/jaxpr_trace_duration", 0.001,
                fun_name=f"f{i}",
            )
        table = ledger.snapshot()
        assert len(table) == CompileLedger.MAX_FUNCTIONS + 1
        assert table["(other)"]["traces"] == 5

    def test_the_listeners_take_no_lock(self):
        """They run inside JAX's compile under the caller's locks: an
        event must go through while a reader holds the ledger's lock."""
        ledger = CompileLedger()
        with ledger._mu:
            ledger._on_event("/jax/compilation_cache/cache_hits")
            ledger._on_duration(
                "/jax/core/compile/backend_compile_duration", 0.5,
                fun_name="jit(f)",
            )
        assert ledger.since_boot() == 1
        assert ledger.table()["f"]["cacheHits"] == 1

    @pytest.mark.parametrize("raw,name", [
        ("als_sweep", "als_sweep"), ("jit(als_sweep)", "als_sweep"),
        ("jit_als_sweep", "als_sweep"), ("pmap(step)", "pmap(step)"),
        (None, "?"),
    ])
    def test_a_function_has_one_name_whatever_the_event(self, raw, name):
        assert spans._function_name(raw) == name
