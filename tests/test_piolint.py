"""piolint (predictionio_tpu.analysis) — fixture tests per rule, the
suppression / baseline mechanics, the ``pio lint`` CLI contract, and the
tier-1 full-tree lint gate.

Every rule gets three fixture flavors where meaningful: a positive
snippet that must fire, the same snippet with an inline suppression
(must not fire), and a baseline exclusion (fires but is not "new").
The fixtures are synthetic sources linted under synthetic repo-relative
paths — the engine never imports what it lints, so no fixture is ever
executed.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from predictionio_tpu.analysis import run_lint
from predictionio_tpu.analysis.engine import (
    Finding,
    lint_file,
    load_baseline,
    split_by_baseline,
    write_baseline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(rel_path: str, source: str) -> list[str]:
    found, _ = lint_file(rel_path, textwrap.dedent(source))
    return [f.code for f in found]


def _find(rel_path: str, source: str) -> list[Finding]:
    found, _ = lint_file(rel_path, textwrap.dedent(source))
    return found


# ---------------------------------------------------------------------------
# PIO1xx layering
# ---------------------------------------------------------------------------


def test_pio101_forbidden_import_fires_and_suppresses():
    src = "import jax\n"
    assert _codes("predictionio_tpu/serving/x.py", src) == ["PIO101"]
    # function-local imports are caught too (the old guard's property)
    local = """\
    def f():
        from jax import numpy
    """
    assert "PIO101" in _codes("predictionio_tpu/serving/x.py", local)
    # outside the manifested package the same import is fine
    assert _codes("predictionio_tpu/ops/x.py", src) == []
    suppressed = "import jax  # piolint: disable=PIO101\n"
    assert _codes("predictionio_tpu/serving/x.py", suppressed) == []


def test_pio102_stdlib_only_package():
    assert _codes("predictionio_tpu/resilience/x.py", "import numpy\n") == [
        "PIO102"
    ]
    assert _codes("predictionio_tpu/resilience/x.py", "import json\n") == []
    # intra-package imports are allow-listed
    ok = "from predictionio_tpu.resilience.retry import RetryPolicy\n"
    assert _codes("predictionio_tpu/resilience/x.py", ok) == []
    # relative imports resolve to the package and stay allowed
    assert _codes("predictionio_tpu/resilience/x.py", "from . import retry\n") == []


def test_pio103_template_sibling_isolation():
    bad = "from predictionio_tpu.templates.bar.engine import Model\n"
    assert _codes("predictionio_tpu/templates/foo/engine.py", bad) == ["PIO103"]
    # bare package-root imports of a sibling are violations too
    bare = "from predictionio_tpu.templates.bar import engine_factory\n"
    assert _codes("predictionio_tpu/templates/foo/engine.py", bare) == ["PIO103"]
    # shared helper modules directly under templates/ are sanctioned
    ok = "from predictionio_tpu.templates.serving_util import chunked_topk\n"
    assert _codes("predictionio_tpu/templates/foo/engine.py", ok) == []
    shared_results = "from predictionio_tpu.templates.results import ItemScore\n"
    assert _codes("predictionio_tpu/templates/foo/engine.py", shared_results) == []
    # a helper module itself (not inside a template dir) may import freely
    assert _codes("predictionio_tpu/templates/serving_util.py", bad) == []


# ---------------------------------------------------------------------------
# PIO2xx concurrency
# ---------------------------------------------------------------------------

_LOCKED_CLASS = """\
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._count = 0  # __init__ is exempt: not shared yet

    def good(self):
        with self._lock:
            self._count += 1

    def bad(self):
        self._count += 1
"""


def test_pio201_unguarded_shared_write():
    found = _find("predictionio_tpu/x.py", _LOCKED_CLASS)
    assert [f.code for f in found] == ["PIO201"]
    assert "_count" in found[0].message and "C" in found[0].message
    # a class with no lock is out of contract — nothing fires
    lockless = _LOCKED_CLASS.replace("self._lock = threading.Lock()", "pass")
    assert _codes("predictionio_tpu/x.py", lockless) == []
    # suppression on the reported line
    suppressed = _LOCKED_CLASS.replace(
        "        self._count += 1\n\n    def bad",
        "        self._count += 1\n\n    def bad",
    ).replace(
        "    def bad(self):\n        self._count += 1",
        "    def bad(self):\n        self._count += 1  # piolint: disable=PIO201",
    )
    assert _codes("predictionio_tpu/x.py", suppressed) == []


def test_pio201_from_import_lock_and_deferred_writes():
    # `from threading import Lock` declares a lock all the same
    from_import = """\
    from threading import Lock

    class C:
        def __init__(self):
            self._lock = Lock()

        def bad(self):
            self._n = 1
    """
    assert _codes("predictionio_tpu/x.py", from_import) == ["PIO201"]
    # a function DEFINED under the lock does not necessarily RUN under
    # it — its writes are not guarded by the enclosing with
    deferred = """\
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()

        def go(self):
            with self._lock:
                def later():
                    self._x = 1
                return later
    """
    assert _codes("predictionio_tpu/x.py", deferred) == ["PIO201"]


def test_pio202_blocking_call_under_lock():
    src = """\
    import threading
    import time

    class C:
        def __init__(self):
            self._lock = threading.Lock()

        def bad(self):
            with self._lock:
                time.sleep(1.0)

        def good(self):
            time.sleep(1.0)
    """
    assert _codes("predictionio_tpu/x.py", src) == ["PIO202"]
    # resolved through the import map: `from time import sleep`
    aliased = """\
    import threading
    from time import sleep

    _lock = threading.Lock()

    def bad():
        with _lock:
            sleep(1.0)
    """
    assert _codes("predictionio_tpu/x.py", aliased) == ["PIO202"]
    # a function DEFINED under the lock does not RUN under it
    deferred = """\
    import threading
    import time

    _lock = threading.Lock()

    def f():
        with _lock:
            def cb():
                time.sleep(1.0)
            return cb
    """
    assert _codes("predictionio_tpu/x.py", deferred) == []


def test_pio203_lock_order_cycle():
    src = """\
    import threading

    class C:
        def __init__(self):
            self._a_lock = threading.Lock()
            self._b_lock = threading.Lock()

        def one(self):
            with self._a_lock:
                with self._b_lock:
                    pass

        def two(self):
            with self._b_lock:
                with self._a_lock:
                    pass
    """
    found = _find("predictionio_tpu/x.py", src)
    assert [f.code for f in found] == ["PIO203"]
    assert "cycle" in found[0].message
    # consistent order across both methods: no finding
    consistent = src.replace(
        "with self._b_lock:\n                with self._a_lock:",
        "with self._a_lock:\n                with self._b_lock:",
    )
    assert _codes("predictionio_tpu/x.py", consistent) == []


def test_pio204_thread_daemon_explicit():
    bad = """\
    import threading
    t = threading.Thread(target=print)
    """
    assert _codes("predictionio_tpu/x.py", bad) == ["PIO204"]
    ok = """\
    import threading
    t = threading.Thread(target=print, daemon=False)
    """
    assert _codes("predictionio_tpu/x.py", ok) == []


def test_pio204_threadpool_executor_needs_bound():
    """ISSUE 8 satellite: the rule also covers ThreadPoolExecutor — the
    default max_workers scales with host cores, so an unbounded pool on
    a big serving host silently multiplies threads."""
    bad = """\
    from concurrent.futures import ThreadPoolExecutor
    ex = ThreadPoolExecutor()
    """
    assert _codes("predictionio_tpu/x.py", bad) == ["PIO204"]
    # an explicit None is the same unbounded default, spelled out
    explicit_none = """\
    import concurrent.futures
    ex = concurrent.futures.ThreadPoolExecutor(max_workers=None)
    """
    assert _codes("predictionio_tpu/x.py", explicit_none) == ["PIO204"]
    ok_kw = """\
    from concurrent.futures import ThreadPoolExecutor
    ex = ThreadPoolExecutor(max_workers=4)
    """
    assert _codes("predictionio_tpu/x.py", ok_kw) == []
    ok_pos = """\
    from concurrent.futures import ThreadPoolExecutor
    ex = ThreadPoolExecutor(8)
    """
    assert _codes("predictionio_tpu/x.py", ok_pos) == []
    suppressed = """\
    from concurrent.futures import ThreadPoolExecutor
    ex = ThreadPoolExecutor()  # piolint: disable=PIO204
    """
    assert _codes("predictionio_tpu/x.py", suppressed) == []


_UNBOUNDED_INSTANCE = """\
class Svc:
    def __init__(self):
        self._cache = {}

    def handle(self, key, value):
        self._cache[key] = value
"""

_BOUNDED_INSTANCE = """\
class Svc:
    def __init__(self):
        self._cache = {}

    def handle(self, key, value):
        self._cache[key] = value
        while len(self._cache) > 10:
            self._cache.popitem()
"""


def test_pio205_unbounded_instance_dict_cache():
    # fires only in the server packages (serving/, api/)
    assert _codes("predictionio_tpu/api/x.py", _UNBOUNDED_INSTANCE) == [
        "PIO205"
    ]
    assert _codes("predictionio_tpu/serving/x.py", _UNBOUNDED_INSTANCE) == [
        "PIO205"
    ]
    assert _codes("predictionio_tpu/workflow/x.py", _UNBOUNDED_INSTANCE) == []
    # any eviction mechanism (pop/popitem/clear/del/rebind) clears it
    assert _codes("predictionio_tpu/api/x.py", _BOUNDED_INSTANCE) == []
    deleted = _UNBOUNDED_INSTANCE + """\

    def evict(self, key):
        del self._cache[key]
"""
    assert _codes("predictionio_tpu/api/x.py", deleted) == []
    rebound = _UNBOUNDED_INSTANCE + """\

    def reset(self):
        self._cache = {}
"""
    assert _codes("predictionio_tpu/api/x.py", rebound) == []


def test_pio205_setdefault_counts_as_growth():
    src = """\
    class Svc:
        def __init__(self):
            self._flights = {}

        def join(self, key):
            return self._flights.setdefault(key, object())
    """
    assert _codes("predictionio_tpu/serving/x.py", src) == ["PIO205"]


def test_pio205_module_dict_cache():
    bad = """\
    _REGISTRY = {}

    def register(name, value):
        _REGISTRY[name] = value
    """
    assert _codes("predictionio_tpu/api/x.py", bad) == ["PIO205"]
    ok = bad + """\

    def unregister(name):
        _REGISTRY.pop(name, None)
    """
    assert _codes("predictionio_tpu/api/x.py", ok) == []
    # non-dict module state and ordinary local dicts never fire
    local = """\
    def f():
        out = {}
        out["k"] = 1
        return out
    """
    assert _codes("predictionio_tpu/api/x.py", local) == []


def test_pio205_suppression():
    suppressed = """\
    class Svc:
        def __init__(self):
            self._cache = {}

        def handle(self, key, value):
            self._cache[key] = value  # piolint: disable=PIO205
    """
    assert _codes("predictionio_tpu/api/x.py", suppressed) == []


# ---------------------------------------------------------------------------
# PIO206–PIO209: whole-program rules over the cross-module call graph
# ---------------------------------------------------------------------------

from predictionio_tpu.analysis.engine import lint_sources  # noqa: E402


def _program_codes(files: dict) -> list[str]:
    found, _sup, _stats, _cycles = lint_sources(
        {p: textwrap.dedent(s) for p, s in files.items()}
    )
    return [f.code for f in found]


def _program_find(files: dict):
    found, _sup, _stats, _cycles = lint_sources(
        {p: textwrap.dedent(s) for p, s in files.items()}
    )
    return found


_PIO206_CALLER = """\
import threading
from predictionio_tpu.helper import slow_helper

class C:
    def __init__(self):
        self._lock = threading.Lock()

    def go(self):
        with self._lock:
            slow_helper()
"""

_PIO206_HELPER = """\
import time

def slow_helper():
    deeper()

def deeper():
    time.sleep(1.0)
"""


def test_pio206_transitive_blocking_under_lock():
    files = {
        "predictionio_tpu/caller.py": _PIO206_CALLER,
        "predictionio_tpu/helper.py": _PIO206_HELPER,
    }
    found = _program_find(files)
    assert [f.code for f in found] == ["PIO206"]
    f = found[0]
    assert f.path == "predictionio_tpu/caller.py"
    assert "time.sleep" in f.message
    # the chain is shown to humans but is render-only detail: a refactor
    # that shortens the path must not invalidate the baseline key
    assert "slow_helper" in f.render() and "deeper" in f.render()
    assert "slow_helper" not in f.message
    # remove the lock: the same chain is harmless
    no_lock = dict(files)
    no_lock["predictionio_tpu/caller.py"] = _PIO206_CALLER.replace(
        "with self._lock:\n            slow_helper()",
        "slow_helper()",
    )
    assert _program_codes(no_lock) == []
    # the DIRECT blocking call under a lock stays PIO202's finding — no
    # PIO206 double report
    direct = {
        "predictionio_tpu/caller.py": """\
        import threading
        import time

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def go(self):
                with self._lock:
                    time.sleep(1.0)
        """,
    }
    assert _program_codes(direct) == ["PIO202"]


def test_pio206_suppression_and_baseline(tmp_path):
    files = {
        "predictionio_tpu/caller.py": _PIO206_CALLER.replace(
            "            slow_helper()",
            "            slow_helper()  # piolint: disable=PIO206",
        ),
        "predictionio_tpu/helper.py": _PIO206_HELPER,
    }
    assert _program_codes(files) == []
    # baseline flavor: the finding is absorbed, a second one is not
    found = _program_find(
        {
            "predictionio_tpu/caller.py": _PIO206_CALLER,
            "predictionio_tpu/helper.py": _PIO206_HELPER,
        }
    )
    path = str(tmp_path / "baseline.json")
    write_baseline(found, path)
    new, old = split_by_baseline(found, load_baseline(path))
    assert new == [] and len(old) == 1


_PIO207_M1 = """\
import threading
from predictionio_tpu.m2 import Other

class A:
    def __init__(self):
        self._a_lock = threading.Lock()
        self.other = Other()

    def one(self):
        with self._a_lock:
            self.other.poke()

    def fold_hot_rows(self):
        with self._a_lock:
            pass
"""

_PIO207_M2 = """\
import threading

class Other:
    def __init__(self, owner=None):
        self._b_lock = threading.Lock()
        self.owner = owner  # duck-typed hand-off, untyped on purpose

    def poke(self):
        with self._b_lock:
            pass

    def two(self):
        with self._b_lock:
            self.owner.fold_hot_rows()
"""


def test_pio210_interprocedural_lock_cycle():
    """A cycle that needs the callgraph to see (locks nested through
    CALLS, not lexically) is PIO210's finding, with full call-chain
    provenance in the rendered detail."""
    files = {
        "predictionio_tpu/m1.py": _PIO207_M1,
        "predictionio_tpu/m2.py": _PIO207_M2,
    }
    found = _program_find(files)
    assert [f.code for f in found] == ["PIO210"]
    f = found[0]
    assert "A._a_lock" in f.message
    assert "Other._b_lock" in f.message
    # the call chains are render-only provenance, never in the baseline
    # key: a refactor that re-routes the path must not churn the baseline
    assert "one" in f.render() and "poke" in f.render()
    assert "via" not in f.message
    # consistent order (break the back edge): no cycle
    consistent = dict(files)
    consistent["predictionio_tpu/m2.py"] = _PIO207_M2.replace(
        "        with self._b_lock:\n            self.owner.fold_hot_rows()",
        "        self.owner.fold_hot_rows()",
    )
    assert _program_codes(consistent) == []
    # a per-module LEXICAL cycle stays PIO203's finding, not PIO210's
    lexical = {
        "predictionio_tpu/solo.py": """\
        import threading

        class C:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()

            def one(self):
                with self._a_lock:
                    with self._b_lock:
                        pass

            def two(self):
                with self._b_lock:
                    with self._a_lock:
                        pass
        """,
    }
    assert _program_codes(lexical) == ["PIO203"]


_PIO207_LOCKS = """\
import threading

INGEST_LOCK = threading.Lock()
FLUSH_LOCK = threading.Lock()
"""

_PIO207_LEX1 = """\
from predictionio_tpu.locks import INGEST_LOCK, FLUSH_LOCK

def one():
    with INGEST_LOCK:
        with FLUSH_LOCK:
            pass
"""

_PIO207_LEX2 = """\
from predictionio_tpu.locks import INGEST_LOCK, FLUSH_LOCK

def two():
    with FLUSH_LOCK:
        with INGEST_LOCK:
            pass
"""


def test_pio207_lexical_cross_module_cycle():
    """PIO207 keeps the purely LEXICAL cross-module cycles: two modules
    visibly nest shared module-level locks in opposite orders — no
    callgraph needed, but no single module shows the inversion either
    (PIO203 is per-module and stays silent)."""
    files = {
        "predictionio_tpu/locks.py": _PIO207_LOCKS,
        "predictionio_tpu/lex1.py": _PIO207_LEX1,
        "predictionio_tpu/lex2.py": _PIO207_LEX2,
    }
    found = _program_find(files)
    assert [f.code for f in found] == ["PIO207"]
    assert "INGEST_LOCK" in found[0].message
    assert "FLUSH_LOCK" in found[0].message
    # consistent nesting across both modules: clean
    consistent = dict(files)
    consistent["predictionio_tpu/lex2.py"] = _PIO207_LEX2.replace(
        "    with FLUSH_LOCK:\n        with INGEST_LOCK:",
        "    with INGEST_LOCK:\n        with FLUSH_LOCK:",
    )
    assert _program_codes(consistent) == []


def test_pio207_pio210_suppression():
    files = {
        "predictionio_tpu/m1.py": _PIO207_M1 + "\n# piolint: disable-file=PIO210\n",
        "predictionio_tpu/m2.py": _PIO207_M2,
    }
    assert _program_codes(files) == []
    lex = {
        "predictionio_tpu/locks.py": _PIO207_LOCKS,
        "predictionio_tpu/lex1.py": _PIO207_LEX1,
        # the finding anchors at the edge that closes the cycle (lex2)
        "predictionio_tpu/lex2.py": (
            _PIO207_LEX2 + "\n# piolint: disable-file=PIO207\n"
        ),
    }
    assert _program_codes(lex) == []


def test_lock_order_cycles_structured_output():
    """`lock_order_cycles` (shared with `pio tsan`) returns the ring,
    the provenance edges, and the module span."""
    from predictionio_tpu.analysis.callgraph import (
        ProgramContext,
        build_callgraph,
    )
    from predictionio_tpu.analysis.engine import FileContext
    from predictionio_tpu.analysis.manifest import DEFAULT_MANIFEST
    from predictionio_tpu.analysis.rules_program import lock_order_cycles

    contexts = {
        p: FileContext(p, textwrap.dedent(s), DEFAULT_MANIFEST)
        for p, s in {
            "predictionio_tpu/m1.py": _PIO207_M1,
            "predictionio_tpu/m2.py": _PIO207_M2,
        }.items()
    }
    program = ProgramContext(contexts, build_callgraph(contexts))
    cycles = lock_order_cycles(program)
    assert len(cycles) == 1
    cyc = cycles[0]
    assert cyc["cycle"][0] == cyc["cycle"][-1]
    assert set(cyc["modules"]) == {
        "predictionio_tpu/m1.py", "predictionio_tpu/m2.py"
    }
    assert not cyc["lexical_only"]
    kinds = {e["kind"] for e in cyc["edges"]}
    assert "interproc" in kinds


def test_digraph_cycles_enumerates_sibling_cycles():
    """Regression: a node can sit on several elementary cycles
    (A->B->C->A and A->C->A share C). The old single-visited-set DFS
    dropped whichever ring was found second — for PIO207 that silently
    hid a real cross-module deadlock whenever a sibling ring was
    enumerated first."""
    from predictionio_tpu.analysis.callgraph import digraph_cycles

    cycles = digraph_cycles([("A", "B"), ("B", "C"), ("C", "A"), ("A", "C")])
    assert sorted(cycles) == [["A", "B", "C"], ["A", "C"]]
    # each ring canonical (smallest node leads) and enumerated once
    assert digraph_cycles([("A", "B"), ("B", "A")]) == [["A", "B"]]
    assert digraph_cycles([("A", "B"), ("B", "C")]) == []


def test_callgraph_resolution_is_file_order_independent():
    """Regression: class finalization (bases, attr types) must complete
    for EVERY file before any file's calls resolve. An alphabetically
    EARLIER file calling an inherited method of a class defined in a
    LATER file used to lose the call edge — and with it the PIO206
    finding — purely because of filename sort order."""
    caller = """\
    import threading
    from predictionio_tpu.z_mod import Svc

    class Driver:
        def __init__(self):
            self._lock = threading.Lock()
            self.svc = Svc()

        def go(self):
            with self._lock:
                self.svc.fold()
    """
    svc = """\
    import time

    class Base:
        def fold(self):
            time.sleep(1.0)

    class Svc(Base):
        pass
    """
    for caller_path in (
        "predictionio_tpu/a_mod.py",  # caller sorts BEFORE the class file
        "predictionio_tpu/zz_mod.py",  # and after
    ):
        codes = _program_codes(
            {caller_path: caller, "predictionio_tpu/z_mod.py": svc}
        )
        assert "PIO206" in codes, (caller_path, codes)


def test_pio206_through_recursive_call_cluster():
    """Regression: a blocking path that only exists THROUGH a recursive
    cluster (b -> a -> c -> time.sleep, with a -> b closing the loop)
    must still be found. The old memoized DFS cached `None` for `b`
    while `a` was on-stack, permanently hiding the convoy."""
    files = {
        "predictionio_tpu/helper.py": """\
        import time

        def a():
            b()
            c()

        def b():
            a()

        def c():
            time.sleep(1.0)
        """,
        "predictionio_tpu/z.py": """\
        import threading
        from predictionio_tpu.helper import b

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def go(self):
                with self._lock:
                    b()
        """,
    }
    found = _program_find(files)
    assert "PIO206" in [f.code for f in found]
    pio206 = [f for f in found if f.code == "PIO206"]
    assert pio206[0].path == "predictionio_tpu/z.py"
    assert "time.sleep" in pio206[0].message


_PIO208_DROP = """\
import urllib.request

def fetch(url, timeout):
    # the literal per-attempt timeout satisfies PIO401 — but the budget
    # the CALLER handed in never reaches the wire: that's PIO208
    return urllib.request.urlopen(url, timeout=30.0).read()
"""


def test_pio208_deadline_not_propagated():
    assert _program_codes({"predictionio_tpu/n.py": _PIO208_DROP}) == ["PIO208"]
    # forwarding through the argument (even via a derived local) is fine
    forwarded = """\
    import urllib.request

    def fetch(url, timeout):
        t = min(timeout, 5.0)
        return urllib.request.urlopen(url, timeout=t).read()
    """
    assert _program_codes({"predictionio_tpu/n.py": forwarded}) == []
    # a poll loop bounded by the budget enforces it around the call
    loop_bounded = """\
    import time
    import urllib.request

    def wait_ready(url, timeout_s):
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout_s:
            urllib.request.urlopen(url, timeout=1.0)
    """
    assert _program_codes({"predictionio_tpu/n.py": loop_bounded}) == []
    # ambient propagation via `with deadline_scope(deadline):`
    ambient = """\
    import urllib.request
    from predictionio_tpu.resilience import deadline_scope

    def fetch(url, deadline_s):
        with deadline_scope(deadline_s):
            return urllib.request.urlopen(url, timeout=1.0).read()
    """
    assert _program_codes({"predictionio_tpu/n.py": ambient}) == []
    # a function with no deadline-ish parameter is out of contract
    no_param = _PIO208_DROP.replace("def fetch(url, timeout):", "def fetch(url):")
    assert _program_codes({"predictionio_tpu/n.py": no_param}) == []


def test_pio208_internal_callee_with_deadline_param():
    """The internal half: calling a package function that itself accepts
    a deadline without passing any budget drops the caller's."""
    files = {
        "predictionio_tpu/svc.py": """\
        from predictionio_tpu.rpc import call_storage

        def handle(query, deadline_s):
            return call_storage(query)
        """,
        "predictionio_tpu/rpc.py": """\
        def call_storage(query, timeout=30.0):
            return query
        """,
    }
    found = _program_find(files)
    assert [f.code for f in found] == ["PIO208"]
    assert "call_storage" in found[0].message
    forwarded = dict(files)
    forwarded["predictionio_tpu/svc.py"] = files[
        "predictionio_tpu/svc.py"
    ].replace("call_storage(query)", "call_storage(query, timeout=deadline_s)")
    assert _program_codes(forwarded) == []


def test_pio208_suppression():
    suppressed = _PIO208_DROP.replace(
        "    return urllib.request.urlopen(url, timeout=30.0).read()",
        "    return urllib.request.urlopen(url, timeout=30.0).read()  "
        "# piolint: disable=PIO208",
    )
    assert _program_codes({"predictionio_tpu/n.py": suppressed}) == []


_PIO209_ESCAPE = """\
import threading

def worker(state):
    state._count += 1

class Owner:
    def __init__(self):
        self._lock = threading.Lock()
        self._count = 0

    def launch(self):
        t = threading.Thread(target=worker, args=(self,), daemon=True)
        t.start()
        return t
"""


def test_pio209_thread_escape():
    found = _program_find({"predictionio_tpu/w.py": _PIO209_ESCAPE})
    assert [f.code for f in found] == ["PIO209"]
    assert "state._count" in found[0].message
    assert "Owner" in found[0].message
    # the worker taking the owning lock is the sanctioned shape
    guarded = _PIO209_ESCAPE.replace(
        "def worker(state):\n    state._count += 1",
        "def worker(state):\n    with state._lock:\n        state._count += 1",
    )
    assert _program_codes({"predictionio_tpu/w.py": guarded}) == []
    # a lock-less class is out of contract (PIO201 parity)
    lockless = _PIO209_ESCAPE.replace(
        "        self._lock = threading.Lock()\n", ""
    )
    assert _program_codes({"predictionio_tpu/w.py": lockless}) == []
    # a bound-method target stays PIO201's territory — no double report
    bound = """\
    import threading

    class Owner:
        def __init__(self):
            self._lock = threading.Lock()
            self._count = 0
            t = threading.Thread(target=self._run, args=(1,), daemon=True)

        def _run(self, n):
            self._count += n
    """
    assert _program_codes({"predictionio_tpu/w.py": bound}) == ["PIO201"]


def test_pio209_suppression_and_baseline(tmp_path):
    suppressed = _PIO209_ESCAPE.replace(
        "    state._count += 1",
        "    state._count += 1  # piolint: disable=PIO209",
    )
    assert _program_codes({"predictionio_tpu/w.py": suppressed}) == []
    found = _program_find({"predictionio_tpu/w.py": _PIO209_ESCAPE})
    path = str(tmp_path / "baseline.json")
    write_baseline(found, path)
    new, old = split_by_baseline(found, load_baseline(path))
    assert new == [] and len(old) == 1


def test_callgraph_resolves_across_modules():
    """The resolution model the PIO206–209 rules stand on: imports,
    constructor-typed attributes, annotated parameters, and the
    unique-method fallback — and its guardrails (foreign constructors
    and ubiquitous names never resolve)."""
    from predictionio_tpu.analysis.callgraph import build_callgraph
    from predictionio_tpu.analysis.engine import FileContext
    from predictionio_tpu.analysis.manifest import DEFAULT_MANIFEST

    files = {
        "predictionio_tpu/m1.py": textwrap.dedent(_PIO207_M1),
        "predictionio_tpu/m2.py": textwrap.dedent(_PIO207_M2),
        "predictionio_tpu/m3.py": textwrap.dedent(
            """\
            import threading

            def free(x):
                return x

            class User:
                def __init__(self, helper):
                    self.helper = helper
                    self._thread = threading.Thread(target=free, daemon=True)

                def go(self):
                    free(1)
                    self._thread.join()  # foreign attr: must NOT resolve
            """
        ),
    }
    contexts = {
        p: FileContext(p, s, DEFAULT_MANIFEST) for p, s in files.items()
    }
    graph = build_callgraph(contexts)
    P = "predictionio_tpu"
    # function + class indexing under module-qualified names
    assert f"{P}.m1.A.one" in graph.functions
    assert f"{P}.m2.Other.poke" in graph.functions
    assert f"{P}.m1.A" in graph.classes
    # constructor-typed attribute: A.other -> Other
    assert graph.classes[f"{P}.m1.A"].attr_types["other"] == f"{P}.m2.Other"
    # lock declarations through the type index
    assert graph.class_locks(f"{P}.m1.A") == {"_a_lock"}
    # self.other.poke() resolved cross-module
    one_callees = {
        c for s in graph.functions[f"{P}.m1.A.one"].calls for c in s.callees
    }
    assert f"{P}.m2.Other.poke" in one_callees
    # unique-method fallback: self.owner.fold_hot_rows() with the owner
    # injected untyped
    two_callees = {
        c for s in graph.functions[f"{P}.m2.Other.two"].calls for c in s.callees
    }
    assert f"{P}.m1.A.fold_hot_rows" in two_callees
    # guardrails: threading.Thread attr is foreign; .join() resolves to
    # nothing in-package
    go_callees = {
        c for s in graph.functions[f"{P}.m3.User.go"].calls for c in s.callees
    }
    assert not any("join" in c for c in go_callees)
    assert f"{P}.m3.free" in go_callees


# ---------------------------------------------------------------------------
# Baseline pruning (pio lint --prune-baseline)
# ---------------------------------------------------------------------------


def test_prune_baseline_drops_stale_and_caps_counts(tmp_path):
    from predictionio_tpu.analysis.engine import prune_baseline

    live = _find("predictionio_tpu/x.py", _LOCKED_CLASS)
    assert len(live) == 1
    stale = Finding("PIO999", "predictionio_tpu/gone.py", 1, "fixed long ago")
    path = str(tmp_path / "baseline.json")
    write_baseline(live + [stale, stale], path)
    # both keys present: one live, one stale with count 2
    assert len(load_baseline(path)) == 2
    pruned = prune_baseline(live, path)
    assert pruned == 1
    kept = load_baseline(path)
    assert len(kept) == 1
    assert live[0].key() in kept
    # over-counted live entries are capped at the current occurrence count
    write_baseline(live + live, path)  # count 2 via duplicated finding
    data = json.loads(open(path).read())
    data["entries"][0]["count"] = 5
    open(path, "w").write(json.dumps({"version": 1, "entries": data["entries"]}))
    assert prune_baseline(live, path) == 1
    assert load_baseline(path)[live[0].key()]["count"] == 1
    # pruning an already-clean baseline is a no-op
    assert prune_baseline(live, path) == 0


def test_pio_lint_prune_baseline_cli(tmp_path):
    """`pio lint --prune-baseline` drops entries for fixed findings and
    the rerun stays green with a clean baseline file."""
    pkg = tmp_path / "predictionio_tpu" / "serving"
    pkg.mkdir(parents=True)
    bad = pkg / "bad.py"
    bad.write_text("import jax\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    def lint(*extra):
        return subprocess.run(
            [
                sys.executable, "-m", "predictionio_tpu.tools.console",
                "lint", "--root", str(tmp_path), *extra,
            ],
            capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
        )

    assert lint("--update-baseline").returncode == 0
    bad.write_text("import json\n")  # fix the finding -> stale entry
    proc = lint()
    assert proc.returncode == 0
    assert "stale" in proc.stdout
    proc = lint("--prune-baseline")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "pruned" in proc.stdout
    data = json.loads((tmp_path / "piolint-baseline.json").read_text())
    assert data["entries"] == []
    proc = lint()
    assert proc.returncode == 0
    assert "stale" not in proc.stdout


# ---------------------------------------------------------------------------
# PIO3xx JAX hygiene (scoped to ops/ and parallel/)
# ---------------------------------------------------------------------------

_JIT_ITEM = """\
import jax

@jax.jit
def f(x):
    return x.sum().item()
"""


def test_pio301_host_sync_in_jit():
    assert _codes("predictionio_tpu/ops/x.py", _JIT_ITEM) == ["PIO301"]
    # the same source outside the device packages is out of scope
    assert _codes("predictionio_tpu/api/x.py", _JIT_ITEM) == []
    # np.asarray through an alias, under functools.partial(jax.jit, ...)
    np_sync = """\
    import functools
    import jax
    import numpy as np

    @functools.partial(jax.jit, static_argnames=("n",))
    def f(x, n):
        return np.asarray(x)
    """
    found = _find("predictionio_tpu/ops/x.py", np_sync)
    assert [f.code for f in found] == ["PIO301"]
    assert "numpy.asarray" in found[0].message
    # float() of a traced parameter
    f_sync = """\
    import jax

    @jax.jit
    def f(x):
        return float(x)
    """
    assert _codes("predictionio_tpu/parallel/x.py", f_sync) == ["PIO301"]
    # float() of a non-parameter local is fine (python scalar math)
    f_ok = """\
    import jax

    @jax.jit
    def f(x):
        n = 3
        return x * float(n)
    """
    assert _codes("predictionio_tpu/ops/x.py", f_ok) == []


def test_pio302_jit_mutable_global():
    src = """\
    import jax

    _CACHE = {}

    @jax.jit
    def f(x):
        return x * len(_CACHE)
    """
    found = _find("predictionio_tpu/ops/x.py", src)
    assert [f.code for f in found] == ["PIO302"]
    assert "_CACHE" in found[0].message
    # an immutable mapping proxy (the als.py fix) does not fire
    frozen = src.replace(
        "_CACHE = {}", "_CACHE = types.MappingProxyType({})"
    ).replace("import jax", "import jax\n    import types")
    assert _codes("predictionio_tpu/ops/x.py", frozen) == []
    # file-level suppression flavor (directive can sit anywhere in file)
    suppressed = textwrap.dedent(src) + "# piolint: disable-file=PIO302\n"
    assert _codes("predictionio_tpu/ops/x.py", suppressed) == []
    # the `all` wildcard suppresses every code in the file
    wildcard = textwrap.dedent(src) + "# piolint: disable-file=all\n"
    assert _codes("predictionio_tpu/ops/x.py", wildcard) == []


def test_pio303_unhashable_static_args():
    src = """\
    import jax

    @jax.jit(static_argnums=[0, 1])
    def f(n, m, x):
        return x
    """
    assert _codes("predictionio_tpu/ops/x.py", src) == ["PIO303"]
    ok = src.replace("[0, 1]", "(0, 1)")
    assert _codes("predictionio_tpu/ops/x.py", ok) == []


def test_pio301_static_args_are_not_traced():
    """int()/float() on a ``static_argnames``/``static_argnums``
    parameter is plain Python shape math, never a host sync — the
    sharded kernels' ``int(k)`` idiom must not fire."""
    named = """\
    import functools
    import jax

    @functools.partial(jax.jit, static_argnames=("k", "mesh"))
    def f(x, k, mesh):
        return x[: int(k)]
    """
    assert _codes("predictionio_tpu/parallel/x.py", named) == []
    nums = """\
    import functools
    import jax

    @functools.partial(jax.jit, static_argnums=(1,))
    def f(x, k):
        return x[: int(k)]
    """
    assert _codes("predictionio_tpu/ops/x.py", nums) == []
    # a NON-static parameter still fires
    traced = named.replace('("k", "mesh")', '("mesh",)')
    assert _codes("predictionio_tpu/parallel/x.py", traced) == ["PIO301"]


def test_pio304_deprecated_shard_map():
    import_from = """\
    from jax.experimental.shard_map import shard_map

    def f(x):
        return shard_map(lambda y: y, mesh=None, in_specs=(), out_specs=())(x)
    """
    assert _codes("predictionio_tpu/ops/x.py", import_from) == ["PIO304"]
    found = _find("predictionio_tpu/parallel/x.py", import_from)
    assert [f.code for f in found] == ["PIO304"]
    assert "jax.shard_map" in found[0].message
    plain_import = "import jax.experimental.shard_map\n"
    assert _codes("predictionio_tpu/ops/x.py", plain_import) == ["PIO304"]
    # host-side packages are out of the jax-hygiene scope
    assert _codes("predictionio_tpu/workflow/x.py", import_from) == []
    # the top-level API is the sanctioned spelling
    ok = """\
    import jax

    def f(x):
        return jax.shard_map(
            lambda y: y, mesh=None, in_specs=(), out_specs=(), check_vma=False
        )(x)
    """
    assert _codes("predictionio_tpu/parallel/x.py", ok) == []
    # inline suppression works like every other rule
    suppressed = (
        "from jax.experimental.shard_map import shard_map"
        "  # piolint: disable=PIO304\n"
    )
    assert _codes("predictionio_tpu/ops/x.py", suppressed) == []


def test_pio305_raw_int8_quantization():
    astype_jnp = """\
    import jax.numpy as jnp

    def f(x):
        return x.astype(jnp.int8)
    """
    # one quantization rule, one module: every scoped package fires
    assert _codes("predictionio_tpu/ops/x.py", astype_jnp) == ["PIO305"]
    assert _codes("predictionio_tpu/parallel/x.py", astype_jnp) == ["PIO305"]
    assert _codes("predictionio_tpu/workflow/x.py", astype_jnp) == ["PIO305"]
    # string-dtype and keyword spellings are the same finding
    astype_str = """\
    def f(x):
        return x.astype("int8")
    """
    assert _codes("predictionio_tpu/ops/x.py", astype_str) == ["PIO305"]
    dtype_kw = """\
    import numpy as np

    def f(n):
        return np.zeros(n, dtype=np.int8)
    """
    found = _find("predictionio_tpu/workflow/x.py", dtype_kw)
    assert [f.code for f in found] == ["PIO305"]
    assert "ops.quant" in found[0].message
    # the quant module itself is the one legal home
    assert _codes("predictionio_tpu/ops/quant.py", astype_jnp) == []
    # host-side packages (templates, serving, ...) are out of scope
    assert _codes("predictionio_tpu/templates/x.py", astype_jnp) == []
    # reading int8 ARRAYS is fine — only constructing the dtype is the
    # contained act (gathers/astype-to-f32 appear all over the kernels)
    reads = """\
    import jax.numpy as jnp

    def f(codes, scales):
        return codes.astype(jnp.float32) * scales[..., None]
    """
    assert _codes("predictionio_tpu/ops/x.py", reads) == []
    suppressed = (
        "import numpy as np\n"
        "x = np.zeros(4, dtype=np.int8)  # piolint: disable=PIO305\n"
    )
    assert _codes("predictionio_tpu/ops/x.py", suppressed) == []


# ---------------------------------------------------------------------------
# PIO4xx server hygiene
# ---------------------------------------------------------------------------


def test_pio401_untimed_network_call():
    bad = """\
    import urllib.request
    def f(url):
        return urllib.request.urlopen(url).read()
    """
    assert _codes("predictionio_tpu/api/x.py", bad) == ["PIO401"]
    ok = bad.replace("urlopen(url)", "urlopen(url, timeout=5)")
    assert _codes("predictionio_tpu/api/x.py", ok) == []
    # resilience/ owns timeout policy — exempt
    assert _codes("predictionio_tpu/resilience/x.py", bad) == []


def test_pio402_bare_except():
    src = """\
    def handler():
        try:
            return 200
        except:
            return 500
    """
    assert _codes("predictionio_tpu/api/x.py", src) == ["PIO402"]
    ok = src.replace("except:", "except Exception:")
    assert _codes("predictionio_tpu/api/x.py", ok) == []


_FSYNCLESS = """\
import os

class Models:
    def insert(self, path, data):
        with open(path + ".tmp", "wb") as f:
            f.write(data)
        os.replace(path + ".tmp", path)
"""


def test_pio403_fsyncless_replace():
    # the exact pattern satellite 1 fixed in localfs.py
    assert _codes("predictionio_tpu/data/storage/x.py", _FSYNCLESS) == ["PIO403"]
    # outside data/storage/ the same pattern is PIO501's finding (the
    # crash-consistency family owns it there) — exactly one of the two
    # rules fires per site, never both
    assert _codes("predictionio_tpu/api/x.py", _FSYNCLESS) == ["PIO501"]
    # an os.fsync between write and replace satisfies PIO403, but the
    # crash-consistency layer still wants the parent-dir fsync after the
    # rename (PIO502) in durable-prefix code — the rules stack
    synced = _FSYNCLESS.replace(
        "            f.write(data)\n",
        "            f.write(data)\n            os.fsync(f.fileno())\n",
    )
    assert _codes("predictionio_tpu/data/storage/x.py", synced) == ["PIO502"]
    # the full protocol (file fsync + rename + dir fsync) is clean
    durable = synced.replace(
        "        os.replace(path + \".tmp\", path)\n",
        "        os.replace(path + \".tmp\", path)\n"
        "        dfd = os.open(os.path.dirname(path), os.O_RDONLY)\n"
        "        try:\n"
        "            os.fsync(dfd)\n"
        "        finally:\n"
        "            os.close(dfd)\n",
    )
    assert _codes("predictionio_tpu/data/storage/x.py", durable) == []
    # a class exposing an fsync toggle is exempt (operator's choice)
    toggled = _FSYNCLESS.replace(
        "class Models:\n",
        "class Models:\n    def __init__(self, fsync=True):\n"
        "        self._fsync = fsync\n",
    )
    assert _codes("predictionio_tpu/data/storage/x.py", toggled) == []
    # module-level functions (no class, no toggle possible) are checked
    flat = """\
    import os

    def save(path, data):
        with open(path + ".tmp", "wb") as f:
            f.write(data)
        os.replace(path + ".tmp", path)
    """
    assert _codes("predictionio_tpu/data/storage/x.py", flat) == ["PIO403"]
    # read-only open + replace (no write) is not the pattern
    readonly = flat.replace('"wb"', '"rb"').replace("f.write(data)", "f.read()")
    assert _codes("predictionio_tpu/data/storage/x.py", readonly) == []
    suppressed = _FSYNCLESS.replace(
        "        os.replace(path + \".tmp\", path)",
        "        os.replace(path + \".tmp\", path)  # piolint: disable=PIO403",
    )
    assert _codes("predictionio_tpu/data/storage/x.py", suppressed) == []


# ---------------------------------------------------------------------------
# Baseline mechanics
# ---------------------------------------------------------------------------


def test_baseline_excludes_exact_findings_but_not_new_ones(tmp_path):
    found = _find("predictionio_tpu/x.py", _LOCKED_CLASS)
    assert len(found) == 1
    path = str(tmp_path / "baseline.json")
    write_baseline(found, path)
    baseline = load_baseline(path)
    # identical finding: baselined, not new
    new, old = split_by_baseline(found, baseline)
    assert new == [] and len(old) == 1
    # a SECOND identical finding exceeds the entry's count -> new
    new, old = split_by_baseline(found + found, baseline)
    assert len(new) == 1 and len(old) == 1
    # entries carry a justification slot for review
    data = json.loads(open(path).read())
    assert data["entries"][0]["justification"]
    # a justification survives --update-baseline
    data["entries"][0]["justification"] = "accepted: fixture"
    open(path, "w").write(json.dumps(data))
    write_baseline(found, path)
    assert (
        json.loads(open(path).read())["entries"][0]["justification"]
        == "accepted: fixture"
    )


# ---------------------------------------------------------------------------
# CLI: pio lint exits nonzero on a seeded violation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", ["text", "json"])
def test_pio_lint_cli_exit_codes(tmp_path, fmt):
    pkg = tmp_path / "predictionio_tpu" / "serving"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text("import jax\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    def lint(*extra):
        return subprocess.run(
            [
                sys.executable, "-m", "predictionio_tpu.tools.console",
                "lint", "--root", str(tmp_path), "--format", fmt, *extra,
            ],
            capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
        )

    proc = lint()
    assert proc.returncode == 1, proc.stdout + proc.stderr
    if fmt == "json":
        rec = json.loads(proc.stdout)
        assert rec["ok"] is False
        assert rec["countsByCode"].get("PIO101") == 1
    else:
        assert "PIO101" in proc.stdout
    # --update-baseline accepts the finding; the re-run is green
    proc = lint("--update-baseline")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert (tmp_path / "piolint-baseline.json").exists()
    proc = lint()
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# Tier-1 gate: the real tree lints clean, fast, without importing it
# ---------------------------------------------------------------------------


def test_full_tree_lints_clean_and_fast():
    """The whole repo passes piolint — per-file rules AND the
    whole-program PIO206–209 pass over the cross-module call graph —
    with no non-baselined findings. AST-only by design: zero imports of
    the linted modules (no jax init, no storage, no servers), and the
    interprocedural full-tree run must stay inside the 30 s CI budget
    (ISSUE 8 acceptance)."""
    t0 = time.perf_counter()
    res = run_lint(root=REPO)
    elapsed = time.perf_counter() - t0
    assert res.files_scanned > 50
    assert res.ok, "new piolint findings:\n" + "\n".join(
        f.render() for f in res.new_findings
    )
    # the checked-in baseline must not carry entries for findings that
    # no longer fire (ISSUE 8 satellite): fix the debt, prune the entry
    # — `pio lint --prune-baseline` is the one-command cleanup
    assert res.stale_baseline == 0, (
        f"{res.stale_baseline} stale piolint-baseline.json entr(y/ies); "
        "run `pio lint --prune-baseline` and commit"
    )
    # the program pass really ran: the call graph covered the tree
    assert res.callgraph["functions"] > 500
    assert res.callgraph["classes"] > 100
    assert res.callgraph["callEdges"] > 500
    assert res.callgraph["lockSites"] > 50
    assert elapsed < 30.0, (
        f"full-tree interprocedural lint took {elapsed:.1f}s (budget 30s)"
    )


def test_deleting_batcher_lock_guard_is_caught():
    """Acceptance criterion (ISSUE 3): removing any `with self._lock`
    write guard in serving/batcher.py must fail the lint. Simulated by
    dedenting each guarded write out of its with-block and linting the
    mutated source under the real path (so the real baseline applies)."""
    path = os.path.join(REPO, "predictionio_tpu", "serving", "batcher.py")
    src = open(path).read()
    assert "with self._lock:" in src, (
        "batcher.py no longer has a lock-guarded write — this guard and "
        "the PIO201 acceptance criterion need updating together"
    )
    mutations = 0
    pos = 0
    while True:
        i = src.find("with self._lock:", pos)
        if i == -1:
            break
        # drop the `with` line and dedent its body by one level — the
        # textual shape of "someone deleted the lock"
        line_start = src.rfind("\n", 0, i) + 1
        indent = src[line_start:i]
        line_end = src.find("\n", i) + 1
        body_end = line_end
        while body_end < len(src):
            nl = src.find("\n", body_end)
            nl = len(src) if nl == -1 else nl + 1
            line = src[body_end:nl]
            if line.strip() and not line.startswith(indent + "    "):
                break
            body_end = nl
        body = src[line_end:body_end].replace("\n" + indent + "    ", "\n" + indent)
        body = body[4:] if body.startswith(indent + "    ") else body
        mutated = src[:line_start] + body + src[body_end:]
        found, _ = lint_file("predictionio_tpu/serving/batcher.py", mutated)
        assert any(f.code == "PIO201" for f in found), (
            f"deleting the with-lock at offset {i} went undetected"
        )
        # and the real baseline must not mask it
        baseline = load_baseline(os.path.join(REPO, "piolint-baseline.json"))
        new, _old = split_by_baseline(found, baseline)
        assert any(f.code == "PIO201" for f in new)
        mutations += 1
        pos = i + 1
    assert mutations >= 1


def test_analysis_package_is_stdlib_only():
    """The linter must never import what it lints: every import in
    predictionio_tpu/analysis/ is stdlib or intra-package. Asserted via
    the engine's own import resolution (dogfooding PIO102), plus a
    belt-and-braces check that importing the package leaves jax and
    numpy unimported in a fresh interpreter."""
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "import sys; import predictionio_tpu.analysis; "
            "import predictionio_tpu.analysis.callgraph; "
            "import predictionio_tpu.analysis.rules_program; "
            "import predictionio_tpu.analysis.rules_compile; "
            "import predictionio_tpu.analysis.rules_durability; "
            "import predictionio_tpu.analysis.witness; "
            "import predictionio_tpu.analysis.jit_witness; "
            "import predictionio_tpu.analysis.lock_witness; "
            "bad = [m for m in ('jax', 'numpy') if m in sys.modules]; "
            "sys.exit(1 if bad else 0)",
        ],
        capture_output=True,
        text=True,
        timeout=60,
        cwd=REPO,
        env={**os.environ, "PYTHONPATH": REPO},
    )
    assert proc.returncode == 0, (
        "importing predictionio_tpu.analysis pulled in jax/numpy:\n"
        + proc.stderr
    )


# ---------------------------------------------------------------------------
# PIO306–PIO308: whole-program compile/transfer rules (ISSUE 14)
# ---------------------------------------------------------------------------

_PIO306_KERNEL = """\
import functools

import jax


@functools.partial(jax.jit, static_argnames=("k",))
def scored_topk(scores, k):
    return jax.lax.top_k(scores, k)


@jax.jit
def dense_score(x):
    return x * 2
"""

_PIO306_SERVICE = """\
import numpy as np

from predictionio_tpu.kernels import scored_topk


class Service:
    def handle_query(self, body):
        k = int(body["num"])
        return scored_topk(np.zeros((4, 8), np.float32), k)
"""


def test_pio306_unbounded_static_arg():
    files = {
        "predictionio_tpu/kernels.py": _PIO306_KERNEL,
        "predictionio_tpu/svc.py": _PIO306_SERVICE,
    }
    found = [f for f in _program_find(files) if f.code == "PIO306"]
    assert len(found) == 1
    f = found[0]
    assert f.path == "predictionio_tpu/svc.py"
    assert "static arg 'k'" in f.message
    assert "pow2-bucket" in f.message
    # the chain is render-only detail, like PIO206's
    assert f.detail.startswith("via ")
    assert "handle_query" in f.render()


def test_pio306_bucket_step_bounds_the_flow():
    bucketed = _PIO306_SERVICE.replace(
        "        return scored_topk(np.zeros((4, 8), np.float32), k)",
        "        kb = max(16, 1 << (k - 1).bit_length())\n"
        "        return scored_topk(np.zeros((4, 8), np.float32), kb)",
    )
    files = {
        "predictionio_tpu/kernels.py": _PIO306_KERNEL,
        "predictionio_tpu/svc.py": bucketed,
    }
    assert [c for c in _program_codes(files) if c == "PIO306"] == []
    # a helper whose NAME says bucket is recognized too (declarative)
    named = _PIO306_SERVICE.replace(
        "        return scored_topk(np.zeros((4, 8), np.float32), k)",
        "        kb = _bucket_for(k)\n"
        "        return scored_topk(np.zeros((4, 8), np.float32), kb)",
    )
    files["predictionio_tpu/svc.py"] = named
    assert [c for c in _program_codes(files) if c == "PIO306"] == []


def test_pio306_config_values_are_not_request_derived():
    """Values read from self/config attributes are deployment-bounded;
    only the request roots' parameters seed the taint."""
    svc = """\
    import numpy as np

    from predictionio_tpu.kernels import scored_topk


    class Service:
        def handle_query(self, body):
            return scored_topk(np.zeros((4, 8), np.float32), self.k)
    """
    files = {
        "predictionio_tpu/kernels.py": _PIO306_KERNEL,
        "predictionio_tpu/svc.py": svc,
    }
    assert [c for c in _program_codes(files) if c == "PIO306"] == []


def test_pio306_request_derived_shape():
    """The SHAPE half: an array whose extent tracks request cardinality
    (``np.zeros((n, 8))`` with ``n = len(bodies)``) retraces the jitted
    consumer per distinct extent."""
    svc = """\
    import numpy as np

    from predictionio_tpu.kernels import dense_score


    class Service:
        def handle_batch(self, bodies):
            n = len(bodies)
            x = np.zeros((n, 8), np.float32)
            return dense_score(x)
    """
    files = {
        "predictionio_tpu/kernels.py": _PIO306_KERNEL,
        "predictionio_tpu/svc.py": svc,
    }
    found = [f for f in _program_find(files) if f.code == "PIO306"]
    assert len(found) == 1
    assert "SHAPE" in found[0].message
    # padding the extent to a bucket bounds it
    bucketed = svc.replace(
        "n = len(bodies)", "n = max(16, 1 << (len(bodies) - 1).bit_length())"
    )
    files["predictionio_tpu/svc.py"] = bucketed
    assert [c for c in _program_codes(files) if c == "PIO306"] == []


def test_pio306_suppression_and_baseline(tmp_path):
    suppressed = _PIO306_SERVICE.replace(
        "        return scored_topk(np.zeros((4, 8), np.float32), k)",
        "        return scored_topk(np.zeros((4, 8), np.float32), k)"
        "  # piolint: disable=PIO306",
    )
    files = {
        "predictionio_tpu/kernels.py": _PIO306_KERNEL,
        "predictionio_tpu/svc.py": suppressed,
    }
    assert [c for c in _program_codes(files) if c == "PIO306"] == []
    found = _program_find(
        {
            "predictionio_tpu/kernels.py": _PIO306_KERNEL,
            "predictionio_tpu/svc.py": _PIO306_SERVICE,
        }
    )
    path = str(tmp_path / "baseline.json")
    write_baseline(found, path)
    new, old = split_by_baseline(found, load_baseline(path))
    assert new == [] and any(f.code == "PIO306" for f in old)


_PIO307_FETCH = """\
import numpy as np


def fetch_rows(table, idx):
    return np.asarray(table)[idx]
"""

_PIO307_ALGO = """\
from predictionio_tpu.ops.fetch import fetch_rows


class Algo:
    def predict(self, model, query):
        return fetch_rows(model, [1])
"""


def test_pio307_transfer_on_serving_path():
    files = {
        "predictionio_tpu/ops/fetch.py": _PIO307_FETCH,
        "predictionio_tpu/algo.py": _PIO307_ALGO,
    }
    found = [f for f in _program_find(files) if f.code == "PIO307"]
    assert len(found) == 1
    f = found[0]
    assert f.path == "predictionio_tpu/ops/fetch.py"
    assert "numpy.asarray" in f.message
    assert "predict" in f.render()  # the chain, render-only
    # same module NOT reachable from a request root: out of scope
    unreached = {
        "predictionio_tpu/ops/fetch.py": _PIO307_FETCH,
        "predictionio_tpu/algo.py": _PIO307_ALGO.replace(
            "def predict", "def train"
        ),
    }
    assert [c for c in _program_codes(unreached) if c == "PIO307"] == []
    # outside the device-facing scope dirs numpy IS the host path
    hostside = {
        "predictionio_tpu/data/fetch.py": _PIO307_FETCH,
        "predictionio_tpu/algo.py": _PIO307_ALGO.replace(
            "predictionio_tpu.ops.fetch", "predictionio_tpu.data.fetch"
        ),
    }
    assert [c for c in _program_codes(hostside) if c == "PIO307"] == []


def test_pio307_allow_list_and_jitted_bodies():
    # the device_state pin/swap module is the sanctioned boundary
    files = {
        "predictionio_tpu/workflow/device_state.py": _PIO307_FETCH,
        "predictionio_tpu/algo.py": _PIO307_ALGO.replace(
            "predictionio_tpu.ops.fetch", "predictionio_tpu.workflow.device_state"
        ),
    }
    assert [c for c in _program_codes(files) if c == "PIO307"] == []
    # a jit-decorated function's body is PIO301's scope, not PIO307's
    jitted = """\
    import jax
    import numpy as np


    @jax.jit
    def fetch_rows(table, idx):
        return np.asarray(table)[idx]
    """
    files = {
        "predictionio_tpu/ops/fetch.py": jitted,
        "predictionio_tpu/algo.py": _PIO307_ALGO,
    }
    codes = _program_codes(files)
    assert "PIO307" not in codes
    assert "PIO301" in codes  # the per-file rule owns it


def test_pio307_suppression_and_baseline(tmp_path):
    suppressed = _PIO307_FETCH.replace(
        "    return np.asarray(table)[idx]",
        "    return np.asarray(table)[idx]  # piolint: disable=PIO307",
    )
    files = {
        "predictionio_tpu/ops/fetch.py": suppressed,
        "predictionio_tpu/algo.py": _PIO307_ALGO,
    }
    assert [c for c in _program_codes(files) if c == "PIO307"] == []
    found = _program_find(
        {
            "predictionio_tpu/ops/fetch.py": _PIO307_FETCH,
            "predictionio_tpu/algo.py": _PIO307_ALGO,
        }
    )
    path = str(tmp_path / "baseline.json")
    write_baseline(found, path)
    new, old = split_by_baseline(found, load_baseline(path))
    assert new == [] and any(f.code == "PIO307" for f in old)


_PIO308_SVC = """\
import jax


class Svc:
    def handle_query(self, body):
        f = jax.jit(lambda x: x * 2)
        return f(body["x"])
"""


def test_pio308_jit_constructed_per_call():
    found = [
        f
        for f in _program_find({"predictionio_tpu/svc.py": _PIO308_SVC})
        if f.code == "PIO308"
    ]
    assert len(found) == 1
    assert "empty compile cache" in found[0].message
    # a nested jit-DECORATED def re-evaluates per call too
    nested = """\
    import jax


    class Svc:
        def handle_query(self, body):
            @jax.jit
            def f(x):
                return x * 2
            return f(body["x"])
    """
    codes = _program_codes({"predictionio_tpu/svc.py": nested})
    assert "PIO308" in codes
    # an UNREACHABLE function may construct freely (one-shot tooling)
    offline = _PIO308_SVC.replace("handle_query", "export_model")
    assert "PIO308" not in _program_codes(
        {"predictionio_tpu/svc.py": offline}
    )


def test_pio308_sanctioned_cache_shapes():
    # the cached-per-key slot idiom (device_state._sharded_set_rows)
    slot = """\
    import jax

    _CACHE = {}


    def handle_query(body):
        key = body["k"]
        fn = _CACHE.get(key)
        if fn is None:
            fn = jax.jit(lambda x: x)
            _CACHE[key] = fn
        return fn(1)
    """
    assert "PIO308" not in _program_codes({"predictionio_tpu/svc.py": slot})
    # direct subscript store
    direct = """\
    import jax

    _CACHE = {}


    def handle_query(body):
        _CACHE[body["k"]] = jax.jit(lambda x: x)
        return _CACHE[body["k"]](1)
    """
    assert "PIO308" not in _program_codes({"predictionio_tpu/svc.py": direct})
    # an lru_cache factory memoizes the construction per key
    factory = """\
    import functools

    import jax


    @functools.lru_cache
    def compiled(k):
        return jax.jit(lambda x: x[:k])


    def handle_query(body):
        return compiled(body["n"])(body["x"])
    """
    assert "PIO308" not in _program_codes(
        {"predictionio_tpu/svc.py": factory}
    )


def test_pio308_suppression_and_baseline(tmp_path):
    suppressed = _PIO308_SVC.replace(
        "        f = jax.jit(lambda x: x * 2)",
        "        f = jax.jit(lambda x: x * 2)  # piolint: disable=PIO308",
    )
    assert "PIO308" not in _program_codes(
        {"predictionio_tpu/svc.py": suppressed}
    )
    found = _program_find({"predictionio_tpu/svc.py": _PIO308_SVC})
    path = str(tmp_path / "baseline.json")
    write_baseline(found, path)
    new, old = split_by_baseline(found, load_baseline(path))
    assert new == [] and any(f.code == "PIO308" for f in old)


def test_pio301_scope_covers_device_state_and_serving():
    """ISSUE 14 satellite: PIO301's scope grew to the jit-adjacent
    layers — workflow/device_state.py and serving/ — beside ops/ and
    parallel/."""
    src = """\
    import jax

    @jax.jit
    def f(x):
        return x.item()
    """
    assert _codes("predictionio_tpu/workflow/device_state.py", src) == [
        "PIO301"
    ]
    # serving/ is jax-free by manifest, so the same fixture ALSO fires
    # PIO101 — the scope extension is what adds the PIO301 beside it
    assert "PIO301" in _codes("predictionio_tpu/serving/helper.py", src)
    # the rest of workflow/ stays out of scope
    assert _codes("predictionio_tpu/workflow/core.py", src) == []


def test_deleting_a_pow2_bucket_step_is_caught():
    """Acceptance criterion (ISSUE 14): removing a pow2-bucketing step
    on a real serving path must fail `pio lint`. Simulated on the REAL
    sources of the three static-visible bucket sites; the fold-in width
    bucket (whose taint flows through state-dict mutation the AST
    analysis cannot see) is covered by the jit-witness compile-count
    regression tests instead (tests/test_jit_witness.py)."""
    from predictionio_tpu.analysis.engine import iter_tree_files, lint_sources

    files = {}
    for abs_path, rel in iter_tree_files(REPO):
        with open(abs_path, encoding="utf-8", errors="replace") as fh:
            files[rel.replace(os.sep, "/")] = fh.read()
    mutations = [
        (
            "predictionio_tpu/ops/ivf.py",
            "kb = bucket_k(k, index.num_items)",
            "kb = k",
        ),
        (
            "predictionio_tpu/templates/serving_util.py",
            "k_max = bucket_k(max(k for _, _, k in valid), n_items)",
            "k_max = min(n_items, max(k for _, _, k in valid))",
        ),
        (
            "predictionio_tpu/templates/retrieval.py",
            "kb = bucket_k(k, int(item_mat.shape[0]))",
            "kb = k",
        ),
    ]
    baseline = load_baseline(os.path.join(REPO, "piolint-baseline.json"))
    for path, bucket, raw in mutations:
        assert bucket in files[path], (
            f"{path} no longer holds its pow2-bucket step — update this "
            "guard and the PIO306 acceptance together"
        )
        mutated = dict(files)
        mutated[path] = files[path].replace(bucket, raw)
        found, _sup, _stats, _cycles = lint_sources(mutated)
        hits = [f for f in found if f.code == "PIO306"]
        assert hits, f"deleting the bucket step in {path} went undetected"
        new, _old = split_by_baseline(found, baseline)
        assert any(f.code == "PIO306" for f in new), (
            f"the real baseline masked the {path} bucket deletion"
        )


def test_sarif_output_schema():
    """`pio lint --format sarif` (ISSUE 14 satellite): a SARIF 2.1.0
    document whose results carry ruleId/level/message/location, with
    every ruleId declared in the driver's rule table — the shape
    code-review tooling needs for inline annotations."""
    from predictionio_tpu.analysis.engine import LintResult

    res = run_lint(root=REPO)
    doc = res.to_sarif()
    assert doc["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in doc["$schema"]
    run = doc["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "piolint"
    rule_ids = {r["id"] for r in driver["rules"]}
    assert {"PIO306", "PIO307", "PIO308"} <= rule_ids
    for r in driver["rules"]:
        assert r["shortDescription"]["text"]
    assert run["originalUriBaseIds"]["SRCROOT"]["uri"].startswith("file://")
    # a seeded violation produces a level=error result at the right spot
    seeded = LintResult(
        root=REPO,
        files_scanned=1,
        new_findings=[
            Finding("PIO306", "predictionio_tpu/x.py", 7, "msg", "via a -> b")
        ],
        baselined=[
            Finding("PIO201", "predictionio_tpu/y.py", 3, "old debt")
        ],
        suppressed_count=0,
        stale_baseline=0,
    )
    doc = seeded.to_sarif()
    results = doc["runs"][0]["results"]
    assert len(results) == 2
    err = results[0]
    assert err["ruleId"] == "PIO306" and err["level"] == "error"
    loc = err["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "predictionio_tpu/x.py"
    assert loc["artifactLocation"]["uriBaseId"] == "SRCROOT"
    assert loc["region"]["startLine"] == 7
    assert "via a -> b" in err["message"]["text"]
    note = results[1]
    assert note["ruleId"] == "PIO201" and note["level"] == "note"
    # the document is genuinely serializable (what --format sarif prints)
    json.dumps(doc)


def test_pio_lint_sarif_cli(tmp_path):
    pkg = tmp_path / "predictionio_tpu" / "serving"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text("import jax\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [
            sys.executable, "-m", "predictionio_tpu.tools.console",
            "lint", "--root", str(tmp_path), "--format", "sarif",
        ],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["version"] == "2.1.0"
    results = doc["runs"][0]["results"]
    assert any(
        r["ruleId"] == "PIO101" and r["level"] == "error" for r in results
    )


# ---------------------------------------------------------------------------
# PIO211 + PIO5xx seeded-bug fixtures, waiver pragmas, callgraph edge
# cases (ISSUE 18)
# ---------------------------------------------------------------------------

_PIO211_COORD = """\
import threading

from predictionio_tpu.sink import persist_state

class Coordinator:
    def __init__(self):
        self._lock = threading.Lock()

    def tick(self, path, payload):
        with self._lock:
            persist_state(path, payload)
"""

_PIO211_SINK = """\
import os

def persist_state(path, payload):
    with open(path + ".tmp", "w") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(path + ".tmp", path)
"""


def test_pio211_durable_syscall_under_foreign_lock():
    """Seeded true positive: a lock owned by one class reaches a
    durable syscall (os.fsync) performed by a function that does NOT
    own the lock — every contender convoys on a foreign disk flush."""
    found = _program_find({
        "predictionio_tpu/coord.py": _PIO211_COORD,
        "predictionio_tpu/sink.py": _PIO211_SINK,
    })
    assert [f.code for f in found] == ["PIO211"]
    f = found[0]
    # anchors at the call site inside the lock region, not at the fsync
    assert f.path == "predictionio_tpu/coord.py"
    assert "Coordinator._lock" in f.message
    assert "os.fsync" in f.message
    # call-chain provenance rides in the render, never the baseline key
    assert "via" in f.render() and "via" not in f.message
    # the lock's own class flushing its own state is the protocol
    # working as designed, not a foreign-flush convoy
    own = {
        "predictionio_tpu/own.py": """\
        import os
        import threading

        class Registry:
            def __init__(self):
                self._lock = threading.Lock()

            def publish(self, path, data):
                with self._lock:
                    with open(path + ".tmp", "w") as f:
                        f.write(data)
                        f.flush()
                        os.fsync(f.fileno())
                    os.replace(path + ".tmp", path)
        """,
    }
    assert _program_codes(own) == []
    # and without the lock held there is nothing to convoy on
    unlocked = {
        "predictionio_tpu/coord.py": _PIO211_COORD.replace(
            "        with self._lock:\n            persist_state",
            "        persist_state",
        ),
        "predictionio_tpu/sink.py": _PIO211_SINK,
    }
    assert _program_codes(unlocked) == []


def test_waiver_pragma_inline_and_preceding_line():
    """`# piolint: waive=CODE -- reason` suppresses on the finding's
    own line AND on a comment-only line directly above (for call sites
    too long to carry an inline pragma)."""
    inline = {
        "predictionio_tpu/coord.py": _PIO211_COORD.replace(
            "            persist_state(path, payload)",
            "            persist_state(path, payload)  "
            "# piolint: waive=PIO211 -- reviewed: cold path",
        ),
        "predictionio_tpu/sink.py": _PIO211_SINK,
    }
    assert _program_codes(inline) == []
    above = {
        "predictionio_tpu/coord.py": _PIO211_COORD.replace(
            "            persist_state(path, payload)",
            "            # piolint: waive=PIO211 -- reviewed: cold path\n"
            "            persist_state(path, payload)",
        ),
        "predictionio_tpu/sink.py": _PIO211_SINK,
    }
    assert _program_codes(above) == []


def test_waiver_without_reason_fires_pio001_and_original():
    """A reasonless waiver is not a waiver: the engine flags the pragma
    (PIO001) and the waived code still fires — the ratchet only moves
    down when someone writes down WHY."""
    files = {
        "predictionio_tpu/coord.py": _PIO211_COORD.replace(
            "            persist_state(path, payload)",
            "            persist_state(path, payload)  "
            "# piolint: waive=PIO211",
        ),
        "predictionio_tpu/sink.py": _PIO211_SINK,
    }
    codes = _program_codes(files)
    assert "PIO001" in codes and "PIO211" in codes


_PIO501_FLEET = """\
import os

def save(path, data):
    with open(path + ".tmp", "w") as f:
        f.write(data)
    os.replace(path + ".tmp", path)
"""


def test_pio501_pio502_protocol_ladder():
    """Seeded true positives: each missing protocol step draws exactly
    the rule that names it, and the full write->flush->fsync->rename->
    dir-fsync ladder is clean."""
    # no fsync at all: the rename publishes torn data (PIO501)
    assert _codes("predictionio_tpu/fleet/x.py", _PIO501_FLEET) == ["PIO501"]
    # file fsync'd but the directory entry is not (PIO502)
    synced = _PIO501_FLEET.replace(
        "        f.write(data)\n",
        "        f.write(data)\n        os.fsync(f.fileno())\n",
    )
    assert _codes("predictionio_tpu/fleet/x.py", synced) == ["PIO502"]
    # full protocol: clean
    durable = synced.replace(
        "    os.replace(path + \".tmp\", path)\n",
        "    os.replace(path + \".tmp\", path)\n"
        "    dfd = os.open(os.path.dirname(path), os.O_RDONLY)\n"
        "    try:\n"
        "        os.fsync(dfd)\n"
        "    finally:\n"
        "        os.close(dfd)\n",
    )
    assert _codes("predictionio_tpu/fleet/x.py", durable) == []
    # PIO502 is durable-roots-only: outside them the dir entry is
    # best-effort by design
    assert _codes("predictionio_tpu/api/x.py", synced) == []
    # rename of a file this function never wrote (claim/mv): not a
    # publish, no finding
    mv = """\
    import os

    def claim(src, dst):
        os.replace(src, dst)
    """
    assert _codes("predictionio_tpu/fleet/x.py", mv) == []


_PIO503_MODULE = """\
import os

def publish(state_path, data):
    tmp = state_path + ".tmp"
    with open(tmp, "w") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, state_path)
    dfd = os.open(os.path.dirname(state_path), os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)

def note(log_path, line):
    with open(log_path, "w") as f:
        f.write(line)
"""


def test_pio503_direct_write_in_protocol_module():
    """Seeded true positive: a module that publishes via temp+rename
    elsewhere writes some OTHER final path in place — readers (and
    crashes) observe the half-written file."""
    found = [
        (c, l) for c, l in
        ((f.code, f.line) for f in lint_sources(
            {"predictionio_tpu/fleet/x.py": _PIO503_MODULE})[0])
    ]
    assert [c for c, _l in found] == ["PIO503"]
    # append mode never truncates published bytes: exempt
    appender = _PIO503_MODULE.replace(
        'open(log_path, "w")', 'open(log_path, "a")'
    )
    assert _codes("predictionio_tpu/fleet/x.py", appender) == []
    # no protocol intent anywhere in the module: no finding
    no_protocol = """\
    def note(log_path, line):
        with open(log_path, "w") as f:
            f.write(line)
    """
    assert _codes("predictionio_tpu/fleet/x.py", no_protocol) == []
    # outside the durable roots the rule stays silent
    assert _codes("predictionio_tpu/api/x.py", _PIO503_MODULE) == []


def test_pio504_truncate_live_file():
    """Seeded true positive: open(p, 'w') on a path that is elsewhere
    the DESTINATION of an atomic rename — the published file is being
    emptied in place. (PIO503 stacks: a truncate of a live path is also
    a direct final-path write; both name the same line.)"""
    src = _PIO503_MODULE.replace(
        "def note(log_path, line):\n"
        "    with open(log_path, \"w\") as f:\n",
        "def reset(state_path, line):\n"
        "    with open(state_path, \"w\") as f:\n",
    )
    found = lint_sources({"predictionio_tpu/fleet/x.py": src})[0]
    assert sorted({f.code for f in found}) == ["PIO503", "PIO504"]
    assert len({f.line for f in found}) == 1
    # writing a tmp-named sibling of the live path is the protocol's
    # own first half, never a truncate-live finding
    tmpwrite = _PIO503_MODULE.replace(
        'open(log_path, "w")', 'open(state_path + ".tmp", "w")'
    )
    assert "PIO504" not in _codes("predictionio_tpu/fleet/x.py", tmpwrite)


_PIO505_QUORUM = """\
import os

class Replicated:
    def _quorum_ack(self, data):
        acked = 1
        for store in self.replicas:
            store.mirror_rows(data)
            acked += 1
        return acked
"""


def test_pio505_quorum_ack_before_fsync():
    """ISSUE 20: a quorum ack that counts a replica without an fsync
    between the mirror and the return is acking page-cache bytes — a
    replica crash silently un-acks an acknowledged write."""
    assert _codes(
        "predictionio_tpu/data/storage/x.py", _PIO505_QUORUM
    ) == ["PIO505"]
    # an fsync between the mirror and the return satisfies the contract
    good = _PIO505_QUORUM.replace(
        "            store.mirror_rows(data)\n",
        "            store.mirror_rows(data)\n"
        "            os.fsync(store.fd)\n",
    )
    assert _codes("predictionio_tpu/data/storage/x.py", good) == []
    # a helper-mediated fsync counts (same convention as PIO501): the
    # real replication module's barrier is self._fsync_stream_replica
    helper = _PIO505_QUORUM.replace(
        "            store.mirror_rows(data)\n",
        "            store.mirror_rows(data)\n"
        "            self._fsync_stream_replica(store)\n",
    )
    assert _codes("predictionio_tpu/data/storage/x.py", helper) == []
    # scoped to the storage surface: quorum-ish names elsewhere (the
    # chaos harness's acked-id accounting, say) are not protocol code
    assert _codes("predictionio_tpu/api/x.py", _PIO505_QUORUM) == []


def test_pio505_name_matching_is_word_exact():
    # rollback/fallback/pack contain 'ack' as a substring, not a word
    # part — a substring match would flag every rollback helper in the
    # storage package
    for name in ("_rollback", "fallback_insert", "pack_rows"):
        src = _PIO505_QUORUM.replace("_quorum_ack", name)
        assert _codes("predictionio_tpu/data/storage/x.py", src) == [], name
    # a return BEFORE any mirror acknowledges nothing; a return after a
    # mirror-then-fsync is the protocol working
    early = """\
import os

class Replicated:
    def _quorum_ack(self, data):
        if not self.replicas:
            return 0
        self.leader.append_rows(data)
        os.fsync(self.leader.fd)
        return 1
"""
    assert _codes("predictionio_tpu/data/storage/x.py", early) == []


def test_pio505_real_replication_module_is_clean():
    """The shipped quorum barrier must satisfy its own rule (mirror →
    _fsync_stream_replica → ack count) with no waiver."""
    path = os.path.join(
        REPO, "predictionio_tpu", "data", "storage", "replication.py"
    )
    with open(path) as f:
        src = f.read()
    found, _ = lint_file("predictionio_tpu/data/storage/replication.py", src)
    assert [f.code for f in found if f.code == "PIO505"] == []
    assert "waive=PIO505" not in src


# ---------------------------------------------------------------------------
# callgraph edge cases: decorators, closures, inheritance, aliases,
# factory attrs, may-call fan-out (ISSUE 18)
# ---------------------------------------------------------------------------


def _graph(files):
    from predictionio_tpu.analysis.callgraph import build_callgraph
    from predictionio_tpu.analysis.engine import FileContext
    from predictionio_tpu.analysis.manifest import DEFAULT_MANIFEST

    contexts = {
        p: FileContext(p, textwrap.dedent(s), DEFAULT_MANIFEST)
        for p, s in files.items()
    }
    return build_callgraph(contexts)


def _edges(graph):
    out = set()
    for qname, fi in graph.functions.items():
        for cs in fi.calls:
            for callee in cs.callees:
                out.add((qname, callee))
    return out


def test_callgraph_decorated_functions():
    """Decorators (bare, parameterized, staticmethod, property) leave
    the decorated function resolvable by its plain qname."""
    g = _graph({"predictionio_tpu/deco.py": """\
    import functools

    def wrap(fn):
        return fn

    @wrap
    def helper():
        pass

    @functools.lru_cache(maxsize=8)
    def cached():
        helper()

    class C:
        @staticmethod
        def s():
            cached()

        @property
        def p(self):
            return helper()
    """})
    edges = _edges(g)
    assert ("predictionio_tpu.deco.cached",
            "predictionio_tpu.deco.helper") in edges
    assert ("predictionio_tpu.deco.C.s",
            "predictionio_tpu.deco.cached") in edges
    assert ("predictionio_tpu.deco.C.p",
            "predictionio_tpu.deco.helper") in edges


def test_callgraph_nested_closures_flatten_into_encloser():
    """A closure's calls belong to the enclosing function — a lock held
    by the outer function therefore covers what the inner one calls,
    which is exactly how the runtime behaves."""
    g = _graph({"predictionio_tpu/clo.py": """\
    import threading

    _lock = threading.Lock()

    def leaf():
        pass

    def outer():
        def inner():
            leaf()
        with _lock:
            inner()
    """})
    edges = _edges(g)
    assert ("predictionio_tpu.clo.outer",
            "predictionio_tpu.clo.leaf") in edges


def test_callgraph_self_method_through_base_class():
    """self.helper() on a subclass resolves to the base-class
    definition, and a lock attribute inherited from the base is still
    tracked as held on the subclass's call sites."""
    g = _graph({"predictionio_tpu/basecls.py": """\
    import threading

    class Base:
        def __init__(self):
            self._lock = threading.Lock()

        def helper(self):
            pass

    class Derived(Base):
        def go(self):
            with self._lock:
                self.helper()
    """})
    fi = g.functions["predictionio_tpu.basecls.Derived.go"]
    resolved = [cs for cs in fi.calls if cs.callees]
    assert resolved, "self.helper() through the base went unresolved"
    assert resolved[0].callees == ("predictionio_tpu.basecls.Base.helper",)
    assert resolved[0].held == ("predictionio_tpu.basecls.Derived._lock",)


def test_callgraph_module_aliases():
    """`import pkg.mod as u` and `from pkg import mod as u2` both
    resolve attribute calls through the alias."""
    g = _graph({
        "predictionio_tpu/util.py": "def helper():\n    pass\n",
        "predictionio_tpu/uses.py": """\
        import predictionio_tpu.util as u
        from predictionio_tpu import util as u2

        def go():
            u.helper()
            u2.helper()
        """,
    })
    edges = [
        cs.callees
        for cs in g.functions["predictionio_tpu.uses.go"].calls
    ]
    assert edges == [
        ("predictionio_tpu.util.helper",),
        ("predictionio_tpu.util.helper",),
    ]


def test_callgraph_factory_attr_alias_and_may_call():
    """The three resolution powers the runtime witness forced (ISSUE
    18): (a) an attr assigned from a lowercase factory call is UNKNOWN,
    not foreign — the duck-typed fallback stays available; (b) a local
    `svc = self._attr` alias carries the receiver through; (c) the
    duck-typed fallback returns ALL candidate definitions (may-call)
    when the method name has a few implementations, not just one."""
    g = _graph({
        "predictionio_tpu/impls.py": """\
        class DriverA:
            def tail_follow(self):
                pass

        class DriverB:
            def tail_follow(self):
                pass
        """,
        "predictionio_tpu/userm.py": """\
        from predictionio_tpu.storage import Storage
        from predictionio_tpu.vendor import OpaqueClient

        class Follower:
            def __init__(self):
                self._pe = Storage.get_p_events()
                self._cli = OpaqueClient()

            def poll(self):
                self._pe.tail_follow()

            def route(self):
                svc = self._pe
                svc.tail_follow()

            def push(self):
                self._cli.tail_follow()
        """,
        "predictionio_tpu/storage.py": """\
        class Storage:
            @staticmethod
            def get_p_events():
                pass
        """,
    })
    may_call = (
        "predictionio_tpu.impls.DriverA.tail_follow",
        "predictionio_tpu.impls.DriverB.tail_follow",
    )
    ci = g.classes["predictionio_tpu.userm.Follower"]
    assert "_pe" not in ci.attr_foreign  # (a) factory attr is unknown
    assert "_cli" in ci.attr_foreign  # unresolvable CLASS ctor is foreign
    poll = g.functions["predictionio_tpu.userm.Follower.poll"].calls
    assert poll[0].callees == may_call  # (c) may-call fan-out
    route = g.functions["predictionio_tpu.userm.Follower.route"].calls
    assert route[0].callees == may_call  # (b) alias carries the receiver
    # a FOREIGN receiver never duck-types: no in-tree edge is recorded
    push = g.functions["predictionio_tpu.userm.Follower.push"].calls
    assert all(not cs.callees for cs in push)


def test_cli_exit_code_contract(tmp_path):
    """docs/development.md exit codes: 0 clean, 1 findings, 2 internal
    error — a CI job can tell a dirty tree from a broken linter. (The
    rc=1 leg lives in test_pio_lint_sarif_cli.)"""
    pkg = tmp_path / "predictionio_tpu"
    pkg.mkdir()
    (pkg / "ok.py").write_text("X = 1\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    base = [
        sys.executable, "-m", "predictionio_tpu.tools.console",
        "lint", "--root", str(tmp_path),
    ]
    proc = subprocess.run(
        base, capture_output=True, text=True, timeout=120, env=env, cwd=REPO
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # a malformed baseline is the LINTER failing, not the tree: rc 2,
    # diagnostic on stderr, and stdout stays parseable (empty)
    broken = tmp_path / "baseline.json"
    broken.write_text("{not json")
    proc = subprocess.run(
        base + ["--baseline", str(broken), "--format", "json"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "piolint: internal error" in proc.stderr
    assert proc.stdout.strip() == ""
