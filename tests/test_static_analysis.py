"""Tier-1 wiring for the static-analysis gate (pilosa_tpu/analysis/).

Two halves:

1. The REAL gate over the repo: every pass, against the committed
   baseline — the same check `python tools/check.py` runs. A new raw
   lock, a sleep under a mutex, an impure jit body, an undeclared stat
   name, or an undocumented config knob fails tier-1 right here with
   file:line evidence.
2. The gate's own behavior on seeded violations: each pass family must
   fire (with correct location) on a synthetic bad module, stale
   baseline entries must fail, and unjustified baseline entries must be
   rejected at load time.
"""

import ast
import os
import subprocess
import sys
import textwrap

import pytest

from pilosa_tpu import analysis
from pilosa_tpu.analysis.framework import (
    Baseline,
    BaselineEntry,
    Module,
    run_gate,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "tools", "analysis_baseline.toml")


def seeded_module(rel: str, src: str) -> Module:
    src = textwrap.dedent(src)
    return Module(
        path=os.path.join("/tmp", rel),
        rel=rel,
        source=src,
        tree=ast.parse(src),
    )


def findings_for(src: str, rel: str = "pilosa_tpu/_seeded.py"):
    return analysis.run_passes(
        analysis.default_passes(), [seeded_module(rel, src)]
    )


# ---------------------------------------------------------------------------
# the real gate
# ---------------------------------------------------------------------------


class TestRepoGate:
    def test_repo_is_clean_under_committed_baseline(self):
        result = analysis.check(REPO, baseline_path=BASELINE)
        assert result.ok, "\n" + result.render()

    def test_baseline_is_small_and_fully_justified(self):
        b = Baseline.load(BASELINE)
        assert b.entries, "baseline exists but is empty?"
        for e in b.entries:
            assert len(e.reason.strip()) > 40, (
                f"baseline entry {e.code}/{e.path} has a perfunctory "
                "reason — document WHY the violation is intentional"
            )

    def test_check_script_exits_zero(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "check.py")],
            capture_output=True,
            text=True,
            cwd=REPO,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "check: OK" in proc.stdout


# ---------------------------------------------------------------------------
# lock hygiene on seeded violations
# ---------------------------------------------------------------------------


class TestLockHygieneSeeded:
    def test_raw_lock_outside_locks_py(self):
        fs = findings_for(
            """
            import threading
            _MU = threading.Lock()
            """
        )
        (f,) = [f for f in fs if f.code == "LOCK001"]
        assert f.line == 3
        assert "TrackedLock" in f.message

    def test_raw_lock_inside_locks_py_allowed(self):
        fs = analysis.run_passes(
            analysis.default_passes(),
            [
                seeded_module(
                    "pilosa_tpu/utils/locks.py",
                    "import threading\n_MU = threading.Lock()\n",
                )
            ],
        )
        assert not [f for f in fs if f.code == "LOCK001"]

    def test_sleep_under_lock(self):
        fs = findings_for(
            """
            import time
            from pilosa_tpu.utils.locks import TrackedLock
            _MU = TrackedLock("x")

            def f():
                with _MU:
                    time.sleep(1.0)
            """
        )
        (f,) = [f for f in fs if f.code == "LOCK002"]
        assert f.line == 8
        assert "time.sleep" in f.message and "_MU" in f.message

    def test_network_io_under_self_lock(self):
        fs = findings_for(
            """
            import urllib.request

            class C:
                def f(self):
                    with self._mu:
                        urllib.request.urlopen("http://x")
            """
        )
        (f,) = [f for f in fs if f.code == "LOCK002"]
        assert "urlopen" in f.message

    def test_device_sync_under_lock(self):
        fs = findings_for(
            """
            class C:
                def f(self):
                    with self._lock:
                        return self.arr.block_until_ready()
            """
        )
        (f,) = [f for f in fs if f.code == "LOCK003"]
        assert "block_until_ready" in f.message

    def test_closure_under_lock_not_flagged(self):
        # a function DEFINED under the lock runs later: not a hold
        fs = findings_for(
            """
            import time

            class C:
                def f(self):
                    with self._mu:
                        def later():
                            time.sleep(1.0)
                        self.cb = later
            """
        )
        assert not [f for f in fs if f.code == "LOCK002"]


# ---------------------------------------------------------------------------
# guarded-by inference (LOCK004/LOCK005) on seeded violations
# ---------------------------------------------------------------------------


class TestGuardedBySeeded:
    def test_mixed_write_fires_lock004(self):
        fs = findings_for(
            """
            from pilosa_tpu.utils.locks import TrackedLock

            class C:
                def __init__(self):
                    self._mu = TrackedLock("c.mu")
                    self._items = {}

                def add(self, k, v):
                    with self._mu:
                        self._items[k] = v

                def replace(self, items):
                    with self._mu:
                        self._items = dict(items)

                def rogue(self):
                    self._items = {}   # line 18: bare write
            """
        )
        (f,) = [f for f in fs if f.code == "LOCK004"]
        assert f.line == 18
        assert "C._items" in f.message and "'_mu'" in f.message

    def test_init_writes_are_exempt(self):
        fs = findings_for(
            """
            from pilosa_tpu.utils.locks import TrackedLock

            class C:
                def __init__(self):
                    self._mu = TrackedLock("c.mu")
                    self._a = {}
                    self._a["k"] = 1   # constructor: pre-publication

                def w1(self, v):
                    with self._mu:
                        self._a["x"] = v

                def w2(self, v):
                    with self._mu:
                        self._a["y"] = v
            """
        )
        assert not [f for f in fs if f.code in ("LOCK004", "LOCK005")]

    def test_single_write_site_claims_no_guard(self):
        # MIN_GUARDED_WRITES: one agreeing site is too little signal
        fs = findings_for(
            """
            from pilosa_tpu.utils.locks import TrackedLock

            class C:
                def __init__(self):
                    self._mu = TrackedLock("c.mu")

                def a(self, v):
                    with self._mu:
                        self._x = v

                def b(self, v):
                    self._x = v
            """
        )
        assert not [f for f in fs if f.code == "LOCK004"]

    def test_bare_read_in_lock_taking_method_fires_lock005(self):
        fs = findings_for(
            """
            from pilosa_tpu.utils.locks import TrackedLock

            class C:
                def __init__(self):
                    self._mu = TrackedLock("c.mu")
                    self._n = 0

                def bump(self):
                    with self._mu:
                        self._n += 1

                def bump2(self):
                    with self._mu:
                        self._n += 1

                def peek_then_lock(self):
                    n = self._n        # line 18: bare read...
                    with self._mu:     # ...in a method that takes _mu
                        self._n += 1
                    return n
            """
        )
        (f,) = [f for f in fs if f.code == "LOCK005"]
        assert f.line == 18
        assert "peek_then_lock" in f.message

    def test_bare_read_in_lockless_method_not_flagged(self):
        # LOCK005 scopes to methods that ELSEWHERE take the lock: a
        # gauge-snapshot method that never does is inference-silent
        # (the runtime race detector owns that territory)
        fs = findings_for(
            """
            from pilosa_tpu.utils.locks import TrackedLock

            class C:
                def __init__(self):
                    self._mu = TrackedLock("c.mu")
                    self._n = 0

                def bump(self):
                    with self._mu:
                        self._n += 1

                def bump2(self):
                    with self._mu:
                        self._n += 1

                def snapshot(self):
                    return self._n
            """
        )
        assert not [f for f in fs if f.code == "LOCK005"]

    def test_guarded_by_annotation_enforces_single_write(self):
        # a declared guard fires on ANY bare write, even below the
        # inference threshold
        fs = findings_for(
            """
            from pilosa_tpu.utils.locks import TrackedLock

            class C:
                def __init__(self):
                    self._mu = TrackedLock("c.mu")
                    self._x = 0   # guarded-by: _mu

                def locked_write(self, v):
                    with self._mu:
                        self._x = v

                def rogue(self, v):
                    self._x = v
            """
        )
        (f,) = [f for f in fs if f.code == "LOCK004"]
        assert "guard declared by annotation" in f.message

    def test_lock_free_annotation_exempts_attribute(self):
        fs = findings_for(
            """
            from pilosa_tpu.utils.locks import TrackedLock

            class C:
                def __init__(self):
                    self._mu = TrackedLock("c.mu")
                    self._x = 0   # lock-free: monotonic int, GIL-atomic reads

                def a(self, v):
                    with self._mu:
                        self._x = v

                def b(self, v):
                    with self._mu:
                        self._x = v

                def rogue(self, v):
                    self._x = v
            """
        )
        assert not [f for f in fs if f.code in ("LOCK004", "LOCK005")]

    def test_lock_free_annotation_without_reason_is_a_finding(self):
        fs = findings_for(
            """
            from pilosa_tpu.utils.locks import TrackedLock

            class C:
                def __init__(self):
                    self._mu = TrackedLock("c.mu")
                    self._x = 0   # lock-free:

                def a(self, v):
                    with self._mu:
                        self._x = v
            """
        )
        assert any(
            f.code == "LOCK004" and "no reason" in f.message for f in fs
        )

    def test_locked_suffix_methods_assume_primary_lock(self):
        fs = findings_for(
            """
            from pilosa_tpu.utils.locks import TrackedLock

            class C:
                def __init__(self):
                    self._mu = TrackedLock("c.mu")
                    self._x = 0

                def a(self, v):
                    with self._mu:
                        self._set_locked(v)

                def b(self, v):
                    with self._mu:
                        self._set_locked(v)

                def _set_locked(self, v):
                    self._x = v   # convention: caller holds _mu
            """
        )
        assert not [f for f in fs if f.code == "LOCK004"]

    def test_def_level_guarded_by_annotation(self):
        fs = findings_for(
            """
            from pilosa_tpu.utils.locks import TrackedLock

            class C:
                def __init__(self):
                    self._mu = TrackedLock("c.mu")
                    self._x = 0

                def a(self, v):
                    with self._mu:
                        self._apply(v)

                def b(self, v):
                    with self._mu:
                        self._apply(v)

                def _apply(self, v):  # guarded-by: _mu (callers hold it)
                    self._x = v
            """
        )
        assert not [f for f in fs if f.code == "LOCK004"]

    def test_condition_aliases_its_lock(self):
        # `with self._cv:` acquires the underlying _mu — one guard
        fs = findings_for(
            """
            from pilosa_tpu.utils.locks import TrackedCondition, TrackedLock

            class C:
                def __init__(self):
                    self._mu = TrackedLock("c.mu")
                    self._cv = TrackedCondition(self._mu, name="c.cv")
                    self._x = 0

                def a(self, v):
                    with self._cv:
                        self._x = v

                def b(self, v):
                    with self._mu:
                        self._x = v
            """
        )
        assert not [f for f in fs if f.code == "LOCK004"]


# ---------------------------------------------------------------------------
# dispatch discipline (LOCK006) on seeded violations
# ---------------------------------------------------------------------------


class TestDispatchDisciplineSeeded:
    REL = "pilosa_tpu/exec/_seeded.py"

    def test_direct_jit_call_flagged(self):
        fs = findings_for(
            """
            import jax

            @jax.jit
            def _tally(x):
                return x

            def leg(x):
                return _tally(x)
            """,
            rel=self.REL,
        )
        (f,) = [f for f in fs if f.code == "LOCK006"]
        assert "_tally" in f.message and "PR-10" in f.message

    def test_block_until_ready_flagged(self):
        fs = findings_for(
            """
            def leg(arr):
                return arr.block_until_ready()
            """,
            rel=self.REL,
        )
        (f,) = [f for f in fs if f.code == "LOCK006"]
        assert "block_until_ready" in f.message

    def test_run_serialized_argument_exempt(self):
        fs = findings_for(
            """
            import jax
            from pilosa_tpu.exec.plan import run_serialized

            @jax.jit
            def _tally(x):
                return x

            def leg(x):
                return run_serialized(lambda: _tally(x))
            """,
            rel=self.REL,
        )
        assert not [f for f in fs if f.code == "LOCK006"]

    def test_run_serialized_eager_argument_still_flagged(self):
        # run_serialized(_tally(x)) evaluates the compiled call EAGERLY
        # on the calling thread before run_serialized runs — the PR-10
        # bug wearing the fix's clothes; only deferred callables
        # (lambda / function reference) are exempt
        fs = findings_for(
            """
            import jax
            from pilosa_tpu.exec.plan import run_serialized

            @jax.jit
            def _tally(x):
                return x

            def leg(x):
                return run_serialized(_tally(x))
            """,
            rel=self.REL,
        )
        (f,) = [f for f in fs if f.code == "LOCK006"]
        assert "_tally" in f.message

    def test_run_serialized_function_reference_exempt(self):
        fs = findings_for(
            """
            import jax
            from pilosa_tpu.exec.plan import run_serialized

            @jax.jit
            def _tally(x):
                return x

            def leg(x):
                return run_serialized(_tally)
            """,
            rel=self.REL,
        )
        assert not [f for f in fs if f.code == "LOCK006"]

    def test_dispatch_mutex_with_block_exempt(self):
        fs = findings_for(
            """
            import jax
            from pilosa_tpu.exec.plan import dispatch_mutex

            @jax.jit
            def _tally(x):
                return x

            def leg(x):
                with dispatch_mutex():
                    return _tally(x).block_until_ready()
            """,
            rel=self.REL,
        )
        assert not [f for f in fs if f.code == "LOCK006"]

    def test_jit_body_calls_are_traced_not_dispatched(self):
        fs = findings_for(
            """
            import jax

            @jax.jit
            def _inner(x):
                return x

            @jax.jit
            def _outer(x):
                return _inner(x)   # inlined into one program
            """,
            rel=self.REL,
        )
        assert not [f for f in fs if f.code == "LOCK006"]

    def test_out_of_scope_modules_not_checked(self):
        fs = findings_for(
            """
            import jax

            @jax.jit
            def _tally(x):
                return x

            def leg(x):
                return _tally(x)
            """,
            rel="pilosa_tpu/server/_seeded.py",
        )
        assert not [f for f in fs if f.code == "LOCK006"]

    def test_dispatch_ok_annotation_exempts_with_reason(self):
        fs = findings_for(
            """
            import jax

            @jax.jit
            def _tally(x):
                return x

            def leg(x):  # dispatch-ok: single-device, no collectives
                return _tally(x)
            """,
            rel=self.REL,
        )
        assert not [f for f in fs if f.code == "LOCK006"]

    def test_dispatch_ok_without_reason_is_a_finding(self):
        fs = findings_for(
            """
            import jax

            @jax.jit
            def _tally(x):
                return x

            def leg(x):  # dispatch-ok:
                return _tally(x)
            """,
            rel=self.REL,
        )
        assert any(
            f.code == "LOCK006" and "no reason" in f.message for f in fs
        )


# ---------------------------------------------------------------------------
# fragment-lock durability discipline (LOCK007) on seeded violations
# ---------------------------------------------------------------------------


class TestFragmentLockDurabilitySeeded:
    REL = "pilosa_tpu/core/_seeded.py"

    def test_os_fsync_under_fragment_lock(self):
        fs = findings_for(
            """
            import os

            class F:
                def write(self, fd):
                    with self._mu:
                        os.fsync(fd)
            """,
            rel=self.REL,
        )
        (f,) = [f for f in fs if f.code == "LOCK007"]
        assert "os.fsync" in f.message and "PR-11" in f.message

    def test_wait_durable_under_fragment_lock(self):
        fs = findings_for(
            """
            from pilosa_tpu.core import wal as walmod

            class F:
                def write(self, tok):
                    with self._mu:
                        walmod.GROUP_COMMIT.wait_durable(tok)
            """,
            rel=self.REL,
        )
        (f,) = [f for f in fs if f.code == "LOCK007"]
        assert "wait_durable" in f.message

    def test_wal_truncate_under_fragment_lock(self):
        fs = findings_for(
            """
            class F:
                def snap(self):
                    with self._mu:
                        self._wal.truncate()
            """,
            rel=self.REL,
        )
        assert [f for f in fs if f.code == "LOCK007"]

    def test_commit_token_past_the_lock_passes(self):
        # the PR-11 convention itself: token returned past the lock
        fs = findings_for(
            """
            from pilosa_tpu.core import wal as walmod

            class F:
                def write(self, positions):
                    with self._mu:
                        tok = self._wal.append(0, positions)
                    if tok is not None:
                        walmod.GROUP_COMMIT.wait_durable(tok)
            """,
            rel=self.REL,
        )
        assert not [f for f in fs if f.code == "LOCK007"]

    def test_out_of_scope_modules_not_checked(self):
        fs = findings_for(
            """
            import os

            class F:
                def write(self, fd):
                    with self._mu:
                        os.fsync(fd)
            """,
            rel="pilosa_tpu/server/_seeded2.py",
        )
        assert not [f for f in fs if f.code == "LOCK007"]


# ---------------------------------------------------------------------------
# jax purity on seeded violations
# ---------------------------------------------------------------------------


class TestJaxPuritySeeded:
    def test_impure_jit_body_all_rules(self):
        fs = findings_for(
            """
            import functools
            import jax
            import numpy as np

            STATS = {"n": 0}

            @functools.partial(jax.jit, static_argnames=("k",))
            def g(x, k):
                print("traced")
                STATS["n"] += 1
                v = np.sum(x)
                return float(x) + x.item() + v
            """
        )
        codes = {f.code for f in fs}
        assert {"JAX001", "JAX002", "JAX003", "JAX004"} <= codes
        np_finding = [f for f in fs if f.code == "JAX002"][0]
        assert "numpy.sum" in np_finding.message
        assert np_finding.line == 12

    def test_static_argnames_mismatch(self):
        fs = findings_for(
            """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("missing",))
            def g(x):
                return x
            """
        )
        (f,) = [f for f in fs if f.code == "JAX005"]
        assert "'missing'" in f.message and "g()" in f.message

    def test_static_argnums_out_of_range(self):
        fs = findings_for(
            """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnums=(5,))
            def g(x):
                return x
            """
        )
        (f,) = [f for f in fs if f.code == "JAX005"]
        assert "out of range" in f.message

    def test_static_coercion_allowed(self):
        # int() of a STATIC argument is legal (it is a Python value)
        fs = findings_for(
            """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("k",))
            def g(x, k):
                return x * int(k)
            """
        )
        assert not [f for f in fs if f.code == "JAX003"]

    def test_pallas_kernel_body_checked(self):
        fs = findings_for(
            """
            import jax
            from jax.experimental import pallas as pl

            def kernel(x_ref, o_ref):
                print("impure")
                o_ref[...] = x_ref[...]

            def call(x):
                return pl.pallas_call(kernel, out_shape=None)(x)
            """
        )
        (f,) = [f for f in fs if f.code == "JAX001"]
        assert "kernel()" in f.message

    def test_pure_jit_clean(self):
        fs = findings_for(
            """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def g(x):
                return jnp.sum(x)
            """
        )
        assert not [f for f in fs if f.code.startswith("JAX")]


# ---------------------------------------------------------------------------
# api invariants on seeded violations
# ---------------------------------------------------------------------------


class TestApiInvariantsSeeded:
    def _with_repo_registry(self, src: str):
        """Seeded module + the real stats.py (for its STAT_NAMES)."""
        stats_path = os.path.join(REPO, "pilosa_tpu", "utils", "stats.py")
        stats_mod = analysis.load_source_module(
            stats_path, rel="pilosa_tpu/utils/stats.py"
        )
        return analysis.run_passes(
            [analysis.ApiInvariantsPass()],
            [stats_mod, seeded_module("pilosa_tpu/_seeded.py", src)],
        )

    def test_undeclared_stat_emission(self):
        fs = self._with_repo_registry(
            """
            class C:
                def f(self):
                    self.stats.count("definitely_not_declared")
            """
        )
        assert any(
            f.code == "API001" and "definitely_not_declared" in f.message
            for f in fs
        )

    def test_dynamic_stat_outside_declared_prefix(self):
        fs = self._with_repo_registry(
            """
            class C:
                def f(self, x):
                    self.stats.count(f"mystery.{x}")
            """
        )
        assert any(
            f.code == "API001" and "mystery." in f.message for f in fs
        )

    def test_with_tags_chain_emission_scanned(self):
        """The inline labeled-family form
        `stats.with_tags(...).gauge(...)` is a real emission: an
        undeclared name through the chain must be flagged (and a
        declared one keeps its registry entry non-stale)."""
        fs = self._with_repo_registry(
            """
            class C:
                def f(self):
                    self.stats.with_tags("index:a").count("chain_undeclared")
            """
        )
        assert any(
            f.code == "API001" and "chain_undeclared" in f.message
            for f in fs
        )

    def test_api008_stat_labels_must_name_declared_stats(self):
        stats_mod = seeded_module(
            "pilosa_tpu/utils/stats.py",
            """
            STAT_NAMES = frozenset({"real.metric"})
            STAT_PREFIXES = frozenset({"dyn."})
            STAT_LABELS = {
                "real.metric": ("index",),   # fine
                "dyn.family": ("node",),     # fine via prefix
                "typo.metric": ("index",),   # API008: undeclared
                "real.metric2": (),          # ...and undeclared + empty
            }
            """,
        )
        emitter = seeded_module(
            "pilosa_tpu/_seeded.py",
            """
            class C:
                def f(self):
                    self.stats.count("real.metric")
            """,
        )
        fs = analysis.run_passes(
            [analysis.ApiInvariantsPass()], [stats_mod, emitter]
        )
        assert any(
            f.code == "API008" and "typo.metric" in f.message for f in fs
        )
        assert any(
            f.code == "API008"
            and "real.metric2" in f.message
            and "no label keys" in f.message
            for f in fs
        )
        assert not any(
            f.code == "API008" and "dyn.family" in f.message for f in fs
        )

    def test_declared_prefix_dynamic_ok(self):
        fs = self._with_repo_registry(
            """
            class C:
                def f(self, state):
                    self.stats.count(f"breaker.{state}")
            """
        )
        assert not [
            f
            for f in fs
            if f.code == "API001" and "breaker." in f.message
        ]

    def _with_span_registry(self, src: str):
        """Seeded module + the real tracing.py (for its SPAN_NAMES)."""
        tracing_path = os.path.join(
            REPO, "pilosa_tpu", "utils", "tracing.py"
        )
        tracing_mod = analysis.load_source_module(
            tracing_path, rel="pilosa_tpu/utils/tracing.py"
        )
        return analysis.run_passes(
            [analysis.ApiInvariantsPass()],
            [tracing_mod, seeded_module("pilosa_tpu/_seeded.py", src)],
        )

    def test_undeclared_span_start(self):
        fs = self._with_span_registry(
            """
            class C:
                def f(self):
                    with self.tracer.start_span("mystery.stage"):
                        pass
            """
        )
        assert any(
            f.code == "API006" and "mystery.stage" in f.message for f in fs
        )

    def test_undeclared_synthetic_span(self):
        fs = self._with_span_registry(
            """
            from pilosa_tpu.utils import tracing

            def f():
                tracing.record_span("rogue.synthetic", 0.1)
            """
        )
        assert any(
            f.code == "API006" and "rogue.synthetic" in f.message
            for f in fs
        )

    def test_declared_span_ok_and_stale_entry_flagged(self):
        fs = self._with_span_registry(
            """
            class C:
                def f(self):
                    with self.tracer.start_span("api.query"):
                        pass
            """
        )
        assert not [
            f
            for f in fs
            if f.code == "API006" and "api.query" in f.message
        ]
        # nothing in the seeded set starts exec.dispatch -> stale entry
        assert any(
            f.code == "API007" and "exec.dispatch" in f.message for f in fs
        )

    def test_a_literal_annotation_starts_a_name(self):
        """A TraceAnnotation under a literal name is a start: declared
        or API006, and tracing.py's own (runtime.gc, node.tick) keep
        their profiler-clock-only entries from reading stale."""
        fs = self._with_span_registry(
            """
            from jax.profiler import TraceAnnotation

            def f():
                with TraceAnnotation("rogue.annotation"):
                    pass
            """
        )
        assert any(
            f.code == "API006" and "rogue.annotation" in f.message
            for f in fs
        )
        assert not [
            f for f in fs
            if f.code == "API007"
            and ("runtime.gc" in f.message or "node.tick" in f.message)
        ]

    def test_config_flag_doc_invariants(self, tmp_path):
        config_src = textwrap.dedent(
            """
            from dataclasses import dataclass

            @dataclass
            class ClusterConfig:
                replicas: int = 1
                secret_knob: float = 0.0

            @dataclass
            class Config:
                bind: str = "localhost:1"
                cluster: ClusterConfig = None
            """
        )
        main_src = textwrap.dedent(
            """
            import argparse

            def build():
                p = argparse.ArgumentParser()
                sub = p.add_subparsers()
                sp = sub.add_parser("server")
                sp.add_argument("--bind")
                sp.add_argument("--replicas")
                sp.add_argument("--orphan-flag")
                return p
            """
        )
        docs = tmp_path / "configuration.md"
        docs.write_text("bind = ...\nreplicas = ...\n")  # secret-knob absent
        config_mod = Module(
            path=str(tmp_path / "config.py"),
            rel="pilosa_tpu/cli/config.py",
            source=config_src,
            tree=ast.parse(config_src),
        )
        main_mod = Module(
            path=str(tmp_path / "main.py"),
            rel="pilosa_tpu/cli/main.py",
            source=main_src,
            tree=ast.parse(main_src),
        )
        fs = analysis.run_passes(
            [analysis.ApiInvariantsPass(docs_path=str(docs))],
            [config_mod, main_mod],
        )
        codes = {(f.code, f.message) for f in fs}
        assert any(
            c == "API003" and "secret_knob" in m for c, m in codes
        ), fs  # undocumented knob
        assert any(
            c == "API004" and "orphan-flag" in m for c, m in codes
        ), fs  # flag with no knob
        assert any(
            c == "API005" and "secret_knob" in m for c, m in codes
        ), fs  # knob with no flag

    def test_non_stats_receivers_ignored(self):
        fs = self._with_repo_registry(
            """
            class C:
                def f(self, rb):
                    return rb.count() + self.plan.count()
            """
        )
        assert not [f for f in fs if f.code == "API001"]


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------


class TestBaseline:
    def test_stale_entry_fails_gate(self):
        baseline = Baseline(
            entries=[
                BaselineEntry(
                    code="LOCK002",
                    path="pilosa_tpu/nowhere.py",
                    match="",
                    reason="entry that matches nothing",
                    rule="lock-hygiene",
                )
            ]
        )
        result = run_gate(analysis.default_passes(), [], baseline)
        assert not result.ok
        assert result.stale_entries and "STALE" in result.render()

    def test_baseline_suppresses_matching_finding(self):
        m = seeded_module(
            "pilosa_tpu/_seeded.py",
            """
            import time
            from pilosa_tpu.utils.locks import TrackedLock
            _MU = TrackedLock("x")

            def f():
                with _MU:
                    time.sleep(1.0)
            """,
        )
        baseline = Baseline(
            entries=[
                BaselineEntry(
                    code="LOCK002",
                    path="pilosa_tpu/_seeded.py",
                    match="time.sleep",
                    reason="seeded on purpose for this test",
                    rule="lock-hygiene",
                )
            ]
        )
        result = run_gate([analysis.LockHygienePass()], [m], baseline)
        assert result.ok, result.render()
        assert len(result.suppressed) == 1

    def test_unjustified_entry_rejected_at_load(self, tmp_path):
        p = tmp_path / "baseline.toml"
        p.write_text(
            '[[allow]]\ncode = "LOCK002"\npath = "x.py"\nmatch = ""\n'
            'rule = "lock-hygiene"\n'
        )
        with pytest.raises(ValueError, match="reason"):
            Baseline.load(str(p))

    def test_entry_without_rule_rejected_at_load(self, tmp_path):
        p = tmp_path / "baseline.toml"
        p.write_text(
            '[[allow]]\ncode = "LOCK002"\npath = "x.py"\nmatch = ""\n'
            'reason = "justified but unowned"\n'
        )
        with pytest.raises(ValueError, match="rule"):
            Baseline.load(str(p))

    def test_entry_naming_removed_pass_fails_gate(self):
        # a renamed/retired pass must take its suppressions with it
        baseline = Baseline(
            entries=[
                BaselineEntry(
                    code="LOCK002",
                    path="pilosa_tpu/x.py",
                    match="",
                    reason="suppression owned by a pass that is gone",
                    rule="lock-hygiene-v1",
                )
            ]
        )
        result = run_gate(analysis.default_passes(), [], baseline)
        assert not result.ok
        assert result.invalid_entries
        assert "lock-hygiene-v1" in result.render()

    def test_entry_naming_removed_rule_code_fails_gate(self):
        baseline = Baseline(
            entries=[
                BaselineEntry(
                    code="LOCK099",
                    path="pilosa_tpu/x.py",
                    match="",
                    reason="suppression for a rule code that is gone",
                    rule="lock-hygiene",
                )
            ]
        )
        result = run_gate(analysis.default_passes(), [], baseline)
        assert not result.ok
        assert result.invalid_entries
        assert "LOCK099" in result.render()

    def test_every_pass_declares_its_rules(self):
        # the validation above is only as good as the declarations: a
        # pass emitting codes it never declared would let its baseline
        # entries be rejected as invalid (or worse, never validated)
        for p in analysis.default_passes():
            assert p.rules, f"pass {p.name} declares no rules"
            for code in p.rules:
                assert code[:3] in ("LOC", "JAX", "API", "RES"), code

    def test_committed_baseline_entries_all_name_live_rules(self):
        from pilosa_tpu.analysis.framework import validate_baseline

        b = Baseline.load(BASELINE)
        assert validate_baseline(analysis.default_passes(), b) == []

    def test_gate_failure_carries_file_line_evidence(self):
        m = seeded_module(
            "pilosa_tpu/_seeded.py",
            """
            import threading
            _MU = threading.Lock()
            """,
        )
        result = run_gate(analysis.default_passes(), [m], Baseline())
        assert not result.ok
        assert "pilosa_tpu/_seeded.py:3" in result.render()


# ---------------------------------------------------------------------------
# resource lifecycle (RES001-RES005) on seeded violations
# ---------------------------------------------------------------------------


class TestLifecycleSeeded:
    """The must-release pass against synthetic modules, one rule at a
    time. CFG shape coverage (finally clones, with-unwind, loop exits)
    lives in test_resource_lifecycle.py; these pin the rule semantics."""

    def _lifecycle(self, src: str, rel: str = "pilosa_tpu/_seeded.py"):
        """Seeded module + the real ledger module (so RES005's
        cross-check sees the registry and stays quiet)."""
        res_mod = analysis.load_source_module(
            os.path.join(REPO, "pilosa_tpu", "utils", "resources.py"),
            rel="pilosa_tpu/utils/resources.py",
        )
        return analysis.run_passes(
            [analysis.LifecyclePass()], [res_mod, seeded_module(rel, src)]
        )

    def test_res001_branch_arm_skips_release(self):
        fs = self._lifecycle(
            """
            from concurrent.futures import ThreadPoolExecutor

            def f(flag):
                pool = ThreadPoolExecutor(max_workers=2)
                if flag:
                    pool.shutdown()
            """
        )
        assert any(f.code == "RES001" and f.line == 5 for f in fs), fs

    def test_release_on_every_path_is_clean(self):
        fs = self._lifecycle(
            """
            from concurrent.futures import ThreadPoolExecutor

            def f(work):
                pool = ThreadPoolExecutor(max_workers=2)
                try:
                    work(pool)
                finally:
                    pool.shutdown()
            """
        )
        assert not [f for f in fs if f.code.startswith("RES")], fs

    def test_res002_exception_path_skips_release(self):
        fs = self._lifecycle(
            """
            from concurrent.futures import ThreadPoolExecutor

            def f(work):
                pool = ThreadPoolExecutor(max_workers=2)
                work(pool)
                pool.shutdown()
            """
        )
        codes = {f.code for f in fs}
        assert "RES002" in codes, fs
        assert "RES001" not in codes, fs  # the straight-line path is fine

    def test_res003_discarded_handle(self):
        fs = self._lifecycle(
            """
            from concurrent.futures import ThreadPoolExecutor

            def f():
                ThreadPoolExecutor(max_workers=2)
            """
        )
        assert any(f.code == "RES003" and f.line == 5 for f in fs), fs

    def test_daemon_thread_exempt_nondaemon_tracked(self):
        fs = self._lifecycle(
            """
            import threading

            def f(cb):
                t = threading.Thread(target=cb, daemon=True)
                t.start()

            def g(cb):
                t = threading.Thread(target=cb)
                t.start()
            """
        )
        assert not [f for f in fs if f.line == 5], fs  # daemon: exempt
        assert any(f.code == "RES001" and f.line == 9 for f in fs), fs

    def test_res004_empty_reason_and_stale_annotation(self):
        fs = self._lifecycle(
            """
            def f():
                x = 1  # owns:
                y = 2  # transfer: consumed by nothing in this module
            """
        )
        assert [f.code for f in fs].count("RES004") == 2, fs

    def test_res005_registry_drift_both_ways(self):
        fake = seeded_module(
            "pilosa_tpu/utils/resources.py",
            """
            RESOURCE_CLASSES = {
                "sched.ticket": "kept",
                "made.up": "ledger entry with no contract",
            }
            """,
        )
        fs = analysis.run_passes([analysis.LifecyclePass()], [fake])
        msgs = [f.message for f in fs if f.code == "RES005"]
        assert any("made.up" in m for m in msgs), fs
        assert any("hbm.pin" in m for m in msgs), fs

    def test_res005_missing_ledger_module(self):
        fs = analysis.run_passes(
            [analysis.LifecyclePass()],
            [seeded_module("pilosa_tpu/_seeded.py", "x = 1\n")],
        )
        assert any(
            f.code == "RES005" and "missing" in f.message for f in fs
        ), fs

    def test_owns_annotation_suppresses_with_reason(self):
        fs = self._lifecycle(
            """
            from concurrent.futures import ThreadPoolExecutor

            def f(registry):
                # owns: registry shuts every pool down at process exit
                pool = ThreadPoolExecutor(max_workers=2)
                registry.append(pool)
            """
        )
        assert not [f for f in fs if f.code.startswith("RES")], fs

    def test_conditional_acquire_with_identity_guard_is_clean(self):
        # `x = acquire() if c else None` + `if x is not None: x.release()`
        # — branch pruning plus the no-exception-edge identity test
        fs = self._lifecycle(
            """
            from concurrent.futures import ThreadPoolExecutor

            def f(flag):
                pool = ThreadPoolExecutor(max_workers=2) if flag else None
                if pool is not None:
                    pool.shutdown()
            """
        )
        assert not [f for f in fs if f.code.startswith("RES")], fs

    def test_with_and_return_are_transfer_by_construction(self):
        fs = self._lifecycle(
            """
            from concurrent.futures import ThreadPoolExecutor

            def f():
                with ThreadPoolExecutor(max_workers=2) as pool:
                    pool.submit(print)

            def g():
                return ThreadPoolExecutor(max_workers=2)

            def h():
                pool = ThreadPoolExecutor(max_workers=2)
                return pool
            """
        )
        assert not [f for f in fs if f.code.startswith("RES")], fs

    def test_manual_lock_acquire_must_release(self):
        fs = self._lifecycle(
            """
            class C:
                def bad(self, work):
                    self._mu.acquire()
                    work()

                def good(self, work):
                    self._mu.acquire()
                    try:
                        work()
                    finally:
                        self._mu.release()
            """
        )
        bad = [f for f in fs if f.line == 4]
        assert any(f.code == "RES002" for f in bad), fs
        assert not [f for f in fs if f.line == 8], fs

    def test_site_mode_pin_requires_kwarg_match(self):
        fs = self._lifecycle(
            """
            def f(cache, key, build):
                arr = cache.get_or_build(key, build, pin=True)
                return arr

            def g(cache, key, build):
                arr = cache.get_or_build(key, build)
                return arr
            """
        )
        assert any(f.code == "RES001" and f.line == 3 for f in fs), fs
        assert not [f for f in fs if f.line == 7], fs


class TestApi009Seeded:
    def test_unread_knob_flagged_read_knob_quiet(self):
        cfg = seeded_module(
            "pilosa_tpu/cli/config.py",
            """
            from dataclasses import dataclass

            @dataclass
            class Config:
                used_knob: int = 1
                dead_knob: int = 2
            """,
        )
        reader = seeded_module(
            "pilosa_tpu/server/consumer.py",
            """
            def f(cfg):
                return cfg.used_knob
            """,
        )
        fs = analysis.run_passes(
            [analysis.ApiInvariantsPass()], [cfg, reader]
        )
        api9 = [f for f in fs if f.code == "API009"]
        assert len(api9) == 1, fs
        assert "dead_knob" in api9[0].message
        assert api9[0].line == 7

    def test_knob_read_only_in_config_module_is_still_dead(self):
        cfg = seeded_module(
            "pilosa_tpu/cli/config.py",
            """
            from dataclasses import dataclass

            @dataclass
            class Config:
                self_knob: int = 1

                def validate(self):
                    return self.self_knob > 0
            """,
        )
        fs = analysis.run_passes([analysis.ApiInvariantsPass()], [cfg])
        assert any(
            f.code == "API009" and "self_knob" in f.message for f in fs
        ), fs
