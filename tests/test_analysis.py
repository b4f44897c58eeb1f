"""Tests for the ISSUE-6 correctness tooling itself.

Two layers, both tested with SEEDED violations (a fixture the analyzer
must flag) and clean counterparts (which it must not):

* :mod:`tpubloom.analysis.lint` — the static AST lint. Fixture sources
  with a blocking call under a lock, a notify-before-append ordering
  bug, an unregistered fault point, an undeclared metric, and an orphan
  protocol method must each produce exactly the expected finding; the
  suppression grammar (mandatory reason, unknown check, unused allow)
  is itself linted. The real tree must lint CLEAN — that assertion IS
  the tier-1 acceptance gate for this PR.
* :mod:`tpubloom.utils.locks` — the runtime lock-order / held-while-
  blocking tracker. A seeded lock-order cycle, a two-instance self
  cycle, a ``Condition.wait`` under a foreign lock, and a
  ``note_blocking`` under a lock must each be flagged; consistent
  orderings, RLock re-entry and allowlisted (reasoned) holds must not.
  The subprocess exit-report plumbing the chaos suites rely on is
  exercised with a real child process.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading

import pytest

from tpubloom.analysis import lint as L
from tpubloom.utils import locks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# tiny injected registries so the fixtures do not depend on the real
# vocabulary (and the lint's tree mode stays off)
CONFIG_KW = dict(
    known_fault_points=frozenset({"ckpt.write", "rpc.pre_handle"}),
    counters=frozenset({"keys_inserted"}),
    gauges=frozenset({"ha_epoch"}),
    tree_checks=False,
)


def _lint_source(tmp_path, source, name="fixture.py", **overrides):
    path = tmp_path / name
    path.write_text(textwrap.dedent(source))
    config = L.LintConfig(**{**CONFIG_KW, **overrides})
    return L.lint_paths([str(path)], config)


def _checks(findings):
    return sorted(f.check for f in findings)


# -- static lint: seeded violations -------------------------------------------


def test_blocking_under_lock_flagged(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        import os

        class S:
            def bad_io(self):
                with self._lock:
                    os.fsync(3)

            def bad_wait(self):
                with self._lock:
                    self._cond.wait()
        """,
    )
    assert _checks(findings) == ["blocking-under-lock"] * 2
    assert "os.fsync" in findings[0].message


def test_barrier_under_lock_gets_its_own_check(tmp_path):
    """ISSUE 13: ``wait_acked``/``commit_barrier`` under a lock is the
    PR-5 invariant with its own name now — previously folded into
    blocking-under-lock, previously prose."""
    findings = _lint_source(
        tmp_path,
        """
        class S:
            def bad_quorum(self):
                with self.lock:
                    self.sessions.wait_acked(1, 1, 5.0)

            def bad_barrier(self):
                with self._lock:
                    self.commit_barrier(req, resp)

            def ok_outside(self):
                self.sessions.wait_acked(1, 1, 5.0)
                return self.commit_barrier(req, resp)
        """,
    )
    assert _checks(findings) == ["barrier-outside-lock"] * 2
    assert "PR-5" in findings[0].message


def test_bounded_wait_on_own_condition_clean(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        class S:
            def ok(self):
                with self._cond:
                    self._cond.wait(0.5)

            def ok_kw(self):
                with self._cond:
                    self._cond.wait_for(lambda: True, timeout=1.0)

            def ok_outside(self):
                self.sessions.wait_acked(1, 1, 5.0)
        """,
    )
    assert findings == []


def test_nested_function_does_not_inherit_lock_region(tmp_path):
    # a closure DEFINED under a lock runs when called, not where it is
    # written — it must not be treated as blocking-under-lock
    findings = _lint_source(
        tmp_path,
        """
        import os

        class S:
            def ok(self):
                with self._lock:
                    def flush_later():
                        os.fsync(3)
                    self.defer(flush_later)
        """,
    )
    assert findings == []


def test_notify_before_append_flagged(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        class S:
            def bad(self, rec):
                self.checkpointer.notify_inserts(3)
                self.oplog.append(rec)

            def good(self, rec):
                self.oplog.append(rec)
                self.checkpointer.notify_inserts(3)
        """,
    )
    assert _checks(findings) == ["notify-before-append"]
    assert "repl_seq" in findings[0].message


def test_unregistered_fault_point_flagged(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        from tpubloom import faults

        def f():
            faults.fire("ckpt.write")        # declared: clean
            faults.fire("definitely.not.declared")
        """,
    )
    assert _checks(findings) == ["fault-registry"]
    assert "definitely.not.declared" in findings[0].message


def test_undeclared_metric_flagged(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        from tpubloom.obs import counters

        def f(metrics):
            counters.incr("keys_inserted")   # declared counter: clean
            counters.set_gauge("ha_epoch", 1.0)  # declared gauge: clean
            counters.incr("mystery_series")
            counters.set_gauge("keys_inserted", 2.0)  # kind mismatch
        """,
    )
    assert _checks(findings) == ["metric-registry", "metric-registry"]
    by_msg = sorted(f.message for f in findings)
    assert "not declared" in by_msg[1]
    assert "other kind" in by_msg[0]


def test_orphan_protocol_method_flagged(tmp_path):
    # a fake repo tree whose protocol declares a method nothing implements
    server = tmp_path / "tpubloom" / "server"
    tests_dir = tmp_path / "tests"
    server.mkdir(parents=True)
    tests_dir.mkdir()
    (server / "protocol.py").write_text(
        'METHODS = ("Ping", "Ghost")\nSTREAM_METHODS = ("Watch",)\n'
    )
    (server / "service.py").write_text(
        textwrap.dedent(
            """
            class BloomService:
                def Ping(self, req):
                    return {"ok": True}

            _STREAM_BEHAVIORS = {}
            """
        )
    )
    (server / "client.py").write_text('_X = "Ping"\n')
    (tests_dir / "test_protocol_golden.py").write_text('_Y = "Ping"\n')
    findings = L.check_protocol_coverage(str(tmp_path))
    missing = sorted(f.message for f in findings)
    assert len(missing) == 5, missing  # Ghost x3, Watch x2
    assert sum("'Ghost'" in m for m in missing) == 3
    assert sum("'Watch'" in m for m in missing) == 2
    assert any("handler" in m for m in missing)
    assert any("golden" in m for m in missing)


def test_ruby_parity_flags_uncovered_and_stale(tmp_path):
    """ISSUE 12 satellite: a protocol method with no Ruby call site, one
    missing from the Ruby METHODS registry, and a stale registry entry
    must each produce exactly one finding; a covered method none."""
    server = tmp_path / "tpubloom" / "server"
    server.mkdir(parents=True)
    (server / "protocol.py").write_text('METHODS = ("Ping", "Ghost")\n')
    driver = tmp_path / L.RUBY_DRIVER_DIR
    driver.mkdir(parents=True)
    (driver / "jax.rb").write_text(
        'METHODS = %w[Ping Stale].freeze\n'
        'def ping; rpc("Ping", {}); end\n'
    )
    msgs = sorted(f.message for f in L.check_ruby_parity(str(tmp_path)))
    assert len(msgs) == 3, msgs
    assert sum("'Ghost'" in m and "call site" in m for m in msgs) == 1
    assert sum("'Ghost'" in m and "registry" in m for m in msgs) == 1
    assert sum("'Stale'" in m for m in msgs) == 1
    assert not any("'Ping'" in m for m in msgs)


def test_ruby_parity_clean_on_real_tree():
    """The real drivers cover the real protocol — part of the clean-tree
    acceptance gate (the analysis CI job runs the same check)."""
    assert L.check_ruby_parity(REPO) == []


# -- static lint: the ISSUE-13 checks ------------------------------------------


def test_donation_safety_flags_use_after_donate(tmp_path):
    """A name passed at a donated position and read again without a
    rebind is the PR-10 InFlight fence bug class; the rebind-from-the-
    call idiom (``blocks = fn(..., blocks)``) is clean."""
    findings = _lint_source(
        tmp_path,
        """
        import functools
        import jax
        import jax.experimental.pallas as pl

        def bad_kernel(starts, upd, blocks):
            fn = pl.pallas_call(kern, input_output_aliases={2: 0})
            out = fn(starts, upd, blocks)
            return blocks.sum() + out

        def good_kernel(starts, upd, blocks):
            fn = pl.pallas_call(kern, input_output_aliases={2: 0})
            blocks = fn(starts, upd, blocks)
            return blocks.sum()

        class F:
            def __init__(self, config):
                self._insert = jax.jit(make_fn(config), donate_argnums=0)

            def bad_insert(self, keys):
                out = self._insert(self.words, keys)
                return self.words

            def good_insert(self, keys):
                self.words = self._insert(self.words, keys)
                return self.words
        """,
    )
    assert _checks(findings) == ["donation-safety"] * 2
    assert "'blocks'" in findings[0].message
    assert "'self.words'" in findings[1].message


def test_donation_safety_suppression(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        import jax.experimental.pallas as pl

        def ok(starts, upd, blocks):
            fn = pl.pallas_call(kern, input_output_aliases={2: 0})
            out = fn(starts, upd, blocks)
            return blocks.shape + out  # lint: allow(donation-safety): .shape reads host metadata, never the donated device buffer
        """,
    )
    assert findings == []


def test_replay_safety_flags_uncached_mutating_handler(tmp_path):
    """A MUTATING_METHODS handler that never touches the dedup cache is
    flagged; one that does (or carries a reasoned allow on the def
    line) is clean."""
    server = tmp_path / "tpubloom" / "server"
    server.mkdir(parents=True)
    (server / "protocol.py").write_text(
        'MUTATING_METHODS = frozenset({"InsertBatch", "Clear", "Drop"})\n'
    )
    (server / "service.py").write_text(
        textwrap.dedent(
            """
            class BloomService:
                def InsertBatch(self, req):
                    cached = self._dedup_get(req.get("rid"))
                    if cached is not None:
                        return cached
                    resp = {"ok": True}
                    self._dedup_put(req.get("rid"), resp)
                    return resp

                def Clear(self, req):  # lint: allow(replay-safety): clearing twice is cleared
                    return {"ok": True}

                def Drop(self, req):
                    return {"ok": True}

                def QueryBatch(self, req):
                    return {"ok": True}
            """
        )
    )
    findings = L.check_replay_safety(str(tmp_path))
    # raw check: Drop AND Clear flagged (suppressions resolve in
    # lint_paths) — but only the mutating set, never QueryBatch
    assert sorted(f.message.split("(")[0] for f in findings) == [
        "mutating handler Clear", "mutating handler Drop",
    ]
    # through the full pipeline the def-line allow silences Clear
    config = L.LintConfig(
        **{**CONFIG_KW, "tree_checks": True, "repo_root": str(tmp_path)}
    )
    piped = [
        f
        for f in L.lint_paths([str(server / "service.py")], config)
        if f.check == "replay-safety"
    ]
    assert len(piped) == 1 and "Drop" in piped[0].message


def test_chaos_coverage_flags_unarmed_points(tmp_path):
    """A KNOWN_POINTS entry with no arm literal and no TPUBLOOM_FAULTS
    string in tests/ is dead chaos surface; armed ones (either way) and
    suppressed ones are clean."""
    faults_dir = tmp_path / "tpubloom" / "faults"
    tests_dir = tmp_path / "tests"
    faults_dir.mkdir(parents=True)
    tests_dir.mkdir()
    # fabricated point names throughout: this file itself lives under
    # tests/, so REAL names here would satisfy the real tree's arming
    # scan and mask a deleted armed test (found by review)
    (faults_dir / "__init__.py").write_text(
        textwrap.dedent(
            """
            KNOWN_POINTS = {
                "zz.armed_by_call",
                "zz.armed_by_env",
                "zz.dead_point",
                "zz.covered_elsewhere",  # lint: allow(chaos-coverage): driven by the exporter's own harness, not pytest
            }
            """
        )
    )
    (tests_dir / "test_x.py").write_text(
        textwrap.dedent(
            """
            from tpubloom import faults

            def test_a(monkeypatch):
                faults.arm("zz.armed_by_call", "once")
                monkeypatch.setenv("TPUBLOOM_FAULTS", "zz.armed_by_env=nth:2")
            """
        )
    )
    findings = L.check_chaos_coverage(str(tmp_path))
    # raw check: the suppression resolves in lint_paths, so both
    # unarmed points surface here
    assert sorted(
        f.message.split("'")[1] for f in findings
    ) == ["zz.covered_elsewhere", "zz.dead_point"]
    assert all(f.line > 0 for f in findings)  # anchored on declarations
    config = L.LintConfig(
        **{**CONFIG_KW, "tree_checks": True, "repo_root": str(tmp_path)}
    )
    piped = L.lint_paths([str(faults_dir / "__init__.py")], config)
    by_check = [f for f in piped if f.check == "chaos-coverage"]
    assert len(by_check) == 1 and "zz.dead_point" in by_check[0].message


def test_chaos_coverage_credits_benchmark_arming(tmp_path):
    """ISSUE 15 satellite (the ROADMAP item 6 seam): a point armed only
    by a benchmark harness's TPUBLOOM_FAULTS string (or faults.arm)
    under benchmarks/ is covered, not dead surface."""
    faults_dir = tmp_path / "tpubloom" / "faults"
    tests_dir = tmp_path / "tests"
    bench_dir = tmp_path / "benchmarks"
    faults_dir.mkdir(parents=True)
    tests_dir.mkdir()
    bench_dir.mkdir()
    (faults_dir / "__init__.py").write_text(
        textwrap.dedent(
            """
            KNOWN_POINTS = {
                "zz.bench_env",
                "zz.bench_call",
                "zz.still_dead",
            }
            """
        )
    )
    (bench_dir / "load_harness.py").write_text(
        textwrap.dedent(
            """
            import os
            from tpubloom import faults

            def run():
                os.environ["TPUBLOOM_FAULTS"] = "zz.bench_env=nth:3"
                faults.arm("zz.bench_call", "once")
            """
        )
    )
    findings = L.check_chaos_coverage(str(tmp_path))
    assert [f.message.split("'")[1] for f in findings] == ["zz.still_dead"]
    assert "benchmark" in findings[0].message


def test_phase_registry_flags_undeclared_and_bad_dynamic(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        from tpubloom import obs

        def f(ctx, i):
            with obs.phase("kernel"):          # declared: clean
                pass
            with obs.phase("kernel_mystery"):  # not declared
                pass
            ctx.add_phase(f"kernel_shard{i}", 0.1)   # declared prefix: clean
            ctx.add_phase(f"mystery_shard{i}", 0.1)  # undeclared prefix
        """,
        phases=frozenset({"kernel"}),
        phase_prefixes=("kernel_shard",),
    )
    assert _checks(findings) == ["phase-registry"] * 2
    msgs = sorted(f.message for f in findings)
    assert "'mystery_shard'" in msgs[0]
    assert "'kernel_mystery'" in msgs[1]


def test_trace_registry_flags_undeclared_spans_and_events(tmp_path):
    """ISSUE 15: the phase-registry pattern extended to the tracing span
    vocabulary and the flight-recorder event vocabulary."""
    findings = _lint_source(
        tmp_path,
        """
        from tpubloom.obs import flight, trace

        def f(rid, method):
            with trace.span("good.span"):              # declared: clean
                pass
            with trace.span("mystery.span"):           # not declared
                pass
            trace.record_span(f"rpc.{method}", rid=rid,
                              start=0.0, duration_s=0.0)  # prefix: clean
            trace.record_span(f"zz.{method}", rid=rid,
                              start=0.0, duration_s=0.0)  # bad prefix
            flight.note("good_event", x=1)             # declared: clean
            flight.note("mystery_event")               # not declared
        """,
        spans=frozenset({"good.span"}),
        span_prefixes=("rpc.",),
        events=frozenset({"good_event"}),
    )
    assert _checks(findings) == ["trace-registry"] * 3
    msgs = " | ".join(sorted(f.message for f in findings))
    assert "'mystery.span'" in msgs
    assert "'zz.'" in msgs
    assert "'mystery_event'" in msgs


def test_trace_registry_reverse_check(tmp_path):
    """Tree mode: declared spans/prefixes/events nobody emits are stale
    vocabulary entries."""
    pkg = tmp_path / "tpubloom" / "obs"
    pkg.mkdir(parents=True)
    (pkg / "names.py").write_text(
        'SPANS = ("client.hop", "ghost.span")\n'
        'SPAN_DYNAMIC_PREFIXES = (("rpc.", "roots"), ("zz.", "ghost"),)\n'
        'EVENTS = ("shed", "ghost_event")\n'
    )
    src = tmp_path / "emit.py"
    src.write_text(
        textwrap.dedent(
            """
            from tpubloom.obs import flight, trace

            def f(rid, method):
                with trace.span("client.hop"):
                    pass
                trace.record_span(f"rpc.{method}", rid=rid,
                                  start=0.0, duration_s=0.0)
                flight.note("shed")
            """
        )
    )
    config = L.LintConfig(
        **{
            **{k: v for k, v in CONFIG_KW.items() if k != "tree_checks"},
            "tree_checks": True,
            "repo_root": str(tmp_path),
        }
    )
    findings = L.lint_paths([str(src)], config)
    tr = sorted(
        f.message for f in findings if f.check == "trace-registry"
    )
    assert len(tr) == 3
    assert "'ghost.span'" in tr[2] or "'ghost.span'" in " ".join(tr)
    assert any("'zz.'" in m for m in tr)
    assert any("'ghost_event'" in m for m in tr)


def test_phase_registry_reverse_check(tmp_path):
    """Tree mode: a declared phase nobody emits is a stale vocabulary
    entry (the counter-registry pattern extended to phases)."""
    pkg = tmp_path / "tpubloom" / "obs"
    pkg.mkdir(parents=True)
    (pkg / "names.py").write_text(
        'PHASES = ("decode", "ghost_phase")\n'
        'PHASE_DYNAMIC_PREFIXES = (("kernel_shard", "per-device"),)\n'
    )
    src = tmp_path / "emit.py"
    src.write_text(
        "def f(ctx, i):\n"
        '    with obs.phase("decode"):\n'
        "        pass\n"
        '    ctx.add_phase(f"kernel_shard{i}", 0.1)\n'
    )
    config = L.LintConfig(
        **{
            **{k: v for k, v in CONFIG_KW.items() if k != "tree_checks"},
            "tree_checks": True,
            "repo_root": str(tmp_path),
        }
    )
    findings = L.lint_paths([str(src)], config)
    phase_findings = [f for f in findings if f.check == "phase-registry"]
    assert len(phase_findings) == 1
    assert "'ghost_phase'" in phase_findings[0].message


def test_histogram_registry_closes_both_directions(tmp_path):
    """Tree mode: the histogram families the exposition renders are
    exactly ``names.HISTOGRAMS`` — a declared family nobody renders and
    a rendered family nobody declared are both findings."""
    pkg = tmp_path / "tpubloom" / "obs"
    pkg.mkdir(parents=True)
    (pkg / "names.py").write_text(
        'HISTOGRAMS = ("rpc_duration_seconds", "ghost_seconds")\n'
    )
    (pkg / "exposition.py").write_text(
        "def render(out, met, bounds):\n"
        '    _render_histogram(out, "rpc_duration_seconds", [], bounds, "x")\n'
        '    _render_histogram(out, "stray_seconds", [], bounds, "y")\n'
    )
    findings = L.check_histogram_registry(str(tmp_path))
    assert _checks(findings) == ["metric-registry"] * 2
    text = " ".join(f.message for f in findings)
    assert "'ghost_seconds'" in text and "'stray_seconds'" in text
    # a tree without the declaration (partial fixtures) is not checked
    (pkg / "names.py").write_text('COUNTERS = ("x",)\n')
    assert L.check_histogram_registry(str(tmp_path)) == []


def test_timed_hold_counts_as_holding_the_lock(tmp_path):
    """``with _timed_hold(x.lock):`` (the ingest flush's timed acquire)
    is a lock region like ``with x.lock:``."""
    findings = _lint_source(
        tmp_path,
        """
        import os

        class S:
            def bad_io(self, mf):
                with _timed_hold(mf.lock):
                    os.fsync(3)

            def ok(self, mf):
                with _timed_hold(mf.lock):
                    pass
                os.fsync(3)
        """,
    )
    assert _checks(findings) == ["blocking-under-lock"]


# -- static lint: the suppression grammar --------------------------------------


def test_reasoned_suppression_silences(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        import os

        class S:
            def allowed(self):
                with self._lock:
                    os.fsync(3)  # lint: allow(blocking-under-lock): fsync of a 12-byte marker; bounded and rare
        """,
    )
    assert findings == []


def test_suppression_on_with_line_covers_the_region(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        import os

        class S:
            def allowed(self):
                with self._lock:  # lint: allow(blocking-under-lock): the whole region is a cold shutdown path
                    os.fsync(3)
        """,
    )
    assert findings == []


def test_reasonless_suppression_is_a_finding(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        import os

        class S:
            def bad(self):
                with self._lock:
                    os.fsync(3)  # lint: allow(blocking-under-lock)
        """,
    )
    # the allow is VOID (no reason), so the original finding stands too
    assert _checks(findings) == ["blocking-under-lock", "suppression-reason"]


def test_unknown_and_unused_suppressions_are_findings(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        X = 1  # lint: allow(not-a-check): whatever
        Y = 2  # lint: allow(fault-registry): nothing here triggers it
        """,
    )
    assert _checks(findings) == ["unknown-suppression", "unused-suppression"]


def test_docstring_mention_is_not_a_suppression(tmp_path):
    findings = _lint_source(
        tmp_path,
        '''
        def doc():
            """Write `# lint: allow(blocking-under-lock): why` inline."""
        ''',
    )
    assert findings == []


# -- static lint: CLI exit codes ----------------------------------------------


def test_cli_flags_seeded_fixture(tmp_path):
    bad = tmp_path / "seeded.py"
    bad.write_text(
        "from tpubloom import faults\n"
        'faults.fire("totally.unknown.point")\n'
    )
    proc = subprocess.run(
        [sys.executable, "-m", "tpubloom.analysis.lint",
         "--no-tree-checks", "--json", str(bad)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    findings = json.loads(proc.stdout)
    assert [f["check"] for f in findings] == ["fault-registry"]


def test_ruff_gate():
    """Baseline style gate SCOPED to the analysis subsystem (the
    ``[tool.ruff]`` include in pyproject.toml): the new code starts
    clean. Config-only wiring in images without ruff — CI installs it
    via the ``dev`` extra and runs this for real."""
    pytest.importorskip("ruff")
    proc = subprocess.run(
        [sys.executable, "-m", "ruff", "check", "."],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_clean_on_the_real_tree():
    """THE acceptance gate: the shipped tree lints clean, suppressions
    included (a reasonless or stale allow fails this too)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tpubloom.analysis.lint",
         os.path.join(REPO, "tpubloom")],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -- runtime tracker: seeded violations ----------------------------------------


@pytest.fixture
def armed():
    locks.set_enabled(True)
    locks.reset()
    yield
    locks.reset()
    locks.set_enabled(None)


def test_lock_order_cycle_detected(armed):
    a = locks.named_lock("t.a")
    b = locks.named_lock("t.b")
    with a:
        with b:
            pass
    with b:
        with a:  # closes t.a -> t.b -> t.a
            pass
    vios = locks.violations()
    assert [v["kind"] for v in vios] == ["lock-order-cycle"]
    assert "t.a" in vios[0]["message"] and "t.b" in vios[0]["message"]


def test_consistent_order_is_clean(armed):
    a = locks.named_lock("t.a")
    b = locks.named_lock("t.b")
    for _ in range(3):
        with a:
            with b:
                pass
    assert locks.violations() == []
    rep = locks.report()
    assert [(e["from"], e["to"], e["count"]) for e in rep["edges"]] == [
        ("t.a", "t.b", 3)
    ]


def test_two_instances_same_name_is_a_cycle(armed):
    # every filter's op lock shares one NAME: nesting two instances is
    # the two-threads-opposite-order deadlock in single-threaded form
    f1 = locks.named_lock("t.filter_op")
    f2 = locks.named_lock("t.filter_op")
    with f1:
        with f2:
            pass
    vios = locks.violations()
    assert [v["kind"] for v in vios] == ["lock-order-cycle"]


def test_rlock_reentry_is_clean(armed):
    r = locks.named_rlock("t.r")
    with r:
        with r:
            pass
    assert locks.violations() == []


def test_cross_thread_cycle_detected(armed):
    # the real shape: two threads, opposite nesting orders, serialized
    # by events so the test itself cannot deadlock
    a = locks.named_lock("t.x")
    b = locks.named_lock("t.y")
    first_done = threading.Event()

    def t1():
        with a:
            with b:
                pass
        first_done.set()

    def t2():
        first_done.wait(5)
        with b:
            with a:
                pass

    th1, th2 = threading.Thread(target=t1), threading.Thread(target=t2)
    th1.start(); th2.start()
    th1.join(5); th2.join(5)
    assert [v["kind"] for v in locks.violations()] == ["lock-order-cycle"]


def test_condition_wait_under_foreign_lock_flagged(armed):
    lock = locks.named_lock("t.outer")
    cond = locks.named_condition("t.cond")
    with lock:
        with cond:
            cond.wait(timeout=0.01)
    vios = locks.violations()
    assert [v["kind"] for v in vios] == ["held-while-blocking"]
    assert "t.outer" in vios[0]["message"]


def test_wait_reports_once_despite_varying_timeouts(armed):
    # retry loops wait on a SHRINKING remaining budget; the violation
    # message must not embed the value or dedup is defeated and the
    # report floods (one entry per wakeup)
    lock = locks.named_lock("t.outer")
    cond = locks.named_condition("t.cond")
    with lock:
        with cond:
            cond.wait(timeout=0.01)
            cond.wait(timeout=0.02)
            # wait_for internally loops over self.wait() — the inner
            # dispatches must not re-report what wait_for checked
            cond.wait_for(lambda: False, timeout=0.03)
    assert len(locks.violations()) == 1, locks.violations()


def test_condition_wait_alone_is_clean(armed):
    cond = locks.named_condition("t.cond")
    with cond:
        cond.wait(timeout=0.01)
        cond.wait_for(lambda: True, timeout=0.01)
    assert locks.violations() == []


def test_note_blocking_under_lock_flagged(armed):
    lock = locks.named_lock("t.held")
    locks.note_blocking("t.op")  # no lock held: clean
    with lock:
        locks.note_blocking("t.op")
    vios = locks.violations()
    assert [v["kind"] for v in vios] == ["held-while-blocking"]
    assert "t.op" in vios[0]["message"]


def test_note_blocking_allowlist_needs_reason(armed):
    lock = locks.named_lock("t.held")
    with lock:
        locks.note_blocking(
            "t.op", allow=("t.held",), reason="cold path, nothing contends"
        )
    assert locks.violations() == []
    sup = locks.report()["suppressed"]
    assert len(sup) == 1 and sup[0]["reason"]
    with pytest.raises(ValueError, match="needs a reason"):
        locks.note_blocking("t.op", allow=("t.held",))


def test_violations_deduplicate(armed):
    a = locks.named_lock("t.a")
    b = locks.named_lock("t.b")
    with a:
        with b:
            pass
    for _ in range(50):
        with b:
            with a:
                pass
    assert len(locks.violations()) == 1  # a hot loop reports once


def test_disarmed_factories_return_bare_primitives():
    locks.set_enabled(False)
    try:
        bare = locks.named_lock("t.bare")
        assert type(bare).__module__ in ("_thread", "threading")
        assert not hasattr(bare, "name")
        # disarmed note_blocking is a no-op even under nothing
        locks.note_blocking("t.op", allow=("x",))  # reasonless allow: ignored
    finally:
        locks.set_enabled(None)


def test_subprocess_exit_report(tmp_path):
    """The chaos-suite plumbing: a child process armed via the env vars
    dumps a lockcheck-<pid>.json at exit; the seeded cycle is in it."""
    child = tmp_path / "child.py"
    child.write_text(
        textwrap.dedent(
            """
            from tpubloom.utils import locks

            a = locks.named_lock("child.a")
            b = locks.named_lock("child.b")
            with a:
                with b:
                    pass
            with b:
                with a:
                    pass
            """
        )
    )
    report_dir = tmp_path / "reports"
    env = {
        **os.environ,
        locks.ENV_VAR: "1",
        locks.REPORT_DIR_ENV: str(report_dir),
        "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    proc = subprocess.run(
        [sys.executable, str(child)], capture_output=True, text=True, env=env,
    )
    assert proc.returncode == 0, proc.stderr
    reports = list(report_dir.glob("lockcheck-*.json"))
    assert len(reports) == 1
    rep = json.loads(reports[0].read_text())
    kinds = [v["kind"] for v in rep["violations"]]
    assert kinds == ["lock-order-cycle"]
    assert "violation(s)" in proc.stderr  # printed to stderr too


# -- lock-order manifest (ISSUE 9 satellite, ROADMAP item 7) ------------------


def test_lock_order_manifest_diff():
    """An edge outside the declared manifest is a finding; declared
    edges are clean — the "new nesting is a reviewed decision" gate."""
    from tpubloom.analysis import lock_order

    assert lock_order.diff_edges([("filter.op", "repl.oplog")]) == []
    findings = lock_order.diff_edges(
        [("filter.op", "repl.oplog"), ("repl.oplog", "filter.op")]
    )
    assert len(findings) == 1
    assert findings[0]["kind"] == "undeclared-lock-edge"
    assert findings[0]["edge"] == ["repl.oplog", "filter.op"]
    # the report-dict form (the lockcheck-<pid>.json shape)
    report = {
        "edges": [
            {"from": "service.registry", "to": "repl.oplog", "count": 3},
            {"from": "obs.counters", "to": "service.registry", "count": 1},
        ],
        "violations": [],
        "suppressed": [],
    }
    findings = lock_order.check_report(report)
    assert [f["edge"] for f in findings] == [
        ["obs.counters", "service.registry"]
    ]


def test_lock_order_manifest_covers_cluster_ranks():
    """The ISSUE-9 seeding: the cluster lock class participates in the
    manifest — migration snapshots arm the dual-write under the filter
    lock, and cluster.state is otherwise a leaf."""
    from tpubloom.analysis import lock_order

    assert ("filter.op", "cluster.state") in lock_order.ALLOWED_EDGES
    assert ("cluster.state", "obs.counters") in lock_order.ALLOWED_EDGES
    # nothing is declared acquirable under cluster.state except the
    # counters bookkeeping — node→node RPCs must run lock-free
    inners = {
        inner for outer, inner in lock_order.ALLOWED_EDGES
        if outer == "cluster.state"
    }
    assert inners == {"obs.counters"}


def test_lock_order_cli(tmp_path, capsys):
    from tpubloom.analysis import lock_order

    clean = tmp_path / "lockcheck-1.json"
    clean.write_text(json.dumps({
        "edges": [{"from": "filter.op", "to": "repl.oplog", "count": 1}],
        "violations": [], "suppressed": [],
    }))
    assert lock_order.main([str(tmp_path)]) == 0
    capsys.readouterr()

    dirty = tmp_path / "lockcheck-2.json"
    dirty.write_text(json.dumps({
        "edges": [{"from": "repl.oplog", "to": "service.registry",
                   "count": 1}],
        "violations": [], "suppressed": [],
    }))
    assert lock_order.main([str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "undeclared-lock-edge" in out and "repl.oplog" in out

    assert lock_order.main(["--list"]) == 0
    listed = capsys.readouterr().out
    assert "filter.op -> repl.oplog" in listed


# -- unified driver (ISSUE 13 tentpole) ---------------------------------------


def test_unified_driver_clean_on_the_real_tree(tmp_path):
    """THE acceptance gate: ``python -m tpubloom.analysis`` exits 0 on
    the shipped tree with all checks enabled — static lint AND the
    lock-order diff over a (clean) runtime report."""
    report = tmp_path / "lockcheck-1.json"
    report.write_text(json.dumps({
        "edges": [{"from": "filter.op", "to": "repl.oplog", "count": 3}],
        "violations": [], "suppressed": [],
    }))
    proc = subprocess.run(
        [sys.executable, "-m", "tpubloom.analysis", "--json",
         "--reports", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    result = json.loads(proc.stdout)
    assert result["lint"] == [] and result["lock_order"] == []
    assert result["reports_checked"] == 1


def test_unified_driver_fails_on_undeclared_edge_or_violation(tmp_path):
    """One exit code covers BOTH halves: an undeclared runtime edge (or
    a recorded violation) in any collected report fails the driver even
    though the static tree is clean."""
    report = tmp_path / "lockcheck-2.json"
    report.write_text(json.dumps({
        "edges": [{"from": "repl.oplog", "to": "filter.op", "count": 1}],
        "violations": [
            {"kind": "lock-order-cycle", "message": "t.a -> t.b -> t.a",
             "site": "x.py:1"},
        ],
        "suppressed": [],
    }))
    proc = subprocess.run(
        [sys.executable, "-m", "tpubloom.analysis", "--json",
         "--reports", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    result = json.loads(proc.stdout)
    kinds = sorted(f["kind"] for f in result["lock_order"])
    assert kinds == ["runtime-lock-order-cycle", "undeclared-lock-edge"]


def test_unified_driver_explicit_empty_reports_is_a_finding(tmp_path):
    """CI wiring rot must not look like a pass: --reports pointing at a
    dir with no lockcheck files is itself a finding (while NO --reports
    and no env var runs the static half alone, exit 0 on clean)."""
    empty = tmp_path / "empty"
    empty.mkdir()
    proc = subprocess.run(
        [sys.executable, "-m", "tpubloom.analysis", "--json",
         "--reports", str(empty)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 1
    result = json.loads(proc.stdout)
    assert [f["kind"] for f in result["lock_order"]] == ["no-reports"]


def test_manifest_prune_left_no_speculative_selfcontradictions():
    """ISSUE 13 spot checks on the pruned manifest: the applier's call
    lock guards stream/ack HANDLES only (its old apply-path edges are
    gone), and the truncation sweep's replica-cursor floor IS declared
    (the latent hole the audit closed)."""
    from tpubloom.analysis import lock_order

    E = lock_order.ALLOWED_EDGES
    assert ("repl.applier_call", "repl.ack_sender") in E
    assert ("repl.applier_call", "filter.op") not in E
    assert ("repl.applier_call", "repl.oplog") not in E
    assert ("filter.op", "repl.sessions") in E  # min_cursor under _log_op
    assert ("filter.op", "obs.metrics") in E   # truncation count
    # pruned X->obs.counters family: counters moved outside these locks
    for outer in ("faults.registry", "obs.slowlog", "service.dedup",
                  "ckpt.trigger", "repl.monitor_hub", "sentinel.topo_events"):
        assert (outer, "obs.counters") not in E, outer
