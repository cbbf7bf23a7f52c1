"""jaxlint: the AST hazard analyzer that gates tier-1 (ISSUE 9).

Per-rule fixtures (violating / suppressed / fixed), suppression-reason
enforcement, baseline add/remove round-trip through the CLI, reporter
shape, and the smoke test that the REAL tree is clean — the property
``tools/check_markers.py`` stakes the tier-1 gate on.
"""
import json
import textwrap
from pathlib import Path

import pytest

from tools.jaxlint import (Linter, all_rule_ids, load_baseline, run,
                           render_json, render_text, save_baseline)
from tools.jaxlint.__main__ import main as jaxlint_main

pytestmark = pytest.mark.lint

REPO = Path(__file__).resolve().parent.parent

#: a relpath inside the declared hot-path set (host-sync fires only there)
HOT = "deeplearning4j_tpu/datavec/pipeline.py"
COLD = "deeplearning4j_tpu/zoo/models.py"


def lint(tmp_path, files, rules=None, baseline=None):
    """Write {relpath: source} under tmp_path and lint those files."""
    paths = []
    for rel, code in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(code), encoding="utf-8")
        paths.append(p)
    return Linter(tmp_path, rules=rules, baseline=baseline).run(paths)


def rule_ids(result):
    return sorted(f.rule for f in result.findings)


# ---------------------------------------------------------------- retrace --

class TestRetraceRules:
    def test_jit_in_loop_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import jax
            def fit(xs):
                for x in xs:
                    f = jax.jit(lambda a: a + 1)
                    f(x)
        """})
        assert rule_ids(res) == ["retrace-loop"]

    def test_jit_hoisted_out_of_loop_is_clean(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import jax
            def fit(xs):
                f = jax.jit(lambda a: a + 1)
                for x in xs:
                    f(x)
        """})
        assert res.findings == []

    def test_jit_in_loop_suppressed_with_reason(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import jax
            def fit(layers, xs):
                for ly in layers:
                    # jaxlint: disable=retrace-loop -- one executable per layer by design
                    f = jax.jit(lambda a: a + ly)
                    for x in xs:
                        f(x)
        """})
        assert res.findings == []
        assert [f.rule for f in res.suppressed] == ["retrace-loop"]

    def test_immediately_invoked_jit_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import jax
            def init():
                return jax.jit(lambda: {"w": 0})()
        """})
        assert "retrace-closure" in rule_ids(res)

    def test_bound_jit_of_lambda_is_clean(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import jax
            class Net:
                def build(self):
                    self._fn = jax.jit(lambda a: a * 2)
        """})
        assert res.findings == []

    def test_from_jax_import_jit_alias_detected(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            from jax import jit
            def f(xs):
                for x in xs:
                    jit(lambda a: a)(x)
        """})
        assert set(rule_ids(res)) == {"retrace-loop", "retrace-closure"}

    def test_static_args_missing_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import jax
            def make():
                def step(x, training=True, mode="fast"):
                    return x
                return jax.jit(step)
        """})
        assert rule_ids(res) == ["retrace-static-args"]
        assert "'training'" in res.findings[0].message
        assert "'mode'" in res.findings[0].message

    def test_static_args_declared_is_clean(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import jax
            def make():
                def step(x, training=True, mode="fast"):
                    return x
                return jax.jit(step,
                               static_argnames=("training", "mode"))
        """})
        assert res.findings == []

    def test_static_args_decorator_form_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import jax
            @jax.jit
            def step(x, causal=False):
                return x
        """})
        assert rule_ids(res) == ["retrace-static-args"]

    def test_partial_jit_decorator_with_static_is_clean(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import functools
            import jax
            @functools.partial(jax.jit, static_argnames=("causal",))
            def step(x, causal=False):
                return x
        """})
        assert res.findings == []


# --------------------------------------------------------------- host-sync --

class TestHostSyncRule:
    def test_sync_in_hot_module_fires(self, tmp_path):
        res = lint(tmp_path, {HOT: """
            def consume(batch):
                return batch.block_until_ready()
        """})
        assert rule_ids(res) == ["host-sync"]

    def test_same_code_in_cold_module_is_clean(self, tmp_path):
        res = lint(tmp_path, {COLD: """
            def consume(batch):
                return batch.block_until_ready()
        """})
        assert res.findings == []

    def test_sync_ok_annotation_suppresses(self, tmp_path):
        res = lint(tmp_path, {HOT: """
            def consume(batch):
                # jaxlint: sync-ok -- the fence IS the H2D completion point
                return batch.block_until_ready()
        """})
        assert res.findings == []
        assert [f.rule for f in res.suppressed] == ["host-sync"]

    def test_item_numpy_asarray_float_all_fire(self, tmp_path):
        res = lint(tmp_path, {HOT: """
            import numpy as np
            def step(loss, out):
                a = loss.item()
                b = out.numpy()
                c = np.asarray(out)
                d = float(loss)
                return a, b, c, d
        """})
        assert rule_ids(res) == ["host-sync"] * 4

    def test_ctor_scalar_coercion_is_clean(self, tmp_path):
        res = lint(tmp_path, {HOT: """
            class Cfg:
                def __init__(self, batch, timeout):
                    self.batch = int(batch)
                    self.timeout = float(timeout)
        """})
        assert res.findings == []


# ------------------------------------------------------------------- locks --

class TestLockRules:
    def test_opposite_order_cycle_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import threading
            a = threading.Lock()
            b = threading.Lock()
            def ab():
                with a:
                    with b:
                        pass
            def ba():
                with b:
                    with a:
                        pass
        """}, rules=["lock-order"])
        assert rule_ids(res) == ["lock-order", "lock-order"]

    def test_consistent_order_is_clean(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import threading
            a = threading.Lock()
            b = threading.Lock()
            def one():
                with a:
                    with b:
                        pass
            def two():
                with a:
                    with b:
                        pass
        """}, rules=["lock-order"])
        assert res.findings == []

    def test_interprocedural_self_deadlock_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import threading
            class R:
                def __init__(self):
                    self._lock = threading.Lock()
                def outer(self):
                    with self._lock:
                        self.inner()
                def inner(self):
                    with self._lock:
                        pass
        """}, rules=["lock-order"])
        assert rule_ids(res) == ["lock-order"]
        assert "not reentrant" in res.findings[0].message

    def test_cross_module_cycle_through_import_fires(self, tmp_path):
        res = lint(tmp_path, {
            "pkg/reg.py": """
                import threading
                reg_lock = threading.Lock()
                def record():
                    with reg_lock:
                        pass
            """,
            "pkg/sched.py": """
                import threading
                from pkg.reg import record
                sched_lock = threading.Lock()
                def tick():
                    with sched_lock:
                        record()
            """,
            "pkg/reg2.py": """
                import threading
                from pkg.reg import reg_lock
                from pkg.sched2 import poke
                def expose():
                    with reg_lock:
                        poke()
            """,
            "pkg/sched2.py": """
                import threading
                from pkg.sched import sched_lock
                def poke():
                    with sched_lock:
                        pass
            """,
        }, rules=["lock-order"])
        # sched_lock -> reg_lock (tick) and reg_lock -> sched_lock
        # (expose): a cross-module order cycle
        assert "lock-order" in rule_ids(res)

    def test_blocking_calls_under_lock_fire(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import threading
            import time
            lock = threading.Lock()
            def f(q, t):
                with lock:
                    time.sleep(0.5)
                    q.get()
                    t.join()
        """}, rules=["lock-blocking-call"])
        assert rule_ids(res) == ["lock-blocking-call"] * 3

    def test_timed_get_and_held_cv_wait_are_clean(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import threading
            class W:
                def __init__(self):
                    self._cv = threading.Condition()
                def loop(self, q):
                    with self._cv:
                        self._cv.wait()      # releases the held cv
                        q.get(timeout=0.2)
        """}, rules=["lock-blocking-call"])
        assert res.findings == []

    def test_sleep_outside_lock_is_clean(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import threading
            import time
            lock = threading.Lock()
            def f():
                with lock:
                    pass
                time.sleep(0.1)
        """}, rules=["lock-blocking-call"])
        assert res.findings == []

    def test_blocking_under_lock_suppressed_with_reason(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import threading
            import time
            lock = threading.Lock()
            def f():
                with lock:
                    # jaxlint: disable=lock-blocking-call -- startup-only path, no contention
                    time.sleep(0.01)
        """}, rules=["lock-blocking-call"])
        assert res.findings == []
        assert len(res.suppressed) == 1


# ----------------------------------------------------------------- threads --

class TestThreadRules:
    def test_missing_daemon_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import threading
            def go(fn):
                threading.Thread(target=fn).start()
        """}, rules=["thread-daemon"])
        assert rule_ids(res) == ["thread-daemon"]

    def test_daemon_kwarg_is_clean(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import threading
            def go(fn):
                threading.Thread(target=fn, daemon=True).start()
        """}, rules=["thread-daemon"])
        assert res.findings == []

    def test_daemon_attribute_fixup_is_clean(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import threading
            def go(fn):
                t = threading.Thread(target=fn)
                t.daemon = True
                t.start()
        """}, rules=["thread-daemon"])
        assert res.findings == []

    def test_stored_never_joined_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import threading
            class Server:
                def start(self, fn):
                    self._thread = threading.Thread(target=fn, daemon=True)
                    self._thread.start()
                def stop(self):
                    pass
        """}, rules=["thread-join"])
        assert rule_ids(res) == ["thread-join"]

    def test_joined_on_stop_is_clean(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import threading
            class Server:
                def start(self, fn):
                    self._thread = threading.Thread(target=fn, daemon=True)
                    self._thread.start()
                def stop(self):
                    self._thread.join(timeout=5.0)
        """}, rules=["thread-join"])
        assert res.findings == []

    def test_join_through_alias_and_pool_loop_is_clean(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import threading
            class Pool:
                def start(self, fn, n):
                    self._threads = []
                    for _ in range(n):
                        t = threading.Thread(target=fn, daemon=True)
                        t.start()
                        self._threads.append(t)
                def stop(self):
                    for t in self._threads:
                        t.join(timeout=5.0)
        """}, rules=["thread-join"])
        assert res.findings == []


# --------------------------------------------------------------- telemetry --

class TestTelemetryRules:
    def test_every_convention_violation_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            def instrument(reg):
                reg.counter("badname", "help text")
                reg.counter("dl4j_tpu_x_requests", "help text")
                reg.gauge("dl4j_tpu_x_depth_total", "help text")
                reg.histogram("dl4j_tpu_x_latency", "help text")
                reg.histogram("dl4j_tpu_x_wait_seconds", "help text")
                reg.gauge("dl4j_tpu_x_queue_depth")
                reg.gauge("dl4j_tpu_x_other_depth", "")
        """})
        got = rule_ids(res)
        assert got == sorted(["telemetry-name", "telemetry-counter-total",
                              "telemetry-unit", "telemetry-unit",
                              "telemetry-buckets", "telemetry-help",
                              "telemetry-help"])

    def test_compliant_registrations_are_clean(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            def instrument(reg):
                reg.counter("dl4j_tpu_x_requests_total", "requests")
                reg.gauge("dl4j_tpu_x_queue_depth", "rows queued")
                reg.histogram("dl4j_tpu_x_wait_seconds", "wait",
                              buckets=(0.1, 1.0))
                reg.counter("dl4j_tpu_x_moved_bytes_total", "bytes moved")
        """})
        assert res.findings == []

    def test_positional_tuple_where_help_belongs_fires(self, tmp_path):
        # the regex linter flagged positional tuples/lists as missing
        # help; the AST re-base must not loosen that
        res = lint(tmp_path, {"m.py": """
            def f(reg):
                reg.gauge("dl4j_tpu_x_state", ("rule",))
        """})
        assert rule_ids(res) == ["telemetry-help"]

    def test_duplicate_module_registration_fires(self, tmp_path):
        res = lint(tmp_path, {
            "a.py": """
                def f(reg):
                    reg.counter("dl4j_tpu_x_events_total", "events")
            """,
            "b.py": """
                def g(reg):
                    reg.counter("dl4j_tpu_x_events_total", "events")
            """,
        })
        assert rule_ids(res) == ["telemetry-dup-module"] * 2

    def test_telemetry_violation_suppressible_with_reason(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            def instrument(reg):
                # jaxlint: disable=telemetry-buckets -- bounds injected by the caller's config
                reg.histogram("dl4j_tpu_x_wait_seconds", "wait")
        """})
        assert res.findings == []
        assert [f.rule for f in res.suppressed] == ["telemetry-buckets"]


class TestTimelineEventNameRule:
    def test_bad_shape_kind_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            from deeplearning4j_tpu.telemetry.runlog import record_event

            def f():
                record_event("Ckpt Save", step=3)
        """})
        assert rule_ids(res) == ["timeline-event-name"]

    def test_out_of_vocabulary_kind_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            def f(self):
                self.timeline.record("ckpt.sealed", generation=2)
        """})
        assert rule_ids(res) == ["timeline-event-name"]

    def test_vocabulary_kinds_pass(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            from deeplearning4j_tpu.telemetry.runlog import record_event

            def f(self, tl):
                record_event("train.step", step=7)
                self.timeline.record("coord.barrier", generation=1)
                tl.record("elastic.shrink")
        """})
        assert res.findings == []

    def test_non_literal_kind_accepted(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            def f(self, kind):
                self.timeline.record(kind, step=1)
        """})
        assert res.findings == []

    def test_unrelated_record_apis_ignored(self, tmp_path):
        # FlightRecorder-style .record and file opens are out of scope
        res = lint(tmp_path, {"m.py": """
            def f(recorder, path):
                recorder.record("whatever I want", detail=1)
                open(path, "a")
        """})
        assert res.findings == []

    def test_suppressible_with_reason(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            def f(tl):
                # jaxlint: disable=timeline-event-name -- experimental kind behind a flag
                tl.record("debug.probe")
        """})
        assert res.findings == []
        assert [f.rule for f in res.suppressed] == ["timeline-event-name"]


# ----------------------------------------------- suppression enforcement --

class TestSuppressionEnforcement:
    def test_reasonless_suppression_raises_bad_suppression(self, tmp_path):
        res = lint(tmp_path, {HOT: """
            def consume(batch):
                # jaxlint: disable=host-sync
                return batch.block_until_ready()
        """})
        # the target IS silenced, but silencing without a reason is
        # itself a finding — the run still fails
        assert rule_ids(res) == ["bad-suppression"]
        assert "no reason" in res.findings[0].message
        assert [f.rule for f in res.suppressed] == ["host-sync"]

    def test_unknown_rule_in_suppression_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            x = 1  # jaxlint: disable=no-such-rule -- because
        """})
        assert rule_ids(res) == ["bad-suppression"]
        assert "unknown rule" in res.findings[0].message

    def test_bad_suppression_cannot_be_suppressed(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            # jaxlint: disable=bad-suppression -- trying to silence the police
            x = 1
        """})
        assert "bad-suppression" in rule_ids(res)

    def test_unparseable_pragma_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            x = 1  # jaxlint: disablee=host-sync -- typo'd directive
        """})
        assert rule_ids(res) == ["bad-suppression"]

    def test_pending_pragma_does_not_leak_past_inline_pragma(self,
                                                             tmp_path):
        # a comment-line pragma is consumed by the NEXT code line even
        # when that line carries its own inline pragma — leaking past it
        # would silently suppress the following unrelated hazard
        res = lint(tmp_path, {HOT: """
            def f(a, b):
                # jaxlint: sync-ok -- covers a only
                x = a.item()  # jaxlint: disable=host-sync -- inline too
                y = b.item()
                return x, y
        """})
        assert rule_ids(res) == ["host-sync"]
        assert res.findings[0].line == 5       # b.item() stays flagged

    def test_same_line_and_line_above_both_attach(self, tmp_path):
        res = lint(tmp_path, {HOT: """
            def f(a, b):
                x = a.item()  # jaxlint: sync-ok -- same-line form
                # jaxlint: sync-ok -- line-above form
                y = b.item()
                return x, y
        """})
        assert res.findings == []
        assert len(res.suppressed) == 2


# ---------------------------------------------------------------- baseline --

class TestBaseline:
    VIOLATING = """
        import threading
        def go(fn):
            threading.Thread(target=fn).start()
    """

    def test_cli_baseline_roundtrip(self, tmp_path, capsys):
        f = tmp_path / "m.py"
        f.write_text(textwrap.dedent(self.VIOLATING), encoding="utf-8")
        bl = tmp_path / "baseline.json"
        # violating + no baseline -> fail
        assert jaxlint_main([str(f), "--baseline", str(bl)]) == 1
        # grandfather it
        assert jaxlint_main([str(f), "--baseline", str(bl),
                             "--baseline-update"]) == 0
        entries = load_baseline(bl)
        assert sum(entries.values()) == 1
        # now clean under the baseline
        assert jaxlint_main([str(f), "--baseline", str(bl)]) == 0
        # --no-baseline still shows it
        assert jaxlint_main([str(f), "--baseline", str(bl),
                             "--no-baseline"]) == 1
        # fix the code: run stays clean but reports the stale entry...
        f.write_text(textwrap.dedent("""
            import threading
            def go(fn):
                threading.Thread(target=fn, daemon=True).start()
        """), encoding="utf-8")
        capsys.readouterr()
        assert jaxlint_main([str(f), "--baseline", str(bl)]) == 0
        assert "stale" in capsys.readouterr().out
        # ...and --baseline-update prunes it
        assert jaxlint_main([str(f), "--baseline", str(bl),
                             "--baseline-update"]) == 0
        assert sum(load_baseline(bl).values()) == 0

    def test_filtered_update_preserves_out_of_scope_entries(self,
                                                            tmp_path):
        # a path-filtered --baseline-update only owns what it scanned:
        # grandfathered entries for other files must survive verbatim
        a = tmp_path / "a.py"
        b = tmp_path / "b.py"
        for f in (a, b):
            f.write_text(textwrap.dedent(self.VIOLATING),
                         encoding="utf-8")
        bl = tmp_path / "bl.json"
        assert jaxlint_main([str(a), str(b), "--baseline", str(bl),
                             "--baseline-update"]) == 0
        assert sum(load_baseline(bl).values()) == 2
        # update over a ONLY (a now clean): b's entry must be preserved
        a.write_text("x = 1\n", encoding="utf-8")
        assert jaxlint_main([str(a), "--baseline", str(bl),
                             "--baseline-update"]) == 0
        remaining = load_baseline(bl)
        assert sum(remaining.values()) == 1
        assert all(key[1].endswith("b.py") for key in remaining)
        # a rules-filtered update must not touch entries of other rules
        assert jaxlint_main([str(b), "--baseline", str(bl),
                             "--rules", "host-sync",
                             "--baseline-update"]) == 0
        assert sum(load_baseline(bl).values()) == 1

    def test_baseline_keys_survive_line_drift(self, tmp_path):
        files = {"m.py": self.VIOLATING}
        res = lint(tmp_path, files)
        bl = tmp_path / "bl.json"
        save_baseline(bl, res.findings)
        drifted = "# a new comment pushing every line down\n" + \
            textwrap.dedent(self.VIOLATING)
        (tmp_path / "m.py").write_text(drifted, encoding="utf-8")
        res2 = Linter(tmp_path, baseline=load_baseline(bl)).run(
            [tmp_path / "m.py"])
        assert res2.findings == []
        assert len(res2.baselined) == 1

    def test_meta_findings_never_baselined(self, tmp_path, capsys):
        f = tmp_path / "m.py"
        f.write_text("x = 1  # jaxlint: disable=host-sync\n",
                     encoding="utf-8")
        bl = tmp_path / "bl.json"
        rc = jaxlint_main([str(f), "--baseline", str(bl),
                           "--baseline-update"])
        assert rc == 1
        assert "not baselineable" in capsys.readouterr().err
        assert sum(load_baseline(bl).values()) == 0


# ----------------------------------------------------------- CLI/reporters --

class TestCliAndReporters:
    def test_json_reporter_shape(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import threading
            def go(fn):
                threading.Thread(target=fn).start()
        """})
        doc = render_json(res)
        assert doc["exit_code"] == 1
        (finding,) = doc["findings"]
        assert finding["rule"] == "thread-daemon"
        assert finding["line"] == 4
        assert finding["context"].startswith("threading.Thread")
        json.dumps(doc)     # must be serializable as-is

    def test_text_reporter_mentions_counts(self, tmp_path):
        res = lint(tmp_path, {"m.py": "x = 1\n"})
        out = render_text(res)
        assert "jaxlint: OK" in out

    def test_cli_json_flag(self, tmp_path, capsys):
        f = tmp_path / "m.py"
        f.write_text("x = 1\n", encoding="utf-8")
        assert jaxlint_main([str(f), "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["exit_code"] == 0

    def test_cli_path_filter_and_rules_filter(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent("""
            import threading
            def go(fn):
                threading.Thread(target=fn).start()
        """), encoding="utf-8")
        ok = tmp_path / "ok.py"
        ok.write_text("x = 1\n", encoding="utf-8")
        assert jaxlint_main([str(ok), "--no-baseline"]) == 0
        assert jaxlint_main([str(bad), "--no-baseline"]) == 1
        # filtering to an unrelated rule silences the thread finding
        assert jaxlint_main([str(bad), "--no-baseline",
                             "--rules", "host-sync"]) == 0

    def test_cli_unknown_rule_is_usage_error(self, tmp_path):
        f = tmp_path / "m.py"
        f.write_text("x = 1\n", encoding="utf-8")
        assert jaxlint_main([str(f), "--rules", "nope"]) == 2

    def test_cli_missing_path_is_usage_error(self, tmp_path):
        assert jaxlint_main([str(tmp_path / "absent.py")]) == 2

    def test_list_rules_covers_shipped_set(self, capsys):
        assert jaxlint_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("retrace-loop", "retrace-closure",
                    "retrace-static-args", "host-sync", "lock-order",
                    "lock-blocking-call", "thread-daemon", "thread-join",
                    "telemetry-name", "telemetry-dup-module",
                    "donation-use-after", "resource-leak",
                    "tracer-escape", "metric-cardinality"):
            assert rid in out

    def test_parse_error_is_a_finding(self, tmp_path):
        res = lint(tmp_path, {"m.py": "def broken(:\n"})
        assert rule_ids(res) == ["parse-error"]


# ------------------------------------------------------------- smoke gate --

class TestRealTree:
    def test_repo_is_clean(self):
        """THE acceptance property: the shipped tree has zero
        unsuppressed findings under the committed baseline, every
        suppression carries a reason (a reasonless one would be a
        bad-suppression finding), and the committed baseline has no
        stale entries."""
        result = run()      # defaults: deeplearning4j_tpu + baseline
        assert result.findings == [], render_text(result)
        assert result.stale_baseline == []
        assert result.files_scanned > 100
        # the sweep is real: the tree carries reasoned suppressions,
        # and the legacy params()/setParams() flatten syncs that used
        # to ride the baseline are FIXED (device-resident views) — the
        # grandfathered baseline is burned down to empty and must stay
        # there (new code gets fixed or a reasoned suppression)
        assert len(result.suppressed) >= 30
        assert len(result.baselined) == 0

    def test_all_rule_ids_registered(self):
        ids = all_rule_ids()
        for rid in ("retrace-loop", "retrace-closure",
                    "retrace-static-args", "host-sync", "lock-order",
                    "lock-blocking-call", "thread-daemon", "thread-join",
                    "telemetry-name", "telemetry-buckets",
                    "telemetry-counter-total", "telemetry-unit",
                    "telemetry-help", "telemetry-dup-module",
                    "donation-use-after", "resource-leak",
                    "tracer-escape", "metric-cardinality"):
            assert rid in ids

    def test_check_markers_requires_lint_marker(self):
        import importlib
        import sys
        sys.path.insert(0, str(REPO / "tools"))
        try:
            cm = importlib.import_module("check_markers")
        finally:
            sys.path.pop(0)
        assert "lint" in cm.REQUIRED


# ---------------------------------------------------------------- dataflow --

class TestDataflowEngine:
    """The CFG/def-use engine itself (tools/jaxlint/dataflow.py)."""

    @staticmethod
    def _cfg(code):
        import ast as _ast
        from tools.jaxlint import dataflow as df
        fn = _ast.parse(textwrap.dedent(code)).body[0]
        return df, df.build_cfg(fn)

    def test_if_else_assignments_join_at_use(self):
        df, cfg = self._cfg("""
            def f(c, x):
                if c:
                    y = x
                else:
                    y = 2
                return y
        """)
        sites = set()

        def transfer(state, ev, _b):
            if ev.kind == df.ASSIGN and ev.text == "y":
                state["y"] = frozenset({ev.node.lineno})
            elif ev.kind == df.USE and ev.text == "y":
                sites.update(state.get("y", ()))

        df.run_forward(cfg, transfer)
        # BOTH branch definitions reach the return's read of y
        assert len(sites) == 2

    def test_loop_back_edge_joins_header(self):
        df, cfg = self._cfg("""
            def f(xs):
                acc = 0
                for x in xs:
                    acc = acc + x
                return acc
        """)
        sites = set()

        def transfer(state, ev, _b):
            if ev.kind == df.ASSIGN and ev.text == "acc":
                state["acc"] = frozenset({ev.node.lineno})
            elif ev.kind == df.USE and ev.text == "acc":
                sites.update(state.get("acc", ()))

        df.run_forward(cfg, transfer)
        # the body's read of acc sees the init AND the back-edge def
        assert len(sites) == 2

    def test_exception_edge_leaves_mid_statement(self):
        # the PR 15 hazard ordering: a `a, b = f(a, b)` inside try
        # raises AFTER f consumed the args but BEFORE the targets are
        # rebound — the handler must see the pre-assignment state
        df, cfg = self._cfg("""
            def f(self, x):
                try:
                    a = work(x)
                except Exception:
                    rescue()
        """)
        handler_state = {}

        def transfer(state, ev, _b):
            if ev.kind == df.CALL and df.expr_text(ev.node.func) == "work":
                state["called"] = frozenset({1})
            elif ev.kind == df.ASSIGN and ev.text == "a":
                state.pop("called", None)
            elif ev.kind == df.CALL and \
                    df.expr_text(ev.node.func) == "rescue":
                handler_state.update(state)

        df.run_forward(cfg, transfer)
        # in the handler the call HAS happened, the assignment has NOT
        assert "called" in handler_state


# -------------------------------------------------------- donation-use-after --

class TestDonationUseAfter:
    def test_read_after_donating_call_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import jax
            def step(p, x):
                return p
            def fit(p, x):
                f = jax.jit(step, donate_argnums=(0,))
                out = f(p, x)
                return p + out
        """}, rules=["donation-use-after"])
        assert rule_ids(res) == ["donation-use-after"]
        assert "'p'" in res.findings[0].message

    def test_rebinding_the_result_is_clean(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import jax
            def step(p, x):
                return p
            def fit(p, xs):
                f = jax.jit(step, donate_argnums=(0,))
                for x in xs:
                    p = f(p, x)
                return p
        """}, rules=["donation-use-after"])
        assert res.findings == []

    def test_donate_argnames_resolved_through_signature(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import jax
            def step(params, batch):
                return params
            def fit(p, x):
                f = jax.jit(step, donate_argnames=("params",))
                out = f(p, x)
                return p
        """}, rules=["donation-use-after"])
        assert rule_ids(res) == ["donation-use-after"]

    def test_except_edge_reuse_fires_normal_path_clean(self, tmp_path):
        # the PR 15 shape, inline: the tuple rebind never happened on
        # the exception edge, so the handler's read sees consumed pools
        files = {"m.py": """
            import jax
            class B:
                def build(self, step):
                    self.stepFn = jax.jit(step, donate_argnums=(0, 1))
                def loop(self, tok):
                    try:
                        self.poolK, self.poolV = self.stepFn(
                            self.poolK, self.poolV)
                    except Exception:
                        return self.poolK
                    return tok
        """}
        res = lint(tmp_path, files, rules=["donation-use-after"])
        assert rule_ids(res) == ["donation-use-after"]
        assert "self.poolK" in res.findings[0].message
        # drop the handler read: the tuple rebind kills on the normal
        # path and nothing reads on the exception edge
        clean = files["m.py"].replace("return self.poolK", "raise")
        res2 = lint(tmp_path, {"n.py": clean},
                    rules=["donation-use-after"])
        assert res2.findings == []

    def test_failbatch_helper_buggy_flagged_fixed_passes(self, tmp_path):
        # interprocedural: the handler delegates to a helper; the buggy
        # helper reads the donated pool, the fixed one rebuilds first
        res = lint(tmp_path, {"m.py": """
            import jax
            def buildPagedDecodeFn():
                def step(k, v, tok):
                    return k, v, tok
                return jax.jit(step, donate_argnums=(0, 1))
            class Batcher:
                def __init__(self):
                    self.stepFn = buildPagedDecodeFn()
                def _buildPools(self):
                    self.poolK = alloc()
                    self.poolV = alloc()
                def _failBatchBad(self, e):
                    print(self.poolK)
                def _failBatchGood(self, e):
                    self._buildPools()
                    print(self.poolK)
                def loop_bad(self, tok):
                    try:
                        self.poolK, self.poolV, out = self.stepFn(
                            self.poolK, self.poolV, tok)
                    except Exception as e:
                        self._failBatchBad(e)
                def loop_good(self, tok):
                    try:
                        self.poolK, self.poolV, out = self.stepFn(
                            self.poolK, self.poolV, tok)
                    except Exception as e:
                        self._failBatchGood(e)
        """}, rules=["donation-use-after"])
        assert rule_ids(res) == ["donation-use-after"]
        f = res.findings[0]
        assert "_failBatchBad" in f.message
        # the finding anchors in loop_bad's handler, not loop_good
        assert "self._failBatchBad(e)" in f.context

    def test_a_run_of_donated_positions_reaches_a_starred_argument(
            self, tmp_path):
        # ServedLM's builders donate ``tuple(range(1, 1 + n))``: the
        # run's literal start is what the starred call site needs
        res = lint(tmp_path, {"m.py": """
            import jax
            class Served:
                def buildPagedDecodeFn(self):
                    n = len(self.kinds)
                    def step(params, *args):
                        return args[:n]
                    return jax.jit(step,
                                   donate_argnums=tuple(range(1, 1 + n)))
            class Batcher:
                def __init__(self, lm):
                    self.stepFn = lm.buildPagedDecodeFn()
                def loop(self, params, tok):
                    try:
                        out, *self.arrays = self.stepFn(
                            params, *self.arrays, tok)
                    except Exception:
                        return self.arrays
                    return params
        """}, rules=["donation-use-after"])
        assert rule_ids(res) == ["donation-use-after"]
        assert "self.arrays" in res.findings[0].message

    def test_aotdispatch_wrapper_preserves_donation(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import jax
            def makeStep(step):
                return AotDispatch(jax.jit(step, donate_argnums=(0,)))
            class T:
                def build(self, step):
                    self.fn = makeStep(step)
                def go(self, p):
                    out = self.fn(p)
                    return p
        """}, rules=["donation-use-after"])
        assert rule_ids(res) == ["donation-use-after"]

    def test_suppression_and_baseline_roundtrip(self, tmp_path):
        bad = """
            import jax
            def step(p):
                return p
            def fit(p):
                f = jax.jit(step, donate_argnums=(0,))
                out = f(p)
                return p
        """
        res = lint(tmp_path, {"m.py": bad}, rules=["donation-use-after"])
        assert rule_ids(res) == ["donation-use-after"]
        bl = tmp_path / "bl.json"
        save_baseline(bl, res.findings)
        res2 = lint(tmp_path, {"m.py": bad}, rules=["donation-use-after"],
                    baseline=load_baseline(bl))
        assert res2.findings == [] and len(res2.baselined) == 1
        res3 = lint(tmp_path, {"n.py": """
            import jax
            def step(p):
                return p
            def fit(p):
                f = jax.jit(step, donate_argnums=(0,))
                out = f(p)
                # jaxlint: disable=donation-use-after -- fixture: buffer provably survives
                return p
        """}, rules=["donation-use-after"])
        assert res3.findings == []
        assert [f.rule for f in res3.suppressed] == ["donation-use-after"]

    def test_orbax_restore_aot_donate_path_clean(self):
        # satellite: the PR 13 fix (_refreshForAot rebuys XLA-owned
        # buffers before the AOT cache can donate restored aliases)
        # keeps the restore path clean under the new rule
        res = run(paths=[REPO / "deeplearning4j_tpu/utils/"
                                "sharded_checkpoint.py"],
                  root=REPO, rules=["donation-use-after"])
        assert res.findings == []

    def test_meshtrainer_donated_reshard_is_reason_suppressed(self):
        res = run(paths=[REPO / "deeplearning4j_tpu/parallel/"
                                "meshtrainer.py"],
                  root=REPO, rules=["donation-use-after"])
        assert res.findings == []
        assert any(f.rule == "donation-use-after"
                   for f in res.suppressed)

    def test_train_step_state_refresh_is_reason_suppressed(self):
        for rel in ("deeplearning4j_tpu/models/multilayer.py",
                    "deeplearning4j_tpu/models/graph.py"):
            res = run(paths=[REPO / rel], root=REPO,
                      rules=["donation-use-after"])
            assert res.findings == [], rel
            assert any(f.rule == "donation-use-after"
                       for f in res.suppressed), rel


# ------------------------------------------------------------ resource-leak --

class TestResourceLeak:
    def test_slot_dropped_on_early_return_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            class Pool:
                def admit(self, seq):
                    slot = self._freeSlots.get()
                    if seq.bad:
                        return None
                    self._active[seq.sid] = slot
                    return slot
        """}, rules=["resource-leak"])
        assert rule_ids(res) == ["resource-leak"]
        assert "'slot'" in res.findings[0].message

    def test_try_finally_release_is_clean(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            class Pool:
                def admit(self, seq):
                    slot = self._freeSlots.get()
                    try:
                        if seq.bad:
                            return None
                        self._active[seq.sid] = slot
                        return seq.sid
                    finally:
                        self._freeSlots.put(slot)
        """}, rules=["resource-leak"])
        assert res.findings == []

    def test_pool_ensure_without_release_on_branch_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            class KV:
                def grab(self, h, n):
                    self.kvPool.ensure(h, n)
                    if n == 0:
                        return
                    self.kvPool.release(h)
        """}, rules=["resource-leak"])
        assert rule_ids(res) == ["resource-leak"]

    def test_handoff_to_owner_field_is_clean(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            class KV:
                def grab(self, h, n):
                    self.kvPool.ensure(h, n)
                    self.owned[h.sid] = h
        """}, rules=["resource-leak"])
        assert res.findings == []

    def test_suppression_and_baseline_roundtrip(self, tmp_path):
        bad = """
            class Pool:
                def admit(self, seq):
                    slot = self._freeSlots.get()
                    if seq.bad:
                        return None
                    return slot
        """
        res = lint(tmp_path, {"m.py": bad}, rules=["resource-leak"])
        assert rule_ids(res) == ["resource-leak"]
        bl = tmp_path / "bl.json"
        save_baseline(bl, res.findings)
        res2 = lint(tmp_path, {"m.py": bad}, rules=["resource-leak"],
                    baseline=load_baseline(bl))
        assert res2.findings == [] and len(res2.baselined) == 1
        res3 = lint(tmp_path, {"n.py": """
            class Pool:
                def admit(self, seq):
                    # jaxlint: disable=resource-leak -- fixture: caller owns the slot
                    slot = self._freeSlots.get()
                    if seq.bad:
                        return None
                    return slot
        """}, rules=["resource-leak"])
        assert res3.findings == []
        assert [f.rule for f in res3.suppressed] == ["resource-leak"]


# ------------------------------------------------------------ tracer-escape --

class TestTracerEscape:
    def test_jit_body_appends_traced_to_module_global_fires(
            self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import jax
            _TRACE = []
            def make():
                @jax.jit
                def body(x):
                    y = x + 1
                    _TRACE.append(y)
                    return y
                return body
        """}, rules=["tracer-escape"])
        assert rule_ids(res) == ["tracer-escape"]
        assert "_TRACE" in res.findings[0].message

    def test_scan_body_writing_self_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            from jax import lax
            class M:
                def roll(self, xs):
                    def step(carry, x):
                        self.last = carry
                        return carry + x, x
                    return lax.scan(step, 0, xs)
        """}, rules=["tracer-escape"])
        assert rule_ids(res) == ["tracer-escape"]

    def test_pure_body_and_static_arg_write_are_clean(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            import functools
            import jax
            _MODES = []
            @functools.partial(jax.jit, static_argnames=("mode",))
            def body(x, mode):
                if mode == "fast":
                    _MODES.append(mode)
                return x + 1
        """}, rules=["tracer-escape"])
        # mode is static (a real Python value), not a tracer
        assert res.findings == []

    def test_suppression_and_baseline_roundtrip(self, tmp_path):
        bad = """
            import jax
            _TRACE = []
            @jax.jit
            def body(x):
                _TRACE.append(x)
                return x
        """
        res = lint(tmp_path, {"m.py": bad}, rules=["tracer-escape"])
        assert rule_ids(res) == ["tracer-escape"]
        bl = tmp_path / "bl.json"
        save_baseline(bl, res.findings)
        res2 = lint(tmp_path, {"m.py": bad}, rules=["tracer-escape"],
                    baseline=load_baseline(bl))
        assert res2.findings == [] and len(res2.baselined) == 1
        res3 = lint(tmp_path, {"n.py": """
            import jax
            _TRACE = []
            @jax.jit
            def body(x):
                # jaxlint: disable=tracer-escape -- fixture: debug capture, removed before ship
                _TRACE.append(x)
                return x
        """}, rules=["tracer-escape"])
        assert res3.findings == []
        assert [f.rule for f in res3.suppressed] == ["tracer-escape"]


# ------------------------------------------------------- metric-cardinality --

class TestMetricCardinality:
    def test_exception_text_label_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            def rec(m, work):
                try:
                    work()
                except Exception as e:
                    m.errors.inc(error=str(e))
        """}, rules=["metric-cardinality"])
        assert rule_ids(res) == ["metric-cardinality"]
        assert "'error'" in res.findings[0].message

    def test_raw_request_field_label_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            def rec(m, payload):
                m.hits.inc(route=payload["path"])
        """}, rules=["metric-cardinality"])
        assert rule_ids(res) == ["metric-cardinality"]

    def test_hash_output_label_fires(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            def rec(m, key):
                m.lookups.inc(bucket=hash(key))
        """}, rules=["metric-cardinality"])
        assert rule_ids(res) == ["metric-cardinality"]

    def test_bounded_labels_are_clean(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            def rec(m, work, host, replica_id):
                try:
                    work()
                except Exception as e:
                    m.errors.inc(kind=type(e).__name__)
                m.steps.inc(host=host, replica=replica_id)
        """}, rules=["metric-cardinality"])
        assert res.findings == []

    def test_exemplar_trace_id_is_exempt(self, tmp_path):
        res = lint(tmp_path, {"m.py": """
            def rec(m, secs, ctx):
                m.latency.observe_exemplar(secs, trace_id=ctx.trace_id)
        """}, rules=["metric-cardinality"])
        assert res.findings == []

    def test_suppression_and_baseline_roundtrip(self, tmp_path):
        bad = """
            def rec(m, payload):
                m.hits.inc(route=payload["path"])
        """
        res = lint(tmp_path, {"m.py": bad},
                   rules=["metric-cardinality"])
        assert rule_ids(res) == ["metric-cardinality"]
        bl = tmp_path / "bl.json"
        save_baseline(bl, res.findings)
        res2 = lint(tmp_path, {"m.py": bad},
                    rules=["metric-cardinality"],
                    baseline=load_baseline(bl))
        assert res2.findings == [] and len(res2.baselined) == 1
        res3 = lint(tmp_path, {"n.py": """
            def rec(m, payload):
                # jaxlint: disable=metric-cardinality -- fixture: route set is a 4-entry enum
                m.hits.inc(route=payload["path"])
        """}, rules=["metric-cardinality"])
        assert res3.findings == []
        assert [f.rule for f in res3.suppressed] == \
            ["metric-cardinality"]


# ------------------------------------------------------------ changed mode --

BAD_THREAD = """
import threading
def go(fn):
    threading.Thread(target=fn).start()
"""


def _git(cwd, *args):
    import subprocess
    subprocess.run(
        ["git", "-C", str(cwd), "-c", "user.email=t@example.com",
         "-c", "user.name=t", *args],
        check=True, capture_output=True)


class TestChangedMode:
    def _repo(self, tmp_path):
        (tmp_path / "a.py").write_text(
            "import b\n" + BAD_THREAD, encoding="utf-8")
        (tmp_path / "b.py").write_text("x = 1\n", encoding="utf-8")
        (tmp_path / "c.py").write_text(BAD_THREAD, encoding="utf-8")
        _git(tmp_path, "init", "-q")
        _git(tmp_path, "add", ".")
        _git(tmp_path, "commit", "-qm", "seed")
        return tmp_path

    def test_changed_scopes_to_module_closure(self, tmp_path, capsys):
        repo = self._repo(tmp_path)
        # touch a.py only: the scan set is a + its import closure (b),
        # NOT c — but a's findings match the full run exactly
        (repo / "a.py").write_text(
            "import b\n# touched\n" + BAD_THREAD, encoding="utf-8")
        rc = jaxlint_main(["--changed", "--root", str(repo),
                           "--no-baseline", "--json"])
        assert rc == 1
        changed_doc = json.loads(capsys.readouterr().out)
        assert changed_doc["files_scanned"] == 2
        assert all(f["path"] == "a.py"
                   for f in changed_doc["findings"])
        jaxlint_main([str(repo), "--root", str(repo),
                      "--no-baseline", "--json"])
        full_doc = json.loads(capsys.readouterr().out)
        assert full_doc["files_scanned"] == 3
        pick = lambda doc: sorted(
            (f["rule"], f["path"], f["line"], f["message"])
            for f in doc["findings"] if f["path"] == "a.py")
        assert pick(changed_doc) == pick(full_doc)
        # the full run also sees c.py's finding; changed mode must not
        assert any(f["path"] == "c.py" for f in full_doc["findings"])

    def test_changed_with_clean_tree_is_ok(self, tmp_path, capsys):
        repo = self._repo(tmp_path)
        rc = jaxlint_main(["--changed", "--root", str(repo),
                           "--no-baseline"])
        assert rc == 0
        assert "no changed Python files" in capsys.readouterr().out

    def test_changed_picks_up_untracked_files(self, tmp_path, capsys):
        repo = self._repo(tmp_path)
        (repo / "d.py").write_text(BAD_THREAD, encoding="utf-8")
        rc = jaxlint_main(["--changed", "--root", str(repo),
                           "--no-baseline", "--json"])
        assert rc == 1
        doc = json.loads(capsys.readouterr().out)
        assert [f["path"] for f in doc["findings"]] == ["d.py"]


# ------------------------------------------------- stats + baseline hygiene --

class TestStatsAndBaselineHygiene:
    def test_timings_populated_and_rendered(self, tmp_path, capsys):
        res = lint(tmp_path, {"m.py": "x = 1\n"})
        t = res.timings
        assert set(t) == {"parse_s", "per_rule_s", "total_s"}
        assert t["total_s"] >= t["parse_s"] >= 0
        assert set(t["per_rule_s"]) == set(res.rules_run)
        out = render_text(res, stats=True)
        assert "stats: total" in out and "stats: parse" in out
        doc = render_json(res)
        assert doc["timings"]["total_s"] == t["total_s"]
        f = tmp_path / "m.py"
        assert jaxlint_main([str(f), "--no-baseline", "--stats"]) == 0
        assert "stats: total" in capsys.readouterr().out

    def test_dead_entry_file_deleted_warns_then_strict_fails(
            self, tmp_path, capsys):
        f = tmp_path / "m.py"
        f.write_text(BAD_THREAD, encoding="utf-8")
        bl = tmp_path / "bl.json"
        assert jaxlint_main([str(f), "--baseline", str(bl),
                             "--baseline-update"]) == 0
        f.unlink()
        ok = tmp_path / "ok.py"
        ok.write_text("x = 1\n", encoding="utf-8")
        capsys.readouterr()
        # default: warning, still exit 0
        assert jaxlint_main([str(ok), "--baseline", str(bl)]) == 0
        assert "dead entry" in capsys.readouterr().out
        # strict: the same run fails
        assert jaxlint_main([str(ok), "--baseline", str(bl),
                             "--baseline-strict"]) == 1
        # --baseline-update prunes the dead entry even though the
        # deleted file is out of the update's scan scope
        assert jaxlint_main([str(ok), "--baseline", str(bl),
                             "--baseline-update"]) == 0
        assert sum(load_baseline(bl).values()) == 0

    def test_dead_entry_line_text_gone_detected(self, tmp_path, capsys):
        f = tmp_path / "m.py"
        f.write_text(BAD_THREAD, encoding="utf-8")
        bl = tmp_path / "bl.json"
        assert jaxlint_main([str(f), "--baseline", str(bl),
                             "--baseline-update"]) == 0
        f.write_text("x = 1\n", encoding="utf-8")
        capsys.readouterr()
        rc = jaxlint_main([str(f), "--baseline", str(bl),
                           "--baseline-strict"])
        assert rc == 1
        assert "line text no longer present" in capsys.readouterr().out

    def test_committed_baseline_has_no_dead_entries(self):
        result = run()
        assert result.dead_baseline == []