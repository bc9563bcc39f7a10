"""repro-lint: every rule exercised against the seeded-violation
fixtures (fire + suppression paths), framework semantics, CLI exit
codes, and the no-findings contract on the real tree."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import RULES, format_json, format_text, run_lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")


@pytest.fixture(scope="module")
def fixture_findings():
    return run_lint([FIXTURES], root=REPO)


# ---------------------------------------------------------------------------
# every rule: fire + suppression
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rule", sorted(RULES))
def test_rule_fires_on_fixtures(fixture_findings, rule):
    active = [f for f in fixture_findings if f.rule == rule and not f.suppressed]
    assert active, f"{rule} ({RULES[rule]}) did not fire on the seeded fixtures"


@pytest.mark.parametrize("rule", sorted(RULES))
def test_rule_suppression_holds(fixture_findings, rule):
    sup = [f for f in fixture_findings if f.rule == rule and f.suppressed]
    assert sup, f"{rule} ({RULES[rule]}) has no working suppression seed"
    for f in sup:
        assert "_suppressed" in f.path or "supkern" in f.path


def test_fixture_findings_land_on_seeded_files(fixture_findings):
    for f in fixture_findings:
        if not f.suppressed:
            assert "seeded_" in f.path or "badkern" in f.path, (
                f"unexpected finding outside seeded files: {f}"
            )


# ---------------------------------------------------------------------------
# targeted rule semantics on minimal sources
# ---------------------------------------------------------------------------


def _lint_source(tmp_path, source, name="sample.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return run_lint([str(p)], root=str(tmp_path))


def test_line_level_suppression(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        import jax

        @jax.jit
        def f(x):
            if x > 0:  # lint: disable=TS101
                return x
            return -x
        """,
    )
    assert [f.rule for f in findings] == ["TS101"]
    assert findings[0].suppressed


def test_long_name_suppression(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        import jax

        @jax.jit
        def f(x):
            if x > 0:  # lint: disable=tracer-branch
                return x
            return -x
        """,
    )
    assert findings and findings[0].suppressed


def test_static_argname_is_not_tainted(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        import jax

        @jax.jit
        def f(x, n):
            if n > 0:
                return x
            return -x

        g = jax.jit(f, static_argnames=("n",))
        """,
    )
    # n is static via the registration -> no TS101.
    assert not [f for f in findings if f.rule == "TS101"]


def test_shape_access_cleanses_taint(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        import jax

        @jax.jit
        def f(x):
            if x.shape[0] > 4 and x.ndim == 2 and len(x) > 1:
                return x
            return x + 1
        """,
    )
    assert not [f for f in findings if f.rule == "TS101"]


def test_is_none_test_allowed(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        import jax

        @jax.jit
        def f(x, scale=None):
            if scale is None:
                return x
            return x * scale
        """,
    )
    assert not [f for f in findings if f.rule == "TS101"]


def test_taint_propagates_into_helper(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        import jax

        @jax.jit
        def f(x):
            return helper(x)

        def helper(y):
            if y > 0:
                return y
            return -y
        """,
    )
    assert [f.rule for f in findings] == ["TS101"]


def test_eager_float_on_jit_result_is_clean(tmp_path):
    # Calling a jitted fn eagerly and float()ing the result is fine.
    findings = _lint_source(
        tmp_path,
        """
        import jax

        @jax.jit
        def step(x):
            return x + 1

        def train(x0):
            out = step(x0)
            return float(out)
        """,
    )
    assert not findings


def test_pallas_kwonly_params_are_static(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        import functools
        from jax.experimental import pallas as pl

        def kern(x_ref, o_ref, *, use_mxu):
            if use_mxu:
                o_ref[...] = x_ref[...]
            else:
                o_ref[...] = x_ref[...] * 2

        def launch(x):
            return pl.pallas_call(functools.partial(kern, use_mxu=True))(x)
        """,
    )
    assert not [f for f in findings if f.rule == "TS101"]


def test_guarded_write_requires_matching_lock(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        import threading

        class C:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
                self.n = 0  # guarded-by: _a

            def wrong_lock(self):
                with self._b:
                    self.n += 1
        """,
    )
    assert [f.rule for f in findings] == ["LD202"]


def test_guarded_by_unknown_lock_is_reported(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0  # guarded-by: _lokc
        """,
    )
    assert [f.rule for f in findings] == ["LD201"]
    assert "_lokc" in findings[0].message


def test_nested_def_does_not_inherit_lock_scope(tmp_path):
    # A closure defined under `with self._lock:` runs later (often on
    # another thread): its writes must not count as guarded.
    findings = _lint_source(
        tmp_path,
        """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0  # guarded-by: _lock

            def make_cb(self):
                with self._lock:
                    def cb():
                        self.n += 1
                    return cb
        """,
    )
    assert [f.rule for f in findings] == ["LD202"]


def test_lock_order_no_false_cycle_on_consistent_order(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        import threading

        class Inner:
            def __init__(self):
                self._lock = threading.Lock()

            def poke(self):
                with self._lock:
                    pass

        class Outer:
            def __init__(self):
                self._lock = threading.Lock()
                self.inner = Inner()

            def a(self):
                with self._lock:
                    self.inner.poke()

            def b(self):
                with self._lock:
                    self.inner.poke()
        """,
    )
    assert not [f for f in findings if f.rule == "LD203"]


def test_blockspec_vararg_lambda_allowed(tmp_path):
    findings = _lint_source(
        tmp_path,
        """
        import jax
        from jax.experimental import pallas as pl

        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def launch(x):
            return pl.pallas_call(
                kern,
                grid=(2, 2),
                in_specs=[pl.BlockSpec((1, 1), lambda *a: (0, 0))],
                out_specs=pl.BlockSpec((1, 1), lambda i, j: (i, j)),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            )(x)
        """,
    )
    assert not [f for f in findings if f.rule == "KC302"]


def test_prefetch_grid_spec_arity_includes_scalar_operands(tmp_path):
    src = """
        import jax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kern(off_ref, x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def launch(x, offs):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(2, 2),
                in_specs=[pl.BlockSpec((1, 1), lambda i, j{EXTRA}: (i, j))],
                out_specs=pl.BlockSpec((1, 1), lambda i, j, off: (i, j)),
            )
            return pl.pallas_call(kern, grid_spec=grid_spec,
                                  out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(offs, x)
    """
    bad = _lint_source(tmp_path, src.replace("{EXTRA}", ""), name="bad.py")
    assert [f.rule for f in bad if f.rule == "KC302"], (
        "2-arg index map with num_scalar_prefetch=1 must be flagged"
    )
    good = _lint_source(tmp_path, src.replace("{EXTRA}", ", off"), name="good.py")
    assert not [f for f in good if f.rule == "KC302"]


# ---------------------------------------------------------------------------
# reporters + CLI
# ---------------------------------------------------------------------------


def test_json_reporter_shape(fixture_findings):
    payload = json.loads(format_json(fixture_findings))
    assert payload["tool"] == "repro-lint"
    assert payload["counts"]["active"] >= len(RULES)
    assert payload["counts"]["suppressed"] >= len(RULES)
    rules_seen = {f["rule"] for f in payload["findings"]}
    assert set(RULES) <= rules_seen
    for f in payload["findings"]:
        assert {"rule", "name", "severity", "path", "line", "col", "message",
                "suppressed"} <= set(f)


def test_text_reporter_summary_line(fixture_findings):
    text = format_text(fixture_findings)
    assert "repro-lint:" in text.splitlines()[-1]


def _run_cli(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"), *args],
        capture_output=True,
        text=True,
        cwd=REPO,
    )


def test_cli_clean_tree_exits_zero():
    proc = _run_cli("src", "benchmarks")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


def test_cli_fixtures_exit_nonzero_and_json_artifact(tmp_path):
    out = tmp_path / "report.json"
    proc = _run_cli(
        "--format", "json", "--output", str(out), os.path.join("tests", "lint_fixtures")
    )
    assert proc.returncode == 1
    payload = json.loads(out.read_text())
    assert payload["counts"]["active"] > 0


def test_cli_self_test_passes():
    proc = _run_cli("--self-test")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout


def test_cli_changed_mode_runs():
    proc = _run_cli("--changed")
    # Exit 0 both when nothing changed and when changed files are clean;
    # must never crash.
    assert proc.returncode in (0, 1), proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# the repo contract: annotated fields stay verified, tree stays clean
# ---------------------------------------------------------------------------


def test_real_tree_is_lint_clean():
    findings = run_lint(
        [os.path.join(REPO, "src"), os.path.join(REPO, "benchmarks")], root=REPO
    )
    active = [f for f in findings if not f.suppressed]
    assert not active, "\n" + format_text(findings)


def test_guarded_annotations_present_in_runtime_classes():
    """The lock-discipline checker only has teeth while the annotations
    exist — pin the classes the serving runtime relies on."""
    from repro.analysis.framework import parse_files
    from repro.analysis.lock_discipline import _collect_classes, _scan_class

    files = parse_files(
        [
            os.path.join(REPO, "src", "repro", "launch", "serve.py"),
            os.path.join(REPO, "src", "repro", "launch", "resilience.py"),
            os.path.join(REPO, "src", "repro", "core", "engine.py"),
            os.path.join(REPO, "src", "repro", "distributed", "fault.py"),
        ],
        root=REPO,
    )
    classes = _collect_classes(files)
    for info in classes.values():
        _scan_class(info, classes, [])
    guarded = {name: set(info.guarded) for name, info in classes.items()}
    assert {"submitted", "completed", "rejected", "failed", "_batch_seq"} <= guarded[
        "MicrobatchScheduler"
    ]
    assert {"hits", "misses", "_entries", "_nbytes", "_inflight"} <= guarded[
        "GratingCache"
    ]
    assert {"_tenants", "_sthcs", "_quarantined"} <= guarded["VideoSearchServer"]
    assert {"_state", "failures", "trips"} <= guarded["CircuitBreaker"]
    assert {"_tracked", "expired"} <= guarded["Watchdog"]
    assert {"_arenas", "_resident", "_zero_rows"} <= guarded["QueryEngine"]
