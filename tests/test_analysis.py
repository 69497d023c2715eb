"""Invariant linter (``repro.analysis``): per-checker fixture pairs, pragma
suppression, baseline diffing, the CLI gate, a repo-wide self-run, and the
five seeded violations the gate must catch when injected into ``src/repro``.
"""
import collections
import json
import os
import shutil
import subprocess
import sys

import pytest

from repro.analysis import (ALL_CHECKERS, analyze_paths, load_baseline, main,
                            make_baseline, new_findings)

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures", "lint")
REPO = os.path.dirname(HERE)


def _run_fixture(fname):
    findings, suppressed, errors = analyze_paths(
        [os.path.join(FIXTURES, fname)], root=FIXTURES)
    assert not errors, errors
    return findings, suppressed


# -- per-checker fixture pairs ----------------------------------------------

CASES = [
    ("host-sync", "host_sync", 5),
    ("retrace", "retrace", 3),
    ("donation-alias", "donation", 2),
    ("concurrency", "concurrency", 5),
    ("determinism", "determinism", 5),
]


@pytest.mark.parametrize("checker,stem,n", CASES, ids=[c[0] for c in CASES])
def test_flagged_fixture_is_fully_flagged(checker, stem, n):
    findings, suppressed = _run_fixture(f"{stem}_flagged.py")
    assert len(findings) == n, "\n".join(f.render() for f in findings)
    assert {f.checker for f in findings} == {checker}
    assert not suppressed
    for f in findings:
        assert f.path == f"{stem}_flagged.py"
        assert f.line > 0 and f.message and f.hint and f.snippet


@pytest.mark.parametrize("checker,stem,n", CASES, ids=[c[0] for c in CASES])
def test_clean_fixture_is_silent(checker, stem, n):
    findings, suppressed = _run_fixture(f"{stem}_clean.py")
    assert findings == [], "\n".join(f.render() for f in findings)
    assert suppressed == []


def test_checker_registry_matches_fixture_coverage():
    assert set(ALL_CHECKERS) == {c[0] for c in CASES}


# -- pragmas ----------------------------------------------------------------

def test_pragma_suppression_same_line_above_line_and_wildcard():
    findings, suppressed = _run_fixture("pragma_suppressed.py")
    # only the wrong-checker pragma site survives as a finding
    assert len(findings) == 1
    assert findings[0].checker == "host-sync"
    assert "allow[determinism]" in findings[0].snippet
    got = collections.Counter(f.checker for f in suppressed)
    assert got == {"host-sync": 2, "determinism": 1}


# -- baseline semantics ------------------------------------------------------

def test_baseline_is_a_per_key_budget(tmp_path):
    """Two occurrences of a baselined pattern with budget 1: one is fresh."""
    src = tmp_path / "mod.py"
    src.write_text("import time\n\ndef a():\n    return time.time()\n\n\n"
                   "def b():\n    return time.time()\n")
    findings, _, errors = analyze_paths([str(src)], root=str(tmp_path))
    assert not errors and len(findings) == 2
    assert findings[0].key() == findings[1].key()     # same stripped line
    fresh = new_findings(findings, make_baseline(findings[:1]))
    assert len(fresh) == 1
    assert findings[0].baselined and not findings[1].baselined


def test_missing_baseline_means_empty(tmp_path):
    base = load_baseline(str(tmp_path / "nope.json"))
    assert base["findings"] == {}


def test_wrong_baseline_version_is_actionable(tmp_path):
    p = tmp_path / "analysis_baseline.json"
    p.write_text(json.dumps({"version": 99, "findings": {}}))
    with pytest.raises(ValueError, match="--write-baseline"):
        load_baseline(str(p))


# -- CLI ---------------------------------------------------------------------

def test_cli_write_baseline_then_gate(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text("import time\n\ndef t():\n    return time.time()\n")
    argv = [str(mod), "--root", str(tmp_path), "--quiet"]
    assert main(argv + ["--fail-on-new"]) == 1         # no baseline yet
    assert main(argv + ["--write-baseline"]) == 0
    assert main(argv + ["--fail-on-new"]) == 0         # accepted debt passes
    assert main(argv + ["--strict"]) == 1              # strict ignores baseline
    # a SECOND occurrence of the baselined pattern still fails the gate
    mod.write_text(mod.read_text() + "\n\ndef u():\n    return time.time()\n")
    assert main(argv + ["--fail-on-new"]) == 1


def test_cli_json_report(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text("import time\n\ndef t():\n    return time.time()\n")
    rep = tmp_path / "report.json"
    # report-only mode (no gate flags) exits 0 but records everything
    assert main([str(mod), "--root", str(tmp_path), "--quiet",
                 "--json", str(rep)]) == 0
    doc = json.loads(rep.read_text())
    assert doc["counts"] == {"determinism": 1}
    assert doc["n_findings"] == 1 and doc["n_new"] == 1
    assert doc["findings"][0]["path"] == "mod.py"
    assert doc["findings"][0]["hint"]


def test_cli_parse_error_fails_even_without_gate_flags(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    assert main([str(bad), "--root", str(tmp_path), "--quiet"]) == 1


def test_cli_missing_path_is_usage_error(tmp_path):
    assert main([str(tmp_path / "nope.py"), "--root", str(tmp_path)]) == 2


def test_cli_list_checkers(capsys):
    assert main(["--list"]) == 0
    out = capsys.readouterr().out
    for cid in ALL_CHECKERS:
        assert cid in out


# -- repo-wide self-run ------------------------------------------------------

def test_repo_has_no_findings_beyond_baseline():
    findings, _, errors = analyze_paths(
        [os.path.join(REPO, "src", "repro")], root=REPO)
    assert not errors, errors
    fresh = new_findings(
        findings, load_baseline(os.path.join(REPO, "analysis_baseline.json")))
    assert fresh == [], "\n".join(f.render() for f in fresh)


def test_module_entrypoint_gate_passes_at_head():
    """`python -m repro.analysis --fail-on-new` exactly as CI invokes it."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--fail-on-new", "--quiet"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -- seeded violations: the gate must catch each one injected at HEAD --------

@pytest.fixture
def repo_copy(tmp_path):
    dst = tmp_path / "repo"
    dst.mkdir()
    shutil.copytree(os.path.join(REPO, "src"), str(dst / "src"))
    for f in ("pyproject.toml", "analysis_baseline.json"):
        shutil.copy(os.path.join(REPO, f), str(dst / f))
    return dst


def _replace(path, needle, repl):
    s = path.read_text()
    assert needle in s, f"{path}: injection anchor moved"
    path.write_text(s.replace(needle, repl, 1))


def _append(path, code):
    path.write_text(path.read_text() + code)


STEP = "state, metrics = step_fn(state, batch)"
INJECTIONS = [
    ("host-sync", "src/repro/train/loop.py", lambda p: _replace(
        p, STEP, STEP + '\n            _l = float(metrics["loss"])')),
    ("retrace", "src/repro/train/loop.py", lambda p: _replace(
        p, STEP, "step_fn = jax.jit(train_step)\n            " + STEP)),
    ("donation-alias", "src/repro/core/grab.py", lambda p: _append(
        p, "\n\ndef _seeded_aliased(d):\n"
           "    z = jnp.zeros((d,), jnp.float32)\n"
           "    return GrabState(running_sum=z, m_prev=z, m_acc=z)\n")),
    ("concurrency", "src/repro/data/prefetch.py", lambda p: _append(
        p, "\n\ndef _seeded_bare_get(q):\n    return q.get()\n")),
    ("determinism", "src/repro/train/loop.py", lambda p: _append(
        p, "\n\ndef _seeded_wallclock():\n    return time.time()\n")),
]


@pytest.mark.parametrize("checker,rel,mutate", INJECTIONS,
                         ids=[i[0] for i in INJECTIONS])
def test_gate_catches_seeded_violation(repo_copy, checker, rel, mutate):
    mutate(repo_copy / rel)
    assert main(["--root", str(repo_copy), "--fail-on-new", "--quiet"]) == 1
    findings, _, errors = analyze_paths(
        [str(repo_copy / "src" / "repro")], root=str(repo_copy))
    assert not errors, errors
    fresh = new_findings(findings, load_baseline(
        str(repo_copy / "analysis_baseline.json")))
    assert [f.checker for f in fresh] == [checker], \
        "\n".join(f.render() for f in fresh)


def test_gate_passes_on_unmodified_copy(repo_copy):
    assert main(["--root", str(repo_copy), "--fail-on-new", "--quiet"]) == 0

