"""reprolint (ISSUE 8 tentpole): every rule flags its known-bad fixture
and stays silent on the known-good twin; the repo itself lints clean; the
CLI honors its documented exit codes."""
import os
import subprocess
import sys

import pytest

from repro.analysis import analyze, collect_files
from repro.analysis.findings import RULES

FIXTURES = os.path.join(os.path.dirname(__file__), "analysis_fixtures")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fixture(name):
    return os.path.join(FIXTURES, name + ".py")


def _rules_of(findings, *, live_only=True):
    return {f.rule for f in findings if not (live_only and f.suppressed)}


# ---------------------------------------------------------------------------
# static rules: bad twin flags, good twin is silent
# ---------------------------------------------------------------------------

STATIC_RULES = ["lck001", "lck002", "lck003", "lck004",
                "trc001", "trc002", "trc003", "trc004", "plk003",
                "tel001"]


@pytest.mark.parametrize("rule", STATIC_RULES)
def test_static_rule_flags_bad_twin_only(rule):
    rule_id = rule.upper()
    bad = analyze([_fixture(rule + "_bad")])
    good = analyze([_fixture(rule + "_good")])
    assert rule_id in _rules_of(bad), \
        f"{rule_id} missed its known-bad fixture"
    assert rule_id not in _rules_of(good), \
        f"{rule_id} false-positived on its known-good twin: " \
        + "; ".join(f.format() for f in good)


def test_lck001_flags_both_the_raw_write_and_the_closure_escape():
    found = [f for f in analyze([_fixture("lck001_bad")])
             if f.rule == "LCK001"]
    assert len(found) == 2


def test_findings_carry_position_and_hint():
    (f,) = [x for x in analyze([_fixture("trc001_bad")])
            if x.rule == "TRC001"]
    assert f.path.endswith("trc001_bad.py") and f.line > 1
    assert f.hint and "lax" in f.hint
    assert f.format().startswith(f"{f.path}:{f.line}: TRC001")


# ---------------------------------------------------------------------------
# suppression discipline
# ---------------------------------------------------------------------------

def test_unjustified_disable_is_itself_a_finding():
    rules = _rules_of(analyze([_fixture("sup001_bad")]))
    assert "SUP001" in rules
    assert "LCK001" not in rules            # the disable still suppresses


def test_justified_disable_suppresses_without_sup001():
    findings = analyze([_fixture("sup001_good")])
    assert _rules_of(findings) == set()
    (sup,) = [f for f in findings if f.suppressed]
    assert sup.rule == "LCK001" and "single-threaded" in sup.justification


# ---------------------------------------------------------------------------
# launch-capture rules (PLK001/PLK002) via fake kernel modules
# ---------------------------------------------------------------------------

@pytest.fixture
def fixture_modules():
    sys.path.insert(0, FIXTURES)
    try:
        yield
    finally:
        sys.path.remove(FIXTURES)


@pytest.mark.parametrize("rule", ["plk001", "plk002"])
def test_launch_rule_flags_bad_twin_only(rule, fixture_modules):
    from repro.analysis import pallas_trace
    bad = pallas_trace.run(modules=(rule + "_bad",))
    good = pallas_trace.run(modules=(rule + "_good",))
    assert rule.upper() in {f.rule for f in bad}
    assert rule.upper() not in {f.rule for f in good}


def test_missing_specs_is_a_hard_error(fixture_modules):
    from repro.analysis import pallas_trace
    with pytest.raises(RuntimeError, match="REPROLINT_SPECS"):
        pallas_trace.run(modules=("trc001_bad",))


# ---------------------------------------------------------------------------
# the repo itself ships clean; the CLI honors its exit-code contract
# ---------------------------------------------------------------------------

def test_repo_is_clean_under_default_passes():
    findings = [f for f in analyze() if not f.suppressed]
    assert findings == [], "\n".join(f.format() for f in findings)


def test_collect_files_skips_fixtures_and_pycache():
    for path in collect_files():
        assert "analysis_fixtures" not in path
        assert "__pycache__" not in path


def _cli(*argv):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *argv],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)


def test_cli_exit_codes():
    clean = _cli()                              # repo: exit 0
    assert clean.returncode == 0, clean.stdout + clean.stderr
    dirty = _cli(_fixture("lck001_bad"))        # findings: exit 1
    assert dirty.returncode == 1
    assert "LCK001" in dirty.stdout


def test_cli_list_rules_covers_the_catalog():
    out = _cli("--list-rules")
    assert out.returncode == 0
    for rule in RULES:
        assert rule in out.stdout
