"""seclint conformance: fixture corpus, self-run gate, corruption drills.

Three layers, mirroring how the analyzer is used:

* fixture corpus (tests/fixtures/seclint/): one known-bad and one
  known-good snippet per rule ID, with EXACT expected active-rule sets --
  a rule that stops firing (or starts over-firing) fails here first;
* the live gate: `repro.analysis` over all of src/repro must be clean and
  finish well inside the CI budget;
* corruption drills: deliberately breaking core/protocol.py (opening a
  share outside a sanctioned sink; dropping a `% field.P` before an int32
  narrow) must flip the CLI to a non-zero exit with the right rule ID.
"""

import os
import subprocess
import sys
import tempfile
import time

import pytest
from hypothesis import given, settings, strategies as st

from repro.analysis import analyze_paths

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC_REPRO = os.path.join(REPO, "src", "repro")
FIXTURES = os.path.join(REPO, "tests", "fixtures", "seclint")


def _active_rules(result):
    return sorted({f.rule for f in result.active})


def _run_cli(*argv):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *argv],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)


# ------------------------------------------------------------- fixture corpus

CORPUS = [
    ("sec001_bad.py", ["SEC001"]),
    ("sec001_good.py", []),
    ("sec002_bad.py", ["SEC002"]),
    ("sec002_good.py", []),
    ("sec003_bad.py", ["SEC003"]),
    ("sec003_good.py", []),
    ("procsend_bad.py", ["SEC001"]),  # hand-rolled socket write of a Share
    ("procsend_good.py", []),         # via the sanctioned wire.share_payload
    ("servesend_bad.py", ["SEC001"]),  # raw model-share bytes on the wire
    ("servesend_good.py", []),         # only logits open (coded.open_logits)
    ("fld001_bad.py", ["FLD001"]),
    ("fld001_good.py", []),
    ("fld002_bad.py", ["FLD002"]),
    ("fld002_good.py", []),
    ("fld003_bad.py", ["FLD003"]),
    ("fld003_good.py", []),
    ("fld004_bad.py", ["FLD004"]),
    ("fld004_good.py", []),
    ("barrett_bad.py", ["FLD001", "FLD002"]),  # lazy accum, no reduce site
    ("barrett_good.py", []),   # barrett_reduce/fold26 sanction the subtree
    ("wvr001_bad.py", ["SEC001", "WVR001"]),  # malformed pragma waives nothing
    ("wvr001_good.py", []),                   # both findings waived
    ("wvr002_strict.py", []),                 # unused waiver: clean by default
]


@pytest.mark.parametrize("name,expected", CORPUS,
                         ids=[c[0].removesuffix(".py") for c in CORPUS])
def test_fixture_corpus(name, expected):
    res = analyze_paths([os.path.join(FIXTURES, name)])
    assert _active_rules(res) == expected


def test_waived_findings_recorded_with_reasons():
    res = analyze_paths([os.path.join(FIXTURES, "wvr001_good.py")])
    assert res.active == []
    waived = res.waived
    assert len(waived) == 2
    assert all(f.rule == "SEC001" and f.waiver_reason for f in waived)


def test_strict_surfaces_unused_waiver():
    path = os.path.join(FIXTURES, "wvr002_strict.py")
    assert _active_rules(analyze_paths([path])) == []
    strict = analyze_paths([path], strict=True)
    assert "WVR002" in _active_rules(strict)


# --------------------------------------------------------------- the live gate

def test_self_run_clean_and_fast():
    """The committed tree carries zero unexplained findings, and the gate
    fits in the CI fast lane (<30 s; typically well under 1 s)."""
    t0 = time.monotonic()
    res = analyze_paths([SRC_REPRO])
    elapsed = time.monotonic() - t0
    assert elapsed < 30.0, f"seclint took {elapsed:.1f}s (budget 30s)"
    assert res.active == [], "\n".join(
        f"{f.location} {f.rule} {f.message}" for f in res.active)


def test_cli_exit_codes():
    ok = _run_cli(os.path.join(FIXTURES, "sec001_good.py"))
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad = _run_cli(os.path.join(FIXTURES, "sec001_bad.py"))
    assert bad.returncode == 1
    assert "SEC001" in bad.stdout
    waived = _run_cli(os.path.join(FIXTURES, "wvr001_good.py"))
    assert waived.returncode == 0
    strict = _run_cli("--strict", os.path.join(FIXTURES, "wvr001_good.py"))
    assert strict.returncode == 1  # strict treats waivers as errors


def test_budget_report_lists_waivers():
    out = _run_cli("--budget-report", "-",
                   os.path.join(FIXTURES, "wvr001_good.py"))
    assert out.returncode == 0
    assert "allow[SEC001]" in out.stdout
    assert "trailing-style waiver" in out.stdout


# ---------------------------------------------------------- corruption drills

def _core_source(name):
    with open(os.path.join(SRC_REPRO, "core", name)) as fh:
        return fh.read()


def _analyze_corrupted(source, name):
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, name)
        with open(path, "w") as fh:
            fh.write(source)
        return _run_cli("--package", "repro.core", path)


# The drills corrupt core/party.py, the one home of the per-party step
# that every engine runs: its Phase-4 update and its decode row.
def test_corrupted_protocol_share_leak_is_flagged():
    """Opening w_rows via print() inside party.update -> SEC001."""
    src = _core_source("party.py")
    anchor = "xtg = jax.vmap("
    assert anchor in src, "party.py changed; update the corruption drill"
    bad = src.replace(anchor, "print(w_rows)\n    " + anchor, 1)
    proc = _analyze_corrupted(bad, "party.py")
    assert proc.returncode == 1
    assert "SEC001" in proc.stdout


def test_corrupted_protocol_dropped_reduction_is_flagged():
    """Removing the `% field.P` before the int32 narrow in
    party.decode_row -> FLD002."""
    src = _core_source("party.py")
    anchor = "(dmat.sum(axis=0) % field.P).astype(np.int32)"
    assert anchor in src, "party.py changed; update the corruption drill"
    bad = src.replace(anchor, "dmat.sum(axis=0).astype(np.int32)", 1)
    proc = _analyze_corrupted(bad, "party.py")
    assert proc.returncode == 1
    assert "FLD002" in proc.stdout


def test_uncorrupted_protocol_copy_is_clean():
    """The drill harness itself must not produce findings on the pristine
    files (otherwise the corruption assertions prove nothing)."""
    for name in ("protocol.py", "party.py"):
        proc = _analyze_corrupted(_core_source(name), name)
        assert proc.returncode == 0, (name, proc.stdout + proc.stderr)


# ------------------------------------------------------------ property: FLD

_PROP_TEMPLATE = """from repro.core import field


def f(x, y):
    z = field.mul(x, y)
    return ({expr}).astype("int32")
"""


@given(st.sampled_from(["+", "-", "*"]), st.integers(1, 4096),
       st.integers(1, 3))
@settings(max_examples=12, deadline=None)
def test_random_unreduced_field_expression_is_flagged(op, k, depth):
    """Any raw-arithmetic chain over a field value, narrowed without a
    dominating `% field.P`, must trip both the raw-op and the
    unreduced-narrow rules."""
    expr = "z"
    for _ in range(depth):
        expr = f"({expr} {op} {k})"
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "snippet.py")
        with open(path, "w") as fh:
            fh.write(_PROP_TEMPLATE.format(expr=expr))
        rules = _active_rules(analyze_paths([path]))
    assert "FLD001" in rules and "FLD002" in rules, (expr, rules)
