"""tpulint: per-rule fixture firing, suppressions, baseline ratchet,
CLI exit codes, and the package-clean gate (the acceptance criterion:
`python -m kaminpar_tpu.lint kaminpar_tpu/` exits 0 vs the checked-in
baseline)."""

import json
import os

import pytest

from kaminpar_tpu.lint import (
    LintConfig,
    diff_against_baseline,
    lint_file,
    lint_paths,
    lint_source,
    load_baseline,
    write_baseline,
)
from kaminpar_tpu.lint.__main__ import DEFAULT_BASELINE, main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")
PACKAGE = os.path.join(REPO, "kaminpar_tpu")


def _findings(name):
    return lint_file(os.path.join(FIXTURES, name))


# --- every rule fires on its bad fixture at the pinned lines --------------

BAD_EXPECT = {
    "r1_bad.py": [("R1", 20), ("R1", 22), ("R1", 23), ("R1", 24), ("R1", 30)],
    # the PR-11 quality-observatory hook shape: per-level cut/cmap
    # pulls lexically inside a driver's uncoarsening span
    "r1_quality_bad.py": [("R1", 19), ("R1", 20)],
    # the PR-13 streaming hook shape: chunk decode + moved-count pulls
    # lexically inside a driver's stream span
    "r1_stream_bad.py": [("R1", 19), ("R1", 21)],
    # the PR-15 dynamic delta-apply hook shape: the host CSR patch
    # pull + cut readback lexically inside a driver's dynamic-apply
    # span
    "r1_dynamic_bad.py": [("R1", 19), ("R1", 21)],
    # the PR-14 supervision hook shape: liveness "proof" pulls device
    # state lexically inside the guarded driver span (the watchdog/
    # heartbeat hooks are host-side bookkeeping and read no device
    # values)
    "r1_supervisor_bad.py": [("R1", 22), ("R1", 23)],
    # the PR-16 fleet-observatory hook shape: live-gauge pulls of
    # device values lexically inside the measured compute span (the
    # metrics producers are host-side request bookkeeping)
    "r1_metrics_bad.py": [("R1", 23), ("R1", 24), ("R1", 25)],
    # the PR-17 call-graph shape: host pulls hidden one helper call
    # deep — the span body only makes function calls, the call graph
    # flags the call sites
    "r1_helper_bad.py": [("R1", 24), ("R1", 25)],
    # the PR-19 execution-ledger hook shape: transfer metering fed by
    # device-value pulls lexically inside the measured upload span
    # (ledger pulls inside a driver span = R1; the factored chokepoint
    # helpers metering from host metadata are clean)
    "r1_ledger_bad.py": [("R1", 22), ("R1", 23)],
    "r2_bad.py": [("R2", 5), ("R2", 9)],
    "r3_bad.py": [("R3", 7), ("R3", 11), ("R3", 16), ("R3", 21)],
    "r4_bad.py": [("R4", 10), ("R4", 17), ("R4", 23)],
    "r6_bad.py": [("R6", 7), ("R6", 11), ("R6", 15), ("R6", 19)],
    # SPMD collective symmetry: direct, helper-reached, and loop-guarded
    "r7_bad.py": [("R7", 18), ("R7", 24), ("R7", 30)],
    # exception hygiene: bare except, except-Exception around site=,
    # and a broad handler around a helper reaching the fault surface
    "r8_bad.py": [("R8", 16), ("R8", 23), ("R8", 30)],
}


@pytest.mark.parametrize("name", sorted(BAD_EXPECT))
def test_rule_fires_on_bad_fixture(name):
    got = [(f.rule, f.line) for f in _findings(name)]
    assert got == BAD_EXPECT[name]


@pytest.mark.parametrize(
    "name", ["r1_good.py", "r1_quality_good.py", "r1_stream_good.py",
             "r1_dynamic_good.py", "r1_helper_good.py", "r1_ledger_good.py",
             "r1_supervisor_good.py", "r1_metrics_good.py", "r2_good.py",
             "r3_good.py", "r4_good.py", "r6_good.py",
             "r7_good.py", "r8_good.py"]
)
def test_rule_silent_on_good_fixture(name):
    assert _findings(name) == []


# --- finding metadata ------------------------------------------------------

def test_findings_carry_symbol_and_code():
    by_line = {f.line: f for f in _findings("r1_bad.py")}
    assert by_line[23].symbol == "helper"
    assert ".item()" in by_line[23].code
    assert by_line[30].symbol == "span_scope_sync"
    mod_level = {f.line: f for f in _findings("r2_bad.py")}
    assert mod_level[5].symbol == "<module>"


# --- suppressions ----------------------------------------------------------

R2_SNIPPET = "import jax\n\n\ndef f():\n    return jax.devices()\n"


def test_same_line_suppression():
    src = R2_SNIPPET.replace(
        "return jax.devices()",
        "return jax.devices()  # tpulint: disable=R2",
    )
    assert lint_source(src, "x.py") == []


def test_comment_line_above_suppression():
    src = R2_SNIPPET.replace(
        "    return jax.devices()",
        "    # bounded: test harness only  # tpulint: disable=R2\n"
        "    return jax.devices()",
    )
    assert lint_source(src, "x.py") == []


def test_file_level_suppression():
    src = "# tpulint: disable-file=R2\n" + R2_SNIPPET
    assert lint_source(src, "x.py") == []


def test_suppression_of_other_rule_does_not_hide():
    src = R2_SNIPPET.replace(
        "return jax.devices()",
        "return jax.devices()  # tpulint: disable=R1",
    )
    assert [f.rule for f in lint_source(src, "x.py")] == ["R2"]


def test_gate_module_is_exempt():
    findings = lint_source(R2_SNIPPET, "kaminpar_tpu/utils/platform.py")
    assert findings == []


# --- baseline --------------------------------------------------------------

def test_baseline_roundtrip_and_diff(tmp_path):
    findings = _findings("r3_bad.py")
    path = tmp_path / "baseline.json"
    write_baseline(str(path), findings)
    entries = load_baseline(str(path))
    assert len(entries) == len(findings)

    diff = diff_against_baseline(findings, entries)
    assert diff.new == [] and len(diff.accepted) == len(findings)
    assert diff.stale == []

    # a fresh finding not in the baseline is NEW
    extra = _findings("r4_bad.py")
    diff = diff_against_baseline(findings + extra, entries)
    assert [f.rule for f in diff.new] == ["R4"] * len(extra)

    # a fixed finding leaves a STALE entry (the ratchet signal)
    diff = diff_against_baseline(findings[1:], entries)
    assert len(diff.stale) == 1 and diff.new == []


def test_baseline_is_line_churn_stable(tmp_path):
    src = R2_SNIPPET
    findings = lint_source(src, "x.py")
    path = tmp_path / "b.json"
    write_baseline(str(path), findings)
    # shift every line down: same code, different line numbers
    shifted = "# a new leading comment\n\n" + src
    diff = diff_against_baseline(
        lint_source(shifted, "x.py"), load_baseline(str(path))
    )
    assert diff.new == [] and diff.stale == []


# --- CLI -------------------------------------------------------------------

def test_cli_exit_codes(tmp_path, capsys):
    bad = os.path.join(FIXTURES, "r2_bad.py")
    good = os.path.join(FIXTURES, "r2_good.py")
    assert main([good, "--no-baseline"]) == 0
    assert main([bad, "--no-baseline"]) == 1
    assert main([str(tmp_path / "missing.py")]) == 2
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "R1" in out and "R9" in out


def test_cli_select_subset():
    bad = os.path.join(FIXTURES, "r2_bad.py")
    # selecting a rule the file does not violate -> clean
    assert main([bad, "--no-baseline", "--select", "R4"]) == 0
    assert main([bad, "--no-baseline", "--select", "R2"]) == 1
    assert main([bad, "--select", "R42"]) == 2  # unknown rule


def test_cli_json_format(capsys):
    bad = os.path.join(FIXTURES, "r4_bad.py")
    assert main([bad, "--no-baseline", "--format", "json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["total"] == 3
    assert payload["new"][0]["rule"] == "R4"


def test_cli_write_baseline_refuses_subsets(tmp_path, capsys):
    """--write-baseline must not truncate the checked-in baseline to a
    rule or path subset's findings."""
    bad = os.path.join(FIXTURES, "r2_bad.py")
    assert main([bad, "--select", "R2", "--write-baseline"]) == 2
    assert main([bad, "--write-baseline"]) == 2  # path subset, default file
    capsys.readouterr()
    # an explicit --baseline target is fine for a subset
    out = tmp_path / "b.json"
    assert main([bad, "--write-baseline", "--baseline", str(out)]) == 0
    assert load_baseline(str(out))


# --- the acceptance gate ---------------------------------------------------

def test_package_is_clean_against_checked_in_baseline():
    """`python -m kaminpar_tpu.lint kaminpar_tpu/` must exit 0: every
    finding is either fixed, suppressed with a justification, or in
    scripts/tpulint_baseline.json (ratchet: only ever shrink it)."""
    assert os.path.exists(DEFAULT_BASELINE), "baseline file is checked in"
    findings = lint_paths([PACKAGE], LintConfig())
    diff = diff_against_baseline(findings, load_baseline(DEFAULT_BASELINE))
    assert diff.new == [], "\n".join(f.render() for f in diff.new)


def test_syntax_error_reports_e0_even_with_rule_subset():
    cfg = LintConfig()
    cfg.rules = ("R2",)
    findings = lint_source("def f(:\n", "broken.py", cfg)
    assert [f.rule for f in findings] == ["E0"]


# --- PR 17: call-graph semantics -------------------------------------------

_HELPER_SRC = (
    "import numpy as np\n"
    "from kaminpar_tpu.utils.timer import scoped_timer\n\n\n"
    "def _pull(x):\n"
    "    return np.asarray(x)\n\n\n"
    "def run(x, out):\n"
    "    with scoped_timer('t'):\n"
    "        out.append(_pull(x))\n"
    "    return out\n"
)


def test_callgraph_flags_same_module_helper_call_site():
    """The pre-PR-17 loophole: a span body that only makes function
    calls.  The call graph flags the CALL SITE, not the helper def."""
    findings = lint_source(_HELPER_SRC, "x.py")
    assert [(f.rule, f.line) for f in findings] == [("R1", 11)]
    assert "_pull" in findings[0].message


def test_def_line_suppression_declares_host_boundary():
    """`# tpulint: disable=R1` on (above) a def clears the helper's
    summary for that rule — every call site at once."""
    src = _HELPER_SRC.replace(
        "def _pull(x):", "# tpulint: disable=R1\ndef _pull(x):"
    )
    assert lint_source(src, "x.py") == []


def test_lambda_payloads_are_deferred():
    """`payload=lambda: ...` thunks (the checkpoint-barrier shape) run
    outside the hot path — never span findings."""
    src = (
        "import numpy as np\n"
        "from kaminpar_tpu.utils.timer import scoped_timer\n\n\n"
        "def run(x, ckpt):\n"
        "    with scoped_timer('t'):\n"
        "        ckpt.barrier(payload=lambda: np.asarray(x))\n"
    )
    assert lint_source(src, "x.py") == []


# --- PR 17: R9 schema-pin consistency --------------------------------------

def _r9_config(root):
    cfg = LintConfig()
    cfg.r9_root = str(root)
    return cfg


def test_r9_good_quad_is_clean():
    from kaminpar_tpu.lint.schema_pins import check_schema_pins

    assert check_schema_pins(_r9_config(
        os.path.join(FIXTURES, "r9_good")
    )) == []


def test_r9_bad_quad_flags_each_stale_site():
    from kaminpar_tpu.lint.schema_pins import check_schema_pins

    findings = check_schema_pins(_r9_config(
        os.path.join(FIXTURES, "r9_bad")
    ))
    assert [f.rule for f in findings] == ["R9"] * 3
    paths = [f.path for f in findings]
    assert any(p.endswith("run_report.schema.json") for p in paths)
    assert sum(p.endswith("check_report_schema.py") for p in paths) == 2


_R9_SKEWS = {
    # bump ONE site of the good quad; the finding must name that site
    # (or, for a producer bump, the producer line — the other three
    # still agree with each other)
    "producer": (
        "kaminpar_tpu/telemetry/report.py",
        "SCHEMA_VERSION = 3", "SCHEMA_VERSION = 4",
        "report.py",
    ),
    "schema": (
        "kaminpar_tpu/telemetry/run_report.schema.json",
        "[1, 2, 3]", "[1, 2, 3, 4]",
        "run_report.schema.json",
    ),
    "checker": (
        "scripts/check_report_schema.py",
        "!= 3:", "!= 4:",
        "check_report_schema.py",
    ),
    "fixture": (
        "scripts/check_report_schema.py",
        "def _minimal_v2_report():", "def _minimal_v3_report():",
        "check_report_schema.py",
    ),
}


@pytest.mark.parametrize("site", sorted(_R9_SKEWS))
def test_r9_fails_when_one_pin_site_bumped_alone(site, tmp_path):
    import shutil

    from kaminpar_tpu.lint.schema_pins import check_schema_pins

    rel, old, new, expect_suffix = _R9_SKEWS[site]
    root = tmp_path / "quad"
    shutil.copytree(os.path.join(FIXTURES, "r9_good"), root)
    target = root / rel
    text = target.read_text()
    assert old in text
    target.write_text(text.replace(old, new))

    findings = check_schema_pins(_r9_config(root))
    assert findings, f"single-site bump of {site} must not pass"
    assert any(f.path.endswith(expect_suffix) for f in findings)


def test_r9_clean_on_the_real_repo_pins():
    """The actual producer/schema/checker/fixture quad is consistent —
    the standalone gate check_all.sh runs."""
    from kaminpar_tpu.lint.schema_pins import check_schema_pins

    assert check_schema_pins() == []


# --- PR 17: CLI output formats, rule filtering, baseline growth ------------

def test_cli_rules_alias_filters(capsys):
    bad = os.path.join(FIXTURES, "r2_bad.py")
    assert main([bad, "--no-baseline", "--rules", "R4"]) == 0
    assert main([bad, "--no-baseline", "--rules", "R2,R4"]) == 1
    capsys.readouterr()


def test_cli_json_reports_baseline_entries(capsys):
    bad = os.path.join(FIXTURES, "r4_bad.py")
    assert main([bad, "--no-baseline", "--format", "json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["baseline_entries"] == 0
    assert payload["stale_baseline_entries"] == 0


def test_cli_sarif_format(capsys):
    bad = os.path.join(FIXTURES, "r4_bad.py")
    assert main([bad, "--no-baseline", "--format", "sarif"]) == 1
    sarif = json.loads(capsys.readouterr().out)
    assert sarif["version"] == "2.1.0"
    run = sarif["runs"][0]
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"R1", "R9"} <= rule_ids
    assert run["results"], "findings must surface as results"
    res = run["results"][0]
    assert res["ruleId"] == "R4"
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("r4_bad.py")
    assert loc["region"]["startLine"] >= 1
    assert run["properties"]["totalFindings"] == 3


def test_cli_write_baseline_refuses_growth(tmp_path, capsys):
    """The ratchet only shrinks: regenerating over an existing baseline
    with MORE findings than entries is refused."""
    good = os.path.join(FIXTURES, "r2_good.py")
    bad = os.path.join(FIXTURES, "r2_bad.py")
    out = tmp_path / "b.json"
    # seed an empty baseline from a clean file
    assert main([good, "--write-baseline", "--baseline", str(out)]) == 0
    assert load_baseline(str(out)) == []
    # growing it is refused, and the file is untouched
    assert main([bad, "--write-baseline", "--baseline", str(out)]) == 2
    assert load_baseline(str(out)) == []
    capsys.readouterr()
    # equal-or-shrinking rewrites still work
    fresh = tmp_path / "fresh.json"
    assert main([bad, "--write-baseline", "--baseline", str(fresh)]) == 0
    assert main([bad, "--write-baseline", "--baseline", str(fresh)]) == 0


def test_checked_in_baseline_is_empty():
    """PR 17 acceptance: the package is clean against an EMPTY baseline
    — zero accepted findings left."""
    assert load_baseline(DEFAULT_BASELINE) == []
