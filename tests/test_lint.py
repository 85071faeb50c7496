"""Fixture tests for the AST lint engine (`repro.analysis.lint`).

Every rule gets a bad fixture (exact rule id + line pinned) and a good
fixture (idiomatic spellings of the same territory, zero findings), under
``tests/data/lint/``. The final test is the repo gate itself: ``src/repro``
lints clean — it runs in well under 10 s (no JAX import) and fails fast
before the tracing suites.
"""
import os

import pytest

from repro.analysis import run_lint, rule_ids
from repro.analysis.lint import main, package_relpath

DATA = os.path.join(os.path.dirname(__file__), "data", "lint")
SRC = os.path.join(os.path.dirname(__file__), "..", "src", "repro")


def _fixture(name):
    return os.path.join(DATA, name)


# (bad fixture, rule id, expected finding lines)
_BAD = [
    ("bad_version_gated.py", "jax-version-gated", {2, 7, 8, 9, 10}),
    ("bad_custom_vjp.py", "custom-vjp-outside-site", {2, 7, 8}),
    ("bad_ctx.py", "ctx-outside-api-nn", {7, 8}),
    ("bad_prng_reuse.py", "prng-key-reuse", {8}),
    ("bad_host_sync.py", "host-sync-in-jit", {11, 12, 13, 18}),
    ("bad_tracer_branch.py", "tracer-branch", {7, 9}),
    ("bad_swallowed.py", "swallowed-exception", {8, 16}),
    ("bad_thread.py", "thread-uncaptured-target", {10, 16}),
    ("bad_wall_clock.py", "wall-clock-outside-obs", {2, 7, 9, 10}),
]

_GOOD = [
    "good_version_gated.py",
    "good_custom_vjp.py",
    "good_ctx.py",
    "good_prng_reuse.py",
    "good_host_sync.py",
    "good_tracer_branch.py",
    "good_swallowed.py",
    "good_thread.py",
    "good_wall_clock.py",
]


@pytest.mark.parametrize("fname,rule,lines", _BAD,
                         ids=[b[0] for b in _BAD])
def test_bad_fixture_trips_exactly(fname, rule, lines):
    result = run_lint([_fixture(fname)])
    assert not result.waived
    assert {f.rule for f in result.findings} == {rule}
    assert {f.line for f in result.findings} == lines
    # findings render as clickable path:line with the rule id
    for f in result.findings:
        assert str(f).startswith(f"{f.path}:{f.line}: [{rule}]")


@pytest.mark.parametrize("fname", _GOOD)
def test_good_fixture_is_clean_under_all_rules(fname):
    result = run_lint([_fixture(fname)])
    assert not result.findings, [str(f) for f in result.findings]
    assert not result.waived


def test_inline_waiver_suppresses_but_records():
    result = run_lint([_fixture("waived.py")])
    assert not result.findings
    assert [(f.line, f.rule) for f in result.waived] == \
        [(6, "custom-vjp-outside-site")]
    assert result.ok


def test_waiver_for_other_rule_does_not_suppress(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text("import jax\n\n\ndef f(g):\n"
                 "    return jax.custom_vjp(g)  # lint: waive=tracer-branch\n")
    result = run_lint([str(p)])
    assert [f.rule for f in result.findings] == ["custom-vjp-outside-site"]
    assert not result.waived


def test_syntax_error_becomes_parse_error_finding(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n    pass\n")
    result = run_lint([str(p)])
    assert [f.rule for f in result.findings] == ["parse-error"]


def test_select_unknown_rule_raises():
    with pytest.raises(ValueError, match="no-such-rule"):
        run_lint([DATA], select=["no-such-rule"])


def test_select_restricts_to_named_rule():
    result = run_lint([_fixture("bad_version_gated.py")],
                      select=["ctx-outside-api-nn"])
    assert not result.findings


def test_package_relpath_normalizes_to_package_root():
    assert package_relpath("src/repro/compat.py") == "compat.py"
    assert package_relpath("./src/repro/core/site.py") == "core/site.py"
    # fixtures outside a repro/ dir keep their basename — never allowlisted
    assert package_relpath("tests/data/lint/bad_ctx.py") == "bad_ctx.py"


def test_cli_exit_codes(capsys):
    assert main([_fixture("good_ctx.py")]) == 0
    assert "0 finding(s)" in capsys.readouterr().out
    assert main([_fixture("bad_ctx.py")]) == 1
    out = capsys.readouterr().out
    assert "[ctx-outside-api-nn]" in out and "2 finding(s)" in out
    assert main(["--list-rules"]) == 0
    listed = capsys.readouterr().out
    for rid in rule_ids():
        assert rid in listed


def test_src_tree_lints_clean():
    """The repo gate: zero findings AND zero waivers across src/repro."""
    result = run_lint([SRC])
    assert not result.findings, "\n".join(str(f) for f in result.findings)
    assert not result.waived, "\n".join(str(f) for f in result.waived)
