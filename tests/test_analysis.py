"""Tests for ``repro.analysis`` — the project static verifier.

The fixture corpus under ``tests/fixtures/analysis`` reproduces the three
bug classes this repo actually shipped, each of which must surface under
its own stable code:

* PR-5: ``np.asarray`` pinning a donated trainer state  → **RPR002**
* PR-4: a tuned block exceeding its lane-padded problem → **RPR201**
* PR-2: backend-string vocabulary drift                 → **RPR005**

The corpus directory is pruned from recursive discovery (the repo tree
must stay clean) but analyzed when named explicitly — both sides are
tested here.
"""

import json
import os

import pytest

from repro.analysis import CODES, Diagnostic, analyze_file
from repro.analysis import cli as analysis_cli
from repro.analysis import configcheck, registry
from repro.analysis.diagnostics import format_github, format_json, render
from repro.core.execution import validate_registry

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "analysis")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fx(name):
    return os.path.join(FIXTURES, name)


def code_lines(diags):
    return sorted((d.code, d.line) for d in diags)


# ---------------------------------------------------------------------------
# Registry contracts (satellite: execution.validate_registry)
# ---------------------------------------------------------------------------


class TestRegistryContracts:
    def test_validate_registry_clean(self):
        assert validate_registry() == []

    def test_registry_check_clean(self):
        assert registry.check_registry() == []

    def test_shipped_trees_clean(self):
        assert configcheck.check_shipped_trees() == []

    def test_vocabulary_spans_both_registries(self):
        vocab = analysis_cli.build_vocabulary()
        assert {"xla", "pallas", "pallas_lean", "auto"} <= vocab
        # Measurement-scorer names are a separate vocabulary and must not
        # be flagged as backend drift.
        assert {"cost-model", "wallclock"} <= vocab

    def test_fault_point_vocabulary_tracks_live_registry(self):
        from repro.runtime.faults import FAULT_POINTS

        points = analysis_cli.build_fault_points()
        assert points == frozenset(FAULT_POINTS)
        assert {"engine_stall", "pod_death", "admission_fail",
                "latency_spike"} <= points


# ---------------------------------------------------------------------------
# AST passes over the fixture corpus
# ---------------------------------------------------------------------------


class TestFixtureCorpus:
    def test_donation_pin_bug_class(self):
        # The PR-5 class: both the inline host copy and the named one are
        # RPR002; the read-after-donate is RPR001; the same-statement
        # rebind idiom is untouched.
        diags = analyze_file(fx("donation_pin.py"))
        assert code_lines(diags) == [
            ("RPR001", 31),
            ("RPR002", 20),
            ("RPR002", 24),
        ]

    def test_jit_in_loop(self):
        diags = analyze_file(fx("jit_in_loop.py"))
        assert code_lines(diags) == [("RPR003", 10), ("RPR003", 11)]

    def test_contextvar_discipline(self):
        # Raw set flagged; finally-paired and __exit__-paired sets pass.
        diags = analyze_file(fx("contextvar_set.py"))
        assert code_lines(diags) == [("RPR004", 9)]

    def test_backend_drift_bug_class(self):
        # The PR-2 class: all four trigger forms, one line each.
        diags = analyze_file(fx("backend_drift.py"))
        assert code_lines(diags) == [
            ("RPR005", 11),
            ("RPR005", 12),
            ("RPR005", 13),
            ("RPR005", 14),
        ]

    def test_fault_point_drift_bug_class(self):
        # The ISSUE-10 class: each trigger form (funnel argument, point=
        # keyword, FAULT_POINTS subscript) fires once; the valid-token
        # twin function passes.
        diags = analyze_file(fx("fault_point_drift.py"))
        assert code_lines(diags) == [
            ("RPR006", 13),
            ("RPR006", 15),
            ("RPR006", 16),
            ("RPR006", 17),
        ]
        assert all("injection registry" in d.message for d in diags)

    def test_fault_point_checks_off_without_vocabulary(self):
        # fault_points=None disables only the RPR006 arm.
        from repro.analysis import ast_checks

        with open(fx("fault_point_drift.py"), encoding="utf-8") as f:
            src = f.read()
        vocab = analysis_cli.build_vocabulary()
        assert ast_checks.run_ast_checks(
            fx("fault_point_drift.py"), src, vocab, fault_points=None,
        ) == []

    def test_suppression_semantics(self):
        # A justified noqa silences its finding; a reason-less noqa
        # silences it too but is itself reported; a noqa on a multi-line
        # statement's closing line covers the statement.
        diags = analyze_file(fx("suppressed.py"))
        assert code_lines(diags) == [("RPR000", 19)]

    def test_clean_file_is_clean(self):
        assert analyze_file(fx("clean.py")) == []

    def test_three_bug_classes_have_distinct_codes(self):
        donation = {d.code for d in analyze_file(fx("donation_pin.py"))}
        drift = {d.code for d in analyze_file(fx("backend_drift.py"))}
        cache = {
            d.code
            for d in configcheck.check_tuning_cache_file(
                fx("oversized_block_cache.json")
            )
        }
        assert "RPR002" in donation and "RPR005" not in donation
        assert drift == {"RPR005"}
        assert cache == {"RPR201"}


# ---------------------------------------------------------------------------
# Config/artifact contracts over the fixture corpus
# ---------------------------------------------------------------------------


class TestConfigContracts:
    def test_oversized_block_is_pr4_class(self):
        diags = configcheck.check_tuning_cache_file(
            fx("oversized_block_cache.json")
        )
        assert len(diags) == 1
        (d,) = diags
        assert d.code == "RPR201"
        assert "lane-padded" in d.message and "PR-4" in d.message

    def test_good_cache_is_clean(self):
        assert configcheck.check_tuning_cache_file(fx("good_cache.json")) == []

    def test_non_cache_json_is_ignored(self):
        assert (
            configcheck.check_tuning_cache_file(fx("BENCH_malformed.json"))
            == []
        )

    def test_bench_artifact_schema(self):
        diags = configcheck.check_bench_artifact(fx("BENCH_malformed.json"))
        assert {d.code for d in diags} == {"RPR202"}
        msgs = " ".join(d.message for d in diags)
        assert "jax_version" in msgs  # missing provenance key named
        assert "records" in msgs

    def test_artifacts_dir_globs_bench_files(self):
        diags = configcheck.check_artifacts_dir(FIXTURES)
        assert diags and all(d.code == "RPR202" for d in diags)


# ---------------------------------------------------------------------------
# CLI behavior
# ---------------------------------------------------------------------------


class TestCli:
    def test_corpus_run_is_dirty_and_exits_nonzero(self, capsys):
        rc = analysis_cli.main([FIXTURES, "--no-contracts", "--format", "json"])
        assert rc == 1
        payload = json.loads(capsys.readouterr().out)
        codes = {d["code"] for d in payload["diagnostics"]}
        assert {"RPR000", "RPR001", "RPR002", "RPR003", "RPR004", "RPR005",
                "RPR006", "RPR201"} <= codes

    def test_clean_file_exits_zero(self, capsys):
        rc = analysis_cli.main([fx("clean.py"), "--no-contracts"])
        capsys.readouterr()
        assert rc == 0

    def test_missing_path_exits_two(self, capsys):
        rc = analysis_cli.main(["no/such/path"])
        capsys.readouterr()
        assert rc == 2

    def test_fixtures_pruned_from_recursive_discovery(self):
        py, js = analysis_cli.discover([os.path.join(REPO_ROOT, "tests")])
        assert py and all("fixtures" not in p for p in py)
        assert all("fixtures" not in p for p in js)

    def test_repo_tree_is_clean(self, capsys, monkeypatch):
        # The acceptance gate: the analyzer over the real tree ends clean.
        monkeypatch.chdir(REPO_ROOT)
        rc = analysis_cli.main(["src", "tests", "benchmarks"])
        out = capsys.readouterr()
        assert rc == 0, out.out

    def test_list_codes(self, capsys):
        assert analysis_cli.main(["--list-codes"]) == 0
        assert set(json.loads(capsys.readouterr().out)) == set(CODES)


# ---------------------------------------------------------------------------
# Diagnostic model / output formats
# ---------------------------------------------------------------------------


class TestDiagnostics:
    def test_unknown_code_rejected(self):
        with pytest.raises(ValueError, match="unknown diagnostic code"):
            Diagnostic(code="RPR999", path="x.py", line=1, message="nope")

    def test_github_format_is_annotation(self):
        d = Diagnostic(code="RPR001", path="a.py", line=3, message="m", col=7)
        out = format_github([d])
        assert out.startswith("::error file=a.py,line=3,col=7,title=RPR001::")

    def test_json_format_round_trips(self):
        d = Diagnostic(code="RPR005", path="a.py", line=2, message="m")
        payload = json.loads(format_json([d]))
        assert payload["diagnostics"][0]["code"] == "RPR005"
        assert payload["codes"] == CODES

    def test_render_sorts_and_rejects_unknown_format(self):
        d1 = Diagnostic(code="RPR003", path="b.py", line=9, message="m")
        d2 = Diagnostic(code="RPR003", path="a.py", line=1, message="m")
        assert render([d1, d2], "text").splitlines()[0].startswith("a.py:1")
        with pytest.raises(ValueError, match="unknown format"):
            render([], "sarif")
