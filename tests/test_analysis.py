"""kllms-check: per-rule fixture tests, CLI contract, and the tier-1 gate.

Every rule is pinned twice: a ``bad`` fixture that must produce the rule's
findings and a ``good`` fixture that must stay silent (a rule that cannot
fire protects nothing; a rule that fires on idiomatic code gets suppressed
into noise). The package-wide run is the tentpole gate: the real serving
stack must be lint-clean on every PR, via the same ``--check`` entry point CI
uses.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from k_llms_tpu.analysis.framework import (
    DEFAULT_CONFIG,
    RULES,
    _ensure_rules_loaded,
    load_project,
    run_rules,
    unsuppressed,
)

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "fixtures" / "lint"

EXPECTED_RULES = {
    "lock-order",
    "dispatch-under-lock",
    "host-sync-hot-path",
    "jit-recompile-hygiene",
    "failpoint-coverage",
    "counter-hygiene",
    "wire-error-contract",
    "guarded-by",
    "guarded-by-unguarded",
    "guarded-by-escape",
    "guarded-by-annotation",
}

GUARDED_BY_FAMILY = (
    "guarded-by",
    "guarded-by-unguarded",
    "guarded-by-escape",
    "guarded-by-annotation",
)


def run_fixture(rule_id, rel, config=None, readme=None, test_sources=None):
    """Run one rule over one fixture subtree as a standalone project."""
    cfg = dict(DEFAULT_CONFIG)
    cfg.update(config or {})
    project = load_project(
        FIXTURES, paths=[FIXTURES / rel], config=cfg, with_context=False
    )
    assert project.files, f"fixture {rel} matched no files"
    assert all(f.parse_error is None for f in project.files)
    project.readme = readme
    project.test_sources = dict(test_sources or {})
    return run_rules(project, [rule_id])


def messages(findings):
    return [f.message for f in findings if not f.suppressed]


# ---------------------------------------------------------------------------
# rule registry
# ---------------------------------------------------------------------------


def test_registry_has_all_project_rules_with_metadata():
    _ensure_rules_loaded()
    assert EXPECTED_RULES <= set(RULES)
    assert len(RULES) >= 6
    for rid, cls in RULES.items():
        rule = cls()
        assert rule.id == rid
        assert rule.summary and rule.invariant and rule.subsystem, rid


# ---------------------------------------------------------------------------
# per-rule fixtures
# ---------------------------------------------------------------------------


def test_lock_order_bad_fixture_finds_cycle_and_raw_lock():
    msgs = messages(run_fixture("lock-order", "lock-order/bad.py"))
    assert len(msgs) == 2
    cycle = [m for m in msgs if "lock-order cycle" in m]
    assert len(cycle) == 1
    assert "fix.a" in cycle[0] and "fix.b" in cycle[0]
    raw = [m for m in msgs if "threading.Lock()" in m]
    assert len(raw) == 1 and "bad.RAW" in raw[0]


def test_lock_order_good_fixture_is_clean():
    assert messages(run_fixture("lock-order", "lock-order/good.py")) == []


def test_dispatch_under_lock_bad_fixture():
    msgs = messages(
        run_fixture("dispatch-under-lock", "dispatch-under-lock/bad.py")
    )
    assert len(msgs) == 2
    assert all("fix.guard" in m and "allow_dispatch" in m for m in msgs)


def test_dispatch_under_lock_good_fixture_is_clean():
    assert (
        messages(run_fixture("dispatch-under-lock", "dispatch-under-lock/good.py"))
        == []
    )


HOT_CFG = {
    "host-sync-hot-path": {
        "hot_functions": [
            "decode_step", "paged_*", "grammar_mask_logits", "grammar_advance",
        ]
    }
}


def test_host_sync_bad_fixture_flags_jitted_and_hot_syncs():
    msgs = messages(
        run_fixture("host-sync-hot-path", "host-sync-hot-path/bad.py", HOT_CFG)
    )
    assert len(msgs) == 5
    assert sum("a jitted body" in m for m in msgs) == 1
    assert sum("a configured hot function" in m for m in msgs) == 4
    assert any("*.item" in m for m in msgs)
    assert any("np.asarray" in m for m in msgs)
    assert any("jax.device_get" in m for m in msgs)
    # The glob-matched paged function is flagged, pinning the pattern
    # matching that the real `paged_decode_attention_*` config relies on.
    assert any("*.tolist" in m and "paged_decode_attention_ref" in m for m in msgs)


def test_host_sync_good_fixture_is_clean():
    assert (
        messages(
            run_fixture(
                "host-sync-hot-path", "host-sync-hot-path/good.py", HOT_CFG
            )
        )
        == []
    )


def test_jit_recompile_bad_fixture():
    msgs = messages(
        run_fixture("jit-recompile-hygiene", "jit-recompile-hygiene/bad.py")
    )
    assert len(msgs) == 1
    assert "recompiles on every call" in msgs[0]


JIT_CFG = {
    "jit-recompile-hygiene": {
        "builder_functions": ["_get_decode_loop", "_grammar_programs"]
    }
}


def test_jit_recompile_good_fixture_sanctions_every_memoized_pattern():
    assert (
        messages(
            run_fixture(
                "jit-recompile-hygiene", "jit-recompile-hygiene/good.py", JIT_CFG
            )
        )
        == []
    )


def test_jit_recompile_builder_config_is_load_bearing():
    # Without the configured builder_functions entries the same fixture must
    # fire on every config-sanctioned builder — proving the pyproject
    # `_get_decode_loop` / `_grammar_programs` entries suppress real findings.
    msgs = messages(
        run_fixture("jit-recompile-hygiene", "jit-recompile-hygiene/good.py")
    )
    assert len(msgs) == 2
    assert any("_get_decode_loop" in m for m in msgs)
    assert any("_grammar_programs" in m for m in msgs)


BAD_FP_TESTS = {
    "tests/test_x.py": 'spec = FailSpec(action="error")\nfire("engine.launch")\n'
}
BAD_FP_README = "| `engine.launch` | engine | batched launch |\n"


def test_failpoint_coverage_bad_fixture():
    msgs = messages(
        run_fixture(
            "failpoint-coverage",
            "failpoint-coverage/bad",
            readme=BAD_FP_README,
            test_sources=BAD_FP_TESTS,
        )
    )
    assert len(msgs) == 6
    assert sum("string literal" in m for m in msgs) == 1
    assert sum("'engine.typo' is not registered" in m for m in msgs) == 1
    assert sum("dead registry entry" in m for m in msgs) == 1
    assert sum("exercised by no test" in m for m in msgs) == 1
    assert sum("README registry-table" in m for m in msgs) == 1
    assert sum("'hang' is never" in m for m in msgs) == 1


GOOD_FP_TESTS = {
    "tests/test_x.py": (
        'FailSpec(action="error")\nFailSpec(action="hang")\n'
        'fire("engine.launch")\nfire("engine.pages")\n'
    )
}
GOOD_FP_README = (
    "| `engine.launch` | engine | batched launch |\n"
    "| `engine.pages` | engine | slot page release |\n"
)


def test_failpoint_coverage_good_fixture_is_clean():
    assert (
        messages(
            run_fixture(
                "failpoint-coverage",
                "failpoint-coverage/good",
                readme=GOOD_FP_README,
                test_sources=GOOD_FP_TESTS,
            )
        )
        == []
    )


def test_counter_hygiene_bad_fixture():
    msgs = messages(run_fixture("counter-hygiene", "counter-hygiene/bad"))
    assert len(msgs) == 9
    # Counter group findings.
    assert sum("counter group" in m and "without declared=" in m for m in msgs) == 1
    assert sum("'a.typo'" in m for m in msgs) == 1
    assert sum("'stale.name'" in m and "never" in m for m in msgs) == 1
    assert sum("not surfaced" in m and "ALPHA_EVENTS" in m for m in msgs) == 1
    # Histogram group findings mirror the counter contract.
    assert sum("histogram group" in m and "without declared=" in m for m in msgs) == 1
    assert sum("'h.typo'" in m for m in msgs) == 1
    assert sum("'h.span_typo'" in m for m in msgs) == 1  # span() literals too
    assert sum("'stale.hist'" in m and "never observed" in m for m in msgs) == 1
    assert sum("not surfaced" in m and "GAMMA_HIST" in m for m in msgs) == 1


def test_counter_hygiene_good_fixture_is_clean():
    assert messages(run_fixture("counter-hygiene", "counter-hygiene/good")) == []


def test_wire_error_contract_bad_fixture():
    msgs = messages(
        run_fixture("wire-error-contract", "wire-error-contract/bad.py")
    )
    assert len(msgs) == 3
    assert sum("BadError" in m and "type, status_code" in m for m in msgs) == 1
    assert sum("PartialError" in m and "status_code" in m for m in msgs) == 1
    assert sum("WorseError.as_wire" in m for m in msgs) == 1


def test_wire_error_contract_good_fixture_is_clean():
    assert (
        messages(run_fixture("wire-error-contract", "wire-error-contract/good.py"))
        == []
    )


def test_guarded_by_good_fixtures_are_clean():
    for rid in GUARDED_BY_FAMILY:
        assert messages(run_fixture(rid, "guarded-by/good")) == [], rid


def test_guarded_by_bad_fixture_flags_minority_declared_and_tie():
    msgs = messages(run_fixture("guarded-by", "guarded-by/bad"))
    assert len(msgs) == 3
    declared = [m for m in msgs if "declared via # kllms: guarded-by" in m]
    assert len(declared) == 1
    assert "Annotated._items" in declared[0] and "Annotated.add" in declared[0]
    inferred = [m for m in msgs if "inferred: held at 2 of 3 access sites" in m]
    assert len(inferred) == 1
    assert "Stats._counts" in inferred[0] and "read in Stats.peek" in inferred[0]
    tie = [m for m in msgs if "cannot infer a guard" in m]
    assert len(tie) == 1
    assert "'fix.torn_a'" in tie[0] and "'fix.torn_b'" in tie[0]
    assert "guarded-by[<lock>]" in tie[0]


def test_guarded_by_unguarded_bad_fixture_names_every_writer():
    msgs = messages(run_fixture("guarded-by-unguarded", "guarded-by/bad"))
    assert len(msgs) == 1
    assert "Gauge.level is written from 2 methods" in msgs[0]
    assert "Gauge.down, Gauge.up" in msgs[0]
    assert "kllms: unguarded" in msgs[0]


def test_guarded_by_unguarded_min_writers_config_is_load_bearing():
    cfg = {"guarded-by": {"min_write_methods": 3}}
    assert messages(run_fixture("guarded-by-unguarded", "guarded-by/bad", cfg)) == []


def test_guarded_by_ignore_pattern_exempts_attribute():
    cfg = {"guarded-by": {"ignore": ["Stats._*"]}}
    assert (
        messages(run_fixture("guarded-by", "guarded-by/bad/inferred.py", cfg)) == []
    )


def test_guarded_by_escape_bad_fixture():
    msgs = messages(run_fixture("guarded-by-escape", "guarded-by/bad"))
    assert len(msgs) == 2
    assert sum("returned raw from Leaky.raw" in m for m in msgs) == 1
    assert (
        sum("passed raw into self._executor.submit" in m for m in msgs) == 1
    )
    assert all("Leaky._ring" in m and "'fix.leaky'" in m for m in msgs)


def test_guarded_by_annotation_bad_fixture_cross_checks_lock_names():
    msgs = messages(run_fixture("guarded-by-annotation", "guarded-by/bad"))
    assert len(msgs) == 2
    unknown = [m for m in msgs if "names no known lock" in m]
    assert len(unknown) == 1
    # The cross-check vocabulary comes from the lock-order extraction: the
    # typo'd name is rejected and the class's canonical names are offered.
    assert "fix.nosuch" in unknown[0]
    assert "canonical names for Annotated: fix.annotated" in unknown[0]
    assert sum("needs a reason" in m for m in msgs) == 1


# ---------------------------------------------------------------------------
# suppression machinery + parse errors
# ---------------------------------------------------------------------------


def test_inline_suppressions_cover_same_line_and_line_above():
    findings = run_fixture("lock-order", "suppression/bad.py")
    assert len(findings) == 3
    silenced = [f for f in findings if f.suppressed]
    loud = [f for f in findings if not f.suppressed]
    assert len(silenced) == 2 and len(loud) == 1
    assert all(f.suppress_reason for f in silenced)
    assert "LOUD" in loud[0].message


def test_syntax_error_becomes_parse_error_finding(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def nope(:\n", encoding="utf-8")
    project = load_project(
        tmp_path, paths=[bad], config=dict(DEFAULT_CONFIG), with_context=False
    )
    findings = run_rules(project, ["lock-order"])
    assert [f.rule for f in findings] == ["parse-error"]
    assert not findings[0].suppressed


def test_unknown_rule_id_raises():
    project = load_project(
        FIXTURES,
        paths=[FIXTURES / "lock-order" / "good.py"],
        config=dict(DEFAULT_CONFIG),
        with_context=False,
    )
    with pytest.raises(ValueError, match="unknown rule"):
        run_rules(project, ["no-such-rule"])


# ---------------------------------------------------------------------------
# CLI contract + the tier-1 package gate
# ---------------------------------------------------------------------------


def _cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "k_llms_tpu.analysis", *args],
        cwd=cwd,
        capture_output=True,
        text=True,
        timeout=120,
    )


@pytest.mark.duration_budget(10)
def test_package_is_lint_clean_via_check_cli():
    """The tentpole gate: `python -m k_llms_tpu.analysis --check` exits 0
    over the real package, with the full rule set enabled."""
    proc = _cli("--check", "--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert set(doc) == {"root", "files", "rules", "findings", "ok"}
    assert doc["ok"] is True
    assert doc["findings"] == []
    assert doc["files"] > 50
    assert EXPECTED_RULES <= set(doc["rules"])


def test_cli_exits_one_with_findings_on_bad_fixture():
    proc = _cli(
        "--root",
        str(FIXTURES),
        str(FIXTURES / "lock-order" / "bad.py"),
        "--rule",
        "lock-order",
        "--json",
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["ok"] is False
    assert doc["rules"] == ["lock-order"]
    for f in doc["findings"]:
        assert set(f) == {
            "rule", "file", "line", "message", "suppressed", "suppress_reason",
        }
        assert f["rule"] == "lock-order" and f["line"] > 0


def test_cli_list_rules_and_usage_error():
    proc = _cli("--list-rules")
    assert proc.returncode == 0
    for rid in EXPECTED_RULES:
        assert rid in proc.stdout
    proc = _cli("--rule", "no-such-rule")
    assert proc.returncode == 2
    assert "unknown rule" in proc.stderr


def test_package_lint_in_process_matches_cli():
    """Same gate without the subprocess, so failures show findings inline."""
    project = load_project(REPO)
    findings = unsuppressed(run_rules(project))
    assert not findings, "\n".join(f.format() for f in findings)


# ---------------------------------------------------------------------------
# SARIF output + baseline suppression
# ---------------------------------------------------------------------------


def test_sarif_output_matches_2_1_0_shape():
    """Pin the SARIF 2.1.0 shape CI consumes: schema/version headers, the
    rule metadata as driver rule descriptors, and per-result locations."""
    proc = _cli(
        "--root",
        str(FIXTURES),
        str(FIXTURES / "guarded-by" / "bad"),
        "--rule",
        "guarded-by",
        "--sarif",
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["version"] == "2.1.0"
    assert doc["$schema"].endswith("sarif-schema-2.1.0.json")
    (run,) = doc["runs"]
    driver = run["tool"]["driver"]
    assert driver["name"] == "kllms-check"
    assert [r["id"] for r in driver["rules"]] == ["guarded-by"]
    for r in driver["rules"]:
        assert r["shortDescription"]["text"]
        assert r["fullDescription"]["text"]
    assert run["originalUriBaseIds"]["SRCROOT"]["uri"].startswith("file://")
    assert len(run["results"]) == 3
    for res in run["results"]:
        assert res["ruleId"] == "guarded-by"
        assert res["ruleIndex"] == 0
        assert res["level"] == "error"
        assert res["message"]["text"]
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith(".py")
        assert loc["artifactLocation"]["uriBaseId"] == "SRCROOT"
        assert loc["region"]["startLine"] >= 1
        assert res["partialFingerprints"]["kllmsFingerprint/v1"]


def test_sarif_and_json_are_mutually_exclusive():
    proc = _cli("--sarif", "--json")
    assert proc.returncode == 2
    assert "mutually exclusive" in proc.stderr


def test_baseline_makes_dirty_tree_pass_but_new_finding_fails(tmp_path):
    bad = str(FIXTURES / "guarded-by" / "bad")
    base = tmp_path / "baseline.json"
    proc = _cli(
        "--root", str(FIXTURES), bad,
        "--rule", "guarded-by",
        "--write-baseline", str(base),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(base.read_text(encoding="utf-8"))
    assert doc["version"] == 1
    assert len(doc["fingerprints"]) == 3
    # The dirty tree passes against its recorded baseline...
    proc = _cli(
        "--root", str(FIXTURES), bad,
        "--rule", "guarded-by",
        "--check", "--baseline", str(base),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # ...while findings NOT in the baseline (here: another family rule over
    # the same tree) still fail the run.
    proc = _cli(
        "--root", str(FIXTURES), bad,
        "--rule", "guarded-by", "--rule", "guarded-by-escape",
        "--check", "--baseline", str(base),
    )
    assert proc.returncode == 1
    assert "guarded-by-escape" in proc.stdout
    assert "declared via # kllms: guarded-by" not in proc.stdout


def test_baseline_usage_error_on_malformed_file(tmp_path):
    broken = tmp_path / "broken.json"
    broken.write_text("not json", encoding="utf-8")
    proc = _cli("--baseline", str(broken))
    assert proc.returncode == 2
    assert "--baseline" in proc.stderr
