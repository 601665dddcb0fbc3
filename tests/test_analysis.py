"""The analyzer analyzed: every lint rule fires exactly once on its planted
fixture, the dispatch auditor catches dropped donation / host callbacks /
dtype widening on synthetic entry points, the real tree is clean with an
empty waiver file, and the shared testlib asserters behave.

Fixtures are PARSED, never imported — importing ``import_reg.py`` would
mutate the real backend registry.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import dispatch as D
from repro.analysis import testlib as TL
from repro.analysis.dispatch import AuditTarget, EntryContract
from repro.analysis.entrypoints import default_targets, prefill_buckets
from repro.analysis.findings import (Finding, Report, is_waived,
                                     load_waivers, split_waived)
from repro.analysis.lint import find_repo_root, lint_file, run_lint
from repro.analysis.rules import LintRule, get_rule, register_rule, rule_ids

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "lint")


def _fixture_source(name):
    with open(os.path.join(FIXTURES, name)) as f:
        return f.read()


# ---------------------------------------------------------------------------
# AST rules: planted violations fire exactly once
# ---------------------------------------------------------------------------

PLANTED = [
    # (fixture file, path the module pretends to live at, rule that fires)
    ("raw_backend.py", "src/repro/serve/sneaky.py", "movement-raw-backend"),
    ("host_sync_tick.py", "src/repro/sched/scheduler.py",
     "host-sync-in-hot-loop"),
    ("nan_json.py", "benchmarks/fixture.py", "json-nan"),
    ("wallclock.py", "src/repro/sched/fixture.py",
     "wallclock-in-virtual-clock"),
    ("import_reg.py", "src/repro/movement/fixture.py",
     "import-time-registration"),
    ("unref_alias.py", "src/repro/serve/fixture.py", "unrefcounted-alias"),
    ("unclosed_span.py", "src/repro/obs/fixture.py", "unclosed-span"),
]


@pytest.mark.parametrize("fixture,spoofed_path,rule",
                         PLANTED, ids=[p[2] for p in PLANTED])
def test_planted_violation_fires_exactly_once(fixture, spoofed_path, rule):
    findings = lint_file(spoofed_path, _fixture_source(fixture))
    assert [f.rule for f in findings] == [rule], findings
    assert findings[0].path == spoofed_path
    assert findings[0].line > 0


def test_raw_backend_allowed_in_backend_registry():
    """The same raw call is CLEAN where the architecture places it."""
    src = _fixture_source("raw_backend.py")
    assert lint_file("src/repro/movement/backends.py", src) == []
    assert lint_file("src/repro/kernels/ops.py", src) == []


def test_host_sync_sanctioned_functions_are_structural():
    """step_end's one transfer per step is allowlisted IN THE RULE, not in
    the waiver file: the same .item() is a finding in any other function."""
    src = ("class Engine:\n"
           "    def step_end(self, handle):\n"
           "        return handle.item()\n"
           "    def tick_helper(self, handle):\n"
           "        return handle.item()\n")
    findings = lint_file("src/repro/serve/engine.py", src)
    assert [f.rule for f in findings] == ["host-sync-in-hot-loop"]
    assert findings[0].line == 5            # tick_helper's, not step_end's


def test_host_sync_out_of_scope_module_is_clean():
    src = "def f(x):\n    return x.item()\n"
    assert lint_file("src/repro/roofline/hlo.py", src) == []


def test_wallclock_rule_covers_obs_package():
    """The tracer records MODELED ns only; a wall-clock read under obs/
    would stamp host time onto the virtual timeline."""
    src = "import time\n\n\ndef stamp():\n    return time.time()\n"
    findings = lint_file("src/repro/obs/clock.py", src)
    assert [f.rule for f in findings] == ["wallclock-in-virtual-clock"]
    assert lint_file("src/repro/roofline/clock.py", src) == []


# ---------------------------------------------------------------------------
# the real tree is clean; the waiver file is empty
# ---------------------------------------------------------------------------

def test_clean_tree_zero_findings_empty_waivers():
    root = find_repo_root()
    report = run_lint(repo_root=root)
    assert report.findings == [], [str(f) for f in report.findings]
    assert report.waived == []
    assert report.files_scanned > 50        # it really walked the tree
    assert set(report.rules) == set(rule_ids())
    # the committed waiver file exists and is EMPTY (comments only)
    assert load_waivers(os.path.join(root, "LINT_WAIVERS")) == []


def test_waiver_matching_and_strict_report():
    f = Finding(rule="json-nan", path="benchmarks/x.py", line=7, message="m")
    assert is_waived(f, ["json-nan:benchmarks/x.py"])
    assert is_waived(f, ["json-nan:benchmarks/x.py:7"])
    assert not is_waived(f, ["json-nan:benchmarks/x.py:8"])
    assert not is_waived(f, ["json-nan:benchmarks/y.py"])
    active, waived = split_waived([f], ["json-nan:benchmarks/x.py"])
    assert active == [] and waived == [f]


def test_report_is_strict_json(tmp_path):
    rep = Report(roots=["src/repro"], rules=["json-nan"],
                 findings=[Finding("json-nan", "a.py", 1, "m")])
    path = tmp_path / "r.json"
    rep.write(str(path))
    loaded = json.loads(path.read_text())
    assert loaded["schema"] == "repro-lint-report/v1"
    assert loaded["counts"]["findings"] == 1
    # NaN anywhere in the report must fail at WRITE time
    rep.audit = {"bad": float("nan")}
    with pytest.raises(ValueError):
        rep.write(str(path))


def test_rule_registry_contract():
    """Fourth registry instance, same contract as mechanisms/backends/
    policies: same-class re-registration is reload-safe, an impostor class
    under a taken id raises."""
    from repro.analysis.rules import JsonNanRule
    assert register_rule(JsonNanRule) is JsonNanRule       # reload-safe

    with pytest.raises(ValueError, match="already registered"):
        @register_rule
        class Impostor(LintRule):
            id = "json-nan"
    assert type(get_rule("json-nan")).__name__ == "JsonNanRule"
    with pytest.raises(ValueError, match="unknown lint rule"):
        get_rule("no-such-rule")


# ---------------------------------------------------------------------------
# dispatch auditor on synthetic entry points
# ---------------------------------------------------------------------------

def _args2():
    return jnp.zeros((2, 2)), jnp.ones((2, 2))


def test_audit_donation_dropped_fires():
    """The planted 'donation dropped' fixture: a wrapper re-jitted WITHOUT
    donate_argnums while the contract still promises in-place update."""
    fn = jax.jit(lambda c, s: (c + 1.0, s * 2.0))        # donation dropped
    t = AuditTarget("fixture", fn, _args2(),
                    EntryContract(donate=frozenset({1})))
    rec, findings = D.audit_target(t, compiled=False)
    assert [f.rule for f in findings] == ["audit-donation"]
    assert "silently dropped" in findings[0].message
    assert rec["donated_leaves"] == 0


def test_audit_undeclared_donation_fires():
    fn = jax.jit(lambda c, s: (c + 1.0, s * 2.0), donate_argnums=(0,))
    t = AuditTarget("fixture", fn, _args2(), EntryContract())
    _, findings = D.audit_target(t, compiled=False)
    assert [f.rule for f in findings] == ["audit-donation"]
    assert "does not declare" in findings[0].message


def test_audit_honored_donation_is_clean():
    fn = jax.jit(lambda c, s: (c + 1.0, s * 2.0), donate_argnums=(1,))
    t = AuditTarget("fixture", fn, _args2(),
                    EntryContract(donate=frozenset({1})))
    rec, findings = D.audit_target(t, compiled=True)
    assert findings == []
    assert rec["donated_leaves"] == rec["expected_donated_leaves"] == 1
    assert rec["hlo_donor_marks"] >= 1
    assert rec["hlo_host_transfer_ops"] == 0


def test_audit_uint8_upcast_fires():
    fn = jax.jit(lambda pages: pages.astype(jnp.float32).sum())
    t = AuditTarget("fixture", fn, (jnp.zeros(8, jnp.uint8),),
                    EntryContract(uint8_preserving=True))
    rec, findings = D.audit_target(t, compiled=False)
    assert [f.rule for f in findings] == ["audit-dtype"]
    assert rec["uint8_upcasts"] == 1


def test_audit_bitcast_page_path_is_clean():
    """The real page discipline — bitcast, never convert — audits clean."""
    fn = jax.jit(
        lambda x: jax.lax.bitcast_convert_type(x, jnp.uint8).reshape(-1))
    t = AuditTarget("fixture", fn, (jnp.zeros((2, 4), jnp.float32),),
                    EntryContract(uint8_preserving=True))
    rec, findings = D.audit_target(t, compiled=False)
    assert findings == []
    assert rec["uint8_upcasts"] == 0


def test_audit_host_callback_fires():
    def leaky(x):
        jax.debug.print("x = {x}", x=x)      # a host callback in the graph
        return x + 1.0

    t = AuditTarget("fixture", jax.jit(leaky), (jnp.zeros(2),),
                    EntryContract())
    rec, findings = D.audit_target(t, compiled=False)
    assert [f.rule for f in findings] == ["audit-host-transfer"]
    assert rec["jaxpr_host_transfer_eqns"] >= 1


def test_audit_bucket_stability():
    class FakeEngine:
        max_len = 32

        def _bucket_len(self, n):
            return n                          # exact lengths: unbounded keys

    assert D.audit_bucket_stability(FakeEngine(), [16, 32]) != []

    class Bucketed(FakeEngine):
        def _bucket_len(self, n):
            return min(max(16, 1 << (n - 1).bit_length()), self.max_len)

    assert D.audit_bucket_stability(Bucketed(), [16, 32]) == []


def test_default_targets_audit_clean():
    """Every registered jitted entry point honors its documented contract
    (lowering + jaxpr layers; CI's lint-audit job adds the compiled-HLO
    walk)."""
    targets, engine = default_targets()
    extra = D.audit_bucket_stability(engine, prefill_buckets(engine))
    audit = D.run_audit(targets, compiled=False, extra_findings=extra)
    assert audit["findings"] == [], audit["findings"]
    names = {t["name"] for t in audit["targets"]}
    assert {"decode", "suspend", "suspend_many", "resume", "resume_many",
            "migrate_gather", "migrate", "simulate_params"} <= names
    assert any(n.startswith("prefill[") for n in names)
    for rec in audit["targets"]:
        assert rec["donated_leaves"] == rec["expected_donated_leaves"]
        assert rec["jaxpr_host_transfer_eqns"] == 0


# ---------------------------------------------------------------------------
# the shared testlib asserters (what the engine/cluster/sched tests gate on)
# ---------------------------------------------------------------------------

def test_testlib_compile_count_contract():
    counts = {"decode": 1, "resume_many": 2, "suspend": 0}
    TL.assert_compile_count(counts, "decode", 1)
    TL.assert_compile_count(counts, "resume_many", range(3))
    TL.assert_compile_count(counts, "suspend", 0)        # unused path
    TL.assert_compile_at_most(counts, "resume_many", 2)
    with pytest.raises(AssertionError, match="decode compiled 1x"):
        TL.assert_compile_count(counts, "decode", 2)
    with pytest.raises(AssertionError, match="> bound"):
        TL.assert_compile_at_most(counts, "resume_many", 1)


def test_testlib_dispatch_delta():
    before = {"decode_dispatches": 3, "host_transfers": 3}
    after = {"decode_dispatches": 9, "host_transfers": 9}
    TL.assert_dispatch_delta(before, after, decode=6, host=6)
    with pytest.raises(AssertionError, match="decode dispatches"):
        TL.assert_dispatch_delta(before, after, decode=5)
    with pytest.raises(AssertionError, match="host transfers"):
        TL.assert_dispatch_delta(before, after, decode=6, host=5)
