"""The compiled-program self-gate: `python -m bigdl_tpu.tools.check
--programs` lowers the package's representative program suite (train/
eval steps, the K=8 window, the ZeRO-2 mesh step, the bf16-policy step,
the generation prefill/decode pair) and every static HLO check passes —
tier-1 keeps the package's own programs clean forever, the way
test_lint_self.py keeps the source clean."""
import json
import os
import subprocess
import sys

import pytest

import bigdl_tpu

PKG_DIR = os.path.dirname(os.path.abspath(bigdl_tpu.__file__))
REPO = os.path.dirname(PKG_DIR)


@pytest.fixture(scope="module")
def suite():
    """ONE enumeration + check run shared by the in-process tests (the
    CLI test pays its own in a subprocess, as users do)."""
    from bigdl_tpu.analysis.programs import verify_programs
    return verify_programs()


def test_verify_programs_self_gate(suite):
    """In-process acceptance: the whole enumerated suite is clean, and
    the suite actually covers the contract surface (window, ZeRO mesh
    step, bf16 policy leg, serving prefill/decode pair)."""
    findings, specs, notes = suite
    active = [f for f in findings if not f.suppressed]
    assert active == [], "\n".join(f.format() for f in active)
    names = {s.name for s in specs}
    assert "train/mlp/window@k8" in names
    assert "train/transformer_lm/step@bf16" in names
    assert "serving/transformer_lm/prefill/16" in names
    assert "serving/transformer_lm/decode/16" in names
    # the fleet speculative-verify rung rides the same enumeration
    # hook: donation + HBM checks cover it like prefill/decode
    assert "serving/transformer_lm/verify/16" in names
    # conftest forces 8 virtual devices, so the mesh leg must be there
    assert "train/mlp/zero2/step" in names, notes
    # ... and so must the seq-parallel window leg
    assert "train/transformer_lm/seq_parallel/window@k2" in names, notes
    assert notes == []
    # every donated program's contract was non-trivial
    donated = [s for s in specs if s.donated > 0]
    assert len(donated) >= 6
    window = next(s for s in specs if s.name == "train/mlp/window@k8")
    assert window.companion is not None and window.scan_length == 8


def test_check_cli_programs_json_exits_zero():
    proc = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.tools.check", "--programs",
         "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)["programs"]
    assert payload["findings"] == []
    assert "train/lenet5/step" in payload["programs"]


def test_check_cli_unknown_rule_exits_two():
    proc = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.tools.check", "--programs",
         "--rules", "no-such-check"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2
    assert "no-such-check" in proc.stderr


def test_check_cli_list_rules_is_unified():
    """--list-rules is ONE catalogue: AST lint rules and HLO program
    checks share the --rules namespace."""
    proc = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.tools.check", "--list-rules"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0
    for name in ("donation-dropped", "entry-collective",
                 "precision-leak", "hbm-over-budget",
                 "scan-dispatch-ratio", "replicated-large-operand",
                 "use-after-donate", "host-sync"):
        assert name in proc.stdout, name
    assert "[hlo]" in proc.stdout and "[lint]" in proc.stdout
    for name in ("unguarded-shared-state", "torn-invariant-write",
                 "lock-order-cycle", "blocking-under-lock",
                 "signal-handler-impure"):
        assert name in proc.stdout, name
    assert "[concur]" in proc.stdout


def test_concur_self_gate_in_process():
    """The package self-analyzes clean under the concurrency analyzer:
    every thread-escaping access of a lock-guarded attribute is locked,
    the package-wide lock-order graph is acyclic, no held-lock region
    blocks, and the preempt signal handler stays flag-only."""
    from bigdl_tpu.analysis.concur import analyze_paths
    findings = analyze_paths([PKG_DIR])
    active = [f for f in findings if not f.suppressed]
    assert active == [], "\n".join(f.format() for f in active)


def test_check_cli_concurrency_exits_zero():
    proc = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.tools.check", "--concurrency",
         "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)["concur"]
    assert [f for f in payload if not f.get("suppressed")] == []


def test_check_cli_concur_rule_subset():
    """--rules with a concur rule name routes to the concurrency pass
    alone (no lint/shape/program passes run)."""
    proc = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.tools.check", "--concurrency",
         "--rules", "lock-order-cycle", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout)["concur"] == []


def test_rule_subset_restricts_checks(suite):
    """A --rules-style subset runs only the named check over the
    suite (and still comes back clean on the package's programs)."""
    from bigdl_tpu.analysis.hlo import run_checks
    _, specs, _ = suite
    findings = run_checks(specs, checks=["donation-dropped"])
    assert [f for f in findings if not f.suppressed] == []
    assert len(specs) >= 8
