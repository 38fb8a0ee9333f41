"""repro-lint end to end: rule fixtures, CLI exit codes, baseline
workflow, the generated contract table, and the runtime DES schedule
sanitizer (including fleet-vs-serial parity with it enabled).

The fixture protocol: every intentional violation in
``tests/data/lint_fixtures/`` carries an ``# expect-lint: <RULE>``
marker on its line.  The analyzer must fire exactly at the markers —
nothing missing, nothing extra — and must stay silent on the real
codebase (minus the checked-in baseline).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import (ScheduleSanitizer, ScheduleSanitizerError,
                            analyze_paths, analyze_repo, maybe_sanitizer)
from repro.analysis import catalog, schemas
from repro.analysis.contracts import (check_contract_table,
                                      generate_contract_table)
from repro.analysis.astutil import import_edges, load_modules
from repro.analysis.findings import load_baseline, write_baseline
from repro.analysis.schemas import (CSV_FAMILY, check_schema_table,
                                    extract_variants, generate_schema_table,
                                    paranoid_validate_rows,
                                    validate_emitted_row)
from repro.core import (FleetEngine, Simulator, get_policy,
                        reset_uid_counters)
from repro.core.types import DeviceModel

ROOT = Path(__file__).resolve().parents[1]
FIXTURES = ROOT / "tests" / "data" / "lint_fixtures"
BASE_PY = ROOT / "src" / "repro" / "core" / "policies" / "base.py"
BENCH_DOC = ROOT / "docs" / "benchmarks.md"

# every statically-checkable rule must have a fixture (the S4xx runtime
# sanitizer rules are exercised by the sanitizer tests below instead)
ALL_RULES = set(catalog.STATIC_RULES)
_MARKER = re.compile(r"#\s*expect-lint:\s*([A-Z]\d{3})")


def _sub_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    return env


def _run_cli(*args, env=None):
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, cwd=ROOT,
        env=env or _sub_env())


def _expected_markers() -> set[tuple[str, str, int]]:
    expected = set()
    files = sorted((FIXTURES / "src").rglob("*.py")) \
        + sorted((FIXTURES / "docs").rglob("*.md"))
    for f in files:
        rel = f.relative_to(FIXTURES).as_posix()
        for lineno, text in enumerate(f.read_text().splitlines(), 1):
            for rule in _MARKER.findall(text):
                expected.add((rule, rel, lineno))
    return expected


# ---------------------------------------------------------------- fixtures
def test_every_rule_has_a_fixture_marker():
    rules = {r for r, _p, _l in _expected_markers()}
    assert rules == ALL_RULES


def test_fixture_findings_match_markers_exactly():
    """Each rule fires exactly at its marker — no silent rules, no
    spurious findings anywhere else in the fixture tree."""
    findings = analyze_paths(FIXTURES)
    actual = {(f.rule, f.path, f.line) for f in findings}
    assert actual == _expected_markers()


@pytest.mark.parametrize("family,rules", [
    ("layering", {"L101", "L102", "L103", "L104", "L105", "L106"}),
    ("determinism", {"D201", "D202", "D203", "D204", "D205"}),
    ("contracts", {"C301", "C302", "C303", "C304"}),
    ("units", {"U501", "U502", "U503", "U504"}),
    ("schemas", {"B601", "B602", "B603"}),
])
def test_each_family_fails_cli_on_fixture(family, rules):
    """Acceptance: every rule family has a fixture that makes the CLI
    exit 1, and the JSON report carries exactly that family's rules."""
    res = _run_cli("--root", str(FIXTURES), "--rules", family,
                   "--format", "json")
    assert res.returncode == 1, res.stderr
    report = json.loads(res.stdout)
    assert {f["rule"] for f in report["fresh"]} == rules


def test_sanitizer_fixture_exits_nonzero():
    """Rule family 4: a corrupted schedule must crash under
    REPRO_SANITIZE=1."""
    res = subprocess.run(
        [sys.executable, str(FIXTURES / "sanitizer_violation.py")],
        capture_output=True, text=True, env=_sub_env())
    assert res.returncode != 0
    assert "S403" in res.stderr


# ------------------------------------------------------------ real codebase
def test_repo_is_clean_minus_baseline():
    findings = analyze_repo()
    assert findings == [], "\n".join(f.format() for f in findings)


def test_cli_exits_zero_on_repo():
    res = _run_cli()
    assert res.returncode == 0, res.stdout + res.stderr


def _package_of(mod) -> str:
    """``repro.<top>`` for a source module, or ``repro.kernels.<sub>`` for
    one inside a kernel subpackage."""
    parts = mod.rel.split("/")[1:]            # drop "src"
    keep = 3 if parts[1] == "kernels" and len(parts) > 3 else 2
    return ".".join(parts[:keep]).removesuffix(".py")


def test_every_package_is_reached_from_an_entry_point():
    """No package of ``repro`` sits outside what the program runs: every
    top-level package or module, and every kernel subpackage, is imported
    (at any depth, inside functions too) from an entry point: the chip
    harness under ``bench/``, ``chip_smoke.py``, ``python -m
    repro.analysis`` or ``python -m repro.bench_kv.db_bench``."""
    src = {m.name: m for m in load_modules(ROOT, [ROOT / "src" / "repro"])}
    todo = [m for m in load_modules(ROOT, [ROOT / "bench",
                                           ROOT / "chip_smoke.py"])
            if not m.rel.startswith("bench/tests/")]
    todo += [src["repro.analysis.__main__"], src["repro.bench_kv.db_bench"]]
    seen: set[str] = set()
    while todo:
        for edge in import_edges(todo.pop(), include_nested=True):
            for target in (edge.target,
                           *(f"{edge.target}.{n}" for n in edge.names)):
                parts = target.split(".")
                # importing a module runs every package above it too
                for i in range(2, len(parts) + 1):
                    name = ".".join(parts[:i])
                    if name in src and name not in seen:
                        seen.add(name)
                        todo.append(src[name])
    every = {_package_of(m) for m in src.values()}
    reached = {_package_of(src[n]) for n in seen}
    missing = sorted(every - reached)
    assert not missing, f"no entry point reaches {missing}"


def test_baseline_suppresses_known_findings(tmp_path):
    """The baseline workflow: accept the fixture findings, rerun, and
    the gate goes green without touching the code."""
    findings = analyze_paths(FIXTURES)
    assert findings
    baseline_path = tmp_path / "baseline.json"
    write_baseline(baseline_path, findings)
    baseline = load_baseline(baseline_path)
    assert all(baseline.covers(f) for f in findings)
    res = _run_cli("--root", str(FIXTURES), "--baseline",
                   str(baseline_path))
    assert res.returncode == 0, res.stdout


def test_fingerprint_survives_line_churn():
    f = analyze_paths(FIXTURES)[0]
    moved = type(f)(rule=f.rule, family=f.family, path=f.path,
                    line=f.line + 40, message=f.message, hint=f.hint,
                    snippet="  " + f.snippet + "  ")
    assert moved.fingerprint() == f.fingerprint()


# -------------------------------------------------------- contract table
def test_contract_table_is_current():
    """C304 on the real base.py: the checked-in table must match what
    the generator produces (they share one implementation, so this is
    the no-drift guarantee)."""
    [mod] = load_modules(BASE_PY.parent, [BASE_PY])
    assert check_contract_table(mod) == []
    table = generate_contract_table(mod)
    assert "default_config(scale, **kw)" in table
    assert "merge_down" in table


def test_write_contract_table_is_idempotent():
    before = BASE_PY.read_text()
    res = _run_cli("--write-contract-table")
    assert res.returncode == 0
    assert BASE_PY.read_text() == before


# --------------------------------------------------------- bench schemas
def test_schema_table_is_current():
    """B601 on the real docs/benchmarks.md: the checked-in generated
    block must match what the extractor produces from the emitters."""
    variants = extract_variants(ROOT)
    assert check_schema_table(ROOT, variants) == []
    table = generate_schema_table(variants)
    assert "shard_sweep" in table
    assert "run_csv" in table
    assert "`p99_get_ms`:ms" in table


def test_write_schema_table_is_idempotent():
    before = BENCH_DOC.read_text()
    res = _run_cli("--write-schema-table")
    assert res.returncode == 0
    assert BENCH_DOC.read_text() == before


def _schema_inputs_copy(tmp_path: Path) -> Path:
    """Copy the fixed inputs the schemas family diffs into a tmp root."""
    for rel in ("src/repro/bench_kv/db_bench.py", "benchmarks/common.py",
                "docs/benchmarks.md", "BENCH_dbbench.json"):
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        dst.write_text((ROOT / rel).read_text())
    return tmp_path


def test_renamed_emitter_key_fires_schema_rules(tmp_path):
    """Acceptance: renaming an emitted key (p99_get_ms) makes both the
    doc table (B601) and the JSON cross-check (B602) fail, each with a
    file:line finding."""
    root = _schema_inputs_copy(tmp_path)
    emitter = root / "src" / "repro" / "bench_kv" / "db_bench.py"
    src = emitter.read_text()
    assert '"p99_get_ms"' in src
    emitter.write_text(src.replace('"p99_get_ms"', '"p99_renamed_ms"'))
    findings = schemas.check(root)
    rules = {f.rule for f in findings}
    assert {"B601", "B602"} <= rules, [f.format() for f in findings]
    for f in findings:
        if f.rule == "B601":
            assert f.path == "docs/benchmarks.md" and f.line > 0
        if f.rule == "B602":
            assert f.path == "src/repro/bench_kv/db_bench.py"
            assert f.line > 0


def test_paranoid_row_validation(monkeypatch):
    good = {"name": "x", "value": 1.0, "derived": "", "wall_clock_s": 0.1}
    bad = {"name": "x", "value": 1.0}
    monkeypatch.delenv("REPRO_PARANOID_CHECKS", raising=False)
    paranoid_validate_rows([bad], family=CSV_FAMILY, root=ROOT)  # gated off
    monkeypatch.setenv("REPRO_PARANOID_CHECKS", "1")
    paranoid_validate_rows([good], family=CSV_FAMILY, root=ROOT)
    with pytest.raises(ValueError, match=CSV_FAMILY):
        paranoid_validate_rows([bad], family=CSV_FAMILY, root=ROOT)
    # families the extractor has never seen stay free-form
    validate_emitted_row({"bench": "no_such_family"}, root=ROOT)


# ------------------------------------------------------------ CLI surface
def test_explain_cli():
    res = _run_cli("--explain", "U501")
    assert res.returncode == 0, res.stderr
    assert "U501" in res.stdout
    assert "unit" in res.stdout.lower()
    res = _run_cli("--explain", "Z999")
    assert res.returncode == 2
    assert "Z999" in res.stderr


def test_explain_covers_every_rule():
    for rule_id in catalog.CATALOG:
        text = catalog.explain(rule_id)
        assert text and rule_id in text


def test_github_format_emits_error_annotations():
    res = _run_cli("--root", str(FIXTURES), "--rules", "units",
                   "--format", "github")
    assert res.returncode == 1
    assert ("::error file=src/repro/core/units_bad.py,line=9,"
            "title=repro-lint U501::") in res.stdout


def test_units_and_schemas_clean_on_repo():
    res = _run_cli("--rules", "units,schemas")
    assert res.returncode == 0, res.stdout + res.stderr


# ------------------------------------------------------------- sanitizer
def test_maybe_sanitizer_env_gate(monkeypatch):
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)
    assert maybe_sanitizer() is None
    monkeypatch.setenv("REPRO_SANITIZE", "0")
    assert maybe_sanitizer() is None
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    assert isinstance(maybe_sanitizer(), ScheduleSanitizer)


class _Job:
    def __init__(self, **kw):
        self.kind = "compact"
        self.level = 1
        self.chain_id = 3
        self.parent_job = None
        self.scheduled = True
        self.t_start = 0.0
        self.t_finish = 1.0
        self.__dict__.update(kw)


def test_sanitizer_rules_unit():
    san = ScheduleSanitizer()
    san.on_event(0, 1.0)
    san.on_event(1, 0.5)          # other tree: independent clock
    with pytest.raises(ScheduleSanitizerError, match="S401"):
        san.on_event(0, 0.9)

    san = ScheduleSanitizer()
    san.on_gate(0, 2.0)
    with pytest.raises(ScheduleSanitizerError, match="S404"):
        san.on_gate(0, 1.0)

    san = ScheduleSanitizer()
    parent = _Job(t_start=0.0, t_finish=5.0)
    child = _Job(t_start=4.0, t_finish=6.0, level=2, parent_job=parent)
    san.on_schedule(0, parent)
    with pytest.raises(ScheduleSanitizerError, match="S402"):
        san.on_schedule(0, child)

    san = ScheduleSanitizer()
    san.on_schedule(0, _Job(t_start=0.0, t_finish=5.0))
    san.on_schedule(1, _Job(t_start=1.0, t_finish=2.0))  # other tree: ok
    san.on_schedule(0, _Job(t_start=1.0, t_finish=2.0, level=2))  # ok
    with pytest.raises(ScheduleSanitizerError, match="S403"):
        san.on_schedule(0, _Job(t_start=4.0, t_finish=9.0))

    san.reset()
    san.on_schedule(0, _Job(t_start=4.0, t_finish=9.0))  # fresh timeline


def _workload(seed=3, n=6_000, read_frac=0.3, rate=5_000.0, scale=1 << 20):
    rng = np.random.default_rng(seed)
    ops = (rng.random(n) < read_frac).astype(np.uint8)
    keys = rng.integers(0, scale, n).astype(np.int64)
    arr = np.arange(n, dtype=np.float64) / rate
    return ops, keys, arr


def test_sanitizer_wired_into_simulator(monkeypatch):
    """The engine's hook sites are live: a clean run audits every event
    and job, and a violated chain edge is caught inside the real
    scheduling path."""
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    cfg = get_policy("vlsm").default_config(scale=1 << 18)
    ops, keys, arr = _workload(n=3_000, scale=1 << 18)
    reset_uid_counters()
    sim = Simulator(cfg)
    sim.run(ops, keys, arr)
    assert sim.sanitizer is not None
    assert sim.sanitizer.events_checked > 0
    assert sim.sanitizer.jobs_checked > 0

    # a dep the pool never saw: the sanitizer rejects it from inside
    # SlotPool.schedule
    ghost_parent = _Job(t_start=0.0, t_finish=1e12, scheduled=True)
    orphan = _Job(level=7, parent_job=ghost_parent)
    orphan.deps = []
    orphan.uid = -1
    with pytest.raises(ScheduleSanitizerError, match="S402"):
        sim.compact_pool.schedule(orphan, ready=0.0, duration=1.0,
                                  region=0)


def test_sanitizer_off_by_default(monkeypatch):
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)
    cfg = get_policy("vlsm").default_config(scale=1 << 18)
    assert Simulator(cfg).sanitizer is None


@pytest.mark.parametrize("policy,k", [("vlsm", 1), ("rocksdb", 4)])
def test_fleet_parity_with_sanitizer(monkeypatch, policy, k):
    """Acceptance: fleet-vs-serial parity holds with REPRO_SANITIZE=1 —
    the sanitizer audits both engines and changes neither."""
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    dev = DeviceModel()
    cfg = get_policy(policy).default_config(scale=1 << 20).with_(n_shards=k)
    ops, keys, arr = _workload()
    reset_uid_counters()
    r_ser = Simulator(cfg, dev).run(ops, keys, arr)
    reset_uid_counters()
    eng = FleetEngine(cfg, dev)
    r_fle = eng.run(ops, keys, arr)
    assert eng.sanitizer is not None
    if k == 1:  # sharded runs delegate to per-shard engines' sanitizers
        assert eng.sanitizer.jobs_checked > 0
    assert r_ser.n_stalls == r_fle.n_stalls
    assert r_ser.stall_events == r_fle.stall_events
    assert float(np.max(np.abs(r_fle.latency - r_ser.latency))) < 1e-9
    assert abs(r_fle.makespan - r_ser.makespan) < 1e-9
