"""repro.analysis: the invariant linter's own contract.

Three layers: the fixture corpus (each intentional violation fires its
rule, exit code 1), the CLI surface (JSON schema, rule selection,
suppression comments, exit codes), and the meta-test — the repo's own
``src/`` (+ sibling tests/ and benchmarks/) is clean at HEAD, which is
the invariant CI enforces."""
import json
from pathlib import Path

import pytest

from repro.analysis import Finding, Rule, names, run_analysis
from repro.analysis.cli import JSON_SCHEMA_VERSION, main, to_json
from repro.analysis.project import Project
from repro.analysis.registry import all_rules, get, register, unregister

pytestmark = pytest.mark.analysis

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "fixtures" / "analysis"
#: the default `*/fixtures/*` exclude must be overridden to scan the corpus
NO_EXCLUDE = ("--exclude", "*/__none__/*")

RULE_FAMILIES = ("traced-purity", "parity-coverage", "registry-completeness",
                 "units-s", "dtype-x64", "no-wallclock-in-sim")

#: fixture file -> (rule that must fire, symbol of the expected finding)
CORPUS = {
    "bad_purity.py": ("traced-purity", "leaky_step"),
    "bad_purity_nested.py": ("traced-purity", "one_seed"),
    "bad_parity_process.py": ("parity-coverage", "doom"),
    "bad_parity_trace.py": ("parity-coverage", "ghost"),
    "bad_registry.py": ("registry-completeness", "_orphan"),
    "bad_units.py": ("units-s", "Window.duration"),
    "bad_dtype.py": ("dtype-x64", "zeros"),
    "bad_wallclock.py": ("no-wallclock-in-sim", "time.monotonic"),
}


def run_cli(capsys, *argv):
    code = main(list(argv))
    return code, capsys.readouterr().out


# ------------------------------------------------------------ registry ---
def test_all_rule_families_registered_in_order():
    assert names() == list(RULE_FAMILIES)
    for rule_cls in all_rules():
        assert rule_cls.name in RULE_FAMILIES
        assert rule_cls.description


def test_rule_registry_register_unregister_roundtrip():
    @register("throwaway-rule")
    class Throwaway(Rule):
        description = "test-local"

        def check(self, project):
            return []

    try:
        assert "throwaway-rule" in names()
        assert isinstance(get("throwaway-rule"), Throwaway)
        with pytest.raises(KeyError):
            register("throwaway-rule")(Throwaway)  # no silent overwrite
        with pytest.raises(TypeError):
            register("not-a-rule")(object)  # must subclass Rule
    finally:
        unregister("throwaway-rule")
    assert "throwaway-rule" not in names()
    with pytest.raises(KeyError):
        get("throwaway-rule")


# ------------------------------------------------------ fixture corpus ---
@pytest.mark.parametrize("fixture,expected", sorted(CORPUS.items()))
def test_fixture_fires_its_rule(capsys, fixture, expected):
    rule, symbol = expected
    code, out = run_cli(
        capsys, str(FIXTURES / fixture), "--no-siblings", *NO_EXCLUDE
    )
    assert code == 1, out
    assert rule in out and symbol in out


@pytest.mark.parametrize("fixture,expected", sorted(CORPUS.items()))
def test_fixture_clean_under_every_other_rule(capsys, fixture, expected):
    """Each fixture violates exactly its own family — rule precision."""
    rule, _ = expected
    others = ",".join(r for r in RULE_FAMILIES if r != rule)
    code, out = run_cli(
        capsys, str(FIXTURES / fixture), "--rules", others,
        "--no-siblings", *NO_EXCLUDE,
    )
    assert code == 0, out


def test_parity_fires_when_kind_removed_from_handler_site(tmp_path, capsys):
    """Removing a dispatch arm (process kind) or a kernel-side emit
    (trace kind) from an otherwise-covered fixture copy flips it dirty."""
    clean_proc = (FIXTURES / "bad_parity_process.py").read_text().replace(
        'if proc.kind == "periodic":',
        'if proc.kind in ("periodic", "doom"):',
    )
    p = tmp_path / "proc.py"
    p.write_text(clean_proc)
    assert run_cli(capsys, str(p), "--no-siblings")[0] == 0
    p.write_text(
        clean_proc.replace('proc.kind in ("periodic", "doom")', 'proc.kind in ("periodic",)')
    )
    code, out = run_cli(capsys, str(p), "--no-siblings")
    assert code == 1 and "doom" in out and "never dispatched" in out

    clean_trace = (FIXTURES / "bad_parity_trace.py").read_text().replace(
        'def reconstruct_traces(rec, t):\n    rec.emit(t, "failure")',
        'def reconstruct_traces(rec, t):\n    rec.emit(t, "failure")\n'
        '    rec.emit(t, "ghost")',
    )
    q = tmp_path / "trace.py"
    q.write_text(clean_trace)
    assert run_cli(capsys, str(q), "--no-siblings")[0] == 0
    q.write_text(
        clean_trace.replace(
            'def reconstruct_traces(rec, t):\n    rec.emit(t, "failure")\n'
            '    rec.emit(t, "ghost")',
            'def reconstruct_traces(rec, t):\n    rec.emit(t, "failure")',
        )
    )
    code, out = run_cli(capsys, str(q), "--no-siblings")
    assert code == 1 and "ghost" in out and "kernel-side" in out


# ------------------------------------------------------------ CLI shape ---
def test_list_rules_names_every_family(capsys):
    code, out = run_cli(capsys, "--list-rules")
    assert code == 0
    for rule in RULE_FAMILIES:
        assert rule in out


def test_json_output_schema(capsys):
    code, out = run_cli(
        capsys, str(FIXTURES / "bad_units.py"), "--json",
        "--no-siblings", *NO_EXCLUDE,
    )
    assert code == 1
    doc = json.loads(out)
    assert doc["schema_version"] == JSON_SCHEMA_VERSION
    assert doc["rules"] == list(RULE_FAMILIES)
    assert doc["n_files"] == 1 and doc["n_findings"] == 2
    assert doc["clean"] is False
    f = doc["findings"][0]
    assert set(f) == {"rule", "path", "line", "symbol", "msg", "severity"}
    assert f["rule"] == "units-s" and f["severity"] == "error"


def test_json_clean_tree(tmp_path, capsys):
    (tmp_path / "ok.py").write_text("x = 1\n")
    code, out = run_cli(capsys, str(tmp_path), "--json", "--no-siblings")
    assert code == 0
    doc = json.loads(out)
    assert doc["clean"] is True and doc["findings"] == []


def test_missing_path_exits_2(capsys):
    assert main([str(REPO / "no_such_dir_xyz")]) == 2


def test_rules_subset_selection(capsys):
    code, out = run_cli(
        capsys, str(FIXTURES / "bad_units.py"), "--rules", "dtype-x64",
        "--no-siblings", *NO_EXCLUDE,
    )
    assert code == 0  # units fixture is clean under the dtype rule


def test_fixtures_excluded_by_default(capsys):
    """Scanning the fixtures dir WITHOUT the exclude override finds no
    files — the corpus can never pollute a real run."""
    code, out = run_cli(capsys, str(FIXTURES), "--no-siblings")
    assert code == 0 and "0 file(s)" in out


# ---------------------------------------------------------- suppression ---
def test_line_suppression_silences_one_finding(tmp_path, capsys):
    src = (FIXTURES / "bad_units.py").read_text().replace(
        "duration: float  #", "duration: float  # repro: ignore[units-s] —"
    )
    p = tmp_path / "sup.py"
    p.write_text(src)
    code, out = run_cli(capsys, str(p), "--no-siblings")
    assert code == 1  # the local-variable finding survives
    assert "Window.duration" not in out and "delay" in out


def test_file_suppression_silences_whole_module(tmp_path, capsys):
    src = "# repro: ignore-file[units-s]\n" + (FIXTURES / "bad_units.py").read_text()
    p = tmp_path / "supfile.py"
    p.write_text(src)
    assert run_cli(capsys, str(p), "--no-siblings")[0] == 0


def test_suppression_is_per_rule(tmp_path, capsys):
    """ignore[other-rule] does not waive a units finding."""
    src = (FIXTURES / "bad_units.py").read_text().replace(
        "duration: float  #", "duration: float  # repro: ignore[dtype-x64] —"
    )
    p = tmp_path / "wrong.py"
    p.write_text(src)
    code, out = run_cli(capsys, str(p), "--no-siblings")
    assert code == 1 and "Window.duration" in out


# ------------------------------------------------------------- API layer ---
def test_run_analysis_returns_sorted_findings():
    project = Project.load([FIXTURES / "bad_units.py"], exclude=())
    findings = run_analysis(project)
    assert all(isinstance(f, Finding) for f in findings)
    assert findings == sorted(findings, key=Finding.sort_key)
    assert [f.line for f in findings] == sorted(f.line for f in findings)


@pytest.mark.parametrize("rel, mode", [
    ("scenarios/trajectory.py", "x64"),
    ("obs/profile.py", "x64"),
    ("kernels/decode_attention.py", "kernel"),
    ("utils/backend.py", None),
])
def test_dtype_scope_follows_the_x64_helper(rel, mode):
    """Importing ``repro.utils.backend.x64`` is what makes a module an x64
    module; the helper's own home is not one."""
    from repro.analysis.rules.dtypes import _mode

    project = Project.load([REPO / "src" / "repro" / rel], exclude=())
    assert _mode(project.modules[0]) == mode


def test_syntax_error_files_are_skipped(tmp_path, capsys):
    (tmp_path / "broken.py").write_text("def oops(:\n")
    (tmp_path / "fine.py").write_text("x = 1\n")
    code, out = run_cli(capsys, str(tmp_path), "--no-siblings")
    assert code == 0 and "1 file(s)" in out


# -------------------------------------------------------------- meta ---
def test_repo_is_clean_at_head(capsys):
    """THE invariant: the linter passes on the repo itself (src/ plus the
    auto-included sibling tests/ and benchmarks/). CI runs exactly this."""
    code, out = run_cli(capsys, str(REPO / "src"))
    assert code == 0, f"repo not lint-clean:\n{out}"
