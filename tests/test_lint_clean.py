"""Tier-1 gate: the shipped tree is `hvt-lint`-clean (ISSUE 6).

Three drift directions are closed here:

* code drift — any non-baselined finding in ``horovod_tpu/`` fails CI
  (the prose invariants of PRs 1-5 are now machine-checked);
* baseline drift — a baseline entry whose flagged line was since fixed or
  edited no longer matches anything and must be deleted;
* doc drift — ``docs/ENVVARS.md`` must be byte-identical to what
  `registry.generate_doc()` renders, and every registered knob must still
  be referenced somewhere in the tree (a knob documented but no longer
  read is drift too, just in the other direction).
"""

import ast
import os
import re
import subprocess
import sys

from horovod_tpu.analysis import core, registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "horovod_tpu")


def _lint_package():
    return core.lint_paths([PACKAGE], root=REPO)


class TestLintClean:
    def test_package_is_lint_clean(self):
        result = _lint_package()
        assert result.files > 50  # the walk actually covered the package
        assert not result.findings, (
            "hvt-lint found non-baselined issues — fix them, or baseline "
            "with a one-line justification "
            "(horovod_tpu/analysis/baseline.json):\n"
            + "\n".join(f.format() for f in result.findings)
        )

    def test_no_stale_baseline_entries(self):
        """Every committed baseline entry still matches a live finding —
        a fixed site must take its grandfather clause with it."""
        entries = core.load_baseline(core.DEFAULT_BASELINE)
        result = _lint_package()
        matched = {(f.rule, f.path, f.snippet) for f in result.baselined}
        stale = [
            e for e in entries
            if (e["rule"], e["path"], e["snippet"]) not in matched
        ]
        assert not stale, (
            "baseline entries no longer match any finding — delete them:\n"
            + "\n".join(f"{e['rule']} {e['path']}: {e['snippet']}"
                        for e in stale)
        )

    def test_cli_exit_code_contract(self):
        """`hvt-lint horovod_tpu/` exits 0 on the shipped tree — the
        pre-commit-hook surface, end to end through the real CLI."""
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.analysis", "horovod_tpu"],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 finding(s)" in proc.stdout


class TestSchedCheckClean:
    """Tier-1 gate (ISSUE 14): the shipped tree passes whole-program
    schedule verification — every unit's rank-feasible paths submit one
    collective sequence per uniform configuration, and the real entry
    paths (Trainer loops, elastic commit/sync, rescale boundary,
    checkpoint save/broadcast) each verify."""

    def test_package_schedule_verifies(self):
        result = core.lint_paths(
            [PACKAGE], root=REPO, select=["HVT010"]
        )
        assert result.files > 50
        assert not result.findings, (
            "hvt-sched found schedule divergences — fix them, or "
            "baseline with a one-line justification:\n"
            + "\n".join(f.format() for f in result.findings)
        )

    def test_entry_paths_all_agree(self):
        """Every declared entry automaton verifies AND exists — a
        renamed entry unit must update schedule.ENTRY_PATHS, not
        silently drop out of the report."""
        from horovod_tpu.analysis import schedule

        modules = []
        for path in core.iter_python_files([PACKAGE]):
            with open(path, encoding="utf-8") as f:
                modules.append(core.ModuleSource(
                    path, os.path.relpath(path, REPO), f.read()
                ))
        graph = core.Project(modules).callgraph()
        rows = schedule.entry_report(graph)
        assert len(rows) == len(schedule.ENTRY_PATHS), (
            "entry units missing from the module set — update "
            "schedule.ENTRY_PATHS for the rename: "
            f"{[r['unit'] for r in rows]}"
        )
        diverging = [r["unit"] for r in rows if not r["agree"]]
        assert not diverging, f"entry automata diverge: {diverging}"
        # The elastic sync boundary is the load-bearing one: its
        # automaton must actually carry the snapshot transport.
        sync = next(r for r in rows if r["unit"].endswith("ElasticState.sync"))
        assert "allgather_object" in sync["sequence"]

    def test_sched_cli_exit_code_contract(self):
        """`hvt-sched check horovod_tpu/` exits 0 on the shipped tree —
        the pre-commit surface, end to end through the real CLI."""
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.analysis.sched_cli",
             "check", "horovod_tpu"],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 schedule finding(s)" in proc.stdout
        assert "entry horovod_tpu.elastic.state:ElasticState.sync" in (
            proc.stdout
        )
        assert "DIVERGE" not in proc.stdout


class TestEnvvarsDoc:
    DOC = os.path.join(REPO, "docs", "ENVVARS.md")

    def test_regeneration_produces_no_diff(self):
        with open(self.DOC) as f:
            on_disk = f.read()
        assert on_disk == registry.generate_doc(), (
            "docs/ENVVARS.md is stale — regenerate: "
            "python -m horovod_tpu.analysis.registry > docs/ENVVARS.md"
        )

    def test_every_registered_knob_is_read_somewhere(self):
        """Reverse drift: a registered knob nothing references anymore
        should be deleted from the registry (and thus from the doc)."""
        referenced = set()
        roots = [PACKAGE, os.path.join(REPO, "examples")]
        for path in core.iter_python_files(roots):
            if os.path.abspath(path).startswith(
                os.path.join(PACKAGE, "analysis") + os.sep
            ):
                continue  # the registry declaring a name is not a use
            with open(path, encoding="utf-8") as f:
                referenced.update(re.findall(r"HVT_[A-Z0-9_]+", f.read()))
        unused = sorted(set(registry.KNOBS) - referenced)
        assert not unused, (
            f"registered knobs referenced nowhere: {unused} — remove the "
            "Knob rows and regenerate docs/ENVVARS.md"
        )

    def test_unregistered_environment_reads_are_the_two_contracts(self):
        """HVT004 only knows the ``HVT_`` prefix, so a literal
        ``os.environ`` read of any other name passes the lint unseen (a
        measuring script's switch once lived in `trace.py` that way). The
        package reads two such names, both contracts with its
        surroundings: the platform's export directory and jax's own
        cache directory."""
        read = set()
        for path in core.iter_python_files([PACKAGE]):
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) and node.args:
                    fn, key = ast.unparse(node.func), node.args[0]
                    if fn not in ("os.environ.get", "os.getenv"):
                        continue
                elif (isinstance(node, ast.Subscript)
                      and ast.unparse(node.value) == "os.environ"):
                    key = node.slice
                else:
                    continue
                if isinstance(key, ast.Constant) and isinstance(key.value, str):
                    read.add(key.value)
        assert read - set(registry.KNOBS) == {
            "PS_MODEL_PATH", "JAX_COMPILATION_CACHE_DIR",
        }

    def test_readme_links_envvars_doc(self):
        with open(os.path.join(REPO, "README.md")) as f:
            assert "docs/ENVVARS.md" in f.read()


class TestLintRulesDoc:
    """The ENVVARS.md contract, applied to the rule registry: the
    committed docs/LINT_RULES.md must be byte-identical to what the rule
    metadata renders (ISSUE 9 satellite)."""

    DOC = os.path.join(REPO, "docs", "LINT_RULES.md")

    def test_regeneration_produces_no_diff(self):
        with open(self.DOC) as f:
            on_disk = f.read()
        assert on_disk == core.generate_rules_doc(), (
            "docs/LINT_RULES.md is stale — regenerate: "
            "python -m horovod_tpu.analysis.rules > docs/LINT_RULES.md"
        )

    def test_every_rule_carries_metadata(self):
        """A rule without rationale/provenance renders an empty doc
        section — refuse at the gate, not in review."""
        for cls in core.iter_rules():
            assert cls.rationale, f"{cls.rule_id} has no rationale"
            assert cls.provenance, f"{cls.rule_id} has no provenance"
            assert cls.example, f"{cls.rule_id} has no example"

    def test_readme_links_rules_doc(self):
        with open(os.path.join(REPO, "README.md")) as f:
            assert "docs/LINT_RULES.md" in f.read()
