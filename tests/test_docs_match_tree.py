"""The documents that describe the system *as it is* name only files and
commands the tree has.

`README.md`, `PARITY.md`, the verify skill and `docs/ENVVARS.md` are read
by every session as the state of the repo; a path or a command line in
them that no longer exists sends the reader after a deleted script (the
README documented one for five PRs after the benchmark replaced it).
`CHANGES.md`, `PERF.md` and `ROADMAP.md` carry history and are exempt.

Two rules, both on what the document itself marks as code:

* a backticked token that is a relative path with a ``.py``, ``.json``,
  ``.md``, ``.yaml`` or ``.cc`` suffix (a ``:line`` or ``::test`` tail is
  cut off) is a file of the tree — at that path from the root, or as the
  tail of a longer path (the documents write ``trainer.py`` and
  ``data/loader.py`` for files under ``horovod_tpu/``);
* a ``python … <script>.py`` / ``python -m <module>`` command line names a
  script or a module that exists.

Absolute paths, placeholders (``<dir>/…``, ``$VAR/…``, ``{N}``, globs)
the files a run writes (`RUNTIME_FILES`) and the source repository's own
(`SOURCE_REPO_FILES`) are not the tree's.
"""

import functools
import importlib.util
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = [
    "README.md",
    "PARITY.md",
    ".claude/skills/verify/SKILL.md",
    "docs/ENVVARS.md",
]

# Written by a run, never committed: the documents name them as outputs.
RUNTIME_FILES = {
    "signature.json", "index.json", "tune.json", "trace.json",
    "tokenizer.json", ".meta.json", "hvt_step_reductions.json",
}
# The source repository's own files, which SURVEY.md cites by line.
SOURCE_REPO_FILES = {
    "config.yaml", "tensorflow2_keras_mnist.py", "mnist_keras.py",
}

_NOT_THE_TREE = {".git", "build", "chiprun_out", "__pycache__",
                 ".jax_cache", ".chipbench_out", ".pytest_cache"}
# `build/` is scratch (gitignored) but for the helpers `.gitignore` excepts.
_COMMITTED_UNDER_BUILD = ("build/flash_bundles.py", "build/kda_probe.py",
                          "build/reduction_table.py", "build/ssd_probe.py",
                          "build/cell_compile.py", "build/moe_logs.py",
                          "build/moe_probe.py")
_PATH = re.compile(
    r"^(?P<path>[\w.-]+(?:/[\w.-]+)*\.(?:py|json|md|yaml|cc))"
    r"(?::[\d,:-]+)?(?:::[\w:\[\]-]+)?$"
)
_COMMAND = re.compile(
    r"\bpython3?\s+(?:-[A-Za-z]\s+)*?"
    r"(?:-m\s+(?P<module>[A-Za-z_][\w.]*)|(?P<script>[\w./-]+\.py)\b)"
)


@functools.lru_cache(maxsize=None)
def _tree_paths() -> frozenset:
    """Every file of the tree under each tail of its path: the documents
    write ``trainer.py`` and ``data/loader.py`` for files under
    ``horovod_tpu/``."""
    paths = set()
    for dirpath, dirnames, filenames in os.walk(REPO):
        dirnames[:] = [d for d in dirnames if d not in _NOT_THE_TREE]
        rel = os.path.relpath(dirpath, REPO)
        for name in filenames:
            parts = os.path.normpath(os.path.join(rel, name)).split(os.sep)
            paths.update("/".join(parts[i:]) for i in range(len(parts)))
    for kept in _COMMITTED_UNDER_BUILD:
        if os.path.isfile(os.path.join(REPO, kept)):
            paths.update({kept, os.path.basename(kept)})
    return frozenset(paths)


def _module_exists(module: str) -> bool:
    top = module.split(".")[0]
    if os.path.isdir(os.path.join(REPO, top)):
        base = os.path.join(REPO, *module.split("."))
        return os.path.isfile(base + ".py") or os.path.isfile(
            os.path.join(base, "__main__.py"))
    return importlib.util.find_spec(top) is not None


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_the_tree_has(document):
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        text = f.read()
    paths = _tree_paths()
    missing = []
    for token in re.findall(r"`([^`\n]+)`", text):
        m = _PATH.match(token.strip())
        if not m or os.path.basename(m["path"]) in (
                RUNTIME_FILES | SOURCE_REPO_FILES):
            continue
        if os.path.normpath(m["path"]) not in paths:
            missing.append(f"path `{token}`")
    for m in _COMMAND.finditer(text):
        if m["module"] and not _module_exists(m["module"]):
            missing.append(f"command `{m[0]}`: no module {m['module']}")
        script = m["script"]
        if script and not script.startswith("/") and not os.path.isfile(
                os.path.join(REPO, script)):
            missing.append(f"command `{m[0]}`: no script {script}")
    assert not missing, (
        f"{document} names what the tree does not have:\n  "
        + "\n  ".join(sorted(set(missing)))
    )
