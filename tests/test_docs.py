"""The documents name files that exist.

A reader follows a document to a file; a document that still sends them
to a harness that was deleted measures nothing.  (``ROADMAP.md``,
``CHANGES.md`` and ``PERF.md`` name removed files as history and are not
held to this.)
"""

import fnmatch
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: what building, testing and chip runs leave behind (.gitignore)
_NOT_THE_TREE = {
    ".git",
    "__pycache__",
    ".pytest_cache",
    ".hypothesis",
    ".jax_cache",
    "chiprun_out",
    "archive_check",
    "parent_copy",
    "chip_probe",
}

_NAME = re.compile(r"[\w.*/-]*[\w*]\.(?:py|sh)\b")


def _tree() -> list:
    files = []
    for base, dirs, names in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in _NOT_THE_TREE]
        rel = os.path.relpath(base, ROOT)
        files += [os.path.normpath(os.path.join(rel, n)) for n in names]
    return files


def _missing(text: str, files: list) -> list:
    basenames = {os.path.basename(f) for f in files}
    missing = []
    for name in sorted(set(_NAME.findall(text))):
        if name.startswith("/"):
            continue  # an absolute path is the reader's, not the tree's
        name = name.lstrip("./")
        if fnmatch.fnmatch(os.path.basename(name), "my_*.py"):
            continue  # the guide's placeholders for the reader's own files
        if "/" in name:
            found = any(
                fnmatch.filter(files, os.path.normpath(os.path.join(at, name)))
                for at in ("", "keystone_tpu")
            )
        else:
            found = bool(fnmatch.filter(basenames, name))
        if not found:
            missing.append(name)
    return missing


@pytest.mark.parametrize(
    "document",
    [
        "README.md",
        "docs/guide.md",
        "docs/architecture.md",
        "docs/migration.md",
        "PARITY.md",
        ".claude/skills/verify/SKILL.md",
    ],
)
def test_document_names_files_that_exist(document):
    with open(os.path.join(ROOT, document), encoding="utf-8") as f:
        text = f.read()
    assert _missing(text, _tree()) == []
