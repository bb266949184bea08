"""The documents a newcomer reads first name things that exist.

``README.md`` and the verify skill send a reader to flags, tools and tests
by name; ``tools/ci.sh`` runs scripts by path.  A name that no longer
resolves sends the reader (or CI) to something deleted, so each is checked
against the tree.
"""

import os
import re
import subprocess

import pytest

from paddle_tpu import flags

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", os.path.join(".claude", "skills", "verify", "SKILL.md")]
_PATH_ROOTS = ("tools/", "tests/", "paddle_tpu/", "benchmark/")


def _read(rel):
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        return f.read()


def _flag_mentions(text):
    """``FLAGS_x`` names; ``FLAGS_fleet_*`` / ``FLAGS_cudnn_`` style
    prefixes stand for every flag that starts so."""
    names, prefixes = set(), set()
    for m in re.finditer(r"FLAGS_\w+", text):
        rest = text[m.end():m.end() + 1]
        if rest == "*" or m.group().endswith("_"):
            prefixes.add(m.group())
        else:
            names.add(m.group())
    return names, prefixes


def _path_mentions(text):
    """Backticked spans whose first word is a path under one of the
    repo's code directories or a root-level ``*.py``."""
    out = set()
    for span in re.findall(r"`([^`\n]+)`", text):
        word = span.split()[0].split(":")[0].rstrip(".,;)")
        if any(ch in word for ch in "*<>{}$"):
            continue
        if word.startswith(_PATH_ROOTS) or re.fullmatch(r"\w+\.py", word):
            out.add(word)
    return out


@pytest.mark.parametrize("doc", DOCS)
def test_every_flag_a_document_names_is_defined(doc):
    defined = set(flags.globals().keys())
    names, prefixes = _flag_mentions(_read(doc))
    unknown = sorted(names - defined)
    unknown += sorted(p + "*" for p in prefixes
                      if not any(d.startswith(p) for d in defined))
    assert not unknown, f"{doc} names flags flags.py lacks: {unknown}"


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_a_document_names_exists(doc):
    paths = _path_mentions(_read(doc))
    assert paths, f"{doc}: no path was found, the pattern is broken"
    missing = sorted(p for p in paths
                     if not os.path.exists(os.path.join(REPO, p)))
    assert not missing, f"{doc} names paths the tree lacks: {missing}"


def test_ci_script_parses_and_runs_only_scripts_that_exist():
    ci = os.path.join(REPO, "tools", "ci.sh")
    r = subprocess.run(["bash", "-n", ci], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    scripts = set(re.findall(r"\b(?:python3?|bash)\s+([\w./-]+\.(?:py|sh))\b",
                             _read("tools/ci.sh")))
    assert len(scripts) > 10, f"pattern found only {sorted(scripts)}"
    missing = sorted(p for p in scripts
                     if not os.path.exists(os.path.join(REPO, p)))
    assert not missing, f"tools/ci.sh runs scripts the tree lacks: {missing}"
