"""The living documents name only what exists.

One case a document: ``README.md``, the builder's
``.claude/skills/verify/SKILL.md`` and every file under ``docs/``. A
back-quoted token that reads as a path of this repository (it starts with
one of the tracked directories, or it is a bare ``*.py`` / ``*.md`` /
``*.json`` name) must be a file or directory of the checkout, and so must
the script of every ``python tools/<x>.py`` / ``python3 benchmarks/<x>.py``
command line. ``PERF.md``, ``ROADMAP.md``, ``CHANGES.md`` and ``SURVEY.md``
tell history, name files that are gone, and are not cases.

Stdlib only: no jax import, well under a second.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: directories whose paths a document may name
TRACKED_DIRS = ("fleetx_tpu/", "tools/", "tests/", "benchmarks/", "docs/",
                "projects/")
#: a bare name with one of these endings is read as a file of the checkout
BARE_ENDINGS = (".py", ".md", ".json")
#: names that are no file of the checkout: what a run writes (a
#: checkpoint's marker and manifest, an export's description, a
#: tokenizer's vocabulary, a profiler window's table of device scopes), a
#: model card's config, the reference
#: project's own sources, and the lint baseline, which
#: `tools/lint.py --write-baseline` writes and which is absent while it
#: has no entry
NOT_OF_THE_CHECKOUT = frozenset({
    "fleetx_meta.json", "fleetx_integrity.json", "meta.json", "vocab.json",
    "device_scopes.json", "config.json", "hybrid_model.py", "language_module.py",
    "tools/lint_baseline.json",
})

DOCS = (["README.md", ".claude/skills/verify/SKILL.md"]
        + sorted(os.path.relpath(p, REPO)
                 for p in glob.glob(os.path.join(REPO, "docs", "*.md"))))

_TICKED = re.compile(r"`([^`\n]+)`")
_COMMAND = re.compile(
    r"python3?\s+((?:tools|benchmarks)/[A-Za-z0-9_./-]+\.py)")
_PLACEHOLDER = re.compile(r"[<{…]|\.\.\.")   # `tools/<x>.py`, `a/{b,c}.py`


def _path_of(token: str):
    """The path a back-quoted token names, or None when it names none."""
    word = token.strip().split()[0] if token.strip() else ""
    word = word.split(":")[0].rstrip(".,;)")   # path:line, path::test
    if word in NOT_OF_THE_CHECKOUT:
        return None
    if word.startswith(TRACKED_DIRS):
        return word
    if "/" not in word and word.endswith(BARE_ENDINGS) \
            and not _PLACEHOLDER.search(word):
        return word
    return None


def _exists(path: str) -> bool:
    """True when ``path`` (maybe a glob, maybe cut at a placeholder) is in
    the checkout; a bare name may live in any tracked directory."""
    cut = _PLACEHOLDER.search(path)
    if cut:
        path = os.path.dirname(path[:cut.start()])
        return bool(path) and os.path.isdir(os.path.join(REPO, path))
    if glob.glob(os.path.join(REPO, path)):
        return True
    if "/" not in path:
        return any(glob.glob(os.path.join(REPO, d, "**", path),
                             recursive=True) for d in TRACKED_DIRS)
    return False


def stale_references(text: str) -> list[str]:
    """Every path or script ``text`` names that the checkout lacks."""
    named = [p for p in map(_path_of, _TICKED.findall(text)) if p]
    named += _COMMAND.findall(text)
    return sorted({p for p in named if not _exists(p)})


@pytest.mark.parametrize("doc", DOCS)
def test_doc_names_only_what_exists(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        stale = stale_references(f.read())
    assert not stale, f"{doc} names what the checkout lacks: {stale}"
