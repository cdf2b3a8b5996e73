"""Every file or directory a document names exists.

One case per document (``README.md`` and each ``docs/*.md``): each
backticked token that looks like a path (``*.py``, ``*.json``, ``*.md``,
``*.cc``, or a name ending in ``/``) must resolve against the root of the
repository or one of ``mxtpu/``, ``tests/``, ``tools/``, ``native/``. A page
that still sends its reader to a deleted harness, a moved module or a record
that is gone fails here with the names it could not find.

What the pages name on purpose without its being a file of this checkout
sits in ``ALLOWED``, one line each with the reason.
"""

import glob
import os
import re

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BASES = ("", "mxtpu", "tests", "tools", "native")

# name -> why a document may name it though no such path is in the checkout
ALLOWED = {
    # the MXNet reference's own files (mounted read-only beside the repo)
    "src/imperative/cached_op.cc": "reference source, cited for parity",
    "src/profiler/": "reference source, cited for parity",
    "docs/architecture/note_engine.md": "the reference's design note",
    "tools/kill-mxnet.py": "the reference's tool tools/kill_mxtpu.py replaces",
    # run-time artefacts: written by the program, never committed
    "meta.json": "a checkpoint directory's manifest, written at commit",
    "step-42/": "an example checkpoint directory",
    "step-42.tmp/": "the same directory before its commit rename",
    "profile.json": "the example file name given to profiler.set_config",
    "trace.json": "a flight-recorder dump's chrome trace",
    "stats.json": "a flight-recorder dump's counters",
}

_TOKEN = re.compile(r"`([^`\s]+)`")
_PATHLIKE = re.compile(r"^[\w./\-]+(\.py|\.json|\.md|\.cc|/)$")


def _documents():
    docs = sorted(glob.glob(os.path.join(_REPO, "docs", "*.md")))
    return [os.path.join(_REPO, "README.md")] + docs


def _names(text):
    for tok in _TOKEN.findall(text):
        tok = re.split(r"[:#]", tok)[0]     # drop ::test, :line, #anchor
        if _PATHLIKE.match(tok) and not tok.startswith(("http", "/")):
            yield tok


def _resolves(name):
    return any(os.path.exists(os.path.join(_REPO, base, name))
               for base in _BASES)


@pytest.mark.parametrize(
    "doc", _documents(), ids=lambda p: os.path.relpath(p, _REPO))
def test_every_path_a_document_names_exists(doc):
    with open(doc, encoding="utf-8") as f:
        names = sorted(set(_names(f.read())))
    missing = [n for n in names if n not in ALLOWED and not _resolves(n)]
    assert not missing, (
        f"{os.path.relpath(doc, _REPO)} names paths that do not exist: "
        f"{missing} (correct the page, or add the name to ALLOWED in "
        f"{os.path.basename(__file__)} with the reason)")
