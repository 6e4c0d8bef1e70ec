"""The documents name files that exist.

One case a document: every path in a code span or a fenced block of
``README.md`` and ``docs/*.md`` that ends in ``.py``, ``.sh``, ``.json``,
``.cc`` or ``.md`` is a file of this checkout (a glob matches at least
one). The documents write a module of the package without its
``geomx_tpu/`` (``kvstore/dist.py``), a sibling document by its bare
name, and a file whose place the sentence has already given by its
basename alone (``local.py``): each of those forms is looked up as what
it stands for. ``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are
records and may name what was removed; they are not read here.
"""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

# files of the reference's tree (MXNet / ps-lite), which the documents
# cite beside ours
REFERENCE = ("python/mxnet/", "src/kvstore/", "3rdparty/", "scripts/cpu/",
             "van.cc")
# what a run writes under a name the user chose
RUN_OUTPUTS = {"merged.json", "n*.json", "node*.json"}

_PATH = re.compile(r"[^\s`'\"()\[\],;=]+\.(?:py|sh|json|cc|md)(?![\w.])")
_CODE = re.compile(r"```.*?```|`[^`\n]+`", re.S)
# builders' scratch and fixture trees: a name found only there is not a
# file of the program
_NOT_OURS = {".git", "__pycache__", "chiprun_out", "benchmark_out",
             "measure", ".jax_cache", "fixtures_analyze"}


@functools.lru_cache(maxsize=None)
def _basenames():
    names = set()
    for _root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in _NOT_OURS]
        names.update(files)
    return names


def _exists(path, doc):
    if "/" not in path and path in _basenames():
        return True
    return any(glob.glob(os.path.join(REPO, base, path))
               for base in ("", "geomx_tpu", os.path.dirname(doc)))


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_a_document_names_exists(doc):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    named = {p for code in _CODE.findall(text) for p in _PATH.findall(code)}
    assert named, f"{doc} names no file: the pattern no longer reads it"
    missing = sorted(
        p for p in named
        # a placeholder (<node>, $DIR, @plan) or a path outside the
        # checkout is nobody's file to keep
        if not (set(p) & set("<>$") or p.startswith(("@", "/", "~"))
                or p.startswith(REFERENCE) or p in RUN_OUTPUTS
                or _exists(p, doc)))
    assert not missing, f"{doc} names files that do not exist: {missing}"
