"""The documents name only what the tree holds.

A case per document: every path it puts in backticks or in a code
block that starts with one of the tree's directories, and every bare
file name in backticks that ends in ``.py``, ``.json`` or ``.jsonl``,
must exist. A ``:line`` (or ``::test``) suffix is cut; a name with
``<``, ``*`` or ``NN`` in it is a pattern and is skipped. ``ROADMAP.md``
and ``CHANGES.md`` are history and are not read.
"""
import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIRS = ("tools/", "mxnet_tpu/", "benchmark/", "tests/", "examples/",
        "docs/")
DOCUMENTS = (["README.md", "PERF.md", ".claude/skills/verify/SKILL.md"]
             + sorted(os.path.relpath(p, REPO) for p in
                      glob.glob(os.path.join(REPO, "docs", "*.md"))))


@functools.lru_cache(maxsize=None)
def _file_names():
    """Every file name of the tree, wherever it lies: a document may
    call ``mxnet_tpu/serving/llm/engine.py`` plain ``engine.py``."""
    names = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        names.update(files)
    return names


def named_paths(text):
    """``(paths under DIRS, bare file names)`` a document names."""
    fenced = re.findall(r"^```.*?^```", text, flags=re.S | re.M)
    inline = re.findall(r"`([^`\n]+)`",
                        re.sub(r"^```.*?^```", "", text,
                               flags=re.S | re.M))
    paths, bare = set(), set()
    for span, is_inline in ([(s, True) for s in inline]
                            + [(s, False) for s in fenced]):
        for token in span.split():
            token = token.split(":")[0].strip("`'\"()[],;.")
            if re.search(r"[<*{$]|NN|\.\.\.", token):
                continue
            if token.startswith(DIRS):
                paths.add(token)
            elif is_inline and re.fullmatch(r"[\w-]+\.(py|jsonl?)", token):
                bare.add(token)
    return paths, bare


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_the_tree_holds(document):
    with open(os.path.join(REPO, document)) as f:
        paths, bare = named_paths(f.read())
    assert paths or bare, "nothing read: the pattern has gone stale"
    gone = sorted(p for p in paths
                  if not os.path.exists(os.path.join(REPO, p)))
    gone += sorted(bare - _file_names())
    assert not gone, f"{document} names what the tree does not hold: {gone}"
