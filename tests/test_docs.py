"""The documents a new owner reads first name only files the tree
holds: a yardstick, a gate or a record that was deleted must not live
on in prose."""
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: where a document's short paths are rooted (``exec/base.py`` is the
#: package's, ``test_obs.py`` the tests')
BASES = ("", "spark_rapids_tpu", "tests", "tools", "benchmark")
#: files the engine writes at run time, named by the documents
WRITTEN_AT_RUN_TIME = {"MANIFEST.json"}
#: a back-ticked token that starts with a path to a source, script,
#: data or prose file: `tools/nds.py`, `chip_smoke.py::phase_nds`,
#: `python chip_smoke.py --chips 4`, `io/scan.py:612`
PATH = re.compile(r"(?<![\w./<>*-])([\w.-]+(?:/[\w.-]+)*\.(?:py|sh|json|md))"
                  r"(?![\w/*])")


def tree_files():
    """Every file of the checkout; scratch and cache directories (a
    leading ``_`` or ``.``, but for ``.claude``) are not the tree."""
    out = []
    for d, subdirs, names in os.walk(ROOT):
        subdirs[:] = [s for s in subdirs
                      if s == ".claude" or s[0] not in "._"]
        out += [os.path.relpath(os.path.join(d, n), ROOT) for n in names]
    return out


def named_paths(text):
    for span in re.findall(r"`([^`\n]+)`", text):
        for path in PATH.findall(span):
            yield path


@pytest.mark.parametrize("doc", ["README.md", "SUPPORTED_OPS.md",
                                 ".claude/skills/verify/SKILL.md"])
def test_doc_names_only_paths_that_exist(doc):
    files = tree_files()
    held = set(files) | {os.path.basename(f) for f in files} \
        | WRITTEN_AT_RUN_TIME
    with open(os.path.join(ROOT, doc)) as f:
        named = sorted(set(named_paths(f.read())))
    assert named, doc
    missing = [p for p in named
               if not any(os.path.normpath(os.path.join(b, p)) in held
                          for b in BASES)]
    assert missing == [], (doc, missing)
