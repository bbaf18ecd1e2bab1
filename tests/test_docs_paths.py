"""The documents that describe the system as it is name only files and
flags that exist: a document must not outlive the code it describes.
(``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are histories and stay
outside this check.)"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "ARCHITECTURE.md", "PARITY.md", "DEPLOY.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))
ROOTS = ["", "dml_cnn_cifar10_tpu", "tests", "tools", "benchmark"]
PATH_RE = re.compile(r"`([A-Za-z0-9_./-]+\.(py|cc))(:[0-9,-]+)?`")
FLAG_RE = re.compile(r"`(--[a-z_0-9]+)")


@pytest.fixture(scope="module")
def known_flags():
    """The program's flags, and those the scripts under ``tools/`` and
    ``benchmark/run.py`` state for themselves (their ``"--flag``
    literals, read as far as the documents' pattern reads)."""
    from dml_cnn_cifar10_tpu.cli.main import build_parser

    known = {s for a in build_parser()._actions for s in a.option_strings}
    for path in glob.glob(os.path.join(REPO, "tools", "*.py")) \
            + [os.path.join(REPO, "benchmark", "run.py")]:
        with open(path) as f:
            known.update(re.findall(r'"(--[a-z_0-9]+)', f.read()))
    return known


@pytest.mark.parametrize("doc", DOCS)
def test_paths_and_flags_in_document_exist(doc, known_flags):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    paths = {m.group(1) for m in PATH_RE.finditer(text)
             if not m.group(1).startswith("/")}
    missing = sorted(
        p for p in paths
        if not any(os.path.isfile(os.path.join(REPO, root, p))
                   for root in ROOTS))
    assert not missing, f"{doc} names files that do not exist: {missing}"
    unknown = sorted(
        {m.group(1) for m in FLAG_RE.finditer(text)} - known_flags)
    assert not unknown, f"{doc} names flags no parser has: {unknown}"
