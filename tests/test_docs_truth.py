"""The documents name only what exists: every `tools/`, `tests/`,
`tendermint_tpu/` or `benchmark/` path a document mentions is in the
tree, every `TENDERMINT_TPU_*` name it mentions is one the package
reads, and every name the package reads has a row in a document's
settings table. A document that outlives the file, test or setting it
describes is how a second account of the system grows beside the code.
"""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO) for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
)

_PATH = re.compile(
    r"(?<![\w/.*-])((?:tools|tests|tendermint_tpu|benchmark)/[\w./*-]*\w)"
)
_TEST_ID = re.compile(r"(tests/\w+\.py)((?:::\w+)+)")
_KNOB = re.compile(r"TENDERMINT_TPU_[A-Z0-9_]+")


def _read(rel: str) -> str:
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        return f.read()


@functools.cache
def _knobs_read_by_package() -> frozenset[str]:
    names: set[str] = set()
    for path in glob.glob(
        os.path.join(REPO, "tendermint_tpu", "**", "*.py"), recursive=True
    ):
        with open(path, encoding="utf-8") as f:
            names.update(_KNOB.findall(f.read()))
    return frozenset(names)


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_what_exists(doc):
    text = _read(doc)
    missing = sorted(
        {
            path
            for path in _PATH.findall(text)
            if not glob.glob(os.path.join(REPO, path))
        }
    )
    assert not missing, f"{doc} names paths that are not in the tree: {missing}"
    for path, names in _TEST_ID.findall(text):
        source = _read(path)
        gone = [
            n for n in names.split("::")[1:]
            if not re.search(rf"^\s*(?:class|def) {n}\b", source, re.M)
        ]
        assert not gone, f"{doc} names {path}::{'::'.join(gone)}, which is not there"

    read = _knobs_read_by_package()
    unread = sorted(
        {
            name
            for name in _KNOB.findall(text)
            # a trailing underscore is a family (`TENDERMINT_TPU_SLO_*`)
            if not (
                any(k.startswith(name) for k in read)
                if name.endswith("_")
                else name in read
            )
        }
    )
    assert not unread, f"{doc} names settings the package does not read: {unread}"


def test_every_setting_the_package_reads_has_a_table_row():
    rows: set[str] = set()
    for doc in DOCS:
        rows.update(
            re.findall(r"^\|\s*`(TENDERMINT_TPU_[A-Z0-9_]+)", _read(doc), re.M)
        )
    undocumented = sorted(_knobs_read_by_package() - rows)
    assert not undocumented, (
        "read by tendermint_tpu/ with no row in a settings table of "
        f"README.md or docs/*.md: {undocumented}"
    )
