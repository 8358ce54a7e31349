"""The documents name only paths that exist.

A reader follows a path a document gives; one that leads nowhere is a claim
about a repository that no longer exists (README.md pointed at a benchmark,
its records and its targets for two rounds after they were superseded). The
histories (CHANGES.md, ROADMAP.md, PERF.md, SURVEY.md) name what was, and are
not held to this.
"""

import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DOCUMENTS = ("README.md", "docs/ARCHITECTURE.md", "docs/OBSERVABILITY.md", "docs/SERVING.md", "docs/ANALYSIS.md",
             "docs/MIGRATION.md")
# the upstream reference's tree, which the documents cite beside ours
UPSTREAM = ("deepspeed/", "csrc/", "op_builder/", "accelerator/")
FILE_TYPES = ("py", "md", "json", "jsonl", "txt", "cpp", "h", "ini", "sh")

_FENCE = re.compile(r"^```.*?^```", re.S | re.M)
_QUOTED = re.compile(r"`([^`\n]+)`")
_LINKED = re.compile(r"\]\(([^)\s]+)\)")
_PATH = re.compile(r"[A-Za-z0-9_.\-/]+")
_SUFFIX = re.compile(r"(::.*|:\d+(-\d+)?|#.*)$")  # a test id, a line, an anchor


def named_paths(text):
    """The tokens of ``text`` that a reader would take for a path of this
    repository: back-quoted or linked, made of path characters only (so no
    placeholder, glob or command), and either containing a ``/`` and ending
    in one or in a file type, or a bare file name with a file type."""
    text = _FENCE.sub("", text)
    for token in _QUOTED.findall(text) + _LINKED.findall(text):
        token = _SUFFIX.sub("", token.strip())
        if not token or not _PATH.fullmatch(token) or token.startswith(("http", "/", "~", ".")):
            continue
        name = token.rsplit("/", 1)[-1]
        if token.endswith("/") or ("." in name and name.rsplit(".", 1)[1] in FILE_TYPES):
            yield token


def resolves(token, document):
    roots = (ROOT, ROOT / "deepspeed_tpu", (ROOT / document).parent)
    return any((root / token).exists() for root in roots)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_a_document_names_only_paths_that_exist(document):
    tokens = set(named_paths((ROOT / document).read_text()))
    assert tokens, "the scan found nothing: has the pattern rotted?"
    missing = sorted(t for t in tokens if not t.startswith(UPSTREAM) and not resolves(t, document))
    assert not missing, f"{document} names paths that do not exist: {missing}"


def test_the_scan_takes_paths_and_leaves_span_names_commands_and_placeholders():
    text = ("see `gone.py`, [the targets](TARGETS.md#rungs), `tools/gate.py:12`, `tests/unit/test_x.py::test_y`, "
            "`inference/v2/`; not `serve/admit`, `GET /perf`, `profiles/<device_kind>.json`, `*.py`, `jax.jit`, "
            "`python gone.py`\n```\nfenced.py\n```\n")
    assert sorted(named_paths(text)) == ["TARGETS.md", "gone.py", "inference/v2/", "tests/unit/test_x.py", "tools/gate.py"]
