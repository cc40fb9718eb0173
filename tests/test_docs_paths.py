"""Documents do not name what is not there: a backticked path under one
of the repo's top-level directories, and a script run with `python` in
a code block or span, has to exist in the tree. Globs, `<placeholders>`
and module-relative shorthand such as `lm_server.py` are not judged,
but a bare `name.py` in a document that lives in one of those
directories is a file beside it."""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP_DIRS = ("dml_tpu/", "tests/", "benchmark/", "examples/", "native/")
DOCS = ("README.md", "PARITY.md", "examples/README.md",
        ".claude/skills/verify/SKILL.md")

_FENCE = re.compile(r"^```.*?^```", re.M | re.S)
_SPAN = re.compile(r"`([^`\n]+)`")
_SCRIPT = re.compile(r"\bpython3?\s+(?:-[A-Za-z]\s+)*([\w./-]+\.py)\b")
_NOT_JUDGED = set("*<>{}[]$…")


def named_paths(text, beside=""):
    """-> {path: the text that names it}; `beside` is the document's
    own directory where that is one of `TOP_DIRS`."""
    named = {}
    spans = _SPAN.findall(_FENCE.sub("", text))
    for span in spans:
        token = span.split()[0]
        if beside and re.fullmatch(r"\w+\.py", token):
            token = beside + token
        if token.startswith(TOP_DIRS):
            # `path.py:12`, `path.py::test`, `path.py:Class.method`
            path = re.split(r"[:(#,]", token)[0].rstrip(".")
            if not _NOT_JUDGED & set(path):
                named[path] = span
    for code in _FENCE.findall(text) + spans:
        for script in _SCRIPT.findall(code):
            named[script] = code.strip()[:80]
    return named


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_paths_that_exist(doc):
    beside = os.path.dirname(doc) + "/"
    with open(os.path.join(ROOT, doc)) as f:
        named = named_paths(f.read(), beside if beside in TOP_DIRS else "")
    assert named, f"{doc} names no path: the reader found nothing to judge"
    missing = {p: why for p, why in named.items()
               if not os.path.exists(os.path.join(ROOT, p))}
    assert not missing, f"{doc} names files that are not in the tree: {missing}"


def test_the_reader_judges_paths_and_scripts_and_nothing_else():
    text = "\n".join([
        "See `dml_tpu/inference/lm_server.py:482` and `tests/test_x.py::test_y`,",
        "not `lm_server.py`, `tests/test_*.py`, `benchmark/<name>.json` or `--flag`.",
        "Run `python3 benchmark/run.py --workload <cell>` or:",
        "```bash",
        "python -m pytest tests/ -q          # a directory after -m is no script",
        "python chip_smoke.py --seed 1",
        "```",
    ])
    assert set(named_paths(text)) == {
        "dml_tpu/inference/lm_server.py", "tests/test_x.py",
        "benchmark/run.py", "chip_smoke.py"}
    assert set(named_paths("`serve_lm.py` and `numpy.py`", "examples/")) == {
        "examples/serve_lm.py", "examples/numpy.py"}
