"""A document names only what exists (ISSUE 28): every repo-relative path
in backticks, every ``python -m <module>`` / ``python <script>`` and every
``make <target>`` that ``README.md``, a page under ``docs/``,
``COVERAGE.md``, the ``Makefile`` or the verify skill names must resolve
against the working tree. Fourteen files kept pointing at a deleted
script for five PRs; this is the guard that was missing."""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = sorted(
    ["README.md", "COVERAGE.md", "Makefile", ".claude/skills/verify/SKILL.md"]
    + [
        os.path.relpath(p, REPO)
        for p in glob.glob(os.path.join(REPO, "docs", "**", "*.md"),
                           recursive=True)
    ]
)

PATH_SUFFIXES = (".py", ".md", ".json", ".yaml", "/")
# a path in a document is relative to the checkout, to the package, or to
# the tests (``gen/engine.py``, ``test_datapack.py``), or to the document
PATH_ROOTS = ("", "areal_tpu", "tests")
# top-level packages of this checkout: ``python -m`` of anything else
# (pytest, http.server) is not the repo's to resolve
OWN_PACKAGES = ("areal_tpu", "benchmark", "tools", "tests")
# the reference project's top-level directories (``SURVEY.md``): a path
# under one of them is the reference's own, written out in full
REFERENCE_DIRS = ("realhf/", "csrc/", "functioncall/", "training/")
# file names of the Hugging Face checkpoint format and of the commit
# protocol: what a RUN reads and writes, not files of the checkout
RUNTIME_NAMES = {"config.json", "COMMIT.json"}

_INLINE = re.compile(r"`([^`\n]+)`")
_FENCE = re.compile(r"^```.*?$(.*?)^```", re.M | re.S)
_PATHISH = re.compile(r"^[\w.\-/{},*<>]+$")


def _expand_braces(token):
    m = re.search(r"\{([^{}]*,[^{}]*)\}", token)
    if not m:
        return [token]
    out = []
    for alt in m.group(1).split(","):
        out += _expand_braces(token[: m.start()] + alt + token[m.end():])
    return out


def _resolves(path, doc_dir):
    """``path`` (no placeholders) exists under one of the roots; a bare
    file name may live anywhere in the checkout's own packages."""
    roots = [os.path.join(REPO, r) for r in PATH_ROOTS] + [doc_dir]
    for root in roots:
        if glob.glob(os.path.join(root, path)):
            return True
    if "/" not in path.rstrip("/"):
        for pkg in OWN_PACKAGES:
            if glob.glob(os.path.join(REPO, pkg, "**", path), recursive=True):
                return True
    return False


def _path_pointers(text):
    for token in _INLINE.findall(text):
        token = token.strip()
        # `gen/engine.py::_chunk_fn`, `base/constants.py:214`, `x.py:10-20`
        token = re.sub(r"(::[\w.:\[\]<>-]+|:[\d,\-]+)$", "", token)
        if not token.endswith(PATH_SUFFIXES) or not _PATHISH.match(token):
            continue
        if token.startswith(("/", "~", "<") + REFERENCE_DIRS) or "..." in token:
            continue    # absolute, a run's own root, the reference's tree
        if os.path.basename(token) in RUNTIME_NAMES:
            continue
        if token.endswith("/") and token.count("/") == 1:
            continue    # `fleet/`, `gw/`: a counter namespace, not a directory
        for path in _expand_braces(token):
            # `<cell>` placeholders match anything
            yield token, re.sub(r"<[^<>]*>", "*", path)


def _code(text):
    """Inline and fenced code: where a command is a command, not prose."""
    return "\n".join(_INLINE.findall(text) + _FENCE.findall(text))


def _module_file(module):
    parts = module.split(".")
    if parts[0] not in OWN_PACKAGES:
        return None
    base = os.path.join(REPO, *parts)
    return base + ".py" if not os.path.isdir(base) else os.path.join(
        base, "__main__.py"
    )


def _make_targets():
    text = open(os.path.join(REPO, "Makefile")).read()
    return set(re.findall(r"^([A-Za-z][\w-]*):", text, re.M))


def broken_pointers(doc):
    path = os.path.join(REPO, doc)
    text = open(path).read()
    doc_dir = os.path.dirname(path)
    broken = []
    for token, pattern in _path_pointers(text):
        if not _resolves(pattern, doc_dir):
            broken.append(f"path `{token}`")
    code = text if doc == "Makefile" else _code(text)
    for module in re.findall(r"python3? -m ([A-Za-z_][\w.]*)", code):
        f = _module_file(module)
        if f is not None and not os.path.exists(f):
            broken.append(f"python -m {module}")
    for script in re.findall(r"python3? ([\w./\-]+\.py)\b", code):
        if not _resolves(script, doc_dir):
            broken.append(f"python {script}")
    targets = _make_targets()
    for target in re.findall(r"(?:^|[\s;&|(])make ([a-z][\w-]*)", code, re.M):
        if target not in targets:
            broken.append(f"make {target}")
    return sorted(set(broken))


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_what_exists(doc):
    assert broken_pointers(doc) == []


def test_the_guard_sees_a_broken_pointer(tmp_path):
    """The checker itself: a deleted script, module, target and path are
    each reported; what exists is not."""
    doc = tmp_path / "page.md"
    doc.write_text(
        "Run `python gone_script.py` or `python -m areal_tpu.apps.nope`, "
        "then `make no-such-target`; see `areal_tpu/base/gone.py` and "
        "`gen/engine.py::_chunk_fn`, `python -m areal_tpu.apps.obs`, "
        "`make lint`, `python -m pytest`, `tests/`.\n"
    )
    assert broken_pointers(os.path.relpath(str(doc), REPO)) == [
        "make no-such-target",
        "path `areal_tpu/base/gone.py`",
        "python -m areal_tpu.apps.nope",
        "python gone_script.py",
    ]


def test_readme_lists_the_benchmark_s_cells():
    """The "Benchmarks" table of ``README.md`` has a row for every entry
    of ``BENCHMARK.json``'s ``workloads`` and for nothing else (the
    sentences around it count nothing)."""
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    text = open(os.path.join(REPO, "README.md")).read()
    section = text[text.index("## Benchmarks"):]
    section = section[: section.index("\n## ", 1)]
    rows = re.findall(r"^\| `([\w.\-]+)` ", section, re.M)
    assert rows == cells
