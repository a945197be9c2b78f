"""The documents, the package and the knob help texts name only files
that are in the tree.

Pure text: nothing here imports the package (or jax).  A path is a token
that starts with one of the repo's top-level directories, a bare
``name.py`` (a root script, or a module the tree has under that name), a
root-level record in capitals (``PERF.md``, ``BENCHMARK.json``), or, in a
document, a relative markdown link.  A trailing ``:line`` / ``::test`` is
cut; a glob has to match something.
"""

import ast
import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "horovod_tpu")
TOP_DIRS = ("horovod_tpu", "benchmark", "tests", "tools", "docs", "examples",
            "native", "ci")

# Paths of the reference Horovod tree (cited with "ref:") and placeholders
# for a user's own files, which start like ours.
NOT_OURS = {
    "docs/benchmarks.rst", "docs/timeline.rst",
    "examples/pytorch/pytorch_synthetic_benchmark.py",
    "examples/tensorflow2/tensorflow2_synthetic_benchmark.py",
    "gloo_run.py", "http_server.py", "driver_service.py",
    "tests/test_x.py", "train.py", "worker.py", "drive.py",
    "examples/s",                      # a rate, not a path
    "horovod_tpu/native/_lib/",        # where setup.py puts the built core
}

_PATH = re.compile(
    r"(?<![\w/.\-])("
    r"(?:" + "|".join(TOP_DIRS) + r")/[\w./*\-]*"      # under a top directory
    r"|[A-Za-z_]\w*\.py"                               # a bare script
    r"|[A-Z][A-Z0-9_]{3,}\.(?:md|jsonl|json)"          # a record in capitals
    r")")
_LINK = re.compile(r"\]\(([^)#\s]+)(?:#[^)]*)?\)")

DOCUMENTS = (["README.md"]
             + sorted(os.path.relpath(p, REPO)
                      for p in glob.glob(os.path.join(REPO, "docs", "*.md")))
             + [".claude/skills/verify/SKILL.md"])
PACKAGES = sorted(
    d for d in os.listdir(PACKAGE)
    if os.path.isfile(os.path.join(PACKAGE, d, "__init__.py"))) + ["<root>"]


@functools.lru_cache(maxsize=None)
def _basenames():
    """Every ``*.py`` file name in the tree."""
    found = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("chiprun_out", "__pycache__")]
        found.update(f for f in files if f.endswith(".py"))
    return found


def missing_paths(text, link_base=None):
    """The path tokens of ``text`` that name nothing in the tree."""
    missing = []
    tokens = [m.group(1) for m in _PATH.finditer(text)]
    links = []
    if link_base is not None:
        links = [t for t in _LINK.findall(text)
                 if "://" not in t and not t.startswith("mailto:")]
    for token in tokens:
        path = re.sub(r"(::.*|:\d[\d,\-]*)$", "", token).rstrip(".-")
        if not path or path in NOT_OURS or "<" in path:
            continue
        if "/" not in path and path.endswith(".py"):
            if path not in _basenames():
                missing.append(token)
        elif "*" in path:
            if not glob.glob(os.path.join(REPO, path)):
                missing.append(token)
        elif not os.path.exists(os.path.join(REPO, path)):
            missing.append(token)
    for link in links:
        if not os.path.exists(os.path.normpath(
                os.path.join(link_base, link))):
            missing.append(f"]({link})")
    return sorted(set(missing))


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_every_repo_path_a_document_names_exists(doc):
    with open(os.path.join(REPO, doc)) as fh:
        text = fh.read()
    assert missing_paths(
        text, os.path.dirname(os.path.join(REPO, doc))) == []


# A script the package may send its reader to: the root script retired in
# PR 44 (spelled in two pieces so that a grep for it finds only real
# pointers) or anything under ``tools/``.
_SCRIPT = re.compile(r"(?<![\w/.\-])(bench" r"\.py|tools/[\w/]+\.py)")


def _package_files(package):
    if package == "<root>":
        return sorted(glob.glob(os.path.join(PACKAGE, "*.py")))
    return sorted(glob.glob(os.path.join(PACKAGE, package, "**", "*.py"),
                            recursive=True))


@pytest.mark.parametrize("package", PACKAGES)
def test_no_module_of_the_package_names_a_missing_script(package):
    files = _package_files(package)
    assert files
    missing = []
    for path in files:
        with open(path) as fh:
            for n, line in enumerate(fh, 1):
                missing += [
                    f"{os.path.relpath(path, REPO)}:{n}: {name}"
                    for name in _SCRIPT.findall(line)
                    if not os.path.exists(os.path.join(REPO, name))]
    assert missing == []


def _knob_helps():
    """(name, help) of every ``_k(name, default, parser, help)`` call in
    ``common/config.py``, read from its source."""
    with open(os.path.join(PACKAGE, "common", "config.py")) as fh:
        tree = ast.parse(fh.read())
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "_k" and len(node.args) >= 4):
            yield (ast.literal_eval(node.args[0]),
                   ast.literal_eval(node.args[3]))


def test_no_knob_help_names_a_missing_file():
    helps = dict(_knob_helps())
    assert len(helps) > 100          # the registry was found
    missing = {name: found for name, text in helps.items()
               if (found := missing_paths(text))}
    assert missing == {}
