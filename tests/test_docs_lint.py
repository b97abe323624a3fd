"""Static guard for the documents: what they name is in the tree.

`README.md`, the hand-written `docs/*.md` and the verify skill tell a
reader which file to open and which command to run. A file that was
deleted or renamed leaves them pointing at nothing, and nobody runs a
document. So, one case a document:

- every path it names that starts inside the checkout — a first
  component found at the root, under `mxnet_tpu/`, `tests/`,
  `perfbench/` or next to the document — exists there;
- every bare `name.py` / `NAME.md` is some file's name in the tree;
- every `python path/to/script.py` runs a script that exists, and every
  `python -m package.module` of a package of this repo names a module
  with a `__main__` entry.

Absolute paths, run-time artifacts named without a directory
(`manifest.json`) and other people's modules (`python -m pytest`) are
not the tree's to hold and are skipped. `docs/API.md` is generated
(`docs/gen_api.py`) and is not linted.
"""
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: directories a checkout does not carry (`.gitignore`), so a document
#: may not lean on them
_NOT_THE_TREE = {"__pycache__", "chiprun_out", "_archive_check",
                 "perfbench_scratch"}

DOCUMENTS = (["README.md", ".claude/skills/verify/SKILL.md"]
             + sorted(os.path.relpath(p, REPO) for p in
                      glob.glob(os.path.join(REPO, "docs", "*.md"))
                      if os.path.basename(p) != "API.md"))

_PATH = re.compile(r"(?<![\w/.\-])((?:[\w.\-]+/)*[\w.\-]+"
                   r"\.(?:py|md|json|jsonl|sh|cc))(?![\w\-])")
_SCRIPT = re.compile(r"\bpython3?\s+((?:[\w.\-]+/)*[\w.\-]+\.py)\b")
_MODULE = re.compile(r"\bpython3?\s+-m\s+([\w.]+)")


def _tree_names():
    names = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d not in _NOT_THE_TREE]
        names.update(files)
    return names


def _bases(doc):
    return [REPO, os.path.join(REPO, "mxnet_tpu"),
            os.path.join(REPO, "tests"), os.path.join(REPO, "perfbench"),
            os.path.dirname(os.path.join(REPO, doc))]


def _missing_paths(doc, text, names):
    missing = set()
    for path in set(_PATH.findall(text)):
        head, _, rest = path.partition("/")
        if not rest:
            if path.endswith((".py", ".md")) and path not in names:
                missing.add(path)
            continue
        homes = [b for b in _bases(doc)
                 if os.path.exists(os.path.join(b, head))]
        if homes and not any(os.path.exists(os.path.join(b, path))
                             for b in homes):
            missing.add(path)
    return missing


def _missing_commands(text):
    missing = set()
    for script in set(_SCRIPT.findall(text)):
        if not os.path.exists(os.path.join(REPO, script)):
            missing.add(f"python {script}")
    for module in set(_MODULE.findall(text)):
        parts = module.rstrip(".").split(".")
        if not os.path.isdir(os.path.join(REPO, parts[0])):
            continue        # not a package of this repo
        stem = os.path.join(REPO, *parts)
        entry = (stem + ".py" if os.path.isfile(stem + ".py")
                 else os.path.join(stem, "__main__.py"))
        if not os.path.isfile(entry):
            missing.add(f"python -m {module} (no such module)")
        else:
            with open(entry) as f:
                if "__main__" not in f.read():
                    missing.add(f"python -m {module} (no __main__)")
    return missing


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_what_the_tree_holds(doc):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    missing = (_missing_paths(doc, text, _tree_names())
               | _missing_commands(text))
    assert not missing, (
        f"{doc} names what the tree does not hold: {sorted(missing)}")
