"""Every path of this repo that README.md or docs/*.md names exists.

Narrow on purpose: a backticked path that starts with one of this repo's
top-level directories and ends in .py/.json/.md/.yml, and the script of a
`python <file>.py` line in a code block.  Bare file names and the reference
repo's paths (`cli_args.py`, `tensor_parallel/modules.py`) are not this
repo's; PERF.md, ROADMAP.md and CHANGES.md name deleted files as history and
are out of reach.
"""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, ROOT) for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))
)

_FENCE = re.compile(r"^```.*?^```", re.M | re.S)
_SPAN = re.compile(r"`([^`]+)`")
_PATH = re.compile(
    r"(?<![\w/.-])((?:scripts|examples|benchmarks|areal_tpu|tests|docs|\.github)"
    r"/[\w./-]+\.(?:py|json|md|yml))(?![\w/-])"
)
_PYTHON_LINE = re.compile(r"\bpython3?[ \t]+([\w./-]+\.py)\b")


def named_paths(text):
    """(path, how it was named) for every path the rule covers."""
    out = []
    for block in _FENCE.findall(text):
        out += [(m, "python line") for m in _PYTHON_LINE.findall(block)]
    for span in _SPAN.findall(_FENCE.sub("", text)):
        out += [(m, "backticked") for m in _PATH.findall(span)]
    return sorted(set(out))


def test_the_rule_sees_what_it_should():
    text = (
        "see `scripts/lint.py --check`, `tests/test_lint.py::test_repo_clean`,\n"
        "`areal_tpu/analysis/wire_contracts.json`, not `cli_args.py`, not\n"
        "`realhf/impl/model/utils/ppo_functional.py`, not `benchmarks/workloads/<cell>.json`\n"
        "```\npython -m areal_tpu.gen.server --tp 4\n"
        "JAX_PLATFORMS=cpu python3 gone.py --flag   # comment\n```\n"
    )
    assert named_paths(text) == [
        ("areal_tpu/analysis/wire_contracts.json", "backticked"),
        ("gone.py", "python line"),
        ("scripts/lint.py", "backticked"),
        ("tests/test_lint.py", "backticked"),
    ]


@pytest.mark.parametrize("doc", DOCS)
def test_every_named_path_exists(doc):
    with open(os.path.join(ROOT, doc)) as f:
        named = named_paths(f.read())
    missing = [
        f"{path} ({how})" for path, how in named
        if not os.path.exists(os.path.join(ROOT, path))
    ]
    assert not missing, f"{doc} names files that do not exist: {missing}"
