"""The documents name only what exists: every path a document quotes in
backticks is in the tree.

A token counts as a path where it starts with one of the repository's
directories (``vllm_omni_tpu/``, ``benchmark/``, ``scripts/``, ``tests/``,
``docs/``) or is a bare ``*.py`` at the root.  A trailing ``:line`` or
``::test`` is stripped; a token with a glob or a placeholder
(``*``, ``<name>``, ``{a,b}``, ``...``) is skipped.  ``CHANGES.md`` is
history and exempt; so are the record documents that narrate what was
removed (``PERF.md``, ``ROADMAP.md``).
"""

import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
DOCUMENTS = [ROOT / "README.md", *sorted((ROOT / "docs").glob("*.md"))]
PREFIXES = ("vllm_omni_tpu/", "benchmark/", "scripts/", "tests/", "docs/")
_TOKEN = re.compile(r"`([^`\s]+)`")
_ROOT_PY = re.compile(r"[\w.-]+\.py")


def quoted_paths(text: str) -> list[str]:
    paths = []
    for token in _TOKEN.findall(text):
        if any(c in token for c in "*<>{}$") or "..." in token:
            continue
        path = re.sub(r"(::.*|:\d+(-\d+)?)$", "", token.rstrip(".,;)"))
        if path.startswith(PREFIXES) or _ROOT_PY.fullmatch(path):
            paths.append(path)
    return paths


@pytest.mark.parametrize("document", DOCUMENTS,
                         ids=[str(d.relative_to(ROOT)) for d in DOCUMENTS])
def test_every_quoted_path_exists(document):
    missing = sorted({p for p in quoted_paths(document.read_text())
                      if not (ROOT / p).exists()})
    assert not missing, f"{document.name} cites paths not in the tree"


def test_the_reader_takes_paths_and_leaves_the_rest():
    text = ("`scripts/gone.py` and `tests/x.py::test_a` and `chip_smoke.py:12`"
            " but not `scripts/<name>.sh`, `tests/*.py`, `jax.numpy`, "
            "`EngineConfig` or `python -m x`; `docs/` is a directory.")
    assert quoted_paths(text) == ["scripts/gone.py", "tests/x.py",
                                  "chip_smoke.py", "docs/"]
