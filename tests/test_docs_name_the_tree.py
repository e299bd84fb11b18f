"""README.md and docs/*.md name only files this checkout holds.

Every backticked word that is a path to a ``.py``, ``.md``, ``.json``,
``.cc`` or ``.yaml`` file has to exist: a document that sends its reader to
a file that went (a benchmark script, a record of another machine) states
something nobody can check. One case a document, so a new document is a new
case and a stale pointer names its file.

A word counts when it has a ``/`` (looked up from the root, from
``dynamo_tpu/`` and from the document's own directory, as the documents
write them) or when it is a bare file name (looked up by name anywhere in
the tree: ``engine.py`` for ``dynamo_tpu/engine_jax/engine.py``). Not
counted: globs, placeholders in ``<...>`` or ``{...}``, absolute and home
paths, ``build/``, and the files of a model directory or a run's output
that no checkout holds (``NOT_OF_THE_TREE``).
"""

import os
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
DOCUMENTS = [ROOT / "README.md"] + sorted((ROOT / "docs").glob("*.md"))

TICKED = re.compile(r"`([^`\n]+)`")
# a path to a file of the five kinds, with an optional `:line` / `:name` tail
FILE_WORD = re.compile(r"^([\w.+\-/]+\.(?:py|md|json|cc|yaml))((?::[\w.\-]+)*)$")
SKIPPED_DIRS = {".git", "build", "__pycache__", ".jax_cache", ".bench_runs",
                ".bench_checkout", "chiprun_out", ".pytest_cache"}
# files a model directory or a run writes, which documents name by right
NOT_OF_THE_TREE = {
    "config.json", "tokenizer.json", "tokenizer_config.json",
    "generation_config.json", "model.safetensors.index.json",
    "manifest.json", "schedule.json", "schedule.min.json", "result.json",
}


def _names_in_tree():
    names = set()
    for _, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in SKIPPED_DIRS]
        names.update(files)
    return names


def file_words(text):
    """The (word, path) pairs of a document that claim a file exists."""
    for ticked in TICKED.findall(text):
        for word in ticked.split():
            word = word.strip("()[],;\"'")
            m = FILE_WORD.match(word)
            if m is None or any(c in word for c in "*<>{}$"):
                continue
            path = m.group(1)
            if path.startswith(("/", "~", "build/", "./build/")):
                continue
            yield word, path


def missing_from(document, names):
    gone = []
    for word, path in file_words(document.read_text()):
        if "/" not in path:
            found = path in names or path in NOT_OF_THE_TREE
        else:
            found = os.path.basename(path) in NOT_OF_THE_TREE or any(
                (base / path).is_file()
                for base in (ROOT, ROOT / "dynamo_tpu", document.parent)
            )
        if not found:
            gone.append(word)
    return sorted(set(gone))


@pytest.fixture(scope="module")
def names():
    return _names_in_tree()


@pytest.mark.parametrize("document", DOCUMENTS, ids=lambda p: p.name)
def test_a_document_names_only_files_the_checkout_holds(document, names):
    assert missing_from(document, names) == []


def test_the_reader_sees_paths_and_skips_what_is_no_claim(tmp_path, names):
    text = (
        "`dynamo_tpu/cli/run.py:328` and `python3 benchmark/run.py --trace 1`, "
        "`engine.py`, `tests/test_*.py`, `<dir>/config.json`, `/root/x.json`, "
        "`build/lib/a.py`, `nowhere/at_all.py`, `gone.md`"
    )
    assert [p for _, p in file_words(text)] == [
        "dynamo_tpu/cli/run.py", "benchmark/run.py", "engine.py",
        "nowhere/at_all.py", "gone.md",
    ]
    document = tmp_path / "doc.md"
    document.write_text(text)
    assert missing_from(document, names) == [
        "gone.md", "nowhere/at_all.py",
    ]
