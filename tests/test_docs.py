"""The documents against the tree: every repository path a document cites
exists, the README names every ``DISTRL_*`` switch the code reads, and no
program file cites the records of the measuring system that ``perfbench/``
replaced."""

import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md", "PERF.md", "ROADMAP.md", "MIGRATING.md", "PARITY.md",
        "tools/README.md"]

# directories that hold no file git would commit
_SKIP_DIRS = {".git", "_parent", "_archive", "chiprun_out", "__pycache__",
              ".perfbench_out", ".jax_cache", ".pytest_cache", ".hypothesis"}

# back-ticked names with one of the four endings that are NOT paths of this
# repository: the reference's own files, the guides' (relative to
# /opt/skills/guides), a checkpoint's and a run's artifacts
NOT_OURS = {
    "distributed_trainer.py", "distributed_actor.py", "helper.py",
    "reward_functions.py",
    "kinds/inference-serving.md", "kinds/distributed-training.md",
    "workloads.md",
    "config.json", "out/trace.json",
}

# files MIGRATING.md tells a user are gone; held to be gone, so that the
# list cannot outlive a file's return
REMOVED = {"ops/paged_int8.py", "tests/test_paged_int8_kernel.py",
           "utils/platform.py"}

_CITED = re.compile(r"`([^`\s]+?\.(?:py|md|json|sh))(?=[`:])")


@functools.cache
def _tree() -> list[str]:
    out = []
    for dirpath, dirnames, filenames in os.walk(REPO):
        dirnames[:] = [d for d in dirnames if d not in _SKIP_DIRS]
        out.extend(
            os.path.relpath(os.path.join(dirpath, f), REPO) for f in filenames
        )
    return out


def _in_tree(path: str, tree: list[str]) -> bool:
    """``path`` names a file of the tree in full or by its tail
    (``paged_engine.py``, ``ops/paged.py``, ``tools/trace_report.py``)."""
    return any(f == path or f.endswith("/" + path) for f in tree)


def missing_paths(text: str, tree: list[str]) -> list[str]:
    cited = set(_CITED.findall(text))
    return sorted(
        p for p in cited
        if not p.startswith(("/", "~")) and "*" not in p and "<" not in p
        and p not in NOT_OURS and p not in REMOVED
        and not _in_tree(p.removeprefix("./"), tree)
    )


@pytest.mark.parametrize("doc", DOCS)
def test_every_cited_path_exists(doc):
    with open(os.path.join(REPO, doc)) as f:
        assert missing_paths(f.read(), _tree()) == []


def test_a_removed_path_is_found_missing():
    tree = _tree()
    text = "see `tools/no_such_tool.py:12`, `README.md`"
    assert missing_paths(text, tree) == ["tools/no_such_tool.py"]
    assert not any(_in_tree(p, tree) for p in REMOVED)


def _program_files() -> list[str]:
    """The package, ``tools/`` and the root's entry points."""
    return [
        f for f in _tree()
        if f.endswith(".py") and (
            f.startswith(("distrl_llm_tpu/", "tools/")) or "/" not in f
        )
    ]


def _switches() -> list[str]:
    found: set[str] = set()
    for rel in _program_files():
        with open(os.path.join(REPO, rel)) as f:
            found.update(re.findall(r"\bDISTRL_[A-Z0-9_]+\b", f.read()))
    return sorted(found)


@pytest.mark.parametrize("switch", _switches())
def test_readme_names_every_switch(switch):
    with open(os.path.join(REPO, "README.md")) as f:
        assert re.search(rf"\b{switch}\b", f.read()), (
            f"{switch} is read by the code and named nowhere in README.md"
        )


def test_no_program_file_cites_the_retired_records():
    # the needles are assembled so that this file does not hold them either
    needles = re.compile(
        r"\b(?:BASE" r"LINE|VER" r"DICT|AD" r"VICE)\b|\bbench" r"\.py\b"
    )
    hits = []
    for rel in _program_files():
        with open(os.path.join(REPO, rel)) as f:
            for n, line in enumerate(f, 1):
                if needles.search(line):
                    hits.append(f"{rel}:{n}: {line.strip()}")
    assert hits == []
