"""The records name the tree that stands: a document that names a file
of this repository names one that exists, and the README's table of
cells is the benchmark's."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = [
    "README.md", "ci.sh", "fedlint.json", "pytest.ini",
    ".claude/skills/verify/SKILL.md", "docs/FAULT_TOLERANCE.md",
    "docs/MIGRATION.md", "docs/OBSERVABILITY.md", "docs/PARALLELISM.md",
    "docs/STATIC_ANALYSIS.md", "docs/PERFORMANCE.md",
]
TREES = ("fedml_tpu", "scripts", "tests", "benchmarks", "docs")
TOKEN = re.compile(r"[\w.<>*{}\[\]$?/-]+")
BARE_FILE = re.compile(r"[\w.-]+\.(?:py|sh|json|md)")
# bare names the documents use for files that are not this repository's:
# the reference project's ...
THE_REFERENCES = {
    "CI-script-fedavg.sh", "CI-script-fednas.sh", "GKTClientTrainer.py",
    "InceptionV3.py", "fedavg_api.py", "guest_trainer.py",
    "main_fedavg.py", "mnn_torch.py", "run_client.sh",
    "run_fed_experiment.sh", "run_fedavg_distributed_pytorch.sh",
    "run_server.sh",
}
# ... and what a user writes for a run or a run leaves behind
OF_A_RUN = {
    "breach.json", "capture.json", "cfg.json", "file.json",
    "findings.json", "ip.json", "merged.json", "metrics_rank0.json",
    "perf_rank0.json", "ring.json", "slo_rank0.json", "summary.json",
}


def _is_ignored(path, ignored):
    parts = path.split("/")
    return any(
        (path == d or path.startswith(d + "/")) if "/" in d else d in parts
        for d in ignored
    )


@pytest.fixture(scope="module")
def ignored():
    """The directories ``.gitignore`` lists."""
    with open(os.path.join(REPO, ".gitignore")) as f:
        return [ln.strip().rstrip("/") for ln in f if ln.strip().endswith("/")]


@pytest.fixture(scope="module")
def basenames(ignored):
    """Every file name of the checkout, the ignored directories left out."""
    names = set()
    for top, dirs, files in os.walk(REPO):
        rel = os.path.relpath(top, REPO)
        dirs[:] = [
            d for d in dirs if d != ".git"
            and not _is_ignored(os.path.normpath(os.path.join(rel, d)), ignored)
        ]
        names.update(files)
    return names


def named_paths(text, ignored):
    """The tokens of ``text`` that read as paths of this repository: a
    bare ``*.py|*.sh|*.json|*.md``, or anything under one of TREES.
    Globs, ``<placeholders>``, shell variables and what ``ignored``
    holds are left out."""
    for tok in TOKEN.findall(text):
        tok = tok.strip(".")
        if re.search(r"[<>*{}\[\]$?]", tok):
            continue
        if "/" in tok:
            if tok.split("/", 1)[0] in TREES and not _is_ignored(tok, ignored):
                yield tok
        elif BARE_FILE.fullmatch(tok):
            yield tok


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_files_that_exist(document, ignored, basenames):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    # a bare name may be a file deeper in the tree (``run.py``)
    bare_ok = basenames | THE_REFERENCES | OF_A_RUN
    gone = sorted({
        tok for tok in named_paths(text, ignored)
        if not os.path.exists(os.path.join(REPO, tok))
        and ("/" in tok or tok not in bare_ok)
    })
    assert gone == [], f"{document} names files that do not exist: {gone}"


def test_readme_lists_the_benchmarks_cells():
    with open(os.path.join(REPO, "README.md")) as f:
        section = f.read().split("\n## Performance\n", 1)[1].split("\n## ", 1)[0]
    listed = re.findall(r"^\| `([^`|]+)` \|", section, flags=re.M)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    assert listed == cells
