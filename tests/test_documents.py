"""One yardstick: what the documents, the Makefile and the env's constants
may say, now that ``benchmark/run.py`` is the one program that measures.

None of this is numerics. A speed is stated in ``PERF_LEDGER.jsonl`` and
``PERF.md`` and nowhere else; a document sends its reader only to a make
target that exists, and a target only runs what is in the tree; the
wall-runner's geometry is the reference's and the cell's.
"""

import functools
import importlib.util
import pathlib
import re
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# The documents a new owner reads for what the system is and does. They
# describe; they do not measure.
DOCUMENTS = ["README.md", "PARITY.md"] + sorted(
    f"docs/{p.name}" for p in (REPO / "docs").glob("*.md")
)

# Where a speed may stand, and why: the builders' account of the chip
# runs (PERF.md), the queue of work that cites the ledger (ROADMAP.md),
# the history of PRs (CHANGES.md) and the yardstick's own manual
# (benchmark/README.md). PERF_LEDGER.jsonl is the driver's.
SPEED_MAY_STAND_IN = {
    "PERF.md", "ROADMAP.md", "CHANGES.md", "benchmark/README.md",
}


# ------------------------------------------------------------- the geometry


@pytest.mark.parametrize(
    "constant, reference, stated_as",
    [
        # ref environments/wall_runner.py:20-21 and the egocentric camera
        ("FEATURE_DIM", 168, "feature_dim"),
        ("FRAME_SHAPE", (64, 64, 3), "frame"),
        ("ACT_DIM", 56, "act_dim"),
    ],
)
def test_wall_runner_geometry_is_the_references_and_the_cells(
    constant, reference, stated_as
):
    """``envs/wall_runner.py``'s constants are the reference's spaces,
    and ``benchmark/configs/wallrunner_cnn.json`` states the same: what
    the cell measures is what the env defines."""
    from benchmark.harness import registry
    from torch_actor_critic_tpu.envs import wall_runner

    _, _, config = registry.resolve("wallrunner_cnn_burst")
    stated = config["model"][stated_as]
    assert getattr(wall_runner, constant) == reference
    assert (tuple(stated) if isinstance(stated, list) else stated) == reference


# ------------------------------------------------------------- the Makefile


@functools.cache
def _makefile():
    """``{target: [recipe lines]}`` and the ``.PHONY`` list."""
    targets, phony, current = {}, [], None
    for line in (REPO / "Makefile").read_text().splitlines():
        if line.startswith(".PHONY:"):
            phony = line.split(":", 1)[1].split()
        elif re.match(r"^[A-Za-z][\w-]*:", line):
            current = line.split(":", 1)[0]
            targets[current] = []
        elif line.startswith("\t") and current is not None:
            targets[current].append(line.strip())
    return targets, phony


def test_every_make_target_runs_something_that_exists():
    targets, phony = _makefile()
    assert sorted(phony) == sorted(targets), "the .PHONY line is the targets"
    missing = []
    for target, recipe in targets.items():
        text = " ".join(recipe)
        for path in re.findall(r"(?<![\w/.-])([\w./-]+\.py)\b", text):
            if not (REPO / path).is_file():
                missing.append(f"{target}: {path}")
        for module in re.findall(r"python -m ([\w.]+)", text):
            if importlib.util.find_spec(module) is None:
                missing.append(f"{target}: python -m {module}")
        for directory in re.findall(r"\$\(MAKE\) -C (\S+)", text):
            if not (REPO / directory / "Makefile").is_file():
                missing.append(f"{target}: make -C {directory}")
    assert not missing, missing


@pytest.mark.parametrize("document", DOCUMENTS)
def test_documents_name_only_make_targets_that_exist(document):
    """``make <name>`` in code quotes, in brackets or at the start of a
    line of a document is a target of the Makefile."""
    targets, _ = _makefile()
    named = re.findall(
        r"(?:^|[`(])make ([a-z][a-z0-9-]*)",
        (REPO / document).read_text(), re.MULTILINE,
    )
    gone = sorted(set(named) - set(targets))
    assert not gone, f"{document} names make targets that do not exist: {gone}"


# ------------------------------------------------------------------ a speed

_A_SPEED = re.compile(
    r"\d[\d,.]*\s?[%+kKMx×]?\s*"
    r"(?:grad[- ]steps?/s|env[- ]steps?/s|steps?/s|examples/s|[TG]FLOP/s|MFU)"
)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_a_speed_is_stated_in_one_place(document):
    """No line of a document holds a number followed by a rate or a
    utilization. The one exception is a hardware peak, on a line that
    says "peak" (``benchmark/harness/peaks.py`` has its source)."""
    assert document not in SPEED_MAY_STAND_IN
    stated = [
        f"{document}:{n}: {line.strip()[:100]}"
        for n, line in enumerate((REPO / document).read_text().splitlines(), 1)
        if _A_SPEED.search(line) and "peak" not in line.lower()
    ]
    assert not stated, (
        "a speed belongs in PERF.md (section and cell) and the ledger:\n"
        + "\n".join(stated)
    )
