"""What the two rehearsal files share: run one tiny cell in this process and
read its lines; hold the result line to the contract. A run costs 6-10 s of
CPU compiles whatever its window, so tests that look at the same run share it
(``shared_cell``) and the window is a fraction of a second."""

import contextlib
import io
import json
import os
import time

COUNTS = {"count"}
#: set round every rehearsal run: the harness has to unset it for the run and
#: put it back after
SWITCH = "DISTRL_SAMPLE_KERNEL"

_RUNS: dict = {}


def run_cell(benchmark, cell, trace, seed=3):
    """One run of a tiny cell: its result line, and its notes by name."""
    from perfbench import run

    out = io.StringIO()
    os.environ[SWITCH] = "xla"
    try:
        with contextlib.redirect_stdout(out):
            rc = run.main([
                "--workload", cell, "--seed", str(seed), "--seconds", "0.3",
                "--trace", str(trace), "--benchmark", benchmark,
            ], t0=time.perf_counter())
        assert os.environ[SWITCH] == "xla"
    finally:
        del os.environ[SWITCH]
    lines = out.getvalue().strip().splitlines()
    notes = [json.loads(x) for x in lines[:-1]]
    assert rc == 0 and all("note" in n for n in notes)
    return json.loads(lines[-1]), {n["note"]: n for n in notes}


def shared_cell(benchmark, cell, trace, seed=3):
    """``run_cell``, once per (cell, trace, seed) of a test file."""
    key = (benchmark, cell, trace, seed)
    if key not in _RUNS:
        _RUNS[key] = run_cell(benchmark, cell, trace, seed)
    return _RUNS[key]


def assert_contract(line, trace):
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device", "check"}
    # each number compared beside its limit comes last; none compiled in the window
    assert list(line)[-1] == "check"
    assert line["check"]["window_compiles"] == {"value": 0, "limit": 0, "at": "most"}
    assert all(set(held) == {"value", "limit", "at"} for held in line["check"].values())
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    # no idle share either: a CPU trace has no device plane
    assert "busy_s" not in line["device"] and "breakdown" not in line
    for name, metric in line["metrics"].items():
        assert set(metric) == {"value", "unit"} and metric["unit"] in COUNTS, name
    if not trace:
        assert line["metrics"] == {}  # every end-to-end metric is a time or a rate


def assert_cell_ran(line, notes, trace):
    assert_contract(line, trace)
    assert notes["check"]["ok"] is True
    assert notes["compiles"]["window"]["programs"] == 0
    if trace:
        assert line["metrics"]["entry.window_compiles"]["value"] == 0
        assert line["metrics"]["entry.programs_built"]["value"] > 0
        # the metric and the reader the tests added as new files
        assert line["metrics"]["tiny.units"]["value"] == notes["window"]["units"]
        assert notes["window"]["traced_units"] == 1
