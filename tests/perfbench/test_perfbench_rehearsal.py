"""The ``rollout`` and ``learner`` drivers end to end on the CPU, through the
tiny cells the tests bring (``tiny_spec.py``): the last line parses, has exactly
the contract's keys, and reports no time, rate, utilization or share, since a
CPU run may report counts only. (``test_perfbench_rehearsal_rl.py`` holds the
``rl_step`` driver and the refusals.)

The chip runs are the builder's and the driver's; these hold what a CPU can:
control flow, the correctness check's own logic, the same traffic from the
same seed.
"""

import json

import pytest

from rehearsal_helpers import SWITCH, assert_cell_ran, run_cell, shared_cell
from tiny_spec import write_tiny_benchmark


@pytest.fixture(scope="module")
def tiny_benchmark(tmp_path_factory):
    return write_tiny_benchmark(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell,trace", [
    ("tiny.rollout", 0), ("tiny.rollout", 1), ("tiny.learner", 0), ("tiny.learner", 1),
])
def test_engine_only_and_learner_only_cells_run_end_to_end(tiny_benchmark, cell, trace):
    line, notes = shared_cell(tiny_benchmark, cell, trace)
    assert_cell_ran(line, notes, trace)


def test_the_environment_is_put_back(tiny_benchmark):
    """The harness unsets every DISTRL_* switch for the run and only for it
    (``run_cell`` sets one round the run and finds it again after)."""
    _, notes = shared_cell(tiny_benchmark, "tiny.learner", 0)
    assert SWITCH in notes["run"]["distrl_switches_unset"]


def test_a_learner_cell_without_a_check_reads_the_two_constants(tiny_benchmark):
    """``learner-1k`` states no tolerances of its own, nor does the tiny learner
    cell: their check lines print ``correct.py``'s defaults (a cell that states
    its own: ``test_perfbench_second_family.py``)."""
    from perfbench import correct, spec
    from tiny_spec import real_benchmark

    _, notes = shared_cell(tiny_benchmark, "tiny.learner", 0)
    assert notes["check"]["tol_loss_scaled"] == correct.LOSS_SCALED_TOL == 2e-3
    assert notes["check"]["tol_grad_sign_mass"] == correct.GRAD_SIGN_MASS_TOL == 0.995
    assert "check" not in spec.load_cell(real_benchmark(), "qwen2.5-7b-L14.learner-1k").traffic


def test_same_seed_same_traffic(tiny_benchmark):
    """Two runs of one seed (one of them traced) check the same rows and read
    the same numbers; another seed does not."""
    _, notes_a = shared_cell(tiny_benchmark, "tiny.rollout", 0)
    _, notes_b = shared_cell(tiny_benchmark, "tiny.rollout", 1)
    _, notes_c = shared_cell(tiny_benchmark, "tiny.rollout", 0, seed=10)
    for key in ("rows", "decoded", "mean_abs"):
        assert notes_a["check"][key] == notes_b["check"][key]
    assert notes_a["check"]["mean_abs"] != notes_c["check"]["mean_abs"]


def test_the_generators_three_ways_to_end_an_answer():
    from perfbench import assembly

    assert assembly.eos_ids({"eos": "never"}, 256, seed=1, real_eos=3) == [-1]
    assert assembly.eos_ids({}, 256, seed=1, real_eos=3) == [3]
    drawn = assembly.eos_ids({"eos_rate": 0.25}, 256, seed=1, real_eos=3)
    assert len(set(drawn)) == 64 and all(0 <= i < 256 for i in drawn)
    assert drawn == assembly.eos_ids({"eos_rate": 0.25}, 256, seed=1, real_eos=3)
    assert drawn != assembly.eos_ids({"eos_rate": 0.25}, 256, seed=2, real_eos=3)


def test_a_wrong_answer_is_not_correct(tiny_benchmark, monkeypatch):
    """The check decides ``correct``: with a tolerance no run can meet, the same
    run reports ``correct: false`` (and still prints its line)."""
    from perfbench import correct

    monkeypatch.setattr(correct, "LOGPROB_MEAN_ABS_TOL", 0.0)
    line, notes = run_cell(tiny_benchmark, "tiny.rollout", 0)
    assert line["correct"] is False and notes["check"]["ok"] is False


def test_a_wrong_gradient_is_not_correct(tiny_benchmark, monkeypatch):
    from perfbench import correct

    monkeypatch.setattr(correct, "GRAD_SIGN_MASS_TOL", 1.5)
    line, notes = run_cell(tiny_benchmark, "tiny.learner", 0)
    assert line["correct"] is False and notes["check"]["ok"] is False


# ------------------------------------ each number compared, beside its limit (PR 35)


def test_the_compared_numbers_stand_beside_their_limits():
    from perfbench import correct

    rollout = {"tokens": 96, "mean_abs": 0.04, "max_abs": 0.9, "tol_mean_abs": 0.0654,
               "tol_max_abs": 1.895, "ok": True}
    assert correct.compared(rollout, 0) == {
        "mean_abs": {"value": 0.04, "limit": 0.0654, "at": "most"},
        "max_abs": {"value": 0.9, "limit": 1.895, "at": "most"},
        "window_compiles": {"value": 0, "limit": 0, "at": "most"}}
    learner = {"loss": 4.5, "reference_loss": 4.4, "loss_scaled_err": 3e-5, "grad_sign_mass": 0.9994,
               "elements_moved": 7, "tol_loss_scaled": 2e-3, "tol_grad_sign_mass": 0.995, "ok": True}
    assert correct.compared(learner, 2) == {
        "loss_scaled_err": {"value": 3e-5, "limit": 2e-3, "at": "most"},
        "grad_sign_mass": {"value": 0.9994, "limit": 0.995, "at": "least"},
        "elements_moved": {"value": 7, "limit": 1, "at": "least"},
        "window_compiles": {"value": 2, "limit": 0, "at": "most"}}
    loop = {**rollout, "invariants": {"versions_in_step": True, "losses_finite": False}}
    held = correct.compared(loop, 0)
    assert held["versions_in_step"] == {"value": 1, "limit": 1, "at": "least"}
    assert held["losses_finite"]["value"] == 0 and list(held)[-1] == "window_compiles"
    none = correct.compared({"ok": False, "why": "the warm-up never finished"}, 0)
    assert none["why"] == "the warm-up never finished" and "mean_abs" not in none


def test_the_compared_numbers_are_the_last_lines_of_standard_error(monkeypatch, capsys):
    from perfbench import run

    line = {"correct": False, "attempted": 1, "failed": 0, "metrics": {}, "device": {},
            "check": {"mean_abs": {"value": 0.07, "limit": 0.0654, "at": "most"},
                      "window_compiles": {"value": 0, "limit": 0, "at": "most"},
                      "why": "a reason"}}
    monkeypatch.setattr(run, "run", lambda args, t0, scrubbed: line)
    assert run.main(["--workload", "x", "--seed", "1", "--seconds", "1", "--trace", "0"]) == 0
    said = capsys.readouterr()
    assert json.loads(said.out.strip().splitlines()[-1]) == line
    assert said.err.strip().splitlines()[-3:] == [
        "perfbench: check mean_abs 0.07 (at most 0.0654)",
        "perfbench: check window_compiles 0 (at most 0)",
        "perfbench: check why a reason"]
