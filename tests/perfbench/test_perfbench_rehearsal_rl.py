"""The ``rl_step`` driver end to end on the CPU, on one device and role-split
over four virtual ones, and the benchmark's refusals: a real cell on the CPU, a
cell that asks for more chips than there are, and a directory without the
program print no result and exit non-zero."""

import glob
import json
import os
import shutil
import subprocess
import sys

import pytest

from rehearsal_helpers import assert_cell_ran, shared_cell
from tiny_spec import CELLS, REPO, real_benchmark, write_tiny_benchmark


@pytest.fixture(scope="module")
def tiny_benchmark(tmp_path_factory):
    return write_tiny_benchmark(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("trace", [0, 1])
def test_whole_loop_on_one_device(tiny_benchmark, trace):
    line, notes = shared_cell(tiny_benchmark, "tiny.rl-dense", trace)
    assert_cell_ran(line, notes, trace)
    assert notes["system"]["timeshared"] is True
    inv = notes["invariants"]
    assert inv["versions_in_step"] and inv["every_update_moved_adapter"]
    assert inv["losses_finite"] and inv["steps"] >= 3


def test_four_chip_cell_on_four_virtual_devices(tiny_benchmark):
    """2 actor + 2 learner devices of conftest's virtual CPU devices. One
    traced run (the untraced path is the one-device test's), shared with the
    test below."""
    line, notes = shared_cell(tiny_benchmark, "tiny.rl-split4", 1)
    assert_cell_ran(line, notes, 1)
    assert notes["system"]["timeshared"] is False
    assert len(notes["system"]["rollout_devices"]) == 2
    assert not set(notes["system"]["rollout_devices"]) & set(notes["system"]["learner_devices"])


def test_each_role_holds_its_own_arrays_and_the_push_is_the_learners(tiny_benchmark):
    _, notes = shared_cell(tiny_benchmark, "tiny.rl-split4", 1)
    for held in ("roles_disjoint", "rollout_arrays_on_actors",
                 "learner_arrays_on_learners", "pushed_adapter_equals_learners",
                 "versions_in_step", "every_update_moved_adapter"):
        assert notes["invariants"][held] is True, held


def cpu_env():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


@pytest.mark.parametrize("cell", [w["name"] for w in real_benchmark()["workloads"]])
def test_a_real_cell_refuses_the_cpu(cell):
    """No TPU: non-zero exit, a reason on stderr, and no result line."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"), "--workload", cell,
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=cpu_env(), cwd=REPO,
    )
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"correct"' not in out.stdout and '"metrics"' not in out.stdout


def test_without_the_program_there_is_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    paths, the command exits non-zero and prints no result."""
    bench = real_benchmark()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = cpu_env()
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload", bench["workloads"][0]["name"],
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=tmp_path,
    )
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_a_cell_that_asks_for_more_chips_than_there_are_is_refused(tmp_path):
    path = write_tiny_benchmark(tmp_path)
    env = cpu_env()
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", "tiny.rl-split4", "--seed", "0", "--seconds", "1",
         "--trace", "0", "--benchmark", path],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert out.returncode != 0 and "needs 4 chip(s)" in out.stderr
    assert '"correct"' not in out.stdout


def test_tiny_cells_cover_every_driver_kind():
    kinds = {os.path.basename(p)[:-3]
             for p in glob.glob(os.path.join(REPO, "perfbench", "drivers", "*.py"))}
    tiny = set()
    for traffic, _, _ in CELLS.values():
        with open(os.path.join(REPO, "tests/perfbench/tiny/traffic", f"{traffic}.json")) as f:
            tiny.add(json.load(f)["kind"])
    assert tiny == kinds
