"""The next PR appends, and may edit no file that is here: a copy of the real
``BENCHMARK.json`` with a made-up configuration, cell and per-layer metric
APPENDED, as a ``model_config`` PR appends its own, passes every structural
test under ``tests/perfbench/`` that does not open the made-up files.

That is the proof that no test here holds a position or a count of the real
benchmark's lists (``perfbench/README.md``, "Adding things"): a test a PR
brings holds what that PR added BY NAME.
"""

import json
import os
import re
import subprocess
import sys

from latent_moe_spec import JOINED
from tiny_spec import REPO, real_benchmark

CONFIG, TRAFFIC, METRIC = "made_up-L4", "made_up-traffic", "made_up.share"
CELL = f"{CONFIG}.{TRAFFIC}"
#: of the thirteen that PR 35 declared: what a later cell that shares
#: ``moe.py``'s router, dispatch and products, or a linear mixer, appends to
SHARED_WITH_A_FAMILY = (
    "model.moe_router_share", "model.moe_dispatch_share", "model.moe_experts_share",
    "kernel.moe_experts_roofline", "engine.expert_load_imbalance", "model.linear_attn_share",
)
#: every structural test: the contract, and what PRs 24, 29 and 33 held of the
#: real benchmark. (The others run cells, or read traces and counts.)
STRUCTURAL = (
    "test_perfbench_spec.py",
    "test_perfbench_trace_scopes.py::test_every_new_metric_resolves_from_its_files_and_is_in_the_benchmark",
    "test_perfbench_trace_scopes.py::test_the_benchmarks_vocabulary_is_the_programs",
    "test_perfbench_rehearsal_sala.py::test_the_real_cell_is_the_issues_letter_for_letter",
    "test_perfbench_rehearsal_sala.py::test_this_familys_metric_has_its_file_and_its_reader",
    "test_perfbench_rehearsal_sala.py::test_the_rehearsal_benchmark_names_only_new_files",
    "test_perfbench_rehearsal_latent_moe.py::test_the_real_cell_is_the_issues_letter_for_letter",
    "test_perfbench_rehearsal_latent_moe.py::test_the_benchmark_gained_one_configuration_and_one_cell_at_the_end",
    "test_perfbench_rehearsal_latent_moe.py::test_this_familys_metric_has_its_file_and_its_reader",
    "test_perfbench_rehearsal_latent_moe.py::test_the_rehearsal_benchmark_names_only_new_files",
)
#: the tests that open a made-up entry's files (its parametrised case), and the
#: one that sets the files on disk against the entries
OPENS_THE_FILES = "not made_up and not test_no_file_waits_for_a_cell"
#: pytest in a process of its own, with ``tiny_spec.real_benchmark`` (the one
#: door every test reads the real benchmark through) handing out the copy
DRIVER = """
import json, sys
sys.path.insert(0, sys.argv[1])
import pytest, tiny_spec
with open(sys.argv[2], encoding="utf-8") as f:
    held = f.read()
tiny_spec.real_benchmark = lambda: json.loads(held)
sys.exit(pytest.main(sys.argv[3:]))
"""


def appended() -> dict:
    bench = real_benchmark()
    bench["configs"].append({
        "name": CONFIG, "source": "https://huggingface.co/made/up/blob/main/config.json",
        "file": f"perfbench/configs/{CONFIG}.json",
        "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
        "why": "one of 8 chips that share each layer: what a later PR may bring"})
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1, "why": "made up"})
    # it joins what its kind of cell reports, and (as a configuration with
    # routed experts or a linear mixer would) a family's own metric that an
    # accepted cell alone listed until now
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric["name"] in (*JOINED, *SHARED_WITH_A_FAMILY):
            metric["workloads"].append(CELL)
    bench["per_layer"].append({
        "name": METRIC, "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "model forward", "moves": "rollout_tok_s", "workloads": [CELL]})
    return bench


def run_structural(bench: dict, path, tests, *more) -> subprocess.CompletedProcess:
    """``tests`` in a pytest of their own over ``bench`` as the real benchmark."""
    path.write_text(json.dumps(bench), encoding="utf-8")
    here = os.path.join(REPO, "tests", "perfbench")
    env = {k: v for k, v in os.environ.items() if k != "PYTEST_ADDOPTS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-c", DRIVER, here, str(path), *(os.path.join(here, t) for t in tests),
         "-q", "-p", "no:cacheprovider", "-p", "no:xdist", *more],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)


def test_a_benchmark_with_entries_appended_passes_every_structural_test(tmp_path):
    out = run_structural(appended(), tmp_path / "BENCHMARK.appended.json", STRUCTURAL,
                         "-k", OPENS_THE_FILES)
    said = out.stdout[-3000:] + out.stderr[-2000:]
    assert out.returncode == 0, said
    summary = re.search(r"(\d+) passed, (\d+) deselected", out.stdout)
    assert summary is not None, said
    # the copy was read: the made-up configuration's two cases, the cell's and
    # the metric's were collected and left out, with the files-on-disk test
    assert int(summary.group(2)) == 5 and int(summary.group(1)) >= 100, said


def test_the_same_tests_do_see_an_entry_put_in_the_middle(tmp_path):
    """The control: the same run over a copy whose made-up metric stands
    INSIDE PR 24's block fails, so the run above does read the copy and the
    block is still held contiguous and in order."""
    bench = appended()
    names = [m["name"] for m in bench["per_layer"]]
    bench["per_layer"].insert(names.index("model.mlp_share"), bench["per_layer"].pop())
    out = run_structural(bench, tmp_path / "BENCHMARK.middle.json", STRUCTURAL[1:2])
    assert out.returncode == 1 and "1 failed" in out.stdout, out.stdout[-2000:]
