"""``perfbench/readings.py``: the two readings a rollout cell's limits are set
from (PR 53 set ``rollout-reasoning``'s max limit from them on the chip). Here
the control is kept at a size a test run can hold: the reference in the
program's place, over weights rounded to 3 mantissa bits, is NOT correct."""

import json
import os
import subprocess
import sys

import numpy as np

from delta_moe_spec import CELL, write_delta_moe_benchmark
from tiny_spec import REPO


def test_three_mantissa_bits_rounds_matrices_to_a_sixteenth_and_nothing_else():
    import jax.numpy as jnp

    from perfbench.readings import three_mantissa_bits

    drawn = np.random.default_rng(0).normal(0.0, 0.02, (64, 32))
    for dtype in (jnp.bfloat16, jnp.float32):
        x = jnp.asarray(drawn, dtype)
        low = np.asarray(three_mantissa_bits(x), np.float64)
        was = np.asarray(x, np.float64)
        off = np.abs(low - was) / np.abs(was)
        assert 0.01 < off.mean() and off.max() <= 1 / 16 + 1e-9  # half of 2**-3
        # 3 bits: a value is one of 8 steps inside its power of two
        steps = low / 2.0 ** np.floor(np.log2(np.abs(low))) * 8
        assert np.array_equal(steps, np.round(steps))
    norm = jnp.asarray(drawn[0], jnp.bfloat16)  # a vector: a norm, a bias
    assert three_mantissa_bits(norm) is norm
    ids = jnp.zeros((4, 4), jnp.int32)
    assert three_mantissa_bits(ids) is ids


def test_the_control_is_not_correct_and_the_sound_run_is(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "PYTEST_ADDOPTS"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "readings.py"), "--workload", CELL,
         "--seed", "5300000507", "--control", "1",
         "--benchmark", write_delta_moe_benchmark(tmp_path)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    (line,) = [x for x in out.stdout.splitlines() if x.startswith("READING ")]
    said = json.loads(line[len("READING "):])
    assert said["seed"] == 5300000507
    sound, control = said["sound"], said["control"]
    assert sound["ok"] is True and control["ok"] is False
    assert sound["tokens"] == control["tokens"] > 0  # the same rows and tokens
    assert control["mean_abs"] > 3 * sound["mean_abs"]
    assert control["max_abs"] > 3 * sound["max_abs"]
    # it left before the window: no result line
    assert not any(x.startswith('{"correct"') for x in out.stdout.splitlines())
