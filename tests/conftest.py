"""Test configuration: force an 8-device CPU mesh before JAX backends initialize.

Sharding/collective tests (DP/TP/FSDP/ring attention, psum gradient sync) run
on virtual CPU devices so CI needs no TPU (SURVEY §4).
"""

import atexit
import os
import shutil
import sys
import tempfile

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Hermetic autotune: engines consult the plan DB at construction
# (distrl_llm_tpu/autotune), and a developer's populated
# ~/.cache/distrl_llm_tpu/plan_db.json — or an exported DISTRL_PLAN_DB —
# would silently change engine defaults under the suite. Force the default
# DB to a fresh empty tempdir path (plain assignment, not setdefault);
# tests that exercise the DB pass explicit paths or monkeypatch this.
os.environ["DISTRL_PLAN_DB"] = os.path.join(
    tempfile.mkdtemp(prefix="distrl_test_"), "plan_db.json"
)

# One compilation cache for the run, shared by its workers: the suite's time
# is mostly XLA compiling the same few tiny programs, in every worker that is
# dealt a case of a module and under every closure that builds them anew. The
# process that starts the run (xdist's controller, or the one process of a
# run without it) makes the directory and removes it at exit; a worker finds
# it in the environment it inherits. Plain assignment, never a directory that
# outlives the run: a test must not pass on a program another run compiled.
# Every program is kept, however quickly it compiled and however small.
if "PYTEST_XDIST_WORKER" not in os.environ:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="distrl_test_jaxcache_"
    )
    atexit.register(
        shutil.rmtree, os.environ["JAX_COMPILATION_CACHE_DIR"], ignore_errors=True
    )
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

