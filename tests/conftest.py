"""Test configuration: force an 8-device CPU mesh before JAX backends initialize.

Sharding/collective tests (DP/TP/FSDP/ring attention, psum gradient sync) run
on virtual CPU devices so CI needs no TPU (SURVEY §4).
"""

import os
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

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
