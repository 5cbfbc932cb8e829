"""Execution-plan autotuner: measured, cached dispatch policies replace
hard-coded perf defaults.

Public surface:

* :mod:`plan` — the plan space (:class:`ExecutionPlan`), plan keys
  (device kind × model hash × shape bucket), candidate enumeration;
* :mod:`store` — the persistent versioned JSON :class:`PlanStore`
  (env/CLI override, corrupt-file-safe);
* :func:`resolve_plan` — the per-engine lookup (explicit kwargs > stored
  plan > static defaults, telemetered);
* :mod:`microbench` — the in-process measurement harness ``tools/autotune.py``
  drives (imported explicitly, not re-exported: it imports the engines,
  which themselves import :func:`resolve_plan`).
"""

from distrl_llm_tpu.autotune.plan import (
    DEFAULT_PLAN,
    ExecutionPlan,
    TUNABLE_FIELDS,
    candidate_plans,
    canonical_device_kind,
    current_device_kind,
    model_config_hash,
    plan_key,
    rows_bucket,
    shape_bucket,
)
from distrl_llm_tpu.autotune.resolve import ResolvedPlan, resolve_plan
from distrl_llm_tpu.autotune.store import (
    DB_ENV,
    ENABLE_ENV,
    SCHEMA_VERSION,
    PlanStore,
    autotune_enabled,
    default_db_path,
)

__all__ = [
    "DEFAULT_PLAN",
    "DB_ENV",
    "ENABLE_ENV",
    "ExecutionPlan",
    "PlanStore",
    "ResolvedPlan",
    "SCHEMA_VERSION",
    "TUNABLE_FIELDS",
    "autotune_enabled",
    "candidate_plans",
    "canonical_device_kind",
    "current_device_kind",
    "default_db_path",
    "model_config_hash",
    "plan_key",
    "resolve_plan",
    "rows_bucket",
    "shape_bucket",
]
