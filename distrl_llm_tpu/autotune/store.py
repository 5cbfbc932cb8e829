"""PlanStore: the persistent, versioned on-disk plan DB.

One JSON file maps ``plan_key`` → {plan, measurements, note}. Design rules:

* **Never crash a run.** A missing, corrupt, truncated, or
  schema-incompatible file loads as EMPTY (with a warning) — the caller
  falls back to the static
  defaults exactly as if nothing had ever been tuned, and the next
  ``tools/autotune.py`` run rewrites the file. Pinned by
  tests/test_autotune.py.
* **Atomic writes.** ``save()`` writes a sibling temp file and
  ``os.replace``s it, so a killed tuner can only ever leave the OLD db or
  the NEW db, never a half-written one (which rule 1 would shrug off
  anyway).
* **Override chain.** ``DISTRL_PLAN_DB`` (env) beats the default
  ``~/.cache/distrl_llm_tpu/plan_db.json``; the ``--plan-db`` CLI flag /
  engine ``plan_db=`` kwarg beats both. ``DISTRL_AUTOTUNE=0`` disables
  consultation entirely (resolution returns the static defaults).
"""

from __future__ import annotations

import json
import logging
import os
import tempfile

from distrl_llm_tpu.autotune.plan import ExecutionPlan

log = logging.getLogger(__name__)

#: 2 since the plan lost its two fields that named a paged-kernel variant
#: (PR 47): a database written with them is met as a mismatch and re-tuned
SCHEMA_VERSION = 2

DB_ENV = "DISTRL_PLAN_DB"
ENABLE_ENV = "DISTRL_AUTOTUNE"


def default_db_path() -> str:
    env = os.environ.get(DB_ENV)
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "distrl_llm_tpu", "plan_db.json"
    )


def autotune_enabled() -> bool:
    return os.environ.get(ENABLE_ENV, "1") != "0"


class PlanStore:
    """In-memory view of one plan-DB file; ``load()`` runs at construction.

    ``entries`` maps key → {"plan": dict, "measurements": list, "note": str}.
    """

    def __init__(self, path: str | None = None):
        self.path = path or default_db_path()
        self.entries: dict[str, dict] = {}
        self.load()

    def load(self) -> "PlanStore":
        self.entries = {}
        if not os.path.exists(self.path):
            return self
        try:
            with open(self.path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
            log.warning(
                "plan DB %s is unreadable (%s: %s) — starting empty; "
                "re-run tools/autotune.py to repopulate",
                self.path, type(e).__name__, e,
            )
            return self
        if not isinstance(doc, dict) or doc.get("schema_version") != SCHEMA_VERSION:
            log.warning(
                "plan DB %s has schema_version %r (this build reads %d) — "
                "starting empty; re-run tools/autotune.py to repopulate",
                self.path,
                doc.get("schema_version") if isinstance(doc, dict) else None,
                SCHEMA_VERSION,
            )
            return self
        entries = doc.get("entries")
        if isinstance(entries, dict):
            self.entries = {
                k: v for k, v in entries.items() if isinstance(v, dict)
            }
        return self

    def get(self, key: str) -> ExecutionPlan | None:
        """The stored plan for ``key``, or None. An entry whose plan fails
        validation (hand-edited file, older buggy writer) counts as absent —
        resolution falls back to defaults rather than crashing, the same
        re-tune semantics as a corrupt file."""
        entry = self.entries.get(key)
        if not entry:
            return None
        try:
            return ExecutionPlan.from_dict(entry.get("plan", {}))
        except (ValueError, TypeError) as e:
            log.warning(
                "plan DB entry %s is invalid (%s) — ignoring it; re-run "
                "tools/autotune.py to repopulate", key, e,
            )
            return None

    def put(self, key: str, plan: ExecutionPlan,
            measurements: list[dict] | None = None,
            note: str | None = None) -> None:
        entry: dict = {"plan": plan.to_dict()}
        if measurements:
            entry["measurements"] = list(measurements)
        if note:
            entry["note"] = note
        self.entries[key] = entry

    def save(self) -> str:
        doc = {"schema_version": SCHEMA_VERSION, "entries": self.entries}
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".plan_db_", suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return self.path

    def report(self) -> str:
        """Human-readable table of every stored plan (the CLI's plan
        report)."""
        if not self.entries:
            return f"plan DB {self.path}: empty"
        lines = [f"plan DB {self.path}: {len(self.entries)} entr"
                 f"{'y' if len(self.entries) == 1 else 'ies'}"]
        for key in sorted(self.entries):
            plan = self.get(key)
            if plan is None:
                lines.append(f"  {key}: <invalid entry>")
                continue
            best = None
            for m in self.entries[key].get("measurements", []):
                if isinstance(m, dict) and isinstance(m.get("tok_s"), (int, float)):
                    best = max(best or 0.0, float(m["tok_s"]))
            perf = f"  ({best:.0f} tok/s measured)" if best else ""
            lines.append(
                f"  {key}: path={plan.decode_path} scan_chunk={plan.scan_chunk}"
                f" formulation={plan.cache_read_formulation or 'auto'}"
                f" top_p={plan.top_p_impl or 'auto'}"
                + (f" buckets={list(plan.prompt_buckets)}"
                   if plan.prompt_buckets else "")
                + perf
            )
        return "\n".join(lines)
