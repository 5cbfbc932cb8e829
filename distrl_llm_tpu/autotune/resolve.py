"""resolve_plan(): the one lookup every engine makes at build time.

Resolution order, PER FIELD: explicit user kwarg > stored plan (exact
rows-bucket key, then the any-rows key) > static default. An empty DB is a
byte-identical no-op — the engines behave exactly as their pre-autotuner
hard-coded defaults did — and pinned flags keep winning over any DB entry,
so an A/B run can never be silently retuned out from under its config.

Every resolution is recorded through the PR-1 telemetry layer: an
``autotune/plan_resolved`` counter plus ``autotune/plan_db_hit`` /
``autotune/plan_default``, and (when tracing is on) an ``autotune/resolve``
span carrying the key, source, and resolved choices — so a trace shows
which plan a round ran under.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Mapping, NamedTuple

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.autotune.plan import (
    DEFAULT_PLAN,
    ExecutionPlan,
    TUNABLE_FIELDS,
    current_device_kind,
    model_config_hash,
    plan_key,
    shape_bucket,
)
from distrl_llm_tpu.autotune.store import PlanStore, autotune_enabled, default_db_path

log = logging.getLogger(__name__)

# resolution-outcome counters (one owner each; three distinct outcomes so
# an operator can tell a DB miss from autotune being disabled)
AUTOTUNE_PLAN_RESOLVED = "autotune/plan_resolved"
AUTOTUNE_PLAN_DB_HIT = "autotune/plan_db_hit"
AUTOTUNE_PLAN_DEFAULT = "autotune/plan_default"
AUTOTUNE_PLAN_DISABLED = "autotune/plan_disabled"


class ResolvedPlan(NamedTuple):
    plan: ExecutionPlan
    # where the plan substantively came from: "db" (a stored entry was
    # found), "default" (no entry — static defaults), or "disabled"
    # (autotune off: kwarg, or DISTRL_AUTOTUNE=0)
    source: str
    # the DB key consulted (the any-rows form when rows was 0)
    key: str
    # per-field provenance: field name -> "user" | "db" | "default"
    sources: dict[str, str]


# stores are cached per path and reloaded when the file changes — engine
# construction happens in loops (tests, per-bucket builds) and must not
# re-parse an unchanged file every time
_STORES: dict[str, tuple[tuple, PlanStore]] = {}
_STORES_MU = threading.Lock()


def _store_for(path: str) -> PlanStore:
    try:
        st = os.stat(path)
        stamp = (st.st_mtime_ns, st.st_size)
    except OSError:
        stamp = (0, -1)  # missing file: one shared empty-store stamp
    with _STORES_MU:
        cached = _STORES.get(path)
        if cached and cached[0] == stamp:
            return cached[1]
        store = PlanStore(path)
        _STORES[path] = (stamp, store)
        return store


def resolve_plan(
    *,
    model_cfg,
    max_prompt_tokens: int,
    max_new_tokens: int,
    rows: int = 0,
    requested: Mapping[str, object] | None = None,
    db_path: str | None = None,
    device_kind: str | None = None,
    enabled: bool = True,
) -> ResolvedPlan:
    """Resolve the execution plan for one (device, model, geometry).

    ``requested`` holds ONLY the fields the caller pinned explicitly (an
    engine kwarg the user actually passed); those always win. Invalid
    requested values raise — a typo'd explicit kwarg must fail loudly, while an invalid STORED plan only logs and falls
    back (PlanStore.get)."""
    requested = dict(requested or {})
    unknown = set(requested) - set(TUNABLE_FIELDS)
    if unknown:
        raise ValueError(f"unknown plan fields requested: {sorted(unknown)}")

    kind = device_kind or current_device_kind()
    mhash = model_config_hash(model_cfg)
    key = plan_key(kind, mhash, shape_bucket(max_prompt_tokens, max_new_tokens, rows))
    consult = enabled and autotune_enabled()

    with telemetry.span("autotune/resolve", key=key) as sp:
        stored = None
        if consult:
            store = _store_for(db_path or default_db_path())
            stored = store.get(key)
            if stored is None and rows:
                # fall back to the any-rows entry for this geometry
                any_key = plan_key(
                    kind, mhash, shape_bucket(max_prompt_tokens, max_new_tokens, 0)
                )
                stored = store.get(any_key)
                if stored is not None:
                    key = any_key
            if stored is not None and "decode_path" in requested:
                # ``decode_path`` may be a single pin or a tuple of paths
                # the caller can actually host (a refill engine with spec
                # unpinned hosts "paged" OR "speculative" — which one is
                # exactly what the DB decides)
                req_path = requested["decode_path"]
                allowed = (
                    (req_path,) if isinstance(req_path, str) else tuple(req_path)
                )
                if stored.decode_path not in allowed:
                    # the stored plan was measured on a decode path the
                    # caller cannot host (e.g. the tuner's winner was
                    # paged, this is a dense engine): its scan_chunk/top_p
                    # were never measured here, and adopting them would be
                    # exactly the unmeasured-lever regression this
                    # subsystem exists to prevent — treat the entry as a
                    # miss
                    log.debug(
                        "autotune: %s stored plan is for decode_path=%s but "
                        "the caller pinned %s — ignoring the entry",
                        key, stored.decode_path, req_path,
                    )
                    stored = None

        fields: dict = {}
        sources: dict[str, str] = {}
        for name in TUNABLE_FIELDS:
            if name == "decode_path" and not isinstance(
                requested.get(name, ""), str
            ):
                # tuple form: a CONSTRAINT, not a pin — the surviving
                # stored entry names the path that actually runs; with no
                # entry the first element is the caller's default path
                if stored is not None:
                    fields[name] = stored.decode_path
                    sources[name] = "db"
                else:
                    fields[name] = tuple(requested[name])[0]
                    sources[name] = "default"
            elif name in requested:
                fields[name] = requested[name]
                sources[name] = "user"
            elif stored is not None:
                fields[name] = getattr(stored, name)
                sources[name] = "db"
            else:
                fields[name] = getattr(DEFAULT_PLAN, name)
                sources[name] = "default"
        plan = ExecutionPlan(**fields)  # validates; user typos raise here

        source = (
            "db" if stored is not None
            else ("default" if consult else "disabled")
        )
        telemetry.counter_add(AUTOTUNE_PLAN_RESOLVED)
        # three distinct outcomes, three counters: an operator triaging
        # "why didn't my tuned plan apply" must be able to tell a DB miss
        # (re-tune) from autotune being disabled (flip the switch)
        telemetry.counter_add(
            AUTOTUNE_PLAN_DB_HIT if stored is not None
            else (AUTOTUNE_PLAN_DEFAULT if consult
                  else AUTOTUNE_PLAN_DISABLED)
        )
        sp.set(source=source, decode_path=plan.decode_path,
               scan_chunk=plan.scan_chunk,
               formulation=plan.cache_read_formulation,
               top_p_impl=plan.top_p_impl)
    if stored is not None:
        log.debug("autotune: %s resolved from DB: %s", key, plan.to_dict())
    return ResolvedPlan(plan=plan, source=source, key=key, sources=sources)
