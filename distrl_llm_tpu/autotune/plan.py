"""Execution-plan space: the discrete dispatch choices the engines used to
hard-code, as one typed record.

A hard-coded default can engage a lever (scan chunking, the paged path) at
a geometry where the device runs it slower than leaving it off. Every knob
in :class:`ExecutionPlan` is one of those choices — the things a
measurement on the device, not a guess in the source, should pick
(the system-level tuning discipline LlamaRL/RLAX apply to keep RL pipelines
at hardware speed across geometries; PAPERS.md).

Plans are keyed by ``(device kind, model-config hash, shape bucket)`` —
``plan_key`` — because every one of these choices is hardware- and
geometry-dependent: chunked dispatch wins where per-dispatch host overhead
bounds the step and loses where the chip does; the paged path wins when
capacity binds and loses when the grid-step floor does.

``DEFAULT_PLAN`` is deliberately identical to the engines' historical
hard-coded defaults, so resolution against an empty DB is a byte-identical
no-op (the acceptance contract pinned by tests/test_autotune.py).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import re
from dataclasses import dataclass

DECODE_PATHS = ("dense", "paged", "speculative")
FORMULATIONS = (None, "dot", "mulred")
SPEC_DRAFTERS = (None, "ngram", "self")
SPEC_VERIFIES = (None, "fused", "unrolled")
#: continuous-batching admission regimes for the paged refill scheduler
#: (ISSUE 12): "continuous" = prefix-shared prompt chains + lazy per-group
#: prefill feeding freed slots; "batch" = the fixed-episode-batch pin;
#: None = the engine default (fixed batches)
CB_MODES = (None, "batch", "continuous")
#: KV-cache storage formats (ISSUE 15): "int8" = per-token absmax int8 KV
#: (compact-scales Pallas variants on the paged/blocked/verify kernels —
#: ops/paged_native.py); "none" = bf16/f32; None = the engine default
#: ("none"), i.e. an empty DB keeps today's behavior byte-identically.
#: Engines take ``kv_quant=None`` → consult this field; an explicit
#: "none"/"int8" kwarg pins past any stored plan (the decode_scan_chunk
#: convention: default ≠ pin).
KV_FORMATS = (None, "none", "int8")
#: frozen-base weight formats (ISSUE 15): int8/int4 weight-only containers
#: (ops/quant.py) consumed by the fused dequant-matmul kernel
#: (ops/quant_matmul.py). The ENGINE never loads weights, so this field is
#: consumed by the callers that build the base tree (tools/autotune.py
#: measure, microbench) — stored so a tuned
#: "int4 base + int8 KV" serving stack is one DB entry, not a flag recipe.
BASE_QUANTS = (None, "none", "int8", "int4")
#: tiered KV prefix cache (ISSUE 18): "on" = cross-request radix prefix
#: index + host-RAM spill store on the refill pool (paged_engine's
#: prefix_cache kwarg); "off" pins it off; None = the engine default
#: (off), so an empty DB keeps today's behavior byte-identically. Engines
#: take ``prefix_cache=None`` → consult this field; an explicit True/False
#: kwarg pins past any stored plan (the decode_scan_chunk convention).
PREFIX_CACHES = (None, "off", "on")
#: draft lengths beyond this waste verify width faster than they amortize
#: weight reads (and the engine rejects them) — plan validation mirrors it
MAX_SPEC_DRAFT_LEN = 16


@dataclass(frozen=True)
class ExecutionPlan:
    """One resolved set of dispatch choices for an engine + geometry.

    Field defaults ARE the engines' pre-autotuner hard-coded defaults;
    ``None``/empty means "derive exactly as the engine always has" (e.g.
    ``cache_read_formulation=None`` → mulred iff scan_chunk, the invariant
    engine.py documents).
    """

    # which engine class/scheduler serves decode. Engines can't change their
    # own class, so this field is consulted by the CALLERS that pick one
    # (tools/autotune.py reports it) and pinned to the actual class by the
    # engine's own resolution.
    decode_path: str = "dense"
    # K decode steps fused per dispatch via lax.scan; 0 = host loop
    scan_chunk: int = 0
    # decode cache-read formulation (dense engine); None derives from
    # scan_chunk (ops/attention.py::attention_cached has the layout story)
    cache_read_formulation: str | None = None
    # top-p filter implementation (a key of ops.sampling.TOP_P_IMPLS); None
    # derives from SamplingConfig.top_p_exact as always. An explicit
    # SamplingConfig pin (top_p_impl / top_p_exact) still wins at generate().
    top_p_impl: str | None = None
    # prompt length buckets for the dense engine; () = the single
    # max_prompt_tokens bucket (engine-compiled per bucket used)
    prompt_buckets: tuple[int, ...] = ()
    # ---- speculative decoding (decode_path="speculative"; engines only
    # adopt these from the DB when they run the refill scheduler — the
    # slot machinery that hosts speculation). 0/None = the engines'
    # historical defaults (off / k=2 / "ngram" / "fused").
    # draft tokens proposed per verify step
    spec_draft_len: int = 0
    # n-gram lookup size for the "ngram" drafter; 0 = engine default (2)
    spec_ngram_k: int = 0
    # draft source: "ngram" (prompt lookup) | "self" (the policy's own
    # previous LoRA version, off the LoraMailbox swap log)
    spec_drafter: str | None = None
    # verify attention: "fused" (one blocked sweep for the whole draft
    # block — ops/paged_native.py) | "unrolled" (d+1 per-position calls)
    spec_verify: str | None = None
    # continuous-batching admission (refill scheduler only): "continuous"
    # turns on prefix-shared prompt chains + the lazy per-group admission
    # queue (paged_engine's continuous_admission kwarg); "batch" pins the
    # fixed-episode-batch regime; None = engine default (fixed). Engines
    # that can't host it (wave scheduler, no row cap) drop a stored
    # "continuous" entry with a warning, same policy as the spec fields.
    cb_mode: str | None = None
    # KV-cache storage format (ISSUE 15): "int8" per-token-absmax KV /
    # "none" bf16-f32; None = engine default ("none"). Engines built with
    # kv_quant=None adopt this; an explicit engine kwarg pins past it.
    kv_format: str | None = None
    # frozen-base weight format (ISSUE 15): "int8"/"int4" weight-only
    # containers / "none" full-width; None = caller default. Consumed by
    # the weight-loading callers (tools/autotune.py), not the engines.
    base_quant: str | None = None
    # tiered KV prefix cache (ISSUE 18): "on" arms the cross-request radix
    # prefix index + host spill store on the refill pool (requires
    # continuous admission — engines that can't host it drop a stored "on"
    # with a warning); "off" pins it off; None = engine default (off).
    prefix_cache: str | None = None

    def __post_init__(self):
        if self.decode_path not in DECODE_PATHS:
            raise ValueError(
                f"decode_path must be one of {DECODE_PATHS}, got "
                f"{self.decode_path!r}"
            )
        if not isinstance(self.scan_chunk, int) or self.scan_chunk < 0:
            raise ValueError(
                f"scan_chunk must be an int >= 0, got {self.scan_chunk!r}"
            )
        if self.cache_read_formulation not in FORMULATIONS:
            raise ValueError(
                f"cache_read_formulation must be one of {FORMULATIONS}, got "
                f"{self.cache_read_formulation!r}"
            )
        if self.top_p_impl is not None:
            from distrl_llm_tpu.ops.sampling import TOP_P_IMPLS

            if self.top_p_impl not in TOP_P_IMPLS:
                raise ValueError(
                    f"top_p_impl must be one of {sorted(TOP_P_IMPLS)}, got "
                    f"{self.top_p_impl!r}"
                )
        # normalize list → tuple (JSON round-trips through lists)
        object.__setattr__(
            self, "prompt_buckets", tuple(int(b) for b in self.prompt_buckets)
        )
        if any(b <= 0 for b in self.prompt_buckets):
            raise ValueError(
                f"prompt_buckets must be positive, got {self.prompt_buckets}"
            )
        if (
            not isinstance(self.spec_draft_len, int)
            or not 0 <= self.spec_draft_len <= MAX_SPEC_DRAFT_LEN
        ):
            raise ValueError(
                f"spec_draft_len must be an int in [0, {MAX_SPEC_DRAFT_LEN}],"
                f" got {self.spec_draft_len!r}"
            )
        if not isinstance(self.spec_ngram_k, int) or self.spec_ngram_k < 0:
            raise ValueError(
                f"spec_ngram_k must be an int >= 0, got {self.spec_ngram_k!r}"
            )
        if self.spec_drafter not in SPEC_DRAFTERS:
            raise ValueError(
                f"spec_drafter must be one of {SPEC_DRAFTERS}, got "
                f"{self.spec_drafter!r}"
            )
        if self.spec_verify not in SPEC_VERIFIES:
            raise ValueError(
                f"spec_verify must be one of {SPEC_VERIFIES}, got "
                f"{self.spec_verify!r}"
            )
        if self.cb_mode not in CB_MODES:
            raise ValueError(
                f"cb_mode must be one of {CB_MODES}, got {self.cb_mode!r}"
            )
        if self.kv_format not in KV_FORMATS:
            raise ValueError(
                f"kv_format must be one of {KV_FORMATS}, got "
                f"{self.kv_format!r}"
            )
        if self.base_quant not in BASE_QUANTS:
            raise ValueError(
                f"base_quant must be one of {BASE_QUANTS}, got "
                f"{self.base_quant!r}"
            )
        if self.prefix_cache not in PREFIX_CACHES:
            raise ValueError(
                f"prefix_cache must be one of {PREFIX_CACHES}, got "
                f"{self.prefix_cache!r}"
            )

    def replace(self, **kw) -> "ExecutionPlan":
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["prompt_buckets"] = list(self.prompt_buckets)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "ExecutionPlan":
        """Tolerant of unknown keys (a newer writer within the same schema
        version may add fields); missing keys take the defaults."""
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in dict(d).items() if k in fields})


DEFAULT_PLAN = ExecutionPlan()

#: the ExecutionPlan fields a caller may pin explicitly (resolution order:
#: explicit user kwarg > stored plan > DEFAULT_PLAN, per field)
TUNABLE_FIELDS = tuple(f.name for f in dataclasses.fields(ExecutionPlan))


# ------------------------------------------------------------------ plan keys


def model_config_hash(model_cfg) -> str:
    """Stable short hash of a ModelConfig: same architecture → same plans,
    regardless of which named constant or checkpoint produced it."""
    blob = json.dumps(
        dataclasses.asdict(model_cfg), sort_keys=True, default=str
    )
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


# one canonical name per accelerator family: jax reports the same silicon as
# "TPU v5e" / "TPU v5 lite" / "tpu v5 litepod" depending on runtime version,
# and plans measured under one alias must resolve under the others
_KIND_ALIASES = (
    ("v6", "tpu_v6"),
    ("v5p", "tpu_v5p"),
    ("v5e", "tpu_v5e"),
    ("v5 lite", "tpu_v5e"),
    ("v5litepod", "tpu_v5e"),
    ("v4", "tpu_v4"),
    ("v3", "tpu_v3"),
    ("v2", "tpu_v2"),
)


def canonical_device_kind(raw: str) -> str:
    low = raw.lower()
    for sub, canon in _KIND_ALIASES:
        if sub in low:
            return canon
    return re.sub(r"[^a-z0-9]+", "_", low).strip("_") or "unknown"


def current_device_kind() -> str:
    """Canonical kind of this host's first accelerator ("cpu" on CPU hosts).
    "unknown" only where no backend initializes at all; a TPU whose
    ``device_kind`` matches no alias above is an error — plans keyed
    "unknown" for a real chip would be shared by every chip nobody named."""
    import jax

    try:
        dev = jax.devices()[0]
    except RuntimeError:  # no backend at all
        return "unknown"
    if dev.platform != "tpu":
        return dev.platform  # "cpu" / "gpu"
    low = dev.device_kind.lower()
    if not any(sub in low for sub, _ in _KIND_ALIASES):
        raise ValueError(
            f"TPU device_kind {dev.device_kind!r} matches no entry of "
            "autotune.plan._KIND_ALIASES — name it there"
        )
    return canonical_device_kind(dev.device_kind)


def rows_bucket(rows: int) -> int:
    """Concurrent-row count bucketed to the next power of two (480 → 512):
    plans generalize across nearby batch sizes, not across orders of
    magnitude."""
    if rows <= 0:
        return 0
    b = 1
    while b < rows:
        b *= 2
    return b


def shape_bucket(max_prompt_tokens: int, max_new_tokens: int,
                 rows: int = 0) -> str:
    """Geometry key component. ``rows=0`` is the any-row-count bucket —
    engines resolve with it (batch size arrives at generate(), after the
    plan is already baked into compiled programs); tuners that know the row
    count write both the exact and the any-rows entry."""
    base = f"p{max_prompt_tokens}_n{max_new_tokens}"
    rb = rows_bucket(rows)
    return f"{base}_r{rb}" if rb else base


def plan_key(device_kind: str, model_hash: str, bucket: str) -> str:
    return f"{device_kind}/{model_hash}/{bucket}"


# ------------------------------------------------------------ candidate space


def candidate_plans(
    *,
    decode_paths=("dense",),
    scan_chunks=(0, 16),
    formulations=(None,),
    top_p_impls=(None,),
    spec_draft_lens=(0,),
    spec_drafters=(None,),
    spec_verifies=(None,),
    cb_modes=(None,),
    kv_formats=(None,),
    base_quants=(None,),
    prefix_caches=(None,),
) -> list[ExecutionPlan]:
    """Enumerate a candidate space for the tuner (cartesian product, with
    the always-meaningless combos dropped: a formulation override without a
    dense path, a scan_chunk of 1 — scan-of-one has no fusion benefit and
    the engines refuse to report it as chunked, spec knobs
    anywhere but the speculative path, a cb_mode on the dense path — the
    admission scheduler is paged-refill machinery — and a speculative path
    with no draft length, which is just the paged path wearing a costume).
    ``kv_formats``/``base_quants`` (ISSUE 15) apply on every path: the
    dense engine hosts the int8 scale-carrying cache and the paged/
    speculative kernels their compact-scales variants, and the quantized
    base rides any decode path."""
    out = []
    for path in decode_paths:
        for chunk in scan_chunks:
            if chunk == 1:
                continue
            for form in formulations:
                if form is not None and path != "dense":
                    continue
                for sd in spec_draft_lens:
                    if (sd > 0) != (path == "speculative"):
                        continue
                    for drafter in spec_drafters:
                        if drafter is not None and not sd:
                            continue
                        for sv in spec_verifies:
                            if sv is not None and not sd:
                                continue
                            for cb in cb_modes:
                                if cb is not None and path == "dense":
                                    continue
                                for pc in prefix_caches:
                                    # the radix cache rides the
                                    # continuous-admission chain
                                    # machinery (ISSUE 18)
                                    if pc == "on" and cb != "continuous":
                                        continue
                                    if pc is not None and path == "dense":
                                        continue
                                    for kvf in kv_formats:
                                        for bq in base_quants:
                                            for tp in top_p_impls:
                                                out.append(ExecutionPlan(
                                                    decode_path=path,
                                                    scan_chunk=chunk,
                                                    cache_read_formulation=form,
                                                    top_p_impl=tp,
                                                    spec_draft_len=sd,
                                                    spec_drafter=drafter,
                                                    spec_verify=sv,
                                                    cb_mode=cb,
                                                    kv_format=kvf,
                                                    base_quant=bq,
                                                    prefix_cache=pc,
                                                ))
    return out
