"""Unified telemetry: span tracing, perf counters, and a Perfetto-exportable
step timeline across the driver, engines, and workers.

The reference's only observability is inline ``time.time()`` pairs (SURVEY §5);
this module gives every layer the same three instruments:

* **Spans** — ``with span("engine/prefill", rows=b): ...`` appends one dict
  per exit (~dict-append cost, thread-aware via the recording thread's id,
  nestable for free: Chrome-trace "X" complete events nest by interval).
  When tracing is disabled ``span()`` returns a shared no-op singleton, so
  the instrumented hot paths cost one module-global read.
* **Counters / gauges / histograms** — a process-global registry whose
  ``metrics_snapshot()`` the trainer merges into the existing ``MetricsSink``
  contract each step (``pool/occupancy``, ``cp/rpc_dispatch_ms_*`` …).
  Gauges additionally emit Chrome-trace counter events ("C" phase) while
  tracing is on, so Perfetto renders them as time-series tracks.
* **Cross-process propagation** — workers record spans locally (enable with
  ``DISTRL_TRACE=1`` or ``worker_main --trace``) and the control plane ships
  a compact blob back piggybacked on RPC responses; ``ingest_remote`` merges
  it into the driver's trace under a per-worker track (pid) so one exported
  JSON shows the driver, its engines, and every worker on aligned timelines
  (span timestamps are wall-clock ``time.time_ns``, shared across processes
  on a host; cross-host tracks are still self-consistent).

``export_chrome_trace`` writes the Chrome trace-event JSON that both
``chrome://tracing`` and https://ui.perfetto.dev load directly;
``tools/trace_report.py`` prints a per-phase/per-worker breakdown with
tok/s and MFU from the same file.
"""

from __future__ import annotations

import bisect
import collections
import gc
import json
import os
import threading
import time
from typing import Any, Mapping

_DRIVER_PID = 1  # local-process track; remote tracks are assigned from 100
_REMOTE_PID0 = 100

# Fixed histogram bucket ladder (upper bounds, inclusive — Prometheus `le`
# semantics) shared by every registry histogram: log-spaced to cover
# sub-ms RPC latencies through minute-scale e2e serving latencies, plus
# the small-integer histograms (rollout/staleness, spec emit counts) in
# the bottom rungs. Cumulative per-bucket counts ride observe_snapshot()
# so the obs endpoint can expose REAL Prometheus histogram types with
# `_bucket{le=...}` lines — scrapable percentiles via histogram_quantile —
# instead of summary stats only (ISSUE 13 satellite).
HIST_BUCKET_BOUNDS = (
    0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0, 5000.0, 10000.0, 30000.0, 60000.0,
)

# ------------------------------------------------------ the timeline's names
#
# One fixed vocabulary for the step timeline, defined here so that one file
# lists every name an operator can meet in a profile or an exported trace.
# Host spans (``span(NAME)``) land in the Chrome JSON and, while a
# ``jax.profiler`` trace runs, on its host plane; device scopes
# (``jax.named_scope(NAME)``) are trace-time metadata on the HLO the jitted
# programs lower to (``op_name``), which the profiler's device lines and
# ``perfbench/trace_scopes.py`` read back. A scope changes no program (JAX
# strips locations from the persistent cache's key), with the one exception
# noted at the kernel names below. README "The timeline's names" has the
# table; PERF.md §3 says which metric reads which.

# engine rounds, host side (engine/engine.py, engine/paged_engine.py)
ENGINE_SETUP = "engine/setup"  # entry to the first dispatch
ENGINE_PREFILL = "engine/prefill"
ENGINE_DECODE = "engine/decode"
ENGINE_REFILL_DECODE = "engine/refill_decode"
ENGINE_ADMIT = "engine/admit"  # host: one admission pass; device: the admit program
ENGINE_GRANT = "engine/grant"  # a budgeted pool's page-grant pass
ENGINE_SNAPSHOT_WAIT = "engine/snapshot_wait"  # the host waits on the device here
# the boundary's own launches: the copies of the done flags (and, in the refill
# loop, the lengths) and their ``copy_to_host_async()``; a fused snapshot that
# rode inside the chunk's dispatch records its two ``copy_to_host_async`` calls
# alone. Once a boundary. ``engine.snapshot_launch_ms`` reads it
ENGINE_SNAPSHOT_LAUNCH = "engine/snapshot_launch"
ENGINE_PREEMPT = "engine/preempt"
ENGINE_READBACK = "engine/readback"  # the round's final blocking reads
# one span round every launch of a decode step program from a host loop (args
# ``step``: the first step it runs, ``steps``: 1, or k for a scanned chunk).
# The one name recorded per STEP; every other span is per host boundary
ENGINE_DISPATCH = "engine/dispatch"
# gauges of the round so far, filed a wave by ``engine.accumulate_round_stats``
# with tracing on or off (``engine.RoundHostAccount`` takes the clock per
# host boundary): % of the decode loop's wall the host was NOT blocked on the
# device; the longest interval between two returns from the snapshot wait;
# the part of THAT interval outside the wait
ENGINE_HOST_BUSY_SHARE = "engine/host_busy_share"
ENGINE_SLOWEST_BOUNDARY_MS = "engine/slowest_boundary_ms"
ENGINE_SLOWEST_BOUNDARY_HOST_MS = "engine/slowest_boundary_host_ms"
# every boundary of the round, filed with tracing on or off: each interval
# between two returns from the snapshot wait into a histogram (the obs endpoint
# serves its buckets, a sink its p50 / p90 / max), the round's median interval
# as a gauge, and a counter of the boundaries ``engine.stalled_boundaries``
# counts: unmarked ones longer than ``STALL_FACTOR`` x their round's median
ENGINE_BOUNDARY_MS = "engine/boundary_ms"                # histogram
ENGINE_BOUNDARY_MEDIAN_MS = "engine/boundary_median_ms"  # gauge
ENGINE_STALLED_BOUNDARIES = "engine/stalled_boundaries"  # counter
# a full (generation 2) collection of Python's cyclic collector: a span while
# tracing is on (``collected``, ``uncollectable``), so that an idle gap under
# one is named ``host/gc``; its milliseconds in a counter always. A round's
# ``gc_full_s`` is the counter's gain over the round
HOST_GC = "host/gc"
HOST_GC_FULL_MS = "host/gc_full_ms"  # counter
# the decode view of the frozen base (models/transformer.py::decode_view):
# a counter, 1 each time an engine builds one (once a base: the memo is
# ``engine.LoraMailbox._decode_params``), and a gauge, the bytes of the
# stacked leaves the present view holds a second time, one array a layer
# (0 where the device had no room and the view was left out). No metric reads
# them
ENGINE_DECODE_VIEW_BUILDS = "engine/decode_view_builds"
ENGINE_DECODE_VIEW_BYTES = "engine/decode_view_bytes"
# sliding-window layers: per live row, window layer and decode step, the keys
# attended (the ring: min(context, window)) and the keys a full-attention layer
# would attend (the context), IN UNITS OF 128 KEYS, rounded up (a round's sum
# in keys passes an int32). Carried in the decode state (``mixer["window_stats"]``)
# and filed at readback; attended / visible is what the window saves
ENGINE_WINDOW_PAGES_ATTENDED = "engine/window_pages_attended"  # counter
ENGINE_WINDOW_PAGES_VISIBLE = "engine/window_pages_visible"    # counter
# what ONE more token of context costs a slot, in bytes, summed over the layers
# that keep pages and read off the pools' own shapes, K's and V's apart (a
# window layer's ring costs a token nothing: ``engine/slot_state_bytes`` holds
# what the slots cost at ANY context). Filed beside that gauge when a round's
# decode state is built (engine/paged_engine.py::_file_slot_state), by every
# paged family, tracing on or off, nothing fetched
ENGINE_CACHE_TOKEN_BYTES = "engine/cache_token_bytes"          # gauge
# a looped model (ouro): layer applications a round, weight layers x passes x
# decode steps plus the same for the prefill forward, host arithmetic like
# ``ops/paged_grid_steps``; a model that runs its layers once files nothing
ENGINE_LOOP_LAYER_STEPS = "engine/loop_layer_steps"            # counter
# a looped model's exit gates, over a round's decoded tokens: the mean of
# ``sum_u (u + 1) p_u``, the pass at which the published exit distribution
# would stop on average, between 1 and ``loop_steps`` (at the published
# threshold of 1 every pass runs whatever it reads). Summed on the device in
# the decode state (``mixer["exit_stats"]``: the sum and the tokens) and filed
# at readback: no extra fetch. A model that runs its layers once files 1.0
ENGINE_EXIT_STEP_MEAN = "engine/exit_step_mean"                # gauge
# a learned index over tokens (glm_moe_dsa): per live row, layer and decode
# step, the tokens attended (min(context, index_topk)) and the tokens latent
# attention without the index would attend (the context), in the same units of
# 128 rounded up. Carried in ``mixer["index_stats"]`` and filed at readback;
# 100% the day a layer silently attends everything
ENGINE_INDEX_TOKENS_ATTENDED = "engine/index_tokens_attended"  # counter
ENGINE_INDEX_TOKENS_VISIBLE = "engine/index_tokens_visible"    # counter
# routed experts' grouped form (models/moe.py::routed_experts: a prefill
# segment's token-rows): blocks whose products ran (those that hold a pair of
# an expert held here) and blocks the scan stepped over, summed over expert
# layers and calls. Carried in ``mixer["moe_blocks"]`` through the prefill's
# segments and the decode steps (whose dense form lays none) and filed at
# readback; run / laid is the share of the laid blocks that did work
ENGINE_MOE_BLOCKS_RUN = "engine/moe_blocks_run"    # counter
ENGINE_MOE_BLOCKS_LAID = "engine/moe_blocks_laid"  # counter
# what the learner's rematerialised layer scan keeps for the backward pass
# (learner/remat.py), filed when a train step first meets a batch shape: how
# many of the five named products of the frozen weights (q, k, v, the MLP's
# gate and up; 0 where the device had no room or reports no memory), and the
# bytes they take a micro-batch over all layers. ``learner.kept_share`` reads
# the bytes
LEARNER_KEPT_PRODUCTS = "learner/kept_products"
LEARNER_KEPT_PRODUCT_BYTES = "learner/kept_product_bytes"
# trainer, host side, nested in the PhaseSpans phases (driver/<phase>)
DRIVER_SHAPING = "driver/shaping"
DRIVER_UPDATE_BATCH = "driver/update/batch"
DRIVER_UPDATE_STEP = "driver/update/step"
DRIVER_PUSH = "driver/push"
DRIVER_LOG = "driver/log"
# one span per program JAX builds while tracing is on: compile/<fun_name>
COMPILE_PREFIX = "compile"
# device scopes: the decoder (models/transformer.py)
MODEL_EMBED = "model/embed"
MODEL_ATTN_PROJ = "model/attn_proj"
MODEL_ATTN_CORE = "model/attn_core"
MODEL_MLP = "model/mlp"
MODEL_HEAD = "model/head"
# the mixers of a model whose layers differ in kind (MiniCPM-SALA): the linear
# attention (chunked or one step), the block-sparse layer's choice of blocks,
# and its attention over the chosen blocks
MODEL_LINEAR_ATTN = "model/linear_attn"
MODEL_SPARSE_SELECT = "model/sparse_select"
MODEL_SPARSE_ATTN = "model/sparse_attn"
# routed experts (models/moe.py): scores, top-k and weights; the sort of
# (token, expert) pairs, the gather of rows and the combine; the grouped
# products. Absorbed latent attention in decode (ops/latent_attention.py); the
# expanded form is ``model/attn_core``
MODEL_MOE_ROUTER = "model/moe_router"
MODEL_MOE_DISPATCH = "model/moe_dispatch"
MODEL_MOE_EXPERTS = "model/moe_experts"
MODEL_LATENT_ATTN = "model/latent_attn"
# the experts that compute nothing (longcat_flash, models/moe.py::zero_part):
# the sum of a token's weights on them times the layer's normed input
MODEL_MOE_ZERO = "model/moe_zero"
# a looped model (ouro, models/transformer.py): what stands BETWEEN passes,
# the final norm that closes each pass and the exit gate's product and
# distribution; the layers of every pass stay under the names above
MODEL_EXIT_GATE = "model/exit_gate"
# a gated delta-rule model (solar_open2, ops/delta_attention.py): the
# recurrence in both forms with its l2norm, decay and beta; the short
# convolutions and their tail's update; both mixers' output gates and the
# delta-rule layers' head-wise norm. q, k, v, o stay ``model/attn_proj``
MODEL_DELTA_ATTN = "model/delta_attn"
MODEL_SHORT_CONV = "model/short_conv"
MODEL_ATTN_GATE = "model/attn_gate"
# power retention (brumby, ops/power_retention.py): the symmetric second power
# of q and k, the one-token step, the chunked form, the normaliser, the
# log-decay's logsigmoid and cumulation, and RoPE. q, k, v, o and the decay's
# projection stay ``model/attn_proj``
MODEL_POWER_ATTN = "model/power_attn"
# state-space layers (jamba, ops/selective_scan.py): the three inner norms,
# softplus, the discretisation, the one-token step or the chunked scan, the D
# skip and the silu(z) gate. W_in, W_x, W_dt and W_out stay ``model/attn_proj``;
# the convolution with its bias and SiLU is ``model/short_conv``. A Mamba-2
# layer (nemotron_h, ops/ssd.py) stands under the same names: softplus, the
# one-token step or the chunked matrix form, the D skip and the gated group
# norm here; W_in and W_out ``model/attn_proj``; the convolution over x, B and
# C ``model/short_conv``
MODEL_SSM = "model/ssm"
# sliding-window layers (exaone_moe, models/hybrid.py::_window_mix): a window
# layer's attention in all three modes (the band over a whole row, over a
# prefill segment and the ring before it, one token over a slot's ring), RoPE
# included. q, k, v, o stay ``model/attn_proj``, the ring's write
# ``engine/kv_write``
MODEL_WINDOW_ATTN = "model/window_attn"
# a learned index over tokens beside latent attention (glm_moe_dsa,
# ops/token_index.py through models/hybrid.py::_latent_block): the index's
# three projections, its key's LayerNorm, RoPE and the scores of a query's
# index heads over the cached index keys; the choice of the top tokens; and,
# in decode, the gather of the chosen latent rows and absorbed attention over
# them. The choice sorts nothing: the k-th largest score is found by counting
# over the scores' ordered bits, a decode row's positions are read off the
# mask by rank within blocks (the counter ``ops/index_counted_choices`` below
# says how often a round chose so). A prefill segment's masked folds stay
# ``model/attn_core``, the index key's write ``engine/kv_write``
MODEL_INDEX_SCORE = "model/index_score"
MODEL_INDEX_SELECT = "model/index_select"
MODEL_INDEXED_ATTN = "model/indexed_attn"
# compressed convolutional attention (zaya, models/hybrid.py::_cca_mix): what
# the mixer does before and after its page walk in all three modes: the two
# causal convolutions over [q~ | k~], the q-k mean, the per-head norm and the
# keys' temperature, RoPE, the value taken a token late, and the tail's
# update. q, k, v, o stay ``model/attn_proj``, the page write
# ``engine/kv_write``, the walk ``kernel/paged_attention`` / ``model/attn_core``
MODEL_CCA_MIX = "model/cca_mix"
# device scopes: the engines' step programs
ENGINE_KV_WRITE = "engine/kv_write"
ENGINE_SAMPLE = "engine/sample"
ENGINE_BOOKKEEPING = "engine/bookkeeping"
# device scopes: the Pallas call sites (ops/), each OUTSIDE the kernel's own
# jit. Round an inline pallas_call a scope is not metadata-only: the TPU
# compiler names the custom call after the innermost scope and the kernel
# body's serialized MLIR carries the name stack, so the fused sampler
# (ops/sampling.py, traced inline) has no scope until the benchmark stops
# finding it by the name ``%_unknown_`` (ROADMAP S0b)
KERNEL_PAGED_ATTENTION = "kernel/paged_attention"
KERNEL_QUANT_MATMUL = "kernel/quant_matmul"
KERNEL_FLASH = "kernel/flash"
KERNEL_SPLASH = "kernel/splash"
# counter: a round's delta-rule layer-steps that went through the one-token
# Mosaic kernel (ops/delta_attention.py::delta_step_kernel): layers x decode
# steps where ``delta_step`` chose it, 0 where it took the plain form (a CPU,
# small heads). Filed by the paged engine next to ``ops/paged_grid_steps``; no
# metric reads it
OPS_DELTA_KERNEL_STEPS = "ops/delta_kernel_steps"
# counter: a round's sparse layer-steps whose attention over the chosen pages
# ran as the Mosaic launch (ops/sparse_attention.py::attend_pages_kernel):
# layers x decode steps where ``sparse_decode`` chose it, 0 where it took the
# plain form (a CPU, small heads, quantized pages). Filed beside the counter
# above; no metric reads it
OPS_SPARSE_KERNEL_STEPS = "ops/sparse_kernel_steps"
# counter: a round's power-retention layer-steps that went through the one-token
# Mosaic kernel (ops/power_retention.py::power_step_kernel): layers x decode
# steps where ``power_step`` chose it, 0 where it took the plain form (a CPU,
# small heads, a bf16 state). Filed beside the two counters above; no metric
# reads it
OPS_POWER_KERNEL_STEPS = "ops/power_kernel_steps"
# counter: a round's folds of a block of keys into a latent-attention prefill
# segment's running softmax that ran as the Mosaic kernel
# (ops/latent_attention.py::expanded_fold_kernel): latent layers x the
# prefill's folds (segment j makes j + 1) where ``expanded_segment`` chose it
# (under a learned index's choice too), 0 where it took the XLA form (a CPU,
# small heads, float32). Filed beside the three counters above; no metric
# reads it
OPS_LATENT_KERNEL_FOLDS = "ops/latent_kernel_folds"
# counter: the same for the layers whose prefill folds the rows' K/V pages
# (models/hybrid.py::_segment_softmax, the same ``expanded_fold_kernel`` handed
# a GQA layer's head layout): "softmax" and "cca" layers x the prefill's folds
# where ``expanded_segment`` chose the kernel, 0 where it took the XLA form.
# Filed beside the counter above; no metric reads it
OPS_SOFTMAX_KERNEL_FOLDS = "ops/softmax_kernel_folds"
# counter: a round's decode layer-steps whose attention over latent pages ran
# as the one Mosaic launch over the pool where it lies
# (ops/latent_attention.py::absorbed_decode_kernel): latent layers x decode
# steps where ``absorbed_decode`` chose it (under a learned index's choice too,
# where the choice walks the pages: models/hybrid.py::_choice_walks_pages), 0
# where the XLA walk or the gather of the chosen rows ran (a CPU, small rows).
# Filed beside the two counters above; no metric reads it
OPS_LATENT_DECODE_LAUNCHES = "ops/latent_decode_launches"
# counter: a round's choices of a learned index that ran by counting
# (ops/token_index.py::kth_largest, no sort): layers x the decode steps whose
# row sees more than ``index_topk`` columns, plus layers x the prefill's
# segments that end past ``index_topk`` (the earlier ones choose all they see
# and count nothing). Host arithmetic a round, tracing on or off, nothing
# fetched; a model without an index files nothing. Filed beside the four
# counters above; no metric reads it
OPS_INDEX_COUNTED_CHOICES = "ops/index_counted_choices"
# device scopes: the train step (learner/). JAX writes the rest of the path:
# ``transpose(jvp(learner/loss))`` is the backward pass and
# ``rematted_computation`` under it the recomputed forward
LEARNER_LOSS = "learner/loss"
LEARNER_LOSS_LOGPROB = "learner/loss/logprob"
LEARNER_GRAD_ACCUM = "learner/grad_accum"
LEARNER_OPTIMIZER = "learner/optimizer"
LEARNER_OPTIMIZER_CODEC = "learner/optimizer/codec"

#: every ``jax.named_scope`` name the jitted programs carry
SCOPE_NAMES = (
    MODEL_EMBED, MODEL_ATTN_PROJ, MODEL_ATTN_CORE, MODEL_MLP, MODEL_HEAD,
    ENGINE_KV_WRITE, ENGINE_SAMPLE, ENGINE_BOOKKEEPING, ENGINE_ADMIT,
    KERNEL_PAGED_ATTENTION, KERNEL_QUANT_MATMUL, KERNEL_FLASH, KERNEL_SPLASH,
    LEARNER_LOSS, LEARNER_LOSS_LOGPROB, LEARNER_GRAD_ACCUM, LEARNER_OPTIMIZER,
    LEARNER_OPTIMIZER_CODEC,
    MODEL_LINEAR_ATTN, MODEL_SPARSE_SELECT, MODEL_SPARSE_ATTN,
    MODEL_MOE_ROUTER, MODEL_MOE_DISPATCH, MODEL_MOE_EXPERTS, MODEL_LATENT_ATTN,
    MODEL_DELTA_ATTN, MODEL_SHORT_CONV, MODEL_ATTN_GATE, MODEL_POWER_ATTN,
    MODEL_SSM, MODEL_WINDOW_ATTN,
    MODEL_INDEX_SCORE, MODEL_INDEX_SELECT, MODEL_INDEXED_ATTN, MODEL_CCA_MIX,
    MODEL_MOE_ZERO, MODEL_EXIT_GATE,
)


class _State:
    """Process-global telemetry state. A plain class (not a dataclass) so
    the hot-path read ``_STATE.enabled`` is one attribute load."""

    def __init__(self):
        self.enabled = os.environ.get("DISTRL_TRACE", "0") == "1"
        self.lock = threading.Lock()
        # trace events: appended lock-free (list.append is atomic under the
        # GIL); drained/exported under the lock
        self.events: list[dict] = []
        self.thread_names: dict[int, str] = {}
        self.remote_tracks: dict[str, int] = {}  # track label -> pid
        self.remote_threads: dict[tuple[int, int], str] = {}
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, float] = {}
        # weighted observations: (value, count) per hist_observe call
        self.hists: dict[str, list[tuple[float, int]]] = {}
        self.touched: set[str] = set()  # series with data since last snapshot
        # --- continuous-observability state (ISSUE 8) -------------------
        # cumulative counter totals: metrics_snapshot pops the per-step
        # delta above, but a live scrape endpoint (obs.py) needs monotonic
        # totals (Prometheus counter semantics) — kept here, never reset
        self.counters_total: dict[str, float] = {}
        # cumulative histogram summaries: [count, weighted sum, max]
        self.hist_totals: dict[str, list[float]] = {}
        # cumulative per-bucket counts aligned to HIST_BUCKET_BOUNDS, one
        # trailing overflow slot (> last bound); never reset — the live
        # endpoint renders them as Prometheus histogram buckets
        self.hist_buckets: dict[str, list[float]] = {}
        # obs export: when on, workers piggyback a registry snapshot on
        # control-plane results (the way span blobs already ride home)
        self.obs_export = os.environ.get("DISTRL_OBS", "0") == "1"
        # driver-side fleet table: track label -> last piggybacked worker
        # registry snapshot (+ receive timestamp), fed by ingest_remote
        self.remote_metrics: dict[str, dict] = {}
        # --- causal trace context (ISSUE 10) ----------------------------
        # one trace id per process run: driver dispatch/weight frames carry
        # it (with a per-frame dispatch id) so worker-side spans attach to
        # the driver dispatch that caused them instead of floating free
        self.trace_id = f"{os.getpid():x}-{time.time_ns() & 0xFFFFFFFFFF:x}"
        self.dispatch_seq = 0
        # base track -> pid of the FIRST incarnation seen: a restarted
        # worker (new pid) gets a DISTINCT trace track instead of aliasing
        # onto its predecessor's timeline (the killed-and-restarted merge
        # bug trace_report used to inherit)
        self.remote_incarnations: dict[str, Any] = {}
        # --- the round ledger (ISSUE 56) ---------------------------------
        # the last ROUND_RING rounds' records, as the engines filed them
        self.rounds: collections.deque = collections.deque(maxlen=ROUND_RING)
        self.rounds_filed = 0
        # full collections: milliseconds so far, and the part not yet in the
        # registry's counter (the collector's callback may run under
        # ``lock``, so it takes no lock: the snapshots fold the part in)
        self.gc_full_ms = 0.0
        self.gc_unfiled_ms = 0.0
        self.gc_open: tuple | None = None  # (t0 ns, its TraceAnnotation)


#: rounds the ledger keeps
ROUND_RING = 64

_STATE = _State()


def configure(enabled: bool) -> None:
    """Turn span recording on/off (counters/gauges always record — they are
    the MetricsSink feed and cost a dict write). Turning it on also starts
    the process's compile listener, so every program JAX builds while
    tracing is on lands on the timeline as a ``compile/<fun_name>`` span."""
    _STATE.enabled = enabled
    if enabled:
        _ensure_compile_spans()
        _ensure_gc_account()


def enabled() -> bool:
    return _STATE.enabled


def reset() -> None:
    """Drop all recorded telemetry and re-read the env enable (tests)."""
    global _STATE, _PHASE_HOOK
    _STATE = _State()
    _PHASE_HOOK = None
    _TLS.ctx = None  # a bound trace context must not leak across resets


# phase-boundary hook (obs.py registers its HBM sampler here): one global
# read on the disabled path, so PhaseSpans stays free when obs is off
_PHASE_HOOK = None

# inbound trace context bound per HANDLER THREAD (worker side): spans
# recorded while a context is bound carry (trace_id, dispatch_id) args and
# the first one emits the flow-finish event that renders the driver→worker
# arrow in Perfetto. Thread-local, so the dispatch connection and the
# weight-bus connection can each serve a causally distinct frame at once.
_TLS = threading.local()


def set_phase_hook(fn) -> None:
    """Install ``fn(phase_name)`` to run at every PhaseSpans exit (None
    uninstalls). obs.enable() uses this to sample HBM at span boundaries."""
    global _PHASE_HOOK
    _PHASE_HOOK = fn


# ----------------------------------------------------- causal trace context


def next_dispatch_context() -> dict:
    """Allocate the ``(trace_id, dispatch_id)`` pair stamped on one outbound
    driver frame (a generation dispatch or a weight push). Always available
    — a locked counter increment — so lineage bookkeeping works with
    tracing off; the wire envelope itself only ships while tracing is on
    (control_plane MSG_DISPATCH_CTX / the weight payload's trace_ctx)."""
    st = _STATE
    with st.lock:
        st.dispatch_seq += 1
        return {"trace_id": st.trace_id, "dispatch_id": st.dispatch_seq}


def bind_trace_context(ctx: Mapping[str, Any] | None) -> None:
    """Bind an inbound frame's trace context to THIS thread: spans recorded
    until :func:`unbind_trace_context` carry its (trace_id, dispatch_id)
    and the first one emits the Perfetto flow-finish event linking back to
    the originating driver dispatch span."""
    _TLS.ctx = dict(ctx) if ctx else None


def unbind_trace_context() -> None:
    _TLS.ctx = None


def current_trace_context() -> dict | None:
    return getattr(_TLS, "ctx", None)


def emit_instant(name: str, **args) -> None:
    """Perfetto instant event ('i' phase, thread scope) — a point-in-time
    marker with args. The control plane's governors stamp every actuation
    with one (ISSUE 14) so ``tools/trace_report.py`` can render a
    "control:" section from the trace file alone. No-op while tracing is
    off (one attribute read)."""
    st = _STATE
    if not st.enabled:
        return
    st.events.append({
        "ph": "i",
        "s": "t",
        "name": name,
        "ts": time.time_ns() // 1000,
        "tid": threading.get_ident(),
        "args": args,
    })


def emit_flow_start(dispatch_id: int) -> None:
    """Driver-side flow-origin event: emitted INSIDE the ``cp/dispatch`` /
    ``cp/weight_push`` span so Perfetto anchors the arrow to that slice;
    the worker's first context-bound span emits the matching finish."""
    st = _STATE
    if not st.enabled:
        return
    st.events.append({
        "ph": "s",
        "cat": "dispatch",
        "name": "dispatch",
        "id": int(dispatch_id),
        "ts": time.time_ns() // 1000,
        "tid": threading.get_ident(),
    })


# --------------------------------------------------------------------- spans


class _NullSpan:
    """Disabled-path singleton: ``span()`` returns this one object, so the
    no-op fast path allocates nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()

# jax.profiler.TraceAnnotation, bound at the first recorded span (this module
# imports without JAX); False where JAX is not installed
_TRACE_ANNOTATION: Any = None


def _trace_annotation(name: str):
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = False
        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION(name) if _TRACE_ANNOTATION else None


class _Span:
    __slots__ = ("name", "args", "_t0", "_annotation")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args

    def __enter__(self) -> "_Span":
        # the same span on the profiler's own clock: any jax.profiler trace
        # that is running (the harness's, --profile_dir's, the sentinel's
        # capture) shows it on its host plane beside the device lines
        self._annotation = _trace_annotation(self.name)
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.time_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.time_ns()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        ident = threading.get_ident()
        st = _STATE
        if ident not in st.thread_names:
            st.thread_names[ident] = threading.current_thread().name
        args = self.args
        ctx = getattr(_TLS, "ctx", None)
        if ctx is not None:
            # inbound trace context (ISSUE 10): every span recorded while a
            # dispatch frame is being handled names the driver dispatch
            # that caused it — the merged trace becomes one causal timeline
            args = {**args, "trace_id": ctx.get("trace_id"),
                    "dispatch_id": ctx.get("dispatch_id")}
            if not ctx.get("_flow_done"):
                # flow-finish INSIDE this span's interval so Perfetto binds
                # the driver→worker arrow to it (bp="e" = enclosing slice)
                ctx["_flow_done"] = True
                st.events.append({
                    "ph": "f", "bp": "e", "cat": "dispatch",
                    "name": "dispatch", "id": int(ctx.get("dispatch_id", 0)),
                    "ts": self._t0 // 1000 + 1, "tid": ident,
                })
        st.events.append({
            "ph": "X",
            "name": self.name,
            "ts": self._t0 // 1000,  # Chrome trace timestamps are µs
            "dur": max((t1 - self._t0) // 1000, 1),
            "tid": ident,
            "args": args,
        })

    def set(self, **args) -> None:
        """Attach args discovered mid-span (e.g. token counts at exit)."""
        self.args.update(args)


def span(name: str, **args) -> _Span | _NullSpan:
    """Trace span context manager; a shared no-op when tracing is off."""
    if not _STATE.enabled:
        return _NULL_SPAN
    return _Span(name, args)


class PhaseSpans:
    """Drop-in for ``metrics.PhaseTimer`` that ALSO records each phase as a
    trace span: ``with phases("generation"): ...`` then ``phases.metrics()``
    yields the reference's exact ``timing/generation_duration`` names
    (distributed_trainer.py:348–366 parity) while the span lands on the
    driver track as ``driver/generation``."""

    def __init__(self):
        self._durations: dict[str, float] = {}
        self._active: str | None = None
        self._span: _Span | _NullSpan = _NULL_SPAN
        self._t0 = 0

    def __call__(self, phase: str) -> "PhaseSpans":
        self._active = phase
        return self

    def __enter__(self) -> "PhaseSpans":
        self._span = span(f"driver/{self._active}")
        self._span.__enter__()
        self._t0 = time.time_ns()
        return self

    def __exit__(self, *exc) -> None:
        assert self._active is not None
        self._durations[self._active] = (time.time_ns() - self._t0) / 1e9
        self._span.__exit__(*exc)
        if _PHASE_HOOK is not None:
            _PHASE_HOOK(self._active)
        self._active = None

    def metrics(self) -> dict[str, float]:
        return {f"timing/{k}_duration": v for k, v in self._durations.items()}

    def get(self, phase: str) -> float:
        return self._durations.get(phase, 0.0)


# ------------------------------------------------------- counters and gauges


def counter_add(name: str, value: float = 1.0) -> None:
    """Monotonic per-step counter; ``metrics_snapshot`` reports and resets
    the delta since the last snapshot. ``counters_total`` keeps the
    monotonic running total for the live scrape endpoint (obs.py)."""
    st = _STATE
    with st.lock:
        st.counters[name] = st.counters.get(name, 0.0) + value
        st.counters_total[name] = st.counters_total.get(name, 0.0) + value
        st.touched.add(name)


def gauge_set(name: str, value: float) -> None:
    """Last-value gauge; while tracing is on, also a Chrome counter event so
    Perfetto renders the series over time (e.g. ``pool/occupancy``)."""
    st = _STATE
    with st.lock:
        st.gauges[name] = value
        st.touched.add(name)
    if st.enabled:
        st.events.append({
            "ph": "C",
            "name": name,
            "ts": time.time_ns() // 1000,
            "tid": 0,
            "args": {name.rsplit("/", 1)[-1]: value},
        })


def hist_observe(name: str, value: float, *, trace_sample: bool = False,
                 count: int = 1) -> None:
    """Latency-style histogram; snapshot reports count/mean/p50/p90/max and
    resets (e.g. ``cp/rpc_dispatch_ms``). ``trace_sample=True`` additionally
    emits each observation as a Chrome counter event while tracing is on, so
    distribution-over-time series (``rollout/staleness``) get a Perfetto
    track AND tools/trace_report.py can summarize them from the trace file
    alone — the sink histogram resets every snapshot, the trace keeps all
    samples. ``count`` records the observation that many times in one call
    (pre-binned device-side histograms — ``engine/spec_emit_tokens`` counts
    a whole round's emissions in d+2 buckets; one Python call per bucket,
    not one per slot-step)."""
    if count < 1:
        return
    st = _STATE
    with st.lock:
        # weighted (value, count) pairs — a pre-binned call stays ONE
        # entry however large its count (a spec round's histogram can
        # cover ~10^5 slot-steps in d+2 calls); metrics_snapshot computes
        # the summary stats from cumulative weights
        st.hists.setdefault(name, []).append((value, count))
        tot = st.hist_totals.setdefault(name, [0.0, 0.0, value])
        tot[0] += count
        tot[1] += value * count
        tot[2] = max(tot[2], value)
        buckets = st.hist_buckets.get(name)
        if buckets is None:
            buckets = st.hist_buckets[name] = (
                [0.0] * (len(HIST_BUCKET_BOUNDS) + 1)
            )
        # bisect_left: first bound >= value, i.e. the inclusive `le` bucket
        buckets[bisect.bisect_left(HIST_BUCKET_BOUNDS, value)] += count
        st.touched.add(name)
    if trace_sample and st.enabled:
        # carry the weight: a count>1 observation must not read as ONE
        # sample in the trace while the sink histogram records count —
        # trace_report's distribution summary weights by this field
        args = {name.rsplit("/", 1)[-1]: value}
        if count > 1:
            args["count"] = count
        st.events.append({
            "ph": "C",
            "name": name,
            "ts": time.time_ns() // 1000,
            "tid": 0,
            "args": args,
        })


def metrics_snapshot() -> dict[str, float]:
    """Flat metric dict for the MetricsSink: counters report-and-reset their
    delta, gauges report their last value, histograms report summary stats
    and reset. Only series touched since the previous snapshot appear, so a
    run without (say) RPCs never logs ``cp/*`` zeros."""
    st = _STATE
    _file_gc_ms(st)
    out: dict[str, float] = {}
    with st.lock:
        for name in sorted(st.touched):
            if name in st.counters:
                out[name] = st.counters.pop(name)
            elif name in st.gauges:
                out[name] = st.gauges[name]
            elif name in st.hists:
                # weighted (value, count) pairs; stats identical to the
                # old expanded-list math (index into the sorted virtual
                # expansion via cumulative counts)
                pairs = sorted(st.hists.pop(name))
                n = sum(c for _, c in pairs)

                def at(idx: int, pairs=pairs) -> float:
                    cum = 0
                    for v, c in pairs:
                        cum += c
                        if idx < cum:
                            return v
                    return pairs[-1][0]

                out[f"{name}_count"] = float(n)
                out[f"{name}_mean"] = sum(v * c for v, c in pairs) / n
                out[f"{name}_p50"] = at(n // 2)
                out[f"{name}_p90"] = at(min(int(n * 0.9), n - 1))
                out[f"{name}_max"] = pairs[-1][0]
        st.touched.clear()
    return out


# ------------------------------------------- continuous observability (obs)


def observe_snapshot() -> dict[str, Any]:
    """Non-destructive registry view for the live metrics endpoint
    (distrl_llm_tpu/obs.py): cumulative counter totals (Prometheus counter
    semantics — monotonic, never reset), last gauge values, and cumulative
    histogram summaries. Unlike ``metrics_snapshot`` this never consumes
    anything, so scraping and the MetricsSink feed cannot fight."""
    st = _STATE
    _file_gc_ms(st)
    with st.lock:
        return {
            "counters": dict(st.counters_total),
            "gauges": dict(st.gauges),
            "hists": {
                name: {
                    "count": t[0], "sum": t[1], "max": t[2],
                    # per-bucket counts aligned to HIST_BUCKET_BOUNDS +
                    # one overflow slot (cumulated at exposition time)
                    "buckets": list(st.hist_buckets.get(name, ())),
                }
                for name, t in st.hist_totals.items()
            },
        }


def configure_obs(export: bool) -> None:
    """Enable/disable the worker-side obs piggyback: when on, every
    control-plane RESULT ships ``observe_snapshot()`` home alongside any
    span blob (worker_main --metrics-port / DISTRL_OBS=1)."""
    _STATE.obs_export = export


def export_obs_blob() -> dict | None:
    """The registry snapshot a worker piggybacks on its RPC response, or
    None when obs export is off (untraced+unobserved runs keep the plain
    MSG_RESULT frame). Carries the process pid: the driver-side fleet
    aggregator detects a worker RESTART by pid change — exact, where
    counter-regression alone misses an incarnation that regenerated past
    its predecessor's count within one refresh gap."""
    if not _STATE.obs_export:
        return None
    snap = observe_snapshot()
    snap["pid"] = os.getpid()
    return snap


def remote_metrics() -> dict[str, dict]:
    """Driver-side fleet table: the last piggybacked registry snapshot per
    worker track (plus its ``_ts`` receive time) — the raw input of
    obs.FleetAggregator."""
    st = _STATE
    with st.lock:
        return {k: dict(v) for k, v in st.remote_metrics.items()}


def drop_remote_track(track: str) -> bool:
    """Forget one worker track from the fleet table (elastic scale-in,
    ISSUE 20): the FleetAggregator folds a retired worker's counter base
    into the fleet totals first, then drops the track here so a
    scaled-in worker doesn't leak into ``/metrics.json`` forever. Also
    clears the trace-track incarnation key — a future worker reusing the
    address starts a fresh track. Returns True when the track existed."""
    st = _STATE
    with st.lock:
        st.remote_incarnations.pop(track, None)
        return st.remote_metrics.pop(track, None) is not None


def recent_events(n: int = 512) -> list[dict]:
    """Copy of the newest ``n`` recorded trace events (the span tail a
    flight-recorder incident bundles). Empty while tracing is off."""
    st = _STATE
    with st.lock:
        return [dict(e) for e in st.events[-n:]]


# ------------------------------------------- the round ledger and host/gc


def round_filed(record: dict) -> dict:
    """Keep one round's record (``engine.file_round`` builds it) in the ring of
    the last ``ROUND_RING``; ``record["round"]`` becomes the count of rounds
    this process filed before it. Tracing on or off."""
    st = _STATE
    with st.lock:
        record["round"] = st.rounds_filed
        st.rounds_filed += 1
        st.rounds.append(record)
    return record


def round_records() -> list[dict]:
    """The ring, oldest first. ``reset()`` drops it."""
    st = _STATE
    with st.lock:
        return list(st.rounds)


def programs_built() -> int:
    """Programs JAX built (compiled, or loaded from the persistent cache) in
    this process since the first call: the process's one ``CompileLog``,
    started here if nothing has. Costs nothing between builds."""
    _ensure_compile_spans()
    return len(_COMPILE_SPANS.events) if _COMPILE_SPANS else 0


def gc_full_ms() -> float:
    """Milliseconds inside full collections so far (0 until the first
    ``configure(True)`` or round installs the callback: ``_ensure_gc_account``)."""
    _ensure_gc_account()
    return _STATE.gc_full_ms


_GC_ACCOUNT = False


def _ensure_gc_account() -> None:
    global _GC_ACCOUNT
    if not _GC_ACCOUNT:
        _GC_ACCOUNT = True
        gc.callbacks.append(_on_gc)


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` entry. Young collections return at once; a full one is
    timed, and named by a span while tracing is on. Runs wherever an
    allocation tripped the collector, possibly under ``_STATE.lock``: it takes
    no lock and files nothing itself."""
    if info["generation"] != 2:
        return
    st = _STATE
    if phase == "start":
        annotation = _trace_annotation(HOST_GC) if st.enabled else None
        if annotation is not None:
            annotation.__enter__()
        st.gc_open = (time.time_ns(), annotation)
        return
    if st.gc_open is None:  # a reset() between the two phases
        return
    t1 = time.time_ns()
    t0, annotation = st.gc_open
    st.gc_open = None
    if annotation is not None:
        annotation.__exit__(None, None, None)
    ms = (t1 - t0) / 1e6
    st.gc_full_ms += ms
    st.gc_unfiled_ms += ms
    if st.enabled:
        st.events.append({
            "ph": "X",
            "name": HOST_GC,
            "ts": t0 // 1000,
            "dur": max((t1 - t0) // 1000, 1),
            "tid": threading.get_ident(),
            "args": {"collected": info.get("collected", 0),
                     "uncollectable": info.get("uncollectable", 0)},
        })


def _file_gc_ms(st: _State) -> None:
    """Fold the collections' milliseconds the callback could not file into
    the counter ``host/gc_full_ms``."""
    ms, st.gc_unfiled_ms = st.gc_unfiled_ms, 0.0
    if ms:
        counter_add(HOST_GC_FULL_MS, ms)


# -------------------------------------------------- cross-process propagation


def drain_remote_blob() -> dict | None:
    """Pop everything a worker recorded since the last drain, as the compact
    blob the control plane piggybacks on its RPC response (None = nothing to
    ship, so untraced runs keep the plain MSG_RESULT frame)."""
    st = _STATE
    with st.lock:
        if not st.events:
            return None
        events, st.events = st.events, []
        threads = dict(st.thread_names)
    # the recording process's pid rides along: the driver keys trace tracks
    # by (worker, pid), so a killed-and-restarted worker's two incarnations
    # render as DISTINCT tracks instead of one aliased timeline
    return {"events": events, "threads": threads, "pid": os.getpid()}


def ingest_remote(blob: Mapping[str, Any], track: str) -> None:
    """Merge a worker's telemetry blob into this (driver) process's trace
    under a per-worker track: each distinct ``track`` label gets a stable
    synthetic pid, named via process_name metadata at export.

    Dropped when this process is not tracing: a traced worker feeding an
    untraced driver (or one whose trace_steps window already closed and
    exported) would otherwise grow the event list unboundedly with blobs
    nothing will ever export. A piggybacked registry snapshot
    (``blob["metrics"]``, obs export) is stored in the fleet table FIRST —
    fleet aggregation works with tracing off (it is bounded: one entry per
    worker track, overwritten in place)."""
    if not blob:
        return
    st = _STATE
    metrics = blob.get("metrics")
    if metrics is not None:
        with st.lock:
            st.remote_metrics[track] = {"_ts": time.time(), **metrics}
    if not st.enabled:
        return
    if not blob.get("events") and not blob.get("threads"):
        return  # metrics-only blob: no empty trace track to register
    # incarnation-keyed tracks (ISSUE 10): the first pid seen for a worker
    # keeps the plain label (healthy runs are unchanged); a RESTARTED
    # worker's new pid gets its own track, so two incarnations never merge
    # into one timeline (the aliasing bug trace_report inherited)
    worker_pid = blob.get("pid")
    with st.lock:
        first_pid = st.remote_incarnations.setdefault(track, worker_pid)
        label = (
            track if worker_pid is None or worker_pid == first_pid
            else f"{track} (pid {worker_pid})"
        )
        pid = st.remote_tracks.setdefault(
            label, _REMOTE_PID0 + len(st.remote_tracks)
        )
        for tid, name in blob.get("threads", {}).items():
            st.remote_threads[(pid, int(tid))] = name
    for ev in blob.get("events", []):
        ev = dict(ev)
        ev["pid"] = pid
        st.events.append(ev)


# ------------------------------------------------------------------- export


def export_chrome_trace(path: str, metadata: Mapping[str, Any] | None = None,
                        clear: bool = True) -> str:
    """Write the recorded events as Chrome trace-event JSON (Perfetto /
    chrome://tracing load it directly). Local events get the driver pid;
    ingested worker events keep their per-track pid. Returns ``path``."""
    st = _STATE
    with st.lock:
        events = list(st.events)
        if clear:
            st.events.clear()
        thread_names = dict(st.thread_names)
        remote_tracks = dict(st.remote_tracks)
        remote_threads = dict(st.remote_threads)
    out: list[dict] = [{
        "ph": "M", "name": "process_name", "pid": _DRIVER_PID, "tid": 0,
        "args": {"name": "driver"},
    }]
    for tid, name in thread_names.items():
        out.append({
            "ph": "M", "name": "thread_name", "pid": _DRIVER_PID, "tid": tid,
            "args": {"name": name},
        })
    for track, pid in remote_tracks.items():
        out.append({
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": track},
        })
    for (pid, tid), name in remote_threads.items():
        out.append({
            "ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
            "args": {"name": name},
        })
    for ev in events:
        if "pid" not in ev:
            ev = {**ev, "pid": _DRIVER_PID}
        out.append(ev)
    doc: dict[str, Any] = {"traceEvents": out, "displayTimeUnit": "ms"}
    if metadata:
        doc["metadata"] = dict(metadata)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


# ------------------------------------------------------------ compile events


class CompileLog:
    """Every program JAX builds (compiles, or loads from the persistent
    cache) from now on: (function name, seconds, when it finished on
    ``perf_counter``), off JAX's own monitoring events. ``spans=True`` also
    records each build, while tracing is on, as a span ``compile/<fun_name>``
    that ends at the event, so a gap or a slow step in an exported trace is
    named by the program that was built in it."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, spans: bool = False):
        import jax.monitoring

        self.events: list[tuple[str, float, float]] = []
        self._spans = spans
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event != self.EVENT:
            return
        name = str(kw.get("fun_name", "?"))
        self.events.append((name, float(duration), time.perf_counter()))
        st = _STATE
        if self._spans and st.enabled:
            t1 = time.time_ns()
            dur_us = max(int(duration * 1e6), 1)
            st.events.append({
                "ph": "X",
                "name": f"{COMPILE_PREFIX}/{name}",
                "ts": t1 // 1000 - dur_us,
                "dur": dur_us,
                "tid": threading.get_ident(),
                "args": {},
            })

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def mark(self) -> int:
        return len(self.events)

    def since(self, mark: int) -> dict:
        new = self.events[mark:]
        return {
            "programs": len(new),
            "seconds": round(sum(s for _, s, _ in new), 3),
        }

    def recompiled_after(self, mark: int, t: float) -> tuple[list, list]:
        """(names compiled again, names compiled for the first time) among
        the programs since ``mark`` that finished after time ``t``."""
        before = {n for n, _, done in self.events[mark:] if done < t}
        late = sorted({n for n, _, done in self.events[mark:] if done >= t})
        return ([n for n in late if n in before],
                [n for n in late if n not in before])


# the process's one listener to JAX's build events, started by the first
# ``configure(True)`` or the first round's ``programs_built()``: it counts
# every build and records a span for those made while tracing is on; False
# where JAX is not installed
_COMPILE_SPANS: Any = None


def _ensure_compile_spans() -> None:
    global _COMPILE_SPANS
    if _COMPILE_SPANS is None:
        try:
            _COMPILE_SPANS = CompileLog(spans=True)
        except ImportError:
            _COMPILE_SPANS = False


# ----------------------------------------------------------- MFU / hardware


# Peak dense bf16 TFLOP/s per chip by device_kind substring (public TPU
# specs); DISTRL_PEAK_FLOPS overrides for hardware not listed here.
_PEAK_TFLOPS_BY_KIND = (
    ("v6", 918.0),  # Trillium
    ("v5p", 459.0),
    ("v5e", 197.0),
    ("v5 lite", 197.0),
    ("v5litepod", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)


def peak_flops_for_kind(device_kind: str) -> float:
    """Peak dense bf16 FLOP/s of one chip of ``device_kind``. A kind the
    table does not hold is an error, never a default peak."""
    low = device_kind.lower()
    for sub, tflops in _PEAK_TFLOPS_BY_KIND:
        if sub in low:
            return tflops * 1e12
    raise ValueError(
        f"device_kind {device_kind!r} is not in telemetry._PEAK_TFLOPS_BY_KIND"
        " — add its published peak there (or set DISTRL_PEAK_FLOPS)"
    )


def device_peak_flops() -> float | None:
    """Peak FLOP/s of one local accelerator chip: the MFU denominator.
    None on a host with no TPU (no utilisation is published there);
    ``DISTRL_PEAK_FLOPS`` (FLOP/s) overrides; a TPU the table cannot name
    raises (``peak_flops_for_kind``)."""
    env = os.environ.get("DISTRL_PEAK_FLOPS")
    if env:
        return float(env)
    import jax

    try:
        dev = jax.devices()[0]
    except RuntimeError:  # no backend at all
        return None
    if dev.platform != "tpu":
        return None
    return peak_flops_for_kind(dev.device_kind)


def mfu(tok_per_s: float, flops_per_token: float, peak_flops: float) -> float:
    """Model-FLOPs utilisation of one chip: achieved FLOP/s over peak.
    ``flops_per_token`` comes from ``ModelConfig.decode_flops_per_token`` /
    ``train_flops_per_token`` (models/configs.py)."""
    return tok_per_s * flops_per_token / peak_flops
