"""The host's side of a generation round as per-layer metrics (PR 38): the eight
entries resolve from their files and stand in ``BENCHMARK.json`` by name, the
two new readers on synthetic inputs, the account closing on a tiny real round,
and a CPU rehearsal line that leaves all eight out (their units are ms and %)."""

from types import SimpleNamespace

import pytest

from distrl_llm_tpu import telemetry
from perfbench import spec
from tiny_spec import real_benchmark, tiny_benchmark

BENCH = real_benchmark()
CELLS = [
    "qwen2.5-7b-L14.rollout-lockstep", "minicpm-sala-L10.rollout-longctx",
    "kimi-vl-a3b-L7.rollout-longctx-latent", "solar-open2-250b-ep8-L4.rollout-reasoning",
]
#: the three newer rollout cells that joined the eight lists in PR 53
JOINED_IN_PR_53 = [
    "brumby-14b-L4.rollout-retention-16k", "jamba2-3b.rollout-wide-480",
    "k-exaone-236b-ep8-L5.rollout-longctx-window",
]
#: name -> (unit, source, reader, what the reader is pointed at)
NEW = {
    "engine.dispatch_host_ms": ("ms", "program_span", "host_spans", telemetry.ENGINE_DISPATCH),
    "engine.dispatch_median_ms": ("ms", "program_span", "host_spans", telemetry.ENGINE_DISPATCH),
    "engine.prefill_ms": ("ms", "program_span", "host_spans", telemetry.ENGINE_PREFILL),
    "engine.readback_ms": ("ms", "program_span", "host_spans", telemetry.ENGINE_READBACK),
    "engine.loop_self_ms": ("ms", "program_span", "span_self", None),
    "engine.host_busy_share": ("%", "program_counter", "program_gauge",
                               telemetry.ENGINE_HOST_BUSY_SHARE),
    "engine.slowest_boundary_ms": ("ms", "program_counter", "program_gauge",
                                   telemetry.ENGINE_SLOWEST_BOUNDARY_MS),
    "engine.slowest_boundary_host_ms": ("ms", "program_counter", "program_gauge",
                                        telemetry.ENGINE_SLOWEST_BOUNDARY_HOST_MS),
}
LOOPS = [telemetry.ENGINE_DECODE, telemetry.ENGINE_REFILL_DECODE]


def reader(name):
    return spec.load_module(BENCH["paths"], "readers", name)


def traced(spans, units=1):
    """What a reader is given in a traced run: the spans as (name, t0, t1) in
    wall nanoseconds, and the traced units."""
    return ({"traced_units": [{}] * units},
            SimpleNamespace(tracer=SimpleNamespace(host_spans=spans)))


# ------------------------------------------------ the entries and their files


@pytest.mark.parametrize("name", list(NEW))
def test_the_metric_resolves_from_its_file_and_is_in_the_benchmark_by_name(name):
    unit, source, reader_name, target = NEW[name]
    held = spec.load_layer_metric(BENCH["paths"], name)
    assert (held["unit"], held["source"], held["reader"]) == (unit, source, reader_name)
    assert (held["layer"], held["moves"], held["better"]) == ("engine", "rollout_tok_s", "lower")
    assert callable(reader(reader_name).read)
    if target is None:
        assert held["args"]["names"] == LOOPS
    else:
        assert held["args"]["name"] == target
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": "engine", "moves": "rollout_tok_s"}
    # PR 38's four cells are in the list, and a later cell may join it (PR 53)
    assert set(CELLS) <= set(entry["workloads"])
    # each cell is there, and reports the end-to-end metric these move
    (moved,) = [m for m in BENCH["end_to_end"] if m["name"] == "rollout_tok_s"]
    assert set(entry["workloads"]) <= {w["name"] for w in BENCH["workloads"]} & set(
        moved["workloads"])


def test_the_eight_read_in_every_rollout_cell_alike():
    """One list for the eight: the account closes only where all its parts are
    read, so a cell joins all eight or none (PR 53 appended three)."""
    lists = {tuple(m["workloads"]) for m in BENCH["per_layer"] if m["name"] in NEW}
    (cells,) = lists
    assert list(cells[:len(CELLS)]) == CELLS  # appended after PR 38's four
    assert set(JOINED_IN_PR_53) <= set(cells)
    # and the part of the sum that every rollout cell listed before
    (wait,) = [m for m in BENCH["per_layer"] if m["name"] == "engine.snapshot_wait_ms"]
    assert set(cells) <= set(wait["workloads"])


def test_the_eight_are_one_block_after_what_was_there():
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(next(iter(NEW)))
    assert names[at:at + len(NEW)] == list(NEW)
    # PR 37's tree ended with this entry: the block was appended, not put inside
    assert at > names.index("engine.expert_held_share")
    # and the two spans' accepted metrics are as they were
    for kept in ("engine.snapshot_wait_ms", "engine.admit_host_ms"):
        assert names.index(kept) < at


# --------------------------------------------------- readers/span_self.py

US = 1000  # the program's spans keep microseconds


def test_children_and_self_time_make_the_parent_to_the_microsecond():
    spans = [
        ("engine.generate", 0, 2_000 * US),  # the parent's own parent: not inside it
        (LOOPS[0], 100 * US, 1_100 * US),
        ("engine/dispatch", 110 * US, 130 * US),
        ("engine/dispatch", 140 * US, 150 * US),
        ("engine/snapshot_wait", 200 * US, 700 * US),
        ("engine/grant", 710 * US, 800 * US),
        ("engine/preempt", 720 * US, 760 * US),  # a child's child counts once
        ("engine/readback", 900 * US, 1_100 * US),
        ("engine/prefill", 10 * US, 90 * US),  # before the loop
        ("engine/setup", 1_100 * US, 1_150 * US),  # begins where the loop ends
    ]
    children = 20 + 10 + 500 + 90 + 200
    observed, ctx = traced(spans)
    value = reader("span_self").read(observed, {"names": LOOPS, "scale": 1e6}, ctx)
    assert round(value) == 1_000 - children  # in microseconds, exactly
    assert value + children == pytest.approx(1_000, abs=1e-6)


@pytest.mark.parametrize("spans, units, want_ms", [
    # two rounds, one loop each: self time summed over both, per round
    ([(LOOPS[1], 0, 10 * US), ("engine/admit", 2 * US, 5 * US),
      (LOOPS[1], 20 * US, 40 * US), ("engine/dispatch", 20 * US, 24 * US)], 2,
     ((10 - 3) + (20 - 4)) / 2 / 1000),
    # a child that the microsecond rounding ends past its parent is cut to it
    ([(LOOPS[0], 0, 10 * US), ("engine/readback", 6 * US, 11 * US)], 1, 6 / 1000),
    # children that overlap are a union, not a sum
    ([(LOOPS[0], 0, 10 * US), ("a", 1 * US, 6 * US), ("b", 4 * US, 8 * US)], 1, 3 / 1000),
    # a bare loop is all self time
    ([(LOOPS[0], 0, 10 * US)], 1, 10 / 1000),
])
def test_span_self_over_rounds_overlaps_and_rounding(spans, units, want_ms):
    observed, ctx = traced(spans, units)
    got = reader("span_self").read(observed, {"names": LOOPS, "scale": 1000.0}, ctx)
    assert got == pytest.approx(want_ms, abs=1e-9)


@pytest.mark.parametrize("observed, ctx", [
    traced([("engine/prefill", 0, 5 * US)]),  # a program without such a loop
    ({"traced_units": []}, traced([(LOOPS[0], 0, 5 * US)])[1]),  # no traced unit
    ({"traced_units": [{}]}, SimpleNamespace(tracer=None)),  # an untraced run
    ({}, None),  # a call without a run
])
def test_span_self_reads_nothing_where_there_is_nothing(observed, ctx):
    assert reader("span_self").read(observed, {"names": LOOPS}, ctx) is None


def test_the_account_closes_on_a_tiny_real_round():
    """A refill round on the CPU, traced: the named parts of the loop and its
    self time make the loop's span within 2% (each span keeps whole microseconds)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distrl_llm_tpu.config import SamplingConfig
    from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
    from distrl_llm_tpu.models import TINY, init_params

    engine = PagedGenerationEngine(
        TINY, max_prompt_tokens=16, max_new_tokens=24, eos_token_ids=[1],
        pad_token_id=0, page_size=8, max_concurrent_rows=4, scheduler="refill",
        decode_chunk=4)
    params = init_params(jax.random.PRNGKey(0), TINY, dtype=jnp.bfloat16)
    ids = np.random.default_rng(0).integers(2, TINY.vocab_size, size=(6, 16)).astype(np.int32)
    telemetry.reset()
    telemetry.configure(True)
    try:
        engine.generate(params, None, ids, np.ones_like(ids),
                        SamplingConfig(max_tokens=24, temperature=0.0, top_p=1.0, n=2),
                        jax.random.PRNGKey(0))
        spans = [(e["name"], e["ts"] * US, (e["ts"] + e["dur"]) * US)
                 for e in telemetry.recent_events(100_000) if e.get("ph") == "X"]
    finally:
        telemetry.reset()
        telemetry.configure(False)
    observed, ctx = traced(spans)
    (loop_ms,) = [(t1 - t0) / 1e6 for name, t0, t1 in spans if name == LOOPS[1]]
    parts = {}
    for name in (telemetry.ENGINE_SETUP, telemetry.ENGINE_ADMIT, telemetry.ENGINE_DISPATCH,
                 telemetry.ENGINE_SNAPSHOT_WAIT, telemetry.ENGINE_READBACK):
        parts[name] = reader("host_spans").read(
            observed, {"name": name, "stat": "sum_per_unit", "scale": 1000.0}, ctx)
    self_ms = reader("span_self").read(observed, {"names": LOOPS, "scale": 1000.0}, ctx)
    assert all(v is not None and v > 0 for v in parts.values()) and self_ms > 0
    assert sum(parts.values()) + self_ms == pytest.approx(loop_ms, rel=0.02)
    # the launches are a part of their own, no longer the loop's self time
    assert self_ms < loop_ms - parts[telemetry.ENGINE_DISPATCH]


# ------------------------------------------------ readers/program_gauge.py


@pytest.fixture
def fresh_registry():
    telemetry.reset()
    yield
    telemetry.reset()


def test_program_gauge_reads_the_last_value_and_nothing_without_the_gauge(fresh_registry):
    read = reader("program_gauge").read
    args = {"name": telemetry.ENGINE_SLOWEST_BOUNDARY_MS, "scale": 1.0}
    ctx = SimpleNamespace(tracer=None)  # traced or not: the registry is the program's
    assert read({}, args, ctx) is None  # the parent of the PR that added the gauge
    telemetry.gauge_set(telemetry.ENGINE_HOST_BUSY_SHARE, 12.5)
    assert read({}, args, ctx) is None  # another gauge is not this one
    telemetry.gauge_set(telemetry.ENGINE_SLOWEST_BOUNDARY_MS, 250.0)
    telemetry.gauge_set(telemetry.ENGINE_SLOWEST_BOUNDARY_MS, 245.5)
    assert read({}, args, ctx) == 245.5
    assert read({}, {**args, "scale": 0.001}, ctx) == pytest.approx(0.2455)
    assert read({}, {"name": telemetry.ENGINE_HOST_BUSY_SHARE}, ctx) == 12.5
    telemetry.metrics_snapshot()  # a sink that drained the registry takes no gauge away
    assert read({}, args, ctx) == 245.5
    assert read({}, args, None) is None  # a call without a run


# ------------------------------------------------------- a CPU rehearsal line


@pytest.mark.parametrize("trace", [0, 1])
def test_a_cpu_rehearsal_line_leaves_all_eight_out(tmp_path, trace):
    from rehearsal_helpers import assert_contract, shared_cell
    from tiny_spec import write_tiny_benchmark

    asked = {m["name"]: m for m in tiny_benchmark()["per_layer"]}
    assert set(NEW) <= set(asked)  # the tiny rollout cell is asked for them too
    assert all(asked[name]["workloads"] == ["tiny.rollout"] for name in NEW)
    line, notes = shared_cell(write_tiny_benchmark(tmp_path), "tiny.rollout", trace)
    assert_contract(line, trace)
    assert not set(NEW) & set(line["metrics"])
    # the program filed its account all the same: the reader would find the gauges
    for name in list(NEW)[5:]:
        held = spec.load_layer_metric(BENCH["paths"], name)
        assert reader("program_gauge").read({}, held["args"], SimpleNamespace()) is not None
