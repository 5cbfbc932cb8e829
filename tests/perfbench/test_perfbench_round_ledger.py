"""The round ledger as per-layer metrics (PR 56): the seven entries resolve from
their files and stand in ``BENCHMARK.json`` by name over the eight rollout cells,
the reader on synthetic records (a round that built a program left out, None
without a ledger), and a CPU rehearsal line that reports the one count and
leaves the six in milliseconds out."""

from types import SimpleNamespace

import pytest

from distrl_llm_tpu import telemetry
from perfbench import spec
from tiny_spec import real_benchmark, tiny_benchmark

BENCH = real_benchmark()
#: name -> (unit, source, reader, the reader's args)
NEW = {
    "engine.boundary_median_ms": ("ms", "program_counter", "round_ledger",
                                  {"what": "boundary_median_ms"}),
    "engine.worst_boundary_ms": ("ms", "program_counter", "round_ledger",
                                 {"what": "worst_boundary_ms"}),
    "engine.worst_boundary_host_ms": ("ms", "program_counter", "round_ledger",
                                      {"what": "worst_boundary_host_ms"}),
    "engine.worst_boundary_cpu_ms": ("ms", "program_counter", "round_ledger",
                                     {"what": "worst_boundary_cpu_ms"}),
    "engine.stalled_boundaries": ("count", "program_counter", "round_ledger",
                                  {"what": "stalled_boundaries"}),
    "engine.stall_recovered_ms": ("ms", "program_counter", "round_ledger",
                                  {"what": "stall_recovered_ms"}),
    "engine.snapshot_launch_ms": ("ms", "program_span", "host_spans", {
        "name": telemetry.ENGINE_SNAPSHOT_LAUNCH, "stat": "sum_per_unit", "scale": 1000.0}),
}
LEDGER = [name for name, held in NEW.items() if held[2] == "round_ledger"]
CTX = SimpleNamespace(tracer=None)  # traced or not: the ledger is the program's


def reader(name):
    return spec.load_module(BENCH["paths"], "readers", name)


def read(what):
    return reader("round_ledger").read({}, {"what": what}, CTX)


# ------------------------------------------------ the entries and their files


@pytest.mark.parametrize("name", list(NEW))
def test_the_metric_resolves_from_its_file_and_is_in_the_benchmark_by_name(name):
    unit, source, reader_name, args = NEW[name]
    held = spec.load_layer_metric(BENCH["paths"], name)
    assert (held["unit"], held["source"], held["reader"]) == (unit, source, reader_name)
    assert (held["layer"], held["moves"], held["better"]) == ("engine", "rollout_tok_s", "lower")
    assert held["args"] == args and callable(reader(reader_name).read)
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": "engine", "moves": "rollout_tok_s"}


def test_the_seven_stand_over_the_cells_the_last_rounds_gauge_is_read_in():
    """One list for the seven, the one ``engine.slowest_boundary_ms`` has: every
    cell that reports ``rollout_tok_s`` (a later cell may join both)."""
    (gauge,) = [m for m in BENCH["per_layer"] if m["name"] == "engine.slowest_boundary_ms"]
    (moved,) = [m for m in BENCH["end_to_end"] if m["name"] == "rollout_tok_s"]
    for name in NEW:
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"] == gauge["workloads"], name
        assert set(entry["workloads"]) == set(moved["workloads"]), name
    assert len(gauge["workloads"]) >= 8


def test_the_seven_are_one_block_appended_after_what_was_there():
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(next(iter(NEW)))
    assert names[at:at + len(NEW)] == list(NEW)
    # PR 55's tree ended with this entry, and the metrics that time the same
    # loop from outside stay where they were
    assert at > names.index("kernel.indexed_attn_roofline")
    for kept in ("engine.slowest_boundary_ms", "engine.slowest_boundary_host_ms",
                 "engine.loop_self_ms", "engine.snapshot_wait_ms"):
        assert names.index(kept) < at


# ------------------------------------------------ readers/round_ledger.py


def record(intervals, *, built=0, stalled=(), recovered_s=0.0, host=0.01, cpu=0.02):
    """A round's record as ``engine.file_round`` files it, as far as the
    reader looks: ``boundaries`` is (interval, host part, CPU, switches, steps,
    marks) each."""
    return {"programs_built": built, "stalled": list(stalled), "recovered_s": recovered_s,
            "boundaries": [[s, host * (i + 1), cpu * (i + 1), 0, 16, ""]
                           for i, s in enumerate(intervals)]}


@pytest.fixture
def ledger():
    telemetry.reset()
    yield telemetry.round_filed
    telemetry.reset()


def test_the_reader_pools_the_measured_rounds_and_leaves_a_built_round_out(ledger):
    ledger(record([9.0, 9.0, 9.0], built=3, stalled=[0, 1, 2]))  # the warm-up: compiles
    ledger(record([0.2, 0.2, 0.9, 0.1], stalled=[2], recovered_s=0.1))
    ledger(record([0.2, 0.3, 0.2]))
    assert read("boundary_median_ms") == pytest.approx(200.0)  # of the seven, pooled
    assert read("stalled_boundaries") == 1
    assert read("worst_boundary_ms") == pytest.approx(900.0)
    assert read("worst_boundary_host_ms") == pytest.approx(30.0)  # THAT boundary's
    assert read("worst_boundary_cpu_ms") == pytest.approx(60.0)
    assert read("stall_recovered_ms") == pytest.approx(100.0)
    # a sound run: nothing stalled, nothing recovered, the worst an ordinary one
    telemetry.reset()
    telemetry.round_filed(record([0.20, 0.21, 0.20]))
    assert (read("stalled_boundaries"), read("stall_recovered_ms")) == (0, 0.0)
    assert read("worst_boundary_ms") == pytest.approx(210.0)
    with pytest.raises(ValueError, match="cannot read"):
        read("the_mean")


def test_the_round_that_holds_the_worst_boundary_says_what_was_recovered(ledger):
    ledger(record([0.2, 0.5, 0.2], stalled=[1], recovered_s=0.05))
    ledger(record([0.2, 0.8, 0.2], stalled=[1], recovered_s=0.0))  # the device sat idle
    assert read("worst_boundary_ms") == pytest.approx(800.0)
    assert read("stall_recovered_ms") == 0.0
    assert read("stalled_boundaries") == 2


@pytest.mark.parametrize("what", [args["what"] for _, _, r, args in NEW.values()
                                  if r == "round_ledger"])
def test_the_reader_reads_nothing_where_there_is_nothing(what, monkeypatch, ledger):
    assert read(what) is None  # a ledger with no round
    ledger(record([9.0], built=1))
    assert read(what) is None  # nothing but the warm-up
    ledger(record([]))
    # a measured round of one or two snapshots: no interval, and none stalled
    assert read(what) == (0 if what == "stalled_boundaries" else None)
    ledger(record([0.2, 0.2]))
    assert read(what) is not None
    assert reader("round_ledger").read({}, {"what": what}, None) is None  # no run
    # the parent of the PR that added the ledger: a program without it
    monkeypatch.delattr(telemetry, "round_records")
    assert read(what) is None


def test_the_ledger_a_real_round_files_is_what_the_reader_reads(ledger):
    """A tiny refill round on the CPU, twice: the first builds its programs
    and is left out, the second is the run's one measured round."""
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
    for _ in range(2):
        engine.generate(params, None, ids, np.ones_like(ids),
                        SamplingConfig(max_tokens=24, temperature=0.0, top_p=1.0, n=2),
                        jax.random.PRNGKey(0))
    warm, round_ = telemetry.round_records()
    assert warm["programs_built"] > 0 and round_["programs_built"] == 0
    assert reader("round_ledger").measured([warm, round_]) == [round_]
    assert reader("round_ledger").measured([{"programs_built": 0, "boundaries": []}])
    intervals = [b[0] for b in round_["boundaries"]]
    assert read("worst_boundary_ms") == pytest.approx(1e3 * max(intervals))
    # never under the last round's gauge, and here the last round is the only one
    gauge = telemetry.observe_snapshot()["gauges"][telemetry.ENGINE_SLOWEST_BOUNDARY_MS]
    assert read("worst_boundary_ms") == pytest.approx(gauge)
    assert read("stalled_boundaries") == len(round_["stalled"])


# ------------------------------------------------------- a CPU rehearsal line


@pytest.mark.parametrize("trace", [0, 1])
def test_a_cpu_rehearsal_line_reports_the_count_and_leaves_the_six_in_ms_out(tmp_path, trace):
    from rehearsal_helpers import assert_contract, shared_cell
    from tiny_spec import write_tiny_benchmark

    asked = {m["name"]: m for m in tiny_benchmark()["per_layer"]}
    assert set(NEW) <= set(asked)  # the tiny rollout cell is asked for them too
    assert all(asked[name]["workloads"] == ["tiny.rollout"] for name in NEW)
    line, notes = shared_cell(write_tiny_benchmark(tmp_path), "tiny.rollout", trace)
    assert_contract(line, trace)
    reported = set(NEW) & set(line["metrics"])
    assert reported == ({"engine.stalled_boundaries"} if trace else set())
    if trace:
        said = line["metrics"]["engine.stalled_boundaries"]
        assert said["unit"] == "count" and said["value"] == int(said["value"]) >= 0
    # the program filed its ledger all the same: the reader finds every metric,
    # over the measured rounds (the warm-up built its programs and is left out)
    rounds = reader("round_ledger").measured(telemetry.round_records())
    assert len(rounds) >= notes["window"]["units"] >= 1
    for name in LEDGER:
        assert reader("round_ledger").read({}, NEW[name][3], CTX) is not None, name
