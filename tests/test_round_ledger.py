"""Every boundary of every round kept (ISSUE 56): the host account's list on an
injected clock, the three older gauges as a derivation of it, what a stall
followed by short boundaries reads against one followed by ordinary ones, the
ring of rounds, the collector's callback, and a tiny real round on the CPU.

Counts and an injected clock: never a time of this machine's."""

import gc
import logging
import statistics
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.config import SamplingConfig
from distrl_llm_tpu.engine import engine as engine_mod
from distrl_llm_tpu.engine.engine import (
    STALL_FACTOR,
    RoundHostAccount,
    RoundMarks,
    accumulate_round_stats,
    file_round,
    run_decode_loop,
    stalled_boundaries,
)
from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
from distrl_llm_tpu.models import TINY, init_params

T = telemetry


@pytest.fixture(autouse=True)
def fresh_telemetry():
    telemetry.reset()
    telemetry.configure(False)
    yield
    telemetry.reset()
    telemetry.configure(False)


# ------------------------------------------------ the account, on a fake clock


class FakeClock:
    """``perf_counter`` and ``process_time`` of the engine module, advanced by
    the test alone; ``switches`` is what ``getrusage`` would say."""

    def __init__(self, monkeypatch):
        self.now = 100.0
        self.cpu = 5.0
        self.switches = 7
        self.reads = []  # (perf_counter reading) in order: the old fields replay them
        monkeypatch.setattr(engine_mod, "time", types.SimpleNamespace(
            perf_counter=self.perf_counter, process_time=lambda: self.cpu,
            time_ns=lambda: int(self.now * 1e9)))
        monkeypatch.setattr(engine_mod, "_involuntary_switches", lambda: self.switches)

    def perf_counter(self):
        self.reads.append(self.now)
        return self.now

    def advance(self, wall, cpu=0.0, switches=0):
        self.now += wall
        self.cpu += cpu
        self.switches += switches


class Flags:
    """Done flags whose host read advances the fake clock by ``late``: a
    snapshot that arrives that much after it was asked for."""

    def __init__(self, clock, late=0.0):
        self.clock, self.late = clock, late

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        self.clock.advance(self.late)
        return np.zeros(3, bool)


def drive(clock, monkeypatch, *, boundaries=10, check=4, step_s=0.010, launch_s=0.001,
          host_sleep=None, late=None):
    """``boundaries`` boundaries of ``check`` steps through the shared loop:
    a launch costs the host ``launch_s``, the snapshot read at boundary ``i``
    arrives ``late[i]`` after it is asked for (default: a device in step, so a
    boundary's wait lasts what is left of ``check`` steps), and the host
    sleeps ``host_sleep[i]`` before the launches of boundary ``i``."""
    monkeypatch.setattr(engine_mod.jnp, "copy", lambda flags: flags)
    host_sleep, late = host_sleep or {}, late or {}
    calls = [0]
    steady = check * (step_s - launch_s)

    def step_fn(state):
        boundary, at = divmod(calls[0], check)
        if at == 0 and boundary in host_sleep:
            clock.advance(host_sleep[boundary], cpu=0.0)
        calls[0] += 1
        clock.advance(launch_s, cpu=launch_s)
        if calls[0] % check:
            return state
        return types.SimpleNamespace(done=Flags(clock, late.get(boundary, steady)))

    host = RoundHostAccount()
    run_decode_loop(step_fn, types.SimpleNamespace(done=Flags(clock)),
                    boundaries * check, check, host=host)
    host.stop()
    return host


def replay_the_old_fields(reads):
    """What ``slowest_s`` / ``slowest_host_s`` were before the list: the loop
    reads ``perf_counter`` at (account, then wait start and return a wait, then
    stop); a strict maximum over the intervals between returns."""
    waits = list(zip(reads[1:-1:2], reads[2:-1:2]))
    slowest = slowest_host = 0.0
    last = None
    for since, now in waits:
        if last is not None and now - last > slowest:
            slowest, slowest_host = now - last, since - last
        last = now
    return len(waits), slowest, slowest_host


@pytest.mark.parametrize("late, host_sleep", [
    ({}, {}), ({5: 0.5}, {}), ({}, {6: 1.0}), ({3: 0.2, 7: 0.2}, {2: 0.3}),
])
def test_the_list_holds_every_boundary_and_the_old_fields_derive_from_it(
        monkeypatch, late, host_sleep):
    clock = FakeClock(monkeypatch)
    host = drive(clock, monkeypatch, late=late, host_sleep=host_sleep)
    waits, slowest, slowest_host = replay_the_old_fields(clock.reads)
    # ten boundaries launch ten snapshots; nine are waited for in the loop, and
    # an interval lies between two returns: eight
    assert waits == 9 and len(host.boundaries) == waits - 1
    assert host.slowest_s == pytest.approx(slowest) and slowest > 0
    assert host.slowest_host_s == pytest.approx(slowest_host)
    intervals = [b[0] for b in host.boundaries]
    assert host.slowest_s == max(intervals)
    assert host.first_s + sum(intervals) == pytest.approx(clock.reads[-2] - host.t0)
    assert all(b[4] == 4 and b[5] == "" for b in host.boundaries)  # steps, no pass
    # the same values reach last_round_stats and the three gauges
    stats = accumulate_round_stats(
        None, prefill_s=0.0, prefill_tokens=0, prompt_rows=0, decode_s=host.loop_s,
        gen_tokens=0, gen_rows=0, host=host)
    assert stats["slowest_boundary_s"] == host.slowest_s
    assert stats["slowest_boundary_host_s"] == host.slowest_host_s
    gauges = telemetry.observe_snapshot()["gauges"]
    assert gauges[T.ENGINE_SLOWEST_BOUNDARY_MS] == pytest.approx(1e3 * slowest)
    assert gauges[T.ENGINE_SLOWEST_BOUNDARY_HOST_MS] == pytest.approx(1e3 * slowest_host)
    assert gauges[T.ENGINE_BOUNDARY_MEDIAN_MS] == pytest.approx(
        1e3 * statistics.median(intervals))
    hist = telemetry.observe_snapshot()["hists"][T.ENGINE_BOUNDARY_MS]
    assert hist["count"] == len(intervals)
    assert hist["max"] == pytest.approx(1e3 * slowest)


def test_a_host_that_slept_a_second_reads_as_a_large_host_part(monkeypatch):
    clock = FakeClock(monkeypatch)
    host = drive(clock, monkeypatch, host_sleep={6: 1.0})
    median_s, stalled, _ = stalled_boundaries(host.boundaries)
    (at,) = stalled
    interval_s, host_s, cpu_s, switches, steps, marks = host.boundaries[at]
    assert median_s == pytest.approx(0.040)
    # the sleep, then the launches; the snapshot it then waits for is long done
    assert host_s == pytest.approx(1.0 + 4 * 0.001)
    assert interval_s >= host_s > 20 * median_s
    assert cpu_s == pytest.approx(4 * 0.001)  # it slept: no CPU but the launches'
    assert (switches, steps, marks) == (0, 4, "")


def test_cpu_seconds_and_switches_are_the_intervals_own(monkeypatch):
    clock = FakeClock(monkeypatch)
    monkeypatch.setattr(engine_mod.jnp, "copy", lambda flags: flags)
    host = RoundHostAccount()
    clock.advance(1.0, cpu=0.5, switches=3)  # before the first return: in no interval
    host.waited(clock.now - 0.1, steps=16)
    clock.advance(0.2, cpu=0.15, switches=2)
    host.waited(clock.now - 0.05, steps=32)
    clock.advance(0.3, cpu=0.01)
    monkeypatch.setattr(engine_mod, "_involuntary_switches", lambda: None)
    host.waited(clock.now - 0.25, steps=40)
    assert host.first_s == pytest.approx(1.0)
    assert host.boundaries[0] == pytest.approx((0.2, 0.15, 0.15, 2, 16, ""), abs=1e-9)
    assert host.boundaries[1][:3] == pytest.approx((0.3, 0.05, 0.01), abs=1e-9)
    assert host.boundaries[1][3:] == (None, 8, "")  # a platform without getrusage


def test_a_late_return_the_device_worked_through_is_recovered_by_short_boundaries(
        monkeypatch):
    """The snapshot of boundary 4 arrives 0.5 s late. The device ran on through
    its queue meanwhile: the next boundaries' snapshots are there when asked
    for, so those boundaries last their launches alone."""
    clock = FakeClock(monkeypatch)
    host = drive(clock, monkeypatch, boundaries=12,
                 late={4: 0.5, 5: 0.0, 6: 0.0, 7: 0.010})
    median_s, stalled, recovered_s = stalled_boundaries(host.boundaries)
    assert median_s == pytest.approx(0.040) and len(stalled) == 1
    interval_s, host_s = host.boundaries[stalled[0]][:2]
    assert interval_s == pytest.approx(0.5 + 4 * 0.001) and host_s == pytest.approx(0.004)
    # two boundaries of 4 ms and one of 14 for a median of 40
    assert recovered_s == pytest.approx(2 * (0.040 - 0.004) + (0.040 - 0.014))
    assert 0 < recovered_s < interval_s


def test_a_late_return_the_device_sat_idle_through_recovers_nothing(monkeypatch):
    clock = FakeClock(monkeypatch)
    host = drive(clock, monkeypatch, boundaries=12, late={4: 0.5})
    median_s, stalled, recovered_s = stalled_boundaries(host.boundaries)
    assert len(stalled) == 1 and recovered_s == 0.0
    assert all(b[0] == pytest.approx(median_s) for b in host.boundaries[stalled[0] + 1:])


@pytest.mark.parametrize("boundaries, want", [
    ([], (0.0, [], 0.0)),
    # a sound round: the slowest boundary is an ordinary one
    ([(0.20, .01, .01, 0, 16, ""), (0.21, .01, .01, 0, 16, ""), (0.29, .01, .01, 0, 16, "")],
     (0.21, [], 0.0)),
    # long by design: an admission, a grant or a preemption pass ran in it
    ([(0.2, .01, .01, 0, 16, ""), (0.9, .7, .7, 0, 16, "a"), (0.8, .6, .6, 0, 16, "gp"),
      (0.2, .01, .01, 0, 16, "")], (0.5, [], 0.0)),
    # the longest of two stalls is the one whose recovery is read; a short last
    # boundary of fewer steps is not the device catching up
    ([(0.2, .01, .01, 0, 16, ""), (0.5, .01, .01, 0, 16, ""), (0.2, .01, .01, 0, 16, ""),
      (0.9, .01, .01, 1, 16, ""), (0.15, .01, .01, 0, 16, ""), (0.2, .01, .01, 0, 16, ""),
      (0.1, .01, .01, 0, 8, "")], (0.2, [1, 3], 0.05)),
])
def test_the_rule_on_written_boundaries(boundaries, want):
    median_s, stalled, recovered_s = stalled_boundaries(boundaries)
    assert (median_s, stalled) == (pytest.approx(want[0]), want[1])
    assert recovered_s == pytest.approx(want[2])
    assert STALL_FACTOR == 1.5


# ------------------------------------------------ the round's record and warning


def a_round(clock, monkeypatch, **drive_kw):
    marks = RoundMarks()
    host = drive(clock, monkeypatch, **drive_kw)
    stats = accumulate_round_stats(
        None, prefill_s=0.25, prefill_tokens=10, prompt_rows=1, decode_s=host.loop_s,
        gen_tokens=40, gen_rows=1, host=host)
    return host, stats, file_round(marks, stats)


def test_a_sound_round_files_its_record_and_says_nothing(monkeypatch, caplog):
    clock = FakeClock(monkeypatch)
    with caplog.at_level(logging.WARNING, logger=engine_mod.__name__):
        host, stats, record = a_round(clock, monkeypatch)
    assert caplog.records == []
    assert telemetry.round_records() == [record] and record["round"] == 0
    assert set(record) == {
        "round", "t0_ns", "wall_s", "prefill_s", "loop_s", "blocked_s", "readback_s",
        "first_s", "boundaries", "median_s", "stalled", "recovered_s", "programs_built",
        "gc_full_s", "majflt", "pressure_us"}
    assert record["boundaries"] == [list(b) for b in host.boundaries]
    assert (record["stalled"], record["recovered_s"], record["gc_full_s"]) == ([], 0.0, 0.0)
    assert record["prefill_s"] == 0.25 and record["loop_s"] == host.loop_s
    assert record["wall_s"] >= record["loop_s"] and record["programs_built"] == 0
    assert record["pressure_us"] is None or set(record["pressure_us"]) == {"cpu", "memory", "io"}
    # the verdict is in last_round_stats too, beside the older keys
    assert (stats["stalled"], stats["recovered_s"]) == ([], 0.0)
    assert stats["boundary_median_s"] == record["median_s"]
    assert T.ENGINE_STALLED_BOUNDARIES not in telemetry.observe_snapshot()["counters"]


def test_a_stalled_round_says_so_once_with_the_boundarys_account(monkeypatch, caplog):
    clock = FakeClock(monkeypatch)
    with caplog.at_level(logging.WARNING, logger=engine_mod.__name__):
        a_round(clock, monkeypatch)  # round 0: sound
        host, stats, record = a_round(clock, monkeypatch, boundaries=12,
                                      late={4: 0.5, 5: 0.0}, host_sleep={9: 0.1})
    assert record["round"] == 1 and record["stalled"] == [3, 7]
    (said,) = [r.getMessage() for r in caplog.records]
    assert said.startswith(
        "round 1: boundary 3 of 10 stalled: 504.0 ms for a median of 40.0 "
        "(host part 4.0 ms, process CPU 4.0 ms, 0 involuntary switches); "
        "2 stalled in the round; the boundaries after it came back 36.0 ms under the median")
    assert "full collections 0.0 ms, programs built 0" in said
    assert telemetry.observe_snapshot()["counters"][T.ENGINE_STALLED_BOUNDARIES] == 2
    assert stats["stalled"] == [3, 7]


def test_an_engine_without_an_account_files_nothing():
    assert file_round(RoundMarks(), None) is None
    stats = accumulate_round_stats(
        None, prefill_s=0.0, prefill_tokens=0, prompt_rows=0, decode_s=1.0,
        gen_tokens=0, gen_rows=0)  # the remote engine's round: no host account
    assert file_round(RoundMarks(), stats) is None and telemetry.round_records() == []


def test_the_ring_holds_sixty_four_rounds_and_reset_empties_it():
    for i in range(70):
        telemetry.round_filed({"i": i})
    held = telemetry.round_records()
    assert len(held) == telemetry.ROUND_RING == 64
    assert [r["i"] for r in held] == list(range(6, 70))
    assert [r["round"] for r in held] == list(range(6, 70))  # the process's count
    held.clear()  # a copy: the ring is the program's
    assert len(telemetry.round_records()) == 64
    telemetry.reset()
    assert telemetry.round_records() == []
    assert telemetry.round_filed({})["round"] == 0


def test_a_round_that_built_a_program_says_how_many():
    marks = RoundMarks()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    host = RoundHostAccount()
    host.waited(host.t0)
    host.waited(host.t0)
    host.stop()
    stats = accumulate_round_stats(
        None, prefill_s=0.0, prefill_tokens=0, prompt_rows=0, decode_s=host.loop_s,
        gen_tokens=0, gen_rows=0, host=host)
    assert file_round(marks, stats)["programs_built"] >= 1
    assert file_round(RoundMarks(), stats)["programs_built"] == 0


# ------------------------------------------------ host/gc


def gc_spans():
    return [e for e in telemetry._STATE.events if e["name"] == T.HOST_GC]


def test_the_collectors_callback_fires_for_a_full_collection_only():
    before = telemetry.gc_full_ms()  # installs the callback: its first use
    assert gc.callbacks.count(telemetry._on_gc) == 1
    gc.collect(0)
    gc.collect(1)
    assert telemetry.gc_full_ms() == before
    gc.collect()
    gained = telemetry.gc_full_ms() - before
    assert gained > 0
    # the counter holds it once a snapshot folds it in; tracing is off: no span
    assert telemetry.observe_snapshot()["counters"][T.HOST_GC_FULL_MS] == pytest.approx(gained)
    assert telemetry.metrics_snapshot()[T.HOST_GC_FULL_MS] == pytest.approx(gained)
    assert gc_spans() == []
    telemetry.gc_full_ms()
    assert gc.callbacks.count(telemetry._on_gc) == 1  # installed once


def test_a_full_collection_names_a_span_while_tracing_is_on():
    telemetry.configure(True)
    gc.collect(1)
    assert gc_spans() == []

    class Cycle:
        pass

    a, b = Cycle(), Cycle()
    a.other, b.other = b, a
    del a, b
    gc.collect()
    (span,) = gc_spans()
    assert span["ph"] == "X" and span["dur"] >= 1
    assert set(span["args"]) == {"collected", "uncollectable"}
    assert span["args"]["collected"] >= 2 and span["args"]["uncollectable"] == 0
    assert telemetry.gc_full_ms() == pytest.approx(span["dur"] / 1e3, abs=1.0)
    telemetry.configure(False)
    gc.collect()
    assert len(gc_spans()) == 1


def test_a_rounds_gc_seconds_are_the_counters_gain_over_it():
    marks = RoundMarks()
    gc.collect()
    host = RoundHostAccount()
    host.waited(host.t0)
    host.waited(host.t0)
    host.stop()
    stats = accumulate_round_stats(
        None, prefill_s=0.0, prefill_tokens=0, prompt_rows=0, decode_s=host.loop_s,
        gen_tokens=0, gen_rows=0, host=host)
    record = file_round(marks, stats)
    assert record["gc_full_s"] == pytest.approx(telemetry.gc_full_ms() / 1e3) and \
        record["gc_full_s"] > 0


# ------------------------------------------------ a tiny real round on the CPU


@pytest.mark.parametrize("scheduler", ["refill", "waves"])
def test_a_real_round_files_one_record_whose_boundaries_close_its_loop(scheduler):
    engine = PagedGenerationEngine(
        TINY, max_prompt_tokens=16, max_new_tokens=24, eos_token_ids=[1],
        pad_token_id=0, page_size=8, max_concurrent_rows=4, scheduler=scheduler,
        decode_chunk=4)
    params = init_params(jax.random.PRNGKey(0), TINY, dtype=jnp.bfloat16)
    ids = np.random.default_rng(0).integers(2, TINY.vocab_size, size=(6, 16)).astype(np.int32)
    collector_was_on = gc.isenabled()
    out = engine.generate(params, None, ids, np.ones_like(ids),
                          SamplingConfig(max_tokens=24, temperature=0.0, top_p=1.0, n=2),
                          jax.random.PRNGKey(0))
    assert gc.isenabled() == collector_was_on
    (record,) = telemetry.round_records()
    stats = engine.last_round_stats
    intervals = [b[0] for b in record["boundaries"]]
    assert len(intervals) >= 4 and all(s > 0 for s in intervals)
    assert sum(b[4] for b in record["boundaries"]) <= out.steps_dispatched
    # the loop's wall is the first interval, the boundaries, and what follows
    # the last return (the steps still in flight, the readback and the span's end)
    tail_s = record["loop_s"] - record["first_s"] - sum(intervals)
    assert record["readback_s"] <= tail_s + 1e-6
    assert tail_s < record["readback_s"] + 0.25 * record["loop_s"]
    assert record["first_s"] > 0 and record["wall_s"] >= record["loop_s"] + record["prefill_s"]
    # a paged round runs with the collector off: the guard is a reading
    assert record["gc_full_s"] == 0.0
    # the older keys as they were, the new ones beside them
    assert record["loop_s"] == stats["loop_s"] and record["blocked_s"] == stats["host_blocked_s"]
    assert max(intervals) == stats["slowest_boundary_s"]
    assert record["median_s"] == stats["boundary_median_s"] == statistics.median(intervals)
    assert stats["boundaries"] == [tuple(b) for b in record["boundaries"]]
    if scheduler == "refill":
        # twelve candidates through four slots: some boundary held an admission
        assert any("a" in b[5] for b in record["boundaries"])
    else:
        assert all(b[5] == "" for b in record["boundaries"])
    filed = telemetry.metrics_snapshot()
    assert filed[T.ENGINE_BOUNDARY_MEDIAN_MS] == pytest.approx(1e3 * record["median_s"])
    assert filed[f"{T.ENGINE_BOUNDARY_MS}_count"] == len(intervals)
    assert filed[f"{T.ENGINE_BOUNDARY_MS}_max"] == pytest.approx(1e3 * max(intervals))


def test_a_dense_round_files_its_record_too():
    from distrl_llm_tpu.engine.engine import GenerationEngine

    params = init_params(jax.random.PRNGKey(0), TINY)  # f32: the CPU has no bf16 dot
    engine = GenerationEngine(
        TINY, max_prompt_tokens=16, max_new_tokens=24, eos_token_ids=[1],
        pad_token_id=0, decode_chunk=4)
    ids = np.random.default_rng(0).integers(2, TINY.vocab_size, size=(4, 16)).astype(np.int32)
    engine.generate(params, None, ids, np.ones_like(ids),
                    SamplingConfig(max_tokens=24, temperature=0.0, top_p=1.0, n=2),
                    jax.random.PRNGKey(0))
    (record,) = telemetry.round_records()
    assert len(record["boundaries"]) == 24 // 4 - 2
    assert all(b[4] == 4 and b[5] == "" for b in record["boundaries"])
    assert record["median_s"] == engine.last_round_stats["boundary_median_s"]
