"""The reduction from a profiler trace to busy share, time per operation and
named gaps: on a trace small enough to work by hand, on a piece of a real v5e
trace kept under ``perfbench/testdata/``, and the reader of ``.xplane.pb`` on a
trace the CPU writes here."""

import json
import os

import pytest

from perfbench import harness, spec, trace_reduce

US = 1_000  # ns


def plane(events, name="/device:TPU:0", line=trace_reduce.OP_LINE):
    return {"name": name, "lines": [{"name": line, "events": events}]}


def by_hand():
    """One device, a 100 us window. A ``while`` of 40 us holds two fusions of
    10 us with 5 us between them; then, after a 30 us gap, a kernel of 20 us.

        0        10   20 25   35      50              80       100
        |while---[fus]--[fus]---------|      gap       |kernel--|
    """
    return {"planes": [plane([
        ["while.1", 0, 50 * US],
        ["fusion.1", 10 * US, 10 * US],
        ["fusion.1", 25 * US, 10 * US],
        ["paged_kernel", 80 * US, 20 * US],
    ])]}


def test_self_time_busy_union_and_idle_share_by_hand():
    r = trace_reduce.reduce(by_hand(), window_wall_ns=(0, 100 * US))
    # busy is the union of the events with no children: 10 + 10 + 20 us
    assert r["busy_s"] == pytest.approx(40e-6)
    assert r["idle_share"] == pytest.approx(0.60)
    assert r["window_s"] == pytest.approx(100e-6)
    # an operation's time is its self time: the while keeps 50 - 20 = 30 us
    assert r["ops_s"]["while.1"] == pytest.approx(30e-6)
    assert r["ops_s"]["fusion.1"] == pytest.approx(20e-6)
    assert trace_reduce.ranked(r["ops_s"], 1) == [["while.1", pytest.approx(30e-6)]]


def test_gaps_are_named_by_the_innermost_host_span_on_the_wall_clock():
    # the trace's clock starts 1,000,000 ns after the wall clock's origin
    offset = 1_000_000
    spans = [
        ("engine.generate", offset + 0, offset + 60 * US),
        ("engine/admit", offset + 52 * US, offset + 58 * US),  # inside it
        ("driver/reward", offset + 60 * US, offset + 79 * US),
    ]
    r = trace_reduce.reduce(
        by_hand(), window_wall_ns=(offset, offset + 100 * US), host_spans=spans,
        offset_ns=offset,
    )
    gaps = r["gaps_s"]
    # [0,10) and [20,25) lie under engine.generate alone (a gap AT the 5 us
    # floor is still attributed); the middle of [35,80), 57.5 us, lies in
    # engine/admit, the shorter of the two spans that cover it
    assert gaps["engine.generate"] == pytest.approx(15e-6)
    assert gaps["engine/admit"] == pytest.approx(45e-6)
    assert sum(gaps.values()) == pytest.approx(60e-6)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    assert r["devices"][0]["longest_gaps"][0]["host"] == "engine/admit"


def test_short_gaps_are_summed_under_one_name():
    events = [["op", i * 4 * US, 2 * US] for i in range(10)]  # 2 us on, 2 us off
    r = trace_reduce.reduce({"planes": [plane(events)]}, window_wall_ns=(0, 40 * US))
    assert r["busy_s"] == pytest.approx(20e-6)
    assert list(r["gaps_s"]) == [trace_reduce.SHORT_GAPS.format(us=5)]


def test_two_devices_are_averaged_and_other_planes_and_lines_ignored():
    trace = {"planes": [
        plane([["a", 0, 50 * US]], "/device:TPU:0"),
        {"name": "/device:TPU:1", "lines": [
            {"name": trace_reduce.OP_LINE, "events": [["a", 0, 100 * US]]},
            {"name": "XLA Modules", "events": [["x", 0, 100 * US]]},
        ]},
        plane([["py", 0, 100 * US]], "/host:CPU", line="python"),
    ]}
    r = trace_reduce.reduce(trace, window_wall_ns=(0, 100 * US))
    assert [d["idle_share"] for d in r["devices"]] == [pytest.approx(0.5), pytest.approx(0.0)]
    assert r["busy_s"] == pytest.approx(75e-6) and r["idle_share"] == pytest.approx(0.25)
    assert r["ops_s"] == {"a": pytest.approx(75e-6)}


def test_events_are_clipped_to_the_window():
    r = trace_reduce.reduce(by_hand(), window_wall_ns=(30 * US, 90 * US))
    # fusion [25,35) keeps 5 us, the kernel [80,100) keeps 10 us
    assert r["busy_s"] == pytest.approx(15e-6)
    assert r["window_s"] == pytest.approx(60e-6)


def test_no_device_plane_no_numbers():
    r = trace_reduce.reduce({"planes": [plane([["x", 0, 5]], "/host:CPU")]})
    assert r["devices"] == []


def test_sync_offset():
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [[harness.SYNC_EVENT, 42_000, 900]]}]}]}
    assert trace_reduce.sync_offset_ns(trace, harness.SYNC_EVENT, 1_000_042_000) == 10**9
    with pytest.raises(LookupError):
        trace_reduce.sync_offset_ns(by_hand(), harness.SYNC_EVENT, 0)


def test_reading_an_xplane_the_cpu_writes(tmp_path):
    """``load_xplane`` on a real ``.xplane.pb``: the host plane keeps the sync
    annotation only, and a CPU trace has no device plane to reduce."""
    import glob

    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation(harness.SYNC_EVENT):
        pass
    jax.jit(lambda x: (x @ x).sum())(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    trace = trace_reduce.load_xplane(path, keep_host_events=(harness.SYNC_EVENT,))
    host = [p for p in trace["planes"] if p["name"] == trace_reduce.HOST_PLANE]
    kept = [e[0] for p in host for line in p["lines"] for e in line["events"]]
    assert kept == [harness.SYNC_EVENT]
    assert trace_reduce.sync_offset_ns(trace, harness.SYNC_EVENT, 10**18) > 0
    assert trace_reduce.reduce(trace)["devices"] == []
    assert trace_reduce.describe(trace)


# ------------------------------------------------- a piece of a real v5e trace

TESTDATA = os.path.join(spec.ROOT, "perfbench", "testdata")


def recorded(name):
    with open(os.path.join(TESTDATA, name), encoding="utf-8") as f:
        return json.load(f)


def test_recorded_decode_step_of_the_7b_rollout_cell():
    """30 ms (one decode step at 64 slots) cut from this PR's traced run of
    qwen2.5-7b-L14.rollout-lockstep on the v5e. Worked by hand with plain loops
    over the file, not with trace_reduce: 1,252 operations from 0 to
    29,692,298 ns whose durations sum to 29,685,606 ns with no nesting, so the
    device is busy 99.98% of the window; paged attention's fourteen calls take
    8,581,318 ns, the most of any operation, 28.9% of busy."""
    trace = recorded("v5e_rollout_decode_step.json")
    r = trace_reduce.reduce(trace)
    assert r["window_s"] == pytest.approx(29_692_298e-9)
    assert r["busy_s"] == pytest.approx(29_685_606e-9, rel=1e-3)
    assert r["idle_share"] == pytest.approx(0.0002, abs=0.0002)
    (top, seconds), (second, _) = trace_reduce.ranked(r["ops_s"], 2)
    assert top == "%paged_attention_native bf16[64,4,7,128]"
    assert seconds == pytest.approx(8_581_318e-9, rel=1e-6)
    assert second == "%copy bf16[4,369,128,128]"  # the KV pool, copied every step
    # the metric files' regexes name the kernels as this trace shows them
    from perfbench.readers import trace_ops

    for metric, share in (("kernel.paged_attn_share", 28.9), ("kernel.sampler_share", 1.9)):
        held = spec.load_layer_metric(("perfbench",), metric)
        got = trace_ops.read({"trace": r}, held["args"], None)
        assert got == pytest.approx(share, abs=0.05), metric
    # the other lines of the plane (modules, asynchronous copies) are not operations
    lines = {line["name"] for p in trace["planes"] for line in p["lines"]}
    assert {"XLA Modules", "Async XLA Ops", trace_reduce.OP_LINE} <= lines


def test_recorded_cross_entropy_chunks_of_the_7b_learner_cell():
    """42 ms of the learner's chunked cross-entropy: a ``while`` of 38.9 ms
    holds the chunk's fusions (those that start in the first 30 ms: the cut
    dropped the rest), so its own time is what is left beside them, 13.0 ms;
    the self times add up to the whole span once, not twice; and busy time,
    the union of the leaves, and the gaps make the window."""
    r = trace_reduce.reduce(recorded("v5e_learner_cross_entropy.json"))
    loop = next(n for n in r["ops_s"] if n.startswith("%while "))
    assert r["ops_s"][loop] == pytest.approx(12_973_932e-9, rel=1e-6)
    assert sum(r["ops_s"].values()) == pytest.approx(r["window_s"], rel=1e-3)
    assert r["busy_s"] == pytest.approx(29_361_223e-9, rel=1e-6)
    assert r["busy_s"] + sum(r["gaps_s"].values()) == pytest.approx(r["window_s"])


@pytest.mark.parametrize("text,want", [
    ("%fusion.1367 = f32[1867776]{0:T(1024)S(1)} fusion(f32[127]{0:T(128)S(1)} %gte.1), kind=kCustom",
     "%fusion f32[1867776]"),
    ("%paged_attention_native.27 = bf16[64,4,7,128]{3,2,1,0:T(8,128)(2,1)S(1)} custom-call(s32[64]{0} %c)",
     "%paged_attention_native bf16[64,4,7,128]"),
    ("%_unknown_.1 = (s32[64]{0:T(128)S(1)}, f32[64]{0:T(128)S(1)}) custom-call(f32[1,1]{1,0} %b)",
     "%_unknown_ (s32[64], f32[64])"),
    ("%all-reduce-start.3 = f32[8]{0} all-reduce-start(f32[8]{0} %x)", "%all-reduce-start f32[8]"),
    ("281", "281"),
])
def test_op_name(text, want):
    assert trace_reduce.op_name(text) == want
