"""One timeline (ISSUE 24): the device programs carry the scope vocabulary, the
round and the step record their sub-spans on the host, a running profile holds
the same spans on its own clock, and with tracing off none of it costs a thing.

Everything here runs at tiny size on the CPU: names and counts, never a time.
"""

import glob
import os
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.config import SamplingConfig
from distrl_llm_tpu.engine import engine as engine_mod
from distrl_llm_tpu.engine.engine import (
    GenerationEngine,
    RoundHostAccount,
    run_decode_loop,
)
from distrl_llm_tpu.engine.paged_engine import PagedGenerationEngine
from distrl_llm_tpu.models import TINY, init_lora_params, init_params

T = telemetry  # the names are read a lot below


@pytest.fixture(autouse=True)
def fresh_telemetry():
    telemetry.reset()
    telemetry.configure(False)
    yield
    telemetry.reset()
    telemetry.configure(False)


@pytest.fixture(scope="module")
def tiny_params():
    return init_params(jax.random.PRNGKey(0), TINY, dtype=jnp.bfloat16)


def prompts(b=4, width=16, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(2, TINY.vocab_size, size=(b, width)).astype(np.int32)
    mask = np.ones((b, width), np.int32)
    for i in range(b):
        pad = int(rng.integers(0, 9))
        ids[i, :pad] = 0
        mask[i, :pad] = 0
    return ids, mask


def paged_engine(rows=4, max_new=24, pool=0, **more):
    return PagedGenerationEngine(
        TINY, max_prompt_tokens=16, max_new_tokens=max_new,
        eos_token_ids=[1], pad_token_id=0, page_size=8,
        max_concurrent_rows=rows, scheduler="refill", max_kv_pages=pool,
        decode_chunk=4, **more,
    )


def dense_engine(max_new=12, **more):
    return GenerationEngine(
        TINY, max_prompt_tokens=16, max_new_tokens=max_new,
        eos_token_ids=[1], pad_token_id=0, decode_chunk=4, **more,
    )


# ------------------------------------------------- (i) scopes in the programs


class _Lowered:
    """Stands in for a jitted program of an engine: lowers the first call's
    arguments to text with locations, then runs the program as before."""

    def __init__(self, fn):
        self.fn = fn
        self.text = None

    def __call__(self, *args, **kwargs):
        if self.text is None:
            self.text = self.fn.lower(*args, **kwargs).as_text(debug_info=True)
        return self.fn(*args, **kwargs)


MODEL = (T.MODEL_EMBED, T.MODEL_ATTN_PROJ, T.MODEL_ATTN_CORE, T.MODEL_MLP, T.MODEL_HEAD)
STEP = MODEL + (T.ENGINE_KV_WRITE, T.ENGINE_SAMPLE, T.ENGINE_BOOKKEEPING)


def _train_step_text():
    from distrl_llm_tpu.learner import UpdateBatch, make_optimizer, make_train_step

    base = init_params(jax.random.PRNGKey(0), TINY)
    lora = init_lora_params(jax.random.PRNGKey(1), TINY, rank=4)
    rng = np.random.default_rng(0)
    n, p, t = 4, 6, 8
    ids = rng.integers(1, TINY.vocab_size, size=(n, p + t))
    batch = UpdateBatch(
        prompt_ids=jnp.asarray(ids[:, :p]), prompt_mask=jnp.ones((n, p), jnp.int32),
        answer_ids=jnp.asarray(ids[:, p:]), answer_mask=jnp.ones((n, t), jnp.int32),
        coeffs=jnp.asarray(rng.normal(size=n), jnp.float32),
        sample_mask=jnp.ones(n, jnp.float32),
    )
    optimizer = make_optimizer(1e-2, use_8bit=True)
    step = make_train_step(
        TINY, learner_type="pg", optimizer=optimizer, lora_scale=0.5,
        micro_size=2, remat=True, donate=False, logit_chunk=4,
    )
    return step.lower(lora, optimizer.init(lora), base, batch).as_text(debug_info=True)


def _dense_step_text():
    eng = dense_engine()
    fns = {}
    real = eng._fns_for_bucket

    def wrapped(bucket):
        if bucket not in fns:
            prefill, step = real(bucket)
            fns[bucket] = (_Lowered(prefill), _Lowered(step))
        return fns[bucket]

    eng._fns_for_bucket = wrapped
    ids, mask = prompts()
    params = init_params(jax.random.PRNGKey(0), TINY)  # f32: the CPU has no bf16 dot
    eng.generate(params, None, ids, mask,
                 SamplingConfig(max_tokens=4, temperature=1.0, top_p=0.9, n=2),
                 jax.random.PRNGKey(3))
    (prefill, step), = fns.values()
    return {"dense_prefill": prefill.text, "dense_decode_step": step.text}


def _refill_texts():
    eng = paged_engine()
    eng._refill_step = _Lowered(eng._refill_step)
    eng._refill_admit = _Lowered(eng._refill_admit)
    eng._prefill = _Lowered(eng._prefill)
    ids, mask = prompts(b=4)
    params = init_params(jax.random.PRNGKey(0), TINY, dtype=jnp.bfloat16)
    eng.generate(params, None, ids, mask,
                 SamplingConfig(max_tokens=6, temperature=1.0, top_p=0.9, n=2),
                 jax.random.PRNGKey(3))
    return {"refill_step": eng._refill_step.text, "refill_admit": eng._refill_admit.text,
            "paged_prefill": eng._prefill.text}


@pytest.fixture(scope="module")
def program_texts():
    texts = {"train_step": _train_step_text()}
    texts.update(_dense_step_text())
    texts.update(_refill_texts())
    return texts


@pytest.mark.parametrize("program, names", [
    ("train_step", MODEL[:4] + (
        T.LEARNER_LOSS, T.LEARNER_LOSS_LOGPROB, T.LEARNER_GRAD_ACCUM,
        T.LEARNER_OPTIMIZER, T.LEARNER_OPTIMIZER_CODEC, T.MODEL_HEAD,
    )),
    ("dense_decode_step", STEP),
    ("dense_prefill", MODEL + (T.ENGINE_KV_WRITE, T.ENGINE_BOOKKEEPING)),
    ("refill_step", STEP),
    ("refill_admit", (T.ENGINE_ADMIT,)),
    ("paged_prefill", MODEL + (T.ENGINE_KV_WRITE, T.ENGINE_BOOKKEEPING)),
])
def test_lowered_program_carries_the_names_it_claims(program_texts, program, names):
    text = program_texts[program]
    assert text is not None, f"{program} was never dispatched"
    for name in names:
        assert f"{name}/" in text, (program, name)
    assert set(names) <= set(telemetry.SCOPE_NAMES)


def test_train_step_tells_forward_recompute_and_backward_apart(program_texts):
    """JAX writes the rest of the path: the reader's three phases are there."""
    text = program_texts["train_step"]
    assert f"jvp({T.LEARNER_LOSS})" in text
    assert f"transpose(jvp({T.LEARNER_LOSS}))" in text
    assert "rematted_computation" in text


def test_paged_kernel_is_scoped_outside_its_own_jit():
    """``kernel/paged_attention`` sits round the CALL of the jitted kernel, so
    the component next to ``pallas_call`` stays ``jit(paged_attention_native)``:
    the TPU compiler names the custom call after it, and the benchmark finds
    the kernel by that name."""
    from functools import partial

    from distrl_llm_tpu.ops.paged import _native_call

    q = jnp.zeros((2, 4, 32), jnp.float32)
    pages = jnp.zeros((2, 6, 8, 32), jnp.float32)
    text = jax.jit(partial(_native_call, quantized=False, interpret=True)).lower(
        q, pages, pages, jnp.array([3, 9], jnp.int32),
        jnp.arange(6, dtype=jnp.int32).reshape(2, 3),
    ).as_text(debug_info=True)
    assert f"{T.KERNEL_PAGED_ATTENTION}/jit(paged_attention_native)" in text


@pytest.mark.parametrize("impl, scoped", [("interpret", False), ("xla", True)])
def test_fused_sampler_stays_outside_every_scope(impl, scoped):
    """A scope round an INLINE pallas_call is not metadata-only (it renames the
    custom call and re-keys the program), so the sampling entry point names
    its multi-pass path and leaves the fused kernel bare."""
    from distrl_llm_tpu.ops.sampling import sample_with_logprob

    text = jax.jit(
        lambda k, x: sample_with_logprob(k, x, 1.0, 0.9, capture_logprob=True, impl=impl)
    ).lower(jax.random.PRNGKey(0), jnp.zeros((4, 256), jnp.float32)).as_text(debug_info=True)
    assert (f"{T.ENGINE_SAMPLE}/" in text) is scoped
    assert "kernel/" not in text


def test_no_pallas_call_is_given_a_name():
    import inspect

    from distrl_llm_tpu.ops import paged_native, quant_matmul, sampling

    for module in (paged_native, quant_matmul, sampling):
        source = inspect.getsource(module)
        assert "pallas_call(" in source
        assert not [line for line in source.splitlines()
                    if line.strip().startswith("name=")]


# --------------------------------------------- (ii), (iii) spans on the host


def spans(events=None):
    events = telemetry.recent_events(100_000) if events is None else events
    return [e for e in events if e.get("ph") == "X"]


def inside(child, parent):
    return (parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1)


GAUGES = (T.ENGINE_HOST_BUSY_SHARE, T.ENGINE_SLOWEST_BOUNDARY_MS,
          T.ENGINE_SLOWEST_BOUNDARY_HOST_MS)
#: every span name that is NOT the launch's: at most once a host boundary
PER_BOUNDARY = (T.ENGINE_ADMIT, T.ENGINE_SNAPSHOT_WAIT, T.ENGINE_SNAPSHOT_LAUNCH,
                T.ENGINE_GRANT, T.ENGINE_PREEMPT)


def by_names():
    out = {}
    for e in spans():
        out.setdefault(e["name"], []).append(e)
    return out


def assert_launches(launches, loop_span, steps, sizes=None):
    """The new rule: one ``engine/dispatch`` a launched program, each inside
    the loop's span, ``step`` rising from 0 and ``steps`` summing to the
    round's (``sizes``: what each launch ran, all 1 where not given)."""
    assert all(set(e["args"]) == {"step", "steps"} for e in launches)
    assert all(inside(e, loop_span) for e in launches)
    ran = [e["args"]["steps"] for e in launches]
    assert ran == (sizes if sizes is not None else [1] * steps)
    assert sum(ran) == steps
    first = [e["args"]["step"] for e in launches]
    assert first == [sum(ran[:i]) for i in range(len(ran))]


def run_paged_round(params, *, rows=4, b=6, max_tokens=24, pool=0, **more):
    eng = paged_engine(rows=rows, max_new=max_tokens, pool=pool, **more)
    ids, mask = prompts(b=b)
    out = eng.generate(
        params, None, ids, mask,
        SamplingConfig(max_tokens=max_tokens, temperature=0.0, top_p=1.0, n=2),
        jax.random.PRNGKey(0),
    )
    return eng, out


def test_paged_round_records_its_sub_spans_nested_and_per_boundary(tiny_params):
    telemetry.configure(True)
    _, out = run_paged_round(tiny_params)
    by_name = by_names()
    (round_span,) = by_name[T.ENGINE_REFILL_DECODE]
    for name in (T.ENGINE_SETUP, T.ENGINE_ADMIT, T.ENGINE_SNAPSHOT_WAIT, T.ENGINE_READBACK):
        assert by_name.get(name), name
        assert all(inside(e, round_span) for e in by_name[name]), name
    assert len(by_name[T.ENGINE_SETUP]) == len(by_name[T.ENGINE_READBACK]) == 1
    # 12 candidates through 4 slots: more than one admission pass, each with
    # its integer args, and at least one that admitted slots
    admits = by_name[T.ENGINE_ADMIT]
    assert len(admits) >= 2
    assert all(set(e["args"]) == {"groups", "slots"} for e in admits)
    assert sum(e["args"]["slots"] for e in admits) == 12
    # per decode step the launch's span and nothing else: exactly one
    # `engine/dispatch` a dispatched step; a boundary is `check` = 4 steps
    steps = round_span["args"]["steps"]
    assert steps == out.steps_dispatched
    boundaries = -(-steps // 4)
    assert steps > 2 * boundaries
    assert_launches(by_name[T.ENGINE_DISPATCH], round_span, steps)
    for name in (T.ENGINE_ADMIT, T.ENGINE_SNAPSHOT_WAIT, T.ENGINE_SNAPSHOT_LAUNCH):
        assert len(by_name[name]) <= boundaries + 1, (name, steps)
    # the refill loop's snapshot launches: one a boundary inside the round's
    # span, one more than its waits, and (no chunk program here) none fused
    launched = by_name[T.ENGINE_SNAPSHOT_LAUNCH]
    assert all(inside(e, round_span) for e in launched)
    assert len(launched) == len(by_name[T.ENGINE_SNAPSHOT_WAIT]) + 1
    assert all(e["args"] == {"fused": False} for e in launched)
    program = [e for e in spans() if not e["name"].startswith(T.COMPILE_PREFIX + "/")
               and e["name"] != T.ENGINE_DISPATCH]
    assert len(program) <= 4 * boundaries + 8


def test_budgeted_round_names_its_grant_passes_and_preemptions(tiny_params):
    telemetry.configure(True)
    eng, _ = run_paged_round(tiny_params, pool=12)
    assert eng.last_pool_stats["budgeted"]
    by_name = by_names()
    (round_span,) = by_name[T.ENGINE_REFILL_DECODE]
    assert by_name.get(T.ENGINE_GRANT)
    assert all(inside(e, round_span) for e in by_name[T.ENGINE_GRANT])
    # one span per eviction attempt (an occupant that finished meanwhile is
    # not counted by the pool as a preemption)
    assert len(by_name.get(T.ENGINE_PREEMPT, [])) >= eng.last_pool_stats["preemptions"] > 0
    for e in by_name.get(T.ENGINE_PREEMPT, []):
        assert any(inside(e, g) for g in by_name[T.ENGINE_GRANT])


def test_dense_round_records_setup_snapshot_wait_and_readback():
    params = init_params(jax.random.PRNGKey(0), TINY)  # f32: the CPU has no bf16 dot
    telemetry.configure(True)
    eng = dense_engine()
    ids, mask = prompts()
    eng.generate(params, None, ids, mask,
                 SamplingConfig(max_tokens=12, temperature=0.0, top_p=1.0, n=2),
                 jax.random.PRNGKey(0))
    by_name = by_names()
    for name in (T.ENGINE_SETUP, T.ENGINE_PREFILL, T.ENGINE_DECODE, T.ENGINE_READBACK):
        assert len(by_name.get(name, [])) == 1, name
    (decode,) = by_name[T.ENGINE_DECODE]
    assert inside(by_name[T.ENGINE_READBACK][0], decode)
    waits = by_name.get(T.ENGINE_SNAPSHOT_WAIT, [])
    assert waits and all(inside(e, decode) for e in waits)
    assert len(waits) <= -(-decode["args"]["steps"] // 4)
    # the dense loop is the shared one: a launch a step, nothing else per step
    assert decode["args"]["steps"] == 12
    assert_launches(by_name[T.ENGINE_DISPATCH], decode, 12)
    assert {n for n in by_name if n.startswith("engine/")} == {
        T.ENGINE_SETUP, T.ENGINE_PREFILL, T.ENGINE_DECODE, T.ENGINE_READBACK,
        T.ENGINE_SNAPSHOT_WAIT, T.ENGINE_SNAPSHOT_LAUNCH, T.ENGINE_DISPATCH}
    # the boundary's own launches (the flags' copy and its transfer): one span
    # a boundary inside the loop's, one more than the waits (the first
    # snapshot is launched before anything is waited for)
    launched = by_name[T.ENGINE_SNAPSHOT_LAUNCH]
    assert all(inside(e, decode) for e in launched)
    assert len(launched) == -(-decode["args"]["steps"] // 4) == len(waits) + 1


@pytest.mark.parametrize("engine_kind", ["dense", "paged_wave"])
def test_chunked_round_records_a_launch_a_chunk_and_the_tail_per_step(engine_kind):
    """``scan_chunk`` 4 over 14 steps: three launches of 4 steps and the
    non-divisor tail of two, one step a launch."""
    params = init_params(jax.random.PRNGKey(0), TINY)  # f32: the CPU has no bf16 dot
    telemetry.configure(True)
    if engine_kind == "dense":
        eng = dense_engine(max_new=14, scan_chunk=4)
    else:
        eng = PagedGenerationEngine(
            TINY, max_prompt_tokens=16, max_new_tokens=14, eos_token_ids=[1],
            pad_token_id=0, page_size=8, decode_chunk=4, scan_chunk=4,
        )
    ids, mask = prompts()
    eng.generate(params, None, ids, mask,
                 SamplingConfig(max_tokens=14, temperature=0.0, top_p=1.0, n=2),
                 jax.random.PRNGKey(0))
    assert eng.scan_chunk_active
    by_name = by_names()
    (decode,) = by_name[T.ENGINE_DECODE]
    assert decode["args"]["steps"] == 14
    assert_launches(by_name[T.ENGINE_DISPATCH], decode, 14, sizes=[4, 4, 4, 1, 1])


def test_chunked_refill_round_records_a_launch_a_chunk(tiny_params):
    """The refill loop's chunk launch site: ``check`` = 4 is one chunk of 4."""
    telemetry.configure(True)
    eng, out = run_paged_round(tiny_params, scan_chunk=4)
    assert eng.scan_chunk_active
    by_name = by_names()
    (round_span,) = by_name[T.ENGINE_REFILL_DECODE]
    steps = out.steps_dispatched
    assert steps % 4 == 0
    assert_launches(by_name[T.ENGINE_DISPATCH], round_span, steps,
                    sizes=[4] * (steps // 4))
    for name in PER_BOUNDARY:
        assert len(by_name.get(name, [])) <= steps // 4 + 1, name
    # the snapshot rode inside the chunk's dispatch: the span holds the two
    # transfers' launches alone, and says so
    assert all(e["args"] == {"fused": True} for e in by_name[T.ENGINE_SNAPSHOT_LAUNCH])


def test_tracing_off_records_nothing_and_spans_are_the_singleton(tiny_params):
    assert telemetry.span(T.ENGINE_ADMIT, groups=1) is telemetry._NULL_SPAN
    assert telemetry.span(T.ENGINE_DISPATCH, step=0, steps=1) is telemetry._NULL_SPAN
    eng, _ = run_paged_round(tiny_params)
    assert telemetry.recent_events(100_000) == []
    assert telemetry.span(T.DRIVER_PUSH, version=1) is telemetry._NULL_SPAN
    # the round's host account is filed all the same: the three gauges reach
    # any sink through metrics_snapshot(), and last_round_stats holds the sums
    filed = telemetry.metrics_snapshot()
    assert set(GAUGES) <= set(filed)
    assert 0.0 <= filed[T.ENGINE_HOST_BUSY_SHARE] <= 100.0
    assert 0.0 <= filed[T.ENGINE_SLOWEST_BOUNDARY_HOST_MS] <= filed[T.ENGINE_SLOWEST_BOUNDARY_MS]
    stats = eng.last_round_stats
    assert 0.0 < stats["host_blocked_s"] < stats["loop_s"]
    assert filed[T.ENGINE_SLOWEST_BOUNDARY_MS] == pytest.approx(1e3 * stats["slowest_boundary_s"])
    assert telemetry.observe_snapshot()["gauges"][T.ENGINE_HOST_BUSY_SHARE] == pytest.approx(
        100.0 * (1.0 - stats["host_blocked_s"] / stats["loop_s"]))


def test_dense_round_files_the_three_gauges_with_tracing_off():
    params = init_params(jax.random.PRNGKey(0), TINY)  # f32: the CPU has no bf16 dot
    eng = dense_engine()
    ids, mask = prompts()
    eng.generate(params, None, ids, mask,
                 SamplingConfig(max_tokens=12, temperature=0.0, top_p=1.0, n=2),
                 jax.random.PRNGKey(0))
    assert telemetry.recent_events(100_000) == []
    assert set(GAUGES) <= set(telemetry.observe_snapshot()["gauges"])
    assert eng.last_round_stats["loop_s"] > 0.0


def test_trace_report_prints_the_host_account_of_each_round_kind(tiny_params, tmp_path):
    import re

    from tools import trace_report

    eng, _ = run_paged_round(tiny_params)  # warm-up: no compile/ span in the traced round
    telemetry.configure(True)
    ids, mask = prompts(b=6)
    eng.generate(tiny_params, None, ids, mask,
                 SamplingConfig(max_tokens=24, temperature=0.0, top_p=1.0, n=2),
                 jax.random.PRNGKey(0))
    path = telemetry.export_chrome_trace(str(tmp_path / "trace.json"), clear=False)
    events, metadata = trace_report.load_trace(path)
    lines = trace_report.build_report(events, metadata).splitlines()
    (at,) = [i for i, line in enumerate(lines) if line.split()[:1] == [T.ENGINE_REFILL_DECODE]]
    said = re.fullmatch(
        r"    host s: launches ([\d.]+), waits ([\d.]+), snapshot_launch ([\d.]+), "
        r"admissions ([\d.]+), readback ([\d.]+), self ([\d.]+)", lines[at + 1])
    assert said, lines[at + 1]
    by_name = by_names()
    (round_span,) = by_name[T.ENGINE_REFILL_DECODE]
    launches, waits, snapshots, admissions, readback, own = map(float, said.groups())
    assert launches == pytest.approx(sum(e["dur"] for e in by_name[T.ENGINE_DISPATCH]) / 1e6, abs=1e-3)
    assert waits == pytest.approx(sum(e["dur"] for e in by_name[T.ENGINE_SNAPSHOT_WAIT]) / 1e6, abs=1e-3)
    setup = by_name[T.ENGINE_SETUP][0]["dur"] / 1e6
    assert snapshots == pytest.approx(
        sum(e["dur"] for e in by_name[T.ENGINE_SNAPSHOT_LAUNCH]) / 1e6, abs=1e-3)
    assert launches + waits + snapshots + admissions + readback + own + setup == pytest.approx(
        round_span["dur"] / 1e6, abs=5e-3)
    assert sum(line.startswith("    host s:") for line in lines) == 1  # one a round kind


# ------------------------------------------ the round's host account (ISSUE 38)


def clock_reads_in_the_engines(monkeypatch):
    """Counts ``time.perf_counter`` calls made from ``distrl_llm_tpu/engine/``."""
    reads = []
    real = time.perf_counter

    def counting():
        caller = sys._getframe(1).f_code.co_filename
        if os.sep + os.path.join("distrl_llm_tpu", "engine") + os.sep in caller:
            reads.append(caller)
        return real()

    monkeypatch.setattr(time, "perf_counter", counting)
    return reads


def test_the_decode_loops_read_the_clock_per_boundary_never_per_step(
        tiny_params, monkeypatch):
    reads = clock_reads_in_the_engines(monkeypatch)
    eng, out = run_paged_round(tiny_params)
    steps = out.steps_dispatched
    boundaries = -(-steps // 4)
    assert steps > 2 * boundaries
    # two reads a boundary (the wait's start and its return) and a round's few
    assert 2 * (boundaries - 2) <= len(reads) <= 2 * boundaries + 12 < steps
    # the same for the loop the dense and wave engines share
    del reads[:]
    fake = types.SimpleNamespace(done=jnp.zeros(3, bool))
    run_decode_loop(lambda s: s, fake, 40, 4, host=RoundHostAccount())
    assert len(reads) == 1 + 2 * 9  # ten boundaries, nine waits, one account


class LateFlags:
    """Done flags whose host read takes ``late`` seconds: a device that is late."""

    def __init__(self, late=0.0):
        self.late = late

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.late)
        return np.zeros(3, bool)


@pytest.mark.parametrize("stall", ["host", "device"])
def test_the_boundary_account_tells_a_stalled_host_from_a_late_device(stall, monkeypatch):
    """Ten boundaries of four steps; in the sixth either the host sleeps
    between two launches or the snapshot's read is late by as much."""
    monkeypatch.setattr(engine_mod.jnp, "copy", lambda flags: flags)
    calls = [0]

    def step_fn(state):
        calls[0] += 1
        if calls[0] == 22 and stall == "host":
            time.sleep(0.2)
        if calls[0] % 4:
            return state
        late = 0.2 if calls[0] == 24 and stall == "device" else 0.0
        return types.SimpleNamespace(done=LateFlags(late))

    host = RoundHostAccount()
    run_decode_loop(step_fn, types.SimpleNamespace(done=LateFlags()), 40, 4, host=host)
    loop_s = host.stop()
    assert calls[0] == 40
    assert 0.2 <= host.slowest_s <= loop_s
    if stall == "host":
        assert host.slowest_host_s >= 0.2 and host.blocked_s < 0.1
    else:
        assert host.slowest_host_s < 0.1 and host.blocked_s >= 0.2
    stats = engine_mod.accumulate_round_stats(
        None, prefill_s=0.0, prefill_tokens=0, prompt_rows=0, decode_s=loop_s,
        gen_tokens=0, gen_rows=0, host=host)
    gauges = telemetry.observe_snapshot()["gauges"]
    assert gauges[T.ENGINE_SLOWEST_BOUNDARY_MS] == pytest.approx(1e3 * host.slowest_s)
    assert gauges[T.ENGINE_SLOWEST_BOUNDARY_HOST_MS] == pytest.approx(1e3 * host.slowest_host_s)
    share = gauges[T.ENGINE_HOST_BUSY_SHARE]
    assert share == pytest.approx(100.0 * (1.0 - host.blocked_s / loop_s))
    assert (share > 50.0) is (stall == "host")
    # a second wave of the round: walls sum, the longest boundary is the maximum
    calm = RoundHostAccount()
    calm.loop_s, calm.blocked_s, calm.slowest_s, calm.slowest_host_s = 1.0, 0.5, 0.01, 0.001
    engine_mod.accumulate_round_stats(
        stats, prefill_s=0.0, prefill_tokens=0, prompt_rows=0, decode_s=1.0,
        gen_tokens=0, gen_rows=0, host=calm)
    assert stats["loop_s"] == pytest.approx(loop_s + 1.0)
    assert stats["slowest_boundary_s"] == host.slowest_s
    assert stats["slowest_boundary_host_s"] == host.slowest_host_s


def test_trainer_step_records_its_sub_spans_inside_their_phases():
    from test_trainer import make_trainer

    from distrl_llm_tpu.metrics import MemorySink

    sink = MemorySink()
    trainer = make_trainer(sink=sink, learner="grpo")
    telemetry.configure(True)
    trainer.train()
    telemetry.configure(False)
    by_name = {}
    for e in spans():
        by_name.setdefault(e["name"], []).append(e)
    steps = trainer.total_batch_steps
    assert steps >= 1
    for name in (T.DRIVER_SHAPING, T.DRIVER_UPDATE_BATCH, T.DRIVER_UPDATE_STEP, T.DRIVER_LOG):
        assert len(by_name.get(name, [])) == steps, name
    # one push before the first round, one after every update
    pushes = by_name[T.DRIVER_PUSH]
    assert len(pushes) == steps
    assert all(e["args"]["mode"] == "timeshared" for e in pushes)
    assert [e["args"]["version"] for e in pushes] == list(range(1, steps + 1))
    updates = by_name["driver/update"]
    for name in (T.DRIVER_UPDATE_BATCH, T.DRIVER_UPDATE_STEP):
        for e in by_name[name]:
            assert any(inside(e, u) for u in updates), name
    # the sink's record keeps exactly the keys it had: no sub-span leaks in
    record = sink.records[-1][1] if hasattr(sink, "records") else None
    if record is not None:
        assert not [k for k in record if k.startswith("timing/") and k not in (
            "timing/generation_duration", "timing/reward_duration",
            "timing/update_duration")]


# ------------------------------------------------ compile events as spans


def test_a_compile_while_tracing_is_a_span_named_by_the_program():
    telemetry.configure(True)

    def a_program_of_this_test(x):
        return x * 3 + 1

    jax.jit(a_program_of_this_test)(jnp.ones(7)).block_until_ready()
    built = [e for e in spans() if e["name"].startswith(T.COMPILE_PREFIX + "/")
             and "a_program_of_this_test" in e["name"]]
    assert len(built) == 1
    assert built[0]["dur"] >= 1
    telemetry.configure(False)
    before = len(telemetry.recent_events(100_000))

    def another_program_of_this_test(x):
        return x * 5 + 1

    jax.jit(another_program_of_this_test)(jnp.ones(7)).block_until_ready()
    assert len(telemetry.recent_events(100_000)) == before


def test_chip_smoke_uses_the_programs_one_compile_log():
    import chip_smoke

    assert chip_smoke.CompileLog is telemetry.CompileLog
    log = telemetry.CompileLog()
    mark = log.mark()
    jax.jit(lambda x: x - 11)(jnp.ones(5)).block_until_ready()
    assert log.since(mark)["programs"] >= 1
    log.close()


# ------------------------------------ (iv) one clock: spans in a live profile


def test_a_span_shows_on_the_host_plane_of_a_running_profile(tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    telemetry.configure(True)
    with telemetry.span(T.DRIVER_PUSH, version=7):
        with telemetry.span(T.ENGINE_SNAPSHOT_WAIT):
            jax.jit(lambda x: (x @ x).sum())(jnp.ones((32, 32))).block_until_ready()
    telemetry.configure(False)
    with telemetry.span(T.ENGINE_READBACK):  # off: the singleton annotates nothing
        pass
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    found = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in (T.DRIVER_PUSH, T.ENGINE_SNAPSHOT_WAIT, T.ENGINE_READBACK):
                    found[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    assert set(found) == {T.DRIVER_PUSH, T.ENGINE_SNAPSHOT_WAIT}
    outer, inner = found[T.DRIVER_PUSH], found[T.ENGINE_SNAPSHOT_WAIT]
    assert outer[0] <= inner[0] and inner[1] <= outer[1]
    # and the same two spans are in the program's own record, as before
    assert {e["name"] for e in spans()} >= {T.DRIVER_PUSH, T.ENGINE_SNAPSHOT_WAIT}
