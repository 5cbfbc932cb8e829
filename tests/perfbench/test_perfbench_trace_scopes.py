"""Device time by scope name (``perfbench/trace_scopes.py``) and the two readers
PR 24 added: the path classifier on paths JAX really writes, the protobuf
decoder on a hand-encoded ``XSpace``, the table on pieces of real v5e traces of
``rollout-lockstep`` and ``learner-1k`` kept with their scope paths under
``perfbench/testdata/``, and the stale-cache case that must yield no table."""

import json
import os
from types import SimpleNamespace

import pytest

from perfbench import spec, trace_reduce, trace_scopes
from tiny_spec import real_benchmark, tiny_benchmark

TESTDATA = os.path.join(spec.ROOT, "perfbench", "testdata")
BENCH = real_benchmark()
#: the names device time is summed under: data, found over the benchmark's paths
VOCABULARY = spec.load_scope_names(BENCH["paths"])
TINY_VOCABULARY = spec.load_scope_names(tiny_benchmark()["paths"])
NEW = [
    "model.attn_proj_share", "model.mlp_share", "model.head_share",
    "engine.kv_write_share", "rollout.unscoped_share", "learner.unscoped_share",
    "rl_step.unscoped_share", "learner.forward_share", "learner.recompute_share",
    "learner.backward_share", "learner.optimizer_share", "engine.admit_host_ms",
    "engine.snapshot_wait_ms", "trainer.batch_prep_ms", "trainer.push_ms",
]


def recorded(name):
    with open(os.path.join(TESTDATA, name), encoding="utf-8") as f:
        return json.load(f)


def test_the_benchmarks_vocabulary_is_the_programs():
    """The yardstick holds its own list (it must run over a program that has no
    scopes); the union of every scopes file under the real benchmark's paths
    is the program's tuple. A PR that adds a name to the program adds a file."""
    from distrl_llm_tpu import telemetry

    assert set(VOCABULARY) == set(telemetry.SCOPE_NAMES)
    assert len(set(VOCABULARY)) == len(VOCABULARY)
    assert not hasattr(trace_scopes, "VOCABULARY")  # no closed tuple in the module
    base = spec.load_json(os.path.join(spec.ROOT, "perfbench", "scopes", "base.json"))
    assert len(base["names"]) == 18 and set(base["names"]) <= set(VOCABULARY)


@pytest.mark.parametrize("path, row", [
    (None, "unscoped"),
    ("", "unscoped"),
    ("jit(<unknown>)/while/body/add:", "unscoped"),
    ("state.v_pages[13]:", "unscoped"),
    ("jit(<unknown>)/model/mlp/...i,io->...o/dot_general:", "model/mlp"),
    ("jit(<unknown>)/model/attn_core/engine/kv_write/scatter:", "engine/kv_write"),
    ("jit(<unknown>)/model/attn_core/kernel/paged_attention/jit(paged_attention_native)/pallas_call:",
     "kernel/paged_attention"),
    ("jit(<unknown>)/my_model/mlp_like/add:", "unscoped"),
    ("jit(step)/learner/optimizer/learner/optimizer/codec/jit(searchsorted)/vmap(vmap())/while:",
     "learner/optimizer/codec"),
    ("jit(step)/learner/optimizer/mul:", "learner/optimizer"),
    ("jit(step)/while/body/closed_call/jvp(learner/loss)/while/body/closed_call/model/mlp/tanh:",
     "learner/loss.forward model/mlp"),
    ("jit(step)/while/body/closed_call/jvp(learner/loss)/learner/loss/logprob/while/body/reduce_max:",
     "learner/loss.forward learner/loss/logprob"),
    ("jit(step)/while/body/closed_call/transpose(jvp(learner/loss))/while:",
     "learner/loss.backward"),
    ("jit(step)/while/body/closed_call/transpose(jvp(learner/loss))/while/body/closed_call/"
     "checkpoint/model/attn_proj/dot_general:", "learner/loss.backward model/attn_proj"),
    ("jit(step)/while/body/closed_call/transpose(jvp(learner/loss))/while/body/closed_call/"
     "checkpoint/rematted_computation/model/mlp/dot_general:",
     "learner/loss.recompute model/mlp"),
    ("jit(step)/while/body/closed_call/learner/grad_accum/add:", "learner/grad_accum"),
])
def test_classify(path, row):
    assert trace_scopes.classify(path, VOCABULARY) == row


# ------------------------------------- scope names are data (PR 27): a new file
# adds a name; nothing that is there is edited


@pytest.mark.parametrize("path, tiny_row, real_row", [
    # a new top-level scope: its own row, where the parent's tuple read unscoped
    ("jit(step)/tiny/extra/dot_general:", "tiny/extra", "unscoped"),
    # nested in an old one: the innermost wins, where the old row swallowed it
    ("jit(step)/model/mlp/tiny/extra/dot_general:", "tiny/extra", "model/mlp"),
    ("jit(step)/jvp(learner/loss)/model/mlp/tiny/extra/add:",
     "learner/loss.forward tiny/extra", "learner/loss.forward model/mlp"),
    ("jit(step)/transpose(jvp(learner/loss))/checkpoint/rematted_computation/tiny/extra/mul:",
     "learner/loss.recompute tiny/extra", "learner/loss.recompute"),
    # an old name inside the new one still wins, and a look-alike is no name
    ("jit(step)/tiny/extra/model/head/dot_general:", "model/head", "model/head"),
    ("jit(step)/tiny/extras/add:", "unscoped", "unscoped"),
])
def test_a_scope_a_new_file_adds_gets_its_own_row(path, tiny_row, real_row):
    assert "tiny/extra" in TINY_VOCABULARY and "tiny/extra" not in VOCABULARY
    assert set(VOCABULARY) < set(TINY_VOCABULARY)
    assert trace_scopes.classify(path, TINY_VOCABULARY) == tiny_row
    assert trace_scopes.classify(path, VOCABULARY) == real_row


def test_the_table_takes_its_rows_from_the_vocabulary_it_is_given():
    trace = {"planes": [{"name": "/device:TPU:0", "events": [
        ["%fusion", 0, 30_000, "jit(f)/model/mlp/tiny/extra/dot_general:"],
        ["%fusion", 40_000, 10_000, "jit(f)/model/mlp/add:"],
        ["%copy", 60_000, 5_000, None],
    ]}]}
    tiny = trace_scopes.table(trace, TINY_VOCABULARY)
    assert tiny["rows_s"] == {"tiny/extra": pytest.approx(30e-6),
                              "model/mlp": pytest.approx(10e-6),
                              "unscoped": pytest.approx(5e-6)}
    real = trace_scopes.table(trace, VOCABULARY)
    assert real["rows_s"] == {"model/mlp": pytest.approx(40e-6),
                              "unscoped": pytest.approx(5e-6)}
    assert tiny["busy_s"] == pytest.approx(real["busy_s"])
    # no vocabulary at all (a benchmark with no scopes file): no table
    assert trace_scopes.classify("jit(f)/model/mlp/add:", ()) == "unscoped"
    assert trace_scopes.table(trace, ()) is None


def test_the_regex_is_built_once_for_each_vocabulary():
    assert trace_scopes._names(VOCABULARY) is trace_scopes._names(tuple(VOCABULARY))
    assert trace_scopes._names(VOCABULARY) is not trace_scopes._names(TINY_VOCABULARY)


def scope_file(directory, name, names, **other):
    os.makedirs(os.path.join(str(directory), "scopes"), exist_ok=True)
    with open(os.path.join(str(directory), "scopes", name), "w", encoding="utf-8") as f:
        json.dump({"names": names, "for": "a test", **other}, f)


def test_the_vocabulary_is_the_union_of_the_files_in_the_order_of_the_paths(tmp_path):
    scope_file(tmp_path / "a", "two.json", ["b/one", "b/two"])
    scope_file(tmp_path / "a", "one.json", ["a/one"])
    scope_file(tmp_path / "b", "more.json", ["c/one"])
    (tmp_path / "c").mkdir()  # a path with no scopes directory holds no name
    paths = [str(tmp_path / d) for d in ("b", "c", "a")]
    assert spec.load_scope_names(paths) == ("c/one", "a/one", "b/one", "b/two")
    assert spec.load_scope_names([str(tmp_path / "c")]) == ()


@pytest.mark.parametrize("first, second", [
    (("a", "one.json"), ("a", "two.json")),  # two files of one directory
    (("a", "one.json"), ("b", "one.json")),  # two paths of the benchmark
])
def test_a_name_held_by_two_scope_files_is_refused(tmp_path, first, second):
    scope_file(tmp_path / first[0], first[1], ["model/mlp", "x/one"])
    scope_file(tmp_path / second[0], second[1], ["x/two", "model/mlp"])
    with pytest.raises(spec.SpecError, match="model/mlp"):
        spec.load_scope_names([str(tmp_path / "a"), str(tmp_path / "b")])
    # and a name of the real benchmark's own, brought again by a later file
    scope_file(tmp_path / "again", "again.json", [VOCABULARY[0]])
    with pytest.raises(spec.SpecError, match=VOCABULARY[0]):
        spec.load_scope_names([*BENCH["paths"], str(tmp_path / "again")])


@pytest.mark.parametrize("held", [
    {"names": ["Model/MLP"], "for": "x"}, {"names": ["model//mlp"], "for": "x"},
    {"names": ["/model"], "for": "x"}, {"names": ["model/mlp "], "for": "x"},
    {"names": ["model.mlp"], "for": "x"}, {"names": [3], "for": "x"},
    {"names": ["model/mlp"]}, {"for": "x"}, ["model/mlp"],
])
def test_a_scope_file_that_is_not_plain_data_is_refused(tmp_path, held):
    os.makedirs(tmp_path / "scopes")
    with open(tmp_path / "scopes" / "bad.json", "w", encoding="utf-8") as f:
        json.dump(held, f)
    with pytest.raises(spec.SpecError):
        spec.load_scope_names([str(tmp_path)])


def test_every_file_under_a_scopes_directory_is_data_with_plain_names():
    import glob

    found = []
    for p in dict.fromkeys([*BENCH["paths"], *tiny_benchmark()["paths"]]):
        for path in glob.glob(os.path.join(spec.ROOT, p, "**", "scopes", "*"), recursive=True):
            found.append(os.path.relpath(path, spec.ROOT))
            assert path.endswith(".json"), path
            held = spec.load_json(path)
            assert set(held) == {"names", "for"} and held["names"], path
            assert isinstance(held["for"], str) and held["for"], path
            for name in held["names"]:
                assert spec.SCOPE_NAME.match(name) and name == name.lower(), (path, name)
                assert "/" in name, (path, name)  # <layer>/<what>, as telemetry.py names them
    assert "perfbench/scopes/base.json" in found
    assert "tests/perfbench/tiny/scopes/tiny.json" in found


def test_table_for_reads_the_vocabulary_of_the_cells_paths(tmp_path, capsys):
    """The whole way a run goes: a trace on disk whose one scoped instruction
    sits under ``tiny/extra``, through ``table_for(ctx)``. Under the tiny
    benchmark's paths the name has a row; under the real benchmark's no
    operation carries a name and the reader says so."""
    def run_over(paths, name):
        path = tmp_path / name
        path.write_bytes(hand_encoded_space("jit(f)/model/mlp/tiny/extra/dot_general:"))
        tracer = SimpleNamespace(xplane_path=lambda: str(path), window_wall_ns=(0, 10**12),
                                 sync_wall_ns=0, host_spans=[])
        return SimpleNamespace(tracer=tracer, cell=SimpleNamespace(paths=tuple(paths)))

    tab = trace_scopes.table_for(run_over(tiny_benchmark()["paths"], "tiny.xplane.pb"))
    assert tab["rows_s"] == {"tiny/extra": pytest.approx(2.5e-6),
                             "unscoped": pytest.approx(1.0e-6)}
    notes = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    assert notes[-1]["note"] == "trace_scopes" and "tiny/extra" in notes[-1]["rows_s"]
    held, reader = metric("model.mlp_share")
    ctx = run_over(BENCH["paths"], "real.xplane.pb")
    assert reader.read({}, held["args"], ctx) == pytest.approx(100 * 2.5 / 3.5)
    # a trace whose only scope is one the vocabulary lacks: no table, and why
    path = tmp_path / "none.xplane.pb"
    path.write_bytes(hand_encoded_space("jit(f)/tiny/extra/dot_general:"))
    ctx.tracer.xplane_path = lambda: str(path)
    assert trace_scopes.table_for(ctx) is None
    assert "no operation carries a name" in capsys.readouterr().out


# ------------------------------------------------------ the decoder, by hand


def varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def entry(key, message):
    return field(1, key) + field(2, message)


def hand_encoded_space(scope_path="jit(f)/model/mlp/dot_general:", sync_at_ns=None):
    """One device plane: two instructions (one with a ``tf_op``), an ``XLA Ops``
    line of three events starting at 5,000 ns, and a line that is not read.
    With ``sync_at_ns`` the host plane carries the harness's sync annotation
    there, as a traced run's does."""
    scope_stat = field(1, 7) + field(5, scope_path)
    other_stat = field(1, 8) + field(4, 123)
    plane = (
        field(2, "/device:TPU:0")
        + field(5, entry(7, field(1, 7) + field(2, "tf_op")))
        + field(5, entry(8, field(1, 8) + field(2, "flops")))
        + field(4, entry(1, field(1, 1) + field(2, "%fusion.3 = bf16[64,18944]{1,0} fusion(x)")
                         + field(5, other_stat) + field(5, scope_stat)))
        + field(4, entry(2, field(1, 2) + field(2, "%copy.9 = bf16[4,8]{1,0} copy(y)")
                         + field(5, other_stat)))
        + field(3, field(2, "XLA Ops") + field(3, 5_000)
                + field(4, field(1, 1) + field(2, 0) + field(3, 2_000_000))
                + field(4, field(1, 2) + field(2, 2_000_000) + field(3, 1_000_000))
                + field(4, field(1, 1) + field(2, 4_000_000) + field(3, 500_000)))
        + field(3, field(2, "Async XLA Ops") + field(3, 5_000)
                + field(4, field(1, 2) + field(2, 0) + field(3, 9_000_000)))
    )
    host = field(2, "/host:CPU") + field(3, field(2, "python3"))
    if sync_at_ns is not None:
        from perfbench import harness

        host = (field(2, "/host:CPU")
                + field(4, entry(1, field(1, 1) + field(2, harness.SYNC_EVENT)))
                + field(3, field(2, "python3") + field(3, sync_at_ns)
                        + field(4, field(1, 1) + field(2, 0) + field(3, 1_000))))
    return field(1, plane) + field(1, host)


def test_load_decodes_events_and_their_metadatas_scope(tmp_path):
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(hand_encoded_space())
    trace = trace_scopes.load(str(path))
    assert trace == {"planes": [{"name": "/device:TPU:0", "events": [
        ["%fusion bf16[64,18944]", 5_000, 2_000, "jit(f)/model/mlp/dot_general:"],
        ["%copy bf16[4,8]", 7_000, 1_000, None],
        ["%fusion bf16[64,18944]", 9_000, 500, "jit(f)/model/mlp/dot_general:"],
    ]}]}
    tab = trace_scopes.table(trace, VOCABULARY)
    assert tab["rows_s"] == {"model/mlp": pytest.approx(2.5e-6),
                             "unscoped": pytest.approx(1.0e-6)}
    assert tab["busy_s"] == pytest.approx(3.5e-6)
    assert tab["unscoped_top"] == [["%copy bf16[4,8]", pytest.approx(1.0e-6)]]
    # a window cuts an event that crosses its edge
    tab = trace_scopes.table(trace, VOCABULARY, window_ns=(6_000, 9_250))
    assert tab["rows_s"]["model/mlp"] == pytest.approx(1.25e-6)


def test_only_leaves_are_counted_so_the_rows_sum_to_busy():
    trace = {"planes": [{"name": "/device:TPU:0", "events": [
        ["%while", 0, 50_000, "jit(f)/model/mlp/while:"],
        ["%fusion", 10_000, 10_000, "jit(f)/model/mlp/while/body/add:"],
        ["%fusion", 25_000, 10_000, None],
        ["%kernel", 80_000, 20_000, "jit(f)/kernel/paged_attention/jit(k)/pallas_call:"],
    ]}]}
    tab = trace_scopes.table(trace, VOCABULARY)
    assert tab["rows_s"] == {
        "kernel/paged_attention": pytest.approx(20e-6),
        "model/mlp": pytest.approx(10e-6), "unscoped": pytest.approx(10e-6),
    }
    reduced = trace_reduce.reduce({"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": trace_reduce.OP_LINE, "events": [e[:3] for e in trace["planes"][0]["events"]]},
    ]}]})
    assert tab["busy_s"] == pytest.approx(reduced["busy_s"])


def test_a_cpu_trace_has_no_device_plane_and_no_table(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    jax.jit(lambda x: (x @ x).sum())(jnp.ones((32, 32))).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    trace = trace_scopes.load(path)
    assert trace == {"planes": []}
    assert trace_scopes.table(trace, VOCABULARY) is None


# ------------------------------------------------- pieces of real v5e traces


def as_reduce_sees_it(trace):
    return {"planes": [{"name": p["name"], "lines": [
        {"name": trace_reduce.OP_LINE, "events": [e[:3] for e in p["events"]]}]}
        for p in trace["planes"]]}


@pytest.mark.parametrize("name", [
    "v5e_rollout_decode_step_scoped.json", "v5e_learner_update_scoped.json",
])
def test_recorded_tables_sum_to_busy_and_to_a_hundred_percent(name):
    trace = recorded(name)
    tab = trace_scopes.table(trace, VOCABULARY)
    reduced = trace_reduce.reduce(as_reduce_sees_it(trace))
    assert tab["busy_s"] == pytest.approx(reduced["busy_s"], rel=1e-9)
    assert sum(tab["rows_s"].values()) == pytest.approx(tab["busy_s"], rel=1e-12)
    shares = [100 * trace_scopes.seconds_under(tab, "^" + r.replace(".", r"\.") + "$")
              / tab["busy_s"] for r in tab["rows_s"] if r != "unscoped"]
    unscoped = 100 * trace_scopes.seconds_under(tab, "unscoped") / tab["busy_s"]
    assert sum(shares) + unscoped == pytest.approx(100.0)
    assert all(row == "unscoped" or row.split(" ")[-1].split(".")[0] in VOCABULARY
               or row.startswith("learner/loss.") for row in tab["rows_s"])


#: the recorded pieces' tables as PR 24's closed tuple gave them (read on the
#: parent of PR 27, whose ``VOCABULARY`` went into ``scopes/base.json``): every
#: row, every digit. A change to how names are found must not move one.
PINNED = {
    "v5e_rollout_decode_step_scoped.json": (0.025654611999999993, {
        "model/mlp": 0.007847356000000002,
        "kernel/paged_attention": 0.0072549170000000005,
        "unscoped": 0.0036369049999999967, "engine/kv_write": 0.0028820579999999985,
        "model/attn_proj": 0.002477321999999998, "model/head": 0.001505581,
        "model/attn_core": 3.3356e-05, "engine/bookkeeping": 1.0534e-05,
        "model/embed": 5.947e-06, "engine/sample": 6.36e-07,
    }),
    "v5e_learner_update_scoped.json": (0.078544432, {
        "learner/optimizer/codec": 0.067334742,
        "learner/loss.forward model/mlp": 0.002982981,
        "learner/loss.recompute model/attn_core": 0.002017158,
        "learner/loss.backward": 0.0016286629999999983,
        "learner/loss.forward model/attn_proj": 0.001458776,
        "learner/loss.recompute model/attn_proj": 0.001417031,
        "learner/loss.forward model/attn_core": 0.0014000280000000002,
        "learner/loss.forward model/embed": 0.00019468500000000002,
        "learner/loss.forward": 5.5204e-05, "unscoped": 5.490299999999997e-05,
        "learner/loss.forward learner/loss/logprob": 2.61e-07,
    }),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_recorded_tables_read_the_same_to_the_last_digit(name):
    busy_s, rows_s = PINNED[name]
    tab = trace_scopes.table(recorded(name), VOCABULARY)
    assert tab["rows_s"] == rows_s and list(tab["rows_s"]) == list(rows_s)
    assert tab["busy_s"] == busy_s
    # a name the program does not carry changes no row of a trace that lacks it
    assert trace_scopes.table(recorded(name), TINY_VOCABULARY) == tab


def test_recorded_decode_step_of_the_7b_rollout_cell():
    """One decode step at 64 slots cut from PR 24's traced run of
    ``qwen2.5-7b-L14.rollout-lockstep`` with scopes in the executables. The
    fourteen layers' paged-attention calls sit under ``kernel/paged_attention``
    (the same events ``kernel.paged_attn_share`` finds by name), the fused
    sampler and one of the two per-layer copies of the pool under no name."""
    trace = recorded("v5e_rollout_decode_step_scoped.json")
    tab = trace_scopes.table(trace, VOCABULARY)
    rows = tab["rows_s"]
    by_name = sum(e[2] for e in trace["planes"][0]["events"]
                  if e[0].startswith("%paged_attention_native ")) / 1e9
    # the scope also holds the adapter's own reshapes: a fraction of a microsecond
    assert by_name <= rows["kernel/paged_attention"] <= by_name * 1.001
    sampler = [e for e in trace["planes"][0]["events"] if e[0].startswith("%_unknown_ (s32[")]
    assert sampler and all(trace_scopes.classify(e[3], VOCABULARY) == "unscoped" for e in sampler)
    for row in ("model/mlp", "model/attn_proj", "model/head", "engine/kv_write", "unscoped"):
        assert rows[row] > 0, row
    assert rows["model/mlp"] > rows["model/attn_proj"] > rows["model/head"]
    top = dict(tab["unscoped_top"])
    assert "%copy bf16[4,241,128,128]" in top


def test_recorded_learner_update_tells_the_three_passes_and_the_codec_apart():
    """Pieces of one update of ``qwen2.5-7b-L14.learner-1k``: the start of the
    forward pass, a stretch of the backward scan (recomputed forward and
    backward interleaved) and the start of the optimizer."""
    tab = trace_scopes.table(recorded("v5e_learner_update_scoped.json"), VOCABULARY)
    for phase in ("forward", "recompute", "backward"):
        under = trace_scopes.seconds_under(tab, rf"^learner/loss\.{phase}( |$)")
        assert under > 0, phase
    assert trace_scopes.seconds_under(tab, "^learner/optimizer") >= \
        trace_scopes.seconds_under(tab, "^learner/optimizer/codec$") > 0
    assert not [r for r in tab["rows_s"] if r.startswith("model/")], \
        "in the learner every model scope sits under learner/loss"


def test_stale_executables_carry_no_scope_and_give_no_table():
    """A piece of a run of the SAME program whose executables were loaded from
    a compilation cache the parent had filled: paths are there
    (``jit(step)/while/body/...``), vocabulary names are not."""
    trace = recorded("v5e_learner_stale_cache.json")
    paths = [e[3] for e in trace["planes"][0]["events"] if e[3]]
    assert paths and not [p for p in paths if trace_scopes.classify(p, VOCABULARY) != "unscoped"]
    assert trace_scopes.table(trace, VOCABULARY) is None


# ----------------------------------------------------------------- the readers

def test_one_parse_of_the_file_serves_the_table_and_the_decode_window(tmp_path, monkeypatch):
    """``loaded_for`` parses a run's trace once; ``table_for`` and the decode
    window's readers (``seconds_in_spans``, which ``sala_work`` and
    ``latent_moe_work`` call) cut that one. The wall clock runs 1,000,000 ns
    ahead of the profiler's here."""
    path = tmp_path / "run.xplane.pb"
    path.write_bytes(hand_encoded_space(sync_at_ns=3_000))
    ahead = 1_000_000
    spans = [("engine/decode", ahead + 6_000, ahead + 9_250), ("engine/prefill", 0, 1)]
    tracer = SimpleNamespace(xplane_path=lambda: str(path), sync_wall_ns=ahead + 3_000,
                             window_wall_ns=(ahead, ahead + 20_000), host_spans=spans)
    ctx = SimpleNamespace(tracer=tracer, cell=SimpleNamespace(paths=tuple(BENCH["paths"])))
    parses = []
    load = trace_scopes.load
    monkeypatch.setattr(trace_scopes, "load", lambda p: parses.append(p) or load(p))
    # the events of [6,000, 9,250) on the profiler's clock: 1,000 + 250 ns of model/mlp
    assert trace_scopes.seconds_in_spans(ctx, "^model/mlp$", "engine/decode") == pytest.approx(1.25e-6)
    assert trace_scopes.seconds_in_spans(ctx, "^unscoped$", "engine/decode") == pytest.approx(1.0e-6)
    assert trace_scopes.seconds_in_spans(ctx, "^model/head$", "engine/decode") is None
    assert trace_scopes.seconds_in_spans(ctx, "^model/mlp$", "engine/refill_decode") is None
    assert trace_scopes.table_for(ctx)["rows_s"]["model/mlp"] == pytest.approx(2.5e-6)
    assert parses == [str(path)]
    assert trace_scopes.loaded_for(ctx)["offset"] == ahead
    # an untraced run, and a trace without the sync annotation
    assert trace_scopes.loaded_for(SimpleNamespace(tracer=None)) is None
    bare = tmp_path / "bare.xplane.pb"
    bare.write_bytes(hand_encoded_space())
    tracer.xplane_path = lambda: str(bare)
    assert trace_scopes.loaded_for(ctx)["offset"] is None
    assert trace_scopes.seconds_in_spans(ctx, "^model/mlp$", "engine/decode") is None


def fake_run(table, host_spans=()):
    """A run context whose trace's table is already reduced."""
    path = f"/nowhere/{id(table)}.xplane.pb"
    trace_scopes._RUNS[path] = table
    tracer = SimpleNamespace(xplane_path=lambda: path, window_wall_ns=(0, 1),
                             sync_wall_ns=0, host_spans=list(host_spans))
    return SimpleNamespace(tracer=tracer)


def metric(name):
    held = spec.load_layer_metric(BENCH["paths"], name)
    return held, spec.load_module(BENCH["paths"], "readers", held["reader"])


def test_scope_metrics_read_the_recorded_rollout_table():
    tab = trace_scopes.table(recorded("v5e_rollout_decode_step_scoped.json"), VOCABULARY)
    ctx = fake_run(tab)
    values = {}
    for name in ("model.attn_proj_share", "model.mlp_share", "model.head_share",
                 "engine.kv_write_share", "rollout.unscoped_share"):
        held, reader = metric(name)
        values[name] = reader.read({}, held["args"], ctx)
        assert 0 < values[name] < 100, name
    rest = 100 * sum(t for r, t in tab["rows_s"].items() if r in (
        "kernel/paged_attention", "model/attn_core", "model/embed", "engine/sample",
        "engine/bookkeeping", "engine/admit")) / tab["busy_s"]
    assert sum(values.values()) + rest == pytest.approx(100.0)
    # a scope that names nothing in this trace is left out, not read as 0%
    held, reader = metric("learner.optimizer_share")
    assert reader.read({}, held["args"], ctx) is None


def test_scope_metrics_read_the_recorded_learner_table():
    tab = trace_scopes.table(recorded("v5e_learner_update_scoped.json"), VOCABULARY)
    ctx = fake_run(tab)
    total = 0.0
    for name in ("learner.forward_share", "learner.recompute_share",
                 "learner.backward_share", "learner.optimizer_share",
                 "learner.unscoped_share"):
        held, reader = metric(name)
        value = reader.read({}, held["args"], ctx)
        assert value is not None and value >= 0, name
        total += value
    grad_accum = 100 * tab["rows_s"].get("learner/grad_accum", 0.0) / tab["busy_s"]
    assert total + grad_accum == pytest.approx(100.0)


@pytest.mark.parametrize("name", NEW)
def test_new_metrics_are_left_out_where_there_is_nothing_to_read(name):
    """No run, an untraced run, a run whose trace has no table (stale
    executables, the parent's program) and a program without the span."""
    held, reader = metric(name)
    assert reader.read({}, held.get("args", {}), None) is None
    assert reader.read({}, held.get("args", {}), SimpleNamespace(tracer=None)) is None
    assert reader.read({"traced_units": [{}]}, held.get("args", {}), fake_run(None)) is None


def test_host_span_metrics_sum_per_unit_and_take_medians():
    ms = 1_000_000
    spans = [
        ("engine.generate", 0, 900 * ms),
        ("engine/admit", 10 * ms, 12 * ms), ("engine/admit", 400 * ms, 401 * ms),
        ("engine/snapshot_wait", 20 * ms, 120 * ms), ("engine/snapshot_wait", 130 * ms, 230 * ms),
        ("driver/update/batch", 0, 9 * ms), ("driver/update/batch", 0, 11 * ms),
        ("driver/update/batch", 0, 40 * ms), ("driver/push", 0, ms // 100),
    ]
    ctx = fake_run(None, spans)
    observed = {"traced_units": [{}, {}]}
    expected = {"engine.admit_host_ms": 1.5, "engine.snapshot_wait_ms": 100.0,
                "trainer.batch_prep_ms": 11.0, "trainer.push_ms": 0.01}
    for name, value in expected.items():
        held, reader = metric(name)
        assert reader.read(observed, held["args"], ctx) == pytest.approx(value), name
    held, reader = metric("engine.admit_host_ms")
    assert reader.read({"traced_units": []}, held["args"], ctx) is None


def test_span_clock_pairs_spans_with_their_annotations(tmp_path):
    """Part 3 of PR 24: a span's start on the wall clock less the sync offset
    against its own annotation's start on the profiler's clock."""
    import glob
    import time

    import jax
    import jax.numpy as jnp

    from distrl_llm_tpu import telemetry
    from perfbench import harness

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    sync_wall = time.time_ns()
    with jax.profiler.TraceAnnotation(harness.SYNC_EVENT):
        pass
    telemetry.reset()
    telemetry.configure(True)
    for _ in range(3):
        with telemetry.span(telemetry.ENGINE_SNAPSHOT_WAIT):
            jax.jit(lambda x: x + 1)(jnp.ones(4)).block_until_ready()
    telemetry.configure(False)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    spans = [(e["name"], e["ts"] * 1000, (e["ts"] + e["dur"]) * 1000)
             for e in telemetry.recent_events() if e.get("ph") == "X"
             and e["name"] == telemetry.ENGINE_SNAPSHOT_WAIT]
    telemetry.reset()
    host = trace_reduce.load_xplane(
        path, keep_host_events=(harness.SYNC_EVENT, telemetry.ENGINE_SNAPSHOT_WAIT))
    offset = trace_reduce.sync_offset_ns(host, harness.SYNC_EVENT, sync_wall)
    clock = trace_scopes.span_clock(host, spans, offset)
    assert clock["pairs"] == 3
    # the program's spans keep microseconds: the two clocks agree to a few ms at worst
    assert clock["max_abs_us"] < 50_000
    assert trace_scopes.span_clock(host, [("no/such_span", 0, 1)], offset) is None


# --------------------------------- (vi) the new files resolve; a CPU line omits them


def test_every_new_metric_resolves_from_its_files_and_is_in_the_benchmark():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        held, reader = metric(name)
        assert declared[name]["moves"] == held["moves"]
        assert held["reader"] in ("trace_scopes", "host_spans")
        assert callable(reader.read)
    # one block, in order, wherever it stands: a later PR appends after it
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW


def test_a_cpu_rehearsal_line_leaves_the_new_metrics_out(tmp_path):
    from rehearsal_helpers import assert_cell_ran, shared_cell
    from tiny_spec import tiny_benchmark, write_tiny_benchmark

    names = {m["name"] for m in tiny_benchmark()["per_layer"]}
    assert set(NEW) <= names  # the tiny cells are asked for them too
    bench = write_tiny_benchmark(tmp_path)
    line, notes = shared_cell(bench, "tiny.rollout", 1)
    assert_cell_ran(line, notes, 1)
    assert not set(NEW) & set(line["metrics"])
    assert "trace_scopes" not in notes
