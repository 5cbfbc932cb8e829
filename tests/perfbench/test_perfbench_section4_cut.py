"""The cut the guide's section 4 allows, whole (PR 53): a made-up tiny
configuration cut the new way (experts over 16 chips beside an eighth of the
vocabulary, one of three leading dense layers) goes through ``spec.load_cell``
and, appended to a copy of the real ``BENCHMARK.json``, through the structural
tests; and what PR 53 found is held as it was: the seven configuration files
and the traffic files by their hashes (one file's ``check`` was set again), ``spec.load_cell`` of the nine cells by
a literal taken at PR 53's parent (``d1de52c``).

A second made-up file (PR 64) names its depth ``num_layers``, as four of the
catalog's published configs do, and goes the same way by its own name.

The literals hold what was THERE by name: a later PR appends cells, metrics
and cell names to a metric's list, and none of that fails here."""

import hashlib
import json
import os

import pytest

from perfbench import spec
from test_perfbench_appended import SHARED_WITH_A_FAMILY, run_structural
from latent_moe_spec import JOINED
from tiny_spec import REPO, TINY_DIR, real_benchmark, tiny_benchmark

CONFIG = "tiny-cut-whole"
FILE = f"{TINY_DIR}/configs/{CONFIG}.json"
#: PR 64's: depth under ``num_layers``, cut by depth and by one of 32 chips' share
NUM_LAYERS = "tiny-num-layers"
#: the accepted traffic the made-up cell rides on in the copy of the real benchmark
TRAFFIC = "rollout-longctx"


def sha256(path: str) -> str:
    with open(os.path.join(REPO, path), "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def file_of(config: str) -> str:
    return f"{TINY_DIR}/configs/{config}.json"


def held_file(config: str = CONFIG) -> dict:
    with open(os.path.join(REPO, file_of(config)), encoding="utf-8") as f:
        return json.load(f)


# ------------------------------------------------- the rehearsal: a new file


def test_the_file_is_cut_the_new_way_and_check_reduced_takes_it():
    held = held_file()
    assert held["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                               "n_routed_experts", "vocab_size"]
    chips = held["share"]["chips_per_layer"]
    published = {**held["share"]["published"], **held["depth"]["published"]}
    # experts by the chips that share a layer, the vocabulary by eight, one dense layer
    assert chips == 16 and held["n_routed_experts"] * chips == published["n_routed_experts"]
    assert held["vocab_size"] * 8 == published["vocab_size"]
    assert (held["first_k_dense_replace"], published["first_k_dense_replace"]) == (1, 3)
    assert held["num_hidden_layers"] - 1 >= spec.MIN_LAYERS_AFTER_DENSE
    assert spec.check_reduced(held, FILE) is None


def test_the_file_that_says_num_layers_is_cut_under_that_name_and_check_reduced_takes_it():
    held = held_file(NUM_LAYERS)
    assert held["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert "num_hidden_layers" not in held and "depth" not in held
    chips, published = held["share"]["chips_per_layer"], held["share"]["published"]
    assert chips == 32 and held["n_routed_experts"] * chips == published["n_routed_experts"]
    assert held["n_routed_experts"] >= spec.MIN_EXPERTS_HELD
    assert held["vocab_size"] * spec.MIN_VOCAB_SHARE == published["vocab_size"]
    assert held["num_layers"] >= spec.MIN_LAYERS_AFTER_DENSE
    assert spec.check_reduced(held, file_of(NUM_LAYERS)) is None


def appended_entry(config: str) -> dict:
    held = held_file(config)
    return {"name": config, "source": held["source"], "file": file_of(config),
            "reduced": held["reduced"], "why": "the section-4 cut, whole"}


@pytest.mark.parametrize("config", [CONFIG, NUM_LAYERS])
def test_a_run_loads_it_whole(config):
    """Through ``spec.load_cell``: the tiny benchmark with the configuration and
    a cell appended, as a later PR appends its own."""
    bench = tiny_benchmark()
    bench["configs"].append(appended_entry(config))
    cell = f"{config}.rollout"
    bench["workloads"].append({"name": cell, "config": config, "traffic": "tiny-rollout",
                               "chips": 1, "why": "rehearsal"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.rollout" in metric.get("workloads", []):
            metric["workloads"].append(cell)
    loaded = spec.load_cell(bench, cell)
    assert loaded.config == held_file(config) and loaded.traffic["kind"] == "rollout"
    assert [m["name"] for m in loaded.per_layer] == [
        m["name"] for m in spec.load_cell(bench, "tiny.rollout").per_layer]


@pytest.mark.parametrize("config, edit, refusal", [
    (CONFIG, {"vocab_size": 128}, "an eighth of the vocabulary"),
    (CONFIG, {"first_k_dense_replace": 3}, "leading dense layers count once"),
    (NUM_LAYERS, {"reduced": ["num_layers", "n_routed_experts", "vocab_size", "q_lora_rank"]},
     "'q_lora_rank': a width is never cut"),
    (NUM_LAYERS, {"num_hidden_layers": 4}, "counts its layers under one name"),
    (NUM_LAYERS, {"reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"]},
     "'num_hidden_layers', which the file does not hold"),
])
def test_a_run_refuses_the_same_file_cut_further(tmp_path, config, edit, refusal):
    bench = tiny_benchmark()
    path = tmp_path / "cut.json"
    path.write_text(json.dumps({**held_file(config), **edit}), encoding="utf-8")
    entry = next(c for c in bench["configs"] if c["name"] == "tiny")
    entry["file"] = str(path)
    with pytest.raises(spec.SpecError, match=refusal):
        spec.load_cell(bench, "tiny.rollout")


def test_appended_to_the_real_benchmark_it_passes_the_structural_tests(tmp_path):
    """``test_perfbench_appended``'s run over a copy of the real benchmark, with
    THESE files as the appended configurations (one run for both: a run costs
    seconds): each one's own ``test_config_file`` case and its cell's case are
    collected and pass, because the file is there."""
    bench = real_benchmark()
    for config in (CONFIG, NUM_LAYERS):
        cell = f"{config}.{TRAFFIC}"
        bench["configs"].append(appended_entry(config))
        bench["workloads"].append({"name": cell, "config": config, "traffic": TRAFFIC,
                                   "chips": 1, "why": "made up"})
        for metric in bench["end_to_end"] + bench["per_layer"]:
            if metric["name"] in (*JOINED, *SHARED_WITH_A_FAMILY):
                metric["workloads"].append(cell)
    out = run_structural(
        bench, tmp_path / "BENCHMARK.cut.json", ("test_perfbench_spec.py",), "-rp", "-k",
        f"{CONFIG} or {NUM_LAYERS} or test_names_are_plain or test_top_level")
    said = out.stdout[-4000:] + out.stderr[-2000:]
    assert out.returncode == 0, said
    # a file: test_config_file, the counts module's case, the cell's case; and the two named
    assert "8 passed" in out.stdout, said
    for config in (CONFIG, NUM_LAYERS):
        assert f"test_config_file[{config}]" in out.stdout, said
        assert f"[{config}.{TRAFFIC}]" in out.stdout, said


# --------------------------------------- what was there at PR 53's parent stays

CONFIG_SHA256 = {'qwen2.5-7b-L14': '3df5634bc4e79b430b0ea9b9074e0342e6995a008d72b4c961499981de4dd987',
 'minicpm-sala-L10': 'ed826024c807d07e14516bb7e7d7232d79a0f6066a911790cb1d5ce2abd5e2ca',
 'kimi-vl-a3b-L7': 'de6f154e707ddcce349445048a4057e866eb50342ccd6b12eca9ca9e23fa10b5',
 'solar-open2-250b-ep8-L4': '395338b790e3f4e86c35cda73a992515b8f1caa7c009c6edaf4027e29837b28a',
 'brumby-14b-L4': '1b5d1ad5f471417f13b22977c03d3f0d3bf8ce238796ff91f57b04c324cada53',
 'jamba2-3b': 'eabbed6fa7b03379689360366b407cd09fd59ad77c05cab694778b010b2f6d7e',
 'k-exaone-236b-ep8-L5': 'c8d4d37c9fb38720e4ad5d6d82b9f4ad8b6d812aadcb03bc628e447570069a84'}
CELLS_AT_PARENT = {'qwen2.5-7b-L14.rollout-lockstep': {'chips': 1,
                                     'config': 'qwen2.5-7b-L14',
                                     'traffic': 'rollout-lockstep',
                                     'traffic_sha256': '50707aae3e8933c6dbb29ea7c8fa2eb730cbb9b54c485c8e366fd8f413bf7313',
                                     'end_to_end': ['rollout_tok_s', 'setup_s'],
                                     'per_layer': ['engine.decode_bandwidth_util', 'engine.decode_step_ms',
                                                   'engine.slot_occupancy', 'entry.cache_misses',
                                                   'entry.compile_s', 'entry.programs_built',
                                                   'entry.window_compiles', 'kernel.paged_attn_share',
                                                   'kernel.sampler_share', 'paged_attn_roofline',
                                                   'model.attn_proj_share', 'model.mlp_share',
                                                   'model.head_share', 'engine.kv_write_share',
                                                   'rollout.unscoped_share', 'engine.admit_host_ms',
                                                   'engine.snapshot_wait_ms', 'engine.dispatch_host_ms',
                                                   'engine.dispatch_median_ms', 'engine.prefill_ms',
                                                   'engine.readback_ms', 'engine.loop_self_ms',
                                                   'engine.host_busy_share', 'engine.slowest_boundary_ms',
                                                   'engine.slowest_boundary_host_ms']},
 'qwen2.5-7b-L14.learner-1k': {'chips': 1,
                               'config': 'qwen2.5-7b-L14',
                               'traffic': 'learner-1k',
                               'traffic_sha256': '176355ca6f1f2039ef94c0aeed5a63cc1e72b4bb9299a2236fcb038c89e09e63',
                               'end_to_end': ['learner_tok_s', 'setup_s'],
                               'per_layer': ['entry.cache_misses', 'entry.compile_s', 'entry.programs_built',
                                             'entry.window_compiles', 'learner.mfu', 'learner.unscoped_share',
                                             'learner.forward_share', 'learner.recompute_share',
                                             'learner.backward_share', 'learner.optimizer_share',
                                             'learner.kept_share']},
 'qwen2.5-7b-L14.rl-step-dense': {'chips': 1,
                                  'config': 'qwen2.5-7b-L14',
                                  'traffic': 'rl-step-dense',
                                  'traffic_sha256': 'a14cf47002ae3556fe3661f51a2f5ef49eb23ef87c47328e1a88dccafad861cc',
                                  'end_to_end': ['step_s', 'setup_s'],
                                  'per_layer': ['engine.generation_s', 'entry.cache_misses', 'entry.compile_s',
                                                'entry.programs_built', 'entry.window_compiles',
                                                'learner.update_s', 'trainer.reward_s', 'trainer.self_s',
                                                'rl_step.unscoped_share', 'trainer.batch_prep_ms',
                                                'trainer.push_ms']},
 'minicpm-sala-L10.rollout-longctx': {'chips': 1,
                                      'config': 'minicpm-sala-L10',
                                      'traffic': 'rollout-longctx',
                                      'traffic_sha256': '0ceed0728a535d928216588b62d9fdb98bedcf92691a0199f3a72f1c2729a34a',
                                      'end_to_end': ['rollout_tok_s', 'setup_s'],
                                      'per_layer': ['engine.decode_bandwidth_util', 'engine.decode_step_ms',
                                                    'engine.slot_occupancy', 'entry.cache_misses',
                                                    'entry.compile_s', 'entry.programs_built',
                                                    'entry.window_compiles', 'kernel.sampler_share',
                                                    'model.attn_proj_share', 'model.mlp_share',
                                                    'model.head_share', 'engine.kv_write_share',
                                                    'rollout.unscoped_share', 'engine.snapshot_wait_ms',
                                                    'model.linear_attn_share', 'model.sparse_select_share',
                                                    'model.sparse_attn_share', 'kernel.linear_attn_roofline',
                                                    'kernel.sparse_attn_roofline',
                                                    'engine.sparse_attended_share', 'engine.dispatch_host_ms',
                                                    'engine.dispatch_median_ms', 'engine.prefill_ms',
                                                    'engine.readback_ms', 'engine.loop_self_ms',
                                                    'engine.host_busy_share', 'engine.slowest_boundary_ms',
                                                    'engine.slowest_boundary_host_ms',
                                                    'engine.prefill_real_share']},
 'kimi-vl-a3b-L7.rollout-longctx-latent': {'chips': 1,
                                           'config': 'kimi-vl-a3b-L7',
                                           'traffic': 'rollout-longctx-latent',
                                           'traffic_sha256': '207156fa6e3abc37a4f1d48677c65e21045313bcee40e608f6ab3c6fca9da7bb',
                                           'end_to_end': ['rollout_tok_s', 'setup_s'],
                                           'per_layer': ['engine.decode_bandwidth_util',
                                                         'engine.decode_step_ms', 'engine.slot_occupancy',
                                                         'entry.cache_misses', 'entry.compile_s',
                                                         'entry.programs_built', 'entry.window_compiles',
                                                         'kernel.sampler_share', 'model.attn_proj_share',
                                                         'model.mlp_share', 'model.head_share',
                                                         'engine.kv_write_share', 'rollout.unscoped_share',
                                                         'engine.snapshot_wait_ms', 'model.moe_router_share',
                                                         'model.moe_dispatch_share', 'model.moe_experts_share',
                                                         'model.latent_attn_share',
                                                         'kernel.moe_experts_roofline',
                                                         'kernel.latent_attn_roofline',
                                                         'engine.expert_load_imbalance',
                                                         'engine.dispatch_host_ms', 'engine.dispatch_median_ms',
                                                         'engine.prefill_ms', 'engine.readback_ms',
                                                         'engine.loop_self_ms', 'engine.host_busy_share',
                                                         'engine.slowest_boundary_ms',
                                                         'engine.slowest_boundary_host_ms',
                                                         'engine.prefill_real_share']},
 'solar-open2-250b-ep8-L4.rollout-reasoning': {'chips': 1,
                                               'config': 'solar-open2-250b-ep8-L4',
                                               'traffic': 'rollout-reasoning',
                                               'traffic_sha256': 'b7afac213e3fef48b1cdcf16ab0b046be08f338de77c908e70be0d44a5dd7781',
                                               'end_to_end': ['rollout_tok_s', 'setup_s'],
                                               'per_layer': ['engine.decode_bandwidth_util',
                                                             'engine.decode_step_ms', 'engine.slot_occupancy',
                                                             'entry.cache_misses', 'entry.compile_s',
                                                             'entry.programs_built', 'entry.window_compiles',
                                                             'kernel.paged_attn_share', 'kernel.sampler_share',
                                                             'model.attn_proj_share', 'model.mlp_share',
                                                             'model.head_share', 'engine.kv_write_share',
                                                             'rollout.unscoped_share',
                                                             'engine.snapshot_wait_ms',
                                                             'model.moe_router_share',
                                                             'model.moe_dispatch_share',
                                                             'model.moe_experts_share',
                                                             'kernel.moe_experts_roofline',
                                                             'engine.expert_load_imbalance',
                                                             'model.delta_attn_share', 'model.short_conv_share',
                                                             'kernel.delta_step_roofline',
                                                             'kernel.delta_chunk_roofline',
                                                             'kernel.softmax_paged_roofline',
                                                             'engine.expert_held_share',
                                                             'engine.dispatch_host_ms',
                                                             'engine.dispatch_median_ms', 'engine.prefill_ms',
                                                             'engine.readback_ms', 'engine.loop_self_ms',
                                                             'engine.host_busy_share',
                                                             'engine.slowest_boundary_ms',
                                                             'engine.slowest_boundary_host_ms',
                                                             'engine.prefill_real_share']},
 'brumby-14b-L4.rollout-retention-16k': {'chips': 1,
                                         'config': 'brumby-14b-L4',
                                         'traffic': 'rollout-retention-16k',
                                         'traffic_sha256': 'b390464dbbedfa8d4274be59be999d8998802fdf0f5d3ce4c010b8a1f602b7a1',
                                         'end_to_end': ['rollout_tok_s', 'setup_s'],
                                         'per_layer': ['engine.decode_bandwidth_util', 'engine.decode_step_ms',
                                                       'engine.slot_occupancy', 'entry.cache_misses',
                                                       'entry.compile_s', 'entry.programs_built',
                                                       'entry.window_compiles', 'kernel.sampler_share',
                                                       'model.attn_proj_share', 'model.mlp_share',
                                                       'model.head_share', 'rollout.unscoped_share',
                                                       'engine.snapshot_wait_ms', 'model.power_attn_share',
                                                       'kernel.power_step_roofline',
                                                       'kernel.power_chunk_roofline', 'engine.slot_state_share',
                                                       'engine.prefill_real_share']},
 'jamba2-3b.rollout-wide-480': {'chips': 1,
                                'config': 'jamba2-3b',
                                'traffic': 'rollout-wide-480',
                                'traffic_sha256': '0821af1d07a523a3058407f80063b8f7f6df7aad7ec07bdcaf55e91e8b43b496',
                                'end_to_end': ['rollout_tok_s', 'setup_s'],
                                'per_layer': ['engine.decode_bandwidth_util', 'engine.decode_step_ms',
                                              'engine.slot_occupancy', 'entry.cache_misses', 'entry.compile_s',
                                              'entry.programs_built', 'entry.window_compiles',
                                              'kernel.paged_attn_share', 'kernel.sampler_share',
                                              'model.attn_proj_share', 'model.mlp_share', 'model.head_share',
                                              'engine.kv_write_share', 'rollout.unscoped_share',
                                              'engine.snapshot_wait_ms', 'model.short_conv_share',
                                              'engine.slot_state_share', 'model.ssm_share',
                                              'kernel.ssm_step_roofline', 'kernel.ssm_scan_roofline',
                                              'engine.prefill_real_share']},
 'k-exaone-236b-ep8-L5.rollout-longctx-window': {'chips': 1,
                                                 'config': 'k-exaone-236b-ep8-L5',
                                                 'traffic': 'rollout-longctx-window',
                                                 'traffic_sha256': 'ff0800aeb1ee27618bdd5cbc62695dcff1eaef98364a124f3bc9f0c390e2524e',
                                                 'end_to_end': ['rollout_tok_s', 'setup_s'],
                                                 'per_layer': ['engine.decode_bandwidth_util',
                                                               'engine.decode_step_ms', 'engine.slot_occupancy',
                                                               'entry.cache_misses', 'entry.compile_s',
                                                               'entry.programs_built', 'entry.window_compiles',
                                                               'kernel.paged_attn_share',
                                                               'kernel.sampler_share', 'model.attn_proj_share',
                                                               'model.mlp_share', 'model.head_share',
                                                               'engine.kv_write_share',
                                                               'rollout.unscoped_share',
                                                               'engine.snapshot_wait_ms',
                                                               'model.moe_router_share',
                                                               'model.moe_dispatch_share',
                                                               'model.moe_experts_share',
                                                               'kernel.moe_experts_roofline',
                                                               'engine.expert_load_imbalance',
                                                               'kernel.softmax_paged_roofline',
                                                               'engine.expert_held_share',
                                                               'engine.slot_state_share',
                                                               'model.window_attn_share',
                                                               'engine.window_attended_share',
                                                               'engine.prefill_real_share']}}
#: ``rollout-reasoning``'s max limit refused an UNCHANGED program at 2 seeds of 46
#: (0.7576 in PR 45, 0.7841 in PR 53, the parent's files and the change's alike):
#: PR 53 set it again from the two readings (PERF.md section 6); the mean limit
#: and everything outside ``check`` are the parent's
RELIMITED_IN_PR_53 = {"rollout-reasoning": {
    "less_check_sha256": "2b9604daa356265278d67cd924af9248b374944568b75fe9785c60f8fa3b8bc7",
    "limits": {"logprob_mean_abs_tol": 0.0962, "logprob_max_abs_tol": 1.5}}}
#: every end-to-end and per-layer entry of BENCHMARK.json less its ``workloads``
METRIC_DEFS_SHA256 = 'ab37ced787d32dd32ee40aad4592bb763a2f137de39add037bb4f5b7c530866a'
BENCH = real_benchmark()


def is_subsequence(held, of) -> bool:
    rest = iter(of)
    return all(name in rest for name in held)


@pytest.mark.parametrize("name", list(CONFIG_SHA256))
def test_the_configuration_file_is_the_parents_byte_for_byte(name):
    (entry,) = [c for c in BENCH["configs"] if c["name"] == name]
    assert sha256(entry["file"]) == CONFIG_SHA256[name]


@pytest.mark.parametrize("name", list(CELLS_AT_PARENT))
def test_load_cell_gives_what_it_gave_at_the_parent(name):
    was = CELLS_AT_PARENT[name]
    cell = spec.load_cell(BENCH, name)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        was["chips"], was["config"], was["traffic"])
    (entry,) = [c for c in BENCH["configs"] if c["name"] == was["config"]]
    with open(os.path.join(REPO, entry["file"]), encoding="utf-8") as f:
        assert cell.config == json.load(f)  # whole, and hashed above
    path = spec.find_file(cell.paths, "traffic", was["traffic"] + ".json")
    assert cell.traffic == spec.load_json(path)
    if was["traffic"] in RELIMITED_IN_PR_53:
        # the one traffic file PR 53 changed: its ``check`` and nothing else
        less_check = {k: v for k, v in cell.traffic.items() if k != "check"}
        digest = hashlib.sha256(json.dumps(less_check, sort_keys=True).encode()).hexdigest()
        assert digest == RELIMITED_IN_PR_53[was["traffic"]]["less_check_sha256"]
        limits = {k: v for k, v in cell.traffic["check"].items() if k != "basis"}
        assert limits == RELIMITED_IN_PR_53[was["traffic"]]["limits"]
    else:
        assert sha256(os.path.relpath(path, REPO)) == was["traffic_sha256"]
    # every metric it reported, in the order it had; a later PR appends
    assert [m["name"] for m in cell.end_to_end] == was["end_to_end"]
    assert is_subsequence(was["per_layer"], [m["name"] for m in cell.per_layer])


def test_the_metrics_that_were_there_are_defined_as_they_were():
    def less_lists(metrics, names):
        return [{k: v for k, v in m.items() if k != "workloads"}
                for m in metrics if m["name"] in names]

    e2e = {n for was in CELLS_AT_PARENT.values() for n in was["end_to_end"]}
    layer = {n for was in CELLS_AT_PARENT.values() for n in was["per_layer"]}
    held = [less_lists(BENCH["end_to_end"], e2e), less_lists(BENCH["per_layer"], layer)]
    assert len(held[0]) == len(e2e) and len(held[1]) == len(layer)
    digest = hashlib.sha256(json.dumps(held, sort_keys=True).encode()).hexdigest()
    assert digest == METRIC_DEFS_SHA256
