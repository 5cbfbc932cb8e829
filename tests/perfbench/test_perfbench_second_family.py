"""What a second model family brings beside its configuration and reference,
rehearsed as NEW files under ``tests/perfbench/tiny/`` (PR 27): its own counts
(``tiny_counts.py``, named by ``configs/tiny-counted.json``) and a learner cell
whose traffic file states its own tolerances
(``traffic/tiny-learner-checked.json``). Its scope name is held in
``test_perfbench_trace_scopes.py``. No file of ``perfbench/`` names any of them.
"""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import correct, roofline, spec
from rehearsal_helpers import assert_cell_ran, run_cell, shared_cell
from tiny_spec import REPO, TINY_DIR, real_benchmark, tiny_benchmark, write_tiny_benchmark

TINY = tiny_benchmark()
CHECKED = "tiny-counted.learner-checked"
#: a dense model small enough to count on paper (``test_perfbench_roofline.py``'s)
TOY = dict(hidden_size=8, num_heads=2, num_kv_heads=1, head_dim=4,
           intermediate_size=16, vocab_size=32, num_layers=3,
           attention_bias=True, tie_word_embeddings=False)
HALF = {**TOY, "intermediate_size": 8}
PEAKS = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e6}
LEARNER = {"seq_len": 16, "answer_len": 12, "lora_rank": 2}
ROLLOUT = {"weight_bytes": 2, "lora_rank": 2, "kv_bytes": 2}


@pytest.fixture(scope="module")
def tiny_bench_file(tmp_path_factory):
    return write_tiny_benchmark(tmp_path_factory.mktemp("tiny"))


def stub_context(config):
    """All of a run that ``required_work`` looks at: the cell's paths and its
    configuration file."""
    return SimpleNamespace(cell=SimpleNamespace(paths=tuple(TINY["paths"]), config=config))


def observed():
    """What a run hands its readers, by hand: two updates of 64 tokens in 4 s,
    one round of 5 decode steps in 2 s, and a traced round whose kernel ran
    for half a second."""
    unit = {"steps_dispatched": 5, "prompt_lens": [3, 0], "gen_lens": [2, 3]}
    return {
        "peaks": PEAKS, "model": TOY, "chips": 1, "learner": LEARNER, "rollout": ROLLOUT,
        "units": [{"tokens": 64, "t0": 0.0, "t1": 2.0, **unit},
                  {"tokens": 64, "t0": 2.0, "t1": 4.0}],
        "traced_units": [unit],
        "trace": {"devices": 1, "ops_s": {"%paged_attention_native bf16[8]": 0.5}},
    }


def required_work():
    return spec.load_module(real_benchmark()["paths"], "readers", "required_work")


def expected(counts_of, model, what):
    """``required_work``'s arithmetic over ``observed()``, written out."""
    if what == "learner_mfu":
        flops = counts_of.train_flops_per_token(model, **LEARNER)
        return 100.0 * flops * (128 / 4.0) / 1e6
    kv = counts_of.kv_read_bytes(model, [3, 0], [2, 3], kv_bytes=2)
    if what == "decode_bandwidth_util":
        weights = counts_of.decode_weight_bytes(model, weight_bytes=2, lora_rank=2)
        return 100.0 * (5 * weights + kv) / 1e6 / 2.0
    return 100.0 * kv / 1e6 / 0.5


ARGS = {
    "learner_mfu": {"what": "learner_mfu"},
    "decode_bandwidth_util": {"what": "decode_bandwidth_util"},
    "paged_attn_roofline": {"what": "paged_attn_roofline", "regex": "^%paged_attention_native "},
}


@pytest.mark.parametrize("what", sorted(ARGS))
def test_with_no_counts_key_the_reader_counts_with_roofline(what):
    for config in ({}, {"counts": "roofline"}):
        value = required_work().read(observed(), ARGS[what], stub_context(config))
        assert value == pytest.approx(expected(roofline, TOY, what), rel=1e-12)
    counts = spec.load_module(TINY["paths"], "", "roofline")
    assert counts.__file__ == os.path.join(REPO, "perfbench", "roofline.py")


@pytest.mark.parametrize("what", sorted(ARGS))
def test_the_reader_calls_the_counts_module_the_configuration_names(what):
    config = spec.load_json(os.path.join(REPO, TINY_DIR, "configs", "tiny-counted.json"))
    assert config["counts"] == "tiny_counts"
    value = required_work().read(observed(), ARGS[what], stub_context(config))
    # tiny_counts: the dense counts at half the MLP width; KV bytes do not differ
    assert value == pytest.approx(expected(roofline, HALF, what), rel=1e-12)
    dense = expected(roofline, TOY, what)
    if what == "paged_attn_roofline":
        assert value == pytest.approx(dense)
    else:
        assert value < dense
    counts = spec.load_module(TINY["paths"], "", "tiny_counts")
    assert counts.__file__ == os.path.join(REPO, TINY_DIR, "tiny_counts.py")


def test_a_counts_module_has_the_three_functions_with_rooflines_signatures():
    import inspect

    counts = spec.load_module(TINY["paths"], "", "tiny_counts")
    for name in ("train_flops_per_token", "decode_weight_bytes", "kv_read_bytes"):
        assert inspect.signature(getattr(counts, name)).parameters.keys() == \
            inspect.signature(getattr(roofline, name)).parameters.keys(), name


def test_a_counts_module_that_is_not_there_is_a_spec_error():
    with pytest.raises(spec.SpecError, match="no_such_counts.py"):
        required_work().read(observed(), ARGS["learner_mfu"],
                             stub_context({"counts": "no_such_counts"}))


def test_the_reader_names_no_counting_module():
    with open(os.path.join(REPO, "perfbench", "readers", "required_work.py")) as f:
        source = f.read()
    assert "import roofline" not in source and "perfbench.roofline" not in source
    # nothing to read: no run, or a run that observed no peaks (the CPU)
    assert required_work().read(observed(), ARGS["learner_mfu"], None) is None
    assert required_work().read({}, ARGS["learner_mfu"], stub_context({})) is None


def test_the_dense_configuration_names_no_counts_and_keeps_rooflines():
    held = spec.load_json(os.path.join(REPO, "perfbench", "configs", "qwen2.5-7b-L14.json"))
    assert "counts" not in held  # the default serves it: its readings must not move


# ------------------------------------------- the learner's tolerances, by cell


class StubReference:
    """A reference whose loss is 1.0 and whose gradient is 1 on three elements
    and -0.01 on the fourth (0.33% of the gradient's absolute mass)."""

    @staticmethod
    def pg_loss_and_lora_grad(params, model_cfg, lora, scale, ids, mask, answer_mask, coeffs):
        import jax.numpy as jnp

        return jnp.float32(1.0), {"a": jnp.asarray([1.0, 1.0, 1.0, -0.01], jnp.float32)}


def stub_update(check=None, loss=1.0):
    """An update that moved every element DOWN (right for the first three,
    wrong for the last) and whose loss is ``loss``: scaled loss error
    |loss - 1| / (0.5 x 10), sign mass 3 / 3.01."""
    before = {"a": np.zeros(4, np.float32)}
    after = {"a": np.full(4, -1e-3, np.float32)}
    ids = np.ones((2, 4), np.int32)
    return correct.learner_update_check(
        StubReference, None, None, before, after, 1.0, loss, ids, ids, ids,
        np.asarray([0.5, -0.5], np.float32), check=check)


def test_the_learner_check_falls_back_to_the_two_constants():
    out = stub_update()
    assert out["tol_loss_scaled"] == correct.LOSS_SCALED_TOL == 2e-3
    assert out["tol_grad_sign_mass"] == correct.GRAD_SIGN_MASS_TOL == 0.995
    assert out["grad_sign_mass"] == pytest.approx(3 / 3.01) and out["ok"] is True
    assert stub_update(check={"basis": "no tolerance of its own"})["ok"] is True
    assert stub_update(loss=1.02)["ok"] is False  # 4e-3 scaled, over 2e-3


@pytest.mark.parametrize("check, loss, ok", [
    ({"loss_scaled_tol": 1e-2}, 1.02, True),  # a looser loss: the cell's own floor
    ({"loss_scaled_tol": 1e-4}, 1.001, False),  # a tighter one: 2e-4 scaled
    ({"grad_sign_mass_tol": 0.999}, 1.0, False),  # 0.99668 under a tighter mass
    ({"grad_sign_mass_tol": 0.99, "loss_scaled_tol": 1e-4}, 1.0, True),
])
def test_the_learner_check_honours_the_cells_tolerances_and_prints_them(check, loss, ok):
    out = stub_update(check={**check, "basis": "a test"}, loss=loss)
    assert out["ok"] is ok
    assert out["tol_loss_scaled"] == check.get("loss_scaled_tol", correct.LOSS_SCALED_TOL)
    assert out["tol_grad_sign_mass"] == check.get(
        "grad_sign_mass_tol", correct.GRAD_SIGN_MASS_TOL)


@pytest.mark.parametrize("trace", [0, 1])
def test_a_learner_cell_that_states_its_tolerances_runs_under_them(tiny_bench_file, trace):
    """The second configuration's cell end to end on the CPU: the check line
    prints the traffic file's tolerances. (The plain learner cell's line
    prints the defaults: ``test_perfbench_rehearsal.py``.)"""
    line, notes = shared_cell(tiny_bench_file, CHECKED, trace)
    assert_cell_ran(line, notes, trace)
    stated = spec.load_cell(TINY, CHECKED).traffic["check"]
    assert notes["check"]["tol_loss_scaled"] == stated["loss_scaled_tol"] == 1e-6
    assert notes["check"]["tol_grad_sign_mass"] == stated["grad_sign_mass_tol"] == 0.999995
    assert notes["run"]["config"] == "tiny-counted"


def test_the_cells_control_is_not_correct_under_its_tolerances(tmp_path):
    """The control of ``tiny-learner-checked.json``'s ``basis``: the same cell
    in bfloat16, the nearest precision below the float32 the configuration
    states. Not correct under the cell's tolerances; under the defaults (set
    at 7B-L14 in bf16) it would pass, which is why a cell states its own."""
    config = spec.load_json(os.path.join(REPO, TINY_DIR, "configs", "tiny-counted.json"))
    config["torch_dtype"] = "bfloat16"
    bench = tiny_benchmark()
    with open(tmp_path / "control.json", "w", encoding="utf-8") as f:
        json.dump(config, f)
    for entry in bench["configs"]:
        if entry["name"] == "tiny-counted":
            entry["file"] = str(tmp_path / "control.json")
    with open(tmp_path / "BENCHMARK.control.json", "w", encoding="utf-8") as f:
        json.dump(bench, f)
    line, notes = run_cell(str(tmp_path / "BENCHMARK.control.json"), CHECKED, 0)
    check = notes["check"]
    assert line["correct"] is False and check["ok"] is False
    assert check["loss_scaled_err"] > check["tol_loss_scaled"]
    assert check["grad_sign_mass"] < check["tol_grad_sign_mass"]
    assert check["loss_scaled_err"] <= correct.LOSS_SCALED_TOL
    assert check["grad_sign_mass"] >= correct.GRAD_SIGN_MASS_TOL


# ------------------------------------------------ a family's weights by rule (PR 35)

#: sha256 over every leaf (key path, then float32 bytes) of the tiny tree as
#: ``weights.py`` made it at PR 34, before any rule file could be named
TINY_TREE_AT_PR_34 = {
    ("tiny", "float32", 11): "eadef47dd05a7e0418c7cb1e821504f2ddfb8e48a2a9109fc2ccd015bc7273a1",
    ("tiny", "bfloat16", 5): "a1b83de7706232cab564057b1ecf41cd6d2e8dc992e52469ae7c52efd95df91b",
    ("tiny-latent-moe", "float32", 11):
        "8da83a99ff9cfea0532794744d95d54d2c8b7a405f325f9d6a58e4b84964c6e0",
}


def tree_digest(tree, leave_out=()):
    import hashlib

    import jax

    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if jax.tree_util.keystr(path) not in leave_out:
            h.update(jax.tree_util.keystr(path).encode())
            h.update(np.asarray(leaf, np.float32).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("preset, dtype, seed", TINY_TREE_AT_PR_34, ids=lambda v: str(v))
def test_with_no_rule_file_the_seeded_weights_are_the_parents_bit_for_bit(preset, dtype, seed):
    from distrl_llm_tpu.models.configs import PRESETS
    from perfbench import weights

    made = weights.make_base_params(PRESETS[preset], dtype, seed)
    assert tree_digest(made) == TINY_TREE_AT_PR_34[preset, dtype, seed]
    assert weights.load_rules(TINY["paths"], {"reference": "reference"}) == ()


def test_a_rule_draws_the_leaf_it_names_and_leaves_every_other_as_it_was():
    from distrl_llm_tpu.models.configs import PRESETS
    from perfbench import weights

    cfg = PRESETS["tiny-latent-moe"]
    plain = weights.make_base_params(cfg, "float32", 11)
    rules = (
        {"leaf": "e_score_bias$", "draw": "constant", "value": 0.0},
        {"leaf": "^layers/latent_moe/router$", "draw": "uniform", "low": -0.5, "high": 0.5},
        {"leaf": "^layers/latent/wq$", "draw": "constant", "value": 9.0},
        {"leaf": "/wq$", "draw": "normal", "std": 0.3, "mean": 2.0},  # the first match won
    )
    ruled = weights.make_base_params(cfg, "float32", 11, rules=rules)
    named = ("['layers']['latent_moe']['e_score_bias']", "['layers']['latent_moe']['router']",
             "['layers']['latent']['wq']", "['layers']['latent_moe']['wq']")
    assert tree_digest(ruled, named) == tree_digest(plain, named)
    assert tree_digest(ruled) != TINY_TREE_AT_PR_34["tiny-latent-moe", "float32", 11]
    moe = ruled["layers"]["latent_moe"]
    assert not np.asarray(moe["e_score_bias"]).any()
    assert np.asarray(plain["layers"]["latent_moe"]["e_score_bias"]).any()  # Normal(0, 0.02)
    router = np.asarray(moe["router"])
    assert -0.5 <= router.min() < -0.4 and 0.4 < router.max() <= 0.5
    assert (np.asarray(ruled["layers"]["latent"]["wq"]) == 9.0).all()
    wq = np.asarray(moe["wq"])
    assert abs(wq.mean() - 2.0) < 0.02 and abs(wq.std() - 0.3) < 0.02
    # the same seed, the same values; a stacked leaf differs layer by layer
    again = weights.make_base_params(cfg, "float32", 11, rules=rules)
    assert tree_digest(again) == tree_digest(ruled)
    assert not np.array_equal(np.asarray(moe["wq"][0]), np.asarray(moe["wq"][1]))


RULE_FILES = {
    "not-a-list": ({"leaf": "wq", "draw": "constant", "value": 1}, "a list of rules"),
    "empty": ([], "a list of rules"),
    "no-leaf": ([{"draw": "constant", "value": 1}], "needs a 'leaf' regex"),
    "unknown-draw": ([{"leaf": "wq", "draw": "lognormal", "std": 1}], "needs a 'leaf' regex"),
    "normal-without-std": ([{"leaf": "wq", "draw": "normal"}], "holds the numbers ('std',)"),
    "uniform-with-std": ([{"leaf": "wq", "draw": "uniform", "low": 0, "high": 1, "std": 2}],
                         "holds the numbers ('low', 'high')"),
    "a-string-for-a-number": ([{"leaf": "wq", "draw": "constant", "value": "0"}],
                              "holds the numbers ('value',)"),
    "no-regex": ([{"leaf": "wq(", "draw": "constant", "value": 0}], "is no regex"),
}


@pytest.mark.parametrize("held, said", RULE_FILES.values(), ids=RULE_FILES)
def test_a_rule_file_that_is_not_plain_rules_is_refused(tmp_path, held, said):
    import re

    from perfbench import weights

    os.makedirs(tmp_path / "weight_rules")
    with open(tmp_path / "weight_rules" / "bad.json", "w", encoding="utf-8") as f:
        json.dump(held, f)
    with pytest.raises(spec.SpecError, match=re.escape(said)):
        weights.load_rules([str(tmp_path)], {"weight_rules": "bad"})


def test_a_rule_that_names_no_leaf_and_a_file_that_is_not_there_are_refused():
    from distrl_llm_tpu.models.configs import TINY as TINY_MODEL
    from perfbench import weights

    with pytest.raises(spec.SpecError, match=r"\['A_log\$'\] draw no leaf"):
        weights.make_base_params(TINY_MODEL, "float32", 1, rules=(
            {"leaf": "^layers/wq$", "draw": "constant", "value": 1.0},
            {"leaf": "A_log$", "draw": "uniform", "low": -4.0, "high": -1.0}))
    with pytest.raises(spec.SpecError, match="an earlier rule takes every leaf"):
        weights.make_base_params(TINY_MODEL, "float32", 1, rules=(
            {"leaf": "wq$", "draw": "constant", "value": 1.0},
            {"leaf": "^layers/wq$", "draw": "constant", "value": 2.0}))
    with pytest.raises(spec.SpecError, match="weight_rules/elsewhere.json"):
        weights.load_rules(TINY["paths"], {"weight_rules": "elsewhere"})


def test_the_counted_configuration_names_a_rule_file_and_its_cell_runs_under_it(tiny_bench_file):
    """The seam end to end on the CPU: ``tiny-counted.json`` names
    ``tiny/weight_rules/tiny-counted.json``, the learner driver draws ``bq`` by
    it, and the cell is correct under its float32 tolerances. ``tiny.json``
    names none."""
    from perfbench import assembly, weights

    cell = spec.load_cell(TINY, CHECKED)
    rules = weights.load_rules(cell.paths, cell.config)
    assert [(r["leaf"], r["draw"], r["std"]) for r in rules] == [("^layers/bq$", "normal", 0.1)]
    assert weights.load_rules(cell.paths, spec.load_cell(TINY, "tiny.learner").config) == ()
    model_cfg = assembly.model_config(cell.config)
    plain = weights.make_base_params(model_cfg, "float32", 3)
    ruled = weights.make_base_params(model_cfg, "float32", 3, rules=rules)
    assert abs(np.asarray(plain["layers"]["bq"]).std() - 0.25) < 0.05
    assert abs(np.asarray(ruled["layers"]["bq"]).std() - 0.1) < 0.02
    assert tree_digest(ruled, ("['layers']['bq']",)) == tree_digest(plain, ("['layers']['bq']",))
    line, notes = shared_cell(tiny_bench_file, CHECKED, 0)
    assert line["correct"] is True and notes["check"]["ok"] is True
