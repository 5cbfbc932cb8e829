"""``chip_smoke.py`` on the CPU: its assembly runs end to end at tiny size,
the script itself refuses to pass without an accelerator, and spawned workers
are each given their own chip.

The chip run is the builder's and the driver's (``python chip_smoke.py`` on
the machine with the TPU); these hold what a CPU can: control flow, the
asserts' own logic, and the refusal.
"""

import dataclasses
import io
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from train_distributed import SmokeSizes  # noqa: E402

from distrl_llm_tpu.models import TINY  # noqa: E402

TINY_SIZES = SmokeSizes(
    prompts=4, candidates=4, steps=3, max_prompt_tokens=96, max_new_tokens=24,
    micro_batch=4, lora_rank=4, page_size=8, max_concurrent_rows=8,
    decode_chunk=4,
)


@pytest.fixture(scope="module")
def compiles():
    return chip_smoke.CompileLog()


def test_the_latent_and_expert_checks_hold_at_tiny_size(monkeypatch):
    """The two checks the kernels phase runs at Kimi-VL-A3B's widths, here at
    the test preset's: absorbed against expanded attention, and an expert layer
    against every pair computed in float32, no pair dropped."""
    import jax

    key = jax.random.PRNGKey(0)
    att = chip_smoke._latent_attention_case(
        key, rows=3, heads=4, nope=16, rope=8, v_dim=16, rank=32, context=40)
    assert att["max_abs_err"] < 5e-2 and att["context"] == 40
    from distrl_llm_tpu.ops import latent_attention

    # a shared block of 3 pages: the scores of 4 rows' 4 heads over 3 pages of 8
    monkeypatch.setattr(latent_attention, "SHARED_SCORE_BYTES", 4 * 4 * 3 * 8 * 4)
    shared = chip_smoke._shared_prefix_attention_case(
        key, rows=4, heads=4, nope=16, rope=8, v_dim=16, rank=32, latent_row=48,
        prompt=53, page=8, per=3)
    # 53 tokens are 6 full pages, two blocks read once; what is left a row
    assert shared["max_abs_err"] < 5e-2 and shared["shared_blocks"] == 2
    assert shared["pages_read"] == 6 + shared["pages_attended"] - 4 * 6
    assert shared["impl"] == "xla"  # a CPU: the chip's run asserts the launch
    chosen = chip_smoke._shared_prefix_attention_case(
        key, rows=4, heads=4, nope=16, rope=8, v_dim=16, rank=32, latent_row=48,
        prompt=53, page=8, per=3, choose=0.4)  # under a drawn choice
    assert chosen["max_abs_err"] < 5e-2 and chosen["impl"] == "xla"
    for tokens, form in ((24, "dense"), (1024, "grouped")):
        layer = chip_smoke._expert_layer_case(
            key, tokens=tokens, hidden=64, width=32, experts=8, per_token=2)
        assert layer["pairs"] == 2 * tokens and layer["form"] == form
        assert layer["fullest_expert"] >= tokens // 4
        run, laid = layer["blocks_run_laid"]  # the dense form lays no block
        assert (laid > 0) == (form == "grouped") and run <= laid


@pytest.mark.parametrize("heads", [
    dict(nope=16, v_dim=16),  # K and V of one width, the positions' mask
    dict(nope=24, v_dim=32, choose=0.3),  # two widths under a drawn choice
], ids=["positions", "chosen"])
def test_the_latent_fold_check_holds_at_tiny_size(heads):
    """The kernels phase's check of a latent prefill segment's folds, here on
    the form a CPU takes: the dispatch record says "xla", which the chip's
    phase refuses (it asserts "kernel")."""
    import jax

    case = chip_smoke._latent_fold_case(
        jax.random.PRNGKey(0), rows=2, heads=2, rope=8, rank=32, segment=16, **heads)
    assert case["impl"] == "xla" and case["folds"] == 3 and case["max_abs_err"] < 2e-2


@pytest.mark.parametrize("heads", [
    dict(kv_heads=2, group=4, head_dim=16, key_row=16, v_dim=16),
    dict(kv_heads=1, group=5, head_dim=24, key_row=32, v_dim=16),  # a padded key row
], ids=["a_head_a_row", "a_padded_row"])
def test_the_softmax_fold_check_holds_at_tiny_size(heads):
    """The kernels phase's check of a full layer's prefill folds over K/V
    pages, here on the form a CPU takes ("xla": the chip's phase asserts
    "kernel")."""
    import jax

    case = chip_smoke._softmax_fold_case(
        jax.random.PRNGKey(0), rows=2, segment=16, page=8, **heads)
    assert case["impl"] == "xla" and case["folds"] == 3 and case["max_abs_err"] < 2e-2


def test_the_delta_step_check_holds_at_tiny_size():
    """The kernels phase's check of the one-token delta rule, here on the form
    a CPU takes: the dispatch record says "plain", which the chip's phase
    refuses (it asserts "kernel")."""
    import jax

    case = chip_smoke._delta_step_case(jax.random.PRNGKey(0), rows=2, heads=3, head_dim=16)
    assert case == {"impl": "plain", "max_abs_err": 0.0}


def test_the_power_step_check_holds_at_tiny_size():
    """The kernels phase's check of the one-token power-retention step, here on
    the form a CPU takes: the dispatch record says "plain", which the chip's
    phase refuses (it asserts "kernel")."""
    import jax

    case = chip_smoke._power_step_case(
        jax.random.PRNGKey(0), rows=2, kv_heads=2, group=5, head_dim=16)
    assert case == {"impl": "plain", "max_abs_err": 0.0}


@pytest.mark.parametrize("engine_impl,steps", [("paged", 3), ("dense", 1)])
def test_trainer_phase_runs_end_to_end_at_tiny_size(compiles, engine_impl, steps):
    """The assembly the chip runs at Qwen2.5-0.5B width, here at TINY: every
    assert of the trainer phases holds (finite losses, a changed adapter
    after each update, step k sampling under version k-1, no program
    compiled twice), on the reference paths a CPU resolves."""
    sizes = dataclasses.replace(TINY_SIZES, steps=steps)
    report = chip_smoke.run_trainer(
        TINY, chip_smoke.trainer_config(engine_impl, 0, sizes), sizes, 0,
        compiles,
    )
    summary = report["summary"]
    json.dumps(summary)  # what the phase prints is JSON
    assert len(summary["losses"]) == steps
    assert summary["policy_versions"] == list(range(steps))
    assert summary["sampler"] == "xla"  # a CPU runs no Pallas sampler
    assert summary["paged_dispatch"] == (
        "reference" if engine_impl == "paged" else None
    )
    assert summary["plan_source"] == "disabled"  # no plan database is read
    assert summary["compiled_after_step_1"] == []


def test_four_chip_phase_on_virtual_devices(compiles, capsys):
    """The role-split comparison on four of conftest's virtual CPU devices:
    2 actors + 2 learners match the one-device run, and every array sits on
    its role's devices."""
    import jax

    sizes = dataclasses.replace(
        TINY_SIZES, prompts=8, steps=2, max_concurrent_rows=16
    )
    four = jax.devices()[:4]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "devices", lambda: four)
        chip_smoke.phase_four_chips(0, compiles, model_cfg=TINY, sizes=sizes)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["phase"] == "four_chips"
    ids = [d.id for d in four]
    assert out["placement"]["kv_pages"] == ids[:2]
    assert out["placement"]["optimizer_state"] == ids[2:]
    assert max(out["loss_rel_err"]) <= chip_smoke.LOSS_RTOL
    assert out["adapter_rel_l2"] <= chip_smoke.ADAPTER_REL_L2_TOL


def test_script_fails_at_the_device_phase_without_a_tpu():
    """Started with ``JAX_PLATFORMS=cpu`` the script exits non-zero before
    any phase and never prints a result."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout and '"phase"' not in out.stdout
    assert "no TPU" in out.stderr


class TestWorkerChipEnvironment:
    """One process per chip: the spawn code names each worker's chip in its
    environment, and refuses — it does not hang — when the parent holds the
    TPU it would have to share."""

    def test_each_worker_gets_its_own_chip(self):
        from distrl_llm_tpu.utils.devices import worker_env

        parent = {"PATH": "/bin", "HOME": "/h"}
        in_use: list[int] = []
        seen = []
        for _ in range(3):
            env, chip = worker_env(
                parent, {"DISTRL_OBS": "1"}, chips_in_use=in_use,
                parent_holds_tpu=False,
            )
            in_use.append(chip)
            seen.append((chip, env["TPU_VISIBLE_CHIPS"], env["TPU_PROCESS_PORT"]))
            assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
            assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
            assert env["DISTRL_OBS"] == "1" and env["PATH"] == "/bin"
        assert [c for c, _, _ in seen] == [0, 1, 2]
        assert [v for _, v, _ in seen] == ["0", "1", "2"]
        assert len({p for _, _, p in seen}) == 3  # a runtime port each
        # a chip freed by a dead worker is the next one handed out
        _, chip = worker_env(parent, {}, chips_in_use=[0, 2],
                             parent_holds_tpu=False)
        assert chip == 1

    def test_parent_confined_to_named_chips_leaves_the_others(self):
        from distrl_llm_tpu.utils.devices import worker_env

        parent = {"TPU_VISIBLE_CHIPS": "0,1"}
        env, chip = worker_env(parent, {}, parent_holds_tpu=True)
        assert chip == 2 and env["TPU_VISIBLE_CHIPS"] == "2"
        _, chip = worker_env(parent, {}, chips_in_use=[2],
                             parent_holds_tpu=True)
        assert chip == 3

    def test_parent_holding_every_chip_is_refused(self):
        from distrl_llm_tpu.utils.devices import worker_env

        with pytest.raises(RuntimeError, match="one process per chip"):
            worker_env({}, {}, parent_holds_tpu=True)

    def test_cpu_worker_needs_no_chip(self):
        from distrl_llm_tpu.utils.devices import holds_tpu, worker_env

        for parent_holds in (False, True):
            env, chip = worker_env(
                {"A": "1"}, {"JAX_PLATFORMS": "cpu"},
                parent_holds_tpu=parent_holds,
            )
            assert chip is None and "TPU_VISIBLE_CHIPS" not in env
        # this process runs on the CPU backend: it holds no TPU
        assert holds_tpu() is False

    def test_fleet_supervisor_spawns_through_it(self, monkeypatch):
        """The supervisor's Popen gets the mapped environment, and each live
        worker's chip stays out of the next one's reach."""
        from distrl_llm_tpu.distributed import fleet

        spawned = []

        class FakeProc:
            def __init__(self, argv, env=None, **kw):
                spawned.append(env)
                self.stdout = io.StringIO(f"PORT {9000 + len(spawned)}\n")
                self.returncode = None

            def poll(self):
                return None

        monkeypatch.setattr(fleet.subprocess, "Popen", FakeProc)
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
        monkeypatch.setattr(fleet, "holds_tpu", lambda: False)
        sup = fleet.FleetSupervisor(fleet.WorkerSpec(), max_workers=4)
        sup.start(2)
        assert [e["TPU_VISIBLE_CHIPS"] for e in spawned] == ["0", "1"]
        monkeypatch.setattr(fleet, "holds_tpu", lambda: True)
        with pytest.raises(RuntimeError, match="one process per chip"):
            sup._spawn()
