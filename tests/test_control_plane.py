"""Multi-process control-plane tests (N5): real worker subprocesses on CPU.

The minimum bar: a 2-process test that dispatches a rollout shard
and collects rewards over the control plane — plus health checks and the
shard-resubmission failure path the reference lacks (its worker death kills
the run, SURVEY §5).
"""

import pickle
import signal
import subprocess
import sys

import numpy as np
import pytest

from distrl_llm_tpu.distributed.control_plane import DriverClient, WorkerDeadError
from distrl_llm_tpu.native.build import native_available
from distrl_llm_tpu.utils.chunking import chunk_sizes, split_dict_lists

pytestmark = [pytest.mark.distributed]
# the native skip applies ONLY to the control-plane classes (their workers
# need the compiled transport); TestJaxDistributed is pure JAX/gloo and must
# run even without g++ — it is the only cross-process gradient-psum coverage
needs_native = pytest.mark.skipif(
    not native_available(), reason="g++ not available"
)


def spawn_worker():
    proc = subprocess.Popen(
        [sys.executable, "-m", "distrl_llm_tpu.distributed.worker_main", "--port", "0"],
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    line = proc.stdout.readline().strip()
    assert line.startswith("PORT "), line
    return proc, int(line.split()[1])


@pytest.fixture
def two_workers():
    procs, addrs = [], []
    for _ in range(2):
        p, port = spawn_worker()
        procs.append(p)
        addrs.append(("127.0.0.1", port))
    yield procs, addrs
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGKILL)
        p.wait(timeout=10)


@needs_native
class TestDispatchCollect:
    def test_rollout_shard_rewards_roundtrip(self, two_workers):
        """Driver splits a candidate batch with the reference chunking math,
        ships each shard to a worker process, and collects (n, 2) reward
        arrays — the reference's _generate_round/_compute_round_rewards RPC
        pattern (distributed_trainer.py:190–215) over our plane."""
        procs, addrs = two_workers
        driver = DriverClient(addrs)

        # two task groups of 2 candidates each, chunked like the reference
        batch = {
            "answers": [
                ["<answer>4</answer>", "wrong"],
                ["<think>t</think>\n<answer>9</answer>", "<answer>8</answer>"],
            ],
            "solution": [["4", "4"], ["9", "9"]],
        }
        sizes = chunk_sizes(2, num_actors=2, num_learners=1, learner_chunk_size=0)
        assert sum(sizes) == 2
        shards = split_dict_lists(batch, sizes[:2])
        payloads = [("rollout_rewards", s) for s in shards]
        results = driver.dispatch_objects(payloads, timeout_ms=30_000)

        assert len(results) == 2
        r0 = results[0][0]  # first shard, first group: (2, 2) rewards
        assert r0.shape == (2, 2)
        assert r0[0, 1] == 1.0 and r0[1, 1] == 0.0  # accuracy column
        r1 = results[1][0]
        assert r1[0, 1] == 1.0 and r1[1, 1] == 0.0
        driver.shutdown()
        for p in procs:
            assert p.wait(timeout=10) == 0

    def test_health_check(self, two_workers):
        procs, addrs = two_workers
        driver = DriverClient(addrs)
        assert driver.ping_all() == [True, True]
        procs[0].send_signal(signal.SIGKILL)
        procs[0].wait(timeout=10)
        assert driver.ping_all() == [False, True]
        driver.shutdown()

    def test_shard_resubmission_on_worker_death(self, two_workers):
        """A dead worker's shard is re-dispatched to the survivor instead of
        killing the round (SURVEY §5 failure: resubmission on timeout)."""
        procs, addrs = two_workers
        driver = DriverClient(addrs)
        procs[0].send_signal(signal.SIGKILL)
        procs[0].wait(timeout=10)

        payloads = [("echo", i) for i in range(4)]
        results = driver.dispatch_objects(payloads, timeout_ms=10_000)
        assert sorted(results) == [0, 1, 2, 3]
        assert driver.num_healthy == 1
        driver.shutdown()

    def test_worker_exception_propagates(self, two_workers):
        _, addrs = two_workers
        driver = DriverClient(addrs[:1])
        with pytest.raises(RuntimeError, match="unknown op"):
            driver.dispatch_objects([("nope", None)], timeout_ms=10_000)
        driver.shutdown()

    def test_all_workers_dead_raises(self, two_workers):
        procs, addrs = two_workers
        driver = DriverClient(addrs)
        for p in procs:
            p.send_signal(signal.SIGKILL)
            p.wait(timeout=10)
        with pytest.raises(WorkerDeadError, match="no healthy workers"):
            driver.dispatch_objects([("echo", 1)], timeout_ms=2000)


@needs_native
class TestDynamicMembership:
    """Elastic fleet (ISSUE 20): add_worker / retire_worker on a live
    plane, and the retire-vs-rejoin aliasing regression."""

    def test_add_worker_admits_third(self, two_workers):
        procs, addrs = two_workers
        driver = DriverClient(addrs)
        p3, port3 = spawn_worker()
        try:
            assert driver.add_worker(("127.0.0.1", port3))
            assert driver.num_healthy == 3
            assert driver.membership_epoch >= 1
            # the new member takes real dispatch work immediately
            got = driver.dispatch_objects(
                [("echo", i) for i in range(6)], timeout_ms=30_000
            )
            assert got == list(range(6))
            # a second add of an active member is refused, not duplicated
            assert not driver.add_worker(("127.0.0.1", port3))
            assert driver.num_healthy == 3
            driver.shutdown()
            assert p3.wait(timeout=10) == 0
        finally:
            if p3.poll() is None:
                p3.send_signal(signal.SIGKILL)
                p3.wait(timeout=10)

    def test_retire_worker_drains_gracefully(self, two_workers):
        procs, addrs = two_workers
        driver = DriverClient(addrs)
        assert driver.retire_worker(addrs[0], drain=True)
        # the drained worker exits 0 — the graceful-shutdown contract, not
        # a kill
        assert procs[0].wait(timeout=15) == 0
        states = {s["address"]: s for s in driver.worker_states()}
        key = f"{addrs[0][0]}:{addrs[0][1]}"
        assert states[key]["retired"] and not states[key]["healthy"]
        # the survivor still serves a full round (conservation)
        got = driver.dispatch_objects(
            [("echo", i) for i in range(4)], timeout_ms=10_000
        )
        assert got == list(range(4))
        assert driver.num_healthy == 1
        driver.shutdown()

    def test_retired_worker_is_never_redialed(self, two_workers):
        """Regression (ISSUE 20 satellite): retire is TERMINAL. The rejoin
        loop must not re-dial a retired address even when a fresh process
        answers on the same port — retired != dead-awaiting-rejoin."""
        import socket
        import time

        procs, addrs = two_workers
        driver = DriverClient(addrs, rejoin=True, rejoin_poll_s=0.05)
        epoch_before = driver.rejoin_epoch
        assert driver.retire_worker(addrs[0], drain=True)
        assert procs[0].wait(timeout=15) == 0
        # resurrect a listener on the SAME port: a rejoin loop that still
        # tracks the address would dial and re-admit it
        with socket.socket() as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(addrs[0])
            s.listen(1)
            s.settimeout(1.5)
            try:
                conn, _ = s.accept()
                conn.close()
                raise AssertionError(
                    "rejoin loop dialed a retired worker's address"
                )
            except socket.timeout:
                pass  # nobody dialed — retired stayed terminal
        assert driver.rejoin_epoch == epoch_before
        assert driver.num_healthy == 1
        # retire never books quarantine/reconnect counters — it has its
        # own series
        from distrl_llm_tpu import telemetry
        from distrl_llm_tpu.distributed import resilience

        snap = telemetry.metrics_snapshot()
        assert snap.get(resilience.CP_RETIRES, 0.0) >= 1.0
        assert snap.get(resilience.CP_QUARANTINES, 0.0) == 0.0
        time.sleep(0.1)
        driver.shutdown()

    def test_scale_event_mid_round_conserves_groups(self, two_workers):
        """A dispatch round racing a retire loses nothing: the retired
        worker's in-flight shard resubmits to the survivors."""
        import threading

        procs, addrs = two_workers
        driver = DriverClient(addrs)
        results: list = []

        def rounds():
            for _ in range(10):
                results.append(
                    driver.dispatch_objects(
                        [("echo", i) for i in range(6)], timeout_ms=30_000
                    )
                )

        th = threading.Thread(target=rounds)
        th.start()
        driver.retire_worker(addrs[1], drain=True)
        th.join(timeout=60)
        assert not th.is_alive()
        assert len(results) == 10
        for got in results:
            assert got == list(range(6))
        assert procs[1].wait(timeout=15) == 0
        driver.shutdown()


class TestJaxDistributed:
    def test_two_process_initialize(self, tmp_path):
        """jax.distributed.initialize across 2 CPU processes: both see the
        global process topology (the multi-controller entry path, SURVEY §7
        stage 8)."""
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        script = (
            "import os, sys\n"
            "sys.path.insert(0, os.getcwd())\n"
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "from distrl_llm_tpu.distributed import initialize_distributed\n"
            f"info = initialize_distributed('127.0.0.1:{port}', 2, int(sys.argv[1]))\n"
            "assert info.num_processes == 2, info\n"
            "assert info.global_device_count == 2 * info.local_device_count\n"
            "print('OK', info.process_id)\n"
        )
        import os

        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        env.pop("XLA_FLAGS", None)
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", script, str(pid)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
            )
            for pid in range(2)
        ]
        try:
            outs = [p.communicate(timeout=120) for p in procs]
        finally:
            for p in procs:  # a hung rendezvous must not leak ranks
                if p.poll() is None:
                    p.kill()
        for p, (out, err) in zip(procs, outs):
            assert p.returncode == 0, f"stdout={out}\nstderr={err}"
            assert "OK" in out

    @pytest.mark.slow
    def test_two_process_rollout_train_round(self):
        """Full round across 2 REAL jax.distributed processes:
        per-process local rollouts through the generation engine,
        then one jitted GRPO train step over the global dp mesh — the
        gradient psum crosses the process boundary (gloo CPU collectives,
        the DCN stand-in). Each rank feeds different batch rows, so the
        identical per-rank loss/adapter checksums asserted here can only
        come from a working cross-host all-reduce. Reference anchor: the
        Ray placement-group round, distributed_actor.py:543–556."""
        import os
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        worker = os.path.join(os.path.dirname(__file__), "dcn_round_worker.py")
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            # 2 local devices per process -> a 4-device global dp mesh
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        }
        procs = [
            subprocess.Popen(
                [sys.executable, worker, str(pid), "2", f"127.0.0.1:{port}"],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                env=env, cwd=os.path.dirname(os.path.dirname(worker)),
            )
            for pid in range(2)
        ]
        try:
            outs = [p.communicate(timeout=600) for p in procs]
        finally:
            for p in procs:  # a rank stuck in a collective must not leak
                if p.poll() is None:
                    p.kill()
        rounds = []
        for p, (out, err) in zip(procs, outs):
            assert p.returncode == 0, f"stdout={out}\nstderr={err}"
            assert "OK" in out, out
            rounds += [ln for ln in out.splitlines() if ln.startswith("ROUND")]
        assert len(rounds) == 2, rounds
        # rank-independent results: loss and updated-adapter checksum agree
        r0 = dict(kv.split("=") for kv in rounds[0].split()[1:])
        r1 = dict(kv.split("=") for kv in rounds[1].split()[1:])
        assert r0["loss"] == r1["loss"], (r0, r1)
        assert r0["checksum"] == r1["checksum"], (r0, r1)
