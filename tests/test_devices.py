"""The small functions that keep a run on the device it was given
(``utils/devices.py``, ``telemetry.py``'s peak table,
``autotune.current_device_kind``, ``engine/budget.py``), with stub devices and
no subprocess."""

import os
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dev(platform: str, kind: str):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


class TestDeviceHandling:
    """A program that measures the accelerator never moves itself to another
    backend, never assumes a peak or a memory size for a chip it cannot name,
    and keeps its compile cache where the environment says (or in the
    checkout)."""

    def test_guard_raises_without_tpu_unless_cpu_was_asked_for(self):
        from distrl_llm_tpu.utils.devices import require_tpu

        cpu = [_dev("cpu", "cpu")]
        tpu = [_dev("tpu", "TPU v5 lite")]
        assert require_tpu(tpu) is tpu
        assert require_tpu(tpu, cpu_requested="cpu") is tpu
        assert require_tpu(cpu, cpu_requested="cpu") is cpu
        assert require_tpu(cpu, cpu_requested=" CPU ") is cpu
        for asked in (None, "", "tpu", "tpu,cpu"):
            with pytest.raises(RuntimeError, match="no TPU"):
                require_tpu(cpu, cpu_requested=asked)
        with pytest.raises(RuntimeError, match="no TPU"):
            require_tpu([_dev("gpu", "A100")], cpu_requested="cpu")

    def test_unknown_device_kind_is_an_error_not_a_default_peak(
        self, monkeypatch
    ):
        import jax

        from distrl_llm_tpu import telemetry

        assert telemetry.peak_flops_for_kind("TPU v5 lite") == 197e12
        assert telemetry.peak_flops_for_kind("TPU v5e") == 197e12
        with pytest.raises(ValueError, match="TPU v9x"):
            telemetry.peak_flops_for_kind("TPU v9x")
        monkeypatch.delenv("DISTRL_PEAK_FLOPS", raising=False)
        monkeypatch.setattr(jax, "devices", lambda: [_dev("tpu", "TPU v9x")])
        with pytest.raises(ValueError, match="TPU v9x"):
            telemetry.device_peak_flops()
        # the CPU has no peak, and publishes no utilisation
        monkeypatch.setattr(jax, "devices", lambda: [_dev("cpu", "cpu")])
        assert telemetry.device_peak_flops() is None

    def test_unnamed_tpu_is_an_error_for_plan_keys_too(self, monkeypatch):
        import jax

        from distrl_llm_tpu.autotune import current_device_kind

        monkeypatch.setattr(jax, "devices", lambda: [_dev("tpu", "TPU v5 lite")])
        assert current_device_kind() == "tpu_v5e"
        monkeypatch.setattr(jax, "devices", lambda: [_dev("tpu", "TPU x1")])
        with pytest.raises(ValueError, match="TPU x1"):
            current_device_kind()

        def no_backend():
            raise RuntimeError("Unable to initialize backend")

        monkeypatch.setattr(jax, "devices", no_backend)
        assert current_device_kind() == "unknown"

    def test_tpu_without_bytes_limit_is_an_error_not_16_gib(self):
        from distrl_llm_tpu.engine.budget import (
            DEFAULT_HBM_BYTES, device_hbm_bytes,
        )

        def dev(platform, stats):
            d = _dev(platform, platform)
            d.memory_stats = lambda: stats
            return d

        assert device_hbm_bytes(dev("tpu", {"bytes_limit": 123})) == 123
        for stats in (None, {}, {"bytes_in_use": 1}):
            with pytest.raises(RuntimeError, match="bytes_limit"):
                device_hbm_bytes(dev("tpu", stats))
        assert device_hbm_bytes(dev("cpu", None)) == DEFAULT_HBM_BYTES

    def test_compile_cache_helper(self, monkeypatch, tmp_path):
        import jax

        from distrl_llm_tpu.utils import devices

        # an exported directory is JAX's own business: nothing is set in code
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
        assert devices.enable_compile_cache() == str(tmp_path / "c")
        assert jax.config.jax_compilation_cache_dir == before
        assert not (tmp_path / "c").exists()
        # a CPU rehearsal keeps none
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert devices.enable_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before
        # otherwise: the fixed path inside the checkout
        monkeypatch.delenv("JAX_PLATFORMS")
        try:
            got = devices.enable_compile_cache()
            assert got == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert "/tmp" not in got and str(os.getpid()) not in got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
