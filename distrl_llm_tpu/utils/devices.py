"""Which device a process runs on, said once: the compile cache's place, the
guard that keeps a measurement off the wrong backend, and the environment
that gives a spawned worker its own chip.

JAX reads ``JAX_PLATFORMS`` and ``JAX_COMPILATION_CACHE_DIR`` itself; nothing
here re-reads them on its behalf. A TPU chip belongs to one process at a
time: a process that initialised a TPU backend holds every chip it can see,
and a child that needs one of those then fails or hangs.
"""

from __future__ import annotations

import os
import sys
from collections.abc import Iterable, Mapping

#: <checkout>/.jax_cache — fixed and derived from the package's own path (the
#: path is part of the cache key, so a directory that moves never hits)
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str | None:
    """Point JAX's persistent compilation cache somewhere durable and return
    the directory. Where ``JAX_COMPILATION_CACHE_DIR`` is exported JAX uses
    it by itself and nothing is set in code; otherwise the cache is
    ``DEFAULT_COMPILE_CACHE``. A run that asked for the CPU
    (``JAX_PLATFORMS=cpu``: tests, rehearsals) keeps none — its compiles
    take seconds, and XLA:CPU warns about machine features on every reload.
    Call before the first compile."""
    exported = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if exported:
        return exported
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE


def require_tpu(devices, cpu_requested: str | None = None):
    """``devices`` if they are TPU chips; raise otherwise — unless the caller
    asked for the CPU in so many words (``cpu_requested`` is the value of
    ``JAX_PLATFORMS``). Nothing downstream of this moves a measurement to
    another backend."""
    platform = devices[0].platform
    if platform == "tpu":
        return devices
    if platform == "cpu" and (cpu_requested or "").strip().lower() == "cpu":
        return devices
    raise RuntimeError(
        f"no TPU: JAX found {len(devices)} {platform} device(s). This "
        "program measures the accelerator and does not fall back; set "
        "JAX_PLATFORMS=cpu only to rehearse its control flow."
    )


def holds_tpu() -> bool:
    """True once THIS process has initialised a TPU backend (it then holds
    its chips until it exits). Never initialises one to find out."""
    if "jax" not in sys.modules:
        return False
    import jax
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized() and (
        jax.default_backend() == "tpu"
    )


def _chips(spec: str | None) -> set[int]:
    return {int(c) for c in (spec or "").split(",") if c.strip()}


#: first port of the per-worker TPU runtime endpoints (one single-process
#: "slice" per worker, so each needs its own)
_TPU_PROCESS_PORT0 = 8476


def worker_env(
    parent_env: Mapping[str, str],
    overlay: Mapping[str, str],
    *,
    chips_in_use: Iterable[int] = (),
    parent_holds_tpu: bool,
) -> tuple[dict[str, str], int | None]:
    """(environment, chip) for one spawned worker process.

    A worker whose environment asks for the CPU (``JAX_PLATFORMS=cpu``) gets
    no chip. Any other worker gets exactly one, named in its environment:
    the lowest chip index that neither the parent (its own
    ``TPU_VISIBLE_CHIPS``) nor a live sibling (``chips_in_use``) holds. A
    parent that holds a TPU backend WITHOUT having been confined to named
    chips holds them all, so a child could only share one — refused here
    with an error, instead of a child that hangs at start-up."""
    env = {**parent_env, **overlay}
    if env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return env, None
    parent_chips = _chips(parent_env.get("TPU_VISIBLE_CHIPS"))
    if parent_holds_tpu and not parent_chips:
        raise RuntimeError(
            "this process initialised a TPU backend over every chip of the "
            "host, so a spawned worker would have to share a chip with it "
            "(one process per chip). Start the driver confined to its own "
            "chips (TPU_VISIBLE_CHIPS=<learner chips>) so workers can take "
            "the others, or give the workers JAX_PLATFORMS=cpu."
        )
    taken = (parent_chips if parent_holds_tpu else set()) | set(chips_in_use)
    chip = next(i for i in range(len(taken) + 1) if i not in taken)
    port = _TPU_PROCESS_PORT0 + chip
    env.update({
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "TPU_PROCESS_PORT": str(port),
        "CLOUD_TPU_TASK_ID": "0",
    })
    return env, chip
