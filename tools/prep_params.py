"""Pre-build the bench's host-quantized param tree on the CPU.

The 7B int4 bench stage must not spend chip minutes on host-side
init+quantize (single core: ~15 GiB of bf16 init + groupwise int4 over
7.6e9 values). This tool runs the exact same build path bench.py uses
(`bench.host_quantized_params`) on the CPU platform and leaves the result
in BENCH_PARAMS_CACHE, where the bench restores it in seconds.

Usage: python tools/prep_params.py [model] [quant] [dtype]
       (defaults: qwen2.5-7b int4 bfloat16 — the 7B matrix stage's config;
        cache dir from BENCH_PARAMS_CACHE, default /tmp/graft_params_cache)
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

jax.config.update("jax_platforms", "cpu")  # host-only work


def main() -> int:
    import time

    import jax.numpy as jnp

    import bench
    from distrl_llm_tpu.models import QWEN2_0_5B, TINY
    from distrl_llm_tpu.models.configs import QWEN2_7B

    name = sys.argv[1] if len(sys.argv) > 1 else "qwen2.5-7b"
    quant = sys.argv[2] if len(sys.argv) > 2 else "int4"
    dtype = jnp.dtype(sys.argv[3] if len(sys.argv) > 3 else "bfloat16")
    cfg = {"tiny": TINY, "qwen2.5-0.5b": QWEN2_0_5B, "qwen2.5-7b": QWEN2_7B}[name]
    os.environ.setdefault("BENCH_PARAMS_CACHE", "/tmp/graft_params_cache")
    t0 = time.perf_counter()
    params = bench.host_quantized_params(
        name, cfg, dtype, quant, jax.devices("cpu")[0]
    )
    n_bytes = sum(
        x.nbytes for x in jax.tree_util.tree_leaves(params)
        if hasattr(x, "nbytes")
    )
    print(
        f"prep_params: {name} {quant} {dtype.name} -> "
        f"{os.environ['BENCH_PARAMS_CACHE']} "
        f"({n_bytes / 1e9:.2f} GB, {time.perf_counter() - t0:.0f}s)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
