"""Measure per-dispatch host overhead vs on-device chaining.

The engines' decode loops issue one jitted dispatch per token step
(engine.py::run_decode_loop). Where an enqueue costs more than the device
takes for the step, it bounds decode throughput regardless of chip speed.
This probe answers that with three timings at a decode-like shape (donated
state, same array in/out):

  a) N chained single-step dispatches, one block at the end
     (exactly the engine's dispatch pattern);
  b) the same N steps inside ONE dispatch via lax.scan;
  c) a trivial 1-element dispatch chain (pure enqueue cost).

If (a)/N >> (b)/N, per-dispatch overhead dominates and scan-chunking the
decode loop is the next big win; if they're close, the chip itself is the
bound and kernel/bandwidth work is where to look.
"""

import sys
import time

sys.path.insert(0, ".")


def main() -> int:
    import jax
    import jax.numpy as jnp

    print("backend:", jax.default_backend())
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 64

    # decode-ish state: [B, H] activations + a step counter
    b, h = 256, 2048
    w = jnp.ones((h, h), jnp.bfloat16) * 0.01

    @jax.jit
    def step(x):
        return jnp.tanh(x @ w)

    x = jnp.ones((b, h), jnp.bfloat16)
    step(x).block_until_ready()  # compile

    t0 = time.perf_counter()
    y = x
    for _ in range(n):
        y = step(y)
    y.block_until_ready()
    chained = (time.perf_counter() - t0) / n

    @jax.jit
    def scanned(x):
        return jax.lax.scan(lambda c, _: (jnp.tanh(c @ w), None), x,
                            None, length=n)[0]

    scanned(x).block_until_ready()  # compile
    t0 = time.perf_counter()
    scanned(x).block_until_ready()
    scan_per = (time.perf_counter() - t0) / n

    @jax.jit
    def tiny(c):
        return c + 1

    c = jnp.zeros((), jnp.int32)
    tiny(c).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n):
        c = tiny(c)
    c.block_until_ready()
    tiny_per = (time.perf_counter() - t0) / n

    print(f"steps={n} shape=({b},{h})")
    print(f"chained dispatches : {chained*1e3:8.3f} ms/step")
    print(f"scanned (1 dispatch): {scan_per*1e3:8.3f} ms/step")
    print(f"tiny dispatch chain : {tiny_per*1e3:8.3f} ms/step")
    ratio = chained / max(scan_per, 1e-9)
    print(f"dispatch-overhead ratio (chained/scanned): {ratio:.2f}x")
    print("verdict:", "DISPATCH-BOUND — scan-chunk the decode loop"
          if ratio > 1.5 else "compute-bound — dispatch overhead is fine")
    return 0


if __name__ == "__main__":
    sys.exit(main())
