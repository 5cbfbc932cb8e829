"""The two readings a rollout cell's limits are set from, one process a seed:

    python3 perfbench/readings.py --workload <cell> --seed <n> [--control 1]

It runs ``run.py``'s own path up to the check of the warm-up round (the timed
entry and programs at the timed sizes) and prints one line, ``READING {...}``:

* ``sound``: what the run's own check read (the engine's log-probabilities of
  the tokens it sampled against the float32 reference's), the LOWER reading;
* ``control`` (``--control 1``): the reference put in the program's place and
  computed in the precision below the configuration's, the UPPER reading. The
  reference's log-probabilities over the weights as they are stand where the
  engine's stood, and the same comparison (``correct.rollout_rows_check``, the
  same rows and tokens) holds them against the reference over weights whose
  every matrix is rounded to 3 mantissa bits (fp8's, below bf16; of a float32
  rehearsal too).

Then it exits, before the window: nothing here is a measurement of speed. No
run of the benchmark calls this file; ``PERF.md`` says which limits were set
from its lines (PR 53: ``rollout-reasoning``'s).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from perfbench import correct, run  # noqa: E402

KEPT = ("mean_abs", "max_abs", "tokens", "ok")


def three_mantissa_bits(x):
    """A matrix with its mantissa rounded to 3 bits (to nearest; the exponent
    is kept, so nothing under- or overflows); any other leaf as it is."""
    import jax
    import jax.numpy as jnp

    words = {jnp.dtype(jnp.bfloat16): (jnp.uint16, 7), jnp.dtype(jnp.float32): (jnp.uint32, 23)}
    if x.ndim < 2 or x.dtype not in words:
        return x
    word, mantissa = words[x.dtype]
    drop = mantissa - 3
    bits = jax.lax.bitcast_convert_type(x, word)
    whole = (1 << 8 * bits.dtype.itemsize) - 1
    bits = (bits + word(1 << (drop - 1))) & word(whole ^ ((1 << drop) - 1))
    return jax.lax.bitcast_convert_type(bits, x.dtype)


def readings(control: bool):
    """``correct.rollout_rows_check``, wrapped to print the readings and exit."""
    compare = correct.rollout_rows_check

    def read(reference, model_cfg, params, lora, lora_scale, prompt_ids, prompt_mask, result,
             *, seed, width, check=None):
        import jax

        rows = (reference, model_cfg, params, lora, lora_scale, prompt_ids, prompt_mask, result)
        sound = compare(*rows, seed=seed, width=width, check=check)
        said = {"seed": seed, "sound": {k: sound.get(k) for k in KEPT}}
        if control and "rows" in sound:
            # the reference's own log-probabilities where the engine's stood
            picked, spans, want = correct.reference_rows(*rows, seed=seed, width=width)
            stood_in = np.zeros(np.shape(result.logprobs), np.float32)
            for r, ((b, j), (p_len, n)) in enumerate(zip(picked, spans)):
                stood_in[b, j, :n] = want[r, p_len - 1: p_len - 1 + n]
            in_its_place = SimpleNamespace(
                lengths=result.lengths, tokens=result.tokens, logprobs=stood_in)
            rounded = jax.jit(lambda tree: jax.tree.map(three_mantissa_bits, tree),
                              donate_argnums=0)(params)
            low = compare(reference, model_cfg, rounded, lora, lora_scale, prompt_ids,
                          prompt_mask, in_its_place, seed=seed, width=width, check=check)
            said["control"] = {k: low.get(k) for k in KEPT}
        print("READING " + json.dumps(said), flush=True)
        os._exit(0)  # before the window; the weights were donated

    return read


def main(argv: list[str]) -> int:
    own = argparse.ArgumentParser(add_help=False)
    own.add_argument("--control", type=int, default=0)
    args, for_run = own.parse_known_args(argv)
    correct.rollout_rows_check = readings(bool(args.control))
    return run.main([*for_run, "--seconds", "1", "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
