"""Reader ``required_work``: measured time against the operations or bytes the
algorithm needs at the chip's published peak (``peaks.json``). The counts are
those of the module the cell's configuration names under ``counts``, found
over the benchmark's ``paths`` as its ``reference`` is (default ``roofline``,
the dense GQA decoder's): ``train_flops_per_token``, ``decode_weight_bytes``
and ``kv_read_bytes``, called as ``roofline.py`` defines them.

``args["what"]``:

* ``learner_mfu``: required training operations per token x the untraced
  updates' tokens per second per chip / peak bf16 FLOP/s, in %. An end-to-end
  utilization (host clock), not a kernel's roofline share.
* ``decode_bandwidth_util``: bytes the untraced rounds' decode steps must move
  (weights once a step, each live row's KV at its context) / peak HBM
  bandwidth / the rounds' wall seconds, in %. Bound: memory. A counts module
  whose ``kv_read_bytes`` takes ``group_size`` (latent rows, which one read
  serves a whole group of candidates with) is told how many rows share a
  prompt; any other is called as ever (``cache_bytes``).
* ``paged_attn_roofline``: KV bytes paged attention must read for the TRACED
  rounds' rows (every decoded token attends over its prompt and the tokens
  before it, exact token granularity) / peak HBM bandwidth / the kernel's
  device time (operations matching ``args["regex"]``), in %. Bound: memory;
  the kernel's operations (2 per KV element per query head of the group) are
  under a tenth of what the bytes cost at these shapes.
"""

from __future__ import annotations

import inspect

from perfbench import spec
from perfbench.readers.trace_ops import matching_seconds

DEFAULT_COUNTS = "roofline"


def cache_bytes(count, model, unit, *, kv_bytes: int) -> float:
    """``count`` (a counts module's ``kv_read_bytes``, or a function called
    like it) over one unit's rows. The unit's ``group_size`` (the rows that
    share a prompt) goes to a function whose signature takes it and to no
    other: whether one read of a prompt's cache can serve a whole group is the
    algorithm's, so the counts module says, and the others' numbers stay."""
    kwargs = {"kv_bytes": kv_bytes}
    if unit.get("group_size") and "group_size" in inspect.signature(count).parameters:
        kwargs["group_size"] = unit["group_size"]
    return count(model, unit["prompt_lens"], unit["gen_lens"], **kwargs)


def read(observed, args, ctx):
    peaks, model = observed.get("peaks"), observed.get("model")
    if peaks is None or model is None or ctx is None:
        return None
    counts = spec.load_module(
        ctx.cell.paths, "", ctx.cell.config.get("counts", DEFAULT_COUNTS))
    what = args["what"]
    if what == "learner_mfu":
        units, shape = observed.get("units"), observed.get("learner")
        if not units or not shape:
            return None
        tok_s = sum(u["tokens"] for u in units) / (units[-1]["t1"] - units[0]["t0"])
        flops = counts.train_flops_per_token(
            model, seq_len=shape["seq_len"], answer_len=shape["answer_len"],
            lora_rank=shape["lora_rank"],
        )
        return 100.0 * flops * tok_s / observed["chips"] / peaks["bf16_flops_per_s"]
    layout = observed.get("rollout")
    if not layout:
        return None
    if what == "decode_bandwidth_util":
        units = [u for u in observed.get("units", []) if u.get("steps_dispatched")]
        if not units:
            return None
        weights = counts.decode_weight_bytes(
            model, weight_bytes=layout["weight_bytes"],
            lora_rank=layout["lora_rank"],
        )
        needed = sum(
            u["steps_dispatched"] * weights + cache_bytes(
                counts.kv_read_bytes, model, u, kv_bytes=layout["kv_bytes"])
            for u in units
        )
        seconds = sum(u["t1"] - u["t0"] for u in units)
        return 100.0 * needed / peaks["hbm_bytes_per_s"] / seconds
    if what == "paged_attn_roofline":
        trace, units = observed.get("trace"), observed.get("traced_units")
        if not trace or not trace.get("devices") or not units:
            return None
        kernel_s = matching_seconds(trace, args["regex"])
        if kernel_s <= 0:
            return None
        needed = sum(
            cache_bytes(counts.kv_read_bytes, model, u, kv_bytes=layout["kv_bytes"])
            for u in units
        )
        return 100.0 * needed / peaks["hbm_bytes_per_s"] / kernel_s
    raise ValueError(f"required_work cannot read {what!r}")
