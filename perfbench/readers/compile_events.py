"""Reader ``compile_events``: JAX's own compile events (``harness.CompileLog``).

``args["phase"]`` is ``setup`` (process start to the window) or ``window``;
``args["what"]`` is ``programs`` (programs built: compiled or loaded from the
persistent cache), ``seconds`` (spent building them) or ``cache_misses``
(programs the persistent cache did not hold, over the whole run).
"""

from __future__ import annotations


def read(observed, args, ctx):
    compiles = observed.get("compiles")
    if compiles is None:
        return None
    if args["what"] == "cache_misses":
        return compiles["cache_misses"]
    return compiles[args["phase"]][args["what"]]
