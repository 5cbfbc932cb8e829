"""Seeded weights, made on the device in one jitted call, in the served type.

The TREE (names, shapes, stacking) is the program's: ``jax.eval_shape`` of its
own ``init_params`` / ``init_lora_params``. The VALUES are the benchmark's, by
rule from each leaf's name, so that the correctness check can tell a dropped
term: the program's constructors zero every bias and every adapter ``b``.

A model family whose leaves need other values than ``_base_rule`` gives brings
its own rules as data: a configuration file's optional ``"weight_rules"``
names ``<path>/weight_rules/<name>.json``, found over the benchmark's ``paths``
like every other file, an ordered list of ``{"leaf": <regex over the leaf's
path>, "draw": "normal" | "uniform" | "constant", ...}`` (``load_rules``). The
first rule whose regex is found in a leaf's path (its keys joined by ``/``:
``layers/wq``, ``final_norm``) draws that leaf; a leaf no rule names falls to
``_base_rule``, so a configuration that names no file has the values it always
had, bit for bit. What it is for: a decay or step-size leaf that must be drawn
so that a recurrent state remembers hundreds of tokens (drawn Normal(0, 0.02)
it forgets in ten, and the check then cannot tell a wrong state), a router
bias that has to start at zero.
"""

from __future__ import annotations

import re
from functools import partial

import jax
import jax.numpy as jnp

from perfbench import spec

WEIGHT_STD = 0.02  # the published initializer_range
BIAS_STD = 0.25  # q/k/v biases large enough that dropping one moves every logit
LORA_B_STD = 0.01  # a trained adapter's b is small and not zero


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def _normal(std: float, mean: float = 0.0):
    """``sample(key, shape)``: float32 Normal(mean, std)."""
    def sample(k, s):
        drawn = std * jax.random.normal(k, s, jnp.float32)
        return drawn + mean if mean else drawn

    return sample


def _fill(key, shape, dtype, sample, stacked: bool):
    """``sample(key, shape)`` (float32) cast to ``dtype``. A stacked [L, ...]
    leaf is drawn layer by layer, so the float32 draw never holds more than one
    layer's worth."""
    def draw(k, s):
        return sample(k, s).astype(dtype)

    if stacked and len(shape) > 1:
        keys = jax.random.split(key, shape[0])
        return jax.lax.map(lambda k: draw(k, shape[1:]), keys)
    return draw(key, shape)


def _make(key, shapes, rule):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(leaves))
    out = [rule(k, path, leaf) for k, (path, leaf) in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def _stacked(path) -> bool:
    return any(getattr(p, "key", None) == "layers" for p in path)


def _base_rule(key, path, leaf):
    name = _leaf_name(path)
    stacked = _stacked(path)
    if name.endswith("norm"):
        return jnp.ones(leaf.shape, leaf.dtype)
    if name.startswith("b"):  # bq, bk, bv (and any later bias)
        return _fill(key, leaf.shape, leaf.dtype, _normal(BIAS_STD), stacked)
    return _fill(key, leaf.shape, leaf.dtype, _normal(WEIGHT_STD), stacked)


#: a rule's ``draw`` -> the numbers it must give beside ``leaf`` and ``draw``
DRAWS = {"normal": ("std",), "uniform": ("low", "high"), "constant": ("value",)}
#: and may give
OPTIONAL = {"normal": ("mean",), "uniform": (), "constant": ()}


def load_rules(paths, config_file) -> tuple[dict, ...]:
    """The rules a configuration names under ``weight_rules``, in the file's
    order; none where it names no file. A rule that is not plain data of the
    form the module's docstring gives is a ``SpecError``."""
    name = config_file.get("weight_rules")
    if not name:
        return ()
    path = spec.find_file(paths, "weight_rules", f"{name}.json")
    rules = spec.load_json(path)
    if not isinstance(rules, list) or not rules:
        raise spec.SpecError(f"{path} must hold a list of rules")
    for rule in rules:
        draw = rule.get("draw") if isinstance(rule, dict) else None
        if draw not in DRAWS or not isinstance(rule.get("leaf"), str):
            raise spec.SpecError(
                f"{path}: {rule!r} needs a 'leaf' regex and a 'draw' of {sorted(DRAWS)}")
        numbers = [rule.get(k) for k in DRAWS[draw]] + [
            rule[k] for k in OPTIONAL[draw] if k in rule]
        if set(rule) - {"leaf", "draw", "why", *DRAWS[draw], *OPTIONAL[draw]} or not all(
            isinstance(v, (int, float)) and not isinstance(v, bool) for v in numbers
        ):
            raise spec.SpecError(
                f"{path}: a {draw!r} rule holds the numbers {DRAWS[draw]} "
                f"(and may hold {OPTIONAL[draw]} and a 'why'), not {rule!r}")
        try:
            re.compile(rule["leaf"])
        except re.error as e:
            raise spec.SpecError(f"{path}: {rule['leaf']!r} is no regex: {e}") from None
    return tuple(rules)


def leaf_path(path) -> str:
    """A leaf's path as a rule's regex sees it: its keys joined by ``/``."""
    return "/".join(_leaf_name((p,)) for p in path)


def _ruled(key, path, leaf, *, rules, used):
    """The first of ``rules`` that names the leaf draws it; else ``_base_rule``."""
    where = leaf_path(path)
    for i, rule in enumerate(rules):
        if re.search(rule["leaf"], where) is None:
            continue
        used.add(i)
        if rule["draw"] == "constant":
            return jnp.full(leaf.shape, rule["value"], leaf.dtype)
        if rule["draw"] == "uniform":
            sample = partial(jax.random.uniform, dtype=jnp.float32,
                             minval=float(rule["low"]), maxval=float(rule["high"]))
        else:
            sample = _normal(float(rule["std"]), float(rule.get("mean", 0.0)))
        return _fill(key, leaf.shape, leaf.dtype, sample, _stacked(path))
    return _base_rule(key, path, leaf)


def make_base_params(model_cfg, dtype, seed: int, mesh=None, rules=()):
    """The frozen base: the program's param tree, values from ``seed``, each
    leaf by the first of ``rules`` (``load_rules``) that names it and by
    ``_base_rule`` where none does; a rule that draws no leaf is an error. With a
    ``mesh`` (a role's submesh) the tree is made ON that mesh's devices, placed
    as the program's own ``param_specs`` place a checkpoint: two roles draw the
    same values from the same seed, and no copy of the base ever crosses
    chips or sits twice on one."""
    from distrl_llm_tpu.models import init_params

    shapes = jax.eval_shape(
        partial(init_params, cfg=model_cfg, dtype=jnp.dtype(dtype)),
        jax.random.PRNGKey(0),
    )
    used: set[int] = set()
    rule = partial(_ruled, rules=rules, used=used) if rules else _base_rule
    make = partial(_make, shapes=shapes, rule=rule)
    if mesh is None:
        made = jax.jit(make)(jax.random.PRNGKey(seed))
    else:
        from jax.sharding import NamedSharding

        from distrl_llm_tpu.parallel.partition import param_specs

        placed = jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec), param_specs(shapes),
            is_leaf=lambda x: not isinstance(x, dict),
        )
        made = jax.jit(make, out_shardings=placed)(jax.random.PRNGKey(seed))
    # the jit traced ``make`` once: ``used`` now holds the rules that drew a leaf
    idle = [r["leaf"] for i, r in enumerate(rules) if i not in used]
    if idle:
        raise spec.SpecError(
            f"weight rules {idle} draw no leaf of the model's tree: no leaf's path "
            "holds the regex, or an earlier rule takes every leaf that does")
    return made


def randomize_lora_b(lora, seed: int):
    """``lora`` with every ``b`` factor drawn Normal(0, LORA_B_STD) from
    ``seed`` (same tree, dtypes and placement): an adapter as it is after
    training, where the program's constructor leaves ``b`` at zero."""
    def rule(key, path, leaf):
        if _leaf_name(path) == "b":
            return _fill(key, leaf.shape, leaf.dtype, _normal(LORA_B_STD), True)
        return leaf

    def fill(key, tree):
        leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [rule(k, p, x) for k, (p, x) in zip(keys, leaves)]
        )

    return jax.jit(fill)(jax.random.PRNGKey(seed + 7919), lora)
