"""Seeded weights, made on the device in one jitted call, in the served type.

The TREE (names, shapes, stacking) is the program's: ``jax.eval_shape`` of its
own ``init_params`` / ``init_lora_params``. The VALUES are the benchmark's, by
rule from each leaf's name, so that the correctness check can tell a dropped
term: the program's constructors zero every bias and every adapter ``b``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

WEIGHT_STD = 0.02  # the published initializer_range
BIAS_STD = 0.25  # q/k/v biases large enough that dropping one moves every logit
LORA_B_STD = 0.01  # a trained adapter's b is small and not zero


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def _fill(key, shape, dtype, std: float, stacked: bool):
    """Normal(0, std) in ``dtype``. A stacked [L, ...] leaf is drawn layer by
    layer, so the float32 draw never holds more than one layer's worth."""
    def draw(k, s):
        return (std * jax.random.normal(k, s, jnp.float32)).astype(dtype)

    if stacked and len(shape) > 1:
        keys = jax.random.split(key, shape[0])
        return jax.lax.map(lambda k: draw(k, shape[1:]), keys)
    return draw(key, shape)


def _make(key, shapes, rule):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(leaves))
    out = [rule(k, path, leaf) for k, (path, leaf) in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def _base_rule(key, path, leaf):
    name = _leaf_name(path)
    stacked = any(getattr(p, "key", None) == "layers" for p in path)
    if name.endswith("norm"):
        return jnp.ones(leaf.shape, leaf.dtype)
    if name.startswith("b"):  # bq, bk, bv (and any later bias)
        return _fill(key, leaf.shape, leaf.dtype, BIAS_STD, stacked)
    return _fill(key, leaf.shape, leaf.dtype, WEIGHT_STD, stacked)


def make_base_params(model_cfg, dtype, seed: int, mesh=None):
    """The frozen base: the program's param tree, values from ``seed``. With a
    ``mesh`` (a role's submesh) the tree is made ON that mesh's devices, placed
    as the program's own ``param_specs`` place a checkpoint: two roles draw the
    same values from the same seed, and no copy of the base ever crosses
    chips or sits twice on one."""
    from distrl_llm_tpu.models import init_params

    shapes = jax.eval_shape(
        partial(init_params, cfg=model_cfg, dtype=jnp.dtype(dtype)),
        jax.random.PRNGKey(0),
    )
    make = partial(_make, shapes=shapes, rule=_base_rule)
    if mesh is None:
        return jax.jit(make)(jax.random.PRNGKey(seed))
    from jax.sharding import NamedSharding

    from distrl_llm_tpu.parallel.partition import param_specs

    placed = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), param_specs(shapes),
        is_leaf=lambda x: not isinstance(x, dict),
    )
    return jax.jit(make, out_shardings=placed)(jax.random.PRNGKey(seed))


def randomize_lora_b(lora, seed: int):
    """``lora`` with every ``b`` factor drawn Normal(0, LORA_B_STD) from
    ``seed`` (same tree, dtypes and placement): an adapter as it is after
    training, where the program's constructor leaves ``b`` at zero."""
    def rule(key, path, leaf):
        if _leaf_name(path) == "b":
            return _fill(key, leaf.shape, leaf.dtype, LORA_B_STD, True)
        return leaf

    def fill(key, tree):
        leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [rule(k, p, x) for k, (p, x) in zip(keys, leaves)]
        )

    return jax.jit(fill)(jax.random.PRNGKey(seed + 7919), lora)
