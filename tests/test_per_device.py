"""``ops/per_device.py``: Pallas kernels inside a program that spans devices.

The refusal itself (GSPMD cannot partition a Mosaic kernel) only shows on a
TPU compile — ``tests/test_tpu_compile.py::TestProgramOverTwoChips`` holds
it for a described v5e. Here, on conftest's virtual CPU devices with the
kernels interpreted: the wrapper changes no result, engages only under a
context mesh of several devices, and steps aside inside a ``shard_map``.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distrl_llm_tpu.ops import per_device as pd
from distrl_llm_tpu.ops.sampling import fused_sample, sample_with_logprob
from distrl_llm_tpu.parallel.mesh import AXES


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]).reshape(n, 1, 1, 1), AXES)


def _shard_maps(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("shard_map")


def test_wraps_only_under_a_context_mesh_of_several_devices():
    kernel = pd.per_device(lambda x, scale=1.0: x * scale)
    x = jnp.arange(8.0)
    assert _shard_maps(kernel, x) == 0
    with jax.set_mesh(_mesh(1)):
        assert _shard_maps(kernel, x) == 0
    with jax.set_mesh(_mesh(2)):
        assert _shard_maps(kernel, x) == 1
        np.testing.assert_array_equal(
            np.asarray(jax.jit(lambda v: kernel(v, scale=3.0))(x)),
            np.asarray(x * 3.0),
        )
    # already manual (the dp-sharded paged engine's own shard_map): bare
    mesh = _mesh(2)
    inner = jax.shard_map(
        kernel, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
        check_vma=False,
    )
    assert _shard_maps(inner, x) == 1


def test_fused_sampler_same_tokens_on_one_device_and_on_a_submesh():
    logits = jnp.asarray(
        np.random.default_rng(0).normal(size=(4, 300)) * 3.0, jnp.float32
    )
    rng = jax.random.PRNGKey(1)
    want_tok, want_lp = fused_sample(rng, logits, 1.2, 0.9, interpret=True)
    mesh = _mesh(2)
    everywhere = NamedSharding(mesh, P())
    with jax.set_mesh(mesh):
        tok, lp = jax.jit(
            lambda r, lg: sample_with_logprob(
                r, lg, 1.2, 0.9, capture_logprob=True, impl="interpret"
            )
        )(jax.device_put(rng, everywhere), jax.device_put(logits, everywhere))
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(want_tok))
    np.testing.assert_allclose(np.asarray(lp), np.asarray(want_lp), rtol=1e-6)
    assert {d.id for d in tok.devices()} == {d.id for d in mesh.devices.flat}


def test_params_mesh_sets_the_mesh_the_parameters_live_on():
    mesh = _mesh(2)
    on_mesh = {"w": jax.device_put(jnp.ones(4), NamedSharding(mesh, P()))}
    assert jax.sharding.get_abstract_mesh().empty
    with pd.params_mesh(on_mesh):
        assert jax.sharding.get_abstract_mesh().size == 2
    assert jax.sharding.get_abstract_mesh().empty
    # one device, a host tree, no tree at all: nothing to set
    for params in ({"w": jnp.ones(4)}, {"w": np.ones(4)}, None,
                   {"w": jax.device_put(jnp.ones(4), NamedSharding(_mesh(1), P()))}):
        with pd.params_mesh(params):
            assert jax.sharding.get_abstract_mesh().empty
