"""Expert-parallel (shard_map) MoE path: exact parity with dense dispatch.

Also regression-tests the shard_map autodiff hazard found during §Perf: a
gather whose operand is unvarying but whose indices vary drops cross-shard
cotangent contributions unless the operand is explicitly pvary'd
(EXPERIMENTS.md §Perf notes).
"""
import os

import pytest

if "XLA_FLAGS" not in os.environ:
    # this module needs >1 device for a real 'model' axis; run in a
    # subprocess-isolated pytest-forked world? simplest: require the flag
    # only for THIS module via a session-scoped skip when single-device.
    pass

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config, reduced_config  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.models.sharding import use_mesh  # noqa: E402

multi_device = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs >=8 devices (run with "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)


@multi_device
@pytest.mark.parametrize("arch", ["deepseek-v3-671b"])
def test_ep_matches_dense_fwd_and_grads(arch):
    cfg = reduced_config(get_config(arch), dtype=jnp.float32,
                         capacity_factor=8.0)
    mesh = make_mesh((2, 4), ("data", "model"))
    p = MOE.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))

    dense_out, dense_aux = MOE._moe_ffn_dense(p, x, cfg)
    g_dense = jax.grad(
        lambda p, x: MOE._moe_ffn_dense(p, x, cfg)[0].sum(), argnums=(0, 1)
    )(p, x)

    with use_mesh(mesh):
        ep_out, ep_aux = jax.jit(lambda p, x: MOE.moe_ffn(p, x, cfg))(p, x)
        g_ep = jax.jit(jax.grad(
            lambda p, x: MOE.moe_ffn(p, x, cfg)[0].sum(), argnums=(0, 1)
        ))(p, x)

    np.testing.assert_allclose(ep_out, dense_out, atol=1e-4, rtol=1e-4)
    assert float(ep_aux) == pytest.approx(float(dense_aux), rel=1e-5)
    scale = max(
        float(jnp.max(jnp.abs(g))) for g in jax.tree.leaves(g_dense)
    )
    for a, b in zip(jax.tree.leaves(g_ep), jax.tree.leaves(g_dense)):
        np.testing.assert_allclose(a, b, atol=1e-4 * scale, rtol=1e-3)


def test_ep_path_gated_off_without_mesh():
    """No mesh (or 1-way model axis) => dense path; smoke tests stay valid."""
    cfg = reduced_config(get_config("deepseek-v3-671b"), dtype=jnp.float32)
    p = MOE.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    out, aux = MOE.moe_ffn(p, x, cfg)  # would raise inside shard_map if taken
    assert out.shape == x.shape
