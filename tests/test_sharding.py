"""Logical-axis resolution, param/cache/opt spec trees, sharded smoke."""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.launch.mesh import make_mesh
from repro.models import get_model
from repro.models.sharding import (
    batch_pspec_tree,
    cache_pspec_tree,
    opt_pspec_tree,
    params_pspec_tree,
    resolve_spec,
    shard_factor,
    use_mesh,
    use_rules,
)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


class TestResolveSpec:
    def test_basic(self, mesh):
        spec = resolve_spec((8, 16), ("batch", "ff"), mesh)
        assert spec == P("data", "model")

    def test_divisibility_drops_axis(self):
        # abstract 16x16 production mesh (no devices needed for specs)
        m = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
        # kv_heads=1 can't shard over a 16-way model axis
        spec = resolve_spec((64, 1), ("batch", "kv_heads"), m)
        assert spec[1] is None
        assert spec[0] == "data"
        # heads=36 doesn't divide 16 either (starcoder2)
        spec = resolve_spec((64, 36), ("batch", "heads"), m)
        assert spec[1] is None

    def test_axis_conflict_single_use(self, mesh):
        with use_mesh(mesh):
            spec = resolve_spec((8, 8), ("batch", "kv_len"))
        # kv_len rule -> 'data', already used by batch
        flat = [a for e in spec if e for a in (e if isinstance(e, tuple) else (e,))]
        assert len(flat) == len(set(flat))

    def test_no_mesh_is_replicated(self):
        assert resolve_spec((8, 8), ("batch", "ff"), None) == P(None, None)

    def test_rules_override(self, mesh):
        with use_rules(ff=None):
            assert resolve_spec((8, 16), (None, "ff"), mesh) == P(None, None)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_cover_all_leaves(arch, mesh):
    """Every param leaf gets a spec of matching rank for every arch."""
    cfg = get_config(arch)
    model = get_model(cfg)
    params_abs = jax.eval_shape(
        functools.partial(model.init_params, cfg=cfg), jax.random.key(0)
    )
    specs = params_pspec_tree(params_abs, expert_sharding=cfg.expert_sharding,
                              mesh=mesh)
    leaves = jax.tree.leaves(params_abs)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(spec_leaves)
    for leaf, spec in zip(leaves, spec_leaves):
        assert len(spec) == len(leaf.shape), (arch, leaf.shape, spec)
        assert shard_factor(spec, mesh) >= 1


def test_opt_specs_mirror_params(mesh):
    from repro.optim import AdamWConfig
    from repro.optim.adamw import init as adamw_init

    cfg = reduced_config(get_config("granite-8b"))
    model = get_model(cfg)
    params_abs = jax.eval_shape(
        functools.partial(model.init_params, cfg=cfg), jax.random.key(0)
    )
    pspecs = params_pspec_tree(params_abs, mesh=mesh)
    opt_abs = jax.eval_shape(
        functools.partial(adamw_init, AdamWConfig(moment_style="int8")), params_abs
    )
    ospecs = opt_pspec_tree(opt_abs, pspecs, mesh)
    for leaf, spec in zip(
        jax.tree.leaves(opt_abs),
        jax.tree.leaves(ospecs, is_leaf=lambda x: isinstance(x, P)),
    ):
        assert len(spec) == len(leaf.shape)


def test_cache_and_batch_specs(mesh):
    cfg = reduced_config(get_config("granite-8b"))
    model = get_model(cfg)
    cache_abs = jax.eval_shape(
        functools.partial(model.init_decode_cache, cfg, 4, 64)
    )
    specs = cache_pspec_tree(cache_abs, mesh)
    for leaf, spec in zip(
        jax.tree.leaves(cache_abs),
        jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)),
    ):
        assert len(spec) == len(leaf.shape)
    b = batch_pspec_tree({"tokens": jax.ShapeDtypeStruct((4, 8), jnp.int32)}, mesh)
    assert b["tokens"][0] in ("data", ("data",), None)


def test_sharded_train_step_single_device(mesh):
    """The fully-annotated train step runs on a 1x1 mesh (CPU smoke)."""
    from repro.models import make_batch
    from repro.optim import AdamWConfig
    from repro.train.step import TrainStepConfig, init_train_state, make_train_step

    cfg = reduced_config(get_config("granite-8b"), dtype=jnp.float32)
    with use_mesh(mesh):
        params, opt_state = init_train_state(
            jax.random.PRNGKey(0), cfg, TrainStepConfig(), AdamWConfig()
        )
        batch = make_batch(cfg, jax.random.PRNGKey(1), 2, 32)
        step = jax.jit(make_train_step(cfg, TrainStepConfig(), AdamWConfig()))
        params, opt_state, metrics = step(params, opt_state, batch)
        assert bool(jnp.isfinite(metrics["loss"]))


def test_fsdp_names_shard_weight_dims(mesh):
    from repro.models.sharding import param_logical_names
    import jax.tree_util as jtu

    path = (jtu.DictKey("layers"), jtu.DictKey("attn"), jtu.DictKey("wq"))
    names = param_logical_names(path, 3, fsdp=True)
    assert names == ("layers", "fsdp", "heads")
