"""Serving engine: batched generation, determinism, DOLMA cache placement,
and the output-equivalence battery (tiered + pooled == untiered, bit-exact)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.core.tiering import supports_host_offload
from repro.models import get_model
from repro.serving import EngineConfig, ServingEngine


@pytest.fixture(scope="module")
def engine_setup():
    cfg = reduced_config(get_config("granite-8b"), dtype=jnp.float32)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, model, params


def test_generate_matches_manual_decode(engine_setup):
    cfg, model, params = engine_setup
    prompts = np.array([[5, 9, 2], [7, 1, 3]], np.int32)
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_len=32))
    out = eng.generate(prompts, max_new=4)

    # manual greedy decode (reference)
    cache = model.init_decode_cache(cfg, 2, 32)
    logits = None
    toks = jnp.asarray(prompts)
    for t in range(prompts.shape[1]):
        logits, cache = model.decode_step(params, cache, toks[:, t:t+1], cfg,
                                          moe_groups=1)
    ref = []
    cur = jnp.argmax(logits[:, :, : cfg.vocab_size], -1).astype(jnp.int32)
    for _ in range(4):
        ref.append(np.asarray(cur))
        logits, cache = model.decode_step(params, cache, cur, cfg, moe_groups=1)
        cur = jnp.argmax(logits[:, :, : cfg.vocab_size], -1).astype(jnp.int32)
    np.testing.assert_array_equal(out, np.concatenate(ref, 1))


def test_generate_deterministic(engine_setup):
    cfg, _model, params = engine_setup
    prompts = np.array([[1, 2, 3, 4]], np.int32)
    a = ServingEngine(cfg, params, EngineConfig(max_batch=1, max_len=16)
                      ).generate(prompts, max_new=3)
    b = ServingEngine(cfg, params, EngineConfig(max_batch=1, max_len=16)
                      ).generate(prompts, max_new=3)
    np.testing.assert_array_equal(a, b)


def test_cache_placement_under_budget(engine_setup):
    cfg, _model, params = engine_setup
    total = sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(params))
    # budget = 40% of params => the policy demotes the biggest objects
    eng = ServingEngine(cfg, params,
                        EngineConfig(max_batch=2, max_len=64,
                                     hbm_budget_bytes=int(total * 0.4)))
    s = eng.stats()
    assert s["placement"]["n_remote"] > 0
    assert s["placement"]["memory_saving"] > 0.3

    # generous budget => everything local
    eng2 = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_len=64))
    assert eng2.stats()["placement"]["n_remote"] == 0


def test_kv_overflow_targets_pool(engine_setup):
    """Demoted KV-cache tiers are striped into the multi-node memory pool."""
    cfg, _model, params = engine_setup
    total = sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(params))
    eng = ServingEngine(
        cfg, params,
        EngineConfig(max_batch=2, max_len=64,
                     hbm_budget_bytes=int(total * 0.2),
                     pool_nodes=2, pool_replication=2,
                     pool_stripe_bytes=64 * 1024),
    )
    demoted_cache = [n for n in eng.placement.remote_names()
                     if n.startswith("cache")]
    if not demoted_cache:
        pytest.skip("budget did not demote any cache tier for this config")
    assert eng.pool is not None
    for name in demoted_cache:
        assert name in eng.pool
    before = eng.pool.stats()["bytes_written"]

    eng.generate(np.array([[5, 9, 2]], np.int32), max_new=2)
    after = eng.pool.stats()
    # the post-wave overflow write-back really hit the pool's fabric
    assert after["bytes_written"] > before
    assert after["n_alive"] == 2
    # pool holds the current cache values for every demoted tier
    leaves = eng._cache_leaves()
    for name in demoted_cache:
        got = eng.pool.payload(name)
        np.testing.assert_array_equal(got, np.asarray(leaves[name]))


# -- output-equivalence battery (ISSUE 5): tiered+pooled == untiered --------
def _setup_arch(arch):
    cfg = reduced_config(get_config(arch), dtype=jnp.float32)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    total = sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(params))
    return cfg, params, total


@pytest.mark.parametrize("arch", ["granite-8b", "glm4-9b"])
def test_output_equivalence_under_pool_pressure(arch):
    """Tokens under HBM pressure + pool overflow are bit-identical to the
    untiered/unpooled engine — tiering must never change what is served."""
    cfg, params, total = _setup_arch(arch)
    prompts = np.array([[5, 9, 2, 11], [7, 1, 3, 4]], np.int32)
    base = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_len=48))
    tiered = ServingEngine(cfg, params, EngineConfig(
        max_batch=2, max_len=48,
        hbm_budget_bytes=int(total * 0.15),
        pool_nodes=2, pool_replication=2, pool_stripe_bytes=64 * 1024,
    ))
    assert tiered.placement.remote_names(), "budget applied no pressure"
    ref = base.generate(prompts, max_new=6)
    out = tiered.generate(prompts, max_new=6)
    np.testing.assert_array_equal(out, ref)


def test_multi_wave_reset_roundtrips_pool(engine_setup):
    """generate -> reset -> generate: reset frees the previous wave's
    demoted KV entries (no stale pool aliases) and the next wave's overflow
    round-trips the fresh cache contents bit-identically."""
    cfg, _model, params = engine_setup
    total = sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(params))
    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch=2, max_len=48, hbm_budget_bytes=int(total * 0.15),
        pool_nodes=2, pool_stripe_bytes=64 * 1024,
    ))
    demoted = [n for n in eng.placement.remote_names()
               if n.startswith("cache")]
    if not demoted:
        pytest.skip("budget did not demote any cache tier for this config")
    prompts = np.array([[5, 9, 2]], np.int32)
    out1 = eng.generate(prompts, max_new=4)
    assert any(n.startswith("cache") for n in eng.pool.names())

    eng.reset()
    # the stale wave's cache objects are gone from the pool (satellite fix)
    assert not any(n.startswith("cache") for n in eng.pool.names())

    out2 = eng.generate(prompts, max_new=4)
    np.testing.assert_array_equal(out2, out1)  # fresh wave, same answer
    leaves = eng._cache_leaves(set(demoted))
    for name in demoted:
        np.testing.assert_array_equal(eng.pool.payload(name), leaves[name])


def test_placement_summary_records_offload_capability(engine_setup):
    """The plan summary must state how demotions would be realized on this
    backend (pinned_host on offload-capable ones) — regression for the dead
    `supports_host_offload()` branch that recorded nothing."""
    cfg, _model, params = engine_setup
    total = sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(params))
    tight = ServingEngine(cfg, params, EngineConfig(
        max_batch=2, max_len=64, hbm_budget_bytes=int(total * 0.3)))
    s = tight.stats()["placement"]
    assert s["n_remote"] > 0
    expected = "pinned_host" if supports_host_offload() else None
    assert s["offload_memory_kind"] == expected

    # no demotions -> nothing to offload, whatever the backend supports
    roomy = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_len=64))
    assert roomy.stats()["placement"]["offload_memory_kind"] is None


# -- the lane step updates its cache in place ---------------------------------
def _compiled_lane_step(cfg, params, lanes=3, max_len=32):
    eng = ServingEngine(cfg, params,
                        EngineConfig(max_batch=lanes, max_len=max_len))
    eng.enable_lane_decode()
    tok = jnp.zeros((lanes, 1), jnp.int32)
    return eng, eng._step.lower(params, eng.cache, tok).compile().as_text()


def test_lane_step_donates_its_cache(engine_setup):
    """The lane step aliases the K/V stacks to its output and consumes the
    cache it is given; ``generate()``, on the same step, leaves the engine a
    live cache to decode from again."""
    cfg, _model, params = engine_setup
    eng, text = _compiled_lane_step(cfg, params)
    (aliases,) = re.findall(r"input_output_alias=\{(.*?) \}, ", text)
    aliased = {int(i) for i in re.findall(r"\((\d+), \{\}, may-alias\)",
                                          aliases)}
    n_params = len(jax.tree.leaves(params))
    keys = [jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_flatten_with_path(eng.cache)[0]]
    assert {n_params + keys.index("['k']"),
            n_params + keys.index("['v']")} <= aliased

    before = eng.cache
    eng.decode_lanes(np.array([3, 5, 7]))
    assert all(leaf.is_deleted() for leaf in before.values())
    assert not any(leaf.is_deleted() for leaf in eng.cache.values())

    wave = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_len=32))
    prompts = np.array([[5, 9, 2], [7, 1, 3]], np.int32)
    first = wave.generate(prompts, max_new=2)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(wave.cache))
    wave.reset()
    np.testing.assert_array_equal(wave.generate(prompts, max_new=2), first)


def test_lane_step_writes_no_whole_layer_of_kv(engine_setup):
    """The compiled lane step writes the new token into the K/V stacks, not a
    whole layer's ``(1, B, S, KV, Dh)`` slice stacked back per layer."""
    cfg, _model, params = engine_setup
    lanes, max_len = 3, 32
    _eng, text = _compiled_lane_step(cfg, params, lanes, max_len)
    shape_of = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", text))
    updates = [shape_of[m] for m in re.findall(
        r"dynamic-update-slice\(%[\w.\-]+, %([\w.\-]+)", text)]
    whole_layer = f"f32[1,{lanes},{max_len},{cfg.n_kv_heads},{cfg.head_dim}]"
    assert whole_layer not in updates
