"""Expert paging (ISSUE 10): bit-identity of pool-paged MoE serving.

The contract under test: a MoE model whose routed-expert weights live in the
remote :class:`~repro.core.pool.MemoryPool` (only a small resident set
assembled in HBM, non-resident rows zero) serves *bit-identical* tokens to
the untiered engine — for both ``expert_sharding`` modes, across cold-start
misses, resident-set sizes, prefetch on/off, and generate→reset→generate
wave boundaries (no pool orphans). Plus the two dispatch-path regressions
this PR fixes: ``_moe_ffn_ep`` ignoring ``groups`` and the dense path's
missing ``pos >= 0`` validity guard (asserted via dense-vs-EP bitwise
parity over random routings).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.core.placement import expert_slab_name, expert_slab_objects
from repro.core.pool import MemoryPool
from repro.core.sizing import advise_expert_residency, decode_state_census
from repro.launch.mesh import make_mesh
from repro.models import get_model
from repro.models import moe as MOE
from repro.serving import EngineConfig, ServingEngine
from repro.serving.expert_paging import (
    ExpertPager,
    ExpertPagingConfig,
    ExpertParamStore,
)

# deepseek pages with expert_sharding="expert", mixtral with "tensor" — the
# two archs cover both sharding modes end to end
ARCHS = ["deepseek-v3-671b", "mixtral-8x7b"]


@pytest.fixture(scope="module")
def moe_setup():
    out = {}
    for arch in ARCHS:
        cfg = reduced_config(get_config(arch), dtype=jnp.float32)
        model = get_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0), cfg)
        out[arch] = (cfg, model, params)
    return out


def _prompts(cfg, batch=2, length=4, seed=1):
    return np.array(jax.random.randint(
        jax.random.PRNGKey(seed), (batch, length), 0, cfg.vocab_size
    ), np.int32)


def _paged_engine(cfg, params, *, resident_max=2, prefetch=True, **ecfg_kw):
    pcfg = ExpertPagingConfig(resident_max=resident_max, prefetch=prefetch,
                              throttle=0.0)
    return ServingEngine(cfg, params, EngineConfig(
        max_batch=2, max_len=32, expert_paging=pcfg, **ecfg_kw))


# -- end-to-end bit-identity ------------------------------------------------
@pytest.mark.parametrize("arch", ARCHS)
def test_paged_generate_bit_identical(moe_setup, arch):
    cfg, _model, params = moe_setup[arch]
    prompts = _prompts(cfg)
    ref = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_len=32)
                        ).generate(prompts, max_new=6)
    eng = _paged_engine(cfg, params, resident_max=2)
    out = eng.generate(prompts, max_new=6)
    np.testing.assert_array_equal(ref, out)
    # the resident set was genuinely under-provisioned: paging happened
    st = eng.expert_store.stats()
    assert st["sync_fetches"] > 0
    assert st["misses"] > 0
    eng.expert_store.close()


@pytest.mark.parametrize("arch", ARCHS)
def test_cold_start_miss_path(moe_setup, arch):
    """The first paged step finds nothing resident: every routed expert
    goes through the blocking sync-fetch path, and the step still produces
    the exact logits (the fixpoint re-run)."""
    cfg, _model, params = moe_setup[arch]
    eng = _paged_engine(cfg, params, resident_max=cfg.n_experts)
    store = eng.expert_store
    assert store.resident_counts == [0] * store.n_moe_layers
    prompts = _prompts(cfg, batch=1, length=1)
    ref = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_len=32)
                        ).generate(np.pad(prompts, ((0, 1), (0, 0))),
                                   max_new=2)[:1]
    out = eng.generate(np.pad(prompts, ((0, 1), (0, 0))), max_new=2)[:1]
    np.testing.assert_array_equal(ref, out)
    # step 1 had zero residency: its routed experts are all misses
    assert store.misses >= store.n_moe_layers
    assert store.sync_fetches == store.misses  # only misses block
    assert store.hit_rate() < 1.0
    eng.expert_store.close()


def test_hit_rate_monotone_in_resident_set(moe_setup):
    """More HBM (larger resident set) never pages worse — the expert
    analogue of the §6.1 local-fraction sweep being monotone."""
    cfg, _model, params = moe_setup["mixtral-8x7b"]
    prompts = _prompts(cfg)
    rates = []
    for r in (1, 2, cfg.n_experts):
        eng = _paged_engine(cfg, params, resident_max=r)
        eng.generate(prompts, max_new=8)
        rates.append(eng.expert_store.hit_rate())
        eng.expert_store.close()
    assert rates == sorted(rates), rates
    assert rates[-1] > rates[0]


def test_prefetch_on_off_equivalence(moe_setup):
    """Prefetch is a latency optimisation, never a correctness knob: the
    served tokens match bitwise with it disabled. The async path fires at
    the wave boundary — the pager's EMA survives ``reset()`` while
    residency goes cold, so the second wave warm-starts from prediction
    (prefetch commits, misses converted to hits) instead of serializing
    cold-start sync fetches."""
    cfg, _model, params = moe_setup["mixtral-8x7b"]
    prompts = _prompts(cfg)
    outs, stores = [], []
    for prefetch in (True, False):
        eng = _paged_engine(cfg, params, resident_max=2, prefetch=prefetch)
        wave1 = eng.generate(prompts, max_new=8)
        eng.reset()
        wave2 = eng.generate(prompts, max_new=8)
        np.testing.assert_array_equal(wave1, wave2)
        outs.append(wave2)
        stores.append(eng.expert_store)
    np.testing.assert_array_equal(outs[0], outs[1])
    on, off = stores
    assert on.prefetch_commits > 0
    assert off.prefetch_commits == 0
    assert on.hit_rate() >= off.hit_rate()
    on.close()
    off.close()


def test_reset_frees_expert_extents(moe_setup):
    """ISSUE 10 satellite: ``reset()`` must free paged expert extents like
    demoted cache tiers — generate→reset→generate leaves no pool orphans
    and still serves identical tokens after the cold restart."""
    cfg, _model, params = moe_setup["deepseek-v3-671b"]
    prompts = _prompts(cfg)
    eng = _paged_engine(cfg, params, resident_max=2)
    first = eng.generate(prompts, max_new=5)
    assert any(n.startswith("expert:") for n in eng.pool.names())
    eng.reset()
    assert not any(n.startswith("expert:") for n in eng.pool.names())
    eng.pool.check_no_orphans()
    second = eng.generate(prompts, max_new=5)  # lazy re-register, cold start
    np.testing.assert_array_equal(first, second)
    eng.pool.check_no_orphans()
    eng.expert_store.close()


def test_paging_rejects_non_moe_and_lane_mode(moe_setup):
    dense_cfg = reduced_config(get_config("granite-8b"), dtype=jnp.float32)
    dense_params = get_model(dense_cfg).init_params(
        jax.random.PRNGKey(0), dense_cfg)
    with pytest.raises(ValueError, match="routed-MoE"):
        ServingEngine(dense_cfg, dense_params, EngineConfig(
            expert_paging=ExpertPagingConfig()))
    cfg, _model, params = moe_setup["mixtral-8x7b"]
    eng = _paged_engine(cfg, params)
    with pytest.raises(ValueError, match="mutually exclusive"):
        eng.enable_lane_decode()
    eng.expert_store.close()


# -- dispatch-path regressions (satellites 1 + 2) ---------------------------
def _mesh11():
    return make_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("groups", [None, 1, 2, 4, 8])
def test_ep_threads_groups(groups):
    """Satellite 1: ``_moe_ffn_ep`` used to accept ``groups`` and silently
    dispatch with T = S regardless; it must now partition (B*S) tokens into
    ``groups`` chunks exactly like the dense path — asserted by bitwise
    parity against dense at every groups value."""
    cfg = reduced_config(get_config("mixtral-8x7b"), dtype=jnp.float32,
                         capacity_factor=8.0)
    p = MOE.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model),
                          jnp.float32)
    dense, aux_d = MOE._moe_ffn_dense(p, x, cfg, groups=groups)
    ep, aux_e = MOE._moe_ffn_ep(p, x, cfg, _mesh11(), groups=groups)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(ep))
    np.testing.assert_allclose(float(aux_d), float(aux_e), rtol=1e-6)


def test_ep_rejects_bad_groups():
    cfg = reduced_config(get_config("mixtral-8x7b"), dtype=jnp.float32)
    p = MOE.moe_init(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((2, 8, cfg.d_model), jnp.float32)
    with pytest.raises(ValueError, match="partition"):
        MOE._moe_ffn_ep(p, x, cfg, _mesh11(), groups=5)
    with pytest.raises(ValueError, match="partition"):
        MOE._moe_ffn_ep(p, x, cfg, _mesh11(), groups=0)


@pytest.mark.parametrize("seed", range(4))
def test_dense_vs_ep_property(seed):
    """Satellite 2: the dense path's validity mask lacked the ``pos >= 0``
    guard the EP path has. Property test: over random routings (random
    inputs + router), dense and EP dispatch agree bitwise — the one shared
    validity definition can never drift between the paths again."""
    cfg = reduced_config(get_config("deepseek-v3-671b"), dtype=jnp.float32,
                         capacity_factor=1.0)  # tight capacity: drops occur
    p = MOE.moe_init(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 100),
                          (2, 12, cfg.d_model), jnp.float32)
    dense, _ = MOE._moe_ffn_dense(p, x, cfg, groups=2)
    ep, _ = MOE._moe_ffn_ep(p, x, cfg, _mesh11(), groups=2)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(ep))


@pytest.mark.parametrize("path", ["dense", "ep"])
def test_zero_rows_are_exact(path):
    """The paging premise: zeroing every expert the router did not select
    leaves the MoE output bit-identical (capacity slots with no valid token
    carry exact-zero activations through silu/einsum)."""
    cfg = reduced_config(get_config("mixtral-8x7b"), dtype=jnp.float32)
    p = MOE.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, cfg.d_model),
                          jnp.float32)
    if path == "dense":
        ref, _aux, (top_i, _top_p) = MOE._moe_ffn_dense(
            p, x, cfg, return_routing=True)
    else:
        ref, _aux, (top_i, _top_p) = MOE._moe_ffn_ep(
            p, x, cfg, _mesh11(), return_routing=True)
    routed = set(np.unique(np.asarray(top_i)).tolist())
    mask = np.zeros((cfg.n_experts, 1, 1), np.float32)
    for e in routed:
        mask[e] = 1.0
    p2 = dict(p)
    for k in ("w_gate", "w_up", "w_down"):
        p2[k] = p[k] * mask
    if path == "dense":
        out, _ = MOE._moe_ffn_dense(p2, x, cfg)
    else:
        out, _ = MOE._moe_ffn_ep(p2, x, cfg, _mesh11())
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


# -- store / pager units ----------------------------------------------------
def test_store_retarget_protects_routed_and_evicts_by_mass(moe_setup):
    cfg, _model, params = moe_setup["deepseek-v3-671b"]
    pool = MemoryPool(2)
    store = ExpertParamStore(params, cfg, pool,
                             paging=ExpertPagingConfig(resident_max=2,
                                                       throttle=0.0))
    store.begin_step()
    store.fetch_sync(0, [0, 1, 2])
    # target = {2, 3}, but 1 was routed this step: 0 evicts, 1 survives
    store.retarget(0, [2, 3], protect={1, 2})
    store.begin_step()  # commits the prefetch of 3
    assert store.resident[0] == {1, 2, 3}
    # evicted rows are zeros again; resident rows match the real weights
    wg = np.asarray(store.params_view()["layers"]["moe"]["w_gate"])
    ref = np.asarray(params["layers"]["moe"]["w_gate"])
    assert not wg[0, 0].any()
    np.testing.assert_array_equal(wg[0, 2], ref[0, 2])
    store.teardown()
    pool.check_no_orphans()
    store.close()


def test_pager_ema_ranking():
    pager = ExpertPager(1, 4, decay=0.5)
    routing = {"top_i": np.array([[[[3, 1]]]]),
               "top_p": np.array([[[[0.9, 0.1]]]])}
    pager.observe(routing)
    assert pager.predict(0, 2) == [3, 1]
    # decay: a newly dominant expert overtakes after repeated observation
    routing2 = {"top_i": np.array([[[[2, 1]]]]),
                "top_p": np.array([[[[0.9, 0.1]]]])}
    for _ in range(4):
        pager.observe(routing2)
    assert pager.predict(0, 1) == [2]
    with pytest.raises(ValueError):
        ExpertPager(1, 4, decay=1.5)


# -- census + advisor -------------------------------------------------------
@pytest.mark.parametrize("arch", ARCHS + ["mamba2-130m", "zamba2-1.2b"])
def test_decode_state_census_matches_real_cache(arch):
    cfg = reduced_config(get_config(arch), dtype=jnp.float32)
    model = get_model(cfg)
    cache = model.init_decode_cache(cfg, 2, 16)
    census = decode_state_census(cfg, 2, 16)
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        name = "cache" + jax.tree_util.keystr(path)
        if leaf.ndim == 0 or name.endswith("['pos']"):
            continue
        assert name in census, name
        assert census[name].size_bytes == leaf.size * leaf.dtype.itemsize, name
    if cfg.is_moe:
        slabs = [o for o in census if o.name.startswith("expert:")]
        n_moe = cfg.n_layers - cfg.first_k_dense
        assert len(slabs) == n_moe * cfg.n_experts
        assert all(o.pinned_remote for o in slabs)


def test_expert_slab_objects_naming():
    cfg = reduced_config(get_config("deepseek-v3-671b"), dtype=jnp.float32)
    objs = expert_slab_objects(cfg)
    # layer index is MoE-relative (matches ExpertParamStore's layer axis)
    assert objs[0].name == expert_slab_name(0, 0)
    slab_bytes = 3 * cfg.d_model * cfg.moe_d_ff * 4
    assert objs[0].size_bytes == slab_bytes
    dense = reduced_config(get_config("granite-8b"))
    assert expert_slab_objects(dense) == []


def test_advise_expert_residency_curve():
    # skewed mass: two hot experts out of eight
    mass = np.array([[8.0, 6.0, 0.5, 0.5, 0.2, 0.2, 0.1, 0.1]])
    adv = advise_expert_residency(
        mass, bytes_per_expert=1 << 20, fetch_us_per_expert=100.0,
        compute_us_per_step=1000.0, experts_per_step=2.0,
        degradation_target=0.16,
    )
    hit = [pt.hit_rate for pt in adv.curve]
    assert hit == sorted(hit) and hit[-1] == pytest.approx(1.0)
    assert adv.feasible
    assert adv.advised_resident <= 4  # the skew makes a small set enough
    # an HBM budget binds the advice even when degradation would allow more
    tight = advise_expert_residency(
        mass, bytes_per_expert=1 << 20, fetch_us_per_expert=5000.0,
        compute_us_per_step=1000.0, experts_per_step=2.0,
        degradation_target=0.0001, hbm_budget_bytes=2 << 20,
    )
    assert tight.advised_resident <= 2
    assert not tight.feasible
