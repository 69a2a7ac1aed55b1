"""Per-arch smoke tests (reduced configs) + attention/SSD numerics."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, reduced_config, runnable_cells
from repro.models import get_model, make_batch
from repro.models.flash import flash_attention, reference_attention
from repro.models.ssm import _ssd_scan, ssd_reference_recurrent

B, S = 2, 32


@pytest.fixture(scope="module")
def arch_setup():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = reduced_config(get_config(arch), dtype=jnp.float32)
            model = get_model(cfg)
            params = model.init_params(jax.random.PRNGKey(0), cfg)
            batch = make_batch(cfg, jax.random.PRNGKey(1), B, S)
            cache[arch] = (cfg, model, params, batch)
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_loss_and_grads(arch, arch_setup):
    """Reduced same-family config: one forward/train step, shapes + no NaNs."""
    cfg, model, params, batch = arch_setup(arch)
    loss, metrics = model.loss_fn(params, batch, cfg, remat="full")
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"
    grads = jax.grad(lambda p: model.loss_fn(p, batch, cfg, remat="full")[0])(params)
    gnorm = sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads))
    assert bool(jnp.isfinite(gnorm)), f"{arch}: non-finite grads"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_decode_step(arch, arch_setup):
    cfg, model, params, batch = arch_setup(arch)
    cache = model.init_decode_cache(cfg, B, S)
    if cfg.family in ("encdec", "audio"):
        cache = model.prefill(params, cache, batch["frames"], cfg)
    logits, cache = model.decode_step(
        params, cache, batch["tokens"][:, :1], cfg, moe_groups=1
    )
    assert logits.shape[0] == B and logits.shape[1] == 1
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert int(cache["pos"]) == 1


@pytest.mark.parametrize(
    "arch", ["granite-8b", "mamba2-130m", "zamba2-1.2b", "internvl2-1b",
             "seamless-m4t-medium"]
)
def test_decode_matches_forward(arch, arch_setup):
    """Token-by-token decode reproduces the teacher-forced logits."""
    cfg, model, params, batch = arch_setup(arch)
    if cfg.family == "vlm":
        batch = dict(batch)
        batch["patches"] = batch["patches"][:, :0]  # decode has no patch prefix
    logits_full, _ = model.forward(params, batch, cfg)
    cache = model.init_decode_cache(cfg, B, S)
    if cfg.family in ("encdec", "audio"):
        cache = model.prefill(params, cache, batch["frames"], cfg)
    errs = []
    for t in range(S):
        lg, cache = model.decode_step(params, cache, batch["tokens"][:, t:t+1],
                                      cfg, moe_groups=1)
        errs.append(float(jnp.max(jnp.abs(lg[:, 0] - logits_full[:, t]))))
    scale = float(jnp.max(jnp.abs(logits_full[..., : cfg.vocab_size])))
    assert max(errs) < 1e-3 * max(scale, 1.0), f"{arch}: decode drift {max(errs)}"


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "deepseek-v3-671b"])
def test_moe_decode_matches_forward_no_drops(arch):
    cfg = reduced_config(get_config(arch), dtype=jnp.float32, capacity_factor=8.0)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg, jax.random.PRNGKey(1), B, 16)
    logits_full, _ = model.forward(params, batch, cfg)
    cache = model.init_decode_cache(cfg, B, 16)
    errs = []
    for t in range(16):
        lg, cache = model.decode_step(params, cache, batch["tokens"][:, t:t+1],
                                      cfg, moe_groups=1)
        errs.append(float(jnp.max(jnp.abs(lg[:, 0] - logits_full[:, t]))))
    assert max(errs) < 1e-3


def _unscanned_gqa_decode_step(params, cache, tokens, cfg):
    """The GQA decode step as a Python loop over layers, each layer's K/V
    sliced out of the stack, updated by ``layers.gqa_decode_step`` and
    stacked back: the oracle of the scan that updates the stacks in place.
    Each layer is its own jitted computation, as the scan body is, so XLA
    rounds bf16 intermediates at the same layer boundaries."""
    from repro.models import layers as L
    from repro.models import moe as MOE

    @jax.jit
    def layer_step(p, x, k_l, v_l, pos):
        o, k_l, v_l = L.gqa_decode_step(p["attn"], L.rmsnorm(p["ln1"], x),
                                        k_l, v_l, pos, cfg)
        x = x + o
        h = L.rmsnorm(p["ln2"], x)
        x = x + (MOE.moe_ffn(p["moe"], h, cfg, groups=1)[0] if "moe" in p
                 else L.mlp(p["mlp"], h))
        return x, k_l, v_l

    pos = cache["pos"]
    x = L.embed(params["embed"], tokens, cfg)
    ks, vs = [], []
    for layer in range(cfg.n_layers):
        p = jax.tree.map(lambda t, i=layer: t[i], params["layers"])
        x, k_l, v_l = layer_step(p, x, cache["k"][layer], cache["v"][layer],
                                 pos)
        ks.append(k_l)
        vs.append(v_l)
    logits = L.logits(params["embed"], L.rmsnorm(params["ln_f"], x), cfg)
    return logits, {**cache, "k": jnp.stack(ks), "v": jnp.stack(vs),
                    "pos": pos + 1}


def _decode_case(arch, max_len, overrides, dtype, per_lane):
    """A reduced model, a random 3-lane cache (lanes at different positions
    if ``per_lane``), 20 steps' token feeds, the jitted decode step and the
    jitted unscanned oracle."""
    cfg = reduced_config(get_config(arch), dtype=dtype, **overrides)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    lanes = 3
    cache = model.init_decode_cache(cfg, lanes, max_len)
    kk, kv, kt = jax.random.split(jax.random.PRNGKey(1), 3)
    cache["k"] = jax.random.normal(kk, cache["k"].shape).astype(dtype)
    cache["v"] = jax.random.normal(kv, cache["v"].shape).astype(dtype)
    if per_lane:
        cache["pos"] = jnp.array([0, 3, 9], jnp.int32)
    tokens = jax.random.randint(kt, (20, lanes, 1), 0, cfg.vocab_size)
    step = jax.jit(lambda p, c, t: model.decode_step(p, c, t, cfg,
                                                     moe_groups=1))
    oracle = jax.jit(lambda p, c, t: _unscanned_gqa_decode_step(p, c, t, cfg))
    return cfg, params, cache, tokens, step, oracle


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("arch,max_len,overrides", [
    ("granite-8b", 32, {}),
    # a 16-slot ring, decoded past its width so it wraps
    ("mixtral-8x7b", 16, {"sliding_window": 16}),
])
@pytest.mark.parametrize("per_lane", [False, True], ids=["scalar", "lanes"])
def test_gqa_decode_step_in_place_matches_unscanned(per_lane, arch, max_len,
                                                    overrides, dtype):
    """The scan that carries the K/V stacks and writes each layer's new
    token in place gives the logits and caches of slicing every layer out
    and stacking it back, bit for bit."""
    cfg, params, cache, tokens, step, oracle = _decode_case(
        arch, max_len, overrides, dtype, per_lane)
    got, want = cache, cache
    for t in range(len(tokens)):
        lg, got = step(params, got, tokens[t])
        lg_want, want = oracle(params, want, tokens[t])
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(lg_want))
        for key in ("k", "v", "pos"):
            np.testing.assert_array_equal(np.asarray(got[key]),
                                          np.asarray(want[key]))
    if cfg.sliding_window:   # every lane went round the ring
        assert int(np.min(np.asarray(got["pos"]))) > got["k"].shape[2]


@pytest.mark.parametrize("arch,max_len,overrides,dtype,tol", [
    ("granite-8b", 32, {}, jnp.float32, 2e-5),
    ("granite-8b", 32, {}, jnp.bfloat16, 3e-2),
    ("mixtral-8x7b", 16, {"sliding_window": 16}, jnp.float32, 2e-5),  # ring
], ids=["granite-f32", "granite-bf16", "mixtral-ring-f32"])
@pytest.mark.parametrize("per_lane", [False, True], ids=["scalar", "lanes"])
def test_gqa_decode_step_kernel_matches_unscanned(monkeypatch, per_lane, arch,
                                                  max_len, overrides, dtype,
                                                  tol):
    """The branch a TPU takes, the decode-attention kernel reading each
    layer from the carried stacks (here interpreted), against the unscanned
    oracle at the kernel's tolerances, relative to each array's largest
    magnitude (a bf16 logit near 20 has a spacing of 0.125): each step
    starts from the oracle's cache, so the gap is one step's and does not
    compound. The MoE ring runs in f32 only: in bf16 a one-ulp attention
    difference can flip the router's top-k, and through expert capacity
    another lane's output, a discrete change that no tolerance bounds."""
    from repro.models import layers as L
    monkeypatch.setattr(L, "decode_kernel_applies", lambda k_stack: True)
    cfg, params, cache, tokens, step, oracle = _decode_case(
        arch, max_len, overrides, dtype, per_lane)

    def close(got, want):
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))

    want = cache
    for t in range(len(tokens)):
        lg, got = step(params, want, tokens[t])
        lg_want, want = oracle(params, want, tokens[t])
        close(lg[..., :cfg.vocab_size], lg_want[..., :cfg.vocab_size])
        close(got["k"], want["k"])
        close(got["v"], want["v"])
        np.testing.assert_array_equal(np.asarray(got["pos"]),
                                      np.asarray(want["pos"]))
    if cfg.sliding_window:   # every lane went round the ring
        assert int(np.min(np.asarray(want["pos"]))) > want["k"].shape[2]


def test_long_500k_applicability():
    subq = {a for a in ARCH_IDS if "long_500k" in runnable_cells(get_config(a))}
    assert subq == {"mixtral-8x7b", "mamba2-130m", "zamba2-1.2b"}


def test_remat_does_not_change_loss(arch_setup):
    cfg, model, params, batch = arch_setup("granite-8b")
    l1, _ = model.loss_fn(params, batch, cfg, remat="none")
    l2, _ = model.loss_fn(params, batch, cfg, remat="full")
    assert jnp.allclose(l1, l2, rtol=1e-5)


def test_moe_aux_loss_near_one_when_balanced():
    """Uniform router => aux loss ~ 1 (the Switch normalization)."""
    from repro.models import moe as MOE

    cfg = reduced_config(get_config("mixtral-8x7b"), dtype=jnp.float32)
    p = MOE.moe_init(jax.random.PRNGKey(0), cfg)
    p["router"] = jnp.zeros_like(p["router"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    _out, aux = MOE.moe_ffn(p, x, cfg)
    assert 0.9 < float(aux) < 1.3


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B_,Sq,Sk,H,KV,D,Dv,causal,window",
        [
            (2, 64, 64, 8, 2, 16, 16, True, None),
            (1, 128, 128, 4, 1, 32, 16, True, 32),
            (2, 1, 96, 8, 8, 16, 16, True, None),
            (2, 48, 80, 6, 3, 16, 16, False, None),
        ],
    )
    def test_matches_reference(self, B_, Sq, Sk, H, KV, D, Dv, causal, window,
                               dtype):
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(ks[0], (B_, Sq, H, D), jnp.float32).astype(dtype)
        k = jax.random.normal(ks[1], (B_, Sk, KV, D), jnp.float32).astype(dtype)
        v = jax.random.normal(ks[2], (B_, Sk, KV, Dv), jnp.float32).astype(dtype)
        qo = Sk - Sq if causal and Sq == 1 else 0
        got = flash_attention(q, k, v, causal=causal, window=window,
                              q_offset=qo, block_k=32, n_strips=4)
        want = reference_attention(q, k, v, causal=causal, window=window,
                                   q_offset=qo)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            got.astype(jnp.float32), want.astype(jnp.float32), atol=tol, rtol=tol
        )

    def test_gradients_match_reference(self):
        ks = jax.random.split(jax.random.PRNGKey(9), 3)
        q = jax.random.normal(ks[0], (2, 64, 4, 16))
        k = jax.random.normal(ks[1], (2, 64, 2, 16))
        v = jax.random.normal(ks[2], (2, 64, 2, 16))
        g1 = jax.grad(lambda *a: (flash_attention(*a, block_k=16) ** 2).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda *a: (reference_attention(*a) ** 2).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


class TestSSD:
    @pytest.mark.parametrize("L,chunk", [(64, 16), (128, 32), (96, 32)])
    def test_chunked_matches_recurrent(self, L, chunk):
        cfg = dataclasses.replace(
            reduced_config(get_config("mamba2-130m")), ssm_chunk=chunk
        )
        ks = jax.random.split(jax.random.PRNGKey(11), 5)
        Bsz, H, P, G, N = 2, cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_ngroups, cfg.ssm_state
        xh = jax.random.normal(ks[0], (Bsz, L, H, P))
        Bm = jax.random.normal(ks[1], (Bsz, L, G, N)) * 0.5
        Cm = jax.random.normal(ks[2], (Bsz, L, G, N)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(ks[3], (Bsz, L, H)))
        A = -jnp.exp(jax.random.normal(ks[4], (H,)) * 0.5)
        y, _ = _ssd_scan(xh, Bm, Cm, dt, A, cfg)
        y_ref = ssd_reference_recurrent(xh, Bm, Cm, dt, A)
        np.testing.assert_allclose(y, y_ref, atol=1e-4, rtol=1e-4)
