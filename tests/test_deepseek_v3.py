"""DeepSeek-V3 on the serving path, at a small size on the CPU: the
group-limited sigmoid gate, the chip's share of the experts, YaRN, the
dropless held-expert layer, the in-place latent-cache step, the MLA
decode-attention and held-expert kernels, and lane-mode serving against the
benchmark's plain reference."""
import dataclasses
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.core.telemetry import Telemetry
from repro.kernels.mla_decode_attention import mla_decode_attention
from repro.kernels.moe_experts import held_experts
from repro.models import get_model
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.serving import EngineConfig, ServingEngine

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


def _cfg(**overrides):
    """Reduced deepseek-v3 in float32: 16 routed experts in 4 groups of 4,
    top-4 inside the 2 best groups, YaRN as published."""
    base = dict(dtype=jnp.float32, n_experts=16, top_k=4, n_group=4,
                topk_group=2)
    base.update(overrides)
    return reduced_config(get_config("deepseek-v3-671b"), **base)


# -- the gate ----------------------------------------------------------------
def _numpy_gate(x, router, bias, cfg):
    """DeepSeek-V3's noaux_tc gate, one token at a time, in float64."""
    E, k = router.shape[1], cfg.top_k
    size = E // cfg.n_group
    ids, weights = [], []
    for tok in np.asarray(x, np.float64).reshape(-1, x.shape[-1]):
        s = 1.0 / (1.0 + np.exp(-(tok @ router)))
        choice = s + bias
        group_score = [np.sort(choice[g * size:(g + 1) * size])[-2:].sum()
                       for g in range(cfg.n_group)]
        kept = np.argsort(group_score)[::-1][:cfg.topk_group]
        masked = np.full(E, -np.inf)
        for g in kept:
            masked[g * size:(g + 1) * size] = choice[g * size:(g + 1) * size]
        top = np.argsort(masked)[::-1][:k]
        w = s[top] / s[top].sum() * cfg.routed_scaling_factor
        order = np.argsort(top)
        ids.append(top[order])
        weights.append(w[order])
    return np.array(ids), np.array(weights)


@pytest.mark.parametrize("bias_scale", [0.0, 0.05, 1.0])
def test_route_matches_numpy_gate(bias_scale):
    """The routing function against a plain gate: group limit, the bias
    used only to choose, weights normalised over the chosen and x 2.5. The
    float32 scores agree with float64 to 1e-6; the weights to 1e-5."""
    cfg = _cfg()
    kx, kr, kb = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(kx, (2, 24, cfg.d_model))
    router = jax.random.normal(kr, (cfg.d_model, 16)) / np.sqrt(cfg.d_model)
    bias = bias_scale * jax.random.normal(kb, (16,))
    top_i, top_w, probs = MOE.route({"router": router, "router_bias": bias},
                                    x, cfg)
    ids, weights = _numpy_gate(x, np.asarray(router), np.asarray(bias), cfg)
    order = np.argsort(np.asarray(top_i).reshape(-1, cfg.top_k), axis=1)
    got_i = np.take_along_axis(np.asarray(top_i).reshape(-1, cfg.top_k), order, 1)
    got_w = np.take_along_axis(np.asarray(top_w).reshape(-1, cfg.top_k), order, 1)
    np.testing.assert_array_equal(got_i, ids)
    np.testing.assert_allclose(got_w, weights, rtol=1e-5)
    assert np.allclose(np.asarray(top_w).sum(-1), cfg.routed_scaling_factor)
    assert probs.shape == (2, 24, 16)


def test_bias_changes_the_choice_but_not_the_weights():
    """A bias that lifts one expert into every token's top-k chooses it,
    while its weight stays its unbiased score over the chosen scores."""
    cfg = _cfg()
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, cfg.d_model))
    router = jax.random.normal(jax.random.PRNGKey(1), (cfg.d_model, 16))
    router = router / np.sqrt(cfg.d_model)
    plain = MOE.route({"router": router, "router_bias": jnp.zeros(16)}, x, cfg)
    lifted = jnp.zeros(16).at[5].set(10.0)
    top_i, top_w, _ = MOE.route({"router": router, "router_bias": lifted},
                                x, cfg)
    assert not np.all(np.any(np.asarray(plain[0]) == 5, axis=-1))
    assert np.all(np.any(np.asarray(top_i) == 5, axis=-1))
    s = jax.nn.sigmoid(x @ router)
    chosen = jnp.take_along_axis(s, top_i, axis=-1)
    np.testing.assert_allclose(top_w, chosen / chosen.sum(-1, keepdims=True)
                               * cfg.routed_scaling_factor, rtol=1e-6)


# -- the chip's share of the experts -------------------------------------------
def _uncut_layer(p, x, cfg):
    """Every token through every expert it chose, weighted, plus the shared
    expert: the whole layer with no capacity and no shards."""
    top_i, top_w, _ = MOE.route(p, x, cfg)
    out = L.mlp(p["shared"], x)
    for e in range(cfg.n_experts):
        w = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)[..., None]
        y = L.mlp({"w_gate": p["w_gate"][e], "w_up": p["w_up"][e],
                   "w_down": p["w_down"][e]}, x)
        out = out + w * y
    return out


def _layer_params(cfg, key=0):
    p = MOE.moe_init(jax.random.PRNGKey(key), cfg)
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(key + 1),
                                                (cfg.n_experts,))
    return p


def _share(p, cfg, shard, held):
    q = dict(p)
    for name in ("w_gate", "w_up", "w_down"):
        q[name] = p[name][shard * held:(shard + 1) * held]
    return q, dataclasses.replace(cfg, n_experts_held=held, expert_shard=shard)


def test_held_shares_sum_to_the_uncut_layer():
    """16 experts held 4 to a shard: the four shards' outputs, with the
    shared expert counted once, add up to the uncut layer. Float32 sums in
    another order: 1e-5 of the output's scale."""
    cfg = _cfg()
    p = _layer_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, cfg.d_model))
    shared = L.mlp(p["shared"], x)
    total = -3 * shared
    for shard in range(4):
        q, c = _share(p, cfg, shard, 4)
        total = total + MOE.moe_ffn(q, x, c)[0]
    ref = _uncut_layer(p, x, cfg)
    np.testing.assert_allclose(total, ref, atol=1e-5 * float(jnp.abs(ref).max()))


def test_held_layer_drops_no_token_at_decode():
    """Every lane routed to one held expert: with the decode step's single
    dispatch group all 24 lanes reach it (the capacity path at factor 1.25
    would keep 8 of them). Tolerance as above."""
    cfg = _cfg()
    p = _layer_params(cfg)
    p["router_bias"] = p["router_bias"].at[1].set(10.0)
    q, c = _share(p, cfg, 0, 4)
    x = jax.random.normal(jax.random.PRNGKey(2), (24, 1, cfg.d_model))
    out, _, (top_i, _) = MOE.moe_ffn(q, x, c, groups=1, return_routing=True)
    assert np.all(np.any(np.asarray(top_i) == 1, axis=-1))
    ref = _uncut_layer(dict(p, w_gate=p["w_gate"].at[4:].set(0)), x, cfg)
    np.testing.assert_allclose(out, ref, atol=1e-5 * float(jnp.abs(ref).max()))


# -- YaRN ------------------------------------------------------------------------
def test_yarn_frequencies_and_softmax_scale():
    """inv_freq = f_e / 40 * ramp + f_e * (1 - ramp), the ramp from
    low = floor(64 ln(4096 / (32 2 pi)) / (2 ln 1e4)) = 10 to high = 23; the
    softmax scale mscale(40, 1)^2 / sqrt(192), mscale = 0.1 ln 40 + 1."""
    cfg = get_config("deepseek-v3-671b")
    dim, theta = 64, 1e4
    low = math.floor(dim * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(dim * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(theta)))
    assert (low, high) == (10, 23)
    f_e = theta ** (-2 * np.arange(32) / dim)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = f_e / 40 * ramp + f_e * (1 - ramp)
    np.testing.assert_allclose(L.rope_inv_freq(cfg, dim), want, rtol=1e-6)
    assert L.rope_cos_scale(cfg) == 1.0
    mscale = 0.1 * math.log(40) + 1
    assert MLA.softmax_scale(cfg) == pytest.approx(mscale ** 2 / math.sqrt(192))
    assert mscale ** 2 == pytest.approx(1.874, abs=1e-3)


# -- the decode step updates the latent stacks in place --------------------------
def _unscanned_mla_decode_step(params, cache, tokens, cfg):
    """The MLA decode step as a Python loop over layers, each layer's latent
    sliced out of the stack, stepped as a one-layer stack and stacked back:
    the oracle of the scan that updates the stacks in place."""
    @jax.jit
    def layer_step(p, x, c_l, kr_l, pos):
        o, c_l, kr_l = MLA.mla_decode_step(
            p["attn"], L.rmsnorm(p["ln1"], x), c_l[None], kr_l[None], 0, pos,
            cfg)
        x = x + o
        h = L.rmsnorm(p["ln2"], x)
        x = x + (MOE.moe_ffn(p["moe"], h, cfg, groups=1)[0] if "moe" in p
                 else L.mlp(p["mlp"], h))
        return x, c_l[0], kr_l[0]

    pos = cache["pos"]
    x = L.embed(params["embed"], tokens, cfg)
    layers = ([jax.tree.map(lambda t, i=i: t[i], params["dense_layers"])
               for i in range(cfg.first_k_dense)]
              + [jax.tree.map(lambda t, i=i: t[i], params["layers"])
                 for i in range(cfg.n_layers - cfg.first_k_dense)])
    cs, krs = [], []
    for i, p in enumerate(layers):
        x, c_l, kr_l = layer_step(p, x, cache["c"][i], cache["kr"][i], pos)
        cs.append(c_l)
        krs.append(kr_l)
    logits = L.logits(params["embed"], L.rmsnorm(params["ln_f"], x), cfg)
    return logits, {**cache, "c": jnp.stack(cs), "kr": jnp.stack(krs),
                    "pos": pos + 1}


@pytest.mark.parametrize("per_lane", [True, False])
def test_inplace_mla_step_matches_slice_and_stack_oracle(per_lane):
    """Three layers (one dense), 8 of 16 experts held, lanes at different
    positions: logits and latent stacks of 12 steps agree with the oracle
    to float32 rounding (1e-5 of the logits' scale; the stacks' writes are
    the same values)."""
    cfg = _cfg(n_layers=3, n_experts_held=8, expert_shard=1)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    lanes, max_len = 3, 24
    cache = model.init_decode_cache(cfg, lanes, max_len)
    kc, kk = jax.random.split(jax.random.PRNGKey(1))
    cache["c"] = jax.random.normal(kc, cache["c"].shape)
    cache["kr"] = jax.random.normal(kk, cache["kr"].shape)
    cache["pos"] = (jnp.array([0, 5, 9], jnp.int32) if per_lane
                    else jnp.int32(4))
    toks = jax.random.randint(jax.random.PRNGKey(2), (12, lanes, 1), 0,
                              cfg.vocab_size)
    step = jax.jit(lambda p, c, t: model.decode_step(p, c, t, cfg,
                                                     moe_groups=1))
    ours, theirs = cache, dict(cache)
    for t in range(12):
        lg, ours = step(params, ours, toks[t])
        ref, theirs = _unscanned_mla_decode_step(params, theirs, toks[t], cfg)
        scale = float(jnp.abs(ref).max())
        np.testing.assert_allclose(lg, ref, atol=1e-5 * scale)
    for key in ("c", "kr"):
        np.testing.assert_allclose(ours[key], theirs[key], atol=1e-5)
    np.testing.assert_array_equal(ours["pos"], theirs["pos"])


@pytest.mark.parametrize("per_lane", [True, False])
def test_mla_step_through_kernel_matches_jnp_path(monkeypatch, per_lane):
    """The branch a TPU takes, the MLA decode-attention kernel reading each
    layer from the carried latent stacks (here interpreted, 8-position
    blocks of a 24-position cache), against the jnp path on the same
    config: logits and latent stacks of 12 steps, each step from the jnp
    path's cache so that the gap is one step's. Float32, with the softmax
    taken online: 1e-5 of the logits' scale."""
    cfg = _cfg(n_layers=3, n_experts_held=8, expert_shard=1)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    lanes, max_len = 3, 24
    cache = model.init_decode_cache(cfg, lanes, max_len)
    kc, kk = jax.random.split(jax.random.PRNGKey(1))
    cache["c"] = jax.random.normal(kc, cache["c"].shape)
    cache["kr"] = jax.random.normal(kk, cache["kr"].shape)
    cache["pos"] = (jnp.array([0, 5, 9], jnp.int32) if per_lane
                    else jnp.int32(4))
    toks = jax.random.randint(jax.random.PRNGKey(2), (12, lanes, 1), 0,
                              cfg.vocab_size)

    def step_fn():
        return jax.jit(lambda p, c, t: model.decode_step(p, c, t, cfg,
                                                         moe_groups=1))

    jnp_step, kernel_step = step_fn(), step_fn()
    want = cache
    for t in range(12):
        with monkeypatch.context() as m:
            m.setattr(L, "decode_kernel_applies", lambda *a, **k: True)
            lg, got = kernel_step(params, want, toks[t])
        ref, want = jnp_step(params, want, toks[t])
        scale = float(jnp.abs(ref).max())
        np.testing.assert_allclose(lg, ref, atol=1e-5 * scale)
        for key in ("c", "kr"):
            np.testing.assert_allclose(got[key], want[key], atol=1e-5)
        np.testing.assert_array_equal(got["pos"], want["pos"])


# -- the MLA decode-attention kernel ------------------------------------------------
@pytest.mark.parametrize("n_valid", [
    [1, 1, 1, 1],
    [16, 32, 48, 16],
    [17, 33, 17, 33],
    [48, 48, 48, 48],
    [1, 16, 17, 40],
    40,
], ids=["one", "block-end", "block-end-plus-one", "whole", "mixed",
        "scalar-pos"])
@pytest.mark.parametrize("H,kr", [(8, 128), (8, 512), (16, 128), (16, 512)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_mla_decode_attention_matches_jnp(dtype, tol, H, kr, n_valid):
    """The kernel in interpret mode, reading layer 1 of two-layer latent
    stacks of 48 positions in their three 16-position blocks, against the
    jnp absorbed attention on that layer. Every position past a lane's
    ``n_valid``, and all of layer 0, is NaN in the kernel's stacks: the
    output stays finite, so the clamped blocks are not read into it and the
    last block's tail is masked. The scalar-pos case is the form a scalar
    ``pos`` takes, ``pos + 1`` for every lane. Tolerance relative to the
    output's largest magnitude: float32 rounding of an online softmax, or a
    bf16 ulp of the probabilities and of the output."""
    B, S, rdim = 4, 48, 64
    if isinstance(n_valid, int):
        n_valid = jnp.broadcast_to(jnp.int32(n_valid - 1) + 1, (B,))
    n_valid = jnp.asarray(n_valid, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(H + kr), 4)
    q_c = jax.random.normal(ks[0], (B, H, kr)).astype(dtype)
    q_rope = jax.random.normal(ks[1], (B, H, rdim)).astype(dtype)
    c = jax.random.normal(ks[2], (2, B, S, kr)).astype(dtype)
    kr_ = jax.random.normal(ks[3], (2, B, S, rdim)).astype(dtype)
    scale = 1.0 / math.sqrt(kr + rdim)
    past = (jnp.arange(S)[None, :] >= n_valid[:, None])[None, :, :, None]
    past = past | (jnp.arange(2) == 0)[:, None, None, None]
    c_nan = jnp.where(past, jnp.nan, c).astype(dtype)
    kr_nan = jnp.where(past, jnp.nan, kr_).astype(dtype)
    got = mla_decode_attention(q_c, q_rope, c_nan, kr_nan, jnp.int32(1),
                               n_valid, scale=scale, interpret=True)
    want = MLA.absorbed_attention(q_c[:, None], q_rope[:, None], c[1], kr_[1],
                                  n_valid, scale)[:, 0]
    assert got.shape == want.shape and got.dtype == dtype
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, atol=tol * np.abs(want).max())


# -- the held-expert kernel -------------------------------------------------------
@pytest.mark.parametrize("counts,ff", [
    ([0, 2, 0, 0, 5, 1, 0, 0], 256),
    ([3, 0, 0, 1, 0, 0, 0, 2], 1024),
    ([0, 0, 0, 0, 0, 0, 0, 4], 1024),
    ([8, 8, 8, 8, 8, 8, 8, 8], 256),
])
def test_held_expert_kernel_matches_jnp(counts, ff):
    """The kernel in interpret mode, reading one layer of the weight stacks,
    against the jnp einsums on the rows of every expert that has tokens
    (the rows of the others are undefined).
    ff 1024 is two hidden blocks, accumulated in float32: 1e-5 of the
    output's scale."""
    E, cap, d = 8, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(len(counts) + ff), 4)
    x = jax.random.normal(ks[0], (E, cap, d))
    wg = jax.random.normal(ks[1], (E, d, ff)) / np.sqrt(d)
    wu = jax.random.normal(ks[2], (E, d, ff)) / np.sqrt(d)
    wd = jax.random.normal(ks[3], (E, ff, d)) / np.sqrt(ff)
    # the weights as the third layer of a stack of four, read in place
    stack = [jnp.zeros((4,) + w.shape).at[2].set(w) for w in (wg, wu, wd)]
    got = held_experts(x, *stack, jnp.array(counts), 2, interpret=True)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, wg)) * jnp.einsum(
        "ecd,edf->ecf", x, wu)
    want = jnp.einsum("ecf,efd->ecd", h, wd)
    active = np.array(counts) > 0
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got)[active],
                               np.asarray(want)[active], atol=1e-5 * scale)


# -- serving through lane mode against the benchmark's reference -------------------
def _tiny_bench_config() -> dict:
    c = json.loads((BENCH / "configs" / "deepseek-v3.json").read_text())
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32,
             n_routed_experts=4, num_experts_per_tok=4, n_group=4,
             topk_group=2, num_hidden_layers=3, vocab_size=200,
             dtype="float32")
    c["deployment"] = dict(c["deployment"], routed_experts=16, expert_shard=1)
    return c


def _bench_modules():
    from common import load_module
    driver = load_module(BENCH / "drivers" / "chat_deepseek.py")
    ref = load_module(BENCH / "configs" / "deepseek-v3.reference.py")
    return driver, ref


def test_lane_serving_matches_the_reference():
    """Two requests served by lane-mode decode steps (prompt one token a
    step, then greedy answers), the second joining three steps late: the
    program's logits at every served position against the float32
    reference's full forward pass. Both are float32 here; absorbed against
    decompressed attention and the engine's order of sums leave 1e-4 of
    the logits' scale."""
    import deepseek_weights
    driver, ref_mod = _bench_modules()
    c = _tiny_bench_config()
    seed = 2**33 + 5
    cfg = driver.program_config(c)
    eng = ServingEngine(cfg, deepseek_weights.params(seed, c),
                        EngineConfig(max_batch=3, max_len=64))
    eng.enable_lane_decode()
    seen = []
    step = eng._step

    def capture(*args):
        out = step(*args)
        seen.append(np.asarray(out[0][:, 0, :c["vocab_size"]]))
        return out
    eng._step = capture

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, c["vocab_size"], 9), rng.integers(0, c["vocab_size"], 5)]
    start, n_out = [0, 3], [6, 7]
    fed = [0, 0]
    served = [[], []]
    logits = [[], []]
    feed = np.zeros(3, np.int32)
    for t in range(20):
        for i in range(2):
            if t == start[i]:
                eng.reset_lanes([i])   # a request joins: a fresh context
            if t < start[i] or len(served[i]) >= n_out[i]:
                continue
            feed[i] = (prompts[i][fed[i]] if fed[i] < len(prompts[i])
                       else served[i][-1])
        nxt, _ = eng.decode_lanes(feed)
        for i in range(2):
            if t < start[i] or len(served[i]) >= n_out[i]:
                continue
            fed[i] += 1
            if fed[i] >= len(prompts[i]):
                served[i].append(int(nxt[i]))
                logits[i].append(seen[-1][i])
    assert [len(s) for s in served] == n_out
    samples = [(prompts[i], np.array(served[i])) for i in range(2)]
    ref, tokens = ref_mod.served_logits(seed, c, samples, 64, 4)
    ours = np.concatenate([np.stack(lg) for lg in logits])
    np.testing.assert_array_equal(tokens, np.concatenate(served))
    scale = float(np.abs(ref).max())
    np.testing.assert_allclose(ours, ref, atol=1e-4 * scale)
    assert ref_mod.widest_gap(ref, tokens) < 1e-4 * scale


def test_engine_counts_held_experts_on_the_device():
    """``moe_counts`` sums, over steps and MoE layers, the token slots routed
    to the held experts and the (layer, held expert) pairs with a token,
    as read from the step's own routing, and sets its two gauges;
    ``reset_lanes`` leaves it alone."""
    cfg = _cfg(n_layers=3, n_experts_held=4, expert_shard=2)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    tel = Telemetry()
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=4, max_len=16),
                        telemetry=tel)
    eng.enable_lane_decode()
    probe = jax.jit(lambda p, c, t: model.decode_step(
        p, c, t, cfg, moe_groups=1, return_routing=True)[2]["top_i"])
    want = np.zeros(2, np.int64)
    for t in range(5):
        feed = np.array([t, 2 * t + 1, 7, 3 * t], np.int32)
        top_i = np.asarray(probe(params, eng.cache, jnp.asarray(feed)[:, None]))
        held = (top_i >= 8) & (top_i < 12)
        want += [held.sum(), sum(len(set(top_i[layer][held[layer]]))
                                 for layer in range(top_i.shape[0]))]
        eng.decode_lanes(feed)
        if t == 2:
            eng.reset_lanes([1])
    assert eng.moe_counts() == {"moe_routed": int(want[0]),
                                "moe_active": int(want[1])}
    assert tel.gauges["serving.moe_routed"] == want[0]
    assert tel.gauges["serving.moe_active"] == want[1]
