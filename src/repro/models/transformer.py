"""Unified model assembly for all assigned families.

Public API (uniform across dense / moe / ssm / hybrid / vlm; encdec lives in
:mod:`repro.models.encdec` with the same signatures):

  init_params(key, cfg)                    -> params pytree
  forward(params, batch, cfg, ...)         -> (logits, aux)
  loss_fn(params, batch, cfg, ...)         -> (loss, metrics)
  init_decode_cache(cfg, batch, max_len)   -> cache pytree
  decode_step(params, cache, tokens, cfg)  -> (logits, cache)

Layers are *stacked* (leading dim = n_layers) and driven by
:func:`repro.core.tiering.tiered_scan` — the compiled form of DOLMA's
dual-buffer: layer k+1's weights are fetched (device copy / all-gather,
depending on their tier/sharding) while layer k computes. The dual buffer
composes with rematerialization (the fetch carry lives inside the block-level
remat boundary, so gathered weights are recomputed rather than saved); the
old "prefetch only when remat is off" caveat is retired (DESIGN.md §2).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.tiering import remote_carry_placer, tiered_scan
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.sharding import constrain, current_mesh, resolve_spec

Params = dict[str, Any]

REMAT_POLICIES = {
    "none": None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}


def _maybe_remat(fn, remat: str):
    if remat == "none":
        return fn
    base = remat.removesuffix("_flat")  # '<policy>_flat' -> '<policy>'
    return jax.checkpoint(fn, policy=REMAT_POLICIES[base])


def _activation_carry_placer():
    """remote_carry_fn for the layer scan's saved block carries.

    Under a mesh, saved activation carries are constrained to their logical
    (batch/seq-sharded) spec — with ``memory_kind="pinned_host"`` where the
    backend's SPMD partitioner accepts it — so persistent activation memory
    follows the same tier budget as weights (DESIGN.md §2).
    """
    mesh = current_mesh()
    if mesh is None:
        return None

    def spec_fn(leaf):
        names = ("batch", "seq_sp") + (None,) * (leaf.ndim - 2)
        return resolve_spec(leaf.shape, names, mesh)

    return remote_carry_placer(mesh, spec_fn=spec_fn)


def scan_stacked_layers(fn, carry, stacked, n_layers: int, *, remat: str,
                        prefetch: bool, prefetch_under_remat: bool = True):
    """Map a remat policy string onto :func:`tiered_scan` (shared w/ encdec).

    ``remat`` ∈ REMAT_POLICIES keys, optionally suffixed ``_flat``:
    '<policy>_flat' = single-level per-layer remat — one fwd + one recompute
    (vs sqrt-L's two) — fewer recomputed collectives at the cost of O(L)
    saved carries; pick via microbatching headroom (§Perf).
    """
    if remat == "none":
        return tiered_scan(fn, carry, stacked, n_layers=n_layers,
                           prefetch=prefetch)
    flat = remat.endswith("_flat")
    base = remat.removesuffix("_flat")
    return tiered_scan(
        fn, carry, stacked, n_layers=n_layers, remat=True,
        policy=REMAT_POLICIES[base],
        prefetch=prefetch and prefetch_under_remat,
        min_layers=10 ** 9 if flat else 12,
        remote_carry_fn=_activation_carry_placer(),
    )


# ---------------------------------------------------------------------------
# per-family layer blocks
# ---------------------------------------------------------------------------

def _attn_block_init(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": L.rmsnorm_init(cfg.d_model, cfg.dtype),
        "ln2": L.rmsnorm_init(cfg.d_model, cfg.dtype),
    }
    if cfg.attention == "mla":
        p["attn"] = MLA.mla_init(k1, cfg)
    else:
        p["attn"] = L.attention_init(k1, cfg)
    return p


def _dense_layer_init(key, cfg: ModelConfig) -> Params:
    p = _attn_block_init(key, cfg)
    p["mlp"] = L.mlp_init(jax.random.fold_in(key, 7), cfg)
    return p


def _moe_layer_init(key, cfg: ModelConfig) -> Params:
    p = _attn_block_init(key, cfg)
    p["moe"] = MOE.moe_init(jax.random.fold_in(key, 7), cfg)
    return p


def _ssm_layer_init(key, cfg: ModelConfig) -> Params:
    return {
        "ln": L.rmsnorm_init(cfg.d_model, cfg.dtype),
        "ssm": SSM.ssm_init(key, cfg),
    }


def _attention_part(p, x, cfg, positions):
    h = L.rmsnorm(p["ln1"], x)
    if cfg.attention == "mla":
        return x + MLA.mla_attention(p["attn"], h, cfg, positions=positions)
    return x + L.gqa_attention(p["attn"], h, cfg, positions=positions)


def _dense_layer(p, x, cfg, positions):
    x = _attention_part(p, x, cfg, positions)
    x = x + L.mlp(p["mlp"], L.rmsnorm(p["ln2"], x))
    return constrain(x, "batch", "seq_sp", None)


def _moe_layer(p, x, cfg, positions, groups=None):
    x = _attention_part(p, x, cfg, positions)
    out, aux = MOE.moe_ffn(p["moe"], L.rmsnorm(p["ln2"], x), cfg, groups=groups)
    return constrain(x + out, "batch", "seq_sp", None), aux


def _ssm_layer(p, x, cfg):
    x = x + SSM.ssm_block(p["ssm"], L.rmsnorm(p["ln"], x), cfg)
    return constrain(x, "batch", "seq_sp", None)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stacked(init_fn, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 8)
    p: Params = {"embed": L.embed_init(keys[0], cfg),
                 "ln_f": L.rmsnorm_init(cfg.d_model, cfg.dtype)}

    if cfg.family in ("dense", "vlm"):
        p["layers"] = _stacked(lambda k: _dense_layer_init(k, cfg), keys[1], cfg.n_layers)
    elif cfg.family == "moe":
        n_moe = cfg.n_layers - cfg.first_k_dense
        if cfg.first_k_dense:
            p["dense_layers"] = _stacked(
                lambda k: _dense_layer_init(k, cfg), keys[1], cfg.first_k_dense
            )
        p["layers"] = _stacked(lambda k: _moe_layer_init(k, cfg), keys[2], n_moe)
    elif cfg.family == "ssm":
        p["layers"] = _stacked(lambda k: _ssm_layer_init(k, cfg), keys[1], cfg.n_layers)
    elif cfg.family == "hybrid":
        p["layers"] = _stacked(lambda k: _ssm_layer_init(k, cfg), keys[1], cfg.n_layers)
        p["shared_attn"] = _dense_layer_init(keys[3], cfg)
    else:
        raise ValueError(f"init_params: family {cfg.family} handled in encdec.py")

    if cfg.mtp_depth:
        p["mtp"] = {
            "proj": L._init(keys[4], (2 * cfg.d_model, cfg.d_model), cfg.dtype),
            "layer": _dense_layer_init(keys[5], cfg),
            "ln": L.rmsnorm_init(cfg.d_model, cfg.dtype),
        }
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _embed_inputs(params, batch, cfg: ModelConfig):
    """Token (+ frontend stub) embedding. Returns (x, positions, label_offset)."""
    tokens = batch["tokens"]
    x = L.embed(params["embed"], tokens, cfg)
    if cfg.family == "vlm":
        patches = batch["patches"].astype(x.dtype)  # (B, F, d) — ViT stub
        x = jnp.concatenate([patches, x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    return x, positions


def _run_trunk(params, x, positions, cfg: ModelConfig, *, remat: str,
               prefetch: bool, prefetch_under_remat: bool = True,
               moe_groups: int | None = None):
    """Scan the stacked layers; returns (hidden, aux_loss).

    Dual-buffer note: the explicit prefetch carry (layer k+1's weights fetched
    while layer k computes) composes with remat — inside the block-level remat
    boundary the carried gathered weights are recomputed for backward, not
    saved, so prefetch no longer defeats FSDP/offload (DESIGN.md §2).
    ``prefetch_under_remat=False`` restores the old behaviour (overlap left
    to XLA's collective pipeliner / latency-hiding scheduler).
    """
    aux0 = jnp.zeros((), jnp.float32)

    def scan_layers(fn, carry, stacked, n):
        return scan_stacked_layers(
            fn, carry, stacked, n, remat=remat, prefetch=prefetch,
            prefetch_under_remat=prefetch_under_remat,
        )

    if cfg.family in ("dense", "vlm"):
        x = scan_layers(lambda c, p: _dense_layer(p, c, cfg, positions),
                        x, params["layers"], cfg.n_layers)
        return x, aux0

    if cfg.family == "moe":
        aux = aux0
        if cfg.first_k_dense:
            x = scan_layers(lambda c, p: _dense_layer(p, c, cfg, positions),
                            x, params["dense_layers"], cfg.first_k_dense)

        def moe_body(carry, p):
            xx, a = carry
            xx, aux_l = _moe_layer(p, xx, cfg, positions, groups=moe_groups)
            return (xx, a + aux_l)

        x, aux = scan_layers(moe_body, (x, aux), params["layers"],
                             cfg.n_layers - cfg.first_k_dense)
        return x, aux

    if cfg.family == "ssm":
        x = scan_layers(lambda c, p: _ssm_layer(p, c, cfg),
                        x, params["layers"], cfg.n_layers)
        return x, aux0

    if cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        n_groups, tail = divmod(cfg.n_layers, k)
        fn = lambda c, p: _ssm_layer(p, c, cfg)  # noqa: E731
        shared_fn = _maybe_remat(
            lambda xx: _dense_layer(params["shared_attn"], xx, cfg, positions), remat
        )
        for g in range(n_groups):
            group = jax.tree.map(
                lambda t: jax.lax.slice_in_dim(t, g * k, (g + 1) * k, axis=0),
                params["layers"],
            )
            x = scan_layers(fn, x, group, k)
            x = shared_fn(x)
        if tail:
            group = jax.tree.map(
                lambda t: jax.lax.slice_in_dim(t, n_groups * k, cfg.n_layers, axis=0),
                params["layers"],
            )
            x = scan_layers(fn, x, group, tail)
        return x, aux0

    raise ValueError(f"unknown family {cfg.family}")


def forward(
    params: Params,
    batch: dict,
    cfg: ModelConfig,
    *,
    remat: str = "none",
    prefetch: bool = True,
    prefetch_under_remat: bool = True,
    moe_groups: int | None = None,
    return_hidden: bool = False,
):
    """Full-sequence forward. Returns (logits[B,S_tokens,V], aux_loss[, hidden])."""
    x, positions = _embed_inputs(params, batch, cfg)
    x = constrain(x, "batch", "seq_sp", None)
    x, aux = _run_trunk(params, x, positions, cfg, remat=remat,
                        prefetch=prefetch,
                        prefetch_under_remat=prefetch_under_remat,
                        moe_groups=moe_groups)
    x = L.rmsnorm(params["ln_f"], x)
    if cfg.family == "vlm":  # only text positions produce logits
        x = x[:, batch["patches"].shape[1]:]
    logits = L.logits(params["embed"], x, cfg)
    if return_hidden:
        return logits, aux, x
    return logits, aux


def loss_fn(
    params: Params,
    batch: dict,
    cfg: ModelConfig,
    *,
    remat: str = "full",
    prefetch: bool = True,
    prefetch_under_remat: bool = True,
    aux_weight: float = 0.01,
    mtp_weight: float = 0.1,
    moe_groups: int | None = None,
) -> tuple[jax.Array, dict]:
    """Next-token cross-entropy (+ MoE aux + MTP losses)."""
    want_hidden = bool(cfg.mtp_depth and "mtp" in params)
    out = forward(params, batch, cfg, remat=remat, prefetch=prefetch,
                  prefetch_under_remat=prefetch_under_remat,
                  moe_groups=moe_groups, return_hidden=want_hidden)
    logits, aux = out[0], out[1]
    labels = batch["labels"]
    nll = L.cross_entropy(logits[:, :-1].astype(jnp.float32), labels[:, 1:])
    loss = nll + aux_weight * aux
    metrics = {"nll": nll, "aux": aux}

    if want_hidden:
        # DeepSeek-style MTP: one extra block predicting token t+2 from
        # (trunk hidden_t, embed(token_{t+1})). Computed over the full S
        # (shift via roll; the invalid tail is masked out of the loss) so
        # sequence-length invariants (flash strips, sharding) hold.
        hidden = out[2]
        B, S, _ = hidden.shape
        emb_next = L.embed(
            params["embed"], jnp.roll(batch["tokens"], -1, axis=1), cfg
        )
        h = jnp.concatenate([hidden, emb_next], axis=-1) @ params["mtp"]["proj"]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        h = _dense_layer(params["mtp"]["layer"], h, cfg, positions)
        h = L.rmsnorm(params["mtp"]["ln"], h)
        mtp_logits = L.logits(params["embed"], h, cfg).astype(jnp.float32)
        # position t predicts labels[t+2]; the last two positions are invalid
        tgt = jnp.roll(labels, -2, axis=1)
        valid = jnp.arange(S) < S - 2
        lse = jax.nn.logsumexp(mtp_logits, axis=-1)
        picked = jnp.take_along_axis(mtp_logits, tgt[..., None], axis=-1)[..., 0]
        mtp_nll = jnp.sum((lse - picked) * valid) / jnp.maximum(
            jnp.sum(valid) * B, 1
        )
        loss = loss + mtp_weight * mtp_nll
        metrics["mtp_nll"] = mtp_nll
    return loss, metrics


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """KV / state caches sized for ``max_len`` context."""
    cache: dict = {"pos": jnp.zeros((), jnp.int32)}
    nL = cfg.n_layers

    if cfg.family in ("dense", "vlm", "moe"):
        if cfg.attention == "mla":
            cache["c"] = jnp.zeros((nL, batch, max_len, cfg.kv_lora_rank), cfg.dtype)
            cache["kr"] = jnp.zeros(
                (nL, batch, max_len, cfg.qk_rope_head_dim), cfg.dtype
            )
        else:
            S_c = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
            shape = (nL, batch, S_c, cfg.n_kv_heads, cfg.head_dim)
            cache["k"] = jnp.zeros(shape, cfg.dtype)
            cache["v"] = jnp.zeros(shape, cfg.dtype)
    elif cfg.family == "ssm":
        st = SSM.ssm_decode_init(cfg, batch)
        cache["conv"] = jnp.zeros((nL, *st["conv"].shape), st["conv"].dtype)
        cache["state"] = jnp.zeros((nL, *st["state"].shape), st["state"].dtype)
    elif cfg.family == "hybrid":
        st = SSM.ssm_decode_init(cfg, batch)
        cache["conv"] = jnp.zeros((nL, *st["conv"].shape), st["conv"].dtype)
        cache["state"] = jnp.zeros((nL, *st["state"].shape), st["state"].dtype)
        n_inv = cfg.n_layers // cfg.hybrid_attn_every
        shape = (n_inv, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        cache["shared_k"] = jnp.zeros(shape, cfg.dtype)
        cache["shared_v"] = jnp.zeros(shape, cfg.dtype)
    else:
        raise ValueError(f"decode cache for {cfg.family} lives in encdec.py")
    return cache


def decode_step(
    params: Params, cache: dict, tokens: jax.Array, cfg: ModelConfig,
    *, moe_groups: int | None = None, return_routing: bool = False,
):
    """One-token decode. tokens: (B, 1). Returns (logits[B,1,V], new cache).

    With ``return_routing`` (moe family only) a third element is appended:
    ``{"top_i": (nL_moe, B, 1, k), "top_p": (nL_moe, B, 1, k)}`` — the
    per-MoE-layer router decision, stacked in scan order over the MoE
    layers. The serving engine's expert pager consumes it both to validate
    that every routed expert was resident (the bit-identity fixpoint) and
    to feed the router-mass EMA that predicts the next step's experts.
    """
    pos = cache["pos"]
    x = L.embed(params["embed"], tokens, cfg)
    routing = None

    if cfg.family in ("dense", "vlm", "moe"):
        nd = cfg.first_k_dense if "dense_layers" in params else 0
        layers = params["layers"]
        if "moe" in layers:
            # the routed experts stay out of the scanned slices: each MoE
            # layer reads its own from the whole stacks, in place
            experts = {k: layers["moe"][k] for k in MOE.EXPERT_KEYS}
            layers = {**layers, "moe": {k: v for k, v in layers["moe"].items()
                                        if k not in experts}}

        def ffn(p, xx, layer):
            """The layer's MLP or MoE block; the router decision, if asked."""
            if "moe" not in p:
                return xx + L.mlp(p["mlp"], L.rmsnorm(p["ln2"], xx)), None
            h2 = L.rmsnorm(p["ln2"], xx)
            out = MOE.moe_ffn(
                {**p["moe"], **experts}, h2, cfg, groups=moe_groups,
                return_routing=return_routing, layer=layer - nd,
            )
            return xx + out[0], (out[2] if return_routing else None)

        # the cache stacks ride in the carry and each layer writes only its
        # new token into them: no per-layer slice out and stack back. The
        # dense prefix and the MoE layers carry the same stacks, the MoE
        # scan's layer ids offset by the prefix.
        if cfg.attention == "mla":
            keys, attend = ("c", "kr"), MLA.mla_decode_step
        else:
            keys, attend = ("k", "v"), L.gqa_decode_step_stacked

        def body(carry, scanned):
            xx, a_all, b_all = carry
            p, layer = scanned
            h = L.rmsnorm(p["ln1"], xx)
            o, a_all, b_all = attend(p["attn"], h, a_all, b_all, layer, pos,
                                     cfg)
            xx, rt = ffn(p, xx + o, layer)
            return (xx, a_all, b_all), rt

        carry = (x, cache[keys[0]], cache[keys[1]])
        if nd:
            carry, _ = jax.lax.scan(
                body, carry, (params["dense_layers"], jnp.arange(nd)))
        (x, new_a, new_b), rt_m = jax.lax.scan(
            body, carry, (layers, nd + jnp.arange(cfg.n_layers - nd)))
        cache = {**cache, keys[0]: new_a, keys[1]: new_b, "pos": pos + 1}
        if rt_m is not None:
            routing = {"top_i": rt_m[0], "top_p": rt_m[1]}

    elif cfg.family == "ssm":
        def body(xx, scanned):
            p, conv_l, state_l = scanned
            h = L.rmsnorm(p["ln"], xx)
            o, st = SSM.ssm_decode_step(p["ssm"], h, {"conv": conv_l, "state": state_l}, cfg)
            return xx + o, (st["conv"], st["state"])

        x, (new_conv, new_state) = jax.lax.scan(
            body, x, (params["layers"], cache["conv"], cache["state"])
        )
        cache = {**cache, "conv": new_conv, "state": new_state, "pos": pos + 1}

    elif cfg.family == "hybrid":
        k_every = cfg.hybrid_attn_every
        n_inv = cfg.n_layers // k_every
        new_conv, new_state = [], []
        new_sk, new_sv = [], []
        for g in range(n_inv + (1 if cfg.n_layers % k_every else 0)):
            lo, hi = g * k_every, min((g + 1) * k_every, cfg.n_layers)

            def body(xx, scanned):
                p, conv_l, state_l = scanned
                h = L.rmsnorm(p["ln"], xx)
                o, st = SSM.ssm_decode_step(
                    p["ssm"], h, {"conv": conv_l, "state": state_l}, cfg
                )
                return xx + o, (st["conv"], st["state"])

            group = jax.tree.map(lambda t: t[lo:hi], params["layers"])
            x, (cv, stt) = jax.lax.scan(
                body, x, (group, cache["conv"][lo:hi], cache["state"][lo:hi])
            )
            new_conv.append(cv)
            new_state.append(stt)
            if g < n_inv:
                p = params["shared_attn"]
                h = L.rmsnorm(p["ln1"], x)
                o, sk, sv = L.gqa_decode_step(
                    p["attn"], h, cache["shared_k"][g], cache["shared_v"][g], pos, cfg
                )
                x = x + o
                x = x + L.mlp(p["mlp"], L.rmsnorm(p["ln2"], x))
                new_sk.append(sk)
                new_sv.append(sv)
        cache = {
            **cache,
            "conv": jnp.concatenate(new_conv, 0),
            "state": jnp.concatenate(new_state, 0),
            "shared_k": jnp.stack(new_sk, 0),
            "shared_v": jnp.stack(new_sv, 0),
            "pos": pos + 1,
        }
    else:
        raise ValueError(cfg.family)

    x = L.rmsnorm(params["ln_f"], x)
    logits = L.logits(params["embed"], x, cfg)
    if return_routing:
        return logits, cache, routing
    return logits, cache
