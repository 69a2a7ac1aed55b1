"""Multi-head Latent Attention (DeepSeek-V2/V3).

Training/prefill uses the decompressed formulation; decode uses the absorbed
formulation whose KV cache is the compressed latent (kv_lora_rank +
qk_rope_head_dim per token) — the reason MLA's cache is small and hot, and why
DOLMA's placement policy keeps it local while demoting routed experts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.mla_decode_attention import mla_decode_attention
from repro.models import layers
from repro.models.flash import flash_attention
from repro.models.layers import (NEG_INF, Params, _init, rmsnorm, rope,
                                 rope_cos_scale, rope_inv_freq, yarn_mscale)
from repro.models.sharding import constrain


def mla_init(key, cfg: ModelConfig) -> Params:
    d, H = cfg.d_model, cfg.n_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rdim, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    return {
        "wq_a": _init(ks[0], (d, qr), cfg.dtype),
        "q_ln": {"scale": jnp.ones((qr,), cfg.dtype)},
        "wq_b": _init(ks[1], (qr, H * (nope + rdim)), cfg.dtype),
        "wkv_a": _init(ks[2], (d, kr + rdim), cfg.dtype),
        "kv_ln": {"scale": jnp.ones((kr,), cfg.dtype)},
        "wkv_b": _init(ks[3], (kr, H * (nope + vh)), cfg.dtype),
        "wo": _init(ks[4], (H * vh, d), cfg.dtype, scale=1.0 / np.sqrt(H * vh)),
    }


def softmax_scale(cfg: ModelConfig) -> float:
    """``1 / sqrt(qk head dim)``, times YaRN's ``mscale(factor,
    mscale_all_dim)^2`` where the model extends its context by YaRN."""
    scale = 1.0 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    if cfg.rope_factor and cfg.rope_mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    return float(scale)


def _rope(x, positions, cfg):
    return rope(x, positions, cfg.rope_theta,
                inv_freq=rope_inv_freq(cfg, cfg.qk_rope_head_dim),
                cos_scale=rope_cos_scale(cfg))


def _project_q(p, x, cfg, positions):
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    cq = rmsnorm(p["q_ln"], x @ p["wq_a"])
    q = (cq @ p["wq_b"]).reshape(B, S, H, nope + rdim)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = _rope(q_rope, positions, cfg)
    return q_nope, q_rope


def _project_kv_latent(p, x, cfg, positions):
    kr, rdim = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    ckv = x @ p["wkv_a"]  # (B,S,kr+rdim)
    c_kv = rmsnorm(p["kv_ln"], ckv[..., :kr])
    k_rope = _rope(ckv[..., None, kr:], positions, cfg)[:, :, 0]  # (B,S,rdim)
    return c_kv, k_rope


def mla_attention(
    p: Params, x: jax.Array, cfg: ModelConfig, *, positions: jax.Array
) -> jax.Array:
    """Decompressed MLA for train/prefill (full causal attention)."""
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rdim, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    q_nope, q_rope = _project_q(p, x, cfg, positions)
    c_kv, k_rope = _project_kv_latent(p, x, cfg, positions)
    kv = (c_kv @ p["wkv_b"]).reshape(B, S, H, nope + vh)
    k_nope, v = kv[..., :nope], kv[..., nope:]

    q = jnp.concatenate([q_nope, q_rope], axis=-1)  # (B,S,H,nope+rdim)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, rdim))], axis=-1
    )
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "heads", None)
    v = constrain(v, "batch", None, "heads", None)
    out = flash_attention(q, k, v, causal=True, scale=softmax_scale(cfg))
    out = constrain(out, "batch", None, "heads", None)
    return out.reshape(B, S, H * vh) @ p["wo"]


def mla_decode_step(
    p: Params,
    x: jax.Array,
    c_stack: jax.Array,   # (L, B, S_max, kv_lora_rank)
    kr_stack: jax.Array,  # (L, B, S_max, qk_rope_head_dim)
    layer: jax.Array,
    pos: jax.Array,
    cfg: ModelConfig,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Absorbed one-token decode against layer ``layer`` of the stacked
    compressed-latent caches.

    ``pos`` is a scalar (whole batch at one position) or a per-lane ``(B,)``
    vector (continuous batching: each lane's latent lands at its own slot
    and is masked to its own prefix). Only the new latent is written, at
    ``[layer, lane, pos]``, and the scores and context read layer ``layer``
    straight from the stacks: a layer loop that carries the stacks updates
    them in place instead of slicing each layer out and stacking it back.
    Compiled on a TPU, attention is the
    :func:`~repro.kernels.mla_decode_attention.mla_decode_attention` kernel,
    which DMAs the layer's latent from the stacks once, only up to each
    lane's position, and keeps its scores in VMEM; elsewhere it is
    :func:`absorbed_attention` on ``c_stack[layer]``, the same arithmetic
    over every position.
    """
    B = x.shape[0]
    H = cfg.n_heads
    nope, vh = cfg.qk_nope_head_dim, cfg.v_head_dim
    kr = cfg.kv_lora_rank

    per_lane = jnp.ndim(pos) > 0
    positions = (jnp.reshape(pos, (B, 1)) if per_lane
                 else jnp.full((B, 1), pos))
    q_nope, q_rope = _project_q(p, x, cfg, positions)  # (B,1,H,*)
    c_new, kr_new = _project_kv_latent(p, x, cfg, positions)
    if per_lane:
        lanes = jnp.arange(B)
        c_stack = c_stack.at[layer, lanes, positions[:, 0]].set(c_new[:, 0])
        kr_stack = kr_stack.at[layer, lanes, positions[:, 0]].set(kr_new[:, 0])
    else:
        at = (layer, 0, pos, 0)
        c_stack = jax.lax.dynamic_update_slice(c_stack, c_new[None], at)
        kr_stack = jax.lax.dynamic_update_slice(kr_stack, kr_new[None], at)
    c_stack = constrain(c_stack, "layers", "batch", "kv_len", None)
    kr_stack = constrain(kr_stack, "layers", "batch", "kv_len", None)

    wkv_b = p["wkv_b"].reshape(kr, H, nope + vh)
    w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]

    # absorb W_uk into q: score directly against the latent cache
    q_c = jnp.einsum("bqhn,lhn->bqhl", q_nope, w_uk)  # (B,1,H,kr)
    n_valid = positions[:, 0] + 1
    if layers.decode_kernel_applies(c_stack, positions_tile=128):
        ctx = mla_decode_attention(q_c[:, 0], q_rope[:, 0], c_stack, kr_stack,
                                   layer, n_valid,
                                   scale=softmax_scale(cfg))[:, None]
    else:
        ctx = absorbed_attention(q_c, q_rope, c_stack[layer], kr_stack[layer],
                                 n_valid, softmax_scale(cfg))
    out = jnp.einsum("bqhl,lhv->bqhv", ctx, w_uv)       # (B,1,H,vh)
    out = out.reshape(B, 1, H * vh) @ p["wo"]
    return out, c_stack, kr_stack


def absorbed_attention(q_c, q_rope, cache_c, cache_kr, n_valid, scale):
    """The absorbed attention in jnp: queries ``q_c`` ``(B,1,H,kr)`` and
    ``q_rope`` ``(B,1,H,rdim)`` against one layer's latent ``(B,S,kr)`` and
    rope keys ``(B,S,rdim)``, lane b over positions ``[0, n_valid[b])``.
    Scores and softmax in float32, the probabilities in the query's dtype.
    Returns the context ``(B,1,H,kr)``."""
    f32 = jnp.float32
    scores = (
        jnp.einsum("bqhl,bsl->bhqs", q_c, cache_c, preferred_element_type=f32)
        + jnp.einsum("bqhr,bsr->bhqs", q_rope, cache_kr,
                     preferred_element_type=f32)
    ) * scale
    valid = jnp.arange(cache_c.shape[1])[None, :] < n_valid[:, None]  # (B,S)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q_c.dtype)
    return jnp.einsum("bhqs,bsl->bqhl", probs, cache_c)  # (B,1,H,kr)

