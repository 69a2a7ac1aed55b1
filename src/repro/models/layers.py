"""Core layers: RMSNorm, RoPE, GQA attention (full/SWA/decode/cross), SwiGLU MLP.

Pure-functional: params are nested dicts of arrays; every function is
jit/scan/vmap-safe. Tensors are annotated with logical axis names resolved by
:mod:`repro.models.sharding`.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import kernel_backend
from repro.kernels.decode_attention import decode_attention
from repro.models.flash import flash_attention
from repro.models.sharding import constrain, current_mesh

Params = dict[str, Any]
NEG_INF = -0.7 * float(np.finfo(np.float32).max)


def _init(key, shape, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(shape[0])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# -- RMSNorm ---------------------------------------------------------------

def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(dt)


# -- rotary ------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(cfg: ModelConfig, dim: int) -> np.ndarray:
    """The ``dim // 2`` rotary frequencies: ``theta^(-2i/dim)``, or with
    ``cfg.rope_factor`` YaRN's blend of them with the interpolated
    ``theta^(-2i/dim) / factor``. Dimensions below ``low`` keep their
    frequency, those above ``high`` are interpolated, and a linear ramp
    joins them; ``low``/``high`` are where ``beta_fast``/``beta_slow``
    rotations fit into the original context."""
    theta = cfg.rope_theta
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not cfg.rope_factor:
        return extra.astype(np.float32)

    def at(rotations):
        return dim * math.log(cfg.rope_original_max_position
                              / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(at(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(at(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = extra / cfg.rope_factor * ramp + extra * (1 - ramp)
    return inv.astype(np.float32)


def rope_cos_scale(cfg: ModelConfig) -> float:
    """YaRN's factor on cos and sin (1 without YaRN)."""
    if not cfg.rope_factor:
        return 1.0
    return (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
            / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))


def rope(x: jax.Array, positions: jax.Array, theta: float = 1e4,
         inv_freq: np.ndarray | None = None, cos_scale: float = 1.0) -> jax.Array:
    """x: (..., S, H, D) with positions (..., S); the two halves of D are
    rotated by ``inv_freq`` (default ``theta^(-2i/D)``)."""
    d = x.shape[-1]
    half = d // 2
    freqs = (jnp.asarray(inv_freq) if inv_freq is not None else
             1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)))
    # positions: (..., S) -> (..., S, 1, 1) broadcast against (half,)
    angles = positions.astype(jnp.float32)[..., None, None] * freqs  # (...,S,1,half)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if cos_scale != 1.0:
        sin, cos = sin * cos_scale, cos * cos_scale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [
            x1 * cos.astype(x.dtype) - x2 * sin.astype(x.dtype),
            x2 * cos.astype(x.dtype) + x1 * sin.astype(x.dtype),
        ],
        axis=-1,
    )
    return out.astype(x.dtype)


# -- GQA attention ----------------------------------------------------------

def attention_init(key, cfg: ModelConfig) -> Params:
    d, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": _init(ks[0], (d, H * Dh), cfg.dtype),
        "wk": _init(ks[1], (d, KV * Dh), cfg.dtype),
        "wv": _init(ks[2], (d, KV * Dh), cfg.dtype),
        "wo": _init(ks[3], (H * Dh, d), cfg.dtype),
    }


def _split_heads(x, n, d):
    return x.reshape(*x.shape[:-1], n, d)


def _sdpa(q, k, v, mask, cfg: ModelConfig):
    """q: (B,Sq,H,Dh)  k,v: (B,Sk,KV,Dh)  mask: broadcastable (B,1,Sq,Sk)."""
    B, Sq, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, Sq, KV, G, Dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32)
    scores = scores / np.sqrt(Dh)
    scores = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, H, Dh)


def causal_mask(Sq: int, Sk: int, *, window: int | None = None,
                offset: int = 0) -> jax.Array:
    """(1,1,Sq,Sk) causal (optionally banded) mask. ``offset`` = Sk - Sq."""
    qi = jnp.arange(Sq)[:, None] + offset
    ki = jnp.arange(Sk)[None, :]
    m = ki <= qi
    if window is not None:
        m &= ki > qi - window
    return m[None, None]


def gqa_attention(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    kv: jax.Array | None = None,
    kv_positions: jax.Array | None = None,
    mask: jax.Array | None = None,
    causal: bool = True,
) -> jax.Array:
    """Self- (kv=None) or cross- (kv = encoder output) attention."""
    B, S, _ = x.shape
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _split_heads(x @ p["wq"], H, Dh)
    q = rope(q, positions, cfg.rope_theta)
    if kv is None:
        k = _split_heads(x @ p["wk"], KV, Dh)
        v = _split_heads(x @ p["wv"], KV, Dh)
        k = rope(k, positions, cfg.rope_theta)
    else:
        k = _split_heads(kv @ p["wk"], KV, Dh)
        v = _split_heads(kv @ p["wv"], KV, Dh)
        if kv_positions is not None:
            k = rope(k, kv_positions, cfg.rope_theta)
        causal = False
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    if mask is None:
        out = flash_attention(
            q, k, v,
            causal=causal,
            window=cfg.sliding_window if kv is None else None,
        )
    else:
        out = _sdpa(q, k, v, mask, cfg)
    out = constrain(out, "batch", None, "heads", None)
    return out.reshape(B, S, H * Dh) @ p["wo"]


def _decode_qkv(p: Params, x: jax.Array, pos: jax.Array, cfg: ModelConfig):
    """q and the new token's k, v for one decode token, roped at ``pos``
    (scalar or per-lane ``(B,)``)."""
    B = x.shape[0]
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    per_lane = jnp.ndim(pos) > 0
    positions = jnp.reshape(pos, (B, 1)) if per_lane else jnp.full((B, 1), pos)
    q = _split_heads(x @ p["wq"], H, Dh)
    q = rope(q, positions, cfg.rope_theta)
    k_new = _split_heads(x @ p["wk"], KV, Dh)
    k_new = rope(k_new, positions, cfg.rope_theta)
    v_new = _split_heads(x @ p["wv"], KV, Dh)
    return q, k_new, v_new


def _decode_slot_mask(pos: jax.Array, S_cache: int, cfg: ModelConfig):
    """The cache slot the new token's KV lands in, and the attention mask
    over the ``S_cache`` slots: ``(B,)`` slots and a ``(B,1,1,S)`` mask for
    a per-lane ``pos``, a scalar slot and a ``(1,1,1,S)`` mask otherwise."""
    idx = jnp.arange(S_cache)
    slot = pos % S_cache if cfg.sliding_window else pos
    if jnp.ndim(pos) > 0:
        if cfg.sliding_window:
            valid = (idx[None, :] <= slot[:, None]) | (pos[:, None] >= S_cache)
        else:
            valid = idx[None, :] <= pos[:, None]
        return slot, valid[:, None, None, :]
    if cfg.sliding_window:
        valid = (idx <= slot) | (pos >= S_cache)  # ring: all valid once wrapped
    else:
        valid = idx <= pos
    return slot, valid[None, None, None, :]


def _decode_attend(p: Params, q, cache_k, cache_v, mask, cfg: ModelConfig):
    B, _, H, Dh = q.shape
    out = _sdpa(q, cache_k, cache_v, mask, cfg)
    return out.reshape(B, 1, H * Dh) @ p["wo"]


def gqa_decode_step(
    p: Params,
    x: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    pos: jax.Array,
    cfg: ModelConfig,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode. x: (B,1,d); cache: (B,S_cache,KV,Dh); pos: scalar
    or per-lane ``(B,)`` vector.

    For SWA the cache is a ring buffer of width ``sliding_window`` indexed by
    ``pos % window``; otherwise the cache holds the full context and new KV is
    written at ``pos``. A per-lane ``pos`` vector decodes every batch lane at
    its own position (the continuous-batching path): lane *b*'s new KV lands
    at ``pos[b]`` and its causal mask covers only ``idx <= pos[b]`` — each
    lane's arithmetic is independent of the others, so results are
    bit-identical to running that lane alone at the same batch shape.
    """
    q, k_new, v_new = _decode_qkv(p, x, pos, cfg)
    slot, mask = _decode_slot_mask(pos, cache_k.shape[1], cfg)
    if jnp.ndim(pos) > 0:
        lanes = jnp.arange(x.shape[0])
        cache_k = cache_k.at[lanes, slot].set(k_new[:, 0])
        cache_v = cache_v.at[lanes, slot].set(v_new[:, 0])
    else:
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k_new, slot, axis=1
        )
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v_new, slot, axis=1
        )
    cache_k = constrain(cache_k, "batch", "kv_len", "kv_heads", None)
    cache_v = constrain(cache_v, "batch", "kv_len", "kv_heads", None)
    return _decode_attend(p, q, cache_k, cache_v, mask, cfg), cache_k, cache_v


def gqa_decode_step_stacked(
    p: Params,
    x: jax.Array,
    k_stack: jax.Array,
    v_stack: jax.Array,
    layer: jax.Array,
    pos: jax.Array,
    cfg: ModelConfig,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`gqa_decode_step` on layer ``layer`` of the stacked caches
    ``(L,B,S_cache,KV,Dh)``, with the same slot and mask rules.

    Only the new token is written, at ``[layer, lane, slot]``, and attention
    reads layer ``layer`` straight from the stack: a layer loop that carries
    the stacks updates them in place instead of slicing each layer's cache
    out and stacking it back. Compiled on a TPU, attention is the
    :func:`~repro.kernels.decode_attention.decode_attention` kernel, which
    DMAs the layer's blocks from the stack (its scores stay in float32);
    elsewhere it is the jnp attention of :func:`gqa_decode_step` on
    ``k_stack[layer]``, the same arithmetic bit for bit, which XLA on a TPU
    would first copy out of the stack.
    """
    S_cache = k_stack.shape[2]
    q, k_new, v_new = _decode_qkv(p, x, pos, cfg)
    slot, mask = _decode_slot_mask(pos, S_cache, cfg)
    if jnp.ndim(pos) > 0:
        lanes = jnp.arange(x.shape[0])
        k_stack = k_stack.at[layer, lanes, slot].set(k_new[:, 0])
        v_stack = v_stack.at[layer, lanes, slot].set(v_new[:, 0])
    else:
        at = (layer, 0, slot, 0, 0)
        k_stack = jax.lax.dynamic_update_slice(k_stack, k_new[None], at)
        v_stack = jax.lax.dynamic_update_slice(v_stack, v_new[None], at)
    k_stack = constrain(k_stack, "layers", "batch", "kv_len", "kv_heads", None)
    v_stack = constrain(v_stack, "layers", "batch", "kv_len", "kv_heads", None)
    if _decode_kernel_applies(k_stack):
        B, _, H, Dh = q.shape
        # valid slots: [0, pos] for a full cache, the whole ring once wrapped
        n_valid = jnp.broadcast_to(jnp.minimum(pos + 1, S_cache), (B,))
        out = decode_attention(q[:, 0], k_stack, v_stack, layer, n_valid)
        return out.reshape(B, 1, H * Dh) @ p["wo"], k_stack, v_stack
    out = _decode_attend(p, q, k_stack[layer], v_stack[layer], mask, cfg)
    return out, k_stack, v_stack


def _decode_kernel_applies(k_stack: jax.Array) -> bool:
    """The decode-attention kernel runs compiled on a TPU, on an unsharded
    stack with lane-aligned heads and whole (16, 128) tiles of positions."""
    S_cache, Dh = k_stack.shape[2], k_stack.shape[4]
    return (kernel_backend() == "pallas" and current_mesh() is None
            and Dh % 128 == 0 and S_cache % 16 == 0)


# -- SwiGLU MLP -----------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: int | None = None) -> Params:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _init(ks[0], (d, ff), cfg.dtype),
        "w_up": _init(ks[1], (d, ff), cfg.dtype),
        "w_down": _init(ks[2], (ff, d), cfg.dtype),
    }


def mlp(p: Params, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    h = constrain(h, "batch", None, "ff")
    return h @ p["w_down"]


# -- embedding / head ------------------------------------------------------

def padded_vocab(cfg: ModelConfig, multiple: int = 2048) -> int:
    return -(-cfg.vocab_size // multiple) * multiple


def embed_init(key, cfg: ModelConfig) -> Params:
    V = padded_vocab(cfg)
    p = {"embedding": _init(key, (V, cfg.d_model), cfg.dtype, scale=1.0)}
    if not cfg.tie_embeddings:
        p["head"] = _init(jax.random.fold_in(key, 1), (V, cfg.d_model),
                          cfg.dtype, scale=1.0 / np.sqrt(cfg.d_model))
    return p


def embed(p: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    e = p["embedding"]
    e = constrain(e, "vocab", None)
    out = jnp.take(e, tokens, axis=0)
    return constrain(out, "batch", None, None)


def logits(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x: (B,S,d) -> (B,S,V_padded), vocab-sharded; padded region masked.
    The head is ``p["head"]`` where the model has one of its own, else the
    input embedding."""
    e = p.get("head", p["embedding"])
    out = (x @ e.T.astype(x.dtype)).astype(jnp.float32)
    V = padded_vocab(cfg)
    if V != cfg.vocab_size:
        pad_mask = jnp.arange(V) >= cfg.vocab_size
        out = jnp.where(pad_mask[None, None, :], NEG_INF, out)
    return constrain(out, "batch", None, "vocab")


def cross_entropy(logit: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token NLL; logit (B,S,V) fp32, labels (B,S) int32."""
    lse = jax.nn.logsumexp(logit, axis=-1)
    picked = jnp.take_along_axis(logit, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)
